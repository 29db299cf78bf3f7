"""Dense-transmit compressors: ``uncompressed`` and ``fedavg``.

``uncompressed`` is the no-compression oracle every other mode's degenerate
settings must reduce to (tests/test_round.py). ``fedavg`` differs only in
the per-client GRADIENT rule — ``num_local_iters`` local SGD steps whose
weight delta is transmitted in gradient scale (reference fed_worker.py
~L240-290 divides by the lr used locally) — the transmit/aggregate/server
algebra is the dense path unchanged.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from commefficient_tpu.compress.base import KIND_DENSE, KIND_NONE, Compressor
from commefficient_tpu.compress.registry import register
from commefficient_tpu.ops.topk import topk_threshold_sharded


class _DenseServerMixin:
    """The dense server update shared by uncompressed / fedavg / local_topk.

    ``_transmit_is_scaled`` — True when workers transmit ALREADY-lr-scaled
    values (local_topk with local error banks ``lr * u`` per the FetchSGD
    Alg-1 semantics, module docstring of compress/), so the server must NOT
    multiply by lr again.
    """

    @property
    def _transmit_is_scaled(self) -> bool:
        return False

    def server_update(self, momentum, error, extra, agg, lr, step):
        rho = self.cfg.virtual_momentum
        applies_lr = not self._transmit_is_scaled
        if rho > 0:
            m = rho * momentum + agg
            return (lr * m if applies_lr else m), m, error, extra
        return (lr * agg if applies_lr else agg), momentum, error, extra


@register("uncompressed")
class DenseCompressor(_DenseServerMixin, Compressor):
    """No compression: dense psum of gradients, plain (momentum) SGD."""

    allowed_error_types = ("none",)
    supports_fsdp = True
    supports_fused_clients = True
    dense_delta = True

    def server_state_kinds(self):
        rho = self.cfg.virtual_momentum
        return (KIND_DENSE if rho > 0 else KIND_NONE, KIND_NONE)

    def fsdp_update(self, p_sh, m_in, e_in, local, lr, *, axis_name, W,
                    d, dp, S):
        # reduce-scatter straight into this chip's slice — the dense server
        # momentum is never materialized full-size
        agg_sh = (
            jax.lax.psum_scatter(
                jnp.pad(local, (0, dp - d)), axis_name,
                scatter_dimension=0, tiled=True,
            )
            / W
        )
        rho = self.cfg.virtual_momentum
        if rho > 0:
            m = rho * m_in + agg_sh
            delta_sh = lr * m
        else:
            m = m_in
            delta_sh = lr * agg_sh
        if self.cfg.do_topk_down:
            # downlink compression: globally top-k the broadcast delta
            delta_sh = topk_threshold_sharded(delta_sh, self.cfg.k, axis_name)
        return p_sh - delta_sh, m, e_in


@register("fedavg")
class FedAvgCompressor(_DenseServerMixin, Compressor):
    """FedAvg: local SGD per client, averaged weight deltas.

    Scaling (DECISION, VERDICT r1 item 4): workers transmit
    ``(w - w_local_final) / local_lr`` (gradient scale) and the server
    applies ``lr * mean``. With ``local_lr=None`` (default) local steps run
    at the server schedule's current lr, so the net applied delta is
    EXACTLY the averaged weight delta — true FedAvg. An explicit
    ``local_lr`` decouples the two and scales the applied delta by
    ``lr/local_lr`` (documented deviation; sometimes wanted as a server
    step size).
    """

    allowed_error_types = ("none",)
    supports_fsdp = False
    supports_fused_clients = False  # the local-SGD scan is inherently per-client
    base_client_rules = False  # client_grad below is the local-SGD scan
    dense_delta = True

    def server_state_kinds(self):
        rho = self.cfg.virtual_momentum
        return (KIND_DENSE if rho > 0 else KIND_NONE, KIND_NONE)

    def client_grad(self, grad_one, params_vec, batches, noise_rng, lr):
        """num_local_iters SGD steps on the client's microbatches
        ({k: [L, B, ...]}); transmit the weight delta in gradient scale.
        Local steps run at ``local_lr`` if set, else at this round's server
        lr (class docstring)."""
        cfg = self.cfg
        # guard lr == 0.0 exactly (the piecewise-linear schedule reaches 0
        # on the final round): local steps then take no step and the delta
        # is 0, not 0/0 = NaN.
        llr = (
            jnp.float32(cfg.local_lr)
            if cfg.local_lr is not None
            else jnp.maximum(lr, 1e-12)
        )

        def one(carry, mb):
            p, it = carry
            g, loss, aux = grad_one(p, mb, jax.random.fold_in(noise_rng, it))
            return (p - llr * g, it + 1), (loss, aux)

        (p_final, _), (losses, auxes) = jax.lax.scan(
            one, (params_vec, jnp.zeros((), jnp.int32)), batches
        )
        delta = (params_vec - p_final) / llr  # gradient-scale transmit
        return delta, jnp.mean(losses), jax.tree.map(
            partial(jnp.mean, axis=0), auxes
        )
