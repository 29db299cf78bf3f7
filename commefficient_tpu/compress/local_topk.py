"""``local_topk`` — per-client top-k with per-client (local) error feedback.

Each client sparsifies its OWN update before transmitting (fed_worker.py
~L200-240), so the uplink really is 2k floats per client; the transmitted
sparse vectors still aggregate linearly (the nonlinear selection happens
per-client, before the sum — see the compress/ package docstring). Local
error banks ``lr * u`` (the per-client mirror of the FetchSGD Alg-1
lr-scaled server banking, pinned by
tests/test_round.py::test_local_error_banks_lr_at_accumulation), and the
server then applies the aggregate WITHOUT a second lr.
"""

from __future__ import annotations

import jax.numpy as jnp

from commefficient_tpu.compress.base import (
    KIND_DENSE,
    KIND_NONE,
    Compressor,
)
from commefficient_tpu.compress.dense import _DenseServerMixin
from commefficient_tpu.compress.registry import register


@register("local_topk")
class LocalTopkCompressor(_DenseServerMixin, Compressor):
    allowed_error_types = ("none", "local")
    supports_fsdp = False  # per-client [num_clients, D] state: the memory
    # wall is offload_client_state's, not FSDP's
    supports_fused_clients = False  # per-client error/selection by definition
    base_client_rules = False  # client_transmit below: local EF + top-k
    # the device's summed transmit has <= w_loc*k nonzeros (each client
    # sends <= k), so the aggregate rebuilds EXACTLY from one W*k-pair
    # all_gather — replicated dense result, server algebra untouched, safe
    # for aggregate='auto' on multi-device meshes
    supports_sparse_aggregate = True
    sparse_aggregate_in_auto = True
    dense_delta = True
    # reference behavior: mask local momentum at transmitted coords (applies
    # only with local_momentum > 0; no contrary evidence — r4 four-corner)
    default_dampening = True

    def server_state_kinds(self):
        rho = self.cfg.virtual_momentum
        return (KIND_DENSE if rho > 0 else KIND_NONE, KIND_NONE)

    @property
    def _transmit_is_scaled(self) -> bool:
        # local error banks lr-scaled values, so the transmit is already in
        # applied scale; without error feedback it stays in gradient scale
        # and the server applies lr (equivalent for any schedule)
        return self.cfg.error_type == "local"

    def client_transmit(self, u, err_row, lr):
        cfg = self.cfg
        dampen = self.resolved_dampening()
        lm = cfg.local_momentum
        e = (err_row + lr * u) if cfg.error_type == "local" else u
        t = self.topk(e, cfg.k)
        new_err = e - t
        new_vel = u
        if dampen and lm > 0:
            new_vel = jnp.where(t != 0, 0.0, u)
        return t, new_vel, new_err

    def upload_floats(self) -> int:
        return 2 * self.cfg.k  # (index, value) pairs
