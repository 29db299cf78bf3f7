"""Kimi Delta Attention (KDA; the Kimi Linear report, arXiv:2510.26692): a
gated delta rule whose decay is one number a key channel, the recurrent
mixer of ``layer_types`` ``kda_attention`` in the decoder of
``models/laguna.py``.

Per head, with ``q_t``, ``k_t`` L2-normalised, ``g_t <= 0`` a key channel and
``beta_t`` in (0, 1), the state ``S`` ``[d_k, d_v]`` starts at 0 at a row's
start and runs

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d_k).

``delta_rule`` computes it ``chunk`` positions at a time (the WY / UT form
of DeltaNet's chunked algorithm with a decay per channel) in two Pallas
kernels, ``ops/pallas/delta_rule.py``. With ``G_r`` the decay summed over a
chunk's positions up to ``r`` and ``S_0`` the state the chunk starts from,
write ``u_r = beta_r (v_r - S_{r-1}^T Diag(exp g_r) k_r)``; then ``S_r =
Diag(exp G_r) S_0 + sum_{i<=r} Diag(exp(G_r - G_i)) k_i u_i^T`` and the ``u``
solve a unit lower-triangular system: ``(I + A) U = Diag(beta) (V - K~
S_0)`` with ``A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)`` (``i < r``)
and ``K~ = k exp(G)``. So ``U = U~ - W S_0`` with ``[W | U~] = (I + A)^-1
Diag(beta) [K~ | V]``, the chunk's output is ``Q' S_0 + P U~`` with ``P_ri =
sum_c q_rc k_ic exp(G_rc - G_ic)`` (``i <= r``) and ``Q' = q exp(G) - P W``,
and the state it leaves is ``Diag(exp G_C) S_0 + K^^T U`` with ``K^ = k
exp(G_C - G)``.

Every exponent is ``<= 0``, for any decay the gate allows: a decay product
between positions ``i < r`` is never split at the chunk's start (``exp(G_r)
exp(-G_i)`` overflows once a chunk's decay passes ~88), but at a boundary
``s`` between them, ``exp(G_r - G_s) exp(G_s - G_i)``.

The forward kernel walks a row's chunks in order with the state in fast
memory and builds each chunk's terms there; it writes the output and each
chunk's starting state (``[B H, n, d_v, d_k]`` float32: 67 MB a layer for
two clients at the published widths and ``T`` = 4,096), the backward
kernel's residual. The backward kernel walks the chunks in reverse with the
state's cotangent in fast memory and recomputes each chunk's terms. Under the
decoder block's ``remat`` the forward kernel runs twice a layer (the forward
pass and the block's recompute) and the backward kernel once; the mixer's
preparation is a ``jax.checkpoint`` of its own, so that the block's backward
pass holds one part's temporaries at a time.

Precision under ``mixed`` (``dtype`` bfloat16): the gates, the cumulative
decays and their exponentials, the triangular solve, the state and the
products that read it (``W S``, ``Q' S``) and ``P W`` in float32, every
float32 product at ``Precision.HIGHEST`` (the chip multiplies float32
operands in bfloat16 passes unless told); the ``q,
k, v`` projection's output (the short convolution's input, float32 inside
it), ``q``, ``k``, ``v`` and the operands of the intra-chunk products (the
pairs' factors, ``P`` and ``U~`` in ``P U~``, ``K^`` and ``U`` in the sum over a
chunk's positions that updates the state) in bfloat16 with float32
accumulation. ``float32``: everything in float32.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from commefficient_tpu.models.decoder_parts import RMSNorm, _dot, _Kernel
from commefficient_tpu.ops.pallas.delta_rule import gated_delta_rule

L2_EPS = 1e-6    # x / sqrt(sum x^2 + eps), the public kernels' q, k norm


def delta_rule(q, k, v, g, beta, *, chunk):
    """The rule of the module docstring over ``[B, T, H, ...]``: ``q``, ``k``
    ``[B, T, H, d_k]`` (normalised, ``q`` already over ``sqrt(d_k)``), ``v``
    ``[B, T, H, d_v]``, all three in the compute dtype, ``g`` ``[B, T, H,
    d_k]`` float32 ``<= 0``, ``beta`` ``[B, T, H]`` float32 -> ``(o [B, T, H,
    d_v] float32, counters)``: the decay summed over positions, heads and
    channels (``kda/decay_sum``), and over chunks, heads and channels the
    share of the state a chunk keeps, ``exp`` of the chunk's summed decay
    (``kda/state_kept``), with the count of its terms (``kda/state_slots``).
    ``T`` need not be a whole number of chunks: the last is padded with
    positions that write nothing (``k``, ``v``, ``beta`` zero) and decay
    nothing (``g`` zero)."""
    B, T, H, K = k.shape
    V = v.shape[-1]
    n = -(-T // chunk)
    pad = n * chunk - T

    def rows(x):             # [B, T, H, ...] -> [B H, n C, ...], head-major
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x, 2, 1).reshape(B * H, n * chunk, *x.shape[3:])

    G = jnp.cumsum(rows(g).reshape(B * H, n, chunk, K), axis=2)     # from each chunk's start
    o = gated_delta_rule(rows(q), rows(k), rows(v), G.reshape(B * H, n * chunk, K),
                         rows(beta[..., None]), chunk)
    o = jnp.moveaxis(o.reshape(B, H, n * chunk, V)[:, :, :T], 1, 2)
    kept = jnp.exp(G[:, :, -1])                                      # [B H, n, K]
    counters = {"kda/decay_sum": jnp.sum(g), "kda/state_kept": jnp.sum(kept),
                "kda/state_slots": jnp.float32(kept.size)}
    return o, counters


def causal_conv(x, w):
    """Depthwise causal convolution along ``T``: ``x`` ``[B, T, N]``, ``w``
    ``[taps, N]``; tap ``j`` weighs position ``t - taps + 1 + j``, zeros
    before a row's start."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * xp[:, j:j + x.shape[1]] for j in range(taps))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


class KimiDeltaAttention(nn.Module):
    """The KDA mixer on normed input ``h`` ``[B, T, E]`` -> ``(out, counters)``.

    ``q, k, v = SiLU(conv(h W_qkv))`` (one depthwise causal convolution of
    ``kda_conv`` taps over the three), per head; ``g = -exp(A_log) softplus(h
    W_fa W_fb + dt_bias)``; ``beta = sigmoid(h W_b)``; the chunked rule; then
    ``W_o (RMSNorm(o) w sigmoid(h W_ga W_gb))``, the norm over each head's
    ``d_v``. No leaf has a small last dimension (on the chip a leaf's last
    dimension is laid out in lanes of 128): ``qkv_proj`` ``[E, 3 H d]``,
    ``conv`` ``[taps, 3 H d]``, ``gate_a_proj`` ``[E, d | d | H]`` (the
    decay's and the output gate's first factors and ``W_b``, side by side),
    ``f_b_proj``, ``g_b_proj`` ``[d, H d]``, ``decay`` ``[H + H d]``
    (``A_log`` a head, then ``dt_bias`` a channel), ``o_norm/scale`` ``[d]``,
    ``o_proj`` ``[H d, E]``."""

    cfg: object
    layer: int

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        B, T, E = h.shape
        H, d = c.kda_heads, c.kda_head_dim
        D = H * d
        w = lambda name, shape: _Kernel(shape, c.initializer_range, name=name)()  # noqa: E731
        with jax.named_scope("attn_proj"):
            qkv = _dot(h, w("qkv_proj", (E, 3 * D)), c.dtype).astype(c.dtype)
            low = _dot(h, w("gate_a_proj", (E, 2 * d + H)), c.dtype)
            f = _dot(low[..., :d], w("f_b_proj", (d, D)), c.dtype)
            gate = _dot(low[..., d:2 * d], w("g_b_proj", (d, D)), c.dtype)

        def prep(qkv, conv, f, decay, b):
            qkv = jax.nn.silu(causal_conv(qkv, conv))
            q, k, v = (x.reshape(B, T, H, d) for x in jnp.split(qkv, 3, -1))
            q, k = _l2(q) / math.sqrt(d), _l2(k)
            a_log, dt_bias = decay[:H], decay[H:].reshape(H, d)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f.reshape(B, T, H, d) + dt_bias)
            return (q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype), g,
                    jax.nn.sigmoid(b))

        # the preparation keeps only its inputs for the backward pass and
        # recomputes the rest there: the block's backward holds its temporaries
        # apart from the rule's
        with jax.named_scope("kda_prep"):
            q, k, v, g, beta = jax.checkpoint(prep)(
                qkv, w("conv", (c.kda_conv, 3 * D)), f, w("decay", (H + D,)), low[..., 2 * d:])
        with jax.named_scope("attn_kda"):
            o, counters = delta_rule(q, k, v, g, beta, chunk=c.kda_chunk)
        with jax.named_scope("kda_prep"):
            o = RMSNorm(c.rms_norm_eps, d, name="o_norm")(o)
            o = o * jax.nn.sigmoid(gate).reshape(B, T, H, d)
        with jax.named_scope("attn_proj"):
            return _dot(o.reshape(B, T, D), w("o_proj", (D, E)), c.dtype), counters
