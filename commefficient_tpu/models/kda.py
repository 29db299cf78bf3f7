"""Kimi Delta Attention (KDA; the Kimi Linear report, arXiv:2510.26692): a
gated delta rule whose decay is one number a key channel, the recurrent
mixer of ``layer_types`` ``kda_attention`` in the decoder of
``models/laguna.py``.

Per head, with ``q_t``, ``k_t`` L2-normalised, ``g_t <= 0`` a key channel and
``beta_t`` in (0, 1), the state ``S`` ``[d_k, d_v]`` starts at 0 at a row's
start and runs

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d_k).

``chunked_delta_rule`` computes it ``chunk`` positions at a time (the
WY / UT form of DeltaNet's chunked algorithm with a decay per channel).
With ``G_r`` the decay summed over a chunk's positions up to ``r`` and
``S_0`` the state the chunk starts from, write ``u_r = beta_r (v_r -
S_{r-1}^T Diag(exp g_r) k_r)``; then ``S_r = Diag(exp G_r) S_0 + sum_{i<=r}
Diag(exp(G_r - G_i)) k_i u_i^T`` and the ``u`` solve a unit lower-triangular
system: ``(I + A) U = Diag(beta) (V - K~ S_0)`` with ``A_ri = beta_r sum_c
k_rc k_ic exp(G_rc - G_ic)`` (``i < r``) and ``K~ = k exp(G)``. So
``U = U~ - W S_0`` with ``[W | U~] = (I + A)^-1 Diag(beta) [K~ | V]``, the
chunk's output is ``Q' S_0 + P U~`` with ``P_ri = sum_c q_rc k_ic exp(G_rc -
G_ic)`` (``i <= r``) and ``Q' = q exp(G) - P W``, and the state it leaves is
``Diag(exp G_C) S_0 + K^^T U`` with ``K^ = k exp(G_C - G)``.

Every exponent is ``<= 0``, for any decay the gate allows: a decay product
between positions ``i < r`` is never split at the chunk's start (``exp(G_r)
exp(-G_i)`` overflows once a chunk's decay passes ~88), but at a boundary
``s`` between them, ``exp(G_r - G_s) exp(G_s - G_i)``. ``_pairs`` builds the
chunk's ``[C, C]`` matrices so: the chunk's halves first (boundary: the left
half's last position), then each half's halves, down to single positions; at
each level every pair of positions in the two halves of a block is one
product of a ``[h, d_k]`` by a ``[d_k, h]`` matrix. No tensor carries two
chunk extents and the key width; the largest per chunk is ``[C, C]``.

All chunks' intra-chunk terms (the pairs, the triangular solve, ``W``,
``U~``, ``Q'``, ``P U~``, ``K^``) are computed at once; only the state a chunk
hands the next is a ``lax.scan``, of three ``[C, d_k] x [d_k, d_v]``
products a step. The backward pass is JAX's own, through the scan, the
solve and the pairs, under the decoder block's ``remat``; inside it the
mixer's preparation and the rule are each a ``jax.checkpoint`` of their own,
so that the block's backward pass holds one part's temporaries at a time
(the rule's are a score of ``[B, H, T, d_k]`` float32 tensors: 1.3 GB a layer
for two clients at the published widths and ``T`` = 4,096).

Precision under ``mixed`` (``dtype`` bfloat16): the gates, the cumulative
decays and their exponentials, the triangular solve, the state and the
products that read it (``W S``, ``Q' S``) and ``P W`` in float32, those three
at ``Precision.HIGHEST`` (the chip multiplies float32 operands in bfloat16
passes unless told; the solve's own products are at ``HIGHEST``); the ``q,
k, v`` projection's output (the short convolution's input, float32 inside
it), ``q``, ``k``, ``v`` and the operands of the intra-chunk products (the
pairs' factors, ``P`` and ``U~`` in ``P U~``, ``K^`` and ``U`` in the sum over a
chunk's positions that updates the state) in bfloat16 with float32
accumulation. ``float32``: everything in float32.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from commefficient_tpu.models.decoder_parts import RMSNorm, _dot, _Kernel

L2_EPS = 1e-6    # x / sqrt(sum x^2 + eps), the public kernels' q, k norm
# a float32 product at the default precision is multiplied in bfloat16 passes
# on the chip: the state's products ask for float32 by name
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, dtype, spec):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32)


def _pairs(a, b, G, dtype):
    """``[..., C, C]``: ``sum_c a[r, c] b[i, c] exp(G[r, c] - G[i, c])`` for
    ``i < r``, 0 on and above the diagonal. ``a``, ``b``, ``G`` ``[..., C,
    d]``, ``C`` a power of two, ``G`` non-increasing along ``C``. Built from
    single positions up: a block of ``2h`` is its two halves' blocks and,
    below them, the products across its boundary, factored there."""
    *lead, C, d = a.shape
    m = jnp.zeros((*lead, C, 1, 1), jnp.float32)
    h = 1
    while h < C:
        nb = C // (2 * h)
        split = lambda x: x.reshape(*lead, nb, 2, h, x.shape[-1])  # noqa: E731
        ar, bl, g = split(a)[..., 1, :, :], split(b)[..., 0, :, :], split(G)
        s = g[..., 0, h - 1:h, :]                       # the left half's last position
        right = ar * jnp.exp(g[..., 1, :, :] - s)       # G_r - G_s <= 0
        left = bl * jnp.exp(s - g[..., 0, :, :])        # G_s - G_i <= 0
        cross = _mm(right, left, dtype, "...rc,...ic->...ri")
        halves = m.reshape(*lead, nb, 2, h, h)
        top = jnp.concatenate([halves[..., 0, :, :], jnp.zeros_like(cross)], -1)
        bottom = jnp.concatenate([cross, halves[..., 1, :, :]], -1)
        m = jnp.concatenate([top, bottom], -2)
        h *= 2
    return m.reshape(*lead, C, C)


def chunked_delta_rule(q, k, v, g, beta, *, chunk, dtype):
    """The rule of the module docstring over ``[B, T, H, ...]``: ``q``, ``k``
    ``[B, T, H, d_k]`` (normalised, ``q`` already over ``sqrt(d_k)``), ``v``
    ``[B, T, H, d_v]``, ``g`` ``[B, T, H, d_k]`` float32 ``<= 0``, ``beta``
    ``[B, T, H]`` float32 -> ``(o [B, T, H, d_v] float32, counters)``: the
    decay summed over positions, heads and channels (``kda/decay_sum``), and
    over chunks, heads and channels the share of the state a chunk keeps,
    ``exp`` of the chunk's summed decay (``kda/state_kept``), with the count
    of its terms (``kda/state_slots``). ``T`` need not be a whole number of
    chunks: the last is padded with positions that write nothing (``k``,
    ``v``, ``beta`` zero) and decay nothing (``g`` zero)."""
    B, T, H, K = k.shape
    V = v.shape[-1]
    n = -(-T // chunk)
    pad = n * chunk - T

    def chunks(x):           # [B, T, H, ...] -> [B, H, n, C, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, n, chunk, H, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    G = jnp.cumsum(g, axis=-2)                               # [B, H, n, C, K] float32
    last = G[..., -1:, :]
    kb = k.astype(jnp.float32) * beta[..., None]
    A = beta[..., None] * _pairs(k, k, G, dtype)              # strictly lower
    P = _pairs(q, k, G, dtype) + jnp.eye(chunk) * _mm(q, k, dtype, "...rc,...rc->...r")[..., None]
    # [W | U~] = (I + A)^-1 Diag(beta) [K~ | V], in float32
    rhs = jnp.concatenate([kb * jnp.exp(G), v.astype(jnp.float32) * beta[..., None]], -1)
    wu = jax.lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    W, U0 = wu[..., :K], wu[..., K:]
    Qs = q.astype(jnp.float32) * jnp.exp(G) - jnp.einsum("...ri,...ic->...rc", P, W,
                                                         precision=HIGHEST)
    O0 = _mm(P, U0, dtype, "...ri,...iv->...rv")
    Kh = k.astype(jnp.float32) * jnp.exp(last - G)            # G_C - G_i <= 0
    kept = jnp.exp(last[..., 0, :])                           # [B, H, n, K]

    def step(S, x):
        W, U0, Qs, O0, Kh, kept = x
        U = U0 - jnp.einsum("bhck,bhkv->bhcv", W, S, precision=HIGHEST)
        o = O0 + jnp.einsum("bhck,bhkv->bhcv", Qs, S, precision=HIGHEST)
        S = kept[..., None] * S + _mm(Kh, U, dtype, "bhck,bhcv->bhkv")
        return S, o

    over_chunks = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    S0 = jnp.zeros((B, H, K, V), jnp.float32)
    vma = tuple(jax.typeof(W).vma)   # inside a shard_map the carry varies as the chunks do
    if vma:
        S0 = jax.lax.pcast(S0, vma, to="varying")
    _, o = jax.lax.scan(step, S0, tuple(map(over_chunks, (W, U0, Qs, O0, Kh, kept))))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * chunk, V)[:, :, :T]
    counters = {"kda/decay_sum": jnp.sum(g), "kda/state_kept": jnp.sum(kept),
                "kda/state_slots": jnp.float32(kept.size)}
    return jnp.moveaxis(o, 1, 2), counters


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

        # each part keeps only its inputs for the backward pass and recomputes
        # the rest there: the block's backward holds one part's temporaries at
        # a time, not the whole mixer's
        with jax.named_scope("kda_prep"):
            q, k, v, g, beta = jax.checkpoint(prep)(
                qkv, w("conv", (c.kda_conv, 3 * D)), f, w("decay", (H + D,)), low[..., 2 * d:])
        with jax.named_scope("attn_kda"):
            o, counters = jax.checkpoint(functools.partial(
                chunked_delta_rule, chunk=c.kda_chunk, dtype=c.dtype))(q, k, v, g, beta)
        with jax.named_scope("kda_prep"):
            o = RMSNorm(c.rms_norm_eps, d, name="o_norm")(o)
            o = o * jax.nn.sigmoid(gate).reshape(B, T, H, d)
        with jax.named_scope("attn_proj"):
            return _dot(o.reshape(B, T, D), w("o_proj", (D, E)), c.dtype), counters
