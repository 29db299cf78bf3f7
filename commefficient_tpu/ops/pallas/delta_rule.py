"""Kimi Delta Attention's chunked rule (``models/kda.py``) as two Pallas TPU
kernels under one ``jax.custom_vjp``: ``kda_fwd`` walks a row's chunks in
order with the ``[d_v, d_k]`` float32 state in fast memory, ``kda_bwd`` walks
them backwards with the state's cotangent there. Every intra-chunk term of a
chunk (its pair matrices, the triangular solve, ``W``, ``U~``, ``Q'``,
``P U~``, ``K^``) is made in fast memory from the chunk's own rows and never
reaches HBM.

Per head, for a chunk of ``C`` positions with ``G`` the decay summed from the
chunk's start (``G_r = sum_{t <= r} g_t``, float32, ``<= 0`` and falling)
and ``S`` the state it starts from, held transposed (``St = S^T``, ``[d_v,
d_k]``, so a key channel's decay scales a lane):

    A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)   (i < r)
    P_ri = sum_c q_rc k_ic exp(G_rc - G_ic)          (i <= r)
    T = (I + A)^-1,  W = T Diag(beta) (k exp G),  U~ = T Diag(beta) v
    U = U~ - W S,    o = (q exp G - P W) S + P U~
    S' = Diag(exp G_C) S + K^^T U,   K^ = k exp(G_C - G).

**The pairs.** A decay product between positions ``i < r`` is factored at a
boundary ``b`` between them, ``exp(G_r - G_b) exp(G_b - G_i)``, never at the
chunk's start, so every exponent is ``<= 0`` for any decay the gate gives.
The chunk is halved level by level (``C / 2``, ``C / 4``, ... down to single
positions); at each level, every pair whose positions lie in the two halves
of one block is one entry of a ``[C, d_k] x [d_k, C]`` product whose rows are
scaled to the block's boundary (the left half's last position), and a mask
keeps the entries whose two positions share a block. ``log2 C`` products of
whole ``[C, C]`` tiles, and no tile sliced at a sub-chunk: the halving of
the ``jax.numpy`` form it replaces, with each level one MXU product. The
boundaries' decays are gathered by one-hot products, exactly (``_gather``).

**The solve.** ``T`` is built by the same halving: at each level a block's
two diagonal halves are already inverted and its lower quadrant is
``-T_22 A_21 T_11`` (``T <- T - T A_h T``, ``A_h`` the level's quadrant of
``A``). Forward substitution within blocks of two, block products across
them; no row-by-row loop, whose 63 dependent steps a chunk would set the
kernel's time.

**Precision** (as ``models/kda.py`` states it): the decays and their
exponentials, the solve (``T``, ``W``, ``U~``), the state and every
product that reads it or its cotangent (``W S``, ``Q' S``, ``P W`` and their
gradients) in float32 at ``Precision.HIGHEST`` (``_exact``); the pair
factors, ``P U~`` and ``K^^T U`` (and the gradients of those products) take
operands in the compute dtype with float32 accumulation (``_mixed``), and
under a float32 compute dtype are float32 products too.

The backward pass holds each chunk's starting state (``[.., n, d_v, d_k]``
float32, written by the forward kernel) and recomputes the chunk's terms
from ``q``, ``k``, ``v``, ``G``, ``beta``; its gradients are derived by hand
from the equations above and held to JAX's gradient of the token-by-token
recurrence (``tests/test_kimi_linear.py``).

Compiled by Mosaic on ``tpu``, interpreted on ``cpu``
(``countsketch_kernels.kernels_interpreted``), but for one place: inside the
round's ``shard_map`` Pallas's interpreter evaluates the kernel's body on
blocks that vary over the mesh beside constants the body makes (its iotas,
its masks) that do not, and is refused by the ``vma`` check (JAX 0.9.0; the
TPU interpreter's callbacks are refused under ``remat``). There, and only
there, the kernels' own chunk functions (``_fwd_chunk``, ``_bwd_chunk``) run
as a ``lax.scan`` over the chunks, as ``library_kernels.grouped_product``
does for its kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.pallas.countsketch_kernels import kernels_interpreted

HIGHEST = jax.lax.Precision.HIGHEST
HEADS_PER_STEP = 4   # heads a grid step: 1, 2, 4 and 8 read within 5 % of one another on a v5e
VMEM_LIMIT = 64 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
F32 = jnp.float32


def _exact(a, b, dims):
    """A float32 product at float32 precision: the solve's and the state's."""
    return jax.lax.dot_general(a.astype(F32), b.astype(F32), dims, precision=HIGHEST,
                               preferred_element_type=F32)


def _mixed(a, b, dims, dtype):
    """Operands in the compute dtype, float32 accumulation: the pair factors,
    ``P U~``, ``K^^T U``. Under a float32 compute dtype a float32 product."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               precision=HIGHEST if dtype == F32 else None,
                               preferred_element_type=F32)


def _gather(onehot, x):
    """``onehot @ x`` exactly, ``onehot`` a 0/1 float32 matrix: ``x`` split in
    three bfloat16 parts that sum to it exactly, each product exact."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(F32)).astype(jnp.bfloat16)
    onehot = onehot.astype(jnp.bfloat16)
    part = lambda y: jax.lax.dot_general(onehot, y, _NN, preferred_element_type=F32)  # noqa: E731
    return (part(hi) + part(mid)) + part(lo)


def _rows(x, axis):
    return jnp.sum(x, axis=axis, keepdims=True)


class _Chunk:
    """One chunk's intra-chunk terms, made in fast memory from its rows:
    ``q``, ``k``, ``v`` ``[C, d]`` (compute dtype), ``G`` ``[C, d_k]``,
    ``beta`` ``[C, 1]`` float32."""

    def __init__(self, q, k, v, G, beta):
        C = k.shape[0]
        self.dtype = k.dtype
        self.qf, self.kf, self.vf = q.astype(F32), k.astype(F32), v.astype(F32)
        self.G, self.beta = G, beta
        self.eG = jnp.exp(G)
        self.last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
        self.G_last = _rows(jnp.where(self.last, G, 0.0), 0)            # [1, d_k]
        r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.eye = r == i
        L = C.bit_length() - 1                                           # log2 C levels
        # one-hot of each level's boundary for each row, all levels stacked:
        # row l C + r picks the last position of the left half of r's block
        row = jax.lax.broadcasted_iota(jnp.int32, (L * C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (L * C, C), 1)
        half = jnp.left_shift(1, row // C)
        pos = jnp.bitwise_and(row, C - 1)
        sel = (col == jnp.bitwise_and(pos, -2 * half) + half - 1).astype(F32)
        Gb = _gather(sel, G)                                      # [L C, d_k]
        pos = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        self.masks, self.right, self.factor = [], [], []
        Mkk = jnp.zeros((C, C), F32)
        P = jnp.where(self.eye, _rows(self.qf * self.kf, 1), 0.0)
        for l in range(L):
            h = 1 << l                                                  # half a block
            right = jnp.bitwise_and(pos, h) != 0                 # [C, 1]: the block's right half
            same = jnp.right_shift(r, l + 1) == jnp.right_shift(i, l + 1)
            mask = same & (jnp.bitwise_and(r, h) != 0) & (jnp.bitwise_and(i, h) == 0)
            d = G - Gb[l * C:(l + 1) * C]
            e = jnp.exp(jnp.where(right, d, -d))               # G_r - G_b, G_b - G_i: <= 0
            left_k = jnp.where(right, self.kf * e, 0.0)
            left_q = jnp.where(right, self.qf * e, 0.0)
            right_k = jnp.where(right, 0.0, self.kf * e)
            both = _mixed(jnp.concatenate([left_k, left_q], 0), right_k, _NT, self.dtype)
            Mkk = Mkk + jnp.where(mask, both[:C], 0.0)
            P = P + jnp.where(mask, both[C:], 0.0)
            self.masks.append(mask)
            self.right.append(right)
            self.factor.append(e)
        self.Mkk, self.P = Mkk, P
        A = beta * Mkk
        T = jnp.where(self.eye, 1.0, 0.0) - jnp.where(self.masks[0], A, 0.0)
        for mask in self.masks[1:]:
            T = T - _exact(_exact(T, jnp.where(mask, A, 0.0), _NN), T, _NN)
        self.T = T
        self.Kt = self.kf * self.eG
        self.W = _exact(T, beta * self.Kt, _NN)
        self.Ut = _exact(T, beta * self.vf, _NN)

    def state_terms(self, St):
        """``U``, ``Q'``, ``K^`` and ``exp(G_C)`` against the starting state
        ``St`` ``[d_v, d_k]``."""
        U = self.Ut - _exact(self.W, St, _NT)
        Qp = self.qf * self.eG - _exact(self.P, self.W, _NN)
        Kh = self.kf * jnp.exp(self.G_last - self.G)               # G_C - G_i <= 0
        return U, Qp, Kh, jnp.exp(self.G_last)


def _fwd_chunk(q, k, v, G, beta, St):
    """One head's chunk: ``(o, the state it leaves)`` from the state ``St``
    it starts from."""
    c = _Chunk(q, k, v, G, beta)
    U, Qp, Kh, kept = c.state_terms(St)
    o = _exact(Qp, St, _NT) + _mixed(c.P, c.Ut, _NN, c.dtype)
    return o, St * kept + _mixed(U, Kh, _TN, c.dtype)


def _bwd_chunk(q, k, v, G, beta, St, dO, dS_out):
    """One head's chunk backwards: ``(dq, dk, dv, dG, dbeta, dS)`` from the
    output's cotangent ``dO`` and ``dS_out``, the cotangent of the state the
    chunk leaves; ``dS`` is that of the state it starts from, ``St``."""
    c = _Chunk(q, k, v, G, beta)
    C = k.shape[0]
    U, Qp, Kh, kept = c.state_terms(St)
    qf, kf = c.qf, c.kf
    # S' = S exp(G_C) + K^^T U
    dSt = dS_out * kept
    dG_last = _rows(dS_out * St, 0) * kept                          # [1, d_k]
    dU = _exact(Kh, dS_out, _NT)
    dKh = _exact(U, dS_out, _NN)
    dk = dKh * jnp.exp(c.G_last - c.G)
    t = dKh * Kh
    dG = -t
    dG_last = dG_last + _rows(t, 0)
    # o = Q' S + P U~
    dQp = _exact(dO, St, _NN)
    dSt = dSt + _exact(dO, Qp, _TN)
    dP = _mixed(dO, c.Ut, _NT, c.dtype)
    dUt = _mixed(c.P, dO, _TN, c.dtype)
    # Q' = q exp(G) - P W
    dq = dQp * c.eG
    dG = dG + dQp * qf * c.eG
    dP = dP - _exact(dQp, c.W, _NT)
    dW = -_exact(c.P, dQp, _TN)
    # U = U~ - W S
    dUt = dUt + dU
    dW = dW - _exact(dU, St, _NN)
    dSt = dSt - _exact(dU, c.W, _TN)
    # [W | U~] = T Diag(beta) [K~ | V]
    dRk = _exact(c.T, dW, _TN)
    dRv = _exact(c.T, dUt, _TN)
    dA = -(_exact(dRk, c.W, _NT) + _exact(dRv, c.Ut, _NT))
    dbeta = _rows(dRk * c.Kt, 1) + _rows(dRv * c.vf, 1) + _rows(dA * c.Mkk, 1)
    dKt = c.beta * dRk
    dv = c.beta * dRv
    dk = dk + dKt * c.eG
    dG = dG + dKt * c.Kt
    dMkk = c.beta * dA
    # the pairs: P's diagonal, then each level's quadrant
    dP_diag = _rows(jnp.where(c.eye, dP, 0.0), 1)
    dq = dq + dP_diag * kf
    dk = dk + dP_diag * qf
    for mask, right, e in zip(c.masks, c.right, c.factor):
        dMk = jnp.where(mask, dMkk, 0.0)
        dMq = jnp.where(mask, dP, 0.0)
        left_k = jnp.where(right, kf * e, 0.0)
        left_q = jnp.where(right, qf * e, 0.0)
        right_k = jnp.where(right, 0.0, kf * e)
        dM = jnp.concatenate([dMk, dMq], 0)
        da = _mixed(dM, right_k, _NN, c.dtype)
        dak, daq = e * da[:C], e * da[C:]
        dbk = e * _mixed(dM, jnp.concatenate([left_k, left_q], 0), _TN, c.dtype)
        dk = dk + dak + dbk
        dq = dq + daq
        dG = dG + kf * dak + qf * daq - kf * dbk
    return dq, dk, dv, dG + jnp.where(c.last, dG_last, 0.0), dbeta, dSt


def _fwd_kernel(q_ref, k_ref, v_ref, G_ref, b_ref, o_ref, s0_ref, st_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, F32)

    # whole blocks in, whole blocks out: the heads are values, not ref indices
    blocks = [r[...] for r in (q_ref, k_ref, v_ref, G_ref, b_ref, st_ref)]
    o, nxt = zip(*(_fwd_chunk(*(x[h] for x in blocks)) for h in range(k_ref.shape[0])))
    s0_ref[...] = blocks[-1]
    o_ref[...] = jnp.stack(o)
    st_ref[...] = jnp.stack(nxt)


def _bwd_kernel(q_ref, k_ref, v_ref, G_ref, b_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dG_ref, db_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, F32)

    blocks = [r[...] for r in (q_ref, k_ref, v_ref, G_ref, b_ref, s0_ref, do_ref, ds_ref)]
    grads = [_bwd_chunk(*(x[h] for x in blocks)) for h in range(k_ref.shape[0])]
    for ref, *per_head in zip((dq_ref, dk_ref, dv_ref, dG_ref, db_ref, ds_ref), *grads):
        ref[...] = jnp.stack(per_head).astype(ref.dtype)


def _chunks(x, chunk):
    """``[N, n C, ...]`` -> ``[n, N, C, ...]``."""
    N, T = x.shape[:2]
    return jnp.moveaxis(x.reshape(N, T // chunk, chunk, *x.shape[2:]), 1, 0)


def _rows_of(x):
    """``[n, N, C, ...]`` -> ``[N, n C, ...]``."""
    n, N, C = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape(N, n * C, *x.shape[3:])


def _scan_forward(q, k, v, G, beta, chunk):
    """The forward kernel's chunk loop as a ``lax.scan`` (module docstring)."""
    N, K, V = k.shape[0], k.shape[-1], v.shape[-1]

    def step(St, x):
        o, nxt = jax.vmap(_fwd_chunk)(*x, St)
        return nxt, (o, St)

    S0 = jax.lax.pcast(jnp.zeros((N, V, K), F32), tuple(jax.typeof(k).vma), to="varying")
    _, (o, states) = jax.lax.scan(step, S0, tuple(_chunks(x, chunk) for x in (q, k, v, G, beta)))
    return _rows_of(o), jnp.moveaxis(states, 0, 1)


def _scan_backward(q, k, v, G, beta, states, do, chunk):
    """The backward kernel's chunk loop as a ``lax.scan`` (module docstring)."""
    def step(dS, x):
        *grads, dS = jax.vmap(_bwd_chunk)(*x, dS)
        return dS, tuple(grads)

    xs = tuple(_chunks(x, chunk) for x in (q, k, v, G, beta, do.astype(F32)))
    xs = xs[:5] + (jnp.moveaxis(states, 1, 0), xs[5])
    # the chunks last to first, as the backward kernel visits them, by a plain
    # forward scan over the reversed chunks (the loop is the forward one's)
    last_first = functools.partial(jax.tree.map, lambda x: x[::-1])
    _, grads = jax.lax.scan(step, jnp.zeros_like(states[:, 0]), last_first(xs))
    return tuple(_rows_of(g).astype(x.dtype) for g, x in zip(last_first(grads), (q, k, v, G, beta)))


def _in_shard_map_on_cpu(like):
    return kernels_interpreted() and bool(jax.typeof(like).vma)


def _struct(shape, dtype, like):
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _heads_per_step(N: int) -> int:
    """Interpreted, one: the body is unrolled a head at a time, and the CPU
    compiles every copy."""
    most = 1 if kernels_interpreted() else HEADS_PER_STEP
    return max(b for b in range(1, most + 1) if N % b == 0)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT)


def _forward(q, k, v, G, beta, chunk):
    if _in_shard_map_on_cpu(k):
        return _scan_forward(q, k, v, G, beta, chunk)
    N, T, K = k.shape
    V, n, hb = v.shape[-1], T // chunk, _heads_per_step(N)
    rows = lambda d: pl.BlockSpec((hb, chunk, d), lambda b, j: (b, j, 0))  # noqa: E731
    return pl.pallas_call(
        _fwd_kernel,
        grid=(N // hb, n),
        in_specs=[rows(K), rows(K), rows(V), rows(K), rows(1)],
        out_specs=[rows(V), pl.BlockSpec((hb, None, V, K), lambda b, j: (b, j, 0, 0))],
        out_shape=[_struct((N, T, V), F32, k), _struct((N, n, V, K), F32, k)],
        scratch_shapes=[pltpu.VMEM((hb, V, K), F32)],
        compiler_params=_params(), interpret=kernels_interpreted(), name="kda_fwd",
    )(q, k, v, G, beta)


def _backward(q, k, v, G, beta, states, do, chunk):
    if _in_shard_map_on_cpu(k):
        return _scan_backward(q, k, v, G, beta, states, do, chunk)
    N, T, K = k.shape
    V, n, hb = v.shape[-1], T // chunk, _heads_per_step(N)
    rows = lambda d: pl.BlockSpec((hb, chunk, d), lambda b, j: (b, n - 1 - j, 0))  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel,
        grid=(N // hb, n),
        in_specs=[rows(K), rows(K), rows(V), rows(K), rows(1),
                  pl.BlockSpec((hb, None, V, K), lambda b, j: (b, n - 1 - j, 0, 0)), rows(V)],
        out_specs=[rows(K), rows(K), rows(V), rows(K), rows(1)],
        out_shape=[_struct(q.shape, q.dtype, k), _struct(k.shape, k.dtype, k),
                   _struct(v.shape, v.dtype, k), _struct(G.shape, F32, k),
                   _struct(beta.shape, F32, k)],
        scratch_shapes=[pltpu.VMEM((hb, V, K), F32)],
        compiler_params=_params(), interpret=kernels_interpreted(), name="kda_bwd",
    )(q, k, v, G, beta, states, do.astype(F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, G, beta, chunk):
    """The rule of the module docstring over head-major rows: ``q``, ``k``
    ``[N, T, d_k]`` (normalised, ``q`` already over ``sqrt(d_k)``), ``v``
    ``[N, T, d_v]`` in the compute dtype, ``G`` ``[N, T, d_k]`` float32 (the
    decay summed from each chunk's start), ``beta`` ``[N, T, 1]`` float32;
    ``T`` a whole number of ``chunk``s, the state zero at each row's start ->
    ``o`` ``[N, T, d_v]`` float32."""
    return _forward(q, k, v, G, beta, chunk)[0]


def _rule_fwd(q, k, v, G, beta, chunk):
    o, states = _forward(q, k, v, G, beta, chunk)
    return o, (q, k, v, G, beta, states)


def _rule_bwd(chunk, res, do):
    return _backward(*res, do, chunk)


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)
