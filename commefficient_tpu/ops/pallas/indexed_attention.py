"""Attention over the keys a learned index chooses, query by query
(DeepSeek-V3.2-Exp's lightning index at Keye-VL-2.0's ``sa_config`` sizes),
without a ``[T, T]`` tensor.

For query ``t`` and key ``s <= t`` the index scores

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

and the query attends to ``S_t``: every ``s <= t`` where ``t + 1 <= topk``,
else the ``topk`` positions with the largest ``I[t, s]``, ties to the lower
``s`` (``lax.top_k``'s rule). Four kernels, all over ``[keys, queries]``
tiles (keys on sublanes, queries on lanes, so every per-query number — the
threshold, the running maximum, the log-sum-exp — is a lane-major row that
broadcasts over the keys for nothing and lies dense in HBM):

- ``_select_kernel`` makes a block of queries' scores against every causal
  key into VMEM as order-preserving int32 keys and finds each query's
  ``topk``-th largest by 32 counting passes (a radix select, exact), then
  the tie rule's cut: ``tau[t]`` (the threshold score), ``cut[t]`` (the last
  position admitted among the scores equal to ``tau[t]``) and whether the
  threshold alone admitted more than ``topk``. 128 KB a sequence a layer is
  all the backward pass needs of it (``SELECT_RESIDUAL``: the name a
  ``remat`` policy saves it under, so the recomputed forward cannot select
  another set).
- ``_fwd_kernel``, ``_dq_kernel``, ``_dkv_kernel``: flash attention that
  visits every causal tile and **rebuilds the mask inside the tile** from
  ``qI``, ``kI``, ``w``, ``tau``, ``cut`` (``I > tau``, or ``I == tau`` at
  ``s <= cut``), once a tile for all the query heads. The scores are made by
  one routine (``_index_scores``) in all four kernels, so they are the same
  floats and the set attended to is exactly ``S_t``.

Gradients flow to ``q``, ``k``, ``v`` only: ``S_t`` is a constant of the
backward pass and the index's operands take no cotangent.

Under ``remat`` a block's policy is expected to save two names
(``save_only_these_names(SELECT_RESIDUAL, ATTEND_RESIDUAL)``,
``models/laguna.py::LagunaLM``). ``SELECT_RESIDUAL`` is the thresholds above.
``ATTEND_RESIDUAL`` (``library_kernels``' kernels name theirs by it too) is
what the forward kernel leaves its own backward pass: the output as it wrote
it (``o_t`` ``[B, KV, G, d, T]``, the compute dtype) and the log-sum-exp
(``[B, H, T]`` float32). With both kept the backward pass recomputes ``q``,
``k``, ``v`` (its kernels read them), rebuilds ``o`` from the saved ``o_t``
and does not run ``indexed_fwd`` again: once a layer. At the Keye cell's shape
(two clients' rows of 16,384, 32 heads of 128, bfloat16) that is 128 KB +
272 MB a layer (``o_t`` 268 MB, ``lse`` 4.2 MB) held from the forward pass to
the layer's backward, against a kernel of 0.038 s a layer. A policy that
leaves ``ATTEND_RESIDUAL`` out gets the same floats and the second kernel.

Compiled by Mosaic on ``tpu``, interpreted on ``cpu``
(``countsketch_kernels.kernels_interpreted``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.pallas.countsketch_kernels import kernels_interpreted

SELECT_RESIDUAL = "attn_select_threshold"
ATTEND_RESIDUAL = "attn_sparse_output"
BLOCK_Q = 256        # queries a tile (lanes)
BLOCK_K = 512        # keys a tile (sublanes)
BLOCK_Q_SELECT = 512  # queries whose causal scores sit in VMEM at once: [T, 512] int32
VMEM_LIMIT = 100 * 1024 * 1024
_NEG = -1e30         # a masked score: exp(_NEG - m) is 0 for any real m
_INT_MIN = -(2 ** 31)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _block(T: int, most: int) -> int:
    """The largest of 512, 256, 128 that is at most ``most`` and divides ``T``."""
    for b in (512, 256, 128):
        if b <= most and T % b == 0:
            return b
    raise ValueError(f"indexed_attention: T={T} is not a multiple of 128 (the kernels' lanes)")


def _tiles(T: int):
    """``(queries, keys)`` a tile of the attention kernels (a key tile is
    whole query blocks)."""
    bk = _block(T, BLOCK_K)
    return min(_block(T, BLOCK_Q), bk), bk


def _struct(shape, dtype, like):
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT)


# ---- what every kernel shares ---------------------------------------------------

def _index_scores(ki, qi_ref, w_ref):
    """``[bk, bq]`` float32: ``I[s, t]`` of the keys ``ki`` ``[bk, e]``
    against the block's queries ``qi_ref`` ``[J, bq, e]``, ``w_ref``
    ``[J, bq]``. Products take the operands as they come (bfloat16 under
    ``mixed``), accumulate in float32; ReLU, weight and the sum over the
    index heads, in head order, in float32."""
    total = None
    for j in range(qi_ref.shape[0]):
        dots = jax.lax.dot_general(ki, qi_ref[j], _NT, preferred_element_type=jnp.float32)
        term = jnp.maximum(dots, 0.0) * w_ref[j:j + 1, :]
        total = term if total is None else total + term
    return total


def _positions(kb, qb, bk, bq):
    """Key position of each row and query position of each lane of a tile."""
    s = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    t = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return s, t


def _admitted(ki, qi_ref, w_ref, tau_ref, cut_ref, kb, qb):
    """``[bk, bq]`` bool: the tile's part of ``S_t``."""
    scores = _index_scores(ki, qi_ref, w_ref)
    s, t = _positions(kb, qb, *scores.shape)
    tau = tau_ref[...]
    chosen = (scores > tau) | ((scores == tau) & (s <= cut_ref[...]))
    return chosen & (s <= t)


def _tile_needed(kb, qb, bk, bq):
    """Whether any key of tile ``kb`` is causal for any query of block ``qb``."""
    return kb * bk <= qb * bq + (bq - 1)


# ---- selection --------------------------------------------------------------------

def _order_key(x):
    """float32 -> int32 with the same order (both zeros to one key)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(x == 0.0, jnp.int32(0), key)


def _key_to_float(key):
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _select_kernel(ki_ref, qi_ref, w_ref, tau_ref, cut_ref, ties_ref, keys_ref, *,
                   topk, bk, T):
    qb = pl.program_id(1)
    bq = w_ref.shape[1]
    t_row = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    tau_ref[...] = jnp.full((1, bq), -jnp.inf, jnp.float32)
    cut_ref[...] = jnp.full((1, bq), T, jnp.int32)
    ties_ref[...] = jnp.zeros((1, bq), jnp.float32)

    @pl.when((qb + 1) * bq > topk)       # else every query takes all its causal keys
    def _():
        tiles = (qb * bq) // bk + 1      # key tiles that hold a causal key (bk % bq == 0)

        def fill(i, carry):
            at = pl.multiple_of(i * bk, bk)
            key = _order_key(_index_scores(ki_ref[pl.ds(at, bk), :], qi_ref, w_ref))
            s, t = _positions(i, qb, bk, bq)
            keys_ref[pl.ds(at, bk), :] = jnp.where(s <= t, key, jnp.int32(_INT_MIN))
            return carry

        jax.lax.fori_loop(0, tiles, fill, 0)

        def count(test):
            """``[1, bq]`` int32: the keys of each query that pass ``test(keys, s)``."""
            def body(i, n):
                at = pl.multiple_of(i * bk, bk)
                s = at + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
                hit = test(keys_ref[pl.ds(at, bk), :], s)
                return n + jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)

            return jax.lax.fori_loop(0, tiles, body, jnp.zeros((1, bq), jnp.int32))

        # the topk-th largest key, bit by bit from the top (unsigned order is
        # the int32 order shifted by 2**31; the adds wrap)
        def bit(b, prefix):
            cand = prefix + jnp.left_shift(jnp.int32(1), 31 - b)
            return jnp.where(count(lambda keys, _: keys >= cand) >= topk, cand, prefix)

        kth = jax.lax.fori_loop(0, 32, bit, jnp.full((1, bq), _INT_MIN, jnp.int32))
        above = count(lambda keys, _: keys > kth)
        equal = count(lambda keys, _: keys == kth)
        room = topk - above                               # how many of the equal ones go in
        tied = equal > room

        # the room-th equal key by position: the largest c with fewer than
        # ``room`` equal keys below it
        def where_bit(b, c):
            cand = c + jnp.left_shift(jnp.int32(1), (T.bit_length() - 1) - b)
            below = count(lambda keys, s: (keys == kth) & (s < cand))
            return jnp.where(below < room, cand, c)

        cut = jax.lax.cond(
            jnp.max(tied.astype(jnp.int32)) > 0,
            lambda: jax.lax.fori_loop(0, T.bit_length(), where_bit,
                                      jnp.zeros((1, bq), jnp.int32)),
            lambda: jnp.full((1, bq), T, jnp.int32))
        short = t_row < topk                               # t + 1 <= topk: all of them
        tau_ref[...] = jnp.where(short, -jnp.inf, _key_to_float(kth))
        cut_ref[...] = jnp.where(short | ~tied, T, cut)
        ties_ref[...] = jnp.where(short, 0.0, tied.astype(jnp.float32))


def select_threshold(qi, ki, w, *, topk):
    """``qi`` ``[B, J, T, e]``, ``ki`` ``[B, T, e]``, ``w`` ``[B, J, T]`` ->
    ``(tau [B, 1, T] float32, cut [B, 1, T] int32, tied [B, 1, T] float32)``."""
    B, J, T, e = qi.shape
    bq, bk = _block(T, BLOCK_Q_SELECT), _block(T, BLOCK_K)
    row = pl.BlockSpec((None, 1, bq), lambda b, i: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, bk=bk, T=T),
        grid=(B, T // bq),
        in_specs=[pl.BlockSpec((None, T, e), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, J, bq, e), lambda b, i: (b, 0, i, 0)),
                  pl.BlockSpec((None, J, bq), lambda b, i: (b, 0, i))],
        out_specs=[row, row, row],
        out_shape=[_struct((B, 1, T), jnp.float32, qi), _struct((B, 1, T), jnp.int32, qi),
                   _struct((B, 1, T), jnp.float32, qi)],
        scratch_shapes=[pltpu.VMEM((T, bq), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=kernels_interpreted(), name="indexed_select",
    )(ki, qi, w)


# ---- attention over the set ---------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, vt_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
                o_ref, lse_ref, n_ref, m_scr, l_scr, acc_scr, n_scr):
    qb, kb, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    KV, G, bq, _ = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        n_scr[...] = jnp.zeros(n_scr.shape, jnp.float32)

    @pl.when(_tile_needed(kb, qb, bk, bq))
    def _():
        keep = _admitted(ki_ref[...], qi_ref, w_ref, tau_ref, cut_ref, kb, qb)
        n_scr[...] += jnp.sum(keep.astype(jnp.float32), axis=0, keepdims=True)
        bias = jnp.where(keep, 0.0, _NEG)
        for kv in range(KV):
            k, vt = k_ref[kv], vt_ref[kv]
            for g in range(G):
                h = kv * G + g
                s = jax.lax.dot_general(k, q_ref[kv, g], _NT,
                                        preferred_element_type=jnp.float32) + bias
                m_prev = m_scr[h:h + 1, :]
                m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
                # a tile with no admitted key for a query adds exp(0) terms
                # under the initial maximum; the first admitted key's rescale
                # (exp(_NEG - m) = 0) wipes them, and every query admits one
                p = jnp.exp(s - m_next)
                alpha = jnp.exp(m_prev - m_next)
                l_scr[h:h + 1, :] = alpha * l_scr[h:h + 1, :] + jnp.sum(p, axis=0, keepdims=True)
                acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
                    vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)
                m_scr[h:h + 1, :] = m_next

    @pl.when(kb == last)
    def _():
        for kv in range(KV):
            for g in range(G):
                h = kv * G + g
                o_ref[kv, g] = (acc_scr[h] / l_scr[h:h + 1, :]).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        n_ref[...] = n_scr[...]


def _scores_and_slopes(q, k, v, do, lse, di, bias):
    """One head's tile in the backward pass: ``p`` and ``ds`` ``[bk, bq]``."""
    s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) + bias
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - di)


def _dq_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, di_ref, qi_ref, ki_ref, w_ref,
               tau_ref, cut_ref, dq_ref, acc_scr):
    qb, kb, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    KV, G, bq, _ = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(_tile_needed(kb, qb, bk, bq))
    def _():
        keep = _admitted(ki_ref[...], qi_ref, w_ref, tau_ref, cut_ref, kb, qb)
        bias = jnp.where(keep, 0.0, _NEG)
        for kv in range(KV):
            k, kt, v = k_ref[kv], kt_ref[kv], v_ref[kv]
            for g in range(G):
                h = kv * G + g
                _, ds = _scores_and_slopes(q_ref[kv, g], k, v, do_ref[kv, g],
                                           lse_ref[h:h + 1, :], di_ref[h:h + 1, :], bias)
                acc_scr[h] += jnp.dot(kt, ds.astype(kt.dtype), preferred_element_type=jnp.float32)

    @pl.when(kb == last)
    def _():
        for kv in range(KV):
            for g in range(G):
                dq_ref[kv, g] = acc_scr[kv * G + g].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qi_ref, ki_ref, w_ref,
                tau_ref, cut_ref, dk_ref, dv_ref, dk_scr, dv_scr):
    kb, qb, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    KV, G, bq, _ = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(qb == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(_tile_needed(kb, qb, bk, bq))
    def _():
        keep = _admitted(ki_ref[...], qi_ref, w_ref, tau_ref, cut_ref, kb, qb)
        bias = jnp.where(keep, 0.0, _NEG)
        for kv in range(KV):
            k, v = k_ref[kv], v_ref[kv]
            for g in range(G):
                h = kv * G + g
                q, do = q_ref[kv, g], do_ref[kv, g]
                p, ds = _scores_and_slopes(q, k, v, do, lse_ref[h:h + 1, :],
                                           di_ref[h:h + 1, :], bias)
                dv_scr[kv] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
                dk_scr[kv] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(qb == last)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _specs(KV, G, d, J, e, bq, bk, *, keys_outer):
    """Block specs by operand kind for a grid ``(B, queries, keys)`` (or
    ``(B, keys, queries)``). A tile the causal order rules out re-reads the
    last one needed, so it moves no bytes."""
    per = bk // bq
    if keys_outer:
        qx = lambda b, j, i: jnp.maximum(i, j * per)      # noqa: E731 - first block that sees j
        kx = lambda b, j, i: j                            # noqa: E731
    else:
        qx = lambda b, i, j: i                            # noqa: E731
        kx = lambda b, i, j: jnp.minimum(j, i // per)     # noqa: E731 - last tile i sees
    return dict(
        heads=pl.BlockSpec((None, KV, G, bq, d), lambda *a: (a[0], 0, 0, qx(*a), 0)),
        heads_t=pl.BlockSpec((None, KV, G, d, bq), lambda *a: (a[0], 0, 0, 0, qx(*a))),
        keys=pl.BlockSpec((None, KV, bk, d), lambda *a: (a[0], 0, kx(*a), 0)),
        keys_t=pl.BlockSpec((None, KV, d, bk), lambda *a: (a[0], 0, 0, kx(*a))),
        rows=pl.BlockSpec((None, KV * G, bq), lambda *a: (a[0], 0, qx(*a))),
        row=pl.BlockSpec((None, 1, bq), lambda *a: (a[0], 0, qx(*a))),
        qi=pl.BlockSpec((None, J, bq, e), lambda *a: (a[0], 0, qx(*a), 0)),
        ki=pl.BlockSpec((None, bk, e), lambda *a: (a[0], kx(*a), 0)),
        w=pl.BlockSpec((None, J, bq), lambda *a: (a[0], 0, qx(*a))),
    )


def _forward(q, k, v, qi, ki, w, tau, cut):
    B, KV, G, T, d = q.shape
    J, e = qi.shape[1], qi.shape[3]
    bq, bk = _tiles(T)
    sp = _specs(KV, G, d, J, e, bq, bk, keys_outer=False)
    H = KV * G
    return pl.pallas_call(
        _fwd_kernel,
        grid=(B, T // bq, T // bk),
        in_specs=[sp["heads"], sp["keys"], sp["keys_t"], sp["qi"], sp["ki"], sp["w"],
                  sp["row"], sp["row"]],
        out_specs=[sp["heads_t"], sp["rows"], sp["row"]],
        out_shape=[_struct((B, KV, G, d, T), q.dtype, q), _struct((B, H, T), jnp.float32, q),
                   _struct((B, 1, T), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((H, bq), jnp.float32), pltpu.VMEM((H, bq), jnp.float32),
                        pltpu.VMEM((H, d, bq), jnp.float32), pltpu.VMEM((1, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=kernels_interpreted(), name="indexed_fwd",
    )(q, k, v.swapaxes(-1, -2), qi, ki, w, tau, cut)


def _backward(q, k, v, qi, ki, w, tau, cut, o_t, lse, do):
    B, KV, G, T, d = q.shape
    J, e = qi.shape[1], qi.shape[3]
    bq, bk = _tiles(T)
    H = KV * G
    di = jnp.sum(do.astype(jnp.float32) * o_t.swapaxes(-1, -2).astype(jnp.float32), -1)
    di = di.reshape(B, H, T)
    sp = _specs(KV, G, d, J, e, bq, bk, keys_outer=False)
    dq_t = pl.pallas_call(
        _dq_kernel,
        grid=(B, T // bq, T // bk),
        in_specs=[sp["heads"], sp["keys"], sp["keys_t"], sp["keys"], sp["heads"], sp["rows"],
                  sp["rows"], sp["qi"], sp["ki"], sp["w"], sp["row"], sp["row"]],
        out_specs=sp["heads_t"],
        out_shape=_struct((B, KV, G, d, T), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((H, d, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=kernels_interpreted(), name="indexed_dq",
    )(q, k, k.swapaxes(-1, -2), v, do, lse, di, qi, ki, w, tau, cut)
    sp = _specs(KV, G, d, J, e, bq, bk, keys_outer=True)
    dk, dv = pl.pallas_call(
        _dkv_kernel,
        grid=(B, T // bk, T // bq),
        in_specs=[sp["heads"], sp["keys"], sp["keys"], sp["heads"], sp["rows"], sp["rows"],
                  sp["qi"], sp["ki"], sp["w"], sp["row"], sp["row"]],
        out_specs=[sp["keys"], sp["keys"]],
        out_shape=[_struct(k.shape, k.dtype, q), _struct(v.shape, v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((KV, bk, d), jnp.float32),
                        pltpu.VMEM((KV, bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=kernels_interpreted(), name="indexed_dkv",
    )(q, k, v, do, lse, di, qi, ki, w, tau, cut)
    return dq_t.swapaxes(-1, -2), dk, dv


@jax.custom_vjp
def _attend(q, k, v, qi, ki, w, tau, cut):
    o_t, _, n = _forward(q, k, v, qi, ki, w, tau, cut)
    return o_t.swapaxes(-1, -2), n


def _attend_fwd(q, k, v, qi, ki, w, tau, cut):
    o_t, lse, n = _forward(q, k, v, qi, ki, w, tau, cut)
    o_t, lse = (checkpoint_name(a, ATTEND_RESIDUAL) for a in (o_t, lse))
    return (o_t.swapaxes(-1, -2), n), (q, k, v, qi, ki, w, tau, cut, o_t, lse)


def _attend_bwd(res, ct):
    dq, dk, dv = _backward(*res, ct[0].astype(res[0].dtype))
    return dq, dk, dv, None, None, None, None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def indexed_attention(q, k, v, qi, ki, w, *, topk):
    """Causal grouped-query attention over the ``topk`` keys a query's index
    scores rank highest (module docstring), never forming ``[T, T]``.

    ``q`` ``[B, T, H, d]`` (already scaled), ``k``, ``v`` ``[B, T, KV, d]``,
    ``qi`` ``[B, T, J, e]``, ``ki`` ``[B, T, e]``, ``w`` ``[B, T, J]``
    (float32) -> ``(o [B, T, H, d], counters)``. Query head ``j`` reads KV
    head ``j // (H / KV)``. The index's operands take no cotangent. Counters
    (float32 scalars): ``selected_pairs`` (``sum_t |S_t|`` as the forward
    kernel counted the keys it admitted), ``causal_pairs`` and
    ``select_ties`` (queries whose threshold alone admitted more than ``topk``
    and were cut by position). ``T`` is a multiple of 128."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    with jax.named_scope("attn_index"):
        qi, w = qi.transpose(0, 2, 1, 3), w.astype(jnp.float32).transpose(0, 2, 1)
        with jax.named_scope("attn_select"):
            tau, cut, tied = select_threshold(qi, ki, w, topk=topk)
            tau, cut, tied = (checkpoint_name(a, SELECT_RESIDUAL) for a in (tau, cut, tied))
    with jax.named_scope("attn_sparse"):
        q = q.reshape(B, T, KV, G, d).transpose(0, 2, 3, 1, 4)          # [B, KV, G, T, d]
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)         # [B, KV, T, d]
        o, n = _attend(q, k, v, qi, ki, w, tau, cut)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, d)
    counters = {"selected_pairs": jnp.sum(n), "causal_pairs": jnp.float32(B * T * (T + 1) / 2),
                "select_ties": jnp.sum(tied)}
    return o, counters
