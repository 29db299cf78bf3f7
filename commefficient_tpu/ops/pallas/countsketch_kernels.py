"""Pallas TPU kernels for the CountSketch hot path (sketch_backend='pallas').

The banded-einsum path (ops/countsketch.py) realizes each row as

    [nc, m] signed values  x  [m, V] STATIC one-hot  ->  [nc, V]  ->  overlap-add

which is MXU-friendly but pays for it three ways at GPT-2 scale
(d=124M, c=5M, m=8192, V~5k — the BENCH_r05 3.5x sketch-round gap):

  1. the [m, V] one-hot is a materialized jit constant (~170 MB f32 at the
     GPT-2 geometry) that streams from HBM on every row;
  2. the [nc, V] window intermediate (~320 MB) round-trips HBM between the
     einsum and the overlap-add;
  3. the sign vector is a materialized [d_eff] table — and for the poly4
     hash family it is HOST-evaluated uint64 numpy, which is why poly4 was
     CV-scale-only before this module.

Here each row is ONE tiled kernel: a grid over chunk tiles keeps a
[TC, V] accumulator in VMEM, loops over offset tiles generating the
[MT, V] one-hot ON THE FLY from the hash (fmix32 or poly4), computes the
per-element sign from the inverse-riffled scrambled position (32-bit
integer arithmetic only — nothing [d_eff]-sized ever exists), and fuses
the band overlap-add before writing its (TC+u-1)*s output tile. The
estimate direction runs the transposed contraction with the same on-the-fly
hashes, and a small compare-exchange kernel takes the median across rows —
the full unsketch front end before top-k selection.

poly4 without uint64: TPUs have no 64-bit integers, so the degree-3
Mersenne-31 polynomial is evaluated with a 16-bit-limb modular multiply
(``_modmul31``/``_poly4_u32``, defined next to the hash family in
ops/countsketch.py): exact for all operands < p = 2^31 - 1, bit-identical
to the host uint64 evaluation (pinned by tests/test_countsketch_pallas.py).
This is what unlocks the 4-universal guarantee class at D=124M.

Numerics: tiles accumulate in f32 via ``preferred_element_type`` exactly
like the einsum path; only the float SUMMATION ORDER differs, so the two
backends agree to fp32 rounding (not bit-exactly). Layout permutations
(scramble, riffle) stay outside the kernels — they are cheap gathers /
transposes and keeping them shared guarantees the two backends use one
geometry.

Mosaic shapes the kernels: blocks are 3-D ``[tile, TC+u-1, s]`` (the last
two dims equal the array's, so no (8, 128) divisibility is asked of the
geometry-given stride ``s``); the ``[TC, V]`` window accumulator is kept as
``u`` per-shift ``[TC, s]`` accumulators in VMEM scratch (a lane-dimension
``reshape(TC, u, s)`` is not expressible when ``s`` is not a multiple of
128), and the band overlap-add is ``u`` static sublane-offset adds; integer
iotas are int32 and the sign bit goes through int32 on its way to f32
(Mosaic has no uint32 iota and no uint32 -> float cast).

On the ``cpu`` backend (tier-1 tests) every kernel runs under Pallas
interpret mode; on ``tpu`` the same calls compile through Mosaic; any other
backend is an error (``_interpret``).
"""

from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.countsketch import (
    _GOLDEN,
    _MERSENNE_P,
    _ceil_mult,
    _from_layout,
    _mix32,
    _poly4_u32,
    _scramble,
    _to_layout,
    _unscramble,
)


def kernels_interpreted() -> bool:
    """Where the kernels run: Mosaic-compiled on ``tpu`` (False),
    interpreted on ``cpu`` (True — the tier-1 suite runs JAX_PLATFORMS=cpu
    and the kernels must stay testable there); every other backend is
    refused — nothing on an accelerator may run interpreted without saying
    so. Evaluated at trace time — static per compilation."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas sketch kernels compile for 'tpu' and interpret on "
        f"'cpu'; backend {backend!r} has neither — use "
        "sketch_backend='einsum' there"
    )


def _interpret(like):
    """The ``interpret=`` argument of a ``pallas_call`` whose input is
    ``like``. Which interpreter, on cpu, follows that input: inside the
    round's shard_map (varying input) only the TPU interpreter
    (``pltpu.InterpretParams``) types its grid-loop buffers as varying;
    under a plain multi-device jit only the generic one (True) partitions
    — the TPU interpreter's host callbacks cannot be replicated."""
    if not kernels_interpreted():
        return False
    return pltpu.InterpretParams() if jax.typeof(like).vma else True


def _out_struct(shape, like) -> jax.ShapeDtypeStruct:
    """f32 ``out_shape`` typed varying over the manual mesh axes ``like``
    varies over — inside the round's shard_map ``pallas_call`` refuses an
    ``out_shape`` whose ``vma`` is unset."""
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# per-row static geometry + in-kernel hash helpers
# ---------------------------------------------------------------------------


@_functools.lru_cache(maxsize=None)
def _row_geom(spec, row: int):
    """Static tile plan for one row. Returns a dict of python ints.

    MT: offset-tile width (the generated per-shift one-hot is [MT, s]).
    TC: chunk-tile height (a multiple of 8), sized so the [TC, m_pad] input
    block stays ~2 MB, floored at the band width u so the body/tail
    recombination below stays a single shifted add."""
    m = spec.chunk_m
    u, s = spec.u_row(row), spec.s_row(row)
    MT = min(256, _ceil_mult(m, 8))
    m_pad = _ceil_mult(m, MT)
    TC = max(8, min(64, (2 << 20) // (m_pad * 4) // 8 * 8))
    TC = _ceil_mult(max(TC, u), 8)
    nc = spec._nc_row(row)
    nc_pad = _ceil_mult(nc, TC)
    return dict(
        m=m, m_pad=m_pad, MT=MT, TC=TC, nc=nc, nc_pad=nc_pad,
        nt=nc_pad // TC, u=u, s=s, V=u * s,
        f=spec._factor(row), L=spec._L_row(row),
    )


def _row_hashes(spec, row: int):
    """(slot_fn, sign_fn) for this row — pure uint32 jnp, safe inside a
    Pallas kernel body. slot_fn: offset array -> int32 in-window bucket.
    sign_fn: riffled layout position -> +-1 f32 (maps the position back to
    its scrambled-space index first, so it agrees with the einsum path's
    pre-layout ``v_s * _row_signs``)."""
    g = _row_geom(spec, row)
    f, G, V = g["f"], g["L"] // g["f"], g["V"]
    if spec.hash_family == "poly4":
        c_slot = tuple(int(c) for c in spec._poly4_coeffs(row, 0))
        c_sign = tuple(int(c) for c in spec._poly4_coeffs(row, 1))

        def slot_fn(off):
            return (_poly4_u32(off, c_slot) % jnp.uint32(V)).astype(jnp.int32)

        def sign_bits(spos):
            return _poly4_u32(spos, c_sign) & jnp.uint32(1)
    else:
        key = spec._row_key(row)

        def slot_fn(off):
            return (_mix32(off, key) % jnp.uint32(V)).astype(jnp.int32)

        def sign_bits(spos):
            return _mix32(spos, key ^ _GOLDEN) & jnp.uint32(1)

    def sign_fn(pos):
        if f > 1:
            spos = (pos % jnp.uint32(f)) * jnp.uint32(G) + pos // jnp.uint32(f)
        else:
            spos = pos
        # via int32: Mosaic has no uint32 -> float32 cast
        bit = sign_bits(spos).astype(jnp.int32).astype(jnp.float32)
        return 1.0 - 2.0 * bit

    return slot_fn, sign_fn


def _check_poly4_field(spec) -> None:
    """The in-kernel Mersenne arithmetic (and 4-universality itself) needs
    every hashed input < p — same contract the host ``_poly4_eval``
    enforces with its ValueError, checked here statically against the
    largest padded layout position."""
    if spec.hash_family != "poly4":
        return
    worst = max(spec._L_row(r) for r in range(spec.r))
    if worst >= int(_MERSENNE_P):
        raise ValueError(
            f"poly4 layout position bound {worst} >= p=2^31-1; the "
            "4-universal family is only defined over GF(p) — use "
            "hash_family='fmix32' at this scale"
        )


def _offsets(shape, dim, MT, j):
    """uint32 within-chunk offsets j*MT..j*MT+MT laid along ``dim`` (int32
    iota: Mosaic has no unsigned one)."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, dim) + MT * j).astype(
        jnp.uint32
    )


def _sign_tile(sign_fn, base, m, TC, MT, j):
    """[TC, MT] signs for chunk rows base..base+TC, offset cols j*MT..+MT."""
    q = (jax.lax.broadcasted_iota(jnp.int32, (TC, MT), 0) + base).astype(
        jnp.uint32
    )
    return sign_fn(q * jnp.uint32(m) + _offsets((TC, MT), 1, MT, j))


# ---------------------------------------------------------------------------
# sketch-accumulate kernel (one row)
# ---------------------------------------------------------------------------


def _sketch_row(spec, v_s: jnp.ndarray, row: int) -> jnp.ndarray:
    """One row of the table from the scrambled [d_eff] vector: tiled
    hash + sign + one-hot contraction + fused overlap-add."""
    g = _row_geom(spec, row)
    TC, MT, m, m_pad = g["TC"], g["MT"], g["m"], g["m_pad"]
    u, s, nt = g["u"], g["s"], g["nt"]
    slot_fn, sign_fn = _row_hashes(spec, row)
    nj = m_pad // MT

    sv = _to_layout(spec, v_s, row)  # [nc, m], unsigned (signs in-kernel)
    sv = jnp.pad(sv, ((0, g["nc_pad"] - g["nc"]), (0, m_pad - m)))

    def kernel(sv_ref, out_ref, acc_ref):
        base = pl.program_id(0) * TC
        acc_ref[...] = jnp.zeros_like(acc_ref)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (MT, s), 1)

        def body(j, carry):
            slot = slot_fn(_offsets((MT, 1), 0, MT, j))  # [MT, 1] in [0, V)
            vals = sv_ref[:, pl.ds(pl.multiple_of(j * MT, MT), MT)]
            signed = (vals * _sign_tile(sign_fn, base, m, TC, MT, j)).astype(
                spec.dtype
            )
            # window slice sh holds buckets [sh*s, (sh+1)*s) of the [MT, V]
            # one-hot: u [MT, s] compares cost what one [MT, V] would
            for sh in range(u):
                onehot = (slot - sh * s == col_ids).astype(spec.dtype)
                acc_ref[sh] += jax.lax.dot_general(
                    signed,
                    onehot,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            return carry

        jax.lax.fori_loop(0, nj, body, 0)
        # fused band overlap-add: window slice sh of chunk row q lands on
        # output row q + sh — u static sublane-offset adds
        out_ref[0] = jnp.zeros((TC + u - 1, s), jnp.float32)
        for sh in range(u):
            out_ref[0, pl.ds(sh, TC), :] += acc_ref[sh]

    tiles = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((TC, m_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, TC + u - 1, s), lambda i: (i, 0, 0)),
        out_shape=_out_struct((nt, TC + u - 1, s), sv),
        scratch_shapes=[pltpu.VMEM((u, TC, s), jnp.float32)],
        interpret=_interpret(sv),
    )(sv)

    # recombine: tile i covers table rows [i*TC, i*TC + TC+u-1) of the
    # [*, s] row view; only the u-1 tail rows overlap the next tile's body
    # (TC >= u by construction), so the whole stitch is ONE shifted add +
    # concat.
    bodies = tiles[:, :TC]
    if u > 1:
        tails = tiles[:, TC:]
        bodies = bodies.at[1:, : u - 1].add(tails[:-1])
        flat = jnp.concatenate([bodies.reshape(-1), tails[-1].reshape(-1)])
    else:
        flat = bodies.reshape(-1)
    n = min(flat.shape[0], spec.c_actual)
    return jnp.pad(flat[:n], (0, spec.c_actual - n))


def sketch_vec_pallas(spec, v: jnp.ndarray) -> jnp.ndarray:
    """Pallas backend of ``sketch_vec`` — same table, kernel-tiled. Rows
    accumulate in f32 inside the kernels; only the final table downcasts
    to ``spec.table_dtype`` (a no-op for the f32 default), mirroring the
    einsum backend."""
    _check_poly4_field(spec)
    v_s = _scramble(spec, v.astype(jnp.float32))  # ONE block-gather, all rows
    table = jnp.stack([_sketch_row(spec, v_s, r) for r in range(spec.r)])
    return table.astype(spec.table_dtype)


# ---------------------------------------------------------------------------
# estimate kernel (transposed direction) + median-of-r
# ---------------------------------------------------------------------------


def _estimate_row(spec, table_row: jnp.ndarray, row: int) -> jnp.ndarray:
    """Per-coordinate estimates of one row in chunk layout [nc, m]."""
    g = _row_geom(spec, row)
    TC, MT, m, m_pad = g["TC"], g["MT"], g["m"], g["m_pad"]
    u, s, nt = g["u"], g["s"], g["nt"]
    slot_fn, sign_fn = _row_hashes(spec, row)
    nj = m_pad // MT

    # windows stack: tile i reads rows [i*TC, i*TC + TC+u-1) of the [*, s]
    # row view — the only overlapping-window view; one small gather outside
    # the kernel keeps every BlockSpec plainly blocked.
    table_row = table_row.astype(jnp.float32)  # bf16-stored tables read f32
    n_rows = g["nc_pad"] + u - 1
    row_len = n_rows * s
    row_p = jnp.pad(table_row[: min(table_row.shape[0], row_len)],
                    (0, max(0, row_len - table_row.shape[0])))
    rows2d = row_p.reshape(n_rows, s)
    win = jax.vmap(
        lambda i: jax.lax.dynamic_slice(rows2d, (i * TC, 0), (TC + u - 1, s))
    )(jnp.arange(nt))

    def kernel(in_ref, out_ref):
        base = pl.program_id(0) * TC
        v_ids = jax.lax.broadcasted_iota(jnp.int32, (s, MT), 0)

        def body(j, carry):
            h = slot_fn(_offsets((1, MT), 1, MT, j))  # [1, MT] window buckets
            est = jnp.zeros((TC, MT), jnp.float32)
            for sh in range(u):
                # transposed one-hot for window slice sh: [s, MT]
                ohT = (v_ids + sh * s == h).astype(spec.dtype)
                est = est + jax.lax.dot_general(
                    in_ref[0, pl.ds(sh, TC), :].astype(spec.dtype),
                    ohT,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            out_ref[:, pl.ds(pl.multiple_of(j * MT, MT), MT)] = (
                est * _sign_tile(sign_fn, base, m, TC, MT, j)
            )
            return carry

        jax.lax.fori_loop(0, nj, body, 0)

    est = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((1, TC + u - 1, s), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((TC, m_pad), lambda i: (i, 0)),
        out_shape=_out_struct((g["nc_pad"], m_pad), win),
        interpret=_interpret(win),
    )(win)
    return est[: g["nc"], :m]


def median_rows_pallas(ests: jnp.ndarray) -> jnp.ndarray:
    """Median over axis 0 of an [r, n] stack as one tiled kernel pass —
    an oblivious compare-exchange sort of the r lanes (r is small and
    static), exact median for odd r and mean-of-middle-two for even r,
    matching ``jnp.median``/``_median_rows``."""
    r, n = ests.shape
    if r == 1:
        return ests[0]
    TD = min(1 << 16, _ceil_mult(n, 1024))
    n_pad = _ceil_mult(n, TD)
    x = jnp.pad(ests, ((0, 0), (0, n_pad - n)))

    def kernel(in_ref, out_ref):
        rows = [in_ref[k : k + 1, :] for k in range(r)]
        for a in range(r):  # selection compare-exchange network
            for b in range(a + 1, r):
                lo = jnp.minimum(rows[a], rows[b])
                hi = jnp.maximum(rows[a], rows[b])
                rows[a], rows[b] = lo, hi
        if r % 2:
            out_ref[:] = rows[r // 2]
        else:
            out_ref[:] = 0.5 * (rows[r // 2 - 1] + rows[r // 2])

    med = pl.pallas_call(
        kernel,
        grid=(n_pad // TD,),
        in_specs=[pl.BlockSpec((r, TD), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, TD), lambda i: (0, i)),
        out_shape=_out_struct((1, n_pad), x),
        interpret=_interpret(x),
    )(x)
    return med[0, :n]


def estimate_all_pallas(spec, table: jnp.ndarray) -> jnp.ndarray:
    """Pallas backend of ``estimate_all``'s matmul path: per-row transposed
    kernels, the median kernel across rows (in scrambled space), then ONE
    unscramble — the full ``unsketch`` front end before top-k."""
    _check_poly4_field(spec)
    ests = jnp.stack(
        [
            _from_layout(spec, _estimate_row(spec, table[r], r), r)
            for r in range(spec.r)
        ]
    )
    return _unscramble(spec, median_rows_pallas(ests))
