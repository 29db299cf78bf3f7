"""Sparse allreduce over a mesh axis — O(W·k) pair exchange, not O(D).

FetchSGD's whole premise is that the transmitted object is small
(arXiv:2007.07682), yet a dense ``jax.lax.psum`` over the model dimension
moves all D slots regardless of sparsity.  This module aggregates
≤k-sparse vectors by exchanging fixed-size ``(idx, val)`` pair buffers
instead, in the style of Near-Optimal Sparse Allreduce (arXiv:2201.07598):
compact the nonzeros (``ops.topk.compact_nonzero``), exchange only the
pairs, rebuild the sum by scatter-add.  All functions run INSIDE
``shard_map`` over the named axis.

Two exchange schedules:

* ``sparse_allreduce`` — one invariant gather of every shard's pair
  buffer (``all_gather_invariant``), then a local scatter-add.  The
  output is REPLICATED (axis-invariant), which is what ``shard_map``
  ``out_specs=P()`` demands: under varying-manual-axes typing only a
  reduction (psum/pmax) output is invariant — ``jax.lax.all_gather`` and
  ``ppermute`` outputs are varying and cannot leave the shard_map as
  ``P()`` — so round paths that keep a replicated server MUST consume
  this form.  Per-chip receive volume: W·k pairs — the O(W·k) bound the
  XLA collective audit enforces.

* ``sparse_allreduce_sharded`` — balanced index-range partitioning +
  recursive-halving ``ppermute`` (the recursive-doubling dual): the index
  space [0, Dp) halves each step; each chip forwards the pair buffer for
  the half it does NOT keep to its hypercube partner and scatter-adds the
  buffer it receives.  After log2(W) steps chip i holds exactly its
  balanced range [i·S, (i+1)·S) of the global sparse sum (S = Dp/W).
  Per-step buffer capacities double (k, 2k, 4k, ...) so total volume is
  (W-1)·k pairs per chip.  The output is VARYING
  (``out_specs=P(WORKERS)``) — for consumers whose server state is itself
  sharded over the axis (true_topk's sparse server update).

Both forms equal the dense psum up to f32 summation order.  Pair buffers
are fixed-size with ``(0, 0.0)`` padding, so scatter-adding padding is a
no-op and every shape is static (zero retraces).

Collective/compute overlap (``overlap_collectives='layerwise'``): the
segmented twins here split ONE collective into independent per-segment
collectives so XLA's latency-hiding scheduler may run them concurrently
with surrounding compute (or each other).  Segmentation never touches
arithmetic: ``all_gather_pairs(segments=S)`` is pure data movement (the
ordered concatenation of segment gathers IS the monolithic gather,
bit-equal), and ``psum_segments`` relies on an all-reduce being
ELEMENTWISE — each element's cross-worker sum happens once, in ring
order, whichever collective op carries it, so per-segment psums are
bit-equal to one psum of the concatenated segments (no reassociation
within a segment).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.topk import compact_nonzero

Array = jax.Array

# Default segment count for the layerwise-overlap chunked exchanges: 4
# in-flight collectives is enough for the latency-hiding scheduler to
# pipeline without shrinking any single message below the bandwidth-bound
# regime at the W*k pair sizes the sparse modes move.
OVERLAP_SEGMENTS = 4


def _segment_bounds(n: int, segments: int):
    """Static [start, stop) bounds splitting [0, n) into up to
    ``segments`` contiguous near-equal chunks (every chunk non-empty)."""
    s = max(1, min(int(segments), int(n)))
    step = -(-n // s)
    return [(a, min(a + step, n)) for a in range(0, n, step)]


def compact_pairs(v: Array, capacity: int) -> Tuple[Array, Array]:
    """``(idx, val)`` pair buffer of the first ``capacity`` nonzeros of
    dense [n] ``v`` — the single spelling of the exchange contract
    (i32 indices, ``(0, 0.0)`` padding, drop-beyond-capacity semantics
    documented on ``ops.topk.compact_nonzero``)."""
    return compact_nonzero(v, capacity)


def all_gather_invariant(x: Array, axis_name) -> Array:
    """[N, *x.shape] stack of every shard's ``x`` in axis order (N = axis
    size), typed INVARIANT over ``axis_name`` — legal to return from
    ``shard_map`` under ``out_specs=P()`` with ``check_vma`` on.

    ``jax.lax.all_gather`` is Varying -> Varying, so its output cannot
    prove replication.  A psum is Varying -> Invariant: each shard places
    its block at its own row of a zeros [N, ...] buffer and the sum over
    the axis reassembles the stack — every slot is one value plus zeros,
    so the result carries the gathered values exactly (NaN included; only
    the sign of a -0.0 is lost).  It lowers to ONE all-reduce of the
    gathered size, the same N·size receive volume the audit bounds."""
    n = jax.lax.axis_size(axis_name)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n,) + (1,) * x.ndim, 0)
    mine = rows == jax.lax.axis_index(axis_name)
    return jax.lax.psum(jnp.where(mine, x[None], jnp.zeros((), x.dtype)),
                        axis_name)


def all_gather_pairs(idx: Array, val: Array, axis_name: str,
                     segments=None) -> Tuple[Array, Array]:
    """Concatenate every shard's [kb] pair buffer into replicated
    [N·kb] buffers (N = axis size).  Invariant output — legal to return
    from ``shard_map`` under ``out_specs=P()`` (``all_gather_invariant``).

    ``segments=S`` (layerwise overlap) splits the [kb] payload into up
    to S contiguous chunks, each exchanged by its own collective;
    concatenating the [N, kb_s] gathers along the pair axis rebuilds the
    exact monolithic [N, kb] layout, so the flattened output — and
    everything scatter-added from it — is BIT-equal to ``segments=None``
    (each slot is its one value whichever collective carries it).
    ``None`` (default) traces the single-gather program."""
    if segments is None or int(segments) <= 1 or idx.shape[0] <= 1:
        g_idx = all_gather_invariant(idx, axis_name).reshape(-1)
        g_val = all_gather_invariant(val, axis_name).reshape(-1)
        return g_idx, g_val
    bounds = _segment_bounds(idx.shape[0], segments)
    g_idx = jnp.concatenate(
        [all_gather_invariant(idx[a:b], axis_name) for a, b in bounds], axis=1
    ).reshape(-1)
    g_val = jnp.concatenate(
        [all_gather_invariant(val[a:b], axis_name) for a, b in bounds], axis=1
    ).reshape(-1)
    return g_idx, g_val


def psum_segments(segments, axis_name):
    """Sum each segment array across ``axis_name`` with its OWN psum —
    independent collectives the latency-hiding scheduler may issue as
    soon as each segment's producer finishes (the layerwise-overlap form
    of one monolithic psum over the concatenated segments).

    An all-reduce is elementwise: every element's cross-worker sum is
    performed once, in the axis reduction order, regardless of which
    collective op carries it — so this is BIT-equal, element for
    element, to ``psum(concat(segments))`` split back apart
    (``tests/test_overlap_collectives.py`` pins it on a real mesh).
    Segments may differ in shape; dtypes follow each input."""
    return tuple(jax.lax.psum(s, axis_name) for s in segments)


def psum_segments_fused(segments, axis_name):
    """The monolithic twin of ``psum_segments``: ONE psum of the
    flattened-and-concatenated segments, split back to the input shapes.
    Exists as the bit-equality reference for the overlap pin (and as the
    spelling of the claim: segmentation changes only which collective
    carries an element, never its reduction).  All segments must share a
    dtype (they do — per-leaf-group sketch tables)."""
    flat = jnp.concatenate([s.reshape(-1) for s in segments])
    summed = jax.lax.psum(flat, axis_name)
    out, off = [], 0
    for s in segments:
        n = s.size
        out.append(summed[off:off + n].reshape(s.shape))
        off += n
    return tuple(out)


def scatter_add_pairs(dim: int, idx: Array, val: Array) -> Array:
    """Dense [dim] vector holding the scatter-add of the pairs.
    Duplicate indices accumulate; the ``(0, 0.0)`` padding pairs add
    nothing."""
    # lint: allow[traced-purity] dim is a static Python int by contract
    n = int(dim)
    return jnp.zeros((n,), val.dtype).at[idx].add(val)


def sparse_allreduce(v: Array, capacity: int, axis_name: str,
                     segments=None) -> Array:
    """Allreduce a ≤capacity-sparse dense [d] vector across ``axis_name``
    by exchanging only (idx, val) pairs: compact → all_gather → local
    scatter-add.  Returns the replicated dense [d] sum (invariant), equal
    to ``psum(v, axis_name)`` up to f32 summation order whenever each
    shard's ``v`` has at most ``capacity`` nonzeros.  ``segments``
    chunks the gather (layerwise overlap) — the gathered pairs, and
    therefore the single scatter-add consuming them, are bit-equal to
    the monolithic exchange (see ``all_gather_pairs``)."""
    idx, val = compact_pairs(v, capacity)
    g_idx, g_val = all_gather_pairs(idx, val, axis_name, segments=segments)
    return scatter_add_pairs(v.shape[0], g_idx, g_val)


def sparse_allreduce_sharded(v: Array, k: int, axis_name: str, *,
                             axis_size: int, axis_sizes=None) -> Array:
    """Reduce-scatter a ≤k-sparse dense [d] vector across ``axis_name``
    via recursive-halving ``ppermute`` pair exchange.

    Chip i returns its balanced index range [i·S, (i+1)·S) of the global
    sparse sum, S = ceil(d / axis_size) (tail padded with zeros).  Equal
    to slicing ``psum(v)`` up to f32 summation order.  The output is
    varying over the axis — return it from ``shard_map`` with
    ``out_specs=P(axis)``, never ``P()``.

    ``axis_size`` must be the DECLARED mesh axis size (a power of two for
    the hypercube schedule); the permutation tables are derived from it,
    never hardcoded.

    Multi-host meshes (multihost/): pass the ``(HOSTS, WORKERS)`` tuple
    as ``axis_name`` plus ``axis_sizes=(H, W_local)`` and the schedule
    becomes TWO-LEVEL — the intra-host hypercube bits run first (cheap
    ICI hops while buffer capacities are smallest), then the cross-host
    bits (DCN hops carry the already-halved index ranges).  Total hop
    count stays log2(axis_size); the returned slice for flat chip
    ``h·W_local + w`` is identical to the single-axis schedule's (both
    equal slicing the psum, up to f32 summation order).
    """
    if isinstance(axis_name, (tuple, list)):
        return _sparse_allreduce_sharded_two_level(
            v, k, tuple(axis_name),
            axis_size=axis_size, axis_sizes=axis_sizes,
        )
    # lint: allow[traced-purity] axis_size is the static mesh axis size
    n_dev = int(axis_size)
    if n_dev <= 0 or (n_dev & (n_dev - 1)) != 0:
        raise ValueError(
            f"sparse_allreduce_sharded needs a power-of-two axis size for "
            f"the recursive-halving schedule, got {n_dev}"
        )
    d = v.shape[0]
    shard = -(-d // n_dev)
    dp = shard * n_dev
    # lint: allow[traced-purity] k is a static Python int by contract
    cap = min(int(k), dp)
    acc = jnp.pad(v, (0, dp - d))
    me = jax.lax.axis_index(axis_name)
    coords = jnp.arange(dp, dtype=jnp.int32)
    start = jnp.zeros((), jnp.int32)  # my active range: [start, start+length)
    length = dp
    bit = n_dev >> 1
    while bit:  # static unroll: log2(axis_size) exchange steps
        half = length // 2
        # partner tables from the declared axis size — never literal ints
        perm = [(i, i ^ bit) for i in range(n_dev)]
        upper = (me & bit) != 0  # this step I keep the upper half
        keep_start = start + jnp.where(upper, half, 0)
        send_start = start + jnp.where(upper, 0, half)
        send = (coords >= send_start) & (coords < send_start + half)
        idx, val = compact_nonzero(jnp.where(send, acc, 0.0), cap)
        r_idx = jax.lax.ppermute(idx, axis_name, perm)
        r_val = jax.lax.ppermute(val, axis_name, perm)
        # the sent half now belongs to the partner; fold in what arrived
        acc = jnp.where(send, 0.0, acc).at[r_idx].add(r_val)
        start, length = keep_start, half
        cap = min(cap * 2, dp)  # accumulated sparsity doubles per step
        bit >>= 1
    return jax.lax.dynamic_slice(acc, (start,), (shard,))


def _sparse_allreduce_sharded_two_level(v: Array, k: int, axis_name, *,
                                        axis_size: int, axis_sizes) -> Array:
    """The two-level hop schedule behind ``sparse_allreduce_sharded`` on a
    ``(hosts, workers)`` axis tuple — intra-host hypercube bits first,
    then cross-host.

    The single-axis schedule tracks one contiguous kept range, which
    forces high-bit-first ordering; here the kept set is a boolean mask
    over coordinate blocks instead, which admits ANY bit order while
    preserving the identity chip↔range mapping consumers rely on (chip
    with flat index m ends holding block m — the slice ``axis_index``
    locates).  At the step for flat bit b, a chip sends exactly the kept
    coords whose owning block differs from its own index at b, to the
    partner differing at that one bit: ``ppermute`` over the WORKERS
    axis for intra-host bits (b < W_local), over the HOSTS axis for
    cross-host bits (b = hb·W_local).  After all log2(axis_size) steps
    the kept set is precisely the chip's own block.
    """
    if axis_sizes is None or len(axis_name) != 2 or len(axis_sizes) != 2:
        raise ValueError(
            "two-level sparse_allreduce_sharded needs a 2-axis tuple "
            f"axis_name with matching axis_sizes=(hosts, workers); got "
            f"axis_name={axis_name!r}, axis_sizes={axis_sizes!r}"
        )
    # lint: allow[traced-purity] axis sizes are static mesh axis sizes
    n_hi, n_lo = (int(s) for s in axis_sizes)
    for n in (n_hi, n_lo):
        if n <= 0 or (n & (n - 1)) != 0:
            raise ValueError(
                f"two-level sparse_allreduce_sharded needs power-of-two "
                f"axis sizes for the hypercube schedule, got {axis_sizes}"
            )
    n_dev = n_hi * n_lo
    if int(axis_size) != n_dev:
        raise ValueError(
            f"axis_size={axis_size} != product of axis_sizes {axis_sizes}"
        )
    d = v.shape[0]
    shard = -(-d // n_dev)
    dp = shard * n_dev
    # lint: allow[traced-purity] k is a static Python int by contract
    cap = min(int(k), dp)
    acc = jnp.pad(v, (0, dp - d))
    # flat chip index over the tuple: host-major, identical to the
    # single-axis index of the same device order (mesh.make_mesh keeps
    # the device order unchanged between the 3- and 4-axis forms)
    me = jax.lax.axis_index(axis_name)
    blocks = jnp.arange(dp, dtype=jnp.int32) // shard  # owning block per coord
    kept = jnp.ones((dp,), bool)
    # static hop schedule: intra-host (low) flat bits first, then
    # cross-host (high) — log2(n_lo) + log2(n_hi) == log2(n_dev) steps.
    # Partner tables come from the declared axis sizes, never literals.
    steps = []
    b = 1
    while b < n_lo:
        steps.append((axis_name[1], [(i, i ^ b) for i in range(n_lo)], b))
        b <<= 1
    hb = 1
    while hb < n_hi:
        steps.append(
            (axis_name[0], [(i, i ^ hb) for i in range(n_hi)], hb * n_lo)
        )
        hb <<= 1
    for hop_axis, perm, bit in steps:
        # send: kept coords whose owner differs from me at this bit —
        # exactly the partner's half of my kept set
        diff = ((blocks ^ me) & bit) != 0
        send = kept & diff
        idx, val = compact_nonzero(jnp.where(send, acc, 0.0), cap)
        r_idx = jax.lax.ppermute(idx, hop_axis, perm)
        r_val = jax.lax.ppermute(val, hop_axis, perm)
        # the sent coords now belong to the partner; fold in what arrived
        acc = jnp.where(send, 0.0, acc).at[r_idx].add(r_val)
        kept = kept & ~diff
        cap = min(cap * 2, dp)  # accumulated sparsity doubles per step
    return jax.lax.dynamic_slice(acc, (me * shard,), (shard,))
