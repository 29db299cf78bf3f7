"""Top-k sparsification primitives.

The reference sparsifies with ``torch.topk`` over the flat gradient/error
vector (``fed_worker.py`` ~L200-240 for local_topk, ``fed_aggregator.py``
``_server_helper_true_topk`` ~L440-480 for server-side top-k). Here the same
semantics are ``jax.lax.top_k`` over the flat [d] vector, with an optional
``jax.lax.approx_max_k`` fast path for very large d (TPU-native, documented
recall guarantees) that callers must opt into.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Every selection below runs under the ``topk_select`` scope
# (telemetry.trace.ROUND_SCOPES: a name in the op metadata, no op), so a
# device trace tells the selection from the estimate before it and the
# re-sketch after it.


@jax.named_scope("topk_select")
def topk_sparsify(v: jnp.ndarray, k: int, *, approx: bool = False):
    """Return (values [k], indices [k]) of the k largest-|.| entries of flat v."""
    mag = jnp.abs(v)
    if approx:
        _, idx = jax.lax.approx_max_k(mag, k)
    else:
        _, idx = jax.lax.top_k(mag, k)
    return v[idx], idx


def topk_dense(v: jnp.ndarray, k: int, *, approx: bool = False) -> jnp.ndarray:
    """Dense [d] vector keeping only the top-k entries of v by magnitude."""
    vals, idx = topk_sparsify(v, k, approx=approx)
    with jax.named_scope("topk_select"):
        return jnp.zeros_like(v).at[idx].set(vals)


@jax.named_scope("topk_select")
def topk_threshold_dense(v: jnp.ndarray, k: int, iters: int = 32) -> jnp.ndarray:
    """Dense top-≤k by magnitude via binary-searched threshold — the TPU
    fast path: no sort (lax.top_k is ~40 ms at d=6.5M on v5e) and no
    [d]-sized scatter, just ``iters`` vectorized passes over |v| (~33 µs
    each at d=6.5M).

    Selects ``|v| >= t`` for the smallest tested ``t`` whose selection count
    is ≤ k, so the result has AT MOST k nonzeros; exact ties at the
    threshold are dropped rather than arbitrarily broken. MEASURED
    (scripts/topk_tie_loss.py, r3): on real float32 ResNet-9 round
    gradients at d=6.5M, k=50k — fresh and partially trained, both
    synthetic variants — the dropped count is exactly 0 and the l1 mass
    gap vs ``lax.top_k`` is 0.0; float32 gradient magnitudes essentially
    never tie within the 2^-32-relative bisection resolution. (Re-measure
    with that script before top-k'ing low-precision vectors, where ties
    are plausible.)
    """
    mag = jnp.abs(v)
    hi0 = jnp.max(mag)

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        too_many = jnp.sum(mag >= mid) > k
        return jnp.where(too_many, mid, lo), jnp.where(too_many, hi, mid)

    # lo derives from hi0 (not a literal) so it inherits v's full vma type —
    # under shard_map a literal init would be axis-invariant while the body
    # output varies, a carry type mismatch (seen in local_topk workers)
    lo, hi = jax.lax.fori_loop(0, iters, body, (hi0 * 0.0, hi0))
    # hi is the smallest tested threshold with count <= k; (mag > 0) guards
    # the all-zero vector (hi stays 0 there and >= would select everything).
    # Degenerate case: >k coordinates tie at the max, so NO magnitude
    # threshold selects <=k — honor the at-most-k contract by dropping the
    # tied set entirely (error feedback retains it for later rounds).
    hi = jnp.where(jnp.sum(mag >= hi) > k, jnp.inf, hi)
    return v * ((mag >= hi) & (mag > 0))


@jax.named_scope("topk_select")
def topk_threshold_sharded(v_local: jnp.ndarray, k: int, axis_name: str,
                           iters: int = 32) -> jnp.ndarray:
    """``topk_threshold_dense`` over a vector SHARDED along ``axis_name`` —
    each device holds a [d/W] slice and returns its slice of the global
    top-<=k selection. The bisection is identical; only the max and the
    selection counts become collectives (one scalar pmax + one scalar psum
    per iteration — nothing vector-sized crosses the ICI). Used by the
    FSDP round (parallel/fsdp.py) to extract a globally-top-k update from
    the sharded error vector without ever materializing [d] anywhere.
    """
    mag = jnp.abs(v_local)
    hi0 = jax.lax.pmax(jnp.max(mag), axis_name)

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        count = jax.lax.psum(jnp.sum(mag >= mid), axis_name)
        too_many = count > k
        return jnp.where(too_many, mid, lo), jnp.where(too_many, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (hi0 * 0.0, hi0))
    # same degenerate-tie contract as the dense kernel (see its docstring)
    hi = jnp.where(
        jax.lax.psum(jnp.sum(mag >= hi), axis_name) > k, jnp.inf, hi
    )
    return v_local * ((mag >= hi) & (mag > 0))


@jax.named_scope("topk_select")
def compact_nonzero(v: jnp.ndarray, k: int):
    """Compact a ≤k-sparse dense vector into fixed-size ``(idx [kb], val
    [kb])`` buffers (``kb = min(k, len(v))``), positions ascending, padded
    with ``(0, 0.0)`` — the TPU-friendly compaction the sharded sketch
    decode and the sparse telemetry paths are built on.

    No sort and no len(v)-sized scatter (both are the TPU slow paths —
    ``lax.top_k`` measures ~40 ms at d=6.5M): one ``cumsum`` pass over the
    mask gives each selected element its output slot, and ``searchsorted``
    over that monotone prefix-count inverts the mapping with kb vectorized
    binary searches (gathers, not scatters). At len(v)=124M, k=50k that is
    51.3 ms on a v5e (PR 27: the cumsum 31.1, the searchsorted 23.4);
    ``compact_nonzero_tree`` below gives the same buffers in 4.1.
    Consumers rely on the padding contract: padded entries carry val==0.0
    so a downstream ``.at[idx].add(val)`` / ``sketch_sparse`` treats them
    as no-ops, and masks derived from ``val != 0`` drop them from norms.
    A vector with MORE than k nonzeros keeps the first kb by position
    (callers in this codebase always pass the output of a top-≤k
    selection, which cannot exceed k).
    """
    n = v.shape[0]
    # k sizes the fixed output buffers, so it CANNOT be a tracer — a
    # traced k would already fail shape inference on the arange below
    # lint: allow[traced-purity] k is a static Python int by contract
    kb = min(int(k), n)
    csum = jnp.cumsum((v != 0).astype(jnp.int32))
    total = csum[-1]
    # slot j (1-indexed) lives at the first position whose prefix count
    # reaches j; past-the-end probes return n and are masked below
    idx = jnp.searchsorted(
        csum, jnp.arange(1, kb + 1, dtype=jnp.int32), side="left"
    )
    idx = jnp.minimum(idx, n - 1).astype(jnp.int32)
    valid = jnp.arange(kb, dtype=jnp.int32) < total
    return jnp.where(valid, idx, 0), jnp.where(valid, v[idx], 0.0)


_LANES = 128  # fan-out of compact_nonzero_tree: one TPU vector row a node


def compact_nonzero_tree(v: jnp.ndarray, k: int):
    """``compact_nonzero``'s contract and values, bit for bit, for a
    ``[D]``-scale vector: the dense decode's zero-HH re-sketch compacts
    the 124M-coordinate ``update`` with it every round. Carries no scope
    of its own; the caller names it (``ef_resketch``).

    ``compact_nonzero`` pays a ``[D]`` int32 ``cumsum`` and 27 dependent
    gathers of k probes over it. Here the nonzero counts of 128-wide rows
    are summed up a 128-ary tree (one pass over ``v`` at bandwidth, then
    arrays of D/128, D/128^2, ... counts, each row holding the running
    count of its children), and slot j walks down it: at every level one
    ``[k, 128]`` row gather and a compare-and-count pick the child that
    holds the (j+1)-th nonzero; the last level reads the row of ``v``
    itself. Measured on a v5e at D = 124,444,417, k = 50,000
    (scripts/resketch_probe.py, PR 27): 4.1 ms against 51.3 ms (31.1 the
    cumsum, 23.4 the searchsorted).
    """
    n, L = v.shape[0], _LANES
    # lint: allow[traced-purity] k is a static Python int by contract
    kb = min(int(k), n)
    lane = jnp.arange(L, dtype=jnp.int32)
    nrows = -(-n // L)
    rows = jnp.pad(v, (0, nrows * L - n)).reshape(nrows, L)
    # bottom-up: per level, in each row of L children, the inclusive
    # running count of their nonzeros
    cnt = jnp.sum(rows != 0, axis=1, dtype=jnp.int32)
    levels = []
    while True:
        m = cnt.shape[0]
        mr = -(-m // L)
        incl = jnp.cumsum(jnp.pad(cnt, (0, mr * L - m)).reshape(mr, L), axis=1)
        levels.append(incl)
        cnt = incl[:, -1]
        if mr == 1:
            break
    total = cnt[0]
    def enter(incl, rank):
        """The lane whose running count first exceeds ``rank``, and the
        rank left inside it."""
        at = jnp.minimum(jnp.sum(incl <= rank[:, None], axis=1), L - 1)
        before = jnp.sum(jnp.where(lane == at[:, None] - 1, incl, 0), axis=1)
        return at, rank - before

    # top-down: slot j wants the nonzero of rank j
    rank = jnp.arange(kb, dtype=jnp.int32)
    node = jnp.zeros((kb,), jnp.int32)
    below = [t.shape[0] for t in levels[-2::-1]] + [nrows]
    for incl, rows_below in zip(reversed(levels), below):
        child, rank = enter(incl[node], rank)
        # a slot past the last nonzero walks off the tree: kept in bounds
        # here, masked below
        node = jnp.minimum(node * L + child, rows_below - 1)
    row = rows[node]
    # running count inside the row on the MXU: 0/1 operands and sums <= 128
    # are exact in bfloat16 x bfloat16 -> float32
    tri = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)
    incl = jnp.dot((row != 0).astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    at, _ = enter(incl, rank)
    val = jnp.sum(jnp.where(lane == at[:, None], row, 0), axis=1)
    valid = jnp.arange(kb, dtype=jnp.int32) < total
    return jnp.where(valid, node * L + at, 0), jnp.where(valid, val, 0)


def mask_out_indices(v: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Zero the given coordinates — the error-feedback "forget what was sent"
    step (``Ve[hh]=0`` in fed_aggregator.py ~L440-480)."""
    return v.at[idx].set(0.0)
