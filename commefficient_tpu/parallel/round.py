"""The federated round engine — one jitted XLA program per round.

This is the TPU-native re-design of the reference's entire L2+L3 runtime
(``fed_aggregator.py`` FedModel/FedOptimizer ~L30-560 + ``fed_worker.py``
worker_loop ~L20-420 + the shared-memory IPC backend, SURVEY.md §3.1): where
the reference runs a parameter-server process and per-GPU worker processes
exchanging tensors through POSIX shm and mp.Queues, here the WHOLE round —
per-client gradients, local momentum/error feedback, compression, cross-
worker aggregation, and the server update — is ONE jitted function over a
``Mesh``:

  * worker processes      -> shards of a ``shard_map`` over the ``workers`` axis
  * shm gradient gather   -> ``lax.psum`` over ICI (exact for every
                             registered compressor: the encoded transmit is
                             linear by contract — see compress/)
  * ``ps_weights`` in shm -> replicated ``[D]`` param vector in HBM
  * per-client state rows -> ``[num_clients, D]`` arrays gathered/scattered
                             for the round's participants at the jit top level,
                             or host-resident rows when
                             ``--client_store host|mmap`` (clientstore/:
                             W*D crosses PCIe per round instead of holding
                             num_clients*D in HBM)
  * server momentum/error -> dense ``[D]`` vectors or ``[r, c]`` sketch tables
                             carried in ``FedState``

Since PR 2 the per-MODE algebra (what a client transmits, how a device
encodes it before the psum, and the server's momentum/error/extract update)
lives in ``commefficient_tpu/compress/`` behind a registry keyed by
``cfg.mode``; this engine is mode-agnostic and calls the compressor's hooks
at fixed points in the trace. Adding a compression mode no longer touches
this file (enforced by scripts/check_mode_dispatch.py).

Learning-rate semantics (DECISION, VERDICT r1 item 5): we follow FetchSGD's
published Algorithm 1 (arXiv:2007.07682), not a guess at the reference's
internals — the mount was empty both rounds, so the paper is the canonical
contract. Error feedback banks **lr-scaled** updates and the extracted
update is applied directly:

    S_u = rho * S_u + S(agg)          # momentum, gradient scale
    S_e = S_e + lr * S_u              # error banks AT THIS ROUND'S lr
    delta = TopK(U(S_e), k);  S_e -= S(delta);  w -= delta

so residual error banked at one lr is later applied at THAT lr, not
whatever lr the schedule has moved to (the two differ under the
piecewise-linear schedule; equivalent for constant lr by linearity —
pinned by varying-lr regression tests in tests/test_round.py). Paths with
no error feedback apply ``w -= lr * update`` at application time, which is
equivalent for any schedule. Local error feedback (local_topk) banks
``lr * u`` in the per-client error for the same reason. Every compressor
implements this contract (compress/ package docstring).

fedavg scaling (DECISION, VERDICT r1 item 4): workers transmit
``(w - w_local_final) / local_lr`` (gradient scale, reference
fed_worker.py ~L240-290 divides by the lr used locally) and the server
applies ``lr * mean`` — see compress/dense.py FedAvgCompressor.

Supported (mode, error_type) pairs mirror the reference's use and are
declared per compressor class (``allowed_error_types``):
  uncompressed/fedavg: error none;   true_topk/sketch/powersgd: virtual or
  none;   local_topk: local or none.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from commefficient_tpu.compress import get_compressor
from commefficient_tpu.compress.base import KIND_DENSE
from commefficient_tpu.models.losses import IGNORE_INDEX
from commefficient_tpu.ops.collectives import (
    OVERLAP_SEGMENTS,
    psum_segments,
    sparse_allreduce,
)
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.ops.param_utils import clip_by_global_norm
from commefficient_tpu.parallel.mesh import (
    WORKERS,
    worker_axes,
    worker_axis_size,
)
from commefficient_tpu.telemetry import (
    round_diagnostics,
    round_diagnostics_sparse,
)
from commefficient_tpu.utils.config import Config

P = jax.sharding.PartitionSpec


class FedState(NamedTuple):
    """All mutable server + client state. Absent pieces are empty tuples so
    the pytree structure is static under jit."""

    params_vec: jnp.ndarray  # [D] — the ps_weights analog
    momentum: Any = ()  # [D] dense | [r, c] sketch table | ()
    error: Any = ()  # [D] dense | [r, c] sketch table | ()
    client_vel: Any = ()  # [num_clients, D] | () (clientstore/ when hosted)
    client_err: Any = ()  # [num_clients, D] | ()
    step: jnp.ndarray = None  # scalar int32
    comp: Any = ()  # compressor-private warm state (powersgd's Q) | ()


def needs_client_vel(cfg: Config) -> bool:
    return cfg.local_momentum > 0


def needs_client_err(cfg: Config) -> bool:
    return cfg.error_type == "local"


def _psum_fused(leaves, axis_name):
    """ONE all-reduce for the round's same-dtype reductions.

    The psum of a concatenation of raveled f32 leaves equals the
    concatenation of the per-leaf psums ELEMENTWISE (an all-reduce adds
    slot-by-slot in a fixed order), so fusing agg/loss/aux into a single
    collective changes no value — only the launch count (the golden
    parity recordings stay bit-identical; the all-reduce op count is
    HLO-pinned by tests/test_sparse_aggregate.py). Non-f32 leaves (the
    bf16 sketch table) keep their own psum: mixing dtypes in one payload
    would force a cast. Returns the summed leaves in input order,
    UN-divided (callers own the /W)."""
    leaves = list(leaves)
    out = list(leaves)
    f32_ix = [i for i, a in enumerate(leaves) if a.dtype == jnp.float32]
    if len(f32_ix) >= 2:
        flat = jnp.concatenate([leaves[i].ravel() for i in f32_ix])
        summed = jax.lax.psum(flat, axis_name)
        off = 0
        for i in f32_ix:
            n = leaves[i].size
            out[i] = summed[off:off + n].reshape(leaves[i].shape)
            off += n
        rest = [i for i in range(len(leaves)) if i not in f32_ix]
    else:
        rest = list(range(len(leaves)))
    for i in rest:
        out[i] = jax.lax.psum(leaves[i], axis_name)
    return out


def init_state(cfg: Config, params_vec: jnp.ndarray, spec: Optional[CountSketch]) -> FedState:
    """Allocate exactly the state the (mode, error_type, momenta) combination
    needs — the analog of FedModel.__init__'s conditional shm allocation
    (fed_aggregator.py ~L60-130); shapes come from the compressor's
    ``server_state_kinds``/``init_server_state``. Client rows are allocated
    here only when device-resident (``--client_store device``); hosted
    stores build a clientstore/ bank in FederatedSession instead."""
    d = params_vec.shape[0]
    f32 = jnp.float32
    comp = get_compressor(cfg, d=d, spec=spec)
    momentum, error, extra = comp.init_server_state()
    client_vel: Any = ()
    client_err: Any = ()
    if not cfg.client_state_hosted:
        if needs_client_vel(cfg):
            client_vel = jnp.zeros((cfg.num_clients, d), f32)
        if needs_client_err(cfg):
            client_err = jnp.zeros((cfg.num_clients, d), f32)
    return FedState(
        params_vec=params_vec.astype(f32),
        momentum=momentum,
        error=error,
        client_vel=client_vel,
        client_err=client_err,
        step=jnp.zeros((), jnp.int32),
        comp=extra,
    )


LEAFWISE = "leafwise"
PER_CLIENT_VECTOR = "per_client_vector"


def resolve_client_path(cfg: Config, comp) -> str:
    """Trace-time choice of how a shard's client gradients reach the
    server, from what the configuration and the compressor declare (never
    from a mode's name; the ONE place the rule lives: build_round_fn,
    parallel/fsdp.py and asyncfed/round.py call it, FederatedSession keeps
    the answer as ``client_path_resolved``).

    ``"leafwise"``: the round needs only the SUM of the clients' clipped
    gradients, so ``make_leafwise_sum`` clips and sums the gradient leaves
    and builds one [D] vector per shard; no [w_loc, D] buffer exists.
    ``"per_client_vector"``: something downstream takes each client's own
    [D] row (``make_per_client`` under vmap): a compressor whose
    per-client rules are not the base ones (local_topk's transmit rule,
    fedavg's local-SGD scan), local momentum or local error (the row is
    client state), DP noise (one [D] draw a client), a fedsim mask (kept
    on this path for now, ROADMAP S4), and the asyncfed engine, whose
    launch program's product IS the rows."""
    leafwise = (
        comp.base_client_rules
        and cfg.local_momentum == 0
        and cfg.error_type != "local"
        and cfg.dp_noise_multiplier == 0
        and not cfg.fedsim_enabled
        and not cfg.asyncfed_enabled
    )
    return LEAFWISE if leafwise else PER_CLIENT_VECTOR


def _client_value_and_grad(loss_fn: Callable, unravel: Callable, params_vec,
                           batch):
    """``((loss, aux), gradient pytree)`` of one client's batch: the
    closure both client paths share, and all they share. The split of the
    [D] vector into the model's leaves is named ``param_unravel``
    (telemetry.trace.MODEL_SCOPES: it nests under the caller's
    ``client_grad``); the gradient is taken with respect to the leaves, so
    the name has no backward wrapping. When the loss shards its compute
    over model/seq axes (tensor.build_tp_flat_loss), the vma transpose
    totals the gradient over those axes by itself."""
    with jax.named_scope("param_unravel"):
        params = unravel(params_vec)
    return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)


def make_grad_one(cfg: Config, loss_fn: Callable, unravel: Callable):
    """Per-client gradient closure (the fed_worker forward_grad analog):
    ``(params_vec, batch, noise_rng) -> (flat grad [D], loss, aux)`` with
    weight decay, global-norm clip, and worker-side DP noise applied: the
    per-client-vector path (``resolve_client_path``). Shared by the
    replicated round (build_round_fn), the asyncfed launch program and the
    FSDP round (parallel/fsdp.py) so the gradient semantics can never
    drift."""
    f32 = jnp.float32

    def grad_one(params_vec, batch, noise_rng):
        # the scopes here and below are telemetry.trace.ROUND_SCOPES: op
        # metadata only (no ops added), read back from the device trace
        with jax.named_scope("client_grad"):
            (loss, aux), grads = _client_value_and_grad(
                loss_fn, unravel, params_vec, batch)
            # marker: the sketch-fused-backward tests pin that THEIR
            # lowered round contains no flat [D] gradient concat
            # (tests/test_sketch_fused_bwd.py)
            with jax.named_scope("flat_grad_concat"):
                g, _ = ravel_pytree(grads)
            g = g.astype(f32)
        with jax.named_scope("client_clip"):
            if cfg.weight_decay:
                g = g + cfg.weight_decay * params_vec
            g = clip_by_global_norm(g, cfg.max_grad_norm)
            if cfg.dp_noise_multiplier > 0 and cfg.max_grad_norm is not None:
                # worker-side DP: clip (above) + gaussian noise, fed_worker ~L380-420
                sigma = cfg.dp_noise_multiplier * cfg.max_grad_norm
                g = g + sigma * jax.random.normal(noise_rng, g.shape, f32)
        return g, loss, aux

    return grad_one


def _param_leaves(unravel: Callable, params_vec):
    """``unravel(params_vec)``'s pytree, each leaf sliced from the vector on
    its own. XLA compiles ``unravel``'s split of the [D] vector into one
    relayout of the WHOLE vector per distinct minor dimension of the
    leaves; asked for a second time after the backward pass (the decay's
    read), those [D] copies are shared with the forward's and stay alive
    across the round: four of them at the start of the Laguna round, and
    7 ms each (PERF.md section 6, PR 30). The barrier keeps every leaf's
    relayout its own, next to its one reader."""
    structs = jax.eval_shape(
        unravel, jax.ShapeDtypeStruct(params_vec.shape, params_vec.dtype))
    leaves, treedef = jax.tree.flatten(structs)
    out, off = [], 0
    for st in leaves:
        n = math.prod(st.shape)
        flat = jax.lax.optimization_barrier(params_vec[off:off + n])
        out.append(flat.reshape(st.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


def make_leafwise_sum(cfg: Config, loss_fn: Callable, unravel: Callable):
    """The leafwise client path (``resolve_client_path``): ``(params_vec,
    batch {k: [w_loc, ...]}) -> (sum of the shard's clipped client
    gradients [D], loss sum, aux sum)``, with the clients' gradients kept
    as the pytree ``value_and_grad`` returns (leaves [w_loc, *shape]) and
    never raveled into [w_loc, D]. A client's global norm is a sum of
    per-leaf sums of squares, and the clipped sum is a per-leaf weighted
    sum under one [w_loc] vector of scales, so per element this is
    ``make_grad_one``'s expression, ``scale_w * (g_w + wd * p)``, summed in
    ``jnp.sum(transmit, axis=0)``'s order over clients; only the order the
    norm's squares are added in differs (per leaf, then across leaves:
    float32 reassociation, ~1e-7 relative on the scale). One
    ``ravel_pytree`` of the SUMMED leaves builds the [D] vector the encode,
    the aggregation tail and the server take."""
    f32 = jnp.float32
    wd = cfg.weight_decay
    max_norm = cfg.max_grad_norm

    def leafwise_sum(params_vec, batch):
        def grads_one(b):
            with jax.named_scope("client_grad"):
                (loss, aux), grads = _client_value_and_grad(
                    loss_fn, unravel, params_vec, b)
            return grads, loss, aux

        grads, losses, auxes = jax.vmap(grads_one)(batch)
        with jax.named_scope("client_clip"):
            gs = jax.tree.map(lambda g: g.astype(f32), grads)
            if wd:
                gs = jax.tree.map(lambda g, p: g + wd * p, gs,
                                  _param_leaves(unravel, params_vec))
            scale = None
            if max_norm is not None:
                sq = None  # [w_loc]: each client's squared global norm
                for g in jax.tree.leaves(gs):
                    leaf_sq = jnp.sum(g * g, axis=tuple(range(1, g.ndim)))
                    sq = leaf_sq if sq is None else sq + leaf_sq
                # clip_by_global_norm's own expression, a client a lane
                scale = jnp.minimum(1.0, max_norm / (jnp.sqrt(sq) + 1e-12))
        with jax.named_scope("client_sum"):
            if scale is not None:
                gs = jax.tree.map(
                    lambda g: g * scale.reshape((-1,) + (1,) * (g.ndim - 1)),
                    gs)
            summed = jax.tree.map(lambda g: jnp.sum(g, axis=0), gs)
            with jax.named_scope("flat_grad_concat"):
                local, _ = ravel_pytree(summed)
            return (
                local,
                jnp.sum(losses),
                jax.tree.map(lambda a: jnp.sum(a, 0), auxes),
            )

    return leafwise_sum


def leaf_groups(sizes, segments):
    """Partition leaf indices [0, len(sizes)) into up to ``segments``
    CONTIGUOUS non-empty groups of near-equal cumulative size — the
    layerwise-overlap bucketing (contiguous in ravel_pytree order ≈
    layer order, so each group's table cotangent completes as backprop
    crosses its layers). Returns a list of (start, stop) leaf-index
    bounds covering every leaf exactly once."""
    n = len(sizes)
    g = max(1, min(int(segments), n))
    cum, total = [], 0
    for sz in sizes:
        total += sz
        cum.append(total)
    bounds, start = [], 0
    for k in range(1, g + 1):
        target = total * k / g
        stop = start + 1
        while stop < n and cum[stop - 1] < target:
            stop += 1
        stop = min(stop, n - (g - k))  # leave >= 1 leaf per later group
        bounds.append((start, stop))
        start = stop
    bounds[-1] = (bounds[-1][0], n)
    return bounds


def _varying_like(x, ref):
    """``x`` marked varying over the manual mesh axes ``ref`` varies over
    (identity outside shard_map). A custom_vjp cotangent must carry its
    primal's varying type: the fused backward's dummy zeros table receives
    sketches of shard-local cotangents, so it is typed like the params."""
    axes = tuple(sorted(jax.typeof(ref).vma))
    return jax.lax.pcast(x, axes, to="varying") if axes else x


def make_sketch_grad_one(cfg: Config, loss_fn: Callable, unravel: Callable,
                         spec: CountSketch, *, d: int,
                         overlap_segments: Optional[int] = None):
    """Sketch-FUSED twin of ``make_grad_one`` for the fused flattened-batch
    path: ``(params_vec, batch, noise_rng) -> (grad TABLE [r, c_actual]
    f32, loss, aux)``.

    Every param leaf is threaded through ``ops.countsketch.sketch_grad_tap``
    (a custom_vjp identity sharing one dummy zeros table), and the loss is
    differentiated w.r.t. THAT TABLE: each tap's backward rule sketches
    its leaf's cotangent into the table where AD produces it
    (``sketch_segment`` at the leaf's static ravel_pytree offset), and
    JAX's cotangent fan-in sums them — by linearity the result is the
    sketch of the full flat gradient, while the flat [D] concat (the
    transpose of ``unravel``, ~500 MB at GPT-2 scale) is never traced:
    the params vector itself is not differentiated. Weight decay composes
    by the same linearity as one matmul-path sketch of the (already
    materialized) params vector. Gates (validated by Config): no clip, no
    DP noise, no local momentum, no fedsim — exactly the fused-path
    conditions, where one gradient per device exists.

    ``overlap_segments`` (layerwise overlap): partition the leaves into
    up to that many contiguous size-balanced groups (``leaf_groups``)
    and differentiate w.r.t. a TUPLE of per-GROUP tables — AD then
    finishes each group's table cotangent as backprop crosses its
    layers, so the caller can issue one psum per group the moment it
    exists (FSDP-style bucketed overlap; the sum of the group tables
    equals the monolithic table up to cotangent fan-in summation order,
    the same tolerance class the fused backward itself carries vs the
    dense-grad path). Returns ``(tuple of [r, c] tables, loss, aux)``
    in that case; ``None`` (default) traces the single-table program
    byte-identically to pre-overlap builds.
    """
    from commefficient_tpu.ops.countsketch import (
        sketch_grad_tap,
        sketch_vec,
    )

    # static per-leaf offsets of the ravel_pytree flat layout (jax.tree
    # leaf order == ravel_pytree order)
    leaf_structs = jax.tree.leaves(
        jax.eval_shape(unravel, jax.ShapeDtypeStruct((d,), jnp.float32))
    )
    sizes = [math.prod(s.shape) if s.shape else 1 for s in leaf_structs]
    offsets = [0]
    for sz in sizes[:-1]:
        offsets.append(offsets[-1] + sz)

    groups = (
        leaf_groups(sizes, overlap_segments) if overlap_segments else None
    )

    def grad_one_table(params_vec, batch, noise_rng):
        del noise_rng  # DP noise is a [D]-vector draw — gated off this path

        def tapped(table):
            params = unravel(params_vec)
            leaves, treedef = jax.tree.flatten(params)
            tapped_leaves = [
                sketch_grad_tap(spec, off, leaf, table)
                for off, leaf in zip(offsets, leaves)
            ]
            return loss_fn(jax.tree.unflatten(treedef, tapped_leaves), batch)

        with jax.named_scope("client_grad"):
            zeros = _varying_like(jnp.zeros(spec.table_shape, jnp.float32),
                                  params_vec)
            (loss, aux), table = jax.value_and_grad(
                tapped, has_aux=True)(zeros)
        if cfg.weight_decay:
            # sketch(g + wd*p) = sketch(g) + wd * sketch(p); the [D]
            # params vector already exists as state, so its sketch takes
            # the matmul path (f32 accumulation — _replace keeps interior
            # algebra f32 under bf16 table storage)
            with jax.named_scope("client_clip"):
                table = table + cfg.weight_decay * sketch_vec(
                    spec._replace(table_dtype=jnp.float32), params_vec
                )
        return table, loss, aux

    def grad_group_tables(params_vec, batch, noise_rng):
        # layerwise overlap: one dummy zeros table PER LEAF GROUP —
        # each tap's backward sketches into its group's table, so a
        # group's cotangent is complete the moment backprop has crossed
        # its layers (no later layer writes it), and the caller may
        # psum it while earlier groups still differentiate
        del noise_rng

        def tapped(tables):
            params = unravel(params_vec)
            leaves, treedef = jax.tree.flatten(params)
            tapped_leaves = list(leaves)
            for gi, (a, b) in enumerate(groups):
                for i in range(a, b):
                    tapped_leaves[i] = sketch_grad_tap(
                        spec, offsets[i], leaves[i], tables[gi]
                    )
            return loss_fn(jax.tree.unflatten(treedef, tapped_leaves), batch)

        with jax.named_scope("client_grad"):
            zeros = tuple(
                _varying_like(jnp.zeros(spec.table_shape, jnp.float32),
                              params_vec)
                for _ in groups
            )
            (loss, aux), tables = jax.value_and_grad(
                tapped, has_aux=True)(zeros)
        if cfg.weight_decay:
            # wd rides the FIRST group's table (the one whose cotangent
            # completes last, so no overlap window shrinks): the group
            # tables only ever matter through their sum
            with jax.named_scope("client_clip"):
                wd = cfg.weight_decay * sketch_vec(
                    spec._replace(table_dtype=jnp.float32), params_vec
                )
                tables = (tables[0] + wd,) + tables[1:]
        return tables, loss, aux

    return grad_group_tables if groups is not None else grad_one_table


def sum_client_grads(grad_one, params_vec, batch, client_ids, rng, *,
                     fused: bool, leafwise_sum=None, live=None, corrupt=None):
    """(sum of client grads [D], loss sum, aux sum) over one shard's clients
    — the NO-client-state aggregation shared by the replicated round's fused
    fast path and the FSDP round (parallel/fsdp.py), extracted so the two
    cannot drift. ``fused``: one flattened-batch grad replaces the per-client
    vmap — identical math when nothing per-client is configured
    (w_loc * flat-mean-grad == sum of per-client mean-grads).
    ``leafwise_sum`` (``make_leafwise_sum``'s product, passed where
    ``resolve_client_path`` says leafwise): the non-fused sum is that
    helper's, the replicated round's own; ``None`` keeps the per-client
    [w_loc, D] vectors (DP noise, fedsim masks).

    ``live``/``corrupt`` ([w_loc] 0/1 floats, fedsim masked aggregation —
    FSDP path only; the round builders disable fusion whenever fedsim is
    on, since a flattened batch has no per-client terms to mask): masked
    clients contribute NOTHING (``jnp.where``, so a zero mask also blocks a
    corrupted NaN), corrupted LIVE clients inject a non-finite payload."""
    w_loc = client_ids.shape[0]
    if fused:
        flat = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            batch,
        )
        g, loss_flat, aux = grad_one(params_vec, flat, rng)
        with jax.named_scope("client_sum"):
            return w_loc * g, w_loc * loss_flat, aux
    if leafwise_sum is not None:
        return leafwise_sum(params_vec, batch)

    def per_client(b, cid):
        return grad_one(params_vec, b, jax.random.fold_in(rng, cid))

    gs, losses, auxes = jax.vmap(per_client)(batch, client_ids)
    with jax.named_scope("client_sum"):
        if live is not None:
            ext = lambda m, a: m.reshape(m.shape + (1,) * (a.ndim - 1))  # noqa: E731
            # corruption first, mask second: a zero mask blocks even a
            # corrupted payload's NaN (same ordering as worker_shard's
            # per_client — only a LIVE corrupted client poisons the
            # aggregate)
            if corrupt is not None:
                gs = jnp.where(ext(corrupt, gs) > 0, jnp.float32(jnp.nan),
                               gs)
            gs = jnp.where(ext(live, gs) > 0, gs, 0.0)
            losses = losses * live
            auxes = jax.tree.map(lambda a: a * ext(live, a), auxes)
        return (
            jnp.sum(gs, axis=0),
            jnp.sum(losses),
            jax.tree.map(lambda a: jnp.sum(a, 0), auxes),
        )


def make_per_client(cfg: Config, comp, grad_one, *, use_fedsim: bool):
    """The per-client compute shared by the synchronous worker shard and the
    asyncfed launch program (asyncfed/round.py): gradient -> local momentum
    -> the compressor's transmit rule -> fedsim corrupt/live masking.
    Extracted verbatim from ``worker_shard`` so the two traces cannot drift
    (the K=W/C=1 bit-identity anchor in tests/test_asyncfed.py depends on
    it). ``params_vec``/``rng``/``lr`` are explicit arguments so callers may
    close over a round-level rng (sync: fold_in(key, state.step)) or a
    launch-version rng (async: fold_in(key, version)) — identical values at
    the anchor."""
    lm = cfg.local_momentum

    def per_client(params_vec, b, cid, vel, err, rng, lr, m=None, c=None):
        noise_rng = jax.random.fold_in(rng, cid)
        g, loss, aux = comp.client_grad(grad_one, params_vec, b, noise_rng, lr)
        with jax.named_scope("client_transmit"):
            u = lm * vel + g if lm > 0 else g
            # the compressor's per-client transmit rule (local_topk: local
            # error feedback + top-k + momentum masking). Dense-transmit
            # modes return u itself: by linearity of device_encode,
            # encode(sum of local clients' u) == sum of their encodings, so
            # each device encodes ONCE downstream instead of per client (8x
            # fewer sketches per chip; ICI still carries only the encoding).
            transmit, new_vel, new_err = comp.client_transmit(u, err, lr)
            if use_fedsim:
                # masked aggregation (fedsim/): chaos corruption NaNs a
                # client's payload FIRST (so the flight-recorder/
                # DivergenceError path is exercised end-to-end), then the
                # live mask zeroes every non-participant's transmit —
                # jnp.where, not multiply, so a zero mask blocks even a
                # corrupted payload's NaN (0 * nan == nan): only a LIVE
                # corrupted client can poison the aggregate. A masked-out
                # client's local momentum/error rows carry forward
                # unmodified (it never participated; reference per-client-
                # state semantics).
                transmit = jnp.where(c > 0, jnp.float32(jnp.nan), transmit)
                transmit = jnp.where(m > 0, transmit, 0.0)
                loss = loss * m
                aux = jax.tree.map(lambda a: a * m, aux)
                if lm > 0:
                    new_vel = jnp.where(m > 0, new_vel, vel)
                if cfg.error_type == "local":
                    new_err = jnp.where(m > 0, new_err, err)
        return transmit, new_vel, new_err, loss, aux

    return per_client


class AggregationPlan(NamedTuple):
    """Trace-time resolution of the aggregation + server-decode strategy
    (cfg.aggregate / cfg.sketch_decode x compressor capability x mesh) —
    shared by the synchronous round and the asyncfed apply program so the
    two resolve identically for a given rung config."""

    use_sparse_agg: bool
    sparse_state: bool  # true_topk sparse agg: server state workers-sharded
    sparse_gather: bool  # local_topk: W*k-pair all_gather rebuild
    sharded_decode: bool  # sketch: per-chip slice decode
    sparse_apply: bool  # either sparse decode: (idx, val) candidate apply


def resolve_aggregation(cfg: Config, comp, Wd: int) -> AggregationPlan:
    use_sparse_agg = comp.use_sparse_aggregate(Wd)
    sparse_state = use_sparse_agg and comp.sparse_aggregate_shards_state
    sparse_gather = (use_sparse_agg and not sparse_state
                     and not comp.needs_sketch_spec)
    sharded_decode = comp.use_sharded_decode(Wd)
    return AggregationPlan(
        use_sparse_agg=use_sparse_agg,
        sparse_state=sparse_state,
        sparse_gather=sparse_gather,
        sharded_decode=sharded_decode,
        sparse_apply=sharded_decode or sparse_state,
    )


def make_aggregate_tail(cfg: Config, comp, plan: AggregationPlan, *,
                        W: int, Wd: int, d: int, axes=WORKERS):
    """The cross-worker aggregation tail, called INSIDE a shard_map body
    over the workers axis: ``(local encoded transmit sum, loss_local, aux
    tree, w_loc) -> (agg, loss_mean, aux_sum)``. Extracted verbatim from
    ``worker_shard`` so the synchronous round and the asyncfed apply
    program share one collective layout per plan.

    ``axes``: the collective axis group — the plain ``WORKERS`` string on
    a single-host mesh, the ``(HOSTS, WORKERS)`` tuple on a multi-host
    one, where every reduction here then spans both levels in one
    collective (a psum over the tuple is bitwise-equal to the flat-axis
    psum over the same devices; the multihost twin tests pin it).

    Layerwise overlap (``cfg.overlap_collectives``): a TUPLE ``local``
    is the sketch-fused backward's per-leaf-group tables — each group
    gets its OWN psum (``psum_segments``) so the latency-hiding
    scheduler can issue it as soon as backprop finishes that group;
    the per-segment psums are bit-equal to one psum of the same
    segments, and the on-chip group sum is the cotangent fan-in the
    monolithic table would have performed (same tolerance class as the
    fused backward itself). The sparse_allreduce leg chunks its pair
    gather (pure data movement — bit-equal)."""
    segs = (
        OVERLAP_SEGMENTS if cfg.overlap_collectives == "layerwise" else None
    )

    def aggregate_tail(local, loss_local, aux, w_loc):
        aux_leaves, aux_def = jax.tree.flatten(aux)
        if isinstance(local, tuple):
            # sketch-fused layerwise: one psum per leaf-group table,
            # issued inside the shard body as the backward produces them
            with jax.named_scope("overlap_layerwise_psum"):
                summed_t = psum_segments(local, axes)
            agg = summed_t[0].astype(jnp.float32)
            for t in summed_t[1:]:
                agg = agg + t.astype(jnp.float32)
            agg = agg / W
            summed = _psum_fused([loss_local] + aux_leaves, axes)
        elif plan.sparse_state:
            # true_topk sparse aggregation: reduce-scatter the dense
            # transmit sum — each chip keeps only its balanced [S] slice
            # of the padded [dp] vector (no O(D) all-reduce ever; the
            # server algebra downstream is sharded to match)
            dp = Wd * -(-d // Wd)
            agg = (
                jax.lax.psum_scatter(
                    jnp.pad(local, (0, dp - d)), axes,
                    scatter_dimension=0, tiled=True,
                )
                / W
            )
            summed = _psum_fused([loss_local] + aux_leaves, axes)
        elif plan.sparse_gather:
            # local_topk sparse aggregation: the device's summed transmit
            # has <= w_loc*k nonzeros (each client sends <= k), so one
            # W*k-pair all_gather + scatter-add rebuilds the replicated
            # dense aggregate — equal to the psum up to f32 summation
            # order, and everything downstream is byte-for-byte the dense
            # server path
            with jax.named_scope("sparse_allreduce"):
                agg = sparse_allreduce(local, w_loc * cfg.k, axes,
                                       segments=segs) / W
            summed = _psum_fused([loss_local] + aux_leaves, axes)
        else:
            # dense path: ONE fused all-reduce carries agg+loss+aux (the
            # bf16 sketch table keeps its own psum — see _psum_fused)
            fused_sum = _psum_fused([local, loss_local] + aux_leaves,
                                    axes)
            agg = fused_sum[0] / W
            summed = fused_sum[1:]
        loss_mean = summed[0] / W
        aux_sum = jax.tree.unflatten(aux_def, summed[1:])
        return agg, loss_mean, aux_sum

    return jax.named_scope("aggregate_tail")(aggregate_tail)


def make_decode_mapped(cfg: Config, comp, mesh, plan: AggregationPlan, *,
                       d: int, Wd: int):
    """The sharded server decode shard_map (None when the plan applies the
    dense decode). Resolved at trace time — a python-level gate like
    telemetry_level/fedsim, so the dense round's trace is untouched when
    off (golden recordings pin it). When on, the server update runs INSIDE
    a second shard_map over the same workers axis: each chip decodes only
    its D/W coordinate slice and the round applies the gathered ~W*k
    (idx, val) candidates as a k-sparse scatter — no [D] estimate, no [D]
    unsketch transient, no dense re-sketch, no D-sized collective (pinned
    by the HLO test in tests/test_sketch_decode.py)."""
    if not plan.sparse_apply:
        return None
    _, e_kind = comp.server_state_kinds()
    axes = worker_axes(mesh)

    def decode_shard(momentum, error, comp_state, agg, lr, step):
        if plan.sparse_state:
            return comp.server_update_sparse(
                momentum, error, comp_state, agg, lr, step,
                axis_name=axes, Wd=Wd, d=d,
            )
        return comp.server_update_sharded(
            momentum, error, comp_state, agg, lr, step,
            axis_name=axes, Wd=Wd, d=d,
        )

    st_spec = P(axes) if plan.sparse_state else P()
    e_spec = (
        P(axes) if plan.sparse_state and e_kind == KIND_DENSE else P()
    )
    return jax.shard_map(
        decode_shard,
        mesh=mesh,
        in_specs=(st_spec, e_spec, P(), st_spec, P(), P()),
        out_specs=(P(), P(), st_spec, e_spec, P()),
    )


def server_phase(cfg: Config, comp, plan: AggregationPlan, decode_mapped,
                 state: FedState, agg, loss, aux, lr, *,
                 count=None, client_err_rows=None):
    """The server half of a round (fed_aggregator _server_helper_*
    ~L380-540), shared by the synchronous round and the asyncfed apply
    program: live-count renormalization -> the compressor's momentum/error
    algebra + update extraction -> the nothing-arrived guard -> params
    apply -> metrics/telemetry assembly.

    ``count``: the traced effective-participation scalar (fedsim live
    count; asyncfed: the staleness-weight sum). ``None`` is a PYTHON-level
    gate — no renorm and no guard are traced at all, the pre-fedsim
    synchronous program. Returns ``(new_params, new_m, new_e, new_comp,
    metrics)``; the caller owns the client-state row scatter and FedState
    assembly (sync scatters once; async writes back in arrival order)."""
    W = cfg.num_workers
    if count is not None:
        # renormalize by the LIVE count: the shard body averaged the
        # psum by W with the dead clients' terms zeroed, and every
        # device_encode is linear (compress/ psum-safety contract), so
        # the scalar correction commutes with the encode for all modes
        # — a masked round with live cohort S equals an unmasked round
        # over exactly S (tests/test_fedsim.py). The max(count, 1)
        # guard keeps an all-dropped round finite; its whole server
        # update is frozen below.
        with jax.named_scope("aggregate_tail"):  # the mean, over LIVE
            scale = W / jnp.maximum(count, 1.0)
            agg = agg * scale
            loss = loss * scale  # loss becomes the mean over LIVE clients
    if plan.sparse_apply:
        # sparse apply: each chip extracts its D/W slice inside the
        # shard_map; the replicated outputs are the gathered ~Wd*k
        # (idx, val) candidate buffers (val==0 padding) + the updated
        # server-state leaves (replicated tables for the sketch
        # decode; workers-sharded [dp] vectors under true_topk sparse
        # aggregation). The update applies as a k-sparse scatter —
        # the dense [D] delta never exists. (do_topk_down is moot
        # here: every sparse-apply mode has dense_delta=False — the
        # candidates are already <= k pairs.)
        scope = ("sketch_decode_sharded" if plan.sharded_decode
                 else "sparse_aggregate_decode")
        with jax.named_scope(scope):
            g_idx, g_val, new_m, new_e, new_comp = decode_mapped(
                state.momentum, state.error, state.comp, agg, lr,
                state.step,
            )
    else:
        # dense decode (legacy path): the compressor returns the
        # APPLIED delta (w -= delta), full-[D] on every chip. The
        # named_scope is an HLO marker like telemetry_diag's: its
        # absence from the compiled sharded round proves this branch
        # was never traced (tests/test_sketch_decode.py).
        with jax.named_scope("server_decode_dense"):
            delta, new_m, new_e, new_comp = comp.server_update(
                state.momentum, state.error, state.comp, agg, lr,
                state.step,
            )
        if cfg.do_topk_down and comp.dense_delta:
            # downlink compression (reference down-compression flag):
            # the broadcast weight delta is itself top-k sparsified, so
            # the download really is 2k floats (bytes_per_round
            # accounting). Lossy by design, as in the reference —
            # coordinates dropped here are NOT re-banked into client
            # error. Skipped for compressors whose delta is already
            # compressed (sketch/true_topk: <= k nonzeros; powersgd:
            # rank-r factored — a full-[D] selection there would be a
            # pure waste).
            delta = comp.topk(delta, cfg.k)
    with jax.named_scope("apply_update"):
        if count is not None:
            # all-clients-dropped guard: nothing arrived, so nothing may
            # move — params freeze (the dense delta, or the sharded
            # candidate VALUES whose scatter then adds 0.0, zero out) and
            # every server-state leaf (momentum/error/compressor-private)
            # carries forward; the host-side fedsim/all_dropped sentinel
            # rides the metrics instead of a 0/0 poisoning the run
            ok = count > 0

            def keep(new, old):
                return jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                    new, old)

            if plan.sparse_apply:
                g_val = jnp.where(ok, g_val, 0.0)
            else:
                delta = jnp.where(ok, delta, 0.0)
            new_m = keep(new_m, state.momentum)
            new_e = keep(new_e, state.error)
            new_comp = keep(new_comp, state.comp)
        new_params = (
            state.params_vec.at[g_idx].add(-g_val)
            if plan.sparse_apply
            else state.params_vec - delta
        )
    metrics = {"loss": loss, **aux}
    if cfg.telemetry_level >= 1:
        # in-graph health diagnostics (telemetry/diagnostics.py): ride
        # the metrics dict -> the deferred drain path, no extra
        # fences. The gate is python-level at trace time, so level 0
        # traces NOTHING here (bit-identical round; HLO smoke test).
        with jax.named_scope("telemetry_diag"):
            common = dict(
                agg=agg, new_params=new_params, loss=loss, lr=lr,
                momentum=state.momentum, error=state.error,
                extra=state.comp, new_error=new_e,
            )
            metrics.update(
                round_diagnostics_sparse(
                    cfg, comp, idx=g_idx, val=g_val, **common
                )
                if plan.sparse_apply
                else round_diagnostics(
                    cfg, comp, delta=delta,
                    client_err_rows=(
                        client_err_rows
                        if cfg.error_type == "local"
                        else None
                    ),
                    **common,
                )
            )
    return new_params, new_m, new_e, new_comp, metrics


def build_round_fn(
    cfg: Config,
    loss_fn: Callable,
    unravel: Callable,
    mesh,
    spec: Optional[CountSketch] = None,
    _jit: bool = True,
    *,
    d: Optional[int] = None,
    trace_hook: Optional[Callable] = None,
):
    """Compile the per-round step.

    Args:
      loss_fn: ``(params_pytree, batch) -> (loss, aux_metrics)``.
      unravel: flat [D] vector -> params pytree (from ``ravel_params``).
      mesh: a Mesh with a ``workers`` axis of size cfg.num_devices.
      spec: CountSketch spec (modes whose compressor needs_sketch_spec).
      d: flat param dimension, REQUIRED (compressor geometry, e.g.
        powersgd's matricization) — pass ``ravel_params(params)[0].size``.
        Keyword-only so legacy positional call sites fail loudly.
      trace_hook: optional callable invoked with the round's arguments at
        TRACE time only (telemetry.RetraceSentinel.hook) — a pure python
        side effect, so the traced program is bit-identical with or
        without it; counts/hard-fails silent mid-run retraces.
    Returns:
      With HBM-resident client state (default):
        ``round_fn(state, client_ids [W], batch {k: [W, ...]}, lr) ->
        (new_state, metrics)`` — jitted, donates ``state``.
      With ``--client_store host|mmap`` (cfg.client_state_hosted):
        ``round_fn(state, client_ids, batch, lr, vel_rows [W,D]|(),
        err_rows [W,D]|()) -> (new_state, metrics, new_vel, new_err)`` —
        the [num_clients, D] banks live in a clientstore/ store (host
        RAM or a memory-mapped file, NOT in FedState) and the session's
        CohortStreamer gathers/scatters the participants' rows around
        each call, so the compiled round never sees a [C, D] operand.
    """
    if d is None:
        raise ValueError(
            "build_round_fn requires d= (the flat param dimension); "
            "pass ravel_params(params)[0].size"
        )
    comp = get_compressor(cfg, d=d, spec=spec)
    # momentum masking (dampening): AUTO (None) resolves per compressor on
    # the measured four-corner evidence (r4 lab, runs/r4_retune.log) — see
    # each compressor's default_dampening / _dampening_warnings in
    # compress/ (sketch warns: FetchSGD Alg 1 does not mask sketched
    # momentum; true_topk warns on AUTO: the reference masks there).
    comp.resolved_dampening()
    W = cfg.num_workers
    f32 = jnp.float32

    # ---- per-client gradient (the fed_worker forward_grad analog) --------
    grad_one = make_grad_one(cfg, loss_fn, unravel)

    lm = cfg.local_momentum

    # fedsim masked aggregation (fedsim/ package): a PYTHON-level gate like
    # cfg.telemetry_level — when off, nothing below is traced and the
    # compiled round is bit-identical to a pre-fedsim program (golden
    # parity recordings pin it).
    use_fedsim = bool(cfg.fedsim_enabled)

    # fused-clients fast path (cfg.fuse_clients): one flattened-batch grad
    # replaces the per-client vmap — identical math when nothing per-client
    # is configured (sum of per-client mean-grads == w_loc * flat mean-grad).
    # fedsim masking is inherently per-client, so it forces the vmap path.
    fused = (
        cfg.fuse_clients
        and comp.supports_fused_clients
        and lm == 0
        and cfg.error_type != "local"
        and cfg.max_grad_norm is None
        and cfg.dp_noise_multiplier == 0
        and not use_fedsim
    )

    # sketch-FUSED backward (cfg.sketch_fused_bwd): the fused path's one
    # gradient per device is produced directly as an encoded sketch table
    # by per-leaf custom_vjp taps — the flat [D] grad concat is never
    # traced (make_sketch_grad_one). Config validated every gate at
    # construction; this assert is the defense against a future gate
    # drifting out of sync with the validation.
    sketch_fused = bool(cfg.sketch_fused_bwd)
    if sketch_fused and not (fused and comp.supports_fused_backward):
        raise ValueError(
            "sketch_fused_bwd requires the fused flattened-batch path and "
            f"a fused-backward-capable compressor (mode={cfg.mode!r}, "
            f"fused={fused}) — Config validation should have caught this"
        )
    # layerwise collective overlap (cfg.overlap_collectives): the fused
    # backward produces per-leaf-group tables so the aggregation tail can
    # psum each the moment backprop finishes it — a python-level gate
    # like telemetry_level (overlap='none' traces byte-identically to a
    # pre-overlap build; tests/test_overlap_collectives.py pins it)
    overlap_layerwise = cfg.overlap_collectives == "layerwise"
    grad_table_one = (
        make_sketch_grad_one(
            cfg, loss_fn, unravel, spec, d=d,
            overlap_segments=OVERLAP_SEGMENTS if overlap_layerwise else None,
        )
        if sketch_fused
        else None
    )

    # ---- on-mesh aggregation strategy (cfg.aggregate; ops/collectives):
    # resolved at trace time from the compressor capability + the mesh —
    # a python-level gate like telemetry_level/fedsim, so the dense
    # round's trace is untouched when off. sparse_gather (local_topk):
    # the replicated dense aggregate rebuilds from one W*k-pair
    # all_gather + scatter-add; everything downstream (server algebra,
    # fedsim scale, dampening, offload) is unchanged. sparse_state
    # (true_topk): the dense transmit reduce-scatters to [S] slices, the
    # server momentum/error live SHARDED over the workers axis, and the
    # decode shard_map below runs the FSDP slice algebra — the only
    # vector exchange is the <= W*k candidate pair all_gather. The sketch
    # EF re-sketch ride lives inside the compressor (compress/sketch.py
    # _ride_pair_exchange); its table psum is already O(r*c), not O(D).
    # worker-axes resolution (multihost/): on a 4-axis (hosts, workers,
    # model, seq) mesh the batch shards and every worker collective runs
    # over the (HOSTS, WORKERS) tuple — Wd is the TOTAL worker-slot count
    # across hosts, so sparse-state slice geometry is unchanged vs the
    # flat mesh of the same size
    axes = worker_axes(mesh)
    Wd = worker_axis_size(mesh)
    plan = resolve_aggregation(cfg, comp, Wd)
    sparse_state = plan.sparse_state

    # leafwise or per-client vectors (resolve_client_path): two paths that
    # share the gradient closure and nothing after it
    leafwise = not fused and resolve_client_path(cfg, comp) == LEAFWISE
    leafwise_sum = make_leafwise_sum(cfg, loss_fn, unravel)
    per_client = make_per_client(cfg, comp, grad_one, use_fedsim=use_fedsim)
    aggregate_tail = make_aggregate_tail(cfg, comp, plan, W=W, Wd=Wd, d=d,
                                         axes=axes)

    # ---- the shard body: this IS the worker process ----------------------
    def worker_shard(params_vec, batch, client_ids, vel_rows, err_rows, rng,
                     lr, *fs):
        # batch: one shard's {k: [w_loc, ...]}; vel/err: [w_loc, D] or ();
        # fs: (live_mask [w_loc], corrupt [w_loc]) iff use_fedsim
        #
        # pcast(to="varying") is load-bearing: under shard_map's vma
        # semantics, differentiating w.r.t. a REPLICATED input auto-inserts a
        # psum over the mesh axis in the transpose, which would hand every
        # shard the cross-worker SUMMED gradient. Marking the param vector
        # varying keeps AD shard-local, so per-client momentum/error/
        # compression below see each client's own gradient; aggregation then
        # happens exactly once, at the explicit psum.
        params_vec = jax.lax.pcast(params_vec, axes, to="varying")

        w_loc = client_ids.shape[0]
        if fused and sketch_fused:
            # the gradient IS the table: per-leaf cotangent sketches
            # accumulated during the backward pass (no flat [D] grad, no
            # separate device_encode sketch pass). Same flattened-batch
            # identity as sum_client_grads' fused branch: w_loc * the
            # flat-batch gradient's sketch == the sketch of the summed
            # client transmits, by linearity.
            flat = jax.tree.map(
                lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
                batch,
            )
            with jax.named_scope("sketch_fused_bwd"):
                table, loss_flat, aux = grad_table_one(params_vec, flat, rng)
            with jax.named_scope("encode"):
                if overlap_layerwise:
                    # per-leaf-group tables (a tuple): encode each group —
                    # the aggregate tail psums them segment-by-segment
                    local = tuple(
                        comp.encode_grad_table(w_loc * t) for t in table
                    )
                else:
                    local = comp.encode_grad_table(w_loc * table)
            loss_local = w_loc * loss_flat
            new_vel = jnp.zeros((w_loc, 1), f32)
            new_err = jnp.zeros((w_loc, 1), f32)
        elif fused:
            local, loss_local, aux = sum_client_grads(
                grad_one, params_vec, batch, client_ids, rng, fused=True
            )
            new_vel = jnp.zeros((w_loc, 1), f32)
            new_err = jnp.zeros((w_loc, 1), f32)
        elif leafwise:
            local, loss_local, aux = leafwise_sum(params_vec, batch)
            new_vel = jnp.zeros((w_loc, 1), f32)
            new_err = jnp.zeros((w_loc, 1), f32)
        else:
            vels = vel_rows if lm > 0 else jnp.zeros((w_loc, 1), f32)
            errs = err_rows if cfg.error_type == "local" else jnp.zeros(
                (w_loc, 1), f32
            )
            # fs is (live, corrupt) under fedsim, () otherwise — per_client
            # defaults m/c to None, so one call site serves both traces
            transmit, new_vel, new_err, loss, aux = jax.vmap(
                lambda b, cid, vel, err, *fs_: per_client(
                    params_vec, b, cid, vel, err, rng, lr, *fs_
                )
            )(batch, client_ids, vels, errs, *fs)
            with jax.named_scope("client_sum"):
                local = jnp.sum(transmit, axis=0)
                loss_local = jnp.sum(loss)
                aux = jax.tree.map(lambda a: jnp.sum(a, 0), aux)
        if not (fused and sketch_fused):  # fused-bwd already encoded above
            with jax.named_scope("encode"):
                local = comp.device_encode(local)  # linear -> psum is exact
        agg, loss_mean, aux_sum = aggregate_tail(local, loss_local, aux,
                                                 w_loc)
        return agg, loss_mean, aux_sum, new_vel, new_err

    shard_spec = P(axes)
    in_specs = (P(), shard_spec, shard_spec, shard_spec, shard_spec, P(), P())
    if use_fedsim:
        in_specs = in_specs + (shard_spec, shard_spec)  # live mask, corrupt
    worker_mapped = jax.shard_map(
        worker_shard,
        mesh=mesh,
        in_specs=in_specs,
        # sparse_state: agg leaves the shard_map as this chip's [S] slice
        # of the workers-sharded [dp] aggregate, not a replicated [d]
        out_specs=(shard_spec if sparse_state else P(), P(), P(),
                   shard_spec, shard_spec),
    )

    # ---- sharded server decode (the FSDP decode discipline on replicated
    # state; compress/sketch.py server_update_sharded) — see
    # make_decode_mapped. Both sparse-apply decodes return gathered
    # (idx, val) candidate pair buffers instead of a dense delta; only the
    # STATE placement differs (sketch: replicated tables, sharded
    # extraction; true_topk sparse aggregation: momentum/error themselves
    # sharded over workers).
    decode_mapped = make_decode_mapped(cfg, comp, mesh, plan, d=d, Wd=Wd)

    def round_fn(state: FedState, client_ids, batch, lr, vel_rows=(),
                 err_rows=(), env=()):
        if trace_hook is not None:  # runs at trace time only (no ops)
            trace_hook(state, client_ids, batch, lr, vel_rows, err_rows,
                       env=env)
        rng = jax.random.fold_in(jax.random.key(cfg.seed), state.step)
        fs = ()
        if use_fedsim:
            if not env:
                raise ValueError(
                    "fedsim is enabled (cfg.fedsim_enabled) but no env was "
                    "passed — supply env=(live_mask [W], corrupt [W], "
                    "live_count) from FedEnvironment.round_env "
                    "(FederatedSession.train_round does this)"
                )
            live_mask, corrupt, live_count = env
            fs = (live_mask, corrupt)
        if not cfg.client_state_hosted:
            vel_rows = (
                state.client_vel[client_ids] if lm > 0 else jnp.zeros((W, 1), f32)
            )
            err_rows = (
                state.client_err[client_ids]
                if cfg.error_type == "local"
                else jnp.zeros((W, 1), f32)
            )
        else:
            if not needs_client_vel(cfg):
                vel_rows = jnp.zeros((W, 1), f32)
            if not needs_client_err(cfg):
                err_rows = jnp.zeros((W, 1), f32)
        agg, loss, aux, new_vel, new_err = worker_mapped(
            state.params_vec, batch, client_ids, vel_rows, err_rows, rng, lr,
            *fs
        )
        # ---- server update (fed_aggregator _server_helper_* ~L380-540):
        # renorm + the compressor's momentum/error algebra + the
        # all-dropped guard + metrics assembly, shared with the asyncfed
        # apply program via server_phase so the semantics cannot drift
        # between decodes or engines. count=None (non-fedsim) is a
        # python-level gate: no renorm/guard ops are traced at all.
        new_params, new_m, new_e, new_comp, metrics = server_phase(
            cfg, comp, plan, decode_mapped, state, agg, loss, aux, lr,
            count=live_count if use_fedsim else None,
            client_err_rows=new_err,
        )
        if cfg.client_state_hosted:
            new_state = FedState(
                new_params, new_m, new_e, (), (), state.step + 1, new_comp
            )
            return new_state, metrics, new_vel, new_err
        with jax.named_scope("apply_update"):
            client_vel = (
                state.client_vel.at[client_ids].set(new_vel)
                if lm > 0 else state.client_vel
            )
            client_err = (
                state.client_err.at[client_ids].set(new_err)
                if cfg.error_type == "local"
                else state.client_err
            )
        return (
            FedState(new_params, new_m, new_e, client_vel, client_err,
                     state.step + 1, new_comp),
            metrics,
        )

    if not _jit:
        # raw traceable round for callers that wrap it in a larger jitted
        # program (the device-resident-data path in FederatedSession)
        return round_fn
    if cfg.client_state_hosted:
        return jax.jit(round_fn, donate_argnums=(0, 4, 5))
    return jax.jit(round_fn, donate_argnums=(0,))


def build_eval_fn(loss_fn: Callable, unravel: Callable, mask_batch: Callable):
    """Jitted eval step: (params_vec, batch-with-_valid) -> metric sums.

    The reference's val path (fed_worker.py ~L290-340) runs loss + #correct
    with no compression; here padded tail rows are masked to IGNORE_INDEX by
    ``mask_batch(batch, valid_row_mask)`` so static shapes survive jit.
    Multi-chip validation comes from the CALLER's batch sharding (the
    session device_puts eval batches over the mesh's ``workers`` axis, see
    FederatedSession._put_eval_batch) — jit then partitions the eval over
    every chip, the analog of the reference round-robining val across
    workers.
    """

    @jax.jit
    def eval_step(params_vec, batch):
        batch = dict(batch)
        valid = batch.pop("_valid")
        n = next(iter(batch.values())).shape[0]
        row_mask = jnp.arange(n) < valid
        batch = mask_batch(batch, row_mask)
        params = unravel(params_vec)
        loss, aux = loss_fn(params, batch)
        return {"loss_sum": loss * valid.astype(jnp.float32), **aux}

    return eval_step


def mask_classification(batch, row_mask):
    return {**batch, "y": jnp.where(row_mask, batch["y"], IGNORE_INDEX)}


def mask_gpt2(batch, row_mask):
    return {
        **batch,
        "mc_labels": jnp.where(row_mask, batch["mc_labels"], IGNORE_INDEX),
        "lm_labels": jnp.where(
            row_mask[:, None, None], batch["lm_labels"], IGNORE_INDEX
        ),
    }
