"""Round/cohort trace ids, critical-path attribution, profiler windows.

Three pieces, all pure host code (nothing here ever runs under trace —
the jitted round programs are bit-identical with tracing on or off):

**Trace ids.** Every round and every async cohort gets a deterministic
id minted at realization time — ``round_trace_id(step) == "r<step>"``
for rounds (the root of each causal tree), ``cohort_trace_id(c) ==
"c<cohort>"`` for asyncfed cohorts (whose ``parent`` is the round that
launched them). All four planes stamp their spans with the owning id
(``PhaseSpans.span(..., trace_id=, parent=)``): the asyncfed staging
lane (sampler draw, fedsim realize, H2D stage), the PR 17 clientstore
streamer (gather, writeback, flush), the PR 15 asyncfed engine (launch,
buffer residency, apply dispatch/drain) and the dispatch plane
(device_put, round dispatch, metric drain). A Perfetto dump then
renders each cohort as a causally-linked tree across lanes instead of
uncorrelated per-lane events. Determinism is deliberate: twin runs mint
identical ids, so trace-correlated dumps stay diffable.

**CriticalPath.** Interval arithmetic over the recorded spans (the same
style as PR 16's ``collective_exposure_ms``) decomposes each round's
wall-clock into EXCLUSIVE stage times. The stage taxonomy is ``STAGES``:
``data`` (sampler draw + fedsim realize + data-load wait), ``h2d``
(device_put / prefetch stage / clientstore gather), ``dispatch`` (round
or cohort dispatch wait), ``collective`` (the exposed — un-overlapped —
part of collective-tagged spans), ``drain`` (metric drain, checkpoint,
snapshot, deferred async drain), ``writeback`` (clientstore writeback +
flush fence) and ``idle`` (wall-clock no recorded span covers).
Exclusivity is by priority assignment — collective first, then drain,
writeback, dispatch, h2d, data, each stage's interval union clipped to
the round window minus everything already assigned, idle last as the
remainder — so per-round stage times are DISJOINT by construction and
sum to exactly the round's wall-clock. The binding (critical) stage is
the argmax. Per-round ``trace/critical_stage`` (index into ``STAGES``)
and ``trace/<stage>_exclusive_ms`` scalars ride telemetry level >= 1
(schema v11) with LAGGED semantics: the scalars emitted at round N
describe round N-2, the newest round whose spans are complete at
emission time (N-1 just dispatched; its drain has not run). Earlier
rounds emit the zeros row — the constant-key-set discipline
pack_metric_dicts requires.

**Run reports & profiler windows.** ``build_run_report(run_dir)`` turns
a run directory (spans dump + metrics.jsonl + flight records +
perf_report.json, whichever exist) into a versioned ``run_report.json``
— per-stage p50/p95, attribution fractions summing to 1, anomaly flags
(staleness drift, cache-hit collapse) — consumed by
``scripts/analyze_run.py`` and written at train-loop close when
``cfg.run_report`` (the default; accuracy_run.py opts out like it does
for perf_audit). ``ProfilerWindow`` arms a programmatic
``jax.profiler`` capture over ``--profile_rounds A-B`` (inclusive),
clamped to the steady-state window (MIN_WARMUP_STEPS, like
StepProfiler), fenced at entry/exit so the deferred-drain pipeline's
in-flight work retires outside the captured window, and degrading
gracefully where the backend cannot trace (the failure is logged with
its named reason, never raised).
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

# Exclusive-stage taxonomy, in report order. ``trace/critical_stage``
# is emitted as the INDEX into this tuple (scalar streams are numeric);
# reports and bench rows carry the name. Order is part of the schema —
# append-only.
STAGES: Tuple[str, ...] = (
    "data", "h2d", "dispatch", "collective", "drain", "writeback", "idle",
)

# The one vocabulary of layer boundaries INSIDE the compiled round: every
# ``jax.named_scope`` the round's code opens, with what it bounds. The
# names are op metadata only (no operation added); a device trace carries
# them wrapped by the transformation they were traced under
# (``vmap(jvp(client_grad))`` forward, ``vmap(transpose(jvp(client_grad)))``
# backward). tests/test_round_scopes.py holds the lowered rounds, the
# source's ``named_scope`` literals and the benchmark's patterns to this
# list; no name contains another, so a substring pattern stays exact. The
# top-level scopes (``data_gather``, ``client_*``, ``encode``,
# ``aggregate_tail``, the three decode markers, ``apply_update``) do not
# overlap; ``estimate_all``/``topk_select``/``ef_resketch`` nest under
# whichever decode marker is traced (the decode's remainder, the momentum
# and error algebra on the banks, carries the marker's name alone).
ROUND_SCOPES: Tuple[Tuple[str, str], ...] = (
    ("data_gather", "in-graph gather of the round's batch from the "
                    "device-resident dataset, and the device-side "
                    "augmentation (parallel/api.py)"),
    ("client_grad", "value_and_grad of the loss (parallel/round.py); on "
                    "the per-client-vector path also each client's "
                    "flat-gradient concat"),
    ("flat_grad_concat", "the [D] concat of gradient leaves: on the leafwise "
                         "path the ONE concat of the summed leaves, under "
                         "client_sum, once a shard; on the per-client-vector "
                         "path each client's own, inside client_grad; absent "
                         "under sketch_fused_bwd"),
    ("sketch_fused_bwd", "the sketch-fused backward: the gradient produced "
                         "as a table by per-leaf taps"),
    ("client_clip", "weight decay and the clip's [w_loc] scales, leaf by "
                    "leaf (a client's squared norm is a sum of per-leaf sums "
                    "of squares); on the per-client-vector path weight "
                    "decay, clip_by_global_norm and DP noise on the flat [D] "
                    "gradient"),
    ("client_transmit", "local momentum and the compressor's per-client "
                        "transmit rule"),
    ("client_sum", "the clipped sum over the shard's clients, leaf by leaf "
                   "under the [w_loc] scales, then flat_grad_concat, with the "
                   "losses and aux; on the per-client-vector path the sum of "
                   "the [w_loc, D] transmits"),
    ("encode", "comp.device_encode / encode_grad_table: the sketch "
               "accumulate (identity for dense modes)"),
    ("aggregate_tail", "the cross-worker psum / sparse all-reduce and the "
                       "mean (make_aggregate_tail)"),
    ("overlap_layerwise_psum", "the per-leaf-group psums of the layerwise "
                               "overlap, inside aggregate_tail"),
    ("sparse_allreduce", "the W*k-pair exchange of local_topk's sparse "
                         "aggregation, inside aggregate_tail"),
    ("server_decode_dense", "the dense server update: comp.server_update"),
    ("sketch_decode_sharded", "the sketch's sharded server decode"),
    ("sparse_aggregate_decode", "true_topk's server update on sharded "
                                "state"),
    ("estimate_all", "median-of-rows estimate of all D coordinates "
                     "(ops/countsketch.py)"),
    ("topk_select", "the selection: threshold bisection, lax.top_k / "
                    "approx_max_k, compact_nonzero (ops/topk.py, unsketch*)"),
    ("ef_resketch", "zeroing the heavy hitters out of the banks: the "
                    "update's <= k pairs (compact_nonzero_tree on the dense "
                    "decode) through sketch_sparse, the dampening's "
                    "estimate_at + sketch_sparse (compress/sketch.py)"),
    ("apply_update", "params_vec - delta / the k-sparse scatter, and the "
                     "client-state row scatter"),
    ("telemetry_diag", "in-graph diagnostics; traced at telemetry level "
                       ">= 1 only"),
)

# The scopes a model opens inside ``client_grad`` (models/laguna.py and the
# loss beside it; ``param_unravel`` is parallel/round.py's, listed here
# because it nests under ``client_grad``): the same vocabulary and the same
# tests as ROUND_SCOPES
# (no name contains another, here or across the two lists; the source opens
# no scope outside them), kept apart because every one nests under
# ``client_grad``: the benchmark's ``round.unscoped_s_per_round`` pattern is
# ROUND_SCOPES and needs none of these. A trace carries them wrapped like
# ``client_grad`` itself, and again by ``checkpoint`` / ``rematted_computation``
# where a block is recomputed in the backward pass.
MODEL_SCOPES: Tuple[Tuple[str, str], ...] = (
    ("moe_route", "the router: float32 product, sigmoid, top-k, weights"),
    ("moe_dispatch", "the sort of a client's assignments by held slot, "
                     "the held slots' counts, and the gather of the held "
                     "experts' rows"),
    ("moe_experts", "the grouped products of the held experts and the "
                    "activation between them"),
    ("moe_combine", "the routing weight on each row and the scatter-add "
                    "back to the tokens"),
    ("attn_full", "the block-sparse attention kernels of a full causal "
                  "layer"),
    ("attn_window", "the block-sparse attention kernels of a "
                    "sliding-window layer"),
    ("lm_head", "the final norm, the logits over the vocabulary held and "
                "the cross-entropy"),
    ("attn_proj", "the attention's dense products: query, key, value, the "
                  "output gate and the output projection"),
    ("mlp_dense", "the dense feed-forward of the leading layer and the "
                  "shared expert of a routed one (SwiGLU)"),
    ("attn_index", "an indexed layer's learned index: its three projections "
                   "and, nested under attn_select, the kernel that makes the "
                   "scores and selects"),
    ("attn_select", "finding each query's topk largest index scores: one "
                    "kernel makes a query block's scores and radix-selects "
                    "their threshold (ops/pallas/indexed_attention.py); "
                    "nests under attn_index"),
    ("attn_sparse", "the attention kernels over the chosen keys (forward, "
                    "dq, dkv: each rebuilds the mask from the index's "
                    "operands) with the transposes around them"),
    ("attn_blockdiff", "the block-sparse attention kernels of a "
                       "block-diffusion layer: a noised and a clean copy of "
                       "a row under block diffusion's mask"),
    ("diffusion_streams", "the noised copy of a row's ids, its join with the "
                          "clean copy into one stream, and the split that "
                          "keeps the noised stream for the head"),
    ("diffusion_loss", "the head of a block-diffusion model (lm_head nests "
                       "under it) and the weighted cross-entropy of the "
                       "noised stream's positions"),
    ("block_norm", "the float32 RMS norm of the residual stream on a block's "
                   "input, twice a layer (the final norm stays in lm_head)"),
    ("attn_qk_prep", "what is done to q, k, v between the projections and the "
                     "attention kernel: the heads' norms, the rotary tables "
                     "and the rotation, 1 / sqrt(d), the casts"),
    ("residual_add", "the residual stream's adds, twice a layer (what XLA "
                     "does not fuse into a neighbour)"),
    ("embed", "the embedding's gather and, transposed, the scatter-add of "
              "its gradient (a whole word in a pattern: the module is "
              "named embed too)"),
    ("moe_loop", "the client-after-client loop the expert layer's "
                 "sequential_vmap makes: its own op, each client's slices and "
                 "updates, the tier cond / switch; moe_dispatch, moe_experts "
                 "and moe_combine nest under it"),
    ("param_unravel", "the split of the [D] vector into the model's leaves "
                      "(parallel/round.py::_client_value_and_grad): it is "
                      "no model's, and sits here because it nests under "
                      "client_grad; the gradient is taken with respect to its "
                      "output, so it has no backward pass"),
)

# Model scopes opened since the three closing metrics were written
# (benchmark/layers/model.unnamed_s_per_round.json,
# model.outside_client_grad_s_per_round.json, round.nameless_s_per_round.json
# name every scope of MODEL_SCOPES in their patterns, and a program change
# does not edit the benchmark's files): the recurrent and the latent mixers'.
# Each has a per-layer metric of its own. A stopgap: the benchmark change
# that appends these names to those three patterns moves them into
# MODEL_SCOPES and deletes this tuple (ROADMAP.md, D20). Until
# then the closing metrics, which no recurrent cell reports, read these ops
# as named by ``client_grad`` alone; read the two tuples together
# (``MODEL_SCOPES + LATER_MODEL_SCOPES``) and add no reader that keeps them
# apart.
LATER_MODEL_SCOPES: Tuple[Tuple[str, str], ...] = (
    ("attn_kda", "the chunked gated delta rule of a KDA layer: the pairs of "
                 "positions factored at a boundary, the triangular solve, the "
                 "products outside the state and the scan that carries the "
                 "state from chunk to chunk"),
    ("kda_prep", "a KDA layer's work around the rule: the short "
                 "convolutions and SiLU, the L2 norms, the decay gate, beta "
                 "and the gated output norm"),
    ("attn_latent", "the attention kernels of a latent-attention layer "
                    "(keys and values decompressed from the latent)"),
)

# Priority order for exclusive assignment (idle is always the remainder).
# Exposed collective first — it is the scarce signal the overlap work
# (PR 16) exists to shrink; then the post-dispatch phases, then the
# producer phases. A microsecond covered by two spans is charged to the
# highest-priority stage only.
_PRIORITY: Tuple[str, ...] = (
    "collective", "drain", "writeback", "dispatch", "h2d", "data",
)

# span name -> stage. Unknown span names still shape the round window
# and cover collective exposure, but are not charged to a named stage
# (their uncovered time lands in idle) — forward-compatible with new
# span sites.
_SPAN_STAGE: Dict[str, str] = {
    "data_load": "data",
    "prefetch_realize": "data",
    "fedsim_env": "data",
    "device_put": "h2d",
    "prefetch_stage": "h2d",
    "clientstore_gather": "h2d",
    "round_dispatch": "dispatch",
    "async_launch": "dispatch",
    "async_apply": "dispatch",
    "async_apply_dispatch": "dispatch",
    "async_apply_drain": "drain",
    "metric_drain": "drain",
    "checkpoint": "drain",
    "snapshot": "drain",
    "clientstore_writeback": "writeback",
    "clientstore_flush": "writeback",
}

# spans recorded for Perfetto correlation only, never path analysis: a
# cohort's buffer residency OVERLAPS several rounds by design — letting
# it shape a round's window (or cover collective exposure) would charge
# wall-clock that was never serial
_NON_PATH_SPANS = frozenset({"async_buffer_residency"})


# ---------------------------------------------------------------------------
# trace ids
# ---------------------------------------------------------------------------
def round_trace_id(step: int) -> str:
    """The round's trace id (``r<step>``) — the root of its causal tree.
    Deterministic on purpose: twin runs mint identical ids."""
    return f"r{int(step)}"


def cohort_trace_id(cohort: int) -> str:
    """An async cohort's trace id (``c<cohort>``); its ``parent`` is
    ``round_trace_id`` of the server round that launched it."""
    return f"c{int(cohort)}"


def step_of_trace_id(trace_id) -> Optional[int]:
    """``"r<step>"`` -> the round index, else None. Span sites that only
    receive a trace id (the clientstore streamer — it does not know the
    round clock) recover the owning step for their events this way; the
    deterministic id format makes it total on round ids."""
    if isinstance(trace_id, str) and trace_id[:1] == "r":
        try:
            return int(trace_id[1:])
        except ValueError:
            return None
    return None


# ---------------------------------------------------------------------------
# interval arithmetic (us since recorder epoch, [a, b) half-open)
# ---------------------------------------------------------------------------
def _union(ivs: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _subtract(ivs, cover) -> List[List[float]]:
    """``union(ivs) - union(cover)`` as a sorted disjoint interval list."""
    out: List[List[float]] = []
    cover = _union(cover)
    for a, b in _union(ivs):
        cur = a
        for ca, cb in cover:
            if cb <= cur:
                continue
            if ca >= b:
                break
            if ca > cur:
                out.append([cur, ca])
            cur = max(cur, cb)
            if cur >= b:
                break
        if cur < b:
            out.append([cur, b])
    return out


def _clip(ivs, lo: float, hi: float) -> List[List[float]]:
    return [[max(a, lo), min(b, hi)] for a, b in ivs
            if min(b, hi) > max(a, lo)]


def _total(ivs) -> float:
    return sum(b - a for a, b in ivs)


# ---------------------------------------------------------------------------
# per-round critical-path decomposition
# ---------------------------------------------------------------------------
class CriticalPath:
    """Decompose rounds' wall-clock into exclusive stage times from a
    sequence of Chrome-trace "X" events (a ``PhaseSpans`` ring or a
    loaded spans dump). Pure interval arithmetic; see the module
    docstring for the assignment rules."""

    def __init__(self, events: Sequence[dict]):
        # bucket once by round: analyzers ask for many rounds
        self._by_step: Dict[int, List[dict]] = {}
        for ev in events:
            if ev.get("ph") != "X" or ev.get("name") in _NON_PATH_SPANS:
                continue
            try:
                step = int(ev.get("args", {}).get("step"))
            except (TypeError, ValueError):
                continue
            self._by_step.setdefault(step, []).append(ev)

    def steps(self) -> List[int]:
        return sorted(self._by_step)

    def round_breakdown(self, step: int) -> Optional[dict]:
        """``{"step", "wall_ms", "critical_stage", "stages_ms": {...}}``
        for one round, or None when no spans carry that step. Stage
        times are disjoint and sum to exactly ``wall_ms``."""
        evs = self._by_step.get(int(step))
        if not evs:
            return None
        ivs = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
               for e in evs]
        lo = min(a for a, _ in ivs)
        hi = max(b for _, b in ivs)
        coll, comp = [], []
        by_stage: Dict[str, List[Tuple[float, float]]] = {}
        for ev, iv in zip(evs, ivs):
            if ev.get("args", {}).get("collective"):
                coll.append(iv)
            else:
                comp.append(iv)
            stage = _SPAN_STAGE.get(ev.get("name"))
            if stage is not None:
                by_stage.setdefault(stage, []).append(iv)
        stages_ms = {s: 0.0 for s in STAGES}
        # exposed collective: collective-tagged time no compute span
        # covers (the PR 16 definition, per round)
        assigned = _clip(_subtract(coll, comp), lo, hi)
        stages_ms["collective"] = _total(assigned) / 1e3
        for stage in _PRIORITY:
            if stage == "collective":
                continue
            excl = _subtract(_clip(by_stage.get(stage, []), lo, hi),
                             assigned)
            stages_ms[stage] = _total(excl) / 1e3
            assigned = _union(assigned + excl)
        wall_ms = (hi - lo) / 1e3
        stages_ms["idle"] = max(0.0, wall_ms - _total(assigned) / 1e3)
        critical = max(STAGES, key=lambda s: stages_ms[s])
        return {"step": int(step), "wall_ms": wall_ms,
                "critical_stage": critical, "stages_ms": stages_ms}


def trace_scalar_keys() -> List[str]:
    """The constant ``trace/*`` scalar key set (schema v11)."""
    return ["trace/critical_stage"] + [
        f"trace/{s}_exclusive_ms" for s in STAGES
    ]


def trace_round_scalars(spans, step: int) -> Dict[str, float]:
    """The per-round ``trace/*`` scalars for round ``step`` from a live
    ``PhaseSpans`` ring — constant key set; zeros (critical_stage
    pinned to the idle index) when the round has no spans yet, so the
    lagged emission's first rounds keep pack_metric_dicts happy."""
    zeros = {k: 0.0 for k in trace_scalar_keys()}
    zeros["trace/critical_stage"] = float(STAGES.index("idle"))
    if spans is None or step < 0:
        return zeros
    bd = CriticalPath(spans.events).round_breakdown(step)
    if bd is None:
        return zeros
    out = {"trace/critical_stage":
           float(STAGES.index(bd["critical_stage"]))}
    for s in STAGES:
        out[f"trace/{s}_exclusive_ms"] = float(bd["stages_ms"][s])
    return out


# ---------------------------------------------------------------------------
# run report
# ---------------------------------------------------------------------------
def _percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation — stable for tiny N)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return float(xs[i])


def _read_metrics_series(path: str) -> Dict[str, List[float]]:
    """metrics.jsonl -> name -> values in step order (header rows and
    stringified non-finites skipped — anomaly detection wants clean
    series, the schema checker owns strictness)."""
    series: Dict[str, List[Tuple[int, float]]] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            name, val = rec.get("name"), rec.get("value")
            if not isinstance(name, str):
                continue
            if isinstance(val, bool) or not isinstance(val, (int, float)):
                continue
            series.setdefault(name, []).append(
                (int(rec.get("step", 0)), float(val)))
    return {k: [v for _, v in sorted(vs)] for k, vs in series.items()}


def _detect_anomalies(series: Dict[str, List[float]]) -> List[dict]:
    """Flag the failure smells the subsystems' scalars expose.
    Thresholds are deliberately coarse — these are triage flags for a
    human, not gates (the checkers own gating)."""
    out: List[dict] = []

    def quarter_means(xs):
        q = max(1, len(xs) // 4)
        return (sum(xs[:q]) / q, sum(xs[-q:]) / q)

    stale = series.get("async/staleness_mean", [])
    if len(stale) >= 8:
        first, last = quarter_means(stale)
        if last > 2.0 * first + 0.5:
            out.append({
                "kind": "staleness_drift", "metric": "async/staleness_mean",
                "detail": f"mean staleness drifted {first:.2f} -> "
                          f"{last:.2f} over the run — arrivals are "
                          "falling behind the apply rate",
            })
    hits = series.get("clientstore/cache_hit_rate", [])
    if len(hits) >= 8:
        first, last = quarter_means(hits)
        if first >= 0.2 and last < 0.5 * first:
            out.append({
                "kind": "cache_hit_collapse",
                "metric": "clientstore/cache_hit_rate",
                "detail": f"cache hit rate collapsed {first:.2f} -> "
                          f"{last:.2f} — the cohort working set outgrew "
                          "--client_store_cache_rows",
            })
    return out


def build_run_report(run_dir: str,
                     generated_by: str = "telemetry.trace") -> dict:
    """Assemble the versioned run report for one run directory. Reads
    whatever artifacts exist (spans dump, metrics.jsonl, flight
    records, perf_report.json); raises ``ValueError`` when the
    directory has neither spans nor metrics to analyze."""
    spans_paths = sorted(glob.glob(os.path.join(run_dir, "spans_*.json")))
    metrics_path = os.path.join(run_dir, "metrics.jsonl")
    flight_n = len(glob.glob(os.path.join(run_dir, "flight_*.json")))
    perf_path = os.path.join(run_dir, "perf_report.json")
    if not spans_paths and not os.path.exists(metrics_path):
        raise ValueError(
            f"{run_dir}: no spans_*.json and no metrics.jsonl — nothing "
            "to analyze (is this a run directory?)"
        )

    rounds: List[dict] = []
    if spans_paths:
        # the LAST dump is the complete one (a run dumps once at close;
        # earlier files would be from a resumed predecessor)
        with open(spans_paths[-1]) as f:
            dump = json.load(f)
        cp = CriticalPath(dump.get("traceEvents", []))
        # step -1 is the recorder's pre-round clock (warmup compile, the
        # first data load): real wall time, but not an attributable round
        rounds = [bd for bd in (cp.round_breakdown(s)
                                for s in cp.steps() if s >= 0)
                  if bd is not None]

    total_wall = sum(r["wall_ms"] for r in rounds)
    stages_block: Dict[str, dict] = {}
    for s in STAGES:
        xs = [r["stages_ms"][s] for r in rounds]
        tot = sum(xs)
        stages_block[s] = {
            "p50_ms": _percentile(xs, 0.5),
            "p95_ms": _percentile(xs, 0.95),
            "total_ms": tot,
            # fractions sum to 1 across stages (idle is the remainder of
            # every round, so the stage totals sum to the wall total)
            "fraction": (tot / total_wall) if total_wall > 0 else 0.0,
        }
    critical_counts = {s: 0 for s in STAGES}
    for r in rounds:
        critical_counts[r["critical_stage"]] += 1
    critical = (max(STAGES, key=lambda s: critical_counts[s])
                if rounds else "idle")

    series = (_read_metrics_series(metrics_path)
              if os.path.exists(metrics_path) else {})

    from commefficient_tpu.telemetry import SCHEMA_VERSION

    return {
        "schema_version": SCHEMA_VERSION,
        "kind": "run_report",
        "run_dir": run_dir,
        "generated_by": generated_by,
        "sources": {
            "spans": os.path.basename(spans_paths[-1])
                     if spans_paths else None,
            "metrics": os.path.exists(metrics_path),
            "flight_records": flight_n,
            "perf_report": os.path.exists(perf_path),
        },
        "rounds_analyzed": len(rounds),
        "critical_stage": critical,
        "critical_counts": critical_counts,
        "stages": stages_block,
        "rounds": rounds,
        "anomalies": _detect_anomalies(series),
    }


def write_run_report(run_dir: str, generated_by: str) -> Optional[str]:
    """Build + write ``run_report.json`` into ``run_dir``; returns the
    path, or None when the directory has nothing to analyze (never
    raises — this runs in the train loop's close path)."""
    try:
        report = build_run_report(run_dir, generated_by=generated_by)
    except (OSError, ValueError):
        return None
    from commefficient_tpu.telemetry import jsonable_tree

    path = os.path.join(run_dir, "run_report.json")
    try:
        with open(path, "w") as f:
            json.dump(jsonable_tree(report), f, indent=1, allow_nan=False)
    except (OSError, ValueError):  # lint: allow[exception-hygiene] close-path best effort: a failed report write must not mask the run's real exit status
        return None
    return path


# ---------------------------------------------------------------------------
# --profile_rounds capture window
# ---------------------------------------------------------------------------
def parse_profile_rounds(spec: str) -> Tuple[int, int]:
    """``"A-B"`` -> ``(A, B)`` inclusive round window. Config validation
    calls this; raises ``ValueError`` with the offending spec."""
    parts = str(spec).split("-")
    if len(parts) != 2:
        raise ValueError(
            f"profile_rounds must be 'A-B' (inclusive round window), "
            f"got {spec!r}"
        )
    try:
        a, b = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"profile_rounds must be 'A-B' with integer A, B, got {spec!r}"
        ) from None
    if a < 0 or b < a:
        raise ValueError(
            f"profile_rounds needs 0 <= A <= B, got {spec!r}"
        )
    return a, b


class ProfilerWindow:
    """Programmatic ``jax.profiler`` capture over ``--profile_rounds A-B``.

    Same protocol as ``StepProfiler`` (``step``/``resume_at``/``close``)
    so the runner stacks both behind one facade. Differences: the window
    comes from the CLI (BENCH_r06 wants specific steady-state rounds,
    e.g. to see whether ``compact_nonzero``'s cumsum dominates the
    sketch round), the start is clamped to ``MIN_WARMUP_STEPS`` so a
    ``0-3`` spec cannot trace compile+warmup, and entry/exit are FENCED
    through ``fence_fn`` — all deferred/in-flight device work (the
    async double-buffer drain, pending writebacks) retires before the
    trace starts and before it stops, so the captured window contains
    exactly the requested rounds and the deferred-drain pipeline's
    overlap pattern is undisturbed outside it. A backend that cannot
    trace (or a dead logdir) disarms the window with a logged named
    reason instead of killing the run.
    """

    def __init__(self, spec: str, logdir: str, fence_fn=None):
        from commefficient_tpu.utils.profiling import MIN_WARMUP_STEPS

        a, b = parse_profile_rounds(spec)
        self.num_steps = b - a + 1
        self.start = max(a, MIN_WARMUP_STEPS)
        self.stop_at = self.start + self.num_steps
        self.logdir = logdir
        self._fence_fn = fence_fn
        self._active = False
        self._armed = bool(logdir)

    def resume_at(self, resume_step: int) -> None:
        from commefficient_tpu.utils.profiling import MIN_WARMUP_STEPS

        floor = resume_step + MIN_WARMUP_STEPS
        if floor > self.start:
            self.start = floor
            self.stop_at = floor + self.num_steps

    def _fence(self) -> None:
        if self._fence_fn is None:
            return
        try:
            self._fence_fn()
        except Exception as e:  # lint: allow[exception-hygiene] observability fence: a failed sync degrades the capture boundary, never the run
            print(f"[profile_rounds] window fence failed "
                  f"({type(e).__name__}: {e}); capture boundary is "
                  f"best-effort", flush=True)

    def step(self, step_idx: int) -> None:
        if not self._armed:
            return
        if self._active and step_idx >= self.stop_at:
            self._fence()
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # lint: allow[exception-hygiene] profiler capture is best-effort off-TPU: log the named reason, keep training
                print(f"[profile_rounds] stop_trace failed "
                      f"({type(e).__name__}: {e})", flush=True)
            self._active = False
            self._armed = False
        elif not self._active and self.start <= step_idx < self.stop_at:
            self._fence()
            try:
                import jax

                jax.profiler.start_trace(self.logdir)
                self._active = True
                print(f"[profile_rounds] capturing rounds "
                      f"[{self.start}, {self.stop_at}) -> {self.logdir}",
                      flush=True)
            except Exception as e:  # lint: allow[exception-hygiene] profiler capture is best-effort off-TPU: log the named reason, keep training
                print(f"[profile_rounds] start_trace unavailable on this "
                      f"backend ({type(e).__name__}: {e}); window "
                      f"disarmed", flush=True)
                self._armed = False

    def close(self) -> None:
        if self._active:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # lint: allow[exception-hygiene] profiler capture is best-effort off-TPU: log the named reason, keep training
                print(f"[profile_rounds] stop_trace failed at close "
                      f"({type(e).__name__}: {e})", flush=True)
            self._active = False


class ProfilerStack:
    """Fan one ``step``/``resume_at``/``close`` stream out to several
    profiler-protocol objects (StepProfiler + ProfilerWindow) — the
    engines keep calling exactly one ``profiler``."""

    def __init__(self, *profilers):
        self.profilers = [p for p in profilers if p is not None]

    def resume_at(self, resume_step: int) -> None:
        for p in self.profilers:
            p.resume_at(resume_step)

    def step(self, step_idx: int) -> None:
        for p in self.profilers:
            p.step(step_idx)

    def close(self) -> None:
        for p in self.profilers:
            p.close()
