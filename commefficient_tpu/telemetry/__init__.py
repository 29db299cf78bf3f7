"""Round-level telemetry — the observability layer over the federated round.

FetchSGD's headline claim is accuracy *per byte communicated*
(arXiv:2007.07682 plots loss against bytes, not rounds), and its
correctness hinges on the error-feedback residual staying bounded (the
sketched-SGD analysis, arXiv:1903.04488, bounds exactly that buffer). This
package makes both observable per round, in three pillars:

  * ``diagnostics`` — in-graph health scalars (grad/update/EF-residual
    norms, compressor fidelity, a non-finite sentinel) computed INSIDE the
    jitted round and returned with the existing metrics dict, so they ride
    the deferred ``drain_round_metrics`` path with no extra dispatch
    fences. Gated by ``cfg.telemetry_level``: at level 0 nothing is traced
    (the round's HLO is bit-identical to the pre-telemetry program — pinned
    by the golden parity recordings and an HLO smoke test).
  * ``ledger`` — per-round and cumulative uplink/downlink bytes sourced
    from each ``Compressor``'s accounting, emitted as ``comm/*`` scalars
    (so ACCURACY runs can plot loss-vs-bytes — the paper's x-axis) and
    summarized in a ``comm_ledger.json`` per run dir.
  * ``flight`` — a ring buffer of the last K drained round records plus
    run metadata; on a non-finite sentinel or an uncaught train-loop
    exception it dumps ``flight_<step>.json`` and raises a
    ``DivergenceError`` naming the first bad round instead of training
    onward on NaNs.

Since the compiled-graph observability PR two more pillars measure the
system FROM THE COMPILED ARTIFACT instead of trusting analytic models:

  * ``xla_audit`` — AOT cost/memory analyses + an HLO collective walk of
    the compiled round, cross-checked against the CommLedger accounting
    and the PR-6 W*k all-gather bound (``perf_report.json`` + ``xla/*``
    scalars), and the ``RetraceSentinel`` that counts/hard-fails silent
    mid-run recompiles naming the argument-signature diff.
  * ``spans`` — host-side Chrome-trace phase spans (data load / fedsim
    env / device_put / round dispatch / drain / checkpoint) dumped as
    ``spans_<step>.json`` next to the StepProfiler's XLA traces.

Since the adaptive-communication PR this package is also the control
plane's sensory path: the ``control/`` subsystem's ``ef_feedback`` policy
consumes the drained ``diag/*`` scalars, its per-round ``control/*``
scalars ride the same metric dicts, the CommLedger bills each drained
round at the rung its ``control/rung`` scalar names (schema v4 per-rung
invariant), and flight dumps carry the dump-time controller snapshot.

Telemetry levels (``--telemetry_level``):

  0 — off (default). Zero traced ops, zero host work; bit-identical rounds.
  1 — health: diag/* norms + sentinel, comm/* scalars, flight recorder.
      Cost: a handful of [D] reductions inside the already-running round.
  2 — + compressor fidelity (sketch round-trip estimation error: one extra
      sketch+estimate pass; powersgd reconstruction residual: vector ops
      only). Intended for ACCURACY runs, not peak-throughput benches.

Layering: ``diagnostics`` imports only jax + ops (L0 — the AMS table
estimator lives with the sketch kernels); ``ledger``/``flight`` are
host-side stdlib-only. ``parallel/`` and ``train/`` import this package;
``compress/`` does NOT (its per-mode ``diagnostics()`` hook lives on the
Compressor classes, keeping the compress layering at ops+jax).
"""

from commefficient_tpu.telemetry.diagnostics import (
    nonfinite_sentinel,
    round_diagnostics,
    round_diagnostics_sparse,
    table_sqnorm_estimate,
)
from commefficient_tpu.telemetry.flight import (
    DivergenceError,
    FleetShrinkError,
    FlightRecorder,
    jsonable_scalar,
    jsonable_tree,
)
from commefficient_tpu.telemetry.ledger import CommLedger, run_metadata
from commefficient_tpu.telemetry.spans import PhaseSpans
from commefficient_tpu.telemetry.trace import (
    STAGES,
    CriticalPath,
    ProfilerStack,
    ProfilerWindow,
    build_run_report,
    cohort_trace_id,
    round_trace_id,
    trace_round_scalars,
    write_run_report,
)
from commefficient_tpu.telemetry.xla_audit import (
    CompiledRoundAudit,
    RetraceError,
    RetraceSentinel,
    audited_mfu,
    chip_peak_flops,
    collective_audit,
    exposed_collective_ms,
)

# versioned schema shared by metrics.jsonl headers, flight_*.json,
# comm_ledger.json, perf_report.json and spans_*.json
# (scripts/check_telemetry_schema.py validates against it).
# v2 (fedsim PR): fedsim/* scalar namespace, the ledger's masked live-byte
# accounting (live_client_rounds/avail_client_rounds + their exactness
# invariant), and the flight dump's participation_history window.
# v3 (compiled-graph observability PR): the xla/* scalar namespace
# (collective bytes, ledger-vs-HLO delta, retrace count, audited FLOPs/
# peak-HBM), the perf_report.json artifact (xla_audit.py) with its
# checker-enforced sharded-decode collective invariant, spans_*.json
# Chrome-trace phase spans, and the header/flight "artifacts" block
# linking a run to its StepProfiler logdir + perf report.
# v4 (adaptive communication-budget PR): the control/* scalar namespace
# (active rung, switch count, budget remainder), the ledger's per-rung
# accounting block ("rungs": rounds + bytes_per_round per ladder rung,
# whose cum-bytes invariant is the sum over rungs of active-rung bytes —
# live-count-weighted under fedsim masking), and the header/flight
# "controller" block (policy, ladder, rung at write/dump time).
# v5: thread-aware spans: per-event lane ``tid``s plus "M" thread_name
# metadata events labeling a worker thread's own track (the pipeline/*
# scalar namespace v5 also brought left with its engines).
# v6 (self-healing training PR): the resilience/* scalar namespace
# (recoveries / rung_demotions / blacklisted_clients — non-negative
# integer counters; preempt_requested in {0, 1}; rollback_round an
# integer >= -1, all checker-enforced host gauges), the flight dump's
# "recovery_history" block (one entry per divergence rollback: policy,
# first bad round, rollback target, outcome), the "_recovery"-tagged
# flight dump written after a successful rollback, and the fedsim/preempt
# scheduled-preemption stat.
# v7 (sparse allreduce collective layer PR): perf_report.json gains the
# resolved "aggregate" path (null | 'dense' | 'sparse') and the
# collectives block's "sparse_agg_bound" + "max_all_reduce_elems" fields;
# on aggregate == 'sparse' the checker ENFORCES that no single all-reduce
# or all-gather moves more elements than sparse_agg_bound (the O(W*k)
# pair-exchange ceiling — a reduce-scatter of [D] stays legal: it moves
# O(D/W) per link and lands sharded), mirroring the v3 sharded-decode
# wk_bound invariant.
# v8 (buffered-asynchronous federation PR): the async/* scalar namespace
# (per-update staleness_mean/staleness_max >= 0, integer-valued
# buffer_fill >= 0 and concurrent_cohorts >= 0, effective_participation
# >= 0 — all checker-enforced), and perf_report.json's engine gains
# "async" with a REQUIRED "async" block {buffer >= 1, concurrency >= 1,
# staleness_exponent >= 0} on async reports (forbidden on synchronous
# ones). Byte billing is unchanged by design: an async update's ledger
# row bills the consumed contributions' uploads, so overlapping cohorts'
# bytes sum exactly to the synchronous ledger under concurrency 1.
# v9 (hidden-collectives PR): the xla/exposed_collective_ms scalar — a
# spans×HLO cross-check (telemetry/xla_audit.exposed_collective_ms) of
# the host-measured un-overlapped collective wait, non-negative and
# pinned to 0.0 when the compiled round contains no collectives; spans
# events may carry args.collective == true (the tag driving the
# exposure accounting) and spans_*.json a top-level
# "exposed_collective_ms" field; perf_report.json gains an "overlap"
# block {collectives: 'none'|'layerwise', double_buffer: bool} REQUIRED
# exactly when a collective-hiding mode is on (overlap_collectives !=
# 'none' or async_double_buffer) and forbidden otherwise, so wall-clock
# rows are always attributable to their overlap setting.
# v10 (clientstore PR): the clientstore/* scalar namespace (cache_hit_rate
# in [0, 1]; evictions a non-negative integer-valued counter;
# h2d_stage_ms and writeback_ms non-negative host gauges — all
# checker-enforced), emitted at level >= 1 exactly when the session hosts
# client state (--client_store host|mmap builds a CohortStreamer; the
# device store constructs nothing, level-0 HLO bit-untouched).
# perf_report.json's collectives block gains "sparse_agg_exemption"
# (null | 'client_state_writeback'): the reason sparse_agg_bound exceeds
# the strict W*k-class ceiling. DEVICE-resident client rows are the only
# legal reason; on a sparse-aggregate report whose meta.config says
# client_store host|mmap the checker REJECTS any exemption, so hosted
# wall-clock rows are provably under the strict bound.
# v11 (round-tracing PR): the trace/* scalar namespace — per-round
# critical-path attribution with LAGGED semantics (telemetry/trace.py:
# the row emitted at round N describes round N-2, the newest round
# whose spans are complete at emission time):
# trace/critical_stage an integer index into trace.STAGES,
# trace/<stage>_exclusive_ms non-negative finite host gauges, one per
# stage, disjoint by construction and summing to <= the analyzed
# round's wall-clock. Spans events may carry args.trace_id (non-empty
# string: the owning round "r<step>" or cohort "c<cohort>") and
# args.parent (non-empty, != trace_id, only beside a trace_id) so a
# dump renders each cohort as a causally-linked tree across lanes. New
# run_report.json artifact (kind "run_report": per-stage p50/p95,
# attribution fractions in [0,1] summing to ~1, per-round stage times
# disjoint and <= wall_ms, anomaly flags), written at train-loop close
# when cfg.run_report and by scripts/analyze_run.py; the header/flight
# artifacts block advertises it under the same gate.
# v12 (multihost PR): the multihost/* scalar namespace, emitted at level
# >= 1 exactly when the run declares a host axis (cfg.num_hosts > 1 —
# fixed for a run, so the key set stays constant): multihost/
# num_processes an integer >= 1 (jax.process_count(): 1 on the
# mesh-faked twin, the pod's process count on a real cluster);
# multihost/host_id an integer in [0, num_processes); multihost/
# cross_host_bytes >= 0 (the round's upload payload — every aggregation
# collective rides the declared host axis, so the whole payload crosses
# the host boundary once); multihost/dcn_exposed_ms >= 0 (un-hidden
# collective wait attributed to DCN; 0.0 below spans attachment, the
# xla/exposed_collective_ms discipline) — all checker-enforced.
# perf_report.json gains a "multihost" block {num_hosts >= 2,
# num_processes >= 1, host_id in [0, num_processes)} REQUIRED exactly
# when the audited mesh declares a host axis and forbidden on
# single-host reports, so wall-clock rows always state their topology.
# v13 (elastic-fleet PR): the fleet/* scalar namespace, emitted exactly
# when the chaos plan schedules a fleet event (cfg.fleet_enabled — fixed
# for a run, so the key set stays constant): fleet/width a positive
# integer (the round's realized worker width; the ledger bills live
# bytes against it instead of num_workers), fleet/resizes a
# non-decreasing integer counter of schedule transitions REALIZED so
# far, fleet/last_resize_round an integer in {-1} ∪ [0, step] (-1 until
# the first transition), fleet/shrink_recoveries a non-decreasing
# integer counter of FleetShrinkError rollbacks survived — all
# checker-enforced. Width/resizes/last_resize_round are SCHEDULE-
# derived (pure in round_idx), so rollback-replayed rounds re-emit
# identical values; shrink_recoveries is the one runtime counter.
# control/ gains optional async_k/async_c/retunes scalars (positive
# integer K/C re-tune state + a non-decreasing counter) emitted only
# when the active policy adapts the asyncfed engine (staleness_aware).
SCHEMA_VERSION = 13

TELEMETRY_LEVELS = (0, 1, 2)


def run_artifacts(cfg, logdir: str) -> dict:
    """The artifact-linking block shared by the metrics.jsonl run header
    and flight-record metadata: where this run's profiling evidence lives
    (StepProfiler trace logdir, the compiled-round perf_report.json), so a
    divergence dump points straight at its perf context. The perf-report
    link is only advertised when the audit will actually run
    (``cfg.perf_audit``; accuracy_run opts out, for instance) — though a
    startup audit that later degrades still leaves the path absent, so
    consumers should stat before reading."""
    out = {}
    if getattr(cfg, "profile_dir", ""):
        out["profile_dir"] = cfg.profile_dir
    if (logdir and getattr(cfg, "telemetry_level", 0) >= 1
            and getattr(cfg, "perf_audit", True)):
        import os

        out["perf_report"] = os.path.join(logdir, "perf_report.json")
    if (logdir and getattr(cfg, "telemetry_level", 0) >= 1
            and getattr(cfg, "run_report", True)):
        # v11: the critical-path run report, written at train-loop close
        # (telemetry/trace.py). Same opt-out discipline as perf_report:
        # accuracy_run passes run_report=False so its headers/flight
        # dumps never link an artifact that will not exist.
        import os

        out["run_report"] = os.path.join(logdir, "run_report.json")
    return out


def build_telemetry_riders(cfg, session, writer):
    """(ledger, flight) for a train loop, or (None, None) below level 1 /
    without a writer — the ONE construction both train entries share, so
    the wiring cannot drift between them. ``session`` is duck-typed (needs
    ``bytes_per_round()``, ``grad_size``, ``mesh``)."""
    if getattr(cfg, "telemetry_level", 0) < 1 or writer is None:
        return None, None
    # control/ ladder runs switch the ledger to per-rung accounting: each
    # drained round is billed at the rung its control/rung scalar names
    # (schema v4); single-rung sessions keep the flat invariant
    rungs = None
    session_rungs = getattr(session, "rungs", None)
    if session_rungs is not None and len(session_rungs) > 1:
        rungs = [(session.rung_bytes_per_round(i), r.compressor)
                 for i, r in enumerate(session_rungs)]
    # fedsim runs switch the ledger to masked live-byte accounting: only
    # live clients' uplink counts, through the compressor's mask-aware
    # accounting hook (compress/base.masked_upload_floats)
    ledger = CommLedger(session.bytes_per_round(), mode=cfg.mode,
                        num_workers=cfg.num_workers,
                        masked=bool(getattr(cfg, "fedsim_enabled", False)),
                        compressor=getattr(session, "compressor", None),
                        rungs=rungs)
    flight = FlightRecorder(
        cfg, logdir=writer.logdir,
        extra_meta={"grad_size": session.grad_size,
                    "mesh": dict(zip(session.mesh.axis_names,
                                     session.mesh.devices.shape)),
                    # link the dump to its profiling artifacts: a
                    # divergence post-mortem starts from the flight record
                    # and must be able to find the trace + perf report
                    "artifacts": run_artifacts(cfg, writer.logdir)},
        # dump-time controller attribution (schema v4) — the controller is
        # attached to the session by build_controller before the riders
        controller=getattr(session, "controller", None),
    )
    return ledger, flight


def build_perf_observability(cfg, session, sampler, writer, lr0,
                             generated_by: str):
    """(spans, audit) for a train loop — the ONE perf-observability wiring
    both entries share (same discipline as build_telemetry_riders).

    At telemetry level >= 1 with a writer: attaches a PhaseSpans recorder
    to the session (host phase spans -> spans_<step>.json) and — unless
    ``cfg.perf_audit`` is off — AOT-compiles the round for the run's REAL
    first batch (``sampler.sample_round(0)``; its trace seeds the retrace
    sentinel's expected first signature) and writes ``perf_report.json``
    plus the one-shot ``xla/*`` scalars. The audit must never kill a run:
    any failure degrades to a console note. Returns (None, None) below
    level 1."""
    if getattr(cfg, "telemetry_level", 0) < 1 or writer is None:
        return None, None
    spans = PhaseSpans(writer.logdir)
    session.spans = spans
    audit = None
    if getattr(cfg, "perf_audit", True):
        try:
            ids, batch = sampler.sample_round(0)
            L = getattr(cfg, "round_microbatches", 0)
            if L:  # fedavg [W, L, B/L, ...] convention (cv_train loop)
                batch = {
                    k: v.reshape(v.shape[0], L, v.shape[1] // L, *v.shape[2:])
                    for k, v in batch.items()
                }
            audit = session.audit_compiled_round(ids, batch, lr0)
            path = audit.write(writer.logdir, generated_by=generated_by,
                               cfg=cfg)
            for name, val in audit.scalars().items():
                writer.scalar(name, val, 0)
            writer.flush()
            print(audit.describe())
            print(f"perf report: {path}")
        except Exception as e:  # noqa: BLE001 — observability never kills
            audit = None
            print(f"compiled-round audit skipped "
                  f"({type(e).__name__}: {e})")
    return spans, audit


def record_crash(flight, exc) -> None:
    """Train-loop except hook: dump the flight trajectory for a crash that
    is NOT a divergence (divergence already dumped its own record inside
    the drain). No-op without a flight recorder."""
    if flight is not None and not isinstance(exc, DivergenceError):
        flight.on_exception(exc)

__all__ = [
    "SCHEMA_VERSION",
    "STAGES",
    "TELEMETRY_LEVELS",
    "CommLedger",
    "CompiledRoundAudit",
    "CriticalPath",
    "DivergenceError",
    "FleetShrinkError",
    "FlightRecorder",
    "PhaseSpans",
    "ProfilerStack",
    "ProfilerWindow",
    "RetraceError",
    "RetraceSentinel",
    "audited_mfu",
    "build_perf_observability",
    "build_run_report",
    "build_telemetry_riders",
    "chip_peak_flops",
    "cohort_trace_id",
    "collective_audit",
    "exposed_collective_ms",
    "jsonable_scalar",
    "jsonable_tree",
    "nonfinite_sentinel",
    "record_crash",
    "round_trace_id",
    "run_artifacts",
    "round_diagnostics",
    "round_diagnostics_sparse",
    "run_metadata",
    "table_sqnorm_estimate",
    "trace_round_scalars",
    "write_run_report",
]
