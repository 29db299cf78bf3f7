"""Validate telemetry artifacts against the versioned schema.

The telemetry subsystem writes six artifact kinds per run dir
(README "Observability" documents the full schema; the version lives in
``commefficient_tpu.telemetry.SCHEMA_VERSION``):

  * ``metrics.jsonl``     — one run-header record per process, then scalar
                            records ``{"name", "value", "step", "t"}``
  * ``comm_ledger.json``  — cumulative communication accounting; the
                            cumulative bytes must equal
                            ``rounds * bytes_per_round`` EXACTLY — or, for
                            fedsim masked runs (live_client_rounds /
                            avail_client_rounds present), the live-byte
                            sums ``live_client_rounds * upload_bytes`` /
                            ``avail_client_rounds * download_bytes``
  * ``flight_<step>.json``— divergence/crash flight record: metadata +
                            ring-buffered round records in step order
                            (+ the fedsim participation_history window)
  * ``perf_report.json``  — compiled-round XLA audit (v3,
                            telemetry/xla_audit.py): cost/memory analyses
                            (nulls + reason where the backend exposes
                            none), the HLO collective walk and its
                            ledger cross-check. The sketch SHARDED-decode
                            invariants are enforced HERE: every all-gather
                            <= the W*k candidate bound and the ledger-vs-
                            HLO byte delta within the recorded tolerance.
  * ``spans_<step>.json`` — host phase spans (v3, telemetry/spans.py) in
                            Chrome-trace/Perfetto event format; v11 adds
                            the optional args.trace_id/args.parent
                            correlation fields (rules enforced below)
  * ``run_report.json``   — critical-path run report (v11,
                            telemetry/trace.py build_run_report, written
                            by the train loop's close path and
                            scripts/analyze_run.py): per-stage exclusive
                            p50/p95 + attribution fractions summing to 1
                            and per-round DISJOINT stage times summing to
                            the round's wall-clock — both enforced here.

Consumers (plotting, run comparison, the driver's ACCURACY tooling) parse
these blind, so the writers and this checker are pinned to each other by
tests/test_telemetry_schema.py + tests/test_xla_audit.py — the tests write
artifacts through the REAL classes and validate them here, plus rejection
cases (same pattern as scripts/check_mode_dispatch.py). Validators are
hand-rolled: no jsonschema dependency in the container.

    python scripts/check_telemetry_schema.py <run_dir> [...]  # exit 1 on bad
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

# v2 (fedsim PR): fedsim/* scalar namespace, ledger masked live-byte
# accounting (live_client_rounds/avail_client_rounds + exactness
# invariant), flight participation_history; v3 (compiled-graph
# observability PR): xla/* scalar namespace, perf_report.json,
# spans_*.json, header/flight "artifacts" block; v4 (adaptive
# communication-budget PR): control/* scalar namespace, the ledger's
# per-rung "rungs" accounting block (cum bytes == sum over rungs of
# active-rung bytes, live-count-weighted under masking), header/flight
# "controller" block; v5: spans thread_name "M" metadata events +
# per-lane tids (its pipeline/* scalar namespace left with the engines
# that wrote it); v6 (self-healing training PR): resilience/* scalar namespace
# (integer counters, preempt_requested in {0, 1}, rollback_round >= -1 —
# enforced below), the flight dump's recovery_history block (one entry
# per divergence rollback), and the fedsim/preempt scheduled-preemption
# stat; v7 (sparse allreduce collective layer PR): perf_report "aggregate"
# field + collectives "sparse_agg_bound"/"max_all_reduce_elems" — on
# aggregate == 'sparse' NO single all-reduce or all-gather may move more
# elements than sparse_agg_bound (enforced below; reduce-scatter is
# exempt by design: O(D/W) per link, sharded result); v8 (buffered-
# asynchronous federation PR): async/* scalar namespace (staleness_mean/
# staleness_max >= 0, integer buffer_fill >= 0 and concurrent_cohorts
# >= 0, effective_participation >= 0 — enforced below), perf_report
# engine "async" with a REQUIRED {buffer, concurrency,
# staleness_exponent} "async" block on async reports and the block
# FORBIDDEN on synchronous ones; v9 (hidden-collectives PR): the
# xla/exposed_collective_ms scalar (non-negative finite host gauge —
# enforced below), spans events' optional args.collective tag + the
# spans_*.json top-level exposed_collective_ms field, and perf_report's
# "overlap" block {collectives: 'none'|'layerwise', double_buffer} —
# REQUIRED when the report's config has a hiding mode on
# (overlap_collectives != 'none' or async_double_buffer), FORBIDDEN when
# both are off, and never all-off when present (enforced below); v10
# (clientstore PR): clientstore/* scalar namespace (cache_hit_rate in
# [0, 1], integer-valued evictions >= 0, h2d_stage_ms / writeback_ms
# >= 0 — enforced below) and perf_report collectives
# "sparse_agg_exemption" (null | 'client_state_writeback') — on a
# sparse-aggregate report whose config hosts client state
# (client_store host|mmap) ANY exemption is rejected: the hosted round
# takes cohort rows as arguments, so the strict W*k-class
# sparse_agg_bound must hold with no [C, D] writeback allowance
# (enforced below); v11 (round-tracing PR): trace/* scalar namespace
# (critical_stage an integer index into the TRACE_STAGES taxonomy, the
# *_exclusive_ms family finite >= 0 — enforced below), spans events'
# optional args.trace_id (non-empty string) and args.parent (only legal
# beside a trace_id, non-empty, != trace_id — enforced below), and the
# run_report.json artifact (validate_run_report: attribution fractions
# in [0, 1] summing to ~1, per-round disjoint exclusive stage times
# summing to the round's wall-clock); v12 (multihost PR): multihost/*
# scalar namespace (num_processes an integer >= 1, host_id an integer
# >= 0, cross_host_bytes / dcn_exposed_ms >= 0 — enforced below) and
# perf_report's "multihost" block {num_hosts >= 2, num_processes >= 1,
# host_id in [0, num_processes)} — REQUIRED when the report's config
# declares a host axis (num_hosts > 1), FORBIDDEN on single-host
# reports (enforced below); v13 (elastic-fleet PR): fleet/* scalar
# namespace (width a positive integer, resizes / shrink_recoveries
# non-negative integers — resizes additionally non-decreasing across a
# flight dump's step-ordered records — last_resize_round an integer
# >= -1 and <= the record's step: a resize cannot postdate the round
# reporting it — enforced below) and the staleness_aware control
# scalars control/async_k (positive integer), control/async_c
# (positive integer), control/retunes (non-negative integer). Older
# artifacts stay valid.
KNOWN_SCHEMA_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13)

# scalar-name schema: bare "lr", or a namespaced name under one of the
# documented prefixes (README "Observability")
SCALAR_PREFIXES = ("train/", "val/", "diag/", "comm/", "fedsim/", "xla/",
                   "control/", "resilience/", "async/",
                   "clientstore/", "trace/", "multihost/", "fleet/")

# pinned copy of telemetry.trace.STAGES (this checker imports nothing
# from the package by design — tests/test_telemetry_schema.py pins the
# two tuples against each other)
TRACE_STAGES = ("data", "h2d", "dispatch", "collective", "drain",
                "writeback", "idle")


class SchemaError(ValueError):
    pass


def _strict_loads(s: str):
    """json.loads that REJECTS bare NaN/Infinity tokens: Python's parser
    accepts them, but the schema promises strict JSON (non-finite values
    are stringified markers — telemetry.jsonable_scalar), so a writer
    regression must fail here, not at some downstream jq/JS consumer."""

    def _bad(tok):
        raise SchemaError(f"bare {tok} token — not strict JSON")

    return json.loads(s, parse_constant=_bad)


def _req(record: dict, field: str, types, where: str):
    if field not in record:
        raise SchemaError(f"{where}: missing required field {field!r}")
    if not isinstance(record[field], types):
        raise SchemaError(
            f"{where}: field {field!r} has type "
            f"{type(record[field]).__name__}, expected {types}"
        )
    return record[field]


def _check_version(record: dict, where: str) -> None:
    v = _req(record, "schema_version", int, where)
    if v not in KNOWN_SCHEMA_VERSIONS:
        raise SchemaError(
            f"{where}: unknown schema_version {v} "
            f"(known: {KNOWN_SCHEMA_VERSIONS})"
        )


def _check_controller_block(block: dict, where: str) -> None:
    """The v4 controller block (metrics run-header + flight dumps):
    enough to attribute a record to its rung/policy — policy + ladder
    identity, the rung at write/dump time, and (flight dumps) the switch
    count and budget state."""
    _req(block, "policy", str, where)
    _req(block, "ladder", str, where)
    rung = _req(block, "rung", int, where)
    n = _req(block, "num_rungs", int, where)
    if n < 1 or not 0 <= rung < n:
        raise SchemaError(
            f"{where}: rung {rung} outside [0, num_rungs={n})"
        )
    for f in ("switches", "rounds_seen", "budget_bytes",
              "budget_remaining_bytes"):
        if f in block and not isinstance(block[f], int):
            raise SchemaError(f"{where}: {f} must be an int")


def _check_header(rec: dict, where: str) -> None:
    _check_version(rec, where)
    _req(rec, "time", (int, float), where)
    _req(rec, "start_time", str, where)
    if "config" in rec:
        _req(rec, "config", dict, where)
    if "controller" in rec:
        _check_controller_block(
            _req(rec, "controller", dict, where), where + ":controller"
        )
    if "artifacts" in rec:
        # v3: links to this run's profiling evidence (StepProfiler trace
        # logdir, perf_report.json path) — string values only
        arts = _req(rec, "artifacts", dict, where)
        for k, v in arts.items():
            if not isinstance(v, str):
                raise SchemaError(
                    f"{where}: artifacts[{k!r}] must be a path string, "
                    f"got {type(v).__name__}"
                )


def _check_scalar_name(name: str, where: str,
                       allow_bare_aux: bool = False) -> None:
    """``allow_bare_aux``: flight records carry the round's RAW metric dict
    (the packed drain output), whose workload aux keys are bare identifiers
    (loss, correct, count, lm_loss, mc_loss, ...) next to the namespaced
    diag/comm scalars; metrics.jsonl names stay strictly namespaced."""
    if name == "lr":
        return
    if any(name.startswith(p) and len(name) > len(p)
           for p in SCALAR_PREFIXES):
        return
    if allow_bare_aux and name.isidentifier() and "/" not in name:
        return
    raise SchemaError(
        f"{where}: scalar name {name!r} outside the documented schema "
        f"(lr | {'|'.join(p + '*' for p in SCALAR_PREFIXES)}"
        + (" | bare aux identifier" if allow_bare_aux else "") + ")"
    )


def _check_scalar_value(v, name: str, where: str) -> None:
    """Numbers, or the "nan"/"inf"/"-inf" markers non-finite values are
    stringified to so every line stays strict JSON
    (telemetry.jsonable_scalar)."""
    if isinstance(v, bool) or (
        not isinstance(v, (int, float)) and v not in ("nan", "inf", "-inf")
    ):
        raise SchemaError(
            f"{where}: scalar {name!r} is neither a number nor a "
            f"nan/inf marker: {v!r}"
        )


def _check_resilience_scalar(name: str, v, where: str) -> None:
    """v6 ``resilience/*`` value invariants. Host-computed gauges (never
    legitimately non-finite, so the nan/inf markers are rejected too): ``recoveries`` / ``rung_demotions`` /
    ``blacklisted_clients`` COUNT whole events/clients and must be
    non-negative integers; ``preempt_requested`` is a 0/1 flag;
    ``rollback_round`` is the last rollback target round, -1 when the run
    never rolled back."""
    if not name.startswith("resilience/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name in ("resilience/recoveries", "resilience/rung_demotions",
                "resilience/blacklisted_clients") and (v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: {name} {v} is not a non-negative integer — it "
            "counts whole recovery events/clients"
        )
    if name == "resilience/preempt_requested" and v not in (0, 1, 0.0, 1.0):
        raise SchemaError(
            f"{where}: resilience/preempt_requested {v} is not a 0/1 flag"
        )
    if name == "resilience/rollback_round" and (v != int(v) or v < -1):
        raise SchemaError(
            f"{where}: resilience/rollback_round {v} must be an integer "
            ">= -1 (-1 = never rolled back)"
        )


def _check_async_scalar(name: str, v, where: str) -> None:
    """v8 ``async/*`` value invariants. Host-computed overlap gauges
    (asyncfed/engine.py), never legitimately non-finite: staleness is a
    server-version delta (>= 0 by construction); ``buffer_fill`` counts
    delivered-unconsumed contributions (non-negative integer);
    ``concurrent_cohorts`` counts in-flight cohorts after the top-up
    (non-negative integer; 0 only on trailing updates, where the
    schedule stops relaunching); ``effective_participation`` is the
    update's weight sum (>= 0; < K under staleness discounting)."""
    if not name.startswith("async/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name in ("async/staleness_mean", "async/staleness_max",
                "async/effective_participation") and v < 0:
        raise SchemaError(
            f"{where}: {name} {v} is negative — staleness is a server-"
            "version delta and participation a weight sum, both >= 0"
        )
    if name == "async/buffer_fill" and (v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: async/buffer_fill {v} is not a non-negative "
            "integer — it counts delivered-unconsumed contributions"
        )
    if name == "async/concurrent_cohorts" and (v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: async/concurrent_cohorts {v} is not a non-negative "
            "integer — it counts whole in-flight cohorts"
        )


def _check_clientstore_scalar(name: str, v, where: str) -> None:
    """v10 ``clientstore/*`` value invariants. Host-computed gauges from
    the CohortStreamer (clientstore/streamer.py), never legitimately
    non-finite: ``cache_hit_rate`` is hits/(hits+misses) over one round
    (a real fraction, 0.0 with no cache); ``evictions`` counts whole
    rows leaving the LRU cache; the ``*_ms`` pair are perf_counter
    timings of the H2D stage and the bank writeback."""
    if not name.startswith("clientstore/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name == "clientstore/cache_hit_rate" and not 0.0 <= v <= 1.0:
        raise SchemaError(
            f"{where}: clientstore/cache_hit_rate {v} outside [0, 1] — "
            "it is hits/(hits+misses) over one round"
        )
    if name == "clientstore/evictions" and (v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: clientstore/evictions {v} is not a non-negative "
            "integer — it counts whole rows written through the cache"
        )
    if name in ("clientstore/h2d_stage_ms",
                "clientstore/writeback_ms") and v < 0:
        raise SchemaError(
            f"{where}: {name} {v} is negative — host wall-clock gauges "
            "are >= 0"
        )


def _check_multihost_scalar(name: str, v, where: str) -> None:
    """v12 ``multihost/*`` value invariants. Host-computed topology/
    traffic gauges (parallel/api.py under cfg.num_hosts > 1), never
    legitimately non-finite: ``num_processes`` is jax.process_count()
    (>= 1 — exactly 1 on the mesh-faked twin); ``host_id`` is
    jax.process_index() (a non-negative integer; the metrics stream is
    per-process so the < num_processes half of the invariant is enforced
    on the perf report's multihost block, where both live together);
    ``cross_host_bytes`` is the round's upload payload riding the host
    axis; ``dcn_exposed_ms`` an interval measure like
    xla/exposed_collective_ms."""
    if not name.startswith("multihost/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name == "multihost/num_processes" and (v != int(v) or v < 1):
        raise SchemaError(
            f"{where}: multihost/num_processes {v} is not a positive "
            "integer — it counts whole pod processes (1 = mesh-faked)"
        )
    if name == "multihost/host_id" and (v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: multihost/host_id {v} is not a non-negative "
            "integer — it is this process's index in the pod"
        )
    if name in ("multihost/cross_host_bytes",
                "multihost/dcn_exposed_ms") and v < 0:
        raise SchemaError(
            f"{where}: {name} {v} is negative — byte counts and "
            "wall-clock exposure gauges are >= 0"
        )


def _check_fleet_scalar(name: str, v, where: str, step=None) -> None:
    """v13 ``fleet/*`` value invariants. Host-computed elastic-fleet
    gauges (parallel/api.py under cfg.fleet_enabled), schedule-derived
    and never legitimately non-finite: ``width`` is the round's REALIZED
    worker count (a positive integer — the width schedule never folds to
    zero, the config validator rejects it); ``resizes`` counts width
    transitions realized so far and ``shrink_recoveries`` completed
    shrink rollbacks (whole events); ``last_resize_round`` is the round
    the width last changed at, -1 before the first transition — and a
    resize cannot postdate the round reporting it, so when the record's
    ``step`` is known the value must be <= it."""
    if not name.startswith("fleet/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name == "fleet/width" and (v != int(v) or v < 1):
        raise SchemaError(
            f"{where}: fleet/width {v} is not a positive integer — it is "
            "the round's realized worker count"
        )
    if name in ("fleet/resizes", "fleet/shrink_recoveries") and (
            v != int(v) or v < 0):
        raise SchemaError(
            f"{where}: {name} {v} is not a non-negative integer — it "
            "counts whole width transitions / shrink rollbacks"
        )
    if name == "fleet/last_resize_round":
        if v != int(v) or v < -1:
            raise SchemaError(
                f"{where}: fleet/last_resize_round {v} must be an integer "
                ">= -1 (-1 = the width never changed)"
            )
        if step is not None and v > step:
            raise SchemaError(
                f"{where}: fleet/last_resize_round {v} postdates the "
                f"record's step {step} — a resize cannot come from the "
                "future"
            )


def _check_control_async_scalar(name: str, v, where: str) -> None:
    """v13 staleness_aware control scalars: the controller's live async
    geometry (control/controller.py, emitted only under an ADAPTS_ASYNC
    policy). ``async_k``/``async_c`` are the retuned buffer size and
    concurrency (positive integers — the controller clamps K >= 1,
    C >= 1); ``retunes`` counts applied (K, C) changes."""
    if name not in ("control/async_k", "control/async_c",
                    "control/retunes"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name == "control/retunes":
        if v != int(v) or v < 0:
            raise SchemaError(
                f"{where}: control/retunes {v} is not a non-negative "
                "integer — it counts whole applied (K, C) retunes"
            )
    elif v != int(v) or v < 1:
        raise SchemaError(
            f"{where}: {name} {v} is not a positive integer — the "
            "controller clamps the async geometry to K >= 1, C >= 1"
        )


def _check_xla_scalar(name: str, v, where: str) -> None:
    """v9 ``xla/exposed_collective_ms`` value invariant: a host-computed
    cumulative gauge (interval arithmetic over the span recorder — never
    legitimately non-finite, so the nan/inf markers are rejected) and
    non-negative by construction: it measures un-overlapped collective
    wait, and negative time means the writer's interval subtraction
    broke."""
    if name != "xla/exposed_collective_ms":
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if v < 0:
        raise SchemaError(
            f"{where}: xla/exposed_collective_ms {v} is negative — "
            "exposed collective time is an interval measure, >= 0"
        )


def _check_trace_scalar(name: str, v, where: str) -> None:
    """v11 ``trace/*`` value invariants. Host-computed critical-path
    gauges (telemetry/trace.py CriticalPath), never legitimately
    non-finite: ``critical_stage`` is the INDEX of the round's binding
    stage in the TRACE_STAGES taxonomy (an integer by construction);
    the ``*_exclusive_ms`` family are disjoint interval measures and
    negative time means the exclusive-assignment subtraction broke."""
    if not name.startswith("trace/"):
        return
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise SchemaError(
            f"{where}: {name!r} must be a finite number (host gauge), "
            f"got {v!r}"
        )
    if name == "trace/critical_stage" and (
            v != int(v) or not 0 <= v < len(TRACE_STAGES)):
        raise SchemaError(
            f"{where}: trace/critical_stage {v} is not an integer index "
            f"into the {len(TRACE_STAGES)}-stage taxonomy "
            f"{TRACE_STAGES}"
        )
    if name.endswith("_exclusive_ms") and v < 0:
        raise SchemaError(
            f"{where}: {name} {v} is negative — exclusive stage times "
            "are disjoint interval measures, >= 0 by construction"
        )


def _check_recovery_history(hist, where: str) -> None:
    """v6 flight ``recovery_history`` block: one entry per divergence
    rollback, in recovery order."""
    if not isinstance(hist, list) or not hist:
        raise SchemaError(f"{where}: recovery_history must be a non-empty "
                          "list of recovery entries")
    for j, entry in enumerate(hist):
        w = f"{where}:recovery_history[{j}]"
        if not isinstance(entry, dict):
            raise SchemaError(f"{w}: expected an object")
        n = _req(entry, "recovery", int, w)
        if n != j + 1:
            raise SchemaError(
                f"{w}: recovery ordinal {n} out of order (expected {j + 1})"
            )
        _req(entry, "policy", str, w)
        fb = _req(entry, "first_bad_step", int, w)
        if fb < 0:
            raise SchemaError(f"{w}: negative first_bad_step")
        _req(entry, "outcome", str, w)
        if "rollback_to" in entry and entry["rollback_to"] is not None:
            rb = _req(entry, "rollback_to", int, w)
            if not 0 <= rb <= fb:
                raise SchemaError(
                    f"{w}: rollback_to {rb} outside [0, first_bad_step="
                    f"{fb}] — a rollback target must be pre-divergence"
                )


def validate_metrics_jsonl(path) -> int:
    """Validate a metrics.jsonl; returns the number of scalar records."""
    n_scalars = 0
    saw_header = False
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            where = f"{path}:{i}"
            try:
                rec = _strict_loads(line)
            except json.JSONDecodeError as e:
                raise SchemaError(f"{where}: not valid JSON ({e.msg})")
            except SchemaError as e:
                raise SchemaError(f"{where}: {e}")
            if not isinstance(rec, dict):
                raise SchemaError(f"{where}: record is not an object")
            if rec.get("type") == "header":
                # one header per process; a resumed run appends another
                _check_header(rec, where)
                saw_header = True
                continue
            if i == 1:
                raise SchemaError(
                    f"{where}: first record must be the run header "
                    "(type='header') — this file predates the header "
                    "schema or was truncated"
                )
            name = _req(rec, "name", str, where)
            _check_scalar_name(name, where)
            if "value" not in rec:
                raise SchemaError(f"{where}: missing required field 'value'")
            _check_scalar_value(rec["value"], name, where)
            _check_resilience_scalar(name, rec["value"], where)
            _check_async_scalar(name, rec["value"], where)
            _check_clientstore_scalar(name, rec["value"], where)
            _check_multihost_scalar(name, rec["value"], where)
            _check_xla_scalar(name, rec["value"], where)
            _check_trace_scalar(name, rec["value"], where)
            _check_control_async_scalar(name, rec["value"], where)
            step = _req(rec, "step", int, where)
            if step < 0:
                raise SchemaError(f"{where}: negative step {step}")
            _check_fleet_scalar(name, rec["value"], where, step=step)
            _req(rec, "t", (int, float), where)
            n_scalars += 1
    if not saw_header:
        raise SchemaError(f"{path}: no run-header record")
    return n_scalars


def validate_comm_ledger(path) -> dict:
    """Validate comm_ledger.json INCLUDING the exactness invariant.

    Full-participation ledgers: cumulative bytes == rounds *
    bytes_per_round. fedsim masked ledgers (the ``live_client_rounds`` /
    ``avail_client_rounds`` keys present): only live clients' uplink and
    available clients' downlink counted, so the invariant becomes
    ``cum_up_bytes == live_client_rounds * upload_bytes`` (with
    live_client_rounds = sum over rounds of that round's live count) and
    likewise for the downlink — exact ints, no tolerance."""
    where = str(path)
    with open(path) as f:
        rec = _strict_loads(f.read())
    _check_version(rec, where)
    _req(rec, "mode", str, where)
    nw = _req(rec, "num_workers", int, where)
    if nw < 1:
        raise SchemaError(f"{where}: num_workers must be >= 1, got {nw}")
    bpr = _req(rec, "bytes_per_round", dict, where)
    for k in ("upload_floats", "download_floats", "upload_bytes",
              "download_bytes"):
        if not isinstance(bpr.get(k), int):
            raise SchemaError(f"{where}: bytes_per_round[{k!r}] missing or "
                              "not an int")
    rounds = _req(rec, "rounds", int, where)
    up = _req(rec, "cum_up_bytes", int, where)
    down = _req(rec, "cum_down_bytes", int, where)
    total = _req(rec, "cum_bytes", int, where)
    masked = "live_client_rounds" in rec or "avail_client_rounds" in rec
    if masked:
        live = _req(rec, "live_client_rounds", int, where)
        avail = _req(rec, "avail_client_rounds", int, where)
        if not 0 <= live <= rounds * nw:
            raise SchemaError(
                f"{where}: live_client_rounds {live} outside "
                f"[0, rounds * num_workers] ({rounds} * {nw})"
            )
        if not live <= avail <= rounds * nw:
            raise SchemaError(
                f"{where}: avail_client_rounds {avail} outside "
                f"[live_client_rounds, rounds * num_workers]"
            )
    if "rungs" in rec:
        # v4 control/ ladder accounting: each round billed at its ACTIVE
        # rung's rate — the invariant is the sum over rungs of that
        # rung's rounds (live/avail counts when masked) x its
        # bytes_per_round. Exact ints, no tolerance, like the flat law.
        rungs = _req(rec, "rungs", list, where)
        if not rungs:
            raise SchemaError(f"{where}: empty rungs block")
        up_want = down_want = rounds_sum = 0
        live_sum = avail_sum = 0
        for i, r in enumerate(rungs):
            w = f"{where}:rungs[{i}]"
            if not isinstance(r, dict):
                raise SchemaError(f"{w}: expected an object")
            rb = _req(r, "bytes_per_round", dict, w)
            for k in ("upload_bytes", "download_bytes"):
                if not isinstance(rb.get(k), int):
                    raise SchemaError(
                        f"{w}: bytes_per_round[{k!r}] missing or not an int"
                    )
            n_r = _req(r, "rounds", int, w)
            if n_r < 0:
                raise SchemaError(f"{w}: negative rounds")
            rounds_sum += n_r
            if masked:
                live_r = _req(r, "live_client_rounds", int, w)
                avail_r = _req(r, "avail_client_rounds", int, w)
                live_sum += live_r
                avail_sum += avail_r
                up_want += live_r * rb["upload_bytes"]
                down_want += avail_r * rb["download_bytes"]
            else:
                up_want += n_r * rb["upload_bytes"]
                down_want += n_r * rb["download_bytes"]
        if rounds_sum != rounds:
            raise SchemaError(
                f"{where}: per-rung rounds sum to {rounds_sum}, ledger "
                f"counted {rounds}"
            )
        if masked and (live_sum != live or avail_sum != avail):
            raise SchemaError(
                f"{where}: per-rung live/avail client-rounds "
                f"({live_sum}/{avail_sum}) != ledger totals "
                f"({live}/{avail})"
            )
        up_law = ("sum_r live_r * up_r" if masked
                  else "sum_r rounds_r * up_r")
        down_law = ("sum_r avail_r * down_r" if masked
                    else "sum_r rounds_r * down_r")
    elif masked:
        up_want, down_want = (live * bpr["upload_bytes"],
                              avail * bpr["download_bytes"])
        up_law = "live_client_rounds * upload_bytes"
        down_law = "avail_client_rounds * download_bytes"
    else:
        up_want, down_want = (rounds * bpr["upload_bytes"],
                              rounds * bpr["download_bytes"])
        up_law = "rounds * upload_bytes"
        down_law = "rounds * download_bytes"
    if up != up_want:
        raise SchemaError(
            f"{where}: cum_up_bytes {up} != {up_law} ({up_want})"
        )
    if down != down_want:
        raise SchemaError(
            f"{where}: cum_down_bytes {down} != {down_law} ({down_want})"
        )
    if total != up + down:
        raise SchemaError(f"{where}: cum_bytes {total} != up + down")
    return rec


def validate_flight(path) -> dict:
    """Validate a flight_<step>.json record."""
    where = str(path)
    with open(path) as f:
        rec = _strict_loads(f.read())
    _check_version(rec, where)
    _req(rec, "reason", str, where)
    if "first_bad_step" in rec and rec["first_bad_step"] is not None:
        _req(rec, "first_bad_step", int, where)
    window = _req(rec, "window", int, where)
    if window < 1:
        raise SchemaError(f"{where}: window must be >= 1")
    _check_header({**_req(rec, "meta", dict, where),
                   "schema_version": rec["schema_version"]}, where + ":meta")
    records = _req(rec, "records", list, where)
    if len(records) > window:
        raise SchemaError(
            f"{where}: {len(records)} records exceed the ring window "
            f"{window}"
        )
    if "controller" in rec:
        # v4 ladder runs: the dump-time controller state surfaced
        # top-level by FlightRecorder.dump — a divergence is attributable
        # to a rung switch from here + the per-record control/rung scalars
        _check_controller_block(
            _req(rec, "controller", dict, where), where + ":controller"
        )
    if "recovery_history" in rec:
        # v6 self-healing runs: every rollback this run survived (policy,
        # first bad round, rollback target, outcome) — surfaced top-level
        # by FlightRecorder.dump via the attached resilience rider
        _check_recovery_history(rec["recovery_history"], where)
    if "participation_history" in rec:
        # fedsim runs: the [step, participation_rate] window surfaced
        # top-level by FlightRecorder.dump
        hist = _req(rec, "participation_history", list, where)
        if len(hist) > window:
            raise SchemaError(
                f"{where}: participation_history exceeds the ring window"
            )
        for j, pair in enumerate(hist):
            w = f"{where}:participation_history[{j}]"
            if (not isinstance(pair, list) or len(pair) != 2
                    or isinstance(pair[0], bool)
                    or not isinstance(pair[0], int)):
                raise SchemaError(f"{w}: expected [step, rate] pair")
            _check_scalar_value(pair[1], "fedsim/participation_rate", w)
    last = None
    last_resizes = None
    for j, r in enumerate(records):
        w = f"{where}:records[{j}]"
        step = _req(r, "step", int, w)
        if "lr" not in r:
            raise SchemaError(f"{w}: missing required field 'lr'")
        _check_scalar_value(r["lr"], "lr", w)  # number or nan/inf marker
        scalars = _req(r, "scalars", dict, w)
        for name, v in scalars.items():
            _check_scalar_name(name, w, allow_bare_aux=True)
            _check_scalar_value(v, name, w)
            _check_resilience_scalar(name, v, w)
            _check_async_scalar(name, v, w)
            _check_clientstore_scalar(name, v, w)
            _check_multihost_scalar(name, v, w)
            _check_xla_scalar(name, v, w)
            _check_trace_scalar(name, v, w)
            _check_control_async_scalar(name, v, w)
            _check_fleet_scalar(name, v, w, step=step)
        # v13: fleet/resizes counts realized width transitions — over the
        # dump's step-ordered ring it can only grow (a drop means the
        # writer re-derived the schedule wrong, or records from two runs
        # were spliced)
        if "fleet/resizes" in scalars:
            rz = scalars["fleet/resizes"]
            if last_resizes is not None and rz < last_resizes:
                raise SchemaError(
                    f"{w}: fleet/resizes fell from {last_resizes} to {rz} "
                    "— resize counts are non-decreasing in step order"
                )
            last_resizes = rz
        if last is not None and step <= last:
            raise SchemaError(f"{w}: records not in increasing step order")
        last = step
    return rec


def _check_analysis_block(block: dict, fields, where: str) -> None:
    """cost/memory analysis block: every field a non-negative number or
    null; degraded blocks must say why (non-empty unavailable_reason)."""
    for f in fields:
        v = block.get(f)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, (int, float)) or v < 0:
            raise SchemaError(
                f"{where}: {f} must be a non-negative number or null, "
                f"got {v!r}"
            )
    if all(block.get(f) is None for f in fields):
        reason = block.get("unavailable_reason")
        if not isinstance(reason, str) or not reason:
            raise SchemaError(
                f"{where}: fully-degraded analysis must carry a non-empty "
                "unavailable_reason"
            )


def validate_perf_report(path) -> dict:
    """Validate a perf_report.json (v3, telemetry/xla_audit.py) INCLUDING
    the collective invariants: total_bytes == sum over ops, delta/
    within_tolerance arithmetic consistent — and on the sketch
    sharded-decode path, the PR-6 design claims are HARD requirements:
    every all-gather <= the recorded W*k bound and the ledger-vs-HLO byte
    delta within the recorded accounting tolerance."""
    where = str(path)
    with open(path) as f:
        rec = _strict_loads(f.read())
    _check_version(rec, where)
    if rec.get("kind") != "perf_report":
        raise SchemaError(f"{where}: kind must be 'perf_report', got "
                          f"{rec.get('kind')!r}")
    _req(rec, "generated_by", str, where)
    engine = _req(rec, "engine", str, where)
    if engine not in ("replicated", "fsdp", "async"):
        raise SchemaError(f"{where}: unknown engine {engine!r}")
    _req(rec, "mode", str, where)
    # v8: the overlap-geometry block is required exactly on async audits —
    # a synchronous report carrying one means the producer mislabeled the
    # engine (or vice versa), so both directions are hard errors
    if engine == "async":
        blk = _req(rec, "async", dict, where)
        for f, lo in (("buffer", 1), ("concurrency", 1),
                      ("staleness_exponent", 0)):
            v = blk.get(f)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise SchemaError(
                    f"{where}:async: missing or non-numeric {f!r}"
                )
            if f != "staleness_exponent" and v != int(v):
                raise SchemaError(f"{where}:async: {f} must be an integer, "
                                  f"got {v!r}")
            if v < lo:
                raise SchemaError(f"{where}:async: {f} {v} below {lo}")
    elif "async" in rec:
        raise SchemaError(
            f"{where}: 'async' block present on a {engine!r} report — the "
            "overlap geometry is an async-engine property (schema v8)"
        )
    # v9: the collective-hiding block is required exactly when the
    # report's config has a hiding mode on — wall-clock rows must always
    # be attributable to their overlap setting, so a report silently
    # produced under layerwise overlap (block missing) and one carrying a
    # both-off block (mislabeled producer) are both hard errors
    cfg_blk = rec.get("meta", {}).get("config") or {}
    cfg_hiding = (cfg_blk.get("overlap_collectives", "none") != "none"
                  or bool(cfg_blk.get("async_double_buffer", False)))
    if "overlap" in rec:
        blk = _req(rec, "overlap", dict, where)
        ov = blk.get("collectives")
        if ov not in ("none", "layerwise"):
            raise SchemaError(
                f"{where}:overlap: collectives must be 'none' or "
                f"'layerwise', got {ov!r}"
            )
        db = blk.get("double_buffer")
        if not isinstance(db, bool):
            raise SchemaError(
                f"{where}:overlap: double_buffer must be a bool, got {db!r}"
            )
        if ov == "none" and not db:
            raise SchemaError(
                f"{where}: 'overlap' block with every hiding mode off — "
                "the block rides the report only when a mode is ON "
                "(schema v9)"
            )
        if cfg_blk and not cfg_hiding:
            raise SchemaError(
                f"{where}: 'overlap' block present but the report's config "
                "has overlap_collectives='none' and async_double_buffer "
                "off — mislabeled producer (schema v9)"
            )
    elif cfg_hiding:
        raise SchemaError(
            f"{where}: config has a collective-hiding mode on "
            f"(overlap_collectives="
            f"{cfg_blk.get('overlap_collectives', 'none')!r}, "
            f"async_double_buffer={cfg_blk.get('async_double_buffer')!r}) "
            "but the report carries no 'overlap' block (schema v9)"
        )
    # v12: the multihost block is required exactly when the report's
    # config declares a host axis — a pod report without one would leave
    # its wall-clock rows unattributable to a topology, and a single-host
    # report carrying one means the producer mislabeled the mesh
    cfg_multihost = int(cfg_blk.get("num_hosts", 1) or 1) > 1
    if "multihost" in rec:
        blk = _req(rec, "multihost", dict, where)
        nh = blk.get("num_hosts")
        if isinstance(nh, bool) or not isinstance(nh, int) or nh < 2:
            raise SchemaError(
                f"{where}:multihost: num_hosts must be an integer >= 2 "
                f"(the block only rides multi-host audits), got {nh!r}"
            )
        nproc = blk.get("num_processes")
        if isinstance(nproc, bool) or not isinstance(nproc, int) or nproc < 1:
            raise SchemaError(
                f"{where}:multihost: num_processes must be an integer "
                f">= 1 (1 = mesh-faked twin), got {nproc!r}"
            )
        hid = blk.get("host_id")
        if (isinstance(hid, bool) or not isinstance(hid, int)
                or not 0 <= hid < nproc):
            raise SchemaError(
                f"{where}:multihost: host_id {hid!r} outside "
                f"[0, num_processes={nproc}) — the writing process's "
                "index in the pod"
            )
        if cfg_blk and not cfg_multihost:
            raise SchemaError(
                f"{where}: 'multihost' block present but the report's "
                "config declares no host axis (num_hosts="
                f"{cfg_blk.get('num_hosts', 1)!r}) — mislabeled producer "
                "(schema v12)"
            )
    elif cfg_multihost:
        raise SchemaError(
            f"{where}: config declares a host axis (num_hosts="
            f"{cfg_blk.get('num_hosts')!r}) but the report carries no "
            "'multihost' block (schema v12)"
        )
    _check_header({**_req(rec, "meta", dict, where),
                   "schema_version": rec["schema_version"]}, where + ":meta")
    cost = _req(rec, "cost", dict, where)
    _check_analysis_block(
        cost, ("flops", "bytes_accessed", "transcendentals"), where + ":cost"
    )
    mem = _req(rec, "memory", dict, where)
    _check_analysis_block(
        mem, ("argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
              "peak_hbm_bytes"), where + ":memory",
    )
    coll = _req(rec, "collectives", dict, where)
    ops = _req(coll, "ops", dict, where + ":collectives")
    total = _req(coll, "total_bytes", int, where + ":collectives")
    op_sum = 0
    for op, stats in ops.items():
        w = f"{where}:collectives.ops[{op}]"
        if op not in ("all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute"):
            raise SchemaError(f"{w}: unknown collective op")
        if not isinstance(stats, dict):
            raise SchemaError(f"{w}: expected {{count, bytes}}")
        c = _req(stats, "count", int, w)
        b = _req(stats, "bytes", int, w)
        if c < 1 or b < 0:
            raise SchemaError(f"{w}: count must be >= 1 and bytes >= 0")
        op_sum += b
    if total != op_sum:
        raise SchemaError(
            f"{where}: collectives.total_bytes {total} != sum over ops "
            f"({op_sum})"
        )
    # cross-check arithmetic (present iff the producer had ledger figures)
    if coll.get("ledger_up_bytes") is not None:
        up = _req(coll, "ledger_up_bytes", int, where + ":collectives")
        delta = _req(coll, "delta_bytes", int, where + ":collectives")
        tol = _req(coll, "tolerance_bytes", int, where + ":collectives")
        within = _req(coll, "within_tolerance", bool, where + ":collectives")
        if delta != total - up:
            raise SchemaError(
                f"{where}: delta_bytes {delta} != total_bytes - "
                f"ledger_up_bytes ({total - up})"
            )
        if within != (abs(delta) <= tol):
            raise SchemaError(
                f"{where}: within_tolerance {within} inconsistent with "
                f"|delta| {abs(delta)} vs tolerance {tol}"
            )
    # the sketch sharded-decode path's design claims are enforced, not
    # merely recorded (ISSUE 7 acceptance: checker-enforced invariant)
    if rec.get("sketch_decode") == "sharded":
        wk = coll.get("wk_bound")
        if not isinstance(wk, int) or wk < 1:
            raise SchemaError(
                f"{where}: sharded decode requires a positive wk_bound"
            )
        mag = coll.get("max_all_gather_elems")
        if mag is not None and mag > wk:
            raise SchemaError(
                f"{where}: sharded decode all-gather of {mag} elements "
                f"exceeds the W*k candidate bound ({wk}) — a d-sized "
                "collective leaked into the compiled round"
            )
        if coll.get("within_tolerance") is False:
            raise SchemaError(
                f"{where}: sharded decode ledger-vs-HLO delta "
                f"{coll.get('delta_bytes')} B outside the accounting "
                f"tolerance {coll.get('tolerance_bytes')} B"
            )
    # the sparse-aggregate path's O(W*k) on-mesh claim is likewise
    # enforced (v7, ISSUE 14 acceptance): neither replicating collective
    # may move a d-sized payload. reduce-scatter is exempt by design —
    # it moves O(D/W) per link and lands sharded, which is exactly the
    # layout the sparse decode consumes.
    if rec.get("aggregate") == "sparse":
        bound = coll.get("sparse_agg_bound")
        if not isinstance(bound, int) or bound < 1:
            raise SchemaError(
                f"{where}: sparse aggregation requires a positive "
                "sparse_agg_bound"
            )
        # v10: a hosted client store (--client_store host|mmap) passes the
        # cohort's rows as round ARGUMENTS, so the [C, D]-scale writeback
        # gather never exists in the HLO and the STRICT W*k-class bound
        # must hold — an exemption marker on such a report means the
        # producer inflated sparse_agg_bound it had no right to, so the
        # elems-vs-bound checks below would be vacuous. Reject it.
        exemption = coll.get("sparse_agg_exemption")
        if exemption is not None and exemption != "client_state_writeback":
            raise SchemaError(
                f"{where}: unknown sparse_agg_exemption {exemption!r} "
                "(known: 'client_state_writeback')"
            )
        hosted = cfg_blk.get("client_store", "device") in ("host", "mmap")
        if hosted and exemption is not None:
            raise SchemaError(
                f"{where}: sparse-aggregate report carries "
                f"sparse_agg_exemption={exemption!r} but its config hosts "
                "client state (client_store="
                f"{cfg_blk.get('client_store')!r}) — hosted rounds take "
                "cohort rows as arguments, so the strict W*k bound holds "
                "with NO writeback allowance (schema v10)"
            )
        for field, opname in (("max_all_gather_elems", "all-gather"),
                              ("max_all_reduce_elems", "all-reduce")):
            mx = coll.get(field)
            if mx is not None and mx > bound:
                raise SchemaError(
                    f"{where}: sparse aggregation {opname} of {mx} "
                    f"elements exceeds the pair-exchange bound ({bound}) "
                    "— a d-sized replicating collective leaked into the "
                    "compiled round"
                )
    return rec


def validate_spans(path) -> dict:
    """Validate a spans_<step>.json (v3, telemetry/spans.py): Chrome-trace
    complete events with step/fenced annotations."""
    where = str(path)
    with open(path) as f:
        rec = _strict_loads(f.read())
    _check_version(rec, where)
    if rec.get("kind") != "spans":
        raise SchemaError(
            f"{where}: kind must be 'spans', got {rec.get('kind')!r}"
        )
    if "exposed_collective_ms" in rec:
        # v9: the dump-level exposure figure (telemetry/spans.py
        # collective_exposure_ms) — same gauge invariant as the scalar
        _check_xla_scalar("xla/exposed_collective_ms",
                          rec["exposed_collective_ms"], where)
    events = _req(rec, "traceEvents", list, where)
    if not events:
        raise SchemaError(f"{where}: empty traceEvents")
    n_spans = 0
    for j, ev in enumerate(events):
        w = f"{where}:traceEvents[{j}]"
        if not isinstance(ev, dict):
            raise SchemaError(f"{w}: event is not an object")
        name = _req(ev, "name", str, w)
        if not name:
            raise SchemaError(f"{w}: empty event name")
        if ev.get("ph") == "M":
            # v5 thread-aware spans: lane-naming metadata (the prefetch
            # worker's track label) — the only metadata kind the writer
            # emits, so anything else is a writer bug
            if name != "thread_name":
                raise SchemaError(
                    f"{w}: unknown metadata event {name!r} (only "
                    "thread_name is in the schema)"
                )
            args = _req(ev, "args", dict, w)
            if not isinstance(args.get("name"), str) or not args["name"]:
                raise SchemaError(
                    f"{w}: thread_name metadata needs a non-empty "
                    "args.name"
                )
            mtid = _req(ev, "tid", int, w)
            if isinstance(mtid, bool) or mtid < 0:
                raise SchemaError(
                    f"{w}: tid must be a non-negative lane int, got "
                    f"{mtid!r}"
                )
            continue
        if ev.get("ph") != "X":
            raise SchemaError(
                f"{w}: ph must be 'X' (complete event) or 'M' "
                "(thread_name metadata, v5)"
            )
        for f_ in ("ts", "dur"):
            v = _req(ev, f_, (int, float), w)
            if v < 0:
                raise SchemaError(f"{w}: negative {f_}")
        tid = ev.get("tid")
        if isinstance(tid, bool) or not isinstance(tid, int) or tid < 0:
            raise SchemaError(
                f"{w}: tid must be a non-negative lane int, got {tid!r}"
            )
        args = _req(ev, "args", dict, w)
        _req(args, "step", int, w + ":args")
        if "collective" in args and args["collective"] is not True:
            # v9: the tag is only ever written as true (absent == false);
            # any other value means a writer regression
            raise SchemaError(
                f"{w}: args.collective must be true when present, got "
                f"{args['collective']!r}"
            )
        # v11 trace correlation: trace_id names the owning round/cohort
        # ("r<step>" / "c<cohort>"); parent is a causal link and only
        # means something on an id-carrying span — the writer
        # (telemetry/spans.py _record) never emits a bare parent, so one
        # here is a writer regression
        if "trace_id" in args and (
                not isinstance(args["trace_id"], str)
                or not args["trace_id"]):
            raise SchemaError(
                f"{w}: args.trace_id must be a non-empty string, got "
                f"{args['trace_id']!r}"
            )
        if "parent" in args:
            if "trace_id" not in args:
                raise SchemaError(
                    f"{w}: args.parent without args.trace_id — a parent "
                    "link rides only on id-carrying spans (schema v11)"
                )
            par = args["parent"]
            if not isinstance(par, str) or not par:
                raise SchemaError(
                    f"{w}: args.parent must be a non-empty string, got "
                    f"{par!r}"
                )
            if par == args["trace_id"]:
                raise SchemaError(
                    f"{w}: args.parent == args.trace_id ({par!r}) — a "
                    "span cannot be its own causal parent"
                )
        n_spans += 1
    if n_spans == 0:
        raise SchemaError(f"{where}: no complete ('X') span events")
    return rec


def validate_run_report(path) -> dict:
    """Validate a run_report.json (v11, telemetry/trace.py
    build_run_report) INCLUDING the attribution invariants: stage
    fractions in [0, 1] summing to ~1 over analyzed rounds (or all zero
    when nothing was attributed), per-round exclusive stage times
    finite, >= 0, and summing to the round's wall-clock — the
    disjointness guarantee CriticalPath makes; an overlap between two
    stages would push the sum past the wall and fail here."""
    where = str(path)
    with open(path) as f:
        rec = _strict_loads(f.read())
    _check_version(rec, where)
    if rec.get("kind") != "run_report":
        raise SchemaError(f"{where}: kind must be 'run_report', got "
                          f"{rec.get('kind')!r}")
    _req(rec, "generated_by", str, where)
    _req(rec, "sources", dict, where)
    n_rounds = _req(rec, "rounds_analyzed", int, where)
    if n_rounds < 0:
        raise SchemaError(f"{where}: negative rounds_analyzed")
    crit = _req(rec, "critical_stage", str, where)
    if crit not in TRACE_STAGES:
        raise SchemaError(
            f"{where}: critical_stage {crit!r} outside the stage "
            f"taxonomy {TRACE_STAGES}"
        )
    counts = _req(rec, "critical_counts", dict, where)
    if set(counts) != set(TRACE_STAGES):
        raise SchemaError(
            f"{where}: critical_counts keys {sorted(counts)} != the "
            "stage taxonomy"
        )
    for s, c in counts.items():
        if isinstance(c, bool) or not isinstance(c, int) or c < 0:
            raise SchemaError(
                f"{where}: critical_counts[{s!r}] must be a non-negative "
                f"integer, got {c!r}"
            )
    if sum(counts.values()) != n_rounds:
        raise SchemaError(
            f"{where}: critical_counts sum to {sum(counts.values())}, "
            f"but {n_rounds} round(s) were analyzed — every analyzed "
            "round has exactly one binding stage"
        )
    stages = _req(rec, "stages", dict, where)
    if set(stages) != set(TRACE_STAGES):
        raise SchemaError(
            f"{where}: stages keys {sorted(stages)} != the stage taxonomy"
        )
    frac_sum = 0.0
    for s, blk in stages.items():
        w = f"{where}:stages[{s}]"
        if not isinstance(blk, dict):
            raise SchemaError(f"{w}: expected an object")
        for f_ in ("p50_ms", "p95_ms", "total_ms"):
            v = _req(blk, f_, (int, float), w)
            if isinstance(v, bool) or v < 0:
                raise SchemaError(f"{w}: {f_} must be >= 0, got {v!r}")
        fr = _req(blk, "fraction", (int, float), w)
        if isinstance(fr, bool) or not 0.0 <= fr <= 1.0:
            raise SchemaError(
                f"{w}: fraction {fr!r} outside [0, 1]"
            )
        frac_sum += fr
    # fractions are total_ms / total wall per stage, idle the remainder
    # of every round — so they sum to 1 whenever anything was attributed
    # (and to exactly 0 for a spans-less report)
    if frac_sum != 0.0 and abs(frac_sum - 1.0) > 1e-6:
        raise SchemaError(
            f"{where}: stage fractions sum to {frac_sum!r}, expected ~1 "
            "(attribution must account for every analyzed microsecond, "
            "idle included)"
        )
    rounds = _req(rec, "rounds", list, where)
    if len(rounds) != n_rounds:
        raise SchemaError(
            f"{where}: {len(rounds)} per-round entries but "
            f"rounds_analyzed={n_rounds}"
        )
    for j, r in enumerate(rounds):
        w = f"{where}:rounds[{j}]"
        if not isinstance(r, dict):
            raise SchemaError(f"{w}: expected an object")
        step = _req(r, "step", int, w)
        if step < 0:
            raise SchemaError(f"{w}: negative step")
        wall = _req(r, "wall_ms", (int, float), w)
        if isinstance(wall, bool) or wall < 0:
            raise SchemaError(f"{w}: wall_ms must be >= 0, got {wall!r}")
        rc_ = _req(r, "critical_stage", str, w)
        if rc_ not in TRACE_STAGES:
            raise SchemaError(
                f"{w}: critical_stage {rc_!r} outside the stage taxonomy"
            )
        sm = _req(r, "stages_ms", dict, w)
        if set(sm) != set(TRACE_STAGES):
            raise SchemaError(
                f"{w}: stages_ms keys {sorted(sm)} != the stage taxonomy"
            )
        tot = 0.0
        for s, v in sm.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise SchemaError(
                    f"{w}: stages_ms[{s!r}] must be a number, got {v!r}"
                )
            if v < 0:
                raise SchemaError(
                    f"{w}: stages_ms[{s!r}] {v} is negative — exclusive "
                    "stage times are interval measures, >= 0"
                )
            tot += v
        # disjointness: exclusive times sum to EXACTLY the wall-clock
        # (idle is the remainder); a sum past the wall means two stages
        # were charged the same microseconds
        if tot > wall + max(1e-6, 1e-6 * wall):
            raise SchemaError(
                f"{w}: exclusive stage times sum to {tot} ms, past the "
                f"round's wall_ms {wall} — stages overlap (schema v11 "
                "requires a disjoint decomposition)"
            )
    anomalies = _req(rec, "anomalies", list, where)
    for j, a in enumerate(anomalies):
        w = f"{where}:anomalies[{j}]"
        if not isinstance(a, dict):
            raise SchemaError(f"{w}: expected an object")
        for f_ in ("kind", "metric", "detail"):
            if not isinstance(a.get(f_), str) or not a[f_]:
                raise SchemaError(
                    f"{w}: anomaly needs a non-empty string {f_!r}"
                )
    return rec


def validate_run_dir(run_dir) -> dict:
    """Validate every telemetry artifact found under one run dir; returns
    {artifact_path: summary}. Missing artifact kinds are fine (a level-0
    run has only metrics.jsonl)."""
    run_dir = Path(run_dir)
    out = {}
    metrics = run_dir / "metrics.jsonl"
    if metrics.exists():
        out[str(metrics)] = f"{validate_metrics_jsonl(metrics)} scalar(s)"
    ledger = run_dir / "comm_ledger.json"
    if ledger.exists():
        rec = validate_comm_ledger(ledger)
        out[str(ledger)] = (f"{rec['rounds']} round(s), "
                            f"{rec['cum_bytes']} cum bytes")
    for flight in sorted(run_dir.glob("flight_*.json")):
        rec = validate_flight(flight)
        out[str(flight)] = (f"{len(rec['records'])} record(s), "
                            f"reason: {rec['reason'][:60]}")
    perf = run_dir / "perf_report.json"
    if perf.exists():
        rec = validate_perf_report(perf)
        coll = rec.get("collectives", {})
        out[str(perf)] = (
            f"{rec['engine']}/{rec['mode']}, "
            f"{coll.get('total_bytes', 0)} collective B"
        )
    for spans in sorted(run_dir.glob("spans_*.json")):
        rec = validate_spans(spans)
        out[str(spans)] = f"{len(rec['traceEvents'])} span event(s)"
    report = run_dir / "run_report.json"
    if report.exists():
        rec = validate_run_report(report)
        out[str(report)] = (f"{rec['rounds_analyzed']} round(s), "
                            f"critical: {rec['critical_stage']}")
    if not out:
        raise SchemaError(f"{run_dir}: no telemetry artifacts found")
    return out


def main(argv) -> int:
    # the last stdout line is ALWAYS a machine-readable JSON summary —
    # {"kind": "telemetry_schema", "run_dirs": N, "artifacts": M,
    #  "failures": [...]} — on every exit path including usage errors,
    # the consumer contract scripts/check_bench_regression.py
    # established for gate scripts (pinned by tests/test_telemetry_schema)
    def summary_line(**kw):
        print(json.dumps({"kind": "telemetry_schema", **kw}))

    if not argv:
        print(__doc__)
        summary_line(run_dirs=0, artifacts=0, failures=[],
                     error="usage: pass one or more run dirs")
        return 2
    rc = 0
    n_artifacts = 0
    failures = []
    for run_dir in argv:
        try:
            for path, summary in validate_run_dir(run_dir).items():
                print(f"OK   {path}: {summary}")
                n_artifacts += 1
        # ValueError covers SchemaError and a truncated/corrupt
        # artifact's raw JSONDecodeError (both subclass it); OSError an
        # unreadable path — each must fail THIS run dir and still end
        # stdout with the summary line, not escape as a traceback (the
        # corrupted-artifact case is what a gate script exists to catch)
        except (OSError, ValueError) as e:
            print(f"FAIL {e}")
            failures.append(str(e))
            rc = 1
    summary_line(run_dirs=len(argv), artifacts=n_artifacts,
                 failures=failures)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
