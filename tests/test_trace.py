"""Round tracing & critical-path attribution (telemetry/trace.py, PR 18).

What this file pins, and why it is shaped as two runs: ``async_buffer``
is mutually exclusive with hosted client stores (utils/config.py
_validate_asyncfed — the asyncfed engine requires HBM-resident banks),
so the planes are covered by a hosted run of the plain loop
(``--client_store host``) and an async run (C = 3) whose span dumps
together carry every gather/writeback/dispatch/launch/apply span with
the owning round's/cohort's trace id.

  * trace-id grammar: deterministic ids minted at realization time —
    ``r<step>`` for rounds, ``c<cohort>`` for async cohorts (parent =
    the launching round's id); ``step_of_trace_id`` inverts only round
    ids.
  * CriticalPath: the exclusive decomposition is DISJOINT — stage times
    sum to exactly the round wall-clock (idle is the remainder), exposed
    collective is assigned first, and non-path spans
    (async_buffer_residency) never stretch the round window.
  * e2e: the hosted dump validates under schema v11, every
    gather/writeback/dispatch span carries its round's id, the lagged
    ``trace/*`` scalars ride the metric stream with a constant key set,
    and the run dir round-trips through write_run_report ->
    validate_run_report -> scripts/analyze_run.py.
  * level-0 discipline: tracing is host-side only — the lowered HLO at
    ``--telemetry_level 0`` is byte-identical with spans attached and a
    ``--profile_rounds`` window configured, and a rung switch under a
    hosted store with tracing active still retraces nothing.
"""

import importlib.util
import json
import os

import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.telemetry.spans import PhaseSpans
from commefficient_tpu.telemetry.trace import (
    STAGES,
    CriticalPath,
    ProfilerWindow,
    cohort_trace_id,
    parse_profile_rounds,
    round_trace_id,
    step_of_trace_id,
    trace_round_scalars,
    trace_scalar_keys,
    write_run_report,
)
from commefficient_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both client banks live (the writeback path has work to do)
KW = dict(mode="local_topk", error_type="local", local_momentum=0.9, k=30)


def _script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lr_fn(step):
    return 0.3 - 0.01 * step


# ---------------------------------------------------------------------------
# trace ids
# ---------------------------------------------------------------------------

def test_trace_id_grammar_and_inverse():
    assert round_trace_id(7) == "r7"
    assert cohort_trace_id(3) == "c3"
    assert step_of_trace_id("r7") == 7
    assert step_of_trace_id(round_trace_id(0)) == 0
    # cohort ids and garbage do NOT invert to a step
    for bad in ("c3", "r", "r-1x", "", None, "x7"):
        assert step_of_trace_id(bad) is None


def test_trace_stage_taxonomy_pinned_to_checker():
    """The checker keeps a deliberate copy of the taxonomy (it imports
    nothing from the package); this pin is what keeps the two tuples
    from drifting apart."""
    assert tuple(_script("check_telemetry_schema").TRACE_STAGES) == \
        tuple(STAGES)


# ---------------------------------------------------------------------------
# CriticalPath: pure interval arithmetic
# ---------------------------------------------------------------------------

def _ev(name, ts, dur, step, collective=False, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 0,
            "tid": 0, "args": {"step": step, "fenced": False,
                               "collective": collective, **args}}


def test_critical_path_exclusive_disjoint_decomposition():
    """The worked example from the module docstring: exposed collective
    is assigned first, the collective-tagged dispatch span's UNEXPOSED
    part charges to dispatch (priority above h2d), and the exclusive
    times sum to exactly the wall-clock."""
    cp = CriticalPath([
        _ev("device_put", 0, 1000, 4),
        _ev("round_dispatch", 500, 2000, 4, collective=True),
        _ev("metric_drain", 2500, 500, 4),
    ])
    bd = cp.round_breakdown(4)
    assert bd["step"] == 4
    assert bd["wall_ms"] == pytest.approx(3.0)
    sm = bd["stages_ms"]
    assert sm["collective"] == pytest.approx(1.5)  # [1000, 2500) exposed
    assert sm["dispatch"] == pytest.approx(0.5)    # [500, 1000) unexposed
    assert sm["h2d"] == pytest.approx(0.5)         # [0, 500) left over
    assert sm["drain"] == pytest.approx(0.5)
    assert sm["data"] == sm["writeback"] == sm["idle"] == 0.0
    assert sum(sm.values()) == pytest.approx(bd["wall_ms"])
    assert bd["critical_stage"] == "collective"


def test_critical_path_idle_remainder_and_non_path_exclusion():
    """Un-spanned wall-clock lands in idle, and the retroactive
    async_buffer_residency span (which OVERLAPS many rounds by design)
    never stretches the round window or double-charges a stage."""
    cp = CriticalPath([
        _ev("data_load", 0, 1000, 1),
        _ev("checkpoint", 2000, 1000, 1),
        _ev("async_buffer_residency", 0, 50_000, 1),
    ])
    bd = cp.round_breakdown(1)
    assert bd["wall_ms"] == pytest.approx(3.0)  # not 50
    assert bd["stages_ms"]["data"] == pytest.approx(1.0)
    assert bd["stages_ms"]["drain"] == pytest.approx(1.0)
    assert bd["stages_ms"]["idle"] == pytest.approx(1.0)
    assert sum(bd["stages_ms"].values()) == pytest.approx(3.0)
    # rounds with no events decompose to None, never to a zeros row
    assert cp.round_breakdown(2) is None
    assert cp.steps() == [1]


def test_trace_round_scalars_constant_keys_and_zeros_row():
    zeros = trace_round_scalars(None, 5)
    assert set(zeros) == set(trace_scalar_keys())
    assert zeros["trace/critical_stage"] == float(STAGES.index("idle"))
    assert all(v == 0.0 for k, v in zeros.items()
               if k != "trace/critical_stage")
    # a negative step (the lagged emission's first rounds) is the zeros
    # row even with a live ring attached
    spans = PhaseSpans(".")
    with spans.span("round_dispatch", step=3):
        pass
    assert trace_round_scalars(spans, -1) == zeros
    live = trace_round_scalars(spans, 3)
    assert set(live) == set(trace_scalar_keys())
    assert sum(v for k, v in live.items()
               if k.endswith("_exclusive_ms")) > 0.0


# ---------------------------------------------------------------------------
# --profile_rounds window
# ---------------------------------------------------------------------------

def test_parse_profile_rounds_grammar():
    assert parse_profile_rounds("3-5") == (3, 5)
    assert parse_profile_rounds("7-7") == (7, 7)
    for bad in ("", "5-3", "3", "a-b", "-1-2", "3-"):
        with pytest.raises(ValueError):
            parse_profile_rounds(bad)


def test_profiler_window_clamps_fences_and_disarms(tmp_path):
    """A 0-1 spec cannot trace compile+warmup: the start clamps to
    MIN_WARMUP_STEPS, entry/exit are fenced, and after the window the
    profiler is permanently disarmed (exactly one capture per run)."""
    from commefficient_tpu.utils.profiling import MIN_WARMUP_STEPS

    fences = []
    win = ProfilerWindow("0-1", str(tmp_path),
                         fence_fn=lambda: fences.append(1))
    assert win.start == MIN_WARMUP_STEPS
    assert win.stop_at == MIN_WARMUP_STEPS + 2
    for s in range(MIN_WARMUP_STEPS):
        win.step(s)
    assert not fences and not win._active
    win.step(win.start)  # entry: fence, then start (or disarm off-TPU)
    assert len(fences) == 1
    assert win._active or not win._armed
    was_active = win._active
    win.step(win.stop_at)
    assert not win._active
    assert not win._armed  # one-shot either way
    if was_active:
        assert len(fences) == 2  # exit fenced too
    win.close()  # idempotent after the window closed itself

    # resume shifts the window past the restart's own warmup
    w2 = ProfilerWindow("5-6", str(tmp_path))
    w2.resume_at(10)
    assert w2.start == 10 + MIN_WARMUP_STEPS
    assert w2.stop_at == w2.start + 2
    # an empty logdir never arms
    w3 = ProfilerWindow("3-4", "")
    w3.step(3)
    assert not w3._active and not w3._armed


# ---------------------------------------------------------------------------
# e2e: the plain loop + hosted clientstore — ids on every plane, then the
# full report chain (write_run_report -> checker -> analyze_run CLI)
# ---------------------------------------------------------------------------

def test_hosted_trace_ids_and_run_report(tmp_path):
    import itertools

    from commefficient_tpu.train.runner import _sync_epoch_rounds
    from commefficient_tpu.utils.profiling import StepProfiler

    cfg = Config(**{**KW, **BASE}, client_store="host", telemetry_level=1)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    spans = PhaseSpans(str(tmp_path))
    sess.spans = spans
    rounds = _sync_epoch_rounds(cfg, sess, sampler, _lr_fn, spans,
                                StepProfiler(""), 0, 0,
                                sampler.steps_per_epoch())
    try:
        ms = [m for _s, _lr, m in itertools.islice(rounds, 6)]
    finally:
        rounds.close()
    assert sess.retrace_sentinel.retraces == 0
    sess.close_client_store()  # flush: writeback spans must be recorded
    path = spans.close()
    sess.spans = None

    # the lagged trace/* scalars ride every round's metrics with a
    # constant key set; the first two rounds are the zeros row
    keys = set(trace_scalar_keys())
    for m in ms:
        assert keys <= set(m)
    idle_ix = float(STAGES.index("idle"))
    assert ms[0]["trace/critical_stage"] == idle_ix
    assert all(ms[0][k] == 0.0 for k in keys
               if k.endswith("_exclusive_ms"))
    # round 2's metrics describe round 0 — real spans, nonzero wall
    assert sum(ms[2][k] for k in keys if k.endswith("_exclusive_ms")) > 0
    assert 0 <= int(ms[2]["trace/critical_stage"]) < len(STAGES)

    # v11 spans dump validates; every gather/writeback/dispatch span
    # carries the OWNING round's id (flush spans carry none by design)
    rec = _script("check_telemetry_schema").validate_spans(path)
    evs = [e for e in rec["traceEvents"] if e["ph"] == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("device_put", "clientstore_gather",
                 "clientstore_writeback", "round_dispatch"):
        group = by_name.get(name, [])
        assert group, f"no {name} spans recorded"
        for e in group:
            assert e["args"].get("trace_id") == \
                round_trace_id(e["args"]["step"]), \
                f"{name} span not stamped with its round's trace id"
    for e in by_name.get("clientstore_flush", []):
        assert "trace_id" not in e["args"]
    # the gather runs inside its round; writebacks cover every round
    assert sorted({e["args"]["step"]
                   for e in by_name["clientstore_gather"]}) == list(range(6))
    assert sorted({e["args"]["step"]
                   for e in by_name["clientstore_writeback"]}) == \
        list(range(6))

    # report chain: write -> checker invariants -> CLI
    out = write_run_report(str(tmp_path), generated_by="tests/test_trace")
    assert out and os.path.basename(out) == "run_report.json"
    rep = _script("check_telemetry_schema").validate_run_report(out)
    assert rep["rounds_analyzed"] == 6
    for r in rep["rounds"]:
        tot = sum(r["stages_ms"].values())
        assert tot <= r["wall_ms"] + max(1e-6, 1e-6 * r["wall_ms"])
    # the CLI re-derives the same report and ends stdout with the
    # machine-readable summary line (gate-script contract)
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = _script("analyze_run").main([str(tmp_path)])
    assert rc == 0
    summary = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert summary == {"kind": "analyze_run", "run_dirs": 1,
                       "reports": 1, "failures": []}


# ---------------------------------------------------------------------------
# e2e: async engine (C = 3) — cohort ids with round parents
# ---------------------------------------------------------------------------

def test_async_spans_carry_cohort_trace_ids(tmp_path):
    from commefficient_tpu.asyncfed import AsyncFederation

    cfg = Config(async_buffer=4, async_concurrency=3,
                 staleness_exponent=0.5, arrival_rate=2.0,
                 mode="uncompressed", telemetry_level=1, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    spans = PhaseSpans(str(tmp_path))
    sess.spans = spans
    eng = AsyncFederation(cfg, sess, sampler, _lr_fn, 6,
                          steps_per_epoch=6, spans=spans).start()
    try:
        ms = [m for _s, _lr, m in eng.epoch_rounds(0, 0)]
    finally:
        eng.close()
    path = spans.close()
    sess.spans = None
    assert len(ms) == 6 and sess.retrace_sentinel.retraces == 0

    rec = _script("check_telemetry_schema").validate_spans(path)
    evs = [e for e in rec["traceEvents"] if e["ph"] == "X"]
    launches = [e for e in evs if e["name"] == "async_launch"]
    assert len(launches) >= 2
    cohorts = set()
    for e in launches:
        tid, parent = e["args"]["trace_id"], e["args"]["parent"]
        # every launch is on the cohort's own trace, parented by the
        # server round (= launch version) that realized it
        assert tid.startswith("c") and step_of_trace_id(tid) is None
        assert parent == round_trace_id(int(parent[1:]))
        cohorts.add(tid)
    assert len(cohorts) == len(launches)  # each cohort launches once
    applies = [e for e in evs if e["name"] == "async_apply"]
    assert applies
    for e in applies:
        assert e["args"]["trace_id"] == round_trace_id(e["args"]["step"])
    resid = [e for e in evs if e["name"] == "async_buffer_residency"]
    assert resid, "retired cohorts must leave a residency span"
    for e in resid:
        assert e["args"]["trace_id"] in cohorts
        assert e["args"]["parent"].startswith("r")


# ---------------------------------------------------------------------------
# level-0 discipline: tracing never touches the traced program
# ---------------------------------------------------------------------------

def test_level0_hlo_byte_identical_with_tracing_armed():
    """Trace ids, spans, and the profiler window are host-side only: at
    telemetry level 0 the lowered round HLO is byte-identical between a
    bare session and one with a spans ring attached AND a
    --profile_rounds window configured."""
    import jax.numpy as jnp

    texts = {}
    for armed in (False, True):
        cfg = Config(mode="uncompressed", telemetry_level=0,
                     profile_rounds="3-4" if armed else "", **BASE)
        ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        if armed:
            sess.spans = PhaseSpans(".")
        sampler = FedSampler(ds, num_workers=cfg.num_workers,
                             local_batch_size=cfg.local_batch_size, seed=1)
        ids, batch = sampler.sample_round(0)
        texts[armed] = sess.round_fn.lower(
            sess.state, jnp.asarray(ids),
            {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.float32(0.2),
        ).as_text()
    assert texts[False] == texts[True]


def test_hosted_rung_switch_with_tracing_zero_retraces(tmp_path):
    """The PR 17 hosted-ladder pin, with the v11 tracing active: a rung
    switch under a hosted store with spans attached still reuses the
    prewarmed programs — zero retraces — and the gather/writeback spans
    keep their round ids across the switch."""
    from commefficient_tpu.control import build_controller

    cfg = Config(**BASE, mode="local_topk", error_type="local",
                 local_momentum=0.9, topk_method="threshold",
                 client_store="host", telemetry_level=1,
                 control_policy="fixed", control_schedule="0-1=0,2-=1",
                 ladder="k=30,15")
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctrl = build_controller(cfg, sess, num_rounds=4)
    ctrl.prewarm(sampler, 0.2)
    spans = PhaseSpans(str(tmp_path))
    sess.spans = spans
    for r in range(4):
        spans.step(r)
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.2)
    assert ctrl.switches == 1 and sess.active_rung == 1
    assert sess.retrace_sentinel.retraces == 0
    sess.close_client_store()
    path = spans.close()
    sess.spans = None
    with open(path) as f:
        evs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    stamped = [e for e in evs if e["name"] in
               ("clientstore_gather", "clientstore_writeback")]
    assert {e["args"]["step"] for e in stamped} == set(range(4))
    for e in stamped:
        assert e["args"]["trace_id"] == round_trace_id(e["args"]["step"])
