"""The per-layer metrics that read the round's scopes and the program's host
spans, on a hand-made trace (``recorded_trace_scopes.json`` beside this
file, in the plain form ``benchmark/reduce.py`` reduces), against values
worked out by hand: two traced rounds, [1.0, 6.0] and [6.0, 11.0].
"""

import json
import os
import time

import jax
import pytest

from benchmark import kernel_bytes, reduce, run
from benchmark.layers import program_host_spans
from commefficient_tpu.telemetry.trace import ROUND_SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = [name for name, _ in ROUND_SCOPES]
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
NEW = {
    "loop.device_gather_s_per_round", "model.fwd_bwd_s_per_round",
    "model.bwd_s_per_round", "round.clip_sum_s_per_round",
    "round.apply_s_per_round", "round.unscoped_s_per_round",
    "compress.encode_s_per_round", "compress.estimate_s_per_round",
    "compress.topk_s_per_round", "compress.resketch_s_per_round",
    "compress.encode_hbm_share", "compress.estimate_hbm_share",
    "compress.topk_hbm_share", "session.stage_s_per_round",
    "session.enqueue_s_per_round",
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace_scopes.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ctx(recorded):
    traced = reduce.summarize(recorded, chips=1)
    traced.update(rounds=2)
    assert (traced["lo"], traced["hi"]) == (1.0, 11.0)
    # d = 1000 coordinates into a 2 x 250 table, and a chip that moves 60 kB/s
    return {"traced": traced, "chips": 1, "values": {},
            "peaks": {"hbm_bytes_per_s": 60000.0}, "config": {"n_params": 1000},
            "cell": {"name": "hand_made", "traffic_file": {
                "reference": {"rows": 2, "cols": 250, "k": 10, "rho": 0.9}}}}


@pytest.mark.parametrize("metric,expected", [
    # 0.2 in each round
    ("loop.device_gather_s_per_round", 0.2),
    # round one: forward 0.5 + backward 0.8 + the concat 0.1; round two 0.5 + 0.6
    ("model.fwd_bwd_s_per_round", 1.25),
    # the ops wrapped by transpose( alone: 0.8 and 0.6
    ("model.bwd_s_per_round", 0.7),
    # client_clip 0.2 + client_sum 0.1, in each round
    ("round.clip_sum_s_per_round", 0.3),
    ("round.apply_s_per_round", 0.1),
    # round one: two body ops of 0.25 (the loop op around them has no scope and
    # the op under ``client_encode`` is not the scope); round two: 0.5
    ("compress.encode_s_per_round", 0.5),
    ("compress.estimate_s_per_round", 0.4),
    ("compress.topk_s_per_round", 0.3),
    ("compress.resketch_s_per_round", 0.2),
    # round one only: the op under client_encode 0.05, the 0.1 of the bare loop
    # op that its body does not cover, the bare copy 0.2
    ("round.unscoped_s_per_round", 0.175),
    # least bytes 4*1000 + 4*500 = 6000 -> 0.1 s of the 0.5 s under encode
    ("compress.encode_hbm_share", 20.0),
    # 6000 bytes again -> 0.1 s of 0.4 s
    ("compress.estimate_hbm_share", 25.0),
    # 4*1000 bytes -> 0.0667 s of 0.3 s
    ("compress.topk_hbm_share", 100 * (4000 / 60000.0) / 0.3),
])
def test_scope_metrics(ctx, metric, expected):
    assert reduce.read_metric(metric, ctx) == pytest.approx(expected)


def test_the_split_closes_on_the_busy_time(ctx):
    """gather + fwd/bwd + clip/sum + encode + decode + apply + unscoped, plus
    what ran under client_transmit (nothing) and aggregate_tail (0.05 a
    round), is all the time the device was busy: 7.1 s over two rounds."""
    read = lambda name: reduce.read_metric(name, ctx)  # noqa: E731
    devs = reduce.device_ops(ctx["traced"]["trace"], 1)
    rest = sum(reduce.busy_union(o, 1.0, 11.0, "client_transmit|aggregate_tail")
               for o in devs.values()) / 2
    assert rest == pytest.approx(0.05)
    parts = ["loop.device_gather_s_per_round", "model.fwd_bwd_s_per_round",
             "round.clip_sum_s_per_round", "compress.encode_s_per_round",
             "compress.decode_s_per_round", "round.apply_s_per_round",
             "round.unscoped_s_per_round"]
    assert read("compress.decode_s_per_round") == pytest.approx(0.975)
    assert read("round.device_busy_s_per_round") == pytest.approx(3.55)
    assert sum(read(p) for p in parts) + rest == pytest.approx(3.55)


@pytest.mark.parametrize("metric,expected", [
    ("compress.encode_hbm_share", 200.0),
    ("compress.estimate_hbm_share", 250.0),
    ("compress.topk_hbm_share", 100 * (4000 / 6000.0) / 0.3),
])
def test_a_share_over_100_is_reported_as_it_reads(ctx, metric, expected):
    """A chip ten times slower puts the bound's own time (1.0 s, 1.0 s,
    0.667 s) over the scope's: the scope cannot cover the work. The reader
    neither cuts the share off at 100 nor leaves it out, so that the fault
    shows where the line is checked."""
    slow = dict(ctx, peaks={"hbm_bytes_per_s": 6000.0})
    assert reduce.read_metric(metric, slow) == pytest.approx(expected)
    line = reduce.per_layer([{"name": metric, "unit": "%"}], slow)
    assert line[metric]["value"] == pytest.approx(expected)
    # nothing without a chip's peaks (the CPU rehearsal) or without a trace
    assert reduce.read_metric(metric, dict(ctx, peaks=None)) is None
    assert reduce.read_metric(metric, dict(ctx, traced=None)) is None


def test_least_bytes_are_functions_of_the_work_alone():
    work = {"d": 124_444_417, "rows": 5, "cols": 5_000_000, "k": 50_000, "rho": 0.9}
    assert kernel_bytes.encode_bytes(**work) == 4 * 124_444_417 + 4 * 25_000_000
    assert kernel_bytes.estimate_bytes(**work) == kernel_bytes.encode_bytes(**work)
    assert kernel_bytes.topk_bytes(**work) == 497_777_668  # 0.61 ms at 819 GB/s


def test_a_program_without_the_scopes_reports_none_of_them(ctx, recorded):
    """The parent of the PR that brought the scopes: every scope metric but
    estimate_all's (an older marker) is left out, nothing raises."""
    old = {"server_decode_dense", "estimate_all", "flat_grad_concat"}
    bare = [[n, "/".join(p for p in sc.split("/")
                         if not any(s in p for s in NAMES) or any(s in p for s in old)),
             s, d] for n, sc, s, d in recorded["devices"]["0"]]
    parent = dict(ctx, traced=dict(ctx["traced"], trace={
        "devices": {"0": bare}, "host": recorded["host"]}))
    got = reduce.per_layer([{"name": m, "unit": "x"} for m in sorted(NEW)], parent)
    assert set(got) == {"compress.estimate_s_per_round", "compress.estimate_hbm_share",
                        "round.unscoped_s_per_round"}


# ---- the program's host spans -------------------------------------------------

@pytest.mark.parametrize("metric,expected", [
    # fed/device_put inside the window: 0.01 and 0.03 (one before it, one that
    # ends after it are left out), over two rounds
    ("session.stage_s_per_round", 0.02),
    ("session.enqueue_s_per_round", 0.15),
])
def test_host_span_seconds_per_round(recorded, metric, expected):
    spec = run.load_json(run.HERE, "layers", metric + ".json")
    got = program_host_spans.seconds_per_round(
        recorded["program_host"], spec["span"], 1.0, 11.0, 2)
    assert got == pytest.approx(expected)
    assert program_host_spans.seconds_per_round(
        recorded["program_host"], "fed/absent", 1.0, 11.0, 2) is None


def test_host_spans_are_read_from_the_trace_file(tmp_path, monkeypatch):
    """A real ``.xplane.pb``: two fed/device_put between the fences and one
    before them; read back on the clock the fences are on."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "cell_a"), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("fed/device_put", round=0):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench/fence"):
            pass
        for r in (1, 2):
            with jax.profiler.StepTraceAnnotation("fed/round", step_num=r):
                with jax.profiler.TraceAnnotation("fed/device_put", round=r):
                    time.sleep(0.01 * r)
        with jax.profiler.TraceAnnotation("bench/fence"):
            pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(program_host_spans, "TRACE_ROOT", str(tmp_path))
    path, = (tmp_path / "cell_a").glob("plugins/profile/*/*.xplane.pb")
    events = program_host_spans.fed_events(str(path))
    assert sorted(n for n, _s, _d in events) == ["fed/device_put"] * 3 + ["fed/round"] * 2
    lo, hi = reduce.window_of(reduce.load_xplane(str(path)))
    ctx = {"traced": {"lo": lo, "hi": hi, "rounds": 2}, "cell": {"name": "cell_a"}}
    got = reduce.read_metric("session.stage_s_per_round", ctx)
    assert 0.015 <= got <= 0.03  # (10 ms + 20 ms) / 2 rounds, and the sleeps' slack
    assert reduce.read_metric("session.enqueue_s_per_round", ctx) is None
    assert reduce.read_metric("session.stage_s_per_round",
                              dict(ctx, cell={"name": "cell_without_a_trace"})) is None
    assert reduce.read_metric("session.stage_s_per_round", dict(ctx, traced=None)) is None


# ---- the files against the one list of names -----------------------------------

def _names_in(pattern):
    return {p.replace("\\b", "") for p in pattern.split("|")}


def test_unscoped_pattern_is_every_name_of_the_vocabulary():
    spec = run.load_json(run.HERE, "layers", "round.unscoped_s_per_round.json")
    assert _names_in(spec["pattern"]) == set(NAMES)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_is_declared_as_the_issue_says(metric):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    spec = run.load_json(run.HERE, "layers", metric + ".json")
    assert entry["moves"] == "round_s"
    assert entry["better"] == ("higher" if metric.endswith("_share") else "lower")
    # the compress metrics read what only the sketch round traces
    assert entry.get("workloads") == (
        ["gpt2_sketch"] if metric.startswith("compress.") else None)
    if "pattern" in spec and metric != "model.bwd_s_per_round":
        assert _names_in(spec["pattern"]) <= set(NAMES)
    # appended: the twelve metrics the benchmark had come first, untouched
    assert BENCH["per_layer"].index(entry) >= 12
