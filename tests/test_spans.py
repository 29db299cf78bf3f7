"""The program's host spans on the profiler's clock.

At every telemetry level the session's phases are ``fed/<name>``
annotations in whatever ``jax.profiler`` trace is open, under one
``fed/round`` step a round; the recorder (ring, fencing window,
``spans_<step>.json``) stays a level >= 1 thing.
"""

import glob
import os

import jax
import pytest
from test_device_data import _mlp_loss, _toy_ds, augment_batch

from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession, make_mesh
from commefficient_tpu.telemetry.spans import PhaseSpans, span_of, wrap_iter
from commefficient_tpu.train import runner
from commefficient_tpu.utils.config import Config

PHASES = ["fed/device_put", "fed/fedsim_env", "fed/round_dispatch"]


def _session(level):
    cfg = Config(mode="uncompressed", num_clients=16, num_workers=8,
                 num_devices=1, local_batch_size=4, seed=1,
                 telemetry_level=level)
    params, loss_fn = _mlp_loss()
    ds = _toy_ds(num_clients=16)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1,
                         augment=augment_batch)
    session.attach_data(ds.data, augment_batch)
    return cfg, session, sampler


def _traced(tmp_path, body):
    """Run ``body`` under a profiler trace; the ``fed/*`` events of the host
    plane as ``[(name, start_ns, end_ns, stats)]`` in start order."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats))
                           for e in line.events if e.name.startswith("fed/")]
    return sorted(events, key=lambda e: e[1])


def _rounds(session, sampler, first, count):
    def body():
        for r in range(first, first + count):
            metrics = session.train_round_indices(
                *sampler.sample_round_indices(r), 0.1)
        jax.block_until_ready(metrics["loss"])

    return body


def test_level_0_rounds_are_steps_with_their_phases_in_the_trace(tmp_path):
    _cfg, session, sampler = _session(0)
    _rounds(session, sampler, 0, 2)()  # compile outside the trace
    events = _traced(tmp_path / "trace", _rounds(session, sampler, 2, 3))
    steps = [e for e in events if e[0] == "fed/round"]
    assert [e[3]["step_num"] for e in steps] == [2, 3, 4]
    for name, lo, hi, stats in steps:
        inside = [e for e in events
                  if e[0] != "fed/round" and lo <= e[1] and e[2] <= hi]
        assert [e[0] for e in inside] == PHASES  # one of each, in order
        assert all(e[3]["round"] == stats["step_num"] for e in inside)
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    assert len(events) == 3 * (1 + len(PHASES))  # nothing outside a step
    # the recorder is still a level >= 1 thing
    assert session.spans is None
    assert not glob.glob(os.path.join(str(tmp_path), "**", "spans_*.json"),
                         recursive=True)


def test_level_1_ring_and_dump_are_as_before_and_annotated_too(tmp_path):
    _cfg, session, sampler = _session(1)
    spans = session.spans = PhaseSpans(str(tmp_path / "run"), start_step=2,
                                       num_steps=2)
    _rounds(session, sampler, 0, 1)()

    def body():
        for r in range(1, 4):
            spans.step(r)
            session.train_round_indices(*sampler.sample_round_indices(r), 0.1)

    events = _traced(tmp_path / "trace", body)
    recorded = [e for e in spans.events if e["args"]["step"] >= 1]
    assert [e["name"] for e in recorded] == [p[4:] for p in PHASES] * 3
    assert [e["args"]["trace_id"] for e in recorded[::3]] == ["r1", "r2", "r3"]
    # the dispatch span fences inside the window [2, 4) only
    assert [e["args"]["fenced"] for e in recorded[2::3]] == [False, True, True]
    assert [e[0] for e in events if e[0] != "fed/round"] == PHASES * 3
    assert [e[3]["round"] for e in events if e[0] == PHASES[0]] == [1, 2, 3]
    path = spans.close()
    assert os.path.basename(path) == "spans_1.json"


def test_the_runner_annotates_the_data_wait_when_handed_no_recorder(tmp_path):
    cfg, session, sampler = _session(0)
    spe = sampler.steps_per_epoch()

    class _NoProfiler:
        @staticmethod
        def step(_s):
            pass

    def body():
        source = runner._sync_epoch_rounds(
            cfg, session, sampler, lambda _s: 0.1, None, _NoProfiler, 0, 0,
            spe)
        for _ in range(3):
            _s, _lr, metrics = next(source)
        source.close()
        jax.block_until_ready(metrics["loss"])

    events = _traced(tmp_path, body)
    names = [e[0] for e in events]
    assert names.count("fed/data_load") == 3
    # each wait ends before the round it fed begins
    for wait, step in zip([e for e in events if e[0] == "fed/data_load"],
                          [e for e in events if e[0] == "fed/round"]):
        assert wait[2] <= step[1]


@pytest.mark.parametrize("enabled", [True, False])
def test_a_recorders_span_is_annotated_whether_it_records_or_not(
        tmp_path, enabled):
    spans = PhaseSpans(str(tmp_path / "run") if enabled else "")

    def body():
        spans.step(7)
        with spans.span("checkpoint") as handle:
            assert (handle is not None) == enabled
        with spans.span("prefetch_stage", step=9):
            pass

    events = _traced(tmp_path / "trace", body)
    assert [(e[0], e[3]["round"]) for e in events] == [
        ("fed/checkpoint", 7), ("fed/prefetch_stage", 9)]
    assert len(spans.events) == (2 if enabled else 0)


@pytest.mark.parametrize("recorder", [True, False])
def test_every_optional_span_site_opens_one_shape(tmp_path, recorder):
    """``span_of`` is what the session, the engines and the runner open: the
    recorder's span where one is attached, the bare annotation where not;
    ``wrap_iter`` puts each ``next()`` of a round source inside it."""
    spans = PhaseSpans(str(tmp_path / "run")) if recorder else None

    def body():
        with span_of(spans, "prefetch_stage", 4, trace_id="r4") as handle:
            assert (handle is not None) == recorder
        with span_of(spans, "checkpoint"):
            pass
        source = spans.wrap_iter([7, 8]) if recorder else wrap_iter([7, 8])
        assert list(source) == [7, 8]

    events = _traced(tmp_path / "trace", body)
    # three waits: two items and the StopIteration
    assert [e[0] for e in events] == [
        "fed/prefetch_stage", "fed/checkpoint"] + ["fed/data_load"] * 3
    assert events[0][3]["round"] == 4
    if recorder:
        assert [e["name"] for e in spans.events] == [
            "prefetch_stage", "checkpoint"] + ["data_load"] * 3
        assert spans.events[0]["args"]["trace_id"] == "r4"
