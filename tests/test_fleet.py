"""Elastic fleet (README "Elastic fleet") — worker join/leave as
first-class, zero-retrace width re-partitioning.

The load-bearing pins:

  * schedule — fleet events (``resize@W'``/``leave@n``/``join@n``/
    ``shrink@W'``) fold deterministically over the base width, are
    validated against the fixed device mesh at Config construction, and
    engines that cannot re-shape a round mid-run are refused there;
  * zero retrace — every realized width dispatches an AOT-prewarmed
    per-width round program: ``xla/retraces == 0`` across shrink AND
    grow transitions, at session level and through the REAL shared
    runner, and a width-W' round is bit-identical to a fresh session
    provisioned at W';
  * recovery — an UNSCHEDULED loss (``shrink@W'``) surfaces as
    ``FleetShrinkError`` and heals under ``--recover_policy retry`` into
    a run bit-identical to the SCHEDULED ``resize@W'`` twin — params,
    scalars, and the ledger's exact byte accounting;
  * gates — ``availability='always'`` with no fleet events constructs
    NOTHING new (empty width tables), preserving golden parity.

Multi-host satellites (topology width re-split, coordinator connect
retry) are pinned here too; the staleness-aware control loop lives in
tests/test_control.py.
"""

import importlib.util
import json
import os

import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.data import FedDataset, FedSampler
from commefficient_tpu.fedsim import parse_chaos
from commefficient_tpu.fedsim.env import FedEnvironment
from commefficient_tpu.fedsim.faults import (
    fleet_shrink_at,
    fleet_transitions,
    fleet_width_at,
    fleet_widths,
    validate_chaos_rounds,
)
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.telemetry.flight import FleetShrinkError
from commefficient_tpu.utils.checkpoint import FedCheckpointer
from commefficient_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# schedule: grammar + fold + validation
# ---------------------------------------------------------------------------

def test_fleet_events_fold_in_start_order():
    plan = parse_chaos("resize@4:rounds=3-5")
    assert [fleet_width_at(plan, 8, r) for r in range(7)] == [
        8, 8, 8, 4, 4, 4, 8]
    assert fleet_transitions(plan, 8) == ((3, 4), (6, 8))
    assert fleet_widths(plan, 8) == (8, 4)
    # deltas compose relative to the width in effect as each window opens
    plan = parse_chaos("leave@4:rounds=2-,join@2:rounds=6-")
    assert [fleet_width_at(plan, 8, r) for r in (0, 2, 5, 6, 9)] == [
        8, 4, 4, 6, 6]
    assert fleet_transitions(plan, 8) == ((2, 4), (6, 6))
    assert fleet_widths(plan, 8) == (8, 4, 6)
    # shrink surfaces only at the round its window OPENS — replays and
    # later in-window rounds run the width quietly
    plan = parse_chaos("shrink@4:rounds=5-")
    assert fleet_shrink_at(plan, 5) == 4
    assert fleet_shrink_at(plan, 6) is None
    assert fleet_width_at(plan, 8, 7) == 4


def test_open_ended_fleet_window_validated_against_run_length():
    validate_chaos_rounds(parse_chaos("resize@4:rounds=3-"), 9)
    with pytest.raises(ValueError, match="only 9 rounds"):
        validate_chaos_rounds(parse_chaos("resize@4:rounds=12-"), 9)


@pytest.mark.parametrize("bad", [
    "resize@0:rounds=3-", "resize@2.5:rounds=3-", "join@0",
])
def test_fleet_grammar_rejects_non_positive_widths(bad):
    with pytest.raises(ValueError, match="positive integer worker count"):
        parse_chaos(bad)


_FLEET_KW = dict(mode="uncompressed", num_clients=16, num_workers=8,
                 num_devices=4, local_batch_size=4, seed=5)


@pytest.mark.parametrize("kw,match", [
    # realized widths must shard the FIXED mesh and stay provisioned
    (dict(chaos="resize@6:rounds=3-"), r"not a multiple of num_devices"),
    (dict(chaos="join@4:rounds=3-"), r"provisioned maximum"),
    (dict(chaos="leave@8:rounds=3-"), r">= 1"),
    # engines that cannot re-shape a round mid-run
    (dict(chaos="resize@4:rounds=3-", async_buffer=4,
          async_concurrency=2), r"async_buffer"),
    (dict(chaos="resize@4:rounds=3-", fsdp=True), r"fsdp"),
    # shrink models a LOSS: needs the recovery path, a round to roll
    # back over, and a width strictly below the one in effect
    (dict(chaos="shrink@4:rounds=5-"), r"recover_policy"),
    (dict(chaos="shrink@4:rounds=0-", recover_policy="retry",
          telemetry_level=1), r"round >= 1"),
    (dict(chaos="shrink@8:rounds=5-", recover_policy="retry",
          telemetry_level=1), r"strictly below"),
])
def test_config_rejects_bad_fleet_plans(kw, match):
    with pytest.raises(ValueError, match=match):
        Config(**{**_FLEET_KW, **kw})


def test_fleet_disabled_constructs_nothing():
    """The construction gate golden parity rides on: no fleet events —
    even with OTHER chaos scheduled — builds zero width programs, and
    the fleet dispatch state stays at the base width."""
    for kw in (dict(), dict(chaos="dropout@0.3:rounds=2-4",
                            telemetry_level=1)):
        cfg = Config(**{**_FLEET_KW, **kw})
        assert not cfg.fleet_enabled
        _ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        assert all(not r.width_fns and not r.width_idx_fns
                   for r in sess.rungs)
        assert sess._fleet_width == cfg.num_workers
        assert sess._fleet_resize_ms == 0.0


def test_env_width_schedule_and_stats():
    env = FedEnvironment(Config(**_FLEET_KW, chaos="resize@4:rounds=3-5"))
    assert env.has_fleet
    assert env.widths() == (8, 4)
    assert env.transitions == ((3, 4), (6, 8))
    assert env.shrink_at(3) is None
    for r, (w, n, last) in enumerate([(8, 0, -1), (8, 0, -1), (8, 0, -1),
                                      (4, 1, 3), (4, 1, 3), (4, 1, 3),
                                      (8, 2, 6)]):
        assert env.fleet_stats(r) == {
            "fleet/width": float(w), "fleet/resizes": float(n),
            "fleet/last_resize_round": float(last)}, r
    # and the fleet/* scalars ride round_env's stats dict
    assert env.round_env(3).stats["fleet/width"] == 4.0
    # fleet-less env: empty stats, constant base width
    env0 = FedEnvironment(Config(**_FLEET_KW, chaos="dropout@0.2"))
    assert not env0.has_fleet and env0.fleet_stats(0) == {}
    assert env0.width_at(5) == 8 and env0.widths() == (8,)


# ---------------------------------------------------------------------------
# session: per-width programs, zero-retrace dispatch, parity
# ---------------------------------------------------------------------------

def _session_inputs(cfg, n=None):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    return sess, sampler


def test_resized_round_matches_fresh_session_at_new_width():
    """Width parity: a round dispatched through the width ladder at W'=4
    is bit-identical to one from a session PROVISIONED at num_workers=4
    — the re-partitioned program is the real program, not an
    approximation of it."""
    cfg8 = Config(**{**_FLEET_KW, "chaos": "resize@4:rounds=0-"})
    # dropout@0.0 keeps session B on the fedsim-masked round path (all
    # slots live, like A) without scheduling any fleet event
    cfg4 = Config(**{**_FLEET_KW, "num_workers": 4,
                     "chaos": "dropout@0.0:rounds=0-0"})
    sess8, sampler = _session_inputs(cfg8)
    sess4, _ = _session_inputs(cfg4)
    ids, batch = sampler.sample_round(0)
    m8 = sess8.train_round(ids, batch, 0.3)  # slices to the 4 live rows
    m4 = sess4.train_round(np.asarray(ids)[:4],
                           {k: v[:4] for k, v in batch.items()}, 0.3)
    assert float(m8["loss"]) == float(m4["loss"])
    assert m8["fleet/width"] == 4.0
    np.testing.assert_array_equal(np.asarray(sess8.state.params_vec),
                                  np.asarray(sess4.state.params_vec))


def test_session_resize_zero_retraces_and_scalars():
    """The tentpole claim at session level: 8 -> 4 -> 8 through prewarmed
    width programs with the retrace sentinel pinned at EXACTLY zero, the
    schedule-derived fleet/* scalars riding every round, and the swap
    cost accumulating on the host gauge."""
    cfg = Config(mode="true_topk", error_type="virtual",
                 virtual_momentum=0.9, k=40, topk_method="threshold",
                 telemetry_level=1,
                 **{k: v for k, v in BASE.items() if k != "num_devices"},
                 num_devices=4, chaos="resize@4:rounds=3-5")
    sess, sampler = _session_inputs(cfg)
    assert sess.fedsim_env.widths() == (8, 4)
    assert all(4 in r.width_fns for r in sess.rungs)
    n = sess.prewarm_from_sampler(sampler, 0.3)
    assert n == 2  # (1 rung) x (base + width-4) programs
    widths, losses = [], []
    for r in range(8):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.3)
        losses.append(float(m["loss"]))
        widths.append(m["fleet/width"])
        assert m["xla/retraces"] == 0.0, f"retraced at round {r}"
        assert m["fleet/shrink_recoveries"] == 0.0
    assert widths == [8.0, 8.0, 8.0, 4.0, 4.0, 4.0, 8.0, 8.0]
    assert np.all(np.isfinite(losses))
    assert sess.retrace_sentinel.retraces == 0
    assert m["fleet/resizes"] == 2.0
    assert m["fleet/last_resize_round"] == 6.0
    assert sess._fleet_resize_ms > 0.0  # two dispatch-table swaps


def test_unprewarmed_shrink_raises_fleet_shrink_error():
    """The unscheduled-loss surface: a shrink window opening is an
    exception on the round's FIRST execution (typed with the old and new
    widths for the manager), and a DivergenceError subclass so every
    existing recovery plumbing catches it."""
    from commefficient_tpu.telemetry import DivergenceError

    cfg = Config(mode="true_topk", error_type="virtual",
                 virtual_momentum=0.9, k=40, topk_method="threshold",
                 telemetry_level=1, recover_policy="retry",
                 **{k: v for k, v in BASE.items() if k != "num_devices"},
                 num_devices=4, chaos="shrink@4:rounds=2-")
    sess, sampler = _session_inputs(cfg)
    for r in range(2):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.3)
    ids, batch = sampler.sample_round(2)
    with pytest.raises(DivergenceError) as ei:
        sess.train_round(ids, batch, 0.3)
    exc = ei.value
    assert isinstance(exc, FleetShrinkError)
    assert exc.step == 2 and exc.fleet_width == 4 and exc.prev_width == 8
    # the raise marked the round executed: a rollback replay runs the
    # shrunk width QUIETLY (transient-fault semantics, like nan_client)
    m = sess.train_round(ids, batch, 0.3)
    assert m["fleet/width"] == 4.0


# ---------------------------------------------------------------------------
# the shared runner at TinyMLP scale (acceptance twins)
# ---------------------------------------------------------------------------

_RUNNER_BASE = dict(
    mode="true_topk", error_type="virtual", virtual_momentum=0.9, k=40,
    topk_method="threshold", telemetry_level=1, perf_audit=False,
    num_epochs=1, pivot_epoch=1, lr_scale=0.1, num_devices=4,
)


def _run_loop(tmp_path, tag, ckpt_kw=None, **kw):
    """One TinyMLP run through the REAL shared runner (cv_train's
    train_loop adapter). 9 rounds (600 samples / (8 workers x 8 batch));
    availability stays 'always' so the realized fleet width is the only
    participation signal and the ledger arithmetic is exact."""
    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.logging import MetricsWriter

    base = {**BASE, "local_batch_size": 8, "num_devices": 4}
    cfg = Config(**{**base, **_RUNNER_BASE, **(ckpt_kw or {}), **kw})
    ds, params, loss_fn = _setup(cfg.num_clients)
    test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                         1, seed=0)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    run_dir = str(tmp_path / f"run{tag}")
    writer = MetricsWriter(run_dir, cfg=cfg)
    ck = FedCheckpointer(cfg)
    try:
        val = train_loop(cfg, sess, sampler, test_ds, writer,
                         eval_batch_size=32, checkpointer=ck)
    finally:
        ck.close()
        writer.close()
    return sess, run_dir, val


def _scalars(run_dir, exclude=("resilience/", "trace/",
                               "fleet/shrink_recoveries",
                               "xla/exposed_collective_ms")):
    """(name, value, step) deduped to the LAST occurrence per (name,
    step) — replayed rounds keep the healed values (the determinism
    contract tests/test_resilience.py documents)."""
    rows = {}
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "name" not in rec or rec["name"].startswith(exclude):
                continue
            rows[(rec["name"], rec["step"])] = (
                rec["name"], rec["value"], rec["step"])
    return list(rows.values())


def _series(run_dir, name):
    return [v for n, v, _s in sorted(_scalars(run_dir, exclude=()),
                                     key=lambda t: t[2]) if n == name]


def test_runner_resize_e2e_zero_retraces_schema_v13(tmp_path):
    """Acceptance: the scheduled resize through the REAL runner — the
    width walks 8 -> 4 -> 8 on schedule, every round reports zero
    retraces, and the full artifact set validates under schema v13."""
    sess, run_dir, val = _run_loop(tmp_path, "_resize",
                                   chaos="resize@4:rounds=3-5")
    assert val and np.isfinite(val["loss"])
    assert _series(run_dir, "fleet/width") == [
        8.0, 8.0, 8.0, 4.0, 4.0, 4.0, 8.0, 8.0, 8.0]
    assert _series(run_dir, "fleet/resizes")[-1] == 2.0
    assert set(_series(run_dir, "xla/retraces")) == {0.0}
    assert sess.retrace_sentinel.retraces == 0
    _checker().validate_run_dir(run_dir)
    # the ledger billed each round at its REALIZED width
    ledger = json.loads(open(
        os.path.join(run_dir, "comm_ledger.json")).read())
    assert ledger["live_client_rounds"] == 6 * 8 + 3 * 4


def test_shrink_recovery_retry_matches_scheduled_resize(tmp_path):
    """Acceptance: an UNSCHEDULED shrink healed under retry is
    bit-identical to the SCHEDULED resize twin — final params, deduped
    scalars, and the ledger byte-for-byte (replayed rounds bill once)."""
    sess_a, run_a, _ = _run_loop(tmp_path, "_sched",
                                 chaos="resize@4:rounds=5-")
    sess_b, run_b, _ = _run_loop(tmp_path, "_shrink",
                                 chaos="shrink@4:rounds=5-",
                                 recover_policy="retry", snapshot_every=4)
    np.testing.assert_array_equal(np.asarray(sess_b.state.params_vec),
                                  np.asarray(sess_a.state.params_vec))
    assert sorted(_scalars(run_b)) == sorted(_scalars(run_a))
    assert _series(run_b, "resilience/recoveries")[-1] == 1.0
    assert _series(run_b, "fleet/shrink_recoveries")[-1] == 1.0
    assert sess_b._fleet_shrink_recoveries == 1
    assert sess_b.retrace_sentinel.retraces == 0
    la = json.loads(open(os.path.join(run_a, "comm_ledger.json")).read())
    lb = json.loads(open(os.path.join(run_b, "comm_ledger.json")).read())
    assert lb == la  # the rollback rewound the accounting exactly
    assert lb["live_client_rounds"] == 5 * 8 + 4 * 4
    _checker().validate_run_dir(run_b)
    # the recovery history names the shrunk width
    rec = json.loads(open(
        os.path.join(run_b, "flight_5_recovery.json")).read())
    hist = rec["recovery_history"]
    assert len(hist) == 1 and hist[0]["outcome"] == "recovered"
    assert hist[0]["fleet_width"] == 4


@pytest.mark.slow  # r20 tier budget: secondary composition (preempt x resize);
# restore-at-width is tier-1 via the shrink-recovery rollback twin and the
# runner e2e width series
def test_preempt_resume_lands_inside_resize_window(tmp_path):
    """Checkpoint kill/resume across a resize: a preemption INSIDE the
    shrunk window force-saves, and --resume re-enters at the restored
    round's realized width (4, not the base 8) purely from the round
    clock — the width schedule has no runtime state to lose. The resumed
    run reproduces the uninterrupted twin bit-exactly, still at zero
    retraces."""
    from commefficient_tpu.resilience import PreemptShutdown

    sess_base, _run, _ = _run_loop(tmp_path, "_unint",
                                   chaos="resize@4:rounds=3-5")
    ckpt_dir = str(tmp_path / "ckpt")
    with pytest.raises(PreemptShutdown) as ei:
        _run_loop(tmp_path, "_pre", chaos="resize@4:rounds=3-5,preempt@4",
                  ckpt_kw=dict(checkpoint_dir=ckpt_dir,
                               checkpoint_every=100))
    assert ei.value.step == 5 and ei.value.saved
    sess, run_dir, _ = _run_loop(
        tmp_path, "_res", chaos="resize@4:rounds=3-5,preempt@4",
        resume=True,
        ckpt_kw=dict(checkpoint_dir=ckpt_dir, checkpoint_every=100))
    assert sess._fleet_width == 8  # grew back on schedule after round 5
    assert _series(run_dir, "fleet/width") == [4.0, 8.0, 8.0, 8.0]
    assert sess.retrace_sentinel.retraces == 0
    np.testing.assert_array_equal(np.asarray(sess.state.params_vec),
                                  np.asarray(sess_base.state.params_vec))


# ---------------------------------------------------------------------------
# multi-host satellites: width re-split + coordinator connect retry
# ---------------------------------------------------------------------------

def test_host_topology_at_width():
    from commefficient_tpu.multihost import HostTopology

    topo = HostTopology(num_hosts=2, host_id=1, num_workers=8,
                        num_clients=100, chips_per_host=4,
                        slot_range=(4, 8), client_range=(50, 100))
    narrowed = topo.at_width(4)
    assert narrowed.slot_range == (2, 4)
    assert narrowed.workers_per_host == 2
    # chip + client ownership untouched: the mesh never resizes
    assert narrowed.chips_per_host == 4
    assert narrowed.client_range == (50, 100)
    assert topo.at_width(8) is topo  # base width: no new object
    with pytest.raises(ValueError):
        topo.at_width(5)  # must split host-major over 2 hosts


def test_initialize_multihost_retries_then_succeeds(monkeypatch):
    from commefficient_tpu.multihost import bringup

    calls, naps = [], []
    monkeypatch.setattr(bringup.time, "sleep", naps.append)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: connection refused")
        return True

    monkeypatch.setattr(bringup, "initialize_distributed", flaky)
    assert bringup._connect_with_retry(Config()) is True
    assert len(calls) == 3
    assert naps == [1.0, 2.0]  # backoff doubles from 1s


def test_initialize_multihost_exhausted_names_coordinator(monkeypatch):
    from commefficient_tpu.multihost import bringup

    monkeypatch.setattr(bringup.time, "sleep", lambda _s: None)
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.7:8476")

    def dead():
        raise RuntimeError("UNAVAILABLE: connection refused")

    monkeypatch.setattr(bringup, "initialize_distributed", dead)
    with pytest.raises(RuntimeError, match="10.0.0.7:8476") as ei:
        bringup._connect_with_retry(
            Config(distributed_connect_retries=2))
    msg = str(ei.value)
    assert "2 attempt(s)" in msg and "connection refused" in msg
    assert isinstance(ei.value.__cause__, RuntimeError)
    # the knob is a TOTAL attempt budget, so < 1 is rejected up front
    with pytest.raises(ValueError, match="distributed_connect_retries"):
        Config(distributed_connect_retries=0)
    # and the retry loop floors duck-typed configs at one dial
    calls = []
    monkeypatch.setattr(bringup, "initialize_distributed",
                        lambda: calls.append(1) or True)

    class _Cfg:
        distributed_connect_retries = 0

    assert bringup._connect_with_retry(_Cfg())
    assert len(calls) == 1


def test_ledger_bills_at_realized_width():
    from commefficient_tpu.telemetry import CommLedger

    bpr = {"upload_floats": 20, "download_floats": 100,
           "upload_bytes": 80, "download_bytes": 400}
    led = CommLedger(bpr, mode="uncompressed", num_workers=8,
                     masked=True)
    led.on_round(0, {"fleet/width": 8.0,
                     "fedsim/participation_rate": 1.0,
                     "fedsim/dropped": 0.0})
    led.on_round(1, {"fleet/width": 4.0,
                     "fedsim/participation_rate": 1.0,
                     "fedsim/dropped": 0.0})
    assert led.live_client_rounds == 12
    assert led.cum_up_bytes == 12 * 80
    # the fedsim rates are RELATIVE to the realized width
    led.on_round(2, {"fleet/width": 4.0,
                     "fedsim/participation_rate": 0.5,
                     "fedsim/dropped": 2.0})
    assert led.live_client_rounds == 14
