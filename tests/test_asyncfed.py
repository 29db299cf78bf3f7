"""asyncfed/ acceptance: buffered-asynchronous federation (PR 15).

The load-bearing claim is the correctness anchor: ``async_buffer=W,
async_concurrency=1, staleness_exponent=0`` reduces BIT-IDENTICALLY to the
synchronous round — same params, same losses, across compression modes,
error modes, and fedsim masking. Everything else (overlap, staleness
discounting, snapshot replay, schedule invariants, config grammar) is
pinned around that anchor:

- AsyncSchedule: anchor degenerates to one-cohort-per-update in launch
  order; at K < W or C > 1 every (cohort, slot) is consumed exactly once,
  in canonical sorted order, with bounded concurrency; the event
  simulation is a pure function of (seed, W, K, C, rate).
- Engine: zero retraces at any concurrency (the launch/apply programs
  compile once per rung and every update re-enters the same signatures);
  snapshot_extra/restore_extra replays the in-flight buffer verbatim so a
  restart from a snapshot is bit-identical to the uninterrupted run.
- Telemetry: under C=1 the async ledger bills exactly the synchronous
  byte count (same rounds x bytes_per_round), and the perf report carries
  the v8 ``async`` block.
"""

import json
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.asyncfed import AsyncFederation, AsyncSchedule, cohort_delays
from commefficient_tpu.data import FedDataset, FedSampler
from commefficient_tpu.models.losses import classification_loss
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils.config import Config


class TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(16)(x))
        return nn.Dense(4)(x)


BASE = dict(num_clients=12, num_workers=8, num_devices=8, local_batch_size=4,
            weight_decay=0.0, seed=5)

MODE_CONFIGS = {
    "uncompressed": dict(mode="uncompressed"),
    "sketch": dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                   k=20, num_rows=3, num_cols=200),
    "true_topk": dict(mode="true_topk", error_type="virtual", k=20),
    "local_topk": dict(mode="local_topk", error_type="local", k=20,
                       local_momentum=0.9),
}

N_ROUNDS = 3


def _setup(num_clients=12, n=400):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 4))
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.normal(size=(n, 4)), axis=1).astype(np.int32)
    ds = FedDataset({"x": x, "y": y}, num_clients, iid=True, seed=0)
    model = TinyMLP()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8)))
    return ds, params, classification_loss(model.apply)


def _run_sync(cfg, num_rounds=N_ROUNDS, lr=0.3):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    losses = []
    for r in range(num_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, lr)
        losses.append(float(np.asarray(m["loss"])))
    return sess, losses


def _run_async(cfg, num_rounds=N_ROUNDS, lr=0.3):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    eng = AsyncFederation(cfg, sess, sampler, lambda s: lr, num_rounds,
                          steps_per_epoch=num_rounds).start()
    records = []
    try:
        for step, _lr, m in eng.epoch_rounds(0, 0):
            records.append((step, m))
    finally:
        eng.close()
    return sess, records, eng


def _anchor(extra):
    return Config(async_buffer=8, async_concurrency=1, staleness_exponent=0.0,
                  **extra, **BASE)


# ---------------------------------------------------------------------------
# AsyncSchedule: the host-side event simulation
# ---------------------------------------------------------------------------

def test_schedule_anchor_degenerates_to_sync_rounds():
    sch = AsyncSchedule(seed=5, num_workers=8, buffer_k=8, concurrency=1,
                        arrival_rate=1.0, num_updates=5)
    assert sch.num_cohorts == 5
    for u, spec in enumerate(sch.updates):
        assert spec.slots == tuple((u, s) for s in range(8))
        assert spec.staleness == (0,) * 8
        assert spec.launches_before == (u,)
        assert spec.buffer_fill_after == 0
    assert tuple(sch.launch_version) == tuple(range(5))
    assert sch.launched_before(3) == 3
    # the final update launches nothing new past itself
    assert sch.updates[-1].concurrent_after == 0


def test_schedule_rate_inf_is_instant_arrivals():
    d = cohort_delays(seed=5, cohort=2, num_workers=8, rate=math.inf)
    assert d.shape == (8,)
    assert np.all(d == 0.0)
    sch = AsyncSchedule(seed=5, num_workers=8, buffer_k=8, concurrency=1,
                        arrival_rate=math.inf, num_updates=4)
    for u, spec in enumerate(sch.updates):
        assert spec.slots == tuple((u, s) for s in range(8))
        assert spec.staleness == (0,) * 8


@pytest.mark.parametrize("k,c", [(5, 1), (4, 3), (8, 2)])
def test_schedule_consumes_every_slot_exactly_once(k, c):
    sch = AsyncSchedule(seed=5, num_workers=8, buffer_k=k, concurrency=c,
                        arrival_rate=2.0, num_updates=12)
    seen = set()
    for spec in sch.updates:
        assert len(spec.slots) == k
        assert list(spec.slots) == sorted(spec.slots), \
            "consumption order must be canonical (cohort, slot) sorted"
        for slot, st in zip(spec.slots, spec.staleness):
            assert slot not in seen, f"slot {slot} consumed twice"
            seen.add(slot)
            assert st >= 0
        assert 0 <= spec.concurrent_after <= c
        assert spec.buffer_fill_after >= 0
    # cohorts launch in order, versions are the update index at launch time
    launch_order = [cc for spec in sch.updates for cc in spec.launches_before]
    assert launch_order == sorted(launch_order)
    assert len(sch.launch_version) == sch.num_cohorts


def test_schedule_overlap_produces_staleness():
    sch = AsyncSchedule(seed=5, num_workers=8, buffer_k=4, concurrency=3,
                        arrival_rate=2.0, num_updates=10)
    stale = [st for spec in sch.updates for st in spec.staleness]
    assert max(stale) > 0, "C=3 overlap must produce stale contributions"


def test_schedule_is_deterministic():
    a = AsyncSchedule(seed=7, num_workers=8, buffer_k=3, concurrency=2,
                      arrival_rate=1.5, num_updates=9)
    b = AsyncSchedule(seed=7, num_workers=8, buffer_k=3, concurrency=2,
                      arrival_rate=1.5, num_updates=9)
    assert a.updates == b.updates
    assert tuple(a.launch_version) == tuple(b.launch_version)


@pytest.mark.parametrize("k", [0, 9])
def test_schedule_rejects_bad_buffer(k):
    with pytest.raises(ValueError, match="buffer_k"):
        AsyncSchedule(seed=5, num_workers=8, buffer_k=k, concurrency=1,
                      arrival_rate=1.0, num_updates=3)


def test_schedule_rejects_bad_concurrency():
    with pytest.raises(ValueError):
        AsyncSchedule(seed=5, num_workers=8, buffer_k=4, concurrency=0,
                      arrival_rate=1.0, num_updates=3)


# ---------------------------------------------------------------------------
# Config grammar
# ---------------------------------------------------------------------------

def test_config_async_rejections():
    with pytest.raises(ValueError, match="async_buffer"):
        Config(async_buffer=-1, **BASE)
    with pytest.raises(ValueError, match="num_workers"):
        Config(async_buffer=9, **BASE)
    with pytest.raises(ValueError, match="async_concurrency"):
        Config(async_buffer=4, async_concurrency=0, **BASE)
    with pytest.raises(ValueError, match="staleness_exponent"):
        Config(async_buffer=4, staleness_exponent=-0.5, **BASE)
    # knobs that silently do nothing without the engine are rejected
    with pytest.raises(ValueError, match="async_concurrency"):
        Config(async_concurrency=2, **BASE)
    with pytest.raises(ValueError, match="staleness_exponent"):
        Config(staleness_exponent=0.5, **BASE)
    # incompatible engines
    with pytest.raises(ValueError, match="fuse_clients|PER-CLIENT"):
        Config(async_buffer=4, fuse_clients=True, **BASE)
    assert Config(async_buffer=8, **BASE).asyncfed_enabled
    assert not Config(**BASE).asyncfed_enabled


# ---------------------------------------------------------------------------
# THE anchor: K=W, C=1, alpha=0 == the synchronous round, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", sorted(MODE_CONFIGS))
def test_anchor_bit_identical_to_sync(mode):
    extra = MODE_CONFIGS[mode]
    sync_sess, sync_losses = _run_sync(Config(**extra, **BASE))
    async_sess, records, eng = _run_async(_anchor(extra))
    async_losses = [float(np.asarray(m["loss"])) for _, m in records]
    assert async_losses == sync_losses, f"{mode}: losses diverge"
    assert np.array_equal(np.asarray(async_sess.state.params_vec),
                          np.asarray(sync_sess.state.params_vec)), \
        f"{mode}: params not bit-identical at the anchor"
    assert eng.stats()["updates"] == N_ROUNDS


def test_anchor_bit_identical_under_fedsim_masking():
    extra = dict(MODE_CONFIGS["sketch"], availability="bernoulli",
                 dropout_prob=0.4)
    sync_sess, sync_losses = _run_sync(Config(**extra, **BASE))
    async_sess, records, _ = _run_async(_anchor(extra))
    async_losses = [float(np.asarray(m["loss"])) for _, m in records]
    assert async_losses == sync_losses
    assert np.array_equal(np.asarray(async_sess.state.params_vec),
                          np.asarray(sync_sess.state.params_vec))
    # fedsim scalars still ride the metrics, plus the async/* block
    _, m0 = records[0]
    for key in ("fedsim/participation_rate", "async/staleness_mean",
                "async/buffer_fill", "async/concurrent_cohorts",
                "async/effective_participation"):
        assert key in m0, f"missing {key}"


# ---------------------------------------------------------------------------
# overlap: genuine async behaviour, still zero retraces
# ---------------------------------------------------------------------------

def test_overlap_runs_with_zero_retraces():
    cfg = Config(async_buffer=4, async_concurrency=3, staleness_exponent=0.5,
                 availability="poisson", arrival_rate=2.0, dropout_prob=0.2,
                 **MODE_CONFIGS["sketch"], **BASE)
    sess, records, eng = _run_async(cfg, num_rounds=8)
    assert len(records) == 8
    for _, m in records:
        assert np.isfinite(float(np.asarray(m["loss"])))
    assert sess.retrace_sentinel.retraces == 0, \
        "async engine must reuse ONE compiled launch/apply pair per rung"
    st = eng.stats()
    assert st["updates"] == 8
    # 8 updates x K=4 slots consume 4 full cohorts' worth; the in-flight
    # window keeps a couple more launched past the last fire
    assert st["cohorts_launched"] >= 4
    stale = [float(m["async/staleness_mean"]) for _, m in records]
    assert max(stale) > 0, "C=3 must surface stale contributions"
    conc = [int(m["async/concurrent_cohorts"]) for _, m in records]
    assert max(conc) >= 2 and min(conc) >= 0


def test_staleness_discount_changes_the_trajectory():
    """alpha is live: with overlap, discounting stale rows must change the
    params (guards against the weight silently collapsing to 1.0)."""
    base = dict(async_buffer=4, async_concurrency=3, arrival_rate=2.0,
                **MODE_CONFIGS["uncompressed"], **BASE)
    s0, _, _ = _run_async(Config(staleness_exponent=0.0, **base), num_rounds=6)
    s1, _, _ = _run_async(Config(staleness_exponent=1.0, **base), num_rounds=6)
    assert not np.array_equal(np.asarray(s0.state.params_vec),
                              np.asarray(s1.state.params_vec))


# ---------------------------------------------------------------------------
# snapshot / restore: in-flight buffer replays verbatim
# ---------------------------------------------------------------------------

def test_snapshot_restore_replays_bit_identically():
    cfg = Config(async_buffer=4, async_concurrency=2, staleness_exponent=0.5,
                 arrival_rate=2.0, **MODE_CONFIGS["uncompressed"], **BASE)
    n, cut = 6, 3

    # uninterrupted reference
    ref_sess, ref_records, _ = _run_async(cfg, num_rounds=n)
    ref_losses = [float(np.asarray(m["loss"])) for _, m in ref_records]

    # same run, but snapshot at `cut` and restart from the blob: the
    # restored pending outputs must be the SAME arrays, so the tail of the
    # run is bit-identical to the uninterrupted one
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    eng = AsyncFederation(cfg, sess, sampler, lambda s: 0.3, n,
                          steps_per_epoch=n).start()
    losses = []
    try:
        for step, _lr, m in eng.epoch_rounds(0, 0):
            losses.append(float(np.asarray(m["loss"])))
            if step == cut - 1:
                break
        blob = eng.snapshot_extra()
        assert int(blob["update"]) == cut
        assert blob["pending"], "C=2 snapshot must carry in-flight cohorts"
        # round-trip through JSON-ish copy semantics: restore and restart
        eng.restore_extra(blob)
        eng.restart(cut)
        for step, _lr, m in eng.epoch_rounds(0, cut):
            losses.append(float(np.asarray(m["loss"])))
    finally:
        eng.close()
    assert losses == ref_losses
    assert np.array_equal(np.asarray(sess.state.params_vec),
                          np.asarray(ref_sess.state.params_vec)), \
        "restored in-flight buffer must replay bit-identically"
    assert eng.stats()["restarts"] == 1


def test_cold_restart_without_blob_is_deterministic():
    """A plain restart (no snapshot blob) rebuilds the in-flight window by
    relaunching the same cohorts at the same versions — deterministic, and
    at the anchor (C=1) it is indistinguishable from never restarting."""
    cfg = _anchor(MODE_CONFIGS["uncompressed"])
    n, cut = 4, 2
    ref_sess, ref_records, _ = _run_async(cfg, num_rounds=n)

    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    eng = AsyncFederation(cfg, sess, sampler, lambda s: 0.3, n,
                          steps_per_epoch=n).start()
    try:
        for step, _lr, m in eng.epoch_rounds(0, 0):
            if step == cut - 1:
                break
        eng.restart(cut)  # no restore_extra: cold window rebuild
        for step, _lr, m in eng.epoch_rounds(0, cut):
            pass
    finally:
        eng.close()
    assert np.array_equal(np.asarray(sess.state.params_vec),
                          np.asarray(ref_sess.state.params_vec))


# ---------------------------------------------------------------------------
# telemetry: C=1 byte parity with the sync ledger + v8 perf report
# ---------------------------------------------------------------------------

def test_anchor_ledger_bills_exactly_the_sync_bytes(tmp_path):
    """Through the REAL train loop: the async run's comm_ledger must equal
    the synchronous twin's byte-for-byte under C=1, and the perf report is
    engine="async" with the v8 async block."""
    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.logging import MetricsWriter

    ledgers, reports = {}, {}
    for tag, extra in (("sync", {}),
                       ("async", dict(async_buffer=8, async_concurrency=1,
                                      staleness_exponent=0.0))):
        cfg = Config(telemetry_level=1, num_epochs=1, pivot_epoch=1,
                     lr_scale=0.1, **MODE_CONFIGS["sketch"], **extra, **BASE)
        ds, params, loss_fn = _setup(cfg.num_clients, n=160)
        test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                             1, seed=0)
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=cfg.num_workers,
                             local_batch_size=cfg.sampler_batch_size, seed=1)
        run_dir = str(tmp_path / f"run_{tag}")
        writer = MetricsWriter(run_dir, cfg=cfg)
        try:
            train_loop(cfg, sess, sampler, test_ds, writer, eval_batch_size=32)
        finally:
            writer.close()
        with open(os.path.join(run_dir, "comm_ledger.json")) as f:
            ledgers[tag] = json.load(f)
        with open(os.path.join(run_dir, "perf_report.json")) as f:
            reports[tag] = json.load(f)

    for key in ("rounds", "cum_up_bytes", "cum_down_bytes", "cum_bytes"):
        assert ledgers["async"][key] == ledgers["sync"][key], \
            f"C=1 async ledger must reconcile with sync: {key}"
    assert reports["async"]["engine"] == "async"
    assert reports["async"]["async"] == {
        "buffer": 8, "concurrency": 1, "staleness_exponent": 0.0}
    assert reports["sync"]["engine"] == "replicated"
    assert "async" not in reports["sync"]


# ---------------------------------------------------------------------------
# double-buffered rounds (ISSUE 16): deferred fence, same bits
# ---------------------------------------------------------------------------

def _run_async_spans(cfg, tmp_path, num_rounds=N_ROUNDS, lr=0.3,
                     ladder_rounds=None):
    """_run_async with a live PhaseSpans attached to session AND engine —
    the double-buffered fence discipline only executes with spans armed
    (without them there is nothing to defer), so these tests must run it
    for real."""
    from commefficient_tpu.telemetry.spans import PhaseSpans

    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    if ladder_rounds:
        from commefficient_tpu.control import build_controller

        ctrl = build_controller(cfg, sess, num_rounds=ladder_rounds)
        ctrl.prewarm(sampler, lr)
    spans = PhaseSpans(str(tmp_path), start_step=2, num_steps=num_rounds)
    sess.spans = spans
    eng = AsyncFederation(cfg, sess, sampler, lambda s: lr, num_rounds,
                          steps_per_epoch=num_rounds, spans=spans).start()
    records = []
    try:
        for step, _lr, m in eng.epoch_rounds(0, 0):
            records.append((step, m))
    finally:
        eng.close()
    return sess, records, eng, spans


@pytest.mark.parametrize("mode", [
    pytest.param("uncompressed", marks=pytest.mark.slow),
    "sketch",  # headline mode holds the default-tier pin (PR-12 precedent)
])
def test_double_buffer_anchor_bit_identical_to_sync(mode, tmp_path):
    """The apply fence parks behind the next cohort's launches, but the
    device programs dispatch in the same order — K=W, C=1, alpha=0 must
    still reduce to the synchronous round bit for bit."""
    extra = MODE_CONFIGS[mode]
    sync_sess, sync_losses = _run_sync(Config(**extra, **BASE))
    cfg = _anchor(dict(extra, async_double_buffer=True))
    async_sess, records, eng, spans = _run_async_spans(cfg, tmp_path)
    async_losses = [float(np.asarray(m["loss"])) for _, m in records]
    assert async_losses == sync_losses
    assert np.array_equal(np.asarray(async_sess.state.params_vec),
                          np.asarray(sync_sess.state.params_vec)), \
        f"{mode}: double-buffered anchor not bit-identical"
    # the deferred discipline actually ran: applies record as dispatch
    # spans (not collective-fenced applies) and the parked fences drained
    names = [ev["name"] for ev in spans.events]
    assert "async_apply_dispatch" in names
    assert "async_apply_drain" in names
    assert "async_apply" not in names, \
        "double-buffer mode must not record sequential apply spans"
    # drain spans are the collective-tagged ones
    for ev in spans.events:
        if ev["name"] == "async_apply_drain":
            assert ev["args"].get("collective") is True
        if ev["name"] == "async_apply_dispatch":
            assert "collective" not in ev["args"]


def test_double_buffer_close_drains_parked_fence(tmp_path):
    """close() (and snapshot_extra) must drain the parked fence — the
    last update's loss cannot stay un-synced past the engine's life."""
    cfg = _anchor(dict(MODE_CONFIGS["uncompressed"],
                       async_double_buffer=True))
    _sess, records, eng, spans = _run_async_spans(cfg, tmp_path)
    assert eng._deferred is None, "close() left a parked fence"
    drains = [ev for ev in spans.events
              if ev["name"] == "async_apply_drain"]
    assert len(drains) == len(records), \
        "every deferred apply fence must drain exactly once"


def test_double_buffer_snapshot_restore_replays_bit_identically(tmp_path):
    """The vault riders under double buffering: snapshot_extra drains the
    parked fence first, and the restored in-flight window replays the
    tail bit-identically — the rollback/recovery path stays exact."""
    extra = dict(MODE_CONFIGS["uncompressed"], async_double_buffer=True)
    cfg = Config(async_buffer=4, async_concurrency=2,
                 staleness_exponent=0.5, arrival_rate=2.0, **extra, **BASE)
    n, cut = 6, 3

    ref_sess, ref_records, _, _ = _run_async_spans(
        cfg, tmp_path / "ref", num_rounds=n)
    ref_losses = [float(np.asarray(m["loss"])) for _, m in ref_records]

    from commefficient_tpu.telemetry.spans import PhaseSpans

    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    spans = PhaseSpans(str(tmp_path / "cut"), start_step=2, num_steps=n)
    sess.spans = spans
    eng = AsyncFederation(cfg, sess, sampler, lambda s: 0.3, n,
                          steps_per_epoch=n, spans=spans).start()
    losses = []
    try:
        for step, _lr, m in eng.epoch_rounds(0, 0):
            losses.append(float(np.asarray(m["loss"])))
            if step == cut - 1:
                break
        blob = eng.snapshot_extra()
        assert eng._deferred is None, "snapshot_extra left a parked fence"
        eng.restore_extra(blob)
        eng.restart(cut)
        for step, _lr, m in eng.epoch_rounds(0, cut):
            losses.append(float(np.asarray(m["loss"])))
    finally:
        eng.close()
    assert losses == ref_losses
    assert np.array_equal(np.asarray(sess.state.params_vec),
                          np.asarray(ref_sess.state.params_vec))


def test_double_buffer_zero_retraces_across_rung_switches(tmp_path):
    """A mid-run ladder switch quiesces the window and recompiles the
    rung's launch/apply pair ONCE; the deferred fence must neither leak
    across the switch nor force extra retraces. telemetry_level=1 also
    exercises the new xla/exposed_collective_ms scalar end-to-end."""
    n = 6
    cfg = Config(async_buffer=8, async_concurrency=1,
                 staleness_exponent=0.0, async_double_buffer=True,
                 mode="local_topk", error_type="local",
                 topk_method="threshold", telemetry_level=1,
                 control_policy="fixed", control_schedule="0-2=0,3-=1",
                 ladder="k=20,10", **BASE)
    sess, records, eng, spans = _run_async_spans(
        cfg, tmp_path, num_rounds=n, ladder_rounds=n)
    assert len(records) == n
    for _, m in records:
        assert np.isfinite(float(np.asarray(m["loss"])))
    assert eng.quiesces == 1, "the ladder switch must quiesce the window"
    assert sess.retrace_sentinel.retraces == 0, \
        "double buffering must not add retraces across rung switches"
    rungs = [float(np.asarray(m["control/rung"])) for _, m in records]
    assert rungs == [0, 0, 0, 1, 1, 1]
    # the v9 scalar rides the metrics whenever spans are armed
    for _, m in records:
        assert float(np.asarray(m["xla/retraces"])) == 0
        assert float(m["xla/exposed_collective_ms"]) >= 0.0
