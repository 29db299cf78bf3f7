"""Self-healing training (resilience/) — rollback-and-recover, preemption
shutdown, checkpoint integrity fallback.

The load-bearing pins, per pillar:

  * divergence recovery — a chaos ``nan_client`` run under
    ``--recover_policy retry`` COMPLETES and is bit-identical to the
    uninterrupted (chaos-free) run: final params AND the deduped scalar
    sequence (the determinism contract README documents); ``demote``
    lands on the expected rung with ``xla/retraces == 0`` across the
    recovery (the AOT-prewarm claim); ``skip_clients`` blacklists the
    suspect and the ledger still satisfies the live-byte exactness
    invariant (checker-enforced);
  * preemption — the seeded ``preempt@R`` chaos event exits through
    ``PreemptShutdown`` with a forced checkpoint from which ``--resume``
    reproduces the uninterrupted run bit-exactly;
  * integrity — a corrupted latest checkpoint restores from the previous
    retained step with a warning naming the rejected step and reason.

All through the REAL shared runner (train/runner.py) at TinyMLP scale —
the femnist cv_train twin is slow-marked per the tier-1 budget. The
``--recover_policy none`` constructs-NOTHING gate is pinned here too
(golden parity / level-0 HLO byte-identity is the existing
test_compress_parity / test_telemetry coverage — this file pins the
construction gate those tests rely on)."""

import json
import os
import signal

import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.data import FedDataset, FedSampler
from commefficient_tpu.fedsim import ChaosEvent, parse_chaos
from commefficient_tpu.fedsim.env import FedEnvironment
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.resilience import (
    EXIT_PREEMPTED,
    PreemptGuard,
    PreemptShutdown,
    RollbackVault,
    available_recover_policies,
    build_resilience,
)
from commefficient_tpu.utils.checkpoint import FedCheckpointer
from commefficient_tpu.utils.config import RECOVER_POLICIES, Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checker():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# config validation + registry + grammar
# ---------------------------------------------------------------------------

def test_recover_policy_registry_matches_config_tuple():
    assert available_recover_policies() == tuple(sorted(RECOVER_POLICIES))


@pytest.mark.parametrize("kw,match", [
    (dict(recover_policy="bogus"), r"recover_policy"),
    (dict(snapshot_every=0), r"snapshot_every"),
    (dict(max_recoveries=0), r"max_recoveries"),
    # detection rides the flight recorder: level 0 never fires it
    (dict(recover_policy="retry", telemetry_level=0), r"telemetry_level"),
    # demote needs a >= 2-rung control ladder to descend
    (dict(recover_policy="demote", telemetry_level=1), r"ladder"),
    (dict(recover_policy="demote", telemetry_level=1,
          control_policy="fixed", control_schedule="0-=0", ladder="k=60",
          mode="true_topk", error_type="virtual", k=60,
          topk_method="threshold"), r">= 2"),
    # skip_clients masks through the fedsim participation mask
    (dict(recover_policy="skip_clients", telemetry_level=1),
     r"fedsim|masking"),
])
def test_config_rejects_bad_resilience_knobs(kw, match):
    with pytest.raises(ValueError, match=match):
        Config(**kw)


def test_chaos_grammar_preempt_and_counted_nan():
    plan = parse_chaos("preempt@7")
    assert plan == (ChaosEvent("preempt", 7.0, 7, 7, 1),)
    # counted form: N clients over a rounds window
    plan = parse_chaos("nan_client@2:rounds=3-4")
    assert plan == (ChaosEvent("nan_client", 2.0, 3, 4, 2),)
    # the single-round equivalence the docstring promises
    assert parse_chaos("nan_client@1:rounds=5-5")[0].active(5)
    assert not parse_chaos("nan_client@1:rounds=5-5")[0].active(6)


@pytest.mark.parametrize("bad", [
    "preempt@-1",            # negative round
    "preempt@0.5",           # fractional round
    "preempt@3:rounds=1-2",  # preempt@R names its round directly
    "nan_client@0:rounds=1-2",  # counted form needs count >= 1
])
def test_chaos_grammar_rejects(bad):
    with pytest.raises(ValueError, match="chaos"):
        parse_chaos(bad)


def test_transient_nan_suppressed_on_replay():
    """fedsim transient-fault semantics: the nan_client injection fires on
    a round's FIRST execution only; every other draw (and so every mask)
    is bit-identical on replay — what makes a 'retry' recovery a
    bit-identical replay."""
    env = FedEnvironment(Config(
        num_workers=8, num_clients=16, seed=7, availability="bernoulli",
        dropout_prob=0.4, chaos="nan_client@2:rounds=3-3",
    ))
    first = env.round_env(3)
    replay = env.round_env(3, replay=True)
    assert first.corrupt.sum() == min(2, int(first.live.sum()))
    assert replay.corrupt.sum() == 0
    np.testing.assert_array_equal(first.live, replay.live)
    assert first.stats["fedsim/preempt"] == 0.0
    # preempt rides the stats, never the masks
    env_p = FedEnvironment(Config(num_workers=8, num_clients=16, seed=7,
                                  chaos="preempt@3"))
    assert env_p.round_env(3).stats["fedsim/preempt"] == 1.0
    assert env_p.round_env(2).stats["fedsim/preempt"] == 0.0


# ---------------------------------------------------------------------------
# construction gate + unit pieces
# ---------------------------------------------------------------------------

def test_default_config_constructs_nothing():
    """recover_policy='none' + no preemption source: build_resilience
    returns None, the session rider slot stays None, and the process
    signal table is untouched — the level-0/availability='always' gate
    discipline golden parity depends on."""
    cfg = Config(mode="uncompressed", **BASE)
    assert not cfg.recovery_enabled
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    before = (signal.getsignal(signal.SIGTERM),
              signal.getsignal(signal.SIGINT))
    assert build_resilience(cfg, sess, sampler) is None
    assert sess.resilience is None
    assert (signal.getsignal(signal.SIGTERM),
            signal.getsignal(signal.SIGINT)) == before


def test_preempt_guard_signals_install_and_restore():
    prev = (signal.getsignal(signal.SIGTERM),
            signal.getsignal(signal.SIGINT))
    guard = PreemptGuard(install_signals=True)
    assert guard.signals_installed
    assert signal.getsignal(signal.SIGTERM) == guard._on_signal
    guard.close()
    assert (signal.getsignal(signal.SIGTERM),
            signal.getsignal(signal.SIGINT)) == prev
    # flag semantics: chaos stat folds in; first source wins; idempotent
    g = PreemptGuard()
    assert not g.check_metrics({"fedsim/preempt": 0.0})
    assert g.check_metrics({"fedsim/preempt": 1.0})
    assert g.source == "chaos preempt@round"
    g.request("signal SIGTERM")
    assert g.source == "chaos preempt@round"  # first wins
    assert EXIT_PREEMPTED == 75  # sysexits EX_TEMPFAIL, README exit table


def test_vault_snapshot_restore_roundtrip_bitwise():
    """The vault restores the exact captured state (params, momentum,
    error, step, round clock) and a re-run of the same rounds reproduces
    the first pass — the retry policy's whole mechanism."""
    cfg = Config(mode="true_topk", error_type="virtual",
                 virtual_momentum=0.9, k=40, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    for r in range(3):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.3)
    vault = RollbackVault(snapshot_every=3)
    assert vault.will_snapshot(3) and not vault.will_snapshot(2)
    vault.snapshot(sess, 3)
    at3 = np.asarray(sess.state.params_vec).copy()
    err3 = np.asarray(sess.state.error).copy()

    def two_more():
        for r in range(3, 5):
            ids, batch = sampler.sample_round(r)
            sess.train_round(ids, batch, 0.3)
        return np.asarray(sess.state.params_vec).copy()

    first_pass = two_more()
    assert not np.array_equal(at3, first_pass)
    snap = vault.latest(max_step=4)
    assert snap is not None and snap.step == 3
    assert vault.restore(sess, snap) == 3
    np.testing.assert_array_equal(np.asarray(sess.state.params_vec), at3)
    np.testing.assert_array_equal(np.asarray(sess.state.error), err3)
    assert int(np.asarray(sess.state.step)) == 3
    assert sess._round_clock == 3  # fedsim/chaos schedule re-synced
    np.testing.assert_array_equal(two_more(), first_pass)


def test_ledger_snapshot_state_roundtrip():
    from commefficient_tpu.telemetry import CommLedger

    bpr = {"upload_floats": 20, "download_floats": 100,
           "upload_bytes": 80, "download_bytes": 400}
    led = CommLedger(bpr, mode="true_topk", num_workers=8)
    for s in range(3):
        led.on_round(s)
    state = led.snapshot_state()
    for s in range(3, 6):
        led.on_round(s)
    assert led.rounds == 6
    led.load_snapshot_state(state)
    assert led.rounds == 3 and led.cum_up_bytes == 3 * 80
    # replaying bills exactly once: the exactness invariant survives
    for s in range(3, 6):
        led.on_round(s)
    assert led.cum_up_bytes == 6 * 80


def test_flight_rewind_drops_rolled_back_records():
    from commefficient_tpu.telemetry import FlightRecorder

    fl = FlightRecorder(logdir="", window=8)
    for s in range(6):
        fl.record(s, 0.1, {"loss": 1.0})
    fl.rewind(3)
    assert [r["step"] for r in fl.records] == [0, 1, 2]
    assert fl.last_step == 2
    fl.rewind(0)
    assert not fl.records and fl.last_step is None


# ---------------------------------------------------------------------------
# the shared runner at TinyMLP scale (default-tier acceptance twins)
# ---------------------------------------------------------------------------

_RUNNER_BASE = dict(
    mode="true_topk", error_type="virtual", virtual_momentum=0.9, k=40,
    topk_method="threshold", telemetry_level=1, perf_audit=False,
    availability="bernoulli", dropout_prob=0.25,
    num_epochs=1, pivot_epoch=1, lr_scale=0.1,
)


class _Rows:
    """Row-capturing stand-in for TableLogger (the epoch-table parity
    checks read the rows instead of the console)."""

    def __init__(self):
        self.rows = []

    def append(self, row):
        self.rows.append(dict(row))


def _run_loop(tmp_path, tag, ckpt_kw=None, table=None, **kw):
    """One TinyMLP run through the REAL shared runner (cv_train's
    train_loop adapter). 9 rounds (600 samples / (8 workers x 8 batch))."""
    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.logging import MetricsWriter

    base = {**BASE, "local_batch_size": 8}
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
        val = train_loop(cfg, sess, sampler, test_ds, writer, table=table,
                         eval_batch_size=32, checkpointer=ck)
    finally:
        ck.close()
        writer.close()
    return sess, run_dir, val


def _scalars(run_dir,
             exclude=("resilience/", "trace/",
                      "xla/exposed_collective_ms")):
    """metrics.jsonl as (name, value, step) in file order, deduped to the
    LAST occurrence per (name, step): a recovery replays its rolled-back
    rounds, so those steps legitimately appear twice — the healed values
    are the survivors the determinism contract compares.
    ``xla/exposed_collective_ms`` (v9) and ``trace/*`` (v11) are
    host-measured wall-clock, so excluded from bit-equality twins."""
    rows = {}
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "name" not in rec or rec["name"].startswith(exclude):
                continue
            rows[(rec["name"], rec["step"])] = (
                rec["name"], rec["value"], rec["step"])
    return list(rows.values())


def _last_value(run_dir, name):
    out = None
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("name") == name:
                out = rec["value"]
    return out


@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    """The chaos-free baseline run every recovery twin compares against —
    checkpointed every 2 rounds so it also pins the end-of-training
    force-save and seeds the integrity-fallback vault."""
    tmp = tmp_path_factory.mktemp("resil_base")
    ckpt_dir = str(tmp / "ckpt")
    rows = _Rows()
    sess, run_dir, val = _run_loop(
        tmp, "_base", table=rows,
        ckpt_kw=dict(checkpoint_dir=ckpt_dir, checkpoint_every=2),
    )
    return {
        "params": np.asarray(sess.state.params_vec).copy(),
        "step": int(np.asarray(sess.state.step)),
        "scalars": _scalars(run_dir),
        "table": rows.rows,
        "run_dir": run_dir,
        "ckpt_dir": ckpt_dir,
        "tmp": tmp,
        "val": val,
    }


def test_retry_heals_nan_client_bit_exactly(tmp_path, uninterrupted):
    """Acceptance pillar 1 (TinyMLP twin of the femnist e2e): a
    nan_client@1:rounds=5-5 injection under retry completes all 9 rounds,
    reports exactly one recovery, and the healed run is BIT-IDENTICAL to
    the uninterrupted run — final params and the deduped scalar sequence
    (ledger bytes included: the rollback rewound the accounting)."""
    rows = _Rows()
    sess, run_dir, _val = _run_loop(
        tmp_path, "_retry", table=rows,
        chaos="nan_client@1:rounds=5-5", recover_policy="retry",
        snapshot_every=4,
    )
    np.testing.assert_array_equal(np.asarray(sess.state.params_vec),
                                  uninterrupted["params"])
    assert _last_value(run_dir, "resilience/recoveries") == 1.0
    assert _last_value(run_dir, "resilience/rollback_round") == 4.0
    assert _scalars(run_dir) == uninterrupted["scalars"], (
        "a healed retry run must reproduce the uninterrupted scalars"
    )
    # the epoch TABLE row too: the accumulator rides the vault snapshot,
    # so the mid-epoch rollback (round 4 of 9) re-seeds rounds 0-3 and
    # the healed epoch averages the FULL epoch, bit-equal to baseline
    # (wall-clock columns excluded)
    times = {"train_time", "val_time"}
    assert [{k: v for k, v in r.items() if k not in times}
            for r in rows.rows] == [
        {k: v for k, v in r.items() if k not in times}
        for r in uninterrupted["table"]]
    # every artifact (incl. the _recovery-tagged flight dump and the
    # replay-rewound ledger) validates under schema v6
    mod = _checker()
    mod.validate_run_dir(run_dir)
    rec = json.loads(open(
        os.path.join(run_dir, "flight_5_recovery.json")).read())
    hist = rec["recovery_history"]
    assert len(hist) == 1 and hist[0]["outcome"] == "recovered"
    assert hist[0]["first_bad_step"] == 5 and hist[0]["rollback_to"] == 4
    # the detection-time dump preserved the diverged trajectory
    assert os.path.exists(os.path.join(run_dir, "flight_5.json"))


def test_retry_rollback_into_completed_epoch_no_duplicate_rows(tmp_path):
    """Review fix: a rollback landing INSIDE an already-completed epoch
    (divergence in epoch 1, newest snapshot mid-epoch 0) must not re-run
    that epoch's end block — the healed table would otherwise carry a
    duplicate epoch-0 row (and re-eval / re-write its val scalars)."""
    base_rows, heal_rows = _Rows(), _Rows()
    _run_loop(tmp_path, "_xepoch_base", table=base_rows, num_epochs=2)
    sess, run_dir, _val = _run_loop(
        tmp_path, "_xepoch_heal", table=heal_rows, num_epochs=2,
        # round 9 opens epoch 1; detection at the round-12 boundary drain
        # rolls back to the mid-epoch-0 snapshot at round 8
        chaos="nan_client@1:rounds=9-9", recover_policy="retry",
        snapshot_every=4,
    )
    assert _last_value(run_dir, "resilience/recoveries") == 1.0
    assert _last_value(run_dir, "resilience/rollback_round") == 8.0
    times = {"train_time", "val_time"}
    strip = lambda rows: [{k: v for k, v in r.items() if k not in times}
                          for r in rows]
    assert len(heal_rows.rows) == 2  # one row per epoch, no duplicate
    assert strip(heal_rows.rows) == strip(base_rows.rows)


def test_retry_exhaustion_reraises_with_history(tmp_path):
    """A PERSISTENT divergence (injection active on every execution, so
    the replay diverges again... modeled by an open-ended window wider
    than max_recoveries can outrun) gives up after --max_recoveries and
    re-raises the ORIGINAL DivergenceError with the full history."""
    from commefficient_tpu.telemetry import DivergenceError

    with pytest.raises(DivergenceError) as ei:
        _run_loop(
            tmp_path, "_exhaust",
            # replay suppresses already-executed rounds' injections, but
            # every recovery advances into rounds that inject on THEIR
            # first execution: each re-entry meets a fresh divergence
            # until the bound trips
            chaos="nan_client@1:rounds=3-8", recover_policy="retry",
            snapshot_every=2, max_recoveries=2,
        )
    hist = ei.value.recovery_history
    assert len(hist) == 3  # two recoveries + the give-up entry
    assert [h["outcome"] for h in hist[:2]] == ["recovered", "recovered"]
    assert "exhausted" in hist[-1]["outcome"]


def test_demote_recovery_descends_ladder_zero_retraces(tmp_path):
    """Acceptance pillar 1, demote flavor: the recovery floors the
    control/ ladder one rung cheaper through the AOT-prewarmed switch —
    the healed run finishes on rung 1, never climbs back above the floor,
    and xla/retraces stays 0 across the whole recovery."""
    sess, run_dir, _val = _run_loop(
        tmp_path, "_demote",
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0, k=60,
        control_policy="fixed", control_schedule="0-=0", ladder="k=60,30",
        chaos="nan_client@3", recover_policy="demote", snapshot_every=2,
    )
    seq = _scalars(run_dir, exclude=())
    rungs = [(s, v) for n, v, s in seq if n == "control/rung"]
    # rounds before the rollback ran rung 0; the healed replay (from
    # round 2 on) runs the demotion floor
    assert [v for s, v in rungs if s < 2] == [0.0, 0.0]
    assert all(v == 1.0 for s, v in rungs if s >= 2), rungs
    assert {v for n, v, _s in seq if n == "xla/retraces"} == {0.0}
    assert sess.retrace_sentinel.retraces == 0
    assert _last_value(run_dir, "resilience/rung_demotions") == 1.0
    assert _last_value(run_dir, "resilience/recoveries") == 1.0
    assert int(np.asarray(sess.state.step)) == 9  # completed all rounds


def test_preloop_failure_restores_signal_dispositions(tmp_path, monkeypatch):
    """Review fix: a failure BEFORE the runner's try/finally (e.g. the
    restore walk-back exhausted every retained step) must still restore
    the signal dispositions build_resilience installed — the surviving
    process would otherwise keep flag-only SIGTERM/SIGINT handlers
    nobody polls."""
    before = (signal.getsignal(signal.SIGTERM),
              signal.getsignal(signal.SIGINT))

    def boom(self, session, step=None):
        raise ValueError("restore failed at every retained checkpoint step")

    monkeypatch.setattr(FedCheckpointer, "restore", boom)
    with pytest.raises(ValueError, match="every retained"):
        _run_loop(
            tmp_path, "_preloop", preempt_signals=True,
            recover_policy="retry",
            ckpt_kw=dict(checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=2, resume=True),
        )
    assert (signal.getsignal(signal.SIGTERM),
            signal.getsignal(signal.SIGINT)) == before


def test_repeated_demote_descends_past_stale_snapshot_floor(tmp_path):
    """Review fix: the demotion floor is MONOTONE across rollback blob
    loads. With snapshot_every wider than an epoch the baseline snapshot
    (rung 0, floor 0) stays the only rollback target — a second
    divergence must still descend to rung 2, not re-demote to the rung 1
    that just diverged (the stale blob used to erase the floor)."""
    sess, run_dir, _val = _run_loop(
        tmp_path, "_demote2",
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0, k=60,
        control_policy="fixed", control_schedule="0-=0",
        ladder="k=60,30,15", num_epochs=2,
        # round 2 diverges in epoch 0 (detected at the epoch-end drain),
        # round 11 is past the first recovery's replay horizon so it
        # injects fresh in epoch 1 — both detections roll back to the
        # baseline snapshot at round 0 (snapshot_every=32 never fires
        # inside the 18-round run)
        chaos="nan_client@1:rounds=2-2,nan_client@1:rounds=11-11",
        recover_policy="demote", snapshot_every=32, max_recoveries=2,
    )
    assert int(np.asarray(sess.state.step)) == 18  # completed all rounds
    assert _last_value(run_dir, "resilience/recoveries") == 2.0
    assert _last_value(run_dir, "resilience/rung_demotions") == 2.0
    # the second recovery descends PAST the first demotion's rung
    assert _last_value(run_dir, "control/rung") == 2.0
    assert sess.controller.min_rung == 2
    assert sess.retrace_sentinel.retraces == 0


def test_skip_clients_recovery_blacklists_and_ledger_exact(tmp_path):
    """Acceptance pillar 1, skip_clients flavor: the suspect client is
    blacklisted out of every future participation mask, the run
    completes, and the ledger still satisfies the live-byte exactness
    invariant (checker-enforced + recomputed from the logged rates)."""
    sess, run_dir, _val = _run_loop(
        tmp_path, "_skip",
        mode="uncompressed", error_type="none", virtual_momentum=0.9,
        chaos="nan_client@3", recover_policy="skip_clients",
        snapshot_every=2,
    )
    assert int(np.asarray(sess.state.step)) == 9
    assert sess._client_blacklist is not None
    assert len(sess._client_blacklist) >= 1
    assert _last_value(run_dir, "resilience/blacklisted_clients") == float(
        len(sess._client_blacklist))
    mod = _checker()
    mod.validate_run_dir(run_dir)  # masked ledger invariant inside
    rates = [
        json.loads(line) for line in open(
            os.path.join(run_dir, "metrics.jsonl"))
        if '"fedsim/participation_rate"' in line
    ]
    # replayed steps appear twice; the rollback rewound the ledger, so
    # only the LAST (healed) billing per step survives in the totals
    live_sum = round(sum({r["step"]: r["value"]
                          for r in rates}.values()) * 8)
    ledger = json.loads(open(
        os.path.join(run_dir, "comm_ledger.json")).read())
    assert ledger["live_client_rounds"] == live_sum
    assert ledger["cum_up_bytes"] == (
        ledger["live_client_rounds"]
        * ledger["bytes_per_round"]["upload_bytes"]
    )


def test_skip_clients_blacklist_survives_checkpoint_resume(tmp_path):
    """Review fix: the session blacklist rides the checkpoint (a
    ``blacklist`` leaf in ``_to_saveable``) and restore re-condemns the
    saved clients — a preempt/resume cycle must not silently re-admit a
    client a recovery already blacklisted."""
    cfg = Config(**{**BASE, "local_batch_size": 8, **_RUNNER_BASE,
                    "mode": "uncompressed", "error_type": "none",
                    "chaos": "nan_client@3",
                    "recover_policy": "skip_clients",
                    "checkpoint_dir": str(tmp_path / "ck"),
                    "checkpoint_every": 2})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sess.blacklist_clients([3, 7])
    ck = FedCheckpointer(cfg)
    assert ck.maybe_save(sess, 2, force=True)
    ck.close()
    sess2 = FederatedSession(cfg, params, loss_fn)
    assert sess2._client_blacklist is None
    ck2 = FedCheckpointer(cfg)
    assert ck2.restore(sess2) == 0  # FedState.step at save time
    ck2.close()
    np.testing.assert_array_equal(sess2._client_blacklist, [3, 7])
    # and a blacklist-free checkpoint restored into a session that
    # already has one keeps the session's (template key absorbed)
    sess3 = FederatedSession(cfg, params, loss_fn)
    ck3 = FedCheckpointer(cfg.replace(
        checkpoint_dir=str(tmp_path / "ck2")))
    assert ck3.maybe_save(sess3, 2, force=True)  # no blacklist saved
    sess4 = FederatedSession(cfg, params, loss_fn)
    sess4.blacklist_clients([5])
    assert ck3.restore(sess4) == 0
    ck3.close()
    np.testing.assert_array_equal(sess4._client_blacklist, [5])


def test_recovery_discards_stale_checkpoints_above_rollback(tmp_path):
    """Review fix: a checkpoint saved between the rollback target and the
    detection point came from the rolled-back trajectory — under a
    demote fork it held the PRE-recovery controller blob (no min_rung
    floor), and the replay's maybe_save at that boundary used to be a
    silent no-op against it. The recovery now discards steps above the
    rollback so the replay re-saves its own state."""
    import orbax.checkpoint as ocp

    # snapshots at 4/8, checkpoint at 5; nan at 6 detected at the
    # snapshot-8 drain -> rollback to 4 < saved step 5
    _sess, run_dir, _val = _run_loop(
        tmp_path, "_stale",
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0, k=60,
        control_policy="fixed", control_schedule="0-=0", ladder="k=60,30",
        chaos="nan_client@6", recover_policy="demote", snapshot_every=4,
        ckpt_kw=dict(checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every=5),
    )
    assert _last_value(run_dir, "resilience/recoveries") == 1.0
    assert _last_value(run_dir, "resilience/rollback_round") == 4.0
    mngr = ocp.CheckpointManager(os.path.abspath(str(tmp_path / "ck")))
    blob = np.asarray(mngr.restore(
        5, args=ocp.args.StandardRestore())["control"])
    mngr.close()
    # the step-5 checkpoint on disk is the REPLAY's: demoted rung (slot
    # 1) and the demotion floor (slot 7) both present — the stale
    # first-pass blob had 0 in both
    assert blob[1] == 1.0 and blob[7] == 1.0


def test_unavailable_policy_aborts_before_rewind(tmp_path):
    """Review fix: when the policy cannot act (here a second demotion
    with the 2-rung ladder already floored), the recovery aborts BEFORE
    the vault/ledger/flight rewind — the dead run's comm_ledger must
    describe the rounds that actually ran, not a rolled-back prefix."""
    from commefficient_tpu.telemetry import DivergenceError

    with pytest.raises(DivergenceError) as ei:
        _run_loop(
            tmp_path, "_unavail",
            mode="local_topk", error_type="local", local_momentum=0.9,
            virtual_momentum=0.0, k=60,
            control_policy="fixed", control_schedule="0-=0",
            ladder="k=60,30",
            chaos="nan_client@3,nan_client@6", recover_policy="demote",
            snapshot_every=2,
        )
    hist = ei.value.recovery_history
    assert [h["outcome"][:10] for h in hist] == ["recovered", "policy una"]
    assert "cheapest rung" in hist[-1]["outcome"]
    # drained rounds billed net of the FIRST (successful) rewind:
    # 0,1 + replayed 2,3 + 4,5 + the bad 6 (the drain bills it before
    # raising; 7 was pending and dropped) = 7 — an aborted second
    # recovery must NOT have rewound these to the snapshot-6 counters
    ledger = json.loads(open(os.path.join(
        str(tmp_path / "run_unavail"), "comm_ledger.json")).read())
    assert ledger["rounds"] == 7


def test_preempt_shutdown_message_honest_without_checkpointing():
    """Review fix: a preemption with checkpointing disabled must not
    claim a checkpoint was saved (the orchestrator would --resume into
    nothing and silently restart from round 0)."""
    e = PreemptShutdown(4, "signal SIGTERM", saved=False)
    assert not e.saved
    assert "NO checkpoint was saved" in str(e)
    assert "--resume to continue bit-exactly" not in str(e)
    assert str(EXIT_PREEMPTED) in str(e)
    assert PreemptShutdown(4, "x").saved  # checkpointed path unchanged


def test_preempt_chaos_forced_checkpoint_and_resume(tmp_path,
                                                    uninterrupted):
    """Acceptance pillar 2: the seeded preempt@3 event exits through
    PreemptShutdown AFTER draining + force-saving a checkpoint at the
    preempted round; a --resume run completes and reproduces the
    uninterrupted run bit-exactly."""
    ckpt_dir = str(tmp_path / "ckpt_pre")
    with pytest.raises(PreemptShutdown) as ei:
        _run_loop(
            tmp_path, "_pre",
            ckpt_kw=dict(checkpoint_dir=ckpt_dir, checkpoint_every=100),
            chaos="preempt@3",
        )
    assert ei.value.step == 4  # rounds 0..3 ran; saved at boundary 4
    assert ei.value.source == "chaos preempt@round"
    assert ei.value.saved  # the message's --resume promise is real
    ck = FedCheckpointer(Config(checkpoint_dir=ckpt_dir))
    assert ck.latest_step() == 4
    ck.close()
    run_pre = str(tmp_path / "run_pre")
    assert _last_value(run_pre, "resilience/preempt_requested") == 1.0
    # the crash teardown wrote the flight record naming the preemption
    flights = [f for f in os.listdir(run_pre) if f.startswith("flight_")]
    assert flights
    rec = json.loads(open(os.path.join(run_pre, flights[0])).read())
    assert "preemption requested" in rec["reason"]
    # resume: round 3 is behind the restore point, so the chaos event
    # never re-fires; the tail reproduces the uninterrupted run
    sess, _run_dir, _val = _run_loop(
        tmp_path, "_pre_resume",
        ckpt_kw=dict(checkpoint_dir=ckpt_dir, checkpoint_every=100),
        chaos="preempt@3", resume=True,
    )
    np.testing.assert_array_equal(np.asarray(sess.state.params_vec),
                                  uninterrupted["params"])


def test_end_of_training_checkpoint_and_resume_after_completion(
        uninterrupted):
    """Satellite: a completed run force-saves its FINAL state (odd-round
    tails included), so --resume on a finished run re-trains NOTHING —
    it restores, skips the epoch loop, and still returns final metrics."""
    ck = FedCheckpointer(Config(
        checkpoint_dir=uninterrupted["ckpt_dir"]))
    assert ck.latest_step() == 9 == uninterrupted["step"]
    ck.close()
    sess, run_dir, val = _run_loop(
        uninterrupted["tmp"], "_postresume",
        ckpt_kw=dict(checkpoint_dir=uninterrupted["ckpt_dir"],
                     checkpoint_every=2),
        resume=True,
    )
    assert int(np.asarray(sess.state.step)) == 9
    np.testing.assert_array_equal(np.asarray(sess.state.params_vec),
                                  uninterrupted["params"])
    assert val and np.isfinite(val["loss"])
    # no round trained, no train scalar written
    assert not [r for r in _scalars(run_dir) if r[0] == "train/loss"]
    # and the finished run's checkpoint was NOT redundantly re-saved
    ck = FedCheckpointer(Config(
        checkpoint_dir=uninterrupted["ckpt_dir"]))
    assert ck.latest_step() == 9
    ck.close()


def test_corrupted_latest_checkpoint_falls_back_with_warning(
        uninterrupted, tmp_path):
    """Acceptance pillar 3: a corrupted latest step is REJECTED by the
    manifest verification with a warning naming the step and reason, and
    restore falls back to the previous retained step; an explicitly
    requested step stays strict (raises, never substitutes)."""
    import shutil

    ckpt_dir = str(tmp_path / "ckpt_corrupt")
    shutil.copytree(uninterrupted["ckpt_dir"], ckpt_dir)
    cfg = Config(**{**BASE, "local_batch_size": 8}, **_RUNNER_BASE,
                 checkpoint_dir=ckpt_dir, checkpoint_every=2)
    ck = FedCheckpointer(cfg)
    steps = sorted(int(s) for s in ck.mngr.all_steps())
    latest, prev = steps[-1], steps[-2]
    # flip bytes in one payload file of the latest step (size preserved:
    # only the sha256 catches it)
    victim = None
    for dirpath, _dirs, files in os.walk(os.path.join(ckpt_dir,
                                                      str(latest))):
        for fn in files:
            p = os.path.join(dirpath, fn)
            if os.path.getsize(p) > 16:
                victim = p
                break
        if victim:
            break
    with open(victim, "r+b") as f:
        data = bytearray(f.read())
        data[-8:] = bytes(8) if bytes(data[-8:]) != bytes(8) else b"\xff" * 8
        f.seek(0)
        f.write(data)
    reason = ck.verify_step(latest)
    assert reason is not None and "sha256 mismatch" in reason
    assert ck.verify_step(prev) is None
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    with pytest.warns(UserWarning, match=rf"step {latest} REJECTED"):
        assert ck.restore(sess) == prev
    assert int(np.asarray(sess.state.step)) == prev
    # explicit step: the caller named it — strict rejection, no fallback
    sess2 = FederatedSession(cfg, params, loss_fn)
    with pytest.raises(ValueError, match="integrity"):
        ck.restore(sess2, step=latest)
    ck.close()
    # truncation is caught by the cheaper size check
    ck2 = FedCheckpointer(cfg)
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) - 4)
    assert "size mismatch" in ck2.verify_step(latest)
    ck2.close()


def test_restore_exhausting_all_steps_chains_failures(tmp_path,
                                                      uninterrupted):
    """Every retained step rejected -> the final error names each step
    with its reason instead of silently reporting only the last."""
    import shutil

    ckpt_dir = str(tmp_path / "ckpt_all_bad")
    shutil.copytree(uninterrupted["ckpt_dir"], ckpt_dir)
    cfg = Config(**{**BASE, "local_batch_size": 8}, **_RUNNER_BASE,
                 checkpoint_dir=ckpt_dir, checkpoint_every=2)
    ck = FedCheckpointer(cfg)
    steps = sorted(int(s) for s in ck.mngr.all_steps())
    for s in steps:  # tamper EVERY manifest's expectations
        mpath = os.path.join(ckpt_dir, "manifests", f"{s}.json")
        man = json.loads(open(mpath).read())
        for info in man["files"].values():
            info["sha256"] = "0" * 64
        with open(mpath, "w") as f:
            json.dump(man, f)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    with pytest.warns(UserWarning):
        with pytest.raises(ValueError) as ei:
            ck.restore(sess)
    for s in steps:
        assert f"step {s}" in str(ei.value)
    ck.close()


def test_restore_template_walk_chains_all_candidate_failures(tmp_path):
    """Satellite: when EVERY rung state template fails to restore (here a
    genuinely corrupted payload on a shape-changing ladder, the exact
    masking hazard: the bare-except walk used to surface only the LAST
    layout's error), the error names each attempt and chains the FIRST —
    the likely save-time layout — as the cause."""
    import glob
    import shutil

    from commefficient_tpu.control import build_controller

    def build():
        kw = dict(BASE)
        kw.update(mode="powersgd", error_type="virtual",
                  virtual_momentum=0.9, powersgd_rank=4,
                  telemetry_level=1, control_policy="fixed",
                  control_schedule="0-=0", ladder="powersgd_rank=4,2",
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2)
        cfg = Config(**kw)
        _ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        build_controller(cfg, sess, num_rounds=4)
        return cfg, sess

    cfg, sess = build()
    ck = FedCheckpointer(cfg)
    assert ck.maybe_save(sess, 2, force=True)
    ck.close()
    # strip the integrity sidecars (a legacy checkpoint: nothing to
    # pre-verify, so restore reaches the template walk) and corrupt the
    # payload so EVERY rung template's attempt fails
    shutil.rmtree(str(tmp_path / "ck" / "manifests"))
    victims = [p for p in glob.glob(str(tmp_path / "ck" / "2" / "**"),
                                    recursive=True) if os.path.isfile(p)]
    os.remove(victims[-1])
    _cfg2, sess2 = build()
    ck2 = FedCheckpointer(cfg)
    with pytest.raises(ValueError, match="every rung state template") as ei:
        ck2.restore(sess2, step=2)
    msg = str(ei.value)
    assert "rung 0 template" in msg and "rung 1 template" in msg
    assert ei.value.__cause__ is not None  # the FIRST attempt's failure
    ck2.close()


def test_checkpointer_closed_on_crash_path(tmp_path):
    """Satellite: the shared runner's finally block closes the Orbax
    manager on crash paths (it used to leak there), and close() is
    idempotent so the entries' own finally stays a no-op."""
    class _Poisoned:
        def __init__(self, real):
            self._real = real

        def steps_per_epoch(self):
            return self._real.steps_per_epoch()

        def epoch(self, e):
            for r, item in enumerate(self._real.epoch(e)):
                if r == 2:
                    raise ValueError("poisoned round 2")
                yield item

        def sample_round(self, r):
            return self._real.sample_round(r)

    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.logging import MetricsWriter

    cfg = Config(**{**BASE, "local_batch_size": 8}, **_RUNNER_BASE,
                 checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1)
    ds, params, loss_fn = _setup(cfg.num_clients)
    test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                         1, seed=0)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = _Poisoned(FedSampler(ds, num_workers=cfg.num_workers,
                                   local_batch_size=cfg.local_batch_size,
                                   seed=1))
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg)
    ck = FedCheckpointer(cfg)
    with pytest.raises(ValueError, match="poisoned round 2"):
        train_loop(cfg, sess, sampler, test_ds, writer,
                   eval_batch_size=32, checkpointer=ck)
    writer.close()
    assert ck.mngr is None, "runner's finally must close the checkpointer"
    ck.close()  # the entry-level belt: idempotent, not a double-close


# ---------------------------------------------------------------------------
# cv_train e2e (slow femnist twin of the TinyMLP acceptance above)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # two femnist/resnet9 cv_main runs (~2 min CPU compiles);
# every claim holds default-tier coverage through the TinyMLP runner twins
def test_cv_train_retry_heals_femnist_e2e(tmp_path):
    """The full-entry acceptance: cv_train with
    chaos "nan_client@1:rounds=5-5" + --recover_policy retry completes
    all rounds, reports resilience/recoveries == 1, and its final
    checkpointed params match the chaos-free run's bit-exactly."""
    import orbax.checkpoint as ocp

    from commefficient_tpu.train.cv_train import main as cv_main

    def kw(tag, **extra):
        return dict(
            dataset_name="femnist", model="resnet9", mode="local_topk",
            error_type="local", k=2000, num_clients=6, num_workers=4,
            num_devices=4, local_batch_size=32, num_epochs=2,
            pivot_epoch=1, lr_scale=0.1, telemetry_level=1,
            perf_audit=False, availability="bernoulli", dropout_prob=0.3,
            dataset_dir=str(tmp_path), seed=0,
            checkpoint_dir=str(tmp_path / f"ckpt{tag}"),
            checkpoint_every=100,  # only the end-of-training force-save
            logdir=str(tmp_path / f"runs{tag}"), **extra,
        )

    def final_params(tag):
        mngr = ocp.CheckpointManager(
            os.path.abspath(str(tmp_path / f"ckpt{tag}")))
        fs = mngr.restore(mngr.latest_step(),
                          args=ocp.args.StandardRestore())["fed_state"]
        mngr.close()
        return np.asarray(fs["params_vec"])

    val = cv_main([], **kw("_clean"))
    assert np.isfinite(val["loss"])
    val = cv_main([], **kw("_chaos", chaos="nan_client@1:rounds=5-5",
                           recover_policy="retry", snapshot_every=4))
    assert np.isfinite(val["loss"])
    run = sorted((tmp_path / "runs_chaos").iterdir())[0]
    assert _last_value(str(run), "resilience/recoveries") == 1.0
    np.testing.assert_array_equal(final_params("_chaos"),
                                  final_params("_clean"))
    _checker().validate_run_dir(run)
