"""The asyncfed staging plane (asyncfed/staging.py): cohorts realized ahead.

What the prefetcher owes its one consumer, the buffered-asynchronous
engine: the staged ``RoundWork`` stream IS the synchronous realization
(every input is a pure function of the index), in order; a fault on the
worker thread (corrupt batch, exhausted range, fedsim realization error,
dead worker) surfaces at the consuming ``get`` with the ORIGINAL
traceback — through the engine, and through the runner with a flight
dump — and shutdown joins, never hangs (the ``timeout`` marks document
the bound; the tests also enforce their own join deadlines since this
container lacks pytest-timeout). The engine-level tests drive
``AsyncFederation`` at the K = W, C = 1 anchor, where one cohort is one
round."""

import os
import traceback

import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.asyncfed import AsyncFederation
from commefficient_tpu.asyncfed.staging import (
    PrefetchWorkerDied,
    RoundPrefetcher,
)
from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one cohort per update, in launch order: cohort c is round c. On ONE
# device: what these tests hold (the worker's fault reaches the consumer,
# the lanes are named) does not depend on the mesh, and on the 8-device
# CPU mesh XLA's in-process all-reduce deadlocked in 3 of 45 runs of this
# file (7 of 8 participants at the rendezvous while the staging thread's
# copies held the eighth pool thread; 40 s, then abort).
ANCHOR = dict(async_buffer=BASE["num_workers"], async_concurrency=1,
              num_devices=1)


def _checker():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _session_and_sampler(**kw):
    cfg = Config(**{**BASE, **kw})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    return cfg, sess, sampler


def _lr_fn(step):
    return 0.3 - 0.01 * step


# ---------------------------------------------------------------------------
# prefetcher: the staged stream IS the synchronous realization
# ---------------------------------------------------------------------------

def test_prefetcher_matches_synchronous_realization():
    cfg, sess, sampler = _session_and_sampler(
        mode="true_topk", error_type="virtual", k=40,
        availability="bernoulli", dropout_prob=0.3,
    )
    pf = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                         depth=2, start_step=0, stop_step=6).start()
    try:
        for step in range(6):
            work = pf.get(step)
            ids, batch = sampler.sample_round(step)
            env = sess.fedsim_env.round_env(step)
            assert work.step == step
            assert work.lr == float(_lr_fn(step))
            np.testing.assert_array_equal(work.client_ids, ids)
            for k in batch:
                # staged device arrays hold the exact host bytes
                np.testing.assert_array_equal(
                    np.asarray(work.batch[k]), batch[k]
                )
            np.testing.assert_array_equal(work.env.live, env.live)
            np.testing.assert_array_equal(work.env.corrupt, env.corrupt)
            assert work.env.stats == env.stats
    finally:
        assert pf.close()


def test_prefetcher_in_order_contract_and_exhaustion():
    cfg, sess, sampler = _session_and_sampler(mode="uncompressed")
    pf = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                         depth=2, start_step=0, stop_step=2).start()
    try:
        pf.get(0)
        with pytest.raises(RuntimeError, match="order violated"):
            pf.get(5)  # the worker staged round 1, the consumer skipped it
    finally:
        assert pf.close()
    pf = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                         depth=2, start_step=0, stop_step=1).start()
    try:
        pf.get(0)
        with pytest.raises(PrefetchWorkerDied, match="exhausted"):
            pf.get(1)  # past stop_step: a loud error, never a hang
    finally:
        assert pf.close()


def test_spans_thread_aware_prefetch_lane(tmp_path):
    """Schema v5 thread-aware spans: the staging worker's spans land on
    their OWN lane (tid != 0) with a thread_name metadata event and the
    cohort they realize; the engine's apply spans stay on lane 0. The
    dump passes the real checker."""
    from commefficient_tpu.telemetry.spans import PhaseSpans

    cfg, sess, sampler = _session_and_sampler(
        mode="uncompressed", telemetry_level=1, **ANCHOR)
    spans = PhaseSpans(str(tmp_path))
    sess.spans = spans
    engine = AsyncFederation(cfg, sess, sampler, _lr_fn, 4,
                             steps_per_epoch=4, spans=spans).start(0)
    try:
        for _ in engine.epoch_rounds(0, 0):
            pass
    finally:
        engine.close()
    sess.spans = None
    path = spans.close()
    rec = _checker().validate_spans(path)
    evs = rec["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["args"]["name"] == "round-prefetch" for e in meta)
    lane = next(e["tid"] for e in meta
                if e["args"]["name"] == "round-prefetch")
    assert lane != 0
    pre = [e for e in evs if e["ph"] == "X"
           and e["name"].startswith("prefetch_")]
    assert pre and all(e["tid"] == lane for e in pre)
    # the staging lane stamps the cohort it REALIZES, not the consumer's
    # current round clock
    assert sorted({e["args"]["step"] for e in pre
                   if e["name"] == "prefetch_realize"}) == [0, 1, 2, 3]
    applies = [e for e in evs if e["ph"] == "X"
               and e["name"] == "async_apply"]
    assert applies and all(e["tid"] == 0 for e in applies)


# ---------------------------------------------------------------------------
# fault paths: original traceback at the consuming round, never a hang
# ---------------------------------------------------------------------------

class _PoisonedSampler:
    """Delegates to a real FedSampler but corrupts round ``bad_round``."""

    def __init__(self, real, bad_round, exc):
        self._real = real
        self._bad = bad_round
        self._exc = exc

    def steps_per_epoch(self):
        return self._real.steps_per_epoch()

    def sample_round(self, r):
        if r == self._bad:
            raise self._exc
        return self._real.sample_round(r)


@pytest.mark.timeout(120)
def test_worker_fault_surfaces_original_traceback():
    """A corrupt batch at cohort 3 raises AT the update that launches
    cohort 3 — original exception object, worker-side frames intact —
    after updates 0..2 applied normally; close() still joins."""
    cfg, sess, sampler = _session_and_sampler(mode="uncompressed", **ANCHOR)
    poisoned = _PoisonedSampler(sampler, 3,
                                ValueError("corrupt batch payload"))
    engine = AsyncFederation(cfg, sess, poisoned, _lr_fn, 6,
                             steps_per_epoch=6).start(0)
    worker = engine._scheduler._prefetcher
    try:
        seen = []
        with pytest.raises(ValueError, match="corrupt batch payload") as ei:
            for s, _lr, _m in engine.epoch_rounds(0, 0):
                seen.append(s)
        assert seen == [0, 1, 2]
        frames = "".join(traceback.format_tb(ei.value.__traceback__))
        assert "_realize" in frames, (
            "the worker-side traceback must survive the thread hop"
        )
    finally:
        engine.close()
    # the worker must be joinable after the fault (bounded deadline)
    assert worker.close(timeout=10.0)


@pytest.mark.timeout(120)
def test_fedsim_realization_fault_surfaces():
    """A fedsim env realization error in the worker surfaces at the
    consuming update with the original frames (the 'fedsim validation
    error' fault class)."""
    cfg, sess, sampler = _session_and_sampler(
        mode="uncompressed", availability="bernoulli", dropout_prob=0.2,
        **ANCHOR,
    )

    def boom(round_idx, replay=False):
        raise RuntimeError(f"fedsim validation failed at {round_idx}")

    sess.fedsim_env.round_env = boom
    engine = AsyncFederation(cfg, sess, sampler, _lr_fn, 4,
                             steps_per_epoch=4).start(0)
    worker = engine._scheduler._prefetcher
    try:
        with pytest.raises(RuntimeError, match="fedsim validation failed"):
            for _ in engine.epoch_rounds(0, 0):
                pass
    finally:
        engine.close()
    assert worker.close(timeout=10.0)


@pytest.mark.timeout(120)
def test_worker_exit_does_not_mask_staged_items_or_faults(monkeypatch):
    """A finished/dead worker must never shadow what it already staged:
    items (and the exhaustion sentinel) enqueued before the thread exited
    are still consumed in order; only a worker that died WITHOUT leaving
    an item or exception raises the generic PrefetchWorkerDied."""
    cfg, sess, sampler = _session_and_sampler(mode="uncompressed")
    pf = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                         depth=3, start_step=0, stop_step=2).start()
    pf._thread.join(timeout=30)  # 2 rounds + _END fit the depth-3 queue
    assert not pf._thread.is_alive()
    assert pf.get(0).step == 0
    assert pf.get(1).step == 1
    with pytest.raises(PrefetchWorkerDied, match="exhausted"):
        pf.get(2)
    assert pf.close()
    # the genuinely-dead case: the worker exits without staging anything
    # (simulated hard death) — a loud, honest error, not a hang
    monkeypatch.setattr(RoundPrefetcher, "_run", lambda self: None)
    dead = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                           depth=2, start_step=0, stop_step=4).start()
    dead._thread.join(timeout=30)
    with pytest.raises(PrefetchWorkerDied, match="died before staging"):
        dead.get(0)
    assert dead.close()


@pytest.mark.timeout(120)
def test_shutdown_joins_cleanly_with_staged_window():
    """Abandoning a full in-flight window (consumer stops early) must
    join the worker within the deadline — the bounded-queue put polls the
    stop flag, so a full queue cannot deadlock shutdown."""
    cfg, sess, sampler = _session_and_sampler(mode="uncompressed")
    pf = RoundPrefetcher(session=sess, sampler=sampler, lr_fn=_lr_fn,
                         depth=3, start_step=0, stop_step=100).start()
    pf.get(0)  # worker is live and the window refills behind this
    assert pf.close(timeout=10.0), "prefetch worker failed to join"
    assert not pf._thread.is_alive()


@pytest.mark.timeout(120)
def test_runner_flight_dump_on_worker_fault(tmp_path):
    """The full-loop contract: a staging-worker fault crashes the shared
    runner, which drains the applied updates (true round indices in the
    ledger/flight) and dumps a flight record for the post-mortem — same
    forensics as a crash of the plain loop."""
    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.logging import MetricsWriter

    cfg = Config(**{**BASE, **ANCHOR, "mode": "uncompressed",
                    "telemetry_level": 1, "num_epochs": 1,
                    "perf_audit": False, "local_batch_size": 4})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    poisoned = _PoisonedSampler(sampler, 4, ValueError("bad round 4"))
    test_ds = ds  # never reached: the crash fires before epoch-end eval
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg)
    with pytest.raises(ValueError, match="bad round 4"):
        train_loop(cfg, sess, poisoned, test_ds, writer)
    writer.close()
    run_dir = tmp_path / "run"
    flights = list(run_dir.glob("flight_*.json"))
    assert flights, "worker fault must dump a flight record"
    rec = _checker().validate_flight(flights[0])
    assert "bad round 4" in rec["reason"]
    # the applied updates 0..3 were drained with their true indices
    assert [r["step"] for r in rec["records"]] == [0, 1, 2, 3]
    ledger = run_dir / "comm_ledger.json"
    assert _checker().validate_comm_ledger(ledger)["rounds"] == 4
