"""The asyncfed staging plane: cohorts realized ahead of their launch.

The buffered-asynchronous engine (asyncfed/engine.py) launches cohorts,
and a cohort's host work is exactly a round's: sample the participants,
assemble the batch, realize the fedsim environment, stage the arrays onto
the mesh. ``RoundPrefetcher`` does that on one background worker thread,
up to ``depth`` items ahead; ``CohortScheduler`` is the same prefetcher
with the step axis read as the cohort index. Nothing but asyncfed/ feeds
through this module: the synchronous loop (train/runner.py) keeps
``data/sampler.py::prefetch``, a plain generator two items ahead with no
staging and no replay horizon.

The worker walks its index range in order (the sampler, the fedsim
environment and the lr schedule are all pure functions of
``(seed, stream, index)``), realizing one ``RoundWork`` per item:

  * the non-IID sampler draw + fused batch assembly,
  * the fedavg microbatch reshape,
  * the fedsim ``RoundEnv`` (masks/chaos for that index),
  * the schedule lr,
  * eager H2D staging of the arrays onto the mesh
    (``FederatedSession.stage_round_payload`` — the session's own
    sharding objects, so the dispatch-time ``device_put`` is an identity).

Because every input is that pure function of the index, prefetching
COMMUTES with execution: the RoundWork stream is bit-identical to what a
synchronous realization would produce, in the same order (pinned by
tests/test_asyncfed_staging.py). The queue is bounded at ``depth`` items,
so at most ``depth`` batches are staged ahead (HBM bound: depth x one
round's batch bytes).

Fault discipline (the part that must never hang):

  * a worker-thread exception (corrupt batch, exhausted iterator, fedsim
    validation error, a failing H2D) is captured WITH its traceback and
    re-raised at the consuming ``get(step)``, where the engine — and
    through it the runner's crash path, which drains the dispatched
    rounds and dumps the flight record — sees it;
  * ``get`` polls with a timeout and fails loudly if the worker died
    without enqueueing anything (a bug, not a wait);
  * ``close`` drains the queue, signals stop, and joins the worker; the
    worker's bounded-queue puts poll the stop flag (the
    data/sampler.prefetch discipline), so shutdown cannot deadlock on a
    full queue.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, NamedTuple, Optional, Sequence

from commefficient_tpu.telemetry.spans import span_of


class RoundWork(NamedTuple):
    """One item's fully realized, staged inputs: the staged
    ``{k: [W, B, ...]}`` device batch (microbatch-reshaped for fedavg),
    the host ``[W]`` client ids, the schedule lr and the fedsim RoundEnv
    (None when the simulator is off)."""

    step: int
    lr: float
    client_ids: Any  # host numpy [W] int32
    batch: dict
    env: Any


_END = object()


class PrefetchWorkerDied(RuntimeError):
    """The prefetch worker exited without delivering the next item or an
    exception — a bug in the worker loop, surfaced instead of a hang."""


class RoundPrefetcher:
    """Bounded-depth background realization of ``RoundWork`` items.

    ``start_step``/``stop_step`` bound the index range (a resumed run
    starts at its restored step). ``spans`` (a telemetry.PhaseSpans or
    None) gets the prefetch lane's ``prefetch_realize``/``prefetch_stage``
    spans on the WORKER thread's own track (thread-aware tids)."""

    def __init__(self, *, session, sampler, lr_fn, depth: int,
                 start_step: int = 0, stop_step: int = 0,
                 microbatches: int = 0, spans=None, replay_until: int = 0):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.session = session
        self.sampler = sampler
        self.lr_fn = lr_fn
        self.depth = int(depth)
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        # resilience/ replay fence: items below it re-execute after a
        # divergence rollback, so their fedsim envs realize with
        # replay=True (transient nan_client injections suppressed —
        # fedsim/faults.py). The engine passes its replay horizon when it
        # restarts the window after a recovery.
        self.replay_until = int(replay_until)
        self.microbatches = int(microbatches)
        self.spans = spans
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="round-prefetch", daemon=True
        )
        self._started = False

    # -- worker side -------------------------------------------------------
    def _span(self, name: str, step: int):
        from commefficient_tpu.telemetry.trace import round_trace_id

        # every prefetch span names the item it is REALIZING (schema
        # v11) — the Perfetto tree links this lane's work to the
        # dispatch-lane spans of the same index
        return span_of(self.spans, name, step,
                       trace_id=round_trace_id(step))

    def _realize(self, step: int) -> RoundWork:
        sess, L = self.session, self.microbatches
        with self._span("prefetch_realize", step):
            cids, batch = self.sampler.sample_round(step)
            if L:  # fedavg [W, L, B/L, ...] convention
                batch = {
                    k: v.reshape(v.shape[0], L, v.shape[1] // L,
                                 *v.shape[2:])
                    for k, v in batch.items()
                }
            env = (sess.fedsim_env.round_env(
                       step, replay=step < self.replay_until)
                   if sess.fedsim_env is not None else None)
            lr = float(self.lr_fn(step))
        with self._span("prefetch_stage", step):
            # eager H2D: the arrays start copying to the mesh NOW, while
            # the device still computes earlier launches
            cids, batch = sess.stage_round_payload(cids, batch)
        return RoundWork(step=step, lr=lr, client_ids=cids, batch=batch,
                         env=env)

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            if self.spans is not None:
                # name this worker's span track (schema v5 thread_name
                # metadata) so the prefetch lane renders labeled
                self.spans.register_lane("round-prefetch")
            for step in range(self.start_step, self.stop_step):
                if self._stop.is_set():
                    return
                if not self._put(self._realize(step)):
                    return
            self._put(_END)
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            self._put(e)

    # -- consumer side -----------------------------------------------------
    def start(self) -> "RoundPrefetcher":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def get(self, step: int) -> RoundWork:
        """The next staged item, which MUST be ``step`` (the in-order
        contract — a mismatch means the caller and the worker disagree
        about the clock, a bug worth failing on, not training on).
        Re-raises a worker exception with its original traceback; raises
        ``PrefetchWorkerDied`` instead of hanging if the worker is gone."""
        if not self._started:
            raise RuntimeError("RoundPrefetcher.get before start()")
        while True:
            try:
                item = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # the worker may have enqueued its final item (the
                    # fault, _END, or the item itself) in the instant
                    # between our timeout and this liveness check — drain
                    # once more before declaring it dead, else the real
                    # worker exception would be masked by this generic one
                    try:
                        item = self._q.get_nowait()
                        break
                    except queue.Empty:
                        raise PrefetchWorkerDied(
                            f"prefetch worker died before staging round "
                            f"{step} (no item, no exception) — see the "
                            "worker thread's stderr for the real failure"
                        ) from None
        if item is _END:
            raise PrefetchWorkerDied(
                f"prefetch exhausted at round {step}: the worker covered "
                f"[{self.start_step}, {self.stop_step}) and the consumer "
                "asked past it"
            )
        if isinstance(item, BaseException):
            # the original traceback rides on the exception object — the
            # consumer sees the true worker-side failure frames
            raise item
        if item.step != step:
            raise RuntimeError(
                f"prefetch order violated: staged round {item.step}, "
                f"consumer expected {step}"
            )
        return item

    def close(self, timeout: Optional[float] = 10.0) -> bool:
        """Stop the worker and join it; returns True iff the join
        completed. Drains the queue so a worker blocked on a full queue
        wakes immediately (its puts also poll the stop flag)."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._started:
            self._thread.join(timeout=timeout)
            return not self._thread.is_alive()
        return True


class CohortScheduler:
    """In-order cohort realization for the asyncfed engine: a
    ``RoundPrefetcher`` whose step axis is the cohort index. The one
    cohort-specific twist is the learning rate,
    ``lr_fn(launch_version[cohort])``: the server version the cohort
    snapshots at launch, NOT the cohort index (under concurrency C > 1 a
    cohort's launch version lags its index). Keeping ``depth >= C``
    cohorts staged ahead is what lets the engine keep C cohorts in flight
    with no host work on the critical path."""

    def __init__(self, *, session, sampler, lr_fn,
                 launch_versions: Sequence[int], start_cohort: int = 0,
                 stop_cohort: int, depth: int, microbatches: int = 0,
                 spans=None, replay_until: int = 0):
        versions = tuple(int(v) for v in launch_versions)

        def cohort_lr(c: int) -> float:
            return float(lr_fn(versions[c]))

        self._prefetcher = RoundPrefetcher(
            session=session,
            sampler=sampler,
            lr_fn=cohort_lr,
            depth=max(1, int(depth)),
            start_step=int(start_cohort),
            stop_step=int(stop_cohort),
            microbatches=microbatches,
            spans=spans,
            replay_until=int(replay_until),
        )

    def start(self) -> "CohortScheduler":
        self._prefetcher.start()
        return self

    def get(self, cohort: int) -> RoundWork:
        """Blocking in-order fetch of cohort ``cohort``'s realized work
        (``RoundWork`` with ``step`` == the cohort index)."""
        return self._prefetcher.get(cohort)

    def close(self, timeout: Optional[float] = 10.0) -> None:
        self._prefetcher.close(timeout)
