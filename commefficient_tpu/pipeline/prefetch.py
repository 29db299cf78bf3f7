"""RoundPrefetcher — realize round t+1..t+depth's host work off the
critical path.

One background worker thread walks the GLOBAL round index (the sampler,
the fedsim environment and the lr schedule are all pure functions of
``(seed, stream, round_idx)`` — epoch boundaries are bookkeeping, not
state), realizing one ``RoundWork`` per round:

  * the non-IID sampler draw + fused batch assembly (or the index-only
    form when the session holds device-resident data),
  * the fedavg microbatch reshape,
  * the fedsim ``RoundEnv`` (masks/chaos for that round),
  * the schedule lr,
  * eager H2D staging of the round's arrays onto the mesh
    (``FederatedSession.stage_round_payload`` / ``stage_round_indices`` —
    the session's own sharding objects, so the dispatch-time
    ``device_put`` is an identity).

Because every input is that pure function of the round index, prefetching
COMMUTES with execution: the RoundWork stream is bit-identical to what the
synchronous loop would have realized, in the same order (pinned by
tests/test_pipeline.py). The queue is bounded at ``depth`` items, so at
most ``depth`` rounds of batches are staged ahead (HBM bound:
depth x one round's batch bytes).

Fault discipline (the part that must never hang):

  * a worker-thread exception (corrupt batch, exhausted iterator, fedsim
    validation error, a failing H2D) is captured WITH its traceback and
    re-raised at the consuming round — ``get(step)`` is where the train
    loop sees it, and the runner's crash path then drains in-flight
    rounds + dumps the flight record exactly as for a synchronous crash;
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
import time
from typing import Any, NamedTuple, Optional

from commefficient_tpu.telemetry.spans import span_of


class RoundWork(NamedTuple):
    """One round's fully realized, staged inputs.

    Exactly one of ``batch`` (host-batch path: staged ``{k: [W, B, ...]}``
    device arrays, microbatch-reshaped for fedavg) and ``idx`` (index
    path: staged ``[W, B]`` int32 sample indices, with ``plan`` the staged
    augmentation plan) is set. ``env`` is the round's fedsim RoundEnv
    (None when the simulator is off). ``cohort`` is the staged
    clientstore StagedCohort — the cohort's hosted [W, D] vel/err device
    rows, gathered + H2D'd on this worker thread so the bank read
    overlaps the previous round's compute; None unless the session hosts
    client state (``--client_store host|mmap``). The dispatcher checks
    its staleness version and regathers if the same client was updated
    inside the pipeline window, so depth > 0 stays bit-exact. ``host_ms``
    is the wall-clock the worker spent realizing + staging this round —
    the host serial time the pipeline moved off the critical path."""

    step: int
    lr: float
    client_ids: Any  # host numpy [W] int32
    batch: Optional[dict]
    idx: Any
    plan: Any
    env: Any
    host_ms: float
    cohort: Any = None


_END = object()


class PrefetchWorkerDied(RuntimeError):
    """The prefetch worker exited without delivering the next item or an
    exception — a bug in the worker loop, surfaced instead of a hang."""


class RoundPrefetcher:
    """Bounded-depth background realization of ``RoundWork`` items.

    ``start_step``/``stop_step`` bound the global round range (a resumed
    run starts at its restored step). ``use_indices`` selects the
    device-resident index form. ``spans`` (a telemetry.PhaseSpans or
    None) gets the prefetch lane's ``prefetch_realize``/``prefetch_stage``
    spans on the WORKER thread's own track (thread-aware tids)."""

    def __init__(self, *, session, sampler, lr_fn, depth: int,
                 start_step: int = 0, stop_step: int = 0,
                 microbatches: int = 0, use_indices: bool = False,
                 spans=None, replay_until: int = 0):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.session = session
        self.sampler = sampler
        self.lr_fn = lr_fn
        self.depth = int(depth)
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        # resilience/ replay fence: rounds below it re-execute after a
        # divergence rollback, so their fedsim envs realize with
        # replay=True (transient nan_client injections suppressed —
        # fedsim/faults.py). The engine passes the session's replay
        # horizon when it restarts the window after a recovery.
        self.replay_until = int(replay_until)
        self.microbatches = int(microbatches)
        self.use_indices = bool(use_indices)
        self.spans = spans
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        # true staged-WORK count (the occupancy numerator): qsize would
        # also count the _END sentinel and queued worker exceptions,
        # over-reporting pipeline/occupancy at the window's tail
        self._staged = 0
        self._staged_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="round-prefetch", daemon=True
        )
        self._started = False

    # -- worker side -------------------------------------------------------
    def _span(self, name: str, step: int):
        from commefficient_tpu.telemetry.trace import round_trace_id

        # every prefetch span names the round it is REALIZING (schema
        # v11) — the Perfetto tree links this lane's work to the
        # dispatch-lane spans of the same round
        return span_of(self.spans, name, step,
                       trace_id=round_trace_id(step))

    def _realize(self, step: int) -> RoundWork:
        t0 = time.perf_counter()
        sess, L = self.session, self.microbatches
        with self._span("prefetch_realize", step):
            if self.use_indices:
                cids, idx, plan = self.sampler.sample_round_indices(step)
                batch = None
            else:
                cids, batch = self.sampler.sample_round(step)
                if L:  # fedavg [W, L, B/L, ...] convention
                    batch = {
                        k: v.reshape(v.shape[0], L, v.shape[1] // L,
                                     *v.shape[2:])
                        for k, v in batch.items()
                    }
                idx = plan = None
            env = (sess.fedsim_env.round_env(
                       step, replay=step < self.replay_until)
                   if sess.fedsim_env is not None else None)
            lr = float(self.lr_fn(step))
        with self._span("prefetch_stage", step):
            # eager H2D: round step's arrays start copying to the mesh NOW,
            # while the device still computes earlier rounds
            if self.use_indices:
                cids, idx, plan = sess.stage_round_indices(cids, idx, plan)
                cohort = None
            else:
                cids, batch = sess.stage_round_payload(cids, batch)
                # hosted client rows (clientstore/): bank gather + H2D
                # off the critical path too — None for device stores;
                # the gather span inherits this round's trace id
                if hasattr(sess, "stage_cohort_rows"):
                    from commefficient_tpu.telemetry.trace import (
                        round_trace_id,
                    )

                    cohort = sess.stage_cohort_rows(
                        cids, trace_id=round_trace_id(step))
                else:
                    cohort = None
        return RoundWork(
            step=step, lr=lr, client_ids=cids, batch=batch, idx=idx,
            plan=plan, env=env, host_ms=(time.perf_counter() - t0) * 1e3,
            cohort=cohort,
        )

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
                with self._staged_lock:
                    self._staged += 1
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
        """The next staged round, which MUST be ``step`` (the in-order
        contract — a mismatch means the caller and the worker disagree
        about the round clock, a bug worth failing on, not training on).
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
                    # fault, _END, or the round itself) in the instant
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
            # consuming round sees the true worker-side failure frames
            raise item
        if item.step != step:
            raise RuntimeError(
                f"prefetch order violated: staged round {item.step}, "
                f"consumer expected {step}"
            )
        with self._staged_lock:
            self._staged -= 1
        return item

    @property
    def staged_rounds(self) -> int:
        """Rounds of real WORK currently staged ahead (0..depth) — the
        occupancy numerator. Counts only RoundWork items (incremented
        after the worker's put, decremented at the consumer's get), so
        the _END sentinel / a queued worker exception never inflate the
        gauge at the window's tail."""
        with self._staged_lock:
            return min(max(self._staged, 0), self.depth)

    def close(self, timeout: float = 10.0) -> bool:
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
