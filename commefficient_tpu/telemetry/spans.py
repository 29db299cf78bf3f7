"""Host-side phase spans — where does the wall-clock of a round GO?

The ``StepProfiler`` answers "what is the device doing" (a real XLA trace);
nothing answered "what is the HOST doing around it" — data load, fedsim
environment realization, device_put, round dispatch, metric drain,
checkpoint writes. Those phases are exactly where a run loses time
invisibly (an H2D batch copy is a host phase, not a device op).
``PhaseSpans`` records them as Chrome-trace/Perfetto "complete" events and
dumps ``spans_<step>.json`` into the run dir, loadable in
``chrome://tracing`` / https://ui.perfetto.dev next to the StepProfiler's
XLA traces.

Fencing discipline (the part that keeps level >= 1 cheap): host timestamps
are recorded for EVERY round — two ``perf_counter`` calls and a dict per
span, no device interaction — but the round-dispatch span only *fences*
(``utils.profiling.fence``: block_until_ready) inside a short
steady-state window, the same ``MIN_WARMUP_STEPS``-clamped
window the StepProfiler uses. Outside the window the dispatch span
honestly measures dispatch (async enqueue) time; inside it, the fenced
span is the real per-round device+host latency. At telemetry level 0 the
train loops construct no recorder at all — zero host work, and nothing in
the jitted program either way (spans are pure host code).

Thread-awareness (schema v5): spans record the CALLING thread as a small
lane id in ``tid`` — the constructing thread is lane 0, every other
thread gets the next lane on first use — so the asyncfed staging worker's
``prefetch_realize``/``prefetch_stage`` spans render as their own
Perfetto track instead of interleaving with the dispatch spans on one
line. ``register_lane(name)`` additionally emits a Chrome-trace
``thread_name`` metadata event so the track is labeled. ``wrap_iter``
still times the CONSUMING thread's ``next()`` — with a threaded producer
that is honestly the consumer's wait (stall), while the producer's own
work now shows on its lane; pre-v5 dumps conflated the two on tid 0.
Recording is thread-safe (lock-guarded lane map; deque appends are
atomic); spans from a worker thread should pass ``step=`` explicitly —
the shared round clock belongs to the consuming thread.

Collective exposure (schema v9): spans that bracket a phase whose device
program waits on a cross-chip collective pass ``collective=True`` — the
event's args gain ``"collective": true`` and ``collective_exposure_ms()``
computes the union of collective-span intervals NOT covered by any other
(compute) span. That difference is the host-visible stall a collective
causes when nothing overlaps it; ``overlap_collectives='layerwise'`` and
``async_double_buffer`` exist to shrink it. The dump carries the number
as a top-level ``"exposed_collective_ms"`` field so the audit's
spans×HLO cross-check (telemetry/xla_audit.py ``exposed_collective_ms``)
can gate it on the compiled programs actually containing collectives.

Trace correlation (schema v11): spans may carry ``trace_id`` — the
owning round's or cohort's id (telemetry/trace.py mints them:
``r<step>`` for rounds, ``c<cohort>`` for async cohorts) — and
``parent`` (the trace id this one causally descends from, e.g. a
cohort's launching round). With all four planes (prefetch, clientstore
writeback, asyncfed, dispatch) stamping their spans, a Perfetto dump
renders each cohort as a causally-linked tree across lanes, and the
``CriticalPath`` analyzer can attribute a round's wall-clock to the
stage that bound it. ``span_at`` records a span RETROACTIVELY from
explicit perf_counter endpoints — the async engine only knows a
cohort's buffer-residency interval when the cohort retires.

The profiler's clock: every span recorded here, and every session phase
when no recorder is attached (telemetry level 0), is also opened as a
``jax.profiler.TraceAnnotation`` named ``fed/<name>`` with the round as
its ``round`` argument (``host_span``). While a profiler trace is open
(``--profile_dir``/``--profile_rounds``, the benchmark's ``--trace 1``)
the spans land on its host plane, on the one clock the device ops are on,
so a device gap can be laid against what the host was doing; with no trace
open an annotation costs a flag test. Every optional-span site goes
through ``span_of``, so the engines' lanes are annotated at level 0 too.
``span_at`` records after the fact and so cannot be annotated. The ring,
the fencing window and the dump below stay as they are, at level >= 1
only.

Format: ``{"schema_version", "kind": "spans", "displayTimeUnit",
"exposed_collective_ms", "traceEvents": [{"name", "ph": "X", "ts",
"dur", "pid", "tid", "args": {"step", "fenced"[, "collective"]
[, "trace_id"][, "parent"]}} |
{"name": "thread_name", "ph": "M", "pid", "tid", "args": {"name"}}]}``
— ts/dur in microseconds since the recorder was constructed (Chrome
trace convention). Validated by scripts/check_telemetry_schema.py
(schema v3; "M" thread-name metadata events since v5;
``exposed_collective_ms`` since v9; ``trace_id``/``parent`` args since
v11).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

import jax

from commefficient_tpu.utils.profiling import MIN_WARMUP_STEPS

# ring bound on recorded events: a long run records ~4-6 events per round;
# the most recent ~1.3k rounds of host phases are plenty for a post-mortem
# and keep the dump a few hundred KB at worst
MAX_EVENTS = 8192


@contextmanager
def host_span(name: str, step: Optional[int] = None):
    """One host phase as the profiler sees it: ``fed/<name>`` on the host
    plane of whatever trace is open, stamped with the round where the site
    knows it. Yields None, like a disabled recorder's ``span``."""
    args = {} if step is None else {"round": int(step)}
    with jax.profiler.TraceAnnotation(f"fed/{name}", **args):
        yield None


def span_of(spans, name: str, step: Optional[int] = None, **kw):
    """The one shape of every optional-span site (session, engines,
    runner): the recorder's span where a train loop attached one, the bare
    annotation where it did not (telemetry level 0)."""
    if spans is None:
        return host_span(name, step)
    return spans.span(name, step=step, **kw)


def wrap_iter(it, name: str = "data_load", span=host_span):
    """Yield from ``it`` with each ``next()`` (the wait for the round
    source) inside ``span(name)``: a recorder's ``span``, or the bare
    annotation for a loop that was handed none."""
    it = iter(it)
    while True:
        with span(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


class _SpanHandle:
    """Yielded by ``PhaseSpans.span``: lets the block arm a fence on a
    value it only produces mid-block (the dispatched round's metrics)."""

    __slots__ = ("fence_target",)

    def __init__(self):
        self.fence_target = None

    def fence(self, x) -> None:
        self.fence_target = x


class PhaseSpans:
    """Chrome-trace span recorder for the train loop's host phases.

    Inert when ``logdir`` is falsy (the train loops pass "" below
    telemetry level 1). ``step(i)`` marks round starts (drives the fenced
    window); ``span(name, fence=...)`` brackets one phase; ``wrap_iter``
    times an iterator's ``next()`` (the data-load phase); ``close()``
    dumps ``spans_<step>.json``.
    """

    def __init__(self, logdir: str, start_step: int = 5, num_steps: int = 3):
        self.logdir = logdir
        self.enabled = bool(logdir)
        self.start = max(start_step, MIN_WARMUP_STEPS)
        self.stop_at = self.start + num_steps
        self._step = -1
        self._t0 = time.perf_counter()
        self.events: deque = deque(maxlen=MAX_EVENTS)
        self._first_step: Optional[int] = None
        self._dumped: Optional[str] = None
        # thread -> lane map (the constructing thread is lane 0): spans
        # from other threads (the asyncfed staging worker) get their own
        # Perfetto track instead of interleaving with dispatch spans
        self._lanes = {threading.get_ident(): 0}
        self._lane_lock = threading.Lock()
        # lane-label metadata lives OUTSIDE the bounded ring: a long run's
        # span events must not evict the thread_name records (one per
        # lane, emitted once) or the dumped tracks render unlabeled
        self._meta_events = []

    def _lane(self) -> int:
        ident = threading.get_ident()
        lane = self._lanes.get(ident)
        if lane is None:
            with self._lane_lock:
                lane = self._lanes.setdefault(ident, len(self._lanes))
        return lane

    def register_lane(self, name: str) -> int:
        """Name the CALLING thread's track (a Chrome-trace ``thread_name``
        metadata event; schema v5) and return its lane id. Worker threads
        (the asyncfed staging worker, the clientstore writeback) call this
        once at startup."""
        lane = self._lane()
        if self.enabled:
            self._meta_events.append({
                "name": "thread_name", "ph": "M", "pid": 0, "tid": lane,
                "args": {"name": name},
            })
        return lane

    # -- round clock -------------------------------------------------------
    def step(self, step_idx: int) -> None:
        self._step = int(step_idx)
        if self.enabled and self._first_step is None:
            self._first_step = self._step

    @property
    def in_window(self) -> bool:
        """True while fenced dispatch spans are wanted (steady-state
        window, post compile+warmup — same clamp as StepProfiler)."""
        return self.start <= self._step < self.stop_at

    def resume_at(self, resume_step: int) -> None:
        """Shift the fenced window past a checkpoint resume (the resumed
        process recompiles from scratch; mirrors StepProfiler.resume_at)."""
        floor = resume_step + MIN_WARMUP_STEPS
        if floor > self.start:
            n = self.stop_at - self.start
            self.start, self.stop_at = floor, floor + n

    # -- recording ---------------------------------------------------------
    @contextmanager
    def span(self, name: str, fence=None, step: Optional[int] = None,
             collective: bool = False, trace_id: Optional[str] = None,
             parent: Optional[str] = None):
        """Record one phase. Yields a handle whose ``fence(x)`` arms a
        scalar-fetch sync on ``x`` before the span closes (for targets only
        known inside the block, e.g. the dispatched round's metrics);
        ``fence=`` arms it up front. The sync only actually runs inside the
        steady-state window, so per-round overhead outside it stays at two
        perf_counter calls. ``step=`` stamps the event with an explicit
        round index — worker-thread spans (the prefetch lane) pass the
        round they are REALIZING; the shared ``step()`` clock belongs to
        the consuming thread. ``collective=True`` tags the span as waiting
        on a cross-chip collective — ``collective_exposure_ms()`` then
        charges any part of it not covered by another span as exposed
        (un-overlapped) collective time. ``trace_id=``/``parent=`` stamp
        the owning round/cohort ids (schema v11; telemetry/trace.py mints
        them). Yields None when disabled. Either way the phase is also
        one ``fed/<name>`` annotation in the profiler's trace
        (``host_span``)."""
        with host_span(name, self._step if step is None else step):
            if not self.enabled:
                yield None
                return
            h = _SpanHandle()
            h.fence_target = fence
            t0 = time.perf_counter()
            fenced = False
            try:
                yield h
                if h.fence_target is not None and self.in_window:
                    from commefficient_tpu.utils.profiling import (
                        fence as _fence,
                    )

                    _fence(h.fence_target)
                    fenced = True
            finally:
                t1 = time.perf_counter()
                self._record(name, t0, t1, step=step, fenced=fenced,
                             collective=collective, trace_id=trace_id,
                             parent=parent)

    def span_at(self, name: str, t0_s: float, t1_s: float,
                step: Optional[int] = None, collective: bool = False,
                trace_id: Optional[str] = None,
                parent: Optional[str] = None) -> None:
        """Record a span RETROACTIVELY from explicit ``perf_counter``
        endpoints (seconds, same clock as the recorder's). The asyncfed
        engine measures a cohort's buffer residency this way: the start is
        captured at launch, but the interval only becomes a span when the
        cohort's last share is consumed. No-op when disabled."""
        if not self.enabled:
            return
        self._record(name, float(t0_s), float(t1_s), step=step,
                     fenced=False, collective=collective,
                     trace_id=trace_id, parent=parent)

    def _record(self, name, t0, t1, *, step, fenced, collective,
                trace_id, parent) -> None:
        args = {"step": self._step if step is None else int(step),
                "fenced": fenced}
        if collective:
            args["collective"] = True
        if trace_id is not None:
            args["trace_id"] = str(trace_id)
            if parent is not None:
                args["parent"] = str(parent)
        self.events.append({
            "name": name,
            "ph": "X",
            "ts": (t0 - self._t0) * 1e6,
            "dur": (t1 - t0) * 1e6,
            "pid": 0,
            "tid": self._lane(),
            "args": args,
        })

    def wrap_iter(self, it, name: str = "data_load"):
        """Yield from ``it``, recording each ``next()`` as one span (the
        data-load/prefetch-wait phase). With a threaded producer this
        charges only the CONSUMING thread's wait to this span — which is
        the honest reading; the producer's own work lands on its own lane
        (``register_lane``) instead of being conflated into this track.
        A disabled recorder records nothing and still annotates, as
        ``span`` does."""
        return wrap_iter(it, name, self.span)

    # -- collective exposure -----------------------------------------------
    def collective_exposure_ms(self) -> float:
        """Wall-clock (ms) spent inside ``collective=True`` spans and NOT
        covered by any other recorded span — the un-overlapped (exposed)
        part of the collective waits. Interval arithmetic over the event
        ring: union the collective spans, union the compute spans,
        measure the set difference. 0.0 when nothing is tagged."""
        coll, comp = [], []
        for ev in self.events:
            if ev.get("ph") != "X":
                continue
            iv = (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]))
            if ev.get("args", {}).get("collective"):
                coll.append(iv)
            else:
                comp.append(iv)
        if not coll:
            return 0.0

        def union(ivs):
            out = []
            for a, b in sorted(ivs):
                if out and a <= out[-1][1]:
                    out[-1][1] = max(out[-1][1], b)
                else:
                    out.append([a, b])
            return out

        comp_u = union(comp)
        exposed_us = 0.0
        for a, b in union(coll):
            cur = a
            for ca, cb in comp_u:
                if cb <= cur:
                    continue
                if ca >= b:
                    break
                if ca > cur:
                    exposed_us += ca - cur
                cur = max(cur, cb)
                if cur >= b:
                    break
            if cur < b:
                exposed_us += b - cur
        return exposed_us / 1000.0

    # -- dump --------------------------------------------------------------
    def dump(self) -> Optional[str]:
        """Write ``spans_<step>.json`` (step = first recorded round);
        returns the path, or None when disabled/empty."""
        if not self.enabled or not self.events:
            return None
        os.makedirs(self.logdir, exist_ok=True)
        from commefficient_tpu.telemetry import SCHEMA_VERSION, jsonable_tree

        step = self._first_step if self._first_step is not None else 0
        path = os.path.join(self.logdir, f"spans_{step}.json")
        payload = {
            "schema_version": SCHEMA_VERSION,
            "kind": "spans",
            "displayTimeUnit": "ms",
            "window": [self.start, self.stop_at],
            "exposed_collective_ms": self.collective_exposure_ms(),
            "traceEvents": self._meta_events + list(self.events),
        }
        with open(path, "w") as f:
            json.dump(jsonable_tree(payload), f, allow_nan=False)
        self._dumped = path
        return path

    def close(self) -> Optional[str]:
        return self.dump()
