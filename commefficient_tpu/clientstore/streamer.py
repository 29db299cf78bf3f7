"""CohortStreamer — host bank <-> device cohort rows, off the round's
critical path.

One streamer per hosted session owns the vel/err stores (``store.py``),
the optional LRU device cache (``cache.py``), and the async writeback
worker. Its contract with the round:

  * ``gather(cids) -> StagedCohort`` — the cohort's [n, D] device rows
    per bank (``()`` for an absent bank, the round extras convention),
    assembled cache-first and staged H2D via the session's
    ``stage_fn``. The round calls it just before its dispatch; it waits
    on any pending writeback that touches the same clients, so the rows
    are always the previous round's.
  * ``scatter(cids, new_vel, new_err)`` — the round's updated rows.
    Cache on: rows land in the device cache dirty (write-through on
    eviction keeps the bank honest). Cache off: the writeback worker
    syncs D2H and scatters into the bank ASYNCHRONOUSLY — the host loop
    never waits on the previous round's writeback.
  * ``flush()`` — the drain fence: joins pending writebacks and writes
    dirty cache rows through, so checkpoint saves / vault snapshots /
    whole-bank reads observe every completed round.

A writeback fault is stored and re-raised at the next gather/flush.
Per-round ``clientstore/*`` scalars (cache hit rate, evictions, H2D stage ms,
writeback ms) accumulate here and drain via ``pop_round_stats``.

Trace correlation (schema v11): when the session attaches a PhaseSpans
recorder (its ``spans`` setter forwards here), gather/writeback/flush
record spans — ``clientstore_gather`` on the calling thread,
``clientstore_writeback`` on the worker's own
labeled lane, ``clientstore_flush`` on the fencing thread — and
gather/scatter accept the owning round's ``trace_id`` from the caller
(the streamer has no round clock of its own), so a Perfetto dump links
a cohort's H2D stage and its async writeback to the round that owned
them. ``spans=None`` (the default, and every level-0 run) keeps all of
it on the zero-cost fast path.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, NamedTuple, Optional

import numpy as np

from commefficient_tpu.clientstore.cache import LRURowCache
from commefficient_tpu.clientstore.store import build_store

_END = object()


class StagedCohort(NamedTuple):
    """A realized cohort payload: per-bank device rows (or ``()``)."""

    vel: Any
    err: Any


class _WriteEntry:
    __slots__ = ("ids", "idset", "vel", "err", "done", "trace_id")

    def __init__(self, ids, vel, err, trace_id=None):
        self.ids = ids
        self.idset = set(int(i) for i in ids)
        self.vel = vel
        self.err = err
        self.done = threading.Event()
        # owning round's trace id (schema v11): the worker stamps its
        # clientstore_writeback span with it, so the async write renders
        # in the round's causal tree even though it runs rounds later
        self.trace_id = trace_id


class CohortStreamer:
    def __init__(self, *, vel_store=None, err_store=None, num_clients: int,
                 cache_rows: int = 0, stage_fn=None):
        if vel_store is None and err_store is None:
            raise ValueError("streamer needs at least one bank")
        self.vel_store = vel_store
        self.err_store = err_store
        self.num_clients = int(num_clients)
        # stage_fn: host [n, D] (or a device array to re-pin) -> device
        # array under the session's batch sharding; identity for tests
        self._stage = stage_fn if stage_fn is not None else (lambda x: x)
        self._lock = threading.Lock()
        self._pending: list = []
        self._fault: Optional[BaseException] = None
        self._q: queue.Queue = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        self._cache = (LRURowCache(cache_rows, self._cache_writeback)
                       if cache_rows else None)
        # per-round telemetry accumulators (pop_round_stats drains them)
        self._stage_ms = 0.0
        self._writeback_ms = 0.0
        self._hits0 = self._misses0 = self._evictions0 = 0
        # PhaseSpans recorder — the session's ``spans`` setter forwards
        # its attachment here; None keeps every span site zero-cost
        self.spans = None
        self._worker_lane_named = False

    # ------------------------------------------------------------------
    # writeback machinery
    def _cache_writeback(self, cid, pair) -> None:
        """Eviction/flush write-through of one cached (vel, err) row
        pair. Runs under the streamer lock (the cache is only touched
        there); the D2H sync is the price of eviction."""
        t0 = time.perf_counter()
        vel_row, err_row = pair
        if vel_row is not None:
            self.vel_store.scatter_rows([cid], np.asarray(vel_row)[None])
        if err_row is not None:
            self.err_store.scatter_rows([cid], np.asarray(err_row)[None])
        self._writeback_ms += (time.perf_counter() - t0) * 1e3

    def _ensure_worker(self) -> None:
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._worker_loop, name="clientstore-writeback",
                daemon=True)
            self._worker.start()

    def _worker_loop(self) -> None:
        while True:
            e = self._q.get()
            if e is _END:
                return
            try:
                t0 = time.perf_counter()
                # np.asarray blocks on the device computation that
                # produced the rows — exactly the wait the async worker
                # exists to take off the host loop
                if e.vel is not None:
                    self.vel_store.scatter_rows(e.ids, np.asarray(e.vel))
                if e.err is not None:
                    self.err_store.scatter_rows(e.ids, np.asarray(e.err))
                t1 = time.perf_counter()
                with self._lock:
                    self._writeback_ms += (t1 - t0) * 1e3
                self._record_writeback_span(e, t0, t1)
            except BaseException as exc:  # noqa: BLE001 — re-raised at the consumer
                with self._lock:
                    self._fault = exc
            finally:
                with self._lock:
                    if e in self._pending:
                        self._pending.remove(e)
                e.done.set()

    def _record_writeback_span(self, e, t0: float, t1: float) -> None:
        """Stamp one ``clientstore_writeback`` span on the worker's own
        labeled lane (schema v11) — retroactive ``span_at`` because the
        interval is already over when we know it completed cleanly."""
        spans = self.spans
        if spans is None:
            return
        if not self._worker_lane_named:
            spans.register_lane("clientstore-writeback")
            self._worker_lane_named = True
        from commefficient_tpu.telemetry.trace import step_of_trace_id

        spans.span_at("clientstore_writeback", t0, t1,
                      step=step_of_trace_id(e.trace_id),
                      trace_id=e.trace_id)

    def _raise_fault(self) -> None:
        with self._lock:
            fault, self._fault = self._fault, None
        if fault is not None:
            raise RuntimeError(
                "clientstore writeback worker died; client state may be "
                "behind — failing the run") from fault

    # ------------------------------------------------------------------
    # the cohort contract
    @property
    def has_vel(self) -> bool:
        return self.vel_store is not None

    @property
    def has_err(self) -> bool:
        return self.err_store is not None

    def gather(self, cids, trace_id=None) -> StagedCohort:
        """Realize the cohort's device rows (cache-first, then bank).
        ``trace_id=`` stamps the ``clientstore_gather`` span with the
        owning round (schema v11) — the caller knows it, we don't."""
        self._raise_fault()
        ids = np.asarray(cids).reshape(-1)
        idset = set(int(i) for i in ids)
        with self._lock:
            cached = {}
            if self._cache is not None:
                for pos, cid in enumerate(int(i) for i in ids):
                    pair = self._cache.get(cid)
                    if pair is not None:
                        cached[pos] = pair
            missing = [p for p in range(len(ids)) if p not in cached]
            waits = [e for e in self._pending
                     if e.idset & idset] if missing else []
        for e in waits:
            e.done.wait()
        self._raise_fault()
        t0 = time.perf_counter()
        vel = self._assemble(self.vel_store, ids, missing, cached, bank=0)
        err = self._assemble(self.err_store, ids, missing, cached, bank=1)
        t1 = time.perf_counter()
        with self._lock:
            self._stage_ms += (t1 - t0) * 1e3
        spans = self.spans
        if spans is not None:
            from commefficient_tpu.telemetry.trace import step_of_trace_id

            spans.span_at("clientstore_gather", t0, t1,
                          step=step_of_trace_id(trace_id),
                          trace_id=trace_id)
        return StagedCohort(vel, err)

    def _assemble(self, store, ids, missing, cached, bank):
        if store is None:
            return ()
        block = np.zeros((len(ids), store.row_dim), np.float32)
        if missing:
            block[missing] = store.gather_rows(ids[missing])
        dev = self._stage(block)
        hot = [(p, pair[bank]) for p, pair in cached.items()
               if pair[bank] is not None]
        if hot:
            if hasattr(dev, "at"):  # jax: splice cached DEVICE rows in
                for pos, row in hot:
                    dev = dev.at[pos].set(row)
                dev = self._stage(dev)  # re-pin the batch sharding
            else:  # identity stage_fn (tests): plain numpy block
                for pos, row in hot:
                    dev[pos] = np.asarray(row)
        return dev

    def scatter(self, cids, new_vel, new_err, trace_id=None) -> None:
        """Write the round's updated rows back (per-bank ``()``/None for
        absent banks). Returns immediately; ``flush()`` is the fence.
        ``trace_id=`` rides the write entry so the async worker's
        ``clientstore_writeback`` span names its owning round."""
        self._raise_fault()
        ids = np.asarray(cids).reshape(-1)
        # an absent bank's return slot is () or a [W, 1] zeros placeholder
        # (the round extras convention) — either way there is no store to
        # scatter into, so drop it here
        vel = new_vel if (self.vel_store is not None and new_vel is not None
                          and not isinstance(new_vel, tuple)) else None
        err = new_err if (self.err_store is not None and new_err is not None
                          and not isinstance(new_err, tuple)) else None
        with self._lock:
            if self._cache is not None:
                for pos, cid in enumerate(int(i) for i in ids):
                    self._cache.put(
                        cid,
                        (vel[pos] if vel is not None else None,
                         err[pos] if err is not None else None),
                        dirty=True)
                return
            entry = _WriteEntry(ids, vel, err, trace_id=trace_id)
            self._pending.append(entry)
            self._ensure_worker()
        self._q.put(entry)

    def flush(self) -> None:
        """The drain fence: join pending writebacks and write dirty
        cache rows through — after it the banks hold every completed
        round's rows (checkpoint save / vault snapshot / whole-bank
        reads all fence here). Recorded as a ``clientstore_flush`` span
        on the fencing thread (no trace id — a flush fences ALL pending
        rounds, it belongs to none of them)."""
        t0 = time.perf_counter()
        with self._lock:
            waits = list(self._pending)
        for e in waits:
            e.done.wait()
        self._raise_fault()
        with self._lock:
            if self._cache is not None:
                self._cache.flush()
        for store in (self.vel_store, self.err_store):
            if store is not None:
                store.flush()
        if self.spans is not None:
            self.spans.span_at("clientstore_flush", t0, time.perf_counter())

    # ------------------------------------------------------------------
    # whole-bank access (checkpoint / vault) — callers fence via the
    # session's host_vel/host_err properties, which flush() first
    def vel_array(self):
        return None if self.vel_store is None else self.vel_store.array()

    def err_array(self):
        return None if self.err_store is None else self.err_store.array()

    def load_vel(self, arr) -> None:
        self._load(self.vel_store, arr)

    def load_err(self, arr) -> None:
        self._load(self.err_store, arr)

    def _load(self, store, arr) -> None:
        if store is None:
            raise ValueError("no such bank in this streamer")
        # drain first: a pending writeback landing AFTER the load would
        # resurrect pre-restore rows over the restored bank
        self.flush()
        store.load(arr)
        with self._lock:
            if self._cache is not None:
                self._cache.invalidate()

    # ------------------------------------------------------------------
    def pop_round_stats(self) -> dict:
        """Drain the per-round ``clientstore/*`` scalars (constant key
        set — pack_metric_dicts requires it)."""
        with self._lock:
            if self._cache is not None:
                dh = self._cache.hits - self._hits0
                dm = self._cache.misses - self._misses0
                de = self._cache.evictions - self._evictions0
                self._hits0 = self._cache.hits
                self._misses0 = self._cache.misses
                self._evictions0 = self._cache.evictions
            else:
                dh = dm = de = 0
            out = {
                "clientstore/cache_hit_rate":
                    float(dh) / (dh + dm) if (dh + dm) else 0.0,
                "clientstore/evictions": float(de),
                "clientstore/h2d_stage_ms": self._stage_ms,
                "clientstore/writeback_ms": self._writeback_ms,
            }
            self._stage_ms = 0.0
            self._writeback_ms = 0.0
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self.flush()
        finally:
            if self._worker is not None:
                self._q.put(_END)
                self._worker.join(timeout=30)
                self._worker = None
            for store in (self.vel_store, self.err_store):
                if store is not None:
                    store.close()


def build_streamer(cfg, row_dim: int, *, needs_vel: bool, needs_err: bool,
                   stage_fn=None) -> Optional[CohortStreamer]:
    """The ONE construction gate: None unless the config hosts client
    state AND a bank is needed — ``client_store='device'`` (the default)
    constructs NOTHING (level-0 HLO and golden parity bit-untouched)."""
    if not cfg.client_state_hosted or not (needs_vel or needs_err):
        return None

    def mk(tag):
        path = ""
        if cfg.client_store == "mmap" and cfg.client_store_path:
            path = f"{cfg.client_store_path}.{tag}"
        return build_store(cfg.client_store, num_rows=cfg.num_clients,
                           row_dim=row_dim, path=path)

    return CohortStreamer(
        vel_store=mk("vel") if needs_vel else None,
        err_store=mk("err") if needs_err else None,
        num_clients=cfg.num_clients,
        cache_rows=cfg.client_store_cache_rows,
        stage_fn=stage_fn,
    )
