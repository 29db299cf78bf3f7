"""AsyncFederation — the buffered-asynchronous round engine.

One engine step == one SERVER UPDATE (the runner's ``(step, lr, metrics)``
unit stays a round, so the drain/checkpoint/crash scaffold is untouched).
Per update ``u`` the engine:

1. launches the cohorts ``AsyncSchedule.updates[u].launches_before``
   scripts — each realized in cohort order by a ``CohortScheduler``
   (asyncfed/staging.py) and dispatched through the active rung's
   ``launch_fn`` against the CURRENT params (server version ``u``);
2. assembles the update's K consumed ``(cohort, slot)`` contributions
   (canonical order — see asyncfed/schedule.py) into fixed [W, ...]
   buffers, padding with zero-weight repeats so every apply at any
   buffer fill or concurrency dispatches ONE compiled program (the
   retrace sentinel pins zero retraces across cohort overlap);
3. weights slot ``i`` by ``live_i * (1 + staleness_i)^(-alpha)`` and
   applies through the active rung's ``apply_fn`` (donating the state,
   like the synchronous round).

Telemetry: per-update ``fedsim/*`` scalars are the consumed-slot mixture
of the contributing cohorts' stats (at K=W, C=1 exactly the cohort's own
— the ledger's masked billing then reconciles byte-for-byte with the
synchronous run), plus ``async/*`` overlap scalars (staleness mean/max,
buffer fill, concurrent cohorts, effective participation) that also feed
the control plane's join inputs.

Double-buffered rounds (``cfg.async_double_buffer``): the apply's host
fence is deferred until AFTER the next update's cohort launches have
dispatched (``_drain_deferred``), so update ``u+1``'s compute is already
queued when the host waits on ``u``'s aggregation collectives — XLA's
async scheduling then overlaps the two. Strictly a host-side fencing
change: the device programs and their dispatch order are untouched, so
the K=W, C=1, alpha=0 synchronous reduction stays bit-identical and the
vault rollback replay is unaffected (every exit path drains first).

Ladder interplay: a mid-run rung switch (control/) changes which
``(launch_fn, apply_fn)`` pair subsequent dispatches use. In-flight rows
launched under the old rung are dense [D] transmits in every mode, so
they aggregate under the NEW rung's apply — semantically the contribution
is re-encoded under the new rung (the ladder's migration story for
in-flight work).

Resilience: the in-flight window (pending cohort outputs, consumed
counts, cohort horizon) rides the drain-certified vault snapshot via
``snapshot_extra``/``restore_extra``, so a rollback replays
bit-identically — including contributions launched before the rollback
point. A plain checkpoint resume (no vault extras) instead cold-restarts
the window: the schedule-pinned pending cohorts relaunch against the
RESUMED params (their scheduled launch versions keep the rng and
staleness bookkeeping deterministic), which is deterministic going
forward but not bit-identical to the uninterrupted run — the FedBuff
trade every practical async system makes on cold restart.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.asyncfed.schedule import AsyncSchedule, UpdateSpec
from commefficient_tpu.asyncfed.staging import CohortScheduler
from commefficient_tpu.telemetry.spans import span_of


class AsyncFederation:
    """Buffered-asynchronous round source (``cfg.async_buffer > 0``).

    The runner's round-source protocol: ``start(resume_step)``,
    ``epoch_rounds(epoch, start_step)`` yielding ``(step, lr, metrics)``,
    ``restart(step)``, ``close()``, ``stats()`` — plus
    ``snapshot_extra``/``restore_extra`` for the vault rider."""

    def __init__(self, cfg, session, sampler, lr_fn, num_rounds,
                 steps_per_epoch=None, spans=None, profiler=None):
        self.cfg = cfg
        self.session = session
        self.sampler = sampler
        self.lr_fn = lr_fn
        self.num_rounds = int(num_rounds)
        self.steps_per_epoch = int(steps_per_epoch or num_rounds)
        self.spans = spans
        self.profiler = profiler
        self.W = int(cfg.num_workers)
        self._alpha = float(cfg.staleness_exponent)
        # engine-local (K, C): the cfg's static values normally; under an
        # ADAPTS_ASYNC control policy the controller owns the live pair
        # (its state blob restores the retuned values before start(), so
        # a checkpoint resume dispatches the retuned schedule, not the
        # cfg one)
        self._k = int(cfg.async_buffer)
        self._c = int(cfg.async_concurrency)
        ctl = session.controller
        if ctl is not None and getattr(ctl.policy, "ADAPTS_ASYNC", False):
            self._k = int(ctl.async_k)
            self._c = int(ctl.async_c)
        self.schedule = self._build_schedule()
        self._scheduler: Optional[CohortScheduler] = None
        # in-flight window: cohort -> launch record (device outputs + the
        # host live mask/stats/version the apply assembly reads)
        self._pending: Dict[int, Dict[str, Any]] = {}
        self._consumed: Dict[int, int] = {}  # cohort -> consumed slots
        self._next_cohort = 0
        # replay horizon in COHORT units (fedsim nan_client transients
        # fire on first realization only — the session's round-unit
        # _replay_horizon discipline)
        self._cohort_horizon = 0
        self._restored = None
        self.restarts = 0
        self.quiesces = 0
        self._updates_run = 0
        self._cohorts_launched = 0
        self._host_stall_ms = 0.0
        # double-buffered rounds (cfg.async_double_buffer): the apply's
        # host fence is PARKED here and drained only after the NEXT
        # update's cohort launches have dispatched, so XLA schedules the
        # apply's collectives behind the new launches' compute instead of
        # the host serializing on them. Pure host scheduling — dispatch
        # order of the device programs is unchanged, so the K=W, C=1,
        # a=0 sync reduction stays bit-identical.
        self._double_buffer = bool(getattr(cfg, "async_double_buffer",
                                           False))
        self._deferred = None
        # staleness-aware (K, C) retune (schema v13): the controller's
        # decision point runs mid-update, so a retune is PARKED here and
        # applied at the top of the next update's loop iteration — a cold
        # window rebuild under the new schedule
        self._retune_pending = None
        self.retunes_applied = 0
        if session.controller is not None:
            session.controller.add_switch_listener(self._on_rung_switch)
            if getattr(session.controller.policy, "ADAPTS_ASYNC", False):
                session.controller.add_retune_listener(self._on_retune)

    # -- lifecycle ---------------------------------------------------------
    def start(self, resume_step: int = 0) -> "AsyncFederation":
        if self._scheduler is not None:
            return self  # idempotent
        self._init_window(int(resume_step), None)
        return self

    def restart(self, step: int) -> None:
        """Quiesce and rebuild the window at update ``step`` — the vault
        rollback path (``restore_extra`` first restores the snapshotted
        in-flight window; without one the window cold-restarts)."""
        self._drain_deferred()
        if self._scheduler is not None:
            self._scheduler.close()
            self._scheduler = None
        blob, self._restored = self._restored, None
        self._pending, self._consumed = {}, {}
        if blob is not None:
            # the snapshot's (K, C) wins: the window it carries was
            # captured under THAT schedule, and the controller's own blob
            # (restored alongside) re-notified the same pair — so any
            # parked retune is stale by construction
            k = int(blob.get("k", self._k))
            c = int(blob.get("c", self._c))
            if (k, c) != (self._k, self._c):
                self._k, self._c = k, c
                self.schedule = self._build_schedule()
            self._retune_pending = None
        self._init_window(int(step), blob)
        self.restarts += 1
        if self.spans is not None:
            with self.spans.span(f"async_recovery_restart:round{step}"):
                pass

    def close(self) -> None:
        self._drain_deferred()
        if self._scheduler is not None:
            self._scheduler.close()
            self._scheduler = None

    def _build_schedule(self) -> AsyncSchedule:
        """The pre-simulated arrival/consumption script for the CURRENT
        engine-local (K, C) — rebuilt whole on retune (same seed, so the
        arrival process is the one deterministic object it always was)."""
        return AsyncSchedule(
            seed=self.cfg.seed,
            num_workers=self.W,
            buffer_k=self._k,
            concurrency=self._c,
            arrival_rate=self.cfg.arrival_rate,
            num_updates=self.num_rounds,
        )

    def _build_scheduler(self, start_cohort: int) -> CohortScheduler:
        return CohortScheduler(
            session=self.session,
            sampler=self.sampler,
            lr_fn=self.lr_fn,
            launch_versions=self.schedule.launch_version,
            start_cohort=start_cohort,
            stop_cohort=self.schedule.num_cohorts,
            depth=max(1, self._c),
            microbatches=self.cfg.round_microbatches,
            spans=self.spans,
            replay_until=self._cohort_horizon,
        ).start()

    def _init_window(self, step: int, blob) -> None:
        """Stand the in-flight window up for update ``step``: from the
        vault blob when one matches (bit-identical replay), else by
        deriving the launched/consumed sets from the schedule and
        relaunching the unconsumed cohorts at the current params."""
        if blob is not None and int(blob.get("update", -1)) == step:
            self._pending = {
                int(c): dict(p) for c, p in blob["pending"].items()
            }
            self._consumed = {
                int(c): int(n) for c, n in blob["consumed"].items()
            }
            self._next_cohort = int(blob["next_cohort"])
            self._cohort_horizon = max(self._cohort_horizon,
                                       int(blob["cohort_horizon"]))
            self._scheduler = self._build_scheduler(self._next_cohort)
            return
        consumed: Dict[int, int] = {}
        for u in range(step):
            for (c, _s) in self.schedule.updates[u].slots:
                consumed[c] = consumed.get(c, 0) + 1
        launched = self.schedule.launched_before(step)
        need = {c for c in range(launched) if consumed.get(c, 0) < self.W}
        self._consumed = consumed
        self._next_cohort = launched
        start_c = min(need) if need else launched
        self._scheduler = self._build_scheduler(start_c)
        # the prefetcher's get() is strictly in-order: walk every cohort
        # in the window, relaunching only those with unconsumed slots
        for c in range(start_c, launched):
            work = self._scheduler.get(c)
            if c in need:
                self._launch_work(c, work)

    # -- launch ------------------------------------------------------------
    def _span(self, name: str, collective: bool = False, trace_id=None,
              parent=None):
        return span_of(self.spans, name, collective=collective,
                       trace_id=trace_id, parent=parent)

    def _drain_deferred(self) -> None:
        """Fence the PREVIOUS update's parked apply (double-buffer mode).
        Called after the next update's launches dispatch — the drain span
        then measures only the collective time the launches failed to
        hide — and on every path that leaves the steady-state loop
        (restart/close/snapshot), so the window never rides an unfenced
        apply into the vault. The drain span carries the PARKED update's
        trace id + step (schema v11): it fences that round's apply, not
        the round whose loop iteration happens to run it."""
        if self._deferred is None:
            return
        (loss, step), self._deferred = self._deferred, None
        from commefficient_tpu.telemetry.trace import round_trace_id

        if self.spans is None:
            return
        with self.spans.span("async_apply_drain", collective=True,
                             step=step,
                             trace_id=round_trace_id(step)) as sp:
            if sp is not None:
                sp.fence(loss)

    def _launch_work(self, c: int, work) -> None:
        """Dispatch cohort ``c``'s launch program against the current
        params and park the outputs in the in-flight window."""
        sess = self.session
        env = work.env
        if env is not None and sess._client_blacklist is not None:
            env = sess._blacklist_env(env, work.client_ids)
        live = None
        stats: Dict[str, float] = {}
        fs = ()
        if env is not None:
            live = np.asarray(env.live, np.float32)
            stats = dict(env.stats)
            fs = (
                jax.device_put(jnp.asarray(env.live), sess._batch_sharding),
                jax.device_put(jnp.asarray(env.corrupt),
                               sess._batch_sharding),
            )
        launch_fn, _ = sess.async_round_fns(sess.active_rung)
        ids = jax.device_put(jnp.asarray(work.client_ids),
                             sess._batch_sharding)
        version = int(self.schedule.launch_version[c])
        st = sess.state
        from commefficient_tpu.telemetry.trace import (
            cohort_trace_id,
            round_trace_id,
        )

        # the cohort's trace id roots its whole lifecycle (launch ->
        # buffer residency -> consuming applies); its parent is the
        # server round whose params it launched against (schema v11)
        with self._span("async_launch", trace_id=cohort_trace_id(c),
                        parent=round_trace_id(version)):
            out = launch_fn(
                st.params_vec, st.client_vel, st.client_err, ids, work.batch,
                jnp.int32(version), jnp.float32(work.lr), env=fs,
            )
        self._pending[c] = {
            "out": out,
            "cids": np.asarray(work.client_ids),
            "live": live,
            "stats": stats,
            "version": version,
            "rung": int(sess.active_rung),
            # launch-time clock for the retroactive buffer-residency
            # span recorded when the cohort fully retires (absent on
            # vault-restored windows — the original launch time did not
            # survive the snapshot, so no residency span is recorded)
            "t_launch": time.perf_counter(),
        }
        self._cohorts_launched += 1
        self._cohort_horizon = max(self._cohort_horizon, c + 1)

    # -- (K, C) retune (staleness_aware control, schema v13) ---------------
    def _on_retune(self, step: int, k: int, c: int) -> None:
        """Controller retune listener — also re-fired by a state-blob
        load, so a no-op pair (checkpoint resume already built this
        schedule) must not force a spurious window rebuild."""
        if (int(k), int(c)) == (self._k, self._c):
            return
        self._retune_pending = (int(k), int(c))

    def _apply_retune(self, step: int) -> None:
        """Rebuild the schedule + in-flight window under the retuned
        (K, C) — a cold window restart like ``restart`` without a vault
        blob: the new schedule's pending cohorts relaunch against the
        CURRENT params, deterministic going forward (the same FedBuff
        trade the plain checkpoint resume makes)."""
        (self._k, self._c), self._retune_pending = self._retune_pending, None
        self._drain_deferred()
        if self._scheduler is not None:
            self._scheduler.close()
            self._scheduler = None
        self.schedule = self._build_schedule()
        self._pending, self._consumed = {}, {}
        self._init_window(int(step), None)
        self.retunes_applied += 1
        if self.spans is not None:
            with self.spans.span(
                    f"async_retune:round{step}:k{self._k}c{self._c}"):
                pass

    # -- the update loop ---------------------------------------------------
    def epoch_rounds(self, epoch: int, start_step: int):
        spe = self.steps_per_epoch
        for step in range(max(epoch * spe, start_step), (epoch + 1) * spe):
            # a retune parked by the PREVIOUS update's decision point
            # lands here, before this update reads its schedule spec
            if self._retune_pending is not None:
                self._apply_retune(step)
            spec = self.schedule.updates[step]
            stall = 0.0
            for c in spec.launches_before:
                t0 = time.perf_counter()
                work = self._scheduler.get(c)
                stall += time.perf_counter() - t0
                self._launch_work(c, work)
                self._next_cohort = c + 1
            self._host_stall_ms += stall * 1000.0
            # double buffer: update step-1's apply fences HERE, after this
            # update's cohort launches are already in flight on device
            self._drain_deferred()
            if self.profiler is not None:
                self.profiler.step(step)
            if self.spans is not None:
                self.spans.step(step)
            lr = float(self.lr_fn(step))
            metrics = self._apply_update(step, spec, lr)
            self._updates_run += 1
            yield step, lr, metrics

    def _slot_weights(self, spec: UpdateSpec) -> np.ndarray:
        """Per-slot aggregation weights: live mask x the polynomial
        staleness discount (FedBuff §4), padded to [W] with zeros."""
        w = np.zeros(self.W, np.float32)
        for i, (c, s) in enumerate(spec.slots):
            lv = self._pending[c]["live"]
            base = 1.0 if lv is None else float(lv[s])
            w[i] = base * (1.0 + spec.staleness[i]) ** (-self._alpha)
        return w

    def _update_stats(self, spec: UpdateSpec, w: np.ndarray,
                      wsum: float) -> Dict[str, float]:
        """The update's host scalars: the consumed-slot mixture of the
        contributing cohorts' fedsim stats (constant key set; at K=W, C=1
        exactly the single cohort's own stats — the ledger's masked
        billing then reconciles with the synchronous run byte-for-byte)
        plus the ``async/*`` overlap scalars."""
        W = self.W
        fs_stats: Dict[str, float] = {}
        if self.session.fedsim_env is not None:
            counts: Dict[int, int] = {}
            n_live = 0.0
            for (c, s) in spec.slots:
                counts[c] = counts.get(c, 0) + 1
                lv = self._pending[c]["live"]
                n_live += 1.0 if lv is None else float(lv[s])

            def mix(key: str) -> float:
                return sum(
                    (n / W) * float(self._pending[c]["stats"].get(key, 0.0))
                    for c, n in counts.items()
                )

            fs_stats = {
                "fedsim/participation_rate": n_live / W,
                "fedsim/dropped": mix("fedsim/dropped"),
                "fedsim/straggler_excluded": mix("fedsim/straggler_excluded"),
                "fedsim/all_dropped": float(wsum == 0.0),
                "fedsim/preempt": max(
                    float(self._pending[c]["stats"].get("fedsim/preempt",
                                                        0.0))
                    for c in counts
                ),
            }
        st = spec.staleness
        fs_stats.update({
            "async/staleness_mean": float(sum(st)) / max(len(st), 1),
            "async/staleness_max": float(max(st)) if st else 0.0,
            "async/buffer_fill": float(spec.buffer_fill_after),
            "async/concurrent_cohorts": float(spec.concurrent_after),
            "async/effective_participation": float(wsum),
        })
        return fs_stats

    def _apply_update(self, step: int, spec: UpdateSpec, lr: float):
        sess = self.session
        W, K = self.W, len(spec.slots)
        # fixed [W, ...] assembly at any K/C: padding repeats slot 0 at
        # weight 0 (the where-gate blocks even a NaN payload), so every
        # apply shares ONE compiled signature — zero retraces
        sel = list(spec.slots) + [spec.slots[0]] * (W - K)
        outs = [self._pending[c]["out"] for (c, _s) in sel]
        rows = jnp.stack([o[0][s] for o, (_c, s) in zip(outs, sel)])
        vel_rows = jnp.stack([o[1][s] for o, (_c, s) in zip(outs, sel)])
        err_rows = jnp.stack([o[2][s] for o, (_c, s) in zip(outs, sel)])
        loss_rows = jnp.stack([o[3][s] for o, (_c, s) in zip(outs, sel)])
        aux_rows = jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *[jax.tree.map(lambda a, s=s: a[s], o[4])
              for o, (_c, s) in zip(outs, sel)],
        )
        cids = np.asarray([self._pending[c]["cids"][s] for (c, s) in sel])
        w = self._slot_weights(spec)
        wsum = float(np.float32(w.sum(dtype=np.float32)))
        bs = sess._batch_sharding

        def put(a):
            return jax.device_put(a, bs)

        fs_stats = self._update_stats(spec, w, wsum)
        # controller decision point BEFORE dispatch (may swap the rung:
        # the update then applies under the NEW rung's program — in-flight
        # rows are dense transmits, re-encoded under the new rung)
        sess._control_round_start(fs_stats)
        _, apply_fn = sess.async_round_fns(sess.active_rung)
        from commefficient_tpu.telemetry.trace import (
            cohort_trace_id,
            round_trace_id,
        )

        name = ("async_apply_dispatch" if self._double_buffer
                else "async_apply")
        with self._span(name, collective=not self._double_buffer,
                        trace_id=round_trace_id(step)) as sp:
            sess.state, metrics = apply_fn(
                sess.state, put(rows), put(vel_rows), put(err_rows),
                put(loss_rows), jax.tree.map(put, aux_rows),
                put(jnp.asarray(cids)), put(jnp.asarray(w)),
                jnp.float32(wsum), jnp.float32(lr),
            )
            if sp is not None:
                if self._double_buffer:
                    # park the fence target (with its step, so the drain
                    # span names the round it fences); _drain_deferred
                    # fences it after the NEXT update's launches dispatch
                    self._deferred = (metrics["loss"], step)
                else:
                    sp.fence(metrics["loss"])
        # mirror train_round's clock discipline: the availability/chaos
        # schedule and the controller key off the host round clock
        sess._round_clock += 1
        sess._replay_horizon = max(sess._replay_horizon, sess._round_clock)
        for (c, _s) in spec.slots:
            self._consumed[c] = self._consumed.get(c, 0) + 1
        for c in {cc for cc, _ in spec.slots}:
            if self._consumed.get(c, 0) >= W:
                p = self._pending.pop(c, None)  # fully consumed -> retire
                if (p is not None and self.spans is not None
                        and "t_launch" in p):
                    # retroactive buffer-residency span: launch ->
                    # retirement, on the cohort's own trace (schema v11)
                    self.spans.span_at(
                        "async_buffer_residency", p["t_launch"],
                        time.perf_counter(), step=step,
                        trace_id=cohort_trace_id(c),
                        parent=round_trace_id(p["version"]),
                    )
        stats = sess._host_round_stats(fs_stats)
        return {**metrics, **stats} if stats else metrics

    # -- rung switch marker ------------------------------------------------
    def _on_rung_switch(self, step: int, old: int, new: int) -> None:
        self.quiesces += 1
        if self.spans is not None:
            with self.spans.span(f"async_rung_switch:round{step}"):
                pass

    # -- vault riders ------------------------------------------------------
    def snapshot_extra(self) -> Dict[str, Any]:
        """Host copy of the in-flight window for the vault snapshot —
        restoring it replays the post-rollback tail bit-identically
        (pending outputs are NOT re-launched: the blacklist may have
        grown since, and the rows must be the ones the first pass saw)."""
        self._drain_deferred()
        pending = {
            int(c): {
                "out": jax.tree.map(np.asarray, p["out"]),
                "cids": np.asarray(p["cids"]).copy(),
                "live": None if p["live"] is None else np.asarray(
                    p["live"]).copy(),
                "stats": dict(p["stats"]),
                "version": int(p["version"]),
                "rung": int(p["rung"]),
            }
            for c, p in self._pending.items()
        }
        return {
            "update": int(self.session._round_clock),
            "next_cohort": int(self._next_cohort),
            "cohort_horizon": int(self._cohort_horizon),
            # the (K, C) the window was captured under — restart() rebuilds
            # the matching schedule before replaying it (retune rider)
            "k": int(self._k),
            "c": int(self._c),
            "consumed": {int(c): int(n)
                         for c, n in self._consumed.items()},
            "pending": pending,
        }

    def restore_extra(self, blob) -> None:
        """Stash a vault snapshot's window for the next ``restart``."""
        self._restored = blob

    # -- observability -----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        return {
            "updates": self._updates_run,
            "cohorts_launched": self._cohorts_launched,
            "host_stall_ms": self._host_stall_ms,
            "restarts": self.restarts,
            "quiesces": self.quiesces,
            "retunes_applied": self.retunes_applied,
        }
