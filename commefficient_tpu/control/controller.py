"""BudgetController — the closed loop that owns rung dispatch + accounting.

Placement in the round pipeline (FederatedSession.train_round*):

    fs_env, fs_stats = session._fedsim_round_env(...)   # host masks
    controller.on_round_start(round_clock, fs_stats)    # decide + switch
    session.round_fn(...)                               # ACTIVE rung's
                                                        # prewarmed program

``on_round_start`` runs BEFORE dispatch, entirely host-side: it asks the
policy for the next rung, clamps the choice against the byte budget
(raising ``BudgetExhaustedError`` before the offending round ever runs),
switches the session's active rung when the decision changed (a
dispatch-table swap of the AOT-prewarmed per-rung round program plus a
``Compressor.migrate_state`` pass over the server-state leaves — never a
retrace), and accounts the round's bytes with EXACTLY the CommLedger's
arithmetic (live-count-aware under fedsim masking), so the controller's
budget view and the ledger can never disagree.

Telemetry flows the other way at drain time: ``observe_drained`` feeds
each drained round's ``diag/*`` scalars to the policy (the ``ef_feedback``
loop's input), and ``scalars()`` rides ``control/rung`` /
``control/switches`` / ``control/budget_remaining_bytes`` on every round's
metric dict — which is also how the per-rung ledger accounting recovers
the active rung per drained round.

Controller state (active rung, switch count, byte spend, policy slots) is
a small float64 blob carried in checkpoints (utils/checkpoint.py), so a
resumed run reproduces the uninterrupted run's rung sequence bit-exactly:
decisions are pure functions of (blob state, round index, drained
telemetry), and drains happen before checkpoint saves.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from commefficient_tpu.control.policy import (
    BudgetExhaustedError,
    DecisionContext,
    FixedPolicy,
    get_policy,
)

_BLOB_VERSION = 3
# blob layout: [version, rung, switches, rounds_seen, spent_up, spent_down,
#               last_switch_round, min_rung, fleet_width, async_k, async_c,
#               retunes, last_retune_round, *policy slots] — float64 is
# exact for every field (byte counts stay far below 2^53). v2 added the
# resilience demotion floor ``min_rung`` at index 7; v3 adds the fleet
# width at capture at index 8 (-1 when the run schedules no fleet events;
# ADVISORY — restore re-derives the width from the round schedule) and
# the asyncfed retune state at 9-12. Older blobs still load, with the
# missing fields defaulting (floor 0; config-initial K/C, zero retunes).
_BLOB_FIXED = 13
_BLOB_FIXED_V2 = 8
_BLOB_FIXED_V1 = 7


class BudgetController:
    """One per session when ``cfg.control_policy != 'none'``."""

    def __init__(self, cfg, session, num_rounds: int):
        self.cfg = cfg
        self.session = session
        self.num_rounds = int(num_rounds)
        self.policy = get_policy(cfg)
        if isinstance(self.policy, FixedPolicy):
            # schedule round ranges vs the run length — only the train
            # loop knows it (same late validation as fedsim chaos rounds)
            self.policy.validate_rounds(self.num_rounds)
        self.num_rungs = len(session.rungs)
        self.budget_bytes: Optional[int] = (
            int(cfg.budget_mb * 1_000_000) if cfg.budget_mb > 0 else None
        )
        self.masked = bool(cfg.fedsim_enabled)
        self._bytes = [session.rung_bytes_per_round(i)
                       for i in range(self.num_rungs)]
        self._comps = [r.compressor for r in session.rungs]
        self.switches = 0
        self.rounds_seen = 0
        self.spent_up = 0
        self.spent_down = 0
        self.last_switch_round = -1
        # resilience demotion floor (resilience/policy.py DemotePolicy):
        # rung indices below it are off-limits — a divergence-driven
        # degradation that outlives the policy's own decisions (every
        # on_round_start clamps to it) and rides the checkpoint blob so a
        # resumed run stays demoted.
        self.min_rung = 0
        # rung-switch observers (asyncfed/engine.py registers one): called
        # host-side, AFTER the dispatch-table swap + state migration and
        # BEFORE the round dispatches. Staged work is rung-INVARIANT
        # (batch geometry, env masks and lr never depend on the rung), so
        # a switch invalidates nothing in the in-flight window, and every
        # rung's program is AOT-prewarmed — the listener lets the engine
        # account/span the switch without re-deriving any of that.
        self._switch_listeners = []
        # asyncfed (K, C) retune state (staleness_aware policy): the
        # controller owns the authoritative pair — the engine registers a
        # retune listener and rebuilds its arrival schedule when the pair
        # moves. Present (at the config's initial values) for every
        # policy; only ADAPTS_ASYNC policies ever move it.
        self.async_k = int(cfg.async_buffer)
        self.async_c = int(cfg.async_concurrency)
        self.retunes = 0
        self.last_retune_round = -1
        self._retune_listeners = []
        session.controller = self

    def add_switch_listener(self, fn) -> None:
        """Register ``fn(step, old_rung, new_rung)``, called at each rung
        switch (see ``_switch_listeners`` above). Listeners must be pure
        observers — raising would abort the round the switch serves."""
        self._switch_listeners.append(fn)

    def add_retune_listener(self, fn) -> None:
        """Register ``fn(step, k, c)``, called when an ADAPTS_ASYNC
        policy moves the asyncfed (buffer K, concurrency C) pair — the
        engine's hook for rebuilding its pre-simulated arrival schedule.
        Same observer discipline as the switch listeners."""
        self._retune_listeners.append(fn)

    # -- byte accounting (mirrors telemetry.CommLedger exactly) ------------
    def _live_avail(self, fs_stats: Optional[Dict[str, float]]):
        s = fs_stats or {}
        # elastic-fleet rounds account at the round's REALIZED width (the
        # fedsim/* rates are relative to it) — exactly CommLedger._counts
        W = int(round(float(s.get("fleet/width", self.cfg.num_workers))))
        rate = s.get("fedsim/participation_rate")
        live = W if rate is None else int(round(float(rate) * W))
        avail = W - int(round(float(s.get("fedsim/dropped", 0.0))))
        return live, avail

    def round_bytes(self, rung: int, live: int, avail: int) -> int:
        """One round's ledger bytes at ``rung`` given the realized
        participation — the same arithmetic CommLedger.on_round applies,
        through the same ``masked_upload_floats`` compressor hook."""
        bpr = self._bytes[rung]
        if self.masked:
            # bytes-per-float through the compressor hook, like the
            # ledger (ledger.py on_round): 2 B/float for bf16 sketch
            # tables — a hardcoded 4 would double-bill those runs and
            # fire BudgetExhaustedError at half the real budget
            comp = self._comps[rung]
            up = (comp.upload_bytes_per_float()
                  * comp.masked_upload_floats(live))
            down = avail * bpr["download_bytes"]
        else:
            up, down = bpr["upload_bytes"], bpr["download_bytes"]
        return int(up) + int(down)

    def _spend(self, rung: int, live: int, avail: int) -> None:
        bpr = self._bytes[rung]
        if self.masked:
            comp = self._comps[rung]
            self.spent_up += (comp.upload_bytes_per_float()
                              * comp.masked_upload_floats(live))
            self.spent_down += avail * bpr["download_bytes"]
        else:
            self.spent_up += bpr["upload_bytes"]
            self.spent_down += bpr["download_bytes"]

    @property
    def spent_bytes(self) -> int:
        return self.spent_up + self.spent_down

    # -- the per-round decision --------------------------------------------
    def on_round_start(self, step: int,
                       fs_stats: Optional[Dict[str, float]] = None) -> int:
        """Pick (and switch to) the rung round ``step`` dispatches at;
        returns it. Raises ``BudgetExhaustedError`` when even the cheapest
        rung would overshoot the budget — BEFORE the round runs."""
        live, avail = self._live_avail(fs_stats)
        rung = self.session.active_rung
        s = fs_stats or {}
        # buffered-async per-update signals (asyncfed/engine.py rides them
        # in fs_stats unconditionally) — None on synchronous rounds
        stale = s.get("async/staleness_mean")
        eff = s.get("async/effective_participation")
        fill = s.get("async/buffer_fill")
        ctx = DecisionContext(
            step=step, num_rounds=self.num_rounds, rung=rung,
            num_rungs=self.num_rungs,
            round_bytes=lambda r: self.round_bytes(r, live, avail),
            spent_bytes=self.spent_bytes, budget_bytes=self.budget_bytes,
            last_switch_round=self.last_switch_round,
            hysteresis=self.cfg.control_hysteresis,
            staleness_mean=None if stale is None else float(stale),
            effective_participation=None if eff is None else float(eff),
            buffer_fill=None if fill is None else float(fill),
            num_workers=self.cfg.num_workers,
        )
        target = self.policy.decide(ctx)
        target = min(max(int(target), 0), self.num_rungs - 1)
        # resilience demotion floor: a divergence-demoted run never climbs
        # back above the floor, whatever the policy says (higher index ==
        # cheaper rung, so the clamp is a max)
        target = max(target, self.min_rung)
        if self.budget_bytes is not None:
            # hard clamp, policy-independent: demote to the most expensive
            # rung that still fits the remaining budget; nothing fits ->
            # stop before dispatching a round the cap cannot pay for
            while (target < self.num_rungs
                   and self.spent_bytes + self.round_bytes(
                       target, live, avail) > self.budget_bytes):
                target += 1
            if target >= self.num_rungs:
                cheapest = self.num_rungs - 1
                raise BudgetExhaustedError(
                    step=step, budget_bytes=self.budget_bytes,
                    spent_bytes=self.spent_bytes,
                    cheapest_round_bytes=self.round_bytes(
                        cheapest, live, avail),
                    rung=cheapest,
                )
        if target != rung:
            self.session.set_active_rung(target, migrate=True)
            self.switches += 1
            self.last_switch_round = step
            for fn in self._switch_listeners:
                fn(step, rung, target)
        if self.policy.ADAPTS_ASYNC:
            self._maybe_retune(step, ctx)
        self._spend(target, live, avail)
        self.rounds_seen += 1
        return target

    def _maybe_retune(self, step: int, ctx: DecisionContext) -> None:
        """Ask an ADAPTS_ASYNC policy for the next asyncfed (K, C) pair,
        clamp it to the engine's legality window (1 <= K <= W, C >= 1),
        and notify the retune listeners on a change. Hysteresis mirrors
        the rung walk's: no retune within ``control_hysteresis`` rounds
        of the last one, so the schedule rebuild cannot thrash."""
        if (self.last_retune_round >= 0
                and step - self.last_retune_round
                < self.cfg.control_hysteresis):
            return
        k, c = self.policy.decide_async(ctx, self.async_k, self.async_c)
        k = min(max(int(k), 1), int(self.cfg.num_workers))
        c = max(int(c), 1)
        if (k, c) == (self.async_k, self.async_c):
            return
        self.async_k, self.async_c = k, c
        self.retunes += 1
        self.last_retune_round = step
        for fn in self._retune_listeners:
            fn(step, k, c)

    def demote(self, step: int) -> int:
        """Resilience recovery action (resilience/policy.py DemotePolicy):
        floor the ladder one rung cheaper than the CURRENT rung and switch
        to it now — through the same AOT-prewarmed ``set_active_rung`` +
        ``migrate_state`` path as a policy switch, so the demotion is
        never a retrace. Returns the new active rung (== the old one iff
        already at the cheapest rung, in which case nothing changes and
        the caller treats the demotion as unavailable)."""
        old = self.session.active_rung
        # descend from the EFFECTIVE rung — the active rung clamped to
        # the floor: a rollback may have re-activated a pre-demotion rung
        # from a stale snapshot blob, but every on_round_start clamps
        # back to the floor, so one-cheaper-than-effective is the true
        # descent (repeated recoveries walk DOWN the ladder, never replay
        # the rung that just diverged)
        effective = max(old, self.min_rung)
        target = min(effective + 1, self.num_rungs - 1)
        if target == effective:
            # already floored at the cheapest rung — return the active
            # rung unchanged so the caller sees the demotion as
            # unavailable
            return old
        self.min_rung = max(self.min_rung, target)
        self.session.set_active_rung(target, migrate=True)
        self.switches += 1
        self.last_switch_round = int(step)
        for fn in self._switch_listeners:
            fn(int(step), old, target)
        return target

    # -- telemetry ---------------------------------------------------------
    def scalars(self) -> Dict[str, float]:
        """Host scalars riding THIS round's metric dict (constant key set,
        as pack_metric_dicts requires). ``control/rung`` is the rung the
        round ran at — the per-rung ledger accounting recovers it from
        here; ``budget_remaining_bytes`` is what is left AFTER this
        round's spend (only emitted when a budget is set — constant across
        the run either way)."""
        out = {
            "control/rung": float(self.session.active_rung),
            "control/switches": float(self.switches),
        }
        if self.budget_bytes is not None:
            out["control/budget_remaining_bytes"] = float(
                self.budget_bytes - self.spent_bytes
            )
        if self.policy.ADAPTS_ASYNC:
            # (K, C) decision trail (schema v13) — capability-gated, so
            # the key set stays constant for the run either way
            out["control/async_k"] = float(self.async_k)
            out["control/async_c"] = float(self.async_c)
            out["control/retunes"] = float(self.retunes)
        return out

    def observe_drained(self, step: int, scalars: Dict[str, float]) -> None:
        """Drain rider (utils.logging.drain_round_metrics): feed one
        drained round's scalars to the policy, in step order."""
        self.policy.observe(step, scalars)

    def snapshot(self) -> dict:
        """The controller block flight dumps and the metrics run-header
        carry — enough to attribute a divergence to a rung switch."""
        out = {
            "policy": self.cfg.control_policy,
            "ladder": self.cfg.ladder,
            "rung": int(self.session.active_rung),
            "num_rungs": self.num_rungs,
            "switches": int(self.switches),
            "rounds_seen": int(self.rounds_seen),
            "last_switch_round": int(self.last_switch_round),
        }
        if self.budget_bytes is not None:
            out["budget_bytes"] = int(self.budget_bytes)
            out["budget_remaining_bytes"] = int(
                self.budget_bytes - self.spent_bytes
            )
        if getattr(self.cfg, "fleet_enabled", False):
            out["fleet_width"] = int(
                getattr(self.session, "_fleet_width", self.cfg.num_workers)
            )
        if self.policy.ADAPTS_ASYNC:
            out["async_k"] = int(self.async_k)
            out["async_c"] = int(self.async_c)
            out["retunes"] = int(self.retunes)
        return out

    def describe(self) -> str:
        bits = [f"policy={self.cfg.control_policy}",
                f"rungs={self.num_rungs}",
                f"start_rung={self.session.active_rung}"]
        if self.budget_bytes is not None:
            bits.append(f"budget={self.budget_bytes / 1e6:g} MB")
        return "control: " + " ".join(bits)

    # -- prewarm (zero mid-run retraces) -----------------------------------
    def prewarm(self, sampler, lr: float) -> int:
        """AOT-lower every rung's round program for the run's REAL round-0
        signature (FederatedSession.prewarm_rungs), so a later rung switch
        dispatches an already-traced program and the RetraceSentinel's
        per-rung signature streams are seeded — any later signature drift
        is a counted (or hard-failed) retrace, never a silent one."""
        session = self.session
        if getattr(session, "_dev_data", None) is not None:
            ids, idx, plan = sampler.sample_round_indices(0)
            return session.prewarm_rungs_indices(ids, idx, plan, lr)
        ids, batch = sampler.sample_round(0)
        L = getattr(self.cfg, "round_microbatches", 0)
        if L:  # fedavg [W, L, B/L, ...] convention
            batch = {
                k: v.reshape(v.shape[0], L, v.shape[1] // L, *v.shape[2:])
                for k, v in batch.items()
            }
        return session.prewarm_rungs(ids, batch, lr)

    # -- checkpoint state --------------------------------------------------
    def state_blob(self) -> np.ndarray:
        # fleet width at capture (v3, ADVISORY — see load): -1 marks a
        # run with no fleet events, so forensics can tell "fleet off"
        # from "fleet at base width"
        fleet_w = (
            int(getattr(self.session, "_fleet_width", self.cfg.num_workers))
            if getattr(self.cfg, "fleet_enabled", False) else -1
        )
        return np.asarray(
            [_BLOB_VERSION, self.session.active_rung, self.switches,
             self.rounds_seen, self.spent_up, self.spent_down,
             self.last_switch_round, self.min_rung, fleet_w,
             self.async_k, self.async_c, self.retunes,
             self.last_retune_round, *self.policy.state()],
            np.float64,
        )

    def load_state_blob(self, blob) -> None:
        blob = np.asarray(blob, np.float64)
        version = int(blob[0])
        if version not in (1, 2, _BLOB_VERSION):
            raise ValueError(
                f"controller checkpoint blob version {version} != "
                f"{_BLOB_VERSION} — checkpoint from an incompatible build"
            )
        fixed = {1: _BLOB_FIXED_V1, 2: _BLOB_FIXED_V2,
                 _BLOB_VERSION: _BLOB_FIXED}[version]
        want = fixed + self.policy.STATE_SLOTS
        if blob.shape != (want,):
            raise ValueError(
                f"controller checkpoint blob has shape {blob.shape}, "
                f"expected ({want},) for policy "
                f"{self.cfg.control_policy!r} — the checkpoint was written "
                "under a different control config"
            )
        rung = int(blob[1])
        if not 0 <= rung < self.num_rungs:
            raise ValueError(
                f"controller checkpoint names rung {rung}, but this "
                f"session's ladder has {self.num_rungs} rung(s) — restore "
                "with the ladder the checkpoint was written under"
            )
        # the restored FedState leaves are ALREADY in the saved rung's
        # layout (the checkpoint template matched) — swap dispatch only
        self.session.set_active_rung(rung, migrate=False)
        self.switches = int(blob[2])
        self.rounds_seen = int(blob[3])
        self.spent_up = int(blob[4])
        self.spent_down = int(blob[5])
        self.last_switch_round = int(blob[6])
        # v1 blobs (pre-resilience) carry no demotion floor — default 0.
        # Monotone on purpose: a resilience rollback may load a snapshot
        # blob captured BEFORE a demote recovery raised the floor, and
        # the floor must outlive that rewind (else a second divergence in
        # the same window re-demotes to the same rung forever instead of
        # descending the ladder). A fresh controller starts at 0, so a
        # checkpoint resume still adopts the saved floor exactly.
        self.min_rung = max(self.min_rung,
                            0 if version == 1 else int(blob[7]))
        if version >= 3:
            # blob[8] (fleet width at capture) is ADVISORY: the session
            # re-derives the width from the round schedule in
            # sync_round_clock, which runs on every restore path — a
            # stale width here must never override the pure schedule
            self.async_k = int(blob[9])
            self.async_c = int(blob[10])
            self.retunes = int(blob[11])
            self.last_retune_round = int(blob[12])
            for fn in self._retune_listeners:
                fn(self.last_retune_round, self.async_k, self.async_c)
        self.policy.load_state(tuple(blob[fixed:]))


def build_controller(cfg, session, num_rounds: int) -> Optional[
        BudgetController]:
    """The single construction gate (mirrors fedsim.build_environment):
    a controller iff the config turns the control plane on; None keeps
    every caller on the untouched fast path."""
    if not getattr(cfg, "control_enabled", False):
        return None
    return BudgetController(cfg, session, num_rounds)


def controller_header(session) -> dict:
    """The run-header/flight controller block for a session — available at
    SESSION build (before the controller exists; MetricsWriter writes its
    header at construction), so it reports the initial rung and the static
    ladder/policy identity. ``{}`` for control-less sessions."""
    rungs = getattr(session, "rungs", None)
    if rungs is None or not getattr(session.cfg, "control_enabled", False):
        return {}
    return {"controller": {
        "policy": session.cfg.control_policy,
        "ladder": session.cfg.ladder,
        "rung": int(session.active_rung),
        "num_rungs": len(rungs),
    }}
