"""RecoveryManager / ResilienceRider — the wiring train/runner.py sees.

One ``build_resilience`` call per train loop (the fedsim
``build_environment`` / control ``build_controller`` discipline): it
returns None unless a recovery policy or a preemption source is
configured, so the default run constructs NOTHING — no vault, no signal
handler, no per-round scalars, level-0 HLO and golden parity recordings
bit-untouched.

The manager's recovery sequence, on a caught ``DivergenceError``:

  1. bounds — ``--max_recoveries`` spent -> attach the history to the
     exception and give up (the runner re-raises the ORIGINAL error);
  2. target — newest vault snapshot with ``step <= first_bad_step``
     (always pre-divergence: snapshots are drain-certified, see
     vault.py; the baseline snapshot makes one always exist);
  3. rewind — restore session state + controller blob + ledger counters
     from the snapshot, rewind the flight ring past the rollback point
     (the detection-time dump already preserved the diverged trajectory);
  4. act — the policy's repair (retry/demote/skip_clients; policy.py);
  5. report — append the history entry, write the ``_recovery``-tagged
     flight dump carrying it, and hand the rollback step back to the
     runner, which restarts the round source there (the asyncfed engine
     rebuilds its in-flight window).

``resilience/*`` scalars (schema v6) ride every round's metric dict
through ``FederatedSession._host_round_stats`` — a constant key set, as
``pack_metric_dicts`` requires.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from commefficient_tpu.resilience.guard import PreemptGuard
from commefficient_tpu.resilience.policy import (
    RecoveryUnavailable,
    get_recovery_policy,
)
from commefficient_tpu.resilience.vault import RollbackVault


class RecoveryManager:
    """Owns the vault, the policy, the counters and the history."""

    def __init__(self, cfg, session, sampler, ledger=None, flight=None):
        self.cfg = cfg
        self.session = session
        self.sampler = sampler
        self.ledger = ledger
        self.flight = flight
        self.policy = get_recovery_policy(cfg)
        self.vault = RollbackVault(cfg.snapshot_every)
        self.max_recoveries = int(cfg.max_recoveries)
        self.recoveries = 0
        self.rung_demotions = 0
        self.last_rollback_round = -1  # -1 = never rolled back
        self.last_restored_extras: Optional[Dict] = None
        self.history: List[Dict] = []
        self._suspects = None  # (step, ids) memo for suspect_clients

    # -- snapshots ---------------------------------------------------------
    def will_snapshot(self, step: int) -> bool:
        return self.vault.will_snapshot(step)

    def snapshot(self, step: int, extras: Optional[Dict] = None) -> None:
        """Capture a boundary snapshot. The runner MUST have drained
        immediately before (the drain certifies rounds < step finite —
        vault.py's whole correctness argument). ``extras`` is an opaque
        host rider (the runner's epoch accumulator) handed back through
        ``last_restored_extras`` after a rollback to this snapshot."""
        self.vault.snapshot(self.session, step, ledger=self.ledger,
                            extras=extras)

    def baseline(self, step: int, extras: Optional[Dict] = None) -> None:
        """Seed the vault at the loop's start round (post-restore), so a
        divergence before the first ``snapshot_every`` boundary is still
        recoverable — back to the very start if need be."""
        self.snapshot(step, extras=extras)

    # -- the recovery itself -----------------------------------------------
    def on_divergence(self, exc) -> Optional[int]:
        """Try to recover from ``exc`` (a telemetry.DivergenceError).
        Returns the round to re-enter the loop at, or None when the run
        must die — in which case ``exc.recovery_history`` carries the
        full history for the post-mortem."""
        entry = {
            "recovery": self.recoveries + 1,
            "policy": self.cfg.recover_policy,
            "first_bad_step": int(exc.step),
            "reason": str(getattr(exc, "reason", exc))[:200],
        }
        if getattr(exc, "path", None):
            entry["flight_dump"] = exc.path
        if self.recoveries >= self.max_recoveries:
            entry["outcome"] = (
                f"exhausted ({self.recoveries}/{self.max_recoveries} "
                "recoveries already spent)"
            )
            return self._give_up(exc, entry)
        snap = self.vault.latest(max_step=exc.step)
        if snap is None:
            entry["outcome"] = "no pre-divergence snapshot in the vault"
            return self._give_up(exc, entry)
        try:
            # applicability BEFORE the rewind: an aborted recovery must
            # die with ledger/flight still describing what actually ran
            # (the rewind would falsify the crash-path artifacts)
            self.policy.check(self.session, self, exc, snap)
        except RecoveryUnavailable as e:
            entry["outcome"] = f"policy unavailable: {e}"
            return self._give_up(exc, entry)
        self.vault.restore(self.session, snap, ledger=self.ledger)
        if self.flight is not None:
            self.flight.rewind(snap.step)
        try:
            details = self.policy.apply(self.session, self, exc) or {}
        except RecoveryUnavailable as e:
            entry["outcome"] = f"policy unavailable: {e}"
            return self._give_up(exc, entry)
        self.recoveries += 1
        self.last_rollback_round = int(snap.step)
        self.last_restored_extras = snap.extras
        entry["outcome"] = "recovered"
        entry["rollback_to"] = int(snap.step)
        # elastic-fleet shrink recoveries (schema v13): duck-typed on the
        # exception so FleetShrinkError needs no import here — the session
        # counter feeds the fleet/shrink_recoveries scalar, and the entry
        # records the width the replay re-enters at
        fleet_w = getattr(exc, "fleet_width", None)
        if fleet_w is not None:
            self.session._fleet_shrink_recoveries += 1
            entry["fleet_width"] = int(fleet_w)
        entry.update(details)
        self.history.append(entry)
        if self.flight is not None:
            # persist the history NOW (the healed run may never dump
            # again): a sibling of the detection-time divergence dump,
            # carrying the rewound ring + the recovery_history block
            self.flight.dump(
                exc.step,
                reason=(f"recovered from divergence at round {exc.step} "
                        f"(policy {self.cfg.recover_policy!r}, rolled "
                        f"back to round {snap.step})"),
                first_bad_step=exc.step,
                tag="_recovery",
            )
        return int(snap.step)

    def _give_up(self, exc, entry) -> None:
        self.history.append(entry)
        exc.recovery_history = list(self.history)
        return None

    # -- suspect attribution (skip_clients) --------------------------------
    def suspect_clients(self, step: int) -> np.ndarray:
        """Client ids suspected of poisoning round ``step``: the chaos-
        corrupted slots when the (pure, replay-free) realization names
        them, else every live participant of that round — the honest
        fallback when the realization cannot localize the fault. Pure and
        memoized per step (check + apply both call it). Only the id draw
        is realized when the sampler exposes ``sample_round_indices``
        (FedSampler does) — at GPT-2 scale assembling [W, B, seq] tokens
        just to read the ids is a large wasted transient on the recovery
        path; a duck-typed sampler without the ids-only draw pays the
        generic ``sample_round`` batch assembly once per recovery
        step."""
        if self._suspects is not None and self._suspects[0] == step:
            return self._suspects[1]
        env = self.session.fedsim_env.round_env(step)
        if hasattr(self.sampler, "sample_round_indices"):
            ids = np.asarray(self.sampler.sample_round_indices(step)[0])
        else:
            ids = np.asarray(self.sampler.sample_round(step)[0])
        slots = env.corrupt > 0
        if not slots.any():
            slots = env.live > 0
        out = np.unique(ids[slots].astype(np.int64))
        self._suspects = (step, out)
        return out


class ResilienceRider:
    """The façade the runner and the session hold: manager (divergence
    recovery; None when ``recover_policy='none'``) + guard (preemption;
    None when no source is configured)."""

    def __init__(self, cfg, session,
                 manager: Optional[RecoveryManager],
                 guard: Optional[PreemptGuard]):
        self.cfg = cfg
        self.session = session
        self.manager = manager
        self.guard = guard

    # -- runner surface ----------------------------------------------------
    def will_snapshot(self, step: int) -> bool:
        return self.manager is not None and self.manager.will_snapshot(step)

    def snapshot(self, step: int, extras: Optional[Dict] = None) -> None:
        self.manager.snapshot(step, extras=extras)

    def baseline(self, step: int) -> None:
        if self.manager is not None:
            self.manager.baseline(step)

    @property
    def last_restored_extras(self) -> Optional[Dict]:
        """The ``extras`` rider of the snapshot the last successful
        recovery restored (None before any rollback, or when the
        snapshot carried none)."""
        return (self.manager.last_restored_extras
                if self.manager is not None else None)

    def on_divergence(self, exc) -> Optional[int]:
        if self.manager is None:
            return None
        return self.manager.on_divergence(exc)

    def preempt_requested(self, metrics) -> bool:
        if self.guard is None:
            return False
        return self.guard.check_metrics(metrics)

    @property
    def preempt_source(self) -> Optional[str]:
        return self.guard.source if self.guard is not None else None

    @property
    def history(self) -> List[Dict]:
        """The flight recorder's recovery_history source (schema v6)."""
        return self.manager.history if self.manager is not None else []

    # -- telemetry ---------------------------------------------------------
    def scalars(self) -> Dict[str, float]:
        """The ``resilience/*`` block riding every round's metric dict —
        constant key set (pack_metric_dicts contract), host floats only."""
        m = self.manager
        bl = getattr(self.session, "_client_blacklist", None)
        return {
            "resilience/recoveries": float(m.recoveries if m else 0),
            "resilience/rollback_round": float(
                m.last_rollback_round if m else -1
            ),
            "resilience/rung_demotions": float(m.rung_demotions if m else 0),
            "resilience/blacklisted_clients": float(
                0 if bl is None else len(bl)
            ),
            "resilience/preempt_requested": float(
                bool(self.guard is not None and self.guard.requested)
            ),
        }

    def describe(self) -> str:
        bits = []
        if self.manager is not None:
            bits.append(f"policy={self.cfg.recover_policy}")
            bits.append(f"snapshot_every={self.cfg.snapshot_every}")
            bits.append(f"max_recoveries={self.cfg.max_recoveries}")
        if self.guard is not None:
            bits.append(
                "preempt_guard="
                + ("signals+chaos" if self.guard.signals_installed
                   else "chaos")
            )
        return "resilience: " + " ".join(bits)

    def close(self) -> None:
        """Runner finally block: restore signal dispositions."""
        if self.guard is not None:
            self.guard.close()


def build_resilience(cfg, session, sampler, ledger=None,
                     flight=None) -> Optional[ResilienceRider]:
    """The single construction gate (mirrors fedsim.build_environment /
    control.build_controller): a rider iff a recovery policy or a
    preemption source is configured. None keeps every caller — and the
    process's signal table — on the untouched fast path."""
    want_recovery = bool(getattr(cfg, "recovery_enabled", False))
    want_signals = bool(getattr(cfg, "preempt_signals", False))
    plan = getattr(getattr(session, "fedsim_env", None), "plan", ())
    from commefficient_tpu.fedsim.faults import has_preempt

    want_chaos_preempt = has_preempt(plan)
    if not (want_recovery or want_signals or want_chaos_preempt):
        return None
    manager = (
        RecoveryManager(cfg, session, sampler, ledger=ledger, flight=flight)
        if want_recovery
        else None
    )
    guard = (
        PreemptGuard(install_signals=want_signals)
        if (want_signals or want_chaos_preempt)
        else None
    )
    rider = ResilienceRider(cfg, session, manager, guard)
    # the session surfaces the resilience/* scalars on every round's
    # metric dict; the flight recorder carries the recovery history in
    # its dumps (riders are built before this layer — attach, don't
    # reconstruct)
    session.resilience = rider
    if flight is not None:
        flight.resilience = rider
    return rider
