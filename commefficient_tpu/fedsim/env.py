"""FedEnvironment — availability + chaos composed into per-round masks.

One ``RoundEnv`` per round: the device-side inputs the masked round
consumes (live mask, corruption mask, live count) plus the host-side
``fedsim/*`` telemetry scalars that ride the drained metrics pack. Masks
are numpy (host-side, like the sampler's client draws); the round engines
apply them IN-GRAPH.

``FederatedSession`` owns one environment (``build_environment(cfg)`` —
None when ``cfg.fedsim_enabled`` is False) and advances a host round clock
alongside ``FedState.step``; a checkpoint resume re-syncs the clock, and
because every mask is a pure function of ``(seed, round_idx)`` the resumed
run reproduces the uninterrupted one's environment exactly.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np

from commefficient_tpu.fedsim.availability import (
    round_rng,
    sample_availability,
)
from commefficient_tpu.fedsim.faults import (
    ChaosEvent,
    apply_chaos,
    fleet_shrink_at,
    fleet_transitions,
    fleet_width_at,
    fleet_widths,
    has_fleet,
    parse_chaos,
    preempt_requested,
    validate_chaos_rounds,
)


class RoundEnv(NamedTuple):
    """One round's realized environment.

    ``live``/``corrupt`` are float32 ``[num_workers]`` 0/1 masks (floats so
    the round's ``jnp.where`` gates need no casts); ``live_count`` the
    scalar the server renormalizes by; ``stats`` the host-side ``fedsim/*``
    scalars (a CONSTANT key set, so the packed metric dicts stay
    same-keyed across rounds)."""

    live: np.ndarray
    corrupt: np.ndarray
    live_count: np.float32
    stats: dict


class FedEnvironment:
    """The run-long simulator: availability model + parsed chaos plan."""

    def __init__(self, cfg):
        # duck-typed cfg (utils.config.Config normally) — same discipline
        # as compress/: this package never imports the config module
        self.num_workers = int(cfg.num_workers)
        self.seed = int(cfg.seed)
        self.availability = cfg.availability
        self.dropout_prob = float(cfg.dropout_prob)
        self.period = int(cfg.availability_period)
        self.num_cohorts = int(cfg.num_cohorts)
        # getattr: older duck-typed cfg stand-ins (tests, bench shims)
        # predate the poisson model's knob
        self.arrival_rate = float(getattr(cfg, "arrival_rate", 1.0))
        self.plan: Tuple[ChaosEvent, ...] = parse_chaos(cfg.chaos)
        # elastic fleet (README "Elastic fleet"): the width schedule is a
        # pure function of (plan, num_workers) — precompute the change
        # points so fleet_stats is O(#transitions) per round
        self.has_fleet = has_fleet(self.plan)
        self.transitions: Tuple[Tuple[int, int], ...] = (
            fleet_transitions(self.plan, self.num_workers)
            if self.has_fleet else ()
        )

    def describe(self) -> str:
        bits = [f"availability={self.availability}"]
        if self.dropout_prob:
            bits.append(f"dropout_prob={self.dropout_prob:g}")
        if self.plan:
            bits.append(f"chaos={len(self.plan)} event(s)")
        return "fedsim: " + " ".join(bits)

    def validate_rounds(self, num_rounds: int) -> None:
        """Reject chaos events referencing rounds the run never reaches —
        callable only where the run length is known (the train entries)."""
        validate_chaos_rounds(self.plan, num_rounds)

    # -- elastic fleet (all pure in round_idx; numpy/host only) ----------

    def width_at(self, round_idx: int) -> int:
        """The realized fleet width at ``round_idx`` — ``num_workers``
        when no fleet events are scheduled."""
        if not self.has_fleet:
            return self.num_workers
        return fleet_width_at(self.plan, self.num_workers, round_idx)

    def widths(self) -> Tuple[int, ...]:
        """Every width the run realizes (base first) — the session's AOT
        prewarm set."""
        return fleet_widths(self.plan, self.num_workers)

    def shrink_at(self, round_idx: int) -> Optional[int]:
        """W' of a shrink event opening at ``round_idx``, else None."""
        if not self.has_fleet:
            return None
        return fleet_shrink_at(self.plan, round_idx)

    def fleet_stats(self, round_idx: int) -> dict:
        """The ``fleet/*`` telemetry scalars for one round (empty when no
        fleet events — callers keep their constant key set either way).
        Schedule-derived, never runtime state, so rollback-replayed
        rounds re-emit identical values."""
        if not self.has_fleet:
            return {}
        resizes = 0
        last = -1
        for r, _w in self.transitions:
            if r <= round_idx:
                resizes += 1
                last = r
        return {
            "fleet/width": float(self.width_at(round_idx)),
            "fleet/resizes": float(resizes),
            "fleet/last_resize_round": float(last),
        }

    def round_env(self, round_idx: int, replay: bool = False,
                  width: Optional[int] = None) -> RoundEnv:
        """Realize round ``round_idx``'s masks + telemetry scalars —
        deterministic and resume-stable from (seed, round_idx). Pure and
        thread-safe: a fresh rng per call, nothing mutated, so the
        asyncfed staging worker realizes it ahead of the launch with the
        same masks. ``replay=True`` marks a round re-executed after a
        resilience/ rollback: the transient nan_client injection is
        suppressed (faults.apply_chaos), every other draw — and therefore
        every mask — is bit-identical to the first pass.

        ``width`` overrides the realized fleet width (the session's
        prewarm path realizes non-current widths ahead of time); by
        default the round's masks have ``width_at(round_idx)`` slots."""
        W = self.width_at(round_idx) if width is None else int(width)
        rng = round_rng(self.seed, round_idx)
        avail = sample_availability(
            self.availability, rng, round_idx,
            num_workers=W, dropout_prob=self.dropout_prob,
            period=self.period, num_cohorts=self.num_cohorts,
            rate=self.arrival_rate,
        )
        avail, straggler, corrupt = apply_chaos(
            self.plan, rng, round_idx, avail, replay=replay
        )
        live = avail & ~straggler
        n_live = int(live.sum())
        stats = {
            # live participants / num_workers — the ledger derives its
            # live-byte count from this scalar (exact for any W < 2^23:
            # the f32 round trip through the metrics pack recovers the
            # integer by rounding)
            "fedsim/participation_rate": n_live / W,
            "fedsim/dropped": float(W - int(avail.sum())),
            "fedsim/straggler_excluded": float(int((avail & straggler).sum())),
            "fedsim/all_dropped": float(n_live == 0),
            # scheduled preemption request (resilience/guard.py reads it
            # from the drained-round metrics at round granularity) —
            # host-side, constant key set, never traced
            "fedsim/preempt": float(preempt_requested(self.plan, round_idx)),
        }
        # fleet/* ride the same constant-key stats dict (3 extra keys for
        # the whole run iff any fleet event is scheduled) — the ledger and
        # controller read fleet/width to bill at the realized width
        stats.update(self.fleet_stats(round_idx))
        return RoundEnv(
            live=live.astype(np.float32),
            corrupt=corrupt.astype(np.float32),
            live_count=np.float32(n_live),
            stats=stats,
        )


def build_environment(cfg) -> Optional[FedEnvironment]:
    """The single construction gate: an environment iff the config turns
    any masking/chaos source on. None keeps every caller on the untouched
    fast path (nothing fedsim-related is traced or computed per round)."""
    if not getattr(cfg, "fedsim_enabled", False):
        return None
    return FedEnvironment(cfg)
