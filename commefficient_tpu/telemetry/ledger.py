"""Communication ledger — loss-vs-BYTES is the paper's actual x-axis.

FetchSGD's headline figures plot accuracy against bytes communicated, not
rounds; this module turns each ``Compressor``'s ``upload_floats`` /
``download_floats`` accounting (the ``bytes_per_round`` dict PR 2 put on
the compressor classes) into per-round ``comm/*`` scalars riding
``drain_round_metrics`` and a ``comm_ledger.json`` summary per run dir, so
ACCURACY runs can draw the paper's curves directly from ``metrics.jsonl``.

All byte counts are per PARTICIPATING CLIENT per round (the reference's
own accounting in BASELINE.md — compression ratios are per-client-link
properties); ``num_workers`` rides the ledger so fleet totals are one
multiply away. Counts are exact ints: ``cum_up_bytes`` after R drained
rounds is EXACTLY ``R * bytes_per_round["upload_bytes"]`` (pinned per mode
by tests/test_telemetry.py). A resumed run counts only the rounds THIS
process drained — the ledger is an observer of the live process, not a
reconstruction of the whole training history (the per-step ``comm/cum_*``
scalars in metrics.jsonl are what survives across resumes).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, Optional


def run_metadata(cfg=None, extra: Optional[dict] = None) -> dict:
    """The run-identifying metadata block shared by the metrics.jsonl
    header, flight records, and the comm ledger: config snapshot, jax +
    device identity, wall-clock start. ``cfg`` is duck-typed (a
    ``utils.config.Config`` dataclass normally; any mapping-convertible
    object otherwise). The device identity is not optional: every record
    of a run names the platform, kind and count it ran on, and a backend
    that cannot say fails the run here rather than leaving an anonymous
    header behind."""
    import jax

    devs = jax.devices()
    meta: dict = {
        "time": time.time(),
        "start_time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "jax_version": jax.__version__,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "backend": jax.default_backend(),
    }
    if cfg is not None:
        if dataclasses.is_dataclass(cfg):
            meta["config"] = dataclasses.asdict(cfg)
        else:
            meta["config"] = {
                k: v for k, v in vars(cfg).items() if not k.startswith("_")
            }
    if extra:
        meta.update(extra)
    return meta


class CommLedger:
    """Exact uplink/downlink byte accounting over the drained rounds.

    ``on_round(step, scalars)`` is called once per DRAINED round (drain
    order == step order) and returns the scalars to emit at that step;
    ``write`` persists the summary. Constructed by the train loops at
    ``telemetry_level >= 1`` from ``session.bytes_per_round()`` — the same
    numbers the session prints at startup, so the ledger can never drift
    from the accounting the compressor declares.

    fedsim masked accounting (``masked=True``, set iff the run's
    ``cfg.fedsim_enabled``): only LIVE clients transmitted, so the round's
    uplink is the live count x the per-client payload (through the
    compressor's ``masked_upload_floats`` hook when one is supplied — the
    hook, not this class, owns the every-mode-is-linear claim), and the
    downlink counts every AVAILABLE client (stragglers downloaded params
    before missing the deadline; dropped clients never joined). The
    exactness invariant becomes ``cum_up_bytes == live_client_rounds x
    upload_bytes`` with ``live_client_rounds = sum of live_i`` — enforced
    by scripts/check_telemetry_schema.py. Live/avail counts are recovered
    from the drained ``fedsim/*`` scalars riding the same metric dict, so
    the ledger can never disagree with what the run logged.
    """

    def __init__(self, bytes_per_round: Dict[str, int], *, mode: str,
                 num_workers: int, masked: bool = False, compressor=None,
                 rungs=None):
        self.bytes_per_round = {k: int(v) for k, v in bytes_per_round.items()}
        self.mode = mode
        self.num_workers = int(num_workers)
        self.masked = bool(masked)
        self._comp = compressor  # duck-typed: masked_upload_floats(live)
        # control/ ladder accounting (schema v4): ``rungs`` is the ordered
        # [(bytes_per_round dict, compressor), ...] of the session's
        # compression ladder; each drained round is billed at the rung its
        # ``control/rung`` scalar names (riding the same metric dict, the
        # fedsim-recovery pattern), and the exactness invariant becomes the
        # SUM over rungs of that rung's rounds x its bytes_per_round
        # (live-count-weighted under masking) — checker-enforced.
        self.rungs = None
        if rungs is not None:
            self.rungs = [
                {"bytes_per_round": {k: int(v) for k, v in bpr.items()},
                 "compressor": comp, "rounds": 0,
                 "live_client_rounds": 0, "avail_client_rounds": 0}
                for bpr, comp in rungs
            ]
        self.rounds = 0
        self.cum_up_bytes = 0
        self.cum_down_bytes = 0
        self.live_client_rounds = 0
        self.avail_client_rounds = 0

    def _counts(self, scalars: Optional[Dict[str, float]]):
        """(live, avail) client counts for one drained round, recovered
        from the fedsim/* scalars (exact: live/W round-trips f32 losslessly
        enough to re-round for any real W). Missing scalars mean full
        participation — a masked ledger stays consistent even if a run
        mixes in fedsim-less rounds."""
        scalars = scalars or {}
        # elastic-fleet rounds bill at the round's REALIZED width (the
        # fedsim/* rates are relative to it, schema v13) — the base
        # num_workers otherwise; the fleet/width scalar rides the same
        # drained dict, so the ledger can never disagree with the run
        W = int(round(float(scalars.get("fleet/width", self.num_workers))))
        rate = scalars.get("fedsim/participation_rate")
        live = W if rate is None else int(round(float(rate) * W))
        avail = W - int(round(float(scalars.get("fedsim/dropped", 0.0))))
        return live, avail

    def on_round(self, step: int,
                 scalars: Optional[Dict[str, float]] = None) -> Dict[str, float]:
        """Account one drained round; returns this step's comm/* scalars.
        ``scalars`` is the round's drained metric dict (the fedsim/*
        participation scalars live there, and — for ladder runs — the
        ``control/rung`` scalar naming which rung this round ran at)."""
        rung_rec = None
        bpr, comp = self.bytes_per_round, self._comp
        if self.rungs is not None:
            # the round's active rung from its own drained scalar — the
            # ledger can never disagree with what the run logged
            r = int(round(float((scalars or {}).get("control/rung", 0.0))))
            if not 0 <= r < len(self.rungs):
                raise ValueError(
                    f"drained round {step} names rung {r}, but the ledger "
                    f"was built for {len(self.rungs)} rung(s)"
                )
            rung_rec = self.rungs[r]
            bpr, comp = rung_rec["bytes_per_round"], rung_rec["compressor"]
        up = bpr["upload_bytes"]
        down = bpr["download_bytes"]
        if self.masked:
            live, avail = self._counts(scalars)
            # bytes-per-float through the compressor hook so bf16-table
            # payloads (2 B/float) keep the exactness invariant
            up = (comp.upload_bytes_per_float()
                  * comp.masked_upload_floats(live)
                  if comp is not None else live * up)
            down = avail * down
            self.live_client_rounds += live
            self.avail_client_rounds += avail
            if rung_rec is not None:
                rung_rec["live_client_rounds"] += live
                rung_rec["avail_client_rounds"] += avail
        if rung_rec is not None:
            rung_rec["rounds"] += 1
        self.rounds += 1
        self.cum_up_bytes += up
        self.cum_down_bytes += down
        return {
            "comm/up_bytes": up,
            "comm/down_bytes": down,
            "comm/cum_up_bytes": self.cum_up_bytes,
            "comm/cum_down_bytes": self.cum_down_bytes,
            "comm/cum_bytes": self.cum_up_bytes + self.cum_down_bytes,
        }

    # -- resilience/ rollback support --------------------------------------
    def snapshot_state(self) -> dict:
        """The ledger's mutable counters, host ints only — captured by the
        resilience RollbackVault at each drain-certified snapshot boundary
        so a divergence rollback can rewind the accounting: replayed
        rounds then bill exactly once and the exactness invariant
        (checker-enforced) survives recovery."""
        out = {
            "rounds": self.rounds,
            "cum_up_bytes": self.cum_up_bytes,
            "cum_down_bytes": self.cum_down_bytes,
            "live_client_rounds": self.live_client_rounds,
            "avail_client_rounds": self.avail_client_rounds,
        }
        if self.rungs is not None:
            out["rungs"] = [
                {k: r[k] for k in ("rounds", "live_client_rounds",
                                   "avail_client_rounds")}
                for r in self.rungs
            ]
        return out

    def load_snapshot_state(self, state: dict) -> None:
        """Rewind to a ``snapshot_state`` capture (resilience rollback)."""
        self.rounds = int(state["rounds"])
        self.cum_up_bytes = int(state["cum_up_bytes"])
        self.cum_down_bytes = int(state["cum_down_bytes"])
        self.live_client_rounds = int(state["live_client_rounds"])
        self.avail_client_rounds = int(state["avail_client_rounds"])
        if self.rungs is not None:
            saved = state.get("rungs")
            if saved is None or len(saved) != len(self.rungs):
                raise ValueError(
                    "ledger snapshot rung count does not match this "
                    "ledger's ladder — the snapshot was captured under a "
                    "different control config"
                )
            for rec, s in zip(self.rungs, saved):
                for k in ("rounds", "live_client_rounds",
                          "avail_client_rounds"):
                    rec[k] = int(s[k])

    def summary(self) -> dict:
        from commefficient_tpu.telemetry import SCHEMA_VERSION

        out = {
            "schema_version": SCHEMA_VERSION,
            "mode": self.mode,
            "num_workers": self.num_workers,
            "bytes_per_round": self.bytes_per_round,
            "rounds": self.rounds,
            "cum_up_bytes": self.cum_up_bytes,
            "cum_down_bytes": self.cum_down_bytes,
            "cum_bytes": self.cum_up_bytes + self.cum_down_bytes,
        }
        if self.masked:
            # fedsim live-byte invariant (checker-enforced):
            #   cum_up_bytes == live_client_rounds * upload_bytes
            #   cum_down_bytes == avail_client_rounds * download_bytes
            out["live_client_rounds"] = self.live_client_rounds
            out["avail_client_rounds"] = self.avail_client_rounds
        if self.rungs is not None:
            # control/ ladder accounting (schema v4): per-rung rounds +
            # byte rates; the checker-enforced invariant becomes
            #   cum_up_bytes == sum_r rounds_r * up_r            (full)
            #   cum_up_bytes == sum_r live_r * up_r              (masked)
            # and likewise for the downlink — exact ints, no tolerance.
            out["rungs"] = [
                {k: v for k, v in r.items() if k != "compressor"
                 and (self.masked or not k.endswith("_client_rounds"))}
                for r in self.rungs
            ]
        return out

    def write(self, logdir: str) -> str:
        """Write ``comm_ledger.json`` into the run dir; returns the path."""
        os.makedirs(logdir, exist_ok=True)
        path = os.path.join(logdir, "comm_ledger.json")
        with open(path, "w") as f:
            json.dump(self.summary(), f, indent=2)
        return path
