"""Console + TensorBoard logging.

Rebuilds the reference's observability layer (SURVEY.md §5: cifar10-fast
style ``TableLogger``/``Timer`` plus a TensorBoard ``SummaryWriter`` rooted
at an args-derived run dir — ``utils.py make_logdir`` ~L320-350,
``TableLogger``/``Timer`` ~L350-400). TensorBoard is optional: if no writer
backend is importable we degrade to console-only rather than crashing.

Since the telemetry PR this is also the drain point for the round-level
observability scalars: ``drain_round_metrics`` writes every namespaced
metric key (``diag/*`` in-graph diagnostics) and threads the optional
``telemetry.CommLedger``/``FlightRecorder`` riders; ``MetricsWriter``
stamps a run-header record and wall times so rows correlate across runs
(schema: README "Observability", scripts/check_telemetry_schema.py).
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional


class Timer:
    """Accumulating phase timer: ``t()`` returns seconds since last call."""

    def __init__(self):
        self._last = time.perf_counter()
        self.total = 0.0

    def __call__(self) -> float:
        now = time.perf_counter()
        dt = now - self._last
        self._last = now
        self.total += dt
        return dt


class TableLogger:
    """Aligned console table, one row per epoch (cifar10-fast style).

    Keys that first appear AFTER the header row was printed used to be
    silently dropped; now each new key warns once and is rendered in this
    and subsequent rows (the header line is not reprinted — the one-time
    warning names the column instead)."""

    def __init__(self, width: int = 12):
        self.width = width
        self._keys: Optional[list[str]] = None

    def append(self, row: dict) -> None:
        if self._keys is None:
            self._keys = list(row.keys())
            print(" | ".join(f"{k:>{self.width}s}" for k in self._keys))
        else:
            for k in row:
                if k not in self._keys:
                    print(f"TableLogger: new column {k!r} appeared after "
                          "the header row; rendering it in subsequent rows "
                          "(header not reprinted)", flush=True)
                    self._keys.append(k)
        cells = []
        for k in self._keys:
            v = row.get(k, "")
            if isinstance(v, float):
                cells.append(f"{v:>{self.width}.4f}")
            else:
                cells.append(f"{str(v):>{self.width}s}")
        print(" | ".join(cells), flush=True)


def make_logdir(cfg) -> str:
    """Run-dir name derived from the salient config fields (the reference
    derives it from args the same way)."""
    tag = f"{cfg.dataset_name}_{cfg.model}_{cfg.mode}_w{cfg.num_workers}_s{cfg.seed}"
    return os.path.join(cfg.logdir, tag + "_" + time.strftime("%Y%m%d-%H%M%S"))


class MetricsWriter:
    """Scalar metrics sink: TensorBoard if available, always a JSONL file.

    Scalar names match the reference's (train/loss, val/loss, val/acc, lr,
    ...) so curves are directly comparable; the telemetry PR adds the
    ``diag/*`` and ``comm/*`` namespaces (README "Observability" documents
    the full schema, scripts/check_telemetry_schema.py validates it).

    Every open writes a RUN-HEADER record first — config snapshot, jax
    version, device kind, wall-clock start — and every scalar record
    carries a wall-time field ``t``, so metrics.jsonl rows can be
    correlated across runs and with profiler traces. A resumed run appends
    a second header (one per process); records are self-describing by
    their ``type``/``name`` keys.
    """

    def __init__(self, logdir: str, enable_tensorboard: bool = False,
                 cfg=None, extra_header=None):
        self.logdir = logdir
        os.makedirs(logdir, exist_ok=True)
        self._jsonl = open(os.path.join(logdir, "metrics.jsonl"), "a")
        self._write_header(cfg, extra_header)
        self._tb = None
        if enable_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter  # type: ignore

                self._tb = SummaryWriter(logdir)
            except Exception as e:
                # degrade to JSONL-only, but SAY so (exception-hygiene):
                # the caller asked for tensorboard, and a silent None here
                # costs them the curves with no clue until hours later
                import warnings

                warnings.warn(
                    f"MetricsWriter: tensorboard unavailable "
                    f"({type(e).__name__}: {e}); logging JSONL-only"
                )
                self._tb = None

    def _write_header(self, cfg, extra_header=None) -> None:
        # lazy import: telemetry owns the versioned schema + the shared
        # run_metadata block (flight records embed the same one); the
        # config snapshot is sanitized like every other artifact so a
        # non-finite config float cannot poison line 1 with a bare NaN
        from commefficient_tpu.telemetry import (
            SCHEMA_VERSION,
            jsonable_tree,
            run_artifacts,
            run_metadata,
        )

        rec = {"type": "header", "schema_version": SCHEMA_VERSION,
               **run_metadata(cfg)}
        if cfg is not None:
            # v3: link the run to its profiling evidence (StepProfiler
            # trace logdir, the compiled-round perf_report.json) so a
            # metrics consumer can find them without guessing paths
            arts = run_artifacts(cfg, self.logdir)
            if arts:
                rec["artifacts"] = arts
        if extra_header:
            # v4: run-identifying blocks a caller supplies beyond the
            # config snapshot — e.g. the adaptive-communication controller
            # block (policy, ladder, initial rung: control.controller_header)
            rec.update(extra_header)
        self._jsonl.write(json.dumps(jsonable_tree(rec),
                                     allow_nan=False) + "\n")
        self._jsonl.flush()

    def scalar(self, name: str, value: float, step: int) -> None:
        # non-finite values (a diverging run's own loss — exactly the rows
        # forensics needs) are stringified "nan"/"inf"/"-inf" so the file
        # stays STRICT JSON per line (json.dumps would emit a bare NaN
        # token that jq/JS/strict parsers reject); allow_nan=False makes
        # any regression here a loud error, not a corrupt artifact
        from commefficient_tpu.telemetry import jsonable_scalar

        self._jsonl.write(
            json.dumps({"name": name, "value": jsonable_scalar(value),
                        "step": int(step), "t": time.time()},
                       allow_nan=False) + "\n"
        )
        if self._tb is not None:
            self._tb.add_scalar(name, float(value), int(step))

    def flush(self) -> None:
        self._jsonl.flush()
        if self._tb is not None:
            self._tb.flush()

    def close(self) -> None:
        self.flush()
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()


_PACKER_CACHE: dict = {}


def pack_metric_dicts(dicts):
    """Fetch N same-keyed dicts of device scalars as ONE host [N, K] array.

    Everything happens inside a single jitted program: one dispatch and
    one fetch instead of an eager op (or a device_get) per scalar.
    Jit caches per (N, key set); train epochs and eval passes have constant
    N, so each shape compiles once per process.

    Returns (names, mat) with ``mat[j, i] == float(dicts[j][names[i]])``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    names = tuple(sorted(dicts[0]))
    for j, m in enumerate(dicts):
        if tuple(sorted(m)) != names:
            # a mixed batch would silently index missing keys inside the
            # jitted pack (KeyError mid-trace at best) — reject it here
            # with the offending entry named
            raise ValueError(
                f"pack_metric_dicts: mixed key sets — dict {j} has "
                f"{tuple(sorted(m))}, expected {names}; all packed "
                "metric dicts must share one key set"
            )
    key = (len(dicts), names)
    pack = _PACKER_CACHE.get(key)
    if pack is None:

        @jax.jit
        def pack(ms):
            return jnp.stack(
                [
                    jnp.stack([jnp.asarray(m[k], jnp.float32) for k in names])
                    for m in ms
                ]
            )

        _PACKER_CACHE[key] = pack
    return names, np.asarray(pack(tuple(dicts)))


def drain_round_metrics(pending, writer, accumulate, ledger=None,
                        flight=None, controller=None) -> None:
    """Fetch buffered per-round DEVICE metrics and clear the buffer.

    Train loops append ``(step, lr, metrics)`` without fetching (a float()
    per round is a full dispatch fence that serializes the round
    pipeline) and drain at epoch end and
    before checkpoint writes (a resume fast-forwards past checkpointed
    rounds, so logs unflushed at save time would be lost for good). Writes
    the common train/loss + lr scalars plus every NAMESPACED metric key
    (``diag/*`` from the in-graph diagnostics — any key containing "/" is
    a scalar by schema); per-workload accumulation goes through
    ``accumulate(loss, metrics)``.

    Telemetry riders (both optional, telemetry_level >= 1):
      ``ledger`` — a telemetry.CommLedger; its per-round ``comm/*`` scalars
        are written at each drained step.
      ``flight`` — a telemetry.FlightRecorder; each drained round is
        recorded, then CHECKED in step order — a non-finite loss or a fired
        ``diag/nonfinite`` sentinel dumps flight_<step>.json and raises
        ``DivergenceError`` naming the first bad round. The buffer is
        cleared and the writer flushed even on that raise, so the bad
        rounds' scalars survive for the post-mortem.
      ``controller`` — a control.BudgetController (duck-typed
        ``observe_drained(step, scalars)``); each drained round's scalars
        feed the rung-selection policy in step order (the ``ef_feedback``
        loop's telemetry input).
    """
    if not pending:
        return
    names, mat = pack_metric_dicts([m for _, _, m in pending])
    try:
        for j, (s, s_lr, _) in enumerate(pending):
            metrics = {k: mat[j, i] for i, k in enumerate(names)}
            loss = float(metrics["loss"])
            if writer:
                writer.scalar("train/loss", loss, s)
                writer.scalar("lr", s_lr, s)
                for k in names:
                    if "/" in k:
                        writer.scalar(k, float(metrics[k]), s)
            # the round's metric dict rides along: a fedsim-masked ledger
            # recovers the live/avail client counts from its fedsim/*
            # scalars (telemetry/ledger.py masked accounting)
            comm = ledger.on_round(s, metrics) if ledger is not None else {}
            if writer:
                for k, v in comm.items():
                    writer.scalar(k, v, s)
            accumulate(loss, metrics)
            if controller is not None:
                controller.observe_drained(s, metrics)
            if flight is not None:
                flight.record(s, s_lr, {
                    **{k: float(metrics[k]) for k in names}, **comm,
                })
                flight.check(s, loss, metrics)  # may raise DivergenceError
    finally:
        pending.clear()
        if writer:
            writer.flush()
