"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls —
``train/cv_train.py::main`` and ``train/gpt2_train.py::main`` ->
``train/runner.py`` -> ``FederatedSession`` -> ``parallel/round.py`` — at the
full width of the models the repo supports, with ordinary flags and random
weights made from the seed, then checks every Pallas kernel left in the tree
against its einsum/jnp twin at both production geometries. One process: a
chip belongs to one process at a time, so nothing here starts a child that
needs it.

    python chip_smoke.py                  # every phase this machine can run
    python chip_smoke.py device multichip # only these (the four-chip host)

Phases (each prints one ``[phase] {json}`` line of smoke OBSERVATIONS — not
metrics: one run, compile mixed in, no warm-up discipline):

  device       what JAX found, versions, and the compile cache in force
  cv_sketch    BASELINE #2: ResNet-9 (D=6.57M) FetchSGD round, 8 workers
  gpt2_sketch  BASELINE #4: GPT-2-small (D=124M) sketch round, entry defaults
               (per-client vmap + clip), eval and sample generation
  kernels      every ``pl.pallas_call`` compiled by Mosaic vs its twin at
               d=6.57M/c=500k and d=124M/c=5M, then cv_sketch through the
               entry with ``--sketch_backend pallas``
  multichip    (>= 4 devices) cv_sketch and local_topk with
               ``--num_devices 4``: sharded decode / sparse aggregate

Any failing phase raises: the exit code is non-zero and no result line is
printed. Exits non-zero naming the platform when JAX finds no TPU. The last
line of stdout is ``{"ok": true, "device": {...}}`` as JAX reports the device.
Run records go to ``chiprun_out/smoke/`` next to this file.
"""

from __future__ import annotations

import gc
import glob
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "smoke")

# the FetchSGD configuration of BASELINE #2 / #4 (the source paper's own)
SKETCH = ["--mode", "sketch", "--k", "50000", "--num_rows", "5",
          "--error_type", "virtual", "--virtual_momentum", "0.9",
          "--topk_method", "threshold", "--num_workers", "8"]
# the two production sketch geometries (d = the models' flat sizes)
GEOMETRIES = {"resnet9": (6_573_130, 500_000), "gpt2": (124_444_417, 5_000_000)}

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
EVERY_EVENT = TRACE_EVENTS + (COMPILE_EVENT,)


class CompileLog:
    """What JAX says it spent compiling, with wall-clock stamps, so a phase
    can report compile seconds apart from the rounds it ran."""

    def __init__(self, cache_dir):
        self.cache_dir = cache_dir
        self.durations = []  # (t_end, event, seconds)
        self.cache = {"hits": 0, "misses": 0}

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in EVERY_EVENT:
            self.durations.append((time.time(), event, float(seconds)))

    def _event(self, event, **_):
        if event.endswith("/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            self.cache["misses"] += 1

    def seconds(self, since, until=None, events=(COMPILE_EVENT,)):
        return sum(s for t, e, s in self.durations
                   if e in events and t >= since
                   and (until is None or t <= until))

    def cache_entries(self):
        d = self.cache_dir
        return len(os.listdir(d)) if os.path.isdir(d) else 0


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _fresh_dir(name):
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _scalars(logdir):
    """{name: [(step, value, wall t), ...]} of the one run under logdir."""
    runs = glob.glob(os.path.join(logdir, "*", "metrics.jsonl"))
    if len(runs) != 1:
        raise RuntimeError(f"expected one run dir under {logdir}, found {runs}")
    out = {}
    with open(runs[0]) as f:
        for line in f:
            rec = json.loads(line)
            if "name" in rec:
                out.setdefault(rec["name"], []).append(
                    (rec["step"], rec["value"], rec["t"]))
    return out, os.path.dirname(runs[0])


def _run_entry(phase, main, argv, log, *, epochs, val_keys):
    """One run through a train entry; returns the phase's observations.
    ``epochs`` >= 2 lets the last epoch stand for the steady state: the
    first one holds the compiles."""
    import jax

    logdir = _fresh_dir(phase)
    argv = argv + ["--num_epochs", str(epochs), "--pivot_epoch", "1",
                   "--dataset_dir", os.path.join(logdir, "no_dataset"),
                   "--logdir", logdir]
    print(f"[{phase}] main({' '.join(argv)})", flush=True)
    t0 = time.time()
    val = main(argv)
    wall = time.time() - t0
    scalars, run_dir = _scalars(logdir)
    losses = scalars["train/loss"]
    rounds = len(losses)
    last_loss = losses[-1][1]
    if not (isinstance(last_loss, float) and math.isfinite(last_loss)):
        raise RuntimeError(f"{phase}: last train loss is {last_loss!r}")
    for k in val_keys:
        if not math.isfinite(float(val[k])):
            raise RuntimeError(f"{phase}: eval {k} is {val[k]!r}")
    obs = {
        "rounds": rounds,
        "last_loss": round(last_loss, 4),
        "eval": {k: round(float(val[k]), 4) for k in val_keys},
        "wall_s": round(wall, 1),
        "compile_s": round(log.seconds(t0), 1),
        "trace_lower_s": round(log.seconds(t0, events=TRACE_EVENTS), 1),
        "process_peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }
    if epochs >= 2:
        # the last epoch's window: from the previous epoch's val records to
        # this epoch's drain (every train/loss of an epoch is stamped at its
        # drain); compiles that still fall inside it are taken out. Host
        # data loading — and gpt2's 24-token sample generation — stay in.
        spe = rounds // epochs
        first = (epochs - 1) * spe
        t_start = max(t for name, recs in scalars.items()
                      if name.startswith("val/")
                      for step, _, t in recs if step == first)
        t_end = min(t for step, _, t in losses if step >= first)
        inside = log.seconds(t_start, t_end, events=EVERY_EVENT)
        obs["last_epoch_rounds"] = rounds - first
        obs["last_epoch_compile_s"] = round(inside, 2)
        obs["steady_s_per_round"] = round(
            (t_end - t_start - inside) / (rounds - first), 4)
    obs["run_dir"] = os.path.relpath(run_dir, HERE)
    return obs


def _release():
    """Drop what a finished phase left on the device before the next one
    (executables hold their constants; GPT-2's one-hot alone is ~170 MB)."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def phase_device(log):
    import importlib.metadata as md

    import jax
    import jaxlib

    from commefficient_tpu.utils.platform import COMPILE_CACHE_ENV

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": md.version("libtpu"),
        "compile_cache_dir": log.cache_dir,
        "compile_cache_placed_by": (
            COMPILE_CACHE_ENV if os.environ.get(COMPILE_CACHE_ENV)
            else "utils.platform.COMPILE_CACHE_DIR"),
        "compile_cache_entries_at_start": log.cache_entries(),
    }


def phase_cv_sketch(log):
    from commefficient_tpu.train import cv_train

    # synthetic CIFAR stand-in (real=False in the entry's own output): 50k
    # samples / (8 workers x 256) = 24 rounds an epoch
    return _run_entry(
        "cv_sketch", cv_train.main,
        SKETCH + ["--num_cols", "500000", "--local_batch_size", "256"],
        log, epochs=2, val_keys=("loss", "accuracy"))


def phase_gpt2_sketch(log):
    from commefficient_tpu.train import gpt2_train

    # published widths (12 L, 768, V=50257+specials), random init; synthetic
    # PersonaChat: 28 clients x 7 train dialogs / (8 x 4) = 6 rounds an epoch
    return _run_entry(
        "gpt2_sketch", gpt2_train.main,
        SKETCH + ["--model", "gpt2", "--num_cols", "5000000",
                  "--local_batch_size", "4", "--max_seq_len", "256",
                  "--num_clients", "28"],
        log, epochs=2, val_keys=("nll", "ppl", "mc_accuracy"))


def _check_close(name, got, want, *, rtol):
    """The CPU parity tests' own closeness (tests/test_countsketch_pallas.py
    ``assert_close``): absolute tolerance scaled to the data, reduced on
    the device (the GPT-2 estimate stack is 2.5 GB)."""
    import jax.numpy as jnp

    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    err = float(jnp.max(jnp.abs(got - want)))
    if not err <= rtol * scale:
        raise RuntimeError(
            f"kernels: {name} differs from its twin by {err:.3e} "
            f"(allowed {rtol:.0e} x scale {scale:.3e})")
    return err / scale


def phase_kernels(log):
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.ops.countsketch import (
        CountSketch,
        _median_rows,
        estimate_all,
        sketch_vec,
    )
    from commefficient_tpu.ops.pallas import (
        kernels_interpreted,
        median_rows_pallas,
    )
    from commefficient_tpu.train import cv_train

    if kernels_interpreted():
        raise RuntimeError("kernels: Pallas would run interpreted here")
    t0 = time.time()
    obs = {}
    for name, (d, c) in GEOMETRIES.items():
        einsum = CountSketch(d=d, c=c, r=5)
        pallas = einsum._replace(backend="pallas")
        v = jax.random.normal(jax.random.key(0), (d,), jnp.float32)
        # planted heavy hitters, like the CPU tests' planted_vector
        v = v.at[jnp.arange(0, d, d // 64)].add(100.0)
        table = jax.jit(lambda x: sketch_vec(einsum, x))(v)
        rel = {"sketch_vec": _check_close(
            f"{name} sketch_vec", jax.jit(lambda x: sketch_vec(pallas, x))(v),
            table, rtol=3e-6)}
        del v
        est = jax.jit(lambda t: estimate_all(einsum, t))(table)
        rel["estimate_all"] = _check_close(
            f"{name} estimate_all",
            jax.jit(lambda t: estimate_all(pallas, t))(table), est, rtol=3e-6)
        del est
        rows = jax.random.normal(jax.random.key(1), (5, d), jnp.float32)
        rel["median_rows"] = _check_close(
            f"{name} median_rows", jax.jit(median_rows_pallas)(rows),
            jax.jit(_median_rows)(rows), rtol=0.0)
        del rows, table
        obs[name] = {"d": d, "c": c, "table": list(einsum.table_shape),
                     "rel_err_vs_twin": rel}
        _release()
    obs["twins_wall_s"] = round(time.time() - t0, 1)
    obs["twins_compile_s"] = round(log.seconds(t0), 1)
    # ... and inside the round, through the entry: 12 rounds, one epoch
    obs["cv_sketch_pallas"] = _run_entry(
        "cv_sketch_pallas", cv_train.main,
        SKETCH + ["--num_cols", "500000", "--local_batch_size", "512",
                  "--sketch_backend", "pallas"],
        log, epochs=1, val_keys=("loss", "accuracy"))
    return obs


def phase_multichip(log):
    import jax

    from commefficient_tpu.train import cv_train

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"multichip needs 4 devices, JAX sees {len(devs)}")
    four = ["--num_devices", "4", "--local_batch_size", "256",
            # level 1 writes perf_report.json: what the session RESOLVED
            "--telemetry_level", "1"]
    runs = {
        "cv_sketch": (SKETCH + ["--num_cols", "500000"] + four,
                      ("sketch_decode", "sharded")),
        "local_topk": (["--mode", "local_topk", "--error_type", "local",
                        "--topk_method", "threshold", "--k", "50000",
                        "--num_workers", "8"] + four,
                       ("aggregate", "sparse")),
    }
    obs = {}
    for name, (argv, (field, want)) in runs.items():
        o = _run_entry(f"multichip_{name}", cv_train.main, argv, log,
                       epochs=2, val_keys=("loss", "accuracy"))
        with open(os.path.join(HERE, o["run_dir"], "perf_report.json")) as f:
            report = json.load(f)
        if report[field] != want:
            raise RuntimeError(
                f"multichip: {name} resolved {field}={report[field]!r}, "
                f"expected {want!r}")
        o[field] = report[field]
        o["collectives"] = report["collectives"]["ops"]
        obs[name] = o
        _release()
    peaks = [_peak_bytes(d) for d in devs[:4]]
    if not all(p > 0 for p in peaks):
        raise RuntimeError(f"multichip: a device was never used: {peaks}")
    obs["process_peak_bytes_in_use_per_device"] = peaks
    return obs


PHASES = {
    "device": phase_device,
    "cv_sketch": phase_cv_sketch,
    "gpt2_sketch": phase_gpt2_sketch,
    "kernels": phase_kernels,
    "multichip": phase_multichip,
}


def main(argv=None) -> int:
    names = list(sys.argv[1:] if argv is None else argv)
    unknown = [n for n in names if n not in PHASES]
    if unknown:
        print(f"chip_smoke: unknown phase(s) {unknown}; known: "
              f"{list(PHASES)}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind!r} x {len(devs)})",
              file=sys.stderr)
        return 1
    if not names:
        names = [n for n in PHASES if n != "multichip" or len(devs) >= 4]
    # first thing after the device check, like every entry point — and the
    # import that fails where this script stands alone without the program
    from commefficient_tpu.utils.platform import configure_compile_cache

    log = CompileLog(configure_compile_cache())
    log.install()
    t0 = time.time()
    for name in names:
        t = time.time()
        obs = PHASES[name](log)
        obs["phase_wall_s"] = round(time.time() - t, 1)
        print(f"[{name}] " + json.dumps(obs), flush=True)
        _release()
    print("[summary] " + json.dumps({
        "phases": names,
        "wall_s": round(time.time() - t0, 1),
        "compile_s": round(log.seconds(t0), 1),
        "compile_cache": log.cache,
        "compile_cache_entries_at_end": log.cache_entries(),
        "note": "smoke observations of one run, not metrics",
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
