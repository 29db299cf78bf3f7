"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json``) under a traffic mix
(``benchmark/traffic/<traffic>.json``). The run builds the federated round
as the configuration's train entry builds it for a user, with weights and
data from ``--seed``; follows its first three rounds for ``correct``; warms
up; measures for ``--seconds``; and prints one JSON object as the last line
of standard output. ``benchmark/README.md`` says how to add a cell, a
configuration or a per-layer metric as files of their own.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start, as near as the interpreter lets us

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEED_MODULUS = 2**31 - 1  # seeds are folded into what every generator takes
RUN_AHEAD = 2             # rounds the host may dispatch ahead of the device
FOLLOWED = 3              # rounds the reference follows
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration and traffic files resolved
    by the names ``BENCHMARK.json`` gives."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(ROOT, entry["file"])
    cell["traffic_file"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["bench"] = bench
    return cell


def cell_metrics(cell: dict, group: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in cell["bench"][group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_peaks(device_kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json "
                       f"(known: {sorted(peaks)}); a peak is never guessed")
    return peaks[device_kind]


class CompileLog:
    """What JAX says it spent tracing, lowering and compiling, with stamps,
    so that set-up and window can be told apart."""

    def __init__(self):
        self.durations = []  # (t_end, event, seconds)

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, seconds, **_):
        if event == COMPILE_EVENT or event in TRACE_EVENTS:
            self.durations.append((time.time(), event, float(seconds)))

    def seconds(self, events, since=0.0, until=math.inf):
        return sum(s for t, e, s in self.durations if e in events and since <= t <= until)

    def count(self, events, since=0.0, until=math.inf):
        return sum(1 for t, e, _ in self.durations if e in events and since <= t <= until)


class Spans:
    """The benchmark's own host spans around the calls into the round source
    and the session. Totals are kept per window; while a trace is on, each
    span is also written into the profiler's trace (``bench/...``) so that
    device gaps can be laid against them."""

    def __init__(self):
        self.fed = []  # the first FOLLOWED items the round source handed over
        self.reset()

    def reset(self):
        self.data_wait_s = 0.0
        self.call_s = 0.0
        self.rounds = 0

    def wrap_iter(self, it, _name):
        # the runner hands its round source through this: each next() is
        # the wait for the sampler / prefetch thread
        import jax

        it = iter(it)
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/data_wait"):
                try:
                    item = next(it)
                except StopIteration:
                    return
                finally:
                    self.data_wait_s += time.perf_counter() - t
            if len(self.fed) < FOLLOWED:
                self.fed.append(item)
            yield item

    def step(self, _s):
        pass

    @property
    def dispatch_s(self):
        return self.call_s - self.data_wait_s


class _NoProfiler:
    @staticmethod
    def step(_s):
        pass


class Rounds:
    """The runner's own synchronous round source, epoch after epoch, at the
    traffic mix's fixed learning rate. ``next()`` draws and dispatches one
    round and returns its (device-side) metrics."""

    def __init__(self, cfg, session, sampler, lr):
        from commefficient_tpu.train import runner

        self._source = runner._sync_epoch_rounds
        self.cfg, self.session, self.sampler, self.lr = cfg, session, sampler, lr
        self.spans = Spans()
        self.steps_per_epoch = sampler.steps_per_epoch()
        self.step = 0
        self._gen = None

    def next(self):
        import jax

        while True:
            if self._gen is None:
                self._gen = self._source(
                    self.cfg, self.session, self.sampler, lambda _s: self.lr,
                    self.spans, _NoProfiler, self.step // self.steps_per_epoch,
                    self.step, self.steps_per_epoch)
            t = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench/dispatch"):
                    s, _lr, metrics = next(self._gen)
            except StopIteration:
                self._gen = None
                continue
            finally:
                self.spans.call_s += time.perf_counter() - t
            self.spans.rounds += 1
            self.step = s + 1
            return metrics

    def close(self):
        if self._gen is not None:
            self._gen.close()
            self._gen = None


def fence(session):
    import jax

    with jax.profiler.TraceAnnotation("bench/fence"):
        jax.block_until_ready(session.state.params_vec)


def drive(rounds: Rounds, session, *, seconds=None, count=None):
    """Dispatch rounds until ``seconds`` have run out (or ``count`` are
    out), at most ``RUN_AHEAD`` ahead of the device; fenced at both ends.
    Returns ``(elapsed seconds between the fences, [loss of each round],
    rounds that raised)``."""
    import jax

    losses, raised = [], 0
    fence(session)
    t0 = time.perf_counter()
    while (count is None or len(losses) + raised < count) and (
            seconds is None or time.perf_counter() - t0 < seconds):
        try:
            losses.append(rounds.next()["loss"])
        except Exception as e:  # noqa: BLE001 — counted, reported, not hidden
            raised += 1
            print(f"benchmark: round raised {type(e).__name__}: {e}", file=sys.stderr)
            if raised > 3:
                raise
            continue
        if len(losses) > RUN_AHEAD:
            with jax.profiler.TraceAnnotation("bench/run_ahead_wait"):
                jax.block_until_ready(losses[-1 - RUN_AHEAD])
    fence(session)
    return time.perf_counter() - t0, losses, raised


def follow(rounds: Rounds, session) -> dict:
    """Drive the first ``FOLLOWED`` rounds through the window's own call and
    feed, keeping host copies of what the comparison reads: the parameters
    before, after one round and after the last, and the momentum bank after
    one round."""
    p0 = host_copy(session.state.params_vec)
    _, first, raised0 = drive(rounds, session, count=1)
    p1, bank1 = host_copy(session.state.params_vec), host_copy(session.state.momentum)
    _, more, raised1 = drive(rounds, session, count=FOLLOWED - 1)
    if raised0 + raised1:
        raise RuntimeError("a round raised among the first rounds")
    return {"p0": p0, "p1": p1, "p3": host_copy(session.state.params_vec),
            "bank1": bank1, "losses": first + more}


def host_copy(x):
    import numpy as np

    return np.asarray(x) if getattr(x, "size", 0) else None


def fed_batches(fed, sampler):
    """The first rounds' batches as the benchmark itself reads them, and how
    far the program's two feeds are from that.

    ``fed`` holds what the round source handed the session in the followed
    rounds. On the device-index path that is ``(client_ids, idx, plan)``: the
    batch is then gathered here, in numpy, from the dataset's rows at the very
    indices the session was given, so the session's own gather has a witness
    that is not the program's. (Where an augmentation ``plan`` rides along,
    the sampler's host path applies it: the augmenter is the program's.) On
    the host path it is ``(client_ids, batch)`` and is taken as fed.

    ``feed`` counts what does not hold, and is compared with the limit 0:
    elements in which the sampler's host path (``sample_round``) draws another
    batch for the same round than the one fed; clients drawn twice in a
    round; rows that are not the drawn client's own."""
    import numpy as np

    data, shards = sampler.dataset.data, sampler.dataset.client_indices
    batches, off = [], 0
    for s, item in enumerate(fed):
        clients, host = sampler.sample_round(s)
        if len(item) == 3:
            ids, idx, plan = item
            idx = np.asarray(idx)
            batch = host if plan else {k: np.asarray(v)[idx] for k, v in data.items()}
            off += sum(int(np.sum(~np.isin(row, shards[int(c)]))) for c, row in zip(ids, idx))
        else:
            ids, batch = item
        off += len(ids) - len(set(int(c) for c in ids)) + int(np.sum(np.asarray(ids) != clients))
        off += sum(int(np.sum(np.asarray(batch[k]) != host[k])) for k in host)
        batches.append({k: np.asarray(v) for k, v in batch.items()})
    return batches, float(off)


def reference_inputs(cell, fed, sampler, params_tree):
    """What the reference needs, none of it computed by the program under
    test: the leaves' names and extents, the first rounds' batches
    (``fed_batches``), and the round's stated algebra (from the configuration
    and traffic files, not from the program's parsed flags)."""
    import jax
    import numpy as np

    from benchmark import weights
    from benchmark.reference.round import Algo

    names = weights.leaf_names(params_tree)
    shapes = [tuple(a.shape) for a in jax.tree.leaves(params_tree)]
    leaves, at = [], 0
    for n, s in zip(names, shapes):
        size = int(np.prod(s))
        leaves.append((n, at, at + size))
        at += size
    batches, feed = fed_batches(fed, sampler)
    ref_c = cell["config_file"]["reference"]
    ref_t = cell["traffic_file"]["reference"]
    algo = Algo(lr=float(cell["traffic_file"]["lr"]),
                weight_decay=float(ref_c.get("weight_decay", 0.0)),
                max_grad_norm=ref_c.get("max_grad_norm"),
                server=ref_t["server"], spec=ref_t)
    return leaves, shapes, batches, algo, feed


def make_unflatten(leaves, shapes):
    def unflatten(flat):
        return {n: flat[a:b].reshape(s) for (n, a, b), s in zip(leaves, shapes)}

    return unflatten


def reference_trace(cell, leaves, shapes, batches, algo, p0, *, precision="float32",
                    on_round=None, first=True):
    from benchmark.reference import round as ref_round

    ref_c = cell["config_file"]["reference"]
    model = importlib.import_module(f"benchmark.reference.{ref_c['module']}")
    kwargs = ref_c.get("loss_kwargs", {})

    def loss_fn(p, batch, prec):
        return model.loss(p, batch, prec, **kwargs)

    client_grad = ref_round.make_client_grad(
        loss_fn, make_unflatten(leaves, shapes), algo, precision)
    return ref_round.run_rounds(client_grad, p0, batches, algo, on_round, first)


def host_memory() -> dict:
    """This process's host memory in bytes: its peak resident size
    (``ru_maxrss``) and, where ``/proc`` gives them, the resident size now
    (``VmRSS``) and the peak again (``VmHWM``). The harness's own reading,
    not a metric."""
    import resource

    out = {"maxrss": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                key, _, value = line.partition(":")
                if key in ("VmRSS", "VmHWM"):
                    out[key] = int(value.split()[0]) * 1024
    except OSError:
        pass
    return out


def memory_peak(devices, chips) -> int:
    """Bytes held at the peak on the fullest chip the cell uses."""
    peak = 0
    for d in devices[:chips]:
        # live buffers plus what compiled programs reserve as scratch: the
        # runtime keeps the two in separate pools and counts them apart
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def device_record(devices, peak, traced=None):
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if traced:
        rec["busy_s"] = traced["busy_s"]
        rec["window_s"] = traced["window_s"]
    return rec


def build(cell, seed, extra_argv=()):
    """The cell's round, built as its entry builds it, on benchmark weights."""
    from benchmark import weights

    conf, traffic = cell["config_file"], cell["traffic_file"]
    # the program's own --seed stays the configuration's: it keys the sketch's
    # hashes, which are constants of the compiled round, so a seed that moved
    # would compile anew in every run. The benchmark's seed makes the weights
    # and drives the sampler's draws.
    argv = list(conf["argv"]) + list(traffic["argv"]) + [
        "--telemetry_level", "0",
        "--dataset_dir", os.path.join(ROOT, "benchmark_out", "no_dataset"),
    ] + list(extra_argv)
    made = {}

    def reweight(params):
        made["tree"] = weights.make(params, seed, conf["init"])
        return made["tree"]

    entry = importlib.import_module(f"benchmark.entries.{conf['entry']}")
    cfg, session, sampler = entry.build(argv, reweight)
    sampler.seed = seed
    return cfg, session, sampler, made["tree"]


def apply_tiny(cell) -> list:
    """Rehearsal only: fold the files' tiny presets into the cell; returns
    the arguments to append to the entry's."""
    conf, traffic = cell["config_file"], cell["traffic_file"]
    for f in (conf, traffic):
        f["reference"] = {**f["reference"], **f.get("tiny_reference", {})}
    return list(conf.get("tiny_argv", ())) + list(traffic.get("tiny_argv", ()))


def main(argv=None, rehearsal=None) -> int:
    """``rehearsal`` (tests only; not reachable from the command line) is
    ``{"argv": [...]}``: skip the look for a chip and append a tiny preset to
    the entry's arguments, so that the same code can be driven on the CPU."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    seed = args.seed % SEED_MODULUS

    import jax

    devices = jax.devices()
    chips = int(cell["chips"])
    if rehearsal is None:
        if devices[0].platform != "tpu" or len(devices) < chips:
            print(f"benchmark: {cell['name']} needs {chips} TPU chip(s); JAX found "
                  f"{devices[0].platform!r} ({devices[0].device_kind!r} x {len(devices)})",
                  file=sys.stderr)
            return 1
        peaks = load_peaks(devices[0].device_kind)
    else:
        peaks = None
    # the program's own placement: JAX_COMPILATION_CACHE_DIR where set, else
    # a fixed directory inside the checkout
    from commefficient_tpu.utils.platform import configure_compile_cache

    if rehearsal is None:
        configure_compile_cache()
    log = CompileLog()
    log.install()

    # ---- set-up: build, follow the first rounds, warm up ----------------
    extra = ()
    if rehearsal is not None:
        extra = apply_tiny(cell) + list(rehearsal.get("argv", ()))
    t_jax = time.time()
    cfg, session, sampler, tree = build(cell, seed, extra)
    t_built = time.time()
    traffic = cell["traffic_file"]
    lr = float(traffic["lr"])
    rounds = Rounds(cfg, session, sampler, lr)
    followed = follow(rounds, session)
    t_followed = time.time()
    guard_round = int(traffic["guard_round"])
    # past the round source's first epoch boundary where an epoch is short
    warm = max(int(traffic.get("warmup_rounds", 8)), min(rounds.steps_per_epoch + 2, 40),
               guard_round + 1) - FOLLOWED
    _, rest, raised_warm = drive(rounds, session, count=warm)
    early = [float(x) for x in jax.device_get(followed["losses"] + rest)]
    if raised_warm:
        raise RuntimeError("a round raised during set-up")
    p0 = followed["p0"]

    # ---- the measured window ---------------------------------------------
    rounds.spans.reset()
    gc.collect()
    gc.freeze()
    gc.disable()
    t_window = time.time()
    setup_s = t_window - T_START
    host = {"window_start": host_memory()}
    elapsed, losses, raised = drive(rounds, session, seconds=args.seconds)
    gc.enable()
    t_window_end = time.time()
    window_spans = (rounds.spans.data_wait_s, rounds.spans.dispatch_s, rounds.spans.rounds)
    attempted = len(losses) + raised
    loss_values = [float(x) for x in jax.device_get(losses)]
    failed = raised + sum(1 for x in loss_values if not math.isfinite(x))
    round_s = elapsed / max(1, len(losses))
    units_per_round = cfg.num_workers * cfg.local_batch_size * int(
        cell["config_file"]["units_per_sample"])
    print(json.dumps({
        "info": "window", "workload": cell["name"], "seed": args.seed,
        "rounds": len(losses), "window_s": elapsed, "round_s": round_s,
        "units_per_round": units_per_round,
        "units_per_s_per_chip": units_per_round / round_s / chips,
        "loss_first": loss_values[0] if loss_values else None,
        "loss_last": loss_values[-1] if loss_values else None,
        "setup_phases_s": {"start_to_jax": t_jax - T_START, "build": t_built - t_jax,
                           "first_rounds": t_followed - t_built,
                           "warm_up": t_window - t_followed},
        "setup_compile_s": log.seconds((COMPILE_EVENT,), until=t_window),
        "setup_trace_lower_s": log.seconds(TRACE_EVENTS, until=t_window),
    }), flush=True)

    # ---- the traced rounds (--trace 1) -----------------------------------
    traced = None
    if args.trace:
        from benchmark import reduce

        trace_dir = os.path.join(ROOT, "benchmark_out", "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        n = int(min(24, max(4, round(3.0 / round_s))))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        fence(session)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        rounds.spans.reset()
        _, _, traced_raised = drive(rounds, session, count=n)
        jax.profiler.stop_trace()
        if traced_raised:
            raise RuntimeError("a round raised while tracing")
        traced = reduce.load_trace(trace_dir, chips)
        traced.update(rounds=n)
    held = memory_peak(devices, chips)
    if args.trace:
        ctx = {
            "cell": cell, "traced": traced, "peaks": peaks, "chips": chips,
            "units_per_round": units_per_round,
            "values": {
                "entry.compile_s": log.seconds((COMPILE_EVENT,), until=t_window),
                "entry.trace_lower_s": log.seconds(TRACE_EVENTS, until=t_window),
                "loop.data_wait_s_per_round": window_spans[0] / max(1, window_spans[2]),
                "session.dispatch_s_per_round": window_spans[1] / max(1, window_spans[2]),
                "session.compiles_in_window": float(log.count(
                    (COMPILE_EVENT,), since=t_window, until=t_window_end)),
                "round.guard_loss": early[guard_round],
                "device.held_hbm_bytes": float(held),
            },
            "config": {"n_params": int(p0.size), **cell["config_file"].get("flops_kwargs", {})},
        }
        metrics = reduce.per_layer(cell_metrics(cell, "per_layer"), ctx)
        # the reduced numbers are all that is kept of the trace
        traced = {k: traced[k] for k in ("busy_s", "window_s", "breakdown", "rounds")}
        del ctx
        gc.collect()
        host["trace_reduced"] = host_memory()
    else:
        metrics = {"round_s": {"value": round_s, "unit": "s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    # ---- free the program, then follow the reference ---------------------
    leaves, shapes, batches, algo, feed = reference_inputs(cell, rounds.spans.fed, sampler, tree)
    state_step = int(session.state.step)
    rounds.close()
    del rounds, session, sampler, tree
    gc.collect()
    jax.clear_caches()
    from benchmark import compare

    # the parameters after the last round are read for each leaf's change
    # alone: taken now, they need not be held while the reference runs
    prog = compare.Followed(early[:FOLLOWED], p0, followed.pop("p1"), None,
                            followed.pop("bank1"), lr,
                            change=compare.changes(followed.pop("p3"), p0, leaves))
    gc.collect()
    host["before_reference"] = host_memory()
    t_ref = time.time()

    def after_round(i):
        host[f"reference_round_{i}"] = host_memory()

    ref = reference_trace(cell, leaves, shapes, batches, algo, p0, on_round=after_round,
                          first=False)
    del batches
    read = compare.readings(prog, ref, leaves)
    host["after_readings"] = host_memory()
    # the program's copies and the reference's trace have no reader left
    del prog, ref, followed, p0
    read["gaps"]["feed"] = feed
    correct, rows = compare.judge(read["gaps"], compare.load_limits(cell["name"]))
    if state_step != FOLLOWED + warm + attempted + (traced["rounds"] if traced else 0):
        correct = False  # a round that did not advance the state
    reference_s = time.time() - t_ref
    host_peak = max(max(m.values()) for m in host.values())
    print(json.dumps({"info": "gaps", "workload": cell["name"], "seed": args.seed,
                      **read, "reference_s": reference_s, "host_memory": host}), flush=True)

    # ---- the result -------------------------------------------------------
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": device_record(devices, held, traced),
    }
    if traced:
        result["breakdown"] = traced["breakdown"]
    result["reference_s"] = reference_s
    result["host_peak_bytes"] = host_peak
    result["compared"] = {name: {"gap": gap, "limit": lim} for name, gap, lim in rows}
    result["compared"]["worst_leaf"] = read["worst"]
    for name, gap, lim in rows:
        print(f"compared {name}: gap {gap:.6g} limit {lim:.6g} "
              f"{'ok' if gap <= lim else 'OVER'}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
