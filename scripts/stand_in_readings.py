"""Chip probe (ISSUE 29): the readings `benchmark/calibrate.py` takes (the
program's gaps to the plain reference, and the gaps of the stand-ins put in
the program's place: the fp8 control, `half_batch`), taken one trace at a
time so that a cell whose flat vector is 1.56 GB fits the one-chip
machine's 40 GiB of host memory, several seeds a process.

    chiprun -- python scripts/stand_in_readings.py --workload laguna_uncompressed \
        --seeds 131,301,302 --controls 1

`calibrate.py` keeps the program's three host copies, the reference's trace
(four `[D]` vectors) and each stand-in's trace alive together, and
`compare.readings` makes float64 temporaries on top: ~36 GB a seed at
D = 389.6M, over 40 with a stand-in or a second seed (PERF.md section 7).
This script calls the same functions (`run.build`, `run.follow`,
`run.reference_trace`, `compare.readings`, `compare.judge`) and differs only
in what it keeps: of each trace the losses, the first aggregate and the
first and last parameters, the reference's on disk (memory-mapped) while a
stand-in is followed. One JSON line a reading, in calibrate's own form,
also appended to `chiprun_out/stand_in_readings_<cell>.jsonl`.
"""

import argparse
import gc
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1,
                    help="stand-ins are read on the first this many seeds")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark import compare, run
    from benchmark.reference.round import Trace
    from commefficient_tpu.utils.platform import configure_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("stand_in_readings: needs the chip", file=sys.stderr)
        return 1
    configure_compile_cache()
    out_path = os.path.join(ROOT, "chiprun_out", f"stand_in_readings_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    scratch = tempfile.mkdtemp()

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    def slim(trace):
        """The trace with the middle round's parameters let go."""
        return Trace(trace.losses, trace.grad1, trace.table1,
                     [trace.params[0], None, trace.params[-1]])

    def on_disk(name, a):
        path = os.path.join(scratch, name + ".npy")
        np.save(path, a)
        return np.load(path, mmap_mode="r")

    for n, seed_arg in enumerate(int(s) for s in args.seeds.split(",")):
        cell = run.load_cell(args.workload)
        limits = compare.load_limits(cell["name"])
        cfg, session, sampler, tree = run.build(cell, seed_arg % run.SEED_MODULUS)
        lr = float(cell["traffic_file"]["lr"])
        rounds = run.Rounds(cfg, session, sampler, lr)
        got = run.follow(rounds, session)
        losses = [float(x) for x in jax.device_get(got["losses"])]
        leaves, shapes, batches, algo, feed = run.reference_inputs(
            cell, rounds.spans.fed, sampler, tree)
        rounds.close()
        del rounds, session, sampler, tree
        gc.collect()
        jax.clear_caches()
        p0 = got["p0"]

        def record(what, stand, ref):
            read = compare.readings(stand, ref, leaves)
            read["gaps"]["feed"] = feed
            emit({"cell": cell["name"], "seed": seed_arg, "what": what, **read,
                  "correct": compare.judge(read["gaps"], limits)[0]})
            gc.collect()

        ref = slim(run.reference_trace(cell, leaves, shapes, batches, algo, p0))
        record("program", compare.Followed(losses, p0, got["p1"], got["p3"], got["bank1"], lr), ref)
        del got
        if n >= args.controls:
            del ref
            gc.collect()
            continue
        ref = Trace(ref.losses, on_disk("grad1", ref.grad1), None,
                    [None, None, on_disk("p3", ref.params[-1])])
        gc.collect()
        for what in ("control", "half_batch"):
            kw, fed = {}, batches
            if what == "half_batch":
                fed = [{k: np.concatenate([v[:, : v.shape[1] // 2]] * 2, axis=1)
                        for k, v in b.items()} for b in batches]
            else:
                kw = dict(precision=cell["config_file"]["reference"]["control_precision"])
            tr = slim(run.reference_trace(cell, leaves, shapes, fed, algo, p0, **kw))
            record(what, compare.from_reference(tr, p0, lr, False), ref)
            del tr
            gc.collect()
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
