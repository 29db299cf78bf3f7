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
`run.reference_inputs`, the reference's `round.make_client_grad` and
`round.run_rounds`, `compare.readings`, `compare.judge`) and differs only in
what it keeps: of each trace the losses, the first aggregate and the first
and last parameters, the reference's on disk (memory-mapped) while a
stand-in is followed. One JSON line a reading, in calibrate's own form,
also appended to `chiprun_out/stand_in_readings_<cell>.jsonl`.

The round is built and compiled once: each seed starts it from the state
the build made (kept on the host, the device's copy freed while the
reference runs) with that seed's weights (`benchmark/weights.make`, as the
build draws them) and sampler, which is what a fresh build for that seed
gives. The program's reading of a seed also carries `round_s`, the mean of
`--rounds` fenced rounds after the followed ones, and the seconds each step
of the seed took. `--rehearse` walks it on the CPU at the cell's tiny
preset (no reading is one of the chip).
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1,
                    help="stand-ins are read on the first this many seeds")
    ap.add_argument("--stand_ins", default="control,half_batch",
                    help="which stand-ins those seeds read")
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds timed a seed after the followed ones")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny preset on the CPU")
    args = ap.parse_args()

    import gc

    import jax
    import numpy as np

    from benchmark import compare, run, weights
    from benchmark.reference import round as ref_round
    from benchmark.reference.round import Trace
    from commefficient_tpu.ops.param_utils import ravel_params
    from commefficient_tpu.utils.platform import configure_compile_cache

    cell = run.load_cell(args.workload)
    extra = ()
    if args.rehearse:
        extra = run.apply_tiny(cell)
    elif jax.devices()[0].platform != "tpu":
        print("stand_in_readings: needs the chip", file=sys.stderr)
        return 1
    else:
        configure_compile_cache()
    out_path = os.path.join(ROOT, "chiprun_out", f"stand_in_readings_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    scratch = tempfile.mkdtemp()
    stand_ins = [s for s in args.stand_ins.split(",") if s]
    seeds = [int(s) for s in args.seeds.split(",")]

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

    limits = compare.load_limits(cell["name"])
    lr = float(cell["traffic_file"]["lr"])
    init = cell["config_file"]["init"]
    cfg, session, sampler, tree = run.build(cell, seeds[0] % run.SEED_MODULUS, extra)
    shapes_tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    # the state the build made but its parameters, on the host (an all-zero
    # leaf as its shape)
    start = jax.tree.map(lambda a: (None if not np.any(np.asarray(a)) else np.asarray(a),
                                    a.shape, a.dtype, a.sharding),
                         session.state._replace(params_vec=None))
    params_at = session.state.params_vec.sharding
    leaf_of = lambda x: isinstance(x, tuple) and len(x) == 4  # noqa: E731

    def restore(vec):
        state = jax.tree.map(
            lambda s: jax.device_put(np.zeros(s[1], s[2]) if s[0] is None else s[0], s[3]),
            start, is_leaf=leaf_of)
        return state._replace(params_vec=jax.device_put(vec, params_at))

    ref_c = cell["config_file"]["reference"]
    model = importlib.import_module(f"benchmark.reference.{ref_c['module']}")
    kwargs = ref_c.get("loss_kwargs", {})
    grads = {}

    def reference(leaves, shapes, batches, algo, p0, precision="float32"):
        """`run.reference_trace`, its compiled client gradient kept a precision."""
        if precision not in grads:
            grads[precision] = ref_round.make_client_grad(
                lambda p, batch, prec: model.loss(p, batch, prec, **kwargs),
                run.make_unflatten(leaves, shapes), algo, precision)
        return ref_round.run_rounds(grads[precision], p0, batches, algo)

    def free_state():
        for a in jax.tree.leaves(session.state):
            a.delete()

    free_state()
    for n, seed_arg in enumerate(seeds):
        seed = seed_arg % run.SEED_MODULUS
        took = {}
        t = time.time()
        vec, _ = ravel_params(weights.make(shapes_tree, seed, init))
        session.state = restore(vec)
        del vec
        session.sync_round_clock()
        sampler.seed = seed
        rounds = run.Rounds(cfg, session, sampler, lr)
        got = run.follow(rounds, session)
        took["follow_s"] = time.time() - t
        round_s = None
        if args.rounds:
            round_s = run.drive(rounds, session, count=args.rounds)[0] / args.rounds
        losses = [float(x) for x in jax.device_get(got["losses"])]
        leaves, shapes, batches, algo, feed = run.reference_inputs(
            cell, rounds.spans.fed, sampler, tree)
        rounds.close()
        del rounds
        free_state()
        gc.collect()
        p0 = got["p0"]

        def record(what, stand, ref, **more):
            t = time.time()
            read = compare.readings(stand, ref, leaves)
            read["gaps"]["feed"] = feed
            more.setdefault("seconds", {})["readings_s"] = time.time() - t
            emit({"cell": cell["name"], "seed": seed_arg, "what": what, **read,
                  "correct": compare.judge(read["gaps"], limits)[0], **more})
            gc.collect()

        t = time.time()
        ref = slim(reference(leaves, shapes, batches, algo, p0))
        took["reference_s"] = time.time() - t
        record("program", compare.Followed(losses, p0, got["p1"], got["p3"], got["bank1"], lr),
               ref, round_s=round_s, rounds_timed=args.rounds, seconds=took)
        del got
        if n >= args.controls or not stand_ins:
            del ref
            gc.collect()
            continue
        ref = Trace(ref.losses, on_disk("grad1", ref.grad1), None,
                    [None, None, on_disk("p3", ref.params[-1])])
        gc.collect()
        for what in stand_ins:
            kw, fed = {}, batches
            if what == "half_batch":
                fed = [{k: np.concatenate([v[:, : v.shape[1] // 2]] * 2, axis=1)
                        for k, v in b.items()} for b in batches]
            else:
                kw = dict(precision=cell["config_file"]["reference"]["control_precision"])
            t = time.time()
            tr = slim(reference(leaves, shapes, fed, algo, p0, **kw))
            record(what, compare.from_reference(tr, p0, lr, False), ref,
                   seconds={"reference_s": time.time() - t})
            del tr
            gc.collect()
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
