"""Reads, on the chip, the two ends every limit of ``correct`` is set from.

    python benchmark/calibrate.py --workload <cell> --seeds 1,2,... [--controls 3]

For each seed, in one process: build the cell's round as ``run.py`` does,
follow its first three rounds (the window's own call and feed), free it,
follow the plain reference, and print the gaps — the LOWER readings. For
the first ``--controls`` seeds also put stand-ins in the program's place
and print their gaps against the same reference — the UPPER readings:

- ``control``: the reference in the precision below the configuration's
  (``reference.control_precision``);
- ``half_batch``: every client's mean taken over the first half of its batch;
- any other name in ``--stand_ins`` is a precision of ``reference/ops.py``:
  ``bfloat16`` is the reference at the products the configuration states.

Each stand-in's trace is let go before the next stand-in is followed, and
each seed's copies and reference before the next seed is built, so that one
process holds one seed's host copies of the flat vector at a time; each
reading carries the process's host memory (``run.host_memory``).

Every reading goes through ``compare.judge`` with the cell's limits. The
exit code is 1 where a run of the program as configured is not correct, or
the control or ``half_batch`` passes every limit. A state left unchanged
reads 1 on ``delta_3`` by the measure itself and needs no run. One JSON line
per reading on standard output, and the same appended to
``chiprun_out/calibrate_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, rehearsal=None) -> int:
    """``rehearsal`` as in ``run.main``: tests only, the tiny preset on the CPU."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--stand_ins", default="control,half_batch",
                    help="which stand-ins to read; 'bfloat16' is the reference with "
                         "bfloat16 products, the precision the configuration states")
    ap.add_argument("--program_argv", default="",
                    help="appended to the program's arguments, e.g. '--compute_dtype float32'")
    ap.add_argument("--loss_kwargs", default="{}",
                    help="JSON merged into the reference's loss_kwargs, with --program_argv: a "
                         "look at one term of the loss, e.g. '{\"mc_coef\": 0.0}' with '--mc_coef 0'")
    ap.add_argument("--leaf", default="",
                    help="also print this leaf's norms: program, reference, their difference")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import compare, run

    if rehearsal is None:
        if jax.devices()[0].platform != "tpu":
            print("calibrate: needs the chip", file=sys.stderr)
            return 1
        from commefficient_tpu.utils.platform import configure_compile_cache

        configure_compile_cache()
    out_path = os.path.join(ROOT, "chiprun_out", f"calibrate_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if rehearsal is None:
            with open(out_path, "a") as f:
                f.write(line + "\n")

    verdicts = []
    for n, seed_arg in enumerate(int(s) for s in args.seeds.split(",")):
        cell = run.load_cell(args.workload)
        seed = seed_arg % run.SEED_MODULUS
        t0 = time.time()
        extra = args.program_argv.split()
        if rehearsal is not None:
            extra = run.apply_tiny(cell) + list(rehearsal.get("argv", ())) + extra
        ref_c = cell["config_file"]["reference"]
        ref_c["loss_kwargs"] = {**ref_c.get("loss_kwargs", {}), **json.loads(args.loss_kwargs)}
        cfg, session, sampler, tree = run.build(cell, seed, extra)
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
        t1 = time.time()
        # the program's last parameters are read for each leaf's change alone
        # (and by --leaf): taken now, they need not be held while the reference runs
        p3 = got.pop("p3")
        prog = compare.Followed(losses, got["p0"], got["p1"], p3 if args.leaf else None,
                                got["bank1"], lr, change=compare.changes(p3, got["p0"], leaves))
        del p3
        ref = run.reference_trace(cell, leaves, shapes, batches, algo, got["p0"], first=False)
        t2 = time.time()
        limits = compare.load_limits(cell["name"])

        def record(what, stand, **more):
            read = compare.readings(stand, ref, leaves)
            read["gaps"]["feed"] = feed
            rec = {"cell": cell["name"], "seed": seed_arg, "what": what, **read,
                   "correct": compare.judge(read["gaps"], limits)[0], **more,
                   "host_memory": run.host_memory()}
            if args.leaf:
                rec["leaf"] = leaf_norms(stand, ref, leaves, args.leaf)
            emit(rec)
            return rec["correct"]

        ok = record("program", prog, program_s=t1 - t0, reference_s=t2 - t1,
                    ref_losses=ref.losses, losses=losses)
        if not ok and not args.program_argv:
            verdicts.append(f"seed {seed_arg}: the program is not correct")
        del prog
        banked = got["bank1"] is not None
        stand_ins = args.stand_ins.split(",") if n < args.controls else []
        for what in filter(None, stand_ins):
            kw, fed = {}, batches
            if what == "half_batch":
                fed = [{k: np.concatenate([v[:, : v.shape[1] // 2]] * 2, axis=1)
                        for k, v in b.items()} for b in batches]
            elif what == "control":
                kw = dict(precision=cell["config_file"]["reference"]["control_precision"])
            else:
                kw = dict(precision=what)
            t3 = time.time()
            tr = run.reference_trace(cell, leaves, shapes, fed, algo, got["p0"], **kw)
            ok = record(what, compare.from_reference(tr, got["p0"], lr, banked),
                        seconds=time.time() - t3)
            del tr, fed  # each stand-in's trace goes before the next is followed
            if ok and what in ("control", "half_batch"):
                verdicts.append(f"seed {seed_arg}: {what} passes every limit")
        # and the seed's copies and reference before the next seed is built
        del got, ref, batches
        gc.collect()
    for v in verdicts:
        print("calibrate:", v, file=sys.stderr)
    return 1 if verdicts else 0


def leaf_norms(stand, ref, leaves, name):
    """One leaf, looked at closely: the norms of the first aggregate and of
    the three rounds' change, program (or stand-in), reference, difference."""
    import numpy as np

    a, b = next((a, b) for n, a, b in leaves if n == name)
    norm = lambda v: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))  # noqa: E731
    out, p0 = {}, stand.p0[a:b]
    if stand.bank1 is None or stand.bank1.ndim == 1:
        g = stand.bank1[a:b] if stand.bank1 is not None else (p0 - stand.p1[a:b]) / np.float32(stand.lr)
        r = ref.grad1[a:b]
        out.update(grad_prog=norm(g), grad_ref=norm(r), grad_diff=norm(g - r))
    d, d_ref = stand.p3[a:b] - p0, ref.params[-1][a:b] - p0
    out.update(delta_prog=norm(d), delta_ref=norm(d_ref), delta_diff=norm(d - d_ref))
    return out


if __name__ == "__main__":
    sys.exit(main())
