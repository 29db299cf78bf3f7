"""Chip probe (ISSUE 33): the pieces of Keye-VL-2.0's indexed attention at the
cell's own sizes (one sequence of T = 16,384, 32 query heads over 4 KV heads
of 128, an index of 16 heads of 64, topk 2,048), candidate by candidate, so
that one form of each is kept on measurement.

    chiprun -- python scripts/keye_probe.py [--only select,attend,check,xla,experts,loads,rounds]

- ``select``: the radix-select kernel (``ops/pallas/indexed_attention.py``)
  by query block, against XLA forms over a materialized ``[512, T]`` tile of
  scores a query block: ``lax.top_k`` and a 32-pass threshold bisection.
- ``attend``: the three attention kernels, forward and forward + backward,
  by tile shape, beside ``splash_attention``'s plain causal kernels at the
  same sizes (what a mask that is a constant of the trace costs).
- ``check``: at T = 2,048, topk 256, bfloat16, the kernels against plain
  ``jax.numpy`` on the chip: how many mask entries differ (the products'
  rounding at the threshold) and the outputs' gap.
- ``experts``: the expert layer's three grouped products for one client
  (32,768 rows offered, 8 experts of 2,048 x 768), forward + backward, by the
  kernel's tile and by how many rows the router sent, with and without the
  floor that ``expert_rows_floor`` puts under the rows the product is given.
- ``loads``: the held assignments a layer a client under the benchmark's own
  weights (``benchmark/weights.make``'s draw) for ``--seeds``, on the cell's
  data: what the seed does to the work of a round.
- ``rounds``: the cell's own compiled round (``benchmark/run.py``'s build,
  round source and feed), the weights and the sampler's draws swapped seed by
  seed in one process: each round's seconds for ``--seeds``, one warm round
  first, so that what a seed does to a round's time is read at 20 s a seed.

Host clock around ``block_until_ready``, the mean of ``--reps`` calls after
one warm call; one JSON line a reading, also appended to
``chiprun_out/keye_probe.jsonl``. It refuses to start without a TPU: a
reading of an interpreted kernel at another size is no reading of these.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.pallas import indexed_attention as ia
from commefficient_tpu.ops.pallas.library_kernels import banded_attention

OUT = os.path.join("chiprun_out", "keye_probe.jsonl")


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(name, fn, *args, reps=5, **more):
    try:
        f = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(f(*args))
        say(what=name, s=(time.perf_counter() - t0) / reps, first_s=first, **more)
        return out
    except Exception as e:  # noqa: BLE001 - a candidate the compiler refuses is a reading
        say(what=name, error=f"{type(e).__name__}: {str(e)[:400]}")


def operands(T, dtype=jnp.bfloat16, B=1, H=32, KV=4, d=128, J=16, e=64, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (B, T, H, d), dtype) / 8, n(ks[1], (B, T, KV, d), dtype),
            n(ks[2], (B, T, KV, d), dtype), n(ks[3], (B, T, J, e), dtype),
            n(ks[4], (B, T, e), dtype), n(ks[5], (B, T, J), jnp.float32))


def tile_scores(qi, ki, w):
    """``[bq, T]`` float32 scores of one block of queries, materialized."""
    dots = jnp.einsum("tje,se->tjs", qi, ki, preferred_element_type=jnp.float32)
    return jnp.einsum("tjs,tj->ts", jnp.maximum(dots, 0.0), w)


def xla_select(qi, ki, w, *, topk, form, bq=512):
    """``tau`` ``[T]`` by query blocks of ``bq`` over materialized tiles."""
    T = ki.shape[0]

    def block(args):
        i, qi_b, w_b = args
        scores = tile_scores(qi_b, ki, w_b)
        t = i * bq + jnp.arange(bq)[:, None]
        scores = jnp.where(jnp.arange(T)[None, :] <= t, scores, -jnp.inf)
        if form == "top_k":
            return jax.lax.top_k(scores, topk)[0][:, -1]
        lo, hi = jnp.min(jnp.where(jnp.isfinite(scores), scores, 0.0), -1), jnp.max(scores, -1)

        def body(_, b):
            lo, hi = b
            mid = 0.5 * (lo + hi)
            many = jnp.sum(scores >= mid[:, None], -1) >= topk
            return jnp.where(many, mid, lo), jnp.where(many, hi, mid)

        return jax.lax.fori_loop(0, 32, body, (lo, hi))[0]

    n = T // bq
    return jax.lax.map(block, (jnp.arange(n), qi.reshape(n, bq, *qi.shape[1:]),
                               w.reshape(n, bq, -1))).reshape(T)


def plain(q, k, v, qi, ki, w, topk):
    """Plain ``jax.numpy`` on materialized ``[T, T]`` (small T only)."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    dots = jnp.einsum("btje,bse->btjs", qi, ki, preferred_element_type=jnp.float32)
    scores = jnp.einsum("btjs,btj->bts", jnp.maximum(dots, 0.0), w)
    t = jnp.arange(T)
    causal = t[:, None] >= t[None, :]
    kth = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)[0][..., -1:]
    keep = causal & (scores >= kth)
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, G, 2), preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), jnp.repeat(v, G, 2),
                   preferred_element_type=jnp.float32)
    return o, keep


def expert_chain(tiling, rows=32768, E=2048, F=768, G=8):
    """One client's routed experts, forward + backward, as
    ``models/laguna.py::_expert_rows`` runs them between dispatch and
    combine: ``(x, gate, up, down, sizes) -> gradients``."""
    from commefficient_tpu.ops.pallas.library_kernels import grouped_product as gp

    def f(x, gate, up, down, sizes):
        a = jax.nn.silu(gp(x, gate, sizes, tiling)) * gp(x, up, sizes, tiling)
        return jnp.sum(gp(a.astype(x.dtype), down, sizes, tiling))

    ks = jax.random.split(jax.random.key(0), 4)
    n = jax.random.normal
    args = (n(ks[0], (rows, E), jnp.bfloat16), 0.02 * n(ks[1], (G, E, F), jnp.bfloat16),
            0.02 * n(ks[2], (G, E, F), jnp.bfloat16), 0.02 * n(ks[3], (G, F, E), jnp.bfloat16))
    return jax.grad(f, (0, 1, 2, 3)), args


# --model: (the cell, its tiny preset, the cell's row length and document median)
MODELS = {"keye_vl2": ("keye_uncompressed", "keye_tiny", 16384, 4096),
          "sdar_30b_a3b": ("sdar_uncompressed", "sdar_tiny", 8192, 2048)}


def held_loads(seeds, clients, model, tiny):
    """``moe/held_assignments`` and ``moe/max_expert_load`` of each layer,
    for each seed's benchmark weights and each client's first row (a
    block-diffusion model's: the row's noised copy under one fixed draw, then
    its clean one, as its blocks see them)."""
    import numpy as np

    from benchmark import weights
    from commefficient_tpu.data.fedtext import load_fed_text
    from commefficient_tpu.models.laguna import Block, LagunaLM
    from commefficient_tpu.train import lm_train

    _, small, T, median = MODELS[model]
    cfg = lm_train.PRESETS[small if tiny else model]()
    T = 128 if tiny else T
    noise = lm_train.round_augment(cfg)
    train, _ = load_fed_text(num_clients=clients, seq_len=T, vocab=cfg.vocab_held, seed=42,
                             doc_median=40 if tiny else median, reserved=1 if noise else 0)
    rows = {k: v[::8][:clients] for k, v in train.data.items()}
    ids = rows["input_ids"]
    if noise:
        masked = noise.apply(rows, *noise.fixed(clients, T, 42))["noise_mask"]
        ids = np.concatenate([np.where(masked, cfg.mask_token, ids), ids], 1)
    ids = jnp.asarray(ids)
    shapes = jax.eval_shape(LagunaLM(cfg).init, jax.random.key(0), jnp.zeros((1, T), jnp.int32))
    leaves, treedef = jax.tree.flatten(shapes)
    names = weights.leaf_names(shapes)

    @jax.jit
    def draw(key):  # benchmark/weights.py::make at init {"std": 0.02}, traced once
        out = []
        for i, (name, a) in enumerate(zip(names, leaves)):
            x = jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32)
            out.append(1.0 + 0.05 * x if name.endswith("/scale") else 0.02 * x)
        return jax.tree.unflatten(treedef, out)

    @jax.jit
    def loads(params, row):
        p = params["params"]
        x, out = p["embed"]["embedding"][row[None]], []
        for i in range(cfg.num_layers):
            x, c, _ = Block(cfg, i).apply({"params": p[f"layer_{i}"]}, x)
            out.append((c["moe/held_assignments"], c["moe/max_expert_load"]))
        return out

    expected = ids.shape[1] * cfg.num_experts_per_tok * len(cfg.experts_held) / cfg.num_experts
    for seed in seeds:
        params = draw(jax.random.key(seed))
        got = np.asarray([[[float(v) for v in lc] for lc in loads(params, row)] for row in ids])
        say(what="held_loads", seed=seed, expected=expected,
            held=got[:, :, 0].tolist(), max_expert=got[:, :, 1].tolist(),
            total_over_expected=float(got[:, :, 0].sum() / (expected * got[:, :, 0].size)))


def round_times(seeds, count, model, tiny):
    """``count`` rounds of the model's cell a seed, each fenced."""
    from benchmark import run, weights
    from commefficient_tpu.ops.param_utils import ravel_params
    from commefficient_tpu.utils.platform import configure_compile_cache

    cell = run.load_cell(MODELS[model][0])
    extra = ()
    if tiny:
        extra = run.apply_tiny(cell)
    else:
        configure_compile_cache()
    cfg, session, sampler, tree = run.build(cell, seeds[0], extra)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    del tree
    lr, init = float(cell["traffic_file"]["lr"]), cell["config_file"]["init"]
    for seed in seeds:
        vec, _ = ravel_params(weights.make(shapes, seed % run.SEED_MODULUS, init))
        was = session.state
        session.state = was._replace(
            params_vec=jax.device_put(vec, was.params_vec.sharding),
            step=jnp.zeros_like(was.step))
        del vec, was
        sampler.seed = seed % run.SEED_MODULUS
        rounds = run.Rounds(cfg, session, sampler, lr)
        run.drive(rounds, session, count=1)
        took = [run.drive(rounds, session, count=1)[0] for _ in range(count)]
        rounds.close()
        say(what="round_times", seed=seed, rounds_s=took, mean_s=sum(took) / len(took))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3300000101,2147483777,71,3300000417,3300000555")
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--T", type=int, default=16384)
    ap.add_argument("--only", default="check,select,attend,xla")
    ap.add_argument("--model", default="keye_vl2", choices=sorted(MODELS),
                    help="whose preset and cell `loads` and `rounds` read")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the script on the CPU at --T 512 (no reading is one of the chip)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit(f"keye_probe: no TPU (backend {jax.default_backend()}); it times the chip only")
    only = set(args.only.split(","))
    say(what="device", kind=jax.devices()[0].device_kind, platform=jax.devices()[0].platform)
    T, topk = args.T, 2048
    make = operands
    if "rounds" in only:
        round_times([int(x) for x in args.seeds.split(",")], 2 if args.rehearse else 9,
                    args.model, args.rehearse)
    if "loads" in only:
        held_loads([int(x) for x in args.seeds.split(",")], args.clients, args.model,
                   args.rehearse)
    if "experts" in only:
        rows = 1024 if args.rehearse else 32768
        tilings = ((128, 512, 512), (256, 512, 512), (512, 512, 512), (512, 1024, 512),
                   (512, 512, 1024), (512, 1024, 1024), (1024, 512, 512), (256, 1024, 1024))
        for tiling in tilings[:1] if args.rehearse else tilings:
            fn, ops = expert_chain(tiling, rows, *((64, 32, 4) if args.rehearse else ()))
            G = ops[1].shape[0]
            for held in (rows // 8, rows // 4, 3 * rows // 8):
                for skew in (False, True):
                    # even loads, or one expert with half of them
                    each = held // (2 * (G - 1)) if skew else held // G
                    sizes = jnp.full((G,), each, jnp.int32).at[0].add(held - each * G)
                    timed("experts_fwd_bwd", fn, *ops, sizes, reps=args.reps,
                          tiling=tiling, held=held, skew=skew)
    if args.rehearse:
        topk = 64
        make = lambda T, **kw: operands(min(T, 512), jnp.float32, H=4, KV=2, d=16, J=4, e=8, **kw)  # noqa: E731

    if "check" in only:
        small = make(2048, seed=1)
        got, counters = jax.jit(lambda *a: ia.indexed_attention(*a, topk=256))(*small)
        want, keep = jax.jit(lambda *a: plain(*a, 256))(*small)
        tau, cut, _ = ia.select_threshold(small[3].transpose(0, 2, 1, 3), small[4],
                                          small[5].transpose(0, 2, 1), topk=256)
        # the kernel's own set, rebuilt from its threshold on XLA's scores
        say(what="check_T2048_topk256", out_gap=float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))),
            out_scale=float(jnp.max(jnp.abs(want))),
            selected_pairs=float(counters["selected_pairs"]), plain_pairs=float(jnp.sum(keep)),
            tied_rows=float(counters["select_ties"]), tau_finite=int(jnp.sum(jnp.isfinite(tau))))
        f = lambda fn: jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(  # noqa: E731
            fn(q, k, v).astype(jnp.float32))), (0, 1, 2)))
        ga = f(lambda q, k, v: ia.indexed_attention(q, k, v, *small[3:], topk=256)[0])(*small[:3])
        gb = f(lambda q, k, v: plain(q, k, v, *small[3:], 256)[0])(*small[:3])
        say(what="check_grads", rel=[float(jnp.linalg.norm((a - b).astype(jnp.float32))
                                           / jnp.linalg.norm(b.astype(jnp.float32)))
                                     for a, b in zip(ga, gb)])

    q, k, v, qi, ki, w = make(T)
    qi_t, w_t = qi.transpose(0, 2, 1, 3), w.transpose(0, 2, 1)
    if "select" in only:
        for bq in (128, 256, 512):
            ia.BLOCK_Q_SELECT = bq
            timed("select_kernel", lambda a, b, c: ia.select_threshold(a, b, c, topk=topk),
                  qi_t, ki, w_t, reps=args.reps, bq=bq, T=T)
        ia.BLOCK_Q_SELECT = 256
    if "xla" in only:
        for form in ("bisect", "top_k"):
            timed("select_xla_" + form,
                  lambda a, b, c, form=form: xla_select(a, b, c, topk=topk, form=form),
                  qi[0], ki[0], w[0], reps=2, T=T)
    if "attend" in only:
        for bq, bk in ((256, 512), (512, 512), (128, 512), (256, 256)):
            ia.BLOCK_Q, ia.BLOCK_K = bq, bk
            fwd = lambda *a: ia.indexed_attention(*a, topk=topk)[0]  # noqa: E731
            timed("indexed_fwd", fwd, q, k, v, qi, ki, w, reps=args.reps, bq=bq, bk=bk, T=T)
            timed("indexed_fwd_bwd", jax.grad(
                lambda q, k, v: jnp.sum(fwd(q, k, v, qi, ki, w).astype(jnp.float32)), (0, 1, 2)),
                q, k, v, reps=args.reps, bq=bq, bk=bk, T=T)
        ia.BLOCK_Q, ia.BLOCK_K = 256, 512
        timed("splash_causal_fwd", lambda q, k, v: banded_attention(q, k, v), q, k, v,
              reps=args.reps, T=T)
        timed("splash_causal_fwd_bwd", jax.grad(
            lambda q, k, v: jnp.sum(banded_attention(q, k, v).astype(jnp.float32)), (0, 1, 2)),
            q, k, v, reps=args.reps, T=T)


if __name__ == "__main__":
    main()
