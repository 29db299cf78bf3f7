"""Chip probe (PR 35, review): what moves a router kernel's gradient in
`sdar_uncompressed` by the seed (`grad_1` read 0.0857 on seed 302, layer 2's
router; 0.0033-0.0125 on 15 seeds of 17).

    chiprun -- python scripts/sdar_router_look.py --seeds 302,301 --sides bfloat16
    chiprun -- python scripts/sdar_router_look.py --seeds 302 --sides float32

For each seed, on the benchmark's own weights and the two clients of the
first round (the sampler's host batch, the round's noise applied), a client
at a time:

- the experts every stream position chooses in every layer, in the program's
  blocks (``--sides``: `bfloat16`, the cell's products; `float32`, the
  witness: every product of the program, the kernels' too, on float32
  operands at `highest`, the expert product at tile (128, 512, 512) because
  the preset's does not fit fast memory at that precision; without
  `highest` the chip multiplies float32 operands in bfloat16 passes and the
  witness reads what `bfloat16` reads, flip for flip)
  and in the plain reference (float32 at `highest`): the positions whose
  top-8 set differs, how many of them are `[MASK]`, how many change a held
  expert, the largest group of them that makes the same exchange, the held
  experts' rows on both sides, and how the reference's positions lie to the
  boundary (the distinct sets, the largest set's share, the positions whose
  8th and 9th probabilities are within a hundredth of each other);
- every router kernel's gradient of the client's loss: each side's and the
  reference's.

One JSON line a (seed, client, layer), also appended to
`chiprun_out/sdar_router_look.jsonl`. `--rehearse` walks it on the CPU at
the tiny preset and keeps no file.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "sdar_uncompressed"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="302,301")
    ap.add_argument("--sides", default="bfloat16,float32")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run, weights
    from benchmark.reference import sdar as ref
    from benchmark.reference.keye import moe
    from benchmark.reference.laguna import _rms
    from benchmark.reference.ops import out
    from commefficient_tpu.models.laguna import Block, LagunaLM
    from commefficient_tpu.models.losses import block_diffusion_lm_loss
    from commefficient_tpu.train import lm_train
    from commefficient_tpu.utils.platform import configure_compile_cache

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("sdar_router_look: needs the chip", file=sys.stderr)
        return 1
    if not args.rehearse:
        configure_compile_cache()
    out_path = os.path.join(ROOT, "chiprun_out", "sdar_router_look.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if not args.rehearse:
            with open(out_path, "a") as f:
                f.write(line + "\n")

    cell = run.load_cell(CELL)
    extra = run.apply_tiny(cell) if args.rehearse else ()
    c = cell["config_file"]["reference"]["loss_kwargs"]
    L, K, held = c["num_hidden_layers"], c["num_experts_per_tok"], len(c["experts_held"])
    router = lambda i: f"params/layer_{i}/moe/router/kernel"  # noqa: E731

    def choices(probs):
        """Sorted top-K ids ``[S, K]`` and the K-th and (K+1)-th probabilities."""
        top_p, top_e = jax.lax.top_k(probs, K + 1)
        return jnp.sort(top_e[:, :K], -1), top_p[:, K - 1], top_p[:, K]

    def program(side):
        lcfg = lm_train.PRESETS[cfg.model](dtype=jnp.dtype(side))
        exact = contextlib.nullcontext
        if side == "float32":
            lcfg = dataclasses.replace(lcfg, expert_tiling=(128, 512, 512))
            exact = lambda: jax.default_matmul_precision("highest")  # noqa: E731

        @jax.jit
        def chosen(params, stream):
            p = params["params"]
            x, per_layer = p["embed"]["embedding"][stream[None]], []
            for i in range(L):
                h_in = x
                x, state = Block(lcfg, i).apply(
                    {"params": p[f"layer_{i}"]}, h_in,
                    capture_intermediates=lambda m, _: m.name == "mlp_norm")
                x = x[0]
                h = state["intermediates"]["mlp_norm"]["__call__"][0][0]
                logits = jnp.dot(h, p[f"layer_{i}"]["moe"]["router"]["kernel"],
                                 precision=jax.lax.Precision.HIGHEST)
                per_layer.append(choices(jax.nn.softmax(logits, -1))[0])
            return per_layer

        loss_fn = block_diffusion_lm_loss(LagunaLM(lcfg).apply)

        @jax.jit
        def router_grads(params, batch):
            g = jax.grad(lambda p: loss_fn(p, batch)[0])(params)["params"]
            return [g[f"layer_{i}"]["moe"]["router"]["kernel"] for i in range(L)]

        return side, chosen, router_grads, exact

    @jax.jit
    def ref_chosen(flat, stream):
        x, per_layer = out(flat["params/embed/embedding"][stream], "float32"), []
        for i in range(L):
            name = f"params/layer_{i}"
            x = ref._attention(flat, name, x, "float32", c)
            h = _rms(flat, f"{name}/mlp_norm", x, c["rms_norm_eps"], "float32")
            per_layer.append(choices(jax.nn.softmax(h @ flat[router(i)], -1)))
            y = moe(flat, name, h, "float32", top_k=K, experts_held=c["experts_held"])
            x = out(x + out(y, "float32"), "float32")
        return per_layer

    @jax.jit
    def ref_router_grads(flat, batch):
        g = jax.grad(lambda p: ref.loss(p, batch, "float32", **c))(flat)
        return [g[router(i)] for i in range(L)]

    def groups(rows):
        """``(distinct rows, the largest group's size)``."""
        if not len(rows):
            return 0, 0
        _, counts = np.unique(rows, axis=0, return_counts=True)
        return int(len(counts)), int(counts.max())

    def against(got, want, is_mask):
        """Positions whose set differs from the reference's."""
        moved = np.any(got != want, -1)
        held_of = lambda a: np.sort(np.where(a < held, a, -1), -1)  # noqa: E731
        exchange = np.concatenate([want[moved], got[moved]], -1)
        return {"flips": int(moved.sum()), "flips_at_mask": int((moved & is_mask).sum()),
                "flips_of_a_held_expert": int(np.any(held_of(got) != held_of(want), -1).sum()),
                "largest_group_making_one_exchange": groups(exchange)[1],
                "held_rows": int((got < held).sum())}

    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))  # noqa: E731

    for seed in (int(s) for s in args.seeds.split(",")):
        cfg, session, sampler, tree = run.build(cell, seed % run.SEED_MODULUS, extra)
        _, host = sampler.sample_round(0)
        del session
        gc.collect()
        jax.clear_caches()
        flat = dict(zip(weights.leaf_names(tree), jax.tree.leaves(tree)))
        sides = [program(side) for side in args.sides.split(",")]
        for w in range(host["input_ids"].shape[0]):
            batch = {k: jnp.asarray(v[w]) for k, v in host.items()}
            ids, masked = np.asarray(batch["input_ids"][0]), np.asarray(batch["noise_mask"][0])
            stream = jnp.asarray(np.concatenate([np.where(masked, c["mask_token"], ids), ids]))
            is_mask = np.concatenate([masked, np.zeros_like(masked)])
            with jax.default_matmul_precision("highest"):
                want = [[np.asarray(a) for a in layer] for layer in ref_chosen(flat, stream)]
                want_g = ref_router_grads(flat, batch)
            got = {}
            for side, chosen, grads, exact in sides:
                with exact():
                    got[side] = ([np.asarray(a) for a in chosen(tree, stream)],
                                 grads(tree, batch))
            for i in range(L):
                sets, p_k, p_next = want[i]
                rec = {"seed": seed, "client": w, "layer": i, "positions": int(len(sets)),
                       "masked": int(masked.sum()),
                       "reference": {
                           "held_rows": int((sets < held).sum()),
                           "distinct_sets": groups(sets)[0],
                           "largest_set_positions": groups(sets)[1],
                           "distinct_sets_at_mask": groups(sets[is_mask])[0],
                           "largest_set_positions_at_mask": groups(sets[is_mask])[1],
                           "within_a_hundredth_of_the_boundary": int(
                               (p_k - p_next < 0.01 * p_k).sum()),
                           "router_grad_norm": norm(want_g[i])}}
                for name, (sets_p, g) in got.items():
                    rec[name] = {
                        **against(sets_p[i], sets, is_mask),
                        "router_grad_norm": norm(g[i]),
                        "norm_gap": abs(norm(g[i]) - norm(want_g[i])) / norm(want_g[i]),
                        "diff_over_norm": norm(g[i].astype(jnp.float32) - want_g[i])
                        / norm(want_g[i])}
                emit(rec)
        del tree, flat, sides, got, want_g
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
