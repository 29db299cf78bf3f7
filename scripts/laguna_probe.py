"""Chip probe (ISSUE 29): the pieces of the Laguna expert layer and attention
at the cell's own sizes (one client: N = 4,096 tokens, hidden 2,048, 8 held
experts of width 512, ~1,024 held assignments; attention T = 2,048, head 128),
candidate by candidate, so that one form of each is kept on measurement.

    chiprun -- python scripts/laguna_probe.py

Host clock around ``block_until_ready``, the mean of ``--reps`` calls after
one warm call; one JSON line a reading, also appended to
``chiprun_out/laguna_probe.jsonl``. It refuses to start without a TPU: a
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
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

OUT = os.path.join("chiprun_out", "laguna_probe.jsonl")


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
        say(what=name, error=f"{type(e).__name__}: {str(e)[:300]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"laguna_probe: no TPU (backend {jax.default_backend()}); it times the chip only")
    say(what="device", kind=jax.devices()[0].device_kind, platform=jax.devices()[0].platform)
    key = jax.random.key(0)
    N, E, F, G, K = 4096, 2048, 512, 8, 8
    R = N * K
    h = jax.random.normal(key, (N, E), jnp.bfloat16)
    wg = jax.random.normal(key, (G, E, F), jnp.bfloat16) * 0.02
    wd = jax.random.normal(key, (G, F, E), jnp.bfloat16) * 0.02
    rng = np.random.default_rng(0)

    # ---- row movement: gather, scatter-add, one-hot products -----------------
    for rows in (R // 16, R // 8, R):
        tok = jnp.asarray(np.sort(rng.integers(0, N, rows)).astype(np.int32))
        tok_shuffled = jnp.asarray(rng.permutation(np.asarray(tok)))
        y = jax.random.normal(key, (rows, E), jnp.float32)
        timed("gather_rows", lambda h, t: h[t], h, tok_shuffled, rows=rows, reps=args.reps)
        timed("scatter_add_rows", lambda y, t: jnp.zeros((N, E), jnp.float32).at[t].add(y),
              y, tok_shuffled, rows=rows, reps=args.reps)
        timed("scatter_add_rows_sorted",
              lambda y, t: jnp.zeros((N, E), jnp.float32).at[t].add(y, indices_are_sorted=True),
              y, tok, rows=rows, reps=args.reps)
        if rows < R:
            def onehot_dispatch(h, t):
                s = jax.nn.one_hot(t, N, dtype=jnp.bfloat16)
                return jnp.dot(s, h, preferred_element_type=jnp.float32).astype(jnp.bfloat16)

            def onehot_combine(y, t):
                s = jax.nn.one_hot(t, N, dtype=jnp.bfloat16)
                return jnp.dot(s.T, y.astype(jnp.bfloat16), preferred_element_type=jnp.float32)

            timed("onehot_dispatch", onehot_dispatch, h, tok_shuffled, rows=rows, reps=args.reps)
            timed("onehot_combine", onehot_combine, y, tok_shuffled, rows=rows, reps=args.reps)

    # ---- routing bookkeeping ---------------------------------------------------
    s = jax.random.uniform(key, (N, 256), jnp.float32)
    timed("top_k_8_of_256", lambda s: jax.lax.top_k(s, K), s, reps=args.reps)
    slots = jnp.asarray(rng.integers(0, 32, R).astype(np.int32))
    timed("argsort_assignments", lambda a: jnp.argsort(a, stable=True), slots, rows=R, reps=args.reps)

    # ---- the grouped product: megablox gmm, rows = capacity, live rows first ---
    for rows, live in ((R // 16, R // 32), (R // 8, R // 32), (R // 8, R // 8), (R, R // 32)):
        sizes = jnp.full((G,), live // G, jnp.int32)
        x = jax.random.normal(key, (rows, E), jnp.bfloat16)
        for tiling in ((128, 512, 512), (128, 1024, 512), (256, 512, 512), (512, 512, 512)):
            def swiglu(x, wg, wd, sizes, tiling=tiling):
                a = megablox.gmm(x, wg, sizes, jnp.float32, tiling)
                return megablox.gmm(jax.nn.silu(a).astype(jnp.bfloat16), wd, sizes, jnp.float32,
                                    tiling)

            timed("gmm_up_down", swiglu, x, wg, wd, sizes, rows=rows, live=live,
                  tiling=list(tiling), reps=args.reps)
            timed("gmm_up_down_grad",
                  jax.grad(lambda x, wg, wd, sizes: jnp.sum(swiglu(x, wg, wd, sizes)), (0, 1, 2)),
                  x, wg, wd, sizes, rows=rows, live=live, tiling=list(tiling), reps=args.reps)

    # ---- attention: splash, grouped queries as MQA per KV head -------------------
    T, d, KV, B = 2048, 128, 8, 2
    for kind, heads, window in (("window", 64, 512), ("full", 48, None)):
        group = heads // KV
        q = jax.random.normal(key, (B, KV, group, T, d), jnp.bfloat16)
        k = jax.random.normal(key, (B, KV, T, d), jnp.bfloat16)
        head_mask = sm.LocalMask((T, T), (window - 1, 0), 0) if window else sm.CausalMask((T, T))
        for blk in (512, 1024, 256):
            bs = sk.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk,
                               block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
                               block_q_dq=blk, block_kv_dq=blk)
            kern = sk.make_splash_mqa_single_device(
                sm.MultiHeadMask([head_mask] * group), block_sizes=bs)
            attn = jax.vmap(jax.vmap(kern))
            timed(f"attn_{kind}_fwd", attn, q, k, k, block=blk, heads=heads, reps=args.reps)
            timed(f"attn_{kind}_fwd_bwd",
                  jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)), (0, 1, 2)),
                  q, k, k, block=blk, heads=heads, reps=args.reps)

        def plain(q, k, v, window=window):
            s = jnp.einsum("bkgtd,bksd->bkgts", q, k, preferred_element_type=jnp.float32)
            i = jnp.arange(T)
            m = i[:, None] >= i[None, :]
            if window:
                m &= (i[:, None] - i[None, :]) < window
            p = jax.nn.softmax(jnp.where(m, s, -1e30), -1).astype(v.dtype)
            return jnp.einsum("bkgts,bksd->bkgtd", p, v)

        timed(f"attn_{kind}_materialized_fwd_bwd",
              jax.grad(lambda q, k, v: jnp.sum(plain(q, k, v).astype(jnp.float32)), (0, 1, 2)),
              q, k, k, heads=heads, reps=args.reps)


if __name__ == "__main__":
    main()
