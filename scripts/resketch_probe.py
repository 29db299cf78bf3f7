"""Chip probe (ISSUE 27): what the zero-HH re-sketch of a top-k's pairs costs
at the paper's GPT-2 geometry (D = 124,444,417, 5 x 5M table, k = 50,000),
piece by piece: the compaction of the dense ``update`` into pairs
(``compact_nonzero`` and ``compact_nonzero_tree``), the table from the pairs
(``sketch_sparse``), and the dense ``sketch_vec`` pass they replace; both
tables against the same sums made on the host in float64.

    chiprun -- python scripts/resketch_probe.py [--d N --c N --k N]

Host clock around ``block_until_ready``, the mean of ``--reps`` calls after
one warm call; one JSON line a reading, also appended to
``chiprun_out/resketch_probe.jsonl``. Seconds are only meaningful on the
chip; on the CPU the script is a rehearsal of its own control flow.
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

from commefficient_tpu.ops import countsketch as cs
from commefficient_tpu.ops import topk

OUT = os.path.join("chiprun_out", "resketch_probe.jsonl")


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(name, fn, *args, reps=5):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(f(*args))
    say(what=name, s=(time.perf_counter() - t0) / reps, first_s=first)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=124_444_417)
    ap.add_argument("--c", type=int, default=5_000_000)
    ap.add_argument("--r", type=int, default=5)
    ap.add_argument("--k", type=int, default=50_000)
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args()
    dev = jax.devices()[0]
    say(what="device", platform=dev.platform, kind=dev.device_kind, d=a.d, c=a.c,
        r=a.r, k=a.k, jax=jax.__version__)
    spec = cs.CountSketch(d=a.d, c=a.c, r=a.r, seed=42)
    k, d = a.k, a.d

    @jax.jit
    def make_update(key):
        ki, kv = jax.random.split(key)
        idx = jax.random.randint(ki, (k,), 0, d, dtype=jnp.int32)
        # float32 values: the dense pass rounds them to bfloat16 on the MXU
        # (the gap printed at the end), the scatter does not
        val = 1e-3 + jnp.abs(jax.random.normal(kv, (k,), jnp.float32))
        val = val * jnp.where(jax.random.bernoulli(ki, shape=(k,)), 1.0, -1.0)
        return jnp.zeros((d,), jnp.float32).at[idx].set(val)

    update = jax.block_until_ready(make_update(jax.random.PRNGKey(27)))
    reps = a.reps

    # ---- the compaction -------------------------------------------------
    pairs = timed("compact_nonzero", lambda v: topk.compact_nonzero(v, k), update,
                  reps=reps)
    timed("count_nonzero_rows", lambda v: jnp.sum(jnp.pad(
        v, (0, -d % 128)).reshape(-1, 128) != 0, axis=1, dtype=jnp.int32), update,
        reps=reps)
    tree = timed("compact_nonzero_tree",
                 lambda v: topk.compact_nonzero_tree(v, k), update, reps=reps)
    say(what="tree_equals_compact_nonzero",
        idx=bool(jnp.array_equal(tree[0], pairs[0])),
        val=bool(jnp.array_equal(tree[1], pairs[1])),
        nonzeros=int(jnp.sum(pairs[1] != 0)))
    idx, val = pairs

    # ---- the table from the pairs, beside the dense pass -------------------
    timed("row_cols_signs_x_r", lambda i: [cs._row_cols_signs(spec, i, r)
                                           for r in range(spec.r)], idx, reps=reps)
    sparse = timed("sketch_sparse", lambda i, v: cs.sketch_sparse(spec, i, v),
                   idx, val, reps=reps)
    dense = timed("sketch_vec", lambda v: cs.sketch_vec(spec, v), update, reps=reps)
    # both against the same sums made on the host in float64
    exact = np.zeros(spec.table_shape, np.float64)
    hv = np.asarray(val, np.float64)
    for row in range(spec.r):
        cols, sign = jax.jit(lambda i, r=row: cs._row_cols_signs(spec, i, r))(idx)
        np.add.at(exact[row], np.asarray(cols), hv * np.asarray(sign, np.float64))
    for name, table in (("sketch_sparse", sparse), ("sketch_vec", dense)):
        gap = np.abs(np.asarray(table, np.float64) - exact)
        say(what=name + "_vs_float64", max_abs=float(gap.max()),
            entries_off_by_1e_6=int((gap > 1e-6).sum()),
            table_max=float(np.abs(exact).max()))
    stats = dev.memory_stats() or {}
    say(what="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        peak_bytes_reserved=stats.get("peak_bytes_reserved"))


if __name__ == "__main__":
    main()
