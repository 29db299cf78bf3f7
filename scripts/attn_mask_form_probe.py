"""Chip probe (ISSUE 38): the library's three attention kernels alone, built
from one mask in its two forms, so that what a mask's form costs is read
apart from the model.

    chiprun -- python scripts/attn_mask_form_probe.py [--only blockdiff,causal]

- ``computable``: the mask as the library's ``_ComputableMask`` (a rule the
  kernels evaluate on every element of every tile they visit). For block
  diffusion that is the form ``library_kernels.BlockDiffusionMask`` had
  before PR 38, kept here as ``ComputableBlockDiffusion``; for the causal
  case the library's own ``CausalMask``.
- ``blocks``: the same mask as a plain ``Mask`` (the library stores the
  straddling tiles' masks as blocks, and a full tile goes unmasked):
  ``BlockDiffusionMask`` as it is, and ``Stored(CausalMask)``.

Each case is one multi-query kernel of ``--group`` query heads, ``vmap``-ped
over ``--batch`` x ``--kv`` as ``banded_attention`` calls it, bfloat16, tiles
of 512 (128 where the length is no multiple of 512), ``ATTEND_RESIDUAL``
named. For each form: the host clock around the forward and around forward +
backward (``block_until_ready``, the mean of ``--reps`` calls after a warm
one), and each kernel's device seconds a call from a profile of
``--calls`` calls of forward + backward (``splash_mqa_fwd_residuals``,
``splash_mqa_dq``, ``splash_mqa_dkv``). Then whether the two forms' outputs
and q, k, v gradients are bit-equal. One JSON line a reading, also appended
to ``chiprun_out/attn_mask_form_probe.jsonl``. ``--rehearse`` walks it on
the CPU at a tiny size (interpreted kernels: no reading of time).
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as _mask

from commefficient_tpu.ops.pallas import library_kernels as lk

OUT = os.path.join("chiprun_out", "attn_mask_form_probe.jsonl")


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


class ComputableBlockDiffusion(_mask._ComputableMask):
    """``BlockDiffusionMask`` before PR 38: the rule as ``mask_function`` on
    ``q_sequence``'s codes (a noised query of block ``b`` carries ``b L``, a
    clean one ``-(b + 1) L``), evaluated inside the kernels."""

    def __init__(self, shape, block_length):
        T, L = shape[0] // 2, block_length
        self.block_length = L

        def block_diffusion_mask_function(c, k):
            at = k - c
            return ((k >= T) & (k - T < abs(c))) | ((k < T) & (at >= 0) & (at < L))

        super().__init__(shape=shape, mask_function=block_diffusion_mask_function)
        first = np.arange(T, dtype=np.int32) // L * L
        self.q_sequence = np.concatenate([first, -(first + L)])

    def __eq__(self, other):
        if not isinstance(other, type(self)):
            return NotImplemented
        return self.shape == other.shape and self.block_length == other.block_length

    def __hash__(self):
        return hash((type(self), self.shape, self.block_length))


class Stored(_mask.Mask):
    """A computable mask read as a plain one: the library stores its blocks."""

    def __init__(self, inner):
        self.inner = inner

    @property
    def shape(self):
        return self.inner.shape

    def __getitem__(self, idx):
        return self.inner[idx]

    def __eq__(self, other):
        return isinstance(other, Stored) and self.inner == other.inner

    def __hash__(self):
        return hash((Stored, self.inner))


def kernel_seconds(fn, *args, calls):
    """Device seconds a call of each ``splash_*`` kernel, by name."""
    from benchmark.reduce import load_xplane

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        paths = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")))
        trace = load_xplane(paths[-1])
    ops = next((o for o in trace["devices"].values() if o), [])
    by = {}
    for op, _scope, _start, dur in ops:
        if op.startswith("splash"):
            name = re.sub(r"\.\d+$", "", op)
            by[name] = by.get(name, 0.0) + dur / calls
    return by


def host_seconds(fn, *args, reps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def probe(case, heads, T, group, batch, kv, d, args):
    blk = 512 if T % 512 == 0 else 128
    keys = jax.random.split(jax.random.key(args.seed), 4)
    n = jax.random.normal
    q = n(keys[0], (batch, kv, group, T, d), jnp.bfloat16) / 8
    k, v = (n(key, (batch, kv, T, d), jnp.bfloat16) for key in keys[1:3])
    ct = n(keys[3], (batch, kv, group, T, d), jnp.bfloat16)
    got = {}
    for form, head in heads.items():
        t0 = time.perf_counter()
        kernel = jax.vmap(jax.vmap(lk.mqa_kernel(head, group, blk, lk.kernels_interpreted())))
        build = time.perf_counter() - t0
        fwd = jax.jit(kernel)
        grad = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(kernel(*a).astype(jnp.float32) * ct), (0, 1, 2)))
        got[form] = (fwd(q, k, v), grad(q, k, v)[1])
        row = dict(case=case, form=form, T=T, group=group, batch=batch, kv=kv, d=d, blk=blk,
                   tables_build_s=build)
        if not args.rehearse:
            row.update(fwd_s=host_seconds(fwd, q, k, v, reps=args.reps),
                       fwd_bwd_s=host_seconds(grad, q, k, v, reps=args.reps),
                       kernels_s=kernel_seconds(grad, q, k, v, calls=args.calls))
        say(**row)
    (o0, g0), (o1, g1) = got.values()
    same = [bool(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)))
            for a, b in zip((o0, *g0), (o1, *g1))]
    say(case=case, what="bit_equal", out_dq_dk_dv=same, all=all(same))
    return all(same)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="blockdiff,causal")
    ap.add_argument("--stream", type=int, default=16384, help="2T of the block-diffusion case")
    ap.add_argument("--block_length", type=int, default=4)
    ap.add_argument("--group", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--kv", type=int, default=4)
    ap.add_argument("--causal_T", type=int, default=2048)
    ap.add_argument("--causal_groups", default="6,8")
    ap.add_argument("--causal_batch", type=int, default=8)
    ap.add_argument("--causal_kv", type=int, default=8)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU at a tiny size: forms and bit-equality, no times")
    args = ap.parse_args()
    if args.rehearse:
        args.stream, args.group, args.batch, args.kv, args.d = 512, 2, 1, 2, 128
        args.causal_T, args.causal_groups, args.causal_batch, args.causal_kv = 256, "2", 1, 2
    elif jax.default_backend() != "tpu":
        say(error=f"no TPU (backend {jax.default_backend()}): run it through chiprun, "
                  "or --rehearse")
        return 1
    only, ok = set(args.only.split(",")), True
    if "blockdiff" in only:
        shape = (args.stream, args.stream)
        ok &= probe("blockdiff", {
            "computable": ComputableBlockDiffusion(shape, args.block_length),
            "blocks": lk.BlockDiffusionMask(shape, args.block_length)},
            args.stream, args.group, args.batch, args.kv, args.d, args)
    if "causal" in only:
        shape = (args.causal_T, args.causal_T)
        for g in map(int, args.causal_groups.split(",")):
            ok &= probe(f"causal_g{g}", {"computable": _mask.CausalMask(shape),
                                         "blocks": Stored(_mask.CausalMask(shape))},
                        args.causal_T, g, args.causal_batch, args.causal_kv, args.d, args)
    say(done=True, all_bit_equal=bool(ok))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
