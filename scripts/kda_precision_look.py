"""The delta rule kernels' precision on the chip, against the token-by-token
recurrence in float64 on the host.

    python3 scripts/kda_precision_look.py [--seed 41] [--out chiprun_out/kda_precision.json]
    python3 scripts/kda_precision_look.py --rehearse      # tiny, on the CPU

At the Kimi cell's widths (8 heads of 128, chunks of 64, one row of 4,096
positions; the decay in the gate's form with ``A_log`` and ``dt_bias`` drawn
at std 1 as the cell's ``fan_in`` draw has them) it draws one input set from
``--seed`` and runs ``models/kda.py::delta_rule`` on the chip: its output and
its gradients in ``q``, ``k``, ``v``, ``g``, ``beta`` under one random
cotangent. Then the same for a copy of the kernels, built here by replacing
``ops/pallas/delta_rule.py::_exact``, whose state products (every float32
product that reads or makes the ``[d_v, d_k]`` state or its cotangent: ``W
S``, ``Q' S`` and their gradients) take one bfloat16 pass, short of the
precision the configuration states. Both are held to the recurrence in
float64 numpy on the same inputs, its backward pass by hand; each twice:
with ``q``, ``k``, ``v`` in
bfloat16 as the cell's ``mixed`` hands them, where the pair factors'
bfloat16 operands set a floor (~3e-3) that both sides share, and in float32
(the ``float32`` configuration: every product float32), where the state's
products alone part the two sides.

One JSON line a side, ``rel``: ``||x - ref|| / ||ref||`` for ``o``, ``dq``,
``dk``, ``dv``, ``dg``, ``dbeta``; after each pair the copy's reading over
the kernel's. Exits 1 without a TPU; ``--rehearse`` runs a tiny size on the
CPU and first holds the float64 backward to JAX's gradient of the float32
recurrence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from commefficient_tpu.models import kda  # noqa: E402
from commefficient_tpu.ops.pallas import delta_rule  # noqa: E402

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def inputs(seed, T, H, d):
    """One row as the mixer hands it to the rule, ``[1, T, H, d]``, float32."""
    ks = jax.random.split(jax.random.key(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, T, H, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (1, T, H, d)))
    v = jax.random.normal(ks[2], (1, T, H, d))
    a_log = jax.random.normal(ks[3], (H, 1))
    dt_bias = jax.random.normal(ks[4], (H, d))
    g = -jnp.exp(a_log) * jax.nn.softplus(jax.random.normal(ks[5], (1, T, H, d)) + dt_bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (1, T, H)))
    ct = jax.random.normal(ks[7], (1, T, H, d))
    return (q, k, v, g, beta), ct


def recurrence64(q, k, v, g, beta, do, block=64):
    """The rule a position at a time in float64 and its backward pass by
    hand: ``q``, ``k``, ``g`` ``[T, H, d_k]``, ``v``, ``do`` ``[T, H, d_v]``,
    ``beta`` ``[T, H]`` -> ``(o, dq, dk, dv, dg, dbeta)``. The state is kept
    every ``block`` positions and recomputed between."""
    T, H, K = k.shape
    e = np.exp(g)

    def step(S, t):
        St = S * e[t][:, :, None]
        u = v[t] - np.einsum("hk,hkv->hv", k[t], St)
        return St, u, St + (beta[t][:, None] * k[t])[:, :, None] * u[:, None, :]

    S = np.zeros((H, K, v.shape[-1]))
    o, kept = np.zeros(v.shape), {}
    for t in range(T):
        if t % block == 0:
            kept[t] = S
        S = step(S, t)[2]
        o[t] = np.einsum("hk,hkv->hv", q[t], S)
    dq, dk, dv, dg = (np.zeros(x.shape) for x in (q, k, v, g))
    dbeta, dS = np.zeros(beta.shape), np.zeros(S.shape)
    for start in reversed(range(0, T, block)):
        S, steps = kept[start], []
        for t in range(start, min(start + block, T)):
            steps.append(step(S, t))
            S = steps[-1][2]
        for t in reversed(range(start, min(start + block, T))):
            St, u, S = steps[t - start]
            dS = dS + q[t][:, :, None] * do[t][:, None, :]
            dq[t] = np.einsum("hkv,hv->hk", S, do[t])
            bk = beta[t][:, None] * k[t]
            dbk = np.einsum("hkv,hv->hk", dS, u)
            du = np.einsum("hkv,hk->hv", dS, bk)
            dSt = dS - k[t][:, :, None] * du[:, None, :]
            dbeta[t] = np.sum(dbk * k[t], -1)
            dk[t] = beta[t][:, None] * dbk - np.einsum("hkv,hv->hk", St, du)
            dv[t] = du
            dg[t] = np.sum(dSt * St, -1)
            dS = dSt * e[t][:, :, None]
    return o, dq, dk, dv, dg, dbeta


def program(args, ct, chunk):
    """``delta_rule``'s output and its five gradients, as float64 ``[T, ...]``."""
    def loss(*a):
        return jnp.sum(kda.delta_rule(*a, chunk=chunk)[0] * ct)

    o = jax.jit(lambda *a: kda.delta_rule(*a, chunk=chunk)[0])(*args)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
    return [np.asarray(x[0], np.float64) for x in (o, *grads)]


def state_in_bfloat16_passes(d):
    """``_exact`` with one bfloat16 pass wherever the state or its cotangent
    (``[d_v, d_k]``) is an operand or the result."""
    exact = delta_rule._exact

    def product(a, b, dims):
        out = exact(a, b, dims)
        if (d, d) in (a.shape, b.shape, out.shape):
            return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
                                       preferred_element_type=jnp.float32)
        return out

    return product


def rel(x, ref):
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "kda_precision.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        T, H, d, chunk = 64, 2, 16, 16
    else:
        T, H, d, chunk = 4096, 8, 128, 64
        if jax.default_backend() != "tpu":
            print(json.dumps({"error": "no TPU: the readings are the chip's "
                                       "(--rehearse walks it)"}))
            return 1
    x, ct = inputs(args.seed, T, H, d)
    lines = []
    if args.rehearse:
        from benchmark.reference import kimi_linear as ref
        want = recurrence64(*(np.asarray(a[0], np.float64) for a in x),
                            np.asarray(ct[0], np.float64))
        with jax.default_matmul_precision("highest"):
            grads = jax.grad(lambda *a: jnp.sum(ref.recurrence(*a, block=16) * ct[0]),
                             argnums=(0, 1, 2, 3, 4))(*(a[0] for a in x))
        lines.append({"what": "float64_backward_vs_jax", "rel": dict(zip(
            NAMES[1:], (rel(w, np.asarray(g, np.float64)) for w, g in zip(want[1:], grads))))})
        print(json.dumps(lines[-1]), flush=True)
    exact = delta_rule._exact
    for dtype in (jnp.bfloat16, jnp.float32):
        args_ = tuple(a.astype(dtype) for a in x[:3]) + x[3:]
        want = recurrence64(*(np.asarray(a[0], np.float64) for a in args_),
                            np.asarray(ct[0], np.float64))
        sides = {}
        for what in ("kernel", "state_bf16_passes"):
            delta_rule._exact = state_in_bfloat16_passes(d) if what != "kernel" else exact
            got = program(args_, ct, chunk)
            sides[what] = dict(zip(NAMES, (rel(a, b) for a, b in zip(got, want))))
            lines.append({"what": what, "operands": jnp.dtype(dtype).name, "seed": args.seed,
                          "T": T, "heads": H, "d": d, "device": jax.devices()[0].device_kind,
                          "rel": sides[what]})
            print(json.dumps(lines[-1]), flush=True)
        lines.append({"what": "copy_over_kernel", "operands": jnp.dtype(dtype).name, "ratio": {
            n: sides["state_bf16_passes"][n] / sides["kernel"][n] for n in NAMES}})
        print(json.dumps(lines[-1]), flush=True)
    delta_rule._exact = exact
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
