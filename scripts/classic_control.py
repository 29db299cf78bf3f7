"""Classic-CountSketch control for the d/c~100 divergence (r3).

The r2 postmortem's decisive experiment, re-run in the GPT-2 sketch regime:
train the quarter/eighth-scale federated ResNet-9 with an EXACT textbook
CountSketch (per-row scatter-add over a global bucket pool, 4-universal-free
fmix32 hashing — the reference csvec's structure) under IDENTICAL FetchSGD
server algebra (virtual momentum rho, virtual error, top-k extract +
sketch-subtract). If THIS diverges at d/c~100 too, the banded layout is
exonerated and the instability is a property of the regime (100 coords per
bucket) on this workload — the fix is then defaults/documentation, not
layout work.

Runs on CPU (scatter is fine there) so it can proceed while the TPU is
busy:  JAX_PLATFORMS=cpu python scripts/classic_control.py --width 16
"""

from __future__ import annotations

import argparse
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--c_div", type=int, default=100)
    ap.add_argument("--k_div", type=int, default=1000)
    ap.add_argument("--num_rows", type=int, default=5)
    ap.add_argument("--lr_scale", type=float, default=0.04)
    ap.add_argument("--rho", type=float, default=0.9)
    ap.add_argument("--num_epochs", type=int, default=12)
    ap.add_argument("--pivot_epoch", type=int, default=3)
    ap.add_argument("--variant", default="concentrated")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.data import FedSampler, augment_batch
    from commefficient_tpu.data.cifar import (
        CIFAR10_MEAN, CIFAR10_STD, _synthetic_by_variant, device_normalizer,
    )
    from commefficient_tpu.data.fed_dataset import FedDataset
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.utils.schedule import piecewise_linear_lr

    model = ResNet9(num_classes=10, width=args.width)
    params = model.init(jax.random.key(42), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(
        model.apply, prep=device_normalizer(CIFAR10_MEAN, CIFAR10_STD)
    )
    vec, unravel = ravel_pytree(params)
    D = vec.size
    C, K, R = D // args.c_div, D // args.k_div, args.num_rows
    print(f"CLASSIC control: D={D} c={C} k={K} r={R} lr={args.lr_scale} "
          f"rho={args.rho}", flush=True)

    # textbook CountSketch: per-row global-pool bucket + sign hashes
    # (fmix32 — the hash family is already exonerated by the poly4 A/B)
    M1, M2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)

    def mix(x, key):
        x = (x ^ key).astype(np.uint32)
        with np.errstate(over="ignore"):
            x ^= x >> np.uint32(16); x *= M1
            x ^= x >> np.uint32(13); x *= M2
            x ^= x >> np.uint32(16)
        return x

    idx = np.arange(D, dtype=np.uint32)
    cols = np.stack([mix(idx, np.uint32(0xA5A5 + 7919 * r)) % np.uint32(C)
                     for r in range(R)])          # [R, D] int
    signs = np.stack([
        1.0 - 2.0 * (mix(idx, np.uint32(0x5A5A + 104729 * r)) & 1)
        for r in range(R)
    ]).astype(np.float32)                          # [R, D]
    cols_j = jnp.asarray(cols.astype(np.int32))
    signs_j = jnp.asarray(signs)

    def sk(v):  # [D] -> [R, C]
        return jnp.stack([
            jnp.zeros((C,), jnp.float32).at[cols_j[r]].add(v * signs_j[r])
            for r in range(R)
        ])

    def est(table):  # [R, C] -> [D] median estimate
        return jnp.median(
            jnp.stack([table[r, cols_j[r]] * signs_j[r] for r in range(R)]),
            axis=0,
        )

    tr_raw, te_raw = _synthetic_by_variant(10, args.variant)
    train = FedDataset(dict(tr_raw), 16, seed=42)
    sampler = FedSampler(train, num_workers=8, local_batch_size=64, seed=42,
                         augment=augment_batch)
    steps = sampler.steps_per_epoch()
    lr_fn = partial(piecewise_linear_lr, steps_per_epoch=steps,
                    pivot_epoch=args.pivot_epoch, num_epochs=args.num_epochs,
                    lr_scale=args.lr_scale)

    @jax.jit
    def round_step(w, mom, err, batch, lr):
        def per_worker_grad(b):
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(unravel(w), b)
            gv, _ = ravel_pytree(g)
            return gv + 5e-4 * w, l

        gs, ls = jax.vmap(per_worker_grad)(batch)
        agg = sk(jnp.mean(gs, axis=0))
        mom = args.rho * mom + agg
        err = err + lr * mom
        e_hat = est(err)
        thr = jnp.sort(jnp.abs(e_hat))[-K]
        upd = jnp.where(jnp.abs(e_hat) >= thr, e_hat, 0.0)
        err = err - sk(upd)
        return w - upd, mom, err, jnp.mean(ls)

    w = vec.astype(jnp.float32)
    mom = jnp.zeros((R, C), jnp.float32)
    err = jnp.zeros((R, C), jnp.float32)
    step = 0
    for ep in range(args.num_epochs):
        for _, batch in sampler.epoch(ep):
            b = {k: jnp.asarray(v) for k, v in batch.items()}
            w, mom, err, loss = round_step(w, mom, err, b, jnp.float32(lr_fn(step)))
            step += 1
        print(f"  ep{ep + 1}: train_loss={float(loss):.4f} "
              f"|err|max={float(jnp.abs(err).max()):.3e}", flush=True)


if __name__ == "__main__":
    main()
