"""The few array operations both reference models share, in float32.

``precision`` is how a product's operands are held: ``"float32"`` (the
reference proper; the caller sets ``jax.default_matmul_precision("highest")``
so the chip does not round them), or one of the lower ones the controls use:
``"bfloat16"`` rounds both operands to bfloat16, ``"fp8"`` to float8 e4m3
under a per-tensor scale, as an fp8 training recipe would hold them.
``"bfloat16_out"`` is ``"bfloat16"`` with, besides, every activation that a
model passes through ``out`` held in bfloat16, value and cotangent, as a
program that computes in bfloat16 throughout holds them: a look at what
that does to a gradient that is a small difference of large terms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

IGNORE = -100


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def lower(x, precision):
    """``x`` as a product in ``precision`` would see it; the gradient passes
    straight through the rounding, as it does in a low-precision recipe."""
    if precision == "float32":
        return x
    if precision in ("bfloat16", "bfloat16_out"):
        # reduce_precision, not a cast there and back: XLA may drop such a pair
        seen = _bf16(x)
    elif precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 224.0 + 1e-30  # e4m3 under a per-tensor scale
        seen = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(seen - x)


@jax.custom_vjp
def _bf16_both_ways(x):
    return _bf16(x)


_bf16_both_ways.defvjp(lambda x: (_bf16(x), None), lambda _, ct: (_bf16(ct),))


def out(x, precision):
    """An activation as ``precision`` keeps it: rounded to bfloat16 on the
    way forward and its cotangent on the way back under ``"bfloat16_out"``,
    untouched otherwise."""
    return _bf16_both_ways(x) if precision == "bfloat16_out" else x


def matmul(x, w, precision):
    return lower(x, precision) @ lower(w, precision)


def cross_entropy_sum(logits, labels):
    """(sum of negative log-likelihood over labels != IGNORE, their count)."""
    keep = labels != IGNORE
    safe = jnp.where(keep, labels, 0)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)), jnp.sum(keep.astype(jnp.float32))


def cross_entropy(logits, labels):
    s, n = cross_entropy_sum(logits, labels)
    return s / jnp.maximum(n, 1.0)
