"""The pieces every mixer and feed-forward of the decoder in
``models/laguna.py`` is built of, kept apart so that a mixer in a file of its
own (``models/kda.py``, ``models/latent_attention.py``) and the decoder that
runs it import them from one place."""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


def _dot(x, w, dtype):
    """Operands in ``dtype``, float32 accumulation and result."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


class RMSNorm(nn.Module):
    """The ``scale`` leaf (float32, ones); ``__call__(x)`` norms, ``scale()``
    hands the leaf out for a caller that norms inside a plain function."""

    eps: float
    width: int

    def setup(self):
        self.weight = self.param("scale", nn.initializers.ones, (self.width,), jnp.float32)

    def scale(self):
        return self.weight

    def __call__(self, x):
        return _rms(x, self.weight, self.eps)


class _Kernel(nn.Module):
    """A bias-free projection's ``kernel`` leaf, float32."""

    shape: Tuple[int, ...]
    std: float

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(self.std), self.shape, jnp.float32)
