"""ResNet-9 for 32x32 images, as the configuration describes it: conv(64),
conv(128)+pool, residual(128), conv(256)+pool, conv(512)+pool,
residual(512), global max pool, linear, logits x 0.125. Every conv is 3x3,
pad 1, no bias, followed by a 16-group GroupNorm (eps 1e-6) and CELU(0.3).
Parameters arrive as a flat ``{path: array}`` dict under the names the
configuration file lists; images arrive uint8 and are normalized here."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ops import cross_entropy, lower

MEAN = jnp.array([0.4914, 0.4822, 0.4465], jnp.float32)
STD = jnp.array([0.2470, 0.2435, 0.2616], jnp.float32)


def _conv_block(p, name, x, precision, pool=False):
    w = p[f"{name}/Conv_0/kernel"]
    x = jax.lax.conv_general_dilated(
        lower(x, precision), lower(w, precision), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, h, wd, c = x.shape
    g = x.reshape(n, h, wd, 16, c // 16)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g), axis=(1, 2, 4), keepdims=True) - jnp.square(mean)
    x = ((g - mean) * jax.lax.rsqrt(var + 1e-6)).reshape(n, h, wd, c)
    x = x * p[f"{name}/GroupNorm_0/scale"] + p[f"{name}/GroupNorm_0/bias"]
    x = jnp.maximum(x, 0.0) + jnp.minimum(0.0, 0.3 * jnp.expm1(x / 0.3))
    if pool:
        x = x.reshape(n, h // 2, 2, wd // 2, 2, c).max(axis=(2, 4))
    return x


def loss(p, batch, precision="float32"):
    """Mean cross-entropy of one client's batch ``{"x": uint8 [B,32,32,3],
    "y": [B]}``."""
    x = (batch["x"].astype(jnp.float32) / 255.0 - MEAN) / STD
    x = _conv_block(p, "params/ConvBlock_0", x, precision)
    x = _conv_block(p, "params/ConvBlock_1", x, precision, pool=True)
    y = _conv_block(p, "params/Residual_0/ConvBlock_0", x, precision)
    x = x + _conv_block(p, "params/Residual_0/ConvBlock_1", y, precision)
    x = _conv_block(p, "params/ConvBlock_2", x, precision, pool=True)
    x = _conv_block(p, "params/ConvBlock_3", x, precision, pool=True)
    y = _conv_block(p, "params/Residual_1/ConvBlock_0", x, precision)
    x = x + _conv_block(p, "params/Residual_1/ConvBlock_1", y, precision)
    x = jnp.max(x, axis=(1, 2))
    logits = (lower(x, precision) @ lower(p["params/Dense_0/kernel"], precision)
              + p["params/Dense_0/bias"]) * 0.125
    return cross_entropy(logits, batch["y"])
