"""Laguna-XS.2's decoder as its published config describes it (ISSUE 29 section 1),
cut to one chip's share: pre-norm blocks of gated grouped-query attention
(full causal with YaRN on half the head, or a causal window with plain rotary
positions, by layer) and a feed-forward that is dense (SwiGLU) on the leading
layer and routed elsewhere: a sigmoid router over all the published experts,
the largest ``num_experts_per_tok`` scores renormalised and scaled, one
shared expert, and of the routed experts only those in ``experts_held``.
What the experts held elsewhere would add is left out (the guide's cut), so
with every id in ``experts_held`` this is the uncut layer. Head over the
vocabulary rows held; loss: mean next-token negative log-likelihood over
labels that are not -100. No auxiliary router loss, positions run across
packed documents, attention is not reset at document boundaries.

Forms the config leaves open, the reader's choice (``assumed`` in the
configuration file): the output gate is one sigmoid per head from the
normed input; the router weights are the selected sigmoid scores over their
sum times ``moe_routed_scaling_factor``; ``silu``; rotary halves are
``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``; no QK norm.

Every product goes through ``ops.lower`` but the router's: the configuration
keeps the router in float32, and the controls lower what it lowers. Scores
are materialized for one sequence, one layer and one KV head at a time
(``lax.map`` over a client's sequences and over a layer's KV heads, with
``jax.checkpoint`` around each layer and each KV head's group of query
heads: one ``[group, T, T]`` block of scores is alive in the backward pass,
so that the published widths fit the chip beside the float32 parameters and
their gradient, and the loops compile once); every held expert is applied to
every token and masked by its weight. Parameters arrive as a flat
``{path: array}`` dict. Nothing here imports the system under test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.ops import cross_entropy_sum, lower, matmul, out


def _rms(p, name, x, eps, precision):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return out(y * p[f"{name}/scale"], precision)


def rope_tables(T, head_dim, rope):
    """``(cos, sin)`` ``[T, r/2]`` and the rotated width ``r`` for one kind
    of layer. ``rope_type`` ``default``: ``theta^(-i/(r/2))``. ``yarn``:
    interpolated and extrapolated frequencies blended by the usual linear
    ramp between the correction dims of ``beta_fast`` and ``beta_slow``, and
    cos / sin times ``attention_factor``."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    half = r // 2
    i = jnp.arange(half, dtype=jnp.float32)
    freq = jnp.float32(rope["rope_theta"]) ** (-i / half)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        orig = rope["original_max_position_embeddings"]

        def dim(beta):
            return r * math.log(orig / (2 * math.pi * beta)) / (2 * math.log(rope["rope_theta"]))

        lo = max(math.floor(dim(rope["beta_fast"])), 0)
        hi = min(math.ceil(dim(rope["beta_slow"])), r - 1)
        ramp = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        freq = freq / rope["factor"] * ramp + freq * (1.0 - ramp)
        scale = rope["attention_factor"]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale, r


def _rotate(x, cos, sin, r):
    """``x`` ``[T, H, d]``: the first ``r`` dims rotated, the rest passed."""
    half = r // 2
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _attention(p, name, x, precision, *, heads, kv_heads, head_dim, window, rope, eps):
    T = x.shape[0]
    h = _rms(p, f"{name}/attn_norm", x, eps, precision)
    proj = lambda w: out(matmul(h, p[f"{name}/attn/{w}/kernel"], precision), precision)  # noqa: E731
    q = proj("q_proj").reshape(T, heads, head_dim)
    k = proj("k_proj").reshape(T, kv_heads, head_dim)
    v = proj("v_proj").reshape(T, kv_heads, head_dim)
    cos, sin, r = rope_tables(T, head_dim, rope)
    q, k = out(_rotate(q, cos, sin, r), precision), out(_rotate(k, cos, sin, r), precision)
    t = jnp.arange(T)
    keep = t[:, None] >= t[None, :]
    if window is not None:
        keep &= (t[:, None] - t[None, :]) < window

    def one_kv_head(q, k, v):
        """``q`` ``[T, group, d]`` against its own ``k``, ``v`` ``[T, d]``."""
        scores = jnp.einsum("tgd,sd->gts", lower(q, precision), lower(k, precision))
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        probs = out(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1), precision)
        return out(jnp.einsum("gts,sd->tgd", lower(probs, precision), lower(v, precision)),
                   precision)

    # query head j reads KV head j // group; one KV head's [group, T, T] scores at a time
    group = heads // kv_heads
    q = q.reshape(T, kv_heads, group, head_dim)
    mixed = jax.lax.map(lambda qkv: jax.checkpoint(one_kv_head)(*qkv),
                        (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    mixed = mixed.transpose(1, 0, 2, 3).reshape(T, heads, head_dim)
    gate = jax.nn.sigmoid(matmul(h, p[f"{name}/attn/g_proj/kernel"], precision))  # [T, heads]
    mixed = out(mixed * gate[:, :, None], precision).reshape(T, heads * head_dim)
    return out(x + matmul(mixed, p[f"{name}/attn/o_proj/kernel"], precision), precision)


def _swiglu(h, gate, up, down, precision):
    a = out(jax.nn.silu(matmul(h, gate, precision)) * matmul(h, up, precision), precision)
    return matmul(a, down, precision)


def routed_weights(scores, top_k, scaling):
    """``[T, E]``: the weight each token gives each expert, 0 where the
    expert is not among its ``top_k`` largest scores (ties to the lower id)."""
    top_s, top_e = jax.lax.top_k(scores, top_k)
    w = scaling * top_s / jnp.sum(top_s, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype) * w[..., None], -2)


def moe(p, name, h, precision, *, top_k, scaling, experts_held, shared=True):
    """The routed feed-forward on normed input ``h`` ``[T, E]``: the shared
    expert (once) plus the held experts' weighted outputs."""
    scores = jax.nn.sigmoid(h @ p[f"{name}/moe/router/kernel"])  # float32, not lowered
    w = routed_weights(scores, top_k, scaling)
    y = 0.0
    if shared:
        s = f"{name}/moe/shared"
        y = _swiglu(h, p[f"{s}/gate_proj/kernel"], p[f"{s}/up_proj/kernel"],
                    p[f"{s}/down_proj/kernel"], precision)
    e = f"{name}/moe/experts"
    held = jnp.asarray(list(experts_held))

    def one_expert(args):
        expert, gate, up, down = args
        return w[:, expert, None] * _swiglu(h, gate, up, down, precision)

    # one held expert at a time (``lax.map``: the loop compiles once), every token, masked
    return y + jnp.sum(jax.lax.map(one_expert, (
        held, p[f"{e}/gate_proj"], p[f"{e}/up_proj"], p[f"{e}/down_proj"])), 0)


def _layer(p, i, x, precision, c):
    name = f"params/layer_{i}"
    sliding = c["layer_types"][i] == "sliding_attention"
    x = _attention(
        p, name, x, precision, heads=c["num_attention_heads_per_layer"][i],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        window=c["sliding_window"] if sliding else None,
        rope=c["rope_parameters"]["sliding_attention" if sliding else "full_attention"],
        eps=c["rms_norm_eps"])
    h = _rms(p, f"{name}/mlp_norm", x, c["rms_norm_eps"], precision)
    if c["mlp_layer_types"][i] == "dense":
        m = f"{name}/mlp"
        y = _swiglu(h, p[f"{m}/gate_proj/kernel"], p[f"{m}/up_proj/kernel"],
                    p[f"{m}/down_proj/kernel"], precision)
    else:
        y = moe(p, name, h, precision, top_k=c["num_experts_per_tok"],
                scaling=c["moe_routed_scaling_factor"], experts_held=c["experts_held"])
    return out(x + out(y, precision), precision)


def loss(p, batch, precision="float32", **c):
    """One client's batch: ``input_ids``, ``lm_labels`` ``[B, T]``; ``c`` is
    the configuration's ``reference.loss_kwargs`` (the published keys the
    layers read, and ``experts_held``)."""
    def one_sequence(row):
        ids, labels = row
        x = out(p["params/embed/embedding"][ids], precision)
        for i in range(len(c["layer_types"])):
            x = jax.checkpoint(lambda x, i=i: _layer(p, i, x, precision, c))(x)
        h = _rms(p, "params/final_norm", x, c["rms_norm_eps"], precision)
        logits = out(matmul(h, p["params/lm_head/kernel"], precision), precision)
        return cross_entropy_sum(logits[:-1], labels[1:])

    total, count = jax.lax.map(one_sequence, (batch["input_ids"], batch["lm_labels"]))
    return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1.0)
