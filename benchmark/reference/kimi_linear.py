"""Kimi-Linear-48B-A3B-Instruct as its published config and the Kimi Linear
report write it down, cut to one chip's share: pre-norm blocks

    x <- x + Mixer(RMSNorm(x));  x <- x + FFN(RMSNorm(x))

whose mixer is, by ``layer_types``, Kimi Delta Attention or latent attention
without positions, and whose feed-forward is dense SwiGLU on the first layer
and Laguna's routed layer elsewhere (``reference/laguna.py``: a sigmoid
router over all the published experts, the largest ``num_experts_per_tok``
renormalised and scaled, one shared expert, the held experts' outputs).

**KDA** on ``h`` ``[T, hidden]``, 32 heads of ``d`` = 128: ``q, k, v =
SiLU(conv4(h W_qkv))`` (a depthwise causal convolution of 4 taps, zeros
before the row's start), per head, ``q, k`` L2-normalised; ``g_t = -exp(A_log)
softplus(h W_fa W_fb + dt_bias)`` a channel; ``beta_t = sigmoid(h W_b)`` a
head; ``S_0 = 0`` and, **one position at a time**,

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)

(a ``lax.scan`` over positions inside a ``lax.scan`` over blocks of
``state_block`` positions, each block under ``jax.checkpoint``, so that the
gradient keeps one state a block; each step's products are float32 sums of
elementwise products, exact in float32 whatever the matrix unit's passes);
out ``= W_o concat_h(RMSNorm(o_t) w
sigmoid(h W_ga W_gb))``.

**Latent attention**: ``q = h W_q`` ``[H, 128 + 64]``; ``[c ; k_pe] = h
W_kva``, ``c <- RMSNorm(c)``; ``[k_nope ; v] = c W_kvb``; ``k = [k_nope ;
k_pe]`` with ``k_pe`` shared by every head; causal ``softmax(q . k /
sqrt(192)) v``, scores over blocks of ``query_block`` queries; then ``W_o``.

Loss: mean next-token negative log-likelihood over labels that are not -100.
Every product goes through ``ops.lower`` but the router's and the
recurrence's (the configuration keeps the state in float32; ``q``, ``k``,
``v`` are lowered where they enter it); activations through ``ops.out``.
Parameters arrive as a flat ``{path: array}`` dict. Nothing here imports the
system under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import _rms, _swiglu, moe
from benchmark.reference.ops import cross_entropy_sum, lower, matmul, out


def recurrence(q, k, v, g, beta, block):
    """``q``, ``k``, ``g`` ``[T, H, d_k]``, ``v`` ``[T, H, d_v]``, ``beta``
    ``[T, H]`` -> ``o`` ``[T, H, d_v]``: the rule one position at a time
    (``q`` already over ``sqrt(d_k)``)."""
    T, H, K = k.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]                          # [H, K, V]
        erased = jnp.sum(k_t[..., None] * S, axis=1)             # k_t^T S
        S = S + (b_t[:, None] * k_t)[..., None] * (v_t - erased)[:, None, :]
        return S, jnp.sum(q_t[..., None] * S, axis=1)            # S^T q_t

    n = -(-T // block)
    pad = n * block - T
    blocks = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(n, block, *a.shape[1:])
              for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
                        jnp.zeros((H, K, v.shape[-1]), jnp.float32), tuple(blocks))
    return o.reshape(n * block, H, -1)[:T]


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _kda(p, name, x, precision, c):
    T = x.shape[0]
    H, d, eps, a = c["kda_heads"], c["kda_head_dim"], c["rms_norm_eps"], f"{name}/attn"
    h = _rms(p, f"{name}/attn_norm", x, eps, precision)
    qkv = out(matmul(h, p[f"{a}/qkv_proj/kernel"], precision), precision)
    conv = p[f"{a}/conv/kernel"]                                    # [taps, 3 H d]
    taps = conv.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(conv[j] * padded[j:j + T] for j in range(taps)))
    q, k, v = (y.reshape(T, H, d) for y in jnp.split(qkv, 3, -1))
    q, k = _l2(q) / jnp.sqrt(jnp.float32(d)), _l2(k)
    low = matmul(h, p[f"{a}/gate_a_proj/kernel"], precision)         # [W_fa | W_ga | W_b]
    f = matmul(low[:, :d], p[f"{a}/f_b_proj/kernel"], precision).reshape(T, H, d)
    gate = matmul(low[:, d:2 * d], p[f"{a}/g_b_proj/kernel"], precision).reshape(T, H, d)
    decay = p[f"{a}/decay/kernel"]                                 # [A_log (H) | dt_bias (H d)]
    g = -jnp.exp(decay[:H])[:, None] * jax.nn.softplus(f + decay[H:].reshape(H, d))
    beta = jax.nn.sigmoid(low[:, 2 * d:])
    o = recurrence(lower(q, precision), lower(k, precision), lower(v, precision), g, beta,
                   c.get("state_block", 64))
    o = _rms(p, f"{a}/o_norm", o, eps, precision) * jax.nn.sigmoid(gate)
    return out(x + matmul(out(o, precision).reshape(T, H * d), p[f"{a}/o_proj/kernel"],
                          precision), precision)


def _latent(p, name, x, precision, c):
    T = x.shape[0]
    H, eps, a = c["num_attention_heads"], c["rms_norm_eps"], f"{name}/attn"
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
                     c["kv_lora_rank"])
    h = _rms(p, f"{name}/attn_norm", x, eps, precision)
    q = out(matmul(h, p[f"{a}/q_proj/kernel"], precision), precision).reshape(T, H, dn + dr)
    latent = out(matmul(h, p[f"{a}/kv_a_proj/kernel"], precision), precision)
    compressed = _rms(p, f"{a}/kv_a_norm", latent[:, :r], eps, precision)
    kv = out(matmul(compressed, p[f"{a}/kv_b_proj/kernel"], precision), precision)
    kv = kv.reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(latent[:, None, r:], (T, H, dr))], -1)
    v = kv[..., dn:]
    bq = min(c.get("query_block", 512), T)

    def query_block(args):
        at, q_b = args                                            # [bq], [bq, H, dn + dr]
        s = jnp.einsum("thd,shd->hts", lower(q_b, precision), lower(k, precision))
        s = s / jnp.sqrt(jnp.float32(dn + dr))
        keep = at[:, None] >= jnp.arange(T)[None, :]
        probs = out(jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), precision)
        o = jnp.einsum("hts,shd->thd", lower(probs, precision), lower(v, precision))
        return out(o, precision).reshape(bq, H * dv)

    n = T // bq
    mixed = jax.lax.map(jax.checkpoint(query_block),
                        (jnp.arange(T).reshape(n, bq), q.reshape(n, bq, H, dn + dr)))
    return out(x + matmul(mixed.reshape(T, H * dv), p[f"{a}/o_proj/kernel"], precision),
               precision)


def _layer(p, i, x, precision, c):
    name = f"params/layer_{i}"
    mixer = _kda if c["layer_types"][i] == "kda_attention" else _latent
    x = mixer(p, name, x, precision, c)
    h = _rms(p, f"{name}/mlp_norm", x, c["rms_norm_eps"], precision)
    if c["mlp_layer_types"][i] == "dense":
        m = f"{name}/mlp"
        y = _swiglu(h, p[f"{m}/gate_proj/kernel"], p[f"{m}/up_proj/kernel"],
                    p[f"{m}/down_proj/kernel"], precision)
    else:
        y = moe(p, name, h, precision, top_k=c["num_experts_per_tok"],
                scaling=c["routed_scaling_factor"], experts_held=c["experts_held"])
    return out(x + out(y, precision), precision)


def loss(p, batch, precision="float32", **c):
    """One client's batch: ``input_ids``, ``lm_labels`` ``[B, T]``; ``c`` is
    the configuration's ``reference.loss_kwargs`` (the published keys the
    layers read, the layer kinds, ``experts_held``, and how the reference is
    blocked)."""
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
