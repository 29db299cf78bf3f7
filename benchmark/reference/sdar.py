"""SDAR-30B-A3B-Chat under block-diffusion training as ISSUE 35 writes it
down, cut to one chip's share: 48 blocks alike (the first
``num_hidden_layers`` run),

    x <- x + Attn(RMSNorm(x));  x <- x + MoE(RMSNorm(x))

over a stream of ``2 T`` positions a row: ``z = [x_t ; x_0]``, the noised
copy of the row's ids (``mask_token`` where ``batch["noise_mask"]`` is set)
then the clean one, both at positions ``0..T-1``.

**Attn** on ``h`` ``[2T, hidden]``: ``q = h Wq`` ``[2T, H, d]``, ``k = h
Wk``, ``v = h Wv`` ``[2T, KV, d]``; RMSNorm over each head of ``q`` and of
``k`` with a learned scale of ``d``; rotate-half rotary on the whole head at
``rope_theta``, position ``i mod T`` for stream position ``i``. With
``b(i) = (i mod T) // block_length`` a query may read a key exactly when

    noised i, noised j:  b(i) == b(j)
    noised i, clean j:   b(j) <  b(i)
    clean i,  noised j:  never
    clean i,  clean j:   b(j) <= b(i)

(``allowed``: four explicit comparisons of indices). Head ``i`` attends to
KV head ``i // (H / KV)`` with scores over ``sqrt(d)``; output
``concat_i(o) Wo``.

**MoE**: Keye's (``reference/keye.py``): a float32 softmax over all the
published experts, the ``num_experts_per_tok`` largest over their sum, the
held experts' SwiGLU weighted and summed; what experts held elsewhere would
add is left out.

Loss of a client's batch: ``sum_i m_i / t_i * (-log softmax(RMSNorm(h_i)
W_head)[x_0[i]]) / max(#(labels != -100), 1)`` over the noised stream's
positions ``i < T`` of every row, ``m = batch["noise_mask"] and labels !=
-100`` (a position without a label is neither noised nor counted, whatever
the batch's mask says of it), ``t = batch["noise_t"]``: no shift between
position and target, nothing from the clean stream's last layer.

Every product goes through ``ops.lower`` but the router's; activations
through ``ops.out``. Scores live for one sequence and one block of
``query_block`` queries at a time (``[H, query_block, 2T]``; ``lax.map``
with ``jax.checkpoint`` around a layer and around a query block).
Parameters arrive as a flat ``{path: array}`` dict. Nothing here imports the
system under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.keye import moe
from benchmark.reference.laguna import _rms, _rotate
from benchmark.reference.ops import IGNORE, lower, matmul, out


def allowed(q_pos, T, block_length):
    """``[n, 2T]`` bool: the keys of the ``[x_t ; x_0]`` stream that the
    queries at stream positions ``q_pos`` ``[n]`` may read."""
    k_pos = jnp.arange(2 * T)[None, :]
    q_pos = q_pos[:, None]
    q_clean, k_clean = q_pos >= T, k_pos >= T
    b_q, b_k = (q_pos % T) // block_length, (k_pos % T) // block_length
    noised_noised = ~q_clean & ~k_clean & (b_q == b_k)
    noised_clean = ~q_clean & k_clean & (b_k < b_q)
    clean_clean = q_clean & k_clean & (b_k <= b_q)
    return noised_noised | noised_clean | clean_clean


def rope_tables(positions, head_dim, theta):
    """``(cos, sin)`` ``[n, head_dim / 2]`` at ``positions`` ``[n]``."""
    half = head_dim // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _attention(p, name, x, precision, c):
    S = x.shape[0]                                   # 2 T stream positions
    T = S // 2
    H, KV, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, a = c["rms_norm_eps"], f"{name}/attn"
    h = _rms(p, f"{name}/attn_norm", x, eps, precision)
    proj = lambda w: out(matmul(h, p[f"{a}/{w}/kernel"], precision), precision)  # noqa: E731
    q = _rms(p, f"{a}/q_norm", proj("q_proj").reshape(S, H, d), eps, precision)
    k = _rms(p, f"{a}/k_norm", proj("k_proj").reshape(S, KV, d), eps, precision)
    v = proj("v_proj").reshape(S, KV, d)
    cos, sin = rope_tables(jnp.arange(S) % T, d, c["rope_theta"])
    q, k = out(_rotate(q, cos, sin, d), precision), out(_rotate(k, cos, sin, d), precision)

    bq = min(c.get("query_block", 512), S)
    group = H // KV

    def query_block(args):
        at, q_b = args                                # [bq], [bq, H, d]
        keep = allowed(at, T, c["block_length"])      # [bq, 2T]
        s = jnp.einsum("tkgd,skd->kgts", lower(q_b.reshape(bq, KV, group, d), precision),
                       lower(k, precision)) / jnp.sqrt(jnp.float32(d))
        probs = out(jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1),
                    precision)
        o = out(jnp.einsum("kgts,skd->tkgd", lower(probs, precision), lower(v, precision)),
                precision)
        return o.reshape(bq, H * d)

    n = S // bq
    mixed = jax.lax.map(jax.checkpoint(query_block),
                        (jnp.arange(S).reshape(n, bq), q.reshape(n, bq, H, d))).reshape(S, H * d)
    return out(x + matmul(mixed, p[f"{a}/o_proj/kernel"], precision), precision)


def _layer(p, i, x, precision, c):
    name = f"params/layer_{i}"
    x = _attention(p, name, x, precision, c)
    h = _rms(p, f"{name}/mlp_norm", x, c["rms_norm_eps"], precision)
    y = moe(p, name, h, precision, top_k=c["num_experts_per_tok"],
            experts_held=c["experts_held"])
    return out(x + out(y, precision), precision)


def loss(p, batch, precision="float32", **c):
    """One client's batch: ``input_ids``, ``lm_labels`` ``[B, T]`` and the
    round's noise ``noise_mask`` (bool), ``noise_t`` (float32) ``[B, T]``;
    ``c`` is the configuration's ``reference.loss_kwargs`` (the published keys
    the layers read, ``block_length``, ``mask_token``, ``experts_held``, and
    how the reference is blocked)."""
    def one_sequence(row):
        ids, labels, masked, t = row
        masked = masked & (labels != IGNORE)
        T = ids.shape[0]
        stream = jnp.concatenate([jnp.where(masked, c["mask_token"], ids), ids])
        x = out(p["params/embed/embedding"][stream], precision)
        for i in range(c["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, i=i: _layer(p, i, x, precision, c))(x)
        h = _rms(p, "params/final_norm", x[:T], c["rms_norm_eps"], precision)
        logits = out(matmul(h, p["params/lm_head/kernel"], precision), precision)
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, ids[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(masked, nll / t, 0.0))

    total = jax.lax.map(one_sequence, (batch["input_ids"], batch["lm_labels"],
                                       batch["noise_mask"], batch["noise_t"]))
    labelled = jnp.sum((batch["lm_labels"] != IGNORE).astype(jnp.float32))
    return jnp.sum(total) / jnp.maximum(labelled, 1.0)
