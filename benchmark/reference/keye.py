"""Keye-VL-2.0-30B-A3B's language model as ISSUE 33 section 1 writes it down,
cut to one chip's share: 48 blocks alike (the first ``num_hidden_layers`` run),

    x <- x + Attn(RMSNorm(x));  x <- x + MoE(RMSNorm(x))

**Attn** on ``h`` ``[T, hidden]``: ``q = h Wq`` ``[T, H, d]``, ``k = h Wk``,
``v = h Wv`` ``[T, KV, d]``; RMSNorm over each head of ``q`` and of ``k``
with a learned scale of ``d``; rotary positions on the whole head in the
sectioned M-RoPE form (``rope_tables``: frequency pair ``i`` reads the id
stream its section names; the text cell's three streams are all
``0..T-1``). The index: ``qI = h WqI`` ``[T, J, e]``, ``kI = h WkI``
``[T, e]``, ``w = h Ww`` ``[T, J]``, all from ``stop_gradient(h)`` (the
three matrices are the column blocks of one leaf, ``index_proj``
``[hidden, J e + e + J]``: a leaf of 16 columns would be padded eightfold
where the harness cuts the flat vector into leaves);
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` is every
``s <= t`` where ``t + 1 <= topk``, else the ``topk`` positions ``s <= t``
of largest ``I[t, s]``, ties to the lower ``s`` (``lax.top_k``). Head ``i``
attends over ``S_t`` to KV head ``i // (H / KV)`` with scores over
``sqrt(d)``; output ``concat_i(o) Wo``. ``S_t`` is a constant of the
backward pass: under the LM loss the index's leaf gets no gradient
(the round adds the weight decay's term).

**MoE**: ``p = softmax(h Wr)`` over all the published experts, the
``num_experts_per_tok`` largest (ties to the lower id) over their sum; the
held experts' ``W2(silu(W1 h) * W3 h)`` weighted and summed. No shared
expert, no scaling factor, no auxiliary loss. What experts held elsewhere
would add is left out, so with every id in ``experts_held`` this is the
uncut layer.

Head over the vocabulary rows held; loss: mean next-token negative
log-likelihood over labels that are not -100; positions and attention run
across packed documents.

Every product goes through ``ops.lower`` but the router's (the
configuration keeps it in float32); the index's score product is lowered
like the others unless ``index_precision`` says ``"float32"``. Scores live
for one sequence, one block of ``query_block`` queries and one KV head at a
time (``[H / KV, query_block, T]``; ``lax.map`` with ``jax.checkpoint``
around a layer and around a query block); a query block's selection is
made once, by ``lax.top_k`` on its ``[query_block, T]`` index scores, and
is kept for the backward pass as each query's threshold score and the last
position admitted at it (two numbers a query: the set is exactly the
``topk`` that ``lax.top_k`` chose). Parameters arrive as a flat
``{path: array}`` dict. Nothing here imports the system under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from benchmark.reference.laguna import _rms, _rotate, _swiglu, routed_weights
from benchmark.reference.ops import cross_entropy_sum, lower, matmul, out

SELECTION = "keye_reference_selection"


def rope_tables(position_ids, head_dim, theta, mrope_section):
    """``(cos, sin)`` ``[T, head_dim / 2]``: frequency pair ``i`` turns by
    ``position_ids[section(i), t] * theta^(-i / (head_dim / 2))``, the
    sections (temporal, height, width) taking ``mrope_section`` pairs each
    in that order. ``position_ids`` ``[3, T]``."""
    half = head_dim // 2
    assert sum(mrope_section) == half, (mrope_section, half)
    i = jnp.arange(half, dtype=jnp.float32)
    freq = jnp.float32(theta) ** (-i / half)
    section = jnp.repeat(jnp.arange(3), jnp.asarray(mrope_section), total_repeat_length=half)
    angle = position_ids.astype(jnp.float32)[section, :].T * freq[None, :]      # [T, half]
    return jnp.cos(angle), jnp.sin(angle)


def index_scores(qi, ki, w, precision):
    """``[t, T]``: ``I[t, s]`` of the queries ``qi`` ``[t, J, e]``, ``w``
    ``[t, J]`` against every key ``ki`` ``[T, e]``, one index head at a time."""
    ki = lower(ki, precision)

    def head(total, args):
        q, wj = args
        return total + wj[:, None] * jax.nn.relu(lower(q, precision) @ ki.T), None

    zero = jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32)
    return jax.lax.scan(head, zero, (qi.transpose(1, 0, 2), w.T))[0]


def select(scores, t, topk):
    """``(tau [n], cut [n])`` of ``lax.top_k``'s choice among the causal
    keys of queries at positions ``t`` ``[n]``: the smallest chosen score,
    and the last position chosen at that score. ``scores`` ``[n, T]``."""
    s = jnp.arange(scores.shape[1])
    causal = s[None, :] <= t[:, None]
    vals, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, scores.shape[1]))
    tau = vals[:, -1]
    cut = jnp.max(jnp.where(vals == tau[:, None], idx, -1), axis=-1)
    # t + 1 <= topk: lax.top_k ran out of causal keys and tau is -inf; all are taken
    return tau, jnp.where(t < topk, scores.shape[1], cut)


def chosen(scores, t, tau, cut):
    """``[n, T]`` bool: ``S_t`` rebuilt from its threshold and cut."""
    s = jnp.arange(scores.shape[1])[None, :]
    keep = (scores > tau[:, None]) | ((scores == tau[:, None]) & (s <= cut[:, None]))
    return keep & (s <= t[:, None])


def _attention(p, name, x, precision, c):
    T = x.shape[0]
    H, KV, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    J, e, topk = c["indexer_num_heads"], c["indexer_head_dim"], c["topk"]
    eps, a = c["rms_norm_eps"], f"{name}/attn"
    h = _rms(p, f"{name}/attn_norm", x, eps, precision)
    proj = lambda v, w: out(matmul(v, p[f"{a}/{w}/kernel"], precision), precision)  # noqa: E731
    q = _rms(p, f"{a}/q_norm", proj(h, "q_proj").reshape(T, H, d), eps, precision)
    k = _rms(p, f"{a}/k_norm", proj(h, "k_proj").reshape(T, KV, d), eps, precision)
    v = proj(h, "v_proj").reshape(T, KV, d)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (3, T))     # text: three equal streams
    cos, sin = rope_tables(positions, d, c["rope_theta"], c["mrope_section"])
    q, k = out(_rotate(q, cos, sin, d), precision), out(_rotate(k, cos, sin, d), precision)
    hi = jax.lax.stop_gradient(h)
    index_precision = "float32" if c.get("index_precision") == "float32" else precision
    index = proj(hi, "index_proj")             # one leaf: [WqI (J x e) | WkI (e) | Ww (J)]
    qi, ki, w = index[:, :J * e].reshape(T, J, e), index[:, J * e:J * e + e], index[:, J * e + e:]

    bq = min(c.get("query_block", 512), T)
    group = H // KV

    def query_block(args):
        t, q_b, qi_b, w_b = args                      # [bq], [bq, H, d], [bq, J, e], [bq, J]
        scores = index_scores(qi_b, ki, w_b, index_precision)
        tau, cut = (checkpoint_name(r, SELECTION) for r in select(scores, t, topk))
        keep = chosen(scores, t, tau, cut)

        def one_kv_head(q_g, k_h, v_h):
            """``q_g`` ``[bq, group, d]`` against its own ``k_h``, ``v_h`` ``[T, d]``."""
            s = jnp.einsum("tgd,sd->gts", lower(q_g, precision), lower(k_h, precision))
            s = s / jnp.sqrt(jnp.float32(d))
            probs = out(jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1), precision)
            return out(jnp.einsum("gts,sd->tgd", lower(probs, precision), lower(v_h, precision)),
                       precision)

        heads = jax.lax.map(lambda a: jax.checkpoint(one_kv_head)(*a), (
            q_b.reshape(bq, KV, group, d).transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
            v.transpose(1, 0, 2)))
        return heads.transpose(1, 0, 2, 3).reshape(bq, H * d)

    blocked = jax.checkpoint(
        query_block, policy=jax.checkpoint_policies.save_only_these_names(SELECTION))
    n = T // bq
    mixed = jax.lax.map(blocked, (
        jnp.arange(T).reshape(n, bq), q.reshape(n, bq, H, d), qi.reshape(n, bq, J, e),
        w.reshape(n, bq, J))).reshape(T, H * d)
    return out(x + matmul(mixed, p[f"{a}/o_proj/kernel"], precision), precision)


def moe(p, name, h, precision, *, top_k, experts_held):
    """The routed feed-forward on normed input ``h`` ``[T, E]``: the held
    experts' outputs under the renormalised softmax weights."""
    probs = jax.nn.softmax(h @ p[f"{name}/moe/router/kernel"], axis=-1)   # float32, not lowered
    w = routed_weights(probs, top_k, 1.0)
    e = f"{name}/moe/experts"
    held = jnp.asarray(list(experts_held))

    def one_expert(args):
        expert, gate, up, down = args
        return w[:, expert, None] * _swiglu(h, gate, up, down, precision)

    # one held expert at a time (``lax.map``: the loop compiles once), every token, masked
    return jnp.sum(jax.lax.map(one_expert, (
        held, p[f"{e}/gate_proj"], p[f"{e}/up_proj"], p[f"{e}/down_proj"])), 0)


def _layer(p, i, x, precision, c):
    name = f"params/layer_{i}"
    x = _attention(p, name, x, precision, c)
    h = _rms(p, f"{name}/mlp_norm", x, c["rms_norm_eps"], precision)
    y = moe(p, name, h, precision, top_k=c["num_experts_per_tok"],
            experts_held=c["experts_held"])
    return out(x + out(y, precision), precision)


def loss(p, batch, precision="float32", **c):
    """One client's batch: ``input_ids``, ``lm_labels`` ``[B, T]``; ``c`` is
    the configuration's ``reference.loss_kwargs`` (the published keys the
    layers read, ``experts_held``, and how the reference is blocked)."""
    keep = jax.checkpoint_policies.save_only_these_names(SELECTION)

    def one_sequence(row):
        ids, labels = row
        x = out(p["params/embed/embedding"][ids], precision)
        for i in range(c["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, i=i: _layer(p, i, x, precision, c), policy=keep)(x)
        h = _rms(p, "params/final_norm", x, c["rms_norm_eps"], precision)
        logits = out(matmul(h, p["params/lm_head/kernel"], precision), precision)
        return cross_entropy_sum(logits[:-1], labels[1:])

    total, count = jax.lax.map(one_sequence, (batch["input_ids"], batch["lm_labels"]))
    return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1.0)
