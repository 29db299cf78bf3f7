"""Operations Kimi-Linear's forward and backward passes require, per token
position: the backward taken as twice the forward, **recomputation not
counted** (the program recomputes each block and the head in its backward
pass; that is its own cost). Functions of the configuration's
``flops_kwargs`` alone, so an implementation that pads, recomputes or chunks
otherwise is read against the same yardstick.

Per position and layer, at 2 operations a weight: the mixer's projections
(KDA: ``q, k, v``, the gates' two factors each and ``W_b``, ``W_o``; latent
attention: ``W_q``, ``W_kva``, ``W_kvb``, ``W_o``), the dense feed-forward or
the router and the shared expert, and the routed experts at the *expected*
held assignments of a uniform router (``top_k * held / num_experts`` experts
a token; the program's product runs over a floor of 4.5 times that, rows of
weight 0 that are its own cost). Latent attention: the score and value
products over the ``t + 1`` positions a causal query admits, averaged over a
row of ``seq``, at ``2 (d_nope + d_rope) + 2 d_v`` a pair and head. KDA: the
products of the chunked form at ``chunk`` positions, whatever chunk the
program uses (``attn_kda_flops_per_token``). The head over the vocabulary
rows held. Left out: the embedding (a gather), the norms, the convolutions,
the gates' nonlinearities, the softmax, the router's top-k and the sort.
"""

from __future__ import annotations


def kda_rule_per_position(*, kda_head_dim, chunk, **_):
    """Forward operations a position and head of the chunked rule: of a
    chunk of ``C`` positions, the key pairs ``A`` (``C (C - 1) / 2`` pairs),
    the query pairs ``P`` (``C (C + 1) / 2``), the unit lower-triangular
    solve over ``[K~ | V]``, ``P W`` and ``P U~``, each pair ``2 d`` a
    column; then ``U~ - W S``, ``Q' S`` and ``K^T U``, ``2 d_k d_v`` each."""
    C, d = chunk, kda_head_dim
    per_chunk = (C * (C - 1) * d            # A
                 + C * (C + 1) * d          # P
                 + C * (C - 1) * 2 * d      # the solve, K~ and V
                 + C * (C + 1) * 2 * d)     # P W and P U~
    return per_chunk / C + 3 * 2 * d * d


def attn_kda_flops_per_token(*, layer_types, kda_heads, **kw):
    """What the ``attn_kda`` scope has to compute, forward + backward."""
    kda = sum(1 for t in layer_types if t == "kda_attention")
    return 3.0 * kda * kda_heads * kda_rule_per_position(**kw)


def attn_latent_flops_per_token(*, layer_types, heads, qk_nope_head_dim, qk_rope_head_dim,
                                v_head_dim, seq, **_):
    """What the ``attn_latent`` scope has to compute, forward + backward:
    score and value products over the causal pairs."""
    latent = sum(1 for t in layer_types if t == "latent_attention")
    pair = 2.0 * (qk_nope_head_dim + qk_rope_head_dim) + 2.0 * v_head_dim
    return 3.0 * latent * heads * pair * (seq + 1) / 2


def kimi_flops_per_token(*, layer_types, mlp_layer_types, hidden, heads, kda_heads, kda_head_dim,
                         kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                         dense_width, expert_width, shared_width, num_experts, experts_held, top_k,
                         vocab_held, **kw):
    """Forward + backward operations a token position (``n_params`` is not
    used: the routed experts are counted at their expected use, not whole)."""
    E, d, D = hidden, kda_head_dim, kda_heads * kda_head_dim
    kda = E * 3 * D + E * (2 * d + kda_heads) + 2 * d * D + D * E
    latent = (E * heads * (qk_nope_head_dim + qk_rope_head_dim) + E * (kv_lora_rank + qk_rope_head_dim)
              + kv_lora_rank * heads * (qk_nope_head_dim + v_head_dim) + heads * v_head_dim * E)
    fwd = 2.0 * hidden * vocab_held
    for kind, mlp in zip(layer_types, mlp_layer_types):
        fwd += 2.0 * (kda if kind == "kda_attention" else latent)
        fwd += 2.0 * 3 * E * dense_width if mlp == "dense" else (
            2.0 * E * num_experts + 2.0 * 3 * E * shared_width
            + 2.0 * (top_k * experts_held / num_experts) * 3 * E * expert_width)
    common = dict(layer_types=layer_types, heads=heads, kda_heads=kda_heads,
                  kda_head_dim=kda_head_dim, qk_nope_head_dim=qk_nope_head_dim,
                  qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim, **kw)
    return 3.0 * fwd + attn_kda_flops_per_token(**common) + attn_latent_flops_per_token(**common)
