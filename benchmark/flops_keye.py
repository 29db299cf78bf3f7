"""Operations the Keye-VL-2.0 decoder's forward and backward passes require,
per token position: the backward taken as twice the forward where a product
has one, **recomputation not counted** (the program recomputes each block,
and the mask inside every attention kernel; that is its own cost), and
**products under the mask not counted**: a kernel that computes a tile and
masks it is read against the keys the query attends to, not against the
tile. Functions of the configuration's ``flops_kwargs`` alone.

Per position and layer, at 2 operations a weight: the attention's four
projections and the router, forward + backward; the routed experts at the
*expected* held assignments of a uniform router (``top_k * held /
num_experts`` experts a token); the index's three projections and its scores
over the ``t + 1`` causal keys **forward only** (the index takes no
cotangent: ISSUE 33's planning figure put its projections under the
backward too, 2.9 % more in all); the attention's score and value products
over the ``min(t + 1, topk)`` keys attended to, forward + backward; means
over a row of ``seq`` positions. The head over the vocabulary rows held.
The embedding is a gather and counts nothing.
"""

from __future__ import annotations


def _mean_keys(seq, topk=None):
    """Mean over ``t < seq`` of ``min(t + 1, topk)`` (``t + 1`` with no ``topk``)."""
    if topk is None or topk >= seq:
        return (seq + 1) / 2
    return (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq


def attn_sparse_flops_per_token(*, layers, heads, head_dim, seq, index_topk, **_):
    """What the ``attn_sparse`` scope has to compute, forward + backward:
    score and value products over the keys attended to."""
    return 3.0 * layers * 4.0 * heads * head_dim * _mean_keys(seq, index_topk)


def attn_index_flops_per_token(*, layers, hidden, index_heads, index_head_dim, seq, **_):
    """What the ``attn_index`` scope has to compute, forward once: the three
    index projections and the scores over the causal keys."""
    width = index_heads * index_head_dim + index_head_dim + index_heads
    return layers * (2.0 * hidden * width
                     + 2.0 * index_heads * index_head_dim * _mean_keys(seq))


def keye_flops_per_token(*, layers, heads, kv_heads, head_dim, hidden, expert_width,
                         num_experts, experts_held, top_k, vocab_held, **kw):
    """Forward + backward operations a token position (``n_params`` is not
    used: the routed experts are counted at their expected use, not whole)."""
    layer = 2.0 * hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim)   # q, k, v, o
    layer += 2.0 * hidden * num_experts                                       # router
    layer += 2.0 * (top_k * experts_held / num_experts) * 3 * hidden * expert_width
    common = dict(layers=layers, heads=heads, head_dim=head_dim, hidden=hidden, **kw)
    return (3.0 * (layers * layer + 2.0 * hidden * vocab_held)
            + attn_sparse_flops_per_token(**common) + attn_index_flops_per_token(**common))
