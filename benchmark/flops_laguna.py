"""Operations the Laguna decoder's forward and backward passes require, per
token position: the backward taken as twice the forward, **recomputation not
counted** (the program recomputes each block in its backward pass; that is
its own cost). Functions of the configuration's ``flops_kwargs`` alone, so an
implementation that pads, recomputes or skips is read against the same
yardstick.

Per position and layer: the products of the attention projections, the
output gate, the router, the shared expert and the dense feed-forward at 2
operations a weight; the routed experts at the *expected* held assignments
of a uniform router (``top_k * held / num_experts`` experts a token); the
score and value products over the positions the mask admits (``t + 1`` on a
full layer, ``min(t + 1, window)`` on a sliding one, averaged over a row of
``seq`` positions). The head over the vocabulary rows held. The embedding
is a gather and counts nothing.
"""

from __future__ import annotations


def _admitted(seq, window=None):
    """Mean over ``t < seq`` of the positions position ``t`` attends to."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def attention_products_per_token(*, layer_types, heads_per_layer, head_dim, seq, window,
                                 kind, **_):
    """Forward score + value operations a token, over the layers of ``kind``."""
    return sum(4.0 * h * head_dim * _admitted(seq, window if kind == "sliding_attention" else None)
               for t, h in zip(layer_types, heads_per_layer) if t == kind)


def experts_per_token(*, mlp_layer_types, hidden, expert_width, top_k, experts_held,
                      num_experts, **_):
    """Forward operations a token of the held routed experts' three products."""
    sparse = sum(1 for t in mlp_layer_types if t == "sparse")
    return 2.0 * sparse * (top_k * experts_held / num_experts) * 3 * hidden * expert_width


def laguna_flops_per_token(*, layer_types, mlp_layer_types, heads_per_layer, kv_heads, head_dim,
                           hidden, dense_width, expert_width, shared_width, num_experts,
                           vocab_held, **kw):
    """Forward + backward operations a token position (``n_params`` is not
    used: the routed experts are counted at their expected use, not whole)."""
    fwd = 2.0 * hidden * vocab_held
    for mlp, heads in zip(mlp_layer_types, heads_per_layer):
        fwd += 2.0 * hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim + heads)
        fwd += 2.0 * 3 * hidden * dense_width if mlp == "dense" else (
            2.0 * hidden * num_experts + 2.0 * 3 * hidden * shared_width)
    common = dict(layer_types=layer_types, mlp_layer_types=mlp_layer_types,
                  heads_per_layer=heads_per_layer, head_dim=head_dim, hidden=hidden,
                  expert_width=expert_width, num_experts=num_experts, **kw)
    fwd += experts_per_token(**common)
    fwd += attention_products_per_token(kind="full_attention", **common)
    fwd += attention_products_per_token(kind="sliding_attention", **common)
    return 3.0 * fwd


def attn_window_flops_per_token(**kw):
    """What the ``attn_window`` scope has to compute, forward + backward."""
    return 3.0 * attention_products_per_token(kind="sliding_attention", **kw)
