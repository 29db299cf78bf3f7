"""Operations SDAR-30B-A3B-Chat's block-diffusion training step requires, per
**token**: a token is two stream positions (its noised and its clean copy),
and both go through every layer. The backward is taken as twice the forward,
**recomputation not counted** (the program recomputes each block and the
head; that is its own cost), and **products under the mask not counted**: a
kernel that computes a tile and masks it is read against the pairs the mask
admits, not against the tile. Functions of the configuration's
``flops_kwargs`` alone.

Per stream position and layer, at 2 operations a weight: the attention's
four projections and the router; the routed experts at the *expected* held
assignments of a uniform router (``top_k * held / num_experts`` experts a
position; the program's product runs over a floor of 4.5 times that, rows of
weight 0 that are its own cost). Attention: a row of ``seq`` tokens has
``seq (seq + block_length)`` (query, key) pairs a head under the mask (a
noised query reads its own block and the clean blocks before it, a clean
query the clean blocks up to its own), ``seq + block_length`` a token, at
``4 head_dim`` operations a pair forward (the score and the value product).
The head over the vocabulary rows held, at the noised stream's ``seq``
positions: once a token (every position, not only the masked half: the
program does the same work whatever the noise masked, and so does this
count). Left out: the embedding (a gather), the norms, the rotary, the
softmax, the router's top-k and the sort, the noise and the stream's join
and split, the clip and the update.
"""

from __future__ import annotations


def attn_blockdiff_flops_per_token(*, layers, heads, head_dim, seq, block_length, **_):
    """What the ``attn_blockdiff`` scope has to compute, forward + backward:
    score and value products over the pairs the mask admits."""
    return 3.0 * layers * 4.0 * heads * head_dim * (seq + block_length)


def sdar_flops_per_token(*, layers, heads, kv_heads, head_dim, hidden, expert_width,
                         num_experts, experts_held, top_k, vocab_held, **kw):
    """Forward + backward operations a token (``n_params`` is not used: the
    routed experts are counted at their expected use, not whole)."""
    position = 2.0 * hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim)   # q, k, v, o
    position += 2.0 * hidden * num_experts                                       # router
    position += 2.0 * (top_k * experts_held / num_experts) * 3 * hidden * expert_width
    return (3.0 * (layers * 2 * position + 2.0 * hidden * vocab_held)
            + attn_blockdiff_flops_per_token(layers=layers, heads=heads, head_dim=head_dim,
                                             **kw))
