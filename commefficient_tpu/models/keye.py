"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, ``model_type: KeyeVL2``),
one chip's share of it, as presets of the decoder in ``models/laguna.py``:
48 layers alike of grouped-query attention (32 query heads over 4 KV heads of
128, a per-head RMSNorm on queries and keys, rotary positions on the whole
head at ``rope_theta`` 1e7, no output gate) over the ``sa_config.topk`` keys
a learned index chooses for each query (16 index heads of width 64 over one
shared key head), and 128 routed experts of width 768 under a softmax
router whose 8 largest weights are renormalised; no shared expert, untied
embedding and head.

Text only: with one position stream the three sections of M-RoPE
(``mrope_section`` [16, 24, 24]) read the same ids and are the plain rotary
(``benchmark/reference/keye.py`` has the sectioned form, and a test holds
the two equal). The vision tower is not here (its sizes are not in the
published keys this repository carries).

Forms the published config leaves open (``assumed`` in
``benchmark/configs/keye_vl2_30b_fedtext.json``): the QK norm; the index as
DeepSeek-V3.2-Exp's report prints it (projections from the normed block
input, ReLU, a weighted sum over the index heads; no rotary, norm or further
scale inside it); ``topk`` counts tokens and the chunk sizes are an
implementation's tile; no index loss, so the index's leaf takes the
weight decay's term alone; no auxiliary router loss.
"""

from __future__ import annotations

from commefficient_tpu.models.laguna import LagunaConfig, Rope

# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
PUBLISHED = dict(
    vocab_size=151936, hidden_size=2048, intermediate_size=6144, num_hidden_layers=48,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    max_position_embeddings=262144, rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling=dict(mrope_section=(16, 24, 24), rope_type="default", type="default"),
    num_experts=128, num_local_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=(), max_window_layers=48,
    sa_config=dict(indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
                   kv_chunk_size=512, q_chunk_size=512, topk=2048),
)


def from_published(keys: dict, *, layers: int, experts_held, vocab_held: int,
                   **kw) -> LagunaConfig:
    """The config as published, its first ``layers`` layers (all alike),
    holding ``experts_held`` of the routed experts and the first
    ``vocab_held`` rows of the vocabulary."""
    sa = keys["sa_config"]
    if (keys["mlp_only_layers"] or keys["decoder_sparse_step"] != 1
            or not keys["norm_topk_prob"] or sa["indexer_num_kv_heads"] != 1):
        raise ValueError("keye: a layer pattern or router form this decoder does not build")
    rope = Rope(rope_theta=float(keys["rope_theta"]))
    return LagunaConfig(
        vocab_size=keys["vocab_size"], hidden_size=keys["hidden_size"],
        intermediate_size=keys["intermediate_size"], head_dim=keys["head_dim"],
        num_key_value_heads=keys["num_key_value_heads"],
        num_attention_heads_per_layer=(keys["num_attention_heads"],) * layers,
        layer_types=("indexed_attention",) * layers, mlp_layer_types=("sparse",) * layers,
        sliding_window=0, num_experts=keys["num_experts"],
        num_experts_per_tok=keys["num_experts_per_tok"],
        moe_intermediate_size=keys["moe_intermediate_size"],
        shared_expert_intermediate_size=0, moe_routed_scaling_factor=1.0,
        rope_full=rope, rope_sliding=rope, experts_held=tuple(experts_held),
        vocab_held=vocab_held, rms_norm_eps=keys["rms_norm_eps"],
        qk_norm=True, output_gate=False, router="softmax_renormalised",
        index_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], **kw)


def keye_vl2(**kw) -> LagunaConfig:
    """One chip of 16 that share each layer: layers 0-3 of 48, experts 0-7
    of 128, rows 0-18,991 of the 151,936-row vocabulary; every width as
    published. D = 314.4M. The head runs 2,048 positions at a time: a row of
    16,384 positions' logits over 18,992 ids is 1.2 GB in float32, a client.

    The expert layer (``scripts/keye_probe.py --only loads,experts``): under
    random weights the tokens whose experts live on other chips get nothing
    from the layer, attention's average over 2,048 keys is most of what is
    left of them, and from layer 1 on a client's tokens nearly all choose the
    same 8 experts: a layer's held rows come in lumps of two times the
    expected 8,192 (0, 2, 4 times; 0.0-4.07 read over 16 seeds), and the
    weights' seed decides which. So the product always runs over the first
    tier's 4.5 x 8,192 rows (two lumps and what spills beside them), a second
    tier holds four lumps, and the tile is the probe's for 1,024 rows and
    more an expert: a round's time is the same whatever the seed sends."""
    return from_published(PUBLISHED, layers=4, experts_held=range(8), vocab_held=18992,
                          head_chunk=2048, expert_tiling=(512, 1024, 1024),
                          expert_row_tiers=(4.5, 8.5), expert_rows_floored=True, **kw)


def keye_tiny(**kw) -> LagunaConfig:
    """The same code path at a size the CPU tests run: every width small,
    ``topk`` 32 so that the selection binds on three rows in four at T = 128."""
    keys = dict(
        PUBLISHED, vocab_size=256, hidden_size=64, intermediate_size=128, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32,
        sa_config=dict(PUBLISHED["sa_config"], indexer_head_dim=8, indexer_num_heads=4, topk=32))
    return from_published(keys, layers=2, experts_held=range(4), vocab_held=256, head_chunk=64,
                          expert_row_tiers=(1.0, 2.0), expert_rows_floored=True, **kw)


PRESETS = {"keye_vl2": keye_vl2, "keye_tiny": keye_tiny}
