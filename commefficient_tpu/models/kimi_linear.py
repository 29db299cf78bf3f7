"""Kimi-Linear-48B-A3B-Instruct (moonshotai, ``model_type: kimi_linear``),
one chip's share of it, as presets of the decoder in ``models/laguna.py``:
27 pre-norm layers, three in four of them Kimi Delta Attention (a gated
delta rule with a decay per key channel, 32 heads of 128, a short
convolution of 4 taps on q, k and v; ``models/kda.py``) and every fourth,
and the last, latent attention without positions (32 heads, a 512-wide
latent, keys of 128 + 64, values of 128, no query compression;
``models/latent_attention.py``). The layer kinds come from
``linear_attn_config``, whose lists are 1-indexed. The feed-forward is
Laguna's: dense SwiGLU of 9,216 on the first layer, then a sigmoid router
over 256 experts whose 8 largest scores are renormalised and scaled by
``routed_scaling_factor``, one shared expert, every expert SwiGLU of 1,024.
Untied embedding and head; ``head_dim`` (72) is read by neither layer kind.

Forms the published config leaves open (``assumed`` in
``benchmark/configs/kimi_linear_48b_fedtext.json``): the recurrent state
starts at zero at a row's start and is not reset at ``<eos>`` inside a packed
row; no selection bias on the router; no bias on the gates' factors; no
auxiliary router loss; the public kernels' L2-norm epsilon.
"""

from __future__ import annotations

from commefficient_tpu.models.laguna import LagunaConfig, Rope

# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json
PUBLISHED = dict(
    first_k_dense_replace=1, head_dim=72, hidden_act="silu", hidden_size=2304,
    intermediate_size=9216, kv_lora_rank=512,
    linear_attn_config=dict(
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27), head_dim=128,
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26),
        num_heads=32, short_conv_kernel_size=4),
    mla_use_nope=True, model_max_length=1048576, model_type="kimi_linear",
    moe_intermediate_size=1024, moe_layer_freq=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_attention_heads=32, num_expert_group=1,
    num_experts=256, num_experts_per_token=8, num_hidden_layers=27, num_key_value_heads=32,
    num_nextn_predict_layers=0, num_shared_experts=1, q_lora_rank=None, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    routed_scaling_factor=2.446, tie_word_embeddings=False, topk_group=1,
    use_grouped_topk=True, v_head_dim=128, vocab_size=163840,
)


def layer_kinds(keys: dict, layers: int):
    """The first ``layers`` layers' kinds from ``linear_attn_config``'s
    1-indexed lists."""
    lac = keys["linear_attn_config"]
    kinds = []
    for i in range(1, layers + 1):
        if (i in lac["kda_layers"]) == (i in lac["full_attn_layers"]):
            raise ValueError(f"kimi_linear: layer {i} is in neither list or in both")
        kinds.append("kda_attention" if i in lac["kda_layers"] else "latent_attention")
    return tuple(kinds)


def from_published(keys: dict, *, layers: int, experts_held, vocab_held: int,
                   heads_held: int = 0, **kw) -> LagunaConfig:
    """The config as published, its first ``layers`` layers, holding
    ``experts_held`` of the routed experts, the first ``vocab_held`` rows of
    the vocabulary and, of every mixer, the first ``heads_held`` heads (0:
    all): a KDA head's state and a latent head's keys and values are its
    own, so a chip's heads give their part of the output projection and the
    other heads' part is left out, as the experts held elsewhere are."""
    if (keys["q_lora_rank"] is not None or not keys["mla_use_nope"]
            or keys["num_expert_group"] != 1 or keys["topk_group"] != 1
            or keys["moe_router_activation_func"] != "sigmoid" or not keys["moe_renormalize"]
            or keys["moe_layer_freq"] != 1 or keys["hidden_act"] != "silu"
            or keys["tie_word_embeddings"]):
        raise ValueError("kimi_linear: a layer pattern, router or attention form this decoder "
                         "does not build")
    lac = keys["linear_attn_config"]
    heads = heads_held or keys["num_attention_heads"]
    kda_heads = heads_held or lac["num_heads"]
    dense = min(keys["first_k_dense_replace"], layers)
    return LagunaConfig(
        vocab_size=keys["vocab_size"], hidden_size=keys["hidden_size"],
        intermediate_size=keys["intermediate_size"], head_dim=keys["head_dim"],
        num_key_value_heads=heads,
        num_attention_heads_per_layer=(heads,) * layers,
        layer_types=layer_kinds(keys, layers),
        mlp_layer_types=("dense",) * dense + ("sparse",) * (layers - dense),
        sliding_window=0, num_experts=keys["num_experts"],
        num_experts_per_tok=keys["num_experts_per_token"],
        moe_intermediate_size=keys["moe_intermediate_size"],
        shared_expert_intermediate_size=keys["num_shared_experts"] * keys["moe_intermediate_size"],
        moe_routed_scaling_factor=keys["routed_scaling_factor"],
        rope_full=Rope(), rope_sliding=Rope(), experts_held=tuple(experts_held),
        vocab_held=vocab_held, rms_norm_eps=keys["rms_norm_eps"],
        kda_heads=kda_heads, kda_head_dim=lac["head_dim"],
        kda_conv=lac["short_conv_kernel_size"], kv_lora_rank=keys["kv_lora_rank"],
        qk_nope_head_dim=keys["qk_nope_head_dim"], qk_rope_head_dim=keys["qk_rope_head_dim"],
        v_head_dim=keys["v_head_dim"], **kw)


def kimi_linear_48b(**kw) -> LagunaConfig:
    """One chip of 32 that share each layer: layers 1-5 of 27 (the dense
    layer, then KDA, KDA, latent, KDA: one whole period of the 3 : 1
    pattern), experts 0-7 of 256, rows 0-20,479 of the 163,840-row
    vocabulary, heads 0-7 of each mixer's 32 (attention four ways: with all
    32 a chip's D is 602.4M, and the benchmark's host copies of the flat
    vector outgrow the one-chip machine's 40 GiB); every width as published.
    D = 464,820,000. The head runs
    2,048 positions at a time; the expert product always runs over the first
    tier's 4.5 times a uniform router's held rows (Keye's floor: under the
    benchmark's random weights a client's tokens lump on few experts, and the
    seed would decide the product's size)."""
    return from_published(PUBLISHED, layers=5, experts_held=range(8), vocab_held=20480,
                          heads_held=8, head_chunk=2048, expert_row_tiers=(4.5, 8.5),
                          expert_rows_floored=True, **kw)


def kimi_linear_tiny(**kw) -> LagunaConfig:
    """The same code path at a size the CPU tests run: the published layer
    pattern, every width small, chunks of 16 positions."""
    keys = dict(
        PUBLISHED, vocab_size=256, hidden_size=64, intermediate_size=128, kv_lora_rank=16,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=16, num_experts_per_token=2, moe_intermediate_size=32,
        linear_attn_config=dict(PUBLISHED["linear_attn_config"], head_dim=16, num_heads=4))
    return from_published(keys, layers=5, experts_held=range(4), vocab_held=256, head_chunk=64,
                          kda_chunk=16, expert_row_tiers=(1.0, 2.0), expert_rows_floored=True,
                          **kw)


PRESETS = {"kimi_linear_48b": kimi_linear_48b, "kimi_linear_tiny": kimi_linear_tiny}
