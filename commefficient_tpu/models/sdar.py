"""SDAR-30B-A3B-Chat (JetLM, ``model_type: sdar_moe``), one chip's share of
it, as presets of the decoder in ``models/laguna.py``: 48 layers alike of
grouped-query attention (32 query heads over 4 KV heads of 128, a per-head
RMSNorm on queries and keys, rotary positions on the whole head at
``rope_theta`` 1e6, no output gate) and 128 routed experts of width 768
under a softmax router whose 8 largest weights are renormalised; no shared
expert, untied embedding and head: Qwen3-MoE's block, as Keye's is.

What is SDAR's own is how it is trained and what its attention may read:
block diffusion (BD3-LMs, arXiv:2503.09573; SDAR, arXiv:2510.06303). Every
row goes through the decoder twice at once, a noised copy and a clean one,
under ``block_diffusion_attention``; the loss is the masked positions'
negative log-likelihood of their own clean token over the block's ``t``
(``models/losses.block_diffusion_lm_loss``), and the noise rides the
round's feed (``data/fedtext.BlockNoise``). Generation by denoising a block
at a time is not here (``models/generate.py`` yields one token a step).

Forms the published config leaves open (``assumed`` in
``benchmark/configs/sdar_30b_a3b_fedtext.json``): the block length (4, the
family's default) and the noise schedule (linear: ``t ~ U[1e-3, 1]`` a
block, each token masked with probability ``t``, weight ``1 / t``), which
the catalog row lists as not given; no shift between position and target;
no next-token term on the clean stream; the loss over the labelled
positions; ``[MASK]`` as the last id but one of the vocabulary slice held;
the QK norm; the router as Keye's; no auxiliary router loss.
"""

from __future__ import annotations

from commefficient_tpu.models.laguna import LagunaConfig, Rope

# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128, hidden_act="silu",
    hidden_size=2048, intermediate_size=6144, max_position_embeddings=32768,
    max_window_layers=48, mlp_only_layers=(), model_type="sdar_moe",
    moe_intermediate_size=768, norm_topk_prob=True, num_attention_heads=32, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4, rms_norm_eps=1e-6,
    rope_scaling=None, rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=151936,
)
BLOCK_LENGTH = 4   # not in the published keys: the family's default


def from_published(keys: dict, *, layers: int, experts_held, vocab_held: int,
                   block_length: int = BLOCK_LENGTH, **kw) -> LagunaConfig:
    """The config as published, its first ``layers`` layers (all alike),
    holding ``experts_held`` of the routed experts and the first
    ``vocab_held`` rows of the vocabulary: the last of them is ``<eos>``
    (``data/fedtext.py``) and the one before it ``[MASK]``."""
    if (keys["mlp_only_layers"] or keys["decoder_sparse_step"] != 1 or not keys["norm_topk_prob"]
            or keys["rope_scaling"] or keys["use_sliding_window"] or keys["attention_bias"]
            or keys["tie_word_embeddings"] or keys["hidden_act"] != "silu"):
        raise ValueError("sdar: a layer pattern, router or attention form this decoder "
                         "does not build")
    rope = Rope(rope_theta=float(keys["rope_theta"]))
    return LagunaConfig(
        vocab_size=keys["vocab_size"], hidden_size=keys["hidden_size"],
        intermediate_size=keys["intermediate_size"], head_dim=keys["head_dim"],
        num_key_value_heads=keys["num_key_value_heads"],
        num_attention_heads_per_layer=(keys["num_attention_heads"],) * layers,
        layer_types=("block_diffusion_attention",) * layers,
        mlp_layer_types=("sparse",) * layers, sliding_window=0,
        num_experts=keys["num_experts"], num_experts_per_tok=keys["num_experts_per_tok"],
        moe_intermediate_size=keys["moe_intermediate_size"],
        shared_expert_intermediate_size=0, moe_routed_scaling_factor=1.0,
        rope_full=rope, rope_sliding=rope, experts_held=tuple(experts_held),
        vocab_held=vocab_held, rms_norm_eps=keys["rms_norm_eps"],
        qk_norm=True, output_gate=False, router="softmax_renormalised",
        block_length=block_length, mask_token=vocab_held - 2, **kw)


def sdar_30b_a3b(**kw) -> LagunaConfig:
    """One chip of 16 that share each layer: layers 0-3 of 48, experts 0-7
    of 128, rows 0-18,991 of the 151,936-row vocabulary; every width as
    published. D = 305.4M. A client's row of 8,192 tokens is 16,384 stream
    positions in every layer, Keye's number, so the head's chunk and the
    expert product's tile are Keye's; the tiers and the floor are set from
    ``scripts/keye_probe.py --only loads --model sdar_30b_a3b`` (PERF.md
    section 4): the product always runs over the first tier's rows, so a
    round's time is the same whatever the weights' seed sends."""
    return from_published(PUBLISHED, layers=4, experts_held=range(8), vocab_held=18992,
                          head_chunk=2048, expert_tiling=(512, 1024, 1024),
                          expert_row_tiers=(4.5, 8.5), expert_rows_floored=True, **kw)


def sdar_tiny(**kw) -> LagunaConfig:
    """The same code path at a size the CPU tests run: every width small."""
    keys = dict(
        PUBLISHED, vocab_size=256, hidden_size=64, intermediate_size=128, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32)
    return from_published(keys, layers=2, experts_held=range(4), vocab_held=256, head_chunk=64,
                          expert_row_tiers=(1.0, 2.0), expert_rows_floored=True, **kw)


PRESETS = {"sdar_30b_a3b": sdar_30b_a3b, "sdar_tiny": sdar_tiny}
