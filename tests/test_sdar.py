"""models/sdar.py at ``sdar_tiny`` on the CPU, in float32, held to the plain
reference (``benchmark/reference/sdar.py``, which imports nothing of the
program): the mask the kernels apply against the four rules' dense mask;
attention forward and its three gradients under it; no ``[2T, 2T]``
operand; the clean stream against the same decoder run on the clean row
alone; loss and every gradient leaf; the loss's weights and counters; the
expert shares of the whole layer; the presets against the published keys,
the cut's D and the required operations. Since PR 36 also what a block's
``remat`` keeps of the library's attention kernel, for this decoder and for
``laguna_tiny`` (one parametrised test for both; Keye's kernel is
``test_keye.py``'s)."""

import collections
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_sdar, weights
from benchmark.reference import sdar as ref
from commefficient_tpu.data.fedtext import BlockNoise
from commefficient_tpu.models import laguna, sdar
from commefficient_tpu.models.laguna import Block, LagunaLM, laguna_tiny
from commefficient_tpu.models.losses import block_diffusion_lm_loss, causal_lm_loss
from commefficient_tpu.models.sdar import sdar_30b_a3b, sdar_tiny
from commefficient_tpu.ops.pallas import library_kernels
from commefficient_tpu.ops.pallas.library_kernels import BlockDiffusionMask, banded_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 128


def _loss_kwargs(cfg, experts_held=None, **more):
    return dict(
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_attention_heads_per_layer[0],
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_full.rope_theta, block_length=cfg.block_length,
        mask_token=cfg.mask_token, num_experts_per_tok=cfg.num_experts_per_tok,
        rms_norm_eps=cfg.rms_norm_eps, query_block=64,
        experts_held=list(cfg.experts_held if experts_held is None else experts_held), **more)


def _seeded(model, *args, seed=3):
    shapes = jax.eval_shape(model.init, jax.random.key(0), *args)
    params = weights.make(shapes, seed, {"std": 0.02})
    return params, weights.leaf_names(params)


def _batch(cfg, rows=2, seed=1):
    """Rows with a tail pad, under one draw of the round's noise."""
    ids = jax.random.randint(jax.random.key(seed), (rows, T), 0, cfg.mask_token)
    labels = np.where(np.arange(T)[None, :] < 120, np.asarray(ids), -100)
    noise = BlockNoise(cfg.block_length)
    made = noise.apply({"lm_labels": labels}, *noise.plan(np.random.default_rng(seed), rows, T))
    return {"input_ids": ids, "lm_labels": jnp.asarray(labels),
            **{k: jnp.asarray(v) for k, v in made.items()}}


@pytest.fixture(scope="module")
def tiny():
    """Program and reference on the same seeded weights, batch and noise."""
    cfg = sdar_tiny(dtype=jnp.float32)
    model = LagunaLM(cfg)
    batch = _batch(cfg)
    params, names = _seeded(model, batch["input_ids"])
    grad = jax.jit(jax.value_and_grad(block_diffusion_lm_loss(model.apply, "float32"),
                                      has_aux=True))
    (loss, aux), grads = grad(params, batch)
    flat = dict(zip(names, jax.tree.leaves(params)))
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch, "float32", **_loss_kwargs(cfg)))(flat)
    return dict(cfg=cfg, model=model, params=params, flat=flat, batch=batch, loss=loss, aux=aux,
                grads=dict(zip(names, jax.tree.leaves(grads))), ref_loss=ref_loss,
                ref_grads=ref_grads)


LEAVES = weights.leaf_names(jax.eval_shape(
    LagunaLM(sdar_tiny()).init, jax.random.key(0), jnp.zeros((1, T), jnp.int32)))


# ---- the mask and the attention under it ------------------------------------------------

def _dense_mask(t, block_length):
    """The four rules, rule by rule, as a ``[2t, 2t]`` array."""
    b = np.arange(t) // block_length
    noised_noised = b[:, None] == b[None, :]
    noised_clean = b[None, :] < b[:, None]
    clean_noised = np.zeros((t, t), bool)
    clean_clean = b[None, :] <= b[:, None]
    return np.block([[noised_noised, noised_clean], [clean_noised, clean_clean]])


@pytest.mark.parametrize("t,block_length", [(128, 1), (128, 4), (128, 128), (256, 4), (384, 6)])
def test_the_kernels_mask_is_the_four_rules(t, block_length):
    """What the library reads off the mask, a tile at a time in numpy and in
    the kernels on ``q_sequence``'s codes, is the dense mask of the rules;
    the reference's own comparisons give the same."""
    mask = BlockDiffusionMask((2 * t, 2 * t), block_length)
    want = _dense_mask(t, block_length)
    got = mask[:, :]
    assert got.dtype == np.bool_ and np.array_equal(got, want)
    assert np.array_equal(mask[128:256, 64:192], want[128:256, 64:192])    # a tile of its own
    assert got.sum() == t * (t + block_length)
    assert np.array_equal(np.asarray(ref.allowed(jnp.arange(2 * t), t, block_length)), want)
    assert mask == BlockDiffusionMask((2 * t, 2 * t), block_length)
    assert hash(mask) != hash(BlockDiffusionMask((4 * t, 4 * t), block_length))


def test_tiles_wholly_outside_the_mask_are_not_visited():
    """At T = 8,192 and tiles of 512: 288 of 1,024 tiles hold a pair, 48 of
    them straddling a boundary (the noised diagonal and the two clean ones)."""
    mask = BlockDiffusionMask((16384, 16384), 4)
    tiles = np.array([[mask[i:i + 512, j:j + 512] for j in range(0, 16384, 512)]
                      for i in range(0, 16384, 512)])
    some, every = tiles.any((2, 3)), tiles.all((2, 3))
    assert some.sum() == 288 and (some & ~every).sum() == 48
    assert not some[16:, :16].any()                      # no clean query reads a noised key


def test_a_mask_of_half_blocks_is_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        BlockDiffusionMask((256, 256), 6)


def _qkv(t, heads=4, kv=2, d=16, seed=0, b=2):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (b, 2 * t, heads, d)) / 4
    k, v = (jax.random.normal(key, (b, 2 * t, kv, d)) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], (b, 2 * t, heads, d))


def _plain_attention(q, k, v, keep):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.where(keep[None, None], jnp.einsum("bthd,bshd->bhts", q, k), -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("t,block_length", [(128, 1), (128, 4), (128, 128), (256, 4)])
def test_attention_and_its_gradients_equal_dense_attention_under_the_mask(t, block_length):
    q, k, v, ct = _qkv(t)
    keep = _dense_mask(t, block_length)
    got = banded_attention(q, k, v, block_length=block_length)
    np.testing.assert_allclose(got, _plain_attention(q, k, v, keep), atol=2e-6)
    kernel = jax.grad(lambda *a: jnp.sum(banded_attention(*a, block_length=block_length) * ct),
                      (0, 1, 2))(q, k, v)
    plain = jax.grad(lambda *a: jnp.sum(_plain_attention(*a, keep) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(kernel, plain):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_lowered_attention_holds_no_two_t_by_two_t_operand():
    q, k, v, _ = _qkv(320)                                     # a stream of five blocks of 128
    text = jax.jit(jax.grad(lambda *a: jnp.sum(banded_attention(*a, block_length=4)), (0, 1, 2))
                   ).lower(q, k, v).as_text()
    assert "128x128x" in text                                  # the kernel's blocks
    assert not re.search(r"640x640|320x320", text)


def test_the_causal_kernels_are_built_as_before():
    """The new kind is a key of the kernel cache beside the old ones."""
    library_kernels._attention_kernel.cache_clear()
    q, k, v, _ = _qkv(64)
    banded_attention(q, k, v)
    banded_attention(q, k, v, window=8)
    banded_attention(q, k, v, block_length=4)
    assert library_kernels._attention_kernel.cache_info().currsize == 3


# ---- the two streams -----------------------------------------------------------------------

def test_the_clean_stream_is_the_decoder_on_the_clean_row_alone(tiny):
    """No clean query reads a noised key: the clean half of every block's
    output is what the same block gives the clean row alone under the
    block-causal mask (the reference's attention with its mask cut to the
    clean quadrant), whatever the noise did to the other half."""
    cfg, p = tiny["cfg"], tiny["params"]["params"]
    ids, masked = tiny["batch"]["input_ids"][:1], tiny["batch"]["noise_mask"][:1]
    noised = jnp.where(masked, cfg.mask_token, ids)
    emb = p["embed"]["embedding"]
    x, other = emb[jnp.concatenate([noised, ids], 1)], emb[jnp.concatenate([ids, ids], 1)]
    for i in range(cfg.num_layers):
        x = Block(cfg, i).apply({"params": p[f"layer_{i}"]}, x)[0]
        other = Block(cfg, i).apply({"params": p[f"layer_{i}"]}, other)[0]
        np.testing.assert_allclose(x[:, T:], other[:, T:], atol=1e-6)
        assert not np.allclose(x[:, :T], other[:, :T], atol=1e-3)

    # and that clean half is block-causal attention over T positions
    kw = _loss_kwargs(cfg)
    h = emb[ids[0]]
    b = np.arange(T) // cfg.block_length
    keep = b[None, :] <= b[:, None]
    a = "params/layer_0/attn"
    flat = tiny["flat"]
    normed = ref._rms(flat, "params/layer_0/attn_norm", h, kw["rms_norm_eps"], "float32")
    H, KV, d = kw["num_attention_heads"], kw["num_key_value_heads"], kw["head_dim"]
    q = ref._rms(flat, f"{a}/q_norm", (normed @ flat[f"{a}/q_proj/kernel"]).reshape(T, H, d),
                 1e-6, "float32")
    k = ref._rms(flat, f"{a}/k_norm", (normed @ flat[f"{a}/k_proj/kernel"]).reshape(T, KV, d),
                 1e-6, "float32")
    v = (normed @ flat[f"{a}/v_proj/kernel"]).reshape(T, KV, d)
    cos, sin = ref.rope_tables(jnp.arange(T), d, kw["rope_theta"])
    q, k = ref._rotate(q, cos, sin, d) / np.sqrt(d), ref._rotate(k, cos, sin, d)
    o = _plain_attention(q[None], k[None], v[None], keep)[0].reshape(T, H * d)
    alone = h + o @ flat[f"{a}/o_proj/kernel"]
    both = ref._attention(flat, "params/layer_0", emb[jnp.concatenate([noised, ids], 1)[0]],
                          "float32", kw)
    np.testing.assert_allclose(both[T:], alone, atol=1e-5)


# ---- loss and gradients against the reference ------------------------------------------------

def test_loss_equals_the_reference(tiny):
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]), rel=1e-6)
    assert float(tiny["aux"]["moe/dropped"]) == 0.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"][leaf], tiny["ref_grads"][leaf]
    assert got.shape == want.shape and float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) <= 2e-5 * float(jnp.linalg.norm(want)) + 1e-12


def test_the_counters_count_what_the_noise_masked(tiny):
    batch, aux, cfg = tiny["batch"], tiny["aux"], tiny["cfg"]
    masked = np.asarray(batch["noise_mask"])
    assert float(aux["diffusion/labelled_tokens"]) == float(aux["token_count"]) == 2 * 120
    assert float(aux["diffusion/masked_tokens"]) == masked.sum() > 0
    assert float(aux["diffusion/weight_sum"]) == pytest.approx(
        float((1.0 / np.asarray(batch["noise_t"]))[masked].sum()), rel=1e-6)
    assert float(aux["attn/blockdiff_pairs"]) == cfg.num_layers * 2 * T * (T + cfg.block_length)
    assert float(aux["lm_loss_sum"]) == pytest.approx(float(tiny["loss"]) * 2 * 120, rel=1e-6)


def test_an_unmasked_position_and_the_pad_weigh_nothing(tiny):
    """The loss reads the noised stream's logits at the masked positions
    only, each under 1 / t: changing the weight of one block moves the loss
    by that block's share, and a mask bit on the pad (eval's padded tail
    rows are relabelled -100 after the noise was drawn) counts for nothing."""
    model, params, batch = tiny["model"], tiny["params"], tiny["batch"]
    loss = jax.jit(block_diffusion_lm_loss(model.apply, "float32"))
    logits, _ = model.apply(params, batch["input_ids"], None,
                            (batch["noise_mask"], batch["noise_t"]))
    assert logits.shape == (2, T, tiny["cfg"].vocab_held)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), batch["input_ids"][..., None],
                               -1)[..., 0]
    by_hand = jnp.sum(jnp.where(batch["noise_mask"], nll / batch["noise_t"], 0.0)) / 240
    assert float(by_hand) == pytest.approx(float(tiny["loss"]), rel=1e-5)
    padded = {**batch, "noise_mask": batch["noise_mask"].at[:, 120:].set(True)}
    assert float(loss(params, padded)[0]) == pytest.approx(float(tiny["loss"]), rel=1e-6)
    # nor in the reference, which masks with the labels itself
    assert float(ref.loss(tiny["flat"], padded, "float32", **_loss_kwargs(tiny["cfg"]))) == (
        pytest.approx(float(tiny["ref_loss"]), rel=1e-6))
    with pytest.raises(ValueError, match="noise"):
        model.apply(params, batch["input_ids"], batch["lm_labels"])


def test_the_loss_without_its_weights_is_another_loss(tiny):
    flat, batch, kw = tiny["flat"], tiny["batch"], _loss_kwargs(tiny["cfg"])
    unweighted = ref.loss(flat, {**batch, "noise_t": jnp.ones_like(batch["noise_t"])},
                          "float32", **kw)
    assert abs(float(unweighted) - float(tiny["ref_loss"])) > 0.1 * float(tiny["ref_loss"])


def test_vmap_over_clients_equals_a_loop_over_clients(tiny):
    model, params = tiny["model"], tiny["params"]
    loss = block_diffusion_lm_loss(model.apply, "float32")
    clients = [_batch(tiny["cfg"], rows=1, seed=s) for s in (5, 6)]
    stacked = {k: jnp.stack([c[k] for c in clients]) for k in clients[0]}
    together = jax.vmap(lambda b: loss(params, b)[0])(stacked)
    np.testing.assert_allclose(together, [loss(params, c)[0] for c in clients], rtol=1e-6)


# ---- what a block's remat keeps of the library's attention kernel ----------------------------

def _kernel_calls(jaxpr):
    """Pallas calls of a jaxpr by the kernel's name, every sub-jaxpr walked
    call site by call site (the printed text shows a repeated one once)."""
    counts = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    counts.update(_kernel_calls(sub))
    return counts


def _tiny_gradient(cfg):
    """A tiny round of the decoder ``cfg``: the kernels its gradient calls,
    its loss and its gradient's leaves."""
    model = LagunaLM(cfg)
    if cfg.block_length:
        batch, loss = _batch(cfg), block_diffusion_lm_loss(model.apply, "float32")
    else:
        ids = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_held)
        batch = {"input_ids": ids, "lm_labels": jnp.where(jnp.arange(T)[None, :] < 120, ids, -100)}
        loss = causal_lm_loss(model.apply, "float32")
    params, _ = _seeded(model, batch["input_ids"])
    traced = jax.jit(jax.value_and_grad(loss, has_aux=True)).trace(params, batch)
    (value, _aux), grads = traced.lower().compile()(params, batch)
    return dict(layers=cfg.num_layers, calls=_kernel_calls(traced.jaxpr.jaxpr), loss=value,
                grads=jax.tree.leaves(grads))


@pytest.fixture(scope="module", params=[sdar_tiny, laguna_tiny], ids=lambda p: p.__name__)
def kept_and_recomputed(request):
    """A decoder whose library kernels name their residuals (the default, and
    both presets') and the same decoder with ``attn_residuals_kept`` off
    (``laguna_xs2``'s setting: plain ``remat``'s program)."""
    cfg = request.param(dtype=jnp.float32)
    assert cfg.attn_residuals_kept
    return (_tiny_gradient(cfg),
            _tiny_gradient(dataclasses.replace(cfg, attn_residuals_kept=False)))


ATTENTION_KERNELS = ("splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                     "splash_mqa_dkv_no_residuals")


def test_the_forward_kernel_runs_once_a_layer(kept_and_recomputed):
    """``test_keye.py``'s test of the same name for the library's kernel: its
    output and log-sum-exp cross the block's ``remat`` under the name
    ``ATTEND_RESIDUAL``, so the gradient calls ``splash_mqa_fwd`` once a
    layer, and ``dq`` and ``dkv`` once; built without the name
    (``laguna_xs2``'s setting) the forward kernel is called twice a layer."""
    kept, recomputed = kept_and_recomputed
    once = dict.fromkeys(ATTENTION_KERNELS, kept["layers"])
    assert {k: kept["calls"][k] for k in once} == once
    assert {k: recomputed["calls"][k] for k in once} == {
        **once, "splash_mqa_fwd_residuals": 2 * kept["layers"]}


def test_the_kept_residuals_are_the_recomputed_ones(kept_and_recomputed):
    """Loss and every gradient leaf, bit for bit: what is kept between the
    passes is what the second forward kernel would have written."""
    kept, recomputed = kept_and_recomputed
    assert np.asarray(kept["loss"]).tobytes() == np.asarray(recomputed["loss"]).tobytes()
    assert len(kept["grads"]) == len(recomputed["grads"]) > 0
    for a, b in zip(kept["grads"], recomputed["grads"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in kept["grads"])


# ---- the shares of the whole layer -------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4's test on this preset: what all four expert shares of
    the 16 experts give (4 each), with attention (and the residual) counted
    once, is the uncut layer as the reference computes it with every expert
    held; and each share alone is the reference's layer with that share."""
    cfg = sdar_tiny(dtype=jnp.float32)
    whole_cfg = type(cfg)(**{**cfg.__dict__, "experts_held": tuple(range(16))})
    x = jax.random.normal(jax.random.key(4), (1, 2 * T, cfg.hidden_size))
    whole, names = _seeded(Block(whole_cfg, 0), x, seed=5)
    flat = {"params/layer_0/" + n.split("/", 1)[1]: a
            for n, a in zip(names, jax.tree.leaves(whole))}
    kw = _loss_kwargs(cfg)
    want = ref._layer(flat, 0, x[0], "float32", {**kw, "experts_held": list(range(16))})
    once = ref._attention(flat, "params/layer_0", x[0], "float32", kw)     # x + Attn(norm(x))
    total = once
    for chip in range(4):
        ids = tuple(range(4 * chip, 4 * chip + 4))
        ccfg = type(cfg)(**{**cfg.__dict__, "experts_held": ids})
        p = {"params": {**whole["params"], "moe": {**whole["params"]["moe"], "experts": {
            k: v[4 * chip:4 * chip + 4]
            for k, v in whole["params"]["moe"]["experts"].items()}}}}
        y, counters, _ = Block(ccfg, 0).apply(p, x)
        share = {**flat, **{f"params/layer_0/moe/experts/{k}": v
                            for k, v in p["params"]["moe"]["experts"].items()}}
        np.testing.assert_allclose(
            y[0], ref._layer(share, 0, x[0], "float32", {**kw, "experts_held": list(ids)}),
            atol=1e-5)
        assert float(counters["moe/dropped"]) == 0.0
        total = total + (y[0] - once)
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---- the presets against the published config ------------------------------------------------

@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b_fedtext.json")) as f:
        return json.load(f)


def test_published_keys_are_the_configuration_files_and_the_catalogs(conf):
    for key, value in sdar.PUBLISHED.items():
        value = list(value) if isinstance(value, tuple) else value
        assert value == conf["published"].get(key, conf[key]), key
    assert conf["reduced"] == sorted(conf["published"], key=conf["reduced"].index)
    assert conf["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    cut = sdar_30b_a3b()
    assert cut.num_layers == conf["num_hidden_layers"] == 4
    assert len(cut.experts_held) == conf["num_experts"] and cut.vocab_held == conf["vocab_size"]
    kw = conf["reference"]["loss_kwargs"]
    assert list(cut.experts_held) == kw["experts_held"]
    assert (cut.block_length, cut.mask_token) == (kw["block_length"], kw["mask_token"]) == (
        4, 18990)
    assert kw["rope_theta"] == conf["rope_theta"] == cut.rope_full.rope_theta == 1e6
    assert (cut.router, cut.qk_norm, cut.output_gate) == ("softmax_renormalised", True, False)
    assert set(cut.layer_types) == {"block_diffusion_attention"}
    assert set(cut.mlp_layer_types) == {"sparse"}
    flops = conf["flops_kwargs"]
    assert (flops["layers"], flops["experts_held"], flops["vocab_held"], flops["seq"],
            flops["block_length"]) == (4, 8, 18992, 8192, 4)
    assert (conf["units_per_sample"], conf["unit"]) == (8192, "token")
    assert {"block_length", "noise_schedule", "target", "clean_stream_loss", "normalisation",
            "mask_token", "qk_norm", "rotary", "router", "activation", "max_seq_len", "data",
            "expert_rows"} <= set(conf["assumed"])
    # the other presets of this decoder have neither field set
    assert (laguna.laguna_xs2().block_length, laguna.laguna_xs2().mask_token) == (0, -1)


def test_the_cut_holds_305_4m_parameters(conf):
    shapes = jax.eval_shape(LagunaLM(sdar_30b_a3b()).init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    sizes = {n: int(np.prod(a.shape))
             for n, a in zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))}
    assert sum(sizes.values()) == conf["parameters_held"] == 305_351_680
    layer = {n.split("/", 2)[2]: s for n, s in sizes.items() if "/layer_0/" in n}
    # ISSUE 35: 18,874,368 + 256 + 262,144 + 4,096 + 37,748,736
    assert sum(s for n, s in layer.items() if n.startswith("attn/") and "_proj" in n) == 18_874_368
    assert layer["attn/q_norm/scale"] + layer["attn/k_norm/scale"] == 256
    assert layer["moe/router/kernel"] == 262_144
    assert sum(s for n, s in layer.items() if "/experts/" in n) == 37_748_736
    assert sum(layer.values()) == 56_889_600
    assert sizes["params/embed/embedding"] + sizes["params/lm_head/kernel"] == 77_791_232


def test_the_cuts_first_expert_tier_is_floored_in_whole_tiles():
    """A row's 16,384 stream positions send 131,072 assignments, 8,192 of
    them to the held experts under a uniform router."""
    cfg = sdar_30b_a3b()
    rows = [laguna._tier_rows(16384, cfg.num_experts_per_tok, len(cfg.experts_held),
                              cfg.num_experts, f, cfg.expert_tiling[0])
            for f in cfg.expert_row_tiers]
    assert cfg.expert_rows_floored and rows == sorted(rows)
    assert all(r % cfg.expert_tiling[0] == 0 and r < 16384 * 8 for r in rows)


def test_required_operations_are_the_issues_arithmetic(conf):
    kw = conf["flops_kwargs"]
    attention = flops_sdar.attn_blockdiff_flops_per_token(**kw)
    assert attention == pytest.approx(3 * 4 * 4 * 32 * 128 * (8192 + 4))
    # T (T + L) pairs a head a row, 512 operations a pair forward: 8.8 TFLOP a round forward
    assert attention / 3 * 16384 == pytest.approx(4 * 2 * 32 * 8192 * 8196 * 512)
    per_token = flops_sdar.sdar_flops_per_token(n_params=305_351_680, **kw)
    position = 2 * (18_874_368 + 262_144 + 0.5 * 4_718_592)
    assert per_token == pytest.approx(
        3 * (4 * 2 * position + 2 * 2048 * 18992) + attention)
    # forward + backward a round of 16,384 tokens: 26.4 attention + 16.9 layers + 3.8 head
    assert per_token * 16384 == pytest.approx(47.1e12, rel=0.01)


def test_the_entry_reports_the_masked_share(tiny):
    from commefficient_tpu.train.lm_train import _LmHooks

    hooks = _LmHooks(None, None, None, 2)
    acc = hooks.new_accumulator()
    for _ in range(3):
        hooks.accumulate(acc, 5.0, {k: float(v) for k, v in tiny["aux"].items()})
    row = hooks.epoch_row(epoch=0, lr=0.1, acc=acc, val={"nll": 1.0, "ppl": 2.7},
                          train_time=1.0, val_time=1.0, steps_per_epoch=3)
    assert row["masked_share"] == pytest.approx(
        float(tiny["aux"]["diffusion/masked_tokens"]) / 240)
    plain = hooks.new_accumulator()
    hooks.accumulate(plain, 5.0, {"moe/held_assignments": 4.0})
    assert "masked_share" not in hooks.epoch_row(
        epoch=0, lr=0.1, acc=plain, val={"nll": 1.0, "ppl": 2.7}, train_time=1.0,
        val_time=1.0, steps_per_epoch=1)
