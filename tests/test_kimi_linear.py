"""models/kimi_linear.py (its mixers ``models/kda.py`` and
``models/latent_attention.py``) at ``kimi_linear_tiny`` on the CPU, in
float32, held to the plain reference (``benchmark/reference/kimi_linear.py``,
which imports nothing of the program): the chunked delta rule against the
token-by-token recurrence, for decays from near 0 to -20 a position, at
lengths that are not a whole number of chunks, and in its gradients; latent
attention against dense softmax, with no ``[T, T]`` operand; the whole
model's loss and every gradient leaf; ``vmap`` over clients; the expert
shares of the uncut layer; the presets against the published keys, the
1-indexed layer lists, the cut's D and the required operations; the entry's
epoch row."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_kimi, weights
from benchmark.reference import kimi_linear as ref
from commefficient_tpu.models import kimi_linear
from commefficient_tpu.models.kda import chunked_delta_rule
from commefficient_tpu.models.kimi_linear import kimi_linear_48b, kimi_linear_tiny
from commefficient_tpu.models.laguna import Block, LagunaLM
from commefficient_tpu.models.latent_attention import LatentAttention
from commefficient_tpu.models.losses import causal_lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 128
INIT = {"std": "fan_in"}    # the cell's: A_log and dt_bias at std 1, so some state crosses a chunk


def _loss_kwargs(cfg, experts_held=None):
    return dict(
        layer_types=list(cfg.layer_types), mlp_layer_types=list(cfg.mlp_layer_types),
        rms_norm_eps=cfg.rms_norm_eps, kda_heads=cfg.kda_heads, kda_head_dim=cfg.kda_head_dim,
        num_attention_heads=cfg.num_attention_heads_per_layer[0],
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, kv_lora_rank=cfg.kv_lora_rank,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.moe_routed_scaling_factor, query_block=64, state_block=16,
        experts_held=list(cfg.experts_held if experts_held is None else experts_held))


def _seeded(model, *args, seed=3):
    shapes = jax.eval_shape(model.init, jax.random.key(0), *args)
    params = weights.make(shapes, seed, INIT)
    return params, weights.leaf_names(params)


def _batch(cfg, rows=2, seed=1):
    ids = jax.random.randint(jax.random.key(seed), (rows, T), 0, cfg.vocab_held)
    return {"input_ids": ids, "lm_labels": jnp.where(jnp.arange(T)[None, :] < 120, ids, -100)}


@pytest.fixture(scope="module")
def tiny():
    """Program and reference on the same seeded weights and batch."""
    cfg = kimi_linear_tiny(dtype=jnp.float32)
    model = LagunaLM(cfg)
    batch = _batch(cfg)
    params, names = _seeded(model, batch["input_ids"])
    grad = jax.jit(jax.value_and_grad(causal_lm_loss(model.apply, "float32"), has_aux=True))
    (loss, aux), grads = grad(params, batch)
    flat = dict(zip(names, jax.tree.leaves(params)))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, "float32", **_loss_kwargs(cfg))))(flat)
    return dict(cfg=cfg, model=model, params=params, flat=flat, batch=batch, loss=loss, aux=aux,
                grads=dict(zip(names, jax.tree.leaves(grads))), ref_loss=ref_loss,
                ref_grads=ref_grads)


LEAVES = weights.leaf_names(jax.eval_shape(
    LagunaLM(kimi_linear_tiny()).init, jax.random.key(0), jnp.zeros((1, T), jnp.int32)))


# ---- the chunked rule against the recurrence --------------------------------------------------

def _rule_inputs(t, decay, heads=2, d=16, dv=8, b=2, seed=0):
    """Normalised ``q`` (over ``sqrt(d)``), ``k``; ``v``; the decay ``g``
    a channel (``near_zero``: ``-1e-3`` and under, a state kept across every
    chunk; ``gated``: the gate's own form on random inputs; ``minus_20``:
    ``-20`` a position and more); ``beta``."""
    keys = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, heads, d))) / np.sqrt(d)
    k = unit(jax.random.normal(keys[1], (b, t, heads, d)))
    v = jax.random.normal(keys[2], (b, t, heads, dv))
    u = jax.random.uniform(keys[3], (b, t, heads, d))
    g = {"near_zero": -1e-3 * u,
         "gated": -jnp.exp(jax.random.normal(keys[4], (heads, 1)))
         * jax.nn.softplus(jax.random.normal(keys[5], (b, t, heads, d)) - 2.0),
         "minus_20": -20.0 - u}[decay]
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, t, heads)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda *a: ref.recurrence(*a, block=16))(q, k, v, g, beta)


DECAYS = ["near_zero", "gated", "minus_20"]


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("t,chunk", [(128, 64), (100, 16), (70, 64)], ids=lambda x: str(x))
def test_the_chunked_rule_is_the_recurrence(decay, t, chunk):
    q, k, v, g, beta = _rule_inputs(t, decay)
    o, counters = jax.jit(lambda *a: chunked_delta_rule(*a, chunk=chunk, dtype=jnp.float32))(
        q, k, v, g, beta)
    want = _recurrence(q, k, v, g, beta)
    assert np.all(np.isfinite(o)) and o.shape == want.shape
    np.testing.assert_allclose(o, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    n = -(-t // chunk)
    assert float(counters["kda/decay_sum"]) == pytest.approx(float(jnp.sum(g)), rel=1e-5)
    assert float(counters["kda/state_slots"]) == 2 * n * 2 * 16
    kept = float(counters["kda/state_kept"]) / float(counters["kda/state_slots"])
    assert (kept > 0.9) if decay == "near_zero" else (kept < 0.5)


@pytest.mark.parametrize("decay", DECAYS)
def test_the_chunked_rules_gradients_are_the_recurrences(decay):
    q, k, v, g, beta = _rule_inputs(100, decay)
    ct = jax.random.normal(jax.random.key(9), v.shape)

    def loss(rule):
        return lambda *a: jnp.sum(rule(*a) * ct)

    chunked = jax.jit(jax.grad(loss(lambda *a: chunked_delta_rule(
        *a, chunk=16, dtype=jnp.float32)[0]), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    plain = jax.grad(loss(_recurrence), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for name, a, b in zip("qkvgb", chunked, plain):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))) + 1e-12,
                                   err_msg=name)


def test_no_exponent_of_the_rule_is_positive():
    """At ``-20`` a position a chunk's decay is ``-1,280``: a product split
    at the chunk's start would read ``exp(1,280)``. Every ``exp`` the rule
    lowers takes an argument the decays make ``<= 0``: none overflows, and
    the outputs, the state's share and every gradient are finite."""
    q, k, v, g, beta = _rule_inputs(128, "minus_20")
    jaxpr = jax.make_jaxpr(lambda g: chunked_delta_rule(q, k, v, g, beta, chunk=64,
                                                        dtype=jnp.float32))(g)
    exps = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "exp"]
    assert exps
    grads = jax.grad(lambda *a: jnp.sum(chunked_delta_rule(*a, chunk=64, dtype=jnp.float32)[0]),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(np.all(np.isfinite(x)) for x in grads)


def test_the_rule_under_bfloat16_operands_stays_near_the_recurrence():
    q, k, v, g, beta = _rule_inputs(128, "gated")
    o, _ = chunked_delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16), g, beta, chunk=64, dtype=jnp.bfloat16)
    want = _recurrence(q, k, v, g, beta)
    err = float(jnp.linalg.norm(o - want) / jnp.linalg.norm(want))
    assert 1e-5 < err < 3e-2


# ---- latent attention ----------------------------------------------------------------------

def _latent_setup(t, seed=4):
    cfg = kimi_linear_tiny(dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(seed), (2, t, cfg.hidden_size))
    mixer = LatentAttention(cfg, 3)
    params = weights.make(jax.eval_shape(mixer.init, jax.random.key(0), h), 5, INIT)
    return cfg, h, mixer, params


def _dense_latent(cfg, p, h):
    """The mixer's equations with the whole ``[T, T]`` causal softmax."""
    B, t, _ = h.shape
    H, dn, dr, dv, r = (4, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                        cfg.kv_lora_rank)
    p = p["params"]
    q = (h @ p["q_proj"]["kernel"]).reshape(B, t, H, dn + dr)
    latent = h @ p["kv_a_proj"]["kernel"]
    c = latent[..., :r]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.rms_norm_eps) * p["kv_a_norm"]["scale"]
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(B, t, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(latent[:, :, None, r:], (B, t, H, dr))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kv[..., dn:])
    return o.reshape(B, t, H * dv) @ p["o_proj"]["kernel"]


def test_latent_attention_equals_dense_softmax_and_its_gradients():
    cfg, h, mixer, params = _latent_setup(T)
    with jax.default_matmul_precision("highest"):
        got, counters = mixer.apply(params, h)
        np.testing.assert_allclose(got, _dense_latent(cfg, params, h), atol=2e-5)
        ct = jax.random.normal(jax.random.key(7), got.shape)
        kernel = jax.grad(lambda p, h: jnp.sum(mixer.apply(p, h)[0] * ct), (0, 1))(params, h)
        dense = jax.grad(lambda p, h: jnp.sum(_dense_latent(cfg, p, h) * ct), (0, 1))(params, h)
    assert counters is None
    for a, b in zip(jax.tree.leaves(kernel), jax.tree.leaves(dense)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_the_lowered_latent_attention_holds_no_t_by_t_operand():
    """Values narrower than keys go to the kernel as they are (16 against
    24 at the tiny widths); its tiles are ``[128, 128]``, never ``[T, T]``."""
    _cfg, h, mixer, params = _latent_setup(384)
    text = jax.jit(jax.grad(lambda p: jnp.sum(mixer.apply(p, h)[0]))).lower(params).as_text()
    assert "128x128x" in text
    assert not re.search(r"384x384", text)


# ---- the whole model against the reference ---------------------------------------------------

def test_loss_equals_the_reference(tiny):
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]), rel=1e-6)
    assert float(tiny["aux"]["moe/dropped"]) == 0.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"][leaf], tiny["ref_grads"][leaf]
    assert got.shape == want.shape and float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) <= 5e-5 * float(jnp.linalg.norm(want)) + 1e-12


def test_the_counters_read_the_state_kept_across_a_chunk(tiny):
    """Four KDA layers of 4 heads x 16 channels, 8 chunks of 16 a row; under
    the cell's draw some channels keep their state across a chunk and most
    do not."""
    aux, cfg = tiny["aux"], tiny["cfg"]
    kda = sum(1 for k in cfg.layer_types if k == "kda_attention")
    assert kda == 4
    assert float(aux["kda/state_slots"]) == kda * 2 * (T // 16) * 4 * 16
    share = float(aux["kda/state_kept"]) / float(aux["kda/state_slots"])
    assert 0.0 < share < 0.5
    assert float(aux["kda/decay_sum"]) < 0


def test_vmap_over_clients_equals_a_loop_over_clients(tiny):
    model, params = tiny["model"], tiny["params"]
    loss = causal_lm_loss(model.apply, "float32")
    clients = [_batch(tiny["cfg"], rows=1, seed=s) for s in (5, 6)]
    stacked = {k: jnp.stack([c[k] for c in clients]) for k in clients[0]}
    together = jax.vmap(lambda b: loss(params, b)[0])(stacked)
    np.testing.assert_allclose(together, [loss(params, c)[0] for c in clients], rtol=1e-6)


# ---- the shares of the whole layer -------------------------------------------------------

@pytest.mark.parametrize("layer", [1, 3], ids=["kda_layer", "latent_layer"])
def test_the_shares_add_up_to_the_uncut_layer(layer):
    """Guide section 4's test on this preset, at a KDA layer and at the
    latent one: what all four expert shares of the 16 experts give (4 each),
    with the mixer, the shared expert and the residual counted once, is the
    uncut layer as the reference computes it with every expert held; and
    each share alone is the reference's layer with that share."""
    cfg = kimi_linear_tiny(dtype=jnp.float32)
    whole_cfg = type(cfg)(**{**cfg.__dict__, "experts_held": tuple(range(16))})
    x = jax.random.normal(jax.random.key(4), (1, T, cfg.hidden_size))
    whole, names = _seeded(Block(whole_cfg, layer), x, seed=5)
    name = f"params/layer_{layer}"
    flat = {f"{name}/" + n.split("/", 1)[1]: a for n, a in zip(names, jax.tree.leaves(whole))}
    kw = _loss_kwargs(cfg)
    mixer = ref._kda if cfg.layer_types[layer] == "kda_attention" else ref._latent
    with jax.default_matmul_precision("highest"):
        want = ref._layer(flat, layer, x[0], "float32", {**kw, "experts_held": list(range(16))})
        once = mixer(flat, name, x[0], "float32", kw)                  # x + Mixer(norm(x))
        s = f"{name}/moe/shared"
        shared = ref._swiglu(ref._rms(flat, f"{name}/mlp_norm", once, cfg.rms_norm_eps, "float32"),
                             flat[f"{s}/gate_proj/kernel"], flat[f"{s}/up_proj/kernel"],
                             flat[f"{s}/down_proj/kernel"], "float32")
        total = once + shared
        for chip in range(4):
            ids = tuple(range(4 * chip, 4 * chip + 4))
            ccfg = type(cfg)(**{**cfg.__dict__, "experts_held": ids})
            p = {"params": {**whole["params"], "moe": {**whole["params"]["moe"], "experts": {
                k: v[4 * chip:4 * chip + 4]
                for k, v in whole["params"]["moe"]["experts"].items()}}}}
            y, counters, _ = Block(ccfg, layer).apply(p, x)
            share = {**flat, **{f"{name}/moe/experts/{k}": v
                                for k, v in p["params"]["moe"]["experts"].items()}}
            np.testing.assert_allclose(
                y[0], ref._layer(share, layer, x[0], "float32", {**kw, "experts_held": list(ids)}),
                atol=2e-5)
            assert float(counters["moe/dropped"]) == 0.0
            total = total + (y[0] - once - shared)
    np.testing.assert_allclose(total, want, atol=5e-5)


def _head_share(kind, p, h, H, d, cfg):
    """Head ``h``'s share of a mixer's leaves: its columns of every
    projection that makes a head's q, k, v, gate or decay, its rows of
    ``o_proj``; the low-rank factors, the latent and the norms whole."""
    one = lambda x, w: x[..., h * w:(h + 1) * w]  # noqa: E731
    if kind == "kda_attention":
        cols = np.concatenate([np.arange(j * H * d + h * d, j * H * d + (h + 1) * d)
                               for j in range(3)])
        low = p["gate_a_proj"]["kernel"]
        decay = p["decay"]["kernel"]
        return {"qkv_proj": {"kernel": p["qkv_proj"]["kernel"][:, cols]},
                "conv": {"kernel": p["conv"]["kernel"][:, cols]},
                "gate_a_proj": {"kernel": jnp.concatenate(
                    [low[:, :2 * d], low[:, 2 * d + h:2 * d + h + 1]], -1)},
                "f_b_proj": {"kernel": one(p["f_b_proj"]["kernel"], d)},
                "g_b_proj": {"kernel": one(p["g_b_proj"]["kernel"], d)},
                "decay": {"kernel": jnp.concatenate([decay[h:h + 1], one(decay[H:], d)])},
                "o_norm": p["o_norm"],
                "o_proj": {"kernel": p["o_proj"]["kernel"][h * d:(h + 1) * d]}}
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"q_proj": {"kernel": one(p["q_proj"]["kernel"], dn + dr)},
            "kv_a_proj": p["kv_a_proj"], "kv_a_norm": p["kv_a_norm"],
            "kv_b_proj": {"kernel": one(p["kv_b_proj"]["kernel"], dn + dv)},
            "o_proj": {"kernel": p["o_proj"]["kernel"][h * dv:(h + 1) * dv]}}


@pytest.mark.parametrize("layer", [1, 3], ids=["kda_layer", "latent_layer"])
def test_the_head_shares_add_up_to_the_uncut_mixer(layer):
    """The cell holds a quarter of each mixer's heads: what the four head
    shares of the tiny preset's four heads give, with the residual counted
    once, is the uncut mixer as the reference computes it with every head;
    and each share alone is the reference's mixer of one head on that
    share's leaves."""
    from commefficient_tpu.models.laguna import MIXERS

    cfg = kimi_linear_tiny(dtype=jnp.float32)
    kind = cfg.layer_types[layer]
    x = jax.random.normal(jax.random.key(4), (1, T, cfg.hidden_size))
    whole, names = _seeded(Block(cfg, layer), x, seed=6)
    name = f"params/layer_{layer}"
    flat = {f"{name}/" + n.split("/", 1)[1]: a for n, a in zip(names, jax.tree.leaves(whole))}
    kw = _loss_kwargs(cfg)
    if kind == "kda_attention":
        H, d, heads_key = cfg.kda_heads, cfg.kda_head_dim, "kda_heads"
        one_cfg = type(cfg)(**{**cfg.__dict__, "kda_heads": 1})
        mixer = ref._kda
    else:
        H, d, heads_key = cfg.num_attention_heads_per_layer[layer], None, "num_attention_heads"
        one_cfg = type(cfg)(**{**cfg.__dict__, "num_attention_heads_per_layer": (1,) * len(
            cfg.layer_types)})
        mixer = ref._latent
    assert H == 4
    with jax.default_matmul_precision("highest"):
        want = mixer(flat, name, x[0], "float32", kw)                 # x + Mixer(norm(x))
        h = ref._rms(flat, f"{name}/attn_norm", x[0], cfg.rms_norm_eps, "float32")[None]
        total = x[0]
        for head in range(H):
            p = _head_share(kind, whole["params"]["attn"], head, H, d, cfg)
            y, _ = MIXERS[kind](one_cfg, layer).apply({"params": p}, h)
            share = {**flat, **{f"{name}/attn/{k}/{leaf}": v
                                for k, sub in p.items() for leaf, v in sub.items()}}
            np.testing.assert_allclose(
                y[0], mixer(share, name, x[0], "float32", {**kw, heads_key: 1}) - x[0], atol=2e-5)
            total = total + y[0]
    np.testing.assert_allclose(total, want, atol=5e-5)


# ---- the presets against the published config ------------------------------------------------

@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi_linear_48b_fedtext.json")) as f:
        return json.load(f)


def _listed(value):
    if isinstance(value, tuple):
        return list(value)
    if isinstance(value, dict):
        return {k: _listed(v) for k, v in value.items()}
    return value


def test_published_keys_are_the_configuration_files_and_the_catalogs(conf):
    for key, value in kimi_linear.PUBLISHED.items():
        if key == "linear_attn_config":
            value = {**value, **conf["published"][key]}
            assert _listed(value) == {**conf[key], **conf["published"][key]}, key
            continue
        assert _listed(value) == conf["published"].get(key, conf[key]), key
    assert conf["reduced"] == sorted(conf["published"], key=conf["reduced"].index)
    assert conf["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "vocab_size": 163840, "num_attention_heads": 32,
                                 "num_key_value_heads": 32, "linear_attn_config": {"num_heads": 32}}
    # the nested group is the published one but for the heads held
    assert conf["linear_attn_config"] == {**_listed(kimi_linear.PUBLISHED["linear_attn_config"]),
                                          "num_heads": 8}
    cut = kimi_linear_48b()
    assert cut.num_layers == conf["num_hidden_layers"] == 5
    assert len(cut.experts_held) == conf["num_experts"] and cut.vocab_held == conf["vocab_size"]
    kw = conf["reference"]["loss_kwargs"]
    assert list(cut.experts_held) == kw["experts_held"]
    assert list(cut.layer_types) == kw["layer_types"] == conf["flops_kwargs"]["layer_types"]
    assert list(cut.mlp_layer_types) == kw["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cut.kda_heads, cut.kda_head_dim, cut.kda_conv, cut.kda_chunk) == (8, 128, 4, 64)
    assert cut.num_attention_heads_per_layer == (8,) * 5 == (conf["num_attention_heads"],) * 5
    assert kw["kda_heads"] == kw["num_attention_heads"] == conf["flops_kwargs"]["heads"] == 8
    assert (cut.kv_lora_rank, cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim) == (
        512, 128, 64, 128)
    assert (cut.router, cut.moe_routed_scaling_factor, cut.shared_expert_intermediate_size) == (
        "sigmoid_scaled_shared", 2.446, 1024)
    assert kw["routed_scaling_factor"] == conf["routed_scaling_factor"]
    assert (conf["units_per_sample"], conf["unit"], conf["init"]) == (4096, "token", INIT)
    assert {"state_reset", "router_bias", "gate_bias", "aux_loss", "max_seq_len", "data",
            "init"} <= set(conf["assumed"])


def test_the_layer_lists_are_one_indexed():
    """``linear_attn_config`` counts layers from 1: the fourth layer (index
    3) is the first latent one, and the 27th, the last, is latent too."""
    kinds = kimi_linear.layer_kinds(kimi_linear.PUBLISHED, 27)
    assert [i + 1 for i, k in enumerate(kinds) if k == "latent_attention"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert kinds.count("kda_attention") == 20
    assert kimi_linear_48b().layer_types == ("kda_attention",) * 3 + (
        "latent_attention", "kda_attention")
    both = dict(kimi_linear.PUBLISHED, linear_attn_config=dict(
        kimi_linear.PUBLISHED["linear_attn_config"], full_attn_layers=(3, 4)))
    with pytest.raises(ValueError, match="layer 3"):
        kimi_linear.layer_kinds(both, 5)


def _sizes(cfg):
    shapes = jax.eval_shape(LagunaLM(cfg).init, jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    return {n: int(np.prod(a.shape))
            for n, a in zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))}


@pytest.mark.parametrize("heads,d,kda,latent", [
    (32, 602_433_408, 39_514_272, 29_114_880),      # the published cut, every head held
    (8, 464_820_000, 10_321_032, 8_274_432)],       # the cell's: 8 of each mixer's 32 heads
    ids=["all_heads", "the_cell"])
def test_the_cut_holds_its_parameters(conf, heads, d, kda, latent):
    cfg = kimi_linear.from_published(kimi_linear.PUBLISHED, layers=5, experts_held=range(8),
                                     vocab_held=20480, heads_held=heads % 32)
    sizes = _sizes(cfg)
    assert sum(sizes.values()) == d
    layer = lambda i: {n.split("/", 2)[2]: s for n, s in sizes.items()  # noqa: E731
                       if f"/layer_{i}/" in n}
    mixer = lambda i: sum(s for n, s in layer(i).items() if n.startswith("attn/"))  # noqa: E731
    # a KDA mixer, a latent mixer, the dense SwiGLU, an expert layer, the norms
    assert mixer(0) == mixer(1) == mixer(2) == mixer(4) == kda
    assert mixer(3) == latent
    assert sum(s for n, s in layer(0).items() if n.startswith("mlp/")) == 63_700_992
    assert sum(s for n, s in layer(1).items() if n.startswith("moe/")) == 64_290_816
    assert layer(1)["attn_norm/scale"] + layer(1)["mlp_norm/scale"] == 4_608
    assert sizes["params/embed/embedding"] + sizes["params/lm_head/kernel"] == 94_371_840
    if heads == 8:
        assert sizes == _sizes(kimi_linear_48b()) and d == conf["parameters_held"]


def test_no_leaf_has_a_small_last_dimension():
    """A leaf's last dimension lies in the chip's lanes of 128: the harness
    relays every leaf out of the flat vector, and a narrow one pads it."""
    shapes = jax.eval_shape(LagunaLM(kimi_linear_48b()).init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    narrow = {n: a.shape for n, a in zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))
              if a.shape[-1] < 128}
    assert narrow == {}


def test_required_operations_are_the_stated_arithmetic(conf):
    kw = conf["flops_kwargs"]
    # the chunked rule a position and head at chunk 64, d 128: the pairs, the
    # solve over K~ and V, P W and P U~, then three state products
    assert flops_kimi.kda_rule_per_position(**kw) == (
        63 * 128 + 65 * 128 + 63 * 256 + 65 * 256 + 6 * 128 * 128) == 147_456
    assert flops_kimi.attn_kda_flops_per_token(**kw) == 3 * 4 * 8 * 147_456
    assert flops_kimi.attn_latent_flops_per_token(**kw) == pytest.approx(
        3 * 8 * (2 * 192 + 2 * 128) * 4097 / 2)
    kda_proj = 2304 * 3072 + 2304 * 264 + 2 * 128 * 1024 + 1024 * 2304
    latent_proj = 2304 * 1536 + 2304 * 576 + 512 * 2048 + 1024 * 2304
    assert kda_proj + 1_032 + 12_288 + 128 == 10_321_032       # the weights, counted
    assert latent_proj + 512 == 8_274_432
    experts = 2 * (8 * 8 / 256) * 3 * 2304 * 1024
    fwd = (2 * 2304 * 20480 + 4 * 2 * kda_proj + 2 * latent_proj + 2 * 3 * 2304 * 9216
           + 4 * (2 * 2304 * 256 + 2 * 3 * 2304 * 1024 + experts))
    per_token = flops_kimi.kimi_flops_per_token(n_params=464_820_000, **kw)
    assert per_token == pytest.approx(3 * fwd + 3 * 4 * 8 * 147_456
                                      + 3 * 8 * 640 * 4097 / 2)
    # a round of 8,192 positions: 10.1 TFLOP forward + backward
    assert per_token * 8192 == pytest.approx(10.11e12, rel=0.01)


def test_the_entry_reports_the_state_kept_share(tiny):
    from commefficient_tpu.train.lm_train import _LmHooks

    hooks = _LmHooks(None, None, None, 2)
    acc = hooks.new_accumulator()
    for _ in range(3):
        hooks.accumulate(acc, 5.0, {k: float(v) for k, v in tiny["aux"].items()})
    row = hooks.epoch_row(epoch=0, lr=0.1, acc=acc, val={"nll": 1.0, "ppl": 2.7},
                          train_time=1.0, val_time=1.0, steps_per_epoch=3)
    assert row["kda_state_kept_share"] == pytest.approx(
        float(tiny["aux"]["kda/state_kept"]) / float(tiny["aux"]["kda/state_slots"]))
    plain = hooks.new_accumulator()
    hooks.accumulate(plain, 5.0, {"moe/held_assignments": 4.0})
    assert "kda_state_kept_share" not in hooks.epoch_row(
        epoch=0, lr=0.1, acc=plain, val={"nll": 1.0, "ppl": 2.7}, train_time=1.0,
        val_time=1.0, steps_per_epoch=1)
