"""models/keye.py at ``keye_tiny`` on the CPU, in float32, held to the plain
reference (``benchmark/reference/keye.py``, which imports nothing of the
program): loss and every gradient leaf; the selection against
``lax.top_k`` on random scores, crafted ties and short rows; a key outside
``S_t``; no ``[T, T]`` operand; one selection a layer for forward and
backward; ``vmap`` over clients; the index's leaf; M-RoPE's equal streams;
the router's weights; the expert shares of the whole layer; the presets
against the published keys, the cut's D and the required operations."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_keye, weights
from benchmark.reference import keye as ref
from commefficient_tpu.models import keye, laguna
from commefficient_tpu.models.keye import keye_tiny, keye_vl2
from commefficient_tpu.models.laguna import Block, LagunaLM
from commefficient_tpu.models.losses import causal_lm_loss
from commefficient_tpu.ops.pallas import indexed_attention as ia

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 128


def _loss_kwargs(cfg, experts_held=None, **more):
    half = cfg.head_dim // 2
    return dict(
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_attention_heads_per_layer[0],
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_full.rope_theta,
        mrope_section=[half // 4, 3 * half // 8, 3 * half // 8],     # [16, 24, 24] at 128
        indexer_num_heads=cfg.index_heads, indexer_head_dim=cfg.index_head_dim,
        topk=cfg.index_topk, num_experts_per_tok=cfg.num_experts_per_tok,
        rms_norm_eps=cfg.rms_norm_eps, query_block=64,
        experts_held=list(cfg.experts_held if experts_held is None else experts_held), **more)


def _seeded(model, *args, seed=3):
    shapes = jax.eval_shape(model.init, jax.random.key(0), *args)
    params = weights.make(shapes, seed, {"std": 0.02})
    return params, weights.leaf_names(params)


@pytest.fixture(scope="module")
def tiny():
    """Program and reference on the same seeded weights and batch."""
    cfg = keye_tiny(dtype=jnp.float32)
    model = LagunaLM(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_held)
    batch = {"input_ids": ids, "lm_labels": jnp.where(jnp.arange(T)[None, :] < 120, ids, -100)}
    params, names = _seeded(model, ids)
    grad = jax.jit(jax.value_and_grad(causal_lm_loss(model.apply, "float32"), has_aux=True))
    (loss, aux), grads = grad(params, batch)
    flat = dict(zip(names, jax.tree.leaves(params)))
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch, "float32", **_loss_kwargs(cfg)))(flat)
    return dict(cfg=cfg, model=model, params=params, batch=batch, loss=loss, aux=aux, grad=grad,
                grads=dict(zip(names, jax.tree.leaves(grads))), ref_loss=ref_loss,
                ref_grads=ref_grads)


LEAVES = weights.leaf_names(jax.eval_shape(
    LagunaLM(keye_tiny()).init, jax.random.key(0), jnp.zeros((1, T), jnp.int32)))


def test_loss_equals_the_reference(tiny):
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]), rel=1e-6)
    assert float(tiny["aux"]["token_count"]) == 2 * 119
    assert float(tiny["aux"]["moe/dropped"]) == 0.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"][leaf], tiny["ref_grads"][leaf]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(got - want)) <= 2e-5 * float(jnp.linalg.norm(want)) + 1e-12


@pytest.mark.parametrize("leaf", [n for n in LEAVES if "/index_" in n])
def test_the_index_leaves_take_no_gradient_from_the_loss(tiny, leaf):
    """``S_t`` is a constant of the backward pass and the config gives no
    index loss: in the round these leaves move by the weight decay alone."""
    assert not jnp.any(tiny["grads"][leaf]) and not jnp.any(tiny["ref_grads"][leaf])
    assert jnp.any(tiny["grads"][leaf.replace("index_proj", "q_proj")])


def test_the_counters_count_the_pairs_attended_to(tiny):
    """2 indexed layers x 2 rows of 128 with topk 32: ``min(t + 1, 32)`` keys
    a query, as the forward kernel counted what it admitted."""
    aux = tiny["aux"]
    assert float(aux["attn/causal_pairs"]) == 2 * 2 * T * (T + 1) / 2
    assert float(aux["attn/selected_pairs"]) == 2 * 2 * (32 * 33 / 2 + (T - 32) * 32)
    assert 0 <= float(aux["attn/select_ties"]) <= 2 * 2 * (T - 32)


# ---- the selection -----------------------------------------------------------------

def _operands(t=256, heads=4, kv=2, d=16, j=4, e=8, seed=0, b=2):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (b, t, heads, d)) / 4, n(ks[1], (b, t, kv, d)), n(ks[2], (b, t, kv, d)),
            n(ks[3], (b, t, j, e)), n(ks[4], (b, t, e)), n(ks[5], (b, t, j)))


def _top_k_set(scores, topk):
    """``[B, T, T]`` bool: ``lax.top_k``'s choice among each row's causal keys."""
    B, t, _ = scores.shape
    at = jnp.arange(t)
    causal = at[:, None] >= at[None, :]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    chosen = jnp.zeros((B, t, t), bool).at[
        jnp.arange(B)[:, None, None], at[None, :, None], idx].set(True)
    return chosen & causal


def _plain(q, k, v, qi, ki, w, topk):
    group = q.shape[2] // k.shape[2]
    scores = jnp.einsum("btj,btjs->bts", w, jax.nn.relu(jnp.einsum("btje,bse->btjs", qi, ki)))
    keep = _top_k_set(scores, topk)
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, group, 2))
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", p, jnp.repeat(v, group, 2)), keep


def _kernel_set(qi, ki, w, topk):
    """The set the kernels attend to, rebuilt from the selection kernel's
    threshold and cut on scores that are exact in any summation order."""
    scores = jnp.einsum("btj,btjs->bts", w, jax.nn.relu(jnp.einsum("btje,bse->btjs", qi, ki)))
    tau, cut, tied = ia.select_threshold(qi.transpose(0, 2, 1, 3), ki, w.transpose(0, 2, 1),
                                         topk=topk)
    at = jnp.arange(scores.shape[1])
    keep = (scores > tau[:, 0, :, None]) | (
        (scores == tau[:, 0, :, None]) & (at[None, None, :] <= cut[:, 0, :, None]))
    return keep & (at[:, None] >= at[None, :]), scores, tied[:, 0]


@pytest.mark.parametrize("case,topk", [("random", 32), ("random", 100), ("ties", 32),
                                       ("all_equal", 32), ("short", 256), ("short", 300)])
def test_the_selection_is_lax_top_ks_set(case, topk):
    """Scores made exact (one index head of width 1, unit weights:
    ``I[t, s] = relu(kI[s])``, or small integers), so that the kernel's
    floats are the test's: random distinct scores, crafted ties at the
    threshold (integers 0..3, a dozen keys a value), every score equal (the
    lowest ``topk`` positions), and rows with ``t + 1 <= topk``."""
    t = 256
    if case == "ties":
        _, _, _, qi, ki, w = _operands(t, seed=3)
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(w)
    else:
        ki = jnp.abs(jax.random.normal(jax.random.key(5), (2, t, 1))) + 0.5
        if case == "all_equal":
            ki = jnp.ones_like(ki)
        qi, w = jnp.ones((2, t, 1, 1)), jnp.ones((2, t, 1))
    got, scores, tied = _kernel_set(qi, ki, w, topk)
    want = _top_k_set(scores, topk)
    assert jnp.array_equal(got, want)
    counts = jnp.sum(got, -1)
    assert jnp.array_equal(counts, jnp.minimum(jnp.arange(t) + 1, topk)[None].repeat(2, 0))
    if case in ("ties", "all_equal"):
        assert float(jnp.sum(tied)) > 0
    if case == "all_equal":
        assert jnp.array_equal(got[0, 200], jnp.arange(t) < topk)     # ties to the lower s
    if case in ("random", "short"):
        assert float(jnp.sum(tied)) == 0


@pytest.mark.parametrize("t,topk", [(256, 32), (128, 128), (512, 100)])
def test_attention_over_the_set_equals_plain_attention(t, topk):
    a = _operands(t)
    got, counters = jax.jit(lambda *x: ia.indexed_attention(*x, topk=topk))(*a)
    want, keep = _plain(*a, topk)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(counters["selected_pairs"]) == float(jnp.sum(keep))
    assert float(counters["causal_pairs"]) == 2 * t * (t + 1) / 2
    f = lambda q, k, v: jnp.sum(jnp.sin(ia.indexed_attention(q, k, v, *a[3:], topk=topk)[0]))  # noqa: E731
    g = lambda q, k, v: jnp.sum(jnp.sin(_plain(q, k, v, *a[3:], topk)[0]))  # noqa: E731
    for x, y in zip(jax.jit(jax.grad(f, (0, 1, 2)))(*a[:3]), jax.grad(g, (0, 1, 2))(*a[:3])):
        np.testing.assert_allclose(x, y, atol=2e-5)


def test_a_key_outside_the_set_contributes_nothing():
    q, k, v, qi, ki, w = _operands(256, seed=2)
    out = ia.indexed_attention(q, k, v, qi, ki, w, topk=32)[0]
    keep = _plain(q, k, v, qi, ki, w, 32)[1]
    t = 200
    outside = int(jnp.argmin(jnp.where(jnp.arange(256) < t, keep[0, t], True)))
    inside = int(jnp.argmax(keep[0, t]))
    assert not keep[0, t, outside] and keep[0, t, inside] and outside < t

    def moved(s):
        return ia.indexed_attention(q, k.at[0, s].add(5.0), v.at[0, s].add(5.0), qi, ki, w,
                                    topk=32)[0]

    assert jnp.array_equal(moved(outside)[0, t], out[0, t])
    assert not jnp.allclose(moved(inside)[0, t], out[0, t])
    # and the index, not the position, decides: with topk >= T the same key is read
    full = ia.indexed_attention(q, k, v, qi, ki, w, topk=256)[0]
    moved_full = ia.indexed_attention(q, k.at[0, outside].add(5.0), v.at[0, outside].add(5.0),
                                      qi, ki, w, topk=256)[0]
    assert not jnp.allclose(moved_full[0, t], full[0, t])


def test_the_lowered_attention_holds_no_t_by_t_operand():
    a = _operands(640, b=1)                                    # five blocks of 128
    f = jax.grad(lambda q, k, v: jnp.sum(ia.indexed_attention(q, k, v, *a[3:], topk=128)[0]),
                 (0, 1, 2))
    text = jax.jit(f).lower(*a[:3]).as_text()
    assert "128x128x" in text                                  # the kernels' tiles
    assert "640x640" not in text


def test_sequence_length_must_be_whole_lanes():
    with pytest.raises(ValueError, match="multiple of 128"):
        ia.indexed_attention(*_operands(100), topk=32)


@pytest.fixture(scope="module")
def kernel_calls(tiny):
    """How often the gradient's jaxpr calls each kernel, by its name."""
    text = str(jax.make_jaxpr(tiny["grad"])(tiny["params"], tiny["batch"]))
    return lambda name: len(re.findall(rf"name={name}\b", text))


def test_forward_and_backward_share_one_selection_a_layer(tiny, kernel_calls):
    """The selection's thresholds cross ``remat`` as a named residual: the
    gradient program runs the selection kernel once a layer, so the
    recomputed block and both backward kernels rebuild the mask of the
    forward's set."""
    assert kernel_calls("indexed_select") == tiny["cfg"].num_layers


def test_the_forward_kernel_runs_once_a_layer(tiny, kernel_calls):
    """The forward kernel's output and log-sum-exp cross ``remat`` as named
    residuals too (``ATTEND_RESIDUAL``): the recomputed block makes ``q``,
    ``k``, ``v`` for the backward kernels and does not call ``indexed_fwd``
    again."""
    names = ("indexed_fwd", "indexed_dq", "indexed_dkv", "indexed_select")
    assert {name: kernel_calls(name) for name in names} == dict.fromkeys(
        names, tiny["cfg"].num_layers)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "mixed"])
def test_a_block_under_the_policy_has_the_gradients_of_a_block_without_remat(dtype):
    """What the policy saves is what the recomputation would have made: the
    same floats, parameters' and input's gradients alike."""
    cfg = keye_tiny(dtype=dtype)
    x = jax.random.normal(jax.random.key(4), (2, T, cfg.hidden_size))
    params, _ = _seeded(Block(cfg, 0), x)
    policy = jax.checkpoint_policies.save_only_these_names(
        ia.SELECT_RESIDUAL, ia.ATTEND_RESIDUAL)

    def grads(block):
        loss = lambda p, x: jnp.sum(jnp.sin(block(cfg, 0).apply(p, x)[0]))  # noqa: E731
        return jax.jit(jax.grad(loss, (0, 1)))(params, x)

    plain, kept = grads(Block), grads(laguna.nn.remat(Block, policy=policy))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kept)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in jax.tree.leaves(plain))


def test_vmap_over_clients_equals_a_loop_over_clients(tiny):
    loss_fn = causal_lm_loss(tiny["model"].apply, "float32")
    ids = jax.random.randint(jax.random.key(8), (3, 1, T), 0, 256)
    batches = {"input_ids": ids, "lm_labels": ids}

    def one(p, b):
        return loss_fn(p, b)[0]

    batched = jax.jit(jax.vmap(jax.value_and_grad(one), (None, 0)))(tiny["params"], batches)
    for c in range(3):
        loss, grads = jax.value_and_grad(one)(
            tiny["params"], {k: v[c] for k, v in batches.items()})
        assert float(batched[0][c]) == pytest.approx(float(loss), rel=1e-6)
        for x, y in zip(jax.tree.leaves(batched[1]), jax.tree.leaves(grads)):
            np.testing.assert_allclose(x[c], y, rtol=1e-5, atol=1e-6)


# ---- rotary positions, the router -----------------------------------------------------

def test_three_equal_mrope_streams_are_the_plain_rotary():
    """``mrope_section`` [16, 24, 24] over three id streams: equal streams
    give the program's tables; a stream that differs moves only its own
    section's frequency pairs."""
    sections = list(keye.PUBLISHED["rope_scaling"]["mrope_section"])
    theta = keye.PUBLISHED["rope_theta"]
    ids = jnp.arange(300)
    cos, sin = ref.rope_tables(jnp.stack([ids] * 3), 128, theta, sections)
    want = laguna.Rope(rope_theta=float(theta)).tables(300, 128)
    assert want[2] == 128 and cos.shape == (300, 64)
    np.testing.assert_allclose(cos, want[0], atol=2e-5)
    np.testing.assert_allclose(sin, want[1], atol=2e-5)
    height = ref.rope_tables(jnp.stack([ids, ids + 7, ids]), 128, theta, sections)[0]
    assert jnp.array_equal(height[:, :16], cos[:, :16])
    assert jnp.array_equal(height[:, 40:], cos[:, 40:])
    assert not jnp.allclose(height[:, 16:40], cos[:, 16:40])


def test_the_routers_weights_sum_to_one_over_the_top_k():
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(0), (50, 128)) * 3, -1)
    w = ref.routed_weights(probs, 8, 1.0)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    assert jnp.array_equal(jnp.sum(w > 0, -1), jnp.full(50, 8))
    top = jnp.argsort(-probs, -1)[:, :8]
    np.testing.assert_allclose(jnp.take_along_axis(w, top, -1),
                               jnp.take_along_axis(probs, top, -1)
                               / jnp.sum(jnp.take_along_axis(probs, top, -1), -1, keepdims=True),
                               atol=1e-6)


# ---- the shares of the whole layer -------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4's test: what all four expert shares of the 16 experts
    give (4 each), with attention (and the residual) counted once, is the
    uncut layer as the reference computes it with every expert held; and
    each share alone is the reference's layer with that share."""
    cfg = keye_tiny(dtype=jnp.float32)
    whole_cfg = type(cfg)(**{**cfg.__dict__, "experts_held": tuple(range(16))})
    x = jax.random.normal(jax.random.key(4), (1, T, cfg.hidden_size))
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
        y, counters, attended = Block(ccfg, 0).apply(p, x)
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
    with open(os.path.join(ROOT, "benchmark", "configs", "keye_vl2_30b_fedtext.json")) as f:
        return json.load(f)


def test_published_keys_are_the_configuration_files(conf):
    def plain(v):
        return ({k: plain(x) for k, x in v.items()} if isinstance(v, dict)
                else list(v) if isinstance(v, tuple) else v)

    for key, value in keye.PUBLISHED.items():
        assert plain(value) == conf["published"].get(key, conf[key]), key
    cut = keye_vl2()
    assert cut.num_layers == conf["num_hidden_layers"] == 4
    assert len(cut.experts_held) == conf["num_experts"] and cut.vocab_held == conf["vocab_size"]
    kw = conf["reference"]["loss_kwargs"]
    assert list(cut.experts_held) == kw["experts_held"]
    sa = conf["sa_config"]
    assert (cut.index_heads, cut.index_head_dim, cut.index_topk) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]) == (
        kw["indexer_num_heads"], kw["indexer_head_dim"], kw["topk"])
    assert kw["mrope_section"] == conf["rope_scaling"]["mrope_section"]
    assert kw["rope_theta"] == conf["rope_theta"] == cut.rope_full.rope_theta
    assert (cut.router, cut.qk_norm, cut.output_gate) == ("softmax_renormalised", True, False)
    assert set(cut.layer_types) == {"indexed_attention"}
    assert set(cut.mlp_layer_types) == {"sparse"}
    flops = conf["flops_kwargs"]
    assert (flops["layers"], flops["experts_held"], flops["vocab_held"], flops["seq"]) == (
        4, 8, 18992, 16384)


def test_the_cut_holds_314_4m_parameters(conf):
    shapes = jax.eval_shape(LagunaLM(keye_vl2()).init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    sizes = {n: int(np.prod(a.shape))
             for n, a in zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))}
    d = sum(sizes.values())
    assert d == conf["parameters_held"] == 314_395_648
    layer = {n.split("/", 2)[2]: s for n, s in sizes.items() if "/layer_0/" in n}
    # ISSUE 33 section 2: 18,874,368 + 256 + 2,260,992 + 262,144 + 4,096 + 37,748,736
    assert sum(s for n, s in layer.items()
               if n.startswith("attn/") and "_proj" in n and "index" not in n) == 18_874_368
    assert layer["attn/q_norm/scale"] + layer["attn/k_norm/scale"] == 256
    assert sum(s for n, s in layer.items() if "/index_" in n) == 2_260_992
    assert layer["moe/router/kernel"] == 262_144
    assert sum(s for n, s in layer.items() if "/experts/" in n) == 37_748_736
    assert sum(layer.values()) == 59_150_592


def test_the_cuts_expert_tiers_are_whole_tiles_of_a_rows_assignments():
    """A row of 16,384 positions sends 131,072 assignments, 8,192 of them
    to the 8 held experts under a uniform router: the floored first tier is
    4.5 times that (two lumps of a whole row's tokens on one expert, and what
    spills beside them), the second 8.5 (four lumps), in tiles of 512 rows."""
    cfg = keye_vl2()
    rows = [laguna._tier_rows(16384, cfg.num_experts_per_tok, len(cfg.experts_held),
                              cfg.num_experts, f, cfg.expert_tiling[0])
            for f in cfg.expert_row_tiers]
    assert rows == [36864, 69632] and cfg.expert_rows_floored
    assert all(r % cfg.expert_tiling[0] == 0 and r < 16384 * 8 for r in rows)
    # Laguna's presets keep the one tier and the tile they had
    xs2 = laguna.laguna_xs2()
    assert (xs2.expert_row_tiers, xs2.expert_tiling, xs2.expert_rows_floored) == (
        (laguna.FAST_ROWS_FACTOR,), (128, 512, 512), False)


def test_required_operations_are_the_issues_arithmetic(conf):
    kw = conf["flops_kwargs"]
    mean_chosen = (2048 * 2049 / 2 + (16384 - 2048) * 2048) / 16384          # 1,920.06
    assert flops_keye._mean_keys(16384, 2048) == pytest.approx(mean_chosen)
    assert flops_keye._mean_keys(16384) == 8192.5
    sparse = flops_keye.attn_sparse_flops_per_token(**kw)
    assert sparse == pytest.approx(3 * 4 * 4 * 32 * 128 * mean_chosen)       # 378 MFLOP
    index = flops_keye.attn_index_flops_per_token(**kw)
    assert index == pytest.approx(4 * (2 * 2_260_992 + 2 * 16 * 64 * 8192.5))  # 18 + 67 MFLOP
    per_token = flops_keye.keye_flops_per_token(n_params=314_395_648, **kw)
    dense = 2 * 4 * (18_874_368 + 262_144 + 0.5 * 4_718_592) + 2 * 2048 * 18992
    assert per_token == pytest.approx(3 * dense + sparse + index)
    # ISSUE 33's planning figure, ~41 TFLOP a round, put the index's three
    # projections under the backward too: 2 x 2 x 2.26M x 4 = 36 MFLOP a position more
    assert (per_token + 36.2e6) * 32768 == pytest.approx(40.8e12, rel=0.01)


def test_the_entry_reports_the_selected_share(tiny):
    """``attn/selected_share`` is the ratio of two counters that add up over
    layers, rows, clients and rounds; the entry's epoch row takes it where
    the sums end. Rows shorter than ``topk`` read 1."""
    from commefficient_tpu.train.lm_train import _LmHooks

    hooks = _LmHooks(None, None, None, 2)
    acc = hooks.new_accumulator()
    for _ in range(3):
        hooks.accumulate(acc, 5.0, {k: float(v) for k, v in tiny["aux"].items()})
    row = hooks.epoch_row(epoch=0, lr=0.1, acc=acc, val={"nll": 1.0, "ppl": 2.7},
                          train_time=1.0, val_time=1.0, steps_per_epoch=3)
    assert row["selected_share"] == pytest.approx((32 * 33 / 2 + 96 * 32) / (128 * 129 / 2))
    assert row["select_ties"] == 3 * float(tiny["aux"]["attn/select_ties"])
    plain = hooks.new_accumulator()
    hooks.accumulate(plain, 5.0, {"moe/held_assignments": 4.0})
    assert "selected_share" not in hooks.epoch_row(
        epoch=0, lr=0.1, acc=plain, val={"nll": 1.0, "ppl": 2.7}, train_time=1.0,
        val_time=1.0, steps_per_epoch=1)
    cfg = keye_tiny(dtype=jnp.float32)
    short = type(cfg)(**{**cfg.__dict__, "index_topk": 128})
    ids = tiny["batch"]["input_ids"]
    (_, counters) = LagunaLM(short).apply(tiny["params"], ids, ids)
    assert float(counters["attn/selected_pairs"]) == float(counters["attn/causal_pairs"])
