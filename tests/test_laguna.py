"""models/laguna.py at ``laguna_tiny`` on the CPU, in float32, held to the
plain reference (``benchmark/reference/laguna.py``, which imports nothing
of the program): loss and every gradient leaf, the two kinds of rotary
positions, the window, the grouped-query mapping, the expert layer's share
of the whole layer, its dropless branch, and ``vmap`` over clients."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import laguna as ref
from commefficient_tpu.models import laguna
from commefficient_tpu.models.laguna import LagunaLM, MoE, laguna_tiny, laguna_xs2
from commefficient_tpu.models.losses import causal_lm_loss
from commefficient_tpu.ops.pallas import library_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 128


def _loss_kwargs(cfg, experts_held=None):
    return dict(
        layer_types=list(cfg.layer_types), mlp_layer_types=list(cfg.mlp_layer_types),
        num_attention_heads_per_layer=list(cfg.num_attention_heads_per_layer),
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.sliding_window, num_experts_per_tok=cfg.num_experts_per_tok,
        moe_routed_scaling_factor=cfg.moe_routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
        rope_parameters=laguna.PUBLISHED["rope_parameters"],
        experts_held=list(cfg.experts_held if experts_held is None else experts_held))


def _seeded(model, *args, seed=3):
    shapes = jax.eval_shape(model.init, jax.random.key(0), *args)
    params = weights.make(shapes, seed, {"std": 0.02})
    return params, weights.leaf_names(params)


@pytest.fixture(scope="module")
def tiny():
    """Program and reference on the same seeded weights and batch."""
    cfg = laguna_tiny(dtype=jnp.float32)
    model = LagunaLM(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_held)
    batch = {"input_ids": ids, "lm_labels": jnp.where(jnp.arange(T)[None, :] < 120, ids, -100)}
    params, names = _seeded(model, ids)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        causal_lm_loss(model.apply, "float32"), has_aux=True))(params, batch)
    flat = dict(zip(names, jax.tree.leaves(params)))
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch, "float32", **_loss_kwargs(cfg)))(flat)
    return dict(cfg=cfg, model=model, params=params, batch=batch, loss=loss, aux=aux,
                grads=dict(zip(names, jax.tree.leaves(grads))), ref_loss=ref_loss,
                ref_grads=ref_grads)


LEAVES = weights.leaf_names(jax.eval_shape(
    LagunaLM(laguna_tiny()).init, jax.random.key(0), jnp.zeros((1, T), jnp.int32)))


def test_loss_equals_the_reference(tiny):
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]), rel=1e-6)
    assert float(tiny["aux"]["token_count"]) == 2 * 119
    assert float(tiny["aux"]["moe/dropped"]) == 0.0
    # 4 routed layers x 256 tokens x top-2, a quarter of the experts held
    assert 0.6 * 512 < float(tiny["aux"]["moe/held_assignments"]) < 1.4 * 512


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(tiny, leaf):
    got, want = tiny["grads"][leaf], tiny["ref_grads"][leaf]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(got - want)) <= 2e-5 * float(jnp.linalg.norm(want)) + 1e-12


def test_logits_path_gives_the_same_loss(tiny):
    from commefficient_tpu.models.losses import softmax_cross_entropy_sum

    logits, _ = tiny["model"].apply(tiny["params"], tiny["batch"]["input_ids"])
    assert logits.shape == (2, T, 256) and logits.dtype == jnp.float32
    s, n = softmax_cross_entropy_sum(logits[:, :-1], tiny["batch"]["lm_labels"][:, 1:])
    assert float(s / n) == pytest.approx(float(tiny["loss"]), rel=1e-6)


# ---- rotary positions -----------------------------------------------------------

def test_plain_rotary_angles_are_theta_to_the_minus_i_over_half():
    cos, sin, r = laguna.Rope(rope_theta=10000.0).tables(6, 8)
    assert r == 8
    for t in (0, 1, 5):
        for i in range(4):
            angle = t * 10000.0 ** (-i / 4)
            assert float(cos[t, i]) == pytest.approx(math.cos(angle), abs=1e-6)
            assert float(sin[t, i]) == pytest.approx(math.sin(angle), abs=1e-6)


def test_yarn_angles_on_half_the_head_as_published():
    rope = laguna.Rope(**laguna.PUBLISHED["rope_parameters"]["full_attention"])
    cos, sin, r = rope.tables(5, 128)
    assert r == 64 and cos.shape == (5, 32)
    # correction dims of beta_fast 64 and beta_slow 1 at 4,096 positions: 5 and 16
    dim = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(500000))  # noqa: E731
    assert (math.floor(dim(64)), math.ceil(dim(1))) == (5, 16)
    for i, ramp in ((0, 0.0), (5, 0.0), (8, 3 / 11), (16, 1.0), (31, 1.0)):
        f = 500000.0 ** (-i / 32)
        inv = f / 64 * ramp + f * (1 - ramp)
        assert float(cos[3, i]) == pytest.approx(1.4158883083359672 * math.cos(3 * inv), abs=1e-6)
        assert float(sin[3, i]) == pytest.approx(1.4158883083359672 * math.sin(3 * inv), abs=1e-6)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_tables_equal_the_references(kind):
    spec = laguna.PUBLISHED["rope_parameters"][kind]
    got = laguna.Rope(**spec).tables(300, 128)
    want = ref.rope_tables(300, 128, spec)
    assert got[2] == want[2]
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)


def test_rotation_touches_only_the_rotated_width():
    x = jax.random.normal(jax.random.key(0), (1, 4, 2, 16))
    cos, sin, r = laguna.Rope(partial_rotary_factor=0.5).tables(4, 16)
    y = laguna._rotate(x, cos, sin, r)
    assert r == 8 and jnp.array_equal(y[..., 8:], x[..., 8:])
    assert jnp.array_equal(y[:, 0], x[:, 0])            # position 0 is not rotated
    np.testing.assert_allclose(jnp.linalg.norm(y[..., :8], axis=-1),
                               jnp.linalg.norm(x[..., :8], axis=-1), rtol=1e-5)


# ---- attention --------------------------------------------------------------------

def _plain_attention(q, k, v, window):
    B, Tq, H, d = q.shape
    group = H // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)   # head j reads j // group
    s = jnp.einsum("bthd,bshd->bhts", q, k)
    t = jnp.arange(Tq)
    keep = t[:, None] >= t[None, :]
    if window is not None:
        keep &= (t[:, None] - t[None, :]) < window
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


def _qkv(heads, kv=2, t=256, d=16, seed=0):
    key = jax.random.key(seed)
    return (jax.random.normal(jax.random.fold_in(key, 0), (2, t, heads, d)) / 4,
            jax.random.normal(jax.random.fold_in(key, 1), (2, t, kv, d)),
            jax.random.normal(jax.random.fold_in(key, 2), (2, t, kv, d)))


@pytest.mark.parametrize("heads", [4, 8])
@pytest.mark.parametrize("window", [None, 8])
def test_grouped_queries_read_their_own_kv_head(heads, window):
    q, k, v = _qkv(heads)
    f = lambda *a: jnp.sum(jnp.sin(library_kernels.banded_attention(*a, window=window)))  # noqa: E731
    g = lambda *a: jnp.sum(jnp.sin(_plain_attention(*a, window)))  # noqa: E731
    got, want = jax.value_and_grad(f, (0, 1, 2))(q, k, v), jax.value_and_grad(g, (0, 1, 2))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_key_a_window_back_contributes_nothing():
    q, k, v = _qkv(8)
    out = library_kernels.banded_attention(q, k, v, window=8)
    t = 200
    far = (k.at[:, t - 8].add(5.0), v.at[:, t - 8].add(5.0))     # 8 back: outside
    near = (k.at[:, t - 7].add(5.0), v.at[:, t - 7].add(5.0))    # 7 back: the window's edge
    assert jnp.array_equal(library_kernels.banded_attention(q, *far, window=8)[:, t], out[:, t])
    assert not jnp.allclose(library_kernels.banded_attention(q, *near, window=8)[:, t], out[:, t])
    # and with no window the same key is read
    full = library_kernels.banded_attention(q, k, v)
    assert not jnp.allclose(library_kernels.banded_attention(q, *far)[:, t], full[:, t])


@pytest.mark.parametrize("window", [None, 8])
def test_the_lowered_attention_holds_no_t_by_t_operand(window):
    q, k, v = _qkv(8, t=640)                                   # five blocks of 128
    f = jax.grad(lambda *a: jnp.sum(library_kernels.banded_attention(*a, window=window)), (0, 1, 2))
    text = jax.jit(f).lower(q, k, v).as_text()
    assert "128x128x" in text                                  # the kernel's blocks
    assert "640x640" not in text


def test_sequence_length_must_be_whole_lanes():
    q, k, v = _qkv(4, t=100)
    with pytest.raises(ValueError, match="multiple of 128"):
        library_kernels.banded_attention(q, k, v)


# ---- the expert layer -----------------------------------------------------------------

def _moe_setup(experts_held, n_tokens=256, seed=5, **kw):
    cfg = laguna_tiny(dtype=jnp.float32)
    cfg = type(cfg)(**{**cfg.__dict__, "experts_held": tuple(experts_held), **kw})
    layer = MoE(cfg)
    h = jax.random.normal(jax.random.key(seed), (2, n_tokens // 2, cfg.hidden_size))
    return cfg, layer, h, _seeded(layer, h, seed=seed)[0]


def _ref_moe(cfg, p, h, experts_held, shared=True):
    flat = {"L/moe/" + n.split("/", 1)[1]: a
            for n, a in zip(weights.leaf_names(p), jax.tree.leaves(p))}
    return ref.moe(flat, "L", h.reshape(-1, h.shape[-1]), "float32",
                   top_k=cfg.num_experts_per_tok, scaling=cfg.moe_routed_scaling_factor,
                   experts_held=list(experts_held), shared=shared).reshape(h.shape)


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4's test: every chip's routed part (disjoint shares of
    the 16 experts, 4 each), plus the shared expert counted once, is the
    whole layer as the reference computes it with every expert held."""
    cfg, _layer, h, whole = _moe_setup(range(16))
    want = _ref_moe(cfg, whole, h, range(16))
    total, held = 0.0, 0.0
    for chip in range(4):
        ids = range(4 * chip, 4 * chip + 4)
        ccfg = type(cfg)(**{**cfg.__dict__, "experts_held": tuple(ids)})
        p = {"params": {**whole["params"], "experts": {
            k: v[4 * chip:4 * chip + 4] for k, v in whole["params"]["experts"].items()}}}
        y, counters = MoE(ccfg, shared=chip == 0).apply(
            {"params": {k: v for k, v in p["params"].items() if chip == 0 or k != "shared"}}, h)
        np.testing.assert_allclose(
            y, _ref_moe(cfg, p, h, ids, shared=chip == 0), atol=1e-5)
        total, held = total + y, held + float(counters["moe/held_assignments"])
        assert float(counters["moe/dropped"]) == 0.0
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert held == 256 * cfg.num_experts_per_tok          # every assignment is held somewhere


@pytest.mark.parametrize("forced", [False, True])
def test_nothing_is_dropped_whatever_the_router_sends(forced):
    """One expert held of 16: the fast branch moves 128 rows. A router
    forced to send every token to that expert fills 256: the branch that
    moves every row is taken and gives the reference's numbers."""
    cfg, layer, h, p = _moe_setup([2])
    assert laguna._tier_rows(256, 2, 1, 16, laguna.FAST_ROWS_FACTOR) == 128 < 256 * 2
    if forced:
        h = jnp.abs(h)
        router = jnp.zeros_like(p["params"]["router"]["kernel"]).at[:, 2].set(1.0)
        p = {"params": {**p["params"], "router": {"kernel": router}}}
    f = lambda p, h: layer.apply(p, h)  # noqa: E731
    (y, counters), pull = jax.vjp(f, p, h)
    assert float(counters["moe/dropped"]) == 0.0
    assert float(counters["moe/held_assignments"]) == (256 if forced else pytest.approx(32, abs=20))
    assert float(counters["moe/max_expert_load"]) == float(counters["moe/held_assignments"])
    want, ref_pull = jax.vjp(lambda p, h: _ref_moe(cfg, p, h, [2]), p, h)
    np.testing.assert_allclose(y, want, atol=1e-5)
    ct = jax.random.normal(jax.random.key(9), y.shape)
    zero = jax.tree.map(jnp.zeros_like, counters)
    for a, b in zip(jax.tree.leaves(pull((ct, zero))), jax.tree.leaves(ref_pull(ct))):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_vmap_over_clients_equals_a_loop_over_clients():
    cfg, layer, _, p = _moe_setup([2, 5, 11])
    hs = jax.random.normal(jax.random.key(4), (3, 2, 128, cfg.hidden_size))
    hs = hs.at[1].set(jnp.abs(hs[1]) * 3)      # clients route apart

    def one(p, h):
        y, counters = layer.apply(p, h)
        return jnp.sum(jnp.sin(y)), counters

    def batched(p, hs):
        losses, counters = jax.vmap(lambda h: one(p, h))(hs)
        return jnp.sum(losses), counters

    def looped(p, hs):
        outs = [one(p, hs[i]) for i in range(hs.shape[0])]
        return sum(o[0] for o in outs), jax.tree.map(lambda *c: jnp.stack(c), *[o[1] for o in outs])

    (a, ca), ga = jax.jit(jax.value_and_grad(batched, (0, 1), has_aux=True))(p, hs)
    (b, cb), gb = jax.value_and_grad(looped, (0, 1), has_aux=True)(p, hs)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    assert jax.tree.all(jax.tree.map(jnp.array_equal, ca, cb))
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def test_the_counter_reads_the_rows_the_branch_took(monkeypatch):
    """``moe/dropped`` counts what the branch that ran left out, so it can
    fail: with the fast branch forced on a client whose router fills 256 rows
    of one expert, the 128 rows past the branch's own are counted, and the
    layer's numbers are no longer the reference's."""
    cfg, layer, h, p = _moe_setup([2])
    h = jnp.abs(h)
    router = jnp.zeros_like(p["params"]["router"]["kernel"]).at[:, 2].set(1.0)
    p = {"params": {**p["params"], "router": {"kernel": router}}}
    sound, counters = layer.apply(p, h)
    assert float(counters["moe/dropped"]) == 0.0
    monkeypatch.setattr(jax.lax, "cond", lambda pred, fast, every, *a: fast(*a))
    short, counters = layer.apply(p, h)
    fast = laguna._tier_rows(256, 2, 1, 16, laguna.FAST_ROWS_FACTOR)
    assert float(counters["moe/dropped"]) == 256 - fast == 128
    assert not jnp.allclose(short, sound, atol=1e-3)


@pytest.mark.parametrize("sent,tier", [("free", 128), ("one", 256), ("both", 512)])
def test_tiers_and_a_floor_change_no_number(sent, tier):
    """Two experts held of 16, tiers of 128 and 256 rows under the 512 of
    every row, the first floored: a free router's ~64 rows run as 128, a
    router forced onto one held expert fills the second tier, onto both the
    last resort; each gives the reference's numbers and drops nothing."""
    cfg, layer, h, p = _moe_setup([2, 5], expert_row_tiers=(2.0, 4.0), expert_rows_floored=True)
    assert [laguna._tier_rows(256, 2, 2, 16, f) for f in (2.0, 4.0)] == [128, 256]
    if sent != "free":
        h = jnp.abs(h)
        router = jnp.zeros_like(p["params"]["router"]["kernel"]).at[:, 2].set(1.0)
        router = router.at[:, 5].set(1.0 if sent == "both" else -1.0)
        p = {"params": {**p["params"], "router": {"kernel": router}}}
    (y, counters), pull = jax.vjp(lambda p, h: layer.apply(p, h), p, h)
    held = float(counters["moe/held_assignments"])
    assert float(counters["moe/dropped"]) == 0.0
    assert {128: held <= 128, 256: held == 256, 512: held == 512}[tier]
    want, ref_pull = jax.vjp(lambda p, h: _ref_moe(cfg, p, h, [2, 5]), p, h)
    np.testing.assert_allclose(y, want, atol=1e-5)
    ct = jax.random.normal(jax.random.key(9), y.shape)
    zero = jax.tree.map(jnp.zeros_like, counters)
    for a, b in zip(jax.tree.leaves(pull((ct, zero))), jax.tree.leaves(ref_pull(ct))):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("sizes,want", [([3, 0, 5], [3, 0, 125]), ([100, 20, 8], [100, 20, 8]),
                                        ([200, 0, 0], [200, 0, 0]), ([0, 0, 0], [0, 0, 128])])
def test_the_floor_fills_the_last_slot_and_never_shrinks_one(sizes, want):
    assert laguna._floored(jnp.array(sizes, jnp.int32), 128).tolist() == want


@pytest.mark.parametrize("tiling", [(128, 512, 512), (256, 32, 16), (128, 64, 32)])
def test_the_grouped_product_is_the_same_at_every_tile(tiling):
    key = jax.random.key(0)
    rows = jax.random.normal(key, (512, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 64, 32))
    sizes = jnp.array([10, 300, 50], jnp.int32)
    kernel = lambda rows, w: library_kernels.grouped_product(rows, w, sizes, tiling)  # noqa: E731
    plain = lambda rows, w: library_kernels._grouped_product_plain(rows, w, sizes)  # noqa: E731
    np.testing.assert_allclose(kernel(rows, w), plain(rows, w), atol=1e-4)
    ct = jax.random.normal(jax.random.fold_in(key, 2), (512, 32))
    for a, b in zip(jax.vjp(kernel, rows, w)[1](ct), jax.vjp(plain, rows, w)[1](ct)):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_the_grouped_product_equals_a_dense_masked_product():
    """The library kernel (interpreted here; compiled on the chip) on rows of
    uneven groups, one of them empty, with a tail that belongs to none,
    against the plain masked einsum that stands in for it inside a
    ``shard_map`` on the CPU: forward, and both cotangents."""
    key = jax.random.key(0)
    rows = jax.random.normal(key, (256, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, 32))
    sizes = jnp.array([10, 100, 0, 50], jnp.int32)
    kernel = lambda rows, w: library_kernels.grouped_product(rows, w, sizes)  # noqa: E731
    plain = lambda rows, w: library_kernels._grouped_product_plain(rows, w, sizes)  # noqa: E731
    np.testing.assert_allclose(kernel(rows, w), plain(rows, w), atol=1e-4)
    assert not jnp.any(kernel(rows, w)[160:])
    ct = jax.random.normal(jax.random.fold_in(key, 2), (256, 32))
    for a, b in zip(jax.vjp(kernel, rows, w)[1](ct), jax.vjp(plain, rows, w)[1](ct)):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_the_library_is_as_it_was_after_a_call():
    """The ``vma`` typing lasts one call: inside a ``shard_map`` the name
    ``jax`` in the library's modules is the typed stand-in while the call is
    open and JAX itself after; outside one (no operand varies over a mesh
    axis) it is never replaced."""
    from jax.sharding import PartitionSpec as P

    modules = (library_kernels._megablox.backend, library_kernels._splash)
    seen = []

    def look(x):
        with library_kernels._library_types_as(x):
            seen.append([type(m.jax).__name__ for m in modules])
            struct = modules[0].jax.ShapeDtypeStruct((3,), jnp.float32)
        seen.append([m.jax is jax for m in modules])
        return x, struct

    assert not look(jnp.zeros(4))[1].vma
    mesh = jax.make_mesh((2,), ("w",))
    typed = []
    jax.shard_map(lambda x: typed.append(look(x)[1]) or x, mesh=mesh, in_specs=P("w"),
                  out_specs=P("w"))(jnp.zeros(4))
    assert typed[0].vma == {"w"}
    assert seen == [["module"] * 2, [True] * 2, ["_TypedVarying"] * 2, [True] * 2]


# ---- the presets against the published config ---------------------------------------------

def test_published_keys_are_the_configuration_files():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna_xs2_fedtext.json")) as f:
        conf = json.load(f)
    for key, value in laguna.PUBLISHED.items():
        want = conf["published"].get(key, conf[key])
        if isinstance(value, tuple):
            value = list(value)
        assert value == want, key
    cut = laguna_xs2()
    assert cut.num_layers == conf["num_hidden_layers"] == 5
    assert len(cut.experts_held) == conf["num_experts"] and cut.vocab_held == conf["vocab_size"]
    assert list(cut.experts_held) == conf["reference"]["loss_kwargs"]["experts_held"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert list(getattr(cut, key)) == conf["reference"]["loss_kwargs"][key] == conf[key][:5]


def test_the_cut_holds_389_6m_parameters():
    shapes = jax.eval_shape(LagunaLM(laguna_xs2()).init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    d = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert d == 389_634_048 and abs(d - 389.6e6) / 389.6e6 < 0.01


def test_required_operations_are_the_issues_arithmetic():
    from benchmark import flops_laguna, run

    kw = run.load_cell("laguna_uncompressed")["config_file"]["flops_kwargs"]
    per_token = flops_laguna.laguna_flops_per_token(n_params=389_634_048, **kw)
    # 266.4M product weights a token (x 6) and ~4.6 TFLOP of attention a round of 16,384
    assert per_token * 16384 == pytest.approx(30.8e12, rel=0.01)
    window = flops_laguna.attn_window_flops_per_token(**kw)
    # 3 sliding layers x 64 heads x 128 x 4 x mean(min(t + 1, 512)) x 3
    assert window == pytest.approx(3 * 3 * 64 * 128 * 4 * (512 * 513 / 2 + 1536 * 512) / 2048)
    assert flops_laguna.experts_per_token(**kw) == pytest.approx(2 * 4 * 0.25 * 3 * 2048 * 512)


def test_the_experts_least_bytes_are_weights_three_times_and_rows_five():
    """Per client and routed layer: 8 experts x 3 matrices of 2,048 x 512 read
    forward, read backward, their gradients written; 1,024 expected rows of
    2,048 read, written, and in the backward pass read twice and written:
    bfloat16. Four clients, four routed layers: 2.75 GB a round."""
    from benchmark import kernel_bytes_laguna, run

    work = run.load_cell("laguna_uncompressed")["traffic_file"]["reference"]
    got = kernel_bytes_laguna.moe_experts_bytes(d=389_634_048, **work)
    assert got == 2 * 4 * 4 * (3 * 3 * 8 * 2048 * 512 + 5 * 1024 * 2048) == 2_751_463_424
