"""The dense decode's zero-HH re-sketch at k scale (PR 27).

``SketchCompressor.server_update`` subtracts from the error bank the sketch
of the ``<= k`` pairs the top-k kept (``compact_nonzero_tree`` ->
``sketch_sparse``), not ``sketch_vec`` of a ``[D]`` vector that is zero
everywhere else. Pinned here on the CPU, where the matmul path is exact
float32 and the two must agree to summation order:

  * the table from the pairs equals ``sketch_vec(update)`` over specs that
    exercise every row shape, for 0, fewer than k and exactly k nonzeros;
  * ``server_update``'s ``(delta, m, e)`` over three rounds equals the
    parent's formula written out with ``sketch_vec``, with and without
    dampening, and dampening compacts once for both branches;
  * ``compact_nonzero_tree`` equals ``compact_nonzero`` bit for bit;
  * the lowered decode holds no ``sketch_vec`` under ``ef_resketch``.
"""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE
from test_sketch_decode import SKETCH, _compiled_round_text

import commefficient_tpu.compress.sketch as sketch_mod
from commefficient_tpu.ops.countsketch import (
    CountSketch,
    estimate_at,
    sketch_sparse,
    sketch_vec,
)
from commefficient_tpu.ops.topk import compact_nonzero, compact_nonzero_tree
from commefficient_tpu.utils.config import Config

D, K = 5003, 40
# every row shape sketch_vec has: riffle factor 1 alone (r=1) and > 1,
# disjoint pools (band 1) and banded, both hash families, both storage
# dtypes, the scramble off and at its adaptive block
SPECS = {
    "default": dict(c=1024, r=3),
    "one_row": dict(c=1024, r=1),
    "band1": dict(c=1024, r=3, band=1),
    "poly4": dict(c=1024, r=3, hash_family="poly4"),
    "bf16_table": dict(c=1024, r=3, table_dtype=jnp.bfloat16),
    "no_scramble": dict(c=1024, r=3, scramble_block=0),
    "five_rows_small_m": dict(c=512, r=5, m=64),
}


def _spec(name):
    return CountSketch(d=D, seed=7, **SPECS[name])


def _update(nonzeros, seed=0):
    """A dense [D] vector with ``nonzeros`` nonzeros, the first and the
    last coordinate among them."""
    rng = np.random.default_rng(seed)
    v = np.zeros(D, np.float32)
    if nonzeros:
        pos = np.concatenate([[0, D - 1], 1 + rng.choice(
            D - 2, size=nonzeros - 2, replace=False)])
        v[pos] = rng.normal(size=nonzeros).astype(np.float32) + 2.0
    return jnp.asarray(v)


def test_specs_cover_the_row_shapes():
    factors = {n: [_spec(n)._factor(r) for r in range(_spec(n).r)]
               for n in SPECS}
    assert factors["one_row"] == [1]
    assert all(f > 1 for f in factors["default"][1:])
    assert _spec("band1").u_row(0) == 1 and _spec("default").u_row(0) > 1
    assert _spec("no_scramble").sblock == 0 and _spec("default").sblock > 0


@pytest.mark.parametrize("nonzeros", [0, 17, K], ids=["none", "fewer", "k"])
@pytest.mark.parametrize("name", sorted(SPECS))
def test_pairs_table_equals_the_dense_pass(name, nonzeros):
    spec = _spec(name)._replace(table_dtype=jnp.float32)  # _spec_acc
    update = _update(nonzeros)
    idx, val = compact_nonzero_tree(update, K)
    assert int(jnp.sum(val != 0)) == nonzeros
    np.testing.assert_allclose(
        np.asarray(sketch_sparse(spec, idx, val)),
        np.asarray(sketch_vec(spec, update)), atol=1e-6)


# ---- server_update against the parent's formula ----------------------------


def _compressor(name, **kw):
    spec = _spec(name)
    cfg = Config(mode="sketch", k=K, num_rows=spec.r, num_cols=spec.c,
                 error_type="virtual", virtual_momentum=0.9,
                 topk_method="threshold", **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the dampening gate's warning
        comp = sketch_mod.SketchCompressor(cfg, D, spec)
        comp.resolved_dampening()
    return comp


def _parent_update(comp, momentum, error, agg, lr):
    """``server_update``'s virtual branch as the parent commit had it: one
    more dense ``sketch_vec`` of the update, and a compaction of its own
    for the dampening."""
    cfg, spec = comp.cfg, comp.spec
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    m = cfg.virtual_momentum * f32(momentum) + f32(agg)
    e = f32(error) + lr * m
    update = comp.unsketch(spec, e, cfg.k)
    e = e - sketch_vec(spec._replace(table_dtype=jnp.float32), update)
    if cfg.error_decay != 1.0:
        e = cfg.error_decay * e
    if comp.resolved_dampening():
        idx, val = compact_nonzero(update, cfg.k)
        at = jnp.where(val != 0, estimate_at(spec, m, idx), 0.0)
        m = m - sketch_sparse(spec, idx, at)
    return update, m.astype(spec.table_dtype), e.astype(spec.table_dtype)


CASES = {
    "default": {},
    "bf16_table": {},
    "poly4": {},
    "band1": dict(error_decay=0.9),
    "five_rows_small_m": dict(momentum_dampening=True,
                              allow_unstable_sketch_dampening=True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_server_update_equals_the_parents_formula(name):
    comp = _compressor(name, **CASES[name])
    spec = comp.spec
    rng = np.random.default_rng(1)
    m_new = e_new = m_old = e_old = spec.empty()
    step = jax.jit(lambda m, e, a: comp.server_update(m, e, (), a, 0.3, 0))
    for _ in range(3):
        agg = sketch_vec(spec, jnp.asarray(
            rng.normal(size=D).astype(np.float32) ** 3))
        delta, m_new, e_new, _ = step(m_new, e_new, agg)
        want, m_old, e_old = _parent_update(comp, m_old, e_old, agg, 0.3)
        assert 0 < int(jnp.sum(delta != 0)) <= K
        for got, ref in ((delta, want), (m_new, m_old), (e_new, e_old)):
            assert got.dtype == ref.dtype
            got, ref = (np.asarray(x, np.float32) for x in (got, ref))
            # float32 summation order; under bfloat16 storage that order
            # can flip a stored bank's last bit, 2^-8 of the entry, which a
            # later subtraction leaves on a small remainder
            atol = (1e-5 if spec.table_dtype == jnp.float32
                    else 2.0 ** -7 * np.abs(ref).max())
            np.testing.assert_allclose(got, ref, atol=atol)


@pytest.mark.parametrize("error_type", ["virtual", "none"])
def test_dampening_compacts_once(monkeypatch, error_type):
    """With ``momentum_dampening`` on, the error feedback's pairs feed the
    dampening too: one compaction a round, also where there is no error
    bank and the dampening compacts for itself."""
    calls = []

    def counted(v, k):
        calls.append(v.shape)
        return compact_nonzero_tree(v, k)

    monkeypatch.setattr(sketch_mod, "compact_nonzero_tree", counted)
    comp = _compressor("default", momentum_dampening=True,
                       allow_unstable_sketch_dampening=True)
    comp.cfg = comp.cfg.replace(error_type=error_type)
    t = sketch_vec(comp.spec, _update(K, seed=3))
    jax.make_jaxpr(lambda m, e, a: comp.server_update(m, e, (), a, 0.3, 0))(
        t, t, t)
    assert calls == [(D,)]


# ---- the compaction ---------------------------------------------------------


def _vector(n, positions):
    v = np.zeros(n, np.float32)
    v[list(positions)] = 1.0 + np.arange(len(positions), dtype=np.float32)
    return jnp.asarray(v)


TREE_CASES = {
    "first_and_last": (40000, [0, 39999], 8),
    "one_block": (40000, range(20480, 20480 + 100), 128),
    "more_than_k": (40000, range(5, 40000, 97), 50),
    "exactly_k": (40000, range(0, 40000, 400), 100),
    "all_zero": (40000, [], 16),
    "dense_tail": (16384 + 3, [16383, 16384, 16385, 16386], 4),
    "shorter_than_a_row": (100, [0, 50, 99], 200),
    "three_levels": (128 * 128 * 2 + 1, [0, 16384, 32767, 32768], 6),
}


@pytest.mark.parametrize("name", sorted(TREE_CASES))
def test_tree_compaction_equals_compact_nonzero(name):
    n, positions, k = TREE_CASES[name]
    v = _vector(n, positions)
    want = compact_nonzero(v, k)
    got = jax.jit(lambda x: compact_nonzero_tree(x, k))(v)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    kept = min(k, n, len(list(positions)))
    np.testing.assert_array_equal(np.asarray(got[0])[:kept],
                                  sorted(positions)[:kept])


# ---- what the lowered decode holds ------------------------------------------


def _ops_under(text, scope):
    """(primitive path, output element count) of every instruction whose
    ``op_name`` lies under ``scope``."""
    out = []
    for ln in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', ln)
        shape = re.search(r"=\s*[a-z]+[0-9]+\[([\d,]*)\]", ln)
        if m and shape and re.search(r"\b" + scope + r"\b", m.group(1)):
            n = int(np.prod([int(x) for x in shape.group(1).split(",") if x]))
            out.append((m.group(1).split(scope, 1)[1], n, ln))
    return out


def test_hlo_dense_round_has_no_dense_pass_under_ef_resketch():
    """The compiled dense-decode round: under ``ef_resketch`` no
    ``sketch_vec`` einsum (the ``[nc, m] x [m, u, s]`` dot_general) and no
    block-scramble gather (the one gather that returns ``d_eff``
    elements); ``encode``, which is ``sketch_vec``, proves both markers.
    The compaction's and the scatter's ops are there, under that name and
    not under ``topk_select`` too."""
    kw = {**SKETCH, "k": 10, "error_type": "virtual", "virtual_momentum": 0.9}
    sess, text = _compiled_round_text(
        Config(sketch_decode="dense", **kw, **BASE))
    d_eff = sess.rungs[0].spec.d_eff

    def dense_pass(ops):
        einsum = [p for p, _n, _l in ops if "cm,mus->cus" in p]
        scramble = [p for p, n, ln in ops
                    if p.endswith("/gather") and " gather(" in ln and n == d_eff]
        return einsum, scramble

    einsum, scramble = dense_pass(_ops_under(text, "encode"))
    assert einsum and scramble  # marker validity
    under = _ops_under(text, "ef_resketch")
    assert dense_pass(under) == ([], [])
    paths = {p for p, _n, _l in under}
    assert not any("topk_select" in p for p in paths)
    for prim in ("cumsum", "gather", "dot_general", "scatter-add"):
        assert any(prim in p for p in paths), (prim, paths)


def test_hlo_server_update_holds_nothing_d_sized_but_the_one_read():
    """At a geometry where ``k * 128 << d``: under ``ef_resketch`` the only
    instructions that touch ``d``-scale data read ``update`` once (the
    padded rows and their nonzero counts, elementwise); no dot and no
    gather returns ``d``-scale data, where ``sketch_vec`` under the same
    name does both."""
    d, k = 200_003, 100
    spec = CountSketch(d=d, c=20_000, r=3, seed=5)

    def text_of(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    def big(text):
        return sorted({p.rsplit("/", 1)[-1] for p, n, ln in
                       _ops_under(text, "ef_resketch")
                       if n >= d // 2 and re.search(r" (dot|gather)\(", ln)})

    update = jnp.zeros((d,), jnp.float32).at[::2003].set(1.0)

    def pairs(v):
        with jax.named_scope("ef_resketch"):
            return sketch_sparse(spec, *compact_nonzero_tree(v, k))

    def dense(v):
        with jax.named_scope("ef_resketch"):
            return sketch_vec(spec, v)

    assert big(text_of(dense, update)) == ["dot_general", "gather"]
    assert big(text_of(pairs, update)) == []
    np.testing.assert_allclose(np.asarray(jax.jit(pairs)(update)),
                               np.asarray(jax.jit(dense)(update)), atol=1e-6)
