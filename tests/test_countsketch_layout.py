"""The einsum backend's two fixed permutations (ISSUE 32): the band kept as
two dimensions through the product and the riffle as tiled moves must leave
the coordinate -> (row, bucket, sign) map where ``_row_cols_signs`` has it.

Every spec below reaches a different branch of ``_to_layout`` /
``_from_layout`` / ``_sketch_one_row`` / ``_estimate_one_row``: f = 1, a
factor under the lane tile and one over it, a chunk size with the riffle's
128-lane tile and without, band 1 and 16, both hash families, both table
dtypes, the scramble on and off, and a ``d`` that is a multiple of nothing.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import countsketch as cs
from commefficient_tpu.ops.countsketch import (
    CountSketch,
    estimate_all,
    estimate_at,
    sketch_sparse,
    sketch_vec,
)

SPECS = {
    "tiled_band16": dict(d=70_001, c=9_000, r=3, m=256),
    "tiled_band1": dict(d=70_001, c=9_000, r=3, m=256, band=1),
    "tiled_poly4": dict(d=70_001, c=9_000, r=3, m=256, hash_family="poly4"),
    "tiled_bf16": dict(d=70_001, c=9_000, r=3, m=256, table_dtype=jnp.bfloat16),
    "tiled_noscramble": dict(d=70_001, c=9_000, r=3, m=128, scramble_block=0),
    "tiled_adaptive_m": dict(d=100_003, c=12_000, r=5),
    "plain_band16": dict(d=5_003, c=600, r=5, m=64),
    "plain_band1": dict(d=5_003, c=600, r=5, m=64, band=1),
    "plain_poly4_noscramble": dict(
        d=5_003, c=600, r=3, m=72, hash_family="poly4", scramble_block=0
    ),
    "one_row": dict(d=9_973, c=1_200, r=1, m=128),
}


@pytest.fixture(params=sorted(SPECS), scope="module")
def spec(request):
    s = CountSketch(seed=11, **SPECS[request.param])
    tiled = {r for r in range(s.r) if cs._riffle_tile(s, r)}
    assert tiled == {
        r for r in range(s.r)
        if request.param.startswith("tiled") and 1 < s._factor(r) < 128
    }
    if s.r > 1:
        assert s._factor(0) == 1 and all(s._factor(r) > 1 for r in range(1, s.r))
    if request.param == "tiled_band16":  # a factor on each side of the tile
        assert [s._factor(r) for r in range(s.r)] == [1, 17, 277]
    return s


def _vec(d, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.normal(size=d) * 3).astype(np.float32))


def test_sketch_vec_is_sketch_sparse_of_every_coordinate(spec):
    v = _vec(spec.d, 0)
    dense = sketch_vec(spec, v).astype(jnp.float32)
    sparse = sketch_sparse(spec, jnp.arange(spec.d, dtype=jnp.uint32), v)
    # float32 summation order; a bfloat16 table is rounded once at the end
    tol = 2e-2 if spec.table_dtype == jnp.bfloat16 else 1e-5
    scale = float(jnp.abs(sparse).max())
    assert float(jnp.abs(dense - sparse).max()) <= tol * scale


def test_estimate_all_is_estimate_at_of_every_coordinate_bit_for_bit(spec):
    table = sketch_sparse(
        spec, jnp.arange(spec.d, dtype=jnp.uint32), _vec(spec.d, 1)
    ).astype(spec.table_dtype)
    full = estimate_all(spec, table)
    point = estimate_at(spec, table, jnp.arange(spec.d, dtype=jnp.uint32))
    assert full.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(full), np.asarray(point))


def test_linearity(spec):
    a, b = _vec(spec.d, 2), _vec(spec.d, 3)
    both = sketch_vec(spec, a + b).astype(jnp.float32)
    parts = sketch_vec(spec, a).astype(jnp.float32) + sketch_vec(spec, b).astype(
        jnp.float32
    )
    tol = 3e-2 if spec.table_dtype == jnp.bfloat16 else 1e-5
    assert float(jnp.abs(both - parts).max()) <= tol * float(jnp.abs(both).max())


def test_layout_helpers_are_the_parents_formula_and_each_others_inverse(spec):
    x = jnp.arange(spec.d_eff, dtype=jnp.float32)  # exact below 2**24
    for row in range(spec.r):
        f, L, m = spec._factor(row), spec._L_row(row), spec.chunk_m
        xp = np.pad(np.asarray(x), (0, L - spec.d_eff))
        want = xp.reshape(f, L // f).T.reshape(L // m, m)  # the parent's riffle
        got = cs._to_layout(spec, x, row)
        np.testing.assert_array_equal(np.asarray(got), want)
        back = cs._from_layout(spec, got, row)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        # the inverse alone against its own formula, on another input
        y = jnp.arange(L, dtype=jnp.float32).reshape(L // m, m) * 2.0
        want_back = np.asarray(y).reshape(L // f, f).T.reshape(L)[: spec.d_eff]
        np.testing.assert_array_equal(
            np.asarray(cs._from_layout(spec, y, row)), want_back
        )


@pytest.mark.parametrize("f,t,A", [(7, 128, 3), (97, 128, 2), (3, 8, 5)])
def test_riffle_moves(f, t, A):
    L = f * t * A
    x = jnp.arange(L, dtype=jnp.float32)
    want = np.asarray(x).reshape(f, L // f).T.reshape(L)
    got = cs._riffle(x, f, t)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(cs._unriffle(got, f, t)), np.asarray(x))


# ---- what the lowered functions may not hold ----------------------------------

# every factor under the lane tile (1, 5, 17, 71), and G / 128 = 8 g composite
PIN = CountSketch(d=70_001, c=9_000, r=4, m=1024, seed=11)


def _lowered(fn, *shapes):
    return jax.jit(fn).lower(
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    ).as_text()


def _tensor_shapes(text):
    return {
        tuple(int(n) for n in m.group(1).split("x"))
        for m in re.finditer(r"tensor<([0-9]+(?:x[0-9]+)*)x[a-z]", text)
    }


def _result_shapes(text, op):
    """Shapes of every result of ``stablehlo.<op>`` in a lowered module."""
    out = []
    for line in text.splitlines():
        if f"stablehlo.{op}" in line:
            out.extend(_tensor_shapes(line.rsplit("->", 1)[-1]))
    return out


def _lowered_pin(which):
    assert PIN.band == 16
    assert all(1 < PIN._factor(r) < 128 for r in range(1, PIN.r))
    if which == "encode":
        return _lowered(lambda v: sketch_vec(PIN, v), (PIN.d,))
    return _lowered(lambda t: estimate_all(PIN, t), PIN.table_shape)


@pytest.mark.parametrize("which", ["encode", "estimate_all"])
def test_the_band_is_never_a_minor_dimension(which):
    """No ``[nc, V]`` window and no ``[m, V]`` one-hot: the band's ``u``
    windows stay a dimension of their own through the product, so no array
    merges them into a ``u * s`` minor dimension (the parent's
    ``reshape(nc, u * s)``: a lane-by-lane relayout, s being no multiple of
    128 at the paper's geometry)."""
    shapes = _tensor_shapes(_lowered_pin(which))
    for row in range(PIN.r):
        nc, u, s = PIN._nc_row(row), PIN.u_row(row), PIN.s_row(row)
        assert (nc, u, s) in shapes  # the windows as two dimensions
        assert not {sh for sh in shapes if len(sh) >= 2 and sh[-1] == u * s}


@pytest.mark.parametrize("which", ["encode", "estimate_all"])
def test_no_transpose_leaves_a_prime_minor_dimension(which):
    shapes = _result_shapes(_lowered_pin(which), "transpose")
    assert len(shapes) >= 2 * (PIN.r - 1)  # two moves a riffled row
    factors = {PIN._factor(r) for r in range(1, PIN.r)}
    for shape in shapes:
        assert shape[-1] not in factors and not cs._is_prime(shape[-1]), shape
