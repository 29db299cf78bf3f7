"""Pallas-backend equivalence tests for the CountSketch hot path.

The ``backend='pallas'`` kernels (ops/pallas/countsketch_kernels.py) must
produce the SAME tables/estimates as the banded-einsum reference path up to
fp32 summation-order rounding — on CPU they run under Pallas interpret mode,
so these tests pin the kernel math itself (hash generation, in-kernel signs,
fused overlap-add, the transposed estimate contraction, the median network)
without a TPU.

Also pinned here:
  * the 16-bit-limb Mersenne multiply (``_modmul31``/``_poly4_u32``) is
    bit-identical to the host uint64 evaluation — the arithmetic that lets
    poly4 run without uint64 (TPU kernels have none);
  * the Pallas path NEVER materializes a [d_eff] sign vector (the property
    that unlocks poly4 at GPT-2 scale, VERDICT r5 missing #2) — enforced
    by poisoning ``_row_signs`` and running the full path at D > 1M.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops.countsketch import (
    _MERSENNE_P,
    _modmul31,
    _poly4_eval,
    _poly4_u32,
    CountSketch,
    estimate_all,
    estimate_at,
    sketch_add_vec,
    sketch_vec,
    unsketch,
)
from commefficient_tpu.ops.pallas import median_rows_pallas

D, C, R = 10_000, 2_000, 5


def planted_vector(d, k, rng, heavy=100.0, noise=1.0):
    v = rng.normal(0, noise, size=d).astype(np.float32)
    idx = rng.choice(d, size=k, replace=False)
    v[idx] += heavy * rng.choice([-1.0, 1.0], size=k)
    return jnp.asarray(v), np.asarray(idx)


def assert_close(a, b, rtol=3e-6):
    """fp32 closeness scaled to the data (summation order differs between
    the backends, so exact equality is not the contract)."""
    a, b = np.asarray(a), np.asarray(b)
    scale = max(np.abs(a).max(), 1.0)
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * scale)


# -- the in-kernel Mersenne arithmetic --------------------------------------


def test_modmul31_bit_exact_vs_host_uint64():
    rng = np.random.default_rng(0)
    p = int(_MERSENNE_P)
    a = rng.integers(0, p, size=4096).astype(np.uint32)
    x = rng.integers(0, p, size=4096).astype(np.uint32)
    # edge operands: 0, 1, p-1 in both slots
    edges = np.array([0, 1, p - 1], np.uint32)
    a = np.concatenate([a, edges, np.full(3, p - 1, np.uint32)])
    x = np.concatenate([x, np.full(3, p - 1, np.uint32), edges])
    got = np.asarray(_modmul31(jnp.asarray(a), jnp.asarray(x)))
    want = ((a.astype(np.uint64) * x.astype(np.uint64)) % np.uint64(p)).astype(
        np.uint32
    )
    np.testing.assert_array_equal(got, want)


def test_poly4_u32_bit_exact_vs_host_uint64():
    rng = np.random.default_rng(1)
    coeffs = rng.integers(1, int(_MERSENNE_P), size=4).astype(np.uint64)
    x = rng.integers(0, int(_MERSENNE_P), size=8192).astype(np.uint64)
    want = _poly4_eval(x, coeffs)
    got = _poly4_u32(
        jnp.asarray(x.astype(np.uint32)), tuple(int(c) for c in coeffs)
    )
    np.testing.assert_array_equal(np.asarray(got).astype(np.uint64), want)


# -- backend equivalence across geometries and hash families ----------------

# (family, d, c, r, m): the CV-like even geometry and a padded ODD d that
# exercises every padding seam (scramble block, per-row riffle padding,
# chunk tail). The odd geometry's fmix32 twin rides the slow tier (PR 21
# budget: the kernels interpret slower since they stopped reshaping across
# lanes); each family and each geometry keeps a tier-1 case.
CASES = [
    ("fmix32", D, C, R, None),
    ("poly4", D, C, R, None),
    pytest.param("fmix32", 20_011, 4_000, 3, 512, marks=pytest.mark.slow),
    ("poly4", 20_011, 4_000, 3, 512),
]


@pytest.mark.parametrize("family,d,c,r,m", CASES)
def test_sketch_and_estimate_match_einsum(family, d, c, r, m):
    spec_e = CountSketch(d=d, c=c, r=r, m=m, seed=7, hash_family=family)
    spec_p = spec_e._replace(backend="pallas")
    rng = np.random.default_rng(2)
    v, _ = planted_vector(d, 20, rng)
    te = sketch_vec(spec_e, v)
    tp = sketch_vec(spec_p, v)
    assert te.shape == tp.shape == spec_e.table_shape
    assert_close(te, tp)
    # estimate: run each backend on ITS OWN table (the round-trip each
    # backend actually performs) and on the shared einsum table (isolates
    # the estimate kernel)
    assert_close(estimate_all(spec_e, te), estimate_all(spec_p, tp))
    assert_close(estimate_all(spec_e, te), estimate_all(spec_p, te))


# the roundtrip rides the slow tier (r20 budget, poly4 since PR 21): both
# families' pallas==einsum equivalence stays tier-1 via the estimate-match
# cases above (a kernel equal to the linear einsum is linear to the same
# tolerance), and heavy-hitter recovery through the full pallas round-trip
# via the GPT-2-scale poly4 test below.
@pytest.mark.slow
@pytest.mark.parametrize("family", ["fmix32", "poly4"])
def test_add_linearity_and_unsketch_roundtrip(family):
    spec_e = CountSketch(d=D, c=C, r=R, seed=7, hash_family=family)
    spec_p = spec_e._replace(backend="pallas")
    rng = np.random.default_rng(3)
    v, hh = planted_vector(D, 10, rng)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    # sketch_add_vec through the pallas dispatch == einsum accumulate
    t0 = sketch_vec(spec_p, w)
    assert_close(sketch_add_vec(spec_p, t0, v), sketch_vec(spec_e, w + v))
    # linearity holds WITHIN the pallas backend (aggregation contract)
    assert_close(
        sketch_vec(spec_p, v + w), sketch_vec(spec_p, v) + sketch_vec(spec_p, w)
    )
    # unsketch recovers the same planted heavy hitters through either backend
    rec_e = np.asarray(unsketch(spec_e, sketch_vec(spec_e, v), k=10))
    rec_p = np.asarray(unsketch(spec_p, sketch_vec(spec_p, v), k=10))
    assert set(np.nonzero(rec_p)[0]) == set(np.nonzero(rec_e)[0])
    assert set(hh.tolist()) <= set(np.nonzero(rec_p)[0].tolist())
    assert_close(rec_e, rec_p, rtol=1e-5)


def test_num_blocks_estimation_is_backend_agnostic():
    # num_blocks > 1 takes the exact gather path regardless of backend —
    # same VALUES as the matmul path (bit-equal on CPU between backends,
    # since neither backend's kernels run)
    spec_e = CountSketch(d=D, c=C, r=3, num_blocks=4, seed=7)
    spec_p = spec_e._replace(backend="pallas")
    rng = np.random.default_rng(4)
    v, _ = planted_vector(D, 10, rng)
    table = sketch_vec(spec_e, v)
    np.testing.assert_array_equal(
        np.asarray(estimate_all(spec_e, table)),
        np.asarray(estimate_all(spec_p, table)),
    )


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True)])
def test_kernels_compile_on_tpu_and_interpret_only_on_cpu(
        monkeypatch, backend, want):
    import jax

    from commefficient_tpu.ops.pallas import kernels_interpreted

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernels_interpreted() is want


def test_kernels_refuse_a_backend_that_can_do_neither(monkeypatch):
    import jax

    from commefficient_tpu.ops.pallas import kernels_interpreted

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kernels_interpreted()


def test_unknown_backend_fails_loudly():
    spec = CountSketch(d=D, c=C, r=3, seed=7, backend="cuda")
    v = jnp.zeros(D, jnp.float32)
    with pytest.raises(ValueError, match="backend"):
        sketch_vec(spec, v)
    with pytest.raises(ValueError, match="backend"):
        estimate_all(spec, jnp.zeros(spec.table_shape, jnp.float32))


# -- the median kernel ------------------------------------------------------


@pytest.mark.parametrize("r", [1, 2, 3, 4, 5, 7])
def test_median_rows_pallas_matches_jnp_median(r):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(r, 3001)).astype(np.float32))
    got = np.asarray(median_rows_pallas(x))
    np.testing.assert_array_equal(got, np.median(np.asarray(x), axis=0))


# -- poly4 at production scale (the capability the kernels unlock) ----------


def test_poly4_at_gpt2_scale_without_sign_materialization(monkeypatch):
    """VERDICT r5 missing #2 / acceptance: poly4 usable at D >= 1M through
    the Pallas path, with NO [d_eff] sign vector ever materialized. The
    einsum path's host sign table is the exact thing poisoning _row_signs
    forbids — the kernels must never touch it."""
    d = 1_200_003  # odd: exercises every padding seam at scale
    spec_e = CountSketch(d=d, c=d // 25, r=3, seed=11, hash_family="poly4")
    spec_p = spec_e._replace(backend="pallas")
    rng = np.random.default_rng(6)
    v, hh = planted_vector(d, 16, rng)
    te = sketch_vec(spec_e, v)  # einsum reference table (signs via host)

    def _poisoned(self, row):
        raise AssertionError(
            "pallas backend materialized the [d_eff] sign vector"
        )

    monkeypatch.setattr(CountSketch, "_row_signs", _poisoned)
    tp = sketch_vec(spec_p, v)
    assert_close(te, tp)
    est_p = estimate_all(spec_p, tp)
    # verify the estimate kernel against the independent exact gather path
    # on the planted coordinates plus a random probe set
    probe = np.concatenate([hh, rng.choice(d, size=256, replace=False)])
    probe = jnp.asarray(np.unique(probe).astype(np.uint32))
    ref = estimate_at(spec_e._replace(backend="einsum"), tp, probe)
    assert_close(np.asarray(est_p)[np.asarray(probe)], ref, rtol=1e-5)
    # the planted heavy hitters survive the full pallas round-trip (top-64
    # margin: at d/c=25 with r=3, median-of-3 collision phantoms can edge
    # individual coordinates in a strict top-16 — recovery, not ranking,
    # is the property under test)
    rec = np.asarray(est_p)
    order = np.argsort(-np.abs(rec))[:64]
    assert set(hh.tolist()) <= set(order.tolist())
