"""Sketch-FUSED backward (cfg.sketch_fused_bwd; parallel/round.py
make_sketch_grad_one + ops/countsketch.py sketch_grad_tap).

The claim under pin: in sketch mode with the fused backward, the flat
[D] gradient — ``make_grad_one``'s ``ravel_pytree`` concat, a ~500 MB
transient at GPT-2 scale — is NEVER materialized. Per-leaf custom_vjp
taps sketch each cotangent into the table where AD produces it, and by
linearity the accumulated table equals the sketch of the full flat
gradient. Pinned here:

  * ops-level: the tap-accumulated table == ``sketch_segment`` of the
    reference per-leaf grads == (within scatter-order rounding) the
    matmul-path sketch of the concatenated grad;
  * HLO: the compiled fused-backward round carries the
    ``sketch_fused_bwd`` scope and NO ``flat_grad_concat`` scope (the
    marker ``make_grad_one`` wraps around its ravel_pytree) — while the
    default sketch round carries the concat marker (marker validity);
  * round-level: training parity vs the default dense-grad sketch round
    (same hash mapping, different summation order — tight tolerance),
    weight decay included (it composes as one matmul-path params
    sketch);
  * config: every incompatible knob is refused at construction with the
    blocker named.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.data import FedSampler
from commefficient_tpu.ops.countsketch import (
    CountSketch,
    sketch_grad_tap,
    sketch_segment,
    sketch_sparse,
    sketch_vec,
)
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils.config import Config


# ---------------------------------------------------------------------------
# ops level: the tap IS the sketch of the gradient
# ---------------------------------------------------------------------------

def test_tap_accumulates_sketch_of_full_gradient():
    spec = CountSketch(d=48, c=32, r=3, seed=3)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))  # 16
    b = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))   # 32
    x = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))

    def loss(leaves):
        aa, bb = leaves
        return jnp.sum(jnp.sin(aa) * x[None, :]) + jnp.sum(bb * bb)

    def tapped(table):
        aa = sketch_grad_tap(spec, 0, a, table)
        bb = sketch_grad_tap(spec, 16, b, table)
        return loss((aa, bb))

    table = jax.grad(tapped)(jnp.zeros(spec.table_shape, jnp.float32))
    ga, gb = jax.grad(loss)((a, b))
    want = np.asarray(sketch_segment(spec, 0, ga)) + np.asarray(
        sketch_segment(spec, 16, gb)
    )
    np.testing.assert_allclose(np.asarray(table), want, rtol=0, atol=1e-6)
    # and the per-leaf segment sum IS the sketch of the concat (same
    # hash mapping as sketch_sparse over the full index range)
    flat = jnp.concatenate([ga.reshape(-1), gb.reshape(-1)])
    full = np.asarray(
        sketch_sparse(spec, jnp.arange(48, dtype=jnp.uint32), flat)
    )
    np.testing.assert_allclose(want, full, rtol=0, atol=1e-6)
    # matmul-path cross-check (summation order differs -> tolerance)
    mm = np.asarray(sketch_vec(spec, flat))
    scale = max(np.abs(mm).max(), 1.0)
    np.testing.assert_allclose(want, mm, rtol=0, atol=1e-5 * scale)


def test_tap_forward_is_identity():
    spec = CountSketch(d=8, c=8, r=1, seed=3)
    leaf = jnp.arange(8.0)
    out = sketch_grad_tap(spec, 0, leaf, jnp.zeros(spec.table_shape))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(leaf))


# ---------------------------------------------------------------------------
# round level: parity + the HLO concat pin
# ---------------------------------------------------------------------------

def _cfg(**kw):
    return Config(**{**BASE, "mode": "sketch", "error_type": "virtual",
                     "virtual_momentum": 0.9, "k": 40, "num_rows": 3,
                     "num_cols": 256, "topk_method": "threshold",
                     "fuse_clients": True, "weight_decay": 1e-4, **kw})


def _run(cfg, n_rounds=4):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.2)
    return sess, float(np.asarray(m["loss"]))


def test_fused_bwd_training_parity_with_dense_grad_path():
    """Same rounds, same data: the fused backward's params track the
    default dense-grad sketch round to summation-order rounding —
    weight decay on (it must compose via the params sketch)."""
    s_dense, l_dense = _run(_cfg())
    s_fused, l_fused = _run(_cfg(sketch_fused_bwd=True))
    p_d = np.asarray(s_dense.state.params_vec)
    p_f = np.asarray(s_fused.state.params_vec)
    scale = max(np.abs(p_d).max(), 1.0)
    np.testing.assert_allclose(p_f, p_d, rtol=0, atol=5e-5 * scale)
    assert abs(l_fused - l_dense) < 1e-3


def test_fused_bwd_hlo_free_of_flat_grad_concat():
    """The acceptance pin: the fused-backward round's compiled HLO holds
    the sketch_fused_bwd scope and NO flat_grad_concat scope; the default
    round holds the concat marker (proving the marker is live)."""
    ds, params, loss_fn = _setup(12)
    sampler_cfg = _cfg(sketch_fused_bwd=True)
    sess = FederatedSession(sampler_cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=sampler_cfg.num_workers,
                         local_batch_size=sampler_cfg.local_batch_size,
                         seed=1)
    ids, batch = sampler.sample_round(0)
    ids_d = jnp.asarray(ids)
    text = sess.round_fn.lower(
        sess.state, ids_d, jax.tree.map(jnp.asarray, batch),
        jnp.float32(0.2),
    ).compile().as_text()
    def scoped(text, scope):
        # the named_scope as op metadata carries it — the compiled text
        # also lists stack frames, whose file and function names (this
        # test's own) contain both marker words
        return re.search(r'op_name="[^"]*\b' + scope + r'\b', text)

    assert scoped(text, "sketch_fused_bwd")
    assert not scoped(text, "flat_grad_concat"), (
        "the fused-backward round materialized the flat [D] grad concat"
    )
    sess2 = FederatedSession(_cfg(), params, loss_fn)
    text2 = sess2.round_fn.lower(
        sess2.state, ids_d, jax.tree.map(jnp.asarray, batch),
        jnp.float32(0.2),
    ).compile().as_text()
    assert scoped(text2, "flat_grad_concat"), "concat marker lost its validity"
    assert not scoped(text2, "sketch_fused_bwd")


def test_fused_bwd_composes_with_bf16_tables():
    s_fused, l = _run(_cfg(sketch_fused_bwd=True,
                           sketch_table_dtype="bfloat16"))
    assert np.isfinite(l)
    assert s_fused.state.momentum.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# config gates: every blocker refused at construction, named
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(mode="true_topk"), "mode"),
    (dict(fuse_clients=False), "fuse_clients"),
    (dict(local_momentum=0.5), "local_momentum"),
    (dict(max_grad_norm=1.0), "max_grad_norm"),
    (dict(dp_noise_multiplier=0.1), "DP noise"),
    (dict(availability="bernoulli", dropout_prob=0.3), "fedsim"),
])
def test_fused_bwd_incompatible_knobs_refused(kw, needle):
    base = dict(BASE, mode="sketch", error_type="virtual", k=40,
                num_rows=3, num_cols=256, topk_method="threshold",
                fuse_clients=True, sketch_fused_bwd=True)
    base.update(kw)
    with pytest.raises((ValueError, NotImplementedError), match=needle):
        Config(**base)
