"""Layer-wise collective overlap (ISSUE 16): chunked exchanges, bit-equal.

``--overlap_collectives layerwise`` splits the round's aggregation
collectives — the sketch-table psum and the top-k modes' pair all_gather
— into per-leaf-group / per-segment collectives the latency-hiding
scheduler can issue as the backward produces them. The knob is a pure
scheduling choice, so the contract pinned here is equality, not speed
(no benchmark cell turns it on; the speed side is not measured):

  * ops level, on the real 8-device mesh: ``psum_segments`` is BIT-equal
    to one psum of the concatenated segments (``psum_segments_fused``),
    and the chunked ``all_gather_pairs`` rebuilds the monolithic layout
    byte for byte — an all-reduce is elementwise and a gather is pure
    data movement, so segmentation changes which collective carries an
    element, never its value;
  * round level: layerwise-vs-none final params and per-round losses are
    BIT-equal for every sparse-exchange mode (local_topk/local,
    true_topk/virtual, sketch on the sharded decode), including under
    fedsim availability masking;
  * the sketch-FUSED-backward layerwise round regroups the per-leaf
    cotangent fan-in (per-GROUP tables), so it is pinned at the fused
    backward's own tolerance class (PR-12: atol 5e-5 * scale; measured
    ~3e-8) and composes with bf16 tables;
  * ``overlap_collectives='none'`` (the default) lowers BYTE-identical
    HLO — the golden registry parity stays untouched by construction;
  * the layerwise fused round carries the ``overlap_layerwise_psum``
    scope so profiles attribute the segmented collectives;
  * config rejections: unknown overlap value; ``async_double_buffer``
    without the asyncfed engine (the deferred fence needs cohort
    launches to hide behind).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE, _final_vec, _run, _setup

from commefficient_tpu.data import FedSampler
from commefficient_tpu.ops.collectives import (
    all_gather_pairs,
    psum_segments,
    psum_segments_fused,
)
from commefficient_tpu.ops.collectives.sparse_allreduce import _segment_bounds
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.parallel.mesh import WORKERS, make_mesh
from commefficient_tpu.parallel.round import leaf_groups
from commefficient_tpu.utils.config import Config

P = jax.sharding.PartitionSpec
Wd = 8


# ---------------------------------------------------------------------------
# segment bookkeeping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,segments", [(1, 4), (3, 4), (4, 4), (17, 4),
                                        (100, 1), (100, 7)])
def test_segment_bounds_cover_exactly_once(n, segments):
    bounds = _segment_bounds(n, segments)
    assert bounds[0][0] == 0 and bounds[-1][1] == n
    for (a, b), (a2, _) in zip(bounds, bounds[1:]):
        assert b == a2
    assert all(b > a for a, b in bounds)  # every chunk non-empty
    assert len(bounds) <= max(1, min(segments, n))


@pytest.mark.parametrize("sizes,segments", [
    ([10, 10, 10, 10], 4),
    ([1, 1, 1], 8),          # fewer leaves than segments
    ([100, 1, 1, 1, 1], 3),  # one dominant leaf
    ([5], 4),
    (list(range(1, 20)), 4),
])
def test_leaf_groups_cover_contiguously(sizes, segments):
    bounds = leaf_groups(sizes, segments)
    assert bounds[0][0] == 0 and bounds[-1][1] == len(sizes)
    for (a, b), (a2, _) in zip(bounds, bounds[1:]):
        assert b == a2
    assert all(b > a for a, b in bounds)  # non-empty groups
    assert len(bounds) <= max(1, min(segments, len(sizes)))


# ---------------------------------------------------------------------------
# ops level: the segmented collectives on the real mesh
# ---------------------------------------------------------------------------

def test_psum_segments_bit_equal_to_fused_psum_on_mesh():
    """The claim in one op: per-segment psums == one psum of the
    concatenated segments, element for element (np.array_equal)."""
    rng = np.random.default_rng(3)
    # deliberately ragged shapes; psum_segments_fused flattens+concats
    shapes = [(13,), (4, 7), (31,), (2, 3, 5)]
    xs = [jnp.asarray(rng.normal(size=(Wd,) + s).astype(np.float32) * 100)
          for s in shapes]
    mesh = make_mesh(Wd)

    def body(*segs):
        segs = tuple(s.reshape(s.shape[1:]) for s in segs)
        a = psum_segments(segs, WORKERS)
        b = psum_segments_fused(segs, WORKERS)
        return tuple(x[None] for x in a), tuple(x[None] for x in b)

    f = jax.shard_map(body, mesh=mesh,
                  in_specs=tuple(P(WORKERS) for _ in xs),
                  out_specs=(tuple(P(WORKERS) for _ in xs),
                             tuple(P(WORKERS) for _ in xs)))
    seg_out, fused_out = jax.jit(f)(*xs)
    for a, b in zip(seg_out, fused_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kb,segments", [(11, 4), (3, 4), (1, 4), (64, 2)])
def test_all_gather_pairs_chunked_rebuilds_monolithic(kb, segments):
    """Chunked gathers concatenated along the pair axis == the single
    monolithic gather, byte for byte (pure data movement)."""
    rng = np.random.default_rng(7)
    idx = jnp.asarray(rng.integers(0, 1000, size=(Wd, kb)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(Wd, kb)).astype(np.float32))
    mesh = make_mesh(Wd)

    def body(i, v):
        i, v = i.reshape(-1), v.reshape(-1)
        gi_m, gv_m = all_gather_pairs(i, v, WORKERS)
        gi_s, gv_s = all_gather_pairs(i, v, WORKERS, segments=segments)
        return gi_m[None], gv_m[None], gi_s[None], gv_s[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(WORKERS), P(WORKERS)),
                  out_specs=(P(WORKERS),) * 4)
    gi_m, gv_m, gi_s, gv_s = jax.jit(f)(idx, val)
    np.testing.assert_array_equal(np.asarray(gi_m), np.asarray(gi_s))
    np.testing.assert_array_equal(np.asarray(gv_m), np.asarray(gv_s))


# ---------------------------------------------------------------------------
# round level: layerwise == none, bit for bit, per sparse mode
# ---------------------------------------------------------------------------

SPARSE_MODES = {
    "local_topk": dict(mode="local_topk", error_type="local", k=7,
                       topk_method="threshold", aggregate="sparse"),
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, k=9, topk_method="threshold",
                      aggregate="sparse"),
    "sketch": dict(mode="sketch", error_type="virtual",
                   virtual_momentum=0.9, k=40, num_rows=3, num_cols=256,
                   topk_method="threshold", aggregate="sparse"),
}


# Only the headline sketch mode stays in the default tier — the other two
# sparse modes exercise the identical chunked-exchange code path and ride
# the slow tier (PR-12 precedent: keep one default-tier pin per claim).
@pytest.mark.parametrize(
    "mode_kw",
    [pytest.param(kw, id=name,
                  marks=() if name == "sketch" else (pytest.mark.slow,))
     for name, kw in SPARSE_MODES.items()],
)
def test_layerwise_bit_equal_to_none_sparse_modes(mode_kw):
    """Same rounds, same data: chunking the pair gathers must not move a
    single bit — params AND every drained loss scalar."""
    s_none, l_none = _run(Config(overlap_collectives="none",
                                 **mode_kw, **BASE))
    s_lw, l_lw = _run(Config(overlap_collectives="layerwise",
                             **mode_kw, **BASE))
    assert l_lw == l_none  # exact float equality, round by round
    np.testing.assert_array_equal(_final_vec(s_lw), _final_vec(s_none))


@pytest.mark.slow
def test_layerwise_bit_equal_under_fedsim_masking():
    """Availability masking is pre-encode; it must commute with the
    chunked exchange exactly as it does with the monolithic one."""
    from test_sketch_decode import _cohort_env

    def masked(ov):
        cfg = Config(availability="bernoulli", dropout_prob=0.5,
                     overlap_collectives=ov,
                     **SPARSE_MODES["local_topk"], **BASE)
        ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
        losses = []
        for r in range(3):
            ids, batch = sampler.sample_round(r)
            m = sess.train_round(ids, batch, 0.3,
                                 env=_cohort_env([0, 2, 3, 5, 7]))
            losses.append(float(m["loss"]))
        return sess, losses

    s_none, l_none = masked("none")
    s_lw, l_lw = masked("layerwise")
    assert l_lw == l_none
    np.testing.assert_array_equal(_final_vec(s_lw), _final_vec(s_none))


# ---------------------------------------------------------------------------
# sketch fused backward: per-GROUP tables, fused-bwd tolerance class
# ---------------------------------------------------------------------------

def _fused_cfg(**kw):
    return Config(**{**BASE, "mode": "sketch", "error_type": "virtual",
                     "virtual_momentum": 0.9, "k": 40, "num_rows": 3,
                     "num_cols": 256, "topk_method": "threshold",
                     "fuse_clients": True, "weight_decay": 1e-4,
                     "sketch_fused_bwd": True, **kw})


def _run_fused(cfg, n_rounds=4):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.2)
    return sess, float(np.asarray(m["loss"]))


def test_fused_bwd_layerwise_parity_with_monolithic():
    """Per-leaf-GROUP tables reorder the cotangent fan-in into the
    table, so layerwise-vs-none here is the fused backward's OWN
    tolerance class (PR-12: atol 5e-5 * scale), not bit-equality."""
    s_none, l_none = _run_fused(_fused_cfg())
    s_lw, l_lw = _run_fused(_fused_cfg(overlap_collectives="layerwise"))
    p_n = np.asarray(s_none.state.params_vec)
    p_l = np.asarray(s_lw.state.params_vec)
    scale = max(np.abs(p_n).max(), 1.0)
    np.testing.assert_allclose(p_l, p_n, rtol=0, atol=5e-5 * scale)
    assert abs(l_lw - l_none) < 1e-3


@pytest.mark.slow
def test_fused_bwd_layerwise_composes_with_bf16_tables():
    s_lw, loss = _run_fused(_fused_cfg(overlap_collectives="layerwise",
                                       sketch_table_dtype="bfloat16"))
    assert np.isfinite(loss)
    assert s_lw.state.momentum.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# HLO pins
# ---------------------------------------------------------------------------

def _lowered_text(cfg, compiled=False):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ids, batch = sampler.sample_round(0)
    lowered = sess.round_fn.lower(
        sess.state, jnp.asarray(ids),
        {k: jnp.asarray(v) for k, v in batch.items()}, jnp.float32(0.2))
    return (lowered.compile() if compiled else lowered).as_text()


def test_overlap_none_lowers_byte_identical_hlo():
    """The default stays golden: overlap='none' (explicit or by default)
    traces the exact pre-overlap program — no layout drift, so the
    registry_parity goldens hold by construction."""
    kw = SPARSE_MODES["local_topk"]
    texts = [_lowered_text(Config(**kw, **BASE)),
             _lowered_text(Config(overlap_collectives="none", **kw, **BASE))]
    assert texts[0] == texts[1]


def test_layerwise_fused_round_carries_overlap_scope():
    """The segmented table psums sit under the overlap_layerwise_psum
    scope (parallel/round.py) so profiles attribute them; the monolithic
    build must NOT carry the scope (marker validity)."""
    text_lw = _lowered_text(_fused_cfg(overlap_collectives="layerwise"),
                            compiled=True)
    assert "overlap_layerwise_psum" in text_lw
    text_none = _lowered_text(_fused_cfg(), compiled=True)
    assert "overlap_layerwise_psum" not in text_none


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_config_rejects_unknown_overlap_value():
    with pytest.raises(ValueError, match="overlap_collectives"):
        Config(mode="uncompressed", overlap_collectives="chunky", **BASE)


def test_config_rejects_double_buffer_without_async_engine():
    with pytest.raises(ValueError, match="async_double_buffer"):
        Config(mode="sketch", k=40, num_rows=3, num_cols=256,
               async_double_buffer=True, **BASE)
