"""Sparse allreduce collective layer (ISSUE 14): O(W*k) on-mesh aggregation.

The top-k modes' device transmits are k-sparse, yet the replicated round
aggregated them with a dense [D] psum. ``ops/collectives/`` exchanges
fixed-size (idx, val) pair buffers instead — ``sparse_allreduce`` (compact
-> pair all_gather -> scatter-add, replicated result) for local_topk's
``aggregate='auto'`` path, a reduce-scatter + workers-sharded server
algebra + W*k candidate gather for true_topk's explicit sparse path, and
the recursive-halving ``ppermute`` schedule (``sparse_allreduce_sharded``)
as the sharded-output primitive. Pinned here, on the virtual 8-device CPU
mesh:

  * sparse == dense-psum final params at atol 1e-6 per mode, across error
    modes, momentum, dampening, fedsim masking (+ all-dropped freeze),
    and offloaded client state;
  * the pair-exchange primitives' contracts (dense-sum equivalence,
    capacity-overflow drop semantics, duplicate-coordinate accumulation,
    the power-of-two schedule guard) and ``compact_nonzero`` edge cases
    (satellite: all-zero, > k nonzeros, k=0, tied magnitudes);
  * compiled-HLO traffic: the sparse round moves NO all-reduce/all-gather
    of >= O(D) elements (a [D] reduce-scatter is legal: O(D/W) per link,
    sharded result); the dense round's three per-round psums are FUSED
    into one all-reduce (satellite: tuple-psum fusion, op-count pinned);
  * defaults stay bit-untouched: ``aggregate='auto'`` on a 1-device mesh
    lowers to byte-identical HLO vs explicit dense;
  * the session audit reports the resolved path + pair-exchange bound
    (schema v7) and scripts/check_telemetry_schema.py accepts the
    artifact (rejection self-tests live in tests/test_telemetry_schema.py);
  * zero retraces across sparse rounds (the AOT-prewarm contract).
"""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE, _final_vec, _run, _setup

from commefficient_tpu.data import FedSampler
from commefficient_tpu.ops.collectives import (
    all_gather_pairs,
    scatter_add_pairs,
    sparse_allreduce,
    sparse_allreduce_sharded,
)
from commefficient_tpu.ops.topk import compact_nonzero
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.parallel.mesh import WORKERS, make_mesh
from commefficient_tpu.utils.config import Config

P = jax.sharding.PartitionSpec

LOCAL = dict(mode="local_topk", k=7, topk_method="threshold")
TRUE = dict(mode="true_topk", k=9, topk_method="threshold")

# the error/momentum corners the sparse aggregation must agree with the
# dense psum on, per mode (dampening masks on the UNSCALED selection —
# the lr=0 corner is pinned separately below)
LOCAL_CASES = {
    "none": dict(error_type="none"),
    "local_err": dict(error_type="local"),
    "local_err_vel": dict(error_type="local", local_momentum=0.9),
    "local_err_rho": dict(error_type="local", virtual_momentum=0.9),
}
TRUE_CASES = {
    "none": dict(error_type="none"),
    "none_rho": dict(error_type="none", virtual_momentum=0.9),
    "virtual": dict(error_type="virtual"),
    "virtual_rho": dict(error_type="virtual", virtual_momentum=0.9),
    "virtual_decay": dict(error_type="virtual", virtual_momentum=0.9,
                          error_decay=0.9),
    "virtual_dampen": dict(error_type="virtual", virtual_momentum=0.9,
                           momentum_dampening=True),
}


# -- parity: sparse aggregation IS the dense psum ------------------------

@pytest.mark.parametrize("name", sorted(LOCAL_CASES))
def test_local_topk_sparse_matches_dense(name):
    kw = {**LOCAL, **LOCAL_CASES[name]}
    sd, ld = _run(Config(aggregate="dense", **kw, **BASE), n_rounds=4)
    ss, ls = _run(Config(aggregate="sparse", **kw, **BASE), n_rounds=4)
    np.testing.assert_allclose(ls, ld, rtol=1e-6,
                               err_msg=f"{name}: losses drifted")
    np.testing.assert_allclose(
        _final_vec(ss), _final_vec(sd), atol=1e-6,
        err_msg=f"{name}: sparse aggregation is NOT the dense psum",
    )


def test_local_topk_auto_is_sparse_and_matches():
    """auto on the multi-device threshold round resolves sparse and runs
    the same program as explicit sparse (local_topk opts in for auto: its
    sparse path changes no state shapes and no server algebra)."""
    kw = {**LOCAL, "error_type": "local"}
    sa, _ = _run(Config(**kw, **BASE), n_rounds=3)
    ss, _ = _run(Config(aggregate="sparse", **kw, **BASE), n_rounds=3)
    assert sa.aggregate_resolved == "sparse"
    np.testing.assert_array_equal(_final_vec(sa), _final_vec(ss))


@pytest.mark.parametrize("name", sorted(TRUE_CASES))
def test_true_topk_sparse_matches_dense(name):
    kw = {**TRUE, **TRUE_CASES[name]}
    sd, ld = _run(Config(aggregate="dense", **kw, **BASE), n_rounds=4)
    ss, ls = _run(Config(aggregate="sparse", **kw, **BASE), n_rounds=4)
    np.testing.assert_allclose(ls, ld, rtol=1e-6,
                               err_msg=f"{name}: losses drifted")
    np.testing.assert_allclose(
        _final_vec(ss), _final_vec(sd), atol=1e-6,
        err_msg=f"{name}: sharded-state aggregation is NOT the dense round",
    )


def test_true_topk_sparse_dampening_lr_zero_round():
    """error_type='none' + dampening at lr == 0 (a warmup round): the
    mask must come from the UNSCALED selection on the sharded slice too,
    or the twins' momentum diverges from round 1."""
    kw = {**TRUE, "error_type": "none", "virtual_momentum": 0.9,
          "momentum_dampening": True}
    finals, moms = [], []
    for agg in ("dense", "sparse"):
        cfg = Config(aggregate=agg, **kw, **BASE)
        ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
        for r, lr in enumerate((0.0, 0.3, 0.3)):
            ids, batch = sampler.sample_round(r)
            sess.train_round(ids, batch, lr)
        finals.append(_final_vec(sess))
        # the sparse rung's momentum is the [dp] workers-sharded vector;
        # D == dp at this geometry would hide a padding bug, so slice
        moms.append(np.asarray(sess.state.momentum)[:sess.grad_size])
    np.testing.assert_allclose(moms[1], moms[0], atol=1e-6,
                               err_msg="momentum diverged at the lr=0 round")
    np.testing.assert_allclose(finals[1], finals[0], atol=1e-6)


def test_local_topk_sparse_offload_matches_hbm():
    """The offloaded-client-state round threads the pair exchange
    identically (client rows ride host RAM; aggregation is on-mesh)."""
    kw = {**LOCAL, "error_type": "local", "local_momentum": 0.9,
          "aggregate": "sparse"}
    s_hbm, _ = _run(Config(**kw, **BASE), n_rounds=3)
    s_off, _ = _run(Config(offload_client_state=True, **kw, **BASE),
                    n_rounds=3)
    np.testing.assert_allclose(_final_vec(s_off), _final_vec(s_hbm),
                               atol=1e-6)


# -- fedsim masking rides the sparse paths unchanged ---------------------

def _masked_run(mode_kw, env, n_rounds=3):
    from test_sketch_decode import _cohort_env  # noqa: F401 (re-export use)

    cfg = Config(availability="bernoulli", dropout_prob=0.5, **mode_kw,
                 **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    m = None
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.3, env=env)
    return sess, sampler, m


@pytest.mark.parametrize("mode_kw", [
    {**LOCAL, "error_type": "local"},
    {**TRUE, "error_type": "virtual", "virtual_momentum": 0.9},
], ids=["local_topk", "true_topk"])
def test_fedsim_masked_sparse_matches_dense(mode_kw):
    """Masking is pre-encode and the live renormalization is a scalar on
    the aggregate, so both commute with the pair exchange."""
    from test_sketch_decode import _cohort_env

    S = [0, 2, 3, 5, 7]
    sd, _, _ = _masked_run({**mode_kw, "aggregate": "dense"},
                           _cohort_env(S))
    ss, _, m = _masked_run({**mode_kw, "aggregate": "sparse"},
                           _cohort_env(S))
    assert m["fedsim/participation_rate"] == len(S) / 8
    np.testing.assert_allclose(_final_vec(ss), _final_vec(sd), atol=1e-6)


def test_fedsim_all_dropped_round_freezes_sparse_state():
    """Zero live clients under true_topk sparse aggregation: the gathered
    candidate VALUES zero out and the workers-sharded momentum/error
    leaves carry forward — the all-dropped guard must hold for sharded
    server state exactly as it does replicated."""
    from test_sketch_decode import _cohort_env

    kw = {**TRUE, "error_type": "virtual", "virtual_momentum": 0.9,
          "aggregate": "sparse"}
    ss, sampler, _ = _masked_run(kw, _cohort_env([0, 2, 3, 5, 7]))
    before = _final_vec(ss).copy()
    mom = np.asarray(ss.state.momentum).copy()
    err = np.asarray(ss.state.error).copy()
    ids, batch = sampler.sample_round(5)
    m = ss.train_round(ids, batch, 0.3, env=_cohort_env([]))
    assert m["fedsim/all_dropped"] == 1.0
    assert np.array_equal(before, _final_vec(ss))
    assert np.array_equal(mom, np.asarray(ss.state.momentum))
    assert np.array_equal(err, np.asarray(ss.state.error))
    assert np.isfinite(float(m["loss"]))


# -- resolution + validation ---------------------------------------------

def test_auto_resolution_and_validation():
    """auto = sparse only where it is a pure aggregation swap: local_topk
    on a multi-device threshold round. true_topk/sketch re-home server
    state / reroute error feedback, so they engage on explicit opt-in
    only; invalid combinations fail at Config time."""
    ds, params, loss_fn = _setup()
    sess = FederatedSession(
        Config(**LOCAL, error_type="local", **BASE), params, loss_fn)
    assert sess.aggregate_resolved == "sparse"
    # exact top-k pads its transmit densely -> stays dense
    sess = FederatedSession(
        Config(**{**LOCAL, "topk_method": "exact"}, error_type="local",
               **BASE), params, loss_fn)
    assert sess.aggregate_resolved == "dense"
    # single-device mesh: nothing to exchange -> dense
    sess = FederatedSession(
        Config(**LOCAL, error_type="local", **{**BASE, "num_devices": 1}),
        params, loss_fn)
    assert sess.aggregate_resolved == "dense"
    # true_topk/sketch: auto never flips them (explicit opt-in only)
    sess = FederatedSession(
        Config(**TRUE, error_type="virtual", **BASE), params, loss_fn)
    assert sess.aggregate_resolved == "dense"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess = FederatedSession(
            Config(mode="sketch", k=40, num_rows=3, num_cols=256,
                   error_type="virtual", topk_method="threshold", **BASE),
            params, loss_fn)
    assert sess.aggregate_resolved == "dense"
    # Config-time validation
    with pytest.raises(ValueError, match="sparse transmit"):
        Config(mode="uncompressed", aggregate="sparse", **BASE)
    with pytest.raises(ValueError, match="fsdp"):
        Config(**TRUE, error_type="virtual", aggregate="sparse",
               fsdp=True, **BASE)
    with pytest.raises(ValueError, match="threshold"):
        Config(**{**TRUE, "topk_method": "exact"}, error_type="virtual",
               aggregate="sparse", **BASE)
    with pytest.raises(ValueError, match="auto|dense|sparse"):
        Config(**LOCAL, aggregate="bogus", **BASE)
    # degenerate explicit sparse on a 1-device mesh: works, but warns
    with pytest.warns(UserWarning, match="degenerate"):
        FederatedSession(
            Config(**LOCAL, error_type="local", aggregate="sparse",
                   **{**BASE, "num_devices": 1}),
            params, loss_fn)


# -- compiled-HLO traffic pins -------------------------------------------

def _compiled_round_text(cfg):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    args = [sess.state, jnp.asarray(ids),
            {k: jnp.asarray(v) for k, v in batch.items()}, jnp.float32(0.2)]
    if cfg.offload_client_state:
        ids_np = np.asarray(ids)
        args.append(jnp.asarray(sess.host_vel[ids_np])
                    if sess.host_vel is not None else ())
        args.append(jnp.asarray(sess.host_err[ids_np])
                    if sess.host_err is not None else ())
    return sess, sess.round_fn.lower(*args).compile().as_text()


def _collective_shapes(text, op):
    """(elems, line) per static ``op`` occurrence, skipping -done halves
    (the -start line carries an (operand, output, ...) tuple — take the
    transferred second component, as telemetry/xla_audit.py does).
    ``elems`` is the largest buffer of a variadic op: XLA's combiner packs
    the idx/val pair exchanges and the loss scalars into one launch."""
    out = []
    for ln in text.splitlines():
        m = re.search(r"=\s*([^=]*?)\s*" + op + r"(-start)?\(", ln)
        if m is None:
            continue
        shapes = [int(np.prod([int(x) for x in dims.split(",") if x]))
                  for _, dims in re.findall(
                      r"([a-z]+[0-9]+[a-z0-9]*|pred)\[([\d,]*)\]",
                      m.group(1))]
        if m.group(2) and len(shapes) > 1:
            shapes = shapes[1:]
        out.append((max(shapes), ln))
    return out


def test_hlo_sparse_round_moves_no_dense_collective():
    """THE acceptance pin: the compiled sparse round (client state
    offloaded — in-graph [C, D] rows have their own pre-existing
    writeback gather) contains no all-reduce or all-gather of >= O(D)
    elements; every exchange is <= the W*k pair bound (times w_loc for
    local_topk's per-client buffers)."""
    cases = [
        (Config(**LOCAL, error_type="local", offload_client_state=True,
                aggregate="sparse", **BASE),
         "sparse_allreduce", 8 * 1 * 7),
        (Config(**TRUE, error_type="virtual", virtual_momentum=0.9,
                aggregate="sparse", **BASE),
         "sparse_aggregate_decode", 8 * 9),
    ]
    for cfg, marker, pair_bound in cases:
        sess, text = _compiled_round_text(cfg)
        d = sess.grad_size
        assert pair_bound < d, "traffic claim trivial at this geometry"
        assert marker in text, f"named-scope marker {marker!r} missing"
        for op in ("all-reduce", "all-gather"):
            for elems, ln in _collective_shapes(text, op):
                assert elems <= pair_bound, (
                    f"{cfg.mode}: {op} of {elems} elements exceeds the "
                    f"pair-exchange bound {pair_bound} — a d-sized "
                    f"collective leaked in: {ln.strip()[:160]!r}"
                )


def test_hlo_true_topk_sparse_uses_reduce_scatter():
    """The dense transmit lands sharded via reduce-scatter (O(D/W) per
    link — the legal dense-payload collective), never via an all-reduce."""
    cfg = Config(**TRUE, error_type="virtual", aggregate="sparse", **BASE)
    _, text = _compiled_round_text(cfg)
    assert _collective_shapes(text, "reduce-scatter"), (
        "the sharded aggregation must lower to reduce-scatter"
    )


def test_hlo_dense_round_fuses_collectives_into_one_psum():
    """Satellite pin (tuple-psum fusion): the uncompressed dense round's
    agg + loss_mean + aux_sum reductions lower to exactly ONE all-reduce
    (concat-of-raveled-f32-leaves — bitwise the same sums, one launch)."""
    cfg = Config(mode="uncompressed", **BASE)
    _, text = _compiled_round_text(cfg)
    ars = _collective_shapes(text, "all-reduce")
    assert len(ars) == 1, (
        f"expected ONE fused all-reduce, found {len(ars)}: "
        + "; ".join(ln.strip()[:100] for _, ln in ars)
    )
    # and the local_topk DENSE round keeps the same fused shape
    cfg = Config(**LOCAL, error_type="local", aggregate="dense", **BASE)
    _, text = _compiled_round_text(cfg)
    assert len(_collective_shapes(text, "all-reduce")) == 1


def test_hlo_one_device_auto_is_bit_identical_to_dense():
    """Defaults stay untouched: on a 1-device mesh auto resolves dense and
    the lowered round is BYTE-identical to explicit dense."""
    base1 = {**BASE, "num_devices": 1, "num_workers": 1, "num_clients": 4}
    texts = {}
    for agg in (None, "dense"):
        kw = {} if agg is None else {"aggregate": agg}
        cfg = Config(**LOCAL, error_type="local", **kw, **base1)
        ds, params, loss_fn = _setup(4)
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=1, local_batch_size=4, seed=1)
        ids, batch = sampler.sample_round(0)
        texts[agg] = sess.round_fn.lower(
            sess.state, jnp.asarray(ids),
            {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.float32(0.2),
        ).as_text()
        assert sess.aggregate_resolved == "dense"
    assert texts[None] == texts["dense"]


# -- audit + schema (producer side; checker rejections in
#    tests/test_telemetry_schema.py) -------------------------------------

def test_audit_reports_sparse_aggregate_and_checker_accepts(tmp_path):
    import importlib.util as iu
    import pathlib

    spec_ = iu.spec_from_file_location(
        "check_telemetry_schema",
        pathlib.Path(__file__).resolve().parents[1]
        / "scripts" / "check_telemetry_schema.py",
    )
    checker = iu.module_from_spec(spec_)
    spec_.loader.exec_module(checker)

    cfg = Config(**TRUE, error_type="virtual", aggregate="sparse", **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    audit = sess.audit_compiled_round(np.asarray(ids), batch, 0.2)
    rep = audit.report(generated_by="test", cfg=cfg)
    assert rep["aggregate"] == "sparse"
    assert rep["collectives"]["sparse_agg_bound"] == 8 * TRUE["k"]
    assert rep["collectives"]["max_all_reduce_elems"] is not None
    path = audit.write(str(tmp_path), generated_by="test", cfg=cfg)
    checker.validate_perf_report(path)  # must not raise

    # the dense twin records aggregate='dense' with no bound
    cfg_d = Config(**TRUE, error_type="virtual", aggregate="dense", **BASE)
    sess_d = FederatedSession(cfg_d, params, loss_fn)
    rep_d = sess_d.audit_compiled_round(
        np.asarray(ids), batch, 0.2).report(generated_by="test")
    assert rep_d["aggregate"] == "dense"
    assert rep_d["collectives"]["sparse_agg_bound"] is None


def test_zero_retraces_across_sparse_rounds():
    """The sparse programs are as signature-stable as the dense ones: no
    silent retrace across rounds or the audit's AOT trace."""
    for kw in ({**LOCAL, "error_type": "local"},
               {**TRUE, "error_type": "virtual", "aggregate": "sparse"}):
        sess, _ = _run(Config(**kw, **BASE), n_rounds=4)
        assert sess.retrace_sentinel.retraces == 0, kw["mode"]


# -- pair-exchange primitive contracts -----------------------------------

def test_sparse_allreduce_matches_dense_sum():
    """compact -> pair all_gather -> scatter-add == the dense psum, for
    W k-sparse vectors with overlapping supports (duplicate coordinates
    accumulate)."""
    rng = np.random.default_rng(0)
    d, k, Wd = 257, 6, 8  # odd d: no accidental alignment
    dense = np.zeros((Wd, d), np.float32)
    for w in range(Wd):
        sup = rng.choice(d // 2, size=k, replace=False)  # forced overlap
        dense[w, sup] = rng.normal(size=k).astype(np.float32)
    mesh = make_mesh(Wd)
    f = jax.shard_map(
        lambda v: sparse_allreduce(v[0], k, WORKERS)[None],
        mesh=mesh, in_specs=(P(WORKERS),), out_specs=P(WORKERS),
    )
    out = np.asarray(jax.jit(f)(jnp.asarray(dense)))
    want = dense.sum(axis=0)
    for w in range(Wd):  # replicated: every chip holds the full sum
        np.testing.assert_allclose(out[w], want, atol=1e-6)


def test_sparse_allreduce_sharded_matches_sum_then_slice():
    """The recursive-halving ppermute schedule: each chip ends with its
    balanced D/W slice of the global sum — psum-then-slice, without the
    psum."""
    rng = np.random.default_rng(1)
    d, k, Wd = 512, 5, 8
    dense = np.zeros((Wd, d), np.float32)
    for w in range(Wd):
        sup = rng.choice(d, size=k, replace=False)
        dense[w, sup] = rng.normal(size=k).astype(np.float32)
    mesh = make_mesh(Wd)
    f = jax.shard_map(
        lambda v: sparse_allreduce_sharded(
            v[0], k, WORKERS, axis_size=Wd)[None],
        mesh=mesh, in_specs=(P(WORKERS),), out_specs=P(WORKERS),
    )
    out = np.asarray(jax.jit(f)(jnp.asarray(dense))).reshape(-1)
    np.testing.assert_allclose(out, dense.sum(axis=0), atol=1e-6)


def test_sparse_allreduce_sharded_lowers_to_ppermute_only():
    """The schedule's traffic claim: pure collective-permute HLO — no
    all-reduce, no all-gather, nothing replicated."""
    d, k, Wd = 512, 5, 8
    mesh = make_mesh(Wd)
    f = jax.shard_map(
        lambda v: sparse_allreduce_sharded(
            v[0], k, WORKERS, axis_size=Wd)[None],
        mesh=mesh, in_specs=(P(WORKERS),), out_specs=P(WORKERS),
    )
    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((Wd, d), jnp.float32)).compile().as_text()
    assert "collective-permute" in text
    assert "all-reduce" not in text
    assert "all-gather" not in text


def test_sparse_allreduce_sharded_rejects_non_power_of_two():
    with pytest.raises(ValueError, match="power-of-two"):
        sparse_allreduce_sharded(jnp.zeros(16), 4, WORKERS, axis_size=6)


def test_all_gather_pairs_and_scatter_add_contracts():
    """all_gather_pairs flattens [W, cap] -> [W*cap] in axis order;
    scatter_add_pairs accumulates duplicate coordinates and treats
    (0, 0.0) padding as a no-op."""
    Wd = 8
    mesh = make_mesh(Wd)
    f = jax.shard_map(
        lambda i, v: tuple(
            a[None] for a in all_gather_pairs(i[0], v[0], WORKERS)),
        mesh=mesh, in_specs=(P(WORKERS), P(WORKERS)),
        out_specs=(P(WORKERS), P(WORKERS)),
    )
    idx = jnp.arange(Wd * 3, dtype=jnp.int32).reshape(Wd, 3)
    val = jnp.asarray(np.arange(Wd * 3, dtype=np.float32).reshape(Wd, 3))
    g_idx, g_val = jax.jit(f)(idx, val)
    np.testing.assert_array_equal(np.asarray(g_idx[0]), np.arange(Wd * 3))
    np.testing.assert_array_equal(np.asarray(g_val[0]),
                                  np.arange(Wd * 3, dtype=np.float32))
    out = scatter_add_pairs(
        6, jnp.asarray([2, 2, 5, 0, 0], jnp.int32),
        jnp.asarray([1.0, 2.5, -1.0, 0.0, 0.0], jnp.float32),
    )
    np.testing.assert_allclose(np.asarray(out),
                               [0.0, 0.0, 3.5, 0.0, 0.0, -1.0])


def test_compact_nonzero_edge_cases():
    """Satellite: the contracts the pair exchange leans on, beyond
    test_sketch_decode's basic round-trip."""
    # > k nonzeros: the FIRST k by position are kept, the tail dropped —
    # documented drop semantics (the sparse capacity is a hard buffer)
    v = jnp.asarray([1.0, 0.0, 2.0, 3.0, 0.0, 4.0, 5.0])
    idx, val = compact_nonzero(v, 3)
    np.testing.assert_array_equal(np.asarray(idx), [0, 2, 3])
    np.testing.assert_array_equal(np.asarray(val), [1.0, 2.0, 3.0])
    # k = 0: a legal empty buffer, scatter-safe
    idx, val = compact_nonzero(v, 0)
    assert idx.shape == val.shape == (0,)
    np.testing.assert_allclose(
        np.asarray(jnp.zeros(7).at[idx].add(val)), np.zeros(7))
    # duplicate magnitudes (ties) are irrelevant to compaction: selection
    # happened upstream; compaction is positional and keeps BOTH
    v = jnp.asarray([0.0, 2.0, -2.0, 0.0, 2.0])
    idx, val = compact_nonzero(v, 4)
    np.testing.assert_array_equal(np.asarray(idx), [1, 2, 4, 0])
    np.testing.assert_array_equal(np.asarray(val), [2.0, -2.0, 2.0, 0.0])
    # all-zero input at k = capacity: pure padding
    idx, val = compact_nonzero(jnp.zeros(5), 5)
    assert not np.any(np.asarray(val)) and not np.any(np.asarray(idx))


def test_sparse_allreduce_capacity_overflow_drops_by_position():
    """More nonzeros than the declared capacity: compact keeps the first
    ``capacity`` by position — the exchange NEVER silently grows. (In the
    round this cannot trigger: local_topk's transmit has <= w_loc*k
    nonzeros by construction and capacity is exactly w_loc*k.)"""
    Wd = 8
    mesh = make_mesh(Wd)
    v = jnp.ones((Wd, 16), jnp.float32)  # 16 nonzeros, capacity 4
    f = jax.shard_map(
        lambda x: sparse_allreduce(x[0], 4, WORKERS)[None],
        mesh=mesh, in_specs=(P(WORKERS),), out_specs=P(WORKERS),
    )
    out = np.asarray(jax.jit(f)(v))[0]
    np.testing.assert_allclose(out[:4], 8.0)  # first 4 coords survive
    np.testing.assert_allclose(out[4:], 0.0)  # the tail is dropped
