"""Sharded sparse sketch decode (PR 6): decode-path equivalence + HLO pins.

The replicated round's sketch server update can decode dense (legacy:
every chip repeats the full-D estimate -> top-k -> unsketch -> re-sketch)
or sharded (``SketchCompressor.server_update_sharded``: each chip
estimates its D/W coordinate slice, the global threshold uses scalar-only
collectives, and one ~W*k candidate all_gather replaces the full-D work).
Pinned here, on the virtual 8-device CPU mesh:

  * dense vs sharded vs Pallas-fused final params atol 1e-6 (bit-equal on
    CPU for the threshold kernel: integer-count bisection + the gather
    estimate path being bit-equal to the matmul path) across error_type
    none/virtual, error_decay, rho>0, degenerate top-k ties, and
    fedsim-masked (+ all-dropped) rounds;
  * the compiled sharded round contains NO full-d ``estimate_all`` (the
    named_scope marker in ops/countsketch.py), NO dense-decode branch
    (round.py's ``server_decode_dense`` marker), and no all-gather beyond
    the ~W*k candidate exchange — the acceptance criterion's traffic
    claim, checked on real lowered shapes;
  * byte accounting and the CommLedger exactness invariant are identical
    across decode paths (decode is server-side; accounting must not
    drift);
  * the dampening branch's sparse support-estimate (satellite fix) equals
    the legacy full-D ``estimate_all`` formula.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE, _final_vec, _run, _setup

from commefficient_tpu.data import FedSampler
from commefficient_tpu.fedsim import RoundEnv
from commefficient_tpu.ops.countsketch import (
    CountSketch,
    estimate_all,
    estimate_at,
    sketch_sparse,
    sketch_vec,
)
from commefficient_tpu.ops.topk import compact_nonzero, topk_threshold_dense
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.telemetry import CommLedger
from commefficient_tpu.utils.config import Config

SKETCH = dict(mode="sketch", k=40, num_rows=3, num_cols=256,
              topk_method="threshold")

# the error-feedback/momentum corners the dense<->sharded algebra must
# agree on (ISSUE 6 satellite: none/virtual, error_decay, rho>0)
DECODE_CASES = {
    "virtual_rho": dict(error_type="virtual", virtual_momentum=0.9),
    "virtual_decay": dict(error_type="virtual", virtual_momentum=0.9,
                          error_decay=0.9),
    "virtual_norho": dict(error_type="virtual"),
    "none_rho": dict(error_type="none", virtual_momentum=0.9),
}


@pytest.mark.parametrize("name", sorted(DECODE_CASES))
def test_sharded_decode_matches_dense(name):
    kw = {**SKETCH, **DECODE_CASES[name]}
    sd, ld = _run(Config(sketch_decode="dense", **kw, **BASE),
                  n_rounds=4, lr=0.2)
    ss, ls = _run(Config(sketch_decode="sharded", **kw, **BASE),
                  n_rounds=4, lr=0.2)
    np.testing.assert_allclose(ls, ld, rtol=1e-6,
                               err_msg=f"{name}: losses drifted")
    np.testing.assert_allclose(
        _final_vec(ss), _final_vec(sd), atol=1e-6,
        err_msg=f"{name}: sharded decode is NOT the dense decode",
    )


def test_pallas_backend_sharded_decode_matches_dense():
    """backend='pallas' twins inside the round: the Pallas encode traces
    under the workers shard_map (its ``out_shape`` carries the varying
    type) and the sharded decode — the backend-agnostic ``estimate_at``
    gather — matches the same backend's dense decode, which runs the
    Pallas estimate kernels (einsum-vs-pallas encode parity is pinned by
    tests/test_countsketch_pallas.py)."""
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9,
          "sketch_backend": "pallas"}
    sd, _ = _run(Config(sketch_decode="dense", **kw, **BASE),
                 n_rounds=2, lr=0.2)
    ss, _ = _run(Config(sketch_decode="sharded", **kw, **BASE),
                 n_rounds=2, lr=0.2)
    np.testing.assert_allclose(_final_vec(ss), _final_vec(sd), atol=1e-6)


def test_auto_resolution_and_validation():
    """auto = sharded iff >1 worker device AND threshold top-k; explicit
    'sharded' demands the threshold kernel + sketch mode at Config time."""
    ds, params, loss_fn = _setup()
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9}
    sess = FederatedSession(Config(**kw, **BASE), params, loss_fn)
    assert sess.sketch_decode_resolved == "sharded"
    # exact top-k keeps the dense path (tie-breaking semantics preserved)
    sess = FederatedSession(
        Config(**{**kw, "topk_method": "exact"}, **BASE), params, loss_fn
    )
    assert sess.sketch_decode_resolved == "dense"
    # single-device mesh: no redundant work to remove -> dense
    sess = FederatedSession(
        Config(**kw, **{**BASE, "num_devices": 1}), params, loss_fn
    )
    assert sess.sketch_decode_resolved == "dense"
    with pytest.raises(ValueError, match="threshold"):
        Config(**{**kw, "topk_method": "exact"},
               sketch_decode="sharded", **BASE)
    with pytest.raises(ValueError, match="sketch"):
        Config(mode="uncompressed", sketch_decode="sharded", **BASE)
    with pytest.raises(ValueError, match="auto|dense|sharded"):
        Config(sketch_decode="bogus", **BASE)
    # degenerate explicit sharded on a 1-device mesh: works, but warns
    with pytest.warns(UserWarning, match="degenerate"):
        FederatedSession(
            Config(**kw, sketch_decode="sharded",
                   **{**BASE, "num_devices": 1}),
            params, loss_fn,
        )


def test_degenerate_topk_ties_drop_identically():
    """>k coordinates tying at the max magnitude: no threshold selects
    <=k, so BOTH decode paths must honor the at-most-k contract by
    dropping the tied set entirely (ops/topk.py degenerate-tie contract;
    error feedback retains it for later rounds)."""
    from commefficient_tpu.compress import get_compressor
    from commefficient_tpu.parallel.mesh import WORKERS, make_mesh

    P = jax.sharding.PartitionSpec
    d, k, Wd = 4096, 30, 8
    cfg = Config(mode="sketch", error_type="none", k=k, num_rows=3,
                 num_cols=32768, topk_method="threshold",
                 sketch_decode="sharded", **BASE)
    spec = CountSketch(d=d, c=32768, r=3, seed=0)
    comp = get_compressor(cfg, d=d, spec=spec)
    v = jnp.zeros(d).at[jnp.arange(0, d, 64)].set(1.0)  # 64 tied maxima
    agg = sketch_vec(spec, v)
    # precondition: the tie really reaches the estimates (c >> d, so the
    # 64 heavy coords estimate exactly 1.0 and outnumber k)
    est = estimate_all(spec, agg)
    assert int(jnp.sum(jnp.abs(est) >= jnp.max(jnp.abs(est)))) > k
    delta, _, _, _ = comp.server_update((), (), (), agg, jnp.float32(0.1),
                                        jnp.int32(0))
    assert float(jnp.max(jnp.abs(delta))) == 0.0, "dense must drop ties"

    mesh = make_mesh(Wd)
    dec = jax.shard_map(
        lambda a: comp.server_update_sharded(
            (), (), (), a, jnp.float32(0.1), jnp.int32(0),
            axis_name=WORKERS, Wd=Wd, d=d,
        ),
        mesh=mesh, in_specs=(P(),), out_specs=(P(),) * 5,
    )
    g_idx, g_val, _, _, _ = jax.jit(dec)(agg)
    assert float(jnp.max(jnp.abs(g_val))) == 0.0, "sharded must drop ties"
    assert g_idx.shape == (Wd * k,)


def _cohort_env(live_slots, num_workers=8):
    live = np.zeros(num_workers, np.float32)
    live[live_slots] = 1.0
    n = float(live.sum())
    return RoundEnv(
        live=live, corrupt=np.zeros(num_workers, np.float32),
        live_count=np.float32(n),
        stats={"fedsim/participation_rate": n / num_workers,
               "fedsim/dropped": num_workers - n,
               "fedsim/straggler_excluded": 0.0,
               "fedsim/all_dropped": float(n == 0)},
    )


def _masked_run(decode, env, n_rounds=3):
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9}
    cfg = Config(sketch_decode=decode, availability="bernoulli",
                 dropout_prob=0.5, **kw, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    m = None
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.3, env=env)
    return sess, sampler, m


def test_fedsim_masked_sharded_matches_dense():
    """Masking is pre-encode, so it commutes with the decode unchanged: a
    masked sharded round equals the masked dense round."""
    S = [0, 2, 3, 5, 7]
    sd, _, _ = _masked_run("dense", _cohort_env(S))
    ss, _, m = _masked_run("sharded", _cohort_env(S))
    assert m["fedsim/participation_rate"] == len(S) / 8
    np.testing.assert_allclose(_final_vec(ss), _final_vec(sd), atol=1e-6)


def test_fedsim_all_dropped_round_freezes_sharded():
    """Zero live clients under the sharded decode: the candidate values
    zero out (the k-sparse scatter applies nothing) and every server-state
    leaf carries forward — the sparse form of the all-dropped guard."""
    ss, sampler, _ = _masked_run("sharded", _cohort_env([0, 2, 3, 5, 7]))
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


def test_offload_sharded_matches_hbm_client_state():
    """The offloaded-client-state round_fn variant threads the sharded
    decode identically (local momentum rows ride host RAM; decode is
    server-side)."""
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9,
          "local_momentum": 0.9, "sketch_decode": "sharded"}
    s_hbm, _ = _run(Config(**kw, **BASE), n_rounds=3, lr=0.2)
    s_off, _ = _run(Config(offload_client_state=True, **kw, **BASE),
                    n_rounds=3, lr=0.2)
    np.testing.assert_allclose(_final_vec(s_off), _final_vec(s_hbm),
                               atol=1e-6)


def test_device_index_path_sharded_matches_dense():
    """The device-resident-data round (attach_data/train_round_indices)
    threads the decode through the same build_round_fn — pin it anyway:
    an index-driven sharded round equals the index-driven dense round."""
    from test_device_data import _mlp_loss, _toy_ds, augment_batch

    from commefficient_tpu.parallel.mesh import make_mesh

    finals = []
    for dec in ("dense", "sharded"):
        cfg = Config(mode="sketch", error_type="virtual",
                     virtual_momentum=0.9, k=64, num_rows=3, num_cols=2048,
                     num_clients=16, num_workers=8, num_devices=8,
                     local_batch_size=4, weight_decay=0.0, seed=1,
                     topk_method="threshold", sketch_decode=dec)
        params, loss_fn = _mlp_loss()
        ds = _toy_ds(num_clients=16)
        session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(8))
        sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1,
                             augment=augment_batch)
        session.attach_data(ds.data, augment_batch)
        for r in range(3):
            ids, idx, plan = sampler.sample_round_indices(r)
            session.train_round_indices(ids, idx, plan, 0.1)
        finals.append(np.asarray(session.state.params_vec))
    np.testing.assert_allclose(finals[1], finals[0], atol=1e-6)


def test_sharded_telemetry_scalars_match_dense():
    """The sparse diagnostics path (diagnostics_sparse/fidelity_sparse)
    reports the SAME scalars as the dense path: update_norm sums disjoint
    candidate values, fidelity re-estimates at the same support."""
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9,
          "telemetry_level": 2}
    mets = {}
    for dec in ("dense", "sharded"):
        cfg = Config(sketch_decode=dec, **kw, **BASE)
        ds, params, loss_fn = _setup(cfg.num_clients)
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
        ids, batch = sampler.sample_round(0)
        mets[dec] = sess.train_round(ids, batch, 0.2)
    for key in ("diag/grad_norm", "diag/update_norm",
                "diag/ef_residual_norm", "diag/ef_residual_max",
                "diag/sketch_est_rel_err"):
        a = float(np.asarray(mets["dense"][key]))
        b = float(np.asarray(mets["sharded"][key]))
        np.testing.assert_allclose(b, a, rtol=1e-4, err_msg=key)
    assert float(np.asarray(mets["sharded"]["diag/nonfinite"])) == 0.0


def _compiled_round_text(cfg):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    lowered = sess.round_fn.lower(
        sess.state, jnp.asarray(ids),
        {k: jnp.asarray(v) for k, v in batch.items()}, jnp.float32(0.2),
    )
    return sess, lowered.compile().as_text()


def test_hlo_sharded_round_has_no_dense_decode():
    """PR-6 acceptance HLO pin (precedent: the telemetry level-0 pin): the
    compiled sharded round contains NO full-d ``estimate_all`` (the
    named_scope marker every full-d estimate carries), NO dense server
    decode branch (round.py's ``server_decode_dense`` marker), and beyond
    its table-sized psums (the aggregate and the EF re-sketch — the
    mode's design payload) it only reduces the ~W*k candidate exchange —
    an invariant gather, so it lowers to an all-reduce of the gathered
    [Wd, k] buffer — no d-sized vector ever crosses the ICI. The dense
    round proves both markers detect what they claim to."""
    kw = {**SKETCH, "k": 10, "error_type": "virtual",
          "virtual_momentum": 0.9}
    sess_d, text_d = _compiled_round_text(
        Config(sketch_decode="dense", **kw, **BASE)
    )
    assert "estimate_all" in text_d  # marker validity
    assert "server_decode_dense" in text_d
    assert "sketch_decode_sharded" not in text_d
    assert "all-gather(" not in text_d  # the dense round has NO gathers

    sess_s, text_s = _compiled_round_text(
        Config(sketch_decode="sharded", **kw, **BASE)
    )
    assert "estimate_all" not in text_s
    assert "server_decode_dense" not in text_s
    assert "sketch_decode_sharded" in text_s
    assert "all-gather(" not in text_s
    d, Wd, k = sess_s.grad_size, 8, 10
    assert Wd * k < d  # the traffic claim is non-trivial at this geometry

    def reduced_buffers(text):
        # every buffer an all-reduce returns (variadic ops list several)
        return [
            int(np.prod([int(x) for x in dims.split(",") if x]))
            for ln in text.splitlines()
            if (m := re.search(r"=\s*([^=]*?)\s*all-reduce(-start)?\(", ln))
            for dims in re.findall(r"[a-z]+[0-9]+\[([\d,]*)\]", m.group(1))
        ]

    r, c = sess_s.rungs[0].spec.table_shape
    bufs = reduced_buffers(text_s)
    assert Wd * k in bufs, "the candidate exchange must exist"
    # a table psum may carry the fused loss/aux scalars
    vectors = [n for n in bufs if not r * c <= n <= r * c + 8]
    assert max(vectors) <= Wd * k, (
        f"all-reduce of {max(vectors)} elements exceeds the W*k candidate "
        f"exchange ({Wd * k}); a d-sized collective leaked in"
    )


def test_accounting_invariant_across_decode_paths():
    """Decode is server-side: upload/download accounting and the
    CommLedger exactness invariant must be byte-identical across decode
    paths (the ledger-invariance satellite)."""
    ds, params, loss_fn = _setup()
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9}
    bpr, ledgers = {}, {}
    for dec in ("dense", "sharded", "auto"):
        sess = FederatedSession(Config(sketch_decode=dec, **kw, **BASE),
                                params, loss_fn)
        bpr[dec] = sess.bytes_per_round()
        assert sess.compressor.masked_upload_floats(5) == (
            5 * sess.compressor.upload_floats()
        )
        led = CommLedger(bpr[dec], mode="sketch", num_workers=8,
                         masked=True, compressor=sess.compressor)
        scal = {"fedsim/participation_rate": 5 / 8, "fedsim/dropped": 3.0}
        rows = [led.on_round(r, scal) for r in range(3)]
        ledgers[dec] = (rows, led.cum_up_bytes, led.cum_down_bytes)
    assert bpr["dense"] == bpr["sharded"] == bpr["auto"]
    assert ledgers["dense"] == ledgers["sharded"] == ledgers["auto"]
    # and the exactness invariant holds for the masked rounds:
    # cum_up_bytes == live_client_rounds x upload_bytes
    _, cum_up, _ = ledgers["sharded"]
    assert cum_up == 3 * 5 * bpr["sharded"]["upload_bytes"]


def test_dampening_support_estimate_matches_legacy_formula():
    """Satellite fix regression (compress/sketch.py dampening branch): the
    sparse support-estimate (compact_nonzero + estimate_at +
    sketch_sparse) equals the legacy full-D formula
    ``sketch_vec(where(update != 0, estimate_all(m), 0))`` it replaced."""
    rng = np.random.default_rng(3)
    spec = CountSketch(d=4096, c=2048, r=3, seed=1)
    m_tab = sketch_vec(spec, jnp.asarray(
        rng.normal(size=4096).astype(np.float32)))
    update = topk_threshold_dense(
        jnp.asarray(rng.normal(size=4096).astype(np.float32)), 50
    )
    legacy = sketch_vec(
        spec, jnp.where(update != 0, estimate_all(spec, m_tab), 0.0)
    )
    idx, val = compact_nonzero(update, 50)
    sparse = sketch_sparse(
        spec, idx,
        jnp.where(val != 0, estimate_at(spec, m_tab, idx), 0.0),
    )
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(legacy),
                               atol=1e-6)


def test_dampening_e2e_dense_matches_sharded():
    """Both decode paths' sparse dampening branches agree end to end (the
    combination is gated as unstable at paper scale — parity-experiment
    flag — but its algebra must still be decode-invariant)."""
    kw = {**SKETCH, "error_type": "virtual", "virtual_momentum": 0.9,
          "momentum_dampening": True,
          "allow_unstable_sketch_dampening": True}
    with pytest.warns(UserWarning, match="dampening"):
        sd, _ = _run(Config(sketch_decode="dense", **kw, **BASE),
                     n_rounds=3, lr=0.2)
    with pytest.warns(UserWarning, match="dampening"):
        ss, _ = _run(Config(sketch_decode="sharded", **kw, **BASE),
                     n_rounds=3, lr=0.2)
    np.testing.assert_allclose(_final_vec(ss), _final_vec(sd), atol=1e-6)


def test_dampening_lr_zero_round_decode_invariant():
    """Regression (review find): with error_type='none' the applied slice
    is lr-scaled, but the dampening mask must come from the UNSCALED
    selection support — at lr == 0 (a warmup round) the dense path still
    dampens momentum at the would-be update's support, so the sharded
    path must too, or the two decodes' momentum diverges from round 1."""
    import warnings

    kw = {**SKETCH, "error_type": "none", "virtual_momentum": 0.9,
          "momentum_dampening": True,
          "allow_unstable_sketch_dampening": True}
    finals, moms = [], []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for dec in ("dense", "sharded"):
            cfg = Config(sketch_decode=dec, **kw, **BASE)
            ds, params, loss_fn = _setup(cfg.num_clients)
            sess = FederatedSession(cfg, params, loss_fn)
            sampler = FedSampler(ds, num_workers=8, local_batch_size=4,
                                 seed=1)
            for r, lr in enumerate((0.0, 0.2, 0.2)):  # warmup-style lr=0
                ids, batch = sampler.sample_round(r)
                sess.train_round(ids, batch, lr)
            finals.append(_final_vec(sess))
            moms.append(np.asarray(sess.state.momentum))
    np.testing.assert_allclose(moms[1], moms[0], atol=1e-6,
                               err_msg="momentum diverged at the lr=0 round")
    np.testing.assert_allclose(finals[1], finals[0], atol=1e-6)


def test_compact_nonzero_contract():
    v = jnp.zeros(20).at[jnp.asarray([3, 7, 15])].set(
        jnp.asarray([1.5, -2.0, 0.25])
    )
    idx, val = compact_nonzero(v, 5)
    assert idx.shape == val.shape == (5,)
    np.testing.assert_array_equal(np.asarray(idx), [3, 7, 15, 0, 0])
    np.testing.assert_array_equal(np.asarray(val), [1.5, -2.0, 0.25, 0, 0])
    # k greater than the vector length clamps the buffer
    idx, val = compact_nonzero(jnp.asarray([0.0, 2.0, 0.0]), 10)
    assert idx.shape == (3,) and float(val[0]) == 2.0
    # all-zero input: full padding, scatter-safe
    idx, val = compact_nonzero(jnp.zeros(8), 4)
    assert not np.any(np.asarray(val))
    # jit + reconstruction round-trip at exactly k nonzeros
    dense = jnp.zeros(64).at[jnp.arange(0, 64, 8)].set(1.0 + jnp.arange(8))
    idx, val = jax.jit(lambda v: compact_nonzero(v, 8))(dense)
    np.testing.assert_array_equal(
        np.asarray(jnp.zeros(64).at[idx].add(val)), np.asarray(dense)
    )
