"""clientstore/ — host-resident per-client state (store, LRU cache,
cohort streamer, round integration).

Parity contract (what these tests pin, and why):

  * host vs mmap vs host+cache share ONE compiled round program (rows
    arrive as jit arguments either way), so they are compared BITWISE —
    params, banks, and the drained scalar sequence.
  * host vs device are DIFFERENT XLA programs (the device round fuses an
    in-graph [C, D] gather/scatter; the hosted round takes [W, D] rows as
    donated arguments), and XLA's FMA/fusion choices differ across
    programs: under ``jax.disable_jit()`` the two paths are bit-identical,
    under jit the participants' bank rows pick up scattered 1-ulp
    differences (observed max 3e-8). That is the same cross-program
    reality the seed's own placement-knob pin accepts
    (test_round.py::test_offloaded_client_state_matches_hbm_resident uses
    allclose(1e-6)), so hosted-vs-device pins the drained loss sequence
    exactly (held empirically) and params at the established
    allclose(atol=1e-6).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from commefficient_tpu.clientstore import (
    CohortStreamer,
    HostStore,
    LRURowCache,
    available_stores,
    build_store,
    register,
)
from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils.config import CLIENT_STORES, Config

from tests.test_round import BASE, _final_vec, _setup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both client banks live: local error feedback + local momentum
KW = dict(mode="local_topk", error_type="local", local_momentum=0.9, k=30)


def _checker():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# store contract
# ---------------------------------------------------------------------------

def test_registry_mirrors_config_client_stores():
    assert available_stores() == tuple(sorted(CLIENT_STORES))


def test_register_duplicate_rejected():
    with pytest.raises(ValueError, match="duplicate client store"):
        register("host")(HostStore)


def test_build_store_unknown_kind():
    with pytest.raises(ValueError, match="unknown client store"):
        build_store("bogus", num_rows=4, row_dim=2)


@pytest.mark.parametrize("kind", ["host", "mmap", "device"])
def test_gather_scatter_roundtrip(kind, tmp_path):
    path = str(tmp_path / "bank.vel") if kind == "mmap" else ""
    store = build_store(kind, num_rows=6, row_dim=3, path=path)
    rows = np.arange(6, dtype=np.float32).reshape(2, 3)
    store.scatter_rows(np.array([1, 4]), rows)
    np.testing.assert_array_equal(store.gather_rows(np.array([4, 1])),
                                  rows[::-1])
    full = np.asarray(store.array())
    np.testing.assert_array_equal(full[[1, 4]], rows)
    assert not full[[0, 2, 3, 5]].any()  # untouched rows stay zero
    # whole-bank load (checkpoint restore path) round-trips
    bank = np.random.default_rng(0).normal(size=(6, 3)).astype(np.float32)
    store.load(bank)
    np.testing.assert_array_equal(np.asarray(store.array()), bank)
    store.close()


def test_mmap_persists_across_reopen(tmp_path):
    path = str(tmp_path / "bank.err")
    store = build_store("mmap", num_rows=5, row_dim=4, path=path)
    rows = np.full((2, 4), 7.0, np.float32)
    store.scatter_rows(np.array([0, 3]), rows)
    store.flush()
    store.close()
    assert os.path.exists(path)  # a named bank survives close
    again = build_store("mmap", num_rows=5, row_dim=4, path=path)
    np.testing.assert_array_equal(again.gather_rows(np.array([0, 3])), rows)
    again.close()


def test_mmap_anonymous_bank_is_cleaned_up():
    store = build_store("mmap", num_rows=3, row_dim=2)
    path = store.path
    assert os.path.exists(path)
    store.close()
    assert not os.path.exists(path)  # owned tempfile unlinked


# ---------------------------------------------------------------------------
# LRU device cache
# ---------------------------------------------------------------------------

def test_lru_eviction_write_through():
    written = {}
    cache = LRURowCache(2, written.__setitem__)
    cache.put(10, "a")
    cache.put(11, "b")
    assert cache.get(10) == "a" and cache.hits == 1
    assert cache.get(99) is None and cache.misses == 1
    cache.put(12, "c")  # capacity 2: evicts LRU entry (11)
    assert cache.evictions == 1 and written == {11: "b"}
    assert 11 not in cache and 10 in cache and 12 in cache
    cache.flush()  # remaining dirty rows write through, stay cached
    assert written == {11: "b", 10: "a", 12: "c"}
    written.clear()
    cache.flush()  # now clean: nothing to write
    assert written == {}
    cache.invalidate()  # drop WITHOUT writeback (restore path)
    assert len(cache) == 0 and written == {}


# ---------------------------------------------------------------------------
# streamer: async writeback fence
# ---------------------------------------------------------------------------

def test_streamer_writeback_fence():
    s = CohortStreamer(
        vel_store=HostStore(num_rows=8, row_dim=2),
        err_store=HostStore(num_rows=8, row_dim=2),
        num_clients=8,
    )
    before = s.gather(np.array([1, 2]))
    np.testing.assert_array_equal(before.vel, np.zeros((2, 2), np.float32))
    new = np.ones((2, 2), np.float32)
    s.scatter(np.array([2, 5]), new, 2 * new)
    # the next gather observes the async write (it waits on the pending
    # entry for overlapping ids), so a client drawn in two consecutive
    # rounds reads the row the first round wrote
    fresh = s.gather(np.array([2, 5]))
    np.testing.assert_array_equal(fresh.vel, new)
    np.testing.assert_array_equal(fresh.err, 2 * new)
    s.flush()
    np.testing.assert_array_equal(s.vel_array()[[2, 5]], new)
    stats = s.pop_round_stats()
    assert set(stats) == {"clientstore/cache_hit_rate",
                          "clientstore/evictions",
                          "clientstore/h2d_stage_ms",
                          "clientstore/writeback_ms"}
    s.close()


def test_streamer_load_wins_over_pending_writeback():
    s = CohortStreamer(vel_store=HostStore(num_rows=4, row_dim=2),
                       num_clients=4)
    s.scatter(np.array([0, 1]), np.ones((2, 2), np.float32), ())
    bank = np.full((4, 2), 3.0, np.float32)
    # checkpoint/vault restore: the load drains the pending write first,
    # so pre-restore rows cannot land over the restored bank
    s.load_vel(bank)
    np.testing.assert_array_equal(s.gather(np.array([0, 1])).vel,
                                  bank[[0, 1]])
    np.testing.assert_array_equal(s.gather(np.array([2])).vel, bank[[2]])
    assert s.gather(np.array([0])).err == ()  # absent bank convention
    s.close()


# ---------------------------------------------------------------------------
# e2e parity (device | host | mmap | host+cache)
# ---------------------------------------------------------------------------

def _run_store(n_rounds=5, **overrides):
    cfg = Config(**{**KW, **BASE, "telemetry_level": 1, **overrides})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    losses, metrics = [], []
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.3)
        losses.append(float(m["loss"]))
        metrics.append(m)
    out = dict(
        losses=np.asarray(losses),
        params=_final_vec(sess).copy(),
        vel=None if sess.host_vel is None else np.asarray(sess.host_vel).copy(),
        err=None if sess.host_err is None else np.asarray(sess.host_err).copy(),
        metrics=metrics,
        retraces=sess.retrace_sentinel.retraces,
        hosted=sess._streamer is not None,
        state_vel=sess.state.client_vel,
    )
    sess.close_client_store()
    return out


@pytest.fixture(scope="module")
def parity(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("clientstore")
    return {
        "device": _run_store(),
        "host": _run_store(client_store="host"),
        "mmap": _run_store(client_store="mmap",
                           client_store_path=str(tmp / "bank")),
        "cached": _run_store(client_store="host",
                             client_store_cache_rows=4),
    }


def test_hosted_variants_bitwise_identical(parity):
    """host / mmap / host+cache run the SAME compiled program — bitwise."""
    ref = parity["host"]
    for name in ("mmap", "cached"):
        run = parity[name]
        np.testing.assert_array_equal(ref["params"], run["params"], err_msg=name)
        np.testing.assert_array_equal(ref["vel"], run["vel"], err_msg=name)
        np.testing.assert_array_equal(ref["err"], run["err"], err_msg=name)
        np.testing.assert_array_equal(ref["losses"], run["losses"], err_msg=name)


def test_hosted_matches_device_store(parity):
    """Cross-program pin (see module docstring): exact loss sequence,
    params at the seed's established placement tolerance."""
    dev, host = parity["device"], parity["host"]
    np.testing.assert_array_equal(dev["losses"], host["losses"])
    np.testing.assert_allclose(dev["params"], host["params"], atol=1e-6)
    # the hosted banks track the device-resident ones to the same ulp noise
    np.testing.assert_allclose(np.asarray(parity["device"]["state_vel"]),
                               host["vel"], atol=1e-6)


def test_hosted_state_has_no_client_banks(parity):
    assert parity["host"]["hosted"] and parity["host"]["state_vel"] == ()
    assert not parity["device"]["hosted"]
    assert np.abs(parity["host"]["vel"]).sum() > 0  # momentum actually flowed


def test_zero_retraces_all_stores(parity):
    for name, run in parity.items():
        assert run["retraces"] == 0, name


def test_clientstore_scalars_ride_metrics(parity):
    keys = {"clientstore/cache_hit_rate", "clientstore/evictions",
            "clientstore/h2d_stage_ms", "clientstore/writeback_ms"}
    for m in parity["cached"]["metrics"]:  # constant key set, every round
        assert keys <= set(m)
        assert 0.0 <= m["clientstore/cache_hit_rate"] <= 1.0
        ev = m["clientstore/evictions"]
        assert ev >= 0 and float(ev) == int(ev)
        assert m["clientstore/h2d_stage_ms"] >= 0
        assert m["clientstore/writeback_ms"] >= 0
    # cache of 4 rows under an 8-worker cohort must actually evict
    assert sum(m["clientstore/evictions"]
               for m in parity["cached"]["metrics"]) > 0
    # device store (or any un-hosted run) carries NO clientstore scalars
    for m in parity["device"]["metrics"]:
        assert not keys & set(m)


def test_clientstore_scalars_absent_at_level_zero():
    run = _run_store(n_rounds=1, client_store="host", telemetry_level=0)
    assert not any(k.startswith("clientstore/") for k in run["metrics"][0])


@pytest.mark.parametrize("extra", [
    dict(error_type="local", local_momentum=0.0),   # err bank only
    dict(error_type="none", local_momentum=0.9),    # vel bank only
])
def test_single_bank_modes_match_device(extra):
    dev = _run_store(n_rounds=4, **extra)
    host = _run_store(n_rounds=4, client_store="host", **extra)
    np.testing.assert_array_equal(dev["losses"], host["losses"])
    np.testing.assert_allclose(dev["params"], host["params"], atol=1e-6)
    # exactly the needed bank is hosted
    assert (host["vel"] is None) == (extra["local_momentum"] == 0.0)
    assert (host["err"] is None) == (extra["error_type"] == "none")


# ---------------------------------------------------------------------------
# config validation + deprecation alias
# ---------------------------------------------------------------------------

def test_config_rejects_bad_client_store_combos():
    with pytest.raises(ValueError, match="client_store"):
        Config(**KW, **BASE, client_store="floppy")
    with pytest.raises(ValueError, match="client_store"):
        Config(**KW, **BASE, client_store_cache_rows=4)  # cache needs hosted
    with pytest.raises(ValueError, match="client_store"):
        Config(**KW, **BASE, client_store="host",
               client_store_path="/tmp/x")  # path is mmap-only
    with pytest.raises(ValueError, match="fsdp"):
        Config(**KW, **BASE, client_store="host", fsdp=True)


def test_offload_alias_maps_to_host_store():
    with pytest.warns(DeprecationWarning, match="client_store"):
        cfg = Config(**KW, **BASE, offload_client_state=True)
    assert cfg.client_store == "host" and cfg.client_state_hosted


def test_host_vel_setter_requires_hosted_store():
    cfg = Config(**KW, **BASE)  # device store: no streamer
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    with pytest.raises(ValueError, match="no hosted client store"):
        sess.host_vel = np.zeros((cfg.num_clients, sess.grad_size), np.float32)


# ---------------------------------------------------------------------------
# fedsim masking: dropped clients' hosted rows carry forward untouched
# ---------------------------------------------------------------------------

def test_fedsim_all_dropped_freezes_hosted_banks():
    from tests.test_fedsim import S, _cohort_env

    cfg = Config(**KW, **BASE, client_store="host",
                 availability="bernoulli", dropout_prob=0.5)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    for r in range(2):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.3, env=_cohort_env(S))
    vel = np.asarray(sess.host_vel).copy()
    err = np.asarray(sess.host_err).copy()
    before = _final_vec(sess).copy()
    ids, batch = sampler.sample_round(2)
    m = sess.train_round(ids, batch, 0.3, env=_cohort_env([]))
    assert m["fedsim/all_dropped"] == 1.0
    np.testing.assert_array_equal(before, _final_vec(sess))
    np.testing.assert_array_equal(vel, np.asarray(sess.host_vel))
    np.testing.assert_array_equal(err, np.asarray(sess.host_err))
    sess.close_client_store()


# ---------------------------------------------------------------------------
# checkpoint / vault: hosted banks ride the saveable state
# ---------------------------------------------------------------------------

def test_kill_and_resume_hosted_bitwise(tmp_path):
    from commefficient_tpu.utils.checkpoint import FedCheckpointer

    cfg = Config(**KW, **BASE, client_store="host")

    def _train(sess, samp, start, stop, ckpt=None):
        for r in range(start, stop):
            ids, batch = samp.sample_round(r)
            sess.train_round(ids, batch, lr=0.1 + 0.02 * r)
            if ckpt is not None:
                ckpt.maybe_save(sess, r + 1)

    ds, params, loss_fn = _setup(cfg.num_clients)
    sess_a = FederatedSession(cfg, params, loss_fn)
    samp = FedSampler(ds, num_workers=cfg.num_workers,
                      local_batch_size=cfg.local_batch_size, seed=1)
    _train(sess_a, samp, 0, 8)

    ck_cfg = cfg.replace(checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=4)
    sess_b = FederatedSession(ck_cfg, params, loss_fn)
    ckpt = FedCheckpointer(ck_cfg)
    _train(sess_b, samp, 0, 4, ckpt)
    ckpt.close()
    sess_b.close_client_store()

    sess_c = FederatedSession(ck_cfg, params, loss_fn)  # fresh state
    ckpt2 = FedCheckpointer(ck_cfg)
    assert ckpt2.restore(sess_c) == 4
    _train(sess_c, samp, 4, 8)
    ckpt2.close()

    np.testing.assert_array_equal(_final_vec(sess_a), _final_vec(sess_c))
    np.testing.assert_array_equal(sess_a.host_vel, sess_c.host_vel)
    np.testing.assert_array_equal(sess_a.host_err, sess_c.host_err)
    sess_a.close_client_store()
    sess_c.close_client_store()


def test_vault_rollback_hosted_replay_bitwise():
    from commefficient_tpu.resilience import RollbackVault

    cfg = Config(**KW, **BASE, client_store="host")
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    for r in range(3):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.3)
    vault = RollbackVault(snapshot_every=3)
    vault.snapshot(sess, 3)
    at3 = _final_vec(sess).copy()
    vel3 = np.asarray(sess.host_vel).copy()

    def two_more():
        for r in range(3, 5):
            ids, batch = sampler.sample_round(r)
            sess.train_round(ids, batch, 0.3)
        return _final_vec(sess).copy(), np.asarray(sess.host_vel).copy()

    first_params, first_vel = two_more()
    assert not np.array_equal(at3, first_params)
    snap = vault.latest(max_step=4)
    assert vault.restore(sess, snap) == 3
    np.testing.assert_array_equal(_final_vec(sess), at3)
    np.testing.assert_array_equal(np.asarray(sess.host_vel), vel3)
    # same hosted program, restored rows -> the replay is bit-identical
    replay_params, replay_vel = two_more()
    np.testing.assert_array_equal(replay_params, first_params)
    np.testing.assert_array_equal(replay_vel, first_vel)
    sess.close_client_store()


# ---------------------------------------------------------------------------
# ladder: rung switches under a hosted store retrace nothing
# ---------------------------------------------------------------------------

def test_ladder_rung_switch_hosted_zero_retraces():
    from commefficient_tpu.control import build_controller

    cfg = Config(**BASE, mode="local_topk", error_type="local",
                 local_momentum=0.9, topk_method="threshold",
                 client_store="host", telemetry_level=1,
                 control_policy="fixed", control_schedule="0-1=0,2-=1",
                 ladder="k=30,15")
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctrl = build_controller(cfg, sess, num_rounds=4)
    ctrl.prewarm(sampler, 0.2)
    for r in range(4):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.2)
    assert ctrl.switches == 1 and sess.active_rung == 1
    assert sess.retrace_sentinel.retraces == 0
    assert np.abs(np.asarray(sess.host_vel)).sum() > 0
    sess.close_client_store()


# ---------------------------------------------------------------------------
# the strict W*k audit bound (no writeback exemption when hosted)
# ---------------------------------------------------------------------------

def test_hosted_audit_strict_sparse_bound_no_exemption(tmp_path):
    checker = _checker()
    kw = dict(mode="local_topk", error_type="local", k=7,
              topk_method="threshold", aggregate="sparse")
    cfg = Config(**kw, **BASE, client_store="host")
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    audit = sess.audit_compiled_round(np.asarray(ids), batch, 0.2)
    rep = audit.report(generated_by="test", cfg=cfg)
    # strict W*k bound, no client_state_writeback inflation
    assert rep["collectives"]["sparse_agg_bound"] == 8 * 7
    assert rep["collectives"]["sparse_agg_exemption"] is None
    ag = rep["collectives"]["max_all_gather_elems"]
    assert ag is None or ag <= 8 * 7
    path = audit.write(str(tmp_path), generated_by="test", cfg=cfg)
    checker.validate_perf_report(path)  # hosted report passes strict
    sess.close_client_store()

    # the device twin still needs (and declares) the exemption
    cfg_d = Config(**kw, **BASE)
    sess_d = FederatedSession(cfg_d, params, loss_fn)
    rep_d = sess_d.audit_compiled_round(
        np.asarray(ids), batch, 0.2).report(generated_by="test", cfg=cfg_d)
    assert rep_d["collectives"]["sparse_agg_exemption"] == \
        "client_state_writeback"
    assert rep_d["collectives"]["sparse_agg_bound"] > 8 * 7

    # checker rejection: a hosted run may NOT carry any exemption
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["sparse_agg_exemption"] = "client_state_writeback"
    bad = tmp_path / "bad_perf.json"
    bad.write_text(json.dumps(rec))
    with pytest.raises(checker.SchemaError, match="exemption"):
        checker.validate_perf_report(str(bad))


def test_hosted_round_hlo_has_no_client_bank_operand():
    """The acceptance pin: with a hosted store the compiled round program
    contains no [num_clients, D]-shaped operand at all (the gather/scatter
    moved off-graph); the device round does."""
    import jax.numpy as jnp

    cfg_h = Config(**KW, **BASE, client_store="host")
    cfg_d = Config(**KW, **BASE)
    ds, params, loss_fn = _setup(cfg_h.num_clients)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}

    sess_h = FederatedSession(cfg_h, params, loss_fn)
    bank_shape = f"tensor<{cfg_h.num_clients}x{sess_h.grad_size}xf32>"
    cohort = sess_h._streamer.gather(np.asarray(ids))
    text_h = sess_h.round_fn.lower(
        sess_h.state, jnp.asarray(ids), jb, jnp.float32(0.2),
        cohort.vel, cohort.err).as_text()
    assert bank_shape not in text_h
    sess_h.close_client_store()

    sess_d = FederatedSession(cfg_d, params, loss_fn)
    text_d = sess_d.round_fn.lower(
        sess_d.state, jnp.asarray(ids), jb, jnp.float32(0.2)).as_text()
    assert bank_shape in text_d


# ---------------------------------------------------------------------------
# scale: C = 1,000,000 on CPU — hosted works where device cannot allocate
# ---------------------------------------------------------------------------

_MILLION_CHILD = textwrap.dedent("""
    import resource, sys
    kind, root = sys.argv[1], sys.argv[2]
    # cap anonymous memory well under the two [1e6, D] f32 banks
    # (~1.7 GB); file-backed mmap pages do not count against RLIMIT_DATA
    LIM = 1_300_000_000
    resource.setrlimit(resource.RLIMIT_DATA, (LIM, LIM))
    try:
        import numpy as np
        import jax, jax.numpy as jnp
        import flax.linen as nn
        from commefficient_tpu.parallel import FederatedSession
        from commefficient_tpu.models.losses import classification_loss
        from commefficient_tpu.utils.config import Config

        class TinyMLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))

        C = 1_000_000
        cfg = Config(mode="local_topk", error_type="local",
                     local_momentum=0.9, k=8, num_clients=C,
                     num_workers=4, num_devices=1, local_batch_size=2,
                     weight_decay=0.0, seed=0, client_store=kind,
                     client_store_path=(root + "/bank" if kind == "mmap"
                                        else ""))
        model = TinyMLP()
        params = model.init(jax.random.key(0), jnp.zeros((1, 8)))
        sess = FederatedSession(cfg, params,
                                classification_loss(model.apply))
        rng = np.random.default_rng(0)
        ids = np.array([3, 999_999, 123_456, 500_000], dtype=np.int32)
        batch = {"x": rng.normal(size=(4, 2, 8)).astype(np.float32),
                 "y": rng.integers(0, 4, size=(4, 2)).astype(np.int32)}
        for _ in range(2):
            m = sess.train_round(ids, batch, 0.1)
        assert np.isfinite(float(m["loss"]))
        # the touched rows really landed in the million-row bank
        rows = sess._streamer.vel_store.gather_rows(ids)
        assert np.abs(rows).sum() > 0
        sess.close_client_store()
        print("OK")
    except Exception as e:
        print(f"{type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(7)
""")


def _run_million(kind, tmp_path):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": REPO}
    script = tmp_path / "child.py"
    script.write_text(_MILLION_CHILD)
    return subprocess.run(
        [sys.executable, str(script), kind, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)


def test_million_clients_mmap_succeeds_where_device_cannot(tmp_path):
    """The tentpole's scale claim, machine-checked: under a hard
    RLIMIT_DATA the device store cannot even allocate the [1e6, D] banks,
    while the mmap store trains rounds (its bank is file-backed)."""
    ok = _run_million("mmap", tmp_path)
    assert ok.returncode == 0, ok.stderr[-2000:]
    assert "OK" in ok.stdout
    dev = _run_million("device", tmp_path)
    assert dev.returncode == 7, (dev.returncode, dev.stderr[-2000:])
