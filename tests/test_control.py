"""Adaptive communication-budget controller (control/) tests.

Covers the ladder/schedule grammars, the three policies (incl. the
hysteresis no-oscillation property and the budget-exhaustion clamp),
per-backend ``Compressor.migrate_state`` semantics, zero-retrace rung
switching on the real 8-device session, the per-rung ledger exactness
invariant (full participation AND fedsim dropout masking, validated by
the REAL schema checker), checkpoint carry of controller state across
rung-shape-changing ladders, and the control-off bit-compat guarantees
(the golden parity recordings in test_compress_parity are the other half
of that pin). The cv_train e2e acceptance run (3-rung ef_feedback ladder:
>= 1 switch, xla/retraces == 0, resume reproduces the rung sequence)
lives at the bottom.
"""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.control import (
    BudgetExhaustedError,
    build_controller,
    controller_header,
    ladder_configs,
    parse_ladder,
    parse_schedule,
    validate_rung_costs,
)
from commefficient_tpu.control.policy import DecisionContext, EfFeedbackPolicy
from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# grammars
# ---------------------------------------------------------------------------

def test_ladder_grammar_parses():
    assert parse_ladder("") == ()
    assert parse_ladder("k=60000,30000,10000") == (
        {"k": 60000}, {"k": 30000}, {"k": 10000},
    )
    assert parse_ladder(" k=50, 25 ; num_cols = 500, 250 ") == (
        {"k": 50, "num_cols": 500}, {"k": 25, "num_cols": 250},
    )


@pytest.mark.parametrize("bad", [
    "k",                      # no values
    "k=",                     # empty values
    "k=a,b",                  # non-int
    "bogus=1,2",              # unknown field
    "k=1,2;k=3,4",            # duplicate field
    "k=10,5;num_cols=100",    # mismatched lengths
    "k=0,5",                  # < 1
])
def test_ladder_grammar_rejects(bad):
    with pytest.raises(ValueError, match="Grammar"):
        parse_ladder(bad)


def test_ladder_configs_resolve_rung_overrides():
    cfg = Config(mode="powersgd", error_type="virtual",
                 control_policy="fixed", control_schedule="0-=0",
                 ladder="powersgd_rank=4,2")
    c0, c1 = ladder_configs(cfg)
    assert (c0.powersgd_rank, c1.powersgd_rank) == (4, 2)
    cfg = Config(mode="sketch", error_type="virtual", topk_method="threshold",
                 telemetry_level=1, control_policy="ef_feedback",
                 ladder="num_cols=512,256", num_rows=3, k=40)
    c0, c1 = ladder_configs(cfg)
    assert (c0.num_cols, c1.num_cols) == (512, 256)


def test_rung_cost_ordering_enforced():
    validate_rung_costs([
        {"upload_bytes": 100, "download_bytes": 10},
        {"upload_bytes": 100, "download_bytes": 10},  # tie is legal
        {"upload_bytes": 50, "download_bytes": 10},
    ])
    with pytest.raises(ValueError, match="MORE than"):
        validate_rung_costs([
            {"upload_bytes": 50, "download_bytes": 10},
            {"upload_bytes": 100, "download_bytes": 10},
        ])


def test_schedule_grammar():
    assert parse_schedule("") == ()
    assert parse_schedule("0-99=2,100-199=1,200-=0") == (
        (0, 99, 2), (100, 199, 1), (200, None, 0),
    )
    assert parse_schedule("5=1") == ((5, 5, 1),)
    for bad in ("abc", "0-99", "99-0=1", "0-5=1,3-9=0", "0-=1,50-=0"):
        with pytest.raises(ValueError, match="Grammar"):
            parse_schedule(bad)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,msg", [
    (dict(ladder="k=10,5"), "ladder without a controller"),
    (dict(control_policy="ef_feedback", telemetry_level=1), ">= 2"),
    (dict(control_policy="ef_feedback", ladder="k=10,5"),
     "telemetry_level"),
    (dict(control_policy="budget_pacing"), "budget_mb"),
    (dict(budget_mb=1.0), "control_policy='budget_pacing'"),
    (dict(control_policy="fixed"), "control_schedule"),
    (dict(control_policy="budget_pacing", budget_mb=1.0,
          control_schedule="0-=0"), "fixed"),
    (dict(control_policy="fixed", control_schedule="0-=3",
          ladder="k=10,5"), "rung 3"),
    (dict(control_policy="fixed", control_schedule="0-=0",
          ladder="num_cols=100,50"), "num_cols has no effect"),
    (dict(mode="uncompressed", control_policy="fixed",
          control_schedule="0-=0", ladder="k=10,5"), "k has no effect"),
    (dict(control_policy="ef_feedback", ladder="k=10,5",
          telemetry_level=1, control_ef_up=0.0, control_ef_down=0.0),
     "dead band"),
    (dict(control_policy="ef_feedback", ladder="k=10,5",
          telemetry_level=1, control_hysteresis=0), "hysteresis"),
])
def test_config_rejects_inconsistent_control(kw, msg):
    base = dict(mode="true_topk", error_type="virtual")
    base.update(kw)
    with pytest.raises(ValueError, match=msg):
        Config(**base)


def test_config_accepts_budget_only_controller():
    # budget_pacing without a ladder = single implicit rung, pure hard cap
    cfg = Config(mode="true_topk", error_type="virtual",
                 control_policy="budget_pacing", budget_mb=1.0)
    assert cfg.control_enabled
    assert ladder_configs(cfg) == (cfg,)


def test_ladder_field_powersgd_rank_requires_powersgd():
    with pytest.raises(ValueError, match="powersgd_rank has no effect"):
        Config(mode="sketch", error_type="virtual",
               control_policy="fixed", control_schedule="0-=0",
               ladder="powersgd_rank=4,2")


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------

def _ctx(step, rung, num_rungs=3, *, spent=0, budget=None, last_switch=-1,
         hysteresis=1, bytes_fn=None, num_rounds=100):
    return DecisionContext(
        step=step, num_rounds=num_rounds, rung=rung, num_rungs=num_rungs,
        round_bytes=bytes_fn or (lambda r: [300, 200, 100][r]),
        spent_bytes=spent, budget_bytes=budget, last_switch_round=last_switch,
        hysteresis=hysteresis,
    )


def test_budget_pacing_picks_most_expensive_affordable():
    from commefficient_tpu.control.policy import BudgetPacingPolicy

    cfg = Config(mode="true_topk", error_type="virtual",
                 control_policy="budget_pacing", budget_mb=1.0)
    p = BudgetPacingPolicy(cfg)
    # allowance 3000/10 = 300 -> rung 0 affordable
    assert p.decide(_ctx(0, 0, budget=3000, num_rounds=10)) == 0
    # allowance (3000-2400)/5=120 -> only rung 2 fits
    assert p.decide(_ctx(5, 0, spent=2400, budget=3000, num_rounds=10)) == 2
    # nothing fits the allowance -> cheapest (the controller clamp owns
    # the hard stop)
    assert p.decide(_ctx(9, 2, spent=2990, budget=3000, num_rounds=10)) == 2


def test_ef_feedback_decisions_and_hysteresis():
    cfg = Config(mode="true_topk", error_type="virtual", telemetry_level=1,
                 control_policy="ef_feedback", ladder="k=30,20,10",
                 control_ef_up=0.10, control_ef_down=-0.05,
                 control_hysteresis=4)
    p = EfFeedbackPolicy(cfg)
    assert p.initial_rung(3) == 2  # starts cheapest
    # no telemetry yet -> hold
    assert p.decide(_ctx(0, 2, hysteresis=4)) == 2
    p.observe(0, {"diag/ef_residual_norm": 1.0})
    p.observe(1, {"diag/ef_residual_norm": 1.5})  # slope 0.5 > up
    assert p.decide(_ctx(2, 2, hysteresis=4)) == 1
    # inside the hysteresis window the signal is ignored
    assert p.decide(_ctx(3, 1, last_switch=2, hysteresis=4)) == 1
    # shrinking bank -> step cheaper once the window passes
    p.observe(2, {"diag/ef_residual_norm": 1.2})  # slope -0.2 < down
    assert p.decide(_ctx(6, 1, last_switch=2, hysteresis=4)) == 2
    # climbs are clamped at rung 0
    p.observe(3, {"diag/ef_residual_norm": 9.9})
    assert p.decide(_ctx(10, 0, last_switch=2, hysteresis=4)) == 0


def test_ef_feedback_no_oscillation_property():
    """Adversarial alternating signal: the switch count over N rounds is
    bounded by N / hysteresis (+1), never one-per-round flapping."""
    H = 5
    cfg = Config(mode="true_topk", error_type="virtual", telemetry_level=1,
                 control_policy="ef_feedback", ladder="k=30,20,10",
                 control_ef_up=0.05, control_ef_down=-0.05,
                 control_hysteresis=H)
    p = EfFeedbackPolicy(cfg)
    rung, last_switch, switches = 1, -1, 0
    ef = 1.0
    N = 40
    for step in range(N):
        # alternate violent growth/collapse — both thresholds crossed
        # every single round
        ef = ef * (3.0 if step % 2 == 0 else 0.2)
        p.observe(step, {"diag/ef_residual_norm": ef})
        nxt = p.decide(_ctx(step, rung, last_switch=last_switch,
                            hysteresis=H))
        if nxt != rung:
            switches += 1
            last_switch = step
            rung = nxt
    assert switches <= N // H + 1, (
        f"{switches} switches in {N} rounds under hysteresis {H}"
    )


def test_fidelity_trigger_climbs():
    cfg = Config(mode="true_topk", error_type="virtual", telemetry_level=2,
                 control_policy="ef_feedback", ladder="k=30,20,10",
                 control_fidelity_max=0.5, control_hysteresis=1)
    p = EfFeedbackPolicy(cfg)
    p.observe(0, {"diag/sketch_est_rel_err": 0.9})  # worse than max
    assert p.decide(_ctx(1, 2)) == 1


# ---------------------------------------------------------------------------
# migrate_state per backend
# ---------------------------------------------------------------------------

def test_migrate_dense_k_change_is_identity():
    from commefficient_tpu.compress import get_compressor

    cfg = Config(mode="true_topk", error_type="virtual",
                 virtual_momentum=0.9, k=40)
    old = get_compressor(cfg, d=200)
    new = get_compressor(cfg.replace(k=10), d=200)
    m = jnp.arange(200.0)
    e = jnp.arange(200.0) * 2
    m2, e2, x2 = old.migrate_state(new, m, e, ())
    assert m2 is m and e2 is e and x2 == ()


def test_migrate_sketch_k_change_is_identity():
    from commefficient_tpu.compress import get_compressor
    from commefficient_tpu.ops.countsketch import CountSketch

    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=40, num_rows=3, num_cols=256)
    spec = CountSketch(d=500, c=256, r=3, seed=1)
    old = get_compressor(cfg, d=500, spec=spec)
    new = get_compressor(cfg.replace(k=10), d=500, spec=spec)
    t = jnp.ones(spec.table_shape)
    m2, e2, _ = old.migrate_state(new, t, t, ())
    assert m2 is t and e2 is t


def test_migrate_sketch_num_cols_resketches_heavy_hitters():
    """A num_cols switch re-sketches the decodable top-k mass: a k-sparse
    signal sketched into the old table must round-trip through migration
    and estimate correctly from the NEW table."""
    from commefficient_tpu.compress import get_compressor
    from commefficient_tpu.ops.countsketch import (
        CountSketch,
        estimate_at,
        sketch_vec,
    )

    d, k = 4000, 8
    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=k, num_rows=5, num_cols=1024)
    spec_old = CountSketch(d=d, c=1024, r=5, seed=3)
    spec_new = CountSketch(d=d, c=512, r=5, seed=3)
    old = get_compressor(cfg, d=d, spec=spec_old)
    new = get_compressor(cfg.replace(num_cols=512), d=d, spec=spec_new)
    rng = np.random.default_rng(0)
    idx = rng.choice(d, size=k, replace=False)
    vec = np.zeros(d, np.float32)
    vec[idx] = rng.normal(size=k).astype(np.float32) * 10 + 20
    table = sketch_vec(spec_old, jnp.asarray(vec))
    m2, e2, _ = old.migrate_state(new, table, table, ())
    assert m2.shape == spec_new.table_shape
    est = np.asarray(estimate_at(spec_new, e2, jnp.asarray(idx)))
    np.testing.assert_allclose(est, vec[idx], rtol=0.2, atol=1.0)


def test_migrate_powersgd_rank_pad_truncate():
    from commefficient_tpu.compress import get_compressor

    cfg = Config(mode="powersgd", error_type="virtual", powersgd_rank=4)
    d = 400
    old = get_compressor(cfg, d=d)
    q = old.init_extra_state()
    m = jnp.zeros(d)
    e = jnp.zeros(d)
    # truncate 4 -> 2: first columns retained exactly
    new2 = get_compressor(cfg.replace(powersgd_rank=2), d=d)
    _, _, q2 = old.migrate_state(new2, m, e, q)
    assert q2.shape == (old.m, 2)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q[:, :2]))
    # pad 2 -> 4: old columns retained, fresh seed-derived tail
    _, _, q4 = new2.migrate_state(old, m, e, q2)
    assert q4.shape == (old.m, 4)
    np.testing.assert_array_equal(np.asarray(q4[:, :2]), np.asarray(q2))
    assert np.any(np.asarray(q4[:, 2:]) != 0)
    # no warm start carries nothing
    cold = get_compressor(cfg.replace(powersgd_warm_start=False), d=d)
    cold2 = get_compressor(
        cfg.replace(powersgd_warm_start=False, powersgd_rank=2), d=d
    )
    assert cold.migrate_state(cold2, m, e, ())[2] == ()


# ---------------------------------------------------------------------------
# controller + real session
# ---------------------------------------------------------------------------

_LADDER_BASE = dict(
    mode="local_topk", error_type="local", topk_method="threshold",
    telemetry_level=1, control_policy="fixed",
    control_schedule="0-1=0,2-3=1,4-=2", ladder="k=60,30,15",
)


def _ladder_session(**kw):
    cfg = Config(**{**BASE, **_LADDER_BASE, **kw})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    return cfg, sess, sampler


def _drive(cfg, sess, sampler, n_rounds, writer, tmp_path):
    from commefficient_tpu.telemetry import build_telemetry_riders
    from commefficient_tpu.utils.logging import drain_round_metrics

    ctrl = build_controller(cfg, sess, num_rounds=n_rounds)
    ctrl.prewarm(sampler, 0.2)
    ledger, flight = build_telemetry_riders(cfg, sess, writer)
    pending = []
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.2)
        pending.append((r, 0.2, m))
    drain_round_metrics(pending, writer, lambda *a: None, ledger=ledger,
                        flight=flight, controller=ctrl)
    return ctrl, ledger, flight


def test_fixed_schedule_switches_and_zero_retraces(tmp_path):
    from commefficient_tpu.utils.logging import MetricsWriter

    cfg, sess, sampler = _ladder_session()
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg,
                           extra_header=controller_header(sess))
    ctrl, ledger, _ = _drive(cfg, sess, sampler, 6, writer, tmp_path)
    writer.close()
    assert ctrl.switches == 2
    assert sess.retrace_sentinel.retraces == 0
    assert sess.active_rung == 2
    # per-rung ledger accounting: 2 rounds at each rung's own byte rate
    s = ledger.summary()
    assert [r["rounds"] for r in s["rungs"]] == [2, 2, 2]
    # per-client-link units (unmasked ledger): 2k floats x 4 B per rung
    want_up = 2 * (2 * 60 * 4) + 2 * (2 * 30 * 4) + 2 * (2 * 15 * 4)
    assert s["cum_up_bytes"] == want_up
    # the real checker enforces the v4 per-rung invariant
    ledger.write(str(tmp_path / "run"))
    mod = _checker()
    rec = mod.validate_comm_ledger(str(tmp_path / "run" / "comm_ledger.json"))
    assert [r["rounds"] for r in rec["rungs"]] == [2, 2, 2]
    # metrics.jsonl validates too (control/ scalars under the v4 schema),
    # and the run header carries the controller block
    mod.validate_metrics_jsonl(str(tmp_path / "run" / "metrics.jsonl"))
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        header = json.loads(f.readline())
    assert header["controller"]["policy"] == "fixed"
    assert header["controller"]["num_rungs"] == 3


def test_checker_rejects_tampered_rung_rounds(tmp_path):
    from commefficient_tpu.utils.logging import MetricsWriter

    cfg, sess, sampler = _ladder_session()
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg)
    _, ledger, _ = _drive(cfg, sess, sampler, 6, writer, tmp_path)
    writer.close()
    path = ledger.write(str(tmp_path / "run"))
    with open(path) as f:
        rec = json.load(f)
    rec["rungs"][0]["rounds"] += 1
    with open(path, "w") as f:
        json.dump(rec, f)
    mod = _checker()
    with pytest.raises(mod.SchemaError, match="rounds sum"):
        mod.validate_comm_ledger(path)


def test_ladder_ledger_exact_under_fedsim_masking(tmp_path):
    """The satellite invariant: cumulative bytes == sum over rounds of the
    ACTIVE rung's bytes, exact under dropout masking — per-rung live
    counts recovered from the same drained scalars the run logged."""
    from commefficient_tpu.utils.logging import MetricsWriter

    cfg, sess, sampler = _ladder_session(
        availability="bernoulli", dropout_prob=0.4, fuse_clients=False,
    )
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg)
    ctrl, ledger, _ = _drive(cfg, sess, sampler, 6, writer, tmp_path)
    writer.close()
    s = ledger.summary()
    # recompute the invariant from the logged per-rung live counts
    want_up = sum(
        r["live_client_rounds"] * r["bytes_per_round"]["upload_bytes"]
        for r in s["rungs"]
    )
    assert s["cum_up_bytes"] == want_up
    assert s["live_client_rounds"] == sum(
        r["live_client_rounds"] for r in s["rungs"]
    )
    # some round actually dropped clients, else the test is vacuous
    assert s["live_client_rounds"] < 6 * cfg.num_workers
    # the controller's own budget view agrees with the ledger exactly
    assert ctrl.spent_up == s["cum_up_bytes"]
    assert ctrl.spent_down == s["cum_down_bytes"]
    ledger.write(str(tmp_path / "run"))
    _checker().validate_comm_ledger(
        str(tmp_path / "run" / "comm_ledger.json")
    )


def test_budget_clamp_demotes_then_exhausts(tmp_path):
    """The hard cap: the controller demotes to cheaper rungs when the
    decided rung would cross the budget, and raises BudgetExhaustedError
    BEFORE the round that even the cheapest rung cannot pay for."""
    # per-round bytes (TinyMLP d=212, W=8 irrelevant — per-client units):
    # rung0 2*60*4+848=1328, rung1 1088, rung2 968
    cfg, sess, sampler = _ladder_session(
        control_schedule="0-=0", budget_mb=0.005,  # 5000 B
    )
    ctrl = build_controller(cfg, sess, num_rounds=10)
    rungs_used = []
    with pytest.raises(BudgetExhaustedError) as ei:
        for r in range(10):
            ids, batch = sampler.sample_round(r)
            m = sess.train_round(ids, batch, 0.2)
            rungs_used.append(int(float(np.asarray(m["control/rung"]))))
    assert rungs_used == [0, 0, 0, 2]  # demoted at round 3, stopped at 4
    assert ctrl.spent_bytes <= 5000  # the cap was never crossed
    assert ei.value.step == 4
    assert "completed 4 full rounds" in str(ei.value)


def test_budget_remaining_scalar_rides_metrics():
    cfg, sess, sampler = _ladder_session(
        control_policy="budget_pacing", control_schedule="",
        budget_mb=1.0,
    )
    build_controller(cfg, sess, num_rounds=4)
    ids, batch = sampler.sample_round(0)
    m = sess.train_round(ids, batch, 0.2)
    assert m["control/budget_remaining_bytes"] == 1_000_000 - 1328
    assert m["control/rung"] == 0.0  # rich budget -> most expensive rung


@pytest.mark.slow  # r20 tier budget (~9 s of sketch compiles): the
# num_cols migration algebra stays tier-1 in the resketch unit test and
# the switch/zero-retrace mechanics in the fixed-schedule e2e
def test_num_cols_ladder_switches_table_shapes():
    """A geometry-changing ladder: the switch migrates the sketch tables
    to the new rung's layout and training stays finite — and the switch
    itself causes no retrace (both rungs were prewarmed)."""
    cfg = Config(**{**BASE, **dict(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        k=40, num_rows=3, num_cols=512, topk_method="threshold",
        telemetry_level=1, control_policy="fixed",
        control_schedule="0-1=0,2-=1", ladder="num_cols=512,256",
    )})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctrl = build_controller(cfg, sess, num_rounds=4)
    ctrl.prewarm(sampler, 0.2)
    shapes = []
    for r in range(4):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.2)
        assert np.isfinite(float(np.asarray(m["loss"])))
        shapes.append(tuple(sess.state.error.shape))
    assert shapes[1] != shapes[2], "table layout must change at the switch"
    assert ctrl.switches == 1
    assert sess.retrace_sentinel.retraces == 0


def test_fsdp_ladder_switch_trains_and_accounts():
    """The FSDP engine under a k-ladder: per-rung fsdp round programs,
    identity state migration over the sharded [Dp] banks, zero retraces
    across the switch, and the same per-rung controller accounting."""
    cfg = Config(**{**BASE, **dict(
        mode="true_topk", error_type="virtual", virtual_momentum=0.9,
        fsdp=True, topk_method="threshold", telemetry_level=1,
        control_policy="fixed", control_schedule="0-1=0,2-=1",
        ladder="k=40,20",
    )})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctrl = build_controller(cfg, sess, num_rounds=4)
    ctrl.prewarm(sampler, 0.2)
    for r in range(4):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, 0.2)
        assert np.isfinite(float(np.asarray(m["loss"])))
    assert ctrl.switches == 1
    assert sess.active_rung == 1
    assert sess.retrace_sentinel.retraces == 0
    # sharded [Dp] server banks carried across the switch untouched
    # (identity migration) and per-rung rounds accounted
    assert ctrl.rounds_seen == 4


def test_control_none_builds_nothing():
    cfg = Config(**{**BASE, "mode": "true_topk", "error_type": "virtual",
                    "k": 40})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    assert len(sess.rungs) == 1 and sess.rungs[0].label == ""
    assert sess.controller is None
    assert controller_header(sess) == {}
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ids, batch = sampler.sample_round(0)
    m = sess.train_round(ids, batch, 0.2)
    assert not any(k.startswith("control/") for k in m)


# ---------------------------------------------------------------------------
# checkpoint carry
# ---------------------------------------------------------------------------

def test_controller_state_checkpoint_roundtrip(tmp_path):
    """Save at a non-initial rung of a GEOMETRY-CHANGING ladder; a fresh
    session+controller restores the exact rung, policy state, and byte
    spend — the template-retry walk finds the saved rung's state layout."""
    from commefficient_tpu.utils.checkpoint import FedCheckpointer

    kw = dict(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        k=40, num_rows=3, num_cols=512, topk_method="threshold",
        telemetry_level=1, control_policy="fixed",
        control_schedule="0-1=0,2-=1", ladder="num_cols=512,256",
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=3,
    )
    cfg = Config(**{**BASE, **kw})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctrl = build_controller(cfg, sess, num_rounds=6)
    ctrl.prewarm(sampler, 0.2)
    ckpt = FedCheckpointer(cfg)
    for r in range(4):
        ids, batch = sampler.sample_round(r)
        sess.train_round(ids, batch, 0.2)
    assert sess.active_rung == 1  # switched at round 2
    ckpt.maybe_save(sess, 4, force=True)
    saved_err = np.asarray(sess.state.error)
    saved_spent = ctrl.spent_bytes

    sess2 = FederatedSession(cfg, params, loss_fn)
    ctrl2 = build_controller(cfg, sess2, num_rounds=6)
    assert sess2.active_rung == 0  # fresh session starts per schedule
    step = ckpt.restore(sess2)
    ckpt.close()
    assert step == 4
    assert sess2.active_rung == 1
    assert ctrl2.switches == 1 and ctrl2.rounds_seen == 4
    assert ctrl2.spent_bytes == saved_spent
    np.testing.assert_array_equal(np.asarray(sess2.state.error), saved_err)
    # the resumed controller continues the same sequence
    ids, batch = sampler.sample_round(4)
    m = sess2.train_round(ids, batch, 0.2)
    assert float(np.asarray(m["control/rung"])) == 1.0


# ---------------------------------------------------------------------------
# cv_train e2e (the PR acceptance run)
# ---------------------------------------------------------------------------

def _metric_records(logdir):
    """Every scalar record under ``logdir`` as (name, value, step) in file
    order: the unit two runs are compared in (wall time ``t`` may differ;
    ``trace/*`` and ``xla/exposed_collective_ms`` are host wall-clock)."""
    out = []
    for root, _, files in os.walk(logdir):
        for f in sorted(files):
            if f != "metrics.jsonl":
                continue
            with open(os.path.join(root, f)) as fh:
                for line in fh:
                    rec = json.loads(line)
                    if "name" not in rec or rec["name"].startswith(
                            ("trace/", "xla/exposed_collective_ms")):
                        continue
                    out.append((rec["name"], rec["value"], rec["step"]))
    return out


def _scalar_trail(logdir, name):
    """{step: value} of one scalar from every metrics.jsonl under
    ``logdir``."""
    return {s: v for n, v, s in _metric_records(logdir) if n == name}


def _rung_sequence(logdir):
    return _scalar_trail(logdir, "control/rung")


@pytest.mark.slow  # ~27 s of femnist compiles; the clamp/exhaustion logic
# and the v4 ledger/flight blocks hold default-tier coverage in the unit
# tests above — this is the full-entry artifact check, kept for local runs
def test_cv_train_budget_hard_stop_e2e(tmp_path):
    """budget_pacing with no ladder = a pure byte cap: cv_train hard-stops
    with BudgetExhaustedError BEFORE the unaffordable round, the ledger is
    still written (within budget, v4-valid), and the crash flight dump
    carries the controller block."""
    from commefficient_tpu.train.cv_train import main as cv_main

    logdir = tmp_path / "runs"
    with pytest.raises(BudgetExhaustedError) as ei:
        cv_main(
            [],
            dataset_name="femnist",
            model="resnet9",
            mode="true_topk",
            error_type="virtual",
            topk_method="threshold",
            k=2000,
            num_clients=6,
            num_workers=4,
            num_devices=4,
            local_batch_size=32,
            num_epochs=1,
            pivot_epoch=1,
            lr_scale=0.1,
            dataset_dir=str(tmp_path),
            logdir=str(logdir),
            seed=0,
            telemetry_level=1,
            perf_audit=False,
            control_policy="budget_pacing",
            # true_topk: up = down = D*4 B ~ 26.6 MB each per round ->
            # ~53 MB/round; 160 MB admits 3 full rounds, not 4
            budget_mb=160.0,
        )
    assert ei.value.step == 3
    run_dir = next(p for p in logdir.iterdir() if p.is_dir())
    mod = _checker()
    ledger = mod.validate_comm_ledger(run_dir / "comm_ledger.json")
    assert ledger["rounds"] == 3  # only the affordable rounds were billed
    assert ledger["cum_bytes"] <= 160_000_000
    flights = list(run_dir.glob("flight_*.json"))
    assert flights, "the hard stop must dump a flight record"
    rec = mod.validate_flight(flights[0])
    assert rec["controller"]["policy"] == "budget_pacing"


def test_runner_ladder_dropout_resume_bit_exact_tinymlp(tmp_path):
    """The cv_train e2e's default-tier twin on the TinyMLP task: the REAL
    shared runner (train_loop) under bernoulli dropout + a 3-rung
    ef_feedback ladder switches at least once with zero retraces, and a
    resume from a mid-run checkpoint reproduces the uninterrupted run:
    final params bitwise, and the scalar tail record for record."""
    import shutil

    from commefficient_tpu.data import FedDataset
    from commefficient_tpu.train.cv_train import train_loop
    from commefficient_tpu.utils.checkpoint import FedCheckpointer
    from commefficient_tpu.utils.logging import MetricsWriter

    ds, params, loss_fn = _setup(12)
    test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                         1, seed=0)

    def run(resume):
        cfg = Config(**{**BASE, **dict(
            mode="true_topk", error_type="virtual", virtual_momentum=0.9,
            topk_method="threshold", telemetry_level=1, perf_audit=False,
            availability="bernoulli", dropout_prob=0.25,
            control_policy="ef_feedback", ladder="k=60,30,15",
            control_ef_up=1e-9, control_ef_down=-1.0, control_hysteresis=1,
            num_epochs=1, pivot_epoch=1, lr_scale=0.1,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=5,
            resume=resume,
        )})
        sess = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=cfg.num_workers,
                             local_batch_size=cfg.local_batch_size, seed=1)
        run_dir = str(tmp_path / ("resume" if resume else "full"))
        writer = MetricsWriter(run_dir, cfg=cfg)
        ck = FedCheckpointer(cfg)
        try:
            train_loop(cfg, sess, sampler, test_ds, writer,
                       eval_batch_size=32, checkpointer=ck)
        finally:
            ck.close()
            writer.close()
        return sess, run_dir

    full, full_dir = run(resume=False)
    seq = _metric_records(full_dir)
    rungs = [v for n, v, _s in seq if n == "control/rung"]
    assert rungs[0] == 2.0 and len(set(rungs)) >= 2, rungs
    assert {v for n, v, _s in seq if n == "xla/retraces"} == {0.0}
    assert full.retrace_sentinel.retraces == 0
    # resume: drop all but the FIRST surviving checkpoint and replay
    kept = sorted(int(p.name) for p in (tmp_path / "ckpt").iterdir()
                  if p.name.isdigit())
    resume_step = kept[0]
    assert resume_step < max(s for _n, _v, s in seq), kept
    for s in kept[1:]:
        shutil.rmtree(tmp_path / "ckpt" / str(s))
    resumed, resumed_dir = run(resume=True)
    np.testing.assert_array_equal(np.asarray(full.state.params_vec),
                                  np.asarray(resumed.state.params_vec))
    drop = ("comm/",)  # process-local cumulative ledger, by design
    tail = [r for r in _metric_records(resumed_dir)
            if r[2] >= resume_step and not r[0].startswith(drop)]
    want = [r for r in seq if r[2] >= resume_step
            and not r[0].startswith(drop)]
    assert tail == want, "resume diverged from the uninterrupted run"


@pytest.mark.slow  # ~130 s of femnist compiles — moved to the slow tier
# in the sketch-gap PR per the 870 s tier-1 budget (the PR-9/10
# precedent). Its claims hold default-tier coverage at TinyMLP scale:
# test_runner_ladder_dropout_resume_bit_exact_tinymlp above runs
# the SAME 3-rung ef_feedback ladder through the REAL shared runner
# (>= 1 switch, zero retraces, mid-run checkpoint resume reproducing the
# tail), and the session-level switch/checkpoint/ledger pins above cover
# the controller mechanics.
def test_cv_train_ladder_ef_feedback_e2e_with_resume(tmp_path):
    """Acceptance: a cv_train e2e run with a 3-rung ladder under
    ef_feedback performs >= 1 rung switch with ZERO RetraceSentinel fires,
    and a checkpoint resume reproduces the identical rung sequence."""
    from commefficient_tpu.train.cv_train import main as cv_main

    kw = dict(
        dataset_name="femnist",
        model="resnet9",
        mode="true_topk",
        error_type="virtual",
        virtual_momentum=0.9,
        topk_method="threshold",
        num_clients=6,
        num_workers=4,
        num_devices=4,
        local_batch_size=32,  # 5 rounds/epoch on the femnist stand-in
        pivot_epoch=1,
        lr_scale=0.1,
        dataset_dir=str(tmp_path),
        seed=0,
        telemetry_level=1,
        perf_audit=False,  # the AOT audit is test_xla_audit's territory
        control_policy="ef_feedback",
        ladder="k=4000,2000,1000",
        # force deterministic climbing: any EF growth at all climbs, and
        # the EF bank grows from zero in the first rounds by construction
        control_ef_up=1e-9,
        control_ef_down=-1.0,
        control_hysteresis=1,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=3,  # mid-epoch drains -> mid-epoch decisions
    )
    # run C: 2 epochs uninterrupted, checkpointing every 3 rounds
    cv_main([], num_epochs=2, logdir=str(tmp_path / "runC"), **kw)
    seq_c = _rung_sequence(tmp_path / "runC")
    assert seq_c[0] == 2.0, "ef_feedback starts at the cheapest rung"
    switches = sum(
        1 for s in range(1, 10) if seq_c[s] != seq_c[s - 1]
    )
    assert switches >= 1, f"no rung switch in {seq_c}"
    retraces = _scalar_trail(tmp_path / "runC", "xla/retraces")
    assert set(retraces.values()) == {0.0}, (
        f"rung switches caused retraces: {retraces}"
    )
    # run B: resume from run C's own MID-RUN checkpoint (drop the later
    # steps so restore picks the round-6 one — a kill at round 6) and
    # replay rounds 6-9; the resumed rung sequence must be bit-identical
    # to the uninterrupted run's (controller blob + drained-state carry)
    kept = sorted(int(p.name) for p in (tmp_path / "ckpt").iterdir()
                  if p.name.isdigit())
    resume_step = kept[0]
    assert resume_step < 10, f"no mid-run checkpoint survived: {kept}"
    for s in kept[1:]:
        import shutil

        shutil.rmtree(tmp_path / "ckpt" / str(s))
    cv_main([], num_epochs=2, logdir=str(tmp_path / "runB"), resume=True,
            **kw)
    seq_b = _rung_sequence(tmp_path / "runB")
    resumed = {s: v for s, v in seq_b.items() if s >= resume_step}
    assert resumed == {s: v for s, v in seq_c.items()
                       if s >= resume_step}, (
        f"resume diverged from the uninterrupted rung sequence: "
        f"B={seq_b} C={seq_c}"
    )
    assert set(_scalar_trail(tmp_path / "runB", "xla/retraces").values()) \
        == {0.0}


# ---------------------------------------------------------------------------
# staleness_aware (elastic-fleet PR): rung walk on the async staleness
# band + live (K, C) retunes through the controller -> engine listener
# ---------------------------------------------------------------------------

_SA_KW = dict(mode="true_topk", error_type="virtual", telemetry_level=1,
              control_policy="staleness_aware", ladder="k=30,20,10",
              async_buffer=4, async_concurrency=2)


def _sa_ctx(step, rung, *, stale=None, fill=None, workers=8,
            last_switch=-1, hysteresis=1):
    return DecisionContext(
        step=step, num_rounds=100, rung=rung, num_rungs=3,
        round_bytes=lambda r: [300, 200, 100][r], spent_bytes=0,
        budget_bytes=None, last_switch_round=last_switch,
        hysteresis=hysteresis, staleness_mean=stale, buffer_fill=fill,
        num_workers=workers,
    )


@pytest.mark.parametrize("kw,msg", [
    ({**_SA_KW, "async_buffer": 0}, "async_buffer"),
    ({**_SA_KW, "ladder": "k=30"}, ">= 2"),
    ({**_SA_KW, "telemetry_level": 0}, "telemetry_level"),
    ({**_SA_KW, "control_staleness_hi": 0.4,
      "control_staleness_lo": 0.5}, "must exceed control_staleness_lo"),
    ({**_SA_KW, "control_fill_hi": 0.2, "control_fill_lo": 0.25},
     "control_fill"),
])
def test_config_rejects_inconsistent_staleness_aware(kw, msg):
    with pytest.raises(ValueError, match=msg):
        Config(**kw)


def test_staleness_aware_walk_band_and_hysteresis():
    from commefficient_tpu.control.policy import (
        ControlPolicy,
        StalenessAwarePolicy,
    )

    # the ADAPTS_ASYNC capability is what gates the retune plumbing and
    # the control/async_* scalars — a class attr, not a name match
    assert not ControlPolicy.ADAPTS_ASYNC
    assert StalenessAwarePolicy.ADAPTS_ASYNC
    p = StalenessAwarePolicy(Config(**_SA_KW))
    assert p.decide(_sa_ctx(0, 1)) == 1  # synchronous round: hold
    assert p.decide(_sa_ctx(0, 1, stale=3.0)) == 2    # over band: cheaper
    assert p.decide(_sa_ctx(0, 2, stale=3.0)) == 2    # clamped at last
    assert p.decide(_sa_ctx(0, 1, stale=0.1)) == 0    # under: fidelity
    assert p.decide(_sa_ctx(0, 0, stale=0.1)) == 0    # clamped at 0
    assert p.decide(_sa_ctx(0, 1, stale=1.0)) == 1    # inside band: hold
    # inside the hysteresis window the signal is ignored
    assert p.decide(_sa_ctx(3, 1, stale=9.0, last_switch=2,
                            hysteresis=4)) == 1


def test_staleness_aware_no_oscillation_property():
    """Adversarial alternating staleness (far over / far under the band
    every update): switches over N updates stay bounded by
    N / hysteresis (+1) — the ef_feedback anti-flap property."""
    from commefficient_tpu.control.policy import StalenessAwarePolicy

    H = 5
    p = StalenessAwarePolicy(Config(**_SA_KW, control_hysteresis=H))
    rung, last_switch, switches = 1, -1, 0
    N = 40
    for step in range(N):
        stale = 9.0 if step % 2 == 0 else 0.0
        nxt = p.decide(_sa_ctx(step, rung, stale=stale,
                               last_switch=last_switch, hysteresis=H))
        if nxt != rung:
            switches += 1
            last_switch = step
            rung = nxt
    assert switches <= N // H + 1, (
        f"{switches} switches in {N} updates under hysteresis {H}"
    )


def test_staleness_aware_retune_moves():
    """decide_async is one move per decision toward the fill band:
    backlog over the band grows K; hot staleness sheds concurrency to 1,
    then shrinks K only while ALSO starved; a fresh fleet restores C up
    to the configured ceiling; in-band (or signal-less) holds."""
    from commefficient_tpu.control.policy import StalenessAwarePolicy

    p = StalenessAwarePolicy(Config(**_SA_KW))
    assert p.decide_async(_sa_ctx(0, 0, stale=1.0, fill=8), 4, 2) == (5, 2)
    assert p.decide_async(_sa_ctx(0, 0, stale=3.0, fill=2), 4, 2) == (4, 1)
    assert p.decide_async(_sa_ctx(0, 0, stale=3.0, fill=0), 4, 1) == (3, 1)
    # stale but neither concurrency to shed nor starvation: hold
    assert p.decide_async(_sa_ctx(0, 0, stale=3.0, fill=3), 4, 1) == (4, 1)
    assert p.decide_async(_sa_ctx(0, 0, stale=0.1, fill=2), 4, 1) == (4, 2)
    assert p.decide_async(_sa_ctx(0, 0, stale=0.1, fill=2), 4, 2) == (4, 2)
    assert p.decide_async(_sa_ctx(0, 0, stale=1.0, fill=2), 4, 2) == (4, 2)
    assert p.decide_async(_sa_ctx(0, 0), 4, 2) == (4, 2)  # sync round
    # backlog over the band but K already at the fleet width: hold, the
    # buffer cannot absorb more than one contribution per live worker
    assert p.decide_async(_sa_ctx(0, 0, stale=1.0, fill=20, workers=4),
                          4, 2) == (4, 2)


def test_fixed_policy_async_run_emits_no_retune_scalars():
    """Capability gating: an asyncfed run under a NON-adaptive policy
    must not grow control/async_* keys (nor register retune listeners) —
    its sync/async scalar sets stay comparable run-to-run."""
    from commefficient_tpu.asyncfed import AsyncFederation

    cfg = Config(mode="true_topk", error_type="virtual", telemetry_level=1,
                 control_policy="fixed", control_schedule="0-=0",
                 ladder="k=30,20", async_buffer=4, async_concurrency=2,
                 **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    ctl = build_controller(cfg, sess, num_rounds=4)
    assert not ctl.policy.ADAPTS_ASYNC
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctl.prewarm(sampler, 0.3)
    eng = AsyncFederation(cfg, sess, sampler, lambda s: 0.3, num_rounds=4)
    eng.start(0)
    for _step, _lr, m in eng.epoch_rounds(0, 0):
        assert "control/async_k" not in m
        assert "control/retunes" not in m
    eng.close()
    assert eng.stats()["retunes_applied"] == 0


def test_staleness_aware_engine_retunes_and_blob_roundtrip():
    """The closed loop end-to-end: a straggler-heavy asyncfed run under
    staleness_aware walks the ladder (>= 1 rung move), retunes the
    ENGINE's live (K, C) through the listener (cold window rebuild, the
    FedBuff trade), carries (K, C) in snapshot_extra for the vault, and
    round-trips the v3 controller blob exactly."""
    from commefficient_tpu.asyncfed import AsyncFederation

    cfg = Config(**{**_SA_KW, **BASE, "ladder": "k=30,20",
                    "async_concurrency": 3, "control_hysteresis": 1,
                    "control_staleness_hi": 0.6,
                    "control_staleness_lo": 0.2})
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    ctl = build_controller(cfg, sess, num_rounds=10)
    assert ctl is not None and ctl.policy.ADAPTS_ASYNC
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ctl.prewarm(sampler, 0.3)
    eng = AsyncFederation(cfg, sess, sampler, lambda s: 0.3, num_rounds=10)
    eng.start(0)
    ks, rungs = [], []
    for _step, _lr, m in eng.epoch_rounds(0, 0):
        assert np.isfinite(float(m["loss"]))
        ks.append(m["control/async_k"])
        rungs.append(m["control/rung"])
        assert m["control/async_k"] >= 1 and m["control/async_c"] >= 1
    eng.close()
    assert ctl.retunes > 0 and len(set(ks)) > 1, (ks, ctl.retunes)
    assert eng.stats()["retunes_applied"] >= 1
    assert len(set(rungs)) > 1, f"no ladder walk: {rungs}"
    assert sess.retrace_sentinel.retraces == 0
    # the engine's live geometry rides the vault snapshot extras
    extra = eng.snapshot_extra()
    assert extra["k"] == eng._k and extra["c"] == eng._c
    # v3 blob: (K, C, retunes) survive a fresh controller load exactly
    blob = ctl.state_blob()
    sess2 = FederatedSession(cfg, params, loss_fn)
    ctl2 = build_controller(cfg, sess2, num_rounds=10)
    ctl2.load_state_blob(blob)
    assert (ctl2.async_k, ctl2.async_c, ctl2.retunes) == (
        ctl.async_k, ctl.async_c, ctl.retunes)
