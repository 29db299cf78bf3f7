"""Compiled-graph performance observability (ISSUE 7) on the 8-device CPU
mesh: the XLA cost/memory/collective audit and its ledger cross-check
(dense vs sharded sketch decode), the retrace sentinel, host phase spans,
the perf_report.json schema round-trip through the checker, and the
level-0 no-added-ops HLO pin (golden registry parity is carried by
tests/test_compress_parity.py — the audit adds NOTHING to the traced
round, pinned here by byte-identical lowered HLO)."""

import glob
import importlib.util
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.data import FedDataset, FedSampler
from commefficient_tpu.models.losses import classification_loss
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.telemetry import PhaseSpans, RetraceError
from commefficient_tpu.telemetry.xla_audit import (
    RetraceSentinel,
    collective_audit,
    signature_diff,
)
from commefficient_tpu.utils.config import Config
from commefficient_tpu.utils.logging import MetricsWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(16)(x))
        return nn.Dense(4)(x)


BASE = dict(num_clients=12, num_workers=8, num_devices=8, local_batch_size=4,
            weight_decay=0.0, seed=5)
SKETCH = dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
              k=40, num_rows=3, num_cols=256, topk_method="threshold")


def _setup(num_clients=12, n=400):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 4))
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.normal(size=(n, 4)), axis=1).astype(
        np.int32
    )
    ds = FedDataset({"x": x, "y": y}, num_clients, iid=True, seed=0)
    model = TinyMLP()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8)))
    return ds, params, classification_loss(model.apply)


def _session_and_round0(cfg):
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    ids, batch = sampler.sample_round(0)
    return sess, sampler, ids, batch


# ---------------------------------------------------------------------------
# collective audit + ledger cross-check (tentpole piece 2)
# ---------------------------------------------------------------------------

def test_collective_cross_check_dense_vs_sharded():
    """The ISSUE-7 acceptance cross-check: on BOTH sketch decode paths the
    compiled round's collective bytes reconcile with the CommLedger's
    analytic accounting (dense: the table psum IS the per-link upload, so
    the delta is scalar slop; sharded: the known extra design traffic —
    EF re-sketch psum + the <= W*k candidate exchange — is inside the
    recorded tolerance), and neither round all-gathers anything (the
    candidate exchange is an invariant gather: it lowers to an
    all-reduce, pinned in tests/test_sketch_decode.py)."""
    audits = {}
    for dec in ("dense", "sharded"):
        cfg = Config(telemetry_level=1, sketch_decode=dec, **SKETCH, **BASE)
        sess, _, ids, batch = _session_and_round0(cfg)
        audits[dec] = (sess, sess.audit_compiled_round(ids, batch, 0.2))
    for dec, (sess, audit) in audits.items():
        coll = audit.collectives
        assert coll["ledger_up_bytes"] == sess.bytes_per_round()[
            "upload_bytes"
        ]
        assert coll["within_tolerance"], (
            f"{dec}: ledger-vs-HLO delta {coll['delta_bytes']} B outside "
            f"the accounting tolerance {coll['tolerance_bytes']} B"
        )
        assert coll["total_bytes"] > 0  # the psum must be visible
        assert audit.cost["flops"] and audit.cost["flops"] > 0
        assert audit.memory["peak_hbm_bytes"] > 0
    # dense: no gathers at all (the PR-6 dense-round property)
    assert audits["dense"][1].collectives["max_all_gather_elems"] is None
    assert audits["dense"][1].sketch_decode == "dense"
    # sharded: the bound rides the report; no gather to hold against it
    sh = audits["sharded"][1].collectives
    assert sh["wk_bound"] == 8 * SKETCH["k"]
    assert sh["max_all_gather_elems"] is None
    # the sharded round's decode genuinely moves less FLOPs than dense
    assert (audits["sharded"][1].cost["flops"]
            < audits["dense"][1].cost["flops"])


def test_chip_peak_flops_known_chip_and_unknown_is_an_error(monkeypatch):
    """A chip outside the peak table raises: a utilization against another
    chip's peak is a wrong number (there is no fallback figure)."""
    from types import SimpleNamespace

    from commefficient_tpu.telemetry.xla_audit import chip_peak_flops

    with pytest.raises(ValueError, match="'cpu'"):  # the test mesh itself
        chip_peak_flops()
    monkeypatch.setattr(
        jax, "devices", lambda: [SimpleNamespace(device_kind="TPU v5 lite")])
    assert chip_peak_flops() == (197e12, "TPU v5 lite")


def test_collective_audit_parses_variadic_and_async_forms():
    """Direct parser pins: tuple-shaped (variadic) all-reduces sum their
    components, async -start/-done pairs count once, and dtype sizes are
    honored."""
    text = """
  %all-reduce.1 = f32[3,264]{1,0} all-reduce(f32[3,264]{1,0} %x), channel_id=1
  %ar2 = (f32[8]{0}, s32[4]{0}) all-reduce(f32[8]{0} %a, s32[4]{0} %b), channel_id=2
  %ag = (bf16[1,27]{1,0}, bf16[8,27]{1,0}) all-gather-start(bf16[1,27]{1,0} %c), channel_id=3
  %agd = bf16[8,27]{1,0} all-gather-done((bf16[1,27]{1,0}, bf16[8,27]{1,0}) %ag)
  %rs = f32[16]{0} reduce-scatter(f32[128]{0} %d), channel_id=4
"""
    out = collective_audit(text)
    assert out["ops"]["all-reduce"] == {"count": 2,
                                        "bytes": 3 * 264 * 4 + 8 * 4 + 4 * 4}
    # the TPU async tuple form (operand, output): ONLY the transferred
    # output buffer counts — the operand alias must not inflate the bytes
    # or push max_all_gather_elems past the W*k bound
    assert out["ops"]["all-gather"] == {"count": 1, "bytes": 8 * 27 * 2}
    assert out["ops"]["reduce-scatter"] == {"count": 1, "bytes": 64}
    assert out["max_all_gather_elems"] == 8 * 27
    assert out["total_bytes"] == sum(v["bytes"] for v in out["ops"].values())
    assert collective_audit("no collectives here")["total_bytes"] == 0


def test_fsdp_round_audits():
    """The audit works on the second engine too (fsdp round_fn): analyses
    present, collectives nonzero (reduce-scatter/all-gather are the FSDP
    round's fabric)."""
    cfg = Config(fsdp=True, telemetry_level=1, **SKETCH, **BASE)
    sess, _, ids, batch = _session_and_round0(cfg)
    audit = sess.audit_compiled_round(ids, batch, 0.2)
    assert audit.engine == "fsdp"
    assert audit.sketch_decode is None  # the knob is moot under fsdp
    assert audit.cost["flops"] and audit.cost["flops"] > 0
    assert audit.collectives["total_bytes"] > 0


# ---------------------------------------------------------------------------
# retrace sentinel (tentpole piece 3)
# ---------------------------------------------------------------------------

def test_retrace_sentinel_zero_across_clean_run_fires_on_dtype():
    """ISSUE-7 acceptance: zero retraces across a clean 5-round run
    (including the audit's AOT trace, which seeds the first signature);
    a dtype-changing input fires the sentinel and the diff NAMES the
    offending leaf."""
    cfg = Config(telemetry_level=1, **SKETCH, **BASE)
    sess, sampler, ids, batch = _session_and_round0(cfg)
    sess.audit_compiled_round(ids, batch, 0.2)
    assert sess.retrace_sentinel.traces == 1
    for r in range(5):
        ids_r, b = sampler.sample_round(r)
        m = sess.train_round(ids_r, b, 0.2)
        assert m["xla/retraces"] == 0.0
    assert sess.retrace_sentinel.retraces == 0
    b2 = {"x": jnp.asarray(b["x"], jnp.bfloat16), "y": b["y"]}
    m2 = sess.train_round(ids_r, b2, 0.2)
    assert m2["xla/retraces"] == 1.0
    diff = sess.retrace_sentinel.last_diff()
    assert "'x'" in diff and "float32" in diff and "bfloat16" in diff


def test_max_retraces_hard_fails_naming_the_diff():
    cfg = Config(telemetry_level=1, max_retraces=0, **SKETCH, **BASE)
    sess, sampler, ids, batch = _session_and_round0(cfg)
    sess.train_round(ids, batch, 0.2)  # first trace: the expected compile
    b2 = {"x": jnp.asarray(batch["x"], jnp.bfloat16), "y": batch["y"]}
    with pytest.raises(RetraceError, match="bfloat16"):
        sess.train_round(ids, b2, 0.2)


def test_sentinel_tracks_streams_independently():
    """Two jitted programs (host-batch round + index round) each get one
    free first trace — neither counts as a retrace of the other."""
    s = RetraceSentinel()
    s.hook_for("a")(jnp.zeros(3))
    s.hook_for("b")(jnp.zeros(4))
    assert s.traces == 2 and s.retraces == 0
    s.hook_for("a")(jnp.zeros(3, jnp.int32))
    assert s.retraces == 1
    assert "int32" in s.last_diff()
    with s.suspended():
        s.hook_for("a")(jnp.zeros(9))
    assert s.retraces == 1  # suspended traces aren't recorded


def test_signature_diff_names_weak_type_flips():
    """The classic invisible retrace: python float vs jnp scalar differs
    only in weak type — the diff must still say so."""
    import jax.tree_util  # noqa: F401

    from commefficient_tpu.telemetry.xla_audit import describe_signature

    @jax.jit
    def probe(x):
        sigs.append(describe_signature((x,), {}))
        return x + 1

    sigs = []
    probe(jnp.float32(1.0))
    probe(1.0)  # weak-typed f32 — retraces
    assert len(sigs) == 2
    d = signature_diff(sigs[0], sigs[1])
    assert "weak" in d


def test_level0_round_hlo_not_changed_by_observability():
    """The level-0 no-added-ops pin: the lowered round HLO is
    byte-identical whether or not the sentinel is armed (its hook is pure
    python at trace time), and still free of the telemetry sentinel op —
    the bit-identity discipline of PR 3 survives this PR."""
    texts = []
    for max_retraces in (None, 3):
        cfg = Config(telemetry_level=0, max_retraces=max_retraces,
                     **SKETCH, **BASE)
        sess, _, ids, batch = _session_and_round0(cfg)
        lowered = sess.round_fn.lower(
            sess.state, jnp.asarray(ids),
            {k: jnp.asarray(v) for k, v in batch.items()}, jnp.float32(0.2),
        )
        texts.append(lowered.as_text())
    assert texts[0] == texts[1]
    assert "is_finite" not in texts[0]


# ---------------------------------------------------------------------------
# phase spans (tentpole piece 4)
# ---------------------------------------------------------------------------

def test_spans_record_fence_window_and_validate(tmp_path):
    spans = PhaseSpans(str(tmp_path), start_step=2, num_steps=2)
    for step in range(5):
        spans.step(step)
        with spans.span("round_dispatch") as h:
            h.fence(jnp.ones(3))
        with spans.span("device_put"):
            pass
    for item, want in zip(spans.wrap_iter([1, 2, 3], "data_load"),
                          [1, 2, 3]):
        assert item == want
    path = spans.close()
    assert os.path.basename(path) == "spans_0.json"
    rec = _checker().validate_spans(path)
    evs = [e for e in rec["traceEvents"] if e["name"] == "round_dispatch"]
    # fences only inside the [2, 4) steady-state window
    assert [e["args"]["fenced"] for e in evs] == [False, False, True, True,
                                                 False]
    assert {e["name"] for e in rec["traceEvents"]} == {
        "round_dispatch", "device_put", "data_load"
    }


def test_spans_disabled_is_inert(tmp_path):
    spans = PhaseSpans("")
    with spans.span("x") as h:
        assert h is None
    assert list(spans.wrap_iter([7])) == [7]
    assert spans.close() is None
    assert not spans.events


def test_spans_resume_shifts_window():
    spans = PhaseSpans("unused-but-truthy", start_step=2, num_steps=3)
    spans.resume_at(100)
    assert spans.start == 102 and spans.stop_at == 105


# ---------------------------------------------------------------------------
# perf_report.json <-> checker round-trip + enforcement self-tests
# ---------------------------------------------------------------------------

def _write_report(tmp_path, dec="sharded"):
    cfg = Config(telemetry_level=1, sketch_decode=dec, **SKETCH, **BASE)
    sess, _, ids, batch = _session_and_round0(cfg)
    audit = sess.audit_compiled_round(ids, batch, 0.2)
    path = audit.write(str(tmp_path), generated_by="test", cfg=cfg)
    return path


def test_perf_report_roundtrips_through_checker(tmp_path):
    mod = _checker()
    path = _write_report(tmp_path)
    rec = mod.validate_perf_report(path)
    assert rec["generated_by"] == "test"
    assert rec["sketch_decode"] == "sharded"
    assert rec["meta"]["config"]["mode"] == "sketch"
    # validate_run_dir picks the report up alongside other artifacts
    out = mod.validate_run_dir(str(tmp_path))
    assert any(p.endswith("perf_report.json") for p in out)


def test_checker_enforces_wk_bound(tmp_path):
    """A d-sized collective leaking into the sharded round must FAIL the
    checker, not just be recorded."""
    mod = _checker()
    path = _write_report(tmp_path)
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["max_all_gather_elems"] = (
        rec["collectives"]["wk_bound"] + 1
    )
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="W\\*k"):
        mod.validate_perf_report(path)


def _write_sparse_report(tmp_path):
    cfg = Config(telemetry_level=1, mode="true_topk", k=9,
                 topk_method="threshold", error_type="virtual",
                 virtual_momentum=0.9, aggregate="sparse", **BASE)
    sess, _, ids, batch = _session_and_round0(cfg)
    audit = sess.audit_compiled_round(ids, batch, 0.2)
    return audit.write(str(tmp_path), generated_by="test", cfg=cfg)


def test_checker_enforces_sparse_agg_gather_bound(tmp_path):
    """ISSUE 14 acceptance: an all-gather over the pair-exchange bound on
    a sparse-aggregate report must FAIL the checker — the O(W*k) claim is
    machine-enforced, not prose."""
    mod = _checker()
    path = _write_sparse_report(tmp_path)
    rec = mod.validate_perf_report(path)  # genuine artifact passes
    assert rec["aggregate"] == "sparse"
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["max_all_gather_elems"] = (
        rec["collectives"]["sparse_agg_bound"] + 1
    )
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="pair-exchange bound"):
        mod.validate_perf_report(path)


def test_checker_enforces_sparse_agg_reduce_bound(tmp_path):
    """Same gate for all-reduce: a dense psum sneaking back into a round
    claiming sparse aggregation is a checker failure (reduce-scatter is
    exempt — O(D/W) per link, sharded result)."""
    mod = _checker()
    path = _write_sparse_report(tmp_path)
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["max_all_reduce_elems"] = (
        rec["collectives"]["sparse_agg_bound"] + 1
    )
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="all-reduce.*pair-exchange"):
        mod.validate_perf_report(path)


def test_checker_rejects_sparse_agg_without_bound(tmp_path):
    """aggregate='sparse' with a missing/degenerate bound is malformed —
    the claim would be unenforceable."""
    mod = _checker()
    path = _write_sparse_report(tmp_path)
    for bad in (None, 0):
        with open(path) as f:
            rec = json.load(f)
        rec["collectives"]["sparse_agg_bound"] = bad
        with open(path, "w") as f:
            json.dump(rec, f)
        with pytest.raises(mod.SchemaError, match="sparse_agg_bound"):
            mod.validate_perf_report(path)


def test_checker_enforces_sharded_tolerance(tmp_path):
    mod = _checker()
    path = _write_report(tmp_path)
    with open(path) as f:
        rec = json.load(f)
    # fake an out-of-tolerance delta CONSISTENTLY (delta arithmetic intact)
    coll = rec["collectives"]
    coll["ledger_up_bytes"] = 0
    coll["delta_bytes"] = coll["total_bytes"]
    coll["tolerance_bytes"] = 1
    coll["within_tolerance"] = False
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="tolerance"):
        mod.validate_perf_report(path)


def test_checker_rejects_inconsistent_delta_and_totals(tmp_path):
    mod = _checker()
    path = _write_report(tmp_path, dec="dense")
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["delta_bytes"] += 4
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="delta_bytes"):
        mod.validate_perf_report(path)
    with open(path) as f:
        rec = json.load(f)
    rec["collectives"]["total_bytes"] += 4
    rec["collectives"]["delta_bytes"] += 4  # keep delta consistent
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="total_bytes"):
        mod.validate_perf_report(path)


def test_checker_requires_reason_when_degraded(tmp_path):
    mod = _checker()
    path = _write_report(tmp_path, dec="dense")
    with open(path) as f:
        rec = json.load(f)
    rec["cost"] = {"flops": None, "bytes_accessed": None,
                   "transcendentals": None, "unavailable_reason": None}
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="unavailable_reason"):
        mod.validate_perf_report(path)


def test_checker_rejects_bad_span_events(tmp_path):
    mod = _checker()
    path = tmp_path / "spans_0.json"
    good = {"schema_version": 3, "kind": "spans",
            "traceEvents": [{"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0,
                             "pid": 0, "tid": 0, "args": {"step": 0}}]}
    path.write_text(json.dumps(good))
    mod.validate_spans(path)  # sanity: the good one passes
    bad = dict(good)
    bad["traceEvents"] = [{**good["traceEvents"][0], "ph": "B"}]
    path.write_text(json.dumps(bad))
    with pytest.raises(mod.SchemaError, match="ph"):
        mod.validate_spans(path)
    bad["traceEvents"] = [{**good["traceEvents"][0], "args": {}}]
    path.write_text(json.dumps(bad))
    with pytest.raises(mod.SchemaError, match="step"):
        mod.validate_spans(path)


# ---------------------------------------------------------------------------
# the real train-loop path: artifacts written + linked + schema-valid
# ---------------------------------------------------------------------------

def test_cv_train_loop_writes_and_links_perf_artifacts(tmp_path):
    """cv_train.train_loop at level 1 on the TinyMLP task: perf_report +
    spans land in the run dir, every artifact (incl. the new ones)
    validates, the xla/* scalars rode metrics.jsonl, and the run header +
    flight metadata link to the perf report (the artifact-links
    satellite)."""
    from commefficient_tpu.train.cv_train import train_loop

    cfg = Config(telemetry_level=1, num_epochs=1, pivot_epoch=1,
                 lr_scale=0.1, **SKETCH, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                         1, seed=0)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    try:
        train_loop(cfg, sess, sampler, test_ds, writer, eval_batch_size=32)
    finally:
        writer.close()
    assert os.path.exists(os.path.join(run_dir, "perf_report.json"))
    assert glob.glob(os.path.join(run_dir, "spans_*.json"))
    out = _checker().validate_run_dir(run_dir)
    kinds = {os.path.basename(p) for p in out}
    assert {"metrics.jsonl", "comm_ledger.json", "perf_report.json"} <= kinds
    assert any(k.startswith("spans_") for k in kinds)
    names = set()
    header = None
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "header":
                header = rec
            elif "name" in rec:
                names.add(rec["name"])
    assert {"xla/retraces", "xla/collective_bytes",
            "xla/ledger_delta_bytes", "xla/audited_flops"} <= names
    assert header["artifacts"]["perf_report"] == os.path.join(
        run_dir, "perf_report.json"
    )
    # a clean run's sentinel stayed at zero
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        retraces = [json.loads(l)["value"] for l in f
                    if '"xla/retraces"' in l]
    assert retraces and all(v == 0.0 for v in retraces)


def test_flight_meta_links_artifacts(tmp_path):
    from commefficient_tpu.telemetry import build_telemetry_riders

    cfg = Config(telemetry_level=1, **SKETCH, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    writer = MetricsWriter(str(tmp_path / "run"), cfg=cfg)
    try:
        _, flight = build_telemetry_riders(cfg, sess, writer)
    finally:
        writer.close()
    assert flight.meta["artifacts"]["perf_report"].endswith(
        "perf_report.json"
    )
    # no dangling link when the audit is opted out (accuracy_run does)
    from commefficient_tpu.telemetry import run_artifacts

    assert "perf_report" not in run_artifacts(
        cfg.replace(perf_audit=False), str(tmp_path)
    )


def test_gpt2_train_entry_writes_perf_report(tmp_path):
    """The second train entry (acceptance: BOTH entries write a
    schema-valid perf_report.json) — tiny-config CPU e2e at level 1."""
    from commefficient_tpu.train import gpt2_train

    gpt2_train.main(
        [],
        model="gpt2_tiny",
        num_epochs=1,
        num_clients=4,
        num_workers=2,
        num_devices=2,
        local_batch_size=2,
        max_seq_len=64,
        num_candidates=2,
        mode="uncompressed",
        telemetry_level=1,
        logdir=str(tmp_path / "runs"),
    )
    run_dirs = glob.glob(str(tmp_path / "runs" / "*"))
    assert len(run_dirs) == 1
    path = os.path.join(run_dirs[0], "perf_report.json")
    assert os.path.exists(path)
    rec = _checker().validate_perf_report(path)
    assert rec["generated_by"] == "train/gpt2_train"
    assert rec["mode"] == "uncompressed"
