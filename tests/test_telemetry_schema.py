"""Writer <-> schema pinning for the telemetry artifacts.

Same pattern as tests/test_mode_dispatch.py: the checker script is loaded
from scripts/ and exercised in tier-1. Artifacts are produced through the
REAL writer classes (MetricsWriter, CommLedger, FlightRecorder), so a
writer format change that breaks the documented schema fails here — and
the rejection cases guard the checker against rotting into a vacuous
pass."""

import importlib.util
import json
import os

import pytest

from commefficient_tpu.telemetry import CommLedger, FlightRecorder
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


def _write_run(tmp_path, rounds=3):
    """A full artifact set through the real writers."""
    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=10, num_rows=3, num_cols=64, telemetry_level=2)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    ledger = CommLedger({"upload_floats": 192, "download_floats": 20,
                         "upload_bytes": 768, "download_bytes": 80},
                        mode="sketch", num_workers=8)
    flight = FlightRecorder(cfg, logdir=run_dir)
    for s in range(rounds):
        writer.scalar("train/loss", 1.0 / (s + 1), s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("diag/grad_norm", 0.5, s)
        for k, v in ledger.on_round(s).items():
            writer.scalar(k, v, s)
        flight.record(s, 0.1, {"train/loss": 1.0 / (s + 1),
                               "diag/nonfinite": 0.0})
    writer.close()
    ledger.write(run_dir)
    flight.dump(rounds - 1, reason="test dump", first_bad_step=rounds - 1)
    return run_dir


def test_real_artifacts_validate(tmp_path):
    mod = _checker()
    out = mod.validate_run_dir(_write_run(tmp_path))
    kinds = {os.path.basename(p) for p in out}
    assert kinds == {"metrics.jsonl", "comm_ledger.json", "flight_2.json"}


def test_artifacts_from_real_drain_path_validate(tmp_path):
    """Review regression: the drain records the round's RAW metric dict
    into the flight ring (bare aux keys: loss, correct, ...) and writes a
    non-finite loss into metrics.jsonl — both must validate, through the
    REAL drain_round_metrics, not hand-crafted records."""
    import jax.numpy as jnp

    from commefficient_tpu.telemetry import DivergenceError
    from commefficient_tpu.utils.logging import drain_round_metrics

    cfg = Config(mode="uncompressed", telemetry_level=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    flight = FlightRecorder(cfg, logdir=run_dir)
    pending = [
        (0, 0.1, {"loss": jnp.float32(1.0), "correct": jnp.float32(3.0),
                  "count": jnp.float32(4.0),
                  "diag/nonfinite": jnp.float32(0.0)}),
        (1, 0.1, {"loss": jnp.float32(float("nan")),
                  "correct": jnp.float32(0.0), "count": jnp.float32(4.0),
                  "diag/nonfinite": jnp.float32(1.0)}),
    ]
    with pytest.raises(DivergenceError):
        drain_round_metrics(pending, writer, lambda *a: None, flight=flight)
    writer.close()
    mod = _checker()
    out = mod.validate_run_dir(run_dir)
    assert {os.path.basename(p) for p in out} == {"metrics.jsonl",
                                                  "flight_1.json"}
    # the non-finite loss landed as a strict-JSON "nan" marker, not a bare
    # NaN token
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        content = f.read()
    assert '"value": "nan"' in content and "NaN" not in content


def test_flight_with_nonfinite_lr_and_config_stays_strict_json(tmp_path):
    """Review regression: a non-finite lr or config float (a sweep-produced
    NaN lr_scale IS a divergence scenario) must not emit bare NaN tokens
    into the flight dump — jsonable_tree stringifies them and the artifact
    still validates."""
    import json as _json

    cfg = Config(mode="uncompressed", telemetry_level=1,
                 lr_scale=float("nan"))
    flight = FlightRecorder(cfg, logdir=str(tmp_path))
    flight.record(0, float("nan"), {"loss": 1.0})
    path = flight.dump(0, reason="nan lr", first_bad_step=0)
    content = open(path).read()
    assert "NaN" not in content  # strict JSON, markers only
    rec = _json.loads(content)
    assert rec["records"][0]["lr"] == "nan"
    assert rec["meta"]["config"]["lr_scale"] == "nan"
    mod = _checker()
    mod.validate_flight(path)


def test_checker_rejects_bare_nan_token(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    with open(os.path.join(run_dir, "metrics.jsonl"), "a") as f:
        f.write('{"name": "train/loss", "value": NaN, "step": 9, "t": 0.0}\n')
    with pytest.raises(mod.SchemaError, match="bare NaN"):
        mod.validate_metrics_jsonl(os.path.join(run_dir, "metrics.jsonl"))


def test_checker_rejects_missing_header(tmp_path):
    mod = _checker()
    p = tmp_path / "metrics.jsonl"
    p.write_text('{"name": "train/loss", "value": 1.0, "step": 0, "t": 0}\n')
    with pytest.raises(mod.SchemaError, match="header"):
        mod.validate_metrics_jsonl(p)


def test_checker_rejects_unknown_scalar_namespace(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    with open(os.path.join(run_dir, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({"name": "bogus/thing", "value": 1.0,
                            "step": 9, "t": 0.0}) + "\n")
    with pytest.raises(mod.SchemaError, match="bogus/thing"):
        mod.validate_metrics_jsonl(os.path.join(run_dir, "metrics.jsonl"))


def test_checker_rejects_missing_walltime(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    with open(os.path.join(run_dir, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({"name": "train/loss", "value": 1.0,
                            "step": 9}) + "\n")
    with pytest.raises(mod.SchemaError, match="'t'"):
        mod.validate_metrics_jsonl(os.path.join(run_dir, "metrics.jsonl"))


def test_checker_enforces_ledger_exactness(tmp_path):
    """The checker itself enforces cum == rounds * bytes_per_round, so a
    drifted ledger writer cannot validate."""
    mod = _checker()
    run_dir = _write_run(tmp_path)
    path = os.path.join(run_dir, "comm_ledger.json")
    with open(path) as f:
        rec = json.load(f)
    rec["cum_up_bytes"] += 4
    rec["cum_bytes"] += 4
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="cum_up_bytes"):
        mod.validate_comm_ledger(path)


def test_checker_rejects_out_of_order_flight_records(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    path = os.path.join(run_dir, "flight_2.json")
    with open(path) as f:
        rec = json.load(f)
    rec["records"] = rec["records"][::-1]
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="step order"):
        mod.validate_flight(path)


def test_checker_rejects_unknown_schema_version(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    path = os.path.join(run_dir, "comm_ledger.json")
    with open(path) as f:
        rec = json.load(f)
    rec["schema_version"] = 999
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="schema_version"):
        mod.validate_comm_ledger(path)


class _FakeController:
    def snapshot(self):
        return {"policy": "fixed", "ladder": "k=20,10", "rung": 1,
                "num_rungs": 2, "switches": 1, "rounds_seen": 3,
                "last_switch_round": 2}


def test_flight_controller_block_validates_and_rejects(tmp_path):
    """v4: a controller-attached flight dump carries the dump-time
    controller snapshot; the checker validates it and rejects an
    out-of-range rung."""
    cfg = Config(mode="uncompressed", telemetry_level=1)
    flight = FlightRecorder(cfg, logdir=str(tmp_path),
                            controller=_FakeController())
    flight.record(0, 0.1, {"loss": 1.0})
    path = flight.dump(0, reason="test", first_bad_step=None)
    mod = _checker()
    rec = mod.validate_flight(path)
    assert rec["controller"]["rung"] == 1
    rec["controller"]["rung"] = 5  # outside num_rungs
    with open(path, "w") as f:
        json.dump(rec, f)
    with pytest.raises(mod.SchemaError, match="num_rungs"):
        mod.validate_flight(path)


def test_header_controller_block_validates_and_rejects(tmp_path):
    """v4: the metrics run-header carries the controller identity block
    (MetricsWriter extra_header); the checker validates it."""
    cfg = Config(mode="uncompressed", telemetry_level=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg, extra_header={
        "controller": {"policy": "ef_feedback", "ladder": "k=20,10",
                       "rung": 1, "num_rungs": 2},
    })
    writer.scalar("control/rung", 1.0, 0)
    writer.close()
    mod = _checker()
    path = os.path.join(run_dir, "metrics.jsonl")
    mod.validate_metrics_jsonl(path)
    # a malformed block (missing policy) must fail
    with open(path) as f:
        lines = f.read().splitlines()
    header = json.loads(lines[0])
    del header["controller"]["policy"]
    with open(path, "w") as f:
        f.write(json.dumps(header) + "\n" + "\n".join(lines[1:]) + "\n")
    with pytest.raises(mod.SchemaError, match="policy"):
        mod.validate_metrics_jsonl(path)


def test_checker_rejects_unknown_control_scalar_only_outside_prefix(
        tmp_path):
    """control/ is a documented v4 prefix; names under it pass, the
    namespace boundary still rejects others."""
    mod = _checker()
    run_dir = _write_run(tmp_path)
    path = os.path.join(run_dir, "metrics.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps({"name": "control/budget_remaining_bytes",
                            "value": 123.0, "step": 9, "t": 0.0}) + "\n")
    mod.validate_metrics_jsonl(path)


def test_cli_exit_codes(tmp_path):
    mod = _checker()
    run_dir = _write_run(tmp_path)
    assert mod.main([run_dir]) == 0
    (tmp_path / "empty").mkdir()
    assert mod.main([str(tmp_path / "empty")]) == 1


def test_cli_json_summary_always_last_line(tmp_path, capsys):
    """The gate-script consumer contract (established by
    scripts/check_bench_regression.py, now uniform across all gate
    scripts): the last stdout line is machine-readable JSON on EVERY
    exit path — pass, fail, and usage error."""
    mod = _checker()
    run_dir = _write_run(tmp_path)

    def last(capsys):
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert mod.main([run_dir]) == 0
    s = last(capsys)
    assert s["kind"] == "telemetry_schema"
    assert s["run_dirs"] == 1 and s["artifacts"] >= 3
    assert s["failures"] == []

    (tmp_path / "empty2").mkdir()
    assert mod.main([str(tmp_path / "empty2")]) == 1
    s = last(capsys)
    assert s["failures"] and "no telemetry artifacts" in s["failures"][0]

    assert mod.main([]) == 2  # usage error still ends with the summary
    s = last(capsys)
    assert s["kind"] == "telemetry_schema" and "error" in s

    # a TRUNCATED artifact (raw JSONDecodeError, not SchemaError) must
    # fail the run dir and still end with the summary, not a traceback
    bad = tmp_path / "corrupt"
    bad.mkdir()
    (bad / "comm_ledger.json").write_text("{truncated")
    assert mod.main([str(bad)]) == 1
    s = last(capsys)
    assert s["failures"], "corrupt artifact must be reported in failures"


# ---------------------------------------------------------------------------
# v5: thread-aware spans (the pipeline/* scalars of v5 left with the
# engines that wrote them)
# ---------------------------------------------------------------------------

def test_retired_pipeline_namespace_is_outside_the_schema(tmp_path):
    """No writer emits pipeline/* any more, so the prefix is not a
    documented namespace: a metrics file that still carries one is
    refused by name, through the REAL writer."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    writer.scalar("train/loss", 1.0, 0)
    writer.scalar("lr", 0.1, 0)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 2
    assert "pipeline/" not in mod.SCALAR_PREFIXES
    with open(path, "a") as f:
        f.write(json.dumps({"name": "pipeline/occupancy", "value": 0.5,
                            "step": 0, "t": 1.0}) + "\n")
    with pytest.raises(mod.SchemaError, match="outside the documented"):
        mod.validate_metrics_jsonl(path)


def test_v5_spans_thread_metadata_validates_and_rejects(tmp_path):
    """Thread-aware spans through the REAL recorder: lane tids + the
    thread_name metadata event validate; a non-thread_name metadata
    event, a negative tid, and a metadata-only dump are rejected."""
    import threading

    from commefficient_tpu.telemetry.spans import PhaseSpans

    mod = _checker()
    spans = PhaseSpans(str(tmp_path))
    spans.step(2)
    with spans.span("round_dispatch"):
        pass

    def worker():
        spans.register_lane("round-prefetch")
        with spans.span("prefetch_realize", step=3):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    path = spans.close()
    rec = mod.validate_spans(path)
    lanes = {e["tid"] for e in rec["traceEvents"] if e["ph"] == "X"}
    assert lanes == {0, 1}
    meta = [e for e in rec["traceEvents"] if e["ph"] == "M"]
    assert [(e["tid"], e["args"]["name"]) for e in meta] == \
        [(1, "round-prefetch")]

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_spans.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_spans(bad)

    tampered(lambda r: r["traceEvents"].append(
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "x"}}), "unknown metadata")
    tampered(lambda r: r["traceEvents"][0].update(tid=-1), "tid")
    tampered(lambda r: r.update(traceEvents=meta), "no complete")


def test_v5_spans_lane_labels_survive_ring_eviction(tmp_path):
    """Lane-label metadata must outlive the bounded span ring: a run long
    enough to wrap the ring many times still dumps the thread_name
    record, or long-run traces lose their track labels."""
    from commefficient_tpu.telemetry.spans import MAX_EVENTS, PhaseSpans

    mod = _checker()
    spans = PhaseSpans(str(tmp_path))
    spans.register_lane("main")
    spans.step(2)
    for _ in range(MAX_EVENTS + 10):  # wrap the ring past the label
        with spans.span("round_dispatch"):
            pass
    rec = mod.validate_spans(spans.close())
    meta = [e for e in rec["traceEvents"] if e["ph"] == "M"]
    assert [(e["tid"], e["args"]["name"]) for e in meta] == [(0, "main")]


# ---------------------------------------------------------------------------
# v6: resilience/* scalars + the flight recovery_history block
# ---------------------------------------------------------------------------

def test_v6_resilience_scalars_validate_and_reject(tmp_path):
    """The resilience/ scalar prefix is in-schema through the REAL
    writer; the counter/flag/rollback-round invariants are enforced
    (tampered values rejected)."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1,
                 recover_policy="retry")
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("resilience/recoveries", float(s > 1), s)
        writer.scalar("resilience/rollback_round", -1.0 if s < 2 else 1.0, s)
        writer.scalar("resilience/rung_demotions", 0.0, s)
        writer.scalar("resilience/blacklisted_clients", 0.0, s)
        writer.scalar("resilience/preempt_requested", 0.0, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 21
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "resilience/recoveries", "value": -1.0, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "resilience/recoveries", "value": 0.5, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "resilience/blacklisted_clients", "value": 1.5,
          "step": 0, "t": 1.0}, "non-negative integer"),
        ({"name": "resilience/preempt_requested", "value": 0.5, "step": 0,
          "t": 1.0}, "0/1 flag"),
        ({"name": "resilience/rollback_round", "value": -2.0, "step": 0,
          "t": 1.0}, ">= -1"),
        ({"name": "resilience/rollback_round", "value": 1.5, "step": 0,
          "t": 1.0}, ">= -1"),
        ({"name": "resilience/recoveries", "value": "nan", "step": 0,
          "t": 1.0}, "finite number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


class _FakeResilienceRider:
    """Duck-typed the way FlightRecorder consumes it: a ``history``
    attribute holding the recovery entries."""

    def __init__(self, history):
        self.history = history


def test_v6_flight_recovery_history_validates_and_rejects(tmp_path):
    """A recovery-carrying flight dump (the _recovery-tagged sibling the
    manager writes) validates through the REAL recorder, and the checker
    rejects out-of-order ordinals, post-divergence rollback targets, and
    empty blocks."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1,
                 recover_policy="retry")
    flight = FlightRecorder(cfg, logdir=str(tmp_path))
    flight.resilience = _FakeResilienceRider([
        {"recovery": 1, "policy": "retry", "first_bad_step": 5,
         "reason": "diag/nonfinite", "outcome": "recovered",
         "rollback_to": 4},
        {"recovery": 2, "policy": "retry", "first_bad_step": 8,
         "reason": "diag/nonfinite", "outcome": "recovered",
         "rollback_to": 8},
    ])
    for s in range(3):
        flight.record(s, 0.1, {"loss": 1.0})
    path = flight.dump(5, reason="recovered from divergence at round 5",
                       first_bad_step=5, tag="_recovery")
    assert path.endswith("flight_5_recovery.json")
    rec = mod.validate_flight(path)
    assert len(rec["recovery_history"]) == 2

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_flight.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_flight(bad)

    tampered(lambda r: r["recovery_history"][1].update(recovery=3),
             "out of order")
    tampered(lambda r: r["recovery_history"][0].update(rollback_to=6),
             "pre-divergence")
    tampered(lambda r: r["recovery_history"][0].pop("policy"), "policy")
    tampered(lambda r: r.update(recovery_history=[]), "non-empty")
    tampered(lambda r: r["recovery_history"][0].update(first_bad_step=-1),
             "negative first_bad_step")


# ---------------------------------------------------------------------------
# v8: async/* scalars + the perf_report overlap-geometry block
# ---------------------------------------------------------------------------

def test_v8_async_scalars_validate_and_reject(tmp_path):
    """The async/ scalar prefix is in-schema through the REAL writer; the
    staleness-sign and integer-gauge invariants are enforced (tampered
    values rejected). The end-to-end form — these scalars riding a real
    asyncfed run's metrics.jsonl — is pinned by tests/test_asyncfed.py."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1, num_workers=8,
                 num_devices=8, async_buffer=4, async_concurrency=2,
                 staleness_exponent=0.5)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("async/staleness_mean", 0.5 * s, s)
        writer.scalar("async/staleness_max", float(s), s)
        writer.scalar("async/buffer_fill", float(s), s)
        # 0 is legal: the run's trailing updates launch no replacement
        writer.scalar("async/concurrent_cohorts", float(2 - s), s)
        writer.scalar("async/effective_participation", 3.5, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 21
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "async/staleness_mean", "value": -0.5, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "async/staleness_max", "value": -1.0, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "async/effective_participation", "value": -3.5,
          "step": 0, "t": 1.0}, "negative"),
        ({"name": "async/buffer_fill", "value": 1.5, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "async/buffer_fill", "value": -1.0, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "async/concurrent_cohorts", "value": 0.5, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "async/concurrent_cohorts", "value": -1.0, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "async/staleness_mean", "value": "nan", "step": 0,
          "t": 1.0}, "finite number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


def _write_perf_report(tmp_path, **extra):
    """A REAL audit-produced perf report on the TinyMLP round (the async
    variant exercises the engine='async' producer path end-to-end)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.data import FedDataset, FedSampler
    from commefficient_tpu.models.losses import classification_loss
    from commefficient_tpu.parallel import FederatedSession

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(4)(x)

    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=20, num_rows=3, num_cols=200, telemetry_level=1,
                 num_clients=12, num_workers=8, num_devices=8,
                 local_batch_size=4, seed=5, **extra)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(200, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=200).astype(np.int32)
    ds = FedDataset({"x": x, "y": y}, cfg.num_clients, iid=True, seed=0)
    model = TinyMLP()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8)))
    sess = FederatedSession(cfg, params, classification_loss(model.apply))
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=1)
    ids, batch = sampler.sample_round(0)
    audit = sess.audit_compiled_round(ids, batch, 0.2)
    return audit.write(str(tmp_path), generated_by="test", cfg=cfg)


def test_v8_perf_report_async_block_required_and_forbidden(tmp_path):
    """A REAL async audit report validates with its overlap-geometry
    block; the checker rejects every mislabeling direction — block on a
    sync report, async engine without a block, and malformed geometry."""
    mod = _checker()
    path = _write_perf_report(tmp_path, async_buffer=4, async_concurrency=2,
                              staleness_exponent=0.5)
    rec = mod.validate_perf_report(path)
    assert rec["engine"] == "async"
    assert rec["async"] == {"buffer": 4, "concurrency": 2,
                            "staleness_exponent": 0.5}

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_report.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_perf_report(bad)

    tampered(lambda r: r.pop("async"), "missing required field 'async'")
    tampered(lambda r: r["async"].update(buffer=0), "below 1")
    tampered(lambda r: r["async"].update(concurrency=1.5),
             "must be an integer")
    tampered(lambda r: r["async"].update(staleness_exponent="x"),
             "non-numeric")
    tampered(lambda r: r["async"].update(staleness_exponent=-0.5),
             "below 0")
    tampered(lambda r: r.update(engine="bogus"), "unknown engine")
    # forbidden direction: the block riding a synchronous report
    tampered(lambda r: r.update(engine="replicated"),
             "present on a 'replicated' report")


# ---------------------------------------------------------------------------
# v9: the exposed-collective gauge + the perf-report overlap block
# ---------------------------------------------------------------------------

def test_v9_exposed_collective_scalar_validates_and_rejects(tmp_path):
    """xla/exposed_collective_ms through the REAL writer validates; the
    gauge invariant (finite, >= 0) rejects every tampering direction."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("xla/exposed_collective_ms", 0.25 * s, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    mod.validate_metrics_jsonl(path)

    lines = open(path).read().splitlines()
    for bad_rec, msg in [
        ({"name": "xla/exposed_collective_ms", "value": -0.5, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "xla/exposed_collective_ms", "value": "nan", "step": 0,
          "t": 1.0}, "finite number"),
        ({"name": "xla/exposed_collective_ms", "value": True, "step": 0,
          "t": 1.0}, "neither a number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(lines[0] + "\n" + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


def test_v9_spans_collective_tag_and_exposure_field(tmp_path):
    """A REAL spans dump with collective-tagged spans validates and
    carries the dump-level exposure figure; the checker rejects a false
    tag and a negative exposure."""
    from commefficient_tpu.telemetry.spans import PhaseSpans

    mod = _checker()
    spans = PhaseSpans(str(tmp_path))
    spans.step(2)
    with spans.span("round_dispatch", collective=True):
        pass
    with spans.span("data_load"):
        pass
    path = spans.close()
    rec = mod.validate_spans(path)
    assert rec["exposed_collective_ms"] >= 0.0
    tagged = [e for e in rec["traceEvents"]
              if e["ph"] == "X" and e["args"].get("collective")]
    assert len(tagged) == 1 and tagged[0]["name"] == "round_dispatch"

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_spans.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_spans(bad)

    tampered(lambda r: r["traceEvents"][0]["args"].update(collective=False),
             "args.collective must be true")
    tampered(lambda r: r["traceEvents"][0]["args"].update(collective=1),
             "args.collective must be true")
    tampered(lambda r: r.update(exposed_collective_ms=-1.0), "negative")
    tampered(lambda r: r.update(exposed_collective_ms="nan"),
             "finite number")


def test_v9_perf_report_overlap_block_required_and_forbidden(tmp_path):
    """A REAL layerwise-overlap audit report validates with its v9
    overlap block; the checker rejects every mislabeling direction —
    config on without the block, block with config off, all-off block,
    and malformed fields."""
    mod = _checker()
    path = _write_perf_report(tmp_path, overlap_collectives="layerwise")
    rec = mod.validate_perf_report(path)
    assert rec["overlap"] == {"collectives": "layerwise",
                              "double_buffer": False}
    assert rec["meta"]["config"]["overlap_collectives"] == "layerwise"

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_report.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_perf_report(bad)

    # required direction: hiding mode on in config, block missing
    tampered(lambda r: r.pop("overlap"), "no 'overlap' block")
    # malformed fields
    tampered(lambda r: r["overlap"].update(collectives="bogus"),
             "'none' or 'layerwise'")
    tampered(lambda r: r["overlap"].update(double_buffer=1),
             "must be a bool")
    # an all-off block is a writer bug (the block exists to mark runs
    # whose wall-clock is overlap-dependent)
    tampered(lambda r: (r["overlap"].update(collectives="none"),
                        r["meta"]["config"].update(
                            overlap_collectives="none")),
             "every hiding mode off")
    # forbidden direction: block riding a config with hiding off
    tampered(lambda r: r["meta"]["config"].update(
        overlap_collectives="none"),
        "config has overlap_collectives='none'")


def test_v9_report_without_hiding_modes_has_no_overlap_block(tmp_path):
    """The default round's report stays block-free (v8 shape), and a v8
    artifact — config predating the overlap keys entirely — still
    validates."""
    mod = _checker()
    path = _write_perf_report(tmp_path)
    rec = mod.validate_perf_report(path)
    assert "overlap" not in rec
    assert rec["meta"]["config"]["overlap_collectives"] == "none"

    # a genuine v8 artifact: no overlap keys in config at all
    with open(path) as f:
        r = json.load(f)
    r["schema_version"] = 8
    r["meta"]["config"].pop("overlap_collectives")
    r["meta"]["config"].pop("async_double_buffer")
    old = os.path.join(str(tmp_path), "v8_report.json")
    with open(old, "w") as f:
        json.dump(r, f)
    mod.validate_perf_report(old)


def test_v10_clientstore_scalars_validate_and_reject(tmp_path):
    """The clientstore/ scalar prefix is in-schema through the REAL
    writer; value invariants (hit-rate fraction, integer eviction gauge,
    non-negative wall-clock) are enforced. The end-to-end form — these
    scalars riding a hosted run's drained metrics — is pinned by
    tests/test_clientstore.py."""
    mod = _checker()
    cfg = Config(mode="local_topk", error_type="local", local_momentum=0.9,
                 k=30, telemetry_level=1, num_workers=8, num_devices=8,
                 client_store="host", client_store_cache_rows=4)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("clientstore/cache_hit_rate", 0.5, s)
        writer.scalar("clientstore/evictions", float(s), s)
        writer.scalar("clientstore/h2d_stage_ms", 0.3, s)
        writer.scalar("clientstore/writeback_ms", 0.0, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 18
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "clientstore/cache_hit_rate", "value": 1.5, "step": 0,
          "t": 1.0}, r"outside \[0, 1\]"),
        ({"name": "clientstore/cache_hit_rate", "value": -0.1, "step": 0,
          "t": 1.0}, r"outside \[0, 1\]"),
        ({"name": "clientstore/evictions", "value": 0.5, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "clientstore/evictions", "value": -1.0, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "clientstore/h2d_stage_ms", "value": -0.1, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "clientstore/writeback_ms", "value": -2.0, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "clientstore/cache_hit_rate", "value": True, "step": 0,
          "t": 1.0}, "neither a number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


def test_v10_perf_report_rejects_hosted_exemption(tmp_path):
    """A sparse-aggregate report whose config hosts client state may not
    carry ANY sparse_agg_exemption (the [C, D] writeback gather does not
    exist in the hosted HLO); unknown exemption markers are rejected
    outright. The accepting side — a REAL hosted audit passing the strict
    bound — is pinned by tests/test_clientstore.py."""
    mod = _checker()
    path = _write_perf_report(tmp_path)
    rec = mod.validate_perf_report(path)
    assert rec["collectives"]["sparse_agg_exemption"] is None

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_perf.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_perf_report(bad)

    # recast as a sparse-aggregate report (generous bound: only the
    # exemption rules should fire)
    def sparse(r):
        r["aggregate"] = "sparse"
        r["collectives"]["sparse_agg_bound"] = 10 ** 9

    def unknown_marker(r):
        sparse(r)
        r["collectives"]["sparse_agg_exemption"] = "hand_wave"

    def host_with_exemption(r):
        sparse(r)
        r["meta"]["config"]["client_store"] = "host"
        r["collectives"]["sparse_agg_exemption"] = "client_state_writeback"

    tampered(unknown_marker, "unknown sparse_agg_exemption")
    tampered(host_with_exemption, "hosts client state")


# ---------------------------------------------------------------------------
# v11: trace/* scalars, span trace ids, and the run report
# ---------------------------------------------------------------------------

def test_v11_trace_scalars_validate_and_reject(tmp_path):
    """The trace/ critical-path prefix is in-schema through the REAL
    writer; the index/interval invariants are enforced on both scalar
    paths (metrics.jsonl and the flight recorder's metric blocks). The
    end-to-end form — these scalars riding a traced run's metrics — is
    pinned by tests/test_trace.py."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1, num_workers=8,
                 num_devices=8)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        # the lagged emission's zeros row, then a real attribution
        writer.scalar("trace/critical_stage", 6.0 if s < 2 else 3.0, s)
        writer.scalar("trace/collective_exclusive_ms",
                      0.0 if s < 2 else 1.25, s)
        writer.scalar("trace/idle_exclusive_ms", 0.0, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 15
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "trace/idle_exclusive_ms", "value": -0.5, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "trace/dispatch_exclusive_ms", "value": -2.0, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "trace/critical_stage", "value": 3.5, "step": 0,
          "t": 1.0}, "integer index"),
        ({"name": "trace/critical_stage", "value": -1.0, "step": 0,
          "t": 1.0}, "integer index"),
        ({"name": "trace/critical_stage", "value": 7.0, "step": 0,
          "t": 1.0}, "integer index"),
        ({"name": "trace/critical_stage", "value": "nan", "step": 0,
          "t": 1.0}, "finite number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))

    # same invariants hold on the flight recorder's metric blocks
    flight = FlightRecorder(cfg, logdir=str(tmp_path))
    for s in range(3):
        flight.record(s, 0.1, {"loss": 1.0, "trace/critical_stage": 6.0,
                               "trace/idle_exclusive_ms": 0.25})
    fpath = flight.dump(2, reason="test dump", first_bad_step=2)
    mod.validate_flight(fpath)

    def tampered(mutate, msg):
        with open(fpath) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_flight.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_flight(bad)

    tampered(lambda r: r["records"][0]["scalars"].update(
        {"trace/idle_exclusive_ms": -1.0}), "negative")
    tampered(lambda r: r["records"][0]["scalars"].update(
        {"trace/critical_stage": 2.5}), "integer index")


def test_v11_spans_trace_id_rules(tmp_path):
    """Span trace correlation through the REAL recorder: a cohort span
    with a round parent validates; an empty trace_id, a bare parent
    (no trace_id), and a self-parented span are rejected."""
    from commefficient_tpu.telemetry.spans import PhaseSpans

    mod = _checker()
    spans = PhaseSpans(str(tmp_path))
    spans.step(2)
    with spans.span("round_dispatch", trace_id="r2"):
        pass
    with spans.span("async_launch", step=2, trace_id="c1", parent="r2"):
        pass
    with spans.span("metric_drain"):  # correlation is OPTIONAL per span
        pass
    path = spans.close()
    rec = mod.validate_spans(path)
    evs = [e for e in rec["traceEvents"] if e["ph"] == "X"]
    assert {e["args"].get("trace_id") for e in evs} == {"r2", "c1", None}
    launch = next(e for e in evs if e["name"] == "async_launch")
    assert launch["args"]["parent"] == "r2"

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_spans.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_spans(bad)

    def x_events(r):
        return [e for e in r["traceEvents"] if e["ph"] == "X"]

    tampered(lambda r: x_events(r)[0]["args"].update(trace_id=""),
             "non-empty string")
    tampered(lambda r: x_events(r)[2]["args"].update(parent="r9"),
             "without args.trace_id")
    tampered(lambda r: x_events(r)[1]["args"].update(parent="c1"),
             "own causal parent")


def test_v11_run_report_validates_and_rejects(tmp_path):
    """The run report through the REAL builder (telemetry/trace.py) over
    a real spans dump, then the attribution invariants: overlapping
    stage intervals (exclusive sums past the wall), negative stage
    times, a broken binding-stage count, and off-taxonomy stages are
    all caught — the checker cannot rot into a vacuous pass."""
    from commefficient_tpu.telemetry.spans import PhaseSpans
    from commefficient_tpu.telemetry.trace import write_run_report

    mod = _checker()
    spans = PhaseSpans(str(tmp_path))
    for s in range(2):
        spans.step(s)
        with spans.span("device_put", step=s, trace_id=f"r{s}"):
            pass
        with spans.span("round_dispatch", step=s, collective=True,
                        trace_id=f"r{s}"):
            pass
    spans.close()
    path = write_run_report(str(tmp_path), generated_by="schema test")
    rec = mod.validate_run_report(path)
    assert rec["rounds_analyzed"] == 2
    # the run-dir walk picks the report up alongside the spans dump
    walk = mod.validate_run_dir(str(tmp_path))
    assert any(p.endswith("run_report.json") for p in walk)

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_report.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_run_report(bad)

    def overlap(r):
        # charge the same microseconds twice: the exclusive sums now
        # exceed the round's wall-clock
        r["rounds"][0]["stages_ms"]["data"] += \
            r["rounds"][0]["wall_ms"] + 1.0

    tampered(overlap, "stages overlap")
    tampered(lambda r: r["rounds"][0]["stages_ms"].update(h2d=-0.25),
             "negative")
    tampered(lambda r: r["rounds"][0].update(critical_stage="turbo"),
             "outside the stage taxonomy")
    tampered(lambda r: r.update(critical_stage="turbo"),
             "outside the stage taxonomy")
    tampered(lambda r: r["critical_counts"].update(idle=5),
             "critical_counts sum")
    tampered(lambda r: r["critical_counts"].pop("idle"),
             "stage taxonomy")
    tampered(lambda r: r["stages"]["idle"].update(fraction=0.9),
             "fractions sum")
    tampered(lambda r: r["stages"]["idle"].update(p50_ms=-1.0),
             ">= 0")
    tampered(lambda r: r.update(rounds=r["rounds"][:1]),
             "per-round entries")
    tampered(lambda r: r.update(kind="bench"), "kind must be")


# ---------------------------------------------------------------------------
# v12: multihost/* scalars and the perf-report multihost block
# ---------------------------------------------------------------------------

def test_v12_multihost_scalars_validate_and_reject(tmp_path):
    """The multihost/ topology prefix is in-schema through the REAL
    writer (the end-to-end form — these scalars riding a num_hosts > 1
    session's rounds — is pinned by tests/test_multihost.py); the
    value invariants reject every tampering direction on both scalar
    paths."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1, num_workers=8,
                 num_devices=8, num_hosts=2)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(3):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        # 1 process = the mesh-faked twin; bytes/exposure are gauges
        writer.scalar("multihost/num_processes", 1.0, s)
        writer.scalar("multihost/host_id", 0.0, s)
        writer.scalar("multihost/cross_host_bytes", 4096.0 * s, s)
        writer.scalar("multihost/dcn_exposed_ms", 0.5 * s, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 18
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "multihost/num_processes", "value": 0.0, "step": 0,
          "t": 1.0}, "positive"),
        ({"name": "multihost/num_processes", "value": 1.5, "step": 0,
          "t": 1.0}, "positive"),
        ({"name": "multihost/host_id", "value": -1.0, "step": 0,
          "t": 1.0}, "non-negative"),
        ({"name": "multihost/host_id", "value": 0.5, "step": 0,
          "t": 1.0}, "non-negative"),
        ({"name": "multihost/cross_host_bytes", "value": -4096.0,
          "step": 0, "t": 1.0}, "negative"),
        ({"name": "multihost/dcn_exposed_ms", "value": -0.5, "step": 0,
          "t": 1.0}, "negative"),
        ({"name": "multihost/num_processes", "value": "nan", "step": 0,
          "t": 1.0}, "finite number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))

    # same invariants hold on the flight recorder's metric blocks
    flight = FlightRecorder(cfg, logdir=str(tmp_path))
    for s in range(3):
        flight.record(s, 0.1, {"loss": 1.0, "multihost/num_processes": 1.0,
                               "multihost/cross_host_bytes": 4096.0})
    fpath = flight.dump(2, reason="test dump", first_bad_step=2)
    mod.validate_flight(fpath)

    def tampered(mutate, msg):
        with open(fpath) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_flight.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_flight(bad)

    tampered(lambda r: r["records"][0]["scalars"].update(
        {"multihost/num_processes": 0.0}), "positive")
    tampered(lambda r: r["records"][0]["scalars"].update(
        {"multihost/cross_host_bytes": -1.0}), "negative")


def test_v12_perf_report_multihost_block_required_and_forbidden(tmp_path):
    """A REAL mesh-faked 2-host audit report carries the topology block
    and validates; the checker rejects every mislabeling direction —
    block removed from a multi-host report, single-host geometry inside
    the block, host_id outside the pod, and the block riding a report
    whose config declares no host axis."""
    mod = _checker()
    path = _write_perf_report(tmp_path, num_hosts=2)
    rec = mod.validate_perf_report(path)
    assert rec["multihost"] == {"num_hosts": 2, "num_processes": 1,
                                "host_id": 0}

    def tampered(mutate, msg):
        with open(path) as f:
            r = json.load(f)
        mutate(r)
        bad = os.path.join(str(tmp_path), "bad_report.json")
        with open(bad, "w") as f:
            json.dump(r, f)
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_perf_report(bad)

    tampered(lambda r: r.pop("multihost"), "no 'multihost' block")
    tampered(lambda r: r["multihost"].update(num_hosts=1),
             "integer >= 2")
    tampered(lambda r: r["multihost"].update(num_hosts=2.5),
             "integer >= 2")
    tampered(lambda r: r["multihost"].update(num_processes=0),
             "integer >= 1")
    tampered(lambda r: r["multihost"].update(host_id=1),
             "outside")
    tampered(lambda r: r["multihost"].update(host_id=-1),
             "outside")
    # forbidden direction: the block riding a single-host report
    tampered(lambda r: r["meta"]["config"].update(num_hosts=1),
             "mislabeled producer")


# ---------------------------------------------------------------------------
# v13: fleet/* + control/async_* (elastic fleet / staleness_aware)
# ---------------------------------------------------------------------------

def test_v13_fleet_scalars_validate_and_reject(tmp_path):
    """The fleet/ prefix is in-schema through the REAL writer (the
    end-to-end form — these scalars riding a real elastic run — is
    pinned by tests/test_fleet.py); the positive-width, counted-event
    and no-resize-from-the-future invariants reject tampering."""
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1, num_workers=8,
                 num_devices=4, chaos="resize@4:rounds=1-2")
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s, (w, n, last) in enumerate([(8, 0, -1), (4, 1, 1), (4, 1, 1)]):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("fleet/width", float(w), s)
        writer.scalar("fleet/resizes", float(n), s)
        writer.scalar("fleet/last_resize_round", float(last), s)
        writer.scalar("fleet/shrink_recoveries", 0.0, s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    assert mod.validate_metrics_jsonl(path) == 18
    header = open(path).readline()
    for bad_rec, msg in [
        ({"name": "fleet/width", "value": 0.0, "step": 0, "t": 1.0},
         "positive integer"),
        ({"name": "fleet/width", "value": 4.5, "step": 0, "t": 1.0},
         "positive integer"),
        ({"name": "fleet/resizes", "value": -1.0, "step": 0, "t": 1.0},
         "non-negative integer"),
        ({"name": "fleet/shrink_recoveries", "value": 0.5, "step": 0,
          "t": 1.0}, "non-negative integer"),
        ({"name": "fleet/last_resize_round", "value": -2.0, "step": 0,
          "t": 1.0}, ">= -1"),
        # a resize cannot postdate the round reporting it
        ({"name": "fleet/last_resize_round", "value": 5.0, "step": 2,
          "t": 1.0}, "postdates"),
        ({"name": "fleet/width", "value": "nan", "step": 0, "t": 1.0},
         "finite number"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(header + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


def test_v13_control_async_scalars_validate_and_reject(tmp_path):
    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1)
    run_dir = str(tmp_path / "run")
    writer = MetricsWriter(run_dir, cfg=cfg)
    for s in range(2):
        writer.scalar("train/loss", 1.0, s)
        writer.scalar("lr", 0.1, s)
        writer.scalar("control/async_k", 4.0, s)
        writer.scalar("control/async_c", float(2 - s), s)
        writer.scalar("control/retunes", float(s), s)
    writer.close()
    path = os.path.join(run_dir, "metrics.jsonl")
    with pytest.raises(mod.SchemaError, match="K >= 1, C >= 1"):
        # the controller clamps C >= 1: the s=1 row above wrote 1.0, so
        # tamper a 0 to prove the rule bites
        bad = tmp_path / "bad.jsonl"
        bad.write_text(open(path).readline() + json.dumps(
            {"name": "control/async_c", "value": 0.0, "step": 0,
             "t": 1.0}) + "\n")
        mod.validate_metrics_jsonl(str(bad))
    assert mod.validate_metrics_jsonl(path) == 10
    for bad_rec, msg in [
        ({"name": "control/async_k", "value": 0.0, "step": 0, "t": 1.0},
         "K >= 1"),
        ({"name": "control/async_k", "value": 2.5, "step": 0, "t": 1.0},
         "positive integer"),
        ({"name": "control/retunes", "value": -1.0, "step": 0, "t": 1.0},
         "non-negative"),
    ]:
        bad = tmp_path / "bad.jsonl"
        bad.write_text(open(path).readline() + json.dumps(bad_rec) + "\n")
        with pytest.raises(mod.SchemaError, match=msg):
            mod.validate_metrics_jsonl(str(bad))


def test_v13_flight_fleet_resizes_monotone(tmp_path):
    """Flight-ring rule: fleet/resizes is a cumulative transition count,
    so within one dump's step-ordered records it may never fall — a fall
    means rolled-back records were spliced into the ring."""
    from commefficient_tpu.telemetry import FlightRecorder

    mod = _checker()
    cfg = Config(mode="uncompressed", telemetry_level=1, num_workers=8,
                 num_devices=4, chaos="resize@4:rounds=1-2")
    good = FlightRecorder(cfg, logdir=str(tmp_path))
    for s, n in enumerate([0.0, 1.0, 1.0, 2.0]):
        good.record(s, 0.1, {"loss": 1.0, "fleet/width": 8.0,
                             "fleet/resizes": n,
                             "fleet/last_resize_round": -1.0})
    path = good.dump(3, reason="ok", first_bad_step=3)
    mod.validate_flight(path)
    bad = FlightRecorder(cfg, logdir=str(tmp_path / "bad"))
    for s, n in enumerate([0.0, 1.0, 0.0]):
        bad.record(s, 0.1, {"loss": 1.0, "fleet/width": 8.0,
                            "fleet/resizes": n,
                            "fleet/last_resize_round": -1.0})
    path = bad.dump(2, reason="bad", first_bad_step=2)
    with pytest.raises(mod.SchemaError, match="fell from 1"):
        mod.validate_flight(path)
