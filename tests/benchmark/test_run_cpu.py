"""The harness on the CPU: every cell's files resolve by name, a measuring
run refuses to start without the chip, and a rehearsal hook drives the same
window code at a tiny preset — sound, and with the timed path broken
underneath, where ``correct`` has to come out false. The plain reference and
the session's round are compared by the comparison code the chip run uses,
in each mode a cell uses."""

import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmark import calibrate, compare, resolve, run

ROOT = run.ROOT
BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_resolve(cell_name):
    cell = run.load_cell(cell_name)
    conf, traffic = cell["config_file"], cell["traffic_file"]
    assert conf["name"] == cell["config"]
    assert os.path.exists(os.path.join(run.HERE, "entries", conf["entry"] + ".py"))
    assert os.path.exists(os.path.join(run.HERE, "reference", conf["reference"]["module"] + ".py"))
    assert callable(resolve(conf["flops_fn"]))
    assert callable(resolve(traffic["reference"]["server"]).step)
    limits = compare.load_limits(cell_name)
    assert limits and all(lim >= 0 for lim in limits.values())
    ends = {m["name"] for m in run.cell_metrics(cell, "end_to_end")}
    assert {"round_s", "setup_s"} <= ends
    for m in run.cell_metrics(cell, "per_layer"):
        spec = run.load_json(run.HERE, "layers", m["name"] + ".json")
        assert (spec["unit"], spec["moves"], spec["layer"], spec["source"]) == (
            m["unit"], m["moves"], m["layer"], m["source"]), m["name"]
        assert m["moves"] in ends


@pytest.mark.parametrize("kind,name", [
    ("configs", "resnet9_cifar10"), ("traffic", "sketch_5x500k_k50k_w8")])
def test_files_kept_as_the_witness_of_the_resnet_fault_still_resolve(kind, name):
    spec = run.load_json(run.HERE, kind, name + ".json")
    assert spec["argv"] and spec["reference"]
    for where in (spec.get("flops_fn"), spec["reference"].get("server")):
        assert where is None or callable(resolve(where))


def test_refuses_to_measure_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs" in out.stderr and "TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def _rehearse(cell, seed, extra=()):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                       "--trace", "0"], rehearsal={"argv": list(extra)})
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _break_session(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.parallel.api import FederatedSession

    real = FederatedSession.train_round_indices

    def state_unchanged(self, client_ids, idx, plan, lr, env=None):
        keep = jax.tree.map(lambda a: jnp.array(a, copy=True), self.state)
        metrics = real(self, client_ids, idx, plan, lr, env)
        self.state = keep
        return metrics

    def half_batch(self, client_ids, idx, plan, lr, env=None):
        idx = np.array(idx)
        half = idx.shape[1] // 2
        idx[:, half:] = idx[:, :half]
        return real(self, client_ids, idx, plan, lr, env)

    monkeypatch.setattr(FederatedSession, "train_round_indices",
                        {"state_unchanged": state_unchanged, "half_batch": half_batch}[fault])


def test_rehearsal_drives_the_window_and_is_correct():
    # the cell's limits are set from the chip at the cell's own size; at the
    # tiny preset bf16 noise is another size, so the sound run is in float32
    result = _rehearse("gpt2_uncompressed", 3000000019, ["--compute_dtype", "float32"])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-2:] == ["host_peak_bytes", "compared"]
    assert result["host_peak_bytes"] > 0
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    named = [k for k in result["compared"] if k != "worst_leaf"]
    assert named == list(compare.load_limits("gpt2_uncompressed"))
    for name in named:
        assert result["compared"][name]["gap"] <= result["compared"][name]["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    _break_session(monkeypatch, fault)
    result = _rehearse("gpt2_uncompressed", 17)
    assert result["correct"] is False
    over = [k for k, v in result["compared"].items()
            if k != "worst_leaf" and v["gap"] > v["limit"]]
    assert over, result["compared"]


def test_sketch_round_equals_the_reference_in_float32():
    # float32 compute takes rounding out of the question: the whole round —
    # client gradients, clip, encode, momentum, error feedback, top-k, apply
    # — has to agree with the plain reference to float32 round-off
    result = _rehearse("gpt2_sketch", 5, ["--compute_dtype", "float32"])
    gaps = {k: v["gap"] for k, v in result["compared"].items() if k != "worst_leaf"}
    assert max(gaps.values()) < 1e-3, gaps


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_and_the_fault_fail_the_cells_own_limits(cell_name, capsys):
    # the reference in the precision below the configuration's, and with half
    # of every batch left out, put in the program's place: compare.judge with
    # the limits the chip run uses has to call both not correct
    rc = calibrate.main(["--workload", cell_name, "--seeds", "7", "--controls", "1"],
                        rehearsal={"argv": ["--compute_dtype", "float32"]})
    by = {r["what"]: r for r in map(json.loads, filter(
        lambda line: line.startswith("{"), capsys.readouterr().out.splitlines()))}
    assert rc == 0 and by["program"]["correct"] is True
    assert by["control"]["correct"] is False and by["half_batch"]["correct"] is False


class _FakeSampler:
    """Two clients of four rows each; a round draws both, two rows apiece."""

    class dataset:  # noqa: N801
        data = {"x": np.arange(80).reshape(8, 10)}
        client_indices = [np.arange(0, 4), np.arange(4, 8)]

    def __init__(self, host_rows):
        self.host_rows = np.asarray(host_rows)

    def sample_round(self, _s):
        return np.array([0, 1], np.int32), {"x": self.dataset.data["x"][self.host_rows]}


@pytest.mark.parametrize("fed_ids,fed_rows,host_rows,sound", [
    ([0, 1], [[0, 3], [5, 6]], [[0, 3], [5, 6]], True),
    ([0, 1], [[0, 3], [5, 6]], [[0, 3], [5, 7]], False),   # the host path draws another row
    ([0, 1], [[0, 5], [5, 6]], [[0, 5], [5, 6]], False),   # a row of the other client
    ([1, 1], [[4, 5], [5, 6]], [[4, 5], [5, 6]], False),   # a client drawn twice
])
def test_feed_counts_what_the_two_feeds_disagree_on(fed_ids, fed_rows, host_rows, sound):
    fed = [(np.array(fed_ids, np.int32), np.array(fed_rows, np.int32), ())]
    batches, off = run.fed_batches(fed, _FakeSampler(host_rows))
    assert (off == 0) is sound
    assert np.array_equal(batches[0]["x"], _FakeSampler.dataset.data["x"][np.array(fed_rows)])
