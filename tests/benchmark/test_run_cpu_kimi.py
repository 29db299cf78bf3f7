"""The ``kimi_linear_uncompressed`` cell's window code on the CPU at its
tiny preset, in float32: the whole round (KDA's chunked rule in four layers
of five, latent attention in the fourth, the dense and the routed
feed-forward, the chunked head, clip, dense aggregate, apply) against the
plain reference, every compared number equal to round-off; the stand-ins and
the faults of this mechanism, which the cell's own limits must call not
correct; and the cell's readers on a hand-made trace. (The cases every cell
shares are the parametrised ones of ``test_run_cpu.py``.)"""

import io
import json
from contextlib import redirect_stdout

import pytest
from test_run_cpu import _break_session

from benchmark import calibrate, compare, run

CELL = "kimi_linear_uncompressed"
NEW = ("model.attn_kda_s_per_round", "model.kda_prep_s_per_round", "model.attn_latent_s_per_round",
       "model.attn_kda_mxu_share", "model.attn_latent_mxu_share")


def _rehearse(seed, extra=("--compute_dtype", "float32")):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                       "--trace", "0"], rehearsal={"argv": list(extra)})
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    return json.loads(lines[-1]), next(
        json.loads(ln) for ln in lines if '"info": "gaps"' in ln)["gaps"]


def _over(result):
    return [k for k, v in result["compared"].items()
            if k != "worst_leaf" and v["gap"] > v["limit"]]


def test_kimi_round_equals_the_reference_in_float32():
    result, read = _rehearse(3000000019)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    gaps = {k: v["gap"] for k, v in result["compared"].items() if k != "worst_leaf"}
    assert set(gaps) == set(compare.load_limits(CELL))
    assert read["feed"] == 0.0
    # grad_1 and grad_1_diff are read back as (p0 - p1) / lr in float32, which
    # rounds at ~1e-3 of a small leaf's norm; under the cell's fan_in draw
    # A_log and dt_bias are N(0, 1), so their rounding reaches the whole too
    assert max(v for k, v in read.items() if not k.startswith("grad_1")) < 1e-4, read
    assert read["grad_1"] < 3e-3 and read["grad_1_diff"] < 1e-3, read


def test_the_cell_runs_its_stated_traffic():
    cell = run.load_cell(CELL)
    argv = cell["config_file"]["argv"] + cell["traffic_file"]["argv"]
    flag = lambda name: argv[argv.index(name) + 1]  # noqa: E731
    assert (flag("--model"), flag("--mode"), flag("--num_workers"), flag("--num_clients")) == (
        "kimi_linear_48b", "uncompressed", "2", "64")
    assert (flag("--local_batch_size"), flag("--max_seq_len"), flag("--doc_median"),
            flag("--max_grad_norm")) == ("1", "4096", "1024", "1.0")
    assert cell["traffic"] == "uncompressed_w2" and cell["traffic_file"]["lr"] == 0.01
    assert cell["traffic_file"]["reference"]["clients"] == 2 and cell["chips"] == 1
    names = {m["name"] for m in run.cell_metrics(cell, "per_layer")}
    assert set(NEW) | {"model.mfu", "model.fwd_bwd_s_per_round"} <= names
    for other in ("keye_uncompressed", "laguna_uncompressed", "sdar_uncompressed", "gpt2_sketch"):
        theirs = {m["name"] for m in run.cell_metrics(run.load_cell(other), "per_layer")}
        assert not theirs & set(NEW)


def test_the_reference_imports_nothing_of_the_program():
    import os
    import re

    with open(os.path.join(run.HERE, "reference", "kimi_linear.py")) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import)\s+commefficient_tpu", source, re.M)
    assert set(re.findall(r"^from (\S+) import", source, re.M)) <= {
        "__future__", "benchmark.reference.laguna", "benchmark.reference.ops"}


def test_the_stand_ins_fail_the_cells_own_limits(capsys):
    """The reference with fp8 products, and with half of every batch left
    out, put in the program's place."""
    rc = calibrate.main(["--workload", CELL, "--seeds", "11", "--controls", "1"],
                        rehearsal={"argv": ["--compute_dtype", "float32"]})
    by = {r["what"]: r for r in map(json.loads, filter(
        lambda line: line.startswith("{"), capsys.readouterr().out.splitlines()))}
    assert rc == 0 and by["program"]["correct"] is True
    assert by["control"]["correct"] is False and by["half_batch"]["correct"] is False


def _drop_the_carried_state(monkeypatch):
    """Every chunk starts from a zero state: what one chunk writes never
    reaches the next (the carried-state witness)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import kda

    scan = jax.lax.scan

    def forgetful(step, init, xs):
        return scan(lambda S, x: (jnp.zeros_like(S), step(jnp.zeros_like(S), x)[1]), init, xs)

    monkeypatch.setattr(kda.jax.lax, "scan", forgetful)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "state_dropped"])
def test_a_fault_in_the_programs_place_comes_out_not_correct(monkeypatch, fault):
    if fault == "state_dropped":
        _drop_the_carried_state(monkeypatch)
    else:
        _break_session(monkeypatch, fault)
    result, read = _rehearse(17)
    assert result["correct"] is False and read["feed"] == 0.0
    assert _over(result), result["compared"]
    if fault == "state_dropped":
        # the recurrence's own fault moves the gradient itself
        assert "grad_1_diff" in _over(result)


def _ctx(ops, **peaks):
    """One device, one traced round from 0 to 1 s, holding ``ops``."""
    from benchmark import reduce

    trace = {"devices": {"0": ops}, "host": [["bench/fence", -0.1, 0.1], ["bench/fence", 0.9, 0.1]],
             "program_host": []}
    traced = reduce.summarize(trace, chips=1)
    traced.update(rounds=1)
    cell = run.load_cell(CELL)
    return {"traced": traced, "chips": 1, "values": {}, "peaks": peaks, "cell": cell,
            "units_per_round": 8192,
            "config": {"n_params": 602_433_408, **cell["config_file"]["flops_kwargs"]}}


def test_the_kernel_shares_and_the_scopes_on_a_hand_made_trace():
    """0.2 s under ``attn_kda``, 0.05 s under ``kda_prep``, 0.1 s under
    ``attn_latent``, each share against its required operations at 197
    TFLOP/s. A program without the scopes (the parent, another model)
    reports none of the five."""
    from benchmark import flops_kimi, reduce

    base = "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/layer_1/attn/"
    ops = [["%conv", base + "kda_prep/mul", 0.00, 0.05],
           ["%scan", base + "attn_kda/while", 0.1, 0.2],
           ["%fwd", base.replace("layer_1", "layer_3") + "attn_latent/pallas_call", 0.4, 0.1],
           ["%dot", base + "attn_proj/dot_general", 0.8, 0.1]]
    ctx = _ctx(ops, hbm_bytes_per_s=819e9, flops_per_s=197e12)
    assert reduce.read_metric("model.attn_kda_s_per_round", ctx) == pytest.approx(0.2)
    assert reduce.read_metric("model.kda_prep_s_per_round", ctx) == pytest.approx(0.05)
    assert reduce.read_metric("model.attn_latent_s_per_round", ctx) == pytest.approx(0.1)
    kda = flops_kimi.attn_kda_flops_per_token(**ctx["config"]) * 8192
    assert reduce.read_metric("model.attn_kda_mxu_share", ctx) == pytest.approx(
        100 * kda / 197e12 / 0.2)
    latent = flops_kimi.attn_latent_flops_per_token(**ctx["config"]) * 8192
    share = reduce.read_metric("model.attn_latent_mxu_share", ctx)
    assert share == pytest.approx(100 * latent / 197e12 / 0.1) and 0 < share < 100
    step = flops_kimi.kimi_flops_per_token(**ctx["config"]) * 8192
    assert reduce.read_metric("model.mfu", ctx) == pytest.approx(100 * step / 197e12 / 1.0)
    bare = _ctx([["%dot", "jit(wrapped)/vmap(client_grad)/jvp(Model)/dot_general", 0.1, 0.5]],
                hbm_bytes_per_s=819e9, flops_per_s=197e12)
    for name in NEW:
        assert reduce.read_metric(name, bare) is None
