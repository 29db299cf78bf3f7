"""The ``sdar_uncompressed`` cell's window code on the CPU at its tiny
preset, in float32: the whole round (the noise as the feed's plan, the two
streams under block diffusion's mask, per-client routing, the weighted loss
through the chunked head, clip, dense aggregate, apply) against the plain
reference, every compared number equal to round-off; the stand-ins and two
faults of this mechanism, which the cell's own limits must call not
correct; and the cell's kernel-share reader on a hand-made trace. (The cases
every cell shares are the parametrised ones of ``test_run_cpu.py``.)"""

import io
import json
from contextlib import redirect_stdout

import pytest
from test_run_cpu import _break_session

from benchmark import calibrate, compare, run

CELL = "sdar_uncompressed"


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


def test_sdar_round_equals_the_reference_in_float32():
    result, read = _rehearse(3000000019)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    gaps = {k: v["gap"] for k, v in result["compared"].items() if k != "worst_leaf"}
    assert set(gaps) == set(compare.load_limits(CELL))
    # with a plan the harness takes the sampler's host batch, so ``feed`` 0 says
    # the session's in-graph gather and noising drew what the host path draws.
    # The losses too, which no limit names; grad_1 is read back as (p0 - p1) / lr
    # in float32, which rounds at ~1e-3 of a small leaf's norm
    assert read["feed"] == 0.0
    assert max(v for k, v in read.items() if k != "grad_1") < 1e-4, read
    assert read["grad_1"] < 3e-3, read


def test_the_cell_is_the_issues_traffic():
    cell = run.load_cell(CELL)
    argv = cell["config_file"]["argv"] + cell["traffic_file"]["argv"]
    flag = lambda name: argv[argv.index(name) + 1]  # noqa: E731
    assert (flag("--model"), flag("--mode"), flag("--num_workers"), flag("--num_clients")) == (
        "sdar_30b_a3b", "uncompressed", "2", "64")
    assert (flag("--local_batch_size"), flag("--max_seq_len"), flag("--doc_median")) == (
        "1", "8192", "2048")
    assert cell["traffic"] == "uncompressed_w2" == run.load_cell("keye_uncompressed")["traffic"]
    assert cell["traffic_file"]["reference"]["clients"] == 2 and cell["chips"] == 1
    assert (cell["config_file"]["units_per_sample"], cell["config_file"]["unit"]) == (
        8192, "token")
    names = {m["name"] for m in run.cell_metrics(cell, "per_layer")}
    assert {"model.attn_blockdiff_s_per_round", "model.attn_blockdiff_mxu_share",
            "model.diffusion_loss_s_per_round", "model.diffusion_streams_s_per_round",
            "model.mfu", "model.fwd_bwd_s_per_round"} <= names
    # Laguna's and Keye's lists, as they were
    assert not {n for n in names if "moe_" in n or "attn_window" in n or "attn_sparse" in n}
    for other in ("keye_uncompressed", "laguna_uncompressed", "gpt2_sketch"):
        theirs = {m["name"] for m in run.cell_metrics(run.load_cell(other), "per_layer")}
        assert not {n for n in theirs if "blockdiff" in n or "diffusion" in n}


def test_the_reference_imports_nothing_of_the_program():
    import os
    import re

    with open(os.path.join(run.HERE, "reference", "sdar.py")) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import)\s+commefficient_tpu", source, re.M)
    assert set(re.findall(r"^from (\S+) import", source, re.M)) <= {
        "__future__", "benchmark.reference.keye", "benchmark.reference.laguna",
        "benchmark.reference.ops"}


def test_the_stand_ins_fail_the_cells_own_limits(capsys):
    """The reference with fp8 products, and with half of every batch left
    out, put in the program's place."""
    rc = calibrate.main(["--workload", CELL, "--seeds", "11", "--controls", "1"],
                        rehearsal={"argv": ["--compute_dtype", "float32"]})
    by = {r["what"]: r for r in map(json.loads, filter(
        lambda line: line.startswith("{"), capsys.readouterr().out.splitlines()))}
    assert rc == 0 and by["program"]["correct"] is True
    assert by["control"]["correct"] is False and by["half_batch"]["correct"] is False


def _break(monkeypatch, fault):
    """One fault in the program's place, under the window's own call: the
    two every cell is held to (``test_run_cpu._break_session``) and two of
    this mechanism."""
    from commefficient_tpu.models import laguna
    from commefficient_tpu.ops.pallas import library_kernels

    if fault in ("state_unchanged", "half_batch"):
        _break_session(monkeypatch, fault)
    elif fault == "causal_mask":
        # a plain causal mask over the 2T stream: a noised query reads the
        # noised keys before it, a clean one every noised key
        monkeypatch.setattr(library_kernels, "BlockDiffusionMask",
                            lambda shape, _block_length: library_kernels._mask.CausalMask(shape))
        library_kernels._attention_kernel.cache_clear()
    elif fault == "unweighted_loss":
        weighted = laguna.weighted_cross_entropy_sum
        monkeypatch.setattr(
            laguna, "weighted_cross_entropy_sum",
            lambda logits, targets, w: weighted(logits, targets, (w > 0).astype(w.dtype)))
    else:
        raise KeyError(fault)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "causal_mask",
                                   "unweighted_loss"])
def test_a_fault_in_the_programs_place_comes_out_not_correct(monkeypatch, fault):
    from commefficient_tpu.ops.pallas import library_kernels

    _break(monkeypatch, fault)
    try:
        result, read = _rehearse(17)
    finally:
        library_kernels._attention_kernel.cache_clear()
    assert result["correct"] is False and read["feed"] == 0.0
    assert _over(result), result["compared"]
    if fault in ("causal_mask", "unweighted_loss"):
        # the mechanism's own faults move the gradient itself, not its scale alone
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
            "units_per_round": 16384,
            "config": {"n_params": 305_351_680, **cell["config_file"]["flops_kwargs"]}}


def test_the_kernel_share_and_the_scopes_on_a_hand_made_trace():
    """0.5 s under ``attn_blockdiff`` against its required operations at 197
    TFLOP/s, 0.05 s under ``diffusion_loss`` (0.03 of it the nested
    ``lm_head``), 0.001 s under ``diffusion_streams``, and the whole step's
    share. A program without the scopes (the parent, another model) reports
    none of the four."""
    from benchmark import flops_sdar, reduce

    base = "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/"
    ops = [["%cat", base + "diffusion_streams/concatenate", 0.00, 0.001],
           ["%fwd", base + "attn_blockdiff/pallas_call", 0.1, 0.5],
           ["%dot", base + "diffusion_loss/lm_head/dot_general", 0.7, 0.03],
           ["%ce", base + "diffusion_loss/reduce_sum", 0.73, 0.02],
           ["%dot", base + "attn_proj/dot_general", 0.8, 0.1]]
    ctx = _ctx(ops, hbm_bytes_per_s=819e9, flops_per_s=197e12)
    assert reduce.read_metric("model.attn_blockdiff_s_per_round", ctx) == pytest.approx(0.5)
    assert reduce.read_metric("model.diffusion_loss_s_per_round", ctx) == pytest.approx(0.05)
    assert reduce.read_metric("model.diffusion_streams_s_per_round", ctx) == pytest.approx(0.001)
    assert reduce.read_metric("model.lm_head_s_per_round", ctx) == pytest.approx(0.03)
    work = flops_sdar.attn_blockdiff_flops_per_token(**ctx["config"]) * 16384
    share = reduce.read_metric("model.attn_blockdiff_mxu_share", ctx)
    assert share == pytest.approx(100 * work / 197e12 / 0.5) and 0 < share < 100
    step = flops_sdar.sdar_flops_per_token(**ctx["config"]) * 16384
    assert reduce.read_metric("model.mfu", ctx) == pytest.approx(100 * step / 197e12 / 1.0)
    bare = _ctx([["%dot", "jit(wrapped)/vmap(client_grad)/jvp(Model)/dot_general", 0.1, 0.5]],
                hbm_bytes_per_s=819e9, flops_per_s=197e12)
    for name in ("model.attn_blockdiff_s_per_round", "model.attn_blockdiff_mxu_share",
                 "model.diffusion_loss_s_per_round", "model.diffusion_streams_s_per_round"):
        assert reduce.read_metric(name, bare) is None
