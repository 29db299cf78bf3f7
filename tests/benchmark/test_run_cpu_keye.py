"""The ``keye_uncompressed`` cell's window code on the CPU at its tiny
preset, in float32: the whole round (the learned index's selection, the
attention over it, per-client routing, the chunked head, clip, dense
aggregate, apply) against the plain reference, every compared number equal
to round-off; and the cell's kernel-share readers on a hand-made trace.
(The cases every cell shares are the parametrised ones of
``test_run_cpu.py``.)"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import compare, run


def test_keye_round_equals_the_reference_in_float32():
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "keye_uncompressed", "--seed", "3000000019",
                       "--seconds", "0.5", "--trace", "0"],
                      rehearsal={"argv": ["--compute_dtype", "float32"]})
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    gaps = {k: v["gap"] for k, v in result["compared"].items() if k != "worst_leaf"}
    assert set(gaps) == set(compare.load_limits("keye_uncompressed"))
    read = next(json.loads(ln) for ln in lines if '"info": "gaps"' in ln)["gaps"]
    # the losses too, which no limit names. grad_1 is read back as (p0 - p1) / lr in
    # float32: on the index's leaves, which move by the weight decay alone (1e-7 a step
    # against weights of 0.02), that subtraction rounds at ~1e-3 of the leaf's norm
    assert max(v for k, v in read.items() if k != "grad_1") < 1e-4, read
    assert read["grad_1"] < 3e-3, read


def test_the_cell_is_the_issues_traffic():
    cell = run.load_cell("keye_uncompressed")
    argv = cell["config_file"]["argv"] + cell["traffic_file"]["argv"]
    flag = lambda name: argv[argv.index(name) + 1]  # noqa: E731
    assert (flag("--model"), flag("--mode"), flag("--num_workers"), flag("--num_clients")) == (
        "keye_vl2", "uncompressed", "2", "64")
    assert (flag("--local_batch_size"), flag("--max_seq_len"), flag("--doc_median")) == (
        "1", "16384", "4096")
    assert cell["traffic_file"]["reference"]["clients"] == 2 and cell["chips"] == 1
    assert cell["config_file"]["units_per_sample"] == 16384
    names = {m["name"] for m in run.cell_metrics(cell, "per_layer")}
    assert {"model.attn_index_s_per_round", "model.attn_select_s_per_round",
            "model.attn_sparse_s_per_round", "model.attn_sparse_mxu_share",
            "model.attn_index_mxu_share", "model.mfu", "model.fwd_bwd_s_per_round"} <= names
    assert not {n for n in names if "moe_" in n or "attn_window" in n}   # Laguna's lists, as they were


def _ctx(ops, **peaks):
    """One device, one traced round from 0 to 1 s, holding ``ops``."""
    from benchmark import reduce

    trace = {"devices": {"0": ops}, "host": [["bench/fence", -0.1, 0.1], ["bench/fence", 0.9, 0.1]],
             "program_host": []}
    traced = reduce.summarize(trace, chips=1)
    traced.update(rounds=1)
    cell = run.load_cell("keye_uncompressed")
    return {"traced": traced, "chips": 1, "values": {}, "peaks": peaks, "cell": cell,
            "units_per_round": 32768,
            "config": {"n_params": 314_395_648, **cell["config_file"]["flops_kwargs"]}}


def test_the_kernel_shares_on_a_hand_made_trace():
    """0.5 s under ``attn_sparse`` and 0.1 s under ``attn_index`` (0.08 of
    it the nested ``attn_select``) against their required operations at 197
    TFLOP/s. A program without the scopes (the parent, another model)
    reports neither, nor the seconds."""
    from benchmark import flops_keye, reduce

    base = "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/"
    ops = [["%dot", base + "attn_index/dot_general", 0.00, 0.02],
           ["%sel", base + "attn_index/attn_select/pallas_call", 0.02, 0.08],
           ["%fwd", base + "attn_sparse/pallas_call", 0.2, 0.5],
           ["%dot", base + "attn_proj/dot_general", 0.7, 0.1]]
    ctx = _ctx(ops, hbm_bytes_per_s=819e9, flops_per_s=197e12)
    assert reduce.read_metric("model.attn_index_s_per_round", ctx) == pytest.approx(0.1)
    assert reduce.read_metric("model.attn_select_s_per_round", ctx) == pytest.approx(0.08)
    assert reduce.read_metric("model.attn_sparse_s_per_round", ctx) == pytest.approx(0.5)
    sparse = flops_keye.attn_sparse_flops_per_token(**ctx["config"]) * 32768
    assert reduce.read_metric("model.attn_sparse_mxu_share", ctx) == pytest.approx(
        100 * sparse / 197e12 / 0.5)
    index = flops_keye.attn_index_flops_per_token(**ctx["config"]) * 32768
    assert reduce.read_metric("model.attn_index_mxu_share", ctx) == pytest.approx(
        100 * index / 197e12 / 0.1)
    assert 0 < reduce.read_metric("model.attn_sparse_mxu_share", ctx) < 100
    bare = _ctx([["%dot", "jit(wrapped)/vmap(client_grad)/jvp(Model)/dot_general", 0.1, 0.5]],
                hbm_bytes_per_s=819e9, flops_per_s=197e12)
    for name in ("model.attn_index_s_per_round", "model.attn_select_s_per_round",
                 "model.attn_sparse_s_per_round", "model.attn_sparse_mxu_share",
                 "model.attn_index_mxu_share"):
        assert reduce.read_metric(name, bare) is None
