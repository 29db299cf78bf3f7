"""The ``laguna_uncompressed`` cell's window code on the CPU at its tiny
preset, in float32: the whole round (per-client routing, the expert layer's
branch, window and full attention, clip, dense aggregate, apply) against the
plain reference, every compared number equal to round-off. (The cases every
cell shares are the parametrised ones of ``test_run_cpu.py``.)"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import compare, run


def test_laguna_round_equals_the_reference_in_float32():
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "laguna_uncompressed", "--seed", "3000000019",
                       "--seconds", "0.5", "--trace", "0"],
                      rehearsal={"argv": ["--compute_dtype", "float32"]})
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    gaps = {k: v["gap"] for k, v in result["compared"].items() if k != "worst_leaf"}
    assert set(gaps) == set(compare.load_limits("laguna_uncompressed"))
    read = next(json.loads(ln) for ln in lines if '"info": "gaps"' in ln)["gaps"]
    assert max(read.values()) < 1e-3, read        # the losses too, which no limit names


def _ctx(ops, **peaks):
    """One device, one traced round from 0 to 1 s, holding ``ops``."""
    from benchmark import reduce

    trace = {"devices": {"0": ops}, "host": [["bench/fence", -0.1, 0.1], ["bench/fence", 0.9, 0.1]],
             "program_host": []}
    traced = reduce.summarize(trace, chips=1)
    traced.update(rounds=1)
    cell = run.load_cell("laguna_uncompressed")
    return {"traced": traced, "chips": 1, "values": {}, "peaks": peaks, "cell": cell,
            "units_per_round": 16384,
            "config": {"n_params": 389_634_048, **cell["config_file"]["flops_kwargs"]}}


def test_the_kernel_shares_on_a_hand_made_trace():
    """0.01 s under ``moe_experts`` against 2.751 GB at 819 GB/s (3.36 ms):
    33.6 %; 0.1 s under ``attn_window`` against its required operations at
    197 TFLOP/s. A program without the scopes (the parent) reports neither,
    nor the seconds."""
    from benchmark import flops_laguna, reduce

    ops = [["%gmm", "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/moe_experts/pallas_call", 0.1, 0.01],
           ["%splash", "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/attn_window/pallas_call", 0.2, 0.1],
           ["%dot", "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/attn_proj/dot_general", 0.4, 0.2],
           ["%dot", "jit(wrapped)/vmap(client_grad)/jvp(LagunaLM)/mlp_dense/dot_general", 0.6, 0.3]]
    ctx = _ctx(ops, hbm_bytes_per_s=819e9, flops_per_s=197e12)
    assert reduce.read_metric("model.moe_experts_hbm_share", ctx) == pytest.approx(
        100 * 2_751_463_424 / 819e9 / 0.01)
    window = flops_laguna.attn_window_flops_per_token(**ctx["config"]) * 16384
    assert reduce.read_metric("model.attn_window_mxu_share", ctx) == pytest.approx(
        100 * window / 197e12 / 0.1)
    assert reduce.read_metric("model.attn_proj_s_per_round", ctx) == pytest.approx(0.2)
    assert reduce.read_metric("model.mlp_dense_s_per_round", ctx) == pytest.approx(0.3)
    bare = _ctx([["%dot", "jit(wrapped)/vmap(client_grad)/jvp(Model)/dot_general", 0.1, 0.5]],
                hbm_bytes_per_s=819e9, flops_per_s=197e12)
    for name in ("model.moe_experts_hbm_share", "model.attn_window_mxu_share",
                 "model.attn_proj_s_per_round", "model.mlp_dense_s_per_round"):
        assert reduce.read_metric(name, bare) is None
