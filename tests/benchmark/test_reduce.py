"""The trace reduction on a small recorded trace, against values worked out
by hand (see the comments; the trace is ``recorded_trace.json`` beside this
file, in the plain form ``benchmark/reduce.py`` reduces)."""

import json
import os

import pytest

from benchmark import reduce, run

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def traced():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        trace = json.load(f)
    t = reduce.summarize(trace, chips=1)
    t.update(rounds=2)
    return t


def test_window_and_busy_union(traced):
    assert (traced["lo"], traced["hi"]) == (1.0, 6.0)
    # [1.0,1.2] + [1.3,2.8] + [3.0,3.5] + [4.2,5.6]
    assert traced["busy_s"] == pytest.approx(3.6)
    assert traced["window_s"] == pytest.approx(5.0)


@pytest.mark.parametrize("metric,expected", [
    ("round.device_busy_s_per_round", 1.8),
    ("device.idle_share", 28.0),
    # 0.5 + 0.4 under server_decode_dense, over two rounds
    ("compress.decode_s_per_round", 0.45),
    # busy 3.6 less the 0.9 under the decode scopes, over two rounds
    ("model.client_encode_s_per_round", 1.35),
    # the all-reduce runs [2.0,2.8]; a fusion covers it until 2.3
    ("collectives.exposed_s_per_round", 0.25),
])
def test_per_layer_reductions(traced, metric, expected):
    ctx = {"traced": traced, "chips": 1, "values": {}}
    assert reduce.read_metric(metric, ctx) == pytest.approx(expected)


def test_mfu_divides_by_the_traces_own_window(traced):
    # 2 rounds in the trace's 5.0 s window: 1e12 operations a unit, 100 units a
    # round, one chip of 197e12 a second -> 100 * 1e14 / (2.5 * 197e12)
    ctx = {"traced": traced, "chips": 1, "values": {}, "units_per_round": 100,
           "peaks": run.load_peaks("TPU v5 lite"), "config": {},
           "cell": {"config_file": {"flops_fn": "test_reduce:_a_teraop"}}}
    assert reduce.read_metric("model.mfu", ctx) == pytest.approx(100 * 1e14 / (2.5 * 197e12))
    assert reduce.read_metric("model.mfu", dict(ctx, traced=None)) is None


def _a_teraop(**_):
    return 1e12


def test_gap_attribution(traced):
    gaps = dict(traced["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench/dispatch": 0.1, "inside_program": 0.2,
                                  "bench/run_ahead_wait": 0.7, "bench/fence": 0.4})
    assert sum(gaps.values()) == pytest.approx(traced["window_s"] - traced["busy_s"])


def test_top_device_ops(traced):
    name, seconds = traced["breakdown"]["device_ops"][0]
    assert name.startswith("%fusion.1") or name.startswith("%fusion.3")
    assert seconds == pytest.approx(1.0)


def test_absent_scope_returns_nothing(traced):
    ops = {"0": [op for op in traced["trace"]["devices"]["0"] if "decode" not in op[1]]}
    bare = dict(traced, trace={"devices": ops, "host": traced["trace"]["host"]})
    ctx = {"traced": bare, "chips": 1, "values": {}}
    assert reduce.read_metric("compress.decode_s_per_round", ctx) is None
    assert "compress.decode_s_per_round" not in reduce.per_layer(
        [{"name": "compress.decode_s_per_round", "unit": "s"}], ctx)


def test_missing_device_raises(traced):
    with pytest.raises(RuntimeError):
        reduce.summarize(traced["trace"], chips=4)


def test_unknown_device_kind_raises():
    assert run.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")


def test_interval_arithmetic():
    assert reduce.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert reduce.subtract([(0, 10)], [(1, 2), (5, 20)]) == [(0, 1), (2, 5)]
    assert reduce.total(reduce.clip([(0, 3), (5, 9)], 2, 6)) == 2
