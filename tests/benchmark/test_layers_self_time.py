"""A scope's self time (``benchmark/layers/scope_self_time.py``) and the
metrics that close ``client_grad``, on a hand-made trace
(``recorded_trace_self_time.json`` beside this file, in the plain form
``benchmark/reduce.py`` reduces) against values worked out by hand: two
traced rounds, [1.0, 6.0] and [6.0, 11.0], on two devices.
"""

import json
import os

import pytest

from benchmark import reduce, run
from benchmark.layers import scope_self_time
from commefficient_tpu.telemetry.trace import MODEL_SCOPES, ROUND_SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
ROUND_NAMES = [name for name, _ in ROUND_SCOPES]
MODEL_NAMES = [name for name, _ in MODEL_SCOPES]
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
SPARSE_LM = ["laguna_uncompressed", "keye_uncompressed", "sdar_uncompressed"]
# metric -> the cells BENCHMARK.json lists for it (none: every cell)
NEW = {
    "model.block_norm_s_per_round": SPARSE_LM,
    "model.attn_qk_prep_s_per_round": SPARSE_LM,
    "model.residual_embed_s_per_round": SPARSE_LM,
    "model.expert_loop_s_per_round": SPARSE_LM,
    "model.param_unravel_s_per_round": None,
    "model.unnamed_s_per_round": SPARSE_LM,
    "model.outside_client_grad_s_per_round": SPARSE_LM,
    "round.nameless_s_per_round": None,
}
ACCEPTED_BEFORE = 46  # per-layer metrics the benchmark had: the new ones come after


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace_self_time.json")) as f:
        return json.load(f)


def _ctx(recorded, chips):
    traced = reduce.summarize(recorded, chips=chips)
    traced.update(rounds=2)
    assert (traced["lo"], traced["hi"]) == (1.0, 11.0)
    return {"traced": traced, "chips": chips, "values": {}, "cell": {"name": "hand_made"}}


@pytest.fixture(scope="module")
def ctx(recorded):
    return _ctx(recorded, 1)


# ---- the reader ------------------------------------------------------------------

def test_a_parent_with_two_children_and_a_gap(recorded):
    """Round one of device 0: ``moe_loop``'s op runs 2.1-3.1; under it
    ``moe_dispatch`` 2.2-2.4 and ``moe_experts`` 2.5-2.8, and ``moe_combine``
    3.0-3.3, which outlasts it: what is left of the parent is 2.1-2.2,
    2.4-2.5 and 2.8-3.0. The children's own ops carry ``moe_loop`` in their
    paths too and are covered whole, the part past the parent's end with
    them."""
    ops = recorded["devices"]["0"]
    less = "moe_dispatch|moe_experts|moe_combine"
    assert scope_self_time.self_seconds(ops, 1.0, 6.0, "moe_loop", less) == pytest.approx(0.4)
    # round two: 6.3-6.8 with one child 6.4-6.7
    assert scope_self_time.self_seconds(ops, 6.0, 11.0, "moe_loop", less) == pytest.approx(0.2)
    # no child at all: the scope's whole union, 2.1-3.3
    assert scope_self_time.self_seconds(ops, 1.0, 6.0, "moe_loop", "absent") == pytest.approx(1.2)


def test_nothing_where_no_op_matches_and_nothing_untraced(ctx):
    spec = {"pattern": "attn_blockdiff", "less": "client_grad"}
    assert scope_self_time.read(ctx, spec) is None
    assert scope_self_time.read(dict(ctx, traced=None), {"pattern": "moe_loop", "less": "x"}) is None
    assert scope_self_time.read({"chips": 1}, {"pattern": "moe_loop", "less": "x"}) is None


@pytest.mark.parametrize("metric,expected", [
    # 1.2-1.4, the backward's 4.5-4.8, 6.0-6.2; the op at 0.2 is before the window
    ("model.block_norm_s_per_round", 0.35),
    ("model.attn_qk_prep_s_per_round", 0.1),
    # embed 1.1-1.2 and residual_add 2.0-2.1
    ("model.residual_embed_s_per_round", 0.1),
    ("model.param_unravel_s_per_round", 0.05),
    # 0.4 and 0.2 (the case above)
    ("model.expert_loop_s_per_round", 0.3),
    # the ops under client_grad and no model scope: 1.9-2.0, 6.2-6.3, and
    # 10.9-11.2 cut at the window's end
    ("model.unnamed_s_per_round", 0.15),
    # model scopes while nothing under client_grad runs: moe_dispatch's op lost
    # the prefix but runs under the loop's op, which kept it (0); moe_combine
    # past the loop's end, 3.1-3.3; the head's body 3.5-3.9
    ("model.outside_client_grad_s_per_round", 0.3),
    # client_grad: 1.0-3.1, 4.5-4.8, 6.0-6.8, 10.9-11.0
    ("model.fwd_bwd_s_per_round", 1.65),
    # the bare loop op around the head's body, 3.4-3.5 and 3.9-4.0, and the copy 4.3-4.5
    ("round.nameless_s_per_round", 0.2),
    ("round.unscoped_s_per_round", 0.5),
])
def test_metrics_on_one_chip(ctx, metric, expected):
    assert reduce.read_metric(metric, ctx) == pytest.approx(expected)


def test_the_step_closes_by_metrics(ctx):
    """``model.fwd_bwd`` + ``model.outside_client_grad`` = the named model
    scopes' union + ``model.unnamed``, and ``round.unscoped`` =
    ``model.outside_client_grad`` + ``round.nameless``."""
    read = lambda name: reduce.read_metric(name, ctx)  # noqa: E731
    spec = run.load_json(run.HERE, "layers", "model.outside_client_grad_s_per_round.json")
    ops = reduce.device_ops(ctx["traced"]["trace"], 1)["0"]
    named = reduce.busy_union(ops, 1.0, 11.0, spec["pattern"]) / 2
    assert named == pytest.approx(1.8)
    assert (read("model.fwd_bwd_s_per_round") + read("model.outside_client_grad_s_per_round")
            == pytest.approx(named + read("model.unnamed_s_per_round")))
    assert read("round.unscoped_s_per_round") == pytest.approx(
        read("model.outside_client_grad_s_per_round") + read("round.nameless_s_per_round"))


@pytest.mark.parametrize("metric,expected", [
    # device 0 as above, device 1: block_norm 0.4; moe_loop 2.0-2.6 less
    # 2.1-2.3; one unnamed op of 0.3; over 2 rounds x 2 chips
    ("model.block_norm_s_per_round", (0.7 + 0.4) / 4),
    ("model.expert_loop_s_per_round", (0.6 + 0.4) / 4),
    ("model.unnamed_s_per_round", (0.3 + 0.3) / 4),
    # nothing outside client_grad on device 1, no split either
    ("model.outside_client_grad_s_per_round", 0.6 / 4),
    ("model.param_unravel_s_per_round", 0.1 / 4),
])
def test_two_chips_are_averaged(recorded, metric, expected):
    assert reduce.read_metric(metric, _ctx(recorded, 2)) == pytest.approx(expected)


def test_a_program_without_the_new_scopes(ctx, recorded):
    """The parent of the PR that brought them: the scopes' own metrics are
    left out and nothing raises; the closing metrics read what is there
    (``embed`` as a whole word finds the module's own name)."""
    new = ("block_norm", "attn_qk_prep", "residual_add", "moe_loop", "param_unravel")
    old = [[n, "/".join(p for p in sc.split("/") if p not in new)
            .replace("embed/embed", "embed"), s, d]
           for n, sc, s, d in recorded["devices"]["0"]]
    parent = dict(ctx, traced=dict(ctx["traced"], trace={
        "devices": {"0": old}, "host": recorded["host"]}))
    got = reduce.per_layer([{"name": m, "unit": "s"} for m in sorted(NEW)], parent)
    assert set(got) == {"model.residual_embed_s_per_round", "model.unnamed_s_per_round",
                        "model.outside_client_grad_s_per_round", "round.nameless_s_per_round"}
    assert got["model.residual_embed_s_per_round"]["value"] == pytest.approx(0.05)
    # everything under client_grad but the named products and kernels:
    # 1.0-1.4, 1.7-3.1 less the children's 2.2-2.4, 2.5-2.8 and 3.0-3.1 (0.8),
    # 4.5-4.8, 6.0-6.8 less 6.4-6.7 (0.5), 10.9-11.0; embed 1.1-1.2 is named
    assert got["model.unnamed_s_per_round"]["value"] == pytest.approx(
        (0.4 + 0.8 + 0.3 + 0.5 + 0.1 - 0.1) / 2)


# ---- the files against BENCHMARK.json and the two lists of names -------------------

def _names_in(pattern):
    return [p.replace("\\b", "") for p in pattern.split("|")]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_is_declared_as_the_issue_says(metric):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    spec = run.load_json(run.HERE, "layers", metric + ".json")
    assert {k: entry[k] for k in ("layer", "unit", "moves", "source")} == {
        k: spec[k] for k in ("layer", "unit", "moves", "source")}
    assert (entry["unit"], entry["better"], entry["moves"], entry["source"]) == (
        "s", "lower", "round_s", "device_trace")
    assert entry["layer"] == metric.split(".")[0]
    assert entry.get("workloads") == NEW[metric]
    assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert BENCH["per_layer"].index(entry) >= ACCEPTED_BEFORE
    assert ("reader" in spec) != ("reduction" in spec) and spec["what"]
    assert set(_names_in(spec["pattern"] + "|" + spec.get("less", "client_grad"))) <= set(
        ROUND_NAMES + MODEL_NAMES)


def test_the_long_patterns_hold_every_name_of_the_lists_as_imported():
    """A scope a later PR appends to ``MODEL_SCOPES`` cannot be forgotten in
    the closing metrics; ``embed`` and ``encode`` are whole words (the module
    ``embed``; ``client_encode`` in older traces), as in
    ``round.unscoped_s_per_round.json``."""
    load = lambda m: run.load_json(run.HERE, "layers", m + ".json")  # noqa: E731
    unnamed, outside = load("model.unnamed_s_per_round"), load("model.outside_client_grad_s_per_round")
    nameless, unscoped = load("round.nameless_s_per_round"), load("round.unscoped_s_per_round")
    assert (unnamed["pattern"], outside["less"]) == ("client_grad", "client_grad")
    assert _names_in(unnamed["less"]) == MODEL_NAMES
    assert outside["pattern"] == unnamed["less"]
    assert nameless["pattern"] == unscoped["pattern"] + "|" + unnamed["less"]
    assert _names_in(nameless["pattern"]) == ROUND_NAMES + MODEL_NAMES
    for word in ("embed", "encode"):
        assert f"\\b{word}\\b" in nameless["pattern"]
        assert nameless["pattern"].count(word) == 1


@pytest.mark.parametrize("scope", MODEL_NAMES)
def test_every_model_scope_is_read_by_a_metric_of_its_own(scope):
    """No scope is opened that no per-layer metric reads (the closing
    metrics, which name every scope, do not count)."""
    closing = {"model.unnamed_s_per_round", "model.outside_client_grad_s_per_round",
               "round.nameless_s_per_round"}
    readers = []
    for m in BENCH["per_layer"]:
        spec = run.load_json(run.HERE, "layers", m["name"] + ".json")
        if m["name"] not in closing and scope in _names_in(spec.get("pattern", "")):
            readers.append(m["name"])
    assert readers, scope


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_cells_list_the_new_metrics(cell_name):
    """The three sparse-LM cells report all eight, the GPT-2 cells the two
    without a ``workloads`` list (``models/gpt2.py`` opens no model scope)."""
    listed = {m["name"] for m in run.cell_metrics(run.load_cell(cell_name), "per_layer")}
    expected = {m for m, cells in NEW.items() if cells is None or cell_name in cells}
    assert listed & set(NEW) == expected
    assert len(expected) == (8 if cell_name in SPARSE_LM else 2)
