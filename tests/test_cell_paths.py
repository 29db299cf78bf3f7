"""What each benchmark cell measures, as a checked table.

PERF.md and ROADMAP.md say it in prose ("entry defaults everywhere", "no
cell with an opt-in speed path on"); this file holds it to the files the
driver runs. For every workload of ``BENCHMARK.json`` the cell's argv
(the configuration file's + the traffic file's + ``--telemetry_level 0``,
as ``benchmark/run.py::build`` joins them) goes through the cell's own
``benchmark/entries/<entry>.py``, which is stopped where it would build
the model: what comes back is the ``Config`` the benchmark's round is
built from. ``CELL_PATHS`` then names, choice by choice, the path the
program takes from that configuration. A PR that flips a default, adds an
opt-in to a cell or re-routes a resolver changes what a cell measures,
and has to change this table in the open to do it.

Reads the benchmark's files, edits none, builds no model.
"""

import importlib

import pytest

from benchmark.run import ROOT, load_cell, load_json
from commefficient_tpu.compress.registry import compressor_class
from commefficient_tpu.parallel.round import (
    LEAFWISE,
    AggregationPlan,
    resolve_aggregation,
    resolve_client_path,
)
from commefficient_tpu.utils.config import Config

DENSE_PLAN = AggregationPlan(
    use_sparse_agg=False, sparse_state=False, sparse_gather=False,
    sharded_decode=False, sparse_apply=False)

# opt-in paths no cell turns on: each holds the value a bare Config() has
OPT_INS = ("fuse_clients", "sketch_fused_bwd", "overlap_collectives",
           "async_double_buffer", "fedsim_enabled", "client_store")

_COMMON = {
    "round_source": "plain_loop",
    "client_path": LEAFWISE,
    "aggregation": DENSE_PLAN,
    "sketch_backend": "einsum",
    "compute_dtype": "mixed",
    "opt_ins": "defaults",
    "data_path": "device_index_round",
}

# THE table: cell -> choice -> the path taken. A sketch cell also names
# where its table is decoded.
CELL_PATHS = {
    "gpt2_sketch": {**_COMMON, "sketch_decode": "dense"},
    "gpt2_uncompressed": dict(_COMMON),
    "laguna_uncompressed": dict(_COMMON),
    "keye_uncompressed": dict(_COMMON),
    "sdar_uncompressed": dict(_COMMON),
    "kimi_linear_uncompressed": dict(_COMMON),
}


class _Parsed(Exception):
    def __init__(self, cfg):
        self.cfg = cfg


def cell_config(cell: dict, monkeypatch) -> Config:
    """The Config the cell's entry parses, with the defaults that entry
    passes: the entry runs until it asks for the model."""
    conf, traffic = cell["config_file"], cell["traffic_file"]
    argv = (list(conf["argv"]) + list(traffic["argv"])
            + ["--telemetry_level", "0"])
    train = importlib.import_module(
        f"commefficient_tpu.train.{conf['entry']}")

    def stop(cfg, *a, **k):
        raise _Parsed(cfg)

    monkeypatch.setattr(train, "build_model_and_data", stop)
    entry = importlib.import_module(f"benchmark.entries.{conf['entry']}")
    with pytest.raises(_Parsed) as ei:
        entry.build(argv, reweight=None)
    return ei.value.cfg


def resolve(cfg: Config, choice: str):
    """The program's own answer for ``choice``, at one device."""
    comp = compressor_class(cfg.mode)(cfg, 1024, spec=None)
    if choice == "round_source":
        return "asyncfed" if cfg.asyncfed_enabled else "plain_loop"
    if choice == "client_path":
        return resolve_client_path(cfg, comp)
    if choice == "aggregation":
        return resolve_aggregation(cfg, comp, 1)
    if choice == "sketch_decode":
        assert comp.supports_sharded_decode, "not a sketch cell"
        return "sharded" if comp.use_sharded_decode(1) else "dense"
    if choice in ("sketch_backend", "compute_dtype"):
        return getattr(cfg, choice)
    if choice == "opt_ins":
        base = Config()
        moved = {k: getattr(cfg, k) for k in OPT_INS
                 if getattr(cfg, k) != getattr(base, k)}
        return moved or "defaults"
    if choice == "data_path":
        # the configuration's side of FederatedSession.maybe_attach_data
        # (the sampler's and the size's sides need the data)
        dev = (cfg.device_data and not cfg.client_state_hosted
               and not cfg.fsdp)
        return "device_index_round" if dev else "host_batches"
    raise KeyError(choice)


def test_table_names_every_cell():
    cells = [w["name"] for w in load_json(ROOT, "BENCHMARK.json")["workloads"]]
    assert sorted(cells) == sorted(CELL_PATHS)
    for name in cells:
        mode = load_cell(name)["traffic_file"]["argv"]
        assert ("sketch_decode" in CELL_PATHS[name]) == ("sketch" in mode)


@pytest.mark.parametrize("name,choice", [
    (name, choice) for name, row in CELL_PATHS.items() for choice in row])
def test_cell_takes_the_path_the_table_names(name, choice, monkeypatch):
    cfg = cell_config(load_cell(name), monkeypatch)
    assert resolve(cfg, choice) == CELL_PATHS[name][choice]


@pytest.mark.parametrize("flag,choice", [
    (["--fuse_clients", "--max_grad_norm", "-1"], "opt_ins"),
    (["--overlap_collectives", "layerwise"], "opt_ins"),
    (["--client_store", "host"], "data_path"),
    (["--availability", "bernoulli", "--dropout_prob", "0.1"],
     "client_path"),
    (["--async_buffer", "8"], "round_source"),
    (["--sketch_backend", "pallas"], "sketch_backend"),
    (["--compute_dtype", "float32"], "compute_dtype"),
])
def test_table_sees_an_opt_in_added_to_a_cell(flag, choice, monkeypatch):
    """The table is only worth its lines if it moves: a copy of a cell's
    traffic with one opt-in added resolves away from the table's row."""
    cell = load_cell("gpt2_sketch")
    cell["traffic_file"] = {**cell["traffic_file"],
                            "argv": cell["traffic_file"]["argv"] + flag}
    cfg = cell_config(cell, monkeypatch)
    assert resolve(cfg, choice) != CELL_PATHS["gpt2_sketch"][choice]
