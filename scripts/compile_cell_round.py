"""Compile a benchmark cell's round at the cell's own size for a described
v5e chip (no chip attached, nothing runs) and print XLA's memory analysis:
what the TPU compiler and Mosaic refuse, and whether the round's temporaries
fit beside its state, before a chip minute is spent. Beside it, how many
times the compiled round calls each attention kernel, this repo's
(`indexed_*`) and the library's (`splash_mqa_*`): a forward kernel
(`indexed_fwd`, `splash_mqa_fwd*`) once a layer where the block's `remat`
keeps its residuals, twice where it recomputes them; and each delta-rule
kernel (`kda_fwd` twice a KDA layer, `kda_bwd` once).

    JAX_PLATFORMS=cpu python scripts/compile_cell_round.py laguna_uncompressed [--num_workers 2 ...]

Builds the host-batch round (`parallel/round.py::build_round_fn`; the index
round adds only the in-graph gather) from the cell's configuration and
traffic files, as the entry builds it, on a one-device mesh of the described
topology. The LM entry only (`benchmark/entries/lm_train.py`'s builders).
Minutes of compile on this CPU; a compile that passes is not a chip run.
"""

import os
import re
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmark import run  # noqa: E402
from commefficient_tpu.ops.pallas import (  # noqa: E402
    delta_rule, indexed_attention, library_kernels)
from commefficient_tpu.ops.param_utils import ravel_params  # noqa: E402
from commefficient_tpu.parallel.mesh import make_mesh  # noqa: E402
from commefficient_tpu.parallel.round import build_round_fn, init_state  # noqa: E402
from commefficient_tpu.train import lm_train  # noqa: E402


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    # the default backend here is the CPU: take the chip's branch
    library_kernels.kernels_interpreted = indexed_attention.kernels_interpreted = lambda: False
    delta_rule.kernels_interpreted = lambda: False
    cell = run.load_cell(sys.argv[1])
    cfg = lm_train.parse_args(
        cell["config_file"]["argv"] + cell["traffic_file"]["argv"]
        + ["--telemetry_level", "0"] + sys.argv[2:], defaults=lm_train.DEFAULTS)
    _train, _test, lcfg, _model, params, loss_fn = lm_train.build_model_and_data(cfg)
    flat, unravel = ravel_params(params)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(1, 1, 1, devices=topo.devices[:1])
    round_fn = build_round_fn(cfg, loss_fn, unravel, mesh, None, d=flat.size)
    rep, workers = NamedSharding(mesh, P()), NamedSharding(mesh, P("workers"))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: init_state(cfg, jnp.zeros(flat.size, jnp.float32), None)))
    W, B, T = cfg.num_workers, cfg.local_batch_size, cfg.max_seq_len
    batch = {k: jax.ShapeDtypeStruct((W, B, T), jnp.int32, sharding=workers)
             for k in ("input_ids", "lm_labels")}
    if lm_train.round_augment(lcfg):   # a block-diffusion preset's noise rides the batch
        batch.update(noise_mask=jax.ShapeDtypeStruct((W, B, T), jnp.bool_, sharding=workers),
                     noise_t=jax.ShapeDtypeStruct((W, B, T), jnp.float32, sharding=workers))
    t0 = time.time()
    compiled = round_fn.trace(
        state, jax.ShapeDtypeStruct((W,), jnp.int32, sharding=workers), batch,
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
    ).lower(lowering_platforms=("tpu",)).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    calls = re.findall(
        r'^\s*%?((?:indexed|splash_mqa)_\w+?)[.\d]* = .*custom_call_target="tpu_custom_call"',
        text, re.M)
    rule = re.findall(
        r'^\s*%?\w*?(kda_(?:fwd|bwd))_*[.\d]* = .*custom_call_target="tpu_custom_call"',
        text, re.M)
    print({"cell": cell["name"], "D": int(flat.size), "compile_s": round(time.time() - t0, 1),
           "kernels": text.count("tpu_custom_call"),
           "attention_calls": {name: calls.count(name) for name in sorted(set(calls))},
           "delta_rule_calls": {name: rule.count(name) for name in sorted(set(rule))},
           # a buffer with two sequence-length extents, whatever else it holds
           "square_buffers": sorted(set(re.findall(
               rf"\[[\d,]*\b(?:{T},{T}|{2 * T},{2 * T})\b[\d,]*\]", text))),
           **{k: round(getattr(m, k) / 1e9, 3) for k in (
               "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
               "generated_code_size_in_bytes")}})


if __name__ == "__main__":
    main()
