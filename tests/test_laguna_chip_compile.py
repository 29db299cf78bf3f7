"""The Laguna cell's two library kernels compiled for a described v5e chip
(no chip attached, nothing runs): at the published widths, inside a
``shard_map`` that checks varying axes, under ``vmap`` over clients, forward
and backward. What interpret mode cannot show: Mosaic's own refusals
(tiling, fast memory) and the ``vma`` typing of the library's
``out_shape``s. The topology is described inside a fixture, never at import
(only one process may hold the TPU library; see the on-chip-measurement
guide), and every such test lives in this one file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from commefficient_tpu.ops.pallas import library_kernels


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1), ("workers", "model", "seq"))


@pytest.fixture()
def compiled_kernels(monkeypatch):
    # the default backend here is the CPU: take the chip's branch
    monkeypatch.setattr(library_kernels, "kernels_interpreted", lambda: False)
    library_kernels._attention_kernel.cache_clear()
    yield
    library_kernels._attention_kernel.cache_clear()


def _total(grads):
    """Every cotangent kept alive, as one number a worker."""
    return sum(jnp.sum(g.astype(jnp.float32)) for g in grads)[None]


def _compile(mesh, body, *structs, specs):
    sharded = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
               for s, spec in zip(structs, specs)]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=P("workers")))
    compiled = f.trace(*sharded).lower(lowering_platforms=("tpu",)).compile()
    return compiled.as_text()


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)])
def test_attention_compiles_at_the_published_widths(mesh, compiled_kernels, heads, window):
    T, d, kv, clients, rows = 2048, 128, 8, 2, 2

    def body(q, k, v):
        def loss(q, k, v):
            o = jax.vmap(lambda *a: library_kernels.banded_attention(*a, window=window))(q, k, v)
            return jnp.sum(o.astype(jnp.float32))

        return _total(jax.grad(loss, (0, 1, 2))(q, k, v))

    q = jax.ShapeDtypeStruct((clients, rows, T, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((clients, rows, T, kv, d), jnp.bfloat16)
    text = _compile(mesh, body, q, k, k, specs=(P("workers"),) * 3)
    assert text.count("tpu_custom_call") >= 3            # forward, dq, dkv
    assert "2048,2048" not in text                       # no [T, T] operand, per head or whole


def test_grouped_product_compiles_at_the_published_widths(mesh, compiled_kernels):
    rows, hidden, width, held = 4096, 2048, 512, 8

    def body(x, w, sizes):
        w = jax.lax.pcast(w, "workers", to="varying")

        def loss(x, w):
            y = library_kernels.grouped_product(x[0], w.astype(jnp.bfloat16), sizes[0])
            return jnp.sum(y)

        return _total(jax.grad(loss, (0, 1))(x, w))

    text = _compile(
        mesh, body, jax.ShapeDtypeStruct((1, rows, hidden), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, hidden, width), jnp.float32),
        jax.ShapeDtypeStruct((1, held), jnp.int32), specs=(P("workers"), P(), P("workers")))
    assert text.count("tpu_custom_call") >= 2            # the product and its transposes
