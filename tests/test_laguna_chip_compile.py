"""The Laguna cell's two library kernels, the Keye cell's four indexed-
attention kernels and the Kimi cell's two delta-rule kernels compiled for a
described v5e chip
(no chip attached, nothing runs): at the published widths, inside a
``shard_map`` that checks varying axes, under ``vmap`` over clients, forward
and backward, and how often a forward attention kernel is called under the
block's ``remat`` policy. What interpret mode cannot show: Mosaic's own refusals
(tiling, fast memory) and the ``vma`` typing of the library's
``out_shape``s. The topology is described inside a fixture, never at import
(only one process may hold the TPU library; see the on-chip-measurement
guide), and every such test lives in this one file."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from commefficient_tpu.models import kda
from commefficient_tpu.ops.pallas import delta_rule, indexed_attention, library_kernels


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
    monkeypatch.setattr(indexed_attention, "kernels_interpreted", lambda: False)
    monkeypatch.setattr(delta_rule, "kernels_interpreted", lambda: False)
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


def _kernel_calls(text):
    """Custom calls of the compiled text by the attention kernel's name,
    this repo's (``indexed_*``) and the library's (``splash_mqa_*``)."""
    calls = re.findall(
        r'^\s*%?((?:indexed|splash_mqa)_\w+?)[.\d]* = .*custom_call_target="tpu_custom_call"',
        text, re.M)
    return {name: calls.count(name) for name in set(calls)}


BLOCK_POLICY = (indexed_attention.SELECT_RESIDUAL, indexed_attention.ATTEND_RESIDUAL)
# how a block differentiates its attention: as it stands, under the policy
# ``LagunaLM`` gives every block (the forward kernel's residuals cross by
# name: one forward kernel a call site), under ``remat`` with no name saved
# (the kernel runs again in the backward pass: what the name is for)
REMATS = pytest.mark.parametrize("saved,forwards", [(None, 1), (BLOCK_POLICY, 1), ((), 2)],
                                 ids=["no_remat", "block_policy", "nothing_saved"])


def _library_attention_grads(saved, **mask):
    """Per-worker body: ``banded_attention`` under ``mask`` and its three
    gradients, the forward pass's result kept alive beside them."""
    def body(q, k, v):
        def loss(q, k, v):
            o = jax.vmap(lambda *a: library_kernels.banded_attention(*a, **mask))(q, k, v)
            return jnp.sum(o.astype(jnp.float32))

        if saved is not None:
            loss = jax.checkpoint(
                loss, policy=jax.checkpoint_policies.save_only_these_names(*saved))
        total, grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        return _total(grads) + total

    return body


def _library_calls(forwards):
    return {"splash_mqa_fwd_residuals": forwards, "splash_mqa_dq_no_residuals": 1,
            "splash_mqa_dkv_no_residuals": 1}


@REMATS
@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)])
def test_attention_compiles_at_the_published_widths(mesh, compiled_kernels, heads, window,
                                                    saved, forwards):
    """Laguna-XS.2's two kinds of layer at the cell's sizes, with the
    forward kernel's output and log-sum-exp named (``ATTEND_RESIDUAL``)."""
    T, d, kv, clients, rows = 2048, 128, 8, 2, 2
    q = jax.ShapeDtypeStruct((clients, rows, T, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((clients, rows, T, kv, d), jnp.bfloat16)
    text = _compile(mesh, _library_attention_grads(saved, window=window), q, k, k,
                    specs=(P("workers"),) * 3)
    assert _kernel_calls(text) == _library_calls(forwards)
    assert "2048,2048" not in text                       # no [T, T] operand, per head or whole


@REMATS
def test_block_diffusion_attention_compiles_at_the_published_widths(mesh, compiled_kernels,
                                                                    saved, forwards):
    """SDAR's cell: a noised and a clean copy of 8,192 tokens, 32 heads over
    4, blocks of 4; the mask's comparisons lower inside all three kernels and
    no ``[2T, 2T]`` or ``[T, T]`` operand exists."""
    T, d, heads, kv, clients, rows = 8192, 128, 32, 4, 2, 1
    q = jax.ShapeDtypeStruct((clients, rows, 2 * T, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((clients, rows, 2 * T, kv, d), jnp.bfloat16)
    text = _compile(mesh, _library_attention_grads(saved, block_length=4), q, k, k,
                    specs=(P("workers"),) * 3)
    assert _kernel_calls(text) == _library_calls(forwards)
    assert "16384,16384" not in text and "8192,8192" not in text


@pytest.mark.parametrize("rows,width,tiling", [
    (4096, 512, library_kernels.GMM_TILING),        # Laguna-XS.2's fast branch
    (36864, 768, (512, 1024, 1024)),                # Keye-VL-2.0's first tier and tile
])
def test_grouped_product_compiles_at_the_published_widths(mesh, compiled_kernels, rows, width,
                                                          tiling):
    hidden, held = 2048, 8

    def body(x, w, sizes):
        w = jax.lax.pcast(w, "workers", to="varying")

        def loss(x, w):
            y = library_kernels.grouped_product(x[0], w.astype(jnp.bfloat16), sizes[0], tiling)
            return jnp.sum(y)

        return _total(jax.grad(loss, (0, 1))(x, w))

    text = _compile(
        mesh, body, jax.ShapeDtypeStruct((1, rows, hidden), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, hidden, width), jnp.float32),
        jax.ShapeDtypeStruct((1, held), jnp.int32), specs=(P("workers"), P(), P("workers")))
    assert text.count("tpu_custom_call") >= 2            # the product and its transposes


@pytest.mark.parametrize("saved,forwards", [(None, 1), (BLOCK_POLICY, 1), (BLOCK_POLICY[:1], 2)],
                         ids=["no_remat", "block_policy", "thresholds_only"])
def test_indexed_attention_compiles_at_the_published_widths(mesh, compiled_kernels, saved,
                                                            forwards):
    """Keye-VL-2.0's attention at the cell's own sizes: two clients' rows of
    16,384 positions, 32 query heads over 4 KV heads of 128, an index of 16
    heads of 64, topk 2,048; the selection kernel holds a query block's
    ``[T, 512]`` keys in VMEM (32 MB: over Mosaic's default limit, inside
    the kernels' own). Differentiated as it stands, through ``jax.checkpoint``
    with the block's policy (the forward kernel's residuals are kept: one
    ``indexed_fwd`` a call site) and with the thresholds' name alone (the
    kernel runs again in the backward pass: what the second name is for)."""
    T, heads, kv, d, J, e, clients, rows = 16384, 32, 4, 128, 16, 64, 2, 1

    def body(q, k, v, qi, ki, w):
        def loss(q, k, v):
            o, counters = jax.vmap(lambda *a: indexed_attention.indexed_attention(
                *a, topk=2048))(q, k, v, qi, ki, w)
            return jnp.sum(o.astype(jnp.float32)), counters

        if saved is not None:
            loss = jax.checkpoint(
                loss, policy=jax.checkpoint_policies.save_only_these_names(*saved))
        (total, counters), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return _total(grads) + total + sum(jnp.sum(c) for c in counters.values())

    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct((clients, rows, T) + s, dtype)  # noqa: E731
    text = _compile(mesh, body, shape(heads, d), shape(kv, d), shape(kv, d), shape(J, e),
                    shape(e), shape(J, dtype=jnp.float32), specs=(P("workers"),) * 6)
    assert _kernel_calls(text) == {"indexed_select": 1, "indexed_fwd": forwards,
                                   "indexed_dq": 1, "indexed_dkv": 1}
    assert text.count("tpu_custom_call") == 3 + forwards
    assert "16384,16384" not in text                     # no [T, T] operand, per head or whole


# ---- Kimi Delta Attention's rule --------------------------------------------------------------

KDA_SHAPE = dict(chunk=64, T=4096, heads=8, d=128, clients=2, rows=1)


def _kda_structs():
    c = KDA_SHAPE
    x = jax.ShapeDtypeStruct((c["clients"], c["rows"], c["T"], c["heads"], c["d"]), jnp.bfloat16)
    return (x, x, x, jax.ShapeDtypeStruct(x.shape, jnp.float32),
            jax.ShapeDtypeStruct(x.shape[:-1], jnp.float32))


def _kda_grads(saved):
    """Per-worker body: the rule under ``vmap`` over clients and its five
    gradients, the forward pass's result kept alive beside them."""
    def body(*args):
        def loss(*args):
            o = jax.vmap(lambda *a: kda.delta_rule(*a, chunk=KDA_SHAPE["chunk"])[0])(*args)
            return jnp.sum(o)

        if saved is not None:
            loss = jax.checkpoint(
                loss, policy=jax.checkpoint_policies.save_only_these_names(*saved))
        total, grads = jax.value_and_grad(loss, tuple(range(5)))(*args)
        return _total(grads) + total

    return body


def _kda_calls(text):
    calls = re.findall(
        r'^\s*%?\w*?(kda_(?:fwd|bwd))_*[.\d]* = .*custom_call_target="tpu_custom_call"',
        text, re.M)
    return {name: calls.count(name) for name in set(calls)}


@pytest.mark.parametrize("saved,forwards", [(None, 1), (BLOCK_POLICY, 2), ((), 2)],
                         ids=["no_remat", "block_policy", "nothing_saved"])
def test_the_delta_rule_kernels_compile_at_the_cells_widths(mesh, compiled_kernels, saved,
                                                            forwards):
    """Kimi's cell: chunks of 64, 8 heads of 128, rows of 4,096, two clients
    under ``vmap``. Under the block's ``remat`` the forward kernel runs in the
    forward pass and again in the block's recompute (the custom VJP's
    residual is the chunks' starting states, which no policy names); the
    backward kernel once."""
    text = _compile(mesh, _kda_grads(saved), *_kda_structs(), specs=(P("workers"),) * 5)
    assert _kda_calls(text) == {"kda_fwd": forwards, "kda_bwd": 1}
    assert "4096,4096" not in text and "64,64,64" not in text


def test_the_delta_rule_kernels_multiply_float32_at_float32_precision(mesh, compiled_kernels,
                                                                     monkeypatch):
    """The Mosaic text the chip's compiler is handed: every product of two
    float32 operands (the solve, the state's products, ``P W``, their
    gradients) asks for float32 contraction, and every product of bfloat16
    operands (the pair factors, ``P U~``, ``K^^T U``) does not."""
    from jax._src.pallas.mosaic import lowering

    modules = []
    lower = lowering.lower_jaxpr_to_module

    def keep(*args, **kw):
        modules.append(lower(*args, **kw))
        return modules[-1]

    monkeypatch.setattr(lowering, "lower_jaxpr_to_module", keep)
    sharded = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, P("workers")))
               for s in _kda_structs()]
    jax.jit(jax.shard_map(_kda_grads(None), mesh=mesh, in_specs=(P("workers"),) * 5,
                          out_specs=P("workers"))).trace(*sharded).lower(
        lowering_platforms=("tpu",))
    assert len(modules) == 2                                   # kda_fwd, then kda_bwd
    heads = delta_rule._heads_per_step(KDA_SHAPE["clients"] * KDA_SHAPE["heads"])
    for module, exact in zip(modules, (15, 26)):
        products = re.findall(r"tpu\.matmul .*: vector<[\dx]+x(\w+)>, vector<[\dx]+x(\w+)>",
                              str(module))
        at_fp32 = re.findall(r"tpu\.matmul .*contract_precision<fp32>", str(module))
        f32 = [p for p in products if p == ("f32", "f32")]
        assert all(p in (("f32", "f32"), ("bf16", "bf16")) for p in products)
        assert len(at_fp32) == len(f32) == exact * heads
