"""The two kernels the Laguna decoder takes from JAX's own Pallas library
rather than writing again: block-sparse flash attention
(``splash_attention``) and the grouped matmul over row groups of uneven size
(``megablox`` ``gmm`` / ``tgmm``). Both compile for ``tpu`` and run
interpreted on ``cpu`` (``kernels_interpreted``: nothing else is accepted),
so the CPU suite drives the model through the kernels the chip compiles
(one exception, inside a ``shard_map``: ``grouped_product``).

Both are called inside the round's ``shard_map``, which checks how values
vary over the mesh, and the library builds its ``out_shape``s without saying
how (``vma`` unset), which ``pallas_call`` refuses there. For the length of
one library call, and only where the call's operand does vary over a mesh
axis, ``_library_types_as`` puts a stand-in under the name ``jax`` in the two
library modules that types every ``ShapeDtypeStruct`` they build like that
operand, and puts the name back when the call returns: outside a
``shard_map`` (every other entry, every unit test) the library is never
touched, and a library that stops building its shapes through that name is
refused by ``pallas_call`` as before, not silently mistyped. The library's
backward kernels are traced when the backward pass reaches them, long after
the call returned, so ``_typed_call`` takes the library function's ``vjp``
inside the forward rule of a ``custom_vjp`` of its own and pulls back inside
the backward rule, each under the operands' ``vma``.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as _mask,
)

from commefficient_tpu.ops.pallas.countsketch_kernels import kernels_interpreted
from commefficient_tpu.ops.pallas.indexed_attention import ATTEND_RESIDUAL

GMM_TILING = (128, 512, 512)   # rows, contraction, columns (scripts/laguna_probe.py)
ATTN_BLOCK = 512               # q and kv block of every splash kernel, forward and backward


class _TypedVarying:
    """The name ``jax`` as the library modules use it, but for
    ``ShapeDtypeStruct``, which says how the shape varies over the mesh."""

    def __init__(self, vma):
        self._vma = vma

    def __getattr__(self, name):
        return getattr(jax, name)

    def ShapeDtypeStruct(self, shape, dtype, **kw):  # noqa: N802 - the library's spelling
        kw.setdefault("vma", self._vma)
        return jax.ShapeDtypeStruct(shape, dtype, **kw)


@contextlib.contextmanager
def _library_types_as(x):
    """While open, what the two library modules trace is typed as varying
    over the mesh axes ``x`` varies over; the modules are as they were after."""
    vma = jax.typeof(x).vma
    if not vma:
        yield
        return
    modules = (_splash, _megablox.backend)
    saved = [m.jax for m in modules]
    for m in modules:
        m.jax = _TypedVarying(vma)
    try:
        yield
    finally:
        for m, was in zip(modules, saved):
            m.jax = was


def _typed_call(call):
    """``call`` (a library function with a ``custom_vjp`` of its own), with
    its forward and its backward kernels traced under the ``vma`` of the
    first operand and of the cotangent."""
    @jax.custom_vjp
    def typed(*args):
        with _library_types_as(args[0]):
            return call(*args)

    def fwd(*args):
        with _library_types_as(args[0]):
            return jax.vjp(call, *args)

    def bwd(pull, ct):
        with _library_types_as(ct):
            return pull(ct)

    typed.defvjp(fwd, bwd)
    return typed


class BlockDiffusionMask(_mask._ComputableMask):
    """Block diffusion's mask (BD3-LMs, arXiv:2503.09573) over a stream of
    ``2 T`` positions, the noised copy of a row then the clean one, blocks of
    ``block_length``: with ``b(i) = i // block_length`` a noised query ``i``
    reads the noised keys of its own block and the clean keys of strictly
    earlier blocks; a clean query reads the clean keys of its own and earlier
    blocks, and no noised key. The library evaluates ``mask_function`` on
    ``q_sequence``'s values and key positions, a tile at a time: in numpy
    where it builds the tables of the tiles to visit (a tile wholly outside
    the mask is skipped by the forward and both backward kernels), and inside
    the kernels on the tiles visited, so the mask is never an array.

    ``q_sequence`` is the library's per-query operand and need not be the
    position: here it is the query's whole rule as one number, so that the
    kernels compare and do not divide. A noised query of block ``b`` carries
    ``c = b L`` (its block's first position), a clean one ``c = -(b + 1) L``;
    a clean key ``k >= T`` is read where ``k - T < |c|``, a noised key where
    ``0 <= k - c < L``, which no ``c < 0`` meets."""

    def __init__(self, shape, block_length: int):
        if shape[0] != shape[1] or shape[0] % 2 or (shape[0] // 2) % block_length:
            raise ValueError(f"block diffusion mask: {shape} is not two rows of whole blocks "
                             f"of {block_length}")
        T, L = shape[0] // 2, block_length
        self.block_length = L

        def block_diffusion_mask_function(c, k):
            at = k - c
            return ((k >= T) & (k - T < abs(c))) | ((k < T) & (at >= 0) & (at < L))

        super().__init__(shape=shape, mask_function=block_diffusion_mask_function)
        first = np.arange(T, dtype=np.int32) // L * L
        self.q_sequence = np.concatenate([first, -(first + L)])

    def __eq__(self, other):
        if not isinstance(other, type(self)):
            return NotImplemented
        return self.shape == other.shape and self.block_length == other.block_length

    def __hash__(self):
        return hash((type(self), self.shape, self.block_length))


@functools.lru_cache(maxsize=None)
def _attention_kernel(T, group, window, blk, interpret, block_length=0, residuals_named=True):
    """One KV head's multi-query kernel over its ``group`` of query heads.
    Built once per shape, its block-mask tables as concrete arrays: the
    caller's ``custom_vjp`` closes over them, which a tracer may not be.
    Whatever the mask, with ``residuals_named`` the forward kernel's output
    and log-sum-exp carry the name ``ATTEND_RESIDUAL`` (the library's
    ``residual_checkpoint_name``)."""
    if block_length:
        head = BlockDiffusionMask((T, T), block_length)
    elif window is None:
        head = _mask.CausalMask((T, T))
    else:
        head = _mask.LocalMask((T, T), (window - 1, 0), 0)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mqa_single_device(
            _mask.MultiHeadMask([head] * group),
            block_sizes=_splash.BlockSizes(
                block_q=blk, block_kv=blk, block_kv_compute=blk,
                block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
                block_q_dq=blk, block_kv_dq=blk),
            residual_checkpoint_name=ATTEND_RESIDUAL if residuals_named else None,
            interpret=interpret)


def banded_attention(q, k, v, *, window=None, block_length=0, residuals_named=True):
    """Causal grouped-query attention that never forms ``[T, T]`` scores.

    ``q`` ``[B, T, H, d]`` (already scaled), ``k``, ``v`` ``[B, T, KV, d]``
    -> ``[B, T, H, d]``. Query head ``j`` reads KV head ``j // (H / KV)``:
    each KV head is one multi-query kernel call over its group, so K and V
    are never repeated in memory. ``window`` keeps ``t - window < s <= t``
    (``None``: all ``s <= t``); blocks wholly outside that band are skipped
    by the kernel's block mask, in the forward and both backward kernels.
    With ``block_length`` the ``T`` positions are a noised and a clean copy
    of a row of ``T / 2`` and the mask is ``BlockDiffusionMask``: not causal,
    and as little an array. Running softmax statistics are float32; ``T`` is
    a multiple of 128.

    What the forward kernel leaves its own backward kernels, the output
    ``[B, KV, group, T, d]`` in ``q``'s dtype and the log-sum-exp
    ``[B, KV, group, T]`` float32, is named ``ATTEND_RESIDUAL``
    (``ops/pallas/indexed_attention.py`` defines the name, one for every
    attention kernel's own residuals): under a ``remat`` whose policy saves
    that name the backward pass recomputes ``q``, ``k``, ``v`` and does not
    run ``splash_mqa_fwd`` a second time; under any other policy, and with no
    ``remat``, the name changes nothing. ``residuals_named`` off builds the
    kernel without the name, for a caller whose memory cannot hold the pair
    from a layer's forward pass to its backward: the same floats, the
    forward kernel twice."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    group = H // KV
    if T % 128:
        raise ValueError(f"banded_attention: T={T} is not a multiple of 128 (the kernel's lanes)")
    kernel = _attention_kernel(T, group, window, ATTN_BLOCK if T % ATTN_BLOCK == 0 else 128,
                               kernels_interpreted(), block_length, residuals_named)
    q = q.reshape(B, T, KV, group, d).transpose(0, 2, 3, 1, 4)      # [B, KV, group, T, d]
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)         # [B, KV, T, d]
    o = _typed_call(jax.vmap(jax.vmap(kernel)))(q, k, v)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, d)


def grouped_product(rows, w, sizes, tiling=GMM_TILING):
    """``rows`` ``[M, K]``, sorted so that group ``g`` owns the
    ``sizes[g]`` rows after those of the groups before it; ``w``
    ``[G, K, N]`` -> ``[M, N]`` float32: each row times its own group's
    matrix. Rows past ``sum(sizes)`` belong to no group: they come back zero
    and take no cotangent. The kernel's grid is as long as the groups' rows
    need (tiles of ``tiling[0]`` rows, a group's last tile padded), so
    its cost follows ``sum(sizes)`` and not ``M``; ``M`` is a multiple of the
    row tile. ``tiling`` is the kernel's (rows, contraction, columns) tile,
    the same in its backward kernels. Not batched: ``vmap`` reaches it
    through the caller's own rule (``models/laguna.py``).

    On ``cpu`` the kernel runs interpreted, but for one place: inside a
    ``shard_map`` Pallas's interpreter slices the kernel's scalar-prefetch
    operands (the groups' tile tables, made from ``sizes``, which vary over
    the mesh) at its own loop counters (which do not) and is refused by the
    same ``vma`` check (``hlo_interpreter.py``, JAX 0.9.0; the attention's
    tables are constants, so it is not). There, and only there, a plain
    masked einsum stands in; ``tests/test_laguna.py`` holds the two equal."""
    M = rows.shape[0]
    live = (jnp.arange(M) < jnp.sum(sizes))[:, None]
    rows = jnp.where(live, rows, 0)
    interpret = kernels_interpreted()
    if interpret and jax.typeof(sizes).vma:
        y = _grouped_product_plain(rows, w, sizes)
    else:
        tm, tk, tn = tiling
        tiling = (tm, min(tk, rows.shape[1]), min(tn, w.shape[2]))
        y = _typed_call(lambda r, m: _megablox.gmm(
            r, m, sizes, jnp.float32, tiling, None, None, False, interpret))(rows, w)
    # the kernel leaves the tiles it never visits as it found them
    return jnp.where(live, y, 0)


def _grouped_product_plain(rows, w, sizes):
    """Every group's product over all rows, masked to the group's own rows."""
    ends = jnp.cumsum(sizes)
    at = jnp.arange(rows.shape[0])[None, :]
    own = (at >= (ends - sizes)[:, None]) & (at < ends[:, None])                # [G, M]
    return jnp.einsum("gm,mk,gkn->mn", own.astype(rows.dtype), rows, w,
                      preferred_element_type=jnp.float32)
