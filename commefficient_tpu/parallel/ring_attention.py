"""Ring attention — sequence-parallel exact causal attention over the mesh.

No reference equivalent: the reference runs GPT-2 at its native <=1024-token
context on one device (SURVEY.md §5 "Long-context / sequence parallelism:
Absent") — this is the TPU-native capability extension the mesh formulation
makes natural (SURVEY.md §5 rebuild column). Design follows the public ring
attention recipe (blockwise attention + K/V rotation, arXiv:2310.01889
lineage; see PAPERS.md): sequence is sharded over the ``seq`` mesh axis;
each device keeps its Q block resident and K/V blocks rotate around the
ring via ``lax.ppermute`` (ICI neighbor exchange), with online-softmax
accumulators (running max / denominator / numerator, fp32) so the result is
EXACT dense causal attention — not an approximation — at O(T/n) activation
memory per device.

Causality over blocks: with per-device global offsets, a K/V block strictly
in the future contributes nothing (fully masked); the diagonal block applies
the triangular mask. All devices still participate in every rotation step so
the collective schedule is uniform (SPMD-safe under jit).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from commefficient_tpu.parallel.mesh import SEQ

_NEG_INF = -1e30


def _block_attn(q, k, v, q_off, k_off, *, causal: bool):
    """One Q-block x K-block pass -> (numerator [B,H,Tq,hd], row max [B,H,Tq],
    row denom [B,H,Tq]) with positions offset for causal masking."""
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    if causal:
        tq, tk = q.shape[-2], k.shape[-2]
        qpos = q_off + jnp.arange(tq)
        kpos = k_off + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would pollute the denom
    p = jnp.where(m[..., None] <= _NEG_INF / 2, 0.0, p)
    num = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    den = jnp.sum(p, axis=-1)
    return num, m, den


def ring_attention(q, k, v, *, axis_name: str = SEQ, causal: bool = True):
    """Exact (causal) attention with q/k/v sharded on T over ``axis_name``.

    Must be called INSIDE shard_map/pmap over ``axis_name``; q/k/v are the
    local blocks [B, H, T_local, hd]. Returns the local output block.
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    t_local = q.shape[-2]
    q_off = me * t_local

    def step(carry, t):
        kv, acc, m_run, den_run = carry
        k_blk, v_blk = kv
        src = (me - t) % n  # whose K/V block we hold at this step
        num, m_blk, den_blk = _block_attn(
            q, k_blk, v_blk, q_off, src * t_local, causal=causal
        )
        m_new = jnp.maximum(m_run, m_blk)
        scale_old = jnp.exp(m_run - m_new)
        scale_blk = jnp.exp(m_blk - m_new)
        acc = acc * scale_old[..., None] + num * scale_blk[..., None]
        den = den_run * scale_old + den_blk * scale_blk
        # rotate K/V one hop around the ring (ICI neighbor exchange)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        return (kv, acc, m_new, den), ()

    # accumulator inits derive from q (not literals) so they inherit q's
    # FULL vma type — varying over ``axis_name`` and, when heads are also
    # tensor-sharded (parallel/tensor.py), over ``model`` — keeping the
    # scan carry types consistent with the body's outputs
    q0 = q.astype(jnp.float32) * 0.0  # [B, H, T_local, hd]
    init = (
        (k, v),
        q0,
        q0[..., 0] + _NEG_INF,
        q0[..., 0],
    )
    (kv, acc, m_run, den), _ = jax.lax.scan(step, init, jnp.arange(n))
    out = acc / jnp.maximum(den[..., None], 1e-30)
    return out.astype(v.dtype)


def ring_attention_sharded(mesh, q, k, v, *, causal: bool = True):
    """Standalone entry: full [B, H, T, hd] arrays in, ring-computed out.

    Shards T over the mesh's ``seq`` axis (T must divide evenly), runs
    ``ring_attention`` under shard_map, and reassembles. For use inside a
    model, pass ``partial(ring_attention, axis_name=SEQ)`` as the GPT-2
    ``attn_fn`` and run the model itself under shard_map (see
    models/gpt2.py ``attn_fn`` hook).
    """
    P = jax.sharding.PartitionSpec
    spec = P(None, None, SEQ, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=SEQ, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
