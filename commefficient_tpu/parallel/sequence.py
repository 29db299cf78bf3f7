"""Sequence-parallel GPT-2 forward: ring attention over the ``seq`` axis.

Runs the GPT-2 backbone under ``shard_map`` with the TOKEN axis sharded
over the mesh's ``seq`` axis: embeddings/LayerNorm/MLP are position-wise
(shard-local), attention is exact ring attention
(``parallel.ring_attention``), and each shard offsets its position
embeddings by its global block start. Per-device activation memory is
O(T / seq) — the long-context capability the reference lacks (SURVEY.md §5
"Long-context: Absent"; this is the documented TPU-native extension, not
reference parity).

Integration status: this module is the STANDALONE long-context forward —
``sp_gpt2_apply`` shard_maps the backbone by itself, verified token-exact
against the dense model in tests/test_ring_attention.py. The federated
round integration landed separately in ``tensor.build_tp_flat_loss``
(which runs ring attention over ``seq`` INSIDE the round's
workers x model x seq shard_map) and is wired into gpt2_train via the
``--model_axis``/``--seq_axis`` flags (train/gpt2_train.py, the
``cfg.model_axis > 1 or cfg.seq_axis > 1`` branch), exercised by the
dp2 x tp2 x sp2 dryrun and tests/test_tensor_parallel.py. Use THIS module
for long-context inference/eval outside the round engine; use the tensor.py
loss for federated training.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from commefficient_tpu.models.gpt2 import GPT2Backbone
from commefficient_tpu.parallel.mesh import SEQ
from commefficient_tpu.parallel.ring_attention import ring_attention

P = jax.sharding.PartitionSpec


def sp_gpt2_apply(mesh, model, params, input_ids, token_type_ids=None,
                  mc_token_ids=None):
    """Sequence-parallel equivalent of ``GPT2DoubleHeads.apply``.

    input_ids/token_type_ids: [B, N, T] with T divisible by the mesh's
    ``seq`` axis size. Returns (lm_logits [B,N,T,V], mc_logits [B,N] | None)
    — same contract as the dense model.
    """
    c = model.cfg
    shape = input_ids.shape
    flat = lambda u: None if u is None else u.reshape(-1, shape[-1])
    ids, tt = flat(input_ids), flat(token_type_ids)
    backbone_params = {"params": params["params"]["transformer"]}

    def local(bp, ids_blk, tt_blk):
        me = jax.lax.axis_index(SEQ)
        t_local = ids_blk.shape[-1]
        positions = me * t_local + jnp.arange(t_local)
        backbone = GPT2Backbone(
            c, attn_fn=partial(ring_attention, axis_name=SEQ)
        )
        h, _ = backbone.apply(bp, ids_blk, tt_blk, positions=positions)
        return h

    seq_size = dict(zip(mesh.axis_names, mesh.devices.shape))[SEQ]
    if shape[-1] % seq_size != 0:
        raise ValueError(f"T={shape[-1]} must divide by seq axis {seq_size}")
    tspec = P(None, SEQ)
    h = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), tspec, tspec if tt is not None else None),
        out_specs=P(None, SEQ, None),
    )(backbone_params, ids, tt)

    wte = params["params"]["transformer"]["wte"]
    lm_logits = (h @ wte.astype(h.dtype).T).astype(jnp.float32)
    lm_logits = lm_logits.reshape(*shape, c.vocab_size)
    if mc_token_ids is None:
        return lm_logits, None
    flat_mc = mc_token_ids.reshape(-1)
    picked = h[jnp.arange(flat_mc.shape[0]), flat_mc]
    mc_p = params["params"]["mc_head"]
    score = picked.astype(c.dtype) @ mc_p["kernel"].astype(c.dtype) + mc_p[
        "bias"
    ].astype(c.dtype)
    return lm_logits, score.astype(jnp.float32).reshape(shape[:-1])
