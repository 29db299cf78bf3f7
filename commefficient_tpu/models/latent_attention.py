"""Latent attention without positions (DeepSeek-V2's MLA, arXiv:2405.04434,
with ``mla_use_nope``), the mixer of ``layer_types`` ``latent_attention`` in
the decoder of ``models/laguna.py``.

With no query compression (``q_lora_rank`` null): ``q = h W_q`` ``[H, d_nope
+ d_rope]``; ``[c ; k_pe] = h W_kva`` ``[r + d_rope]``, ``c <- RMSNorm(c)``;
``[k_nope ; v] = c W_kvb`` ``[H, d_nope + d_v]``; ``k = [k_nope ; k_pe]`` with
``k_pe`` shared by every head; no rotary anywhere; causal
``softmax(q . k / sqrt(d_nope + d_rope)) v``, then ``W_o``. Training reads the
keys and values decompressed, one ``[T, d_nope + d_rope]`` key and one
``[T, d_v]`` value a head, through the library's flash kernel
(``library_kernels.banded_attention``), which takes values narrower than the
keys as they are (192 and 128 at the published widths): nothing is padded.
The absorbed form that decoding would read from the ``[T, r + d_rope]``
cache is not here.

Precision under ``mixed``: bfloat16 operands into every product with float32
accumulation, the latent's norm and the kernel's softmax statistics in
float32.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from commefficient_tpu.models.decoder_parts import RMSNorm, _dot, _Kernel
from commefficient_tpu.ops.pallas.library_kernels import banded_attention


class LatentAttention(nn.Module):
    """The MLA mixer on normed input ``h`` ``[B, T, E]`` -> ``(out, None)``;
    leaves ``q_proj``, ``kv_a_proj``, ``kv_a_norm/scale``, ``kv_b_proj``,
    ``o_proj``."""

    cfg: object
    layer: int

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        B, T, E = h.shape
        H = c.num_attention_heads_per_layer[self.layer]
        dn, dr, dv, r = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank
        w = lambda name, shape: _Kernel(shape, c.initializer_range, name=name)()  # noqa: E731
        with jax.named_scope("attn_proj"):
            q = _dot(h, w("q_proj", (E, H * (dn + dr))), c.dtype).reshape(B, T, H, dn + dr)
            latent = _dot(h, w("kv_a_proj", (E, r + dr)), c.dtype)
        with jax.named_scope("attn_qk_prep"):
            compressed = RMSNorm(c.rms_norm_eps, r, name="kv_a_norm")(latent[..., :r])
        with jax.named_scope("attn_proj"):
            kv = _dot(compressed, w("kv_b_proj", (r, H * (dn + dv))), c.dtype)
            kv = kv.reshape(B, T, H, dn + dv)
        with jax.named_scope("attn_qk_prep"):
            k_pe = jnp.broadcast_to(latent[:, :, None, r:], (B, T, H, dr))
            k = jnp.concatenate([kv[..., :dn], k_pe], -1).astype(c.dtype)
            q = (q / math.sqrt(dn + dr)).astype(c.dtype)
            v = kv[..., dn:].astype(c.dtype)
        with jax.named_scope("attn_latent"):
            o = banded_attention(q, k, v, residuals_named=c.attn_residuals_kept)
        with jax.named_scope("attn_proj"):
            return _dot(o.reshape(B, T, H * dv), w("o_proj", (H * dv, E)), c.dtype), None
