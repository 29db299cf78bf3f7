"""Laguna-XS.2's decoder (poolside, ``model_type: laguna``, 33.4B-A3B), one
chip's share of it: a flax module whose whole state is the parameter tree.

Pre-norm blocks (RMSNorm) of gated grouped-query attention and a SwiGLU
feed-forward. Attention is full causal with YaRN rotary positions on half
the head, or a causal window with plain rotary positions on the whole head,
by ``layer_types``; the number of query heads changes by layer
(``num_attention_heads_per_layer``) over the same KV heads. The feed-forward
is dense on the leading layer and routed elsewhere: a sigmoid router over all
``num_experts``, the ``num_experts_per_tok`` largest scores renormalised and
scaled by ``moe_routed_scaling_factor``, one shared expert, and the routed
experts **this chip holds** (``experts_held``, ids into the published
``num_experts``). The layer routes over all experts, computes the part of
the result its own experts give, and leaves the rest out: on one chip of an
expert-parallel deployment that is the layer without its exchange
(``benchmark/reference/laguna.py`` is given the same share). The vocabulary
held (``vocab_held`` rows of embedding and head) is a smaller vocabulary.

The expert layer is dropless: nothing bounds an expert's load. One stable
sort of a client's ``tokens x top_k`` assignments by held slot puts the held
experts' rows first, grouped; the grouped product
(``ops/pallas/library_kernels.grouped_product``) visits only the tiles those
rows fill. Row movement is sized for ``FAST_ROWS_FACTOR`` times the expected
number of held assignments, and for all ``tokens x top_k`` of them on a
branch taken only when a client's router sends more (``lax.cond``; both
branches give the same numbers). A preset may name more such tiers
(``expert_row_tiers``; ``lax.switch``), the product's tile
(``expert_tiling``), and a floor (``expert_rows_floored``: the product
always runs over the first tier's rows, so that a round's time does not
follow the router's skew below it). The branch is per client, so ``vmap`` over
clients runs the layer client after client (``sequential_vmap``), and the
backward pass is written out (``custom_vjp``) so that it branches the same
way and keeps no residual of the branch not taken.

Attention never forms ``[T, T]`` scores (``library_kernels.banded_attention``).
Each block is rematerialized in the backward pass (``remat``) under one
policy, whatever its layers: saved per block are its input, the float32
residual stream ``[B, T, hidden]``, and what its kernels name. Every
attention kernel, the library's and this repo's, names its output and
log-sum-exp (``ATTEND_RESIDUAL``, ``[B, T, heads x head_dim]`` in the compute
dtype and ``[B, heads, T]`` float32): no backward pass runs a forward
attention kernel a second time. An ``indexed_attention`` layer names its
selection's thresholds too (``SELECT_RESIDUAL``, two ``[T]`` rows a sequence).
A preset whose memory cannot hold the pair from a layer's forward pass to its
backward says so (``attn_residuals_kept`` off: the library's kernels are built
without the name and the policy finds nothing of theirs to keep).

Precision under ``compute_dtype`` ``mixed`` (``dtype`` bfloat16): bfloat16
operands into every product with float32 accumulation; the residual stream,
RMSNorm, the router (its product too, at ``Precision.HIGHEST``: the top-k is
a discrete choice), softmax statistics, the output gate, the logits and the
loss in float32. ``float32``: everything in float32.

Forms the published config leaves open (the reader's choice; the public
modeling code settles them; ``assumed`` in
``benchmark/configs/laguna_xs2_fedtext.json``): the output gate is one
sigmoid per head from the normed input; router weights are the selected
sigmoid scores over their sum, times the scaling factor; ``silu``; rotary
halves ``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``; no QK norm, no
selection bias, no auxiliary router loss.

Since PR 33 this file is also the decoder other presets are made of
(``models/keye.py``): what differs between them is a field of the
configuration — a layer's attention kind (``full_attention`` /
``sliding_attention`` / ``indexed_attention``: a learned index chooses
``index_topk`` keys a query, ``ops/pallas/indexed_attention.py``),
``qk_norm`` (a per-head RMSNorm on queries and keys before the rotary),
``output_gate`` and the ``router`` form (``sigmoid_scaled_shared``: the
above; ``softmax_renormalised``: a softmax over all experts, the largest
renormalised, no scaling factor, no shared expert). The defaults are
Laguna's, whose presets build the tree and lower to the program they did
before the fields were there (``tests/test_decoder_presets.py``).

Since PR 35 a fourth attention kind, ``block_diffusion_attention``
(``models/sdar.py``), and with it another objective: the decoder reads a
stream of ``2 T`` positions, a row's noised copy then its clean one, under
block diffusion's mask (``library_kernels.BlockDiffusionMask``: no ``[2T,
2T]`` tensor either), both copies at the row's rotary positions ``0..T-1``.
``LagunaLM`` makes the noised copy from ``input_ids`` and the batch's
``noise_mask`` (``mask_token`` where it is set), joins and splits the two
streams (scope ``diffusion_streams``), and gives the loss the noised
stream's ``T`` positions through the same chunked, rematerialized head,
each position's negative log-likelihood of its *own* clean token under its
weight (scope ``diffusion_loss``): ``1 / t`` of its block where the noise
masked it, 0 elsewhere, so a round does the same work whatever was drawn.
The clean stream's last-layer output feeds nothing here. Such a layer saves
what a full-attention layer saves, over its ``2 T`` positions.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models.losses import (
    IGNORE_INDEX,
    softmax_cross_entropy_sum,
    weighted_cross_entropy_sum,
)
from commefficient_tpu.ops.pallas.indexed_attention import (
    ATTEND_RESIDUAL,
    SELECT_RESIDUAL,
    indexed_attention,
)
from commefficient_tpu.ops.pallas.library_kernels import (
    GMM_TILING,
    banded_attention,
    grouped_product,
)

FAST_ROWS_FACTOR = 4  # the fast branch moves this many times the expected held rows

# https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
PUBLISHED = dict(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=40, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=262144, rms_norm_eps=1e-6,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, moe_routed_scaling_factor=2.5,
    sliding_window=512,
    layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 10,
    num_attention_heads_per_layer=(48, 64, 64, 64) * 10,
    mlp_layer_types=("dense",) + ("sparse",) * 39,
    rope_parameters=dict(
        full_attention=dict(rope_theta=500000, rope_type="yarn", factor=64,
                            original_max_position_embeddings=4096, beta_slow=1,
                            beta_fast=64, attention_factor=1.4158883083359672,
                            partial_rotary_factor=0.5),
        sliding_attention=dict(rope_type="default", rope_theta=10000,
                               partial_rotary_factor=1),
        original_max_position_embeddings=4096),
)


@dataclass(frozen=True)
class Rope:
    """One kind of layer's rotary positions, as ``rope_parameters`` gives them."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def tables(self, T: int, head_dim: int):
        """``(cos, sin)`` ``[T, r/2]`` float32 and the rotated width ``r``."""
        r = int(head_dim * self.partial_rotary_factor)
        i = np.arange(r // 2, dtype=np.float64)
        freq = float(self.rope_theta) ** (-i / (r // 2))
        scale = 1.0
        if self.rope_type == "yarn":
            def dim(beta):
                return (r * math.log(self.original_max_position_embeddings / (2 * math.pi * beta))
                        / (2 * math.log(self.rope_theta)))

            lo = max(math.floor(dim(self.beta_fast)), 0)
            hi = min(math.ceil(dim(self.beta_slow)), r - 1)
            ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
            freq = freq / self.factor * ramp + freq * (1.0 - ramp)
            scale = self.attention_factor
        angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
        return (jnp.asarray(np.cos(angle) * scale, jnp.float32),
                jnp.asarray(np.sin(angle) * scale, jnp.float32), r)


@dataclass(frozen=True)
class LagunaConfig:
    """The published keys the layers read, plus what this chip holds."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    head_dim: int
    num_key_value_heads: int
    num_attention_heads_per_layer: Tuple[int, ...]
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    sliding_window: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    moe_routed_scaling_factor: float
    rope_full: Rope
    rope_sliding: Rope
    experts_held: Tuple[int, ...]
    vocab_held: int
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    # what other presets of this decoder set (module docstring)
    qk_norm: bool = False
    output_gate: bool = True
    router: str = "sigmoid_scaled_shared"
    index_heads: int = 0        # ``indexed_attention`` layers: the index's heads,
    index_head_dim: int = 0     # their width (one shared key head of that width),
    index_topk: int = 0         # and the keys a query attends to
    head_chunk: int = 0         # positions whose logits live at once (0: a row's, whole)
    # the library attention kernels' output and log-sum-exp cross a block's
    # ``remat`` by name (off: the forward kernel runs twice a layer; memory)
    attn_residuals_kept: bool = True
    expert_tiling: Tuple[int, int, int] = GMM_TILING   # the grouped product's tile
    # the expert layer's branches, each this many times the held rows a
    # uniform router sends (the last resort, every row, needs no entry)
    expert_row_tiers: Tuple[float, ...] = (FAST_ROWS_FACTOR,)
    expert_rows_floored: bool = False  # the product always runs over the first tier's rows
    block_length: int = 0       # ``block_diffusion_attention`` layers: positions a block,
    mask_token: int = -1        # and the id a noised position reads

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


def from_published(keys: dict, *, layers: int, experts_held, vocab_held: int,
                   **kw) -> LagunaConfig:
    """The config as published, its first ``layers`` layers, holding
    ``experts_held`` of the routed experts and the first ``vocab_held`` rows
    of the vocabulary."""
    ropes = keys["rope_parameters"]
    return LagunaConfig(
        vocab_size=keys["vocab_size"], hidden_size=keys["hidden_size"],
        intermediate_size=keys["intermediate_size"], head_dim=keys["head_dim"],
        num_key_value_heads=keys["num_key_value_heads"],
        num_attention_heads_per_layer=tuple(keys["num_attention_heads_per_layer"][:layers]),
        layer_types=tuple(keys["layer_types"][:layers]),
        mlp_layer_types=tuple(keys["mlp_layer_types"][:layers]),
        sliding_window=keys["sliding_window"], num_experts=keys["num_experts"],
        num_experts_per_tok=keys["num_experts_per_tok"],
        moe_intermediate_size=keys["moe_intermediate_size"],
        shared_expert_intermediate_size=keys["shared_expert_intermediate_size"],
        moe_routed_scaling_factor=keys["moe_routed_scaling_factor"],
        rope_full=Rope(**ropes["full_attention"]),
        rope_sliding=Rope(**ropes["sliding_attention"]),
        experts_held=tuple(experts_held), vocab_held=vocab_held,
        rms_norm_eps=keys["rms_norm_eps"], **kw)


def laguna_xs2(**kw) -> LagunaConfig:
    """One chip of 32 that share each layer: layers 0-4 of 40 (the dense
    layer, then one whole period: sliding x 3, full), experts 0-7 of 256,
    rows 0-12,543 of the 100,352-row vocabulary; every width as published.
    D = 389.6M. The attention kernels' residuals are not kept across
    ``remat``: four clients' rows of 16,384 positions x 288 heads x 128 x 2 B
    are 1.21 GB, and with them the benchmark's cell held 16,502,302,208 B of
    the chip's 16.9 GB where it holds 15,413,011,968 without (TPU v5e,
    PR 36), for 0.026 s of a 0.738 s round."""
    return from_published(PUBLISHED, layers=5, experts_held=range(8), vocab_held=12544,
                          attn_residuals_kept=False, **kw)


def laguna_tiny(**kw) -> LagunaConfig:
    """The same code path at a size the CPU tests run: the published layer
    pattern and mechanisms, every width small."""
    keys = dict(
        PUBLISHED, vocab_size=256, hidden_size=64, intermediate_size=128, head_dim=16,
        num_key_value_heads=2, num_attention_heads_per_layer=(4, 8, 8, 8, 4),
        sliding_window=8, num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32)
    return from_published(keys, layers=5, experts_held=range(4), vocab_held=256, **kw)


PRESETS = {"laguna_xs2": laguna_xs2, "laguna_tiny": laguna_tiny}


# ---- pieces -------------------------------------------------------------------

def _dot(x, w, dtype):
    """Operands in ``dtype``, float32 accumulation and result."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def _rotate(x, cos, sin, r):
    """``x`` ``[B, T, H, d]`` float32: the first ``r`` dims rotated."""
    half = r // 2
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


class RMSNorm(nn.Module):
    """The ``scale`` leaf (float32, ones); ``__call__(x)`` norms, ``scale()``
    hands the leaf out for a caller that norms inside a plain function."""

    eps: float
    width: int

    def setup(self):
        self.weight = self.param("scale", nn.initializers.ones, (self.width,), jnp.float32)

    def scale(self):
        return self.weight

    def __call__(self, x):
        return _rms(x, self.weight, self.eps)


class _Kernel(nn.Module):
    """A bias-free projection's ``kernel`` leaf, float32."""

    shape: Tuple[int, ...]
    std: float

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(self.std), self.shape, jnp.float32)


class SwiGLU(nn.Module):
    cfg: LagunaConfig
    width: int

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        w = lambda name, shape: _Kernel(shape, c.initializer_range, name=name)()  # noqa: E731
        with jax.named_scope("mlp_dense"):
            a = jax.nn.silu(_dot(h, w("gate_proj", (c.hidden_size, self.width)), c.dtype))
            a = a * _dot(h, w("up_proj", (c.hidden_size, self.width)), c.dtype)
            return _dot(a, w("down_proj", (self.width, c.hidden_size)), c.dtype)


class Attention(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        B, T, E = h.shape
        H, KV, d = c.num_attention_heads_per_layer[self.layer], c.num_key_value_heads, c.head_dim
        kind = c.layer_types[self.layer]
        sliding, diffusion = kind == "sliding_attention", kind == "block_diffusion_attention"
        w = lambda name, shape: _Kernel(shape, c.initializer_range, name=name)()  # noqa: E731
        with jax.named_scope("attn_proj"):
            q = _dot(h, w("q_proj", (E, H * d)), c.dtype).reshape(B, T, H, d)
            k = _dot(h, w("k_proj", (E, KV * d)), c.dtype).reshape(B, T, KV, d)
            v = _dot(h, w("v_proj", (E, KV * d)), c.dtype).reshape(B, T, KV, d)
        with jax.named_scope("attn_qk_prep"):
            if c.qk_norm:
                q = RMSNorm(c.rms_norm_eps, d, name="q_norm")(q)
                k = RMSNorm(c.rms_norm_eps, d, name="k_norm")(k)
            cos, sin, r = (c.rope_sliding if sliding else c.rope_full).tables(
                T // 2 if diffusion else T, d)
            if diffusion:   # the noised and the clean copy of a row sit at the row's positions
                cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))
            q = _rotate(q, cos, sin, r) / math.sqrt(d)
            k = _rotate(k, cos, sin, r)
            q, k, v = q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype)
        counters = None
        if kind == "indexed_attention":
            J, e = c.index_heads, c.index_head_dim
            with jax.named_scope("attn_index"):
                # the three projections are one leaf and one product, columns
                # [queries J x e | the shared key e | the heads' weights J].
                # S_t is a constant of the backward pass: no cotangent reaches
                # the index, whose leaf takes the weight decay's term alone
                x = jax.lax.stop_gradient(h)
                proj = _dot(x, w("index_proj", (E, J * e + e + J)), c.dtype)
                qi = proj[..., :J * e].reshape(B, T, J, e)
                ki, wi = proj[..., J * e:J * e + e], proj[..., J * e + e:]
                qi, ki = qi.astype(c.dtype), ki.astype(c.dtype)
            # the selection (attn_index/attn_select: scores and selection in
            # one kernel) and the attention (attn_sparse) open their own scopes
            o, counters = indexed_attention(q, k, v, qi, ki, wi, topk=c.index_topk)
        elif diffusion:
            with jax.named_scope("attn_blockdiff"):
                o = banded_attention(q, k, v, block_length=c.block_length,
                                     residuals_named=c.attn_residuals_kept)
            # the pairs under the mask, a head: T (T + L) a row of T tokens
            counters = {"blockdiff_pairs": jnp.float32(B * (T // 2) * (T // 2 + c.block_length))}
        else:
            with jax.named_scope("attn_window") if sliding else jax.named_scope("attn_full"):
                o = banded_attention(q, k, v, window=c.sliding_window if sliding else None,
                                     residuals_named=c.attn_residuals_kept)
        with jax.named_scope("attn_proj"):
            if c.output_gate:
                gate = jax.nn.sigmoid(_dot(h, w("g_proj", (E, H)), c.dtype))  # [B, T, H]
                o = o.astype(jnp.float32) * gate[..., None]
            return _dot(o.reshape(B, T, H * d), w("o_proj", (H * d, E)), c.dtype), counters


# ---- the routed experts ---------------------------------------------------------

def _expert_rows(tok, sizes, rows, dtype, tiling):
    """The held experts' part of the layer over the first ``rows`` sorted
    assignments, as a function of what is differentiated:
    ``(h [N, E], wrow [R], gate, up, down [G, ...]) -> [N, E]`` float32."""
    def apply(h, wrow, gate, up, down):
        t = tok[:rows]
        with jax.named_scope("moe_dispatch"):
            x = h.astype(dtype)[t]
        with jax.named_scope("moe_experts"):
            a = jax.nn.silu(grouped_product(x, gate.astype(dtype), sizes, tiling))
            a = a * grouped_product(x, up.astype(dtype), sizes, tiling)
            y = grouped_product(a.astype(dtype), down.astype(dtype), sizes, tiling)
        with jax.named_scope("moe_combine"):
            return jnp.zeros(h.shape, jnp.float32).at[t].add(y * wrow[:rows, None])

    return apply


def _tier_rows(n_tokens, top_k, held, num_experts, factor, tile=GMM_TILING[0]) -> int:
    """Rows a tier moves: ``factor`` times the held assignments a uniform
    router sends, in whole row tiles."""
    expected = n_tokens * top_k * held / num_experts
    return min(n_tokens * top_k, tile * max(1, math.ceil(factor * expected / tile)))


def make_routed_experts(tiers, dtype, tiling=GMM_TILING):
    """``(h, tok, wrow, sizes, gate, up, down) -> ([N, E], rows taken)``:
    the held experts' weighted outputs summed per token, and how many of the
    sorted assignments the branch that ran gave the product (float32, no
    cotangent). ``tok`` ``[R]`` int32 is the token of each assignment in
    sorted order (held experts' first, grouped by slot), ``wrow`` ``[R]`` its
    routing weight (0 where the expert is not held), ``sizes`` ``[G]`` the
    rows each held slot's product is given. Per client it takes the first
    branch of ``tiers`` (rows moved, ascending) that holds ``sum(sizes)``
    rows, and all ``R`` where none does."""

    def either(tok, sizes, make, *args):
        """``make(rows)(*args)`` at the rows this client needs."""
        every = tok.shape[0]
        rows = [r for r in tiers if r < every]
        if not rows:
            return make(every)(*args)
        need = jnp.sum(sizes)
        if len(rows) == 1:
            return jax.lax.cond(need <= rows[0], make(rows[0]), make(every), *args)
        return jax.lax.switch(sum((need > r).astype(jnp.int32) for r in rows),
                              [make(r) for r in rows] + [make(every)], *args)

    def forward_one(h, tok, wrow, sizes, gate, up, down):
        def taking(rows):
            apply = _expert_rows(tok, sizes, rows, dtype, tiling)
            return lambda *a: (apply(*a), jnp.float32(rows))

        # opened inside the mapped function: a loop's body is named from the
        # scopes opened inside it down
        with jax.named_scope("moe_loop"):
            return either(tok, sizes, taking, h, wrow, gate, up, down)

    def backward_one(h, tok, wrow, sizes, gate, up, down, ct):
        def pull(rows):
            return lambda *a: jax.vjp(_expert_rows(tok, sizes, rows, dtype, tiling), *a)[1](ct)

        with jax.named_scope("moe_loop"):
            return either(tok, sizes, pull, h, wrow, gate, up, down)

    forward = jax.custom_batching.sequential_vmap(forward_one)
    backward = jax.custom_batching.sequential_vmap(backward_one)

    @jax.custom_vjp
    def routed_experts(h, tok, wrow, sizes, gate, up, down):
        return forward(h, tok, wrow, sizes, gate, up, down)

    def fwd(*args):
        return forward(*args), args

    def bwd(res, ct):
        dh, dwrow, dgate, dup, ddown = backward(*res, ct[0])
        return dh, None, dwrow, None, dgate, dup, ddown

    routed_experts.defvjp(fwd, bwd)
    return routed_experts


def _floored(sizes, floor: int):
    """``sizes`` with the last slot grown until they sum to ``floor``: the
    sorted rows after the held ones are assignments held elsewhere (weight 0:
    they add nothing and take no cotangent), and with them in the last
    slot's product its time no longer follows the router's skew below
    ``floor`` rows."""
    return sizes.at[-1].add(jnp.maximum(floor - jnp.sum(sizes), 0))


class MoE(nn.Module):
    """The routed feed-forward on normed input ``h`` ``[B, T, E]``; returns
    ``(y, counters)``. ``shared`` off is for the share test alone."""

    cfg: LagunaConfig
    shared: bool = True

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        B, T, E = h.shape
        N, K, G, F = B * T, c.num_experts_per_tok, len(c.experts_held), c.moe_intermediate_size
        std = c.initializer_range
        with jax.named_scope("moe_route"):
            h = h.reshape(N, E)
            router = _Kernel((E, c.num_experts), std, name="router")()
            logits = jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST)
            if c.router == "sigmoid_scaled_shared":
                scores, scaling = jax.nn.sigmoid(logits), c.moe_routed_scaling_factor
            elif c.router == "softmax_renormalised":
                scores, scaling = jax.nn.softmax(logits, -1), 1.0
            else:
                raise ValueError(f"unknown router form {c.router!r}")
            top_s, top_e = jax.lax.top_k(scores, K)                # ties to the lower id
            weight = scaling * top_s / jnp.sum(top_s, -1, keepdims=True)
        shared = self.shared and c.router == "sigmoid_scaled_shared"
        y = SwiGLU(c, c.shared_expert_intermediate_size, name="shared")(h) if shared else 0.0
        experts = _Experts(G, E, F, std, name="experts")()
        with jax.named_scope("moe_dispatch"):
            slot_of = np.full(c.num_experts, G, np.int32)
            slot_of[list(c.experts_held)] = np.arange(G)
            slot = jnp.asarray(slot_of)[top_e].reshape(-1)         # [N K], G = held elsewhere
            order = jnp.argsort(slot, stable=True)                 # held first, by slot
            sizes = jnp.sum(slot[:, None] == jnp.arange(G)[None, :], 0, dtype=jnp.int32)
            tok = (order // K).astype(jnp.int32)
            wrow = jnp.where(slot[order] < G, weight.reshape(-1)[order], 0.0)
        tiers = [_tier_rows(N, K, G, c.num_experts, f, c.expert_tiling[0])
                 for f in c.expert_row_tiers]
        apply = make_routed_experts(tiers, c.dtype, c.expert_tiling)
        with jax.named_scope("moe_dispatch"):
            given = _floored(sizes, tiers[0]) if c.expert_rows_floored else sizes
        # the loop over clients that ``sequential_vmap`` makes of the call: its
        # own op, each client's slices and updates (the body's scopes nest)
        with jax.named_scope("moe_loop"):
            routed_y, taken = apply(h, tok, wrow, given, *experts)
        with jax.named_scope("moe_combine"):
            y = y + routed_y
        with jax.named_scope("moe_dispatch"):
            routed = jnp.sum(slot < G).astype(jnp.float32)
            counters = {
                "moe/held_assignments": routed,
                "moe/max_expert_load": jnp.max(sizes).astype(jnp.float32),
                # routed to a held expert and not among the rows the branch that
                # ran gave the product (the held ones come first in sorted order)
                "moe/dropped": routed - jnp.minimum(routed, taken),
            }
        with jax.named_scope("moe_combine"):
            return y.reshape(B, T, E), counters


class _Experts(nn.Module):
    """The held experts' three stacked matrices, leaves ``gate_proj``,
    ``up_proj`` ``[G, E, F]`` and ``down_proj`` ``[G, F, E]``."""

    held: int
    hidden: int
    width: int
    std: float

    @nn.compact
    def __call__(self):
        init = nn.initializers.normal(self.std)
        G, E, F = self.held, self.hidden, self.width
        return (self.param("gate_proj", init, (G, E, F), jnp.float32),
                self.param("up_proj", init, (G, E, F), jnp.float32),
                self.param("down_proj", init, (G, F, E), jnp.float32))


class Block(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        with jax.named_scope("block_norm"):
            h = RMSNorm(c.rms_norm_eps, c.hidden_size, name="attn_norm")(x)
        a, attended = Attention(c, self.layer, name="attn")(h)
        with jax.named_scope("residual_add"):
            x = x + a
        with jax.named_scope("block_norm"):
            h = RMSNorm(c.rms_norm_eps, c.hidden_size, name="mlp_norm")(x)
        if c.mlp_layer_types[self.layer] == "dense":
            y, counters = SwiGLU(c, c.intermediate_size, name="mlp")(h), None
        else:
            y, counters = MoE(c, name="moe")(h)
        with jax.named_scope("residual_add"):
            return x + y, counters, attended


class LagunaLM(nn.Module):
    """``input_ids`` ``[B, T]`` (ids below ``vocab_held``; ``T`` a multiple
    of 128) -> ``(logits [B, T, vocab_held] float32, counters)``. With
    ``lm_labels`` ``[B, T]`` (-100 masked) the first result is instead the
    next-token ``(nll sum, labels kept)``, the head and the cross-entropy
    rematerialized like a block: the ``[B, T, vocab_held]`` logits are not
    kept for the backward pass (with ``head_chunk`` they never exist whole:
    the head and its cross-entropy run ``head_chunk`` positions at a time).

    A block-diffusion decoder (module docstring) also takes ``noise``, the
    batch's ``(noise_mask [B, T] bool, noise_t [B, T] float32)`` (none: no
    position is noised); its logits are the noised stream's, and with
    ``lm_labels`` its first result is ``(sum of nll x weight, labels kept)``,
    with ``diffusion/*`` among the counters."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, lm_labels=None, noise=None):
        c = self.cfg
        diffusion = "block_diffusion_attention" in c.layer_types
        B, T = input_ids.shape
        ids = input_ids
        if diffusion:
            with jax.named_scope("diffusion_streams"):
                noised = input_ids if noise is None else jnp.where(
                    noise[0], jnp.asarray(c.mask_token, input_ids.dtype), input_ids)
                ids = jnp.concatenate([noised, input_ids], 1)         # [B, 2 T]
        with jax.named_scope("embed"):
            x = nn.Embed(c.vocab_held, c.hidden_size, name="embed", param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(c.initializer_range))(ids)
        # kept for the backward pass beside a block's input, where a block's
        # kernels name them: the attention kernel's own residuals (its output
        # and log-sum-exp: the recomputed forward does not run the kernel again)
        # and an indexed layer's thresholds (128 KB a sequence a layer: the
        # recomputed forward attends to the same set)
        block = nn.remat(Block, policy=jax.checkpoint_policies.save_only_these_names(
            SELECT_RESIDUAL, ATTEND_RESIDUAL))
        per_layer, attended = [], []
        for i in range(c.num_layers):
            x, counters, pairs = block(c, i, name=f"layer_{i}")(x)
            if counters is not None:
                per_layer.append(counters)
            if pairs is not None:
                attended.append(pairs)
        # over the routed layers: assignments and drops add up, the load is the worst
        reduce = {"moe/max_expert_load": jnp.max}
        totals = {k: reduce.get(k, jnp.sum)(jnp.stack([p[k] for p in per_layer]))
                  for k in (per_layer[0] if per_layer else ())}
        # over the indexed layers: the pairs attended to, of the causal ones, and
        # the queries whose threshold tied (``attn/selected_share`` is the ratio
        # of the first two, taken where the sums over clients and rounds end)
        totals.update({f"attn/{k}": jnp.sum(jnp.stack([p[k] for p in attended]))
                       for k in (attended[0] if attended else ())})
        scale = RMSNorm(c.rms_norm_eps, c.hidden_size, name="final_norm").scale()
        head = _Kernel((c.hidden_size, c.vocab_held), c.initializer_range, name="lm_head")()

        def logits(x, scale, head):
            with jax.named_scope("lm_head"):
                return _dot(_rms(x, scale, c.rms_norm_eps), head, c.dtype)

        if diffusion:
            with jax.named_scope("diffusion_streams"):
                x = x[:, :T]            # the clean stream's last output feeds nothing here
        if lm_labels is None:
            return logits(x, scale, head), totals

        if diffusion:
            if noise is None:
                raise ValueError("a block-diffusion loss needs the batch's noise")

            def weighted_nll_chunk(args):
                """One chunk's weighted sum, every position of it computed
                (weight 0 where the noise left the token), logits recomputed
                in the backward pass."""
                x, targets, w = args
                with jax.named_scope("diffusion_loss"):
                    return weighted_cross_entropy_sum(logits(x, scale, head), targets, w)

            # the weights, the chunks' reshapes and the loop's own op carry
            # the name from here; the body's ops from the scope inside it
            with jax.named_scope("diffusion_loss"):
                kept = lm_labels != IGNORE_INDEX
                masked = noise[0] & kept
                weight = jnp.where(masked, 1.0 / noise[1], 0.0)
                chunk = c.head_chunk or T
                by_chunk = lambda a: a.reshape(B, T // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731
                sums = jax.lax.map(jax.checkpoint(weighted_nll_chunk),
                                   (by_chunk(x), by_chunk(input_ids), by_chunk(weight)))
                labelled = jnp.sum(kept, dtype=jnp.float32)
                totals.update({"diffusion/masked_tokens": jnp.sum(masked, dtype=jnp.float32),
                               "diffusion/labelled_tokens": labelled,
                               "diffusion/weight_sum": jnp.sum(weight)})
                return (jnp.sum(sums), labelled), totals

        def nll(x, scale, head):
            with jax.named_scope("lm_head"):
                return softmax_cross_entropy_sum(
                    logits(x, scale, head)[..., :-1, :], lm_labels[..., 1:])

        if not c.head_chunk:
            return jax.checkpoint(nll)(x, scale, head), totals

        def nll_chunk(args):
            """One chunk's ``(nll sum, labels kept)``, its logits recomputed
            in the backward pass like the whole head's."""
            x, targets = args
            with jax.named_scope("lm_head"):
                return softmax_cross_entropy_sum(logits(x, scale, head), targets)

        # position t's target is label t + 1, and the last position has none:
        # the same sum, a chunk of positions at a time
        n = T // c.head_chunk
        with jax.named_scope("lm_head"):    # the targets, the chunks and the loop's own op
            targets = jnp.concatenate(
                [lm_labels[:, 1:], jnp.full((B, 1), IGNORE_INDEX, lm_labels.dtype)], 1)
            sums, kept = jax.lax.map(jax.checkpoint(nll_chunk), (
                x.reshape(B, n, c.head_chunk, -1).swapaxes(0, 1),
                targets.reshape(B, n, c.head_chunk).swapaxes(0, 1)))
            return (jnp.sum(sums), jnp.sum(kept)), totals
