"""GPT-2 with the double head (language model + multiple choice), as
published: token + position (+ token-type, through the token table)
embeddings, pre-LN blocks of causal multi-head attention and a 4x tanh-GELU
MLP, a final LayerNorm (eps 1e-5), the LM head tied to the token table, and
a linear scorer on the hidden state at each candidate's summary token.
Loss: ``lm_coef * CE(next token, -100 ignored) + mc_coef * CE(candidate)``.
Parameters arrive as a flat ``{path: array}`` dict."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ops import cross_entropy, cross_entropy_sum, matmul, out

N_HEAD = 12
LM_COEF = 1.0
MC_COEF = 1.0


def _layer_norm(p, name, x, precision):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * p[f"{name}/scale"] + p[f"{name}/bias"]
    return out(y, precision)


def _dense(p, name, x, precision):
    return out(matmul(x, p[f"{name}/kernel"], precision) + p[f"{name}/bias"], precision)


def _block(p, name, x, precision, n_head):
    B, T, E = x.shape
    hd = E // n_head
    qkv = _dense(p, f"{name}/attn/c_attn", _layer_norm(p, f"{name}/ln_1", x, precision), precision)
    q, k, v = (u.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
               for u in jnp.split(qkv, 3, axis=-1))
    scores = matmul(q, k.transpose(0, 1, 3, 2), precision) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    probs = out(jax.nn.softmax(scores, axis=-1), precision)
    mixed = out(matmul(probs, v, precision), precision).transpose(0, 2, 1, 3).reshape(B, T, E)
    x = out(x + _dense(p, f"{name}/attn/c_proj", mixed, precision), precision)
    h = _dense(p, f"{name}/mlp/c_fc", _layer_norm(p, f"{name}/ln_2", x, precision), precision)
    h = out(jax.nn.gelu(h, approximate=True), precision)
    return out(x + _dense(p, f"{name}/mlp/c_proj", h, precision), precision)


def loss(p, batch, precision="float32", n_head=N_HEAD, lm_coef=LM_COEF, mc_coef=MC_COEF):
    """One client's batch: ``input_ids``, ``token_type_ids``, ``lm_labels``
    ``[B, N, T]``, ``mc_token_ids`` ``[B, N]``, ``mc_labels`` ``[B]``."""
    ids = batch["input_ids"]
    B, N, T = ids.shape
    flat = ids.reshape(B * N, T)
    wte, wpe = p["params/transformer/wte"], p["params/transformer/wpe"]
    x = out(wte[flat] + wpe[jnp.arange(T)] + wte[batch["token_type_ids"].reshape(B * N, T)],
            precision)
    layers = sum(1 for k in p if k.endswith("/ln_1/scale"))
    for i in range(layers):
        x = _block(p, f"params/transformer/h_{i}", x, precision, n_head)
    x = _layer_norm(p, "params/transformer/ln_f", x, precision)
    lm_logits = out(matmul(x, wte.T, precision), precision).reshape(B, N, T, -1)
    lm_sum, count = cross_entropy_sum(lm_logits[..., :-1, :], batch["lm_labels"][..., 1:])
    picked = x[jnp.arange(B * N), batch["mc_token_ids"].reshape(-1)]
    mc_logits = _dense(p, "params/mc_head", picked, precision).reshape(B, N)
    return (lm_coef * lm_sum / jnp.maximum(count, 1.0)
            + mc_coef * cross_entropy(mc_logits, batch["mc_labels"]))
