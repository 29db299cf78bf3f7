"""Loss conventions.

The reference drives every model through a ``compute_loss(model, batch)``
convention (SURVEY.md §1 L1): cv workloads return (loss, #correct)
(``fed_worker.py`` eval path ~L290-340), the GPT-2 workload returns
``lm_coef * CE_lm + mc_coef * CE_mc`` (``gpt2_train.py`` ~L60-140). Here the
convention is a pure function ``loss_fn(params, batch, rng) -> (loss,
metrics_dict)`` so it sits directly under ``jax.grad`` inside the jitted
round.

``compute_dtype`` by model (ROADMAP R0): ``mixed`` is what each model's
modules make of ``dtype=bfloat16``, and it is not the same thing in each.
ResNet-9 and GPT-2 keep bfloat16 activations between layers. The Laguna
decoder (``models/laguna.py``) rounds only the operands of its products to
bfloat16 and accumulates in float32: the residual stream, RMSNorm, the
router (its product at ``Precision.HIGHEST``, scores, top-k, weights),
attention's softmax statistics, the output gate, the logits and this loss
stay float32, and each weight's gradient is rounded to bfloat16 once, where
it leaves its product. On the chip at the cell's size that read
``grad_1_diff`` 0.0201-0.0210 on each of the first eight seeds against the float32
reference, most of it top-8 boundaries that a rounded product upstream moves,
where the reference with fp8 operands reads 0.204 or more (PERF.md section
2, ``laguna_uncompressed``; my chip runs, PR 29).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100  # masked-label sentinel, same convention as the reference


def _resolve_compute_dtype(compute_dtype):
    """Loss-boundary cast dtype for the three compute modes.

    "mixed" (default) and "float32" cast NOTHING here — they differ at
    MODEL CONSTRUCTION (the flax modules' ``dtype`` field: bf16 matmuls
    for mixed, true f32 for float32; the entry points thread it via
    ``model_dtype``). "bfloat16" additionally casts params (+ inputs) at
    the loss boundary, which is what flips the parts the module dtype
    cannot reach: the GPT-2 residual stream is set f32 by the f32 wte
    GATHER and re-promoted at every residual add, keeping layernorms,
    residuals, and the tied-head [*, E] x [E, V] matmul f32 under
    "mixed" — an accuracy/memory distinction, measured SPEED-NEUTRAL at
    single-chip microbatches (r3's corrected multi-epoch twin; not
    re-measured on today's code). ResNet-9 casts
    its stream at entry, so "bfloat16" is a no-op there too."""
    if compute_dtype in (None, "mixed", "float32", jnp.float32):
        return None
    if compute_dtype in ("bfloat16", jnp.bfloat16):
        return jnp.bfloat16
    raise ValueError(
        f"compute_dtype must be mixed|float32|bfloat16, got {compute_dtype!r}"
    )


def model_dtype(compute_dtype):
    """The flax module ``dtype`` for a Config.compute_dtype value."""
    return jnp.float32 if compute_dtype == "float32" else jnp.bfloat16


def _cast_floats(tree, dtype):
    """Cast the float leaves of a pytree (params) to ``dtype``.

    Mixed-precision convention: master params stay float32 in FedState;
    the cast happens INSIDE the loss so ``jax.grad`` w.r.t. the f32 params
    flows through the cast (its transpose casts the cotangent back to
    f32). The forward/backward matmuls then run native-bf16 on the MXU
    while gradients, compression, and the server update remain f32.
    Cross-entropies compute in f32 regardless (softmax_cross_entropy_sum
    upcasts logits)."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else a,
        tree,
    )


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over positions whose label != IGNORE_INDEX.

    logits [..., V], labels [...] int. Matches
    ``torch.nn.CrossEntropyLoss(ignore_index=-100)`` semantics used by the
    GPT-2 LM head in the reference.
    """
    s, n = softmax_cross_entropy_sum(logits, labels)
    return s / jnp.maximum(n, 1.0)


def softmax_cross_entropy_sum(logits: jnp.ndarray, labels: jnp.ndarray):
    """(sum of NLL over non-ignored positions, #non-ignored positions).

    The sum/count pair lets callers weight correctly across ragged batches
    (a per-batch MEAN weighted by batch count biases the result when the
    final batch is partially padded — VERDICT r2 item 6)."""
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.where(labels == IGNORE_INDEX, 0, labels)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask), jnp.sum(mask)


def weighted_cross_entropy_sum(logits: jnp.ndarray, targets: jnp.ndarray,
                               weights: jnp.ndarray) -> jnp.ndarray:
    """``sum(weights * nll)``: each position's negative log-likelihood of its
    target under its own weight (0 leaves a position out; every ``targets``
    entry is a valid id). logits [..., V], targets and weights [...]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights)


def classification_loss(apply_fn, prep=None, compute_dtype=None):
    """Build the cv ``loss_fn``: batch = {"x": [B,H,W,C], "y": [B]}.

    Returns (mean CE, {"correct": #correct, "count": B}) — the worker eval
    path's metrics (fed_worker.py ~L290-340).

    ``prep`` maps the raw batch images on DEVICE before the model (e.g.
    ``data.cifar.device_normalizer``: uint8 -> normalized float32). Keeping
    batches uint8 until this point quarters the host->TPU transfer.

    ``compute_dtype="bfloat16"`` runs the model forward/backward in bf16
    (see ``_cast_floats``; CE and all federated algebra stay f32).
    """
    cd = _resolve_compute_dtype(compute_dtype)

    def loss_fn(params, batch, rng=None):
        x = batch["x"] if prep is None else prep(batch["x"])
        if cd is not None:
            params = _cast_floats(params, cd)
            x = x.astype(cd)
        logits = apply_fn(params, x)
        loss = softmax_cross_entropy(logits, batch["y"])
        mask = batch["y"] != IGNORE_INDEX  # padded eval rows carry -100
        correct = jnp.sum(
            (jnp.argmax(logits, -1) == batch["y"]) & mask
        ).astype(jnp.float32)
        count = jnp.sum(mask).astype(jnp.float32)
        return loss, {"correct": correct, "count": count}

    return loss_fn


def gpt2_double_heads_loss(apply_fn, lm_coef: float = 1.0, mc_coef: float = 1.0,
                           compute_dtype=None):
    """Build the GPT-2 twin loss (gpt2_train.py ~L60-140).

    batch = {"input_ids": [B,N,T], "token_type_ids": [B,N,T],
             "lm_labels": [B,N,T] (-100 masked), "mc_token_ids": [B,N],
             "mc_labels": [B]} with N candidate continuations per dialog.
    ``compute_dtype="bfloat16"``: see ``classification_loss``.
    """
    cd = _resolve_compute_dtype(compute_dtype)

    def loss_fn(params, batch, rng=None):
        if cd is not None:
            params = _cast_floats(params, cd)
        lm_logits, mc_logits = apply_fn(
            params,
            batch["input_ids"],
            token_type_ids=batch.get("token_type_ids"),
            mc_token_ids=batch["mc_token_ids"],
        )
        # next-token shift, as in the reference workload
        lm_sum, tok_count = softmax_cross_entropy_sum(
            lm_logits[..., :-1, :], batch["lm_labels"][..., 1:]
        )
        lm_loss = lm_sum / jnp.maximum(tok_count, 1.0)
        mc_loss = softmax_cross_entropy(mc_logits, batch["mc_labels"])
        loss = lm_coef * lm_loss + mc_coef * mc_loss
        mc_mask = batch["mc_labels"] != IGNORE_INDEX  # padded eval rows
        mc_correct = jnp.sum(
            (jnp.argmax(mc_logits, -1) == batch["mc_labels"]) & mc_mask
        ).astype(jnp.float32)
        count = jnp.sum(mc_mask).astype(jnp.float32)
        return loss, {
            "lm_loss": lm_loss,
            "mc_loss": mc_loss,
            "correct": mc_correct,
            "count": count,
            # token-weighted pair: exact nll under ragged final batches
            # (VERDICT r2 item 6) — evaluate() sums *_sum/*_count keys
            # instead of row-weighting them
            "lm_loss_sum": lm_sum,
            "token_count": tok_count,
        }

    return loss_fn


def causal_lm_loss(apply_fn, compute_dtype=None):
    """Build the causal-LM loss: batch = {"input_ids": [B,T], "lm_labels":
    [B,T] (-100 masked)}; mean next-token NLL over the labels kept.
    ``apply_fn(params, input_ids, lm_labels) -> ((nll sum, labels kept),
    counters)``: the model takes the labels so that it can rematerialize its
    head with the cross-entropy (``softmax_cross_entropy_sum``, shifted by
    one). The model's counters (``moe/*``) ride in the aux beside the
    token-weighted pair ``evaluate()`` sums."""
    cd = _resolve_compute_dtype(compute_dtype)

    def loss_fn(params, batch, rng=None):
        if cd is not None:
            params = _cast_floats(params, cd)
        (lm_sum, tok_count), counters = apply_fn(params, batch["input_ids"], batch["lm_labels"])
        with jax.named_scope("lm_head"):    # telemetry.trace.MODEL_SCOPES: the loss's mean
            loss = lm_sum / jnp.maximum(tok_count, 1.0)
        return loss, {"lm_loss": loss, "lm_loss_sum": lm_sum,
                      "token_count": tok_count, **counters}

    return loss_fn


def block_diffusion_lm_loss(apply_fn, compute_dtype=None):
    """Build block diffusion's loss (BD3-LMs, arXiv:2503.09573; MDLM's
    linear schedule): batch = ``causal_lm_loss``'s keys and the round's noise,
    ``noise_mask`` ``[B, T]`` bool (the positions the noised copy reads as
    ``[MASK]``) and ``noise_t`` ``[B, T]`` float32 (each position's own
    block's ``t``), as ``data.fedtext.BlockNoise`` adds them. The loss is
    ``sum_i m_i / t_i * nll_i(x_0[i]) / max(#labels kept, 1)``: no shift
    between position and target, nothing from the clean stream.
    ``apply_fn(params, input_ids, lm_labels, (noise_mask, noise_t)) ->
    ((weighted nll sum, labels kept), counters)``; the counters
    (``moe/*``, ``attn/blockdiff_pairs``, ``diffusion/*``) ride in the aux."""
    cd = _resolve_compute_dtype(compute_dtype)

    def loss_fn(params, batch, rng=None):
        if cd is not None:
            params = _cast_floats(params, cd)
        (weighted, kept), counters = apply_fn(
            params, batch["input_ids"], batch["lm_labels"],
            (batch["noise_mask"], batch["noise_t"]))
        with jax.named_scope("diffusion_loss"):
            loss = weighted / jnp.maximum(kept, 1.0)
        return loss, {"lm_loss": loss, "lm_loss_sum": weighted,
                      "token_count": kept, **counters}

    return loss_fn
