"""Tensor-parallel GPT-2 (parallel/tensor.py): exactness vs the dense
single-device model on the virtual 8-CPU mesh — TP alone, TP x SP, and the
full 3-axis dp x tp x sp train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.models.losses import gpt2_double_heads_loss
from commefficient_tpu.parallel.mesh import make_mesh
from commefficient_tpu.parallel.tensor import (
    build_tp3d_train_step,
    tp_gpt2_apply,
    tp_shard_params,
    tp_transform_params,
    tp_untransform_params,
)

T = 64
CFG = GPT2Config(vocab_size=128, n_positions=T, n_embd=32, n_layer=2,
                 n_head=4, dtype=jnp.float32)


def _setup(seed=0, B=2, N=2):
    model = GPT2DoubleHeads(CFG)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(B, N, T)).astype(np.int32))
    tt = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(B, N, T)).astype(np.int32))
    mc = jnp.asarray(rng.integers(0, T, size=(B, N)).astype(np.int32))
    params = model.init(jax.random.key(0), ids, token_type_ids=tt, mc_token_ids=mc)
    return model, params, ids, tt, mc


def test_tp_transform_roundtrip():
    model, params, *_ = _setup()
    back = tp_untransform_params(tp_transform_params(params, CFG), CFG)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, back,
    )


@pytest.mark.parametrize(
    "axes",
    [
        # default tier keeps the MIXED case (exercises both tp and sp
        # paths); the single-axis cases are the slow tier — same code
        # paths, one axis trivial (1-core CPU suite budget, VERDICT r2
        # item 8)
        pytest.param((1, 4, 1), marks=pytest.mark.slow),
        (1, 2, 2),
        pytest.param((1, 1, 4), marks=pytest.mark.slow),
    ],
)
def test_tp_forward_matches_dense(axes):
    mesh = make_mesh(*axes)
    model, params, ids, tt, mc = _setup()
    lm_d, mc_d = model.apply(params, ids, token_type_ids=tt, mc_token_ids=mc)
    tp = tp_shard_params(mesh, params, CFG)
    lm_t, mc_t = tp_gpt2_apply(mesh, model, tp, ids, token_type_ids=tt,
                               mc_token_ids=mc)
    np.testing.assert_allclose(np.asarray(lm_t), np.asarray(lm_d), atol=3e-4)
    np.testing.assert_allclose(np.asarray(mc_t), np.asarray(mc_d), atol=3e-4)


@pytest.mark.slow  # branch variant of test_tp_forward_matches_dense
def test_tp_forward_no_mc_head():
    mesh = make_mesh(1, 2, 1)
    model, params, ids, tt, _ = _setup()
    lm_d, _ = model.apply(params, ids, token_type_ids=tt)
    tp = tp_shard_params(mesh, params, CFG)
    lm_t, mc_t = tp_gpt2_apply(mesh, model, tp, ids, token_type_ids=tt)
    assert mc_t is None
    np.testing.assert_allclose(np.asarray(lm_t), np.asarray(lm_d), atol=3e-4)


def test_tp_rejects_indivisible_sequence():
    mesh = make_mesh(1, 1, 4)
    model, params, *_ = _setup()
    ids = jnp.zeros((1, 1, T + 2), jnp.int32)
    tp = tp_shard_params(mesh, params, CFG)
    with pytest.raises(ValueError, match="divide"):
        tp_gpt2_apply(mesh, model, tp, ids)


@pytest.mark.parametrize(
    "compute_dtype",
    [
        "mixed",
        # bf16 variant pins the compute_dtype plumbing through
        # build_tp_flat_loss; precision-looser compare, slow tier
        pytest.param("bfloat16", marks=pytest.mark.slow),
    ],
)
def test_federated_tp_sp_round_matches_dp_oracle(compute_dtype):
    """VERDICT r2 item 3 'done' criterion: a workers=2 x model=2 x seq=2
    federated SKETCH round trajectory matches the DP-only oracle — the TP/SP
    axes shard each client's loss compute without changing the compression
    or server algebra."""
    from commefficient_tpu.data import FedSampler, load_fed_personachat
    from commefficient_tpu.data.fed_dataset import FedDataset
    from commefficient_tpu.models import GPT2DoubleHeads, gpt2_double_heads_loss
    from commefficient_tpu.parallel import FederatedSession, mask_gpt2
    from commefficient_tpu.parallel.tensor import build_tp_flat_loss
    from commefficient_tpu.utils.config import Config

    cfg_kw = dict(
        mode="sketch", error_type="virtual", virtual_momentum=0.9, k=200,
        num_rows=3, num_cols=10_000, num_epochs=1, num_clients=4,
        num_workers=2, num_devices=2, local_batch_size=2, max_seq_len=T,
        weight_decay=0.0, lr_scale=0.05, pivot_epoch=1, device_data=False,
    )
    train, test, real, vocab = load_fed_personachat(
        "./nonexistent", num_clients=4, num_candidates=2, max_history=2,
        max_seq_len=T, base_vocab=CFG.vocab_size - 5, seed=0,
    )
    gcfg = GPT2Config(
        vocab_size=vocab, n_positions=T, n_embd=CFG.n_embd,
        n_layer=CFG.n_layer, n_head=CFG.n_head, dtype=jnp.float32,
    )
    model = GPT2DoubleHeads(gcfg)
    sample = next(iter(FedDataset(dict(train.data), 1, seed=0).eval_batches(1)))
    params = model.init(
        jax.random.key(0),
        jnp.asarray(sample["input_ids"][:1]),
        token_type_ids=jnp.asarray(sample["token_type_ids"][:1]),
        mc_token_ids=jnp.asarray(sample["mc_token_ids"][:1]),
    )
    dense_loss = gpt2_double_heads_loss(model.apply, compute_dtype=compute_dtype)

    def run(cfg):
        if cfg.model_axis > 1 or cfg.seq_axis > 1:
            mesh = make_mesh(cfg.num_devices, cfg.model_axis, cfg.seq_axis)
            sess = FederatedSession(
                cfg, params,
                build_tp_flat_loss(gcfg, mesh, compute_dtype=compute_dtype),
                mesh=mesh,
                eval_loss_fn=dense_loss, mask_batch=mask_gpt2,
            )
        else:
            sess = FederatedSession(cfg, params, dense_loss,
                                    mask_batch=mask_gpt2)
        sampler = FedSampler(train, num_workers=2, local_batch_size=2, seed=3)
        losses = []
        for r in range(4):
            ids, batch = sampler.sample_round(r)
            m = sess.train_round(ids, batch, 0.05)
            losses.append(float(np.asarray(m["loss"])))
        return losses, np.asarray(sess.state.params_vec)

    # NB Config.compute_dtype is inert here — both sessions' precision
    # comes from the loss closures built above
    oracle_losses, oracle_params = run(Config(**cfg_kw))
    tp_losses, tp_params = run(Config(**cfg_kw, model_axis=2, seq_axis=2))
    # bf16: sharded reduction orders differ at bf16 resolution, so the
    # trajectories track rather than match; the param atol additionally
    # absorbs top-k selection-boundary flips (a coordinate extracted in
    # one path and banked in the other — measured: ~3 of 32k params, abs
    # diff < 7e-3, after 4 rounds)
    lt = (2e-4, 2e-4) if compute_dtype == "mixed" else (2e-2, 2e-2)
    # pt: (rtol, atol, flip cap) — the cap bounds a flipped coordinate's
    # magnitude and must sit ABOVE the flip-detection atol (a flip is by
    # definition a diff exceeding the atol), scaled per dtype.
    pt = (2e-3, 2e-4, 1e-2) if compute_dtype == "mixed" else (5e-2, 1e-2, 5e-2)
    np.testing.assert_allclose(tp_losses, oracle_losses, rtol=lt[0], atol=lt[1])
    # params: strict tolerance for the bulk, but a FEW isolated
    # selection-boundary flips are fp-rounding lottery, not error — the
    # rank-k boundary of the unsketch extraction flips under any
    # perturbation of summation order, and a
    # flipped coordinate differs by the full extracted value. A systematic
    # gradient error flips thousands of coordinates AND breaks the loss
    # trajectory pinned above.
    diff = np.abs(tp_params - oracle_params)
    flipped = diff > pt[1] + pt[0] * np.abs(oracle_params)
    assert int(flipped.sum()) <= 8, (
        f"{int(flipped.sum())} of {diff.size} params outside tolerance "
        f"(max abs diff {diff.max():.2e})"
    )
    assert float(diff[flipped].max(initial=0.0)) < pt[2]


@pytest.mark.parametrize(
    "axes,eval_bs",
    [
        ((2, 2, 2), 4),  # rows shard over workers (4 % 2 == 0)
        pytest.param((1, 2, 2), 3, marks=pytest.mark.slow),  # replicated rows
    ],
)
def test_tp_eval_matches_dense_eval(axes, eval_bs):
    """VERDICT r3 missing 5 'done' criterion: the model/seq-sharded eval
    path (build_tp_eval_fn) reproduces the dense jit-replicated eval's
    metrics — incl. on a ragged final batch (padded rows masked via
    _valid), so models that NEED the model axis to fit can validate."""
    from commefficient_tpu.data import load_fed_personachat
    from commefficient_tpu.ops.param_utils import ravel_params
    from commefficient_tpu.parallel import FederatedSession, mask_gpt2
    from commefficient_tpu.parallel.tensor import (
        build_tp_eval_fn,
        build_tp_flat_loss,
    )
    from commefficient_tpu.utils.config import Config

    train, test, real, vocab = load_fed_personachat(
        "./nonexistent", num_clients=4, num_candidates=2, max_history=2,
        max_seq_len=T, base_vocab=CFG.vocab_size - 5, seed=0,
    )
    gcfg = GPT2Config(
        vocab_size=vocab, n_positions=T, n_embd=CFG.n_embd,
        n_layer=CFG.n_layer, n_head=CFG.n_head, dtype=jnp.float32,
    )
    model = GPT2DoubleHeads(gcfg)
    sample = next(iter(test.eval_batches(1)))
    params = model.init(
        jax.random.key(0),
        jnp.asarray(sample["input_ids"][:1]),
        token_type_ids=jnp.asarray(sample["token_type_ids"][:1]),
        mc_token_ids=jnp.asarray(sample["mc_token_ids"][:1]),
    )
    dense_loss = gpt2_double_heads_loss(model.apply)
    cfg = Config(
        mode="uncompressed", num_epochs=1, num_clients=4,
        num_workers=axes[0], num_devices=axes[0], local_batch_size=2,
        max_seq_len=T, model_axis=axes[1], seq_axis=axes[2],
        device_data=False,
    )
    mesh = make_mesh(*axes)
    tp_sess = FederatedSession(
        cfg, params, build_tp_flat_loss(gcfg, mesh), mesh=mesh,
        eval_fn=build_tp_eval_fn(gcfg, mesh, ravel_params(params)[1]),
        mask_batch=mask_gpt2,
    )
    dense_cfg = cfg.replace(model_axis=1, seq_axis=1)
    dense_sess = FederatedSession(
        dense_cfg, params, dense_loss, mask_batch=mask_gpt2
    )
    got = tp_sess.evaluate(test.eval_batches(eval_bs))
    want = dense_sess.evaluate(test.eval_batches(eval_bs))
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-4,
                                   err_msg=k)


@pytest.mark.slow  # the federated composition below (dp oracle test) holds
# the default-tier coverage for the 3-axis step
def test_tp3d_train_step_matches_single_device_sgd():
    """One dp x tp x sp SGD step == one dense single-device SGD step."""
    mesh = make_mesh(2, 2, 2)
    model, params, ids, tt, mc = _setup(B=4)
    rng = np.random.default_rng(7)
    lm_labels = np.asarray(ids).copy()
    lm_labels[..., : T // 2] = -100  # mask a prefix, as the workload does
    batch = {
        "input_ids": ids,
        "token_type_ids": tt,
        "lm_labels": jnp.asarray(lm_labels),
        "mc_token_ids": mc,
        "mc_labels": jnp.asarray(rng.integers(0, 2, size=(4,)).astype(np.int32)),
    }
    lr = 0.1

    # oracle: dense loss -> plain SGD
    loss_fn = gpt2_double_heads_loss(model.apply)
    (loss_d, aux_d), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch
    )
    dense_new = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    tp = tp_shard_params(mesh, params, CFG)
    step = build_tp3d_train_step(mesh, model)
    new_tp, metrics = step(tp, batch, jnp.float32(lr))

    np.testing.assert_allclose(float(metrics["loss"]), float(loss_d), atol=2e-4)
    np.testing.assert_allclose(
        float(metrics["lm_loss"]), float(aux_d["lm_loss"]), atol=2e-4
    )
    back = tp_untransform_params(new_tp, CFG)
    flat_a = jax.tree.leaves(jax.tree.map(np.asarray, dense_new))
    flat_b = jax.tree.leaves(jax.tree.map(np.asarray, back))
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(b, a, atol=5e-4)
