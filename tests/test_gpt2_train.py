"""gpt2_train workload tests (BASELINE config #4, tiny-config CPU e2e)."""

import json
import os

import numpy as np
import pytest


def test_gpt2_train_e2e_uncompressed(tmp_path):
    from commefficient_tpu.train import gpt2_train

    val = gpt2_train.main(
        [],
        model="gpt2_tiny",
        num_epochs=1,
        num_clients=4,
        num_workers=2,
        num_devices=2,
        local_batch_size=2,
        max_seq_len=64,
        num_candidates=2,
        mode="uncompressed",
        checkpoint_dir=str(tmp_path / "ck"),
        logdir=str(tmp_path / "runs"),
    )
    assert np.isfinite(val["nll"]) and val["ppl"] > 0
    assert 0.0 <= val["mc_accuracy"] <= 1.0
    # save_pretrained wrote an HF-style checkpoint
    assert (tmp_path / "ck" / "config.json").exists()
    assert (tmp_path / "ck" / "flax_model.msgpack").exists()
    cfg = json.loads((tmp_path / "ck" / "config.json").read_text())
    assert cfg["vocab_size"] == 512 + 5  # base vocab + special tokens


def test_gpt2_train_e2e_sketch_trains(tmp_path):
    """Sketch mode on the GPT-2 twin-loss path: loss decreases over epochs."""
    from commefficient_tpu.train import gpt2_train
    from commefficient_tpu.utils.logging import TableLogger

    rows = []

    class Capture(TableLogger):
        def append(self, row):
            rows.append(row)
            super().append(row)

    from commefficient_tpu.data import load_fed_personachat
    from commefficient_tpu.data.sampler import FedSampler
    from commefficient_tpu.parallel import FederatedSession, mask_gpt2
    from commefficient_tpu.utils.config import Config

    cfg = Config(
        model="gpt2_tiny", dataset_name="personachat", mode="sketch",
        error_type="virtual", virtual_momentum=0.9, k=400, num_rows=3,
        num_cols=20_000, num_epochs=3, num_clients=4, num_workers=2,
        num_devices=2, local_batch_size=2, max_seq_len=64, weight_decay=0.0,
        lr_scale=0.05, pivot_epoch=1,
    )
    train, test, real, hf, gcfg, model, params, loss_fn = (
        gpt2_train.build_model_and_data(cfg)
    )
    session = FederatedSession(cfg, params, loss_fn, mask_batch=mask_gpt2)
    sampler = FedSampler(train, num_workers=2, local_batch_size=2, seed=1)
    gpt2_train.train_loop(cfg, session, sampler, test, table=Capture())
    assert len(rows) == 3
    # epoch 2 runs at peak lr (pivot_epoch=1); epoch 3's lr decays to ~0, so
    # compare while the schedule is active
    assert rows[1]["train_loss"] < rows[0]["train_loss"]
    assert np.isfinite(rows[-1]["val_ppl"])


def test_ppl_token_weighted_under_ragged_batches():
    """nll must be identical whether the val set is evaluated in one exact
    batch or in batches whose final one is ragged/padded — true only under
    token weighting (VERDICT r2 item 6: row-weighted per-batch means bias
    ppl when the tail batch is padded and rows carry unequal token counts)."""
    import dataclasses

    from commefficient_tpu.train import gpt2_train
    from commefficient_tpu.parallel import FederatedSession, mask_gpt2
    from commefficient_tpu.utils.config import Config

    cfg = Config(
        model="gpt2_tiny", dataset_name="personachat", mode="uncompressed",
        num_epochs=1, num_clients=4, num_workers=2, num_devices=2,
        local_batch_size=2, max_seq_len=64, num_candidates=2,
    )
    train, test, real, hf, gcfg, model, params, loss_fn = (
        gpt2_train.build_model_and_data(cfg)
    )
    n = len(next(iter(test.data.values())))
    # make per-row token counts strongly unequal (the synthetic stand-in's
    # rows are near-uniform, which would hide row-weighting bias): keep only
    # the last few label tokens in half the rows
    from commefficient_tpu.models.losses import IGNORE_INDEX

    lab = np.array(test.data["lm_labels"])
    lab[: n // 2, :, : lab.shape[-1] - 6] = IGNORE_INDEX
    test.data["lm_labels"] = lab
    # a batch size that does NOT divide the set => ragged padded tail
    bs = 4
    while n % bs == 0:
        bs += 1
    session = FederatedSession(cfg, params, loss_fn, mask_batch=mask_gpt2)
    ragged = gpt2_train.evaluate_ppl(session, test, bs)
    exact = gpt2_train.evaluate_ppl(session, test, n)
    assert ragged["nll"] == pytest.approx(exact["nll"], rel=1e-5)

    # Aggregation semantics pinned with a stub (at random init every token's
    # nll is ~log V, so a real model can't expose row-weighting bias): two
    # batches with unequal token counts — token weighting must yield the
    # exact totals, and differ from the row-weighted mean.
    import jax.numpy as jnp

    fake = [
        {"lm_loss": jnp.float32(1.0), "lm_loss_sum": jnp.float32(100.0),
         "token_count": jnp.float32(100.0), "loss_sum": jnp.float32(4.0)},
        {"lm_loss": jnp.float32(2.0), "lm_loss_sum": jnp.float32(20.0),
         "token_count": jnp.float32(10.0), "loss_sum": jnp.float32(2.0)},
    ]
    calls = iter(fake)
    session.eval_fn = lambda pv, b: next(calls)
    batches = [
        {"input_ids": np.zeros((4, 1)), "_valid": np.float32(4)},
        {"input_ids": np.zeros((4, 1)), "_valid": np.float32(2)},
    ]
    out = session.evaluate(batches)
    assert out["lm_loss_sum"] == pytest.approx(120.0)
    assert out["token_count"] == pytest.approx(110.0)
    token_weighted = out["lm_loss_sum"] / out["token_count"]
    row_weighted = out["lm_loss"]  # (1.0*4 + 2.0*2) / 6
    assert token_weighted == pytest.approx(120.0 / 110.0)
    assert row_weighted == pytest.approx(8.0 / 6.0)
    assert abs(token_weighted - row_weighted) > 0.1


def test_hf_gpt2_weight_mapping_roundtrip(tmp_path):
    """A torch GPT-2 state dict written to disk maps into our tree: mapped
    leaves match, and the special-token embedding rows keep fresh init."""
    torch = pytest.importorskip("torch")
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.models.hf_gpt2 import load_hf_gpt2_params

    gcfg = GPT2Config(vocab_size=101, n_positions=32, n_embd=16, n_layer=2, n_head=2)
    hf_vocab = 96  # ours = hf + 5 specials
    g = torch.Generator().manual_seed(0)
    sd = {
        "transformer.wte.weight": torch.randn(hf_vocab, 16, generator=g),
        "transformer.wpe.weight": torch.randn(32, 16, generator=g),
        "transformer.ln_f.weight": torch.randn(16, generator=g),
        "transformer.ln_f.bias": torch.randn(16, generator=g),
    }
    for i in range(2):
        p = f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = torch.randn(16, generator=g)
        sd[p + "ln_1.bias"] = torch.randn(16, generator=g)
        sd[p + "ln_2.weight"] = torch.randn(16, generator=g)
        sd[p + "ln_2.bias"] = torch.randn(16, generator=g)
        sd[p + "attn.c_attn.weight"] = torch.randn(16, 48, generator=g)
        sd[p + "attn.c_attn.bias"] = torch.randn(48, generator=g)
        sd[p + "attn.c_proj.weight"] = torch.randn(16, 16, generator=g)
        sd[p + "attn.c_proj.bias"] = torch.randn(16, generator=g)
        sd[p + "mlp.c_fc.weight"] = torch.randn(16, 64, generator=g)
        sd[p + "mlp.c_fc.bias"] = torch.randn(64, generator=g)
        sd[p + "mlp.c_proj.weight"] = torch.randn(64, 16, generator=g)
        sd[p + "mlp.c_proj.bias"] = torch.randn(16, generator=g)
    ckdir = tmp_path / "gpt2-local"
    os.makedirs(ckdir)
    torch.save(sd, ckdir / "pytorch_model.bin")

    model = GPT2DoubleHeads(gcfg)
    ids = jnp.zeros((1, 2, 8), jnp.int32)
    params = model.init(jax.random.key(0), ids, token_type_ids=ids,
                        mc_token_ids=jnp.zeros((1, 2), jnp.int32))
    fresh_wte = np.asarray(params["params"]["transformer"]["wte"]).copy()
    mapped, loaded = load_hf_gpt2_params(str(ckdir), gcfg, params, seed=0)
    assert loaded
    wte = np.asarray(mapped["params"]["transformer"]["wte"])
    np.testing.assert_allclose(wte[:hf_vocab], sd["transformer.wte.weight"].numpy(),
                               rtol=1e-6)
    np.testing.assert_allclose(wte[hf_vocab:], fresh_wte[hf_vocab:], rtol=1e-6)
    k = np.asarray(
        mapped["params"]["transformer"]["h_1"]["attn"]["c_attn"]["kernel"]
    )
    np.testing.assert_allclose(
        k, sd["transformer.h.1.attn.c_attn.weight"].numpy(), rtol=1e-6
    )
    # the mapped model still runs
    lm, mc = model.apply(mapped, ids, token_type_ids=ids,
                         mc_token_ids=jnp.zeros((1, 2), jnp.int32))
    assert np.isfinite(np.asarray(lm)).all()

    # missing checkpoint -> graceful no-op
    _, loaded2 = load_hf_gpt2_params(str(tmp_path / "nope"), gcfg, params)
    assert not loaded2
