"""lm_train workload tests: the causal-LM entry end to end at ``laguna_tiny``
on the CPU, through the shared runner, session and sampler."""

import numpy as np
import pytest


def test_lm_train_e2e_uncompressed(tmp_path, capsys):
    from commefficient_tpu.train import lm_train

    val = lm_train.main(
        [], model="laguna_tiny", num_epochs=1, num_clients=8, num_workers=2,
        num_devices=2, local_batch_size=2, max_seq_len=128, mode="uncompressed",
        logdir=str(tmp_path / "runs"),
    )
    # 256 ids at random weights: nll near ln 256
    assert np.isfinite(val["nll"]) and abs(val["nll"] - np.log(256)) < 0.5
    assert val["ppl"] == pytest.approx(np.exp(val["nll"]))
    out = capsys.readouterr().out
    assert "model=laguna_tiny (V=256 of 256, L=5, E=64, experts 4 of 16)" in out


def test_unknown_model_and_dataset_are_named():
    from commefficient_tpu.train import lm_train

    for flags, word in ((["--model", "gpt2"], "laguna_xs2 | laguna_tiny"),
                        (["--dataset_name", "personachat"], "fedtext")):
        cfg = lm_train.parse_args(flags, defaults=lm_train.DEFAULTS)
        with pytest.raises(ValueError, match=word.replace("|", r"\|")):
            lm_train.build_model_and_data(cfg)


def test_moe_counters_reach_the_rounds_metrics():
    """``moe/*`` ride in the loss's aux and come out of a round summed over
    the clients, as ``lm_loss`` does; nothing is ever dropped."""
    from commefficient_tpu.train import lm_train

    cfg = lm_train.parse_args(
        ["--model", "laguna_tiny", "--max_seq_len", "128", "--num_clients", "8",
         "--num_workers", "2", "--num_devices", "1", "--mode", "uncompressed"],
        defaults=lm_train.DEFAULTS)
    train, _test, lcfg, _model, params, loss_fn = lm_train.build_model_and_data(cfg)
    session, sampler = lm_train.build_session_and_sampler(cfg, train, params, loss_fn)
    for step in range(2):
        metrics = session.train_round_indices(*sampler.sample_round_indices(step), 0.01)
        assert float(metrics["moe/dropped"]) == 0.0
        # 2 clients x 4 routed layers x 256 tokens x top-2, a quarter of the experts held
        assert 0.5 * 1024 < float(metrics["moe/held_assignments"]) < 1.5 * 1024
        assert float(metrics["moe/max_expert_load"]) >= float(metrics["moe/held_assignments"]) / 32
        assert np.isfinite(float(metrics["loss"]))


def test_sdar_tiny_trains_under_block_diffusion_and_its_loss_falls(tmp_path, capsys):
    """Two epochs of ``sdar_tiny`` on the normal path (session, the sampler's
    index path with the noise as its plan, ``_sync_epoch_rounds``): eval's
    noise is fixed, so its nll is comparable from epoch to epoch, and falls."""
    from commefficient_tpu.train import lm_train

    val = lm_train.main(
        [], model="sdar_tiny", num_epochs=2, num_clients=8, num_workers=2, num_devices=1,
        local_batch_size=2, max_seq_len=128, doc_median=40.0, mode="uncompressed",
        lr_scale=1.0, logdir=str(tmp_path / "runs"),
    )
    out = capsys.readouterr().out
    assert "model=sdar_tiny (V=256 of 256, L=2, E=64, experts 4 of 16)" in out
    header = next(ln for ln in out.splitlines() if "masked_share" in ln)
    cols = [c.strip() for c in header.split("|")]
    rows = [[float(c) for c in ln.split("|")] for ln in out.splitlines()
            if ln.strip()[:1] in "12" and ln.count("|") == header.count("|")]
    assert len(rows) == 2
    nll = [r[cols.index("val_nll")] for r in rows]
    assert nll[1] < nll[0] - 0.05 and val["nll"] == pytest.approx(nll[1], abs=1e-3)
    # fresh noise every round: half the labelled tokens masked, more or less
    assert all(0.4 < r[cols.index("masked_share")] < 0.6 for r in rows)
    assert rows[0][cols.index("dropped")] == 0.0


def test_diffusion_counters_reach_the_rounds_metrics_and_eval_is_repeatable():
    from commefficient_tpu.train import lm_train

    cfg = lm_train.parse_args(
        ["--model", "sdar_tiny", "--max_seq_len", "128", "--num_clients", "8", "--doc_median",
         "40", "--num_workers", "2", "--num_devices", "1", "--mode", "uncompressed"],
        defaults=lm_train.DEFAULTS)
    train, test, lcfg, _model, params, loss_fn = lm_train.build_model_and_data(cfg)
    assert not (train.data["input_ids"] == lcfg.mask_token).any()
    assert set(test.data) == {"input_ids", "lm_labels", "noise_mask", "noise_t"}
    assert set(train.data) == {"input_ids", "lm_labels"}          # the rounds' noise is a plan
    session, sampler = lm_train.build_session_and_sampler(cfg, train, params, loss_fn)
    assert session._dev_data is not None and sampler.augment is not None
    seen = []
    for step in range(2):
        ids, idx, plan = sampler.sample_round_indices(step)
        assert [a.shape for a in plan] == [(4, 32), (4, 128)]    # 2 clients x 2 rows
        metrics = session.train_round_indices(ids, idx, plan, 0.01)
        labelled = float(metrics["diffusion/labelled_tokens"])
        assert 0 < float(metrics["diffusion/masked_tokens"]) < labelled <= 4 * 128
        assert float(metrics["diffusion/weight_sum"]) >= float(metrics["diffusion/masked_tokens"])
        assert float(metrics["attn/blockdiff_pairs"]) == 2 * 4 * 128 * 132       # layers x rows
        seen.append(float(metrics["diffusion/masked_tokens"]))
    assert seen[0] != seen[1]
    a, b = (lm_train.evaluate_ppl(session, test, 2)["nll"] for _ in range(2))
    assert a == b and np.isfinite(a)
