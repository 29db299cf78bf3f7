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
