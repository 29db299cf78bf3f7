"""The round has one host loop (train/runner.py::_sync_epoch_rounds) and
one alternative schedule (asyncfed). ``--pipeline_depth`` and
``--scan_rounds`` went with the engines behind them: an old command line
is refused by argparse, and a run at defaults builds no engine object
and no staging worker."""

import inspect

import numpy as np
import pytest
from test_round import BASE, _setup

from commefficient_tpu.data import FedDataset, FedSampler
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.train import cv_train, gpt2_train, lm_train, runner
from commefficient_tpu.utils.config import Config


@pytest.mark.parametrize("flag", ["--pipeline_depth", "--scan_rounds"])
@pytest.mark.parametrize("entry", [cv_train, gpt2_train, lm_train],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_removed_flags_are_refused(entry, flag, capsys):
    with pytest.raises(SystemExit) as ei:
        entry.parse_args([flag, "2"])
    assert ei.value.code == 2  # argparse's own refusal, no shim
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err
    assert not hasattr(Config(), flag.lstrip("-"))


def test_sync_epoch_rounds_keeps_the_signature_the_benchmark_calls():
    """benchmark/run.py drives the loop by this name with these
    positional arguments and reads ``(step, lr, metrics)`` from it."""
    assert list(inspect.signature(runner._sync_epoch_rounds).parameters) == [
        "cfg", "session", "sampler", "lr_fn", "spans", "profiler", "epoch",
        "start_step", "steps_per_epoch"]
    assert inspect.isgeneratorfunction(runner._sync_epoch_rounds)


def test_default_run_builds_no_engine(monkeypatch):
    """asyncfed off constructs NOTHING: no engine, no scheduler, no
    staging worker; every round comes from the plain loop."""
    import commefficient_tpu.asyncfed as asyncfed
    from commefficient_tpu.asyncfed import staging

    def refuse(*a, **k):
        raise AssertionError("a default run built an engine object")

    monkeypatch.setattr(asyncfed, "AsyncFederation", refuse)
    monkeypatch.setattr(staging.CohortScheduler, "__init__", refuse)
    monkeypatch.setattr(staging.RoundPrefetcher, "__init__", refuse)
    calls, real = [], runner._sync_epoch_rounds

    def counted(*a, **k):
        calls.append(a[6])  # the epoch
        return real(*a, **k)

    monkeypatch.setattr(runner, "_sync_epoch_rounds", counted)
    cfg = Config(**{**BASE, "mode": "uncompressed", "num_epochs": 2,
                    "pivot_epoch": 1, "lr_scale": 0.1})
    assert not cfg.asyncfed_enabled
    ds, params, loss_fn = _setup(cfg.num_clients)
    test_ds = FedDataset({"x": ds.data["x"][:40], "y": ds.data["y"][:40]},
                         1, seed=0)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    val = cv_train.train_loop(cfg, sess, sampler, test_ds,
                              eval_batch_size=32)
    assert np.isfinite(val["loss"])
    assert calls == [0, 1]
    assert int(np.asarray(sess.state.step)) == 2 * sampler.steps_per_epoch()
