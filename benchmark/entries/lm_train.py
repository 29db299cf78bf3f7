"""Builds the round as ``train/lm_train.py::main`` builds it for a user:
the same parser defaults, builders and session wiring, in the same order."""

from __future__ import annotations


def build(argv, reweight):
    from commefficient_tpu.train import lm_train

    cfg = lm_train.parse_args(argv, defaults=lm_train.DEFAULTS)
    train, _test, _lcfg, _model, params, loss_fn = lm_train.build_model_and_data(cfg)
    session, sampler = lm_train.build_session_and_sampler(cfg, train, reweight(params), loss_fn)
    return cfg, session, sampler
