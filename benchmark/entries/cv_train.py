"""Builds the round as ``train/cv_train.py`` builds it for a user."""

from __future__ import annotations


def build(argv, reweight):
    """``argv`` -> ``(cfg, session, sampler)``; ``reweight`` swaps the entry's
    own initial weights for the benchmark's, leaf for leaf."""
    from commefficient_tpu.train import cv_train

    cfg = cv_train.parse_args(argv)
    train, _test, _real, _model, params, loss_fn, augment = (
        cv_train.build_model_and_data(cfg))
    session, sampler = cv_train.build_session_and_sampler(
        cfg, train, reweight(params), loss_fn, augment)
    return cfg, session, sampler
