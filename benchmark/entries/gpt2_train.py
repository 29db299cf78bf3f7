"""Builds the round as ``train/gpt2_train.py::main`` builds it for a user:
the same parser defaults, builders and session wiring, in the same order."""

from __future__ import annotations


def build(argv, reweight):
    from commefficient_tpu.train import gpt2_train

    cfg = gpt2_train.parse_args(
        argv,
        defaults=dict(model="gpt2", dataset_name="personachat",
                      local_batch_size=4, lr_scale=0.16, max_grad_norm=1.0),
    )
    train, _test, _real, _loaded, _gcfg, _model, params, loss_fn = (
        gpt2_train.build_model_and_data(cfg))
    session = gpt2_train.FederatedSession(
        cfg, reweight(params), loss_fn, mask_batch=gpt2_train.mask_gpt2)
    sampler = gpt2_train.FedSampler(
        train, num_workers=cfg.num_workers,
        local_batch_size=cfg.sampler_batch_size, seed=cfg.seed)
    session.maybe_attach_data(train, sampler)
    return cfg, session, sampler
