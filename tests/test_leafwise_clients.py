"""The leafwise client path (parallel/round.py::make_leafwise_sum).

Where a round needs only the SUM of its clients' clipped gradients, the
default round clips and sums the gradient leaves and builds one [D] vector
per shard: no [w_loc, D] buffer. Pinned here: the path agrees with the
per-client-vector path on the same inputs (bit for bit with no clip, to the
golden test's 1e-6 with one: only the order the norm's squares are added in
differs), the compiled default rounds of the tiny GPT-2 and Laguna entries
hold no float32 [w_loc, D] buffer, the record of which path a session traced
reads as ``resolve_client_path`` rules for every mode, and the FSDP round
sums as the replicated one does.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest
from test_round import _final_vec, _setup

from commefficient_tpu.compress import compressor_class
from commefficient_tpu.compress.base import Compressor
from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession, make_mesh
from commefficient_tpu.parallel import round as round_mod
from commefficient_tpu.parallel.round import LEAFWISE, PER_CLIENT_VECTOR
from commefficient_tpu.utils.config import Config

BASE = dict(num_clients=12, num_workers=8, num_devices=2, local_batch_size=4,
            seed=5, topk_method="threshold")
DENSE_TRANSMIT = {
    "uncompressed": dict(mode="uncompressed", virtual_momentum=0.9),
    "sketch": dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                   k=40, num_rows=3, num_cols=256),
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, k=40),
    "powersgd": dict(mode="powersgd", error_type="virtual", powersgd_rank=2,
                     virtual_momentum=0.9),
}
PER_CLIENT = {
    "local_topk": dict(mode="local_topk", error_type="local", k=30),
    "fedavg": dict(mode="fedavg", num_local_iters=2, local_lr=0.1,
                   local_batch_size=8),
    "local_momentum": dict(mode="uncompressed", local_momentum=0.9),
    "local_error": dict(mode="local_topk", error_type="local", k=30,
                        local_momentum=0.9),
    "dp_noise": dict(mode="uncompressed", max_grad_norm=1.0,
                     dp_noise_multiplier=0.1),
    "fedsim_mask": dict(mode="uncompressed", availability="bernoulli",
                        dropout_prob=0.25),
    "asyncfed_launch": dict(mode="uncompressed", async_buffer=8),
}


def _rounds(cfg, n_rounds=3, lr=0.2):
    """(session, losses, every other metric of the last round)."""
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    losses = []
    for r in range(n_rounds):
        m = sess.train_round(*sampler.sample_round(r), lr)
        losses.append(np.asarray(m["loss"]))
    aux = {k: np.asarray(v) for k, v in m.items() if k != "loss"}
    return sess, np.asarray(losses), aux


@pytest.mark.parametrize("wd", [0.0, 5e-4], ids=["wd0", "wd"])
@pytest.mark.parametrize("clip", [None, 0.5], ids=["noclip", "clip"])
@pytest.mark.parametrize("mode", sorted(DENSE_TRANSMIT))
def test_leafwise_equals_the_per_client_vector_path(mode, clip, wd,
                                                    monkeypatch):
    """Three rounds on the same inputs, the second session forced onto the
    per-client-vector path: the aggregate as the server applied it (the
    parameters), the losses and the aux. ``clip`` 0.5 binds on every client
    of this model. With no clip the two are the same per-element expression
    summed over clients in the same order: bit for bit, unless weight decay
    is off too; then nothing element-wise sits between the backward pass and
    the sum, and XLA folds the sum over clients into the gradients' own
    products (no per-client gradient is formed), which adds in another
    order. With a clip the norm's squares are added per leaf, then across
    leaves: the golden test's 1e-6."""
    cfg = Config(**{**BASE, **DENSE_TRANSMIT[mode], "max_grad_norm": clip,
                    "weight_decay": wd})
    leaf, l_leaf, aux_leaf = _rounds(cfg)
    assert leaf.client_path_resolved == LEAFWISE
    monkeypatch.setattr(round_mod, "resolve_client_path",
                        lambda cfg, comp: PER_CLIENT_VECTOR)
    vec, l_vec, aux_vec = _rounds(cfg)
    assert aux_leaf.keys() == aux_vec.keys() and aux_leaf
    if clip is None and wd:
        np.testing.assert_array_equal(_final_vec(leaf), _final_vec(vec))
        np.testing.assert_array_equal(l_leaf, l_vec)
        for k in aux_leaf:
            np.testing.assert_array_equal(aux_leaf[k], aux_vec[k], err_msg=k)
        return
    np.testing.assert_allclose(_final_vec(leaf), _final_vec(vec), rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(l_leaf, l_vec, rtol=1e-6)
    for k in aux_leaf:
        np.testing.assert_allclose(aux_leaf[k], aux_vec[k], rtol=1e-6,
                                   err_msg=k)
    if clip is not None:  # the clip binds: without it the rounds differ
        free, _l, _a = _rounds(Config(**{**BASE, **DENSE_TRANSMIT[mode],
                                         "weight_decay": wd}))
        assert np.abs(_final_vec(free) - _final_vec(leaf)).max() > 1e-3


def _entry_round(entry, argv):
    """(lowered, session, w_loc) of the index round an entry dispatches."""
    if entry == "gpt2":
        from commefficient_tpu.train import gpt2_train

        cfg = gpt2_train.parse_args(
            ["--model", "gpt2_tiny", "--max_seq_len", "32", "--num_clients",
             "8", "--local_batch_size", "2", "--max_grad_norm", "1.0",
             "--num_workers", "2", "--num_devices", "1"] + argv)
        train, _t, _r, _l, _g, _m, params, loss_fn = (
            gpt2_train.build_model_and_data(cfg))
        session = FederatedSession(cfg, params, loss_fn,
                                   mask_batch=gpt2_train.mask_gpt2)
        sampler = FedSampler(train, num_workers=cfg.num_workers,
                             local_batch_size=cfg.sampler_batch_size,
                             seed=cfg.seed)
        session.maybe_attach_data(train, sampler)
    else:
        from commefficient_tpu.train import lm_train

        cfg = lm_train.parse_args(
            ["--model", "laguna_tiny", "--max_seq_len", "128",
             "--num_clients", "8", "--num_workers", "2", "--num_devices",
             "1"] + argv, defaults=lm_train.DEFAULTS)
        train, _t, _lc, _m, params, loss_fn = lm_train.build_model_and_data(cfg)
        session, sampler = lm_train.build_session_and_sampler(
            cfg, train, params, loss_fn)
    assert cfg.max_grad_norm == 1.0 and cfg.weight_decay > 0  # the defaults
    ids, idx, plan = sampler.sample_round_indices(0)
    cids, idxd, pl = session.stage_round_indices(ids, idx, plan)
    lowered = session._round_idx_fn.lower(
        session.state, session._dev_data, jnp.asarray(cids), idxd, pl,
        jnp.float32(0.1), env=())
    return lowered, session, cfg.num_workers // cfg.num_devices


SKETCH_ARGV = ["--mode", "sketch", "--error_type", "virtual", "--k", "500",
               "--num_rows", "3", "--num_cols", "20000", "--topk_method",
               "threshold"]


@pytest.mark.parametrize("entry,argv", [
    ("gpt2", ["--mode", "uncompressed"]),
    ("gpt2", SKETCH_ARGV),
    ("laguna", ["--mode", "uncompressed"]),
], ids=["gpt2_uncompressed", "gpt2_sketch", "laguna_uncompressed"])
def test_default_round_holds_no_w_by_d_buffer(entry, argv):
    """Clip and weight decay on, as the entries default: neither what the
    program traced nor what the compiler made of it holds a float32
    [w_loc, D] operand or result, and the program still opens the three
    scopes the benchmark's readers match. (The per-client-vector round of
    the same entry does hold one: the patterns are live.)"""
    lowered, session, w_loc = _entry_round(entry, argv)
    assert session.client_path_resolved == LEAFWISE
    d = session.grad_size
    traced = lowered.as_text(debug_info=True)
    compiled = lowered.compile().as_text()
    assert f"tensor<{w_loc}x{d}xf32>" not in traced
    assert f"f32[{w_loc},{d}]" not in compiled
    for scope in ("flat_grad_concat", "client_clip", "client_sum"):
        assert re.search(r"\b" + scope + r"\b", traced), scope
    # one [D] concat a shard, under client_sum, and none under client_grad
    assert "client_sum/flat_grad_concat" in traced
    assert "client_grad)/flat_grad_concat" not in traced
    if entry == "gpt2" and argv[1] == "uncompressed":
        lowered_vec, vec, _ = _entry_round(
            entry, argv + ["--dp_noise_multiplier", "0.1"])
        assert vec.client_path_resolved == PER_CLIENT_VECTOR
        assert f"tensor<{w_loc}x{d}xf32>" in lowered_vec.as_text()
        assert f"f32[{w_loc},{d}]" in lowered_vec.compile().as_text()


@pytest.mark.parametrize("name", sorted(DENSE_TRANSMIT) + sorted(PER_CLIENT))
def test_the_path_record_reads_the_rule(name):
    """``FederatedSession.client_path_resolved``: leafwise for the four
    dense-transmit modes, per-client vectors wherever something downstream
    takes a client's own row."""
    kw = {**DENSE_TRANSMIT, **PER_CLIENT}[name]
    cfg = Config(**{**BASE, "num_devices": 1, "max_grad_norm": 1.0, **kw})
    _ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    want = LEAFWISE if name in DENSE_TRANSMIT else PER_CLIENT_VECTOR
    assert sess.client_path_resolved == want
    assert sess.rungs[0].client_path_resolved == want
    if name == "asyncfed_launch":
        sess.async_round_fns()  # the launch program builds on this path


def test_a_synchronous_config_has_no_launch_program():
    cfg = Config(**{**BASE, "num_devices": 1, "mode": "uncompressed"})
    _ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    with pytest.raises(ValueError, match="per-client rows"):
        sess.async_round_fns()


@pytest.mark.parametrize("mode", ["uncompressed", "sketch", "true_topk",
                                  "powersgd", "local_topk", "fedavg"])
def test_base_client_rules_says_what_the_class_overrides(mode):
    cls = compressor_class(mode)
    base = (cls.client_grad is Compressor.client_grad
            and cls.client_transmit is Compressor.client_transmit)
    assert cls.base_client_rules == base


@pytest.mark.parametrize("mode", ["uncompressed", "true_topk"])
def test_fsdp_sum_equals_the_replicated_rounds_on_one_device(mode):
    """Both rounds call ``make_leafwise_sum`` (fsdp through
    ``sum_client_grads``), clip and weight decay on: on one device the
    aggregates are the same numbers, so the parameters after three rounds
    agree to the server algebras' own rounding."""
    kw = {**BASE, **DENSE_TRANSMIT[mode], "num_devices": 1,
          "max_grad_norm": 0.5, "weight_decay": 5e-4}
    rep, l_rep, _ = _rounds(Config(**kw))
    fs, l_fs, _ = _rounds(Config(**kw, fsdp=True))
    assert rep.client_path_resolved == fs.client_path_resolved == LEAFWISE
    np.testing.assert_allclose(l_fs, l_rep, rtol=1e-6)
    np.testing.assert_allclose(_final_vec(fs)[: fs.grad_size],  # [Dp] padded
                               _final_vec(rep), rtol=0, atol=1e-6)
