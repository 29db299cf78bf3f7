"""Device-resident data path: bit-equality with the host batch path.

The index-driven round (FederatedSession.attach_data /
train_round_indices) must train EXACTLY like the host path — same sampled
rows, same augmentation, same resulting parameters — because the sampler
draws indices/plans with the identical rng sequence and the device
gather+augment mirrors the numpy/native pixel ops.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.data import FedSampler, augment_batch, prefetch
from commefficient_tpu.data.cifar import CifarAugment, device_augment
from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.models import ResNet9, classification_loss
from commefficient_tpu.models.losses import softmax_cross_entropy  # noqa: F401
from commefficient_tpu.parallel import FederatedSession, make_mesh
from commefficient_tpu.utils.config import Config


def _toy_ds(n=512, num_clients=8, seed=0, uint8=True):
    rng = np.random.default_rng(seed)
    if uint8:
        x = rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.uint8)
    else:
        x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=n).astype(np.int32)
    return FedDataset({"x": x, "y": y}, num_clients, seed=seed)


def _mlp_loss():
    """Tiny linear model over flattened pixels; loss_fn convention."""

    def loss_fn(params, batch, rng=None):
        x = batch["x"].astype(jnp.float32).reshape(batch["x"].shape[0], -1)
        logits = x @ params["w"] + params["b"]
        loss = softmax_cross_entropy(logits, batch["y"])
        correct = jnp.sum(jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32)
        return loss, {"correct": correct,
                      "count": jnp.asarray(batch["y"].size, jnp.float32)}

    params = {
        "w": np.zeros((32 * 32 * 3, 10), np.float32),
        "b": np.zeros((10,), np.float32),
    }
    return params, loss_fn


def test_device_augment_matches_numpy_bitexact():
    aug = CifarAugment()
    rng = np.random.default_rng(3)
    for uint8 in (True, False):
        if uint8:
            x = rng.integers(0, 256, size=(40, 32, 32, 3)).astype(np.uint8)
        else:
            x = rng.normal(size=(40, 32, 32, 3)).astype(np.float32)
        p = aug.plan(rng, 40)
        want = aug.apply_pixels(x.copy(), p)
        got = np.asarray(
            device_augment(
                jnp.asarray(x),
                jnp.asarray(p.ys), jnp.asarray(p.xs), jnp.asarray(p.flips),
                jnp.asarray(p.cys), jnp.asarray(p.cxs),
                fill=aug._fill(x.dtype, 3),
            )
        )
        np.testing.assert_array_equal(got, want)


def _run_paths(cfg, ds, augment, rounds=3):
    """Train `rounds` rounds via host-batch and via device-index paths;
    return both final param vectors."""
    params, loss_fn = _mlp_loss()
    finals = []
    for use_idx in (False, True):
        session = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(
            ds, num_workers=cfg.num_workers,
            local_batch_size=cfg.local_batch_size, seed=cfg.seed,
            augment=augment,
        )
        if use_idx:
            session.attach_data(ds.data, augment)
        for r in range(rounds):
            lr = 0.1 + 0.05 * r
            if use_idx:
                ids, idx, plan = sampler.sample_round_indices(r)
                session.train_round_indices(ids, idx, plan, lr)
            else:
                ids, batch = sampler.sample_round(r)
                if cfg.mode == "fedavg":
                    L = cfg.num_local_iters
                    batch = {
                        k: v.reshape(v.shape[0], L, v.shape[1] // L, *v.shape[2:])
                        for k, v in batch.items()
                    }
                session.train_round(ids, batch, lr)
        finals.append(np.asarray(session.state.params_vec))
    return finals


def test_index_path_matches_batch_path_uncompressed():
    cfg = Config(mode="uncompressed", num_clients=8, num_workers=4,
                 num_devices=1, local_batch_size=8, weight_decay=0.0, seed=7,
                 fuse_clients=True)
    a, b = _run_paths(cfg, _toy_ds(), augment_batch)
    np.testing.assert_allclose(a, b, rtol=0, atol=0)


def test_index_path_matches_batch_path_sketch():
    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=64, num_rows=3, num_cols=2048, num_clients=8,
                 num_workers=4, num_devices=1, local_batch_size=8,
                 weight_decay=0.0, seed=7, topk_method="threshold")
    a, b = _run_paths(cfg, _toy_ds(), augment_batch)
    np.testing.assert_allclose(a, b, rtol=0, atol=0)


def test_index_path_matches_batch_path_fedavg_no_augment():
    # L=1 included: the host path reshapes to [W, 1, B, ...] for fedavg
    # unconditionally, and the index path must too (code-review r2 find 1)
    for L in (1, 2):
        cfg = Config(mode="fedavg", num_local_iters=L, num_clients=8,
                     num_workers=4, num_devices=1, local_batch_size=8,
                     weight_decay=0.0, seed=3)
        a, b = _run_paths(cfg, _toy_ds(), None)
        np.testing.assert_allclose(a, b, rtol=0, atol=0)


def test_uint8_cutout_fills_dataset_mean():
    """Cutout on uint8 must fill the per-channel MEAN bytes, not black —
    the reference cuts out AFTER normalization where 0.0 IS the mean."""
    from commefficient_tpu.data.cifar import CIFAR10_MEAN

    aug = CifarAugment()
    x = np.full((1, 32, 32, 3), 200, np.uint8)
    p = aug.plan(np.random.default_rng(0), 1)
    out = aug.apply_pixels(x, p)
    cut_vals = out[out != 200]
    assert cut_vals.size > 0
    expect = np.round(255.0 * CIFAR10_MEAN).astype(np.uint8)
    assert set(np.unique(cut_vals)) <= set(expect.tolist())
    # float input keeps the 0.0 fill (already-normalized space)
    xf = np.full((1, 32, 32, 3), 5.0, np.float32)
    outf = aug.apply_pixels(xf, p)
    assert set(np.unique(outf)) <= {0.0, 5.0}


def test_prefetch_consumer_abandon_stops_producer():
    import time

    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    it = prefetch(gen(), depth=2)
    assert next(it) == 0
    it.close()  # abandon mid-stream
    time.sleep(0.5)
    n = len(produced)
    time.sleep(0.3)
    assert len(produced) == n, "producer kept running after consumer close"


def test_index_path_multidevice():
    n_dev = min(8, jax.device_count())
    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=64, num_rows=3, num_cols=2048, num_clients=2 * n_dev,
                 num_workers=n_dev, num_devices=n_dev, local_batch_size=4,
                 weight_decay=0.0, seed=1, topk_method="threshold")
    params, loss_fn = _mlp_loss()
    ds = _toy_ds(num_clients=2 * n_dev)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(n_dev))
    sampler = FedSampler(ds, num_workers=n_dev, local_batch_size=4, seed=1,
                         augment=augment_batch)
    session.attach_data(ds.data, augment_batch)
    for r in range(2):
        ids, idx, plan = sampler.sample_round_indices(r)
        m = session.train_round_indices(ids, idx, plan, 0.1)
    assert np.isfinite(float(np.asarray(m["loss"])))


@pytest.mark.slow  # r5 tier budget: the e2e EXERCISE of the device-data
# path stays default-tier via test_train_entry's femnist e2e (device_data
# defaults true there too) and the index==batch parity tests above; this
# 70s test only adds the spy ASSERTION that the path was taken
def test_cv_train_takes_device_data_path_e2e(tmp_path):
    """cv_train end-to-end (femnist: small, augment-free) must take the
    device-data path by default and produce finite metrics."""
    from commefficient_tpu.train import cv_train

    built = {}
    orig = cv_train.build_session_and_sampler

    def spy(*a, **k):
        session, sampler = orig(*a, **k)
        built["session"] = session
        return session, sampler

    cv_train.build_session_and_sampler = spy
    try:
        val = cv_train.main(
            [],
            dataset_name="femnist",
            mode="uncompressed",
            num_clients=4,
            num_workers=2,
            num_devices=1,
            local_batch_size=16,  # 1-core CPU budget: 15 rounds, not 30
            num_epochs=1,
            pivot_epoch=1,
            lr_scale=0.05,
            dataset_dir=str(tmp_path),
            logdir=str(tmp_path / "runs"),
            seed=0,
        )
    finally:
        cv_train.build_session_and_sampler = orig
    assert built["session"]._dev_data is not None, "device-data path not taken"
    assert np.isfinite(val["loss"])


# ---- a plan on named keys that adds keys: block diffusion's noise (PR 35) -----------------

def _text_ds(num_clients=6, seq_len=128):
    from commefficient_tpu.data import load_fed_text

    return load_fed_text(num_clients=num_clients, rows_per_client=4, seq_len=seq_len, vocab=300,
                         seed=5, doc_median=40.0, reserved=1)[0]


def _noise_loss():
    """A loss that reads every key the augmenter adds."""

    def loss_fn(params, batch, rng=None):
        x = jnp.where(batch["noise_mask"], 298, batch["input_ids"])
        logits = params["e"][x] @ params["w"]
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, batch["input_ids"][..., None], -1)[..., 0]
        w = jnp.where(batch["noise_mask"], 1.0 / batch["noise_t"], 0.0)
        loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(batch["lm_labels"] != -100), 1)
        return loss, {"count": jnp.sum(batch["noise_mask"]).astype(jnp.float32)}

    rng = np.random.default_rng(0)
    return {"e": rng.normal(size=(300, 8)).astype(np.float32),
            "w": rng.normal(size=(8, 300)).astype(np.float32)}, loss_fn


def test_block_noise_host_and_device_paths_give_the_same_batch_bit_for_bit():
    """The sampler's host batch (``sample_round``) against the device path's
    own making of it: the rows gathered at ``idx`` and ``device_apply`` on
    the shipped plan, key by key, dtype and bit."""
    from commefficient_tpu.data.fedtext import BlockNoise

    ds, aug = _text_ds(), BlockNoise(4)
    sampler = FedSampler(ds, num_workers=3, local_batch_size=2, seed=9, augment=aug)
    assert sampler.fusable
    for r in (0, 1, 7):
        ids, host = sampler.sample_round(r)
        ids2, idx, plan = sampler.sample_round_indices(r)
        assert np.array_equal(ids, ids2) and len(plan) == 2
        assert list(host) == ["input_ids", "lm_labels", "noise_mask", "noise_t"]
        flat = idx.reshape(-1)
        rows = {k: jnp.asarray(v)[flat] for k, v in ds.data.items()}
        rows.update(jax.jit(aug.device_apply)(rows, *map(jnp.asarray, plan)))
        for k, want in host.items():
            got = np.asarray(rows[k]).reshape(want.shape)
            assert got.dtype == want.dtype, k
            np.testing.assert_array_equal(got, want)
        assert host["noise_mask"].any() and not host["noise_mask"][host["lm_labels"] == -100].any()


def test_the_same_round_draws_the_same_noise_and_another_round_another():
    from commefficient_tpu.data.fedtext import BlockNoise

    ds, aug = _text_ds(), BlockNoise(4)
    a = FedSampler(ds, num_workers=3, local_batch_size=2, seed=9, augment=aug)
    b = FedSampler(ds, num_workers=3, local_batch_size=2, seed=9, augment=aug)
    plain = FedSampler(ds, num_workers=3, local_batch_size=2, seed=9)
    p0, p0_again, p1 = (s.sample_round_indices(r) for s, r in ((a, 0), (b, 0), (a, 1)))
    assert all(np.array_equal(x, y) for x, y in zip(p0[2], p0_again[2]))
    assert not np.array_equal(p0[2][1], p1[2][1])
    # the plan's draws come after the clients' and the rows': a sampler with
    # no plan draws the same clients and rows
    ids, idx, none = plain.sample_round_indices(0)
    assert np.array_equal(ids, p0[0]) and np.array_equal(idx, p0[1]) and none == ()
    assert not np.array_equal(
        FedSampler(ds, num_workers=3, local_batch_size=2, seed=10,
                   augment=aug).sample_round_indices(0)[2][1], p0[2][1])


def test_index_path_matches_batch_path_with_block_noise():
    """Three rounds through the session by host batches and by indices + plan
    end on the same parameters, bit for bit."""
    from commefficient_tpu.data.fedtext import BlockNoise

    cfg = Config(mode="uncompressed", num_clients=6, num_workers=3, num_devices=1,
                 local_batch_size=2, weight_decay=0.0, seed=7)
    ds, aug = _text_ds(), BlockNoise(4)
    params, loss_fn = _noise_loss()
    finals = []
    for use_idx in (False, True):
        session = FederatedSession(cfg, params, loss_fn)
        sampler = FedSampler(ds, num_workers=3, local_batch_size=2, seed=7, augment=aug)
        if use_idx:
            assert session.maybe_attach_data(ds, sampler, aug)
        for r in range(3):
            if use_idx:
                metrics = session.train_round_indices(*sampler.sample_round_indices(r), 0.1)
            else:
                metrics = session.train_round(*sampler.sample_round(r), 0.1)
            assert float(metrics["count"]) > 0
        finals.append(np.asarray(session.state.params_vec))
    np.testing.assert_array_equal(finals[0], finals[1])
    assert not np.array_equal(finals[0], np.concatenate(
        [np.ravel(v) for v in jax.tree.leaves(params)]))


def test_cifar_augment_is_an_instance_of_the_keyed_plan():
    """``CifarAugment`` through the keyed calls the sampler and the session
    make is its pixel path on ``"x"``, and the per-batch call draws the plan
    it drew."""
    from commefficient_tpu.data.augment import BatchAugment

    aug = CifarAugment()
    assert isinstance(aug, BatchAugment) and aug.reads == ("x",)
    ds = _toy_ds(64)
    assert aug.accepts(ds.data) and aug.plan_args(ds.data) == (32, 32)
    assert not aug.accepts({"x": ds.data["x"].astype(np.float64)})
    rows = {k: v[:16] for k, v in ds.data.items()}
    p = aug.plan(np.random.default_rng(4), 16, 32, 32)
    want = aug.apply_pixels(rows["x"].copy(), p)
    assert list(aug.apply(rows, *p)) == ["x"]
    np.testing.assert_array_equal(aug.apply(rows, *p)["x"], want)
    np.testing.assert_array_equal(np.asarray(aug.device_apply(
        {k: jnp.asarray(v) for k, v in rows.items()}, *map(jnp.asarray, p))["x"]), want)
    called = aug(rows, np.random.default_rng(4))
    np.testing.assert_array_equal(called["x"], want)
    np.testing.assert_array_equal(called["y"], rows["y"])
