"""Native C++ loader vs numpy: bit-exact equality + fused-sampler contracts."""

import numpy as np
import pytest

from commefficient_tpu import native
from commefficient_tpu.data import FedSampler, augment_batch, prefetch
from commefficient_tpu.data.cifar import CifarAugment
from commefficient_tpu.data.fed_dataset import FedDataset


def _toy_images(n=64, h=32, w=32, c=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, h, w, c)).astype(np.float32)


def _toy_dataset(n=256, num_clients=8, seed=0):
    rng = np.random.default_rng(seed)
    return FedDataset(
        {
            "x": rng.normal(size=(n, 32, 32, 3)).astype(np.float32),
            "y": rng.integers(0, 10, size=n).astype(np.int32),
        },
        num_clients,
        seed=seed,
    )


def test_native_builds():
    # the baked-in toolchain must build the kernel; if this fails the
    # framework still runs (numpy fallback) but the native path is part of
    # the deliverable, so the suite flags it loudly.
    assert native.available(), "native fedloader failed to build with g++"


@pytest.mark.skipif(not native.available(), reason="no native lib")
def test_gather_augment_matches_numpy_bitexact():
    aug = CifarAugment()
    data = _toy_images(n=128)
    rng = np.random.default_rng(7)
    idx = rng.integers(0, data.shape[0], size=96).astype(np.int64)
    p = aug.plan(rng, 96)
    got = native.gather_augment(data, idx, p, fill=aug._fill(data.dtype, 3))
    want = aug.apply_pixels(np.ascontiguousarray(data[idx]), p)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


@pytest.mark.skipif(not native.available(), reason="no native lib")
def test_gather_augment_uint8_matches_numpy():
    """The training pipeline ships uint8 batches (device-side
    normalization); the u8 kernel must match the numpy path exactly."""
    aug = CifarAugment()
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(100, 32, 32, 3)).astype(np.uint8)
    idx = rng.integers(0, 100, size=64).astype(np.int64)
    p = aug.plan(rng, 64)
    got = native.gather_augment(data, idx, p, fill=aug._fill(data.dtype, 3))
    want = aug.apply_pixels(np.ascontiguousarray(data[idx]), p)
    assert got.dtype == np.uint8
    np.testing.assert_array_equal(got, want)


@pytest.mark.skipif(not native.available(), reason="no native lib")
def test_gather_rows_uint8_and_int32():
    rng = np.random.default_rng(12)
    idx = np.asarray([5, 0, 5, 9], np.int64)
    for dt in (np.uint8, np.int32, np.float32):
        data = rng.integers(0, 100, size=(10, 7)).astype(dt)
        np.testing.assert_array_equal(native.gather_rows(data, idx), data[idx])


@pytest.mark.skipif(not native.available(), reason="no native lib")
def test_plain_gather_matches_numpy():
    data = _toy_images(n=50)
    idx = np.asarray([3, 3, 49, 0, 17], np.int64)
    np.testing.assert_array_equal(native.gather_augment(data, idx), data[idx])
    np.testing.assert_array_equal(native.gather_rows(data, idx), data[idx])


def test_vectorized_augment_matches_legacy_loop():
    """The vectorized CifarAugment.apply must reproduce the r1 per-image
    loop (crop -> flip -> cutout with clamped window) exactly."""
    aug = CifarAugment()
    x = _toy_images(n=40)
    p = aug.plan(np.random.default_rng(3), 40)
    got = aug.apply_pixels(x, p)
    n, h, w, _ = x.shape
    padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect")
    for i in range(n):
        img = padded[i, p.ys[i] : p.ys[i] + h, p.xs[i] : p.xs[i] + w]
        if p.flips[i]:
            img = img[:, ::-1]
        img = img.copy()
        y0, y1 = max(0, p.cys[i] - 4), min(h, p.cys[i] + 4)
        x0, x1 = max(0, p.cxs[i] - 4), min(w, p.cxs[i] + 4)
        img[y0:y1, x0:x1] = 0.0
        np.testing.assert_array_equal(got[i], img)


def test_fused_sampler_shapes_and_determinism():
    ds = _toy_dataset()
    s = FedSampler(ds, num_workers=4, local_batch_size=8, seed=1,
                   augment=augment_batch)
    assert s._fusable
    ids1, b1 = s.sample_round(5)
    ids2, b2 = s.sample_round(5)
    assert b1["x"].shape == (4, 8, 32, 32, 3)
    assert b1["y"].shape == (4, 8)
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(b1["x"], b2["x"])
    # every gathered row must belong to its client's shard
    for wi, cid in enumerate(ids1):
        client_set = {tuple(np.round(r, 4)) for r in
                      ds.data["x"][ds.client_indices[cid]][:, 0, 0, :]}
        # augmentation moves pixels; check labels instead
        labels = set(ds.data["y"][ds.client_indices[cid]].tolist())
        assert set(b1["y"][wi].tolist()) <= labels


def test_fused_gather_no_augment_matches_dataset_rows():
    ds = _toy_dataset()
    s = FedSampler(ds, num_workers=4, local_batch_size=8, seed=2, augment=None)
    assert s._fusable
    ids, b = s.sample_round(0)
    # reproduce the index draws and compare the gathered pixels exactly
    rng = np.random.default_rng((2, 0))
    clients = rng.choice(ds.num_clients, size=4, replace=False)
    np.testing.assert_array_equal(ids, clients.astype(np.int32))
    flat = np.concatenate(
        [ds.client_batch_indices(int(c), 8, rng) for c in clients]
    )
    np.testing.assert_array_equal(b["x"], ds.data["x"][flat].reshape(4, 8, 32, 32, 3))
    np.testing.assert_array_equal(b["y"], ds.data["y"][flat].reshape(4, 8))


def test_prefetch_order_and_exception():
    assert list(prefetch(iter(range(100)), depth=3)) == list(range(100))

    def boom():
        yield 1
        raise ValueError("producer failed")

    it = prefetch(boom())
    assert next(it) == 1
    with pytest.raises(ValueError, match="producer failed"):
        next(it)
