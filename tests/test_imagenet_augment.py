"""ImageNet random-resized-crop augmenter: three-path equivalence.

The plan-based ImageNetAugment (data/imagenet.py) mirrors CifarAugment's
contract: ``plan`` draws the randomness once, and the numpy ``apply``, the
native C++ ``gather_pixels`` kernel, and the traced ``device_pixels`` realize
the same batch. Bilinear interpolation is float arithmetic, so the native
and XLA paths may differ from numpy by FMA contraction — pinned here to
<= 1 uint8 LSB on a small fraction of pixels (the CIFAR paths stay
bit-exact; they are pure copies).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import native
from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.data.imagenet import ImageNetAugment, RRCPlan
from commefficient_tpu.data.sampler import FedSampler


def _toy(n=40, h=48, w=48, c=3, seed=0, uint8=True):
    rng = np.random.default_rng(seed)
    if uint8:
        return rng.integers(0, 256, size=(n, h, w, c)).astype(np.uint8)
    return rng.normal(size=(n, h, w, c)).astype(np.float32)


def test_plan_boxes_valid_and_deterministic():
    aug = ImageNetAugment()
    p = aug.plan(np.random.default_rng(3), 500, 48, 48)
    assert (p.hs >= 1).all() and (p.ws >= 1).all()
    assert (p.ys >= 0).all() and (p.xs >= 0).all()
    assert (p.ys + p.hs <= 48).all() and (p.xs + p.ws <= 48).all()
    # torchvision-style: area fractions spread well below 1 (real crops)
    assert (p.hs * p.ws < 0.9 * 48 * 48).sum() > 100
    p2 = aug.plan(np.random.default_rng(3), 500, 48, 48)
    for a, b in zip(p, p2):
        np.testing.assert_array_equal(a, b)


def test_plan_fallback_full_image():
    """Impossible aspect ratios exhaust all attempts -> torchvision's
    fallback, which for square sources is the full image."""
    aug = ImageNetAugment(scale=(1.0, 1.0), ratio=(3.0, 3.0))
    p = aug.plan(np.random.default_rng(0), 16, 32, 32)
    np.testing.assert_array_equal(p.hs, 32)
    np.testing.assert_array_equal(p.ws, 32)
    np.testing.assert_array_equal(p.ys, 0)
    np.testing.assert_array_equal(p.xs, 0)


def test_identity_crop_is_identity():
    """A full-image crop box resized to the same size must reproduce the
    input exactly (the bilinear grid then lands on integer coordinates)."""
    aug = ImageNetAugment()
    x = _toy(n=8)
    n = x.shape[0]
    p = RRCPlan(
        ys=np.zeros(n, np.int32), xs=np.zeros(n, np.int32),
        hs=np.full(n, 48, np.int32), ws=np.full(n, 48, np.int32),
        flips=np.zeros(n, bool),
    )
    np.testing.assert_array_equal(aug.apply_pixels(x, p), x)


def test_flip_semantics():
    aug = ImageNetAugment()
    x = _toy(n=4)
    n = x.shape[0]
    base = RRCPlan(
        ys=np.zeros(n, np.int32), xs=np.zeros(n, np.int32),
        hs=np.full(n, 48, np.int32), ws=np.full(n, 48, np.int32),
        flips=np.zeros(n, bool),
    )
    flipped = base._replace(flips=np.ones(n, bool))
    np.testing.assert_array_equal(
        aug.apply_pixels(x, flipped), aug.apply_pixels(x, base)[:, :, ::-1]
    )


@pytest.mark.parametrize("uint8", [True, False])
def test_device_apply_matches_numpy(uint8):
    aug = ImageNetAugment()
    x = _toy(n=32, uint8=uint8)
    p = aug.plan(np.random.default_rng(5), 32, 48, 48)
    want = aug.apply_pixels(x, p)
    got = np.asarray(aug.device_pixels(jnp.asarray(x), *map(jnp.asarray, p)))
    if uint8:
        diff = np.abs(got.astype(np.int32) - want.astype(np.int32))
        assert diff.max() <= 1, f"max LSB diff {diff.max()}"
        assert (diff > 0).mean() < 0.05  # only rounding-edge pixels
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.skipif(not native.available(), reason="no native lib")
@pytest.mark.parametrize("uint8", [True, False])
def test_native_gather_rrc_matches_numpy(uint8):
    aug = ImageNetAugment()
    data = _toy(n=64, uint8=uint8)
    rng = np.random.default_rng(9)
    idx = rng.integers(0, 64, size=48).astype(np.int64)
    p = aug.plan(rng, 48, 48, 48)
    got = native.gather_rrc(data, idx, p)
    want = aug.apply_pixels(np.ascontiguousarray(data[idx]), p)
    assert got.dtype == data.dtype
    if uint8:
        diff = np.abs(got.astype(np.int32) - want.astype(np.int32))
        assert diff.max() <= 1, f"max LSB diff {diff.max()}"
        assert (diff > 0).mean() < 0.05
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.skipif(not native.available(), reason="no native lib")
def test_native_gather_rrc_bounds_check():
    aug = ImageNetAugment()
    data = _toy(n=8)
    idx = np.arange(4, dtype=np.int64)
    p = aug.plan(np.random.default_rng(0), 4, 48, 48)
    bad = p._replace(ys=p.ys + 48)  # box bottom beyond the image
    with pytest.raises(IndexError):
        native.gather_rrc(data, idx, bad)


def test_fused_sampler_round_with_rrc():
    """The fused sampler path (native or numpy-fallback) must agree with a
    hand-computed gather+apply on the same rng stream."""
    rng = np.random.default_rng(1)
    ds = FedDataset(
        {"x": _toy(n=256), "y": rng.integers(0, 10, 256).astype(np.int32)},
        8, seed=1,
    )
    aug = ImageNetAugment()
    s = FedSampler(ds, num_workers=4, local_batch_size=8, seed=3, augment=aug)
    assert s.fusable
    ids, batch = s.sample_round(0)
    # replay the identical draw sequence
    rng2 = np.random.default_rng((3, 0))
    clients = rng2.choice(8, size=4, replace=False)
    np.testing.assert_array_equal(ids, clients.astype(np.int32))
    flat = np.concatenate(
        [ds.client_batch_indices(int(c), 8, rng2) for c in clients]
    )
    p = aug.plan(rng2, 32, 48, 48)
    want = aug.apply_pixels(np.ascontiguousarray(ds.data["x"][flat]), p)
    got = batch["x"].reshape(32, 48, 48, 3)
    diff = np.abs(got.astype(np.int32) - want.astype(np.int32))
    assert diff.max() <= 1  # native path may differ by FMA rounding
