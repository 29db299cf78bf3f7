"""FedImageNet — ImageNet for the FixupResNet runs, sharded over clients.

Behavioral spec from the reference's ``data_utils/fed_imagenet.py`` ~L1-120
(SURVEY.md §2): ImageFolder-style layout (``train/<wnid>/*.JPEG``), client
sharding over classes. Three sources, in order of preference:

  (a) a preprocessed ``.npy`` cache (``imagenet_x.npy``/``imagenet_y.npy``
      under ``dataset_dir/imagenet``) — fastest, recommended for TPU runs;
  (b) an ImageFolder tree (``dataset_dir/imagenet/train/<wnid>/*.JPEG``)
      decoded with PIL if available (resized+center-cropped to ``size``,
      then cached to (a) so decoding happens once);
  (c) a synthetic stand-in at reduced resolution for pipeline/benchmark
      runs with zero egress.
"""

from __future__ import annotations

import os
import warnings
from typing import NamedTuple, Optional, Tuple

import numpy as np

from commefficient_tpu.data.augment import ImageAugment
from commefficient_tpu.data.fed_dataset import FedDataset

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


class RRCPlan(NamedTuple):
    """Per-image random-resized-crop draws: integer crop box (top, left,
    height, width in source pixels) + horizontal flip — the randomness
    separated from the pixel work so any of the three execution paths
    (numpy / native C++ / on-device jnp) can realize the same batch."""

    ys: np.ndarray  # [n] int32 crop top
    xs: np.ndarray  # [n] int32 crop left
    hs: np.ndarray  # [n] int32 crop height (>= 1)
    ws: np.ndarray  # [n] int32 crop width (>= 1)
    flips: np.ndarray  # [n] bool


def _bilinear_grid(out_len: int, crop_len, xp):
    """Sampling coordinates for resizing a ``crop_len``-pixel axis to
    ``out_len`` pixels — torch/PIL bilinear convention (align_corners=False):
    ``src = (dst + 0.5) * crop/out - 0.5``, clamped to the crop. Returns
    (lo index, hi index, hi weight), all [n, out_len]."""
    f32 = np.float32
    crop = crop_len[:, None].astype(f32)
    g = (xp.arange(out_len, dtype=f32)[None, :] + f32(0.5)) * (
        crop / f32(out_len)
    ) - f32(0.5)
    g = xp.clip(g, f32(0.0), crop - f32(1.0))
    lo = xp.floor(g).astype(np.int32)
    hi = xp.minimum(lo + 1, crop_len[:, None] - 1)
    return lo, hi, (g - lo.astype(f32)).astype(f32)


def _rrc_pixels(x, p: RRCPlan, xp):
    """Shared numpy/jnp bilinear crop-resize: [n, H, W, C] -> same shape,
    each image's (ys, xs, hs, ws) box resized back to (H, W). The lerp is
    written ``a + (b - a) * t`` in float32 in all three paths (numpy, C++,
    XLA) so results agree to the last bit up to FMA contraction (the native
    path is pinned within 1 uint8 LSB by tests)."""
    n, H, W, C = x.shape
    f32 = np.float32
    y0, y1, wy = _bilinear_grid(H, p.hs, xp)
    x0, x1, wx = _bilinear_grid(W, p.ws, xp)
    ay0, ay1 = p.ys[:, None] + y0, p.ys[:, None] + y1
    ax0, ax1 = p.xs[:, None] + x0, p.xs[:, None] + x1
    ii = xp.arange(n)[:, None, None]
    p00 = x[ii, ay0[:, :, None], ax0[:, None, :]].astype(f32)
    p01 = x[ii, ay0[:, :, None], ax1[:, None, :]].astype(f32)
    p10 = x[ii, ay1[:, :, None], ax0[:, None, :]].astype(f32)
    p11 = x[ii, ay1[:, :, None], ax1[:, None, :]].astype(f32)
    wyE, wxE = wy[:, :, None, None], wx[:, None, :, None]
    top = p00 + (p01 - p00) * wxE
    bot = p10 + (p11 - p10) * wxE
    return top + (bot - top) * wyE


class ImageNetAugment(ImageAugment):
    """Random-resized-crop + horizontal flip — the reference's ImageNet
    train transform (``data_utils/fed_imagenet.py`` ~L1-120 uses
    torchvision ``RandomResizedCrop`` + ``RandomHorizontalFlip``), realized
    plan-based like ``CifarAugment`` so the fused native kernel and the
    device-resident path can apply it.

    Sampling follows torchvision's RRC exactly: up to 10 attempts drawing
    area fraction ~ U(scale) and aspect ~ exp(U(log ratio)), first attempt
    whose integer crop box fits wins; the fallback for square inputs is the
    full image (same as torchvision's ratio-clamped fallback when the
    source ratio is inside [3/4, 4/3]). The crop is resized back to the
    source (H, W) with bilinear interpolation, then flipped with p=0.5.
    Note the source here is the size x size decode cache, not the original
    JPEG, so scale fractions are relative to the center-cropped cache.
    """

    Plan = RRCPlan

    def __init__(self, scale=(0.08, 1.0), ratio=(3.0 / 4.0, 4.0 / 3.0),
                 attempts: int = 10):
        self.scale = scale
        self.ratio = ratio
        self.attempts = attempts

    def plan(self, rng: np.random.Generator, n: int, h: int, w: int) -> RRCPlan:
        T = self.attempts
        area = h * w * rng.uniform(self.scale[0], self.scale[1], size=(n, T))
        aspect = np.exp(
            rng.uniform(np.log(self.ratio[0]), np.log(self.ratio[1]), size=(n, T))
        )
        ws = np.round(np.sqrt(area * aspect)).astype(np.int64)
        hs = np.round(np.sqrt(area / aspect)).astype(np.int64)
        # uniform position draw per attempt (consumed from the rng stream
        # whether or not the attempt wins, keeping the plan a pure function
        # of the draw count)
        uy = rng.random((n, T))
        ux = rng.random((n, T))
        valid = (ws > 0) & (ws <= w) & (hs > 0) & (hs <= h)
        first = np.argmax(valid, axis=1)  # index of first True; 0 if none
        any_valid = valid[np.arange(n), first]
        hs_f = hs[np.arange(n), first]
        ws_f = ws[np.arange(n), first]
        ys_f = np.floor(uy[np.arange(n), first] * (h - hs_f + 1)).astype(np.int64)
        xs_f = np.floor(ux[np.arange(n), first] * (w - ws_f + 1)).astype(np.int64)
        # fallback: full image (torchvision's ratio-clamp fallback reduces
        # to this for square sources)
        hs_f = np.where(any_valid, hs_f, h)
        ws_f = np.where(any_valid, ws_f, w)
        ys_f = np.where(any_valid, ys_f, 0)
        xs_f = np.where(any_valid, xs_f, 0)
        return RRCPlan(
            ys=ys_f.astype(np.int32), xs=xs_f.astype(np.int32),
            hs=hs_f.astype(np.int32), ws=ws_f.astype(np.int32),
            flips=rng.random(n) < 0.5,
        )

    def apply_pixels(self, x: np.ndarray, p: RRCPlan) -> np.ndarray:
        """[n, h, w, c] -> augmented copy (vectorized numpy path)."""
        val = _rrc_pixels(x, p, np)
        if x.dtype == np.uint8:
            out = np.clip(np.rint(val), 0, 255).astype(np.uint8)
        else:
            out = val.astype(x.dtype)
        out[p.flips] = out[p.flips, :, ::-1]
        return out

    def gather_pixels(self, data: np.ndarray, idx: np.ndarray, p: RRCPlan):
        """Fused native gather+augment; None when the C++ lib is absent
        (the sampler then falls back to ``apply`` on a numpy gather)."""
        from commefficient_tpu import native

        return native.gather_rrc(data, idx, p)

    def device_pixels(self, x, *plan):
        """``apply_pixels`` as traced jnp ops for the device-resident data path."""
        import jax.numpy as jnp

        p = RRCPlan(*plan)
        val = _rrc_pixels(x, p, jnp)
        if x.dtype == jnp.uint8:
            out = jnp.clip(jnp.rint(val), 0, 255).astype(jnp.uint8)
        else:
            out = val.astype(x.dtype)
        return jnp.where(p.flips[:, None, None, None], out[:, :, ::-1, :], out)


def _load_imagefolder(
    train_root: str, size: int, max_per_class: Optional[int] = None
) -> Optional[dict]:
    """Decode an ImageFolder tree with PIL (None if PIL is unavailable).

    Images are resized so the short side is ``size`` then center-cropped to
    ``size x size`` — the reference's val-style deterministic transform (its
    random-resized-crop augmentation is train-time policy, applied by the
    sampler's augment hook, not baked into the cache). Returns UINT8 pixels
    (normalization happens after load) so the .npy cache is 4x smaller, and
    caps decoding at ``max_per_class`` so a full ImageNet tree cannot OOM
    the host.
    """
    try:
        from PIL import Image
    except ImportError:
        return None
    exts = (".jpeg", ".jpg", ".png")
    wnids = sorted(
        d for d in os.listdir(train_root)
        if os.path.isdir(os.path.join(train_root, d))
    )
    xs, ys = [], []
    truncated = 0
    for label, wnid in enumerate(wnids):
        cdir = os.path.join(train_root, wnid)
        all_files = sorted(
            f for f in os.listdir(cdir) if f.lower().endswith(exts)
        )
        files = all_files[:max_per_class]
        truncated += len(all_files) - len(files)
        for fn in files:
            with Image.open(os.path.join(cdir, fn)) as im:
                im = im.convert("RGB")
                w, h = im.size
                scale = size / min(w, h)
                im = im.resize((round(w * scale), round(h * scale)))
                w, h = im.size
                left, top = (w - size) // 2, (h - size) // 2
                im = im.crop((left, top, left + size, top + size))
                xs.append(np.asarray(im, np.uint8))
            ys.append(label)
    if not xs:
        return None
    if truncated:
        # loud: a silently capped decode must never masquerade as the full
        # dataset in accuracy claims (VERDICT r2 weak 8) — raise
        # max_per_class (the cap exists only as a host-OOM guard) or stage
        # a full .npy cache to train on everything.
        warnings.warn(
            f"ImageFolder decode kept at most {max_per_class} images/class "
            f"({truncated} images SKIPPED); the .npy cache written from "
            "this decode is a SUBSET of the tree. Accuracy from this run "
            "is not full-ImageNet accuracy.",
            stacklevel=3,
        )
    return {"x": np.stack(xs), "y": np.asarray(ys, np.int32)}


def _synthetic_imagenet(
    num_classes: int = 1000, n: int = 20_000, size: int = 64, seed: int = 9
):
    rng = np.random.default_rng(seed)
    protos = rng.uniform(-1, 1, size=(num_classes, size, size, 3)).astype(np.float32)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0, 0.5, size=(n, size, size, 3)).astype(np.float32)
    return {"x": x.astype(np.float32), "y": y}


def load_fed_imagenet(
    dataset_dir: str,
    *,
    num_clients: int,
    iid: bool = False,
    seed: int = 42,
    num_classes: int = 1000,
    synthetic_size: int = 64,
    max_per_class: int = 300,
) -> Tuple[FedDataset, FedDataset, bool]:
    root = os.path.join(dataset_dir, "imagenet")
    xp, yp = os.path.join(root, "imagenet_x.npy"), os.path.join(root, "imagenet_y.npy")
    real = os.path.exists(xp) and os.path.exists(yp)
    if real:
        # uint8 stays uint8: normalization happens on device inside the
        # loss (cv_train passes device_normalizer) — 4x less H2D traffic
        data = {"x": np.load(xp), "y": np.load(yp)}
    else:
        train_root = os.path.join(root, "train")
        data = None
        if os.path.isdir(train_root):
            data = _load_imagefolder(
                train_root, size=max(synthetic_size, 64),
                max_per_class=max_per_class,
            )
            if data is not None:
                real = True
                np.save(xp, data["x"])  # uint8 cache: decode happens once
                np.save(yp, data["y"])
        if data is None:
            data = _synthetic_imagenet(num_classes, size=synthetic_size, seed=seed)
    n = len(data["y"])
    # the ImageFolder decode is class-sorted: shuffle (seeded) before the
    # positional split so the test tail isn't just the last classes
    perm = np.random.default_rng(seed).permutation(n)
    data = {k: v[perm] for k, v in data.items()}
    cut = int(0.95 * n)
    train = FedDataset(
        {k: v[:cut] for k, v in data.items()}, num_clients, iid=iid, seed=seed
    )
    test = FedDataset({k: v[cut:] for k, v in data.items()}, 1, iid=True, seed=seed)
    return train, test, real
