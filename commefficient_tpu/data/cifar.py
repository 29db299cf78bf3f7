"""FedCIFAR10 / FedCIFAR100 — CIFAR with cifar10-fast prep + federated sharding.

Behavioral spec from the reference's ``data_utils/fed_cifar.py`` ~L1-120
(SURVEY.md §2): per-channel normalization, pad(4)+random-crop(32),
horizontal flip, cutout(8) augmentation; non-IID label sharding via the
FedDataset split.

Loading is filesystem-only (this environment has zero egress): the standard
``cifar-10-batches-py`` pickle layout is read if present under
``dataset_dir``; otherwise a deterministic synthetic stand-in with
class-dependent structure is generated so every pipeline and test runs
end-to-end without the real data. The synthetic set is clearly labelled in
logs — accuracy numbers on it are NOT CIFAR numbers.
"""

from __future__ import annotations

import os
import pickle
from typing import Dict, NamedTuple, Tuple

import numpy as np

from commefficient_tpu.data.augment import ImageAugment
from commefficient_tpu.data.fed_dataset import FedDataset

CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)


def _load_cifar10_batches(root: str) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    d = os.path.join(root, "cifar-10-batches-py")
    def read(fname):
        with open(os.path.join(d, fname), "rb") as f:
            raw = pickle.load(f, encoding="bytes")
        x = raw[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y = np.asarray(raw[b"labels"], np.int32)
        return x, y
    xs, ys = zip(*[read(f"data_batch_{i}") for i in range(1, 6)])
    xte, yte = read("test_batch")
    return (
        {"x": np.concatenate(xs), "y": np.concatenate(ys)},
        {"x": xte, "y": yte},
    )


def _synthetic_cifar(
    num_classes: int, n_train: int = 50_000, n_test: int = 10_000, seed: int = 0
) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Class-conditional images: per-class mean pattern + noise. Learnable by
    a convnet, deterministic, and honest about not being CIFAR.

    NB this variant's ResNet-9 gradients are pathologically FLAT (every
    pixel of the uniform-random prototypes is equally informative), which
    breaks the heavy-hitter premise FetchSGD rides on real images — see
    ``_synthetic_cifar_concentrated`` for the stand-in built to reproduce
    real data's gradient concentration (r2 VERDICT item 1)."""
    rng = np.random.default_rng(seed)
    protos = rng.uniform(0, 255, size=(num_classes, 32, 32, 3)).astype(np.float32)

    def make(n):
        y = rng.integers(0, num_classes, size=n).astype(np.int32)
        noise = rng.normal(0, 64, size=(n, 32, 32, 3)).astype(np.float32)
        x = np.clip(protos[y] + noise, 0, 255).astype(np.uint8)
        return {"x": x, "y": y}

    return make(n_train), make(n_test)


def _pink_fields(rng: np.random.Generator, n: int, alpha: float = 1.8,
                 hw: int = 32) -> np.ndarray:
    """[n, hw, hw, 3] unit-std smooth random fields with a 1/f^alpha spatial
    spectrum — the natural-image statistic the flat stand-in lacks. Real
    photographs have steep power-law spectra (alpha ~ 2), which is what
    makes early-conv responses correlated and gradient energy non-uniform."""
    fy = np.fft.fftfreq(hw)[:, None]
    fx = np.fft.fftfreq(hw)[None, :]
    f = np.sqrt(fy * fy + fx * fx)
    f[0, 0] = 1.0
    amp = 1.0 / f ** alpha
    amp[0, 0] = 0.0  # no DC: fields are zero-mean by construction
    spec = (
        rng.normal(size=(n, hw, hw, 3)) + 1j * rng.normal(size=(n, hw, hw, 3))
    ) * amp[None, :, :, None]
    img = np.real(np.fft.ifft2(spec, axes=(1, 2)))
    img /= img.std(axis=(1, 2, 3), keepdims=True) + 1e-8
    return img.astype(np.float32)


def _synthetic_cifar_concentrated(
    num_classes: int, n_train: int = 50_000, n_test: int = 10_000, seed: int = 0,
    *,
    bg_rank: int = 12,
    bg_scale: float = 5.0,
    patch: int = 12,
    patches_per_class: int = 3,
    class_scale: float = 42.0,
    amp_jitter: float = 0.35,
    jitter_px: int = 2,
    noise_scale: float = 10.0,
    label_noise: float = 0.06,
    patch_dropout: float = 0.1,
) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Synthetic CIFAR stand-in whose ResNet-9 gradients CONCENTRATE like
    real data's (r2 VERDICT item 1: the flat stand-in's uniform-random
    prototypes spread gradient energy evenly over all 6.5M coordinates,
    recall@k ~0.38 at k=d/130, so FetchSGD's heavy-hitter extraction has
    nothing to extract).

    Construction (shared low-rank backbone + strong per-class directions +
    label noise, the VERDICT recipe):
      * background: rank-``bg_rank`` basis of 1/f^1.8 smooth fields with
        N(0,1) sample coefficients — class-independent nuisance variation
        with natural-image spectra;
      * class signal: ``patches_per_class`` localized texture patches per
        class, each (class, patch) pair owning a DISTINCT smooth atom, with
        per-sample amplitude jitter, ±``jitter_px`` position jitter, and
        ``patch_dropout`` (each patch independently absent) — class
        information is "which textures are present", a few low-dimensional
        features that survive ResNet-9's global max pool (position-coded
        classes would not: max pooling erases location), so only a few
        filters need to respond and gradient energy concentrates;
      * per-pixel noise + ``label_noise`` flipped train/test labels, so the
        val ceiling sits near 1 - p(1 - 1/C) and no mode can memorize to
        1.0000 (r2 VERDICT weak 1).

    Validated by ``scripts/grad_probe.py``: single-shot sketch recall@k on
    real ResNet-9 round gradients (the go/no-go gate before accuracy runs).

    v3 parameterization (r4, VERDICT r3 missing 1): the defaults above are
    the values DENSE SGD can train to the label-noise ceiling on. The
    original r2/r3 values (``bg_scale=30, patch_dropout=0.25`` — variant
    name "concentrated_v2") made tuned dense SGD plateau at 0.61 TRAIN acc
    0.56 — underfitting, while local_topk fit to 0.93: the rank-12
    background at pixel std 30 is a low-rank nuisance subspace whose
    variance caps the stable lr (divergence at lr>=1.2) and starves the
    class-signal directions; per-coordinate error-feedback methods
    sidestep exactly that, so the v2 task couldn't reproduce real CIFAR's
    dense-SGD trainability (94% in 24 epochs). Measured (24-epoch tuned
    dense, runs/r4_gen_lab.log): bg30 0.615 / bg10 0.793 / bg5 0.831 /
    bg0 0.851; patch_dropout 0.25 -> 0.1 recovers another ~5.5 pts (bg5+
    drop0.1 = 0.8999 vs label-noise ceiling ~0.946). Momentum and longer
    budgets do NOT fix the v2 pathology (bg10+mom 0.789; 48/72-epoch runs
    REGRESS). bg_scale=5 keeps a real correlated-nuisance background at a
    variance dense SGD tolerates.
    """
    rng = np.random.default_rng(seed)
    B = _pink_fields(rng, bg_rank)
    # one distinct atom per (class, patch): class identity = which textures
    # are present, decodable from max-pooled conv features
    atoms = _pink_fields(rng, num_classes * patches_per_class, alpha=1.2)
    atoms = atoms.reshape(num_classes, patches_per_class, 32, 32, 3)
    pos = rng.integers(jitter_px, 32 - patch - jitter_px,
                       size=(num_classes, patches_per_class, 2))

    def make(n):
        y_true = rng.integers(0, num_classes, size=n).astype(np.int32)
        z = rng.normal(size=(n, bg_rank)).astype(np.float32)
        # /sqrt(rank): keep background PIXEL std at bg_scale regardless of
        # rank (the basis fields are independent unit-std). np.float32 scale:
        # a float64 numpy scalar would NEP50-promote the whole [n,32,32,3]
        # buffer to float64 (~2x transient memory at n=50k).
        x = 128.0 + np.float32(bg_scale / np.sqrt(bg_rank)) * np.tensordot(
            z, B, axes=(1, 0)
        )
        # per-sample class patches (amplitude + position jitter + dropout)
        amps = (1.0 + amp_jitter * rng.normal(size=(n, patches_per_class))
                ).astype(np.float32)
        amps *= rng.random((n, patches_per_class)) >= patch_dropout
        dy = rng.integers(-jitter_px, jitter_px + 1, size=(n, patches_per_class))
        dx = rng.integers(-jitter_px, jitter_px + 1, size=(n, patches_per_class))
        for p in range(patches_per_class):
            a = atoms[y_true, p][:, :patch, :patch, :]  # [n, patch, patch, 3]
            ys = pos[y_true, p, 0] + dy[:, p]
            xs = pos[y_true, p, 1] + dx[:, p]
            # vectorized paste via windowed fancy indexing (indices within
            # one patch are unique per sample, so += semantics are exact)
            iy = ys[:, None] + np.arange(patch)  # [n, patch]
            ix = xs[:, None] + np.arange(patch)
            x[np.arange(n)[:, None, None], iy[:, :, None], ix[:, None, :]] += (
                class_scale * amps[:, p, None, None, None] * a
            )
        # float32 draw directly — rng.normal would materialize a float64
        # buffer of the whole set first
        x += np.float32(noise_scale) * rng.standard_normal(
            x.shape, dtype=np.float32
        )
        y = y_true.copy()
        flip = rng.random(n) < label_noise
        y[flip] = rng.integers(0, num_classes, size=int(flip.sum())).astype(np.int32)
        return {"x": np.clip(x, 0, 255).astype(np.uint8), "y": y}

    return make(n_train), make(n_test)


def normalize(x_uint8: np.ndarray) -> np.ndarray:
    """uint8 HWC -> normalized float32 (cifar10-fast prep) — host-side.

    The training pipeline no longer calls this at load: batches stay uint8
    end-to-end on the host and normalization happens ON DEVICE inside the
    loss (``device_normalizer``): a uint8 round is a quarter of the
    float32 round's host->TPU transfer. Kept for tools that want host-side
    floats.
    """
    return ((x_uint8.astype(np.float32) / 255.0) - CIFAR10_MEAN) / CIFAR10_STD


def device_normalizer(mean: np.ndarray, std: np.ndarray):
    """Build the on-device input prep for ``classification_loss``: uint8
    [B,H,W,C] -> normalized float32 (a VPU op XLA fuses into the model's
    first conv); float inputs pass through unchanged (legacy/normalized
    caches)."""

    def prep(x):
        import jax.numpy as jnp

        if x.dtype == jnp.uint8:
            return (x.astype(jnp.float32) / 255.0 - mean) / std
        return x

    return prep


class AugmentPlan(NamedTuple):
    """Per-image augmentation draws (crop offsets in padded coords, flips,
    cutout centers) — separated from the pixel work so the sampler can hand
    the plan to the native fused gather+augment kernel
    (commefficient_tpu.native)."""

    ys: np.ndarray  # [n] int, 0..2*pad
    xs: np.ndarray  # [n] int
    flips: np.ndarray  # [n] bool
    cys: np.ndarray  # [n] int, cutout center rows
    cxs: np.ndarray  # [n] int


class CifarAugment(ImageAugment):
    """pad(4) + random crop + hflip + cutout(8) — cifar10-fast prep, the
    analog of the reference's torchvision transform pipeline
    (``data_utils/fed_cifar.py`` ~L1-120).

    ``plan()`` draws the randomness; ``apply_pixels()`` is the vectorized
    numpy pixel path (the native C++ kernel in native/fedloader.cc and the
    jnp ``device_augment`` are bit-identical — pinned by
    tests/test_native_loader.py and tests/test_device_data.py). The keyed
    ``apply`` / ``device_apply`` / ``gather_apply`` the sampler and the
    session call, and the per-batch ``(batch, rng)`` call, are
    ``data.augment.ImageAugment``'s over these.

    Cutout fill: the reference applies cutout AFTER normalization, so its
    fill of 0.0 is the per-channel MEAN pixel. This pipeline augments
    uint8 (pre-normalization — the host->device link wants uint8), so the
    uint8 fill must be the mean in BYTE space (``fill_uint8``, default
    round(255*CIFAR10_MEAN)); float inputs are assumed already normalized
    and keep the 0.0 fill. Filling plain black in uint8 would inject a
    ~2-sigma outlier patch into every image after normalization.
    """

    pad = 4
    cut_half = 4  # cutout8: an 8x8 window [c-4, c+4)
    Plan = AugmentPlan

    def __init__(self, fill_uint8=None):
        if fill_uint8 is None:
            fill_uint8 = np.round(255.0 * CIFAR10_MEAN).astype(np.uint8)
        self.fill_uint8 = np.asarray(fill_uint8, np.uint8)

    def _fill(self, dtype, c: int) -> np.ndarray:
        if dtype == np.uint8:
            f = self.fill_uint8
            return np.broadcast_to(f, (c,)).astype(np.uint8)
        return np.zeros((c,), dtype)

    def plan(self, rng: np.random.Generator, n: int, h: int = 32, w: int = 32) -> AugmentPlan:
        return AugmentPlan(
            ys=rng.integers(0, 2 * self.pad + 1, size=n),
            xs=rng.integers(0, 2 * self.pad + 1, size=n),
            flips=rng.random(n) < 0.5,
            cys=rng.integers(0, h, size=n),
            cxs=rng.integers(0, w, size=n),
        )

    def apply_pixels(self, x: np.ndarray, p: AugmentPlan) -> np.ndarray:
        """[n, h, w, c] -> augmented copy (crop, then flip, then cutout —
        the order matters: cutout centers are in post-flip coords)."""
        n, h, w, c = x.shape
        pad = self.pad
        padded = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
        iy = p.ys[:, None] + np.arange(h)  # [n, h]
        ix = p.xs[:, None] + np.arange(w)  # [n, w]
        out = padded[np.arange(n)[:, None, None], iy[:, :, None], ix[:, None, :]]
        out[p.flips] = out[p.flips, :, ::-1]
        ch = self.cut_half
        ymask = (np.arange(h)[None, :] >= p.cys[:, None] - ch) & (
            np.arange(h)[None, :] < p.cys[:, None] + ch
        )
        xmask = (np.arange(w)[None, :] >= p.cxs[:, None] - ch) & (
            np.arange(w)[None, :] < p.cxs[:, None] + ch
        )
        mask = ymask[:, :, None] & xmask[:, None, :]
        fill = self._fill(out.dtype, c)
        out[mask] = fill
        return out

    def gather_pixels(self, data: np.ndarray, idx: np.ndarray, p: AugmentPlan):
        """Fused native gather+augment; None when the C++ lib is absent
        (the sampler then falls back to ``apply`` on a numpy gather)."""
        from commefficient_tpu import native

        return native.gather_augment(
            data, idx, p, pad=self.pad, cut_half=self.cut_half,
            fill=self._fill(data.dtype, data.shape[-1]),
        )

    def device_pixels(self, x, *plan):
        """``apply_pixels`` as traced jnp ops for the device-resident data path."""
        return device_augment(
            x, *plan, pad=self.pad, cut_half=self.cut_half,
            fill=self._fill(np.dtype(x.dtype), x.shape[-1]),
        )


#: module-level instance — the historical function-style entry point.
augment_batch = CifarAugment()


def device_augment(x, ys, xs, flips, cys, cxs, *, pad: int = 4,
                   cut_half: int = 4, fill=None):
    """``CifarAugment.apply_pixels`` as traced jnp ops, for the device-resident
    data path (the round gathers + augments INSIDE the jitted program, so
    only indices and this plan cross the host->device link).

    Crop/flip/cutout are pure index/select ops — bit-identical to the
    numpy/native paths on any dtype (pinned by tests/test_device_data.py).
    x: [n, h, w, c]; plan arrays: [n]; fill: [c] cutout fill (see
    CifarAugment's fill note; None = zeros).
    """
    import jax.numpy as jnp

    n, h, w, c = x.shape
    padded = jnp.pad(
        x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect"
    )
    iy = ys[:, None] + jnp.arange(h)  # [n, h]
    ix = xs[:, None] + jnp.arange(w)  # [n, w]
    out = padded[jnp.arange(n)[:, None, None], iy[:, :, None], ix[:, None, :]]
    out = jnp.where(flips[:, None, None, None], out[:, :, ::-1, :], out)
    ymask = (jnp.arange(h)[None, :] >= cys[:, None] - cut_half) & (
        jnp.arange(h)[None, :] < cys[:, None] + cut_half
    )
    xmask = (jnp.arange(w)[None, :] >= cxs[:, None] - cut_half) & (
        jnp.arange(w)[None, :] < cxs[:, None] + cut_half
    )
    mask = ymask[:, :, None] & xmask[:, None, :]
    fill_v = (
        jnp.zeros((c,), x.dtype)
        if fill is None
        else jnp.asarray(np.broadcast_to(fill, (c,)), x.dtype)
    )
    return jnp.where(mask[..., None], fill_v, out)


def _load_cifar100(root: str) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """The ``cifar-100-python`` pickle layout (train/test files, fine labels)."""
    d = os.path.join(root, "cifar-100-python")

    def read(fname):
        with open(os.path.join(d, fname), "rb") as f:
            raw = pickle.load(f, encoding="bytes")
        x = raw[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y = np.asarray(raw[b"fine_labels"], np.int32)
        return {"x": x, "y": y}

    return read("train"), read("test")


def _synthetic_by_variant(num_classes: int, variant: str):
    if variant == "concentrated":
        return _synthetic_cifar_concentrated(num_classes)
    if variant == "concentrated_v2":
        # the r2/r3 parameterization, kept for reproducing those rounds'
        # tables (dense-SGD-hostile — see _synthetic_cifar_concentrated)
        return _synthetic_cifar_concentrated(
            num_classes, bg_scale=30.0, patch_dropout=0.25
        )
    if variant == "flat":
        return _synthetic_cifar(num_classes)
    raise ValueError(
        f"unknown synthetic_variant {variant!r} "
        "(flat|concentrated|concentrated_v2)"
    )


def load_fed_cifar10(
    dataset_dir: str,
    *,
    num_clients: int,
    iid: bool = True,
    seed: int = 42,
    num_classes: int = 10,
    synthetic_variant: str = "flat",
) -> Tuple[FedDataset, FedDataset, bool]:
    """(train FedDataset, test FedDataset, is_real_data).

    ``synthetic_variant`` picks the stand-in generator when the real pickles
    are absent: "flat" (legacy template+noise; gradient spectrum is
    unrealistically flat), "concentrated" (v3 — gradients concentrate like
    real CIFAR's AND dense SGD trains to the ceiling; the FetchSGD evidence
    runs use this, see ACCURACY.md), or "concentrated_v2" (the r2/r3
    dense-SGD-hostile parameterization, kept to reproduce those tables)."""
    real = os.path.isdir(os.path.join(dataset_dir, "cifar-10-batches-py"))
    if real:
        train, test = _load_cifar10_batches(dataset_dir)
    else:
        train, test = _synthetic_by_variant(num_classes, synthetic_variant)
    tr = FedDataset(dict(train), num_clients, iid=iid, seed=seed)
    te = FedDataset(dict(test), 1, iid=True, seed=seed)
    return tr, te, real


def load_fed_cifar100(
    dataset_dir: str,
    *,
    num_clients: int,
    iid: bool = True,
    seed: int = 42,
) -> Tuple[FedDataset, FedDataset, bool]:
    """FedCIFAR100 (reference ``data_utils/fed_cifar.py`` ~L1-120): same
    prep/augment as CIFAR-10, 100 fine labels."""
    real = os.path.isdir(os.path.join(dataset_dir, "cifar-100-python"))
    if real:
        train, test = _load_cifar100(dataset_dir)
    else:
        train, test = _synthetic_cifar(100)
    tr = FedDataset(dict(train), num_clients, iid=iid, seed=seed)
    te = FedDataset(dict(test), 1, iid=True, seed=seed)
    return tr, te, real
