"""Native (C++) runtime kernels, loaded via ctypes.

The reference gets its data-path speed from libtorch's native DataLoader
workers (SURVEY.md §2 L4); this package is the TPU build's first-party
equivalent: small C++ kernels for the host-side work that sits between the
federated sampler and ``jax.device_put`` — fused gather+augment batch
assembly (fedloader.cc). ctypes releases the GIL for the duration of each
call, so under the sampler's prefetch thread the host batch assembly
overlaps the TPU round.

The library is compiled on first use with the baked-in ``g++`` (no
pip/pybind11 — plain ``-shared -fPIC``, see ENVIRONMENT constraints) for
the generic target CPU (no ``-march=native``: the checkout is copied
between machines as it stands) and cached next to the source under a name
keyed to the source's content, so an artefact built from other source is
never loaded. Every entry point has a pure-numpy fallback, so the
framework runs unchanged where a toolchain is missing — and
``describe()`` says in-band which of the two a run used (the train
entries print it).
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fedloader.cc")

_lock = threading.Lock()
_lib = None
_build_failed = False
_why_numpy = ""  # set when the build or the dlopen failed


@functools.cache
def _lib_path() -> str:
    """The artefact's path, keyed to the source it was built from."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libfedloader-{digest}.so")

_F32P = ctypes.POINTER(ctypes.c_float)
_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _compile(lib_path: str) -> str:
    """Build ``lib_path``; returns "" on success, else why it failed."""
    # Build to a per-process temp path and os.replace() into place: a second
    # process (multi-host launch, parallel pytest) dlopening a partially
    # written .so would fail or crash; rename on the same filesystem is
    # atomic (ADVICE r2).
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    flag_sets = [
        ["-O3", "-fopenmp"],
        ["-O3"],
    ]
    why = "no flag set tried"
    try:
        for flags in flag_sets:
            cmd = ["g++", *flags, "-shared", "-fPIC", "-o", tmp, _SRC]
            try:
                r = subprocess.run(cmd, capture_output=True, timeout=120)
            except (FileNotFoundError, subprocess.TimeoutExpired) as e:
                return f"g++ did not run: {type(e).__name__}"
            if r.returncode == 0:
                os.replace(tmp, lib_path)
                return ""
            why = "g++ failed: " + r.stderr.decode(errors="replace")[-200:]
        return why
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(path: str):
    lib = ctypes.CDLL(path)
    for name, ptr in (
        ("fedloader_gather_augment", _F32P),
        ("fedloader_gather_augment_u8", _U8P),
    ):
        fn = getattr(lib, name)
        fn.argtypes = [
            ptr, ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            _I64P, ctypes.c_int64,
            _I32P, _I32P, _U8P, _I32P, _I32P,
            ctypes.c_int, ctypes.c_int, _F32P, ptr,
        ]
        fn.restype = None
    lib.fedloader_gather_rows.argtypes = [
        ctypes.c_char_p, _I64P, ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p,
    ]
    lib.fedloader_gather_rows.restype = None
    for name, ptr in (
        ("fedloader_gather_rrc", _F32P),
        ("fedloader_gather_rrc_u8", _U8P),
    ):
        fn = getattr(lib, name)
        fn.argtypes = [
            ptr, ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            _I64P, ctypes.c_int64,
            _I32P, _I32P, _I32P, _I32P, _U8P, ptr,
        ]
        fn.restype = None
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The bound library, building it if needed; None when unavailable."""
    global _lib, _build_failed, _why_numpy
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _why_numpy = _compile(lib_path)
            if _why_numpy:
                _build_failed = True
                return None
        try:
            _lib = _bind(lib_path)
        except OSError as e:
            _why_numpy = f"dlopen failed: {e}"
            _build_failed = True
            return None
        return _lib


def available() -> bool:
    return load() is not None


def describe() -> str:
    """Which host loader this process runs, for the run's own output:
    the native library by file name, or numpy and why."""
    if load() is not None:
        return f"native ({os.path.basename(_lib_path())})"
    return f"numpy ({_why_numpy})"


def gather_augment(
    data: np.ndarray,
    idx: np.ndarray,
    plan=None,
    *,
    pad: int = 4,
    cut_half: int = 4,
    fill: Optional[np.ndarray] = None,
) -> Optional[np.ndarray]:
    """out[i] = augment(data[idx[i]]) via the native kernel.

    ``data`` is [N, H, W, C] float32 or uint8 (the training pipeline ships
    uint8 — 4x less host->device traffic). ``plan`` is an AugmentPlan
    (ys/xs/flips/cys/cxs arrays, see data.cifar.CifarAugment) or None for a
    pure gather. ``fill`` is the [C] cutout fill in source-dtype scale
    (None = zeros; pipelines fill the dataset mean for uint8 — see
    CifarAugment). Returns None when the native library is unavailable
    (callers fall back to numpy).
    """
    lib = load()
    if lib is None or data.ndim != 4:
        return None
    if data.dtype == np.uint8:
        fn, ptr = lib.fedloader_gather_augment_u8, _U8P
    elif data.dtype == np.float32:
        fn, ptr = lib.fedloader_gather_augment, _F32P
    else:
        return None
    data = np.ascontiguousarray(data)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    _check_idx(idx, data.shape[0])
    n = int(idx.shape[0])
    _, h, w, c = data.shape
    out = np.empty((n, h, w, c), data.dtype)
    if plan is None:
        null32, null8 = _I32P(), _U8P()
        args = (null32, null32, null8, null32, null32, 0, 0, _F32P())
    else:
        ys = np.ascontiguousarray(plan.ys, np.int32)
        xs = np.ascontiguousarray(plan.xs, np.int32)
        flips = np.ascontiguousarray(plan.flips, np.uint8)
        cys = np.ascontiguousarray(plan.cys, np.int32)
        cxs = np.ascontiguousarray(plan.cxs, np.int32)
        fill_arr = (
            np.zeros((c,), np.float32)
            if fill is None
            else np.ascontiguousarray(np.broadcast_to(fill, (c,)), dtype=np.float32)
        )
        args = (
            ys.ctypes.data_as(_I32P), xs.ctypes.data_as(_I32P),
            flips.ctypes.data_as(_U8P),
            cys.ctypes.data_as(_I32P), cxs.ctypes.data_as(_I32P),
            pad, cut_half, fill_arr.ctypes.data_as(_F32P),
        )
    fn(
        data.ctypes.data_as(ptr), data.shape[0], h, w, c,
        idx.ctypes.data_as(_I64P), n, *args,
        out.ctypes.data_as(ptr),
    )
    return out


def gather_rrc(data: np.ndarray, idx: np.ndarray, plan) -> Optional[np.ndarray]:
    """out[i] = random_resized_crop(data[idx[i]], plan[i]) via the native
    kernel — the ImageNet train transform (data.imagenet.ImageNetAugment).

    ``plan`` is an RRCPlan (ys/xs/hs/ws int32 crop boxes + flips). Returns
    None when the library is unavailable (callers fall back to numpy).
    Interpolated pixels can differ from the numpy path by 1 uint8 LSB
    (FMA contraction under -O3) — pinned by tests/test_imagenet_augment.py.
    """
    lib = load()
    if lib is None or data.ndim != 4:
        return None
    if data.dtype == np.uint8:
        fn, ptr = lib.fedloader_gather_rrc_u8, _U8P
    elif data.dtype == np.float32:
        fn, ptr = lib.fedloader_gather_rrc, _F32P
    else:
        return None
    data = np.ascontiguousarray(data)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    _check_idx(idx, data.shape[0])
    n = int(idx.shape[0])
    _, h, w, c = data.shape
    ys = np.ascontiguousarray(plan.ys, np.int32)
    xs = np.ascontiguousarray(plan.xs, np.int32)
    hs = np.ascontiguousarray(plan.hs, np.int32)
    ws = np.ascontiguousarray(plan.ws, np.int32)
    # the kernel reads plan[i] for every i < n unchecked: a plan built for
    # a smaller batch would be a silent out-of-bounds heap read
    if not (len(ys) == len(xs) == len(hs) == len(ws) == len(plan.flips) == n):
        raise ValueError(
            f"plan arrays must match idx length {n}, got "
            f"{[len(a) for a in (ys, xs, hs, ws, plan.flips)]}"
        )
    # the kernel reads rows ys+hs-1 / cols xs+ws-1 unchecked: validate the
    # crop boxes like _check_idx validates sample indices
    if n and (
        int(hs.min()) < 1 or int(ws.min()) < 1
        or int(ys.min()) < 0 or int(xs.min()) < 0
        # int64 sums: int32 ys+hs could wrap negative for corrupt plans
        # and sneak past the max() check
        or int((ys.astype(np.int64) + hs).max()) > h
        or int((xs.astype(np.int64) + ws).max()) > w
    ):
        raise IndexError("RRC crop box out of image bounds")
    flips = np.ascontiguousarray(plan.flips, np.uint8)
    out = np.empty((n, h, w, c), data.dtype)
    fn(
        data.ctypes.data_as(ptr), data.shape[0], h, w, c,
        idx.ctypes.data_as(_I64P), n,
        ys.ctypes.data_as(_I32P), xs.ctypes.data_as(_I32P),
        hs.ctypes.data_as(_I32P), ws.ctypes.data_as(_I32P),
        flips.ctypes.data_as(_U8P),
        out.ctypes.data_as(ptr),
    )
    return out


def _check_idx(idx: np.ndarray, n_rows: int) -> None:
    """The C kernels do no bounds checking ((void)N in fedloader.cc) — a
    corrupt or negative index would be a silent out-of-bounds READ in the
    OpenMP copy loop. Validate on the Python side instead (ADVICE r2);
    numpy's min/max over an index batch is noise next to the copy itself."""
    if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= n_rows):
        raise IndexError(
            f"gather index out of range: [{int(idx.min())}, {int(idx.max())}] "
            f"vs {n_rows} data rows"
        )


def gather_rows(data: np.ndarray, idx: np.ndarray) -> Optional[np.ndarray]:
    """out[i] = data[idx[i]] for any fixed-row-size array; None = no lib."""
    lib = load()
    if lib is None or data.dtype == object:
        return None
    data = np.ascontiguousarray(data)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    _check_idx(idx, data.shape[0])
    n = int(idx.shape[0])
    row_bytes = int(data.dtype.itemsize) * (
        int(np.prod(data.shape[1:], dtype=np.int64)) if data.ndim > 1 else 1
    )
    out = np.empty((n,) + data.shape[1:], data.dtype)
    lib.fedloader_gather_rows(
        data.ctypes.data_as(ctypes.c_char_p), idx.ctypes.data_as(_I64P), n,
        row_bytes, out.ctypes.data_as(ctypes.c_char_p),
    )
    return out
