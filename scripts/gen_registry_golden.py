"""Regenerate the registry-port parity goldens (tests/golden/).

The compress/ registry refactor (PR 2) moved every mode's round algebra out
of parallel/round.py into per-mode compressor classes. The contract is that
the refactor is a MECHANICAL extraction: the traced XLA program — and
therefore every round output — is unchanged. This script pins that contract
by recording, for each legacy mode, the final params vector and per-round
losses of a short multi-round run on the standard 8-device virtual CPU mesh
(the same harness tier-1 uses). tests/test_compress_parity.py replays the
identical configs and compares against the recording.

The recording was first taken at the LAST pre-refactor commit (PR 1,
644a056), so the registry was pinned to the legacy dispatch's behavior.
It was re-recorded once at PR 21 under the installed stack (jax/jaxlib
0.9.0): another JAX's RNG and XLA gave every config a different round-0
loss (fedavg 1.5036 vs the recorded 1.7450), so the old file pinned
nothing. Regenerate ONLY when the installation changes or a deliberate,
documented semantic change to a mode's algebra lands (record why in
CHANGES.md), with:

    JAX_PLATFORMS=cpu python scripts/gen_registry_golden.py
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from commefficient_tpu.utils.platform import force_virtual_cpu_devices

force_virtual_cpu_devices(8)

OUT = Path(__file__).resolve().parent.parent / "tests" / "golden"


# One representative config per legacy mode, exercising the mode's full
# state machinery (momentum + error feedback where the mode supports it).
# Kept deliberately small so the parity test stays in the fast tier.
GOLDEN_CONFIGS = {
    "uncompressed": dict(mode="uncompressed", virtual_momentum=0.9),
    "sketch": dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                   k=40, num_rows=3, num_cols=256),
    "sketch_threshold": dict(mode="sketch", error_type="virtual",
                             virtual_momentum=0.9, k=40, num_rows=3,
                             num_cols=256, topk_method="threshold"),
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, k=40),
    "local_topk": dict(mode="local_topk", error_type="local", k=30,
                       local_momentum=0.9),
    "fedavg": dict(mode="fedavg", num_local_iters=2, local_lr=0.1,
                   local_batch_size=8),
    "uncompressed_fused": dict(mode="uncompressed", virtual_momentum=0.9,
                               fuse_clients=True),
    "uncompressed_topk_down": dict(mode="uncompressed", do_topk_down=True,
                                   k=25),
}

N_ROUNDS = 4
LR = 0.2


def run_one(extra: dict):
    # imported late so force_virtual_cpu_devices runs first
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tests"))
    from test_round import BASE, _run

    from commefficient_tpu.utils.config import Config

    cfg = Config(**{**BASE, **extra})
    sess, losses = _run(cfg, n_rounds=N_ROUNDS, lr=LR)
    return np.asarray(sess.state.params_vec), np.asarray(losses, np.float64)


def main():
    os.makedirs(OUT, exist_ok=True)
    blobs = {}
    for name, extra in GOLDEN_CONFIGS.items():
        vec, losses = run_one(extra)
        blobs[f"{name}__params"] = vec
        blobs[f"{name}__losses"] = losses
        print(f"{name:24s} |params|={np.abs(vec).sum():.6f} "
              f"losses={losses.round(4).tolist()}")
    path = OUT / "registry_parity.npz"
    np.savez_compressed(path, **blobs)
    print(f"wrote {path} ({path.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
