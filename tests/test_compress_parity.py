"""Round-output parity: no refactor may change what a round computes.

tests/golden/registry_parity.npz holds the final params vector + per-round
losses for one representative config per legacy mode on the standard
8-device virtual CPU mesh. First recorded at the last commit before the
compress/ registry port (a mechanical extraction), re-recorded once at
PR 21 under the installed jax 0.9.0, whose RNG and XLA differ from the
recording JAX's (scripts/gen_registry_golden.py documents how and when to
regenerate). Outputs must match the recording on this platform; the
assertions allow only fp32-noise headroom (1e-6 relative) for the paths
whose op ORDER the round never pinned (XLA may re-fuse across function
boundaries).
"""

import os

import numpy as np
import pytest
from test_round import _final_vec, _run, BASE

from commefficient_tpu.utils.config import Config

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "registry_parity.npz")

# must match scripts/gen_registry_golden.py exactly
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


@pytest.fixture(scope="module")
def golden():
    assert os.path.exists(GOLDEN), (
        "tests/golden/registry_parity.npz missing — regenerate with "
        "JAX_PLATFORMS=cpu python scripts/gen_registry_golden.py (see that "
        "script's docstring for when regeneration is legitimate)"
    )
    return np.load(GOLDEN)


@pytest.mark.parametrize("name", sorted(GOLDEN_CONFIGS))
def test_registry_round_outputs_match_pre_refactor(name, golden):
    cfg = Config(**{**BASE, **GOLDEN_CONFIGS[name]})
    sess, losses = _run(cfg, n_rounds=N_ROUNDS, lr=LR)
    want_params = golden[f"{name}__params"]
    want_losses = golden[f"{name}__losses"]
    np.testing.assert_allclose(
        np.asarray(losses, np.float64), want_losses, rtol=1e-6,
        err_msg=f"{name}: per-round losses drifted from the pre-refactor "
        "recording",
    )
    np.testing.assert_allclose(
        _final_vec(sess), want_params, rtol=0, atol=1e-6,
        err_msg=f"{name}: final params drifted from the pre-refactor "
        "recording",
    )
