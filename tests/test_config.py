"""Config + schedule unit tests."""

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.utils import Config, parse_args, piecewise_linear_lr


def test_defaults_valid():
    cfg = Config()
    assert cfg.mode == "uncompressed"
    assert cfg.clients_per_device == 8


def test_cli_roundtrip():
    cfg = parse_args(
        [
            "--mode", "sketch",
            "--k", "100",
            "--num_rows", "3",
            "--num_cols", "1000",
            "--virtual_momentum", "0.9",
            "--error_type", "virtual",
            "--num_clients", "40",
            "--num_workers", "4",
            "--iid", "false",
        ]
    )
    assert cfg.mode == "sketch" and cfg.k == 100 and cfg.num_rows == 3
    assert cfg.virtual_momentum == 0.9 and cfg.error_type == "virtual"
    assert not cfg.iid


def test_validation():
    with pytest.raises(ValueError):
        Config(mode="bogus")
    with pytest.raises(ValueError):
        Config(num_workers=3, num_devices=2)
    with pytest.raises(ValueError):
        Config(num_clients=2, num_workers=8)
    with pytest.raises(ValueError):
        Config(synthetic_variant="bogus")
    with pytest.raises(ValueError, match="sketch_backend"):
        Config(sketch_backend="cuda")


def test_sketch_backend_cli_reaches_spec():
    # the backend flag must flow CLI -> Config -> CountSketch (the Pallas
    # dispatch is a spec property, ops/countsketch.py)
    cfg = parse_args(["--sketch_backend", "pallas"])
    assert cfg.sketch_backend == "pallas"
    from commefficient_tpu.ops.countsketch import CountSketch

    spec = CountSketch(d=1000, c=200, r=3, backend=cfg.sketch_backend)
    assert spec.backend == "pallas"


def test_sketch_dampening_gated():
    # known-divergent combination requires explicit opt-in (VERDICT r2 item 9)
    with pytest.raises(ValueError, match="momentum_dampening"):
        Config(mode="sketch", momentum_dampening=True)
    # explicit opt-in for parity experiments still works
    cfg = Config(mode="sketch", momentum_dampening=True,
                 allow_unstable_sketch_dampening=True)
    assert cfg.momentum_dampening is True
    # AUTO (None) and False are unaffected
    Config(mode="sketch", momentum_dampening=None)
    Config(mode="sketch", momentum_dampening=False)
    # dense-mode dampening unaffected
    Config(mode="true_topk", momentum_dampening=True)


def test_powersgd_flags_cli_roundtrip():
    cfg = parse_args(
        [
            "--mode", "powersgd",
            "--powersgd_rank", "7",
            "--powersgd_warm_start", "false",
            "--error_type", "virtual",
            "--virtual_momentum", "0.9",
        ]
    )
    assert cfg.mode == "powersgd"
    assert cfg.powersgd_rank == 7
    assert cfg.powersgd_warm_start is False
    # defaults
    cfg2 = parse_args(["--mode", "powersgd"])
    assert cfg2.powersgd_rank == 4 and cfg2.powersgd_warm_start is True


def test_powersgd_validation():
    with pytest.raises(ValueError, match="powersgd_rank"):
        Config(mode="powersgd", powersgd_rank=0)
    with pytest.raises(ValueError, match="do_topk_down"):
        Config(mode="powersgd", do_topk_down=True)
    with pytest.raises(ValueError, match="dampening"):
        Config(mode="powersgd", momentum_dampening=True)
    # AUTO/False dampening fine; rank flags don't disturb other modes
    Config(mode="powersgd", momentum_dampening=None)
    Config(mode="sketch", powersgd_rank=9)


def test_label_noise_cli_and_validation():
    assert parse_args(["--label_noise", "0.0"]).label_noise == 0.0
    assert parse_args(["--label_noise", "0.25"]).label_noise == 0.25
    with pytest.raises(ValueError, match="label_noise"):
        Config(label_noise=1.5)
    with pytest.raises(ValueError, match="label_noise"):
        Config(label_noise=-0.1)


def test_round_microbatches_property():
    # the mode-derived reshape knob train loops use instead of branching on
    # mode strings (scripts/check_mode_dispatch.py boundary)
    assert Config(mode="fedavg", num_local_iters=4).round_microbatches == 4
    assert Config(mode="uncompressed").round_microbatches == 0
    assert Config(mode="powersgd", num_local_iters=4).round_microbatches == 0


def test_piecewise_linear_shape():
    kw = dict(steps_per_epoch=10, pivot_epoch=5, num_epochs=20, lr_scale=0.4)
    lrs = np.array(
        [float(piecewise_linear_lr(jnp.asarray(s), **kw)) for s in range(200)]
    )
    peak = lrs.argmax()
    assert abs(peak - 49) <= 1  # peak at pivot_epoch
    assert lrs[0] < 0.01 and lrs[-1] < 0.01  # ~0 at both ends
    np.testing.assert_allclose(lrs.max(), 0.4, atol=0.01)
    assert np.all(np.diff(lrs[: peak + 1]) >= -1e-9)  # monotone up
    assert np.all(np.diff(lrs[peak:]) <= 1e-9)  # monotone down
