"""Mesh helpers: multi-host bring-up guards (parallel/mesh.py)."""

import os
from unittest import mock

import jax


def test_initialize_distributed_noop_single_host():
    from commefficient_tpu.parallel.mesh import initialize_distributed

    clean = {
        k: None
        for k in (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "MEGASCALE_COORDINATOR_ADDRESS",
            "TPU_WORKER_HOSTNAMES",
        )
    }
    env = {k: v for k, v in os.environ.items() if k not in clean}
    with mock.patch.dict(os.environ, env, clear=True):
        assert initialize_distributed() is False


def test_initialize_distributed_ignores_single_hostname():
    """A single-host TPU VM sets TPU_WORKER_HOSTNAMES=localhost; one host
    is not a pod, and must not trigger jax.distributed.initialize()."""
    from commefficient_tpu.parallel.mesh import initialize_distributed

    with mock.patch.dict(os.environ, {"TPU_WORKER_HOSTNAMES": "localhost"}):
        assert initialize_distributed() is False


def test_initialize_distributed_after_backend_init_warns_not_raises(recwarn):
    """With a real coordinator configured but the backend already up (e.g.
    called twice, or from tests), degrade to single-process with a warning
    instead of RuntimeError (regression: r2 gpt2_train e2e failure)."""
    from commefficient_tpu.parallel.mesh import initialize_distributed

    jax.devices()  # ensure the backend is initialized
    with mock.patch.dict(
        os.environ, {"TPU_WORKER_HOSTNAMES": "host-a,host-b"}
    ):
        assert initialize_distributed() is False
    assert any("already initialized" in str(w.message) for w in recwarn.list)
