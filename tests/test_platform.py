"""Process-level bring-up: where the compile cache goes, and that the chip
smoke refuses a machine without a chip."""

import os
import subprocess
import sys

import jax
import pytest

from commefficient_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the cache options a test's configure call may have set."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_dir_from_environment_sets_nothing_in_code(
        monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, str(tmp_path))
    assert platform.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself (at import); the function touched nothing
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_defaults_to_fixed_path_inside_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    placed = platform.configure_compile_cache()
    assert placed == os.path.join(REPO, ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == placed
    # the same directory every time: no pid, timestamp or temp name in it
    assert platform.configure_compile_cache() == placed


def test_chip_smoke_refuses_a_machine_without_a_chip():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line without a chip
