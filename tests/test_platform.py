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
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_dir_from_environment_sets_nothing_in_code(
        monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, str(tmp_path))
    assert platform.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself (at import); the directory is not set
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_defaults_to_fixed_path_inside_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    placed = platform.configure_compile_cache()
    assert placed == os.path.join(REPO, ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == placed
    # the same directory every time: no pid, timestamp or temp name in it
    assert platform.configure_compile_cache() == placed


_SCOPED = """
def f(x):
    with jax.named_scope("{scope}"):
        y = x * 2
    return y + 1
"""


def _lowered_with_locations(scope, lines_above):
    """What the cache key hashes once metadata is in it: the lowered module,
    locations and all, of one function traced under ``scope`` from a source
    whose lines sit ``lines_above`` further down."""
    ns = {"jax": jax}
    exec(compile("\n" * lines_above + _SCOPED.format(scope=scope),
                 "moved_source.py", "exec"), ns)
    ir = jax.jit(ns["f"]).lower(jax.numpy.ones(4)).compiler_ir()
    return ir.operation.get_asm(enable_debug_info=True)


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_key_holds_the_ops_names_and_not_their_source_lines(
        monkeypatch, cache_config, tmp_path, from_env):
    """A round whose named_scopes changed must not load the executable
    compiled under the old names (the default key leaves metadata out); an
    edit that only moves a traced line must still load it."""
    if from_env:
        monkeypatch.setenv(platform.COMPILE_CACHE_ENV, str(tmp_path))
    else:
        monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    platform.configure_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    here = _lowered_with_locations("alpha", 0)
    assert "jit(f)/alpha/mul" in here and "moved_source.py" not in here
    assert _lowered_with_locations("alpha", 7) == here
    assert _lowered_with_locations("beta", 0) != here


def test_chip_smoke_refuses_a_machine_without_a_chip():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line without a chip
