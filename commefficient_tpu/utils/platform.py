"""Process-level JAX set-up: the virtual CPU mesh the tests run on, and the
persistent compile cache the entry points share.

Tests and ``__graft_entry__.dryrun_multichip`` run multi-device code on N
virtual CPU devices (``--xla_force_host_platform_device_count``) — the
TPU-world analog of the reference's virtual-worker simulation (SURVEY.md
§4). ``force_virtual_cpu_devices(n)`` must run BEFORE any jax backend
initializes (importing jax is fine; running an op is not).

On the chip the program runs as it is: JAX picks the TPU by default and
every entry point calls ``configure_compile_cache()`` first, so a second
process (or a second call of the chip tool that kept the directory) finds
the first one's executables instead of compiling for minutes.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed and inside the checkout (git-ignored): the directory is part of
# nothing's identity, but a cache that moves with a pid, a timestamp or a
# temp name is a cache that never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in
    force. Called first thing by every entry point (the train mains,
    ``benchmark/run.py``, ``__graft_entry__``, chip_smoke.py).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the caller owns the cache:
    JAX reads the variable itself and nothing is set in code. Otherwise
    the cache is the fixed in-checkout ``COMPILE_CACHE_DIR``, and every
    compile is kept (JAX's default skips those under one second, which
    would make "the warm run wrote nothing" depend on timing noise).

    Either way the ops' names are part of the cache key, and nothing else
    of their metadata is. JAX's default key leaves all metadata out, so a
    round whose ``named_scope``s changed and whose computation did not
    would load the executable another commit compiled, and a profiler
    trace would show that commit's names: the per-layer metrics read those
    names (telemetry.trace.ROUND_SCOPES). With the metadata in the key as
    JAX writes it, every edit that moves a traced line would recompile the
    round instead; so the Python tracebacks are left out of the lowered
    locations (an op's metadata is then its ``op_name`` alone: a device
    trace shows the scope path of an op and no longer its source line)."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return COMPILE_CACHE_DIR


def force_virtual_cpu_devices(n: int = 8) -> None:
    """Pin jax to ``n`` virtual CPU devices.

    Idempotent; safe to call multiple times with the same ``n``. A device
    count already in ``XLA_FLAGS`` is left as the caller set it.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
