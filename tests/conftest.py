"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the TPU-world analog of the reference's virtual-worker simulation
(SURVEY.md §4): multi-device semantics are exercised on CPU with
``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8`` so
every shard_map/psum path is tested without real chips (the set-up lives in
``commefficient_tpu.utils.platform``, shared with the driver's
``__graft_entry__.dryrun_multichip``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from commefficient_tpu.utils.platform import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)
# the train entries place a persistent compile cache inside the checkout
# (utils.platform.configure_compile_cache); the suite stays hermetic — no
# test's compile time may depend on what an earlier run left on disk
jax.config.update("jax_enable_compilation_cache", False)
