"""Pallas TPU kernel backends for the ops layer (``backend='pallas'``).

Import surface for the dispatchers in ``ops/countsketch.py`` — keep this
light: importing the subpackage must not trigger any pallas_call tracing
(tier-1 collection runs on CPU with JAX_PLATFORMS=cpu).
"""

from commefficient_tpu.ops.pallas.countsketch_kernels import (
    estimate_all_pallas,
    kernels_interpreted,
    median_rows_pallas,
    sketch_vec_pallas,
)

__all__ = [
    "estimate_all_pallas",
    "kernels_interpreted",
    "median_rows_pallas",
    "sketch_vec_pallas",
]
