"""The least bytes a kernel of the compression must move over HBM, as a
function of the *work* and never of the implementation: an einsum, a Pallas
kernel and a fused backward are read against the same yardstick. Each is a
true lower bound (every input read once, every output written once,
float32), so ``benchmark/layers/kernel_hbm_share.py`` reading a share over
100 % means the scope does not cover the work, not that the kernel is fast.

``d`` is the flat parameter count, ``rows`` x ``cols`` the sketch table
and ``k`` the heavy hitters kept, as the traffic file's ``reference`` block
states them.
"""

from __future__ import annotations

F32 = 4


def encode_bytes(*, d, rows, cols, **_):
    """Sketch accumulate: read the [d] vector, write the [rows, cols] table."""
    return F32 * d + F32 * rows * cols


def estimate_bytes(*, d, rows, cols, **_):
    """Estimate of all coordinates: read the table, write [d] estimates."""
    return F32 * rows * cols + F32 * d


def topk_bytes(*, d, **_):
    """Selection: read the [d] estimates once; what it writes may be as
    little as k pairs, so nothing is counted for it."""
    return F32 * d
