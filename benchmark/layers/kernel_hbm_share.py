"""``compress.<kernel>_hbm_share``: the least time the chip's HBM could take
for the kernel's work over the device time its scope took. The work's least
bytes come from the function the metric's file names as ``bytes_fn``
(``benchmark/kernel_bytes.py``), the bandwidth from ``benchmark/peaks.json``,
the seconds from the metric its file names as ``seconds`` (read the same way,
so the two cannot disagree). The bound is a true lower bound, so a share
over 100 says that the scope does not cover the work or that the bytes are
counted too high: it is reported as it reads, never cut off or left out,
for whoever checks it to see."""

from __future__ import annotations

from benchmark import reduce, resolve


def read(ctx, spec):
    peaks = ctx.get("peaks")
    if not ctx.get("traced") or not peaks:
        return None
    seconds = reduce.read_metric(spec["seconds"], ctx)
    if not seconds:
        return None
    work = {**ctx["cell"]["traffic_file"]["reference"], "d": ctx["config"]["n_params"]}
    least_s = resolve(spec["bytes_fn"])(**work) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
