"""``model.<kernel>_mxu_share``: the least time the chip's matrix units could
take for the kernel's work over the device time its scope took. The work's
operations come from the function the metric's file names as ``flops_fn``
(``benchmark/flops_laguna.py``: forward + backward per token position,
recomputation not counted), the peak from ``benchmark/peaks.json``, the
seconds from the metric its file names as ``seconds`` (read the same way,
so the two cannot disagree; the scope's time does hold the recomputed
forward). Reported as it reads, over 100 too, as ``kernel_hbm_share`` does.
A program without the scope, the CPU rehearsal and an untraced run report
nothing."""

from __future__ import annotations

from benchmark import reduce, resolve


def read(ctx, spec):
    peaks = ctx.get("peaks")
    if not ctx.get("traced") or not peaks:
        return None
    seconds = reduce.read_metric(spec["seconds"], ctx)
    if not seconds:
        return None
    per_unit = resolve(spec["flops_fn"])(**ctx["config"])
    least_s = per_unit * ctx["units_per_round"] / (ctx["chips"] * peaks["flops_per_s"])
    return 100.0 * least_s / seconds
