"""``model.mfu``: the whole step's share of the chip's peak. Operations the
forward and backward passes require per round (the function the
configuration names as ``flops_fn``, ``"module:name"``) over the traced
rounds' seconds per round, on the trace's own clock (its window between the
two fences over the rounds traced), times chips times the peak of
``benchmark/peaks.json``."""

from __future__ import annotations

from benchmark import resolve


def read(ctx, _spec):
    traced, peaks = ctx.get("traced"), ctx.get("peaks")
    if not traced or not peaks:
        return None
    per_unit = resolve(ctx["cell"]["config_file"]["flops_fn"])(**ctx["config"])
    return 100.0 * per_unit * ctx["units_per_round"] / (
        traced["window_s"] / traced["rounds"] * ctx["chips"] * peaks["flops_per_s"])
