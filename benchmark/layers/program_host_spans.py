"""``session.<phase>_s_per_round``: seconds per traced round inside one of
the program's own host annotations (``fed/device_put``, ``fed/round_dispatch``
...: ``commefficient_tpu/telemetry/spans.py``), on the trace's clock.

``reduce.load_xplane`` keeps only the benchmark's ``bench/*`` spans of the
host plane, so the same ``.xplane.pb`` (the newest under
``benchmark_out/trace/<cell>/``, where ``run.py`` wrote it) is read again
here for the ``fed/*`` events, and those inside the traced window (between
the two ``bench/fence`` ends) are kept. A program that writes no such span
gives nothing, and the metric is left out of the line."""

from __future__ import annotations

import glob
import os

from benchmark import reduce

TRACE_ROOT = os.path.join(os.path.dirname(reduce.HERE), "benchmark_out", "trace")


def fed_events(path: str) -> list:
    """``[[name, start_s, dur_s], ...]`` of the host planes' ``fed/*``
    events, on the clock ``reduce.load_xplane`` puts the device ops on."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, plane in reduce._fields(space):
        if f != 1:
            continue
        name, lines, names = "", [], {}
        for pf, v in reduce._fields(plane):
            if pf == 2:
                name = reduce._text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                mid, meta = reduce._map_entry(v)
                names[mid] = next(
                    (reduce._text(x) for ef, x in reduce._fields(meta) if ef == 2), "")
        if not name.startswith("/host:CPU"):
            continue
        for line in lines:
            t0, events = 0, []
            for lf, v in reduce._fields(line):
                if lf == 3:
                    t0 = v
                elif lf == 4:
                    events.append(dict(reduce._fields(v)))
            out += [[names[e[1]], t0 * 1e-9 + e.get(2, 0) * 1e-12, e.get(3, 0) * 1e-12]
                    for e in events if names.get(e.get(1), "").startswith("fed/")]
    return out


def seconds_per_round(events, span, lo, hi, rounds):
    inside = [d for n, s, d in events if n == span and lo <= s and s + d <= hi]
    return sum(inside) / rounds if inside else None


def read(ctx, spec):
    traced = ctx.get("traced")
    if not traced:
        return None
    paths = sorted(glob.glob(os.path.join(
        TRACE_ROOT, ctx["cell"]["name"], "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    return seconds_per_round(fed_events(paths[-1]), spec["span"],
                             traced["lo"], traced["hi"], traced["rounds"])
