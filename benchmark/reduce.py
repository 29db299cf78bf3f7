"""From a profiler trace and the benchmark's spans to per-layer numbers.

The trace is first brought into a plain form (``load_xplane``; the recorded
trace beside the tests is already in it):

    {"devices": {"<id>": [[name, scope, start_s, dur_s], ...]},   # device ops
     "host": [[name, start_s, dur_s], ...]}                        # bench/* spans

``scope`` is the op's ``named_scope`` path as the compiler kept it. Every
reduction below is a pure function of that form, so it can be checked by
hand on a small trace. A reader that finds nothing returns ``None`` and the
metric is left out of the line; nothing here returns 0 for "not found".
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
HOST_SPANS = ("bench/data_wait", "bench/dispatch", "bench/run_ahead_wait", "bench/fence")


# ---- interval arithmetic ----------------------------------------------------

def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The parts of ``intervals`` (merged) that no hole covers."""
    out = []
    holes = union(holes)
    for a, b in union(intervals):
        at = a
        for ha, hb in holes:
            if hb <= at or ha >= b:
                continue
            if ha > at:
                out.append((at, ha))
            at = max(at, hb)
        if at < b:
            out.append((at, b))
    return out


# ---- loading ----------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: ints for varints,
    a memoryview for length-delimited fields; fixed-width ones are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: wire type {wire} is not expected")
        yield key >> 3, v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def load_xplane(path: str) -> dict:
    """The plain form of one ``.xplane.pb`` (an ``XSpace`` message, read
    here field by field: the profiler's Python reader does not give the
    ops' metadata, where the ``named_scope`` path, ``tf_op``, lives). Kept
    are each TPU plane's ``XLA Ops`` line and the ``bench/*`` annotations
    of the host plane, on the trace's one clock."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, host = {}, []
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                k, val = _map_entry(v)
                event_meta[k] = val
            elif pf == 5:
                k, val = _map_entry(v)
                stat_names[k] = next((_text(x) for sf, x in _fields(val) if sf == 2), "")
        m = re.match(r"/device:TPU:(\d+)$", name)
        is_host = name.startswith("/host:CPU")
        if not (m or is_host):
            continue
        names = {}

        def describe(mid):
            if mid not in names:
                ev_name = display = scope = ""
                for ef, v in _fields(event_meta.get(mid, b"")):
                    if ef == 2:
                        ev_name = _text(v)
                    elif ef == 4:
                        display = _text(v)
                    elif ef == 5:
                        stat = dict(_fields(v))
                        if stat_names.get(stat.get(1)) == "tf_op":
                            scope = (_text(stat[5]) if 5 in stat
                                     else stat_names.get(stat.get(7), ""))
                names[mid] = (display or ev_name, scope)
            return names[mid]

        ops = []
        for line in lines:
            line_name, t0, events = "", 0, []
            for lf, v in _fields(line):
                if lf == 2:
                    line_name = _text(v)
                elif lf == 3:
                    t0 = v
                elif lf == 4:
                    events.append(v)
            if m and line_name != "XLA Ops":
                continue
            for ev in events:
                e = dict(_fields(ev))
                ev_name, scope = describe(e.get(1, 0))
                start, dur = t0 * 1e-9 + e.get(2, 0) * 1e-12, e.get(3, 0) * 1e-12
                if m:
                    ops.append([ev_name, scope, start, dur])
                elif ev_name in HOST_SPANS:
                    host.append([ev_name, start, dur])
        if m:
            devices[m.group(1)] = ops
    return {"devices": devices, "host": host}


def load_trace(trace_dir: str, chips: int) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace was written under {trace_dir}")
    return summarize(load_xplane(paths[-1]), chips)


# ---- the reductions -----------------------------------------------------------

def window_of(trace: dict):
    """The traced window: from the end of the first ``bench/fence`` to the
    end of the last (both fences of the traced ``drive``)."""
    fences = sorted((s, s + d) for n, s, d in trace["host"] if n == "bench/fence")
    if len(fences) < 2:
        raise RuntimeError("the trace lacks the two bench/fence spans of its window")
    return fences[0][1], fences[-1][1]


def device_ops(trace: dict, chips: int):
    ids = sorted(trace["devices"], key=int)[:chips]
    if len(ids) < chips or not all(trace["devices"][i] for i in ids):
        raise RuntimeError(f"the trace holds device ops for {ids}, the cell uses {chips} chip(s)")
    return {i: trace["devices"][i] for i in ids}


def busy_union(ops, lo, hi, pattern=None):
    """Seconds in ``[lo, hi]`` during which an op (under a scope matching
    ``pattern``, if given) ran. A union, not a sum: a loop's op and the ops
    of its body overlap in the trace."""
    rx = re.compile(pattern) if pattern else None
    return total(clip(union([(s, s + d) for _n, scope, s, d in ops
                             if rx is None or rx.search(scope)]), lo, hi))


def exposed(ops, lo, hi):
    """Seconds of collective ops during which no other op ran on the device."""
    coll = [(s, s + d) for n, _sc, s, d in ops if COLLECTIVE.search(n)]
    rest = [(s, s + d) for n, _sc, s, d in ops if not COLLECTIVE.search(n)]
    return total(clip(subtract(coll, rest), lo, hi))


def gap_attribution(trace: dict, ops, lo, hi):
    """Idle seconds of one device, by the innermost benchmark span the host
    was in when the gap began; ``inside_program`` where it was in none."""
    busy = union([(s, s + d) for _n, _sc, s, d in ops])
    gaps = subtract([(lo, hi)], busy)
    spans = sorted(((s, s + d, n) for n, s, d in trace["host"]), key=lambda x: x[0])
    out = {}
    for a, b in gaps:
        inside = [x for x in spans if x[0] <= a < x[1]]
        name = max(inside, key=lambda x: x[0])[2] if inside else "inside_program"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def summarize(trace: dict, chips: int) -> dict:
    lo, hi = window_of(trace)
    devs = device_ops(trace, chips)
    busy = [busy_union(ops, lo, hi) for ops in devs.values()]
    by_op, gaps = {}, {}
    for ops in devs.values():
        for n, scope, s, d in ops:
            if min(s + d, hi) > max(s, lo):
                key = f"{n} {scope}"[:96]
                by_op[key] = by_op.get(key, 0.0) + (min(s + d, hi) - max(s, lo)) / chips
        for k, v in gap_attribution(trace, ops, lo, hi).items():
            gaps[k] = gaps.get(k, 0.0) + v / chips
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {"trace": trace, "lo": lo, "hi": hi, "window_s": hi - lo,
            "busy_s": sum(busy) / chips,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)}}


# ---- per-layer readers, found by name -----------------------------------------

def read_metric(name: str, ctx: dict):
    """``benchmark/layers/<name>.json`` says how the metric is read:
    ``{"reduction": "value"}`` takes what the harness measured under that
    name; ``scope_time`` / ``busy_outside_scope`` / ``busy_union`` /
    ``idle_share`` / ``exposed`` reduce the trace (per traced round, averaged over the chips used);
    ``{"reader": "<module>"}`` calls ``benchmark.layers.<module>.read(ctx)``."""
    with open(os.path.join(HERE, "layers", name + ".json")) as f:
        spec = json.load(f)
    if "reader" in spec:
        mod = importlib.import_module(f"benchmark.layers.{spec['reader']}")
        return mod.read(ctx, spec)
    how = spec["reduction"]
    if how == "value":
        return ctx["values"].get(name)
    t = ctx["traced"]
    devs = device_ops(t["trace"], ctx["chips"])
    lo, hi, n = t["lo"], t["hi"], t["rounds"] * len(devs)
    if how == "busy_union":
        return sum(busy_union(o, lo, hi) for o in devs.values()) / n
    if how == "idle_share":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if how in ("scope_time", "busy_outside_scope"):
        found = any(re.search(spec["pattern"], sc) for o in devs.values() for _n, sc, *_ in o)
        inside = sum(busy_union(o, lo, hi, spec["pattern"]) for o in devs.values()) / n
        if how == "scope_time":
            return inside if found else None
        return sum(busy_union(o, lo, hi) for o in devs.values()) / n - inside
    if how == "exposed":
        if not any(COLLECTIVE.search(nm) for o in devs.values() for nm, *_ in o):
            return None
        return sum(exposed(o, lo, hi) for o in devs.values()) / n
    raise ValueError(f"benchmark/layers/{name}.json: unknown reduction {how!r}")


def per_layer(metrics: list, ctx: dict) -> dict:
    out = {}
    for m in metrics:
        v = read_metric(m["name"], ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
