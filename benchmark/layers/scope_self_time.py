"""``scope_self_time``: a scope's own device time, its span less the part of
it that child scopes cover.

A metric's file names two patterns, ``{"reader": "scope_self_time",
"pattern": P, "less": L}``. Per device: the union of the ops whose scope
matches ``P``, less the union of the ops whose scope matches ``L``, clipped
to the traced window; then per traced round and averaged over the chips used,
as ``reduce.read_metric`` does for a ``scope_time``. Where no op matches ``P``
(a program without the scope) and on an untraced run it gives nothing, and the
metric is left out of the line. An op without a scope is given none: a fused
op carries one name, its root's, and this reads the names as they are."""

from __future__ import annotations

import re

from benchmark import reduce


def self_seconds(ops, lo, hi, pattern, less):
    """Seconds of ``[lo, hi]`` in which an op under ``pattern`` ran and none
    under ``less`` did; ``None`` where no op's scope matches ``pattern``."""
    own, child = re.compile(pattern), re.compile(less)
    spans = [(s, s + d) for _n, scope, s, d in ops if own.search(scope)]
    if not spans:
        return None
    holes = [(s, s + d) for _n, scope, s, d in ops if child.search(scope)]
    return reduce.total(reduce.clip(reduce.subtract(spans, holes), lo, hi))


def read(ctx, spec):
    t = ctx.get("traced")
    if not t:
        return None
    devs = reduce.device_ops(t["trace"], ctx["chips"])
    per_device = [self_seconds(ops, t["lo"], t["hi"], spec["pattern"], spec["less"])
                  for ops in devs.values()]
    if all(s is None for s in per_device):
        return None
    return sum(s or 0.0 for s in per_device) / (t["rounds"] * len(devs))
