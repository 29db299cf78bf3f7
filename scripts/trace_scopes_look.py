"""What a cell's device trace holds under each vocabulary, by op.

    python3 scripts/trace_scopes_look.py <cell> [--root <checkout>] [--out f.json]

Reads the newest ``.xplane.pb`` a ``benchmark/run.py --trace 1`` run left
under ``<root>/benchmark_out/trace/<cell>/`` (``benchmark/reduce.py``'s plain
form) and prints one JSON object, seconds a traced round:

- ``client_grad_unnamed``: the longest ops whose scope holds ``client_grad``
  and no name of ``MODEL_SCOPES`` (what ``model.unnamed_s_per_round`` reads);
- ``model_outside_round``: the ops whose scope holds a name of
  ``MODEL_SCOPES`` and none of ``ROUND_SCOPES`` (a loop's body, lowered from
  the model's scope down), by model scope
  (``model.outside_client_grad_s_per_round``);
- ``nameless``: the longest ops with no scope at all, and
  ``nameless_uncovered``: the ops under no name of either list by the time no
  named op covers (what ``round.nameless_s_per_round`` is made of: a loop's
  own op runs over its named body and counts for what the body leaves);
- ``loops``: every ``while`` op of a millisecond a round or more, with the
  named ops that run inside its interval summed by scope: which loop it is;
- ``sums``: both closures written out (the named model scopes' union, the
  self times, ``round.unscoped`` and its two parts), each computed here from
  the trace with ``reduce``'s interval arithmetic, for a trace whose program
  or whose benchmark files lack the metrics;
- ``by_scope``: each name of the two lists, the union of its ops.

Reading only: nothing here is a metric, and the names come from
``telemetry/trace.py`` as imported.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reduce  # noqa: E402
from benchmark.run import RUN_AHEAD  # noqa: E402
from benchmark.layers.scope_self_time import self_seconds  # noqa: E402
from commefficient_tpu.telemetry.trace import (  # noqa: E402
    LATER_MODEL_SCOPES, MODEL_SCOPES, ROUND_SCOPES)
MODEL_SCOPES = MODEL_SCOPES + LATER_MODEL_SCOPES   # every model name the look reads

WHOLE_WORDS = {"encode", "embed"}   # as the benchmark's patterns have them
NAME_RX = {n: re.compile(rf"\b{n}\b" if n in WHOLE_WORDS else n)
           for n, _ in ROUND_SCOPES + MODEL_SCOPES}


def joined(scopes) -> str:
    return "|".join(NAME_RX[n].pattern for n, _ in scopes)


def span(ops):
    return [(s, s + d) for _n, _sc, s, d in ops]


def seconds(intervals, lo, hi, rounds):
    return reduce.total(reduce.clip(reduce.union(intervals), lo, hi)) / rounds


def longest(ops, lo, hi, rounds, count):
    """``[[name, scope, seconds a round, calls a round], ...]`` by (name, scope)."""
    by = {}
    for n, sc, s, d in ops:
        inside = min(s + d, hi) - max(s, lo)
        if inside > 0:
            t = by.setdefault((n, sc), [0.0, 0])
            t[0] += inside / rounds
            t[1] += 1
    rows = sorted(by.items(), key=lambda kv: -kv[1][0])[:count]
    return [[n, sc, t, c / rounds] for (n, sc), (t, c) in rows]


def longest_uncovered(ops, cover, lo, hi, rounds, count):
    """As ``longest``, each op counted for the part of its interval in which
    no op of ``cover`` runs: a loop's own op under its named body reads what
    the body leaves."""
    merged = reduce.union(span(cover))
    starts = [a for a, _b in merged]
    by = {}
    for n, sc, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        j = bisect.bisect_left(starts, b)
        left = reduce.total(reduce.subtract([(a, b)], merged[i:j]))
        if left > 0:
            t = by.setdefault((n, sc), [0.0, 0])
            t[0] += left / rounds
            t[1] += 1
    rows = sorted(by.items(), key=lambda kv: -kv[1][0])[:count]
    return [[n, sc, t, c / rounds] for (n, sc), (t, c) in rows]


def first_name(scope, names):
    """The name of ``names`` that comes first in the scope's path."""
    hits = [(m.start(), n) for n in names for m in [NAME_RX[n].search(scope)] if m]
    return min(hits)[1] if hits else None


def look(trace, rounds=None, top=15):
    lo, hi = reduce.window_of(trace)
    ops = reduce.device_ops(trace, 1)
    ops = ops[sorted(ops, key=int)[0]]
    if rounds is None:   # drive() waits for round i - RUN_AHEAD before it dispatches round i + 1
        rounds = RUN_AHEAD + sum(1 for n, s, d in trace["host"]
                                 if n == "bench/run_ahead_wait" and lo <= s < hi)
    round_pattern, model_pattern = joined(ROUND_SCOPES), joined(MODEL_SCOPES)
    round_rx, model_rx = re.compile(round_pattern), re.compile(model_pattern)
    model_names = [n for n, _ in MODEL_SCOPES]
    round_names = [n for n, _ in ROUND_SCOPES]
    in_grad = [o for o in ops if "client_grad" in o[1]]
    model = [o for o in ops if model_rx.search(o[1])]
    named = [o for o in ops if round_rx.search(o[1]) or model_rx.search(o[1])]
    per = lambda iv: seconds(iv, lo, hi, rounds)  # noqa: E731
    busy, grad_u, model_u = per(span(ops)), per(span(in_grad)), per(span(model))
    unscoped = busy - per(span([o for o in ops if round_rx.search(o[1])]))
    outside = [o for o in model if not round_rx.search(o[1])]
    out = {
        "rounds": rounds, "window_s_per_round": (hi - lo) / rounds,
        "sums": {
            "busy": busy, "model.fwd_bwd": grad_u,
            "model_scopes_union": model_u,
            # the reader's own function, as the two metrics' files call it
            "model.unnamed": (self_seconds(ops, lo, hi, "client_grad", model_pattern)
                              or 0.0) / rounds,
            "model.outside_client_grad": (self_seconds(ops, lo, hi, model_pattern, "client_grad")
                                          or 0.0) / rounds,
            "round.unscoped": unscoped,
            "round.nameless": busy - per(span(named)),
        },
        "by_scope": {n: per(span([o for o in ops if NAME_RX[n].search(o[1])]))
                     for n in round_names + model_names},
        "client_grad_unnamed": longest(
            [o for o in in_grad if not model_rx.search(o[1])], lo, hi, rounds, top),
        "model_outside_round": {
            n: per(span(under)) for n in model_names
            for under in [[o for o in outside if NAME_RX[n].search(o[1])]] if under},
        "model_outside_round_longest": longest(outside, lo, hi, rounds, top),
        "nameless": longest([o for o in ops if not o[1]], lo, hi, rounds, 10),
        # what round.nameless_s_per_round is made of: the ops under no name of
        # either list, by the time no named op covers
        "nameless_uncovered": longest_uncovered(
            [o for o in ops if not (round_rx.search(o[1]) or model_rx.search(o[1]))],
            named, lo, hi, rounds, 2 * top),
        "longest": longest(ops, lo, hi, rounds, 60),
    }
    loops = {}
    for o in ops:
        if o[0].lstrip("%").startswith("while") and min(o[2] + o[3], hi) > max(o[2], lo):
            loops.setdefault((o[0], o[1]), []).append((o[2], o[2] + o[3]))
    out["loops"] = []
    for (name, scope), ivs in sorted(loops.items(), key=lambda kv: -reduce.total(kv[1])):
        if reduce.total(ivs) / rounds < 0.001:
            continue
        inside = [o for o in ops if (o[0], o[1]) != (name, scope)
                  and any(a <= o[2] and o[2] + o[3] <= b for a, b in ivs)]
        by = {}
        for o in inside:
            key = first_name(o[1], model_names) or first_name(o[1], round_names) or "(none)"
            by.setdefault(key, []).append(o)
        out["loops"].append({
            "name": name, "scope": scope, "s_per_round": per(ivs),
            "calls_per_round": len(ivs) / rounds,
            "inside_by_scope": {k: per(span(v)) for k, v in sorted(by.items())},
            "inside_keeps_client_grad": per(span([o for o in inside if "client_grad" in o[1]])),
            "inside_longest": longest(inside, lo, hi, rounds, 6),
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--root", default=ROOT, help="the checkout whose benchmark_out holds the trace")
    ap.add_argument("--rounds", type=int, default=None,
                    help="traced rounds (default: the bench/run_ahead_wait spans in the window "
                         "and the rounds run ahead)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    paths = sorted(glob.glob(os.path.join(
        args.root, "benchmark_out", "trace", args.cell, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        print(json.dumps({"cell": args.cell, "error": "no trace"}))
        return 1
    out = {"cell": args.cell, **look(reduce.load_xplane(paths[-1]), args.rounds)}
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text if not args.out else json.dumps({"cell": args.cell, "sums": out["sums"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
