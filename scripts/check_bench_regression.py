"""Bench regression gate: compare the latest BENCH_*.json to the trajectory.

(Dead since PR 31 deleted ``bench.py``: nothing writes these records any
more, the driver judges ``benchmark/run.py`` against ``PERF_LEDGER.jsonl``.
Kept with its 18 tests as ROADMAP debt D7.)

The repo carries one ``BENCH_rNN.json`` per build round (the driver wraps
bench.py's stdout JSON line in ``{"parsed": {...}}``), but until this
script nothing *read* the trajectory — a 20% throughput regression rode a
green test suite straight into main. This gate compares the LATEST bench
record against the median of the prior ones, metric by metric, with
per-metric noise tolerances, and exits nonzero on regression:

    python scripts/check_bench_regression.py            # repo-root BENCH_r*
    python scripts/check_bench_regression.py --dir D --glob 'BENCH_r*.json'
    python scripts/check_bench_regression.py --tolerance 0.2

Comparison rules:

  * Direction is per metric kind: throughput-ish metrics (``value``,
    ``*_tokens_per_sec``, ``mfu``/``*_mfu``, ``vs_baseline``,
    ``*_vs_uncompressed``) regress DOWN; latency-ish (``*_sec_per_round``)
    regress UP. Everything else (strings, provenance, ``*_error``/
    ``*_skipped`` markers, audited byte counts) is informational.
  * Baseline = MEDIAN of the prior records carrying that metric — robust
    to one outlier round, unlike best-ever (which ratchets noise) or
    last-only (which lets a slow drift through one step at a time).
  * Tolerance: relative, default 15% (the suite's wall-clock measurements
    are load-dependent; CHANGES.md round 3 measured ~40% spread under
    load). Per-metric overrides in ``TOLERANCES``.
  * Apples-to-apples (the bench provenance satellite): prior records whose
    ``chip`` differs from the latest record's are EXCLUDED from the
    baseline — a v4 number is not a regression baseline for a v5e run.
    Records without a ``chip`` key (pre-provenance rounds) are kept.
  * A metric new in the latest record, or with no comparable history, is
    UNGATED — but no longer silently: new metrics are counted in the exit
    summary and the JSON summary line, and ``--max_new_metrics N`` turns
    "more than N gated-direction metrics with no history" into exit 1. A
    renamed metric looks exactly like a new one, so without the guard a
    rename could dodge the gate forever (every round "new", never
    compared); the driver passes the expected churn (usually 0 between
    feature PRs).
  * No BENCH files or only one -> pass (nothing to compare).

The last stdout line is a machine-readable JSON summary:
``{"kind": "bench_regression", "gated": N, "regressions": [...],
"new_metrics": [...], "skipped_chip_records": K}`` — so the driver (and
tests) consume the result without scraping the prose.

Exit codes: 0 pass, 1 regression (or new-metric guard tripped), 2 usage
error. Wired into tier-1 by tests/test_bench_regression.py, which
includes a detects-regression self-test on a synthetic BENCH pair (same
pattern as scripts/check_mode_dispatch.py).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from statistics import median

# default relative noise tolerance; per-metric overrides below (exact
# names, plus the MFU family via tolerance_for's suffix rule)
DEFAULT_TOLERANCE = 0.15
TOLERANCES = {
    # MFU divides two measured quantities of the same run — steadier than
    # raw throughput, so the whole family (mfu, *_mfu, *_audited_mfu)
    # gets a tighter band
    "mfu": 0.10,
    # sketch-gap PR: the headline GPT-2 ratios divide two measurements of
    # the same run on the same mesh (load cancels), so they get the tight
    # band too — this is what makes the 0.6x sketch-vs-uncompressed
    # target TRAJECTORY-enforced: once an optimized record lands, any
    # later drop below median*(1-0.10) fails the gate. The other new
    # gpt2_sketch_* legs (gpt2_sketch_scan_*) gate through the generic
    # suffix rules (_tokens_per_sec/_mfu/_vs_uncompressed all UP);
    # *_rounds_per_dispatch is configuration, not measurement —
    # informational by having no gated suffix.
    "gpt2_sketch_vs_uncompressed": 0.10,
    "gpt2_sketch_scan_vs_uncompressed": 0.10,
    # sparse-aggregate PR: the *_sparse_agg_vs_dense twins divide two
    # measurements of the same run on the same mesh (load cancels) — the
    # tight ratio band, same reasoning as the gpt2 ratios above
    "local_topk_sparse_agg_vs_dense": 0.10,
    "true_topk_sparse_agg_vs_dense": 0.10,
    # asyncfed PR: the update-rate ratio divides two same-mesh
    # measurements (tight band); the time-to-loss ratio folds in the loss
    # trajectory under a stochastic straggler schedule, so it keeps the
    # default wider band (no entry)
    "sketch_async_vs_sync": 0.10,
    # hidden-collectives PR: both overlap ratios divide two same-mesh
    # measurements of the same program shape (load cancels), so they get
    # the tight band — and gate UP: overlapped must not lose to
    # sequential. The band makes the design claim trajectory-enforced,
    # same pattern as sketch_async_vs_sync above.
    "sketch_overlap_layerwise_vs_sequential": 0.10,
    "async_double_buffered_vs_sequential": 0.10,
    # clientstore PR: host-resident client state vs the device-resident
    # twin on the same mesh. Same-run ratio, but the host twin's
    # numerator includes real host-side work (cohort gather + async
    # writeback drain), which is load-dependent in a way the in-graph
    # twins above are not — so it keeps the default 15% band
    # deliberately (no entry would mean the same; this comment is the
    # registration the bench leg's docstring points at).
    "local_topk_hostclient_vs_device": DEFAULT_TOLERANCE,
    # multihost PR: the mesh-faked 2-host round vs its single-host twin
    # on the same devices — a same-run ratio of two same-shape programs
    # (load cancels), so it gets the tight band and gates UP: declaring
    # the host axis must not cost throughput (the tuple-axis psum lowers
    # to ONE all-reduce; tests/test_multihost.py pins the HLO)
    "sketch_multihost_vs_singlehost": 0.10,
}

# pipeline PR: the sketch_pipelined leg's samples/s + occupancy are gated
# (throughput-ish; occupancy falling means the prefetcher stopped hiding
# host time). Its *_host_stall_ms stays INFORMATIONAL on purpose: near-zero
# stalls make relative tolerances meaningless (0.2 ms vs a 0.1 ms median
# is +100% of noise), so the stall regression shows up through occupancy
# and samples/s instead.
# round-tracing PR: the sketch_traced leg's per-stage
# sketch_traced_*_exclusive_ms rows and sketch_traced_wall_ms are
# INFORMATIONAL by the same rule (*_exclusive_ms / *_wall_ms carry no
# gated suffix) — they measure a fenced-every-round diagnostic loop,
# wall-clock-excluded from twin comparisons exactly like the
# xla/exposed_collective_ms family; sketch_traced_critical_stage is a
# stage NAME (string — never gated by construction). A real attribution
# regression shows up through the gated headline/pipelined rows, with
# these rows saying WHICH stage moved.
LOWER_IS_BETTER_SUFFIXES = ("_sec_per_round",)
HIGHER_IS_BETTER_KEYS = ("value", "mfu", "vs_baseline")
HIGHER_IS_BETTER_SUFFIXES = ("_tokens_per_sec", "_mfu", "_vs_uncompressed",
                             "_samples_per_sec", "_occupancy", "_vs_dense",
                             # asyncfed PR: both twins' server-update rates
                             # and the async/sync ratios gate up
                             # (*_time_to_loss_sec itself stays
                             # informational — its ratio carries the gate)
                             "_updates_per_sec", "_rounds_per_sec",
                             "_vs_sync",
                             # hidden-collectives PR: overlapped vs
                             # sequential twins — the ratio gates up
                             # (*_exposed_collective_ms stays
                             # informational: near-zero ms makes relative
                             # bands meaningless, like *_host_stall_ms)
                             "_vs_sequential",
                             # clientstore PR: the hosted round must not
                             # lose to its device-resident twin
                             # (*_cache_hit_rate and *_h2d_stage_ms stay
                             # informational — near-zero ms again, and the
                             # hit rate is config, not performance)
                             "_vs_device",
                             # multihost PR: the mesh-faked 2-host round
                             # must not lose to its flat single-host twin
                             "_vs_singlehost")
# resilience/control PRs: every *_retraces leg gauge is a hard invariant,
# not a throughput — the AOT-prewarm contract says rung switches and
# rollback restores never retrace, so ANY non-zero value fails outright
# (no history or tolerance involved; a relative band on an
# all-zero trajectory would divide by zero anyway)
# elastic-fleet PR: sketch_elastic_retraces joins the family through this
# suffix — a width resize dispatches a prewarmed per-width program, so
# any retrace across the leg's shrink+grow transitions fails outright.
# sketch_elastic_samples_per_sec gates UP via the generic suffix;
# sketch_elastic_resize_ms stays INFORMATIONAL (microsecond-scale
# dispatch-table swaps make relative bands meaningless, the
# *_host_stall_ms rule) and sketch_elastic_resizes is schedule
# configuration, not measurement.
EXACT_ZERO_SUFFIXES = ("_retraces",)


def metric_direction(name: str):
    """'up' (higher is better), 'down' (lower is better), or None
    (informational — never gated)."""
    if name.endswith(LOWER_IS_BETTER_SUFFIXES):
        return "down"
    if name in HIGHER_IS_BETTER_KEYS or name.endswith(
        HIGHER_IS_BETTER_SUFFIXES
    ):
        return "up"
    return None


def load_bench(path: str) -> dict:
    """The metric dict of one BENCH file: the driver wrapper's ``parsed``
    block when present, else the object itself (a raw bench.py line)."""
    with open(path) as f:
        rec = json.load(f)
    if isinstance(rec, dict) and isinstance(rec.get("parsed"), dict):
        rec = rec["parsed"]
    if not isinstance(rec, dict):
        raise ValueError(f"{path}: not a bench record")
    return rec


def tolerance_for(name: str, default: float) -> float:
    if name in TOLERANCES:
        return TOLERANCES[name]
    if name == "mfu" or name.endswith("_mfu"):  # the whole MFU family
        return TOLERANCES["mfu"]
    return default


def check_regression(history, latest, default_tolerance=DEFAULT_TOLERANCE):
    """(regressions, new_metrics, notes) comparing ``latest`` (metric
    dict) against ``history`` (list of metric dicts, oldest first). Each
    regression is a dict naming the metric, direction, latest value,
    baseline and bound; ``new_metrics`` lists the gated-direction metrics
    that had NO comparable history (ungated this round — the
    ``--max_new_metrics`` guard's input)."""
    regressions, new_metrics, notes = [], [], []
    chip = latest.get("chip")
    comparable = []
    for h in history:
        if chip and h.get("chip") and h["chip"] != chip:
            notes.append(
                f"skipping a prior record on {h['chip']!r} "
                f"(latest ran on {chip!r})"
            )
            continue
        comparable.append(h)
    for name, v in sorted(latest.items()):
        if (name.endswith(EXACT_ZERO_SUFFIXES)
                and isinstance(v, (int, float)) and not isinstance(v, bool)):
            if v != 0:
                regressions.append({
                    "metric": name,
                    "direction": "exact_zero",
                    "latest": v,
                    "baseline_median": 0,
                    "bound": 0,
                    "tolerance": 0.0,
                    "n_prior": len(comparable),
                })
            continue
        direction = metric_direction(name)
        if direction is None or not isinstance(v, (int, float)) \
                or isinstance(v, bool):
            continue
        prior = [
            h[name] for h in comparable
            if isinstance(h.get(name), (int, float))
            and not isinstance(h.get(name), bool)
        ]
        if not prior:
            new_metrics.append(name)
            notes.append(f"{name}: no comparable history (new metric?)")
            continue
        base = median(prior)
        tol = tolerance_for(name, default_tolerance)
        if direction == "up":
            bound = base * (1.0 - tol)
            bad = v < bound
        else:
            bound = base * (1.0 + tol)
            bad = v > bound
        if bad:
            regressions.append({
                "metric": name,
                "direction": direction,
                "latest": v,
                "baseline_median": base,
                "bound": bound,
                "tolerance": tol,
                "n_prior": len(prior),
            })
    return regressions, new_metrics, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare the latest BENCH_*.json against the trajectory"
    )
    ap.add_argument("--dir", default=".", help="directory holding the files")
    ap.add_argument("--glob", default="BENCH_r*.json",
                    help="bench-record pattern, sorted lexically "
                    "(BENCH_r01 < BENCH_r02 < ...); the last one is the "
                    "record under test")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="default relative noise tolerance "
                    f"(default {DEFAULT_TOLERANCE}; per-metric overrides "
                    "in TOLERANCES)")
    ap.add_argument("--max_new_metrics", type=int, default=None,
                    help="fail (exit 1) when MORE than this many "
                    "gated-direction metrics have no comparable history — "
                    "a renamed metric reads as 'new' every round and would "
                    "otherwise dodge the gate forever (default: no limit; "
                    "the driver passes the expected churn, usually 0)")
    def summary_line(**kw):
        # machine-readable result, ALWAYS the last stdout line on every
        # exit path (the driver/tests consume this instead of scraping
        # the prose)
        print(json.dumps({"kind": "bench_regression", **kw}))

    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        # argparse already printed usage to stderr; --help exits 0 and
        # keeps argparse's behavior, but a bad/unknown flag must still
        # honor the summary-line contract on stdout
        if e.code in (0, None):
            raise
        summary_line(compared=False, gated=0, regressions=[],
                     new_metrics=[], skipped_chip_records=0,
                     error="argument parsing failed (see usage on stderr)")
        return 2

    def usage_error(msg):
        print(msg)
        summary_line(compared=False, gated=0, regressions=[],
                     new_metrics=[], skipped_chip_records=0, error=msg)
        return 2

    if args.tolerance < 0:
        return usage_error("tolerance must be >= 0")
    if args.max_new_metrics is not None and args.max_new_metrics < 0:
        return usage_error("max_new_metrics must be >= 0")

    paths = sorted(glob.glob(os.path.join(args.dir, args.glob)))
    if len(paths) < 2:
        print(f"nothing to compare ({len(paths)} bench record(s) match "
              f"{args.glob!r} in {args.dir!r}) — pass")
        summary_line(compared=False, gated=0, regressions=[],
                     new_metrics=[], skipped_chip_records=0)
        return 0
    try:
        history = [load_bench(p) for p in paths[:-1]]
        latest = load_bench(paths[-1])
    except (ValueError, json.JSONDecodeError, OSError) as e:
        # the summary-line contract holds on EVERY exit path — a consumer
        # json-parsing the last line must not choke on the prose error
        print(f"unreadable bench record: {e}")
        summary_line(compared=False, gated=0, regressions=[],
                     new_metrics=[], skipped_chip_records=0,
                     error=f"unreadable bench record: {e}")
        return 2
    regressions, new_metrics, notes = check_regression(
        history, latest, args.tolerance
    )
    for n in notes:
        print(f"note: {n}")
    gated = sorted(
        k for k in latest
        if metric_direction(k) and isinstance(latest[k], (int, float))
    )
    n_skipped = len(notes) - len(new_metrics)  # chip-provenance skips
    print(f"latest: {paths[-1]} vs {len(paths) - 1} prior record(s); "
          f"{len(gated)} gated metric(s), {len(new_metrics)} ungated as "
          "new/no-history")
    rc = 0
    for r in regressions:
        arrow = "fell below" if r["direction"] == "up" else "rose above"
        print(
            f"REGRESSION {r['metric']}: {r['latest']:g} {arrow} "
            f"{r['bound']:g} (median of {r['n_prior']} prior: "
            f"{r['baseline_median']:g}, tolerance {r['tolerance']:.0%})"
        )
        rc = 1
    if (args.max_new_metrics is not None
            and len(new_metrics) > args.max_new_metrics):
        print(
            f"NEW-METRIC GUARD: {len(new_metrics)} gated-direction "
            f"metric(s) have no comparable history "
            f"({', '.join(new_metrics)}) — more than the allowed "
            f"{args.max_new_metrics}. A renamed metric dodges the gate as "
            "a perpetual 'new' one; re-register the rename or raise "
            "--max_new_metrics for a round that really adds legs."
        )
        rc = 1
    if rc == 0:
        print("OK — no metric regressed past its tolerance")
    summary_line(compared=True, gated=len(gated), regressions=regressions,
                 new_metrics=new_metrics, skipped_chip_records=n_skipped)
    return rc


if __name__ == "__main__":
    sys.exit(main())
