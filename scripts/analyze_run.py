"""Turn one run directory into a versioned ``run_report.json``.

The critical-path CLI (telemetry/trace.py owns the analysis; this script
only drives it): reads whatever artifacts the run dir holds —
``spans_*.json`` (the per-round stage decomposition), ``metrics.jsonl``
(the anomaly series), ``flight_*`` / ``perf_report.json`` (provenance) —
and writes ``run_report.json`` next to them:

  * per-stage exclusive-time p50/p95 over the analyzed rounds,
  * critical-path attribution fractions summing to 1 (idle included —
    unattributed wall-clock is a finding, not a rounding error),
  * the modal binding stage + per-stage binding counts,
  * anomaly flags: staleness drift (async/staleness_mean), cache-hit
    collapse (clientstore/cache_hit_rate).

    python scripts/analyze_run.py RUN_DIR [RUN_DIR ...] [--out NAME]

``--out`` renames the report file inside each run dir (default
``run_report.json``). The last stdout line is ALWAYS a machine-readable
JSON summary — ``{"kind": "analyze_run", "run_dirs": N, "reports": M,
"failures": [...]}`` — on every exit path, the gate-script contract
scripts/check_bench_regression.py established. Reports validate under
``scripts/check_telemetry_schema.py`` (schema v11 validate_run_report).
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _human_lines(report: dict) -> None:
    stages = report["stages"]
    print(f"{report['run_dir']}: {report['rounds_analyzed']} round(s) "
          f"analyzed, critical stage: {report['critical_stage']}")
    for name, blk in stages.items():
        print(f"  {name:11s} p50 {blk['p50_ms']:9.3f} ms   "
              f"p95 {blk['p95_ms']:9.3f} ms   "
              f"{100.0 * blk['fraction']:5.1f}% of wall")
    for a in report["anomalies"]:
        print(f"  ANOMALY [{a['kind']}] {a['metric']}: {a['detail']}")


def main(argv) -> int:
    def summary_line(**kw):
        print(json.dumps({"kind": "analyze_run", **kw}))

    out_name = "run_report.json"
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv):
            print(__doc__)
            summary_line(run_dirs=0, reports=0, failures=[],
                         error="--out needs a file name")
            return 2
        out_name = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if not argv:
        print(__doc__)
        summary_line(run_dirs=0, reports=0, failures=[],
                     error="usage: pass one or more run dirs")
        return 2

    # heavy import AFTER usage handling so `analyze_run.py` with no args
    # answers instantly even where jax takes seconds to import
    from commefficient_tpu.telemetry import build_run_report, jsonable_tree

    rc = 0
    reports = 0
    failures = []
    for run_dir in argv:
        try:
            report = build_run_report(run_dir,
                                      generated_by="scripts/analyze_run.py")
            path = os.path.join(run_dir, out_name)
            with open(path, "w") as f:
                json.dump(jsonable_tree(report), f, indent=1,
                          allow_nan=False)
            _human_lines(report)
            print(f"wrote {path}")
            reports += 1
        # ValueError covers an empty/corrupt run dir (build_run_report
        # raises it, json decode errors subclass it); OSError an
        # unreadable path — each fails THIS dir and still ends stdout
        # with the summary line instead of a traceback
        except (OSError, ValueError) as e:
            print(f"FAIL {run_dir}: {e}")
            failures.append(f"{run_dir}: {e}")
            rc = 1
    summary_line(run_dirs=len(argv), reports=reports, failures=failures)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
