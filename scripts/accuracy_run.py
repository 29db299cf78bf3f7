"""North-star evidence run: sketch vs uncompressed accuracy at iso-bytes.

VERDICT r1 item 7: demonstrate the FetchSGD accuracy story on ResNet-9 at
multi-round scale — final accuracy per mode alongside upload bytes/round.
Writes the results table to ACCURACY.md.

Runs on whatever CIFAR-10 is available: the real pickles if present under
--dataset_dir, else the deterministic synthetic stand-in (clearly labelled
— synthetic numbers are pipeline evidence, not paper numbers).

    python scripts/accuracy_run.py [--num_epochs 8] [--dataset_dir ./data]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    from commefficient_tpu.utils.config import AVAILABILITY_MODELS

    ap = argparse.ArgumentParser()
    ap.add_argument("--num_epochs", type=int, default=8)
    ap.add_argument("--dataset_dir", default="./data")
    ap.add_argument("--out", default="ACCURACY.md")
    ap.add_argument("--skip", type=int, default=0,
                    help="crash resume: skip the first N runs and carry "
                         "their rows over from the existing ACCURACY.md "
                         "table")
    ap.add_argument("--variant", default="concentrated",
                    help="synthetic stand-in when real data absent: "
                         "flat|concentrated|concentrated_v2 (v2 = the "
                         "dense-SGD-hostile r2/r3 parameterization; see "
                         "data/cifar.py)")
    ap.add_argument("--telemetry_level", type=int, default=1,
                    choices=(0, 1, 2),
                    help="per-run telemetry (telemetry/ package): level 1 "
                         "writes the loss-vs-BYTES curve — the paper's "
                         "actual x-axis — into each run dir's "
                         "metrics.jsonl (comm/cum_bytes vs train/loss) + "
                         "comm_ledger.json; 0 restores the pre-telemetry "
                         "bit-identical round")
    ap.add_argument("--logdir", default="runs",
                    help="root for the per-run metrics/ledger/flight dirs")
    ap.add_argument("--budget_mb", type=float, default=None,
                    help="hard communication budget (decimal MB of "
                         "cumulative ledger bytes, up + down) applied to "
                         "EVERY run via the control plane "
                         "(control_policy=budget_pacing, no ladder — a "
                         "pure cap): runs that exhaust it stop with "
                         "BudgetExhaustedError and are recorded as honest "
                         "truncated rows (accuracy of the model at the "
                         "stop round), so loss-vs-bytes curves can be "
                         "read at a FIXED byte budget. NB budgeted rows "
                         "change the x-axis semantics — every run ends at "
                         "<= the same cum bytes instead of the same "
                         "round count (see ACCURACY.md).")
    ap.add_argument("--dropout", type=float, default=None,
                    help="fedsim bernoulli per-client dropout probability "
                         "applied to EVERY run: masked clients transmit "
                         "nothing, the server renormalizes by the live "
                         "count, and the ledger counts only live-client "
                         "bytes. NB masked runs log comm/* in FLEET bytes "
                         "(live x per-client), not the classic per-client-"
                         "link units — so for comparable 0%% vs 30%% "
                         "loss-vs-bytes curves run BOTH points through "
                         "this flag (--dropout 0.0 keeps full "
                         "participation but switches to the same fleet "
                         "accounting). Omit the flag entirely for the "
                         "classic per-client table.")
    ap.add_argument("--availability", default=None,
                    choices=sorted(AVAILABILITY_MODELS),
                    help="fedsim availability model for EVERY run (was "
                         "hardwired to bernoulli whenever --dropout was "
                         "given). --dropout still sets the decline "
                         "probability; the model-specific knobs below "
                         "shape who arrives. Passing --availability alone "
                         "(no --dropout) enables the environment at "
                         "dropout 0 in fleet byte units.")
    ap.add_argument("--arrival_rate", type=float, default=1.0,
                    help="poisson model: exponential arrival rate in "
                         "round-deadline units (participation 1-exp(-rate)"
                         "; inf = everyone instant). Also paces the "
                         "asyncfed cohort schedule when --async_buffer "
                         "style runs adopt this table's configs.")
    ap.add_argument("--availability_period", type=int, default=64,
                    help="sine model: rounds per diurnal cycle")
    ap.add_argument("--num_cohorts", type=int, default=4,
                    help="cohort model: number of correlated-outage groups")
    args = ap.parse_args()

    from commefficient_tpu.control import BudgetExhaustedError
    from commefficient_tpu.telemetry import DivergenceError
    from commefficient_tpu.train.cv_train import (
        build_model_and_data,
        build_session_and_sampler,
        train_loop,
    )
    from commefficient_tpu.utils.config import Config
    from commefficient_tpu.utils.logging import MetricsWriter, make_logdir

    base = dict(
        dataset_name="cifar10", dataset_dir=args.dataset_dir, model="resnet9",
        num_epochs=args.num_epochs,
        num_clients=16, num_workers=8, num_devices=1, local_batch_size=64,
        weight_decay=5e-4, seed=42, topk_method="threshold",
        synthetic_variant=args.variant,
        telemetry_level=args.telemetry_level, logdir=args.logdir,
        # the compiled-round audit costs one extra XLA compile PER RUN
        # x a dozen table rows — this suite
        # measures accuracy-vs-bytes, not perf (that is benchmark/run.py's)
        perf_audit=False,
        # same opt-out for the critical-path run report: a dozen table
        # rows would each write a run_report.json into the shared logdir
        # and ACCURACY.md rows would dangle links to whichever survived
        run_report=False,
    )
    if args.dropout is not None or args.availability is not None:
        # fedsim partial participation for the whole table (masking forces
        # the per-client vmap path; fuse_clients flags below are ignored).
        # An EXPLICIT --dropout 0.0 still enables the environment so the
        # ledger uses the same fleet live-byte units as the lossy runs —
        # that is what makes the 0%-vs-30% loss-vs-bytes comparison valid.
        # --availability picks the model (bernoulli stays the --dropout
        # shorthand default) and the model knobs ride along; Config
        # validation rejects nonsensical combinations.
        base.update(availability=args.availability or "bernoulli",
                    dropout_prob=args.dropout or 0.0,
                    arrival_rate=args.arrival_rate,
                    availability_period=args.availability_period,
                    num_cohorts=args.num_cohorts)
    if args.budget_mb is not None:
        # the control plane enforces the cap (controller accounting ==
        # ledger accounting exactly); no ladder -> a single implicit rung,
        # so this is the pure fixed-byte-budget x-axis, not adaptation
        base.update(control_policy="budget_pacing",
                    budget_mb=args.budget_mb)
    k = 50_000
    # Per-mode (lr_scale, pivot_epoch), tuned by scripts/archive/r3_sweep.py — the
    # FetchSGD paper tunes lr per compression config the same way (§5).
    # Momentum modes need ~(1-rho)x the SGD lr: with server momentum the
    # effective step is lr/(1-rho), so rho=0.9 at the SGD-tuned 0.4 was
    # training at effective lr 4.0 and stalling (the r3 pre-sweep table).
    piv = max(2, args.num_epochs // 4)
    # r4: schedules re-tuned on the v3 concentrated task by
    # scripts/archive/r4_retune.py (runs/r4_retune.log) — every grid single-peaked;
    # the v2-task optima transferred almost everywhere (sketch_rho0 and
    # local_topk moved to 0.8; true_topk runs the unmasked-momentum corner
    # whose tuned lr is 0.04 — see the four-corner ablation).
    sched = {
        "uncompressed": (0.8, piv),
        "uncompressed_mom": (0.06, piv),
        "sketch_rho09": (0.04, 2),
        "sketch_rho09_r7": (0.1, 2),
        # r5 fast geometry: chunk m pinned under the adaptive floor +
        # band=24 pool restore — 0.9004 at 1.69x uncompressed wall-clock
        # (runs/r5_sketch5.log; grid 0.06/0.1/0.15 interior at 0.1)
        "sketch_rho09_r7_fast": (0.1, 2),
        "sketch_rho0": (0.8, piv),
        # AUTO dampening now resolves False for true_topk (r4 four-corner
        # ablation) — tuned lr for the unmasked corner
        "true_topk": (0.04, 2),
        "local_topk": (0.8, piv),
        "fedavg": (0.4, piv),
    }

    def mk(name, **kw):
        lr, p = sched[name]
        return Config(lr_scale=lr, pivot_epoch=p, **kw, **base)

    runs = [
        ("uncompressed", mk("uncompressed", mode="uncompressed", fuse_clients=True)),
        ("uncompressed (momentum 0.9)", mk(
            "uncompressed_mom", mode="uncompressed", virtual_momentum=0.9,
            fuse_clients=True)),
        ("sketch (FetchSGD, rho=0.9)", mk(
            "sketch_rho09", mode="sketch", error_type="virtual",
            virtual_momentum=0.9, k=k, num_rows=5, num_cols=500_000,
            fuse_clients=True)),
        ("sketch (FetchSGD, rho=0.9, 7x357k)", mk(
            "sketch_rho09_r7", mode="sketch", error_type="virtual",
            virtual_momentum=0.9, k=k, num_rows=7, num_cols=357_143,
            fuse_clients=True)),
        ("sketch (7x357k, m=4096, band=24 — r5 fast geometry)", mk(
            "sketch_rho09_r7_fast", mode="sketch", error_type="virtual",
            virtual_momentum=0.9, k=k, num_rows=7, num_cols=357_143,
            sketch_m=4096, sketch_band=24, fuse_clients=True)),
        ("sketch (FetchSGD, rho=0)", mk(
            "sketch_rho0", mode="sketch", error_type="virtual",
            virtual_momentum=0.0, k=k, num_rows=5, num_cols=500_000,
            fuse_clients=True)),
        ("true_topk", mk(
            "true_topk", mode="true_topk", error_type="virtual",
            virtual_momentum=0.9, k=k, fuse_clients=True)),
        ("local_topk", mk("local_topk", mode="local_topk", error_type="local", k=k)),
        ("fedavg (4 local iters)", mk("fedavg", mode="fedavg", num_local_iters=4)),
    ]

    pre_rows = []
    if args.skip:
        old = Path(args.out).read_text().splitlines()
        tbl = [
            l for l in old
            if l.startswith("| ")
            and not l.startswith("| mode")
            and not l.startswith("|---")
        ]
        pre_rows = tbl[: args.skip]
        assert len(pre_rows) == args.skip, (
            f"--skip {args.skip} but only {len(pre_rows)} existing rows"
        )
    rows = []
    real = None
    for name, cfg in runs[args.skip:]:
        train, test, real, model, params, loss_fn, augment = build_model_and_data(cfg)
        session, sampler = build_session_and_sampler(
            cfg, train, params, loss_fn, augment
        )
        bpr = session.bytes_per_round()
        from commefficient_tpu.control import controller_header

        writer = MetricsWriter(make_logdir(cfg), cfg=cfg,
                               extra_header=controller_header(session))
        t0 = time.time()
        try:
            val = train_loop(cfg, session, sampler, test, writer)
        except DivergenceError as e:
            # one diverging config must not kill the suite: its flight
            # record has the forensics; the table gets an honest NaN row
            print(f"== {name}: DIVERGED — {e}", flush=True)
            val = {"loss": float("nan")}
        except BudgetExhaustedError as e:
            # the budget stopped the run BEFORE the unaffordable round:
            # the params are finite and every spent byte is within the
            # cap, so the honest truncated row is the model's accuracy AT
            # the stop round (the fixed-budget loss-vs-bytes point),
            # clearly labelled — mirroring the DivergenceError handling
            print(f"== {name}: BUDGET EXHAUSTED — {e}", flush=True)
            val = session.evaluate(test.eval_batches(512))
            name = f"{name} (budget-truncated @ round {e.step})"
        finally:
            writer.close()
        dt = time.time() - t0
        acc = val.get("accuracy", float("nan"))
        rows.append((name, cfg.lr_scale, cfg.pivot_epoch, cfg.dropout_prob,
                     cfg.budget_mb,
                     bpr["upload_bytes"], bpr["download_bytes"],
                     acc, val["loss"], dt))
        print(f"== {name}: acc={acc:.4f} upload={bpr['upload_bytes']:,}B "
              f"({dt:.0f}s)", flush=True)
        _write(args, base, k, rows, real, pre_rows)  # incremental


def _write(args, base, k, rows, real, pre_rows=()):
    label = "REAL CIFAR-10" if real else (
        f"SYNTHETIC CIFAR stand-in, variant={args.variant!r} (real pickles "
        "not on disk; numbers are pipeline/compression-quality evidence, "
        "NOT paper accuracy)")
    lines = [
        "# Accuracy at iso-bytes — ResNet-9 federated CIFAR runs",
        "",
        f"Data: {label}. {base['num_epochs']} epochs, 8 workers/round, "
        f"local batch {base['local_batch_size']}, piecewise-linear lr "
        "TUNED PER MODE by scripts/archive/r4_retune.py (the FetchSGD paper tunes "
        "lr per compression config, §5; momentum modes need ~(1-rho)x the "
        f"SGD lr — see accuracy_run.py). k={k}; sketch rows name their "
        "r x c split (identical table bytes). Produced by "
        "`python scripts/accuracy_run.py` on one TPU v5e chip.",
        "",
        "| mode | lr (peak) | pivot ep | dropout | budget MB | upload B/client/round | download B/round | final val acc | final val loss | train time (s) |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    ncols = lines[-2].count("|")
    for r in pre_rows:
        if r.count("|") != ncols:
            # --skip carries rows verbatim from the existing file; a row
            # written under an older column layout (e.g. pre-dropout-column)
            # would silently shift every cell — refuse instead
            raise SystemExit(
                f"--skip row has {r.count('|') - 1} columns, current table "
                f"has {ncols - 1} (the layout changed since that file was "
                f"written — rerun without --skip): {r}"
            )
    lines.extend(pre_rows)
    for name, lr, pv, drop, budget, up, down, acc, loss, dt in rows:
        budget_cell = f"{budget:g}" if budget else "—"
        lines.append(
            f"| {name} | {lr} | {pv} | {drop:g} | {budget_cell} | {up:,} | "
            f"{down:,} | {acc:.4f} | {loss:.4f} | {dt:.0f} |"
        )
    lines += [
        "",
        "The FetchSGD north star (BASELINE.md) is sketch matching the",
        "uncompressed baseline's accuracy at reduced upload bytes/round —",
        "compare the sketch rows against row 1 at the byte counts shown.",
        "",
        "Budgeted rows (`--budget_mb`, the control/ hard cap) CHANGE the",
        "loss-vs-bytes x-axis semantics: unbudgeted rows all end at the",
        "same ROUND count (cum bytes differ per mode), budgeted rows all",
        "end at <= the same CUM BYTES (round counts differ — cheap modes",
        "run the full schedule, expensive ones stop early as",
        "budget-truncated rows). Compare budgeted rows only against",
        "budgeted rows.",
    ]
    # Preserve any hand-written analysis section in the existing file: the
    # table is regenerated, the narrative (e.g. "## Reading these numbers
    # (r3)" in ACCURACY.md) is NOT this script's to destroy. Synthetic-run
    # narratives must NOT leak into a real-data report, so a real-CIFAR
    # run writes table-only (analyze it fresh).
    out_path = Path(args.out)
    marker = "\n## Reading these numbers"
    if out_path.exists() and not real:
        old = out_path.read_text()
        cut = old.find(marker)
        if cut != -1:
            lines += ["", old[cut:].strip()]
    out_path.write_text("\n".join(lines) + "\n")
    print(f"wrote {args.out} ({len(rows)} rows)", flush=True)


if __name__ == "__main__":
    main()
