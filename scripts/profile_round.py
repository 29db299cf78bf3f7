"""Stage-level timing of the bench round on the real chip (VERDICT r1 item 2).

Times each stage of the federated sketch round separately between fences
(utils.profiling.fence), so the
perf work attacks measured hot spots instead of guesses. The sketch /
estimate / unsketch phases are timed for BOTH CountSketch backends
(einsum and pallas — ops/pallas/) so the r5 sketch-round gap is tracked
at phase granularity, and the server-DECODE phases (PR 6) are split
dense vs sharded-slice. ``--d`` runs the phase split at
an arbitrary dimension — e.g. GPT-2 scale:

    python scripts/profile_round.py --d 124000000 --shards 8

times the decode phases at D=124M (c defaults to D/25, the stability
envelope floor) without needing a CV model of that size. Run it on the
chip (one process per chip):

    python scripts/profile_round.py [--dtype bfloat16] [--reps 10] \
        [--sketch_backend pallas] [--d N] [--num_cols C] [--shards W]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.flatten_util  # noqa: F401 — binds jax.flatten_util for the stages
import jax.numpy as jnp
import numpy as np

# shared micro-bench helpers (moved to utils.profiling so bench.py and the
# telemetry span recorder use the same fencing/warmup discipline; timeit
# now warms MIN_WARMUP_STEPS=2 calls — one warm call left the second
# donated-buffer layout uncompiled, so the first timed rep paid a compile
# on donated paths)
from commefficient_tpu.utils.profiling import fence, timeit  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument(
        "--sketch_backend", default="einsum", choices=("einsum", "pallas"),
        help="backend for the full-round ground-truth section; the "
        "per-phase sketch/unsketch breakdown always times BOTH backends",
    )
    ap.add_argument(
        "--mode", default="sketch", choices=("sketch", "powersgd"),
        help="compressor for the full-round ground-truth section (the "
        "sketch phase breakdown always runs; powersgd adds its own "
        "matricize/GS/reconstruct phase lines)",
    )
    ap.add_argument("--powersgd_rank", type=int, default=4)
    ap.add_argument(
        "--telemetry_level", type=int, default=0, choices=(0, 1, 2),
        help="telemetry level for the full-round ground-truth section: "
        "0 is the bit-identical pre-telemetry round (the default, so the "
        "headline number IS the no-overhead acceptance measurement); 1/2 "
        "time the in-graph diagnostics tax (level 2 adds the sketch "
        "round-trip fidelity / powersgd reconstruction residual)",
    )
    ap.add_argument(
        "--profile_rounds", default="",
        help="'A-B' inclusive round window arming a programmatic "
        "jax.profiler capture over the traced ground-truth rounds (the "
        "same telemetry.trace.ProfilerWindow --profile_rounds wires into "
        "the train loop: clamped past warmup, fenced at entry/exit, "
        "degrades with a named reason where the backend cannot trace); "
        "the trace lands in ./profile_round_trace",
    )
    ap.add_argument(
        "--d", type=int, default=0,
        help="override the sketch dimension for the phase split (0 = the "
        "ResNet-9 D). Set 124_000_000 to run the decode phases at GPT-2 "
        "scale — the model/ground-truth sections are skipped then (no "
        "CV model exists at that D; the decode numbers are the point)",
    )
    ap.add_argument(
        "--num_cols", type=int, default=0,
        help="sketch columns for the phase split (0 = 500k at CV scale, "
        "d//25 under --d — the stability envelope's c >= D/25 floor)",
    )
    ap.add_argument(
        "--shards", type=int, default=8,
        help="worker-mesh width W the sharded-decode phase lines model: "
        "each line times ONE shard's d/W slice work (the per-chip cost "
        "of the sharded decode; its collectives are scalar-only + one "
        "~W*k gather, negligible next to the slice work)",
    )
    args = ap.parse_args()

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.ops import ravel_params
    from commefficient_tpu.ops.countsketch import (
        CountSketch, estimate_all, estimate_at, sketch_sparse, sketch_vec,
        unsketch_sparse,
    )
    from commefficient_tpu.ops.topk import compact_nonzero

    print(f"devices: {jax.devices()}")
    workers, batch = 8, 256  # the bench r2 shape (2048 samples/round)
    if args.d:
        # decode-phase-only run at an arbitrary D (the GPT-2-scale split
        # VERDICT r5 asked for): no CV model exists at this dimension, so
        # the model fwd/bwd + powersgd + ground-truth sections are skipped
        model = params = loss_fn = vec = unravel = None
        d = args.d
    else:
        model = ResNet9(num_classes=10)
        params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        loss_fn = classification_loss(model.apply)
        vec, unravel = ravel_params(params)
        d = int(vec.size)
    num_cols = args.num_cols or (max(500_000, d // 25) if args.d else 500_000)
    print(f"D = {d}")
    spec = CountSketch(
        d=d, c=num_cols, r=5, seed=42,
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
    )
    print(f"table: {spec.table_shape} (c_actual={spec.c_actual}, s={spec.s}, nc={spec.nc})")

    rng = np.random.default_rng(0)
    if not args.d:
        x = jnp.asarray(rng.normal(size=(workers * batch, 32, 32, 3)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 10, size=(workers * batch,)).astype(np.int32))
    v = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    k = 50_000
    idx = jnp.asarray(rng.choice(d, size=k, replace=False).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))

    if not args.d:

        @jax.jit
        def fwd_bwd(pv, x, y):
            p = unravel(pv)
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, {"x": x, "y": y})
            g, _ = jax.flatten_util.ravel_pytree(grads)
            return g

        @jax.jit
        def per_worker_fwd_bwd(pv, x, y):
            # the actual bench shape: vmap over `workers` grads of `batch` each
            xs = x.reshape(workers, batch, 32, 32, 3)
            ys = y.reshape(workers, batch)
            gs = jax.vmap(lambda xx, yy: fwd_bwd(pv, xx, yy))(xs, ys)
            return jnp.sum(gs, 0)

    from commefficient_tpu.ops.countsketch import unsketch_dense
    from commefficient_tpu.ops.topk import topk_threshold_dense

    topk_j = jax.jit(lambda e: jax.lax.top_k(jnp.abs(e), k)[1])
    approx_j = jax.jit(lambda e: jax.lax.approx_max_k(jnp.abs(e), k)[1])
    thr_j = jax.jit(lambda e: topk_threshold_dense(e, k))
    ssp_j = jax.jit(lambda i, va: sketch_sparse(spec, i, va))
    scatter_j = jax.jit(lambda i, va: jnp.zeros(d, jnp.float32).at[i].set(va))

    r = args.reps
    t_modelw = 0.0
    if not args.d:
        timeit(f"fwd+bwd batch {workers*batch} (monolithic)", fwd_bwd, vec, x, y, reps=r)
        t_modelw = timeit(f"fwd+bwd {workers}x{batch} (vmap per-worker)", per_worker_fwd_bwd, vec, x, y, reps=r)

    # -- sketch/unsketch phase split, BOTH backends ------------------------
    # (the r5 VERDICT gap is a kernel property: the einsum path pays the
    # [m, V] one-hot constant + [nc, V] HBM round-trip + [d_eff] signs,
    # the Pallas path generates all three on the fly in-kernel). Off-TPU
    # the pallas legs run under interpret mode — minutes per call at this
    # d, meaningless as perf data — so they auto-skip there (same policy
    # as bench.py's GPT-2 legs; --sketch_backend pallas forces them).
    backends = ("einsum", "pallas")
    if jax.devices()[0].platform != "tpu" and args.sketch_backend != "pallas":
        print("[pallas] phase legs skipped on non-TPU host "
              "(pass --sketch_backend pallas to force interpret-mode timing)")
        backends = ("einsum",)
    phase = {}
    for backend in backends:
        sp = spec._replace(backend=backend)
        sketch_j = jax.jit(lambda v, sp=sp: sketch_vec(sp, v))
        est_j = jax.jit(lambda t, sp=sp: estimate_all(sp, t))
        unsk_j = jax.jit(lambda t, sp=sp: unsketch_sparse(sp, t, k))
        unskd_j = jax.jit(lambda t, sp=sp: unsketch_dense(sp, t, k))
        table = sketch_j(v)
        est = est_j(table)
        t_sk = timeit(f"[{backend}] sketch_vec (dense d)", sketch_j, v, reps=r)
        t_est = timeit(f"[{backend}] estimate_all", est_j, table, reps=r)
        timeit(f"[{backend}] unsketch_sparse (est+top_k)", unsk_j, table, reps=r)
        t_unskd = timeit(f"[{backend}] unsketch_dense (est+threshold)",
                         unskd_j, table, reps=r)
        phase[backend] = (t_sk, t_est, t_unskd)
        if backend == "einsum":
            # selection-kernel lines are backend-independent (they consume
            # the estimate vector) — time them once
            timeit("lax.top_k k=50k over d", topk_j, est, reps=r)
            timeit("approx_max_k k=50k over d", approx_j, est, reps=r)
            timeit("topk_threshold_dense k=50k", thr_j, est, reps=r)
            timeit("sketch_sparse k=50k (scatter)", ssp_j, idx, vals, reps=r)
            timeit("dense scatter of k", scatter_j, idx, vals, reps=r)

    # -- server-decode phase lines (PR 6: dense vs sharded) ----------------
    # The dense decode line is the per-chip cost EVERY chip of a
    # replicated mesh pays redundantly (est_all + threshold + the error
    # feedback's re-sketch); the sharded line is ONE shard's d/W slice of
    # the same extraction (estimate_at over offset global hashes +
    # threshold passes + candidate compaction + the slice sketch_sparse)
    # — its cross-chip traffic is scalar bisection collectives + one ~W*k
    # gather, negligible next to the slice work, so the single-device
    # stand-in here times the real per-chip decode cost.
    W = args.shards
    S = -(-d // W)
    sidx = jnp.minimum(jnp.arange(S, dtype=jnp.int32), d - 1)
    table = jax.jit(lambda vv: sketch_vec(spec, vv))(v)

    dense_dec_j = jax.jit(
        lambda t: sketch_vec(spec, unsketch_dense(spec, t, k))
    )

    def shard_decode(t):
        est = estimate_at(spec, t, sidx)
        sel = topk_threshold_dense(est, k)
        loc, val = compact_nonzero(sel, k)
        return sketch_sparse(spec, jnp.minimum(loc, d - 1), val)

    timeit("[decode dense] est_all+threshold+resketch (per chip)",
           dense_dec_j, table, reps=r)
    timeit(f"[decode sharded W={W}] per-shard slice "
           "(est_at+thr+compact+slice-sketch)",
           jax.jit(shard_decode), table, reps=r)
    # -- aggregation phase lines (sparse-allreduce PR) ---------------------
    # single-device stand-ins, same convention as the decode lines above:
    # the dense line is the W-way [D] reduction every chip's all-reduce
    # realizes; the sparse line is the pair-exchange realization — compact
    # each chip's <= k-sparse transmit, then scatter-add all W*k gathered
    # (idx, val) pairs into the dense aggregate. Cross-chip it moves
    # O(W*k) elements instead of O(D); on one chip the lines compare the
    # two realizations' arithmetic.
    from commefficient_tpu.ops.collectives import scatter_add_pairs
    from commefficient_tpu.ops.topk import (
        compact_nonzero as _compact,
        topk_threshold_dense as _thr_dense,
    )

    sparse_bufs = jax.jit(jax.vmap(lambda key: _thr_dense(
        jax.random.normal(key, (d,)), k)))(
            jax.random.split(jax.random.key(0), W))

    def dense_agg(bufs):
        return jnp.sum(bufs, axis=0)

    def sparse_agg(bufs):
        loc, val = jax.vmap(lambda b: _compact(b, k))(bufs)
        return scatter_add_pairs(d, loc.reshape(-1), val.reshape(-1))

    timeit(f"[aggregate dense] W-way [D] reduction (W={W})",
           jax.jit(dense_agg), sparse_bufs, reps=r)
    timeit(f"[aggregate sparse W={W}] compact + {W}x{k // 1000}k-pair "
           "scatter-add",
           jax.jit(sparse_agg), sparse_bufs, reps=r)

    # -- sketch-fused backward phase line (sketch-gap PR) ------------------
    # the fused path produces the grad DIRECTLY as a table (per-leaf
    # custom_vjp cotangent sketches — no flat [D] concat, no separate
    # sketch pass); its honest comparator is the dense path's grad +
    # sketch_vec SUM, which is what the legacy round pays per device.
    if not args.d:
        from commefficient_tpu.parallel.round import make_sketch_grad_one
        from commefficient_tpu.utils.config import Config as _Cfg

        _fb_cfg = _Cfg(mode="sketch", error_type="virtual", k=k,
                       num_rows=5, num_cols=num_cols,
                       topk_method="threshold", fuse_clients=True,
                       sketch_fused_bwd=True, weight_decay=0.0,
                       num_clients=2 * workers, num_workers=workers,
                       local_batch_size=batch)

        grad_table = jax.jit(
            make_sketch_grad_one(_fb_cfg, loss_fn, unravel, spec,
                                 d=d)
        )
        bflat = {"x": x, "y": y}
        dense_then_sketch = jax.jit(
            lambda pv, xx, yy: sketch_vec(spec, fwd_bwd(pv, xx, yy))
        )
        timeit(f"[sketch fused-bwd] grad->table (batch {workers*batch})",
               lambda pv, b: grad_table(pv, b, None)[0], vec, bflat,
               reps=r)
        timeit("[sketch fused-bwd] dense grad + sketch_vec (comparator)",
               dense_then_sketch, vec, x, y, reps=r)

    print()
    for backend, (t_sk, t_est, t_unskd) in phase.items():
        total = t_modelw + t_sk + t_unskd + t_sk
        print(f"[{backend}] round ≈ model {t_modelw:.1f} + sketch {t_sk:.1f} "
              f"+ unsketch_dense {t_unskd:.1f} (est {t_est:.1f} + select "
              f"{t_unskd - t_est:.1f}) + resketch {t_sk:.1f} = {total:.1f} ms"
              f" -> {workers * batch / total * 1e3:,.0f} samples/s "
              f"(bench does {workers * batch}/round)")
    if args.d:
        return  # decode-phase-only run (no CV model at this D)

    # -- powersgd phase split (PR 2: compress/powersgd.py) -----------------
    # the server-side cost the mode adds per round: matricize + P = M Q +
    # Gram-Schmidt + Q_new = M^T P_hat + rank-r reconstruct — all MXU work
    from commefficient_tpu.compress.powersgd import gram_schmidt, matrix_shape

    n_rows_m, m_cols_m = matrix_shape(d)
    rank = args.powersgd_rank
    q0 = jnp.asarray(rng.normal(size=(m_cols_m, rank)).astype(np.float32))

    @jax.jit
    def powersgd_approx(vec, Q):
        M = jnp.pad(vec, (0, n_rows_m * m_cols_m - d)).reshape(
            n_rows_m, m_cols_m)
        P_hat = gram_schmidt(M @ Q)
        Q_new = M.T @ P_hat
        return (P_hat @ Q_new.T).reshape(-1)[:d], Q_new

    gs_j = jax.jit(gram_schmidt)
    p0 = jnp.asarray(rng.normal(size=(n_rows_m, rank)).astype(np.float32))
    timeit(f"[powersgd] GS orthonormalize [n={n_rows_m}, r={rank}]",
           gs_j, p0, reps=r)
    t_psgd = timeit(
        f"[powersgd] full approx (matricize+P+GS+Q+reconstruct) r={rank}",
        powersgd_approx, v, q0, reps=r)
    total = t_modelw + t_psgd
    print(f"[powersgd] round ≈ model {t_modelw:.1f} + approx {t_psgd:.1f} "
          f"= {total:.1f} ms -> {workers * batch / total * 1e3:,.0f} "
          f"samples/s")

    # ground truth: the EXACT bench config (bench.py r2: fuse_clients,
    # batch 256, num_blocks 1) so this number reconciles against bench.py
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.config import Config

    bench_batch = batch  # == the bench r2 shape profiled above
    common = dict(error_type="virtual", virtual_momentum=0.9,
                  topk_method="threshold", fuse_clients=True,
                  num_clients=2 * workers, num_workers=workers,
                  num_devices=1, local_batch_size=bench_batch,
                  weight_decay=5e-4, telemetry_level=args.telemetry_level)
    if args.mode == "powersgd":
        cfg = Config(mode="powersgd", powersgd_rank=rank, **common)
    else:
        cfg = Config(mode="sketch", k=k, num_rows=5, num_cols=500_000,
                     num_blocks=1, sketch_backend=args.sketch_backend,
                     **common)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    ids = jnp.arange(workers, dtype=jnp.int32)
    data = {"x": jnp.asarray(rng.normal(
                size=(workers, bench_batch, 32, 32, 3)).astype(np.float32)),
            "y": jnp.asarray(rng.integers(
                0, 10, size=(workers, bench_batch)).astype(np.int32))}
    # compiled-round audit (telemetry/xla_audit.py): the artifact's OWN
    # FLOPs/peak-HBM/collective numbers printed next to the measured lines
    # so the hand model and the compiler can be diffed (ISSUE 7); the
    # audit's AOT trace doubles as the round's first compile-cache fill
    try:
        audit = session.audit_compiled_round(np.asarray(ids), data, 0.1)
        print(audit.describe())
        if audit.cost.get("flops") is not None:
            from commefficient_tpu.telemetry.xla_audit import chip_peak_flops

            peak, kind = chip_peak_flops()
            floor_ms = audit.cost["flops"] / peak * 1e3
            print(f"[audited] {audit.cost['flops'] / 1e9:.2f} GFLOP/round "
                  f"-> compute-bound floor {floor_ms:.3f} ms on {kind}")
    except Exception as e:  # noqa: BLE001 — the audit must not kill the lab
        print(f"[audited] compiled-round audit unavailable: {e}")
    # -- asyncfed phase lines (buffered-async PR) --------------------------
    # the engine's round splits into cohort LAUNCH (one cohort's W
    # per-client grads + encode — device work paid once per cohort, then
    # amortized over ceil(W/K) server updates), ARRIVAL (the host-side
    # continuous-time schedule simulation + per-update slot bookkeeping —
    # the only work the buffered-async layer adds on the critical path),
    # and APPLY (the staleness-weighted K-row server update). These lines
    # dispatch THE compiled pair the engine itself reuses
    # (session.async_round_fns), so the split reconciles against
    # AsyncFederation's async_launch/async_apply spans.
    if args.mode == "sketch":
        try:
            from commefficient_tpu.asyncfed import AsyncSchedule

            K, C = workers // 2, 2
            acfg = cfg.replace(fuse_clients=False, async_buffer=K,
                               async_concurrency=C, staleness_exponent=0.5)
            asess = FederatedSession(acfg, params, loss_fn, mesh=make_mesh(1))
            launch_fn, apply_fn = asess.async_round_fns()
            ast = asess.state
            t0 = time.perf_counter()
            for _ in range(r):
                sch = AsyncSchedule(seed=acfg.seed, num_workers=workers,
                                    buffer_k=K, concurrency=C,
                                    arrival_rate=1.0, num_updates=50)
            dt_arr = (time.perf_counter() - t0) / r * 1e3
            print(f"[async arrival] 50-update host schedule (K={K}, C={C}): "
                  f"{dt_arr:.2f} ms ({dt_arr / 50 * 1e3:.0f} us/update)")
            launch_j = lambda: launch_fn(  # noqa: E731
                ast.params_vec, ast.client_vel, ast.client_err, ids, data,
                jnp.int32(0), jnp.float32(0.1))
            out = launch_j()
            fence(out[3])
            t0 = time.perf_counter()
            for _ in range(r):
                out = launch_j()
            fence(out[3])
            dt_l = (time.perf_counter() - t0) / r * 1e3
            print(f"[async launch] cohort W={workers} grads+encode: "
                  f"{dt_l:.2f} ms")
            weights = jnp.ones((workers,), jnp.float32)
            # donated first arg: thread the returned state back through
            ast, m = apply_fn(ast, *out, ids, weights,
                              jnp.float32(workers), jnp.float32(0.1))
            fence(m["loss"])
            t0 = time.perf_counter()
            for _ in range(r):
                ast, m = apply_fn(ast, *out, ids, weights,
                                  jnp.float32(workers), jnp.float32(0.1))
            fence(m["loss"])
            dt_a = (time.perf_counter() - t0) / r * 1e3
            print(f"[async apply] staleness-weighted {workers}-row server "
                  f"update: {dt_a:.2f} ms (launch amortized over "
                  f"~{-(-workers // K)} updates -> "
                  f"{dt_l / -(-workers // K) + dt_a:.2f} ms/update)")
            # double-buffer twin (hide-the-collectives PR): the sequential
            # engine fences each apply's loss before dispatching the next
            # update; the double-buffered engine (--async_double_buffer)
            # defers that fence one update, so update i+1 is already in
            # XLA's queue while apply i's collectives run. The twin lines
            # time the same apply chain under both fence disciplines — the
            # delta is the host stall the deferred fence removes.
            t0 = time.perf_counter()
            for _ in range(r):
                ast, m = apply_fn(ast, *out, ids, weights,
                                  jnp.float32(workers), jnp.float32(0.1))
                fence(m["loss"])  # per-update fence = sequential engine
            dt_seq = (time.perf_counter() - t0) / r * 1e3
            print(f"[async sequential] apply + per-update fence: "
                  f"{dt_seq:.2f} ms/update")
            prev = None
            t0 = time.perf_counter()
            for _ in range(r):
                ast, m = apply_fn(ast, *out, ids, weights,
                                  jnp.float32(workers), jnp.float32(0.1))
                if prev is not None:
                    fence(prev)  # drained AFTER the next apply dispatches
                prev = m["loss"]
            fence(prev)
            dt_db = (time.perf_counter() - t0) / r * 1e3
            print(f"[async double-buffer] apply + deferred fence: "
                  f"{dt_db:.2f} ms/update (overlap delta "
                  f"{dt_seq - dt_db:+.2f} ms/update)")
        except Exception as e:  # noqa: BLE001 — lab line, never kills the run
            print(f"[async] phase lines unavailable: {e}")

    round_fn = session.round_fn
    n = 10

    @jax.jit
    def run_rounds(state):
        def body(s, _):
            s2, m = round_fn(s, ids, data, jnp.float32(0.1))
            return s2, m["loss"]
        return jax.lax.scan(body, state, None, length=n)

    tag = args.mode if args.mode != "sketch" else args.sketch_backend
    if args.telemetry_level:
        tag += f"+telemetry_l{args.telemetry_level}"
    # per-round python dispatch twin FIRST (what the default train loop
    # pays), then the scanned block — the [scan xK] delta is exactly the
    # dispatch overhead the scan engine (pipeline/scan_engine.py,
    # --scan_rounds) amortizes
    state = session.state
    for _ in range(2):  # compile + warm both donated layouts
        state, m = round_fn(state, ids, data, jnp.float32(0.1))
    fence(m["loss"])
    t0 = time.perf_counter()
    for _ in range(n):
        state, m = round_fn(state, ids, data, jnp.float32(0.1))
    fence(m["loss"])
    dt_loop = (time.perf_counter() - t0) / n * 1e3
    print(f"per-round dispatch [{tag}]: {dt_loop:.2f} ms -> "
          f"{workers * bench_batch / dt_loop * 1e3:,.0f} samples/s")
    # -- critical path (round-tracing PR) ----------------------------------
    # a SEPARATE n-round loop with a PhaseSpans recorder and a per-round
    # fence, decomposed by the SAME CriticalPath analyzer the run reports
    # use (telemetry/trace.py — reused, not reimplemented). The per-round
    # fence makes each dispatch span the true device+host round latency,
    # so this loop is slower than the free-running line above by design.
    # --profile_rounds A-B arms a programmatic jax.profiler capture
    # window over exactly these rounds.
    try:
        from commefficient_tpu.telemetry.spans import PhaseSpans
        from commefficient_tpu.telemetry.trace import (
            STAGES, CriticalPath, ProfilerWindow, round_trace_id,
        )

        spans = PhaseSpans(".", start_step=2, num_steps=n)
        window = None
        if args.profile_rounds:
            window = ProfilerWindow(
                args.profile_rounds, "profile_round_trace",
                fence_fn=lambda: fence(state.params_vec))
        for i in range(n):
            step = 2 + i
            spans.step(step)
            if window is not None:
                window.step(step)
            with spans.span("round_dispatch", collective=True, step=step,
                            trace_id=round_trace_id(step)) as sp:
                state, m = round_fn(state, ids, data, jnp.float32(0.1))
                sp.fence(m["loss"])
        if window is not None:
            window.step(2 + n)
            window.close()
        cp = CriticalPath(spans.events)
        bds = [bd for bd in (cp.round_breakdown(s) for s in cp.steps())
               if bd is not None]
        wall = sum(bd["wall_ms"] for bd in bds) / len(bds)
        tot = {s: sum(bd["stages_ms"][s] for bd in bds) / len(bds)
               for s in STAGES}
        crit = max(STAGES, key=lambda s: tot[s])
        parts = " + ".join(f"{s} {tot[s]:.2f}" for s in STAGES
                           if tot[s] > 0)
        print(f"[critical path] {len(bds)} fenced round(s): {parts} "
              f"= {wall:.2f} ms/round; binding stage: {crit}")
    except Exception as e:  # noqa: BLE001 — lab line, never kills the run
        print(f"[critical path] unavailable: {e}")
    # layerwise-overlap twin (hide-the-collectives PR): the same round
    # with the aggregation psum and the top-k gathers split into
    # per-leaf-group segments (--overlap_collectives layerwise) so XLA
    # can run each segment's ring concurrently with the next segment's
    # reduction work. The delta vs the sequential line above is the
    # exposed-collective time the chunking hides (≈0 on a one-chip mesh
    # — there is no cross-chip ring to hide there).
    if args.mode == "sketch":
        try:
            ov_sess = FederatedSession(
                cfg.replace(overlap_collectives="layerwise"),
                params, loss_fn, mesh=make_mesh(1))
            ov_fn = ov_sess.round_fn
            ov_state = ov_sess.state
            for _ in range(2):  # compile + warm both donated layouts
                ov_state, m = ov_fn(ov_state, ids, data, jnp.float32(0.1))
            fence(m["loss"])
            t0 = time.perf_counter()
            for _ in range(n):
                ov_state, m = ov_fn(ov_state, ids, data, jnp.float32(0.1))
            fence(m["loss"])
            dt_ov = (time.perf_counter() - t0) / n * 1e3
            print(f"[overlap layerwise] per-round dispatch: {dt_ov:.2f} ms "
                  f"-> {workers * bench_batch / dt_ov * 1e3:,.0f} samples/s "
                  f"(overlap delta vs sequential "
                  f"{dt_loop - dt_ov:+.2f} ms/round)")
        except Exception as e:  # noqa: BLE001 — lab line, never kills the run
            print(f"[overlap layerwise] twin unavailable: {e}")
    state, losses = run_rounds(state)
    fence(losses)
    t0 = time.perf_counter()
    state, losses = run_rounds(state)
    fence(losses)
    dt = (time.perf_counter() - t0) / n * 1e3
    print(f"[scan x{n}] full round [{tag}]: {dt:.2f} ms -> "
          f"{workers * bench_batch / dt * 1e3:,.0f} samples/s "
          f"(dispatch overhead amortized: {dt_loop - dt:+.2f} ms/round)")


if __name__ == "__main__":
    main()
