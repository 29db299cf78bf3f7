"""Benchmark: federated ResNet-9/CIFAR-10 training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Measures the headline metric from BASELINE.json — samples/sec/chip of the
full federated training step (8 virtual workers multiplexed on the chip,
sketch-mode compression + server unsketch update, the FetchSGD hot path) on
real CIFAR-shaped data. ``vs_baseline`` normalizes against an A100-class
reference throughput for ResNet-9 federated training (the reference
publishes no tables — BASELINE.json ``published: {}`` — so the denominator
is the documented estimate below, not a measured upstream number).

r2 changes: the round uses the TPU fast paths — banded matmul CountSketch
(ops/countsketch.py v5: one [m, V] one-hot einsum + overlap-add per row;
the band buys FetchSGD-stable collision statistics at some MXU cost — see
the module postmortem), threshold top-k selection (ops/topk.py: no sort,
no scatter), and the fused flattened-batch gradient (round.py
fuse_clients, numerically identical here — pinned by tests). Methodology
is the same python-loop dispatch as r1 with one fence at the end
(steady-state pipelined dispatch) for the CV headline; the opt-in scan
engine (pipeline/scan_engine.py) has its own ``gpt2_sketch_scan_*`` leg
below, which MEASURES the scan dispatch win/loss on the bench chip
instead of assuming either way (the CV headline methodology is
unchanged).

Pipelined leg (pipeline/ PR): ``sketch_pipelined_*`` keys on the headline
line measure the depth-2 pipelined engine against its synchronous twin on
the SAME session — both paying real per-round host work (sampler batch
assembly + H2D), since that host serial time is what the pipeline hides;
the engine's mean occupancy and residual host stall ride along
(check_bench_regression gates samples/s + occupancy).

GPT-2 legs: the BASELINE #4 sketch round rides the headline line per
SKETCH BACKEND (einsum = legacy keys, pallas = ``gpt2_sketch_pallas_*``)
next to its uncompressed twin — the r5 VERDICT's 3.5x sketch-round gap is
a kernel property, so both realizations are tracked. Since the sketch-gap
PR the sketch legs run the OPTIMIZED hot path (sketch_fused_bwd: per-leaf
cotangent sketches replace the flat [D] grad concat; bf16 tables with
f32 accumulation: half the table HBM + psum bytes at unchanged num_cols
— below iso-bytes), and a ``gpt2_sketch_scan_*`` leg times 8 rounds per
lax.scan dispatch (the scan-engine amortization). The 0.6x
``gpt2_sketch_vs_uncompressed`` target is gated by
scripts/check_bench_regression.py once the first optimized record lands.

Every number here is a device number, so ``main`` refuses to start on
anything but a TPU, and a leg that recorded a ``*_error`` key makes the
exit code non-zero. Artifacts (perf_report.json, BENCH_MATRIX.json) go
to ``OUT_DIR``, not the checkout root.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

# A100-class ResNet-9 CIFAR training throughput (samples/s) — cifar10-fast
# lineage trains 50k x ~25 epochs in ~60-75 s on one fast GPU (~17-20k
# samples/s); the reference's federated wrapper adds compression overhead.
# Used only as a fixed denominator so vs_baseline is comparable across rounds.
BASELINE_SAMPLES_PER_SEC = 20_000.0

# where the artifacts go (git-ignored) — never the checkout root
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_out")

# Peak dense matmul throughput of the bench chip, for the MFU line
# (VERDICT r3 weak 5: anchor perf to hardware, not to the estimate above).
# TPU v5e (v5 lite): 197 TFLOP/s bf16 / 394 int8 (public spec). The table
# itself lives in telemetry/xla_audit.py since the compiled-graph
# observability PR, so bench, profile_round and the audit share one
# denominator; a chip that is not in it is an error.


def _chip_peak_flops() -> tuple[float, str]:
    """(peak bf16 FLOP/s, device_kind); raises on an unknown chip."""
    from commefficient_tpu.telemetry.xla_audit import chip_peak_flops

    return chip_peak_flops()


def _audit_leg(session, ids, batch, sec_per_round):
    """Audited keys for one bench leg from the COMPILED round artifact
    (telemetry/xla_audit.py): the compiler's own FLOP count and the
    derived peak-HBM next to the legacy hand-model numbers, so the two
    can be diffed across rounds. NB ``cost_analysis()`` reports the
    PER-DEVICE SPMD module (verified on the 8-dev CPU mesh), so audited
    MFU is per-device FLOPs over ONE chip's peak — no device-count
    division (dividing by nd again under-reported multichip legs nd-fold)
    — and ``audited_flops_per_round`` is the per-device figure, which on
    replicated sections counts each chip's redundant copy of the work.
    Failures degrade to an ``audit_error`` key — the measured row must
    survive a broken analysis. Returns (keys dict, audit | None)."""
    from commefficient_tpu.telemetry.xla_audit import audited_mfu

    try:
        audit = session.audit_compiled_round(ids, batch, 0.1)
    except Exception as e:  # noqa: BLE001
        return {"audit_error": f"{type(e).__name__}: {e}"[:200]}, None
    out = {}
    flops = audit.cost.get("flops")
    if flops is not None:
        out["audited_flops_per_round"] = flops
        if sec_per_round:
            peak, _ = _chip_peak_flops()
            out["audited_mfu"] = round(
                audited_mfu(flops, sec_per_round, peak), 4
            )
    if audit.memory.get("peak_hbm_bytes") is not None:
        out["audited_peak_hbm_bytes"] = audit.memory["peak_hbm_bytes"]
    out["audited_collective_bytes"] = audit.collectives["total_bytes"]
    return out, audit


def resnet9_train_flops_per_sample() -> float:
    """Analytic fwd+bwd FLOPs/sample for ResNet-9 at 32x32 (the model term
    of the MFU line; sketch/top-k FLOPs are excluded, so sketch-mode MFU is
    an UNDERestimate of chip utilization — the conservative direction).

    Convs: 2*H*W*Cin*Cout*9 each; backward ~2x forward (dL/dx + dL/dW).
    """
    convs = [
        (32, 3, 64),     # prep
        (32, 64, 128),   # layer1 conv (pool after)
        (16, 128, 128), (16, 128, 128),   # residual 1
        (16, 128, 256),  # layer2 conv (pool after)
        (8, 256, 512),   # layer3 conv (pool after)
        (4, 512, 512), (4, 512, 512),     # residual 2
    ]
    fwd = sum(2 * h * h * cin * cout * 9 for h, cin, cout in convs)
    fwd += 2 * 512 * 10  # head
    return 3.0 * fwd  # fwd + ~2x for backward


def gpt2_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                         seq: int) -> float:
    """Analytic train (fwd+bwd) FLOPs per processed token for the GPT-2
    double-heads model: ``6*D + 12*L*T*E``.

    6*D with D = TOTAL params (incl. embeddings) is the right count here,
    not an overcount: the input embedding rows do no matmul FLOPs, but the
    TIED lm_head matmul (2*V*E/token fwd) almost exactly replaces them
    (V*E ~ the embedding table), so 6*D_total ~ 6*D_nonemb + 6*V*E. The
    12*L*T*E term is the QK^T/AV attention work (4*T*E per layer fwd, x3
    for backward). Sketch/compression FLOPs are EXCLUDED, as in the
    ResNet-9 MFU line — the conservative direction."""
    return 6.0 * n_params + 12.0 * n_layer * seq * n_embd


def _measure_gpt2(mode: str, n_rounds: int = 10, sketch_backend: str = "einsum",
                  scan_rounds: int = 0):
    """tokens/s + MFU of the full federated GPT-2-small round (one chip),
    sketch 5x5M (the BASELINE #4 shape) or uncompressed. ``sketch_backend``
    picks the CountSketch kernel realization (einsum | pallas) — the r5+
    sketch-round gap is a kernel property, so the bench carries both.

    Since the sketch-gap PR the sketch legs run the OPTIMIZED hot path
    (sketch_fused_bwd + bf16 tables — the configuration the
    gpt2_sketch_vs_uncompressed >= 0.6 target is gated on; bytes are
    BELOW iso: bf16 halves the psum payload at unchanged num_cols), and
    ``scan_rounds`` > 1 times K rounds per dispatch through a
    lax.scan-of-rounds block (the scan-engine dispatch amortization,
    pipeline/scan_engine.py — fixed staged batch, so the leg isolates
    dispatch overhead exactly).
    Returns (tokens_per_sec, mfu, seconds_per_round, audited-keys dict)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import gpt2_double_heads_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.ops.param_utils import ravel_params
    from commefficient_tpu.parallel import FederatedSession, mask_gpt2
    from commefficient_tpu.utils.config import Config

    W, B, N, T = 8, 4, 2, 256
    gcfg = GPT2Config(vocab_size=50262, n_positions=1024, n_embd=768,
                      n_layer=12, n_head=12)
    model = GPT2DoubleHeads(gcfg)
    ids0 = jnp.zeros((1, 1, 8), jnp.int32)
    params = model.init(jax.random.key(0), ids0, token_type_ids=ids0,
                        mc_token_ids=jnp.zeros((1, 1), jnp.int32))
    # *_multichip modes spread the 8 workers over every local chip
    # (largest power-of-2 divisor) — the sharded-decode leg needs a real
    # workers mesh, and its uncompressed twin must run on the SAME mesh
    # so the _vs_uncompressed ratio isolates the decode, not added chips
    nd = 1
    if mode.endswith("_multichip") or mode == "sketch_sharded":
        nd = next(n for n in (8, 4, 2, 1)
                  if len(jax.devices()) >= n and W % n == 0)
    base = dict(num_clients=2 * W, num_workers=W, num_devices=nd,
                local_batch_size=B, weight_decay=0.0,
                topk_method="threshold", device_data=False,
                fuse_clients=True)
    if mode in ("sketch", "sketch_sharded"):
        cfg = Config(mode="sketch", error_type="virtual",
                     virtual_momentum=0.9, k=50_000, num_rows=5,
                     num_cols=5_000_000, sketch_backend=sketch_backend,
                     sketch_decode=("sharded" if mode == "sketch_sharded"
                                    else "auto"),
                     # the sketch-gap PR's hot path: per-leaf cotangent
                     # sketches replace the flat [D] grad concat, tables
                     # store/psum bf16 with f32 accumulation
                     sketch_fused_bwd=True,
                     sketch_table_dtype="bfloat16",
                     **base)
    elif mode == "powersgd":
        # rank-4 warm-started PowerSGD (compress/powersgd.py): D=124M
        # matricizes ~[11.2k, 11.2k], downlink r*(n+m) ~ 89k floats
        cfg = Config(mode="powersgd", error_type="virtual",
                     virtual_momentum=0.9, powersgd_rank=4, **base)
    else:
        cfg = Config(mode="uncompressed", virtual_momentum=0.9, **base)
    session = FederatedSession(cfg, params, gpt2_double_heads_loss(model.apply),
                               mask_batch=mask_gpt2)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 50257, size=(W, B, N, T)).astype(np.int32))
    lm = np.full((W, B, N, T), -100, np.int32)
    lm[..., N - 1, T // 2:] = np.asarray(ids)[..., N - 1, T // 2:]
    batch = {
        "input_ids": ids, "token_type_ids": ids,
        "lm_labels": jnp.asarray(lm),
        "mc_token_ids": jnp.full((W, B, N), T - 1, jnp.int32),
        "mc_labels": jnp.zeros((W, B), jnp.int32),
    }
    client_ids = jnp.arange(W, dtype=jnp.int32)
    state, round_fn = session.state, session.round_fn
    lr = jnp.float32(0.1)
    from commefficient_tpu.utils.profiling import fence

    if scan_rounds > 1:
        # scan-of-rounds dispatch amortization: ONE jitted block runs K
        # rounds (the inlined round trace — same program the per-round
        # path dispatches K times), fixed staged batch
        K = scan_rounds

        # donate the state like the per-round twin (round_fn donates its
        # arg 0): without it the leg holds input AND output FedState
        # (~600 MB extra at GPT-2 scale) and biases the very dispatch
        # delta it isolates
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_block(state):
            def body(s, _):
                s2, mm = round_fn(s, client_ids, batch, lr)
                return s2, mm["loss"]

            return jax.lax.scan(body, state, None, length=K)

        for _ in range(2):  # compile + warm the donated layout
            state, losses = run_block(state)
            assert np.isfinite(fence(losses[-1]))
        reps = max(1, n_rounds // K)
        t0 = time.perf_counter()
        for _ in range(reps):
            state, losses = run_block(state)
        assert np.isfinite(fence(losses[-1]))
        dt = time.perf_counter() - t0
        n_rounds = reps * K
    else:
        for _ in range(3):  # compile + warm both donated-buffer layouts
            state, m = round_fn(state, client_ids, batch, lr)
            assert np.isfinite(fence(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            state, m = round_fn(state, client_ids, batch, lr)
        assert np.isfinite(fence(m["loss"]))  # scalar-fetch fence
        dt = time.perf_counter() - t0
    d = int(ravel_params(params)[0].size)
    tokens = n_rounds * W * B * N * T  # every candidate's tokens do compute
    peak, _ = _chip_peak_flops()
    tps = tokens / dt
    # MFU against the peak of the chips the leg USED (nd > 1 for the
    # multichip/sharded legs) — dividing an nd-chip throughput by one
    # chip's peak would report an MFU that can exceed 1.0
    mfu = tps * gpt2_flops_per_token(d, gcfg.n_layer, gcfg.n_embd, T) / (
        peak * nd
    )
    # audited twin of the hand-model numbers, from the compiled artifact
    # (one extra AOT compile per leg — tracked perf beats bench wall-clock).
    # The scan leg reuses the per-round leg's program, so re-auditing it
    # would only pay the AOT compile twice for the same artifact.
    audit_keys = {}
    if scan_rounds <= 1:
        audit_keys, _ = _audit_leg(
            session, np.arange(W, dtype=np.int32), batch, dt / n_rounds
        )
    return tps, mfu, dt / n_rounds, audit_keys


def _headline_cfg():
    from commefficient_tpu.utils.config import Config

    # 8 virtual workers x 256-sample local batches (FetchSGD's CIFAR configs
    # run local batches up to 500/client, paper §5) = 2048 samples/round.
    workers, batch = 8, 256
    return Config(
        mode="sketch",
        error_type="virtual",
        virtual_momentum=0.9,
        k=50_000,
        num_rows=5,
        num_cols=500_000,
        num_blocks=1,  # r3: num_blocks>1 now really chunks (slower); 1 keeps
        # the computation identical to the r1/r2 headline runs
        topk_method="threshold",
        fuse_clients=True,
        num_clients=2 * workers,
        num_workers=workers,
        num_devices=1,
        local_batch_size=batch,
        weight_decay=5e-4,
    )


def _measure(cfg, n_rounds: int = 20, audit_box: dict = None) -> float:
    """samples/s of the full federated round under ``cfg`` (one chip).
    ``audit_box``: a dict to fill with the leg's audited keys + the
    CompiledRoundAudit itself (headline leg only — matrix legs skip the
    extra AOT compile)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.parallel import FederatedSession, make_mesh

    workers, batch = cfg.num_workers, cfg.local_batch_size
    from commefficient_tpu.models.losses import model_dtype

    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply, compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))

    rng = np.random.default_rng(0)
    # Device-resident batch: models a prefetching input pipeline (the steady
    # state of real training, where H2D overlaps compute). The round itself —
    # grads, compression, aggregation, server update — is what's timed.
    ids = jnp.asarray(
        rng.choice(cfg.num_clients, size=workers, replace=False).astype(np.int32)
    )
    shape = (workers, batch, 32, 32, 3)
    if cfg.mode == "fedavg":  # microbatch convention [W, L, B/L, ...]
        L = cfg.num_local_iters
        shape = (workers, L, batch // L, 32, 32, 3)
    data = {
        "x": jnp.asarray(rng.normal(size=shape).astype(np.float32)),
        "y": jnp.asarray(
            rng.integers(0, 10, size=shape[:-3]).astype(np.int32)
        ),
    }
    state, round_fn = session.state, session.round_fn
    lr = jnp.float32(0.1)

    # fedsim legs (sketch_dropout30): the masked round consumes one RoundEnv
    # per round; realize the real environment's schedule up front so the
    # timed loop measures the in-graph masking, not host mask draws
    envs = [()] * (3 + n_rounds)
    if cfg.fedsim_enabled:
        from commefficient_tpu.fedsim import build_environment

        fe = build_environment(cfg)
        envs = [
            (jnp.asarray(e.live), jnp.asarray(e.corrupt),
             jnp.float32(e.live_count))
            for e in fe.round_envs(0, 3 + n_rounds)
        ]

    # compile + warmup: the first TWO calls compile (donated-buffer layouts
    # differ between the fresh state and the returned state), so warm both.
    from commefficient_tpu.utils.profiling import fence

    for i in range(3):
        state, m = round_fn(state, ids, data, lr, env=envs[i])
        assert np.isfinite(fence(m["loss"]))

    t0 = time.perf_counter()
    for i in range(n_rounds):
        state, m = round_fn(state, ids, data, lr, env=envs[3 + i])
    assert np.isfinite(fence(m["loss"]))
    dt = time.perf_counter() - t0
    sps = n_rounds * workers * batch / dt
    if audit_box is not None:
        keys, audit = _audit_leg(
            session, np.asarray(ids), data, dt / n_rounds
        )
        audit_box.update(keys)
        audit_box["_audit"] = audit
        audit_box["_cfg"] = cfg
    return sps


def _measure_pipeline(base_cfg, n_rounds: int = 8, depth: int = 2) -> dict:
    """Pipelined round execution (pipeline/ PR) vs its synchronous twin on
    the headline sketch round, through the REAL engine. Unlike the other
    legs' device-resident batches, BOTH twins pay real per-round host
    work — non-IID sampler draw + [W*B] batch assembly + H2D ``device_put``
    — because that host serial time is exactly what the pipeline hides.
    The sync twin runs it on the critical path between dispatches (the
    depth-0 train loop); the pipelined twin stages ``depth`` rounds ahead
    on the worker thread. Reports samples/s for both, the engine's mean
    occupancy/residual host stall, and ``host_stall_delta_ms`` = mean
    per-round host realization time minus the residual stall — the host
    milliseconds per round the pipeline moved off the critical path."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.data import FedDataset, FedSampler
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.pipeline import PipelinedRounds
    from commefficient_tpu.utils.profiling import fence

    cfg = base_cfg.replace(pipeline_depth=depth, device_data=False)
    W, B = cfg.num_workers, cfg.local_batch_size
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply, compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    rng = np.random.default_rng(0)
    n = 4 * W * B  # enough rows that per-client draws stay CIFAR-shaped
    ds = FedDataset(
        {"x": rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.uint8),
         "y": rng.integers(0, 10, size=(n,)).astype(np.int32)},
        cfg.num_clients, iid=True, seed=0,
    )
    sampler = FedSampler(ds, num_workers=W, local_batch_size=B, seed=0)

    def lr_fn(_step):
        return 0.1

    def run_sync(start):
        t0 = time.perf_counter()
        for r in range(start, start + n_rounds):
            ids, batch = sampler.sample_round(r)
            m = session.train_round(ids, batch, 0.1)
        fence(m["loss"])
        return time.perf_counter() - t0

    # compile + warm both donated-buffer layouts (bench warmup discipline)
    run_sync(0)
    dt_sync = run_sync(n_rounds)
    start = 2 * n_rounds
    stop = start + n_rounds
    engine = PipelinedRounds(
        cfg, session, sampler, lr_fn, num_rounds=stop, steps_per_epoch=stop
    ).start(start)
    try:
        t0 = time.perf_counter()
        for _s, _lr, m in engine.epoch_rounds(0, start):
            pass
        fence(m["loss"])
        dt_pipe = time.perf_counter() - t0
    finally:
        engine.close()
    st = engine.stats()
    return {
        "sketch_pipelined_samples_per_sec": round(n_rounds * W * B / dt_pipe, 2),
        "sketch_pipeline_sync_samples_per_sec": round(
            n_rounds * W * B / dt_sync, 2
        ),
        "sketch_pipelined_sec_per_round": round(dt_pipe / n_rounds, 4),
        "sketch_pipelined_depth": depth,
        "sketch_pipelined_occupancy": round(st["occupancy"], 4),
        "sketch_pipelined_host_stall_ms": round(st["host_stall_ms"], 2),
        "sketch_pipelined_host_stall_delta_ms": round(
            st["prefetch_host_ms"] - st["host_stall_ms"], 2
        ),
    }


def _measure_traced(base_cfg, n_rounds: int = 8) -> dict:
    """Critical-path attribution of the headline sketch round (trace PR):
    the REAL dispatch path with a PhaseSpans recorder attached — every
    span stamped with its round's trace id — decomposed by
    telemetry.trace.CriticalPath into DISJOINT exclusive stage times.
    Reports the mean per-round exclusive ms per stage plus the binding
    stage's name. Every measured round fences (the recorder window covers
    the whole loop), so the dispatch span is the true device+host round
    latency and the decomposition accounts for real wall-clock — these
    rows are therefore slower than the headline by design and stay
    INFORMATIONAL (no gated suffix; scripts/check_bench_regression.py
    registers them next to *_host_stall_ms)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.data import FedDataset, FedSampler
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.telemetry.spans import PhaseSpans
    from commefficient_tpu.telemetry.trace import (
        STAGES, CriticalPath, round_trace_id,
    )
    from commefficient_tpu.utils.profiling import fence

    cfg = base_cfg.replace(device_data=False)
    W, B = cfg.num_workers, cfg.local_batch_size
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply, compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    rng = np.random.default_rng(0)
    n = 4 * W * B
    ds = FedDataset(
        {"x": rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.uint8),
         "y": rng.integers(0, 10, size=(n,)).astype(np.int32)},
        cfg.num_clients, iid=True, seed=0,
    )
    sampler = FedSampler(ds, num_workers=W, local_batch_size=B, seed=0)

    # compile + warm both donated layouts BEFORE attaching the recorder:
    # the traced window must hold steady-state rounds only
    for r in range(3):
        ids, batch = sampler.sample_round(r)
        m = session.train_round(ids, batch, 0.1)
    fence(m["loss"])

    # logdir enables recording; nothing dumps (close() is never called)
    spans = PhaseSpans(".", start_step=3, num_steps=n_rounds)
    session.spans = spans
    try:
        for r in range(3, 3 + n_rounds):
            spans.step(r)
            # the sampler draw is the leg's data stage — the train loops
            # record it via wrap_iter/prefetch; here we bracket it by hand
            with spans.span("data_load", step=r,
                            trace_id=round_trace_id(r)):
                ids, batch = sampler.sample_round(r)
            m = session.train_round(ids, batch, 0.1)
        fence(m["loss"])
    finally:
        session.spans = None

    cp = CriticalPath(spans.events)
    bds = [bd for bd in (cp.round_breakdown(s) for s in cp.steps())
           if bd is not None and bd["step"] >= 3]
    if not bds:
        return {"sketch_traced_error": "no rounds decomposed"}
    tot = {s: sum(bd["stages_ms"][s] for bd in bds) for s in STAGES}
    out = {
        "sketch_traced_critical_stage": max(STAGES, key=lambda s: tot[s]),
        "sketch_traced_rounds": len(bds),
        "sketch_traced_wall_ms": round(
            sum(bd["wall_ms"] for bd in bds) / len(bds), 3),
    }
    for s in STAGES:
        out[f"sketch_traced_{s}_exclusive_ms"] = round(tot[s] / len(bds), 3)
    return out


def _measure_ladder_switch(base_cfg, n_rounds: int = 8) -> dict:
    """Cost of a mid-run compression-ladder rung switch (control/ PR) on
    the headline sketch round: a 2-rung k-ladder under a fixed schedule
    that switches halfway. Reports the steady samples/s, the wall-clock of
    the FIRST round after the switch (state migration + the prewarmed
    rung's first dispatch — its XLA backend-compile, but never a
    re-trace), and the sentinel's retrace count, which must be 0 — the
    whole point of AOT rung prewarming."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.control import build_controller
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.profiling import fence

    half = n_rounds // 2
    cfg = base_cfg.replace(
        control_policy="fixed",
        control_schedule=f"0-{half - 1}=0,{half}-=1",
        ladder=f"k={base_cfg.k},{max(base_cfg.k // 2, 1)}",
    )
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply, compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    ctrl = build_controller(cfg, session, num_rounds=n_rounds + 3)

    rng = np.random.default_rng(0)
    W, B = cfg.num_workers, cfg.local_batch_size
    ids = rng.choice(cfg.num_clients, size=W, replace=False).astype(np.int32)
    batch = {
        "x": rng.normal(size=(W, B, 32, 32, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(W, B)).astype(np.int32),
    }
    session.prewarm_rungs(ids, batch, 0.1)
    # warm rung 0 (compile + donated-layout second compile) OUTSIDE the
    # schedule by driving the session's round clock through rounds 0..2 of
    # a schedule that holds rung 0 until the switch
    times = []
    for r in range(3 + n_rounds):
        t0 = time.perf_counter()
        m = session.train_round(ids, batch, 0.1)
        assert np.isfinite(fence(m["loss"]))
        times.append(time.perf_counter() - t0)
    # the switch fires at round index `half` (clock r == half)
    switch_ms = times[half] * 1e3
    steady = times[3:half] + times[half + 1:]
    sps = W * B / (sum(steady) / len(steady))
    return {
        "sketch_ladder_steady": round(sps, 2),
        "sketch_ladder_switch_round_ms": round(switch_ms, 1),
        "sketch_ladder_retraces": session.retrace_sentinel.retraces,
    }


def _measure_recovery(base_cfg, n_rounds: int = 4) -> dict:
    """Cost of the resilience/ self-healing primitives on the headline
    sketch round: the vault snapshot capture (a deliberate host sync —
    the per-`--snapshot_every` tax a recovery-enabled run pays) and the
    rollback restore (snapshot -> leaf re-commit through the same
    checkpoint path), plus the sentinel's retrace count across a
    post-rollback dispatch — which must be 0: the restored leaves land on
    their original shardings, so the round re-dispatches the same
    compiled program."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.resilience import RollbackVault
    from commefficient_tpu.utils.profiling import fence

    cfg = base_cfg
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply, compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))

    rng = np.random.default_rng(0)
    W, B = cfg.num_workers, cfg.local_batch_size
    ids = rng.choice(cfg.num_clients, size=W, replace=False).astype(np.int32)
    batch = {
        "x": rng.normal(size=(W, B, 32, 32, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(W, B)).astype(np.int32),
    }
    for _ in range(2):  # compile + donated-layout warmup
        fence(session.train_round(ids, batch, 0.1)["loss"])
    vault = RollbackVault(snapshot_every=1)
    t0 = time.perf_counter()
    snap = vault.snapshot(session, 2)
    snapshot_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(n_rounds):
        fence(session.train_round(ids, batch, 0.1)["loss"])
    t0 = time.perf_counter()
    vault.restore(session, snap)
    rollback_ms = (time.perf_counter() - t0) * 1e3
    fence(session.train_round(ids, batch, 0.1)["loss"])
    return {
        "sketch_resilience_snapshot_ms": round(snapshot_ms, 1),
        "sketch_resilience_snapshot_mb": round(snap.nbytes / 2**20, 1),
        "sketch_resilience_rollback_ms": round(rollback_ms, 1),
        "sketch_resilience_retraces": session.retrace_sentinel.retraces,
    }


def _measure_sparse_agg(base, n_rounds: int = 10) -> dict:
    """Sparse-aggregate PR: the O(W*k) pair-exchange aggregation vs its
    dense-psum twin, per mode, on the SAME multi-device mesh and round
    shape. The ``_vs_dense`` ratio (sparse sps / dense sps, higher is
    better — registered in scripts/check_bench_regression.py) is the
    leg's design claim: at bench scale (D ~ 6.5M, k = 50k) the exchange
    drops from O(D) to O(W*k) elements, so sparse must not lose to
    dense. Requires a multi-device host — on one chip the sparse
    schedule is degenerate (Config warns) and the comparison is
    meaningless, so the leg reports a skip marker instead of a fake 1.0."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.profiling import fence

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"sparse_agg_skipped": f"single-device host ({n_dev} chip)"}

    out: dict = {}
    B = base.local_batch_size
    for mode, extra in (
        ("local_topk", dict(error_type="local", virtual_momentum=0.0,
                            fuse_clients=False, client_store="host")),
        ("true_topk", dict(error_type="virtual", virtual_momentum=0.9)),
    ):
        twin_cfg = base.replace(
            mode=mode, k=50_000, topk_method="threshold",
            num_devices=n_dev, num_workers=n_dev, num_clients=2 * n_dev,
            **extra,
        )
        name = f"{mode}_sparse_agg"
        try:
            model = ResNet9(
                num_classes=10, dtype=model_dtype(twin_cfg.compute_dtype)
            )
            params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
            loss_fn = classification_loss(
                model.apply, compute_dtype=twin_cfg.compute_dtype
            )
            rng = np.random.default_rng(0)
            ids = jnp.asarray(np.arange(n_dev, dtype=np.int32))
            data = {
                "x": jnp.asarray(
                    rng.normal(size=(n_dev, B, 32, 32, 3)).astype(np.float32)
                ),
                "y": jnp.asarray(
                    rng.integers(0, 10, size=(n_dev, B)).astype(np.int32)
                ),
            }
            sps = {}
            for agg in ("dense", "sparse"):
                session = FederatedSession(
                    twin_cfg.replace(aggregate=agg), params, loss_fn,
                    mesh=make_mesh(n_dev),
                )
                state, round_fn = session.state, session.round_fn
                # hosted banks (clientstore/): the round takes the
                # cohort's rows as donated arguments and returns the
                # updated ones — thread them through the timing loop so
                # the bank writeback stays off the measured path
                hosted = session._streamer is not None
                vel = err = ()
                if hosted:
                    cohort = session._streamer.gather(np.asarray(ids))
                    vel, err = cohort.vel, cohort.err

                def step(state, vel, err):
                    if hosted:
                        return round_fn(state, ids, data, jnp.float32(0.1),
                                        vel, err)
                    state, m = round_fn(state, ids, data, jnp.float32(0.1))
                    return state, m, vel, err

                for _ in range(3):  # compile + donated-layout warmup
                    state, m, vel, err = step(state, vel, err)
                    assert np.isfinite(fence(m["loss"]))
                t0 = time.perf_counter()
                for _ in range(n_rounds):
                    state, m, vel, err = step(state, vel, err)
                assert np.isfinite(fence(m["loss"]))
                dt = time.perf_counter() - t0
                sps[agg] = n_rounds * n_dev * B / dt
                if hosted:
                    session.close_client_store()
            out[name] = round(sps["sparse"], 2)
            out[f"{name}_vs_dense"] = round(sps["sparse"] / sps["dense"], 3)
        except Exception as e:  # noqa: BLE001 — per-leg error isolation
            out[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def _measure_hostclient(base, n_rounds: int = 10) -> dict:
    """clientstore PR: the hosted round (per-client vel/err banks in host
    RAM, cohort rows streamed per round) vs its device-resident twin on
    the SAME mesh and round shape. The ``_vs_device`` ratio (host sps /
    device sps, higher is better — registered in
    scripts/check_bench_regression.py) is the leg's design claim: with
    the cohort gather staged H2D and the writeback async, hosting the
    [C, D] banks must not cost the round loop more than noise — while
    bounding C by host RAM/disk instead of HBM (the C = 1e6 smoke in
    tests/test_clientstore.py). Sliding cohorts (overlap W-1 per round)
    exercise the LRU device cache, whose hit rate and H2D stage time ride
    along as informational gauges; the retrace gauge is the hard zero."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.profiling import fence

    n_dev = len(jax.devices())
    out: dict = {}
    B = base.local_batch_size
    C = 4 * n_dev
    twin = base.replace(
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0, fuse_clients=False, k=50_000,
        topk_method="threshold", num_devices=n_dev, num_workers=n_dev,
        num_clients=C, telemetry_level=1,
    )
    name = "local_topk_hostclient"
    try:
        model = ResNet9(num_classes=10, dtype=model_dtype(twin.compute_dtype))
        params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        loss_fn = classification_loss(
            model.apply, compute_dtype=twin.compute_dtype
        )
        rng = np.random.default_rng(0)
        data = {
            "x": jnp.asarray(
                rng.normal(size=(n_dev, B, 32, 32, 3)).astype(np.float32)
            ),
            "y": jnp.asarray(
                rng.integers(0, 10, size=(n_dev, B)).astype(np.int32)
            ),
        }
        sps, gauges = {}, {}
        for store in ("device", "host"):
            cfg = twin.replace(
                client_store=store,
                client_store_cache_rows=2 * n_dev if store == "host" else 0,
            )
            session = FederatedSession(cfg, params, loss_fn,
                                       mesh=make_mesh(n_dev))

            def one_round(r):
                # sliding cohort: W-1 clients repeat from round r-1, so
                # the device cache sees real hits AND real evictions
                ids = (np.arange(n_dev, dtype=np.int32) + r) % C
                return session.train_round(ids, data, 0.1)

            for r in range(3):  # compile + donated-layout warmup
                m = one_round(r)
                assert np.isfinite(fence(m["loss"]))
            hit = h2d = 0.0
            t0 = time.perf_counter()
            for r in range(3, 3 + n_rounds):
                m = one_round(r)
                hit += float(m.get("clientstore/cache_hit_rate", 0.0))
                h2d += float(m.get("clientstore/h2d_stage_ms", 0.0))
            assert np.isfinite(fence(m["loss"]))
            dt = time.perf_counter() - t0
            sps[store] = n_rounds * n_dev * B / dt
            if store == "host":
                gauges = {
                    f"{name}_cache_hit_rate": round(hit / n_rounds, 3),
                    f"{name}_h2d_stage_ms": round(h2d / n_rounds, 3),
                    f"{name}_retraces": session.retrace_sentinel.retraces,
                }
                session.close_client_store()
        out[f"{name}_samples_per_sec"] = round(sps["host"], 2)
        out[f"{name}_vs_device"] = round(sps["host"] / sps["device"], 3)
        out.update(gauges)
    except Exception as e:  # noqa: BLE001 — per-leg error isolation
        out[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def _measure_async(base, n_updates: int = 8) -> dict:
    """Buffered-async PR: the asyncfed engine vs its synchronous twin on
    the headline sketch round under ~40% stragglers (poisson arrivals at
    rate 0.9: participation 1-exp(-0.9) ~ 0.59). Both twins run the SAME
    task, sampler stream, and per-client vmap round body (async requires
    per-client rows, so the sync twin drops fuse_clients too — the ratio
    isolates the SCHEDULE, not the fusion). The sync twin pays one full
    barrier round per server update; the async engine fires on the Kth
    arrival with C cohorts in flight, so it lands more server updates per
    unit wall-clock on the same hardware budget. Reported:

      * sketch_async_updates_per_sec / sketch_async_sync_rounds_per_sec —
        server-update rates of the two twins (both gated up);
      * sketch_async_vs_sync — their ratio (tight band in
        scripts/check_bench_regression.py; the leg's design claim);
      * sketch_async_time_to_loss_sec + the _vs_sync ratio — wall seconds
        for the async run to first reach the sync twin's final training
        loss (the staleness-discounting quality story under stragglers;
        if never reached, the full async duration is reported — honest
        pessimism, and the ratio then gates the shortfall);
      * sketch_async_retraces — hard-zero invariant (one compiled
        launch/apply pair per rung at ANY concurrency).
    """
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.asyncfed import AsyncFederation
    from commefficient_tpu.data import FedDataset, FedSampler
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.profiling import fence

    W, B = base.num_workers, base.local_batch_size
    K, C, rate = max(W // 2, 1), 2, 0.9
    common = dict(fuse_clients=False, device_data=False,
                  availability="poisson", arrival_rate=rate)
    cfg_async = base.replace(async_buffer=K, async_concurrency=C,
                             staleness_exponent=0.5, **common)
    cfg_sync = base.replace(**common)

    model = ResNet9(num_classes=10, dtype=model_dtype(base.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply,
                                  compute_dtype=base.compute_dtype)
    rng = np.random.default_rng(0)
    n = 4 * W * B
    ds = FedDataset(
        {"x": rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.uint8),
         "y": rng.integers(0, 10, size=(n,)).astype(np.int32)},
        base.num_clients, iid=True, seed=0,
    )

    def run_sync():
        session = FederatedSession(cfg_sync, params, loss_fn,
                                   mesh=make_mesh(1))
        sampler = FedSampler(ds, num_workers=W, local_batch_size=B, seed=0)
        losses = []
        for r in range(2):  # compile + donated-layout warmup
            ids, batch = sampler.sample_round(r)
            fence(session.train_round(ids, batch, 0.1)["loss"])
        t0 = time.perf_counter()
        for r in range(2, 2 + n_updates):
            ids, batch = sampler.sample_round(r)
            m = session.train_round(ids, batch, 0.1)
            losses.append(float(fence(m["loss"])))
        return time.perf_counter() - t0, losses

    def run_async():
        session = FederatedSession(cfg_async, params, loss_fn,
                                   mesh=make_mesh(1))
        sampler = FedSampler(ds, num_workers=W, local_batch_size=B, seed=0)
        total = 2 + n_updates
        engine = AsyncFederation(cfg_async, session, sampler,
                                 lambda _s: 0.1, total,
                                 steps_per_epoch=total).start()
        losses, stamps = [], []
        try:
            t0 = None
            for step, _lr, m in engine.epoch_rounds(0, 0):
                loss = float(fence(m["loss"]))
                if step == 1:  # warmup: both compiled layouts dispatched
                    t0 = time.perf_counter()
                elif step >= 2:
                    losses.append(loss)
                    stamps.append(time.perf_counter() - t0)
            dt = time.perf_counter() - t0
        finally:
            engine.close()
        return dt, losses, stamps, session.retrace_sentinel.retraces

    dt_sync, sync_losses = run_sync()
    dt_async, async_losses, stamps, retraces = run_async()
    target = sync_losses[-1]
    reached = [t for t, l in zip(stamps, async_losses) if l <= target]
    t2l = reached[0] if reached else dt_async
    return {
        "sketch_async_buffer": K,
        "sketch_async_concurrency": C,
        "sketch_async_straggler_rate": round(float(np.exp(-rate)), 3),
        "sketch_async_updates_per_sec": round(n_updates / dt_async, 3),
        "sketch_async_sync_rounds_per_sec": round(n_updates / dt_sync, 3),
        "sketch_async_vs_sync": round(dt_sync / dt_async, 3),
        "sketch_async_time_to_loss_sec": round(t2l, 3),
        "sketch_async_time_to_loss_vs_sync": round(dt_sync / t2l, 3),
        "sketch_async_retraces": retraces,
    }


def _measure_overlap(base, n_rounds: int = 10, n_updates: int = 8) -> dict:
    """Hidden-collectives PR: the two overlap modes vs their sequential
    twins, on the SAME mesh and round shape (the ratios divide two
    measurements of the same run, so load cancels — both get the tight
    band in scripts/check_bench_regression.py and gate UP).

      * sketch_overlap_layerwise_samples_per_sec / _vs_sequential — the
        fused sketch
        round with the table psum + candidate pair-gathers chunked into
        per-leaf-group segments (``--overlap_collectives layerwise``)
        against the monolithic-collective twin;
      * async_double_buffered_updates_per_sec / _vs_sequential — the
        asyncfed engine with the apply fence deferred behind the next
        cohort's launches (``--async_double_buffer``) against the
        sequential-fence twin, spans attached to BOTH so the fence
        discipline (the only thing the double buffer moves) is active;
        the leg also reports both twins' exposed_collective_ms (the new
        v9 metric, informational — near-zero ms bands are noise).

    Requires a multi-device host: on one chip there is no cross-chip
    collective to hide, so both legs report a skip marker instead of a
    fake 1.0 ratio."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from commefficient_tpu.asyncfed import AsyncFederation
    from commefficient_tpu.data import FedDataset, FedSampler
    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.telemetry import PhaseSpans
    from commefficient_tpu.utils.profiling import fence

    n_dev = len(jax.devices())
    if n_dev < 2:
        reason = (f"single-device host ({n_dev} chip) — no cross-chip "
                  "collective to hide")
        return {"sketch_overlap_layerwise_skipped": reason,
                "async_double_buffered_skipped": reason}

    out: dict = {}
    B = base.local_batch_size
    cfg = base.replace(num_devices=n_dev, num_workers=n_dev,
                       num_clients=2 * n_dev)
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply,
                                  compute_dtype=cfg.compute_dtype)
    rng = np.random.default_rng(0)

    # -- leg 1: layerwise-segmented collectives on the fused sketch round
    try:
        ids = jnp.asarray(np.arange(n_dev, dtype=np.int32))
        data = {
            "x": jnp.asarray(
                rng.normal(size=(n_dev, B, 32, 32, 3)).astype(np.float32)
            ),
            "y": jnp.asarray(
                rng.integers(0, 10, size=(n_dev, B)).astype(np.int32)
            ),
        }
        sps = {}
        for ov in ("none", "layerwise"):
            session = FederatedSession(
                cfg.replace(overlap_collectives=ov), params, loss_fn,
                mesh=make_mesh(n_dev),
            )
            state, round_fn = session.state, session.round_fn
            for _ in range(3):  # compile + donated-layout warmup
                state, m = round_fn(state, ids, data, jnp.float32(0.1))
                assert np.isfinite(fence(m["loss"]))
            t0 = time.perf_counter()
            for _ in range(n_rounds):
                state, m = round_fn(state, ids, data, jnp.float32(0.1))
            assert np.isfinite(fence(m["loss"]))
            sps[ov] = n_rounds * n_dev * B / (time.perf_counter() - t0)
        out["sketch_overlap_layerwise_samples_per_sec"] = round(
            sps["layerwise"], 2
        )
        out["sketch_overlap_layerwise_vs_sequential"] = round(
            sps["layerwise"] / sps["none"], 3
        )
    except Exception as e:  # noqa: BLE001 — per-leg error isolation
        out["sketch_overlap_layerwise_error"] = (
            f"{type(e).__name__}: {e}"[:200]
        )

    # -- leg 2: double-buffered asyncfed apply fencing
    try:
        W = n_dev
        cfg_a = cfg.replace(
            fuse_clients=False, device_data=False,
            async_buffer=W, async_concurrency=1,
        )
        n = 4 * W * B
        ds = FedDataset(
            {"x": rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.uint8),
             "y": rng.integers(0, 10, size=(n,)).astype(np.int32)},
            cfg_a.num_clients, iid=True, seed=0,
        )

        def run_engine(double_buffer: bool):
            cfg_run = cfg_a.replace(async_double_buffer=double_buffer)
            session = FederatedSession(cfg_run, params, loss_fn,
                                       mesh=make_mesh(n_dev))
            spans = PhaseSpans(tempfile.mkdtemp(prefix="bench_overlap_"))
            session.spans = spans
            sampler = FedSampler(ds, num_workers=W, local_batch_size=B,
                                 seed=0)
            total = 2 + n_updates
            engine = AsyncFederation(cfg_run, session, sampler,
                                     lambda _s: 0.1, total,
                                     steps_per_epoch=total,
                                     spans=spans).start()
            last = None
            try:
                t0 = None
                for step, _lr, m in engine.epoch_rounds(0, 0):
                    # no per-update fence: the fence discipline under
                    # test is the engine's own (spans-armed) one
                    last = m["loss"]
                    if step == 1:  # warmup: both compiled layouts done
                        fence(last)
                        t0 = time.perf_counter()
                assert np.isfinite(fence(last))
                dt = time.perf_counter() - t0
            finally:
                engine.close()
            stall = engine.stats()["host_stall_ms"]
            return dt, spans.collective_exposure_ms(), stall

        dt_seq, exp_seq, _ = run_engine(False)
        dt_db, exp_db, stall_db = run_engine(True)
        out.update({
            "async_double_buffered_updates_per_sec": round(
                n_updates / dt_db, 3
            ),
            "async_double_buffered_vs_sequential": round(dt_seq / dt_db, 3),
            "async_double_buffered_exposed_collective_ms": round(exp_db, 3),
            "async_sequential_exposed_collective_ms": round(exp_seq, 3),
            "async_double_buffered_host_stall_ms": round(stall_db, 3),
        })
    except Exception as e:  # noqa: BLE001
        out["async_double_buffered_error"] = (
            f"{type(e).__name__}: {e}"[:200]
        )
    return out


def _measure_elastic(base, n_rounds: int = 8) -> dict:
    """Elastic-fleet PR (schema v13): the headline sketch round under a
    scheduled width resize (8 -> 4 for three rounds, then back) through
    the REAL width ladder — one shrink and one grow transition inside
    the timed window. The design claim is the retrace gauge: every
    realized width dispatches a prewarmed per-width program, so a resize
    is a dispatch-table swap (``sketch_elastic_resize_ms`` totals the
    swap cost — microseconds, not a re-trace) and
    ``sketch_elastic_retraces`` must be EXACTLY 0 (gated by
    scripts/check_bench_regression.py). Samples/s counts each round's
    REALIZED width — the fleet does less work while shrunk, and the leg
    reports the real rate, not the base-width fiction."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession, make_mesh
    from commefficient_tpu.utils.profiling import fence

    cfg = base.replace(chaos="resize@4:rounds=3-5")
    W, B = cfg.num_workers, cfg.local_batch_size
    model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    loss_fn = classification_loss(model.apply,
                                  compute_dtype=cfg.compute_dtype)
    session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
    rng = np.random.default_rng(0)
    ids = rng.choice(cfg.num_clients, size=W, replace=False).astype(np.int32)
    batch = {
        "x": rng.normal(size=(W, B, 32, 32, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(W, B)).astype(np.int32),
    }
    # AOT-lower every width's round program (the runner's prewarm path) —
    # without it the first shrunk round would pay a fresh trace and the
    # retrace gauge below would catch it
    session.prewarm_rungs(ids, batch, 0.1)
    env = session.fedsim_env
    # warmup: rounds 0-2 run at the base width (the resize window opens
    # at round 3) — compile + donated-layout warmup outside the window
    for _ in range(3):
        fence(session.train_round(ids, batch, 0.1)["loss"])
    t0 = time.perf_counter()
    samples = 0
    for r in range(3, 3 + n_rounds):
        m = session.train_round(ids, batch, 0.1)
        samples += env.width_at(r) * B  # the round's REALIZED width
    assert np.isfinite(fence(m["loss"]))
    dt = time.perf_counter() - t0
    resizes = sum(1 for rr, _w in env.transitions if rr < 3 + n_rounds)
    return {
        "sketch_elastic_samples_per_sec": round(samples / dt, 2),
        "sketch_elastic_resizes": resizes,
        "sketch_elastic_resize_ms": round(session._fleet_resize_ms, 3),
        "sketch_elastic_retraces": session.retrace_sentinel.retraces,
    }


def _measure_multihost(base, n_rounds: int = 10) -> dict:
    """Multihost PR: the mesh-faked 2-host sketch round (4-axis
    ``(hosts, workers, model, seq)`` mesh, the table psum riding the
    ``(hosts, workers)`` tuple axis) vs its single-host twin on the SAME
    devices and round shape. The ``sketch_multihost_vs_singlehost``
    ratio (multihost sps / singlehost sps, higher is better — registered
    in scripts/check_bench_regression.py) is the leg's design claim:
    declaring the host axis re-SHAPES the mesh without adding a second
    reduction, so the 2-host round must not lose to the flat one (XLA
    lowers the tuple-axis psum to one all-reduce; tests/test_multihost.py
    pins the HLO). Requires >= 2 devices split evenly across the 2
    virtual hosts — a single-chip host reports a named skip marker
    instead of a fake 1.0."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.models import ResNet9, classification_loss
    from commefficient_tpu.models.losses import model_dtype
    from commefficient_tpu.parallel import FederatedSession
    from commefficient_tpu.utils.profiling import fence

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2:
        return {"sketch_multihost_skipped": (
            f"{n_dev} device(s) — the mesh-faked twin needs an even "
            "multi-device host (2 virtual hosts x n chips; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 on cpu)"
        )}

    out: dict = {}
    B = base.local_batch_size
    cfg = base.replace(num_devices=n_dev, num_workers=n_dev,
                       num_clients=2 * n_dev)
    try:
        model = ResNet9(num_classes=10, dtype=model_dtype(cfg.compute_dtype))
        params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
        loss_fn = classification_loss(model.apply,
                                      compute_dtype=cfg.compute_dtype)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(np.arange(n_dev, dtype=np.int32))
        data = {
            "x": jnp.asarray(
                rng.normal(size=(n_dev, B, 32, 32, 3)).astype(np.float32)
            ),
            "y": jnp.asarray(
                rng.integers(0, 10, size=(n_dev, B)).astype(np.int32)
            ),
        }
        sps = {}
        for hosts in (1, 2):
            # no explicit mesh: the session builds its own from the
            # config, which is exactly the num_hosts dispatch under test
            session = FederatedSession(cfg.replace(num_hosts=hosts),
                                       params, loss_fn)
            state, round_fn = session.state, session.round_fn
            for _ in range(3):  # compile + donated-layout warmup
                state, m = round_fn(state, ids, data, jnp.float32(0.1))
                assert np.isfinite(fence(m["loss"]))
            t0 = time.perf_counter()
            for _ in range(n_rounds):
                state, m = round_fn(state, ids, data, jnp.float32(0.1))
            assert np.isfinite(fence(m["loss"]))
            sps[hosts] = n_rounds * n_dev * B / (time.perf_counter() - t0)
        out["sketch_multihost_samples_per_sec"] = round(sps[2], 2)
        out["sketch_multihost_vs_singlehost"] = round(sps[2] / sps[1], 3)
    except Exception as e:  # noqa: BLE001 — per-leg error isolation
        out["sketch_multihost_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--matrix", action="store_true",
        help="also time the non-headline federated paths (sketch-vmap with "
        "clipping, local_topk + local error, fedavg) and write "
        "BENCH_MATRIX.json; the headline line stays the LAST stdout line",
    )
    # the GPT-2-small legs dominate wall-clock; --no-gpt2 keeps a run to
    # the CV legs
    ap.add_argument("--no-gpt2", dest="gpt2", action="store_false",
                    help="skip the GPT-2-small legs")
    args = ap.parse_args()

    from commefficient_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # every leg below prints under a device-metric name: a CPU timing
        # there is a wrong number, not a slow one
        raise SystemExit(
            f"bench.py measures the chip and found platform {platform!r}: "
            "run it on a TPU (one process per chip)"
        )

    rows = {}
    if args.matrix:
        # The paths the reference actually calls federated (VERDICT r2 item
        # 5): clip/DP/local-state configs are vmap-per-client (the fused
        # flat-batch identity needs nothing per-client), so they pay W
        # separate gradient passes at B instead of one at W*B.
        base = _headline_cfg()
        matrix = {
            "sketch_vmap_clip": base.replace(
                fuse_clients=False, max_grad_norm=1.0
            ),
            "local_topk_local_err": base.replace(
                mode="local_topk", error_type="local", virtual_momentum=0.0,
                fuse_clients=False,
            ),
            "fedavg_4local": base.replace(
                mode="fedavg", error_type="none", virtual_momentum=0.0,
                num_local_iters=4,
            ),
            "uncompressed_fused": base.replace(
                mode="uncompressed", error_type="none", virtual_momentum=0.0,
            ),
            # r3 mixed precision: model fwd/bwd in bf16 (native MXU),
            # master params / grads / sketch algebra stay f32 —
            # lab-validated accuracy parity (CHANGELOG_r3)
            "sketch_fused_bf16": base.replace(compute_dtype="bfloat16"),
            # PR 2: rank-4 PowerSGD vs the sketch headline at the same
            # round shape (server-side GS power iteration replaces the
            # unsketch extract)
            "powersgd_r4_fused": base.replace(mode="powersgd",
                                              powersgd_rank=4),
            # PR 3 telemetry: the level-2 in-graph diagnostics (norms +
            # sentinel + sketch round-trip fidelity) riding the headline
            # round — tracks the observability tax against the level-0
            # headline (which is bit-identical to pre-telemetry rounds)
            "sketch_telemetry_l2": base.replace(telemetry_level=2),
            # fedsim PR: the headline sketch round under bernoulli 30%
            # dropout — masked per-client transmits (vmap path: masking
            # disables the fused fast path) + live-count renormalization.
            # Tracks the partial-participation tax against the fused
            # headline AND against sketch_vmap_clip (its vmap twin).
            "sketch_dropout30": base.replace(
                availability="bernoulli", dropout_prob=0.3
            ),
        }
        for name, cfg in matrix.items():
            # per-leg error isolation (the GPT-2 legs' pattern): one leg's
            # failure must not discard the others' measured rows
            try:
                sps = _measure(cfg)
            except Exception as e:  # noqa: BLE001
                rows[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
                print(json.dumps({"metric": name,
                                  "error": rows[f"{name}_error"]}))
                continue
            rows[name] = round(sps, 2)
            print(json.dumps({"metric": name, "value": rows[name],
                              "unit": "samples/s"}))
        # control PR: the rung-switch cost on the headline sketch round —
        # 2-rung k-ladder, fixed schedule switching halfway. The retrace
        # count is the design claim (0: the switch dispatches a prewarmed
        # program); switch_round_ms is its one-off backend-compile +
        # state-migration cost; steady sps tracks the (expected-zero)
        # controller host tax vs the headline.
        try:
            ctl = _measure_ladder_switch(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_ladder_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_ladder_switch",
                              "error": rows["sketch_ladder_error"]}))
        else:
            rows.update(ctl)
            print(json.dumps({"metric": "sketch_ladder_switch", **ctl}))
        # resilience PR: snapshot/rollback primitive cost on the headline
        # round — the recovery tax is paid per --snapshot_every boundary
        # (snapshot) and per divergence (rollback); retraces must be 0
        # (the restore re-commits leaves onto their original shardings).
        try:
            res = _measure_recovery(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_resilience_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_resilience",
                              "error": rows["sketch_resilience_error"]}))
        else:
            rows.update(res)
            print(json.dumps({"metric": "sketch_resilience", **res}))
        # sparse-aggregate PR: pair-exchange vs dense-psum twins per topk
        # mode on the multi-device mesh (per-mode error isolation happens
        # inside; a single-device host yields only a skip marker)
        try:
            sa = _measure_sparse_agg(base)
        except Exception as e:  # noqa: BLE001
            rows["sparse_agg_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sparse_agg",
                              "error": rows["sparse_agg_error"]}))
        else:
            rows.update(sa)
            print(json.dumps({"metric": "sparse_agg", **sa}))
        # clientstore PR: the host-resident client-state round vs its
        # device-resident twin (per-leg error isolation happens inside)
        try:
            hc = _measure_hostclient(base)
        except Exception as e:  # noqa: BLE001
            rows["local_topk_hostclient_error"] = \
                f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "local_topk_hostclient",
                              "error": rows["local_topk_hostclient_error"]}))
        else:
            rows.update(hc)
            print(json.dumps({"metric": "local_topk_hostclient", **hc}))
        # asyncfed PR: the buffered-async engine vs its synchronous twin
        # under ~40% poisson stragglers — server-update rate, time to the
        # sync twin's final loss, and the hard-zero retrace invariant
        try:
            asy = _measure_async(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_async_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_async",
                              "error": rows["sketch_async_error"]}))
        else:
            rows.update(asy)
            print(json.dumps({"metric": "sketch_async", **asy}))
        # hidden-collectives PR: layerwise-segmented collectives and the
        # double-buffered asyncfed apply vs their sequential twins (skip
        # markers on a single-device host — nothing cross-chip to hide)
        try:
            ovl = _measure_overlap(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_overlap_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_overlap",
                              "error": rows["sketch_overlap_error"]}))
        else:
            rows.update(ovl)
            print(json.dumps({"metric": "sketch_overlap", **ovl}))
        # round-tracing PR: critical-path attribution of the headline
        # sketch round — mean exclusive ms per stage + the binding
        # stage's name (every measured round fenced, so rows are
        # honest wall-clock but slower than the headline by design:
        # informational, never gated)
        try:
            tr = _measure_traced(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_traced_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_traced",
                              "error": rows["sketch_traced_error"]}))
        else:
            rows.update(tr)
            print(json.dumps({"metric": "sketch_traced", **tr}))
        # multihost PR: the mesh-faked 2-host round vs its single-host
        # twin (per-leg error isolation happens inside; an odd/single
        # device host yields only a named skip marker)
        try:
            mh = _measure_multihost(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_multihost_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_multihost",
                              "error": rows["sketch_multihost_error"]}))
        else:
            rows.update(mh)
            print(json.dumps({"metric": "sketch_multihost", **mh}))
        # elastic-fleet PR: the headline round across a scheduled width
        # shrink + grow through the real width ladder — resize cost and
        # the hard-zero retrace gauge (per-leg error isolation as above)
        try:
            el = _measure_elastic(base)
        except Exception as e:  # noqa: BLE001
            rows["sketch_elastic_error"] = f"{type(e).__name__}: {e}"[:200]
            print(json.dumps({"metric": "sketch_elastic",
                              "error": rows["sketch_elastic_error"]}))
        else:
            rows.update(el)
            print(json.dumps({"metric": "sketch_elastic", **el}))

    # pipeline PR: the pipelined-execution leg rides the HEADLINE line
    # (gated by scripts/check_bench_regression.py — occupancy + samples/s
    # directions registered there), with the same per-leg error isolation
    # as the GPT-2 legs: an engine failure must not discard the headline.
    pipe: dict = {}
    try:
        pipe = _measure_pipeline(_headline_cfg())
        print(json.dumps({"metric": "sketch_pipelined", **pipe}))
    except Exception as e:  # noqa: BLE001
        pipe = {"sketch_pipelined_error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps({"metric": "sketch_pipelined",
                          "error": pipe["sketch_pipelined_error"]}))

    audit_box: dict = {}
    headline = _measure(_headline_cfg(), audit_box=audit_box)
    headline_audit = audit_box.pop("_audit", None)
    headline_cfg = audit_box.pop("_cfg", None)
    peak, chip = _chip_peak_flops()
    mfu = headline * resnet9_train_flops_per_sample() / peak
    # GPT-2 line (VERDICT r4 weak 5 / item 8): language-scale perf was
    # wall-clock seconds in lab logs with nobody tracking regressions —
    # now tokens/s + MFU for the BASELINE #4 sketch round and its
    # uncompressed twin ride the same headline JSON line every round.
    gpt2 = {}
    if not args.gpt2:
        gpt2 = {"gpt2_skipped": "--no-gpt2"}
    else:
        # the sketch leg runs PER BACKEND (the r5 3.5x sketch-round gap
        # is a kernel property): einsum keeps the legacy key names so
        # BENCH_r* rows stay comparable; pallas gets suffixed keys. Each
        # leg fails INDEPENDENTLY (per-leg *_error key) — a Mosaic/pallas
        # failure must not discard the measured legacy einsum rows, and
        # the CV headline must survive any of them.
        legs = [("uncompressed", "einsum", "gpt2_uncompressed", 0),
                ("sketch", "einsum", "gpt2_sketch", 0),
                # scan-engine dispatch amortization on the SAME optimized
                # sketch config: 8 rounds per lax.scan dispatch (the
                # sketch-gap PR; pipeline/scan_engine.py is the train-loop
                # realization, this leg isolates the dispatch win)
                ("sketch", "einsum", "gpt2_sketch_scan", 8),
                # per-mode leg (PR 2): the PowerSGD round rides the same
                # line so its GS/matmul server cost is tracked vs the twins
                ("powersgd", "einsum", "gpt2_powersgd", 0)]
        if len(jax.devices()) > 1:
            # sharded-decode leg (PR 6): the change that targets the
            # headline gpt2_sketch_vs_uncompressed gap — each chip decodes
            # only its D/W slice, ~W*k candidate pairs replace the full-D
            # server extraction. Its uncompressed twin runs on the SAME
            # multichip mesh so the ratio isolates the decode (a 1-chip
            # denominator would credit the added chips to the decode).
            # Single-chip hosts skip both: with one worker device the
            # 'sharded' decode is the degenerate full-range gather path
            # (strictly worse — auto picks dense there), not a
            # measurement of the design.
            legs.append(("uncompressed_multichip", "einsum",
                         "gpt2_uncompressed_multichip", 0))
            legs.append(("sketch_sharded", "einsum", "gpt2_sketch_sharded",
                         0))
        else:
            gpt2["gpt2_sketch_sharded_skipped"] = (
                "sharded decode needs a >1-device workers mesh (auto "
                "resolves dense on one chip; nothing to measure)"
            )
        legs.append(("sketch", "pallas", "gpt2_sketch_pallas", 0))
        for m, backend, key, scan in legs:
            try:
                tps, gmfu, spr, audit_keys = _measure_gpt2(
                    m, sketch_backend=backend, scan_rounds=scan
                )
            except Exception as e:  # noqa: BLE001
                gpt2[f"{key}_error"] = f"{type(e).__name__}: {e}"[:200]
                continue
            gpt2[f"{key}_tokens_per_sec"] = round(tps, 1)
            gpt2[f"{key}_mfu"] = round(gmfu, 4)
            gpt2[f"{key}_sec_per_round"] = round(spr, 4)
            if scan:
                gpt2[f"{key}_rounds_per_dispatch"] = scan
            for ak, av in audit_keys.items():
                # audited per-leg FLOPs / peak-HBM / MFU from the compiled
                # artifact, next to the hand-model numbers above
                gpt2[f"{key}_{ak}"] = av
        for key in ("gpt2_sketch", "gpt2_sketch_scan", "gpt2_sketch_pallas",
                    "gpt2_powersgd", "gpt2_sketch_sharded"):
            num = gpt2.get(f"{key}_tokens_per_sec")
            # the sharded leg compares against its SAME-mesh uncompressed
            # twin; everything else against the 1-chip baseline
            den = gpt2.get(
                "gpt2_uncompressed_multichip_tokens_per_sec"
                if key == "gpt2_sketch_sharded"
                else "gpt2_uncompressed_tokens_per_sec"
            )
            if num is not None and den:
                gpt2[f"{key}_vs_uncompressed"] = round(num / den, 4)
    import jaxlib

    line = {
        "metric": "fed_resnet9_sketch_train_samples_per_sec_per_chip",
        "value": round(headline, 2),
        "unit": "samples/s",
        "vs_baseline": round(headline / BASELINE_SAMPLES_PER_SEC, 4),
        # model-FLOPs utilization: samples/s x analytic ResNet-9
        # fwd+bwd FLOPs / chip bf16 peak — hardware-anchored, unlike
        # vs_baseline's A100-class estimate (VERDICT r3 weak 5)
        "mfu": round(mfu, 4),
        "chip": chip,
        # run provenance, so trajectory comparisons (scripts/
        # check_bench_regression.py) are apples-to-apples across hosts
        "devices": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        # audited twin of mfu/headline from the compiled round artifact
        # (telemetry/xla_audit.py; `audit_error` when it degraded)
        **audit_box,
        # pipelined-execution leg (pipeline/ PR): depth-2 vs synchronous
        # host staging, engine occupancy + residual host stall
        **pipe,
        **gpt2,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    if headline_audit is not None:
        # the schema-valid perf_report.json artifact for the headline
        # round (acceptance: bench writes one; checker-validated)
        try:
            headline_audit.write(OUT_DIR, generated_by="bench",
                                 cfg=headline_cfg)
        except Exception as e:  # noqa: BLE001
            line["perf_report_error"] = f"{type(e).__name__}: {e}"[:200]
    if args.matrix:
        rows["sketch_fused_headline"] = round(headline, 2)
        rows["mfu_model_flops"] = round(mfu, 4)
        rows["chip"] = chip
        rows.update(audit_box)
        rows.update(pipe)
        rows.update(gpt2)
        with open(os.path.join(OUT_DIR, "BENCH_MATRIX.json"), "w") as f:
            json.dump(rows, f, indent=2)
    print(json.dumps(line))
    # per-leg error isolation keeps the other legs' rows; it does not make
    # a run with a failed leg a passing one
    failed = sorted(k for k in {**rows, **line} if k.endswith("_error"))
    if failed:
        print(f"bench: {len(failed)} leg(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
