"""Typed configuration system.

The reference threads a single flat ``argparse.Namespace`` through every
layer (``utils.py parse_args`` ~L20-180, SURVEY.md §2 "Config system"). We
keep the *flag names* for run-command parity (``--mode``, ``--k``,
``--num_rows``, ...), but back them with a frozen dataclass so the config is
hashable (usable as a static jit argument), documented, and validated at
construction instead of at first crash.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass
from typing import Optional

# mirrors the compress/ registry (compress.available_modes); the two are
# pinned equal by tests/test_mode_dispatch.py
MODES = ("uncompressed", "sketch", "true_topk", "local_topk", "fedavg",
         "powersgd")
ERROR_TYPES = ("none", "local", "virtual")
# mirrors the fedsim/ availability registry (fedsim.available_models);
# pinned equal by tests/test_fedsim.py — same no-cycle pattern as MODES
AVAILABILITY_MODELS = ("always", "bernoulli", "cohort", "poisson", "sine")
# mirrors the control/ policy registry (control.CONTROL_POLICIES); pinned
# equal by tests/test_control.py — same no-cycle pattern as MODES
CONTROL_POLICIES = ("none", "fixed", "budget_pacing", "ef_feedback",
                    "staleness_aware")
# mirrors the resilience/ recovery-policy registry (resilience.policy
# POLICIES); pinned equal by tests/test_mode_dispatch.py — same no-cycle
# pattern as MODES/CONTROL_POLICIES
RECOVER_POLICIES = ("none", "retry", "demote", "skip_clients")
# mirrors the clientstore/ store registry (clientstore.available_stores);
# pinned equal by tests/test_clientstore.py — same no-cycle pattern as MODES
CLIENT_STORES = ("device", "host", "mmap")


@dataclass(frozen=True)
class Config:
    """All knobs of a federated run. Field names follow the reference flags."""

    # --- compression / mode (reference: --mode, --k, --num_rows, --num_cols,
    # --num_blocks) ---
    mode: str = "uncompressed"
    k: int = 50_000  # sparsity of the extracted update (sketch/topk modes)
    # top-k selection kernel: "exact" (lax.top_k), "threshold" (binary-
    # searched magnitude threshold, ≤k nonzeros, no sort/scatter — the TPU
    # fast path), "approx" (lax.approx_max_k, ~0.95 recall).
    topk_method: str = "exact"
    num_rows: int = 5  # sketch rows r
    num_cols: int = 500_000  # sketch columns c
    # >1 bounds full-d unsketch-estimate transients to r*D/num_blocks via
    # the exact-gather path (slower; reference --num_blocks memory trade)
    num_blocks: int = 1
    do_topk_down: bool = False  # top-k compress the downlink too

    # --- powersgd (compress/powersgd.py; PowerSGD, arXiv:1905.13727) ---
    # rank r of the warm-started power-iteration approximation; the flat
    # [D] update is matricized near-square [n, m] (n ~ m ~ sqrt(D)), so the
    # factored downlink is r*(n+m) floats — compression ~ sqrt(D)/(2r).
    powersgd_rank: int = 4
    # carry Q = M^T P_hat across rounds in FedState (the paper's warm
    # start — one power iteration per round then tracks the top subspace);
    # False resamples a fresh Gaussian Q from (seed, step) each round.
    powersgd_warm_start: bool = True

    # --- momentum / error feedback (reference: --virtual_momentum,
    # --local_momentum, --error_type) ---
    virtual_momentum: float = 0.0  # server-side momentum factor rho
    local_momentum: float = 0.0  # per-client momentum factor
    error_type: str = "none"  # where error feedback lives

    # --- federation shape (reference: --num_clients, --num_workers,
    # --num_devices, --local_batch_size, --iid / --non_iid) ---
    num_clients: int = 16  # total virtual clients
    num_workers: int = 8  # participating clients per round
    num_devices: int = 1  # mesh size the workers are multiplexed onto
    local_batch_size: int = 8  # per-client batch per round
    iid: bool = True  # IID vs pathological-non-IID client sharding

    # --- fedavg (reference: --num_local_iters, --local_lr) ---
    num_local_iters: int = 1
    # None (default): local SGD steps run at the server schedule's current
    # lr and the net applied delta is the true FedAvg averaged weight delta.
    # Setting it decouples local from server lr (see round.py docstring).
    local_lr: Optional[float] = None

    # --- optimization (reference: --lr_scale, --pivot_epoch, --num_epochs,
    # --max_grad_norm, --weight_decay, --momentum_type) ---
    lr_scale: float = 0.4
    pivot_epoch: int = 5
    num_epochs: int = 24
    max_grad_norm: Optional[float] = None
    weight_decay: float = 5e-4
    # Zero momentum at the extracted/transmitted coordinates ("momentum
    # masking"/dampening). None = AUTO, resolved per mode on the
    # r4 four-corner evidence (see round.py build_round_fn): local_topk ->
    # True (reference behavior, applies with local momentum); true_topk ->
    # False (r4: unmasked 0.8923 vs masked 0.8595 at tuned lr on the v3
    # task — the earlier overshoot reading was a v2-task artifact; the
    # reference masks here, so set True explicitly for exact reference
    # behavior); sketch -> False (FetchSGD Alg 1; masking via noisy
    # estimates destabilizes — see round.py warning).
    momentum_dampening: Optional[bool] = None
    # momentum_dampening=True with mode=sketch subtracts sketches of NOISY
    # momentum estimates every round and measurably diverges at paper-scale
    # settings (round.py warning; ~step 70 where unmasked converges). It is
    # kept only for parity experiments and must be opted into explicitly.
    allow_unstable_sketch_dampening: bool = False
    # Virtual-error decay gamma: e <- gamma * e after each round's
    # extract-and-subtract (sketch + true_topk virtual error). 1.0 (default,
    # reference behavior) carries residual error indefinitely; < 1.0 leaks
    # stale error mass — the d/c-envelope mitigation probed by the r4 lab
    # (high d/c diverges through error-feedback SNR collapse; see
    # CHANGELOG_r3 regime account and scripts/sketch_lab.py --error_decay).
    error_decay: float = 1.0

    # --- model / dataset (reference: --model, --dataset_name,
    # --dataset_dir) ---
    model: str = "resnet9"
    dataset_name: str = "cifar10"
    dataset_dir: str = "./data"
    # Stand-in generator used when the real dataset is absent (zero-egress
    # environments): "flat" (legacy template+noise; gradient spectrum is
    # unrealistically flat — FetchSGD's heavy-hitter premise fails on it by
    # construction) or "concentrated" (shared low-rank backbone + localized
    # per-class texture patches + label noise; ResNet-9 gradients
    # concentrate like real CIFAR's — see scripts/grad_probe.py).
    synthetic_variant: str = "flat"
    # Label-noise fraction for the synthetic FEMNIST stand-in
    # (data/emnist.py): that fraction of each client's samples is relabeled
    # uniformly within the client's OWN class subset (non-IID structure
    # preserved), bounding the accuracy ceiling below 1.0 (see
    # _synthetic_femnist's ceiling math). Default 0.06 is the r5 value;
    # exposed so the pre-r5 (r4) noise-free stand-in is reconstructible for
    # audit with --label_noise 0 (ADVICE.md round-5 item). Ignored when
    # real LEAF data is on disk, and by the CIFAR synthetic (which has its
    # own fixed recipe).
    label_noise: float = 0.06
    # None (default): derived from dataset_name (cifar10->10, cifar100->100,
    # femnist->62, imagenet->1000) — guards against silently training a
    # 10-class head on ImageNet (VERDICT r1 weak 6).
    num_classes: Optional[int] = None

    # --- GPT-2 workload (reference: --model_checkpoint, --num_candidates,
    # --max_history, --lm_coef, --mc_coef) ---
    model_checkpoint: str = "gpt2"
    num_candidates: int = 2
    max_history: int = 2
    lm_coef: float = 1.0
    mc_coef: float = 1.0
    max_seq_len: int = 256
    # lm_train's FedText: the median document length the packed rows are made
    # of (log-normal, sigma 1, clipped to 16..max_seq_len)
    doc_median: float = 300.0

    # --- privacy (reference: DP clip+noise flags, fed_worker.py ~L380-420) ---
    dp_noise_multiplier: float = 0.0

    # --- TPU fast path ---
    # Fuse the per-device clients' gradients into ONE flattened-batch grad
    # (2x faster than the per-client vmap on v5e). Mathematically identical
    # to the reference's average-of-per-client-gradients whenever no
    # per-client state/clip/noise is configured AND every sample carries
    # valid labels (true for the CV workloads; for GPT-2's masked LM loss
    # the flat mean weights clients by token count instead of equally, so
    # leave it off there). Ignored (vmap path used) for fedavg/local_topk
    # or when local momentum / local error / clip / DP noise is on.
    fuse_clients: bool = False

    # Keep the whole (uint8) training set resident in device HBM and ship
    # only [W, B] sample indices + the augmentation plan each round (~KBs
    # instead of the pixel batch). CIFAR-scale sets (154 MB) fit HBM
    # trivially. Auto-disabled by cv_train when the
    # dataset exceeds device_data_max_mb or the mode needs host batches.
    device_data: bool = True
    device_data_max_mb: int = 512

    # --- memory (TPU-native; SURVEY.md §7 hard-parts) ---
    # Where the per-client momentum/error rows live (clientstore/ registry):
    # "device" (default — today's [num_clients, D] device arrays inside
    # FedState, bit-untouched; NOTHING clientstore-related is constructed,
    # the telemetry_level-0 discipline), "host" (pinned-numpy bank in host
    # RAM; only the round's W participant rows cross PCIe each round, C
    # bounded by host DRAM), "mmap" (the same cohort-streaming contract
    # over a memory-mapped file; C bounded by disk). host/mmap gather the
    # cohort's rows inside its round and write back asynchronously after
    # the drain fence, so the compiled round's HLO carries no [C, D]-scale
    # gather and the strict O(W·k) sparse-aggregate bound holds with no
    # exemption (README "Host-resident client state").
    client_store: str = "device"
    # LRU device cache capacity (rows) for hot cohort rows under a
    # host/mmap store — availability models make some clients far more
    # frequent than others, and a cached row skips both the host gather
    # and the H2D stage. 0 (default) = no cache (every round gathers from
    # the bank). Write-through-on-eviction keeps the bank authoritative.
    client_store_cache_rows: int = 0
    # Backing file for --client_store mmap ("" = a run-scoped temp file,
    # deleted on close). A named path persists across reopen — the store
    # contract pins gather-after-reopen equality.
    client_store_path: str = ""
    # DEPRECATED: whole-store host offload, superseded by the per-cohort
    # clientstore (--client_store host). Setting it warns and aliases to
    # client_store="host"; the flag will be removed.
    offload_client_state: bool = False
    # FSDP-shard the flat param vector AND dense server momentum/error over
    # the workers mesh axis (parallel/fsdp.py): persistent per-chip state
    # drops from up to 3x[D] to ~[D/W] (+ small replicated sketch tables).
    # Server modes only (uncompressed/true_topk/sketch, threshold top-k);
    # local modes shard their memory wall via --client_store host|mmap
    # instead.
    fsdp: bool = False
    # Model compute precision: "mixed" (default — flax module matmuls
    # bf16, params/residual-boundaries f32), "bfloat16" (params also cast
    # at the loss boundary: the FULL stream incl. GPT-2 embeddings/
    # residuals/tied head runs bf16 — an accuracy/memory control,
    # speed-neutral at single-chip microbatches per CHANGELOG_r3's
    # corrected measurement; see models/losses._resolve_compute_dtype), or
    # "float32" (true f32 throughout — the reference's precision).
    # Master params, gradients, compression, and the server update are
    # f32 in every mode; cross-entropies compute f32.
    compute_dtype: str = "mixed"
    # Sketch matmul dtype ("float32" | "bfloat16"). Measured r2: NO speed
    # or accuracy difference on v5e (default f32 matmul precision is
    # already bf16-pass and the round is not matmul-bound) — kept as an
    # explicit knob for hardware where it matters.
    sketch_dtype: str = "float32"
    # Sketch table STORAGE dtype ("float32" | "bfloat16") — distinct from
    # sketch_dtype (the matmul OPERAND dtype above). "bfloat16" stores
    # and psums the [r, c] tables in bf16 while every accumulation (the
    # in-row reductions, the server momentum/error algebra) stays f32:
    # table HBM traffic and the device_encode psum's collective bytes
    # halve (100 MB -> 50 MB per round per link at the GPT-2 5x5M
    # geometry), at ~2^-8 relative rounding per downcast — the compress/
    # LINEAR contract then holds to that pinned tolerance instead of
    # bit-exactly (tests/test_countsketch_bf16.py). "float32" (default)
    # is bit-untouched: every golden recording pins it.
    sketch_table_dtype: str = "float32"
    # Sketch-FUSED backward (parallel/round.py make_sketch_grad_one):
    # per-leaf custom_vjp taps sketch each param leaf's cotangent
    # directly into the [r, c] table during the backward pass, so
    # make_grad_one's ravel_pytree flat [D] grad — a 500 MB transient at
    # GPT-2 scale — is NEVER materialized in sketch mode (the compiled
    # round is pinned free of the flat_grad_concat marker). Linearity
    # makes it exact up to float summation order (pinned tolerance, not
    # bit-equal — hence opt-in; the default keeps golden parity
    # bit-untouched). Requires the fused flattened-batch path: mode=
    # sketch, fuse_clients, no local momentum/clip/DP-noise/fedsim
    # (validated at construction).
    sketch_fused_bwd: bool = False
    # CountSketch banded-bucket width (ops/countsketch.py v5): each chunk's
    # collision pool is band*stride buckets; larger = closer to classic
    # sketch statistics (stabler FetchSGD feedback), smaller = cheaper
    # matmuls. band=16 measured stable at paper-scale d/c=13.
    sketch_band: int = 16
    # Explicit CountSketch chunk size m (None = the measured adaptive rule,
    # ops/countsketch.py chunk_m). Lab knob for the d/c~100 regime.
    sketch_m: Optional[int] = None
    # Hash family: "fmix32" (production default) or "poly4" — seed-derived
    # 4-universal Mersenne polynomials, the reference csvec's guarantee
    # class, for lab A/B runs against fmix32 (see
    # ops/countsketch.py CountSketch.hash_family). With
    # sketch_backend="einsum" poly4 is CV-scale-only (host-materialized
    # [d_eff] sign vector); sketch_backend="pallas" evaluates the
    # polynomial in-kernel and lifts poly4 to GPT-2 scale.
    hash_family: str = "fmix32"
    # Sketch server-decode strategy for the REPLICATED round ("auto" |
    # "dense" | "sharded"). "dense": the legacy path — every chip
    # redundantly runs the full-D estimate_all -> top-k -> unsketch ->
    # re-sketch server extraction (at D=124M that IS the round; BENCH_r05
    # gpt2_sketch_vs_uncompressed=0.287). "sharded": the FSDP decode
    # discipline on replicated state — each chip estimates only its D/W
    # coordinate slice (estimate_at over offset global hashes), the
    # global top-<=k threshold uses scalar-only collectives, and ONE
    # ~W*k-pair all_gather of compacted candidates replaces the per-chip
    # full-D decode (requires topk_method='threshold'; mode='sketch').
    # "auto" (default): sharded exactly when it can win and cannot change
    # results — >1 worker device AND threshold top-k; single-device
    # rounds and exact/approx selections keep the dense path, so golden
    # recordings and CPU tier-1 defaults are bit-untouched. See README
    # "Sketch decode architecture".
    sketch_decode: str = "auto"
    # On-mesh aggregation strategy for the top-k modes ("auto" | "dense"
    # | "sparse"). "dense": the legacy full-[D] psum of the per-device
    # client-transmit sum. "sparse": the ops/collectives pair exchange —
    # compact the <=k-sparse transmit to (idx, val) buffers and move
    # O(W*k) pairs instead of O(D) slots (arXiv:2201.07598 style).
    # local_topk rebuilds the replicated dense aggregate from one
    # W*k-pair all_gather; true_topk re-homes server momentum/error onto
    # the workers axis (reduce-scatter aggregate + sharded threshold
    # select + candidate pair exchange, the FSDP decode discipline on the
    # replicated round — requires topk_method='threshold'); sketch keeps
    # its dense [r,c] table psum but rides the pair exchange for the
    # zero-HH EF re-sketch (sharded decode only). "auto" (default):
    # sparse exactly when it cannot change stored state shapes — mode
    # 'local_topk' AND >1 worker device AND topk_method='threshold';
    # 1-device meshes and every other mode keep the dense psum, so golden
    # recordings and level-0 HLO are bit-untouched. true_topk/sketch
    # engage only on an explicit "sparse" (their summation order or state
    # placement changes). See README "Sparse allreduce collective layer".
    aggregate: str = "auto"
    # Collective/compute overlap ("none" | "layerwise"). "layerwise"
    # chunks the round's aggregation collectives into independent
    # segments so XLA's latency-hiding scheduler can run them
    # concurrently with remaining compute: the sketch-FUSED backward
    # (sketch_fused_bwd) accumulates per-leaf-GROUP tables and psums
    # each group as its own collective the moment backprop finishes
    # producing it (FSDP-style bucketed overlap — early layers'
    # aggregation starts while later layers still differentiate), and
    # the sparse pair exchanges (local_topk / true_topk / the sketch
    # EF ride) split their W*k all_gather into segment gathers whose
    # ordered concatenation is BIT-equal to the monolithic gather
    # (pure data movement). Segmented psums are bit-equal to ONE psum
    # of the same segments (an all-reduce is elementwise; no
    # reassociation within a segment) — but per-GROUP table
    # accumulation reorders the per-chip cotangent fan-in, so the
    # fused-backward layerwise round tracks overlap="none" at the same
    # summation-order tolerance sketch_fused_bwd itself is pinned to.
    # "none" (default): nothing overlap-related is traced and the round
    # stays byte-identical to a pre-overlap build (the telemetry_level-0
    # discipline; golden recordings pin it). See README "Hiding the
    # collectives".
    overlap_collectives: str = "none"
    # CountSketch kernel backend for the matmul-path ops ("einsum" |
    # "pallas"). "einsum" (default): the banded one-hot einsum +
    # overlap-add — runs everywhere, the r1-r5 production path. "pallas":
    # tiled Pallas TPU kernels (ops/pallas/countsketch_kernels.py) that
    # generate hashes/signs/one-hots on the fly inside the kernel — no
    # [m, V] one-hot constant, no [nc, V] HBM round-trip, no [d_eff] sign
    # vector; targets the GPT-2-scale sketch-round gap (BENCH_r05:
    # sketch 0.50 s vs uncompressed 0.14 s). On CPU hosts the Pallas path
    # runs under interpret mode (slow; for tests/labs, not production).
    sketch_backend: str = "einsum"

    # --- mesh axes beyond the reference (TPU-native; VERDICT r2 item 3) ---
    # The federated round's mesh is (workers=num_devices, model=model_axis,
    # seq=seq_axis); total chips = product. model/seq > 1 shards each
    # client's loss COMPUTE (Megatron-style heads/MLP-hidden over `model`,
    # ring-attention tokens over `seq` — parallel/tensor.py
    # build_tp_flat_loss) while params/compression stay the replicated flat
    # vector, so every mode's server algebra is unchanged. Consumed by
    # gpt2_train; cv_train is data-parallel only (as is the reference).
    model_axis: int = 1
    seq_axis: int = 1
    # --- multi-host topology (commefficient_tpu/multihost/) ---
    # Declared host axis size: > 1 prepends a `hosts` axis to the mesh
    # ((hosts, workers, model, seq); parallel/mesh.py make_mesh), splits
    # the client population into per-host partitions (multihost/
    # topology.py), and routes every worker-axis collective over the
    # (hosts, workers) tuple. 1 (default) = the single-host 3-axis mesh,
    # byte-identical to a pre-multihost build. Works both with real
    # multi-process runs (--distributed) and mesh-faked on one process
    # (N virtual hosts over the local devices — the CI twin).
    num_hosts: int = 1
    # Call the jax.distributed bring-up at train entry (multihost/
    # bringup.py initialize_multihost): reads JAX_COORDINATOR_ADDRESS /
    # JAX_NUM_PROCESSES / JAX_PROCESS_ID and connects this process to
    # the pod before any device query. False (default): single-process —
    # mesh-faked multihost (num_hosts > 1) still works without it.
    distributed: bool = False
    # Bounded retry-with-backoff on the coordinator connect: a pod
    # bring-up races the coordinator process, so the first refused
    # connection is normal — retry up to N attempts total (exponential
    # backoff between them) before failing with an error naming the
    # coordinator address and the attempt count (multihost/bringup.py).
    distributed_connect_retries: int = 3

    # --- telemetry (commefficient_tpu/telemetry/; TPU-native, no reference
    # analog — the reference logs only train/loss + lr) ---
    # 0 = off (default): the jitted round is bit-identical to a pre-
    # telemetry program (nothing is traced; pinned by golden parity + the
    # HLO smoke test). 1 = health: diag/* norms + non-finite sentinel
    # in-graph, comm/* byte scalars, flight recorder. 2 = + compressor
    # fidelity (sketch round-trip estimation error — one extra sketch+
    # estimate pass per round; powersgd reconstruction residual — vector
    # ops only). See telemetry/ package docstring for per-level cost.
    telemetry_level: int = 0
    # Ring-buffer size of the divergence flight recorder: how many drained
    # round records ride in flight_<step>.json when a run goes non-finite
    # (telemetry/flight.py). Active at telemetry_level >= 1.
    flight_window: int = 16
    # Retrace budget for the jitted round (telemetry/xla_audit.py
    # RetraceSentinel): None (default) only counts — `xla/retraces` rides
    # the drained metrics at telemetry_level >= 1; an int N hard-fails
    # (RetraceError naming the offending argument-signature diff) on the
    # N+1-th retrace. A mid-run retrace silently recompiles the whole XLA
    # round — minutes at GPT-2 scale — so perf-critical runs should set 0.
    # The first trace is the expected compile and never counts.
    max_retraces: Optional[int] = None
    # Compiled-round XLA audit (telemetry/xla_audit.py) at train-entry
    # startup when telemetry_level >= 1: cost/memory analyses + HLO
    # collective walk -> perf_report.json + xla/* scalars. Costs ONE extra
    # AOT compile of the round (seconds at CV scale, minutes for GPT-2) —
    # set false to skip it on huge models where the double compile hurts.
    perf_audit: bool = True
    # Critical-path run report (telemetry/trace.py build_run_report):
    # written as run_report.json at train-loop close when telemetry_level
    # >= 1 — per-stage p50/p95 + attribution fractions + anomaly flags
    # over the recorded spans. Same opt-out discipline as perf_audit
    # (accuracy_run passes False so its headers never link a report that
    # will not exist). Free at level 0 either way (no spans recorder).
    run_report: bool = True

    # --- federated environment simulation (commefficient_tpu/fedsim/;
    # TPU-native — the reference assumes all num_workers arrive every
    # round) ---
    # Availability model emitting the per-round [num_workers] participation
    # mask from (round_idx, seed): "always" (default — nothing fedsim is
    # traced, the round stays bit-identical to a pre-fedsim build, same
    # discipline as --telemetry_level 0), "bernoulli" (iid per-client
    # dropout at dropout_prob), "sine" (diurnal: drop prob oscillates
    # 0..dropout_prob over availability_period rounds), "cohort"
    # (correlated outages: num_cohorts slot groups, each fully out with
    # prob dropout_prob). Masked clients transmit NOTHING and the server
    # renormalizes by the live count (fedsim/ package docstring).
    availability: str = "always"
    # Per-client drop probability (bernoulli), peak drop probability
    # (sine), or per-cohort outage probability (cohort). Must be in
    # [0, 1): 1.0 would drop every client every round and nothing would
    # ever train (a single all-dropped round is survivable — the guard
    # freezes params and flags fedsim/all_dropped — but a certainty of it
    # is a config error).
    dropout_prob: float = 0.0
    availability_period: int = 64  # sine period (rounds per diurnal cycle)
    num_cohorts: int = 4  # cohort model: slot i belongs to cohort i % n
    # poisson model: per-client arrival rate (1 / mean exponential delay,
    # in round-deadline units) — marginal participation probability is
    # 1 - exp(-rate), and rate=inf degenerates to "always" (delay 0).
    # Also paces the asyncfed/ continuous-time cohort arrival schedule
    # (asyncfed/schedule.py draws per-cohort delays at this rate).
    arrival_rate: float = 1.0
    # Scheduled chaos plan (fedsim/faults.py grammar): comma-separated
    # "kind@value[:rounds=A-B]" with kinds dropout (extra iid dropout),
    # straggler (deadline miss: excluded from aggregation + ledger live
    # bytes, local state untouched), nan_client (corrupt one live client's
    # payload at round value — proves the flight-recorder/DivergenceError
    # path; DETECTION needs telemetry_level >= 1), plus the elastic-fleet
    # events resize@W'/leave@n/join@n (deterministic per-round fleet
    # widths — the session prewarms a round program per realized width,
    # so a resize is a dispatch-table swap with zero retraces) and
    # shrink@W' (unscheduled loss: raises FleetShrinkError for the
    # resilience manager to roll back and re-enter at W'). Example:
    # "dropout@0.3:rounds=50-100,nan_client@120". Syntax validated here
    # (realized fleet widths via _validate_fleet); round indices are
    # validated against the run length at train-entry time (Config cannot
    # know steps_per_epoch).
    chaos: str = ""

    # --- buffered-asynchronous federation (commefficient_tpu/asyncfed/;
    # FedBuff-style — the reference's round is a synchronous barrier
    # over num_workers) ---
    # K: the server applies an update once K of the in-flight cohorts'
    # contributions have arrived. 0 (default): synchronous rounds —
    # NOTHING asyncfed-related is constructed and the round stays
    # bit-identical to a pre-asyncfed build (the telemetry_level-0
    # discipline). The correctness anchor:
    # async_buffer=num_workers with async_concurrency=1 and
    # staleness_exponent=0 reduces BIT-IDENTICALLY to the synchronous
    # round across every mode/error-type/fedsim combination
    # (tests/test_asyncfed.py pins it).
    async_buffer: int = 0
    # C: cohorts kept in flight concurrently. Each cohort is a full
    # W-slot launch against the server params AT ITS LAUNCH VERSION;
    # contributions from different cohorts interleave in the arrival
    # buffer. 1 = at most one cohort outstanding (still async when
    # async_buffer < num_workers: updates fire on partial cohorts).
    async_concurrency: int = 1
    # alpha: each arriving contribution is weighted by the polynomial
    # staleness discount (1 + s)^-alpha, where s = server versions
    # advanced since the contribution's cohort launched (FedBuff/
    # FedAsync-style). 0 = no discount (pure live-mask weighting).
    staleness_exponent: float = 0.0
    # Double-buffered round overlap (asyncfed/engine.py): defer the
    # host fence on update u's applied metrics until AFTER update
    # u+1's cohort launches have been dispatched, so the launch
    # programs' forward/backward queues behind the in-flight apply and
    # the device never waits on the host between an apply and the next
    # launches. Pure host scheduling — every value the engine computes
    # (staleness weights, consumed bookkeeping, the applied update) is
    # unchanged, so the K=W, C=1, alpha=0 anchor still reduces
    # BIT-IDENTICALLY to the synchronous round. Requires the asyncfed
    # engine (async_buffer > 0). False (default): the apply fences
    # inside its own span before the next launches (the measured
    # sequential baseline).
    async_double_buffer: bool = False

    # --- adaptive communication budget (commefficient_tpu/control/;
    # TPU-native — the reference fixes k/num_cols/rank once per run) ---
    # Rung-selection policy: "none" (default — NOTHING control-related is
    # built and the round stays bit-identical to a pre-control build, the
    # telemetry_level-0 discipline), "fixed" (round-range schedule via
    # control_schedule), "budget_pacing" (spend budget_mb evenly over the
    # remaining rounds, dropping to cheaper rungs as the ledger's cum
    # bytes approach the cap; hard BudgetExhaustedError when even the
    # cheapest rung would overshoot), "ef_feedback" (closed loop on the
    # diag/ef_residual_norm slope + level-2 fidelity, with hysteresis).
    control_policy: str = "none"
    # Compression ladder (control/ladder.py grammar): ";"-separated
    # "field=v1,v2,..." lists over k / num_cols / powersgd_rank, one value
    # per rung, ordered most-expensive first — e.g.
    # "k=60000,30000,10000". Every rung's round program is AOT-prewarmed
    # at run start, so a switch is a dispatch-table lookup, never a
    # mid-run retrace. Empty with budget_pacing = a single implicit rung
    # (pure budget cap enforcement, no switching).
    ladder: str = ""
    # Total communication budget in MB (decimal, 10^6 B) over the run's
    # cumulative ledger bytes (up + down, live-byte units under fedsim
    # masking — the same units comm/cum_bytes logs). 0 = no budget.
    # Enforced by the controller for ANY policy; required > 0 for
    # budget_pacing.
    budget_mb: float = 0.0
    # fixed-policy schedule: comma-separated "A-B=rung" round ranges
    # (B empty = open-ended), e.g. "0-99=2,100-=0". Rounds outside every
    # range run rung 0.
    control_schedule: str = ""
    # ef_feedback thresholds on the per-round RELATIVE slope of
    # diag/ef_residual_norm: slope > control_ef_up -> climb one rung
    # toward more bytes; slope < control_ef_down -> step one rung cheaper;
    # in between -> hold. up > down required (the dead band is half the
    # anti-oscillation story; the hysteresis window is the other half).
    control_ef_up: float = 0.15
    control_ef_down: float = 0.0
    # Worst level-2 fidelity (any diag/*_rel_err: sketch round-trip error,
    # powersgd reconstruction residual) above which ef_feedback climbs
    # regardless of the EF slope; 0 disables the fidelity trigger (it
    # needs telemetry_level >= 2 to have data).
    control_fidelity_max: float = 0.0
    # Minimum rounds between ef_feedback switches (hysteresis): within the
    # window the policy holds whatever the signals say, so the loop cannot
    # oscillate every round (tests/test_control.py pins the property).
    control_hysteresis: int = 8
    # staleness_aware band on the drained async/staleness_mean EMA (server
    # versions a contribution lags by, asyncfed/): above hi -> walk one
    # rung CHEAPER (stale cohorts' contributions are discounted anyway, so
    # spend fewer bytes on them) and shed concurrency; below lo -> climb
    # back / restore concurrency. hi > lo required (the dead band + the
    # shared control_hysteresis window are the anti-oscillation story,
    # exactly ef_feedback's).
    control_staleness_hi: float = 2.0
    control_staleness_lo: float = 0.5
    # staleness_aware band on the normalized buffer backlog
    # (async/buffer_fill / K — contributions still buffered after an
    # update fires, in buffer units): persistently over fill_hi the
    # arrival process outpaces the updates -> grow K back toward
    # --async_buffer (consume more per fire); under fill_lo while
    # staleness runs hot -> shrink K so updates fire sooner. The policy
    # adapts K/C toward this band and the controller re-tunes the
    # asyncfed engine at round granularity (FedBuff arXiv:2106.06639 §5
    # tunes these statically; ROADMAP item 4 makes it dynamic).
    control_fill_hi: float = 1.0
    control_fill_lo: float = 0.25

    # --- self-healing training (commefficient_tpu/resilience/;
    # TPU-native — the reference treats every failure as terminal) ---
    # Divergence recovery policy: "none" (default — NOTHING resilience-
    # related is constructed; the telemetry_level-0 discipline, golden
    # parity and level-0 HLO bit-untouched), "retry" (roll back to the
    # last vault snapshot and replay bit-identically — heals transient
    # faults; a recovered run matches the uninterrupted one bit-exactly),
    # "demote" (roll back AND floor the control/ ladder one rung cheaper
    # via the AOT-prewarmed switch path — needs a >= 2-rung ladder),
    # "skip_clients" (roll back AND blacklist the bad round's suspect
    # client ids from all future participation masks — needs fedsim;
    # unbiasedness preserved by linearity, renormalized by live count).
    # Detection rides the flight recorder, so != "none" needs
    # --telemetry_level >= 1. Recoveries exhausted (--max_recoveries) ->
    # the original DivergenceError re-raises with the recovery history
    # attached. See README "Failure handling & recovery".
    recover_policy: str = "none"
    # Rounds between in-memory rollback snapshots (resilience/vault.py):
    # each snapshot is preceded by a metric drain, so every snapshot in
    # the vault is certified finite (the divergence check runs in the
    # drain) and the rollback target is always pre-divergence. The vault
    # retains the last two snapshots host-side (~2x the FedState bytes of
    # host RAM); a baseline snapshot at the start round makes recovery
    # possible before the first boundary. Active iff recover_policy is
    # not "none".
    snapshot_every: int = 16
    # Recoveries before the run gives up and re-raises the original
    # DivergenceError (with the full recovery history attached). A
    # genuinely deterministic divergence replays identically under
    # "retry", so this bound is what terminates that loop.
    max_recoveries: int = 2
    # Install SIGTERM/SIGINT riders that request a preemption-safe
    # shutdown at round granularity: drain pending metrics, force-save a
    # checkpoint, write ledger/flight/spans, exit with the distinct code
    # resilience.EXIT_PREEMPTED (75). Off by default (no handler is
    # installed — constructs nothing). The fedsim chaos event
    # "preempt@R" injects the same request deterministically for tests.
    preempt_signals: bool = False

    # --- misc (reference: --seed; the mesh-shape flags above are ours) ---
    seed: int = 42
    checkpoint_dir: str = ""
    checkpoint_every: int = 0  # rounds between checkpoints; 0 = off
    resume: bool = False
    tensorboard: bool = False
    logdir: str = "runs"
    profile_dir: str = ""  # jax.profiler trace of a few steady-state rounds
    # Programmatic jax.profiler capture window over rounds "A-B"
    # (inclusive; telemetry/trace.py ProfilerWindow): arms start/stop
    # around exactly those rounds — clamped to the steady-state window
    # (MIN_WARMUP_STEPS) and fenced so deferred/in-flight work retires
    # outside the capture — into profile_dir (or <logdir>/profile_rounds
    # when profile_dir is unset). "" (default) constructs nothing.
    # Degrades gracefully (logged named reason) where the backend cannot
    # trace. This is the BENCH_r06 per-op TPU profile hook.
    profile_rounds: str = ""

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.error_type not in ERROR_TYPES:
            raise ValueError(
                f"error_type must be one of {ERROR_TYPES}, got {self.error_type!r}"
            )
        if self.topk_method not in ("exact", "threshold", "approx"):
            raise ValueError(
                f"topk_method must be exact|threshold|approx, got {self.topk_method!r}"
            )
        if (
            self.mode == "sketch"
            and self.momentum_dampening is True
            and not self.allow_unstable_sketch_dampening
        ):
            raise ValueError(
                "momentum_dampening=True with mode='sketch' is a known-"
                "divergent combination (it re-sketches NOISY momentum "
                "estimates each round; measured to destabilize training at "
                "paper-scale settings — see round.py). FetchSGD Alg 1 does "
                "not mask sketched momentum: use momentum_dampening=None/"
                "False, or set allow_unstable_sketch_dampening=True for "
                "parity experiments."
            )
        if self.mode == "powersgd":
            if self.powersgd_rank < 1:
                raise ValueError(
                    f"powersgd_rank must be >= 1, got {self.powersgd_rank}"
                )
            if self.do_topk_down:
                raise ValueError(
                    "do_topk_down with mode='powersgd' is contradictory: "
                    "the downlink is already the factored rank-r pair "
                    "(r*(n+m) floats); top-k'ing the reconstructed delta "
                    "would only un-compress it. Drop one of the two flags."
                )
            if self.momentum_dampening is True:
                raise ValueError(
                    "momentum_dampening is undefined for mode='powersgd': "
                    "dampening zeroes momentum at EXTRACTED COORDINATES, "
                    "and a rank-r subspace update has no coordinate "
                    "selection to mask. Use momentum_dampening=None/False."
                )
        if self.label_noise < 0.0 or self.label_noise > 1.0:
            raise ValueError(
                f"label_noise must be in [0, 1], got {self.label_noise}"
            )
        if self.error_decay != 1.0 and self.error_type != "virtual":
            raise ValueError(
                "error_decay only acts on the server-side virtual error "
                f"bank (error_type='virtual'); with error_type="
                f"{self.error_type!r} it would be a silent no-op"
            )
        if self.compute_dtype not in ("mixed", "float32", "bfloat16"):
            raise ValueError(
                "compute_dtype must be mixed|float32|bfloat16, "
                f"got {self.compute_dtype!r}"
            )
        if self.hash_family not in ("fmix32", "poly4"):
            raise ValueError(
                f"hash_family must be fmix32|poly4, got {self.hash_family!r}"
            )
        if self.sketch_backend not in ("einsum", "pallas"):
            raise ValueError(
                "sketch_backend must be einsum|pallas, "
                f"got {self.sketch_backend!r}"
            )
        if self.sketch_decode not in ("auto", "dense", "sharded"):
            raise ValueError(
                "sketch_decode must be auto|dense|sharded, "
                f"got {self.sketch_decode!r}"
            )
        if self.sketch_decode == "sharded":
            if self.mode != "sketch":
                raise ValueError(
                    "sketch_decode='sharded' is the sketch server-decode "
                    f"strategy; mode={self.mode!r} has no sketch decode. "
                    "Leave sketch_decode='auto' (a no-op for other modes)."
                )
            if self.topk_method != "threshold":
                raise ValueError(
                    "sketch_decode='sharded' extracts the global top-<=k "
                    "with the sharded threshold kernel (scalar-only "
                    "collectives); set topk_method='threshold' (the TPU "
                    "fast path), or leave sketch_decode='auto' to keep "
                    f"topk_method={self.topk_method!r} on the dense decode"
                )
        if self.aggregate not in ("auto", "dense", "sparse"):
            raise ValueError(
                "aggregate must be auto|dense|sparse, "
                f"got {self.aggregate!r}"
            )
        if self.aggregate == "sparse":
            if self.mode not in ("local_topk", "true_topk", "sketch"):
                raise ValueError(
                    "aggregate='sparse' exchanges <=k-sparse (idx, val) "
                    f"pairs on-mesh; mode={self.mode!r} has no sparse "
                    "transmit. Leave aggregate='auto' (a no-op there)."
                )
            if self.fsdp:
                raise ValueError(
                    "aggregate='sparse' targets the replicated round; the "
                    "FSDP round already reduce-scatters O(D/W) per chip "
                    "and exchanges only W*k candidate pairs. Leave "
                    "aggregate='auto' under fsdp=True."
                )
            if self.mode == "true_topk" and self.topk_method != "threshold":
                raise ValueError(
                    "aggregate='sparse' with mode='true_topk' selects the "
                    "global top-<=k with the sharded threshold kernel; "
                    "set topk_method='threshold', or leave "
                    "aggregate='auto' to keep the dense psum with "
                    f"topk_method={self.topk_method!r}"
                )
            if self.mode == "sketch":
                if self.topk_method != "threshold":
                    raise ValueError(
                        "aggregate='sparse' with mode='sketch' rides the "
                        "sharded-decode pair exchange for the EF "
                        "re-sketch; set topk_method='threshold' (the "
                        "sharded decode's requirement), or leave "
                        "aggregate='auto'"
                    )
                if self.sketch_decode == "dense":
                    raise ValueError(
                        "aggregate='sparse' with mode='sketch' requires "
                        "the sharded server decode (its pair exchange is "
                        "what the EF re-sketch rides); remove "
                        "sketch_decode='dense' or leave aggregate='auto'"
                    )
        if self.synthetic_variant not in (
            "flat", "concentrated", "concentrated_v2"
        ):
            raise ValueError(
                "synthetic_variant must be flat|concentrated|"
                f"concentrated_v2, got {self.synthetic_variant!r}"
            )
        if self.sketch_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"sketch_dtype must be float32|bfloat16, got {self.sketch_dtype!r}"
            )
        if self.sketch_table_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "sketch_table_dtype must be float32|bfloat16, "
                f"got {self.sketch_table_dtype!r}"
            )
        self._validate_client_store()
        self._validate_sketch_fused_bwd()
        self._validate_overlap_collectives()
        if self.num_workers % self.num_devices != 0:
            raise ValueError(
                "num_workers must be divisible by num_devices "
                f"({self.num_workers} % {self.num_devices} != 0). If you "
                "were resizing num_workers to model PARTIAL PARTICIPATION, "
                "don't — keep the round shape fixed and mask clients out "
                "with the fedsim environment instead (--availability "
                "bernoulli --dropout_prob p, or --chaos 'dropout@p'); "
                "masked clients transmit nothing and the server "
                "renormalizes by the live count"
            )
        if self.availability not in AVAILABILITY_MODELS:
            raise ValueError(
                f"availability must be one of {AVAILABILITY_MODELS}, got "
                f"{self.availability!r}"
            )
        if not 0.0 <= self.dropout_prob < 1.0:
            raise ValueError(
                f"dropout_prob must be in [0, 1), got {self.dropout_prob} "
                "(at 1.0 every client drops every round and nothing ever "
                "trains)"
            )
        if self.dropout_prob > 0 and self.availability == "always":
            raise ValueError(
                "dropout_prob > 0 has no effect with availability="
                "'always'; pick a model that uses it (bernoulli|sine|"
                "cohort), or schedule it via --chaos 'dropout@p'"
            )
        if self.availability_period < 1:
            raise ValueError(
                f"availability_period must be >= 1, got "
                f"{self.availability_period}"
            )
        if self.num_cohorts < 1:
            raise ValueError(
                f"num_cohorts must be >= 1, got {self.num_cohorts}"
            )
        if not self.arrival_rate > 0:  # rejects 0, negatives, and NaN
            raise ValueError(
                f"arrival_rate must be > 0 (rate=inf is the degenerate "
                f"everyone-arrives-instantly case), got {self.arrival_rate}"
            )
        if self.chaos:
            # syntax + range validation (ValueError with the grammar);
            # lazy import keeps the no-cycle layering (fedsim never
            # imports config)
            from commefficient_tpu.fedsim.faults import parse_chaos

            parse_chaos(self.chaos)
        if self.model_axis < 1 or self.seq_axis < 1:
            raise ValueError(
                f"model_axis/seq_axis must be >= 1, got "
                f"{self.model_axis}/{self.seq_axis}"
            )
        if self.num_clients < self.num_workers:
            raise ValueError("num_clients must be >= num_workers")
        if self.telemetry_level not in (0, 1, 2):
            raise ValueError(
                f"telemetry_level must be 0 (off), 1 (health) or 2 "
                f"(+fidelity), got {self.telemetry_level!r}"
            )
        if self.flight_window < 1:
            raise ValueError(
                f"flight_window must be >= 1, got {self.flight_window}"
            )
        if self.profile_rounds:
            # lazy import keeps the no-cycle layering (telemetry never
            # imports config); parse_profile_rounds raises the ValueError
            # naming the offending spec
            from commefficient_tpu.telemetry.trace import (
                parse_profile_rounds,
            )

            parse_profile_rounds(self.profile_rounds)
        if self.max_retraces is not None and self.max_retraces < 0:
            raise ValueError(
                f"max_retraces must be >= 0 (0 = fail on ANY retrace "
                f"beyond the first compile) or None (count only), got "
                f"{self.max_retraces}"
            )
        self._validate_asyncfed()
        self._validate_multihost()
        self._validate_control()
        self._validate_resilience()
        self._validate_fleet()

    def _validate_fleet(self) -> None:
        """Elastic-fleet events (fedsim/faults.py FLEET_KINDS in the
        chaos plan). The realized per-round widths must shard the fixed
        device mesh and stay within the provisioned maximum
        (faults.validate_fleet); engines that cannot re-shape a round
        mid-run are refused here at construction. Runs LAST: it reads
        gates the other validators resolve."""
        if not self.fleet_enabled:
            return
        from commefficient_tpu.fedsim.faults import (
            parse_chaos,
            validate_fleet,
        )

        plan = parse_chaos(self.chaos)
        validate_fleet(plan, num_workers=self.num_workers,
                       num_devices=self.num_devices)
        if self.asyncfed_enabled:
            raise ValueError(
                "fleet events are incompatible with async_buffer > 0: the "
                "asyncfed schedule pre-simulates every cohort at the fixed "
                "width W, so a mid-run resize would orphan in-flight "
                "slots — model elastic participation there with "
                "availability='poisson' instead"
            )
        if self.fsdp:
            raise ValueError(
                "fleet events are incompatible with fsdp: the FSDP round "
                "shards server state [D/W] over the workers axis, so a "
                "width change would re-partition persistent state, not "
                "just the round program — use the replicated round"
            )
        if any(ev.kind == "shrink" for ev in plan):
            if not self.recovery_enabled:
                raise ValueError(
                    "shrink@W' models an unscheduled worker loss: it "
                    "raises FleetShrinkError for the resilience manager "
                    "to roll back and re-enter at W' — set "
                    "--recover_policy retry|demote (and its "
                    "--telemetry_level >= 1 requirement), or use "
                    "resize@W' for a scheduled, non-faulting change"
                )

    def _validate_client_store(self) -> None:
        """Client-state placement flags (clientstore/). Runs FIRST among
        the feature validators: the deprecated ``offload_client_state``
        flag aliases into ``client_store='host'`` here, and every later
        validator keys off the resolved ``client_state_hosted`` gate."""
        if self.client_store not in CLIENT_STORES:
            raise ValueError(
                f"client_store must be one of {CLIENT_STORES}, got "
                f"{self.client_store!r}"
            )
        if self.offload_client_state:
            import warnings

            warnings.warn(
                "offload_client_state is deprecated: the whole-store "
                "offload became the per-cohort client-state store — use "
                "--client_store host (identical semantics at whole-store "
                "granularity; adds mmap backing and the LRU device cache)",
                DeprecationWarning,
                stacklevel=3,
            )
            if self.client_store == "device":
                object.__setattr__(self, "client_store", "host")
        if self.client_store_cache_rows < 0:
            raise ValueError(
                f"client_store_cache_rows must be >= 0 (0 = no cache), "
                f"got {self.client_store_cache_rows}"
            )
        if self.client_store == "device":
            if self.client_store_cache_rows:
                raise ValueError(
                    "client_store_cache_rows caches host-store cohort rows "
                    "on device; with client_store='device' the whole bank "
                    "already lives in HBM — drop the cache flag or pick "
                    "--client_store host|mmap"
                )
            if self.client_store_path:
                raise ValueError(
                    "client_store_path backs the mmap store; with "
                    f"client_store={self.client_store!r} it would be "
                    "silently ignored — use --client_store mmap"
                )
        if self.client_store == "host" and self.client_store_path:
            raise ValueError(
                "client_store_path backs the mmap store; the host store "
                "is a RAM bank — use --client_store mmap to persist to "
                f"{self.client_store_path!r}"
            )
        if self.client_state_hosted and self.fsdp:
            raise ValueError(
                "client_store='host'/'mmap' streams per-cohort rows "
                "through the replicated round builder; the FSDP round "
                "shards server state instead (local modes host their "
                "memory wall via --client_store, server modes via "
                "--fsdp) — run one or the other"
            )

    def _validate_sketch_fused_bwd(self) -> None:
        """The sketch-fused backward produces the gradient directly as an
        encoded table, so it only exists on the fused flattened-batch
        path with nothing per-[D] configured — every blocker is named
        here at construction instead of at first trace."""
        if not self.sketch_fused_bwd:
            return
        if self.mode != "sketch":
            raise ValueError(
                "sketch_fused_bwd sketches per-leaf cotangents into the "
                f"CountSketch table; mode={self.mode!r} has no table — "
                "drop the flag or use mode='sketch'"
            )
        if not self.fuse_clients:
            raise ValueError(
                "sketch_fused_bwd needs the fused flattened-batch path "
                "(ONE gradient per device -> one table); with "
                "fuse_clients=False each client's grad would pay its own "
                "sketch — set fuse_clients=True"
            )
        if self.local_momentum > 0:
            raise ValueError(
                "sketch_fused_bwd is incompatible with local_momentum: "
                "per-client velocity needs the dense per-client gradient "
                "the fused backward never materializes"
            )
        if self.max_grad_norm is not None:
            raise ValueError(
                "sketch_fused_bwd is incompatible with max_grad_norm "
                "(clipping also forces the per-client vmap path; the "
                "fused-batch gate already excludes it)"
            )
        if self.dp_noise_multiplier > 0:
            raise ValueError(
                "sketch_fused_bwd is incompatible with DP noise: the "
                "noise is a [D]-vector draw, which is exactly the "
                "transient the fused backward exists to avoid"
            )
        if self.fedsim_enabled:
            raise ValueError(
                "sketch_fused_bwd needs the fused flattened-batch path, "
                "and fedsim masking is inherently per-client (it forces "
                "the vmap path) — run one or the other"
            )

    def _validate_overlap_collectives(self) -> None:
        """Layer-wise collective overlap (parallel/round.py +
        ops/collectives/). Only the value set is validated here — the
        knob is a pure collective-scheduling choice that composes with
        every mode (paths without a chunkable collective trace the same
        program as overlap='none')."""
        if self.overlap_collectives not in ("none", "layerwise"):
            raise ValueError(
                "overlap_collectives must be 'none' (monolithic "
                "aggregation collectives, the golden-pinned default) or "
                "'layerwise' (segmented collectives issued as the "
                f"backward produces them), got "
                f"{self.overlap_collectives!r}"
            )

    def _validate_asyncfed(self) -> None:
        """Buffered-asynchronous federation flags (asyncfed/). The async
        engine launches overlapping per-client cohorts and applies a
        staleness-weighted update once K contributions arrive, so anything
        that assumes one cohort per server version — or that removes the
        per-client transmit rows the launch program ships — is refused
        here at construction instead of at first trace."""
        if self.async_buffer < 0:
            raise ValueError(
                f"async_buffer must be >= 0 (0 = synchronous barrier "
                f"rounds), got {self.async_buffer}"
            )
        if self.async_concurrency < 1:
            raise ValueError(
                f"async_concurrency must be >= 1, got "
                f"{self.async_concurrency}"
            )
        if self.staleness_exponent < 0:
            raise ValueError(
                f"staleness_exponent must be >= 0 ((1+s)^-alpha is a "
                f"DISCOUNT; a negative alpha would amplify stale "
                f"contributions), got {self.staleness_exponent}"
            )
        if self.async_buffer == 0:
            if self.async_concurrency != 1:
                raise ValueError(
                    "async_concurrency > 1 has no effect without "
                    "--async_buffer K; set async_buffer > 0 to enable the "
                    "asyncfed engine"
                )
            if self.staleness_exponent != 0.0:
                raise ValueError(
                    "staleness_exponent has no effect without "
                    "--async_buffer K: synchronous rounds have staleness 0 "
                    "by construction"
                )
            if self.async_double_buffer:
                raise ValueError(
                    "async_double_buffer defers the asyncfed apply fence "
                    "behind the next cohort launches, which only exist "
                    "with --async_buffer K; set async_buffer > 0 to "
                    "enable the asyncfed engine"
                )
            return
        if self.async_buffer > self.num_workers:
            raise ValueError(
                f"async_buffer must be <= num_workers ("
                f"{self.num_workers}): an update consumes at most one full "
                f"cohort's W slots per in-flight cohort, and K > W would "
                f"just wait for the next cohort anyway — raise "
                f"async_concurrency instead, got {self.async_buffer}"
            )
        if self.fuse_clients or self.sketch_fused_bwd:
            raise ValueError(
                "async_buffer > 0 needs PER-CLIENT transmit rows (each "
                "arrival is weighted by its own staleness/live factor); "
                "the fused flattened-batch paths produce one device-level "
                "gradient — drop fuse_clients/sketch_fused_bwd"
            )
        if self.client_state_hosted or self.fsdp:
            raise ValueError(
                "async_buffer > 0 currently requires HBM-resident client "
                "state on the replicated engine (--client_store host|mmap "
                "and fsdp run their own round builders)"
            )
        if self.preempt_signals or "preempt@" in self.chaos:
            raise ValueError(
                "async_buffer > 0 cannot yet honor round-granular "
                "preemption: in-flight cohorts would be abandoned "
                "mid-arrival — disable preempt_signals / the preempt@ "
                "chaos event"
            )

    def _validate_multihost(self) -> None:
        """Multi-host topology flags (multihost/). num_hosts > 1 reroutes
        every worker-axis collective over the (hosts, workers) tuple, so
        the two round builders that still hardcode the plain workers axis
        (fsdp, the tensor-parallel loss) are refused here at construction
        instead of producing a wrong-axis program at first trace."""
        if self.num_hosts < 1:
            raise ValueError(
                f"num_hosts must be >= 1, got {self.num_hosts}"
            )
        if self.distributed_connect_retries < 1:
            raise ValueError(
                f"distributed_connect_retries must be >= 1 (total connect "
                f"attempts, not extra retries), got "
                f"{self.distributed_connect_retries}"
            )
        if self.distributed and self.num_hosts < 2:
            raise ValueError(
                "distributed=True runs the jax.distributed bring-up to "
                "declare a host axis, which needs --num_hosts >= 2 (a "
                "single-host run has nothing to connect; mesh-faked "
                "multihost tests set num_hosts > 1 WITHOUT --distributed)"
            )
        if self.num_hosts == 1:
            return
        if self.num_hosts & (self.num_hosts - 1):
            raise ValueError(
                f"num_hosts must be a power of two, got {self.num_hosts}: "
                "the two-level butterfly aggregation schedules cross-host "
                "hops over a hypercube of hosts (ops/collectives/"
                "sparse_allreduce.py), which only exists at 2^n"
            )
        if self.num_devices % self.num_hosts != 0:
            raise ValueError(
                "num_devices must be divisible by num_hosts "
                f"({self.num_devices} % {self.num_hosts} != 0): the mesh "
                "is (hosts, workers, model, seq) with workers = "
                "num_devices / num_hosts chips per host"
            )
        if self.fsdp:
            raise ValueError(
                "num_hosts > 1 is incompatible with fsdp: the FSDP round "
                "builder (parallel/fsdp.py) names the plain workers axis "
                "in every shard spec and collective, so a declared host "
                "axis would silently exclude cross-host devices from its "
                "reduce-scatters — run the replicated round (the multihost "
                "path) or fsdp, not both"
            )
        if self.model_axis > 1 or self.seq_axis > 1:
            raise ValueError(
                "num_hosts > 1 is incompatible with model_axis/seq_axis "
                "> 1: the tensor-parallel loss (parallel/tensor.py) "
                "shards batch rows with the plain workers axis spec, "
                "which on a (hosts, workers, ...) mesh would replicate "
                "the batch across hosts instead of sharding it"
            )

    def _validate_resilience(self) -> None:
        """Self-healing flags (resilience/). Same late-validation split as
        control/: grammar/shape here, anything needing the run length or
        the realized session at train-entry/build time."""
        if self.recover_policy not in RECOVER_POLICIES:
            raise ValueError(
                f"recover_policy must be one of {RECOVER_POLICIES}, got "
                f"{self.recover_policy!r}"
            )
        if self.snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1 round, got "
                f"{self.snapshot_every}"
            )
        if self.max_recoveries < 1:
            raise ValueError(
                f"max_recoveries must be >= 1, got {self.max_recoveries} "
                "(use recover_policy='none' to disable recovery entirely)"
            )
        if self.recover_policy == "none":
            return
        if self.telemetry_level < 1:
            raise ValueError(
                f"recover_policy={self.recover_policy!r} recovers from the "
                "flight recorder's DivergenceError, which only fires at "
                "--telemetry_level >= 1 (the in-graph non-finite sentinel "
                "+ drain-time check) — at level 0 a divergence is never "
                "detected, so the policy would silently never act"
            )
        if self.recover_policy == "demote":
            if not self.control_enabled or not self.ladder:
                raise ValueError(
                    "recover_policy='demote' descends the control/ "
                    "compression ladder — configure a controller with a "
                    'ladder (e.g. --control_policy fixed --ladder '
                    '"k=60000,30000")'
                )
            from commefficient_tpu.control.ladder import parse_ladder

            if len(parse_ladder(self.ladder)) < 2:
                raise ValueError(
                    "recover_policy='demote' needs a ladder with >= 2 "
                    "rungs to demote between"
                )
        if self.recover_policy == "skip_clients" and not self.fedsim_enabled:
            raise ValueError(
                "recover_policy='skip_clients' masks blacklisted clients "
                "through the fedsim participation mask, but this config "
                "traces no masking (availability='always', no chaos) — "
                "enable fedsim (e.g. --availability bernoulli) or pick "
                "another policy"
            )

    def _validate_control(self) -> None:
        """Adaptive-communication-budget flags (control/). Grammar/shape
        validation happens here at construction; byte-cost ordering of the
        rungs needs the realized compressor geometry and is validated at
        session build, and schedule ranges vs the run length at
        train-entry time (the chaos-rounds pattern)."""
        if self.control_policy not in CONTROL_POLICIES:
            raise ValueError(
                f"control_policy must be one of {CONTROL_POLICIES}, got "
                f"{self.control_policy!r}"
            )
        # lazy imports keep the no-cycle layering (control never imports
        # config at runtime — the fedsim.faults pattern)
        rungs = ()
        if self.ladder:
            from commefficient_tpu.control.ladder import (
                LADDER_FIELDS,
                parse_ladder,
            )

            rungs = parse_ladder(self.ladder)  # syntax ValueError w/ grammar
            if self.control_policy == "none":
                raise ValueError(
                    "a ladder without a controller would silently never "
                    "switch — set control_policy (fixed | budget_pacing | "
                    "ef_feedback), or drop --ladder"
                )
            if self.mode != "powersgd" and any(
                    "powersgd_rank" in r for r in rungs):
                raise ValueError(
                    f"ladder field powersgd_rank has no effect with "
                    f"mode={self.mode!r} — the rung switch would be a "
                    "silent no-op; ladder fields must act on the active "
                    f"mode ({LADDER_FIELDS} minus the inert ones)"
                )
            if self.mode != "sketch" and any("num_cols" in r for r in rungs):
                raise ValueError(
                    f"ladder field num_cols has no effect with "
                    f"mode={self.mode!r} (no sketch table) — the rung "
                    "switch would be a silent no-op"
                )
            if (self.mode in ("uncompressed", "fedavg")
                    and not self.do_topk_down
                    and any("k" in r for r in rungs)):
                # (with do_topk_down, k sizes the downlink top-k — a k
                # ladder is then a real downlink-budget ladder)
                raise ValueError(
                    f"ladder field k has no effect with mode={self.mode!r} "
                    "(dense transmit, no top-k extraction) — the rung "
                    "switch would be a silent no-op"
                )
        if self.control_policy == "ef_feedback":
            if len(rungs) < 2:
                raise ValueError(
                    "control_policy='ef_feedback' needs a ladder with >= 2 "
                    "rungs to move between — pass --ladder (e.g. "
                    '"k=60000,30000,10000")'
                )
            if self.telemetry_level < 1:
                raise ValueError(
                    "control_policy='ef_feedback' consumes the drained "
                    "diag/ef_residual_norm telemetry — set "
                    "--telemetry_level >= 1 (>= 2 if control_fidelity_max "
                    "is used)"
                )
            if not self.control_ef_up > self.control_ef_down:
                raise ValueError(
                    f"control_ef_up ({self.control_ef_up}) must exceed "
                    f"control_ef_down ({self.control_ef_down}): the dead "
                    "band between them is what stops threshold flapping"
                )
        if self.control_policy == "staleness_aware":
            if not self.asyncfed_enabled:
                raise ValueError(
                    "control_policy='staleness_aware' acts on the drained "
                    "async/staleness_mean and async/buffer_fill scalars, "
                    "which only the asyncfed engine emits — set "
                    "--async_buffer K (synchronous rounds have staleness 0 "
                    "by construction, so the policy would never act)"
                )
            if len(rungs) < 2:
                raise ValueError(
                    "control_policy='staleness_aware' walks the "
                    "compression ladder by observed staleness — pass "
                    '--ladder with >= 2 rungs (e.g. "k=60000,30000")'
                )
            if self.telemetry_level < 1:
                raise ValueError(
                    "control_policy='staleness_aware' consumes drained "
                    "telemetry scalars — set --telemetry_level >= 1"
                )
            if not self.control_staleness_hi > self.control_staleness_lo:
                raise ValueError(
                    f"control_staleness_hi ({self.control_staleness_hi}) "
                    f"must exceed control_staleness_lo "
                    f"({self.control_staleness_lo}): the dead band between "
                    "them is what stops threshold flapping"
                )
            if not self.control_fill_hi > self.control_fill_lo >= 0:
                raise ValueError(
                    f"control_fill_hi ({self.control_fill_hi}) must exceed "
                    f"control_fill_lo ({self.control_fill_lo}) >= 0 — the "
                    "normalized backlog band the K/C re-tune targets"
                )
        if self.control_policy == "fixed":
            from commefficient_tpu.control.policy import parse_schedule

            sched = parse_schedule(self.control_schedule)
            if not sched:
                raise ValueError(
                    "control_policy='fixed' needs --control_schedule "
                    '(e.g. "0-99=2,100-=0")'
                )
            n_rungs = max(len(rungs), 1)
            for start, end, rung in sched:
                if rung >= n_rungs:
                    raise ValueError(
                        f"control_schedule names rung {rung}, but the "
                        f"ladder has {n_rungs} rung(s) (indices 0.."
                        f"{n_rungs - 1})"
                    )
        elif self.control_schedule:
            raise ValueError(
                "control_schedule only drives control_policy='fixed'; "
                f"with {self.control_policy!r} it would be silently ignored"
            )
        if self.budget_mb < 0:
            raise ValueError(f"budget_mb must be >= 0, got {self.budget_mb}")
        if self.control_policy == "budget_pacing" and not self.budget_mb > 0:
            raise ValueError(
                "control_policy='budget_pacing' paces against --budget_mb; "
                "set it > 0"
            )
        if self.budget_mb > 0 and self.control_policy == "none":
            raise ValueError(
                "budget_mb is enforced by the control plane; with "
                "control_policy='none' nothing would watch it — use "
                "control_policy='budget_pacing' (a ladder is optional: "
                "without one the budget is a pure hard cap)"
            )
        if self.control_hysteresis < 1:
            raise ValueError(
                f"control_hysteresis must be >= 1 round, got "
                f"{self.control_hysteresis}"
            )

    @property
    def clients_per_device(self) -> int:
        return self.num_workers // self.num_devices

    @property
    def fedsim_enabled(self) -> bool:
        """True when the federated-environment simulator must be threaded
        through the jitted round (any masking/chaos source is on). False
        keeps the round trace IDENTICAL to a fedsim-less build — the
        golden parity recordings pin that (fedsim/ package docstring)."""
        return self.availability != "always" or bool(self.chaos)

    @property
    def fleet_enabled(self) -> bool:
        """True when the chaos plan schedules any elastic-fleet event
        (resize/leave/join/shrink): the session then prewarms a round
        program per realized width and swaps programs at the schedule's
        transition rounds. False constructs NOTHING fleet-related — the
        fedsim_enabled gate discipline (golden parity and level-0 HLO
        bit-untouched). Implies ``fedsim_enabled`` (the plan is
        non-empty)."""
        if not self.chaos:
            return False
        from commefficient_tpu.fedsim.faults import has_fleet, parse_chaos

        return has_fleet(parse_chaos(self.chaos))

    @property
    def control_enabled(self) -> bool:
        """True when the adaptive-communication control plane must be
        built (multi-rung session + controller). False keeps the session
        single-rung and bit-identical to a pre-control build — the golden
        parity recordings pin that (control/ package docstring)."""
        return self.control_policy != "none"

    @property
    def recovery_enabled(self) -> bool:
        """True when the divergence rollback-and-recover machinery must be
        built (resilience/ vault + manager). False keeps the train loop on
        the untouched fast path with nothing resilience-related
        constructed — the fedsim/control gate discipline. (The
        preemption guard has its own gate: ``preempt_signals`` or a
        ``preempt@R`` chaos event.)"""
        return self.recover_policy != "none"

    @property
    def client_state_hosted(self) -> bool:
        """True when per-client momentum/error rows live OUTSIDE the
        traced graph (clientstore/ host or mmap bank): the round functions
        take the cohort's [W, D] rows as arguments and FedState carries no
        [num_clients, D] leaves. False keeps today's device-resident
        arrays and constructs nothing clientstore-related — the
        fedsim_enabled/control_enabled gate discipline (golden parity and
        level-0 HLO bit-untouched)."""
        return self.client_store in ("host", "mmap")

    @property
    def asyncfed_enabled(self) -> bool:
        """True when the buffered-asynchronous engine must be built
        (asyncfed/ package). False keeps the train loop on the synchronous
        engines with nothing asyncfed-related constructed — the
        fedsim_enabled/control_enabled gate discipline."""
        return self.async_buffer > 0

    @property
    def sampler_batch_size(self) -> int:
        """Samples the sampler draws per client per round: a fedavg round
        batch carries ``round_microbatches`` microbatches of
        ``local_batch_size`` each (derived from that property so the
        fedavg convention stays defined in exactly one place)."""
        return self.local_batch_size * (self.round_microbatches or 1)

    @property
    def round_microbatches(self) -> int:
        """Microbatches per client per round: ``num_local_iters`` for
        fedavg's [W, L, B/L, ...] batch convention, else 0 (flat [W, B]
        batches). THE mode-derived reshape knob, kept here so train loops
        and the index-round path never branch on mode strings
        (scripts/check_mode_dispatch.py)."""
        return self.num_local_iters if self.mode == "fedavg" else 0

    @property
    def resolved_num_classes(self) -> int:
        """num_classes if set, else derived from dataset_name."""
        if self.num_classes is not None:
            return self.num_classes
        return {"cifar10": 10, "cifar100": 100, "femnist": 62,
                "imagenet": 1000}.get(self.dataset_name, 10)

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


def _add_flags(p: argparse.ArgumentParser) -> None:
    """One flag per Config field, reference-compatible names."""
    for f in dataclasses.fields(Config):
        name = "--" + f.name
        default = f.default
        ann = str(f.type)
        if f.type in ("bool", bool) or isinstance(default, bool):
            p.add_argument(
                name,
                type=lambda s: s.lower() in ("1", "true", "yes"),
                nargs="?",
                const=True,
                default=default,
            )
        elif "Optional" in ann or "None" in ann:
            if "bool" in ann:  # tri-state: None (auto) | true | false
                p.add_argument(
                    name,
                    type=lambda s: s.lower() in ("1", "true", "yes"),
                    nargs="?",
                    const=True,
                    default=default,
                )
            else:
                inner = float if "float" in ann else (int if "int" in ann else str)

                def opt(s, _inner=inner):
                    # Optional fields are resettable to None from the CLI
                    # ("--max_grad_norm none" turns clipping off even when
                    # an entry's defaults set it — without this, a default
                    # like gpt2_train's max_grad_norm=1.0 was one-way and
                    # e.g. --sketch_fused_bwd was unreachable there)
                    return None if s.lower() in ("none", "null") else _inner(s)

                p.add_argument(name, type=opt, default=default)
        else:
            p.add_argument(name, type=type(default), default=default)


def parse_args(argv=None, defaults=None, **overrides) -> Config:
    """CLI -> Config. The analog of the reference's ``utils.parse_args``.

    ``defaults`` changes parser defaults (still user-overridable on the CLI,
    e.g. gpt2_train sets ``model="gpt2"``); ``overrides`` win over the CLI.
    """
    p = argparse.ArgumentParser(description="commefficient_tpu")
    _add_flags(p)
    if defaults:
        p.set_defaults(**defaults)
    ns = p.parse_args(argv)
    d = vars(ns)
    d.update(overrides)
    return Config(**d)
