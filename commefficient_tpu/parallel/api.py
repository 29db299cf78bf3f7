"""FedModel / FedOptimizer — the reference-shaped public API.

The reference exposes two objects (SURVEY.md §2): ``FedModel`` (callable
like a module; owns workers + shared state) and ``FedOptimizer``
(``.step()`` applies the server update). Here both are thin views over one
``FederatedSession``, because on TPU the whole round is a single fused XLA
program (SURVEY.md §7) — splitting compute-grads from apply-update into two
device programs would only add an HBM round-trip. The call *sequence* is
preserved:

    metrics = fed_model(client_ids, batch)   # runs the fused round at
    fed_opt.step()                           # fed_opt's current LR; step()
                                             # advances the schedule clock

Deviation from the reference, by design: ``__call__`` already applies the
update (there is no observable intermediate state between the two calls in
the reference's API contract either — workers and server state are opaque).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.clientstore import build_streamer
from commefficient_tpu.compress import compressor_class, get_compressor
from commefficient_tpu.compress.base import KIND_DENSE, KIND_TABLE
from commefficient_tpu.fedsim import build_environment
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.ops.param_utils import ravel_params
from commefficient_tpu.parallel.mesh import (
    make_mesh,
    replicated,
    worker_axis_size,
    worker_sharding,
)
from commefficient_tpu.parallel.round import (
    FedState,
    build_eval_fn,
    build_round_fn,
    init_state,
    mask_classification,
    needs_client_err,
    needs_client_vel,
    resolve_client_path,
)
from commefficient_tpu.telemetry.spans import span_of
from commefficient_tpu.utils.config import Config

_log = logging.getLogger(__name__)


def _round_step(train_round):
    """Each call of a session's ``train_round*`` is one ``fed/round`` step
    in whatever profiler trace is open, numbered by the round clock (the
    ``r<step>`` of telemetry/trace.py): the parent of that round's
    ``fed/*`` host spans, and the step marker the profiler's tools group
    device ops by. A flag test when no trace is open; nothing here
    touches a device value."""

    @functools.wraps(train_round)
    def stepped(self, *args, **kwargs):
        with jax.profiler.StepTraceAnnotation("fed/round",
                                              step_num=self._round_clock):
            return train_round(self, *args, **kwargs)

    return stepped


def _rung_hook_name(label: str, base: str = "round_fn") -> str:
    """RetraceSentinel signature-stream name for one rung's round
    program. Load-bearing: the single-rung names ("round_fn" /
    "round_idx_fn") are the legacy streams tests pin, and the per-rung
    suffix is what makes a ladder switch a first-trace rather than a
    retrace — keep this the ONLY derivation."""
    return f"{base}[{label}]" if label else base


class _Rung:
    """One compression-ladder rung's resolved runtime: the rung Config,
    its CountSketch spec/compressor geometry, and the built round
    program(s). The control-less session is exactly one rung over the base
    config (label ""), so the single-rung fast path IS the legacy build.
    ``round_idx_fn`` is filled by ``attach_data`` when the device-resident
    index path is active."""

    __slots__ = ("cfg", "label", "spec", "compressor", "round_fn",
                 "sketch_decode_resolved", "aggregate_resolved",
                 "client_path_resolved", "round_idx_fn", "width_fns",
                 "width_idx_fns")

    def __init__(self, cfg, label, spec, compressor, round_fn,
                 sketch_decode_resolved, aggregate_resolved,
                 client_path_resolved):
        self.cfg = cfg
        self.label = label  # "" (single rung) | "rung0", "rung1", ...
        self.spec = spec
        self.compressor = compressor
        self.round_fn = round_fn
        self.sketch_decode_resolved = sketch_decode_resolved
        self.aggregate_resolved = aggregate_resolved  # "sparse" | "dense"
        # "leafwise" | "per_client_vector" (round.resolve_client_path)
        self.client_path_resolved = client_path_resolved
        self.round_idx_fn = None
        # elastic fleet (README "Elastic fleet"): one round program per
        # NON-BASE realized width, keyed by width — empty unless
        # cfg.fleet_enabled (the base width stays on round_fn above, so a
        # fleet-less session is bit-identical to the legacy build)
        self.width_fns = {}
        self.width_idx_fns = {}

    @property
    def sparse_state(self) -> bool:
        """True when this rung's server momentum/error leaves live
        SHARDED over the workers axis (true_topk sparse aggregation) —
        drives the state commit/prewarm placement in the session."""
        return (self.aggregate_resolved == "sparse"
                and self.compressor.sparse_aggregate_shards_state)

    @property
    def idx_hook_name(self) -> str:
        return _rung_hook_name(self.label, "round_idx_fn")


class FederatedSession:
    """Owns the mesh, the jitted round, and the FedState.

    With ``--client_store host|mmap`` (cfg.client_state_hosted) the
    [num_clients, D] per-client momentum/error banks live in a
    clientstore/ store (host RAM or a memory-mapped file) — the analog of
    the reference's shm ``client_velocities`` (fed_aggregator.py
    ~L60-130), but deliberately outside HBM so GPT-2-scale
    ``num_clients x 124M`` state never has to fit device memory; only the
    round's W participant rows cross PCIe, staged by the session's
    CohortStreamer (gather before dispatch, ASYNC writeback after — the
    host loop never waits on the previous round's scatter).
    """

    def __init__(
        self,
        cfg: Config,
        params: Any,
        loss_fn: Callable,
        *,
        mesh=None,
        eval_loss_fn: Optional[Callable] = None,
        eval_fn: Optional[Callable] = None,
        mask_batch: Callable = mask_classification,
    ):
        self.cfg = cfg
        self.mesh = (
            mesh
            if mesh is not None
            else make_mesh(cfg.num_devices, cfg.model_axis, cfg.seq_axis,
                           hosts=cfg.num_hosts)
        )
        self._loss_fn = loss_fn
        vec, unravel = ravel_params(params)
        self.unravel = unravel
        self.grad_size = int(vec.size)  # args.grad_size analog
        # federated environment simulator (fedsim/): None unless the config
        # turns masking/chaos on — the round builders then trace the masked
        # aggregation and every train_round consumes one RoundEnv. The host
        # round clock mirrors FedState.step so the availability schedule is
        # a pure function of the round index (resume-stable; a checkpoint
        # restore re-syncs it via sync_round_clock).
        self.fedsim_env = build_environment(cfg)
        self._round_clock = 0
        # resilience/ replay horizon: rounds below it have EXECUTED in
        # this process before — a rollback rewinds the round clock but
        # never the horizon, so a re-executed round realizes its fedsim
        # env with replay=True (transient nan_client injections fire on
        # first execution only; see fedsim/faults.py). A fresh process
        # (checkpoint resume included) starts at 0: it re-executes
        # nothing, so every round is a first execution here.
        self._replay_horizon = 0
        # resilience/ client blacklist (recover_policy='skip_clients'):
        # sorted unique client ids masked out of every future round's
        # participation via the SAME pre-device_encode live mask fedsim
        # applies — None until blacklist_clients is first called.
        self._client_blacklist = None
        # resilience rider (resilience/manager.py): attached by
        # build_resilience at train-entry time; None keeps every round on
        # the untouched fast path (no resilience/* scalars assembled).
        self.resilience = None
        # retrace sentinel (telemetry/xla_audit.py): counts traces of the
        # jitted round via the builders' trace_hook — pure python at trace
        # time, zero traced ops, so the compiled program is bit-identical
        # (pinned by tests/test_xla_audit.py). `xla/retraces` rides the
        # drained metrics at telemetry_level >= 1; cfg.max_retraces makes
        # a silent mid-run recompile a hard RetraceError naming the
        # argument-signature diff. Multi-rung sessions record one
        # signature stream per rung ("round_fn[rungN]"), so a rung
        # switch onto a prewarmed program is never a retrace — while a
        # signature DRIFT on any rung still is.
        from commefficient_tpu.telemetry.xla_audit import RetraceSentinel

        self.retrace_sentinel = RetraceSentinel(
            max_retraces=cfg.max_retraces, name="round_fn"
        )
        # asyncfed (launch_fn, apply_fn) pairs, one per rung, built lazily
        # and SHARED between the perf-observability audit and the engine —
        # two builds would feed one sentinel stream and count phantom
        # retraces.
        self._async_programs: Dict[int, Any] = {}
        # host-side phase-span recorder (telemetry/spans.py); a train loop
        # attaches one at telemetry_level >= 1 — None keeps every span
        # site on the zero-cost fast path.
        self.spans = None
        # last compiled-round audit (telemetry/xla_audit.py), kept for the
        # xla/exposed_collective_ms spans×HLO cross-check: the spans-side
        # exposure is only a collective wait if the compiled program
        # actually contains collectives.
        self.last_audit = None
        # adaptive-communication controller (control/): attached by
        # build_controller at train-entry time (it needs the run length);
        # None keeps every round on the untouched fast path.
        self.controller = None
        # clientstore/ streamer — None unless cfg.client_state_hosted AND
        # a bank is needed (build_streamer's construction gate); host_vel/
        # host_err are PROPERTIES over it (flush-then-view) so checkpoint/
        # vault code reads and assigns whole banks unchanged.
        self._streamer = None
        self._dev_data = self._round_idx_fn = None
        self._dev_augment = None
        # ---- compression-rung resolution (control/ ladder) ---------------
        # The control-less default is ONE rung over cfg itself — that
        # branch builds exactly the legacy session (same sentinel stream
        # name, same warnings, same compiled round; golden parity pins
        # it). With a controller, every ladder rung's spec + compressor +
        # round program are resolved HERE, so a mid-run switch is a
        # dispatch-table lookup over prewarmed programs, never a rebuild.
        if cfg.control_enabled:
            from commefficient_tpu.control import (
                initial_rung_index,
                ladder_configs,
                validate_rung_costs,
            )

            rung_cfgs = ladder_configs(cfg)
            self.rungs = [
                self._build_rung(rc, f"rung{i}")
                for i, rc in enumerate(rung_cfgs)
            ]
            if len(self.rungs) > 1:
                validate_rung_costs(
                    [self.rung_bytes_per_round(i)
                     for i in range(len(self.rungs))]
                )
            self.active_rung = initial_rung_index(cfg, len(self.rungs))
        else:
            self.rungs = [self._build_rung(cfg, "")]
            self.active_rung = 0
        # ---- elastic fleet (fedsim resize/leave/join) --------------------
        # Every realized fleet width gets its own round program PER RUNG
        # (its own sentinel stream, "round_fn[label][wN]"), built here and
        # AOT-prewarmed like the rung ladder — a width transition is then
        # a dispatch-table lookup, never a trace (xla/retraces stays 0
        # across shrink AND grow). Gated on cfg.fleet_enabled: a fleet-less
        # config builds NOTHING here (golden-parity discipline).
        self._fleet_width = cfg.num_workers
        self._fleet_shrink_recoveries = 0
        self._fleet_resize_ms = 0.0
        if cfg.fleet_enabled:
            for fr in self.rungs:
                for w in self.fedsim_env.widths()[1:]:
                    fr.width_fns[w] = self._build_width_fn(fr, w)
        rung = self.rungs[self.active_rung]
        self.spec = rung.spec
        # session-owned compressor instance (the active rung's): validates
        # the (mode, error_type) combination up front and serves the
        # communication accounting (bytes_per_round); the round builders
        # construct their own trace-time instances from the same registry.
        self.compressor = rung.compressor
        self.sketch_decode_resolved = rung.sketch_decode_resolved
        self.aggregate_resolved = rung.aggregate_resolved
        self.client_path_resolved = rung.client_path_resolved
        self.round_fn = rung.round_fn
        if cfg.fsdp:
            # FSDP round (parallel/fsdp.py): params + dense server state
            # sharded [D/W] over the workers axis; state arrives committed
            # to its per-leaf shardings, so the replicated device_put below
            # must not touch it.
            from commefficient_tpu.parallel.fsdp import init_fsdp_state

            self.state = init_fsdp_state(rung.cfg, vec, rung.spec, self.mesh)
        else:
            self.state = init_state(rung.cfg, vec, rung.spec)
            # stage_fn is late-bound on self: _batch_sharding is assigned
            # below, and the streamer only stages at gather time
            self._streamer = build_streamer(
                cfg,
                self.grad_size,
                needs_vel=needs_client_vel(cfg),
                needs_err=needs_client_err(cfg),
                stage_fn=lambda a: jax.device_put(
                    jnp.asarray(a), self._batch_sharding
                ),
            )
        # eval_fn: a prebuilt (params_vec, batch) -> metric-sums step — the
        # TP/SP eval path (tensor.build_tp_eval_fn) when the model needs the
        # model axis to fit; else the jit-replicated dense eval over
        # eval_loss_fn (or the train loss).
        self.eval_fn = eval_fn or build_eval_fn(
            eval_loss_fn or loss_fn, unravel, mask_batch
        )
        self._batch_sharding = worker_sharding(self.mesh)
        self._replicated = replicated(self.mesh)
        # eval batches shard their rows over the worker axes only (they
        # stay replicated over any model/seq axes), so row divisibility is
        # against the worker-axes size — the (hosts x workers) product on
        # a multi-host mesh — not the whole mesh
        self._n_mesh_devices = worker_axis_size(self.mesh)
        # Commit the state to the mesh's replicated sharding up front: the
        # jitted round outputs mesh-sharded arrays, and a first call fed
        # SingleDeviceSharding inputs compiles a SECOND program whose
        # donated-output layout then persists — one whole extra XLA compile
        # buried in epoch 1.
        # (FSDP state is committed to its per-leaf shardings in
        # init_fsdp_state already.)
        if not cfg.fsdp:
            self.state = jax.tree.map(
                lambda a: jax.device_put(a, self._replicated)
                if isinstance(a, jnp.ndarray)
                else a,
                self.state,
            )
            if rung.sparse_state:
                # true_topk sparse aggregation: momentum/error live as
                # [padded_dim] vectors SHARDED over the workers axis (the
                # decode shard_map consumes each chip's slice in place —
                # an O(D) replicated copy per chip is exactly what the
                # sparse path removes)
                self.state = self.state._replace(
                    momentum=self._shard_server_leaf(self.state.momentum),
                    error=self._shard_server_leaf(self.state.error),
                )

    # -- clientstore/ bank access (checkpoint / vault contract) ------------
    # host_vel/host_err read as the WHOLE [num_clients, D] bank after a
    # flush (drain fence: pending async writebacks + dirty cache rows land
    # first), or None when the bank doesn't exist — exactly the contract
    # the pre-clientstore numpy attributes had, so utils/checkpoint.py and
    # resilience/vault.py get/set them unchanged. Assigning loads the bank
    # and invalidates staged/cached rows (restore/rollback path).
    @property
    def host_vel(self):
        if self._streamer is None or not self._streamer.has_vel:
            return None
        self._streamer.flush()
        return self._streamer.vel_array()

    @host_vel.setter
    def host_vel(self, arr):
        if self._streamer is None:
            raise ValueError(
                "cannot load host_vel: this session has no hosted client "
                "store (--client_store device, or no client-state mode)"
            )
        self._streamer.load_vel(arr)

    @property
    def host_err(self):
        if self._streamer is None or not self._streamer.has_err:
            return None
        self._streamer.flush()
        return self._streamer.err_array()

    @host_err.setter
    def host_err(self, arr):
        if self._streamer is None:
            raise ValueError(
                "cannot load host_err: this session has no hosted client "
                "store (--client_store device, or no client-state mode)"
            )
        self._streamer.load_err(arr)

    def close_client_store(self) -> None:
        """Drain and release the clientstore streamer (writeback worker
        joined, mmap files flushed/unlinked). Idempotent; a no-op for
        device-resident sessions. train/runner.py calls it in its finally
        block so a surviving process (embedding, pytest) doesn't leak the
        writeback thread."""
        if self._streamer is not None:
            self._streamer.close()

    # -- rung build / switch (control/ compression ladder) -----------------
    def _build_rung(self, rcfg: Config, label: str) -> _Rung:
        """Resolve one rung: CountSketch spec (+ envelope/backend
        warnings, per rung — the envelope is a num_cols property),
        compressor, decode resolution, and the built round program with
        its own RetraceSentinel signature stream."""
        spec = None
        # mode dispatch happens exactly once, here, through the compress/
        # registry; everything downstream calls compressor hooks
        comp_cls = compressor_class(rcfg.mode)
        if comp_cls.needs_sketch_spec:
            spec = CountSketch(
                d=self.grad_size,
                c=rcfg.num_cols,
                r=rcfg.num_rows,
                num_blocks=rcfg.num_blocks,
                seed=rcfg.seed,
                dtype=jnp.bfloat16 if rcfg.sketch_dtype == "bfloat16" else jnp.float32,
                band=rcfg.sketch_band,
                hash_family=rcfg.hash_family,
                m=rcfg.sketch_m,
                backend=rcfg.sketch_backend,
                table_dtype=(
                    jnp.bfloat16
                    if rcfg.sketch_table_dtype == "bfloat16"
                    else jnp.float32
                ),
            )
            if rcfg.sketch_backend == "pallas":
                from commefficient_tpu.ops.pallas import kernels_interpreted

                # refuses a backend that can neither compile nor interpret
                # the kernels; one warning per session, not per rung: the
                # first rung built is "" (single-rung) or "rung0" (ladder)
                if kernels_interpreted() and label in ("", "rung0"):
                    import warnings

                    warnings.warn(
                        "sketch_backend='pallas' on the cpu backend runs "
                        "every kernel under Pallas INTERPRET mode — orders "
                        "of magnitude slower than the einsum backend (fine "
                        "for tests/dryruns, hopeless for training at "
                        f"D={self.grad_size:,}). Use sketch_backend="
                        "'einsum' on cpu hosts."
                    )
            # d/c against the REALIZED per-row width (the blocked layout
            # rounds the requested num_cols; VERDICT r3 weak 3 asked the
            # envelope check to use what the table actually is).
            c_real = spec.c_actual
            from commefficient_tpu.parallel.envelope import (
                predicted_dc_max,
                stable_dc_bound,
            )

            bound = stable_dc_bound(rcfg.error_decay)
            if self.grad_size > bound * c_real:
                import warnings

                # suggestion in REQUESTED-num_cols space: the realized width
                # deviates a few percent from the request (stride rounding),
                # so pad the realized target by 5% — enough that following
                # the advice clears the realized-d/c check (pinned by
                # tests/test_round.py::test_envelope_warning_suggestion)
                need_real = int(self.grad_size / bound) + 1
                suggest = -(-need_real * 21 // 20)
                decay_note = (
                    "" if rcfg.error_decay < 0.95 else
                    " or lower error_decay (gamma=0.9 moves the fitted "
                    f"cliff to d/c ~{predicted_dc_max(0.9):.0f}; the r4 "
                    "sweep measured d/c 35/40 training fully at gamma=0.9 "
                    "where undecayed runs sit at chance — CHANGELOG_r4)"
                )
                rung_note = f" (ladder {label})" if label else ""
                warnings.warn(
                    f"sketch mode{rung_note} at realized d/c = "
                    f"{self.grad_size / c_real:.1f} (c_actual={c_real:,}) "
                    "is OUTSIDE the stable envelope for error_decay="
                    f"{rcfg.error_decay:g}: the fitted error-bank model "
                    "(parallel/envelope.py — steady-state bank mass / "
                    "extraction SNR balance, fitted to the r4 quarter-scale "
                    "sweep and held-out-validated in r5) puts the cliff at "
                    f"d/c ~{predicted_dc_max(rcfg.error_decay):.0f} for this "
                    f"gamma (warning threshold {bound:.0f} = the last "
                    "measured-fully-stable point). The cliff is an "
                    "error-feedback SNR property of the regime, not a "
                    "layout or hash artifact (CHANGELOG_r3/r4). Raise "
                    f"num_cols to >= {suggest:,}{decay_note}, or validate "
                    "this exact config with scripts/sketch_lab.py before a "
                    "long run."
                )
        compressor = get_compressor(rcfg, d=self.grad_size, spec=spec)
        # sketch server-decode resolution (cfg.sketch_decode; the round
        # builder makes the same call from the same inputs) — surfaced so
        # bench/profiling/tests can report which decode a session compiled
        # without re-deriving the auto rule. FSDP rounds have their own
        # (always-sharded) extraction, so the knob is moot there.
        _ws = worker_axis_size(self.mesh)
        decode_resolved = (
            "sharded"
            if not rcfg.fsdp and compressor.use_sharded_decode(_ws)
            else "dense"
        )
        # on-mesh aggregation resolution (cfg.aggregate; same call the
        # round builder makes) — surfaced so bench/audit/tests can report
        # which aggregation a session compiled without re-deriving the
        # auto rule. Moot under FSDP (its reduce-scatter already moves
        # O(D/W) per chip; Config rejects an explicit 'sparse' there).
        aggregate_resolved = (
            "sparse"
            if not rcfg.fsdp and compressor.use_sparse_aggregate(_ws)
            else "dense"
        )
        if (
            rcfg.aggregate == "sparse"
            and not rcfg.fsdp
            and _ws == 1
            and label in ("", "rung0")  # once per session (first rung)
        ):
            import warnings

            warnings.warn(
                "aggregate='sparse' on a 1-device workers mesh is the "
                "degenerate case: there is no cross-chip exchange to "
                "shrink, so the pair compaction/scatter is pure overhead "
                "on top of a psum XLA already elides. 'auto' picks dense "
                "here for exactly that reason."
            )
        if (
            rcfg.sketch_decode == "sharded"
            and not rcfg.fsdp
            and _ws == 1
            and label in ("", "rung0")  # once per session (first rung)
        ):
            import warnings

            warnings.warn(
                "sketch_decode='sharded' on a 1-device workers mesh is the "
                "degenerate case: one 'shard' decodes the FULL coordinate "
                "range through the estimate_at gather path (the TPU slow "
                "path — the FSDP analog measured ~6x the replicated round "
                "at D=124M, runs/r5_fsdp_gpt2.log). The sharded win only "
                "exists when the workers axis is real; 'auto' picks dense "
                "here for exactly that reason."
            )
        # how the shard's client gradients reach the server (the round
        # builders make the same call from the same inputs): static per
        # compiled round, so the record is one string here and no per-round
        # op. The flattened-batch fast path sits before either.
        client_path_resolved = resolve_client_path(rcfg, compressor)
        _log.info("round%s: client path %s (mode=%s)",
                  f" [{label}]" if label else "", client_path_resolved,
                  rcfg.mode)
        hook = self.retrace_sentinel.hook_for(_rung_hook_name(label))
        if rcfg.fsdp:
            from commefficient_tpu.parallel.fsdp import build_fsdp_round_fn

            round_fn = build_fsdp_round_fn(
                rcfg, self._loss_fn, self.unravel, self.mesh, spec,
                d=self.grad_size, trace_hook=hook,
            )
        else:
            round_fn = build_round_fn(
                rcfg, self._loss_fn, self.unravel, self.mesh, spec,
                d=self.grad_size, trace_hook=hook,
            )
        return _Rung(rcfg, label, spec, compressor, round_fn,
                     decode_resolved, aggregate_resolved,
                     client_path_resolved)

    def set_active_rung(self, i: int, *, migrate: bool = True) -> None:
        """Switch dispatch to rung ``i``: swap the session's active
        compressor/spec/round program (table lookup — the programs were
        built at session init and AOT-prewarmed, so no trace happens
        here) and, with ``migrate``, carry the compressor-managed FedState
        leaves across via ``Compressor.migrate_state``. ``migrate=False``
        is for checkpoint restore, where the restored leaves are ALREADY
        in rung ``i``'s layout."""
        i = int(i)
        if not 0 <= i < len(self.rungs):
            raise ValueError(
                f"rung {i} out of range (ladder has {len(self.rungs)})"
            )
        if i == self.active_rung:
            return
        old, new = self.rungs[self.active_rung], self.rungs[i]
        if migrate:
            m, e, x = old.compressor.migrate_state(
                new.compressor, self.state.momentum, self.state.error,
                self.state.comp,
            )
            m, e, x = self._commit_rung_leaves(new, m, e, x)
            self.state = self.state._replace(momentum=m, error=e, comp=x)
        self.active_rung = i
        self.spec = new.spec
        self.compressor = new.compressor
        self.sketch_decode_resolved = new.sketch_decode_resolved
        self.aggregate_resolved = new.aggregate_resolved
        self.client_path_resolved = new.client_path_resolved
        self._select_programs()

    # -- elastic fleet (per-width round programs; README "Elastic fleet") --
    def _width_cfg(self, rcfg: Config, w: int) -> Config:
        """``rcfg`` with ``num_workers = w`` — the trace-time config for
        one non-base fleet width's round program. Bypasses
        ``__post_init__`` deliberately: the base config already validated
        everything width-independent, ``validate_fleet`` already proved
        ``w`` device-compatible, and re-validating the UNCHANGED chaos
        plan against the narrowed width would spuriously reject it (the
        plan's widths are relative to the BASE fleet)."""
        import copy

        wcfg = copy.copy(rcfg)
        object.__setattr__(wcfg, "num_workers", int(w))
        return wcfg

    def _build_width_fn(self, rung: _Rung, w: int):
        """One rung's host-batch round program traced for fleet width
        ``w``, on its own RetraceSentinel stream — a later transition to
        ``w`` dispatches this table entry instead of re-tracing."""
        hook = self.retrace_sentinel.hook_for(
            _rung_hook_name(rung.label) + f"[w{w}]"
        )
        return build_round_fn(
            self._width_cfg(rung.cfg, w), self._loss_fn, self.unravel,
            self.mesh, rung.spec, d=self.grad_size, trace_hook=hook,
        )

    def _select_programs(self) -> None:
        """Re-point session dispatch at the (active rung x current fleet
        width) round programs — the ONE place the rung and width tables
        compose, so rung switches and width transitions cannot disagree
        about which program runs next."""
        rung = self.rungs[self.active_rung]
        if self._fleet_width == self.cfg.num_workers:
            fn, idx_fn = rung.round_fn, rung.round_idx_fn
        else:
            fn = rung.width_fns[self._fleet_width]
            idx_fn = rung.width_idx_fns.get(self._fleet_width)
        self.round_fn = fn
        if self._dev_data is not None:
            self._round_idx_fn = idx_fn

    def _set_fleet_width(self, w: int) -> None:
        """Commit a fleet width: table lookup + dispatch swap (no trace —
        the per-width programs were built at session init and prewarmed).
        ``_fleet_resize_ms`` accumulates the host-side swap cost so the
        bench's elastic leg can assert it stays in the microsecond class."""
        w = int(w)
        if w == self._fleet_width:
            return
        import time

        t0 = time.perf_counter()
        self._fleet_width = w
        self._select_programs()
        self._fleet_resize_ms += (time.perf_counter() - t0) * 1e3

    def _fleet_round_begin(self) -> int:
        """Fleet bookkeeping at round dispatch: raise ``FleetShrinkError``
        the FIRST time a shrink event opens (the resilience manager rolls
        back to the newest vault snapshot and re-enters), then swap
        dispatch to the round's scheduled width. Returns the realized
        width — ``num_workers`` whenever no fleet events are scheduled."""
        env = self.fedsim_env
        if env is None or not env.has_fleet:
            return self.cfg.num_workers
        r = self._round_clock
        shrink = env.shrink_at(r)
        if shrink is not None and r >= self._replay_horizon:
            from commefficient_tpu.telemetry import FleetShrinkError

            # bump the horizon AT the raise: the rollback rewinds the
            # round clock but never the horizon, so the replayed pass
            # re-enters at the shrunk width instead of re-losing the
            # same cohort forever
            self._replay_horizon = r + 1
            raise FleetShrinkError(r, shrink, self._fleet_width)
        self._set_fleet_width(env.width_at(r))
        return self._fleet_width

    def _base_width_env(self, env):
        """Round-0 fedsim env at BASE width for prewarm/audit lowering:
        when the fleet schedule opens a resize at round 0 the default env
        would realize ``width_at(0)`` mask slots and the base-width
        lowering would shape-mismatch. Passthrough for explicit envs and
        fleet-less sessions."""
        if env is None and self.cfg.fleet_enabled:
            return self.fedsim_env.round_env(0, width=self.cfg.num_workers)
        return env

    def _commit_rung_leaves(self, rung: _Rung, m, e, x):
        """Re-commit migrated leaves to their mesh shardings (identity
        migrations pass the SAME array objects through — left untouched,
        no device round-trip)."""
        old = (self.state.momentum, self.state.error, self.state.comp)
        if self.cfg.fsdp:
            from commefficient_tpu.parallel.fsdp import fsdp_state_shardings

            sh = fsdp_state_shardings(rung.cfg, self.mesh)
            shardings = (sh.momentum, sh.error, self._replicated)
        elif rung.sparse_state:
            # workers-sharded [padded_dim] momentum/error (commit pads a
            # [D] leaf arriving from a dense-layout rung)
            shardings = (self._batch_sharding, self._batch_sharding,
                         self._replicated)
        else:
            shardings = (self._replicated,) * 3

        def commit(leaf, sharding, old_leaf):
            if isinstance(leaf, tuple) or leaf is old_leaf:
                return leaf
            s = sharding if not isinstance(sharding, tuple) else self._replicated
            leaf = jnp.asarray(leaf)
            if (s is self._batch_sharding and leaf.ndim == 1
                    and leaf.shape[0] == self.grad_size):
                dp = self._padded_grad_size()
                leaf = jnp.pad(leaf, (0, dp - self.grad_size))
            return jax.device_put(leaf, s)

        return tuple(
            commit(leaf, sh_, o)
            for leaf, sh_, o in zip((m, e, x), shardings, old)
        )

    def _padded_grad_size(self) -> int:
        """grad_size rounded up to a workers-axis multiple — the length of
        workers-sharded [padded_dim] server-state vectors."""
        from commefficient_tpu.parallel.fsdp import padded_dim

        return padded_dim(self.grad_size, self._n_mesh_devices)

    def _shard_server_leaf(self, leaf):
        """Pad a dense [D] server leaf to [padded_dim] and commit it
        sharded over the workers axis (true_topk sparse aggregation)."""
        if not isinstance(leaf, jnp.ndarray) or leaf.ndim != 1:
            return leaf
        if leaf.shape[0] == self.grad_size:
            leaf = jnp.pad(leaf, (0, self._padded_grad_size() - self.grad_size))
        return jax.device_put(leaf, self._batch_sharding)

    def rung_bytes_per_round(self, i: int) -> Dict[str, int]:
        """``bytes_per_round`` for rung ``i`` (the controller's and the
        per-rung ledger accounting's source — same arithmetic as the
        active-rung ``bytes_per_round`` below)."""
        rung = self.rungs[i]
        up = rung.compressor.upload_floats()
        down = (
            2 * rung.cfg.k
            if rung.cfg.do_topk_down
            else rung.compressor.download_floats()
        )
        # uplink bytes go through the compressor's bytes-per-float hook
        # (2 for bf16 sketch tables — the psum payload really is half);
        # the downlink stays the conservative 4 B/float dense broadcast
        return {"upload_floats": up, "download_floats": down,
                "upload_bytes": rung.compressor.upload_bytes_per_float() * up,
                "download_bytes": 4 * down}

    # -- rung prewarm (AOT trace of every rung's round program) ------------
    def _rung_state_struct(self, rung: _Rung):
        """A ShapeDtypeStruct FedState in rung ``rung``'s layout — what
        ``prewarm_rungs`` lowers against. Params/client rows/step come
        from the live state (rung-independent shapes); momentum/error/comp
        take the rung compressor's own geometry. Every struct carries the
        sharding its live array is committed to: the trace cache keys on
        it, so an unplaced struct would seed a signature no dispatch ever
        matches and the first real call would retrace."""
        def sds(a):
            return (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=a.sharding)
                    if hasattr(a, "shape") else a)

        base = jax.tree.map(
            sds, self.state,
            is_leaf=lambda a: isinstance(a, tuple) and len(a) == 0,
        )
        if self.cfg.fsdp:
            from commefficient_tpu.parallel.fsdp import (
                _workers_size,
                padded_dim,
            )

            dp = padded_dim(self.grad_size, _workers_size(self.mesh))
            m_kind, e_kind = rung.compressor.server_state_kinds()

            def shape(kind):
                if kind == KIND_DENSE:
                    return jax.ShapeDtypeStruct(
                        (dp,), jnp.float32, sharding=self._batch_sharding
                    )
                if kind == KIND_TABLE:
                    return jax.ShapeDtypeStruct(
                        rung.spec.table_shape, rung.spec.table_dtype,
                        sharding=self._replicated,
                    )
                return ()

            m, e, x = shape(m_kind), shape(e_kind), ()
        elif rung.sparse_state:
            # workers-sharded server state: dense [D] kinds become
            # [padded_dim] (same geometry as the FSDP branch above, but
            # only for momentum/error — params stay replicated)
            dp = self._padded_grad_size()
            m_kind, e_kind = rung.compressor.server_state_kinds()

            def shape(kind):
                if kind == KIND_DENSE:
                    return jax.ShapeDtypeStruct(
                        (dp,), jnp.float32, sharding=self._batch_sharding
                    )
                return ()

            m, e, x = shape(m_kind), shape(e_kind), ()
        else:
            m, e, x = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=self._replicated),
                jax.eval_shape(rung.compressor.init_server_state),
            )
        return base._replace(momentum=m, error=e, comp=x)

    def prewarm_rungs(self, client_ids, batch, lr: float, env=None) -> int:
        """AOT-lower EVERY rung's host-batch round program against this
        round signature (``jit.lower`` shares the call trace cache on this
        jax — see ``audit_compiled_round``), so (a) each rung's
        RetraceSentinel stream is seeded with its expected steady-state
        signature, and (b) a later rung switch dispatches an
        already-traced program: ``xla/retraces`` stays 0 across switches
        and any later signature drift is a COUNTED retrace, never a
        silent one. Returns the number of rungs lowered. (XLA still
        backend-compiles a rung's executable on its first dispatch — a
        one-off per rung; what this removes is the silent RE-trace class
        of stall, which is also the one the sentinel polices.)"""
        cids = np.asarray(client_ids)
        ids = jax.device_put(jnp.asarray(cids), self._batch_sharding)
        dev_batch = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), self._batch_sharding),
            batch,
        )
        lr = jnp.float32(lr)
        fs_env, _ = self._fedsim_round_env(self._base_width_env(env))

        def extras(w):
            if self._streamer is None:
                return []
            rows = jax.ShapeDtypeStruct((w, self.grad_size), np.float32,
                                        sharding=self._batch_sharding)
            return [
                rows if self._streamer.has_vel else (),
                rows if self._streamer.has_err else (),
            ]

        extra = extras(self.cfg.num_workers)
        for rung in self.rungs:
            rung.round_fn.lower(
                self._rung_state_struct(rung), ids, dev_batch, lr, *extra,
                env=fs_env,
            )
        n = len(self.rungs)
        if not self.cfg.fleet_enabled:
            return n
        # the width ladder: lower every non-base width's program against
        # the SAME round-0 cohort sliced to w rows, with round-0 masks
        # realized AT width w — the exact signature a transition dispatches
        for w in self.fedsim_env.widths()[1:]:
            idsw = jax.device_put(jnp.asarray(cids[:w]),
                                  self._batch_sharding)
            bw = jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(np.asarray(a)[:w]),
                                         self._batch_sharding),
                batch,
            )
            envw, _ = self._fedsim_round_env(
                self.fedsim_env.round_env(0, width=w)
            )
            extraw = extras(w)
            for rung in self.rungs:
                rung.width_fns[w].lower(
                    self._rung_state_struct(rung), idsw, bw, lr, *extraw,
                    env=envw,
                )
                n += 1
        return n

    def prewarm_rungs_indices(self, client_ids, idx, plan, lr: float,
                              env=None) -> int:
        """``prewarm_rungs`` for the device-resident index round (the
        program ``train_round_indices`` dispatches)."""
        if self._dev_data is None:
            raise ValueError(
                "prewarm_rungs_indices needs device-resident data — call "
                "attach_data first (or prewarm_rungs for host batches)"
            )
        ids = jax.device_put(jnp.asarray(client_ids), self._batch_sharding)
        idxd = jax.device_put(
            jnp.asarray(np.asarray(idx, np.int32)), self._batch_sharding
        )
        pl = (
            tuple(
                jax.device_put(jnp.asarray(np.asarray(a)), self._replicated)
                for a in plan
            )
            if plan
            else ()
        )
        lr = jnp.float32(lr)
        fs_env, _ = self._fedsim_round_env(self._base_width_env(env))
        for rung in self.rungs:
            rung.round_idx_fn.lower(
                self._rung_state_struct(rung), self._dev_data, ids, idxd,
                pl, lr, env=fs_env,
            )
        n = len(self.rungs)
        if not self.cfg.fleet_enabled:
            return n
        cids = np.asarray(client_ids)
        idx_h = np.asarray(idx, np.int32)
        B = idx_h.shape[1]
        for w in self.fedsim_env.widths()[1:]:
            idsw = jax.device_put(jnp.asarray(cids[:w]),
                                  self._batch_sharding)
            idxw = jax.device_put(jnp.asarray(idx_h[:w]),
                                  self._batch_sharding)
            # augmentation-plan rows are per-SAMPLE ([W*B, ...] leading)
            plw = (
                tuple(
                    jax.device_put(jnp.asarray(np.asarray(a)[: w * B]),
                                   self._replicated)
                    for a in plan
                )
                if plan
                else ()
            )
            envw, _ = self._fedsim_round_env(
                self.fedsim_env.round_env(0, width=w)
            )
            for rung in self.rungs:
                rung.width_idx_fns[w].lower(
                    self._rung_state_struct(rung), self._dev_data, idsw,
                    idxw, plw, lr, env=envw,
                )
                n += 1
        return n

    def prewarm_from_sampler(self, sampler, lr: float) -> int:
        """``ControlLoop.prewarm`` for controller-less sessions: AOT-lower
        every (rung x fleet width) round program from the run's REAL
        round-0 cohort. The train runner calls it when ``cfg.fleet_enabled``
        and no controller is attached, so the width ladder is always
        seeded by the time the first transition dispatches — a resize is a
        table lookup, never a trace."""
        if self._dev_data is not None:
            ids, idx, plan = sampler.sample_round_indices(0)
            return self.prewarm_rungs_indices(ids, idx, plan, lr)
        ids, batch = sampler.sample_round(0)
        L = self.cfg.round_microbatches
        if L:  # fedavg [W, L, B/L, ...] convention
            batch = {
                k: v.reshape(v.shape[0], L, v.shape[1] // L, *v.shape[2:])
                for k, v in batch.items()
            }
        return self.prewarm_rungs(ids, batch, lr)

    # -- device-resident data (TPU-native; ships only indices per round) ---
    def maybe_attach_data(self, dataset, sampler, augment=None) -> bool:
        """Attach ``dataset``'s arrays device-resident iff the config allows
        it, the sampler can drive index-only rounds, and the data fits
        ``cfg.device_data_max_mb``. The single gate shared by the train
        entry points — returns True when the index path is active."""
        if not (
            self.cfg.device_data
            and not self.cfg.client_state_hosted
            and not self.cfg.fsdp  # index round builds the replicated round
            and sampler.fusable
            and all(isinstance(v, np.ndarray) for v in dataset.data.values())
            and sum(v.nbytes for v in dataset.data.values())
            <= self.cfg.device_data_max_mb * 1_000_000
        ):
            return False
        self.attach_data(dataset.data, augment)
        return True

    def attach_data(self, data: Dict[str, np.ndarray], augment=None) -> None:
        """Put the WHOLE training set in device HBM (uint8 images: CIFAR-10
        is 154 MB) and compile an index-driven round: each call ships only
        ``[W, B]`` int32 sample indices plus the augmentation plan (~KBs).
        The gather AND the crop/flip/cutout run inside the jitted round, so
        the host->device link carries practically nothing.

        ``augment`` is a plan-based augmenter (data.augment.BatchAugment:
        data.cifar.CifarAugment, data.imagenet.ImageNetAugment,
        data.fedtext.BlockNoise) or None; its ``device_apply(batch,
        *plan)`` realizes the same plan as the host paths inside the trace,
        on the gathered rows' named keys, so training is unchanged
        (bit-identical for the pure index/select CIFAR ops and for the
        block noise's comparisons; within 1 uint8 LSB for bilinear RRC —
        see the augmenters).
        """
        if self.cfg.client_state_hosted:
            raise NotImplementedError(
                "device-resident data + host-resident client state "
                "(--client_store host|mmap) is contradictory; pick one"
            )
        self._dev_data = {
            k: jax.device_put(jnp.asarray(v), self._replicated)
            for k, v in data.items()
        }
        self._dev_augment = augment
        # one index round per rung, so a controller switch on the
        # device-resident path is the same dispatch-table lookup as the
        # host-batch path (single-rung sessions build exactly one, under
        # the legacy "round_idx_fn" sentinel stream)
        for rung in self.rungs:
            rung.round_idx_fn = self._build_round_idx_fn(rung, augment)
            for w in rung.width_fns:
                rung.width_idx_fns[w] = self._build_round_idx_fn(
                    rung, augment, width=w
                )
        self._select_programs()

    def raw_round_idx_fn(self, rung: Optional[_Rung] = None, augment=None,
                         cfg: Optional[Config] = None):
        """The UNJITTED index-round closure
        ``(state, data, client_ids, idx, plan, lr, env=()) -> (state,
        metrics)`` — the traceable body the jitted per-round program
        (``_build_round_idx_fn``) wraps; the ``data_gather`` scope is
        named here. Defaults to the active rung and the attached
        augmenter; ``cfg`` overrides the trace-time config (the fleet
        width builds pass the rung config narrowed to
        ``num_workers = w``)."""
        from commefficient_tpu.parallel.round import build_round_fn as _brf

        if rung is None:
            rung = self.rungs[self.active_rung]
        if augment is None:
            augment = self._dev_augment
        rcfg = rung.cfg if cfg is None else cfg
        raw_round = _brf(
            rcfg, self._loss_fn, self.unravel, self.mesh, rung.spec,
            _jit=False, d=self.grad_size,
        )
        has_aug = augment is not None
        L = rcfg.round_microbatches  # fedavg [W, L, B/L, ...] convention

        def round_idx_fn(state, data, client_ids, idx, plan, lr, env=()):
            W, B = idx.shape
            # telemetry.trace.ROUND_SCOPES: a name in the op metadata, no op
            with jax.named_scope("data_gather"):
                flat = idx.reshape(-1)
                batch = {k: v[flat] for k, v in data.items()}
                if has_aug:  # the keys it replaces or adds (data/augment.py)
                    batch.update(augment.device_apply(batch, *plan))
                batch = {k: g.reshape((W, B) + g.shape[1:])
                         for k, g in batch.items()}
                if L:  # fedavg microbatch convention ([W, L, B/L, ...])
                    batch = {
                        k: v.reshape(v.shape[0], L, v.shape[1] // L,
                                     *v.shape[2:])
                        for k, v in batch.items()
                    }
            return raw_round(state, client_ids, batch, lr, env=env)

        return round_idx_fn

    def _build_round_idx_fn(self, rung: _Rung, augment,
                            width: Optional[int] = None):
        hook_name = rung.idx_hook_name
        wcfg = None
        if width is not None:  # fleet: this width's own sentinel stream
            hook_name += f"[w{width}]"
            wcfg = self._width_cfg(rung.cfg, width)
        round_idx_fn = self.raw_round_idx_fn(rung, augment, cfg=wcfg)
        # the retrace sentinel watches the OUTER jitted program (the raw
        # round inside it is traced as part of the same trace — hooking
        # both would double-count every legitimate compile)
        return jax.jit(
            self.retrace_sentinel.wrap(round_idx_fn, hook_name),
            donate_argnums=(0,),
        )

    # -- H2D staging ------------------------------------------------------
    def stage_round_payload(self, client_ids, batch):
        """Commit one round's host batch to the mesh. ``train_round``
        calls it at dispatch; the asyncfed staging worker
        (asyncfed/staging.py) calls it ahead of a cohort's launch, so the
        host->device copy overlaps earlier launches' compute. Returns
        ``(client_ids_np, dev_batch)``; committed arrays pass through a
        later ``device_put`` as an identity (same sharding, no copy).
        Safe from a worker thread (pure ``device_put``, no tracing, no
        session state touched). client_ids stay host-side numpy: the offload path indexes host stores with
        them, and at [W] ints their dispatch-time put is noise."""
        cids = np.asarray(client_ids)
        dev_batch = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), self._batch_sharding),
            batch,
        )
        return cids, dev_batch

    def stage_round_indices(self, client_ids, idx, plan):
        """``stage_round_payload`` for the device-resident index round:
        commits the [W, B] sample indices and the augmentation plan (the
        only per-round H2D traffic on that path). Returns
        ``(client_ids_np, idx_dev, plan_dev)``."""
        cids = np.asarray(client_ids)
        idxd = jax.device_put(
            jnp.asarray(idx if isinstance(idx, jax.Array)
                        else np.asarray(idx, np.int32)),
            self._batch_sharding,
        )
        pl = (
            tuple(
                jax.device_put(
                    jnp.asarray(a if isinstance(a, jax.Array)
                                else np.asarray(a)),
                    self._replicated,
                )
                for a in plan
            )
            if plan
            else ()
        )
        return cids, idxd, pl

    # -- fedsim (fedsim/: availability masking + chaos) --------------------
    def sync_round_clock(self) -> None:
        """Align the host round clock — which drives the fedsim
        environment's availability/chaos schedule — with FedState.step.
        Called after a checkpoint restore replaced ``self.state``; a no-op
        cost otherwise (one scalar fetch, once per restore)."""
        self._round_clock = int(jax.device_get(self.state.step))
        # every restore path (vault rollback, checkpoint resume) lands
        # width-correct for free: the fleet schedule is pure in the round
        # index, so re-applying it here needs no extra bookkeeping
        if self.fedsim_env is not None and self.fedsim_env.has_fleet:
            self._set_fleet_width(
                self.fedsim_env.width_at(self._round_clock)
            )

    def blacklist_clients(self, client_ids) -> np.ndarray:
        """Add ``client_ids`` to the session blacklist
        (resilience/policy.py skip_clients): blacklisted clients are
        masked out of every future round's live mask BEFORE
        ``device_encode`` — the same ``jnp.where`` gate fedsim's
        participation mask rides, so unbiasedness over the surviving
        cohort is preserved by linearity and the server renormalizes by
        the reduced live count. Returns the cumulative blacklist.
        Requires a fedsim session (without one the round traced no
        masking and the blacklist would be silently inert)."""
        if self.fedsim_env is None:
            raise ValueError(
                "blacklist_clients needs a fedsim session (the round must "
                "have traced masking — cfg.fedsim_enabled); this session "
                "was built without it"
            )
        ids = np.unique(np.asarray(client_ids, np.int64))
        if self._client_blacklist is not None:
            ids = np.union1d(self._client_blacklist, ids)
        self._client_blacklist = ids
        return ids

    def _blacklist_env(self, env, client_ids):
        """Compose the session blacklist into one round's RoundEnv:
        blacklisted LIVE slots drop out (their category moves to
        dropped — the server neither accepts their uplink nor serves
        their downlink), the live count and the ``fedsim/*`` stats the
        ledger bills from re-derive from the reduced mask. Slots already
        dead stay whatever they were."""
        bl = np.isin(np.asarray(client_ids, np.int64),
                     self._client_blacklist)
        hit = bl & (env.live > 0)
        n_hit = int(hit.sum())
        if n_hit == 0:
            return env
        live = env.live.copy()
        live[hit] = 0.0
        n_live = float(live.sum())
        stats = dict(env.stats)
        stats["fedsim/participation_rate"] = n_live / live.shape[0]
        stats["fedsim/dropped"] = (
            float(stats.get("fedsim/dropped", 0.0)) + n_hit
        )
        stats["fedsim/all_dropped"] = float(n_live == 0)
        return env._replace(
            live=live.astype(np.float32),
            live_count=np.float32(n_live),
            stats=stats,
        )

    def _fedsim_round_env(self, env=None, client_ids=None):
        """(device env tuple for round_fn, host ``fedsim/*`` stats) for the
        CURRENT round — ``((), {})`` when the simulator is inactive.
        ``env`` (a fedsim.RoundEnv) overrides the session environment's
        schedule; tests drive explicit masks through it.
        ``client_ids`` (host [W]) lets the resilience blacklist compose
        into the mask — trace-only callers (prewarm/audit) may omit it."""
        if env is None:
            if self.fedsim_env is None:
                return (), {}
            env = self.fedsim_env.round_env(
                self._round_clock,
                replay=self._round_clock < self._replay_horizon,
            )
        elif self.fedsim_env is None:
            # symmetric guard to the round's "fedsim enabled but no env"
            # error: a session built without fedsim traced NO masking, so
            # an explicit env would be silently dropped by the round while
            # its stats still reached the metrics — reject instead
            raise ValueError(
                "env= passed but this session was built without fedsim "
                "(cfg.fedsim_enabled is False — the round traced no "
                "masking); construct the Config with availability/chaos "
                "set to drive masked rounds"
            )
        if self._client_blacklist is not None and client_ids is not None:
            env = self._blacklist_env(env, client_ids)
        live = jax.device_put(jnp.asarray(env.live), self._batch_sharding)
        corr = jax.device_put(jnp.asarray(env.corrupt), self._batch_sharding)
        cnt = jax.device_put(jnp.float32(env.live_count), self._replicated)
        return (live, corr, cnt), dict(env.stats)

    # -- host-side round observability (telemetry) -------------------------
    @property
    def spans(self):
        """The attached PhaseSpans recorder (None below level 1). A
        property so attaching/detaching also reaches the clientstore
        streamer's writeback lane — the streamer is constructed at
        session build time, long before build_perf_observability runs."""
        return self._spans

    @spans.setter
    def spans(self, value) -> None:
        self._spans = value
        streamer = getattr(self, "_streamer", None)
        if streamer is not None:
            streamer.spans = value

    def _span(self, name: str, fence=None, collective: bool = False,
              trace_id=None):
        """Phase-span context (telemetry/spans.py): a ``fed/<name>``
        annotation in whatever profiler trace is open, at every telemetry
        level, and recorded in the ring where a train loop attached a
        recorder (level >= 1; yields None otherwise).
        ``collective=True`` tags the span for the exposed-collective
        accounting (the round-dispatch spans: their fence waits on the
        program's aggregation collectives); ``trace_id=`` stamps the
        owning round's id (schema v11)."""
        return span_of(self.spans, name,
                       self._round_clock if self.spans is None else None,
                       fence=fence, collective=collective, trace_id=trace_id)

    def _host_round_stats(self, fs_stats: dict) -> dict:
        """Host scalars riding this round's metric dict: the fedsim stats,
        (level >= 1) the retrace sentinel's count, the controller's
        ``control/*`` scalars, and the resilience rider's ``resilience/*``
        scalars — constant key set across an epoch, as pack_metric_dicts
        requires."""
        stats = dict(fs_stats)
        if "fleet/width" in stats:
            # the ONE runtime fleet counter (schema v13): bumped by the
            # resilience manager when a FleetShrinkError recovery lands —
            # everything else under fleet/* is schedule-derived in the
            # fedsim environment, so rollback replay re-emits it exactly
            stats["fleet/shrink_recoveries"] = float(
                self._fleet_shrink_recoveries
            )
        if self.cfg.telemetry_level >= 1:
            stats["xla/retraces"] = float(self.retrace_sentinel.retraces)
            if self.spans is not None:
                from commefficient_tpu.telemetry.xla_audit import (
                    exposed_collective_ms,
                )

                stats["xla/exposed_collective_ms"] = exposed_collective_ms(
                    self.spans, self.last_audit
                )
            if self.cfg.num_hosts > 1:
                # multihost/* scalars (schema v12): process topology plus
                # the cross-host traffic/exposure attribution. Emitted
                # only on multi-host configs — num_hosts is fixed for a
                # run, so the key set stays constant (pack_metric_dicts).
                # On the mesh-faked twin process_count() is 1 and host_id
                # 0; the real pod reports its jax.distributed topology.
                stats["multihost/num_processes"] = float(jax.process_count())
                stats["multihost/host_id"] = float(jax.process_index())
                # every aggregation collective rides the declared host
                # axis, so the round's whole upload payload crosses (or
                # on one process, would cross) the host boundary once
                stats["multihost/cross_host_bytes"] = float(
                    self.bytes_per_round()["upload_bytes"]
                )
                # exposed collective wait attributed to DCN: with the
                # worker collectives spanning the host axis, un-hidden
                # collective time IS cross-host exposure (0.0 below
                # spans attachment, same as xla/exposed_collective_ms)
                stats["multihost/dcn_exposed_ms"] = float(
                    stats.get("xla/exposed_collective_ms", 0.0)
                )
        if self.controller is not None:
            stats.update(self.controller.scalars())
        if self.resilience is not None:
            stats.update(self.resilience.scalars())
        if self._streamer is not None and self.cfg.telemetry_level >= 1:
            # clientstore/* scalars (schema v10): cache hit rate,
            # evictions, H2D stage ms, async writeback ms — drained per
            # round so the key set stays constant
            stats.update(self._streamer.pop_round_stats())
        if self.spans is not None and self.cfg.telemetry_level >= 1:
            # trace/* critical-path scalars (schema v11), LAGGED: at
            # this point round _round_clock-1 just dispatched (its drain
            # has not run), so the newest round whose spans are complete
            # is _round_clock-2 — early rounds emit the zeros row
            # (constant key set, pack_metric_dicts discipline)
            from commefficient_tpu.telemetry.trace import (
                trace_round_scalars,
            )

            stats.update(
                trace_round_scalars(self.spans, self._round_clock - 2)
            )
        return stats

    def _control_round_start(self, fs_stats: dict) -> None:
        """Controller decision point, host-side, BEFORE dispatch: may swap
        the active rung (and migrate server state) or raise
        BudgetExhaustedError — so the offending round never runs."""
        if self.controller is not None:
            self.controller.on_round_start(self._round_clock, fs_stats)

    @_round_step
    def train_round_indices(self, client_ids, idx, plan, lr: float, env=None):
        """Run one round from device-resident data (see ``attach_data``)."""
        from commefficient_tpu.telemetry.trace import round_trace_id

        w = self._fleet_round_begin()
        if w != self.cfg.num_workers:
            # session-owned width slicing: the sampler keeps drawing base-
            # width cohorts (its draw sequence stays resume-stable); the
            # round consumes the first w — plan rows are per-sample, so
            # the slice is w*B there
            client_ids = np.asarray(client_ids)[:w]
            idx = idx[:w]
            if plan:
                B = idx.shape[1]
                plan = tuple(a[: w * B] for a in plan)
        tid = round_trace_id(self._round_clock)
        with self._span("device_put", trace_id=tid):
            cids, idxd, pl = self.stage_round_indices(client_ids, idx, plan)
            ids = jax.device_put(jnp.asarray(cids), self._batch_sharding)
        with self._span("fedsim_env", trace_id=tid):
            fs_env, fs_stats = self._fedsim_round_env(env, client_ids=cids)
        self._control_round_start(fs_stats)
        with self._span("round_dispatch", collective=True,
                        trace_id=tid) as sp:
            self.state, metrics = self._round_idx_fn(
                self.state, self._dev_data, ids, idxd, pl, jnp.float32(lr),
                env=fs_env,
            )
            if sp is not None:
                sp.fence(metrics["loss"])
        self._round_clock += 1
        self._replay_horizon = max(self._replay_horizon, self._round_clock)
        stats = self._host_round_stats(fs_stats)
        return {**metrics, **stats} if stats else metrics

    # -- train ------------------------------------------------------------
    @_round_step
    def train_round(self, client_ids: np.ndarray, batch: Dict[str, np.ndarray],
                    lr: float, env=None):
        from commefficient_tpu.telemetry.trace import round_trace_id

        w = self._fleet_round_begin()
        if w != self.cfg.num_workers:
            # session-owned width slicing (the sampler stays base-width)
            client_ids = np.asarray(client_ids)[:w]
            batch = jax.tree.map(lambda a: a[:w], batch)
        tid = round_trace_id(self._round_clock)
        with self._span("device_put", trace_id=tid):
            cids, dev_batch = self.stage_round_payload(client_ids, batch)
            ids = jax.device_put(jnp.asarray(cids), self._batch_sharding)
        lr = jnp.float32(lr)
        with self._span("fedsim_env", trace_id=tid):
            fs_env, fs_stats = self._fedsim_round_env(env, client_ids=cids)
        self._control_round_start(fs_stats)
        if self._streamer is None:
            with self._span("round_dispatch", collective=True,
                            trace_id=tid) as sp:
                self.state, metrics = self.round_fn(
                    self.state, ids, dev_batch, lr, env=fs_env
                )
                if sp is not None:
                    sp.fence(metrics["loss"])
            self._round_clock += 1
            self._replay_horizon = max(self._replay_horizon,
                                       self._round_clock)
            stats = self._host_round_stats(fs_stats)
            return {**metrics, **stats} if stats else metrics
        # hosted client state (clientstore/): cohort rows are ARGUMENTS of
        # the compiled round — no [num_clients, D] operand in the HLO. The
        # gather waits on any pending writeback of the same clients, so
        # the rows are the previous round's.
        cohort = self._streamer.gather(cids, trace_id=tid)
        with self._span("round_dispatch", collective=True,
                        trace_id=tid) as sp:
            self.state, metrics, new_vel, new_err = self.round_fn(
                self.state, ids, dev_batch, lr, cohort.vel, cohort.err,
                env=fs_env,
            )
            if sp is not None:
                sp.fence(metrics["loss"])
        self._round_clock += 1
        self._replay_horizon = max(self._replay_horizon, self._round_clock)
        # async writeback: the worker thread syncs new_vel/new_err D2H and
        # scatters into the bank off the host loop's critical path; the
        # flush fence (checkpoint/vault via host_vel, or close) joins it
        self._streamer.scatter(cids, new_vel, new_err, trace_id=tid)
        stats = self._host_round_stats(fs_stats)
        return {**metrics, **stats} if stats else metrics

    # -- eval -------------------------------------------------------------
    def _put_eval_batch(self, b: Dict[str, np.ndarray]):
        """Shard eval batch rows over the mesh so validation uses every chip
        (the reference round-robins val across workers, fed_worker ~L290-340)."""
        n_dev = self._n_mesh_devices
        out = {}
        for k, v in b.items():
            a = jnp.asarray(v)
            if k != "_valid" and a.ndim >= 1 and a.shape[0] % n_dev == 0 and n_dev > 1:
                out[k] = jax.device_put(a, self._batch_sharding)
            else:
                out[k] = jax.device_put(a, self._replicated)
        return out

    def evaluate(self, batches: Iterable[Dict[str, np.ndarray]]) -> Dict[str, float]:
        # Dispatch every batch WITHOUT fetching, then stack the per-batch
        # metric dicts on device and fetch once — a per-batch float() is a
        # host<->device round trip that serializes the whole val pass.
        outs = []
        valids = []
        pv = self.state.params_vec
        if self.cfg.fsdp:
            pv = pv[: self.grad_size]  # drop the [Dp] shard padding once
        for b in batches:
            outs.append(self.eval_fn(pv, self._put_eval_batch(b)))
            valids.append(float(np.asarray(b["_valid"])))
        if not outs:
            return {"loss": float("nan")}
        from commefficient_tpu.utils.logging import pack_metric_dicts

        names, mat = pack_metric_dicts(outs)
        sum_keys = {
            k for k in names
            if k in ("loss_sum", "correct", "count")
            or k.endswith("_sum") or k.endswith("_count")
        }
        totals: Dict[str, float] = {}
        n = 0.0
        for j, valid in enumerate(valids):
            for i, k in enumerate(names):
                # sum-style keys (loss_sum/correct/count and any *_sum /
                # *_count aux, e.g. the GPT-2 token-weighted lm_loss_sum/
                # token_count pair) are already masked per-element sums;
                # weight any other (per-batch mean) aux key by the batch's
                # valid rows so the padded tail batch doesn't bias the
                # average (ADVICE r1, VERDICT r2 item 6).
                w = 1.0 if k in sum_keys else valid
                totals[k] = totals.get(k, 0.0) + w * float(mat[j, i])
            n += valid
        result = {"loss": totals.get("loss_sum", 0.0) / max(n, 1.0)}
        if "count" in totals and totals["count"] > 0:
            result["accuracy"] = totals.get("correct", 0.0) / totals["count"]
        for k, v in totals.items():
            if k in ("loss_sum", "correct", "count"):
                continue
            # raw totals for sum-style aux; row-weighted mean for the rest
            result[k] = v if k in sum_keys else v / max(n, 1.0)
        return result

    # -- weights ----------------------------------------------------------
    @property
    def params(self):
        vec = self.state.params_vec
        if self.cfg.fsdp:
            vec = vec[: self.grad_size]
        return self.unravel(vec)

    # -- compiled-graph audit (telemetry/xla_audit.py) ---------------------
    def audit_compiled_round(self, client_ids, batch, lr: float, env=None):
        """AOT-compile the round for ``batch``'s signature and audit the
        artifact: XLA cost/memory analyses + the HLO collective walk,
        cross-checked against this session's ledger accounting and (on the
        sharded sketch decode) the PR-6 ``<= W*k`` all-gather bound.
        Returns a ``telemetry.CompiledRoundAudit``.

        Costs one extra XLA compile (the AOT ``compile()`` artifact is
        separate from the jit call cache). The ``lower()`` TRACE, however,
        is shared with the call path on this jax, so it counts as the
        round's expected first trace — audit with the run's real first
        batch (the train entries pass ``sampler.sample_round(0)``) and the
        sentinel stays at zero retraces for a clean run. Audits the
        host-batch round — the device-resident index round wraps the same
        program plus an in-graph gather, so this is the representative
        artifact for both entry paths. Pure observer: no state, round
        clock, or donation side effects.
        """
        from commefficient_tpu.telemetry.xla_audit import CompiledRoundAudit

        if self.cfg.asyncfed_enabled:
            return self._audit_compiled_async_round(
                client_ids, batch, lr, env=env
            )
        cids = np.asarray(client_ids)
        ids = jax.device_put(jnp.asarray(cids), self._batch_sharding)
        dev_batch = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), self._batch_sharding),
            batch,
        )
        args = [self.state, ids, dev_batch, jnp.float32(lr)]
        if self._streamer is not None:
            # concrete staged rows (not ShapeDtypeStructs) so the lowered
            # program carries the exact shardings the dispatch path uses —
            # a struct-lowered twin could compile a second layout
            staged = self._streamer.gather(cids)
            args.extend([staged.vel, staged.err])
        fs_env, _ = self._fedsim_round_env(self._base_width_env(env))
        lowered = self.round_fn.lower(*args, env=fs_env)
        compiled = lowered.compile()
        audit = CompiledRoundAudit.from_compiled(
            compiled,
            engine="fsdp" if self.cfg.fsdp else "replicated",
            **self._audit_bounds(cids),
        )
        self.last_audit = audit
        return audit

    def _audit_bounds(self, cids) -> Dict[str, Any]:
        """The ledger/collective bounds every compiled-round audit is
        checked against — shared by the synchronous and asyncfed audits
        (the bounds depend on the active rung's geometry, not on which
        engine dispatches the program)."""
        from commefficient_tpu.telemetry.xla_audit import ledger_tolerance

        cids = np.asarray(cids)
        W = self._n_mesh_devices
        # capability, not a mode string (scripts/check_mode_dispatch.py):
        # only compressors with a server-decode strategy knob report one
        is_sketch = (
            not self.cfg.fsdp and self.compressor.supports_sharded_decode
        )
        sharded = is_sketch and self.sketch_decode_resolved == "sharded"
        up = self.bytes_per_round()["upload_bytes"]
        # k from the ACTIVE rung's config (the program being audited)
        k_active = self.rungs[self.active_rung].cfg.k
        has_sparse_agg = (
            not self.cfg.fsdp and self.compressor.supports_sparse_aggregate
        )
        aggregate = self.aggregate_resolved if has_sparse_agg else None
        sparse_agg_bound = None
        sparse_agg_exemption = None
        if aggregate == "sparse":
            # the largest LEGAL all-reduce/all-gather on the sparse path:
            # the pair exchange. local_topk gathers each chip's w_loc*k
            # candidate buffer; true_topk gathers k per shard; sketch keeps
            # its O(r*c) table psum (the mode's design payload) and rides
            # only the EF re-sketch on the pair exchange.
            sparse_agg_bound = W * k_active
            if self.compressor.needs_sketch_spec:
                spec = self.rungs[self.active_rung].spec
                table_elems = 1
                for dim in spec.table_shape:
                    table_elems *= int(dim)
                sparse_agg_bound = max(sparse_agg_bound, table_elems)
            elif not self.compressor.sparse_aggregate_shards_state:
                w_loc = max(1, cids.shape[0] // W)
                sparse_agg_bound = W * w_loc * k_active
            active_cfg = self.rungs[self.active_rung].cfg
            if not active_cfg.client_state_hosted and (
                needs_client_vel(active_cfg) or needs_client_err(active_cfg)
            ):
                # in-graph per-client rows predate sparse aggregation: the
                # scatter-back into the replicated [num_clients, D] state
                # all-gathers the w participating rows (w*D elems). It is
                # state residency, not aggregation traffic — host the
                # client state (--client_store host|mmap) and the strict
                # O(W*k) bound holds with NO exemption: the rows are round
                # arguments, so the [C, D] gather never appears in the
                # HLO. The marker below rides the report so the schema
                # checker can REJECT any sparse-aggregate report that
                # claims a host store while carrying the exemption.
                sparse_agg_bound = max(
                    sparse_agg_bound, cids.shape[0] * self.grad_size
                )
                sparse_agg_exemption = "client_state_writeback"
        # collective-hiding attribution (schema v9): the block rides the
        # report exactly when a hiding mode is ON, so downstream wall-clock
        # comparisons can never mix overlapped and sequential figures
        overlap_info = None
        if (self.cfg.overlap_collectives != "none"
                or self.cfg.async_double_buffer):
            overlap_info = {
                "collectives": self.cfg.overlap_collectives,
                "double_buffer": bool(self.cfg.async_double_buffer),
            }
        # host-axis topology (schema v12): present exactly when the mesh
        # declares a hosts axis, so every collective figure in the report
        # states which topology its all-reduces spanned
        multihost_info = None
        if self.cfg.num_hosts > 1:
            multihost_info = {
                "num_hosts": int(self.cfg.num_hosts),
                "num_processes": int(jax.process_count()),
                "host_id": int(jax.process_index()),
            }
        return dict(
            mode=self.cfg.mode,
            sketch_decode=self.sketch_decode_resolved if is_sketch else None,
            aggregate=aggregate,
            grad_size=self.grad_size,
            workers_mesh=W,
            ledger_up_bytes=up,
            wk_bound=W * k_active if sharded else None,
            sparse_agg_bound=sparse_agg_bound,
            sparse_agg_exemption=sparse_agg_exemption,
            tolerance_bytes=ledger_tolerance(
                up, sharded=sharded, workers=W, k=k_active
            ),
            overlap_info=overlap_info,
            multihost_info=multihost_info,
        )

    # -- asyncfed programs -------------------------------------------------
    def async_round_fns(self, rung_index: Optional[int] = None):
        """The asyncfed ``(launch_fn, apply_fn)`` pair for one rung,
        built lazily and cached on the SESSION so the perf-observability
        audit (which the runner builds first) and the engine dispatch the
        same jitted objects — one trace cache, one sentinel stream per
        rung, zero phantom retraces."""
        # lazy: parallel.__init__ -> api would otherwise cycle through
        # asyncfed.round -> parallel.round
        from commefficient_tpu.asyncfed.round import build_async_round_fns

        idx = self.active_rung if rung_index is None else int(rung_index)
        cached = self._async_programs.get(idx)
        if cached is not None:
            return cached
        rung = self.rungs[idx]
        pair = build_async_round_fns(
            rung.cfg, self._loss_fn, self.unravel, self.mesh, rung.spec,
            d=self.grad_size,
            launch_hook=self.retrace_sentinel.hook_for(
                _rung_hook_name(rung.label, "async_launch_fn")
            ),
            apply_hook=self.retrace_sentinel.hook_for(
                _rung_hook_name(rung.label, "async_apply_fn")
            ),
        )
        self._async_programs[idx] = pair
        return pair

    def _audit_compiled_async_round(self, client_ids, batch, lr, env=None):
        """The asyncfed variant of the compiled-round audit: RUN the
        launch program once (pure — donates nothing, touches no state) to
        obtain concrete apply inputs, then AOT-compile the apply — the
        phase that carries every collective — and audit it against the
        same ledger/collective bounds as the synchronous round. Doubles
        as the engine's warmup: both programs are traced here, so a clean
        run's sentinel stays at zero retraces at any buffer/concurrency.
        """
        from commefficient_tpu.telemetry.xla_audit import CompiledRoundAudit

        cfg = self.cfg
        launch_fn, apply_fn = self.async_round_fns(self.active_rung)
        cids = np.asarray(client_ids)
        ids = jax.device_put(jnp.asarray(cids), self._batch_sharding)
        dev_batch = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), self._batch_sharding),
            batch,
        )
        fs_env, _ = self._fedsim_round_env(env, client_ids=cids)
        # launch_fn takes (live, corrupt) only — the count is an apply-
        # side quantity (wsum) in the async round
        launch_env = tuple(fs_env[:2]) if fs_env else ()
        st = self.state
        out = launch_fn(
            st.params_vec, st.client_vel, st.client_err, ids, dev_batch,
            jnp.int32(0), jnp.float32(lr), env=launch_env,
        )
        W = cfg.num_workers
        weights = jax.device_put(
            jnp.ones((W,), jnp.float32), self._batch_sharding
        )
        # lower() never executes, so donation stays un-triggered and the
        # session state survives the audit untouched
        compiled = apply_fn.lower(
            self.state, *out, ids, weights, jnp.float32(W), jnp.float32(lr)
        ).compile()
        audit = CompiledRoundAudit.from_compiled(
            compiled,
            engine="async",
            async_info={
                "buffer": int(cfg.async_buffer),
                "concurrency": int(cfg.async_concurrency),
                "staleness_exponent": float(cfg.staleness_exponent),
            },
            **self._audit_bounds(cids),
        )
        self.last_audit = audit
        return audit

    def bytes_per_round(self) -> Dict[str, int]:
        """Upload/download bytes per participating client (BASELINE.md
        accounting) — the headline communication metric, delegated to the
        ACTIVE rung's compressor (sketch reports the REALIZED
        ``r * c_actual`` table and warns when the blocked layout inflates
        the request >25%, ADVICE r1; powersgd's downlink is the factored
        ``r * (n + m)`` pair). Per-rung figures: ``rung_bytes_per_round``."""
        return self.rung_bytes_per_round(self.active_rung)


class FedModel:
    """Callable façade (the ``FedCommEffModel`` analog)."""

    def __init__(self, session: FederatedSession):
        self.session = session
        self.optimizer: Optional["FedOptimizer"] = None  # set by make_fed_pair

    def __call__(self, client_ids, batch, lr: Optional[float] = None):
        if lr is None:
            if self.optimizer is None:
                raise ValueError(
                    "no lr given and no FedOptimizer attached; pass lr= or "
                    "construct via make_fed_pair"
                )
            lr = self.optimizer.get_lr()
        return self.session.train_round(client_ids, batch, lr)

    def evaluate(self, batches):
        return self.session.evaluate(batches)

    def save_pretrained(self, out_dir: str, gcfg) -> None:
        """HF-format export passthrough for the GPT-2 workload
        (``FedModel.save_pretrained``, fed_aggregator.py ~L260-280)."""
        from commefficient_tpu.models.hf_gpt2 import save_pretrained

        save_pretrained(out_dir, gcfg, self.session.params)

    @property
    def params(self):
        return self.session.params


class FedOptimizer:
    """Schedule clock (the ``FedCommEffOptimizer`` analog). The server update
    itself is fused into the round program; ``step()`` advances the LR."""

    def __init__(self, session: FederatedSession, lr_fn: Callable[[int], float]):
        self.session = session
        self.lr_fn = lr_fn
        self._step = 0

    def get_lr(self) -> float:
        return float(self.lr_fn(self._step))

    def step(self) -> None:
        self._step += 1

    def zero_grad(self) -> None:  # API parity; nothing to zero functionally
        pass


def make_fed_pair(cfg: Config, params, loss_fn, lr_fn, **kw):
    """Reference-style constructor: (FedModel, FedOptimizer) sharing a session."""
    session = FederatedSession(cfg, params, loss_fn, **kw)
    model, opt = FedModel(session), FedOptimizer(session, lr_fn)
    model.optimizer = opt
    return model, opt
