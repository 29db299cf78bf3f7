"""Compressor base class — the protocol every mode implements.

Layering: compress/ sits between ops/ (kernels it may use) and parallel/
(the round engines that consume it). It therefore imports ONLY ops and jax;
mesh axis names are passed in by the caller, and ``cfg`` is duck-typed (a
``utils.config.Config``, but never imported here, so config.py may validate
against the registry without a cycle).

A compressor instance is a TRACE-TIME object: the round builders construct
it once per compile and call its hooks while tracing, so every method body
below runs under jit — keep them functional (no python-side state mutation
beyond memoized resolution done before tracing starts).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp

from commefficient_tpu.ops.countsketch import unsketch, unsketch_dense
from commefficient_tpu.ops.topk import topk_dense, topk_threshold_dense

# server-state leaf kinds (init_state / FSDP sharding decisions):
#   None    — leaf absent (empty tuple in FedState)
#   "dense" — [D] vector (FSDP shards it [D/W] over workers)
#   "table" — [r, c] sketch table (small; FSDP keeps it replicated)
KIND_NONE = None
KIND_DENSE = "dense"
KIND_TABLE = "table"


class Compressor:
    """One compression mode's full algebra. Subclass + ``@register``."""

    name: str = "?"  # stamped by @register
    # (mode, error_type) support table — the legacy _validate contract
    allowed_error_types: Tuple[str, ...] = ("none",)
    # False -> the FSDP round refuses this mode with a pointer to the
    # memory-wall knob that DOES apply (offload_client_state for local
    # modes); True -> the class implements fsdp_update()
    supports_fsdp: bool = False
    # True -> FederatedSession builds a CountSketch spec and passes it in
    needs_sketch_spec: bool = False
    # True -> the class implements server_update_sharded(): the REPLICATED
    # round can decode the aggregate shard-wise (each chip works on its
    # D/W coordinate slice, candidates ride a ~W*k all_gather) instead of
    # every chip redundantly repeating the full-D server extraction. Gated
    # by cfg.sketch_decode through use_sharded_decode() below.
    supports_sharded_decode: bool = False
    # True -> the fused flattened-batch gradient fast path is mathematically
    # identical for this mode (nothing per-client in the transmit rule)
    supports_fused_clients: bool = False
    # True -> the per-client rules are the base ones below: client_grad is
    # the single gradient pass and client_transmit returns u unchanged, so
    # the round needs only the SUM of the clients' clipped gradients and
    # may take it leaf by leaf (parallel/round.py::resolve_client_path).
    # A subclass that overrides either rule sets False.
    base_client_rules: bool = True
    # True -> the class implements encode_grad_table() and the round may
    # run the sketch-fused backward (cfg.sketch_fused_bwd): the worker's
    # gradient is produced directly as an encoded table by per-leaf
    # custom_vjp taps (ops.countsketch.sketch_grad_tap), so the flat [D]
    # grad concat is never traced. Only meaningful on the fused
    # flattened-batch path (one gradient per device).
    supports_fused_backward: bool = False
    # True -> this mode's on-mesh aggregation can ride the sparse
    # allreduce pair exchange (ops/collectives): its transmit (or server
    # candidate set) is <= O(W*k)-sparse. Gated by cfg.aggregate through
    # use_sparse_aggregate() below.
    supports_sparse_aggregate: bool = False
    # True -> aggregate='auto' MAY resolve to sparse on a multi-device
    # mesh (only safe when sparse changes neither stored state shapes nor
    # the server summation order — local_topk's replicated dense rebuild)
    sparse_aggregate_in_auto: bool = False
    # True -> under sparse aggregation the server momentum/error leaves
    # live SHARDED over the workers axis as [padded_dim(d, Wd)] arrays
    # (true_topk: reduce-scatter aggregate + sharded select); the session
    # commits/prewarms those leaves with P(WORKERS) placement
    sparse_aggregate_shards_state: bool = False
    # True -> the applied delta is dense, so do_topk_down's downlink top-k
    # is meaningful (sketch/true_topk deltas already have <= k nonzeros;
    # powersgd's delta is rank-r factored)
    dense_delta: bool = True
    # momentum_dampening=None (AUTO) resolves to this (r4 four-corner
    # evidence; see resolved_dampening overrides for the per-mode warnings)
    default_dampening: bool = False

    def __init__(self, cfg, d: int, spec=None):
        self.cfg = cfg
        self.d = d
        self.spec = spec
        # top-k selection kernel (cfg.topk_method): "threshold" is the TPU
        # fast path — no sort, no scatter (ops.topk.topk_threshold_dense)
        if cfg.topk_method == "threshold":
            self.topk = topk_threshold_dense
            self.unsketch = lambda sp, t, k: unsketch_dense(sp, t, k)  # noqa: E731
        else:
            approx = cfg.topk_method == "approx"
            self.topk = partial(topk_dense, approx=approx)
            self.unsketch = partial(unsketch, approx=approx)
        self._dampen: Optional[bool] = None

    @property
    def overlap_segments(self) -> Optional[int]:
        """``None`` (monolithic collectives — the golden-pinned default)
        or the segment count the layerwise-overlap chunked pair
        exchanges split their payload into
        (``cfg.overlap_collectives='layerwise'``; ops/collectives
        ``all_gather_pairs(segments=...)``). Segmentation is pure data
        movement, bit-equal to the monolithic gather."""
        if getattr(self.cfg, "overlap_collectives", "none") == "layerwise":
            from commefficient_tpu.ops.collectives import OVERLAP_SEGMENTS

            return OVERLAP_SEGMENTS
        return None

    # ---- validation ------------------------------------------------------
    def validate(self) -> None:
        """Raise on unsupported (mode, error_type) combinations — the
        reference-supported table, NotImplementedError for API parity with
        the legacy round's _validate."""
        if self.cfg.error_type not in self.allowed_error_types:
            raise NotImplementedError(
                f"(mode={self.name}, error_type={self.cfg.error_type}) is "
                f"not a reference-supported combination; allowed: "
                f"{self.allowed_error_types}"
            )

    def validate_fsdp(self) -> None:
        """FSDP-specific constraints; base refusal points at the knob that
        addresses this mode's memory wall instead."""
        if not self.supports_fsdp:
            raise NotImplementedError(
                f"fsdp supports server-state modes (uncompressed/true_topk/"
                f"sketch); mode={self.name} keeps per-client "
                "[num_clients, D] state — use offload_client_state for "
                "that memory wall"
            )

    # ---- dampening -------------------------------------------------------
    def resolved_dampening(self, warn: bool = True) -> bool:
        """Resolve momentum_dampening AUTO (None) for this mode, emitting
        the mode's evidence/parity warnings when ``warn`` (the replicated
        round builder warns; FSDP resolves silently, matching its legacy
        inline resolution). Memoized so repeated hook calls are free."""
        if self._dampen is None:
            md = self.cfg.momentum_dampening
            self._dampen = md if md is not None else self.default_dampening
            if warn:
                self._dampening_warnings(self._dampen)
        return self._dampen

    def _dampening_warnings(self, dampen: bool) -> None:
        pass

    # ---- server state ----------------------------------------------------
    def server_state_kinds(self) -> Tuple[Optional[str], Optional[str]]:
        """(momentum_kind, error_kind) — drives allocation in init_state,
        FSDP sharding specs, and the per-chip memory accounting."""
        rho = self.cfg.virtual_momentum
        return (KIND_DENSE if rho > 0 else KIND_NONE, KIND_NONE)

    def init_server_state(self) -> Tuple[Any, Any, Any]:
        """(momentum, error, extra) FedState leaves; () where absent.
        ``extra`` is compressor-private warm state (powersgd's Q)."""
        f32 = jnp.float32
        m_kind, e_kind = self.server_state_kinds()
        table = self.spec.table_shape if self.spec is not None else None

        def alloc(kind):
            if kind == KIND_DENSE:
                return jnp.zeros((self.d,), f32)
            if kind == KIND_TABLE:
                # tables carry the spec's STORAGE dtype (bf16 halves the
                # server-state HBM at GPT-2 scale; f32 default unchanged)
                return jnp.zeros(table, self.spec.table_dtype)
            return ()

        return alloc(m_kind), alloc(e_kind), self.init_extra_state()

    def init_extra_state(self) -> Any:
        return ()

    # ---- worker side (inside shard_map) ----------------------------------
    def client_grad(self, grad_one: Callable, params_vec, batch, noise_rng,
                    lr):
        """Per-client gradient rule: ``-> (g [D], loss, aux)``. Default is
        one gradient pass; fedavg overrides with its local-SGD scan."""
        return grad_one(params_vec, batch, noise_rng)

    def client_transmit(self, u, err_row, lr):
        """Per-client transmit rule AFTER local momentum:
        ``-> (transmit [D], new_vel [D], new_err_row)``. Default transmits
        the dense update and leaves client error untouched; local_topk
        overrides with its error-feedback + top-k + dampening."""
        return u, u, err_row

    # ---- device side (inside shard_map, once per device) -----------------
    def device_encode(self, local_sum):
        """LINEAR encode of the device's summed transmit, applied once per
        device just before the cross-worker psum (see the package docstring
        for the psum-safety contract). Default: identity."""
        return local_sum

    # ---- server side -----------------------------------------------------
    def server_update(self, momentum, error, extra, agg, lr, step):
        """Server momentum/error algebra + update extraction:
        ``-> (delta, new_momentum, new_error, new_extra)`` where ``delta``
        is the APPLIED update (``w -= delta``). ``agg`` is the psum-averaged
        (decoded-domain or encoded-domain) aggregate; ``step`` the round
        counter (powersgd's non-warm-start Q derives from it)."""
        raise NotImplementedError

    # ---- sharded server decode (replicated engine) -----------------------
    def use_sharded_decode(self, mesh_workers: int) -> bool:
        """Resolve ``cfg.sketch_decode`` for this mode on a replicated
        mesh whose ``workers`` axis has ``mesh_workers`` devices.

        ``dense`` / modes without the capability -> False (the legacy
        full-D ``server_update`` path, bit-identical to pre-PR-6 rounds).
        ``sharded`` -> True (Config already validated the mode/topk
        combination). ``auto`` -> sharded exactly when splitting the
        decode can win AND cannot change results: >1 worker device (on
        one device there is no redundant work to remove — and the
        single-device golden recordings stay bit-untouched) and the
        threshold top-k kernel (the sharded global selection is built on
        ``topk_threshold_sharded``; exact/approx selections keep the
        dense path so their tie-breaking semantics are preserved)."""
        if not self.supports_sharded_decode:
            return False
        decode = getattr(self.cfg, "sketch_decode", "auto")
        if decode == "dense":
            return False
        if decode == "sharded":
            return True
        return mesh_workers > 1 and self.cfg.topk_method == "threshold"

    # ---- sparse on-mesh aggregation (replicated engine) ------------------
    def use_sparse_aggregate(self, mesh_workers: int) -> bool:
        """Resolve ``cfg.aggregate`` for this mode on a replicated mesh
        whose ``workers`` axis has ``mesh_workers`` devices.

        ``dense`` / modes without the capability -> False (the legacy
        full-[D] psum). ``sparse`` -> True (Config already validated the
        mode/topk/fsdp combination). ``auto`` -> sparse exactly when the
        pair exchange can win AND cannot change results beyond f32
        summation order: >1 worker device (a 1-device mesh has no
        exchange to shrink — and the single-device golden recordings stay
        bit-untouched), the threshold top-k kernel (the family whose
        selections the sparse paths are built on), and a mode that opts
        into auto (``sparse_aggregate_in_auto`` — local_topk only, whose
        sparse path keeps state shapes and server algebra identical)."""
        if not self.supports_sparse_aggregate:
            return False
        agg = getattr(self.cfg, "aggregate", "auto")
        if agg == "dense":
            return False
        if agg == "sparse":
            return True
        return (self.sparse_aggregate_in_auto and mesh_workers > 1
                and self.cfg.topk_method == "threshold")

    def server_update_sparse(self, momentum, error, extra, agg_sh, lr,
                             step, *, axis_name, Wd, d):
        """Sparse-aggregate server update, called INSIDE a shard_map over
        the ``workers`` axis with SHARDED server state: ``momentum`` /
        ``error`` / ``agg_sh`` are this chip's [S] = [padded_dim(d,Wd)/Wd]
        slices (``agg_sh`` from the reduce-scattered transmit sum).
        Returns ``(idx [Wd*kb], val [Wd*kb], new_momentum_sh,
        new_error_sh, new_extra)`` — idx/val are REPLICATED (post-gather)
        global candidate pair buffers with val==0 padding, and the round
        applies ``params.at[idx].add(-val)`` exactly like the sharded
        sketch decode. Only classes with ``sparse_aggregate_shards_state``
        implement it."""
        raise NotImplementedError

    def server_update_sharded(self, momentum, error, extra, agg, lr, step,
                              *, axis_name, Wd, d):
        """Sharded decode of the replicated round's server update, called
        INSIDE a shard_map over the ``workers`` axis (size ``Wd``) with
        every input replicated: this device estimates/extracts only its
        ``ceil(d/Wd)`` coordinate slice and the cross-shard candidate
        exchange happens internally (scalar-only threshold collectives +
        one ~Wd*k all_gather). Returns ``(idx [Wd*kb], val [Wd*kb],
        new_momentum, new_error, new_extra)`` with idx/val REPLICATED
        (post-gather) global candidate buffers, val==0 on padding — the
        round applies ``params.at[idx].add(-val)``. Only classes with
        ``supports_sharded_decode`` implement it."""
        raise NotImplementedError

    # ---- FSDP (sharded server state) hooks -------------------------------
    def fsdp_update(self, p_sh, m_in, e_in, local, lr, *, axis_name, W,
                    d, dp, S):
        """Sharded server path, called INSIDE the FSDP round's shard_map
        after the gradient: ``local`` is this device's dense transmit sum,
        ``p_sh``/dense state are [S] = [dp/W] slices. Returns
        ``(new_p_sh, new_momentum, new_error)``. Only classes with
        ``supports_fsdp`` implement it."""
        raise NotImplementedError

    # ---- telemetry (telemetry/diagnostics.py round hook) -----------------
    def diagnostics(self, level: int, *, agg, delta, momentum, error, extra,
                    new_error, lr) -> dict:
        """In-graph diagnostic scalars for one round, keyed WITHOUT the
        ``diag/`` prefix (``telemetry.round_diagnostics`` adds it). Runs
        under jit like every other hook; called by the round builders only
        at ``cfg.telemetry_level >= 1``, so level 0 traces nothing.

        ``agg`` is the psum-averaged aggregate in this mode's encoded
        domain (dense [D] for dense-transmit modes, the [r, c] table for
        sketch); ``momentum``/``error``/``extra`` are the PRE-update
        FedState leaves (what ``server_update`` consumed — ``fidelity``
        recomputes from them, XLA CSEs the overlap); ``new_error`` the
        post-extract bank; ``delta`` the applied update (always dense [D]
        in the replicated round). Subclasses override the ``_agg_sqnorm``/
        ``_error_sqnorm`` primitives (sketch: AMS table estimates) and
        ``fidelity`` (level >= 2), not this driver."""
        return self._norm_diagnostics(
            level, agg=agg, new_error=new_error,
            update_sqnorm=jnp.sum(jnp.square(delta)),
            fidelity_fn=lambda: self.fidelity(
                agg=agg, delta=delta, momentum=momentum, error=error,
                extra=extra, lr=lr,
            ),
        )

    def _norm_diagnostics(self, level, *, agg, new_error, update_sqnorm,
                          fidelity_fn) -> dict:
        """Shared scaffold of ``diagnostics``/``diagnostics_sparse`` —
        only how the update's squared norm and the fidelity scalars are
        obtained differs between the dense and sparse representations, so
        a new diag scalar lands in both decode paths by construction."""
        d = {
            "grad_norm": jnp.sqrt(self._agg_sqnorm(agg)),
            "update_norm": jnp.sqrt(update_sqnorm),
        }
        ef = self._error_sqnorm(new_error)
        if ef is not None:
            # single server bank: mean == max (local-error modes report
            # per-participant rows via round_diagnostics instead)
            d["ef_residual_norm"] = jnp.sqrt(ef)
            d["ef_residual_max"] = d["ef_residual_norm"]
        if level >= 2:
            d.update(fidelity_fn())
        return d

    def diagnostics_sparse(self, level: int, *, agg, idx, val, momentum,
                           error, extra, new_error, lr) -> dict:
        """``diagnostics`` for a round whose applied update exists only as
        the sharded decode's ``(idx, val)`` candidate buffers (val==0 on
        padding) — same scalar names and semantics, no dense [D] delta
        ever materialized: update_norm sums the candidate values directly
        (shards own disjoint coordinates, so the sum of squares is exact),
        and level-2 fidelity goes through ``fidelity_sparse``."""
        return self._norm_diagnostics(
            level, agg=agg, new_error=new_error,
            update_sqnorm=jnp.sum(jnp.square(val)),
            fidelity_fn=lambda: self.fidelity_sparse(idx=idx, val=val,
                                                     lr=lr),
        )

    def fidelity_sparse(self, *, idx, val, lr) -> dict:
        """Level-2 fidelity from the sparse ``(idx, val)`` update (sharded
        decode); base modes are exact — nothing to report."""
        return {}

    def _agg_sqnorm(self, agg):
        """Squared L2 norm of the decoded transmitted aggregate; the base
        aggregate is already dense."""
        return jnp.sum(jnp.square(agg))

    def _error_sqnorm(self, error):
        """Squared norm of the server error bank, or None when this mode
        keeps no server-side bank (() leaf / local error)."""
        if isinstance(error, tuple):
            return None
        return jnp.sum(jnp.square(error))

    def fidelity(self, *, agg, delta, momentum, error, extra, lr) -> dict:
        """Level-2 compression-fidelity scalars (how well the extracted
        update represents what it approximates); base modes are exact, so
        nothing to report."""
        return {}

    # ---- rung migration (control/ compression ladder) --------------------
    def migrate_state(self, new: "Compressor", momentum, error, extra):
        """Carry compressor-managed FedState leaves across a ladder-rung
        switch: ``self`` is the OLD rung's compressor, ``new`` the one the
        next round dispatches (same mode, different rung parameters —
        control/ladder.py restricts rungs to ``k``/``num_cols``/
        ``powersgd_rank``). Returns ``(momentum, error, extra)`` shaped
        for ``new``. Runs eagerly on the host round boundary (switches are
        rare; nothing here is traced into the round).

        Base implementation: identity — for every dense-state mode a
        ``k`` change alters only the EXTRACTION sparsity, and the [D]
        momentum/error banks (and absent () leaves) are
        rung-parameter-independent, so the switch is free. Modes whose
        state layout depends on a ladder field override (sketch re-sketches
        its tables across column geometries; powersgd pads/truncates its
        warm Q across ranks)."""
        return momentum, error, extra

    # ---- communication accounting (bytes_per_round) ----------------------
    def upload_floats(self) -> int:
        """Per-client uplink floats per round."""
        return self.d

    def upload_bytes_per_float(self) -> int:
        """Bytes per uplink float (4 for every f32-payload mode; sketch
        overrides to 2 when the tables — the psum payload — are stored
        bf16). The session's ``bytes_per_round`` and the CommLedger's
        live-byte accounting both multiply through this hook so the
        ledger-vs-HLO cross check (telemetry/xla_audit.py) stays exact."""
        return 4

    def download_floats(self) -> int:
        """Downlink floats per round (before any do_topk_down top-k)."""
        return self.d

    # ---- fedsim mask-aware accounting (telemetry/ledger.py) --------------
    def masked_upload_floats(self, live_clients: int) -> int:
        """Fleet uplink floats for a round in which only ``live_clients``
        participated (fedsim masked aggregation): every registered mode's
        per-client payload is participation-independent, so the fleet
        uplink is LINEAR in the live count. The CommLedger's live-byte
        exactness invariant (cum bytes == sum of live_i x upload_bytes)
        leans on this hook rather than assuming linearity — a future mode
        whose payload depends on the cohort overrides it here. (There is
        deliberately no downlink twin: the masked downlink is
        ``avail x bytes_per_round["download_bytes"]`` computed by the
        ledger itself, because the per-client download figure already
        carries the session-level do_topk_down adjustment that this class
        cannot see.)"""
        return int(live_clients) * self.upload_floats()
