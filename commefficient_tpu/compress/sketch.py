"""``sketch`` — FetchSGD: CountSketch compression with sketched server state.

The canonical linear compressor: each device sketches its summed transmit
ONCE (``device_encode``), the psum of [r, c] tables IS the sketch of the
global sum (linearity), and the server's momentum/error feedback run
entirely in sketch space (FetchSGD Algorithm 1, arXiv:2007.07682) before a
top-k unsketch extracts the applied update.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from commefficient_tpu.compress.base import KIND_NONE, KIND_TABLE, Compressor
from commefficient_tpu.compress.registry import register
from commefficient_tpu.ops.collectives import all_gather_pairs
from commefficient_tpu.ops.countsketch import (
    estimate_at,
    sketch_sparse,
    sketch_vec,
    table_sqnorm_estimate,
)
from commefficient_tpu.ops.topk import (
    compact_nonzero,
    compact_nonzero_tree,
    topk_threshold_sharded,
)


@register("sketch")
class SketchCompressor(Compressor):
    allowed_error_types = ("none", "virtual")
    supports_fsdp = True
    needs_sketch_spec = True
    supports_fused_clients = True
    supports_sharded_decode = True  # server_update_sharded below
    supports_fused_backward = True  # encode_grad_table below
    # aggregate='sparse': the [r, c] table psum stays (it is already
    # O(r*c) << O(D)), but the zero-HH EF re-sketch psums ride the
    # sparse-allreduce pair exchange instead — gather the <= Wd*k
    # (idx, val) pairs and re-sketch them locally (linearity: the sketch
    # of all pairs IS the sum of the per-shard slice sketches). Changes
    # the f32 summation order, so 'auto' never picks it (explicit only).
    supports_sparse_aggregate = True
    dense_delta = False  # the unsketched delta already has <= k nonzeros

    # ---- bf16 table discipline ------------------------------------------
    # Tables may be STORED (and psummed) in spec.table_dtype (bf16 halves
    # HBM + collective bytes at GPT-2 scale); every piece of server
    # ALGEBRA upcasts to f32 first and downcasts only what is stored back
    # — "bf16 tables, f32 accumulation". Both casts are no-ops for the
    # f32 default (convert_element_type to the same dtype folds away), so
    # the golden parity recordings are bit-untouched.
    def _up(self, table):
        return table if isinstance(table, tuple) else table.astype(jnp.float32)

    def _down(self, table):
        if isinstance(table, tuple):
            return table
        return table.astype(self.spec.table_dtype)

    @property
    def _spec_acc(self):
        """The spec with f32 storage: interior re-sketches (zero-HH error
        feedback, dampening) accumulate at f32, so only STORED state and
        psum payloads pay the bf16 rounding. Identical to ``spec`` for
        the f32 default (NamedTuple value equality keeps every lru-cached
        geometry hit)."""
        return self.spec._replace(table_dtype=jnp.float32)

    @property
    def _ride_pair_exchange(self) -> bool:
        """True when the zero-HH EF re-sketch psums ride the sparse
        pair exchange (explicit aggregate='sparse' only; Config already
        validated threshold + sharded decode). The FSDP round never rides
        — Config rejects aggregate='sparse' under fsdp."""
        return getattr(self.cfg, "aggregate", "auto") == "sparse"

    def _dampening_warnings(self, dampen: bool) -> None:
        if dampen:
            import warnings

            warnings.warn(
                "momentum_dampening in sketch mode subtracts the sketch of "
                "ESTIMATED momentum values; the estimate noise injected "
                "into the momentum sketch every round measurably "
                "destabilizes training at paper-scale settings (diverges "
                "~step 70 where the unmasked run converges). FetchSGD's "
                "Algorithm 1 does not mask sketched momentum — prefer "
                "momentum_dampening=False here (dense modes mask exactly "
                "and are unaffected)."
            )

    def validate_fsdp(self) -> None:
        if self.cfg.momentum_dampening:
            raise NotImplementedError(
                "sketch momentum dampening is gated as unstable in the "
                "replicated round already; not offered under fsdp"
            )

    def server_state_kinds(self):
        cfg = self.cfg
        return (
            KIND_TABLE if cfg.virtual_momentum > 0 else KIND_NONE,
            KIND_TABLE if cfg.error_type == "virtual" else KIND_NONE,
        )

    def device_encode(self, local_sum):
        # one sketch per device; the psum over tables is exact by linearity
        # (to bf16 rounding when table_dtype is bfloat16 — sketch_vec
        # accumulates f32 and downcasts the final table, so the psum
        # payload is half the bytes; see the class bf16 discipline note)
        return sketch_vec(self.spec, local_sum)

    def encode_grad_table(self, table):
        """``device_encode`` twin for the sketch-fused backward: the
        worker's summed transmit arrives ALREADY as a sketch table (the
        per-leaf custom_vjp taps accumulated their segment sketches in
        f32 — ops.countsketch.sketch_grad_tap); only the psum payload
        cast remains."""
        return self._down(table)

    def server_update(self, momentum, error, extra, agg, lr, step):
        cfg, spec = self.cfg, self.spec
        dampen = self.resolved_dampening()
        rho = cfg.virtual_momentum
        agg, momentum, error = map(self._up, (agg, momentum, error))
        m = rho * momentum + agg if rho > 0 else agg
        hh = None  # the update's <= k (idx, val) pairs, compacted once
        if cfg.error_type == "virtual":
            e = error + lr * m
            update = self.unsketch(spec, e, cfg.k)  # dense, <= k nonzeros
            # zero HH (linearity) at k scale: the <= k pairs the selection
            # kept, scatter-added into a fresh f32 table (_spec_acc: the
            # EF bank's algebra never pays a bf16 round-trip mid-round),
            # not one more [D] sketch_vec pass over a vector that is zero
            # everywhere else (ef_resketch: telemetry.trace.ROUND_SCOPES)
            with jax.named_scope("ef_resketch"):
                hh = compact_nonzero_tree(update, cfg.k)
                e = e - sketch_sparse(self._spec_acc, *hh)
            if cfg.error_decay != 1.0:
                e = cfg.error_decay * e  # d/c-envelope mitigation
            delta = update
        else:
            e = error
            update = self.unsketch(spec, m, cfg.k)
            delta = lr * update
        if dampen and rho > 0:
            # zero the momentum sketch at HH coords (fed_aggregator
            # ~L380-440): estimate m at the update's <= k-coordinate
            # support (the error feedback's pairs, compacted once) and
            # subtract the sketch of those point values — the gather
            # estimate is bit-equal to the matmul path on CPU and
            # sketch_sparse is the same hash mapping; pinned by
            # tests/test_sketch_decode.py's dampening regression.
            with jax.named_scope("ef_resketch"):
                hh_idx, hh_val = hh or compact_nonzero_tree(update, cfg.k)
                m_at_hh = jnp.where(hh_val != 0,
                                    estimate_at(spec, m, hh_idx), 0.0)
                m = m - sketch_sparse(spec, hh_idx, m_at_hh)
        new_m = m if rho > 0 else momentum
        return delta, self._down(new_m), self._down(e), extra

    def server_update_sharded(self, momentum, error, extra, agg, lr, step,
                              *, axis_name, Wd, d):
        """The FSDP decode discipline applied to the REPLICATED round
        (runs inside a shard_map over ``axis_name``, every input
        replicated): the sketch tables stay replicated — only the
        EXTRACTION is sharded. Each chip estimates its ceil(d/Wd)
        coordinate slice via ``estimate_at`` over offset global hashes,
        the global top-<=k threshold comes from ``topk_threshold_sharded``
        (one scalar pmax + one scalar psum per bisection iteration), each
        shard compacts its selected entries into a fixed [kb] candidate
        buffer, and ONE all_gather of those ~Wd*kb (idx, val) pairs (<< D
        floats) replaces the per-chip full-D decode. Zero-HH error
        feedback reuses the proven linearity trick: the psum of per-shard
        ``sketch_sparse`` slice sketches IS the sketch of the full
        extracted update. No [D] estimate, no [D] unsketch transient, no
        dense re-sketch — per-chip decode FLOPs drop ~Wd x."""
        cfg, spec = self.cfg, self.spec
        dampen = self.resolved_dampening()
        rho = cfg.virtual_momentum
        S = -(-d // Wd)
        my, idx_c, in_range = self._slice_coords(axis_name, S, d)
        agg, momentum, error = map(self._up, (agg, momentum, error))
        m = rho * momentum + agg if rho > 0 else agg
        sel, upd, e = self._slice_extract(m, error, lr, idx_c, in_range,
                                          axis_name)
        if dampen and rho > 0:
            # sharded twin of the dense branch's sparse dampening: each
            # shard estimates m at ITS selected coords (compacted to the
            # <= k support first — estimating the whole slice to read k
            # entries is the waste the dense-branch satellite removed)
            # and the psum of slice sketches is the sketch of the full
            # masked-momentum vector (same linearity as the error
            # feedback). The mask is the UNSCALED selection support, like
            # the dense branch's `update != 0` — `sel != 0` would differ
            # at lr == 0.
            loc_d, upd_val = compact_nonzero(upd, cfg.k)
            with jax.named_scope("ef_resketch"):
                hh_gidx = jnp.minimum(my * S + loc_d, d - 1)
                m_at_hh = jnp.where(
                    upd_val != 0,
                    estimate_at(spec, m, hh_gidx), 0.0,
                )
                if self._ride_pair_exchange:
                    g_i, g_v = all_gather_pairs(
                        hh_gidx, m_at_hh, axis_name,
                        segments=self.overlap_segments)
                    m = m - sketch_sparse(spec, g_i, g_v).astype(
                        spec.table_dtype)
                else:
                    m = m - jax.lax.psum(
                        sketch_sparse(spec, hh_gidx,
                                      m_at_hh).astype(spec.table_dtype),
                        axis_name,
                    )
        new_m = m if rho > 0 else momentum
        # compact this shard's <= k selected entries into a fixed-size
        # candidate buffer and exchange ~Wd*kb pairs — the ONLY vector
        # collective in the decode, and it is k-scale, not D-scale
        loc, val = compact_nonzero(sel, cfg.k)
        gidx = jnp.minimum(my * S + loc, d - 1)  # padding rows clip
        # in-range; their val is 0.0, so the apply scatter ignores them
        g_idx, g_val = all_gather_pairs(gidx, val, axis_name,
                                        segments=self.overlap_segments)
        return g_idx, g_val, self._down(new_m), self._down(e), extra

    @staticmethod
    def _slice_coords(axis_name, S, d):
        """This shard's offset-slice geometry, shared by both sharded
        decodes so the layout convention cannot drift: ``(my, idx_c,
        in_range)`` — the shard index, the clipped global coordinate
        slice ``my*S .. my*S+S-1``, and the float mask of coordinates
        actually inside [0, d)."""
        my = jax.lax.axis_index(axis_name)
        idx = my * S + jnp.arange(S, dtype=jnp.int32)
        return my, jnp.minimum(idx, d - 1), (idx < d).astype(jnp.float32)

    def _slice_extract(self, m, error, lr, idx_c, in_range, axis_name):
        """Shard-local extraction shared by BOTH sharded decodes (the
        replicated engine's ``server_update_sharded`` and the FSDP round's
        ``fsdp_update``), so the algebra cannot drift between them:
        estimate this shard's coordinate slice, select the global top-<=k
        (``topk_threshold_sharded``: scalar-only collectives), and run the
        zero-HH error feedback — the psum of per-shard ``sketch_sparse``
        slice sketches IS the sketch of the full extracted update
        (linearity). Returns ``(sel, upd, new_error)``: ``sel`` the
        lr-resolved APPLIED slice (virtual error banks lr-scaled updates,
        so sel==upd there; no-error applies lr at extraction), ``upd`` the
        unscaled selection whose support drives momentum dampening."""
        cfg, spec = self.cfg, self.spec
        if cfg.error_type == "virtual":
            e = error + lr * m
            est = estimate_at(spec, e, idx_c) * in_range
            upd = topk_threshold_sharded(est, cfg.k, axis_name)
            # zero-HH feedback at k-scale: compact the <= k selected
            # entries before the slice sketch — scatter is the TPU slow
            # path, and a scatter over the whole D/W slice to add <= k
            # nonzeros (the rest exact-zero no-ops) is the same waste the
            # dampening satellite removed. Same table values; the psum of
            # the <= k-pair slice sketches is still the sketch of the
            # full extracted update (linearity).
            loc, val = compact_nonzero(upd, cfg.k)
            # the psum payload carries the STORAGE dtype (halved collective
            # bytes under bf16 tables — and what keeps the xla_audit
            # ledger-vs-HLO tolerance arithmetic exact); the subtraction
            # promotes back to e's f32
            with jax.named_scope("ef_resketch"):
                if self._ride_pair_exchange:
                    # aggregate='sparse': the table psum becomes a <= Wd*k
                    # pair all_gather + ONE local re-sketch of all pairs
                    # (linearity — same table up to f32 summation order)
                    g_i, g_v = all_gather_pairs(
                        idx_c[loc], val, axis_name,
                        segments=self.overlap_segments)
                    e = e - sketch_sparse(spec, g_i, g_v).astype(
                        spec.table_dtype)
                else:
                    e = e - jax.lax.psum(
                        sketch_sparse(spec, idx_c[loc],
                                      val).astype(spec.table_dtype),
                        axis_name,
                    )
            if cfg.error_decay != 1.0:
                e = cfg.error_decay * e
            return upd, upd, e
        est = estimate_at(spec, m, idx_c) * in_range
        upd = topk_threshold_sharded(est, cfg.k, axis_name)
        return lr * upd, upd, error

    def fsdp_update(self, p_sh, m_in, e_in, local, lr, *, axis_name, W,
                    d, dp, S):
        cfg, spec = self.cfg, self.spec
        rho = cfg.virtual_momentum
        table = sketch_vec(spec, local)  # storage dtype — the psum payload
        agg = self._up(jax.lax.psum(table, axis_name)) / W
        # each chip estimates only its own D/W coordinate range via
        # offset-indexed global hashes; the shared ``_slice_coords`` /
        # ``_slice_extract`` helpers (also the replicated engine's
        # sharded decode) own the slice geometry + scalar-collective
        # threshold + zero-HH error feedback
        _, idx_c, in_range = self._slice_coords(axis_name, S, d)
        m_in, e_in = self._up(m_in), self._up(e_in)
        m = rho * m_in + agg if rho > 0 else agg
        delta_sh, _, e = self._slice_extract(m, e_in, lr, idx_c, in_range,
                                             axis_name)
        new_m = m if rho > 0 else m_in
        return p_sh - delta_sh, self._down(new_m), self._down(e)

    # ---- telemetry -------------------------------------------------------
    # the dense aggregate never exists in sketch mode (device_encode runs
    # before the psum), so norm diagnostics use the AMS/CountSketch F2
    # estimator on the tables (ops.countsketch.table_sqnorm_estimate) —
    # free (no unsketch, no [D] transient), unbiased per row.
    def _agg_sqnorm(self, agg):
        return table_sqnorm_estimate(agg)

    def _error_sqnorm(self, error):
        if isinstance(error, tuple):
            return None
        return table_sqnorm_estimate(error)

    def fidelity(self, *, agg, delta, momentum, error, extra, lr) -> dict:
        """Round-trip estimation relative error at the extracted update's
        own support: sketch ``delta`` into a fresh table, re-estimate it at
        its nonzero coordinates, and report ``||est - delta|| / ||delta||``
        over that support. This measures the table's collision noise at the
        current k/c occupancy — the quantity the sketched-SGD analysis
        (arXiv:1903.04488) bounds; at small d/c it tracks the estimation
        error against the exact top-k the unsketch approximates (a huge
        table drives it to ~0 — pinned by tests/test_telemetry.py).

        Sparse-aware since the decode PR: the delta has <= k nonzeros, so
        the fresh table comes from ``sketch_sparse`` at its compacted
        support and the re-estimate from ``estimate_at`` there — same
        values (same hash mapping; gather == matmul path on CPU), but
        level 2 no longer adds a full-[D] sketch + estimate matmul pass
        per round (one cumsum over delta to find the support, then
        k-scale work)."""
        idx, val = compact_nonzero(delta, self.cfg.k)
        return self._fidelity_at(idx, val)

    def fidelity_sparse(self, *, idx, val, lr) -> dict:
        """Sharded-decode twin of ``fidelity``: the update already exists
        as (idx, val) candidate buffers (val==0 padding) — no compaction,
        no dense delta."""
        return self._fidelity_at(idx, val)

    def _fidelity_at(self, idx, val) -> dict:
        spec = self.spec
        live = val != 0
        rt = estimate_at(spec, sketch_sparse(spec, idx, val), idx)
        num = jnp.sqrt(jnp.sum(jnp.square(jnp.where(live, rt - val, 0.0))))
        den = jnp.sqrt(jnp.sum(jnp.square(val)))
        return {"sketch_est_rel_err": num / jnp.maximum(den, 1e-30)}

    # ---- rung migration (control/ compression ladder) --------------------
    def migrate_state(self, new, momentum, error, extra):
        """Sketch-mode rung migration. ``k``-only switches are FREE: the
        tables are a function of the spec geometry, not of k (k only
        selects how many heavy hitters the unsketch extracts), so identical
        specs pass through untouched. A ``num_cols`` switch changes the
        table layout, and a table sketched under one layout is
        meaningless under another — so each [r, c_old] bank is decoded to
        its top-k heavy-hitter support and RE-SKETCHED into the new
        layout: ``new_table = S_new(U_old(table, k))``. By linearity of
        both maps this carries exactly the decodable signal mass; the
        sub-threshold residual the old table still held is dropped (the
        same kind of controlled leak as ``error_decay``), which is the
        honest trade — there is no lossless map between CountSketch
        geometries. The decode uses this rung's top-k kernel at
        ``cfg.k`` (the old rung's own extraction semantics)."""
        if new.spec is not None and self.spec is not None and (
                new.spec.table_shape == self.spec.table_shape
                and new.spec.c == self.spec.c
                and new.spec.num_blocks == self.spec.num_blocks):
            return momentum, error, extra

        def move(table):
            if isinstance(table, tuple):
                return table
            dense = self.unsketch(self.spec, table, self.cfg.k)
            idx, val = compact_nonzero(dense, self.cfg.k)
            return sketch_sparse(new.spec, idx, val).astype(
                new.spec.table_dtype
            )

        return move(momentum), move(error), extra

    def upload_floats(self) -> int:
        """The REALIZED table size ``r * c_actual`` (the blocked layout
        rounds the requested num_cols to bucket-block multiples), not the
        request (ADVICE r1: the request can silently understate the
        payload)."""
        r, c_actual = self.spec.table_shape
        up = r * c_actual
        requested = self.cfg.num_rows * self.cfg.num_cols
        # (bytes follow upload_bytes_per_float below: 2 under bf16 tables)
        if up > 1.25 * requested:
            import warnings

            warnings.warn(
                f"realized sketch table ({up} floats) exceeds the "
                f"requested num_rows*num_cols ({requested}) by >25%: "
                "the blocked layout's per-chunk bucket floor inflated "
                "it — raise num_cols or chunk size m.",
                stacklevel=2,
            )
        return up

    def upload_bytes_per_float(self) -> int:
        """2 when the tables — the psum payload — are stored bfloat16
        (the collective-bytes half of the bf16-table win), else 4."""
        return jnp.dtype(self.spec.table_dtype).itemsize
