"""The asyncfed round programs — one launch, one apply, per rung.

The synchronous round (parallel/round.py) is one fused XLA program:
per-client gradients -> compress -> psum -> server update. Buffered
asynchrony splits it at the only seam the algebra allows — AFTER each
client's transmit is computed, BEFORE anything is summed:

* ``launch_fn`` runs one cohort's per-client half against the params
  snapshot at launch: the [W, D] raw transmit rows (pre-encode, pre-sum),
  the updated per-client momentum/error rows, and the per-client
  loss/aux. It reuses ``make_per_client`` — the exact closure the
  synchronous worker shard vmaps — so a launched row is bit-identical to
  the row the synchronous round would have produced from the same params.

* ``apply_fn`` consumes K rows (padded to a fixed [W, ...] so any buffer
  fill / concurrency compiles ONE program — zero retraces), weights each
  by its staleness discount ``(1+s)^(-alpha)`` times its fedsim live
  mask, sums, device-encodes (linear, so encode(sum w*row) ==
  sum w*encode(row) — the psum-safety contract every compressor already
  signs), and runs the shared aggregation tail + server phase
  (``make_aggregate_tail`` / ``server_phase``). ``server_phase`` sees
  ``count = sum(weights)``: the effective participation the update
  renormalizes by, exactly the fedsim live count when alpha=0.

Bit-identity anchor (K=W, C=1, staleness_exponent=0 == the synchronous
round, pinned across modes by tests/test_asyncfed.py): every weight is
the 0/1 live mask, ``row * 1.0`` is bitwise ``row`` (NaN included),
``jnp.where(w > 0, ., 0.0)`` reproduces the synchronous dead-slot zeros,
the canonical (cohort, slot) consumption order makes the sum's reduction
order the synchronous one, ``fold_in(key, version)`` equals
``fold_in(key, state.step)``, and ``count == live_count`` exactly (small
ints in f32) — so agg, the server algebra, and the params update match
bit for bit.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.compress import get_compressor
from commefficient_tpu.ops.countsketch import CountSketch
from commefficient_tpu.parallel.mesh import (
    worker_axes,
    worker_axis_size,
)
from commefficient_tpu.parallel.round import (
    PER_CLIENT_VECTOR,
    FedState,
    make_aggregate_tail,
    make_decode_mapped,
    make_grad_one,
    make_per_client,
    resolve_aggregation,
    resolve_client_path,
    server_phase,
)
from commefficient_tpu.utils.config import Config

P = jax.sharding.PartitionSpec


def build_async_round_fns(
    cfg: Config,
    loss_fn: Callable,
    unravel: Callable,
    mesh,
    spec: Optional[CountSketch] = None,
    *,
    d: int,
    launch_hook: Optional[Callable] = None,
    apply_hook: Optional[Callable] = None,
):
    """Build ``(launch_fn, apply_fn)`` for one rung config.

    ``launch_fn(params_vec, client_vel, client_err, client_ids [W],
    batch {k: [W, ...]}, version, lr, env=(live, corrupt)) ->
    (rows [W, D], vel_rows, err_rows, loss_rows [W], aux_rows)`` — jitted,
    donates nothing (params/client state stay live for the next launch).

    ``apply_fn(state, rows, vel_rows, err_rows, loss_rows, aux_rows,
    client_ids [W], weights [W], wsum, lr) -> (new_state, metrics)`` —
    jitted, donates ``state``. ``weights`` are the per-slot staleness
    discounts times the live mask (0 for padding slots); the where-gate
    keeps a zero-weight slot's NaN (corrupt payload, or a padded repeat
    of one) out of the sum. Client vel/err rows write back per slot in
    canonical (cohort, slot) order — deterministic last-wins when two
    consumed contributions carry the same client.

    ``launch_hook``/``apply_hook``: RetraceSentinel trace hooks (pure
    python at trace time, zero traced ops).
    """
    comp = get_compressor(cfg, d=d, spec=spec)
    comp.resolved_dampening()
    # the launch program's product IS each client's own [D] row (the buffer
    # weights and consumes them one by one), so a config whose rounds would
    # sum the clients leaf by leaf has no launch program
    if resolve_client_path(cfg, comp) != PER_CLIENT_VECTOR:
        raise ValueError(
            "the asyncfed programs buffer per-client rows: build them from "
            "a config with async_buffer > 0 (resolve_client_path says "
            "leafwise for this one)"
        )
    W = cfg.num_workers
    f32 = jnp.float32
    lm = cfg.local_momentum
    use_fedsim = bool(cfg.fedsim_enabled)
    grad_one = make_grad_one(cfg, loss_fn, unravel)
    # multihost meshes: every collective and shard spec below rides the
    # (HOSTS, WORKERS) tuple, same resolution as the synchronous round
    axes = worker_axes(mesh)
    Wd = worker_axis_size(mesh)
    plan = resolve_aggregation(cfg, comp, Wd)
    per_client = make_per_client(cfg, comp, grad_one, use_fedsim=use_fedsim)
    aggregate_tail = make_aggregate_tail(cfg, comp, plan, W=W, Wd=Wd, d=d,
                                         axes=axes)
    decode_mapped = make_decode_mapped(cfg, comp, mesh, plan, d=d, Wd=Wd)

    # ---- launch: the per-client half of worker_shard ---------------------
    def launch_shard(params_vec, batch, client_ids, vel_rows, err_rows, rng,
                     lr, *fs):
        # same vma discipline as the synchronous worker shard: varying
        # params keep AD shard-local so each client sees its own gradient
        params_vec = jax.lax.pcast(params_vec, axes, to="varying")
        return jax.vmap(
            lambda b, cid, vel, err, *fs_: per_client(
                params_vec, b, cid, vel, err, rng, lr, *fs_
            )
        )(batch, client_ids, vel_rows, err_rows, *fs)

    shard_spec = P(axes)
    in_specs = (P(), shard_spec, shard_spec, shard_spec, shard_spec, P(), P())
    if use_fedsim:
        in_specs = in_specs + (shard_spec, shard_spec)  # live mask, corrupt
    launch_mapped = jax.shard_map(
        launch_shard,
        mesh=mesh,
        in_specs=in_specs,
        # raw per-client rows leave sharded: the apply consumes them row-
        # wise, nothing is reduced at launch time
        out_specs=(shard_spec,) * 5,
    )

    def launch_fn(params_vec, client_vel, client_err, client_ids, batch,
                  version, lr, env=()):
        if launch_hook is not None:  # trace time only, no ops
            launch_hook(params_vec, client_ids, batch, version, lr, env=env)
        # rng from the LAUNCH version: at the anchor version == state.step,
        # so fold_in reproduces the synchronous round's stream exactly
        rng = jax.random.fold_in(jax.random.key(cfg.seed), version)
        fs = ()
        if use_fedsim:
            if not env:
                raise ValueError(
                    "fedsim is enabled (cfg.fedsim_enabled) but no env was "
                    "passed — supply env=(live_mask [W], corrupt [W]) from "
                    "the cohort's FedEnvironment.round_env realization "
                    "(asyncfed.AsyncFederation does this)"
                )
            fs = tuple(env)
        # same participant-row gather as the synchronous round_fn
        vel_rows = (
            client_vel[client_ids] if lm > 0 else jnp.zeros((W, 1), f32)
        )
        err_rows = (
            client_err[client_ids]
            if cfg.error_type == "local"
            else jnp.zeros((W, 1), f32)
        )
        return launch_mapped(
            params_vec, batch, client_ids, vel_rows, err_rows, rng, lr, *fs
        )

    # ---- apply: weighted buffer drain + the shared server tail -----------
    def apply_shard(rows, loss_rows, aux_rows, weights):
        w_loc = rows.shape[0]
        wcol = weights[:, None]
        # where, not multiply: a zero-weight slot (dead client, or the
        # fixed-shape padding repeating a consumed slot) contributes
        # EXACTLY 0.0 even when its row is NaN — the same gate the
        # synchronous masked round applies pre-sum. A live slot's
        # row * 1.0 is bitwise the row (alpha=0 anchor).
        contrib = jnp.where(wcol > 0, rows * wcol, 0.0)
        local = jnp.sum(contrib, axis=0)
        loss_local = jnp.sum(jnp.where(weights > 0, loss_rows * weights, 0.0))
        ext = lambda m, a: m.reshape(m.shape + (1,) * (a.ndim - 1))  # noqa: E731
        aux = jax.tree.map(
            lambda a: jnp.sum(
                jnp.where(ext(weights, a) > 0, a * ext(weights, a), 0.0),
                axis=0,
            ),
            aux_rows,
        )
        # encode the weighted sum once per device (linearity: equals the
        # sum of weighted encodings; identical to the synchronous shard's
        # encode-of-sum at the anchor)
        local = comp.device_encode(local)
        return aggregate_tail(local, loss_local, aux, w_loc)

    apply_mapped = jax.shard_map(
        apply_shard,
        mesh=mesh,
        in_specs=(shard_spec, shard_spec, shard_spec, shard_spec),
        out_specs=(shard_spec if plan.sparse_state else P(), P(), P()),
    )

    def apply_fn(state: FedState, rows, vel_rows, err_rows, loss_rows,
                 aux_rows, client_ids, weights, wsum, lr):
        if apply_hook is not None:  # trace time only, no ops
            apply_hook(client_ids, weights, wsum, lr)
        agg, loss, aux = apply_mapped(rows, loss_rows, aux_rows, weights)
        new_params, new_m, new_e, new_comp, metrics = server_phase(
            cfg, comp, plan, decode_mapped, state, agg, loss, aux, lr,
            count=wsum, client_err_rows=err_rows,
        )
        # per-slot writeback in canonical (cohort, slot) order: slot i's
        # row lands iff its weight is live; the unrolled loop makes a
        # duplicate client id a deterministic last-wins (the synchronous
        # batched scatter is elementwise identical for distinct ids)
        client_vel = state.client_vel
        client_err = state.client_err
        if lm > 0:
            for i in range(W):
                client_vel = client_vel.at[client_ids[i]].set(
                    jnp.where(weights[i] > 0, vel_rows[i],
                              client_vel[client_ids[i]])
                )
        if cfg.error_type == "local":
            for i in range(W):
                client_err = client_err.at[client_ids[i]].set(
                    jnp.where(weights[i] > 0, err_rows[i],
                              client_err[client_ids[i]])
                )
        return (
            FedState(new_params, new_m, new_e, client_vel, client_err,
                     state.step + 1, new_comp),
            metrics,
        )

    return jax.jit(launch_fn), jax.jit(apply_fn, donate_argnums=(0,))
