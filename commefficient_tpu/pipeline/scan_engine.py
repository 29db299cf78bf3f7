"""ScanRounds — K rounds per XLA dispatch via ``lax.scan`` over the
device-resident index round.

The per-round dispatch path pays host serial time per round even when the
inputs are staged: python argument marshaling, the jit call boundary, the
runtime enqueue (not measured on today's code).
This engine executes blocks of up to ``cfg.scan_rounds`` rounds as ONE
jitted program whose body is the SAME unjitted index-round closure the
per-round path wraps (``FederatedSession.raw_round_idx_fn`` — one round
trace shared by construction):

  * **Sampler indices staged per epoch.** At epoch entry the epoch's
    ``[spe, W, B]`` sampler draws, client ids, augmentation plans, lrs
    and fedsim envs are realized host-side in one pass (each a pure
    function of the round index — the prefetcher's determinism contract)
    and committed to the mesh with ONE ``device_put`` per array, not one
    per round.
  * **Telemetry packs stacked.** The scan's ys stack each round's metric
    dict into ``[L]`` device arrays; the engine yields per-round views
    of those stacks, so the runner's deferred-drain discipline is
    untouched — packs drain at the same points (epoch end,
    pre-checkpoint), and the drained scalar SEQUENCE is pinned equal to
    per-round dispatch (tests/test_scan_engine.py).
  * **Blocks chop at every state-observation boundary.** The runner acts
    on ``session.state`` only at checkpoint saves (``will_save``), vault
    snapshots (``will_snapshot``) and epoch ends; a scanned block's
    intermediate states exist only on-device, so blocks END exactly at
    those boundaries (``checkpoint_every`` / ``snapshot_every``
    multiples, epoch end) — the state the runner sees at such a step is
    bit-identical to the synchronous loop's. Anything that must act
    host-side between two ARBITRARY rounds (the control plane's
    pre-dispatch decision, round-granular preemption) is refused at
    Config validation instead of silently misbehaving.
  * **Deferred-drain / resilience composition.** A ``DivergenceError``
    still fires at the drain; a rollback restores the vault snapshot
    wholesale and the runner re-enters ``epoch_rounds`` at the rollback
    step — the engine is stateless across blocks (``restart`` is just a
    staging-cache drop), and its first block after re-entry starts at
    the rollback round with freshly realized (replay-aware) envs.

Distinct block lengths compile once each (at most a handful per run: K,
the pre-boundary remainders, the epoch tail); every length gets its own
RetraceSentinel signature stream (``round_scan_fn[xL]``), so a length's
first trace is an expected compile and any later drift on it is a
counted retrace — the prewarm discipline at scan granularity.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.telemetry.spans import span_of


class ScanRounds:
    """One per train loop when ``cfg.scan_rounds > 1`` (train/runner.py).

    API-compatible with ``PipelinedRounds`` where the runner touches it:
    ``start(resume_step)``, ``epoch_rounds(epoch, start_step)``,
    ``restart(step)``, ``close()``, ``stats()``.
    """

    def __init__(self, cfg, session, sampler, lr_fn, num_rounds: int,
                 steps_per_epoch: Optional[int] = None, spans=None,
                 profiler=None):
        if cfg.scan_rounds <= 1:
            raise ValueError(
                "ScanRounds needs cfg.scan_rounds > 1 (0/1 = the per-round "
                "dispatch path — build nothing)"
            )
        if getattr(session, "_dev_data", None) is None:
            raise ValueError(
                "scan_rounds > 1 needs device-resident data (the index "
                "round): the session attached none — the dataset exceeded "
                "device_data_max_mb, the sampler is not fusable, or the "
                "mode forced host batches. Drop scan_rounds or fix the "
                "device-data gate (FederatedSession.maybe_attach_data)."
            )
        if session.controller is not None:
            raise ValueError(
                "scan_rounds > 1 with a controller should have been "
                "refused at Config validation (per-round pre-dispatch "
                "decisions cannot run inside a scanned block)"
            )
        self.cfg = cfg
        self.session = session
        self.spans = spans
        self.profiler = profiler
        self.K = int(cfg.scan_rounds)
        self.num_rounds = int(num_rounds)
        self.steps_per_epoch = int(
            steps_per_epoch if steps_per_epoch is not None
            else sampler.steps_per_epoch()
        )
        self._sampler = sampler
        self._lr_fn = lr_fn
        # ONE raw round closure shared by every block length: rebuilding
        # it per L would re-run the compressor construction (duplicate
        # dampening/geometry warnings) and only guarantee equivalent —
        # not identical — closures across lengths
        self._raw_round = session.raw_round_idx_fn()
        self._scan_fns: dict = {}  # block length L -> jitted scan program
        # aggregate stats (bench's scan leg / the runner's info line)
        self._rounds = 0
        self._dispatches = 0

    # -- lifecycle (PipelinedRounds API parity) ----------------------------
    def start(self, resume_step: int = 0) -> "ScanRounds":
        del resume_step  # stateless across blocks; staging is per-epoch
        return self

    def restart(self, step: int) -> None:
        """Resilience recovery fence: nothing is staged across
        ``epoch_rounds`` calls, so a rollback needs no quiesce — the
        runner's re-entry at the rollback step restages that epoch's
        remainder with replay-aware envs (the session's horizon)."""
        if self.spans is not None:
            with self.spans.span(f"scan_recovery_restart:round{step}",
                                 step=int(step)):
                pass

    def close(self) -> None:
        """No worker thread to join — present for engine API parity."""

    # -- block plan --------------------------------------------------------
    def _boundaries(self):
        """Step multiples a block must not cross (the runner observes
        ``session.state`` there): checkpoint saves and vault snapshots.
        ``will_save``/``will_snapshot`` fire on ``step % every == 0`` with
        step = round + 1, so a gate at T means a block ends AT round T-1
        (covers rounds [..., T))."""
        gates = []
        if self.cfg.checkpoint_every > 0 and self.cfg.checkpoint_dir:
            gates.append(int(self.cfg.checkpoint_every))
        if self.cfg.recovery_enabled:
            gates.append(int(self.cfg.snapshot_every))
        return gates

    def _blocks(self, start: int, stop: int):
        """Chop [start, stop) into scan blocks of <= K rounds that end at
        every boundary gate (yields (block_start, block_len))."""
        gates = self._boundaries()
        s = start
        while s < stop:
            e = min(s + self.K, stop)
            for g in gates:
                # first multiple of g STRICTLY after s bounds the block:
                # the runner must see state at round (mult - 1)'s yield
                nxt = (s // g + 1) * g
                e = min(e, nxt)
            yield s, e - s
            s = e

    # -- per-epoch staging -------------------------------------------------
    def _stage_range(self, start: int, stop: int):
        """Realize rounds [start, stop)'s inputs host-side (sampler draws,
        plans, lrs, fedsim envs — each a pure function of the round
        index), then commit each STACKED array to the mesh once. Returns
        (staged dict, per-round host ``fedsim/*`` stats list)."""
        sess = self.session
        with self._span("scan_stage", start):
            cids, idxs, plans, lrs = [], [], [], []
            live, corrupt, cnt, stats = [], [], [], []
            fedsim = sess.fedsim_env is not None
            for r in range(start, stop):
                c, i, p = self._sampler.sample_round_indices(r)
                cids.append(c)
                idxs.append(i)
                plans.append(p)
                lrs.append(float(self._lr_fn(r)))
                if fedsim:
                    env = sess.fedsim_env.round_env(
                        r, replay=r < sess._replay_horizon
                    )
                    if sess._client_blacklist is not None:
                        env = sess._blacklist_env(env, c)
                    live.append(env.live)
                    corrupt.append(env.corrupt)
                    cnt.append(env.live_count)
                    stats.append(dict(env.stats))
                else:
                    stats.append({})
            # epoch stacks commit REPLICATED: the leading axis is the
            # ROUND, not a mesh axis (the per-round [W] sharding the
            # direct path uses would mis-shard dim 0 here); the scan body
            # slices each round's inputs and the round's own shard_map
            # partitions them — and the whole epoch's indices are KBs.
            put_r = lambda a: jax.device_put(  # noqa: E731
                jnp.asarray(a), sess._replicated
            )
            staged = {
                "cids": put_r(np.stack(cids).astype(np.int32)),
                "idx": put_r(np.stack(idxs).astype(np.int32)),
                # plans stack element-wise ([L] leading axis per plan
                # array); () when the augmenter ships no plan
                "plan": tuple(
                    put_r(np.stack([p[j] for p in plans]))
                    for j in range(len(plans[0]))
                ) if plans and plans[0] else (),
                "lr": put_r(np.asarray(lrs, np.float32)),
                "env": (
                    (put_r(np.stack(live).astype(np.float32)),
                     put_r(np.stack(corrupt).astype(np.float32)),
                     put_r(np.asarray(cnt, np.float32)))
                    if fedsim else ()
                ),
            }
        return staged, stats

    # -- the scanned program ----------------------------------------------
    def _scan_fn(self, L: int):
        """The jitted L-round block program (cached per distinct L). Body
        = the session's raw index-round closure; xs = the staged per-round
        inputs; ys = the stacked metric packs."""
        if L in self._scan_fns:
            return self._scan_fns[L]
        sess = self.session
        raw = self._raw_round
        fedsim = sess.fedsim_env is not None

        def scan_block(state, data, cids_L, idx_L, plan_L, lr_L, env_L):
            def body(st, xs):
                cids, idx, plan, lr, env = xs
                st2, metrics = raw(st, data, cids, idx, plan, lr,
                                   env=env if fedsim else ())
                return st2, metrics

            xs = (cids_L, idx_L, plan_L, lr_L, env_L)
            return jax.lax.scan(body, state, xs)

        fn = jax.jit(
            sess.retrace_sentinel.wrap(scan_block, f"round_scan_fn[x{L}]"),
            donate_argnums=(0,),
        )
        self._scan_fns[L] = fn
        return fn

    # -- the per-epoch round source (what the runner iterates) -------------
    def epoch_rounds(self, epoch: int, start_step: int):
        """Yield ``(step, lr, metrics)`` for epoch ``epoch``'s rounds at or
        past ``start_step`` — same triples, same order, same drain points
        as the synchronous loop; each block of <= K rounds is one device
        dispatch and each yielded metrics dict is a per-round view of the
        block's stacked telemetry pack."""
        sess = self.session
        spe = self.steps_per_epoch
        lo = max(epoch * spe, start_step)
        hi = min((epoch + 1) * spe, self.num_rounds)
        if lo >= hi:
            return
        staged, host_stats = self._stage_range(lo, hi)
        for bstart, blen in self._blocks(lo, hi):
            o = bstart - lo
            sl = lambda a: a[o:o + blen] if not isinstance(a, tuple) else (  # noqa: E731
                tuple(x[o:o + blen] for x in a)
            )
            if self.profiler is not None:
                self.profiler.step(bstart)
            if self.spans is not None:
                self.spans.step(bstart)
            with self._span("round_dispatch", bstart) as sp:
                sess.state, packs = self._scan_fn(blen)(
                    sess.state, sess._dev_data, sl(staged["cids"]),
                    sl(staged["idx"]), sl(staged["plan"]), sl(staged["lr"]),
                    sl(staged["env"]),
                )
                if sp is not None:
                    sp.fence(packs["loss"][-1])
            sess._round_clock += blen
            sess._replay_horizon = max(sess._replay_horizon,
                                       sess._round_clock)
            self._rounds += blen
            self._dispatches += 1
            for i in range(blen):
                s = bstart + i
                stats = sess._host_round_stats(host_stats[s - lo])
                metrics = {k: v[i] for k, v in packs.items()}
                if self.cfg.telemetry_level >= 1:
                    # constant key set across the run (pack_metric_dicts);
                    # rides the existing pipeline/ scalar namespace
                    metrics["pipeline/scan_rounds_per_dispatch"] = float(blen)
                yield s, float(self._lr_fn(s)), (
                    {**metrics, **stats} if stats else metrics
                )

    def _span(self, name: str, step: int):
        return span_of(self.spans, name, int(step))

    # -- aggregate stats (runner info line / bench) ------------------------
    def stats(self) -> dict:
        return {
            "rounds": self._rounds,
            "dispatches": self._dispatches,
            "rounds_per_dispatch": self._rounds / max(self._dispatches, 1),
            "block_lengths": sorted(self._scan_fns),
        }
