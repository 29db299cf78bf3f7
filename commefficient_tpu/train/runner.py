"""The shared train-loop runner — one epoch/drain/crash scaffold for every
workload entry.

``run_train_loop`` owns the epoch loop, the deferred-drain buffer and its
``live_drain`` crash-flush closure, the checkpoint ``will_save``-then-drain
ordering, ``DivergenceError`` surfacing, the telemetry-rider/controller/
perf-observability construction order and the resume fast-forward; each
entry supplies only its workload-specific pieces through ``WorkloadHooks``
(accumulation, eval, the console row, the optional per-epoch hook).

There is one host loop, ``_sync_epoch_rounds``: the sampler runs two
rounds ahead on a thread (``data/sampler.py::prefetch``), each round is
staged and dispatched in order, and XLA's asynchronous dispatch hides the
rest of the host. The one alternative schedule is the buffered-
asynchronous engine (``--async_buffer K``, asyncfed/), an object with
``start``/``epoch_rounds``/``restart``/``close`` built only when that flag
is set. Both yield the same ``(step, lr, metrics)`` triples to the same
drain/checkpoint/crash machinery.

The scaffold also hosts the resilience/ layer: a ``DivergenceError``
raised by any drain is offered to the ``ResilienceRider`` first — a
successful rollback restores the last drain-certified vault snapshot,
restarts the round source at the rollback round (the plain loop simply
re-enters at that step; the asyncfed engine rebuilds its in-flight
window) and re-enters the epoch loop; only an unrecoverable divergence
(policy 'none', recoveries exhausted, no snapshot) reaches the crash
path. A preemption request (SIGTERM/SIGINT rider or the seeded
``preempt@R`` chaos event) is honored at round granularity: drain,
``maybe_save(force=True)``, then ``PreemptShutdown`` — which rides the
normal crash teardown (flight dump, ledger write, spans close) out to the
entries' distinct ``EXIT_PREEMPTED`` code. ``--recover_policy none``
with no preemption source constructs NOTHING (README "Failure handling &
recovery").
"""

from __future__ import annotations

from functools import partial
from typing import Optional

from commefficient_tpu.data import prefetch
from commefficient_tpu.telemetry.spans import span_of, wrap_iter
from commefficient_tpu.utils import TableLogger, Timer, piecewise_linear_lr
from commefficient_tpu.utils.logging import drain_round_metrics


class WorkloadHooks:
    """What a workload entry plugs into the shared runner. Subclasses
    override everything except ``on_epoch_end`` (optional)."""

    def new_accumulator(self):
        """Fresh per-epoch accumulation state (any mutable object)."""
        raise NotImplementedError

    def accumulate(self, acc, loss, metrics) -> None:
        """Fold one drained round into ``acc`` (drain order == step
        order)."""
        raise NotImplementedError

    def evaluate(self) -> dict:
        """End-of-epoch validation metrics (also the final-eval fallback
        when a resume lands at/after the last round)."""
        raise NotImplementedError

    def epoch_row(self, *, epoch, lr, acc, val, train_time, val_time,
                  steps_per_epoch) -> dict:
        """The console TableLogger row for one epoch."""
        raise NotImplementedError

    def write_val(self, writer, val, step) -> None:
        """Write the epoch's val/* scalars."""
        raise NotImplementedError

    def on_epoch_end(self, epoch, val) -> None:
        """Optional per-epoch side effect (gpt2's sample generation)."""


def _sync_epoch_rounds(cfg, session, sampler, lr_fn, spans, profiler,
                       epoch, start_step, steps_per_epoch):
    """The host loop: take each round's draw from the sampler's
    look-ahead thread, stage it and dispatch it, in order. Yields
    ``(step, lr, metrics)``."""
    use_idx = getattr(session, "_dev_data", None) is not None
    rounds = (
        prefetch(sampler.epoch_indices(epoch))
        if use_idx
        else prefetch(sampler.epoch(epoch))
    )
    # each next() is the data-load/prefetch-wait phase: recorded by the
    # recorder where there is one, and either way a fed/data_load
    # annotation in whatever profiler trace is open
    rounds = (spans.wrap_iter(rounds, "data_load") if spans is not None
              else wrap_iter(rounds, "data_load"))
    for round_idx, item in enumerate(rounds):
        s = epoch * steps_per_epoch + round_idx
        if s < start_step:
            continue  # fast-forward within the resumed epoch
        lr = float(lr_fn(s))
        profiler.step(s)
        if spans is not None:
            spans.step(s)
        if use_idx:
            client_ids, idx, plan = item
            metrics = session.train_round_indices(client_ids, idx, plan, lr)
        else:
            client_ids, batch = item
            L = cfg.round_microbatches  # fedavg [W, L, B/L, ...]
            if L:
                batch = {
                    k: v.reshape(v.shape[0], L, v.shape[1] // L,
                                 *v.shape[2:])
                    for k, v in batch.items()
                }
            metrics = session.train_round(client_ids, batch, lr)
        yield s, lr, metrics


def run_train_loop(cfg, session, sampler, hooks: WorkloadHooks,
                   writer=None, table: Optional[TableLogger] = None,
                   checkpointer=None, generated_by: str = "train"):
    """The epoch loop shared by both entries. Returns final val metrics.

    With ``checkpointer`` (utils.checkpoint.FedCheckpointer) the loop
    honors ``cfg.checkpoint_every``/``cfg.resume``: a resumed run
    fast-forwards to the checkpointed round (sampler, lr schedule and the
    fedsim environment are pure functions of the step, so this reproduces
    the uninterrupted run exactly)."""
    steps_per_epoch = sampler.steps_per_epoch()
    num_rounds = steps_per_epoch * cfg.num_epochs
    if session.fedsim_env is not None:
        # chaos round indices can only be checked against the run length
        # here — Config cannot know steps_per_epoch (it derives from the
        # dataset size)
        session.fedsim_env.validate_rounds(num_rounds)
        print(session.fedsim_env.describe())
    lr_fn = partial(
        piecewise_linear_lr,
        steps_per_epoch=steps_per_epoch,
        pivot_epoch=cfg.pivot_epoch,
        num_epochs=cfg.num_epochs,
        lr_scale=cfg.lr_scale,
    )
    table = table or TableLogger()
    timer = Timer()
    from commefficient_tpu.telemetry import (
        DivergenceError,
        build_perf_observability,
        build_telemetry_riders,
        record_crash,
    )
    from commefficient_tpu.utils.profiling import StepProfiler

    profiler = StepProfiler(cfg.profile_dir)
    if cfg.profile_rounds:
        # --profile_rounds A-B (telemetry/trace.py ProfilerWindow): a
        # CLI-chosen jax.profiler capture window, stacked behind the same
        # profiler facade the round sources already drive.
        # The entry/exit fence syncs on the params so deferred applies /
        # pending writebacks retire OUTSIDE the captured rounds.
        import os

        from commefficient_tpu.telemetry.trace import (
            ProfilerStack,
            ProfilerWindow,
        )
        from commefficient_tpu.utils.profiling import fence

        window_dir = cfg.profile_dir or os.path.join(
            writer.logdir if writer is not None else cfg.logdir,
            "profile_rounds",
        )
        profiler = ProfilerStack(
            profiler,
            ProfilerWindow(
                cfg.profile_rounds, window_dir,
                fence_fn=lambda: fence(session.state.params_vec),
            ),
        )
    # adaptive-communication controller (control/): None unless the config
    # turns the control plane on. Built BEFORE the telemetry riders (the
    # ledger switches to per-rung accounting, the flight recorder carries
    # the controller snapshot) and BEFORE any restore (a resumed rung
    # sequence needs the controller attached); prewarm AOT-traces every
    # rung's round program for the run's real round-0 signature, so a
    # mid-run rung switch can never be a silent retrace.
    from commefficient_tpu.control import build_controller

    controller = build_controller(cfg, session, num_rounds=num_rounds)
    if controller is not None:
        controller.prewarm(sampler, float(lr_fn(0)))
        print(controller.describe())
    elif getattr(cfg, "fleet_enabled", False):
        # elastic fleet without a control ladder: the width rungs still
        # need their AOT prewarm (same zero-retrace pin the controller's
        # prewarm gives ladder runs) before the first resize dispatches
        session.prewarm_from_sampler(sampler, float(lr_fn(0)))
    # telemetry riders (level >= 1): comm ledger + flight recorder
    ledger, flight = build_telemetry_riders(cfg, session, writer)
    # perf observability (level >= 1): host phase spans + the compiled-
    # round XLA audit -> perf_report.json + xla/* scalars
    spans, _ = build_perf_observability(
        cfg, session, sampler, writer, float(lr_fn(0)),
        generated_by=generated_by,
    )
    # self-healing layer (resilience/): None unless a recovery policy or a
    # preemption source is configured — the default run constructs
    # NOTHING (no vault, no signal handler, no resilience/* scalars).
    # Built AFTER the riders (the manager rewinds the ledger and rides the
    # flight recorder) and BEFORE the restore/engine (the baseline
    # snapshot must capture the restored state).
    from commefficient_tpu.resilience import PreemptShutdown, build_resilience

    resil = build_resilience(cfg, session, sampler, ledger=ledger,
                             flight=flight)
    if resil is not None:
        print(resil.describe())
    val = {}
    step = 0
    # the current epoch's drain closure, reachable from the crash handler:
    # a BudgetExhaustedError, a staging-worker fault, or any mid-epoch
    # crash fires BEFORE the deferred epoch-end drain, so without this
    # flush the ledger/flight would be blind to the crashed epoch's
    # completed rounds
    live_drain = [None]
    engine = None
    try:
        if checkpointer is not None and cfg.resume:
            restored = checkpointer.restore(session)
            if restored is not None:
                step = restored
                profiler.resume_at(step)  # clamp trace window post-resume
                if spans is not None:
                    spans.resume_at(step)
                print(f"resumed from checkpoint at round {step}")
        if cfg.asyncfed_enabled:
            # buffered-asynchronous engine (asyncfed/): each engine step
            # is one SERVER UPDATE consuming K of the C in-flight cohorts'
            # contributions, staleness-discounted. Built AFTER the restore
            # so its window starts at the resumed update (the schedule is
            # a pure function of the config).
            from commefficient_tpu.asyncfed import AsyncFederation

            engine = AsyncFederation(
                cfg, session, sampler, lr_fn, num_rounds,
                steps_per_epoch=steps_per_epoch, spans=spans,
                profiler=profiler,
            ).start(step)
            print(f"asyncfed: buffer K={cfg.async_buffer} "
                  f"concurrency C={cfg.async_concurrency} "
                  f"staleness_exponent={cfg.staleness_exponent:g} "
                  "(K=W, C=1, exponent 0 == the synchronous round, "
                  "bit-exact)")
        if resil is not None:
            # seed the rollback vault at the start round (post-restore): a
            # divergence before the first snapshot_every boundary is then
            # still recoverable — back to the very start if need be
            resil.baseline(step)
    except BaseException:
        # a pre-loop failure (restore walk-back exhausted, engine start,
        # baseline capture) never reaches the finally below — join the
        # already-started staging worker and restore the signal
        # dispositions before propagating, or a surviving process
        # (embedding, pytest) leaks the staging thread and keeps
        # flag-only SIGTERM/SIGINT handlers nobody polls
        if engine is not None:
            engine.close()
        if resil is not None:
            resil.close()
        raise

    def span(name, trace_id=None):
        # one shape for every optional-span site (drain / checkpoint /
        # snapshot) — the bare fed/<name> annotation when spans are off
        return span_of(spans, name, trace_id=trace_id)

    def ckpt_save(force=False):
        with span("checkpoint"):
            return checkpointer.maybe_save(session, step, force=force)

    resume_acc = None  # accumulator rider restored by the last rollback
    # highest epoch whose END block (table row, eval, val scalars,
    # on_epoch_end) already ran: a rollback can land inside a completed
    # epoch, and a non-forking (retry) replay must not duplicate those
    # side effects — the replayed rows would double in the table and
    # break the healed-run == uninterrupted-run contract. A resume at
    # step S has completed exactly the epochs below S's (works at exact
    # boundaries too: S // spe - 1 == the last finished epoch).
    completed_epoch = step // steps_per_epoch - 1
    try:
        while True:  # recovery loop: one iteration per (re-)entry
            try:
                for epoch in range(step // steps_per_epoch, cfg.num_epochs):
                    timer()
                    pending = []  # (step, lr, device-metrics)
                    acc_state = hooks.new_accumulator()
                    if resume_acc is not None and isinstance(acc_state, dict):
                        # a mid-epoch rollback replays only rounds >= the
                        # snapshot; the snapshot's accumulator re-seeds
                        # the rounds before it, so the epoch row still
                        # averages the FULL epoch (and a healed retry
                        # run's table matches the uninterrupted one)
                        acc_state.clear()
                        acc_state.update(resume_acc)
                    resume_acc = None

                    def acc(loss, metrics, _a=acc_state):
                        hooks.accumulate(_a, loss, metrics)

                    def drain(_acc=acc):
                        # the drain span names the NEWEST pending round
                        # (schema v11): the fetch fences through that
                        # round's device work, so that is the trace the
                        # drain wait belongs to
                        tid = None
                        if pending:
                            from commefficient_tpu.telemetry.trace import (
                                round_trace_id,
                            )

                            tid = round_trace_id(pending[-1][0])
                        with span("metric_drain", trace_id=tid):
                            drain_round_metrics(pending, writer, _acc,
                                                ledger=ledger, flight=flight,
                                                controller=controller)

                    live_drain[0] = drain
                    rounds = (
                        engine.epoch_rounds(epoch, step)
                        if engine is not None
                        else _sync_epoch_rounds(cfg, session, sampler, lr_fn,
                                                spans, profiler, epoch, step,
                                                steps_per_epoch)
                    )
                    lr = float(lr_fn(step))
                    for s, lr, metrics in rounds:
                        pending.append((s, lr, metrics))
                        step = s + 1
                        if checkpointer is not None:
                            if checkpointer.will_save(step):
                                drain()
                            ckpt_save()
                        if resil is not None and resil.will_snapshot(step):
                            # the drain certifies rounds < step finite (it
                            # IS the divergence check) BEFORE the vault
                            # admits the snapshot — the checkpoint
                            # will_save-then-save discipline
                            drain()
                            with span("snapshot"):
                                # the epoch accumulator rides the snapshot
                                # (host copy) so a rollback here can
                                # re-seed it for the replayed tail; the
                                # asyncfed engine adds its in-flight
                                # window so the rolled-back replay reuses
                                # the SAME launched contributions
                                # (bit-identical recovery at any C)
                                extras = ({"acc": dict(acc_state)}
                                          if isinstance(acc_state, dict)
                                          else {})
                                if engine is not None:
                                    extras["asyncfed"] = (
                                        engine.snapshot_extra()
                                    )
                                resil.snapshot(step, extras=extras or None)
                        if (resil is not None
                                and resil.preempt_requested(metrics)):
                            # preemption-safe shutdown at round
                            # granularity: flush everything this round
                            # included, force a checkpoint, then let the
                            # crash teardown write flight/ledger/spans
                            drain()
                            # a boundary the loop JUST saved dedups the
                            # force-save to False — a checkpoint at this
                            # exact step still exists, so the message's
                            # --resume promise holds
                            saved = bool(checkpointer is not None
                                         and (ckpt_save(force=True)
                                              or checkpointer.latest_step()
                                              == step))
                            if writer:
                                writer.scalar("resilience/preempt_requested",
                                              1.0, s)
                                writer.flush()
                            raise PreemptShutdown(step, resil.preempt_source,
                                                  saved=saved)
                    drain()
                    train_time = timer()
                    if epoch > completed_epoch:
                        val = hooks.evaluate()
                        val_time = timer()
                        table.append(hooks.epoch_row(
                            epoch=epoch, lr=lr, acc=acc_state, val=val,
                            train_time=train_time, val_time=val_time,
                            steps_per_epoch=steps_per_epoch,
                        ))
                        if writer:
                            hooks.write_val(writer, val, step)
                            writer.flush()
                        hooks.on_epoch_end(epoch, val)
                    completed_epoch = max(completed_epoch, epoch)
                break  # clean completion of the epoch loop
            except DivergenceError as e:
                # divergence rollback-and-recover (resilience/): restore
                # the last drain-certified snapshot and re-enter the loop
                # there; None -> unrecoverable, fall through to the legacy
                # crash path with e.recovery_history attached
                rollback = (resil.on_divergence(e)
                            if resil is not None else None)
                if rollback is None:
                    raise
                step = rollback
                # re-seed the epoch accumulator only when the rollback
                # lands MID-epoch: a boundary snapshot's accumulator
                # covers the epoch that just finished, and a fresh epoch
                # correctly starts from zeros
                extras = resil.last_restored_extras or {}
                resume_acc = (extras.get("acc")
                              if step % steps_per_epoch else None)
                if resil.manager.policy.forks:
                    # a forking recovery (demote/skip_clients) changes the
                    # replayed trajectory: re-run the end blocks of any
                    # re-trained epoch so the table/val scalars report the
                    # fork honestly (retry keeps them skipped — its replay
                    # is bit-identical, re-reporting would only duplicate)
                    completed_epoch = min(completed_epoch,
                                          step // steps_per_epoch - 1)
                if checkpointer is not None:
                    # checkpoints above the rollback came from the
                    # rolled-back trajectory: drop them so the replay's
                    # own saves land (a demote/skip_clients fork would
                    # otherwise leave a stale pre-recovery state for a
                    # later --resume)
                    checkpointer.discard_steps_after(step)
                    if resil.manager.policy.forks:
                        # a forking recovery mutated state every retained
                        # checkpoint predates (the demotion floor / the
                        # blacklist): persist it NOW, or a crash before
                        # the next boundary resumes without the fork
                        checkpointer.resave(session, step)
                if engine is not None:
                    # hand the snapshot's in-flight window back before the
                    # restart rebuilds it (pending launches restore
                    # verbatim -> bit-identical replay; absent/None ->
                    # deterministic cold rebuild at the rollback point)
                    engine.restore_extra(extras.get("asyncfed"))
                    engine.restart(step)
                m = resil.manager
                print(f"resilience: recovered from divergence at round "
                      f"{e.step} — rolled back to round {step} under "
                      f"policy {cfg.recover_policy!r} "
                      f"(recovery {m.recoveries}/{m.max_recoveries})")
        # end-of-training checkpoint: a run that completes round
        # num_rounds would otherwise leave its last
        # num_rounds % checkpoint_every rounds unsaved and --resume on a
        # finished run would re-train them (the epoch-end drain above
        # already flushed everything this save covers)
        if checkpointer is not None:
            ckpt_save(force=True)
    except Exception as e:
        # best-effort flush of the crashed epoch's completed rounds so the
        # ledger totals and the flight ring cover them (a flush-time
        # DivergenceError supersedes: it names the true first bad round)
        if live_drain[0] is not None and not isinstance(e, DivergenceError):
            try:
                live_drain[0]()
            except DivergenceError:
                raise
            # flushing inside the original failure's handler — a flush
            # error must not mask it; record_crash below preserves it
            # lint: allow[exception-hygiene] the original error wins
            except Exception:
                pass
        # divergence already dumped its own flight record in the drain;
        # any OTHER crash dumps the recent trajectory for the post-mortem
        record_crash(flight, e)
        raise
    finally:
        if engine is not None:
            engine.close()  # join the staging worker (crash paths too)
        profiler.close()
        if spans is not None:
            session.spans = None
            spans.close()  # dumps spans_<step>.json (crash included)
            if cfg.run_report and writer is not None:
                # critical-path run report over the just-dumped spans +
                # metrics (telemetry/trace.py; schema v11) — best-effort
                # on crash paths too, a partial report is still evidence
                from commefficient_tpu.telemetry.trace import (
                    write_run_report,
                )

                path = write_run_report(writer.logdir,
                                        generated_by=generated_by)
                if path:
                    print(f"run report: {path}")
        if ledger is not None:
            # partial ledgers are still evidence — write on crash too
            ledger.write(writer.logdir)
        if checkpointer is not None:
            # close alongside profiler/spans/ledger: the Orbax manager
            # used to leak on crash paths when only the entries' own
            # finally closed it (close() is idempotent, so an entry-level
            # close after this one is a no-op)
            checkpointer.close()
        if resil is not None:
            resil.close()  # restore signal dispositions (crash paths too)
        # drain + join the clientstore writeback worker and release the
        # store (mmap flush/unlink) — a surviving process (embedding,
        # pytest) must not leak the thread; no-op for device stores
        if hasattr(session, "close_client_store"):
            session.close_client_store()
    if not val:
        # resumed at/after the final round (the epoch loop never ran):
        # still evaluate so callers get final metrics instead of a KeyError
        val = hooks.evaluate()
    return val
