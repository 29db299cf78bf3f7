"""Profiling hooks — ``jax.profiler`` traces around a window of rounds,
plus ``fence``, the one way the host waits on a device value.

The reference's only tracing is a console Timer around epoch phases
(SURVEY.md §5 "Tracing/profiling"); the rebuild equivalent is a real XLA
trace viewable in TensorBoard/Perfetto. ``StepProfiler`` wraps a few
steady-state rounds (after compile/warmup) so the trace shows the real hot
path, not compilation. The train runner drives it every round; the
telemetry span recorder and the ``--profile_rounds`` window share its
fencing and warm-up discipline.
"""

from __future__ import annotations

import jax

# The first executed round compiles and the second fills the other donated-
# buffer layout; a trace window that includes them measures XLA, not the
# round. Every window starts at least this many steps after the first
# executed round.
MIN_WARMUP_STEPS = 2


def fence(x) -> float:
    """Wait for a pytree of device values (``jax.block_until_ready`` on the
    tree) and return the first element of its first leaf, which callers
    assert finite."""
    jax.block_until_ready(x)
    return float(jax.tree.leaves(x)[0].ravel()[0])


class StepProfiler:
    """Trace rounds [start_step, start_step + num_steps) into ``logdir``.

    Call ``step(i)`` once per executed training round (monotonic ``i``);
    call ``resume_at(step0)`` after a checkpoint restore so the window
    clamps to post-resume steps; call ``close()`` in a finally block.
    Inactive (zero overhead) when ``logdir`` is falsy.

    Window semantics: the trace starts at the first ``step()`` that lands
    INSIDE the window (not only on exact equality with ``start_step`` — a
    resume that fast-forwards into the middle of the window used to leave
    the trace permanently un-started, and one that started could never
    stop) and stops at the first step at/past the end. ``start_step`` is
    clamped to at least ``MIN_WARMUP_STEPS`` so ``start_step=0`` cannot
    trace compile+warmup.
    """

    def __init__(self, logdir: str, start_step: int = 5, num_steps: int = 3):
        self.logdir = logdir
        self.num_steps = num_steps
        self.start = max(start_step, MIN_WARMUP_STEPS)
        self.stop_at = self.start + num_steps
        self._active = False

    def resume_at(self, resume_step: int) -> None:
        """Clamp the window to post-resume steps: the resumed process's
        first executed round is ``resume_step`` and it compiles from
        scratch, so any window overlapping or predating it shifts to
        ``resume_step + MIN_WARMUP_STEPS`` (same length)."""
        floor = resume_step + MIN_WARMUP_STEPS
        if floor > self.start:
            self.start = floor
            self.stop_at = floor + self.num_steps

    def step(self, step_idx: int) -> None:
        if not self.logdir:
            return
        if self._active and step_idx >= self.stop_at:
            jax.profiler.stop_trace()
            self._active = False
        elif not self._active and self.start <= step_idx < self.stop_at:
            jax.profiler.start_trace(self.logdir)
            self._active = True

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
