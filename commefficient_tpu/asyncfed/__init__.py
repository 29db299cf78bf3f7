"""Buffered-asynchronous federation (``--async_buffer K``).

FetchSGD's synchronous round blocks every update on the slowest of W
participants. This package layers FedBuff-style buffered asynchrony
(arXiv:2106.06639) on the existing compress/EF/momentum pipeline: the
server keeps ``C`` cohorts in flight (``--async_concurrency``), fires an
update once ``K`` contributions have arrived, and weights each
contribution by the polynomial staleness discount ``(1+s)^(-alpha)``
(``--staleness_exponent``) before it enters the shared aggregation tail.

Four pieces:

* ``schedule``: ``AsyncSchedule`` — the pre-simulated deterministic
  arrival process (per-cohort exponential delays on a dedicated rng
  stream); every downstream consumer keys off its ``UpdateSpec``s.
* ``round``: ``build_async_round_fns`` — the synchronous round split at
  the per-client/aggregate seam into a ``launch_fn`` (params snapshot ->
  per-client transmit rows) and an ``apply_fn`` (weighted buffer drain ->
  server update), sharing the synchronous helpers so the K=W, C=1,
  alpha=0 anchor reduces bit-identically to ``build_round_fn``.
* ``staging``: ``CohortScheduler`` over ``RoundPrefetcher`` — the
  background worker that realizes and stages cohorts ahead of their
  launch, with crash propagation and a replay horizon.
* ``engine``: ``AsyncFederation`` — the round source the runner drives
  instead of its plain loop (``start``/``epoch_rounds``/``restart``/
  ``close``), owning the in-flight window, staleness weighting, overlap
  telemetry, and the vault snapshot riders.

``--async_buffer 0`` (default) constructs nothing — the synchronous
loop and its golden recordings are untouched.
"""

from commefficient_tpu.asyncfed.engine import AsyncFederation
from commefficient_tpu.asyncfed.round import build_async_round_fns
from commefficient_tpu.asyncfed.schedule import (
    ASYNC_STREAM,
    AsyncSchedule,
    UpdateSpec,
    cohort_delays,
)

__all__ = [
    "ASYNC_STREAM",
    "AsyncFederation",
    "AsyncSchedule",
    "UpdateSpec",
    "build_async_round_fns",
    "cohort_delays",
]
