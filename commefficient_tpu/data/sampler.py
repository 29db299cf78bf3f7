"""FedSampler — per-round client participation + batch assembly.

Behavioral spec from the reference's ``data_utils/fed_sampler.py`` ~L1-80
(SURVEY.md §2 "FedSampler"): each round, sample ``num_workers`` distinct
clients uniformly from ``num_clients`` (the participation fraction), and
group each participant's ``local_batch_size`` examples so every worker gets
its clients' shards.

Here a round's output is ONE device-ready structure instead of per-process
queue messages: ``client_ids [W]`` plus a batch dict of ``[W, B, ...]``
arrays, which the round engine shards over the ``workers`` mesh axis.
Deterministic from (seed, round) so runs are reproducible and resumable
without serializing generator state.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterable, Iterator, Optional, Tuple

import numpy as np

from commefficient_tpu.data.fed_dataset import FedDataset

Batch = Dict[str, np.ndarray]
Augment = Callable[[Batch, np.random.Generator], Batch]


class FedSampler:
    def __init__(
        self,
        dataset: FedDataset,
        *,
        num_workers: int,
        local_batch_size: int,
        seed: int = 42,
        augment: Optional[Augment] = None,
    ):
        if dataset.num_clients < num_workers:
            raise ValueError("need num_clients >= num_workers")
        self.dataset = dataset
        self.num_workers = num_workers
        self.local_batch_size = local_batch_size
        self.seed = seed
        self.augment = augment
        # fused batch assembly: one flat [W*B] gather (+ augment) per round
        # instead of per-client gather/augment/stack — the native C++
        # kernel when available, vectorized numpy otherwise. Requires a
        # plan-based augment (data.augment.BatchAugment: CifarAugment,
        # ImageNetAugment, fedtext.BlockNoise) or none.
        self._planner = augment if hasattr(augment, "plan") else None
        self._fusable = (
            (augment is None or self._planner is not None)
            and all(isinstance(v, np.ndarray) for v in dataset.data.values())
            and (self._planner is None or self._planner.accepts(dataset.data))
        )

    @property
    def fusable(self) -> bool:
        """True when rounds can be assembled by fused index-gather (and so
        also driven fully from device-resident data via
        ``sample_round_indices``)."""
        return self._fusable

    def steps_per_epoch(self) -> int:
        """Rounds per epoch such that one epoch visits ~the whole dataset,
        matching the reference's effective epoch = N / (workers * B)."""
        per_round = self.num_workers * self.local_batch_size
        return max(1, len(self.dataset) // per_round)

    def sample_round(self, round_idx: int) -> Tuple[np.ndarray, Batch]:
        """(client_ids [W] int32, batch {k: [W, B, ...]}) for one round."""
        rng = np.random.default_rng((self.seed, round_idx))
        clients = rng.choice(
            self.dataset.num_clients, size=self.num_workers, replace=False
        )
        if self._fusable:
            return clients.astype(np.int32), self._fused_round(clients, rng)
        shards = []
        for c in clients:
            b = self.dataset.client_batch(int(c), self.local_batch_size, rng)
            if self.augment is not None:
                b = self.augment(b, rng)
            shards.append(b)
        batch = {
            k: np.stack([s[k] for s in shards]) for k in shards[0]
        }
        return clients.astype(np.int32), batch

    def _fused_round(self, clients: np.ndarray, rng: np.random.Generator) -> Batch:
        """One flat gather (+ augment) for the whole round's [W*B] samples."""
        from commefficient_tpu import native

        W, B = self.num_workers, self.local_batch_size
        flat = np.concatenate(
            [
                self.dataset.client_batch_indices(int(c), B, rng)
                for c in clients
            ]
        ).astype(np.int64)
        data = self.dataset.data
        plan, made = (), None
        if self._planner is not None:
            plan = self._planner.plan(rng, W * B, *self._planner.plan_args(data))
            # fused native gather+augment (planner-specific kernel); None
            # when the planner has none or the C++ lib is absent
            made = self._planner.gather_apply(data, flat, plan)
        rows: Batch = {}
        for k, v in data.items():
            if made is not None and k in made:
                rows[k] = made[k]  # the fused kernel gathered it
                continue
            out = native.gather_rows(v, flat)
            rows[k] = v[flat] if out is None else out
        if self._planner is not None and made is None:  # numpy gather + apply
            made = self._planner.apply(rows, *plan)
        # a key the planner replaces stays in its place, the keys it adds
        # come after the dataset's
        rows.update(made or {})
        return {k: out.reshape((W, B) + out.shape[1:]) for k, out in rows.items()}

    def sample_round_indices(self, round_idx: int):
        """(client_ids [W] int32, idx [W, B] int32, plan) — the index-only
        form of ``sample_round`` for the device-resident-data path: the rng
        draw sequence is IDENTICAL to ``_fused_round``, so gathering
        ``data[idx]`` and applying ``plan`` on device reproduces the host
        batch bit-for-bit."""
        rng = np.random.default_rng((self.seed, round_idx))
        clients = rng.choice(
            self.dataset.num_clients, size=self.num_workers, replace=False
        )
        W, B = self.num_workers, self.local_batch_size
        # loud guard for the int32 narrowing below: a >= 2^31-row dataset
        # would silently wrap sample indices (ADVICE r2). (_fused_round keeps
        # int64 on the host path; the device path ships int32 on purpose —
        # half the bytes over the host->device link.)
        if len(self.dataset) >= 2**31:
            raise OverflowError(
                f"dataset has {len(self.dataset)} rows; the device-resident "
                "index path ships int32 sample indices — use the host batch "
                "path for datasets >= 2^31 rows"
            )
        flat = np.concatenate(
            [self.dataset.client_batch_indices(int(c), B, rng) for c in clients]
        ).astype(np.int32)
        plan = ()
        if self._planner is not None:
            plan = tuple(self._planner.plan(
                rng, W * B, *self._planner.plan_args(self.dataset.data)))
        return clients.astype(np.int32), flat.reshape(W, B), plan

    def epoch(self, epoch_idx: int):
        steps = self.steps_per_epoch()
        base = epoch_idx * steps
        for s in range(steps):
            yield self.sample_round(base + s)

    def epoch_indices(self, epoch_idx: int):
        steps = self.steps_per_epoch()
        base = epoch_idx * steps
        for s in range(steps):
            yield self.sample_round_indices(base + s)


def prefetch(it: Iterable, depth: int = 2) -> Iterator:
    """Run ``it`` in a background thread, ``depth`` items ahead.

    The host-side batch assembly (sampler gather + augment — C++ with the
    GIL released, or numpy which also drops the GIL inside vectorized ops)
    then overlaps the device round: the analog of the reference's
    DataLoader worker processes feeding the GPU train loop. Exceptions in
    the producer re-raise at the consuming site; if the CONSUMER stops
    early (exception mid-epoch, generator close), the producer notices via
    the stop flag within one put-timeout and exits instead of blocking on
    the bounded queue forever."""
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _END = object()
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def run():
        try:
            for item in it:
                if not _put(item):
                    return
            _put(_END)
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            _put(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
