"""FedText — a synthetic federated token set for causal language modelling.

Every client owns ``rows_per_client`` rows of ``seq_len`` positions, packed:
documents of log-normal length (median ``doc_median``, sigma 1.0, clipped to
16..``seq_len``) follow one another with ``<eos>`` between, across row
boundaries, until the next one no longer fits; what is left of the client's
last row is pad, and only there is ``lm_labels`` -100. Nothing else is
masked and positions do not restart at a document's start, so a causal
window shorter than a row binds on every row.

Ids come from a vocabulary slice ``[0, vocab)`` (a chip's share of a sliced
vocabulary is a smaller vocabulary); ``<eos>`` is its last id and doubles
as the pad. The non-IID axis: four tokens in five come from the client's own
band of ``band`` consecutive ids, one in five from the whole slice.

Drawn from the seed alone (``default_rng((seed, client))``), no files.

``reserved`` ids just under ``<eos>`` are kept out of every document (0: as
before, draw for draw): a block-diffusion model's ``[MASK]`` is the last of
them, ``vocab - 2``. ``BlockNoise`` is that objective's part of a round's
feed: the noise drawn per round as a plan (``data/augment.py``), added to
the rows as ``noise_mask`` and ``noise_t``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from commefficient_tpu.data.augment import BatchAugment
from commefficient_tpu.data.fed_dataset import FedDataset

IGNORE = -100
T_MIN = 1e-3      # the smallest noise level a block draws (its weight 1 / t stays under 1,000)


class BlockNoise(BatchAugment):
    """Block diffusion's noise on rows of ``seq_len`` tokens in blocks of
    ``block_length`` (MDLM's linear schedule, as BD3-LMs draws it): a level
    ``t ~ U[T_MIN, 1]`` a block and a uniform ``u ~ U[0, 1)`` a token. It
    adds ``noise_mask`` ``[n, seq_len]`` bool, true where ``u < t`` of the
    token's block and the token carries a label (a client's tail pad is
    never masked), and ``noise_t`` ``[n, seq_len]`` float32, each position's
    own block's ``t``. The plan is ``(t [n, seq_len / block_length], u [n,
    seq_len])`` in float32: both paths make the same comparison of the same
    numbers."""

    reads = ("lm_labels",)

    def __init__(self, block_length: int):
        self.block_length = block_length

    def accepts(self, data) -> bool:
        return super().accepts(data) and data["lm_labels"].shape[1] % self.block_length == 0

    def plan_args(self, data) -> tuple:
        return (data["lm_labels"].shape[1],)

    def plan(self, rng, n, seq_len):
        t = rng.uniform(T_MIN, 1.0, (n, seq_len // self.block_length)).astype(np.float32)
        return t, rng.random((n, seq_len), np.float32)

    def _noise(self, xp, labels, t, u):
        t = xp.repeat(t, self.block_length, axis=1)
        return {"noise_mask": (u < t) & (labels != IGNORE), "noise_t": t}

    def apply(self, batch, t, u):
        return self._noise(np, batch["lm_labels"], t, u)

    def device_apply(self, batch, t, u):
        import jax.numpy as jnp

        return self._noise(jnp, batch["lm_labels"], t, u)

    def fixed(self, rows: int, seq_len: int, seed: int) -> tuple:
        """One plan for ``rows`` rows that no round draws: row ``i``'s noise
        from ``default_rng((seed, i))`` (eval's)."""
        plans = [self.plan(np.random.default_rng((seed, i)), 1, seq_len) for i in range(rows)]
        return tuple(np.concatenate(a) for a in zip(*plans))


def _client_rows(rng, client: int, *, rows: int, seq_len: int, vocab: int, band: int,
                 doc_median: float, reserved: int = 0):
    eos, total = vocab - 1, rows * seq_len
    top = eos - reserved                          # documents draw ids below it
    start = (client * 997) % max(1, top - band)   # the client's own band of ids
    ids = np.full(total, eos, np.int32)
    labels = np.full(total, IGNORE, np.int32)
    at = 0
    while True:
        n = int(np.clip(rng.lognormal(np.log(doc_median), 1.0), 16, seq_len))
        if at + n + 1 > total:
            break
        own = rng.random(n) < 0.8
        doc = np.where(own, start + rng.integers(0, band, n), rng.integers(0, top, n))
        ids[at:at + n] = doc
        at += n + 1                               # the <eos> after it is already there
    labels[:at] = ids[:at]
    return ids.reshape(rows, seq_len), labels.reshape(rows, seq_len)


def load_fed_text(*, num_clients: int = 64, rows_per_client: int = 8, seq_len: int = 2048,
                  vocab: int = 12544, seed: int = 42, doc_median: float = 300.0,
                  test_rows: int = 8, reserved: int = 0) -> Tuple[FedDataset, FedDataset]:
    """``(train, test)``: rows ``input_ids``, ``lm_labels`` ``[seq_len]``
    int32, one shard of ``rows_per_client`` rows per client; the test set is
    one more client's worth drawn past the last. No document holds one of
    the ``reserved`` ids under ``<eos>``."""
    band = min(2000, max(1, vocab // 4))
    kw = dict(seq_len=seq_len, vocab=vocab, band=band, doc_median=doc_median,
              reserved=reserved)

    def build(clients, rows):
        made = [_client_rows(np.random.default_rng((seed, c)), c, rows=rows, **kw)
                for c in clients]
        return {"input_ids": np.concatenate([m[0] for m in made]),
                "lm_labels": np.concatenate([m[1] for m in made])}

    shards = [np.arange(c * rows_per_client, (c + 1) * rows_per_client)
              for c in range(num_clients)]
    train = FedDataset(build(range(num_clients), rows_per_client), num_clients,
                       seed=seed, client_indices=shards)
    test = FedDataset(build([num_clients], test_rows), 1, seed=seed)
    return train, test
