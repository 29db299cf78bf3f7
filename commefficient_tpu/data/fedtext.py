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
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from commefficient_tpu.data.fed_dataset import FedDataset

IGNORE = -100


def _client_rows(rng, client: int, *, rows: int, seq_len: int, vocab: int, band: int,
                 doc_median: float):
    eos, total = vocab - 1, rows * seq_len
    start = (client * 997) % max(1, eos - band)   # the client's own band of ids
    ids = np.full(total, eos, np.int32)
    labels = np.full(total, IGNORE, np.int32)
    at = 0
    while True:
        n = int(np.clip(rng.lognormal(np.log(doc_median), 1.0), 16, seq_len))
        if at + n + 1 > total:
            break
        own = rng.random(n) < 0.8
        doc = np.where(own, start + rng.integers(0, band, n), rng.integers(0, eos, n))
        ids[at:at + n] = doc
        at += n + 1                               # the <eos> after it is already there
    labels[:at] = ids[:at]
    return ids.reshape(rows, seq_len), labels.reshape(rows, seq_len)


def load_fed_text(*, num_clients: int = 64, rows_per_client: int = 8, seq_len: int = 2048,
                  vocab: int = 12544, seed: int = 42, doc_median: float = 300.0,
                  test_rows: int = 8) -> Tuple[FedDataset, FedDataset]:
    """``(train, test)``: rows ``input_ids``, ``lm_labels`` ``[seq_len]``
    int32, one shard of ``rows_per_client`` rows per client; the test set is
    one more client's worth drawn past the last."""
    band = min(2000, max(1, vocab // 4))
    kw = dict(seq_len=seq_len, vocab=vocab, band=band, doc_median=doc_median)

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
