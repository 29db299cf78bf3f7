"""data/fedtext.py: the packed federated token set of the causal-LM entry."""

import numpy as np
import pytest

from commefficient_tpu.data import load_fed_text

KW = dict(num_clients=6, rows_per_client=4, seq_len=256, vocab=1000, seed=7, doc_median=40.0)


@pytest.fixture(scope="module")
def sets():
    return load_fed_text(**KW)


def test_shapes_and_shards(sets):
    train, test = sets
    assert train.data["input_ids"].shape == (24, 256) == train.data["lm_labels"].shape
    assert train.data["input_ids"].dtype == np.int32
    assert [list(ix) for ix in train.client_indices] == [
        list(range(c * 4, c * 4 + 4)) for c in range(6)]
    assert test.data["input_ids"].shape == (8, 256) and test.num_clients == 1


def test_ids_stay_inside_the_slice(sets):
    for ds in sets:
        ids = ds.data["input_ids"]
        assert ids.min() >= 0 and ids.max() <= KW["vocab"] - 1


@pytest.mark.parametrize("client", range(6))
def test_only_a_clients_tail_is_unlabelled(sets, client):
    """Labels are the ids themselves up to the end of the client's last
    whole document (its <eos> included) and -100 on the pad after it: no
    -100 inside the stream, none at a row's start but in the last row."""
    train, _ = sets
    rows = train.client_indices[client]
    ids = train.data["input_ids"][rows].reshape(-1)
    labels = train.data["lm_labels"][rows].reshape(-1)
    kept = labels != -100
    end = int(kept.sum())
    assert kept[:end].all() and not kept[end:].any()
    assert np.array_equal(labels[:end], ids[:end])
    assert ids[end - 1] == KW["vocab"] - 1              # the stream ends on an <eos>
    assert (ids[end:] == KW["vocab"] - 1).all()         # the pad is the <eos> id
    assert len(ids) - end < 256                          # less than the longest document


def test_documents_are_packed_with_eos_between(sets):
    train, _ = sets
    eos = KW["vocab"] - 1
    ids = train.data["input_ids"][train.client_indices[0]].reshape(-1)
    labels = train.data["lm_labels"][train.client_indices[0]].reshape(-1)
    stops = np.flatnonzero((ids == eos) & (labels != -100))
    lengths = np.diff(np.concatenate([[-1], stops])) - 1
    assert len(lengths) > 4                              # several documents a client
    assert lengths.min() >= 16 and lengths.max() <= 256       # no drawn id is the <eos>
    # documents cross row boundaries: some row does not start a document
    starts = set((stops + 1).tolist()) | {0}
    assert any(r * 256 not in starts for r in range(1, 4))


def test_four_in_five_tokens_come_from_the_clients_own_band(sets):
    train, _ = sets
    band = min(2000, KW["vocab"] // 4)
    shares = []
    for c, rows in enumerate(train.client_indices):
        ids = train.data["input_ids"][rows].reshape(-1)
        labels = train.data["lm_labels"][rows].reshape(-1)
        ids = ids[(labels != -100) & (ids != KW["vocab"] - 1)]
        start = (c * 997) % (KW["vocab"] - 1 - band)
        shares.append(np.mean((ids >= start) & (ids < start + band)))
    # 0.8 + 0.2 * band / vocab = 0.85
    assert all(0.8 < s < 0.9 for s in shares), shares
    assert len({(c * 997) % (KW["vocab"] - 1 - band) for c in range(6)}) == 6


def test_the_seed_alone_decides_the_data():
    a, _ = load_fed_text(**KW)
    b, _ = load_fed_text(**KW)
    c, _ = load_fed_text(**{**KW, "seed": 8})
    assert np.array_equal(a.data["input_ids"], b.data["input_ids"])
    assert not np.array_equal(a.data["input_ids"], c.data["input_ids"])
    # a client's rows do not depend on how many clients there are
    d, _ = load_fed_text(**{**KW, "num_clients": 3})
    assert np.array_equal(d.data["input_ids"], a.data["input_ids"][:12])
