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


# ---- rows and draws as they were, and block diffusion's part of the feed (PR 35) ----------

def test_rows_and_draws_without_reserved_ids_are_what_they_were(sets):
    """``reserved`` 0 (Laguna's and Keye's rows) leaves every row bit for bit,
    and a sampler without an augmenter draws what it drew (checksums taken on
    the commit before the parameter and the plan's new shape)."""
    import zlib

    from commefficient_tpu.data import FedSampler

    train, test = sets
    assert (zlib.crc32(train.data["input_ids"].tobytes()),
            zlib.crc32(train.data["lm_labels"].tobytes()),
            zlib.crc32(test.data["input_ids"].tobytes())) == (3125163985, 494471509, 2285509507)
    sampler = FedSampler(train, num_workers=2, local_batch_size=2, seed=11)
    want = {0: ([0, 5], [[1, 2], [20, 22]], 2928073658), 5: ([0, 4], [[3, 0], [16, 18]], 992454782)}
    for r, (clients, rows, crc) in want.items():
        ids, idx, plan = sampler.sample_round_indices(r)
        assert (ids.tolist(), idx.tolist(), plan) == (clients, rows, ())
        _, batch = sampler.sample_round(r)
        assert list(batch) == ["input_ids", "lm_labels"]
        assert zlib.crc32(batch["input_ids"].tobytes()) == crc


def test_the_mask_token_occurs_in_no_document():
    """With one id reserved under ``<eos>`` no document draws it, own band or
    whole slice; nothing else about the rows changes kind."""
    train, test = load_fed_text(**{**KW, "vocab": 300, "reserved": 1})
    for ds in (train, test):
        ids = ds.data["input_ids"]
        assert not (ids == 298).any() and (ids == 299).any() and ids.max() == 299
    assert (load_fed_text(**{**KW, "vocab": 300})[0].data["input_ids"] == 298).any()


@pytest.fixture(scope="module")
def noise():
    from commefficient_tpu.data.fedtext import BlockNoise

    return BlockNoise(4)


def test_block_noise_names_its_keys_and_its_plan_is_small(sets, noise):
    train, _ = sets
    assert noise.reads == ("lm_labels",)
    assert noise.accepts(train.data) and noise.plan_args(train.data) == (256,)
    t, u = noise.plan(np.random.default_rng(0), 5, 256)
    assert (t.shape, t.dtype, u.shape, u.dtype) == ((5, 64), np.float32, (5, 256), np.float32)
    assert 1e-3 <= t.min() and t.max() <= 1.0 and 0.0 <= u.min() and u.max() < 1.0
    from commefficient_tpu.data.fedtext import BlockNoise

    assert not BlockNoise(7).accepts(train.data)            # 256 is not whole blocks of 7


def test_a_blocks_masked_share_follows_its_t(noise):
    """Each labelled token of a block is masked with probability ``t`` of
    that block, and carries that ``t``."""
    labels = np.zeros((400, 256), np.int32)
    t, u = noise.plan(np.random.default_rng(1), 400, 256)
    made = noise.apply({"lm_labels": labels}, t, u)
    assert made["noise_mask"].dtype == np.bool_ and made["noise_t"].dtype == np.float32
    assert np.array_equal(made["noise_t"], np.repeat(t, 4, axis=1))
    per_block = made["noise_mask"].reshape(400, 64, 4).mean(-1)
    for lo, hi in ((0.0, 0.2), (0.4, 0.6), (0.8, 1.0)):
        band = (t >= lo) & (t < hi)
        assert abs(per_block[band].mean() - t[band].mean()) < 0.02
    assert abs(made["noise_mask"].mean() - 0.5) < 0.01      # the mean of U[1e-3, 1]


def test_pad_is_never_masked(sets, noise):
    train, _ = sets
    rows = {k: v[3::4] for k, v in train.data.items()}       # every client's last row: its tail
    made = noise.apply(rows, *noise.plan(np.random.default_rng(2), 6, 256))
    pad = rows["lm_labels"] == -100
    assert pad.any() and not made["noise_mask"][pad].any() and made["noise_mask"][~pad].any()


def test_the_fixed_plan_is_keyed_by_seed_and_row(noise):
    a, b = noise.fixed(3, 256, 7), noise.fixed(5, 256, 7)
    assert all(np.array_equal(x, y[:3]) for x, y in zip(a, b))
    assert not np.array_equal(a[1][0], a[1][1])
    assert not np.array_equal(a[1], noise.fixed(3, 256, 8)[1])
