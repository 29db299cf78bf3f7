"""Data-layer tests: sharding semantics, determinism, batch shapes."""

import numpy as np
import pytest

from commefficient_tpu.data import (
    FedDataset,
    FedSampler,
    load_fed_cifar10,
    load_fed_emnist,
    load_fed_personachat,
    augment_batch,
)


def _toy(n=1000, num_classes=10, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(size=(n, 8)).astype(np.float32),
        "y": rng.integers(0, num_classes, size=n).astype(np.int32),
    }


def test_iid_split_partitions_everything():
    ds = FedDataset(_toy(), num_clients=7, iid=True, seed=1)
    allix = np.concatenate(ds.client_indices)
    assert len(allix) == 1000
    assert len(np.unique(allix)) == 1000
    assert ds.images_per_client.min() >= 1000 // 7


def test_non_iid_split_concentrates_labels():
    data = _toy(n=2000)
    iid = FedDataset(data, num_clients=20, iid=True, seed=1)
    non = FedDataset(data, num_clients=20, iid=False, seed=1)
    # labels seen per client: non-IID clients see far fewer distinct labels
    nuniq = lambda ds: np.mean([len(np.unique(data["y"][ix])) for ix in ds.client_indices])
    assert nuniq(non) <= 4 < nuniq(iid)
    allix = np.concatenate(non.client_indices)
    assert len(np.unique(allix)) == 2000  # still a partition


def test_split_deterministic_across_instances():
    a = FedDataset(_toy(), num_clients=5, iid=False, seed=9)
    b = FedDataset(_toy(), num_clients=5, iid=False, seed=9)
    for ia, ib in zip(a.client_indices, b.client_indices):
        np.testing.assert_array_equal(ia, ib)


def test_sampler_round_shapes_and_determinism():
    ds = FedDataset(_toy(), num_clients=16, seed=3)
    s = FedSampler(ds, num_workers=4, local_batch_size=8, seed=3)
    ids1, batch1 = s.sample_round(5)
    ids2, batch2 = s.sample_round(5)
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(batch1["x"], batch2["x"])
    assert ids1.shape == (4,)
    assert len(np.unique(ids1)) == 4  # distinct participants
    assert batch1["x"].shape == (4, 8, 8)
    assert batch1["y"].shape == (4, 8)


def test_sampler_batches_come_from_the_right_client():
    data = _toy()
    ds = FedDataset(data, num_clients=10, iid=False, seed=0)
    s = FedSampler(ds, num_workers=3, local_batch_size=4, seed=0)
    ids, batch = s.sample_round(0)
    for w, cid in enumerate(ids):
        client_rows = data["x"][ds.client_indices[cid]]
        for b in range(4):
            assert (batch["x"][w, b] == client_rows).all(axis=1).any()


def test_cifar10_synthetic_fallback_pipeline(tmp_path):
    tr, te, real = load_fed_cifar10(str(tmp_path), num_clients=8, iid=False)
    assert not real
    assert tr.data["x"].shape[1:] == (32, 32, 3)
    # batches stay uint8 end-to-end on the host; normalization happens on
    # device inside the loss (device_normalizer) — 4x less H2D traffic
    assert tr.data["x"].dtype == np.uint8
    s = FedSampler(tr, num_workers=4, local_batch_size=2, augment=augment_batch, seed=0)
    _, batch = s.sample_round(0)
    assert batch["x"].shape == (4, 2, 32, 32, 3)
    assert batch["x"].dtype == np.uint8


def test_femnist_natural_clients(tmp_path):
    tr, te, real = load_fed_emnist(str(tmp_path), num_clients=12)
    assert not real
    assert tr.num_clients == 12
    assert tr.data["x"].shape[1:] == (28, 28, 1)
    # naturally non-IID: each client sees a small subset of the 62 classes
    for ix in tr.client_indices:
        assert len(np.unique(tr.data["y"][ix])) <= 15


def test_femnist_label_noise_reconstructible(tmp_path):
    """label_noise now reaches the synthetic stand-in through Config/CLI
    (ADVICE r5 on data/emnist.py): --label_noise 0 reconstructs the pre-r5
    (r4) noise-free distribution exactly; the default 0.06 flips ~6% of
    labels WITHIN each client's class subset (inputs untouched)."""
    clean_tr, clean_te, _ = load_fed_emnist(
        str(tmp_path), num_clients=10, label_noise=0.0
    )
    noisy_tr, noisy_te, _ = load_fed_emnist(
        str(tmp_path), num_clients=10, label_noise=0.3
    )
    default_tr, _, _ = load_fed_emnist(str(tmp_path), num_clients=10)
    # inputs are bit-identical across noise settings — only labels move
    np.testing.assert_array_equal(clean_tr.data["x"], noisy_tr.data["x"])
    flipped = np.mean(clean_tr.data["y"] != noisy_tr.data["y"])
    # relabels draw uniformly from the client's OWN subset, so a ~1/|C|
    # fraction of flips lands back on the true class: observed rate is
    # p*(1 - E[1/|C|]) ~ 0.3 * 0.885
    assert 0.18 < flipped < 0.3
    # the noise stays inside each client's class subset (non-IID structure
    # — the thing FEMNIST exists to test — is preserved)
    for ix in noisy_tr.client_indices:
        assert set(np.unique(noisy_tr.data["y"][ix])) <= set(
            np.unique(clean_tr.data["y"][ix])
        )
    # the default (0.06) is noisy: r4 reconstruction REQUIRES passing 0
    assert np.any(default_tr.data["y"] != clean_tr.data["y"])

    # BIT-EXACT r4 reconstruction: label_noise=0 must reproduce the
    # pre-r5 generator's draw sequence (this inline oracle is the r4
    # algorithm verbatim — commit ebb267a's _synthetic_femnist)
    rng = np.random.default_rng(42)  # load_fed_emnist's default seed
    protos = rng.normal(0, 1, size=(62, 28, 28, 1)).astype(np.float32)
    xs, ys = [], []
    for _ in range(10):
        style = rng.normal(0, 0.5, size=(28, 28, 1)).astype(np.float32)
        classes = rng.choice(62, size=rng.integers(5, 15), replace=False)
        y = rng.choice(classes, size=120).astype(np.int32)
        x = protos[y] + style + rng.normal(
            0, 0.3, size=(120, 28, 28, 1)
        ).astype(np.float32)
        xs.append(x.astype(np.float32))
        ys.append(y)
    r4_x, r4_y = np.concatenate(xs), np.concatenate(ys)
    # the train FedDataset holds the FULL generated arrays (client_indices
    # carve the train/test views), so the comparison is direct + bit-exact
    np.testing.assert_array_equal(clean_tr.data["y"], r4_y)
    np.testing.assert_array_equal(clean_tr.data["x"], r4_x)


def test_personachat_assembly_contract(tmp_path):
    tr, te, real, vocab = load_fed_personachat(
        str(tmp_path), num_clients=6, num_candidates=2, max_seq_len=64
    )
    assert not real
    d = tr.data
    N, C, T = d["input_ids"].shape
    assert C == 2 and T == 64
    assert d["lm_labels"].shape == (N, C, T)
    assert d["mc_token_ids"].shape == (N, C)
    # only the true (last) candidate carries LM labels
    assert (d["lm_labels"][:, :-1] == -100).all()
    assert (d["lm_labels"][:, -1] != -100).any(axis=-1).all()
    # mc_token points at a real (non-pad) position
    pad = vocab - 1
    for i in range(min(N, 10)):
        for c in range(C):
            t = d["mc_token_ids"][i, c]
            assert d["input_ids"][i, c, t] != pad
    # all ids within vocab
    assert d["input_ids"].max() < vocab


def test_cifar100_loader_synthetic_fallback(tmp_path):
    from commefficient_tpu.data import load_fed_cifar100

    train, test, real = load_fed_cifar100(str(tmp_path), num_clients=10)
    assert not real
    assert train.data["y"].max() == 99 and train.data["y"].min() == 0
    assert train.data["x"].shape[1:] == (32, 32, 3)
    assert train.num_clients == 10


def test_cifar100_loader_real_pickles(tmp_path):
    """The cifar-100-python pickle layout is read when present."""
    import pickle

    import numpy as np

    from commefficient_tpu.data import load_fed_cifar100

    d = tmp_path / "cifar-100-python"
    d.mkdir()
    rng = np.random.default_rng(0)
    for name, n in (("train", 40), ("test", 20)):
        raw = {
            b"data": rng.integers(0, 255, size=(n, 3072), dtype=np.uint8).astype(np.uint8),
            b"fine_labels": rng.integers(0, 100, size=n).tolist(),
        }
        with open(d / name, "wb") as f:
            pickle.dump(raw, f)
    train, test, real = load_fed_cifar100(str(tmp_path), num_clients=4)
    assert real
    assert len(train) == 40 and len(test) == 20


def test_imagenet_imagefolder_decode_and_cache(tmp_path):
    """ImageFolder JPEG tree decodes via PIL and caches to .npy."""
    import numpy as np
    import pytest

    PIL = pytest.importorskip("PIL")
    from PIL import Image

    from commefficient_tpu.data import load_fed_imagenet

    root = tmp_path / "imagenet" / "train"
    rng = np.random.default_rng(0)
    for wnid in ("n01440764", "n01443537"):
        (root / wnid).mkdir(parents=True)
        for i in range(3):
            arr = rng.integers(0, 255, size=(80, 96, 3), dtype=np.uint8)
            Image.fromarray(arr.astype(np.uint8)).save(root / wnid / f"{i}.JPEG")
    train, test, real = load_fed_imagenet(
        str(tmp_path), num_clients=2, iid=True, synthetic_size=64
    )
    assert real
    assert train.data["x"].shape[1:] == (64, 64, 3)
    assert set(np.unique(np.concatenate([train.data["y"], test.data["y"]]))) == {0, 1}
    # the decode was cached for the next run
    assert (tmp_path / "imagenet" / "imagenet_x.npy").exists()
    train2, _, real2 = load_fed_imagenet(str(tmp_path), num_clients=2, iid=True)
    assert real2 and len(train2) == len(train)
