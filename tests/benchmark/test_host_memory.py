"""What the correctness path holds on the host after the window: the
comparison reads the flat vectors in pieces and the reference's rounds keep
round 1's gradient and the parameters after the first and the last round,
and both give what their whole-vector forms gave. The whole-vector forms are
kept here as the oracles."""

import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.reference import round as ref_round
from benchmark.reference.sketch import top_k_dense

LR = 0.01


def _leaves():
    """~8M coordinates over 40 leaves of uneven size: two larger than a
    piece of ``compare.CHUNK``, some of a handful of numbers."""
    sizes = [2_500_000, 1_300_001, 7, 3, 128] + [
        (37_813 * (k + 1) ** 2) % 400_000 + 1 for k in range(35)]
    out, at = [], 0
    for i, n in enumerate(sizes):
        out.append((f"layer_{i}/w", at, at + n))
        at += n
    return out, at


def _whole_readings(prog, ref, leaves):
    """``compare.readings`` as it read the whole vectors at once."""
    def leaf_norms(v):
        return np.array([np.sqrt(np.sum(np.square(v[a:b], dtype=np.float64)))
                         for _, a, b in leaves])

    def worst_of(p, r, keep=None):
        floor = np.median(r)
        gap = np.abs(p - r) / np.maximum(np.maximum(r, floor), 1e-30)
        if keep is not None:
            gap = np.where(keep, gap, 0.0)
        i = int(np.argmax(gap))
        return float(gap[i]), i

    out, worst = {}, {}
    for i, (a, b) in enumerate(zip(prog.losses, ref.losses)):
        out[f"loss_{i + 1}"] = abs(a - b) / abs(b)
    g_ref = leaf_norms(ref.grad1)
    if prog.bank1 is not None and prog.bank1.ndim == 2:
        rows = lambda t: np.sqrt(np.sum(np.square(t, dtype=np.float64), axis=1))  # noqa: E731
        out["grad_1"], i = worst_of(rows(prog.bank1), rows(ref.table1))
        worst["grad_1"] = f"row {i}"
        out["grad_1_diff"] = float(np.sqrt(np.sum(np.square(prog.bank1 - ref.table1, dtype=np.float64))
                                           / np.sum(np.square(ref.table1, dtype=np.float64))))
    else:
        g = prog.bank1 if prog.bank1 is not None else (prog.p0 - prog.p1) / np.float32(prog.lr)
        out["grad_1"], i = worst_of(leaf_norms(g), g_ref)
        worst["grad_1"] = leaves[i][0]
        out["grad_1_diff"] = float(np.sqrt(np.sum(np.square(g - ref.grad1, dtype=np.float64))
                                           / np.sum(np.square(ref.grad1, dtype=np.float64))))
    moved = g_ref >= 1e-3 * np.median(g_ref)
    d_prog = leaf_norms(prog.p3 - prog.p0)
    d_ref = leaf_norms(ref.params[-1] - prog.p0)
    out["delta_3"], i = worst_of(d_prog, d_ref, moved)
    worst["delta_3"] = leaves[i][0]
    order = np.argsort(-d_ref)
    carried = np.cumsum(np.square(d_ref[order])) / max(np.sum(np.square(d_ref)), 1e-300)
    heavy = np.zeros(len(leaves), bool)
    heavy[order[: int(np.searchsorted(carried, 0.9)) + 1]] = True
    out["delta_3_heavy"], i = worst_of(d_prog, d_ref, heavy & moved)
    worst["delta_3_heavy"] = leaves[i][0]
    whole = lambda v: np.sqrt(np.sum(np.square(v)))  # noqa: E731
    out["delta_3_whole"] = float(abs(whole(d_prog) - whole(d_ref)) / max(whole(d_ref), 1e-30))
    return {"gaps": out, "worst": worst}


def _followed(kind, seed=0):
    """A program's three rounds and a reference's, close but not equal, on
    leaves of scales from 1e-7 to 1 (a few too small to count as moved)."""
    leaves, d = _leaves()
    rng = np.random.default_rng(seed)
    scale = np.concatenate([np.full(b - a, 10.0 ** -(i % 8), np.float32)
                            for i, (_, a, b) in enumerate(leaves)])
    grad = (rng.standard_normal(d, np.float32) * scale).astype(np.float32)
    g_prog = grad * (1 + 0.01 * rng.standard_normal(d, np.float32))
    p0 = rng.standard_normal(d, np.float32)
    p3_ref = p0 - np.float32(3 * LR) * grad
    p3 = p0 - np.float32(3 * LR) * g_prog * np.float32(1.002)
    p1 = p0 - np.float32(LR) * g_prog
    bank1, table1 = None, None
    if kind == "banked":
        bank1 = g_prog
    elif kind == "sketch":
        table1 = rng.standard_normal((5, 4096), np.float32)
        bank1 = table1 * (1 + 0.02 * rng.standard_normal(table1.shape, np.float32))
    prog = compare.Followed([2.5, 2.4, 2.3], p0, p1, p3, bank1, LR)
    ref = ref_round.Trace([2.5001, 2.4002, 2.2997], grad, table1, [p0 - np.float32(LR) * grad, p3_ref])
    return prog, ref, leaves, d


@pytest.mark.parametrize("kind,change_first", [
    ("dense", False), ("dense", True), ("banked", False), ("sketch", False)])
def test_readings_in_pieces_equal_the_whole_vector_form(kind, change_first):
    prog, ref, leaves, d = _followed(kind)
    want = _whole_readings(prog, ref, leaves)
    if change_first:
        # the program's change taken before the reference runs, its p3 let go
        prog = prog._replace(p3=None, change=compare.changes(prog.p3, prog.p0, leaves))
    tracemalloc.start()
    try:
        got = compare.readings(prog, ref, leaves)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert got["worst"] == want["worst"]
    assert set(got["gaps"]) == set(want["gaps"])
    for name, value in want["gaps"].items():
        assert got["gaps"][name] == pytest.approx(value, rel=1e-9, abs=1e-300), name
    assert 0 < got["gaps"]["grad_1_diff"] < 1 and got["gaps"]["delta_3"] > 1e-3
    largest = max(b - a for _, a, b in leaves)
    assert peak < 4 * d + 16 * largest, peak


def _whole_run_rounds(client_grad, p0, rounds, algo):
    """``round.run_rounds`` as it kept every round's parameters, with the
    servers' step as it made a new vector at each operation."""
    p = np.asarray(p0, np.float32).copy()
    d = p.size
    server = ref_round.resolve(algo.server)(d, algo.spec)
    if isinstance(server, ref_round.DenseServer):
        m = np.zeros(d, np.float32)

        def step(g, lr):
            nonlocal m
            m = server.rho * m + g
            return lr * m, None
    else:
        def step(g, lr):
            sk = server
            table = sk.sk.sketch(g)
            sk.m = sk.rho * sk.m + table
            sk.e = sk.e + lr * sk.m
            delta = top_k_dense(sk.sk.estimate(sk.e), sk.k)
            nz = np.flatnonzero(delta)
            sk.e = sk.e - sk.sk.sketch_sparse(nz, delta[nz])
            return delta, table
    lr = np.float32(algo.lr)
    losses, params, grad1, table1 = [], [], None, None
    for batch in rounds:
        W = next(iter(batch.values())).shape[0]
        p_dev = jnp.asarray(p)
        total, loss_sum = jnp.zeros(d, jnp.float32), 0.0
        for w in range(W):
            g, loss = client_grad(p_dev, {k: jnp.asarray(v[w]) for k, v in batch.items()})
            total = total + g
            loss_sum += float(loss)
        g = np.asarray(total) / np.float32(W)
        losses.append(loss_sum / W)
        delta, table = step(g, lr)
        p = p - delta
        if grad1 is None:
            grad1, table1 = g, table
        params.append(p.copy())
    return ref_round.Trace(losses, grad1, table1, params)


@jax.jit
def _stub_grad(p, batch):
    """A client's clipped gradient and loss, standing in for ``jax.grad``."""
    s, b = batch["x"][0], batch["x"][1]
    return jnp.sin(p * s) * 0.1 + b * 1e-3, jnp.mean(jnp.cos(p[:4096] * s)) + b


@pytest.mark.parametrize("server,spec,d,first,vectors", [
    ("DenseServer", {"rho": 0.0}, 8_000_000, True, 5),
    ("DenseServer", {"rho": 0.0}, 8_000_000, False, 4),
    ("DenseServer", {"rho": 0.9}, 8_000_000, True, 6),    # the momentum is the server's own
    ("SketchServer", {"rho": 0.9, "rows": 5, "cols": 20_000, "k": 5_000, "hash_seed": 42},
     400_000, True, None),
])
def test_run_rounds_keeps_what_is_read_and_equals_the_whole_form(server, spec, d, first, vectors):
    algo = ref_round.Algo(lr=LR, server=f"benchmark.reference.round:{server}", spec=spec)
    rng = np.random.default_rng(3)
    p0 = rng.standard_normal(d, np.float32)
    rounds = [{"x": rng.standard_normal((2, 2)).astype(np.float32)} for _ in range(3)]
    want = _whole_run_rounds(_stub_grad, p0, rounds, algo)
    seen = []
    tracemalloc.start()
    try:
        got = ref_round.run_rounds(_stub_grad, p0, rounds, algo, on_round=seen.append,
                                   first=first)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert seen == [1, 2, 3]
    assert got.losses == want.losses
    assert np.array_equal(got.grad1, want.grad1)
    assert (got.table1 is None) == (want.table1 is None)
    if got.table1 is not None:
        assert np.array_equal(got.table1, want.table1)
    assert len(got.params) == 2
    assert np.array_equal(got.params[-1], want.params[-1])
    if first:
        assert np.array_equal(got.params[0], want.params[0])
        assert not np.array_equal(got.params[0], got.params[-1])
    else:
        assert got.params[0] is None
    if vectors is not None:
        assert peak <= vectors * d * 4, peak / (d * 4)
