"""The comparison that decides ``correct``: what the timed path left behind
over its first three rounds against what the plain reference leaves.

Numbers compared (each a gap, 0 = equal; each held to its own limit from
``benchmark/limits/<cell>.json``):

- ``loss_1`` .. ``loss_3``: ``|program - reference| / |reference|`` of each
  round's mean client loss.
- ``grad_1``: the first round's aggregate as the server gets it, read from
  the program's state after one round — the momentum bank where the mix has
  one (sketch mode: the table, row by row), else ``(p0 - p1) / lr`` — worst
  leaf (or row) by ``| ||program|| - ||reference|| |`` over the larger of
  the reference's norm of that leaf and of the median leaf.
- ``grad_1_diff``: the same aggregate, whole: ``||program - reference|| /
  ||reference||`` (the table in sketch mode, the flat gradient otherwise).
  A norm of the difference, which the dense aggregate allows (no part of it
  is all but zero) and which, unlike a gap of norms, sees a wrong direction:
  each client's gradient is clipped to unit norm, so norms alone say little.
- ``delta_3``: the change of the parameters over the three rounds, worst
  leaf by the same measure. Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out: they move by round-off alone.
- ``delta_3_heavy``: the same, over the leaves that carry the change (the
  largest of the reference's change, down to nine tenths of its squared
  norm); ``delta_3_whole``: the gap of the whole vector's change. A sparse
  update (top-k) puts a handful of coordinates into a small leaf, and which
  ones is decided at the selection threshold, so the worst small leaf is
  noise there; these two are what such a cell's limits name.
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Followed(NamedTuple):
    """What a run of three rounds left, program or stand-in."""

    losses: list
    p0: np.ndarray
    p1: np.ndarray
    p3: np.ndarray
    bank1: Optional[np.ndarray]   # momentum bank after round 1, or None
    lr: float


def from_reference(trace, p0, lr, banked: bool) -> Followed:
    """The reference's own trace in the program's place (controls, faults)."""
    bank = None
    if banked:
        bank = trace.table1 if trace.table1 is not None else trace.grad1
    return Followed(trace.losses, p0, trace.params[0], trace.params[-1], bank, lr)


def _leaf_norms(v, leaves):
    return np.array([np.sqrt(np.sum(np.square(v[a:b], dtype=np.float64)))
                     for _, a, b in leaves])


def _worst(prog, ref, keep=None):
    floor = np.median(ref)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def readings(prog: Followed, ref, leaves) -> dict:
    """``{number: gap}`` plus ``worst``: which leaf each worst gap sits on.
    ``ref`` is a ``reference.round.Trace``; ``leaves`` is ``[(name, start,
    stop)]`` over the flat vector."""
    out, worst = {}, {}
    for i, (a, b) in enumerate(zip(prog.losses, ref.losses)):
        out[f"loss_{i + 1}"] = (abs(a - b) / abs(b)) if np.isfinite(a) else float("inf")
    g_ref = _leaf_norms(ref.grad1, leaves)
    if prog.bank1 is not None and prog.bank1.ndim == 2:
        rows = lambda t: np.sqrt(np.sum(np.square(t, dtype=np.float64), axis=1))  # noqa: E731
        out["grad_1"], i = _worst(rows(prog.bank1), rows(ref.table1))
        worst["grad_1"] = f"row {i}"
        out["grad_1_diff"] = float(np.sqrt(np.sum(np.square(prog.bank1 - ref.table1, dtype=np.float64))
                                           / np.sum(np.square(ref.table1, dtype=np.float64))))
    else:
        g = prog.bank1 if prog.bank1 is not None else (prog.p0 - prog.p1) / np.float32(prog.lr)
        out["grad_1"], i = _worst(_leaf_norms(g, leaves), g_ref)
        worst["grad_1"] = leaves[i][0]
        out["grad_1_diff"] = float(np.sqrt(np.sum(np.square(g - ref.grad1, dtype=np.float64))
                                           / np.sum(np.square(ref.grad1, dtype=np.float64))))
    moved = g_ref >= 1e-3 * np.median(g_ref)
    d_prog = _leaf_norms(prog.p3 - prog.p0, leaves)
    d_ref = _leaf_norms(ref.params[-1] - prog.p0, leaves)
    out["delta_3"], i = _worst(d_prog, d_ref, moved)
    worst["delta_3"] = leaves[i][0]
    # the same change over the leaves that carry it: the largest leaves of
    # the reference's change, down to nine tenths of its squared norm
    order = np.argsort(-d_ref)
    carried = np.cumsum(np.square(d_ref[order])) / max(np.sum(np.square(d_ref)), 1e-300)
    heavy = np.zeros(len(leaves), bool)
    heavy[order[: int(np.searchsorted(carried, 0.9)) + 1]] = True
    out["delta_3_heavy"], i = _worst(d_prog, d_ref, heavy & moved)
    worst["delta_3_heavy"] = leaves[i][0]
    whole = lambda v: np.sqrt(np.sum(np.square(v)))  # noqa: E731
    out["delta_3_whole"] = float(abs(whole(d_prog) - whole(d_ref)) / max(whole(d_ref), 1e-30))
    out = {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}
    return {"gaps": out, "worst": worst}


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def judge(gaps: dict, limits: dict):
    """``(correct, [(name, gap, limit)])``: every number a limit names must
    be there and within it."""
    rows = [(name, gaps.get(name, float("inf")), lim) for name, lim in limits.items()]
    return all(g <= lim for _, g, lim in rows), rows
