"""collective-axis: mesh axes are named by constants, never inline
string literals.

Every collective in the package runs over an axis of the (workers,
model, seq) mesh that ``parallel/mesh.py`` declares as constants
(``WORKERS``/``MODEL``/``SEQ``). The moment a call site writes
``jax.lax.psum(x, "workers")`` instead, two things rot: a mesh-axis
rename (ROADMAP item 1's ``hosts x chips`` 2D mesh will add axes and
re-plumb existing ones) becomes a repo-wide grep for magic strings, and
a typo'd axis (``"worker"``) surfaces only as a runtime NameError deep
inside a traced program instead of an undefined-name at import. The
constants are the single point of truth; this analyzer makes them the
only legal spelling at collective call sites.

Flagged:

  * a string literal (or a tuple/list containing one) passed as the
    axis argument of a known collective — ``psum``/``pmean``/``pmax``/
    ``pmin``/``psum_scatter``/``all_gather``/``all_to_all``/
    ``ppermute``/``pshuffle``/``axis_index``/``pbroadcast``/``pcast``
    (final-name match, so ``jax.lax.psum`` and a bare imported
    ``psum`` both count); the axis argument is the first positional for
    ``axis_index``, the second otherwise, or the ``axis_name=`` kwarg;
  * a string literal passed as an ``axis_name=`` keyword to ANY call —
    the kwarg name is distinctive enough that ``partial(ring_attention,
    axis_name="seq")`` and ``server_update_sharded(..., axis_name=...)``
    are covered without enumerating every wrapper;
  * an integer literal in a source/destination slot of a ``ppermute``
    ``perm=`` table. A perm entry is a (source, destination) DEVICE
    ID, valid only for one hardcoded mesh size — ``perm=[(0, 1),
    (1, 0)]`` silently drops chips the moment the workers axis grows
    past two. Perm tables must be built from the declared axis size
    (the ``axis_size`` parameter / ``mesh.shape[axis]``), the way
    ``ops/collectives/sparse_allreduce.py`` derives its
    recursive-halving schedule (``[(i, i ^ bit) for i in
    range(n_dev)]``) or ``parallel/tensor.py`` its ring shift
    (``[(i, (i - 1) % seq_size) ...]``) — entries COMPUTED from a size
    variable contain no literal in the id slot and stay legal, even
    when the arithmetic uses constants like the ring's ``- 1``.

Declaring the constant itself (``WORKERS = "workers"`` in
``parallel/mesh.py``) is an assignment, not a call, and stays legal —
as do ``PartitionSpec`` strings, which name shardings, not collective
axes.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from commefficient_tpu.analysis.core import (
    Finding,
    PackageIndex,
    final_name,
)

RULE = "collective-axis"
DESCRIPTION = (
    "collective axis names must be declared mesh-axis constants "
    "(WORKERS/MODEL/SEQ), never inline string literals"
)

COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "axis_index", "pbroadcast",
    "pcast",
})


def _literal_axes(expr: ast.AST):
    """The string-literal leaves of an axis expression (handles single
    strings and tuple/list axis groups like ``(WORKERS, "seq")``)."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        yield expr
    elif isinstance(expr, (ast.Tuple, ast.List)):
        for el in expr.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                yield el


def _axis_arg(call: ast.Call) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value
    pos = 0 if final_name(call.func) == "axis_index" else 1
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _perm_arg(call: ast.Call) -> Optional[ast.AST]:
    """``ppermute``'s perm table: the ``perm=`` kwarg or the third
    positional (``ppermute(x, axis_name, perm)``)."""
    for kw in call.keywords:
        if kw.arg == "perm":
            return kw.value
    if len(call.args) > 2:
        return call.args[2]
    return None


def _perm_int_literals(expr: ast.AST):
    """Integer literals in the id slots of a perm table: direct elements
    of any tuple/list under the perm expression (``(0, 1)`` is a baked
    device id; ``(i, (i - 1) % n)`` computes its ids from a size
    variable — the shift constant lives inside a BinOp, not an id slot,
    and is legal). Booleans are Constant ints in the ast; they can't be
    device ids from a hardcoded table, so they're skipped."""
    for node in ast.walk(expr):
        if not isinstance(node, (ast.Tuple, ast.List)):
            continue
        for el in node.elts:
            if (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)
                    and not isinstance(el.value, bool)):
                yield el


def analyze(index: PackageIndex) -> List[Finding]:
    findings: List[Finding] = []
    for sf in index.trees():
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = final_name(node.func)
            checked = None
            if name in COLLECTIVES:
                checked = _axis_arg(node)
            else:
                for kw in node.keywords:
                    if kw.arg == "axis_name":
                        checked = kw.value
                        break
            if name == "ppermute":
                perm = _perm_arg(node)
                if perm is not None:
                    for lit in _perm_int_literals(perm):
                        findings.append(sf.finding(
                            RULE, lit.lineno,
                            f"integer literal {lit.value!r} in a ppermute "
                            "perm table — perm entries are device ids, "
                            "valid only for one hardcoded mesh size; "
                            "build the table from the declared axis size "
                            "(e.g. [(i, i ^ bit) for i in "
                            "range(axis_size)])",
                        ))
            if checked is None:
                continue
            for lit in _literal_axes(checked):
                findings.append(sf.finding(
                    RULE, lit.lineno,
                    f"inline axis-name literal {lit.value!r} at a "
                    f"collective call ({name or 'axis_name kwarg'}) — "
                    "use the declared mesh-axis constant "
                    "(parallel.mesh.WORKERS/MODEL/SEQ)",
                ))
    return findings
