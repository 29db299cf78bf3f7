"""rng-stream: every random stream must be declared, derived, and
consumed exactly once.

The repo's randomness is layered — fedsim availability draws, the
client sampler, DP noise, powersgd's sketch matrices, data augmentation
— and the resume/replay contracts (resilience/, asyncfed/) hold only
because each layer's stream is (a) deterministic given ``cfg.seed`` and
(b) disjoint from every other layer's. The conventions that keep that
true (established by the fedsim PR's ``FEDSIM_STREAM`` tag):

  * numpy: ``np.random.default_rng((seed, STREAM, ...))`` — a
    tuple-seeded generator whose stream tag is a *declared module-level
    constant*, or a generator seeded from a seed variable that the
    caller derived. Never ``default_rng()`` (OS entropy: two replays of
    the same round disagree), never an inline literal seed or stream
    tag (two modules picking the same magic number silently collide,
    and nothing greppable declares the stream exists).
  * jax: keys come from ``jax.random.key(seed_expr)`` /
    ``fold_in(key, tag)`` where literal tags are declared constants,
    and a consumed key is never reused — every reuse makes two "independent"
    draws identical (the classic silent-correlation bug), so a key
    feeding two draws must be ``split`` / ``fold_in``-derived first.
  * never the global stdlib/numpy module streams (``random.random()``,
    ``np.random.seed``/``np.random.normal``): global state is
    invisible to checkpointing and shared across subsystems.

Violations flagged per call site:

  * ``default_rng()`` with no seed;
  * ``default_rng(<int literal>)`` or a tuple/list seed containing a
    bare int literal (declare ``X_STREAM = 0x...`` and use the name);
  * ``jax.random.key(<literal>)`` / ``PRNGKey(<literal>)`` /
    ``fold_in(k, <literal>)``;
  * stdlib ``random.*`` and module-level ``np.random.<draw>`` /
    ``np.random.seed``;
  * a bare name used as the key argument of two or more jax.random
    draw calls in one function scope with no rebinding in between.
"""

from __future__ import annotations

import ast
from typing import List

from commefficient_tpu.analysis.core import (
    Finding,
    PackageIndex,
    dotted_path,
    module_imports,
)

RULE = "rng-stream"
DESCRIPTION = (
    "rng seeds derive from declared stream constants/tuples; no bare "
    "default_rng(), inline literal seeds, global streams, or key reuse "
    "without split/fold_in"
)

# jax.random draws that CONSUME a key (first positional arg).
# split/fold_in/key/PRNGKey are derivation, not consumption.
KEY_CONSUMERS = frozenset({
    "normal", "uniform", "categorical", "bernoulli", "bits",
    "permutation", "choice", "gumbel", "truncated_normal", "randint",
    "exponential", "laplace", "poisson", "rademacher", "ball",
    "dirichlet", "beta", "gamma", "cauchy", "orthogonal", "t",
})

# numpy.random attributes that are NOT the module-level global stream
_NP_RANDOM_OK = frozenset({
    "default_rng", "SeedSequence", "Generator", "BitGenerator", "PCG64",
    "Philox", "SFC64", "MT19937",
})


def _is_int_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return True
    # a negated literal (-1) parses as UnaryOp(USub, Constant)
    return (isinstance(node, ast.UnaryOp)
            and isinstance(node.operand, ast.Constant)
            and isinstance(node.operand.value, int))


def _check_seed_value(sf, seed: ast.AST, out: List[Finding]) -> None:
    """Literal checks on one seed expression — shared by default_rng's
    direct argument and SeedSequence's entropy list, so a literal
    stream tag cannot hide one call deeper."""
    if _is_int_literal(seed):
        out.append(sf.finding(
            RULE, seed.lineno,
            "inline literal seed — declare a module-level stream "
            "constant (e.g. X_STREAM = 0x...) and seed from it",
        ))
    elif isinstance(seed, (ast.Tuple, ast.List)):
        for el in seed.elts:
            if _is_int_literal(el):
                out.append(sf.finding(
                    RULE, el.lineno,
                    "inline literal stream tag in a tuple seed — declare "
                    "a module-level *_STREAM constant so streams are "
                    "greppable and provably disjoint",
                ))


def _check_seed_expr(sf, call: ast.Call, out: List[Finding]) -> None:
    """The seed argument of default_rng / key / PRNGKey."""
    if not call.args and not call.keywords:
        out.append(sf.finding(
            RULE, call.lineno,
            "bare default_rng() draws OS entropy — seed it from cfg.seed "
            "and a declared stream constant so replay/resume stay exact",
        ))
        return
    seed = call.args[0] if call.args else call.keywords[0].value
    _check_seed_value(sf, seed, out)


def _mutually_exclusive(path_a, path_b) -> bool:
    """Two branch paths are mutually exclusive when they sit in
    different arms of some shared if/else — only one of them can
    execute, so the key is consumed once per run, not reused."""
    arms = dict(path_a)
    return any(k in arms and arms[k] != arm for k, arm in path_b)


def _check_function_key_reuse(sf, fn: ast.AST, imports: dict,
                              out: List[Finding]) -> None:
    """Within one function scope: a bare-name key feeding >= 2 jax
    draws that can execute in the SAME run, with no rebinding of the
    name BETWEEN the two draws, is a reuse — so the textbook bug
    (``key = jax.random.key(seed)`` once, then two draws) fires, while
    the correct ``rng, r = split(rng)``-between-draws idiom stays
    legal. "Between" is judged by line order (a CFG would be sounder;
    straight-line rng code makes line order the honest approximation).
    Draws in different arms of one if/else (statement or ternary) are
    mutually exclusive and legal."""
    rebinds, uses = {}, {}

    def visit(node, path):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # nested scope: gets its own pass
        if isinstance(node, (ast.If, ast.IfExp)):
            visit(node.test, path)
            body = node.body if isinstance(node.body, list) else [node.body]
            orelse = (node.orelse if isinstance(node.orelse, list)
                      else [node.orelse] if node.orelse is not None else [])
            for n in body:
                visit(n, path + ((id(node), "body"),))
            for n in orelse:
                visit(n, path + ((id(node), "orelse"),))
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                             ast.NamedExpr, ast.For)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name):
                        rebinds.setdefault(leaf.id, []).append(node.lineno)
        elif isinstance(node, ast.Call):
            dotted = dotted_path(node.func, imports) or ""
            name = dotted.rsplit(".", 1)[-1] if dotted else (
                node.func.id if isinstance(node.func, ast.Name) else
                node.func.attr if isinstance(node.func, ast.Attribute)
                else ""
            )
            if name in KEY_CONSUMERS and (
                dotted.startswith("jax.random.") or not dotted
            ):
                # unresolved bare/attr names only count when they look
                # like jax.random draws (`jrandom.normal`, `random.normal`
                # via `from jax import random`) — numpy draws on a
                # GENERATOR object (rng.normal) must not count, so bare
                # attribute calls need a key-looking first argument
                if node.args and isinstance(node.args[0], ast.Name):
                    if dotted or _looks_like_key(node.args[0].id):
                        uses.setdefault(node.args[0].id, []).append(
                            (node, path)
                        )
        for child in ast.iter_child_nodes(node):
            visit(child, path)

    for child in ast.iter_child_nodes(fn):
        visit(child, ())

    for name, calls in uses.items():
        if len(calls) < 2:
            continue
        calls = sorted(calls, key=lambda c: (c[0].lineno, c[0].col_offset))
        rebind_lines = sorted(rebinds.get(name, []))
        flagged = set()
        for j, (cj, pj) in enumerate(calls):
            for ci, pi in calls[:j]:
                if _mutually_exclusive(pi, pj):
                    continue
                if any(ci.lineno < ln <= cj.lineno for ln in rebind_lines):
                    continue  # rebound between the draws: the legal idiom
                if id(cj) not in flagged:
                    flagged.add(id(cj))
                    out.append(sf.finding(
                        RULE, cj.lineno,
                        f"rng key {name!r} consumed by multiple draws "
                        "in one scope without split/fold_in — reused "
                        "keys make 'independent' draws identical",
                    ))
                break


def _looks_like_key(name: str) -> bool:
    low = name.lower()
    return any(t in low for t in ("key", "rng", "seed"))


def analyze(index: PackageIndex) -> List[Finding]:
    findings: List[Finding] = []
    for sf in index.trees():
        imports = module_imports(sf.tree)
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                # lambdas are scopes too — a two-draw lambda body is the
                # same silent-correlation bug as in a def
                _check_function_key_reuse(sf, node, imports, findings)
            if not isinstance(node, ast.Call):
                continue
            dotted = dotted_path(node.func, imports)
            if dotted is None:
                continue
            if dotted == "numpy.random.default_rng":
                _check_seed_expr(sf, node, findings)
            elif dotted == "numpy.random.SeedSequence":
                # a literal stream tag must not hide one call deeper:
                # SeedSequence([seed, 0x123]) is the same violation as
                # default_rng((seed, 0x123))
                if node.args:
                    _check_seed_value(sf, node.args[0], findings)
            elif dotted.startswith("numpy.random.") and \
                    dotted.rsplit(".", 1)[-1] not in _NP_RANDOM_OK:
                findings.append(sf.finding(
                    RULE, node.lineno,
                    f"module-level numpy global stream {dotted} — use a "
                    "tuple-seeded default_rng generator instead",
                ))
            elif dotted == "random" or dotted.startswith("random."):
                findings.append(sf.finding(
                    RULE, node.lineno,
                    f"stdlib global rng {dotted} — invisible to "
                    "checkpoint/replay; use a seeded generator",
                ))
            elif dotted in ("jax.random.key", "jax.random.PRNGKey"):
                if node.args and _is_int_literal(node.args[0]):
                    findings.append(sf.finding(
                        RULE, node.lineno,
                        "inline literal jax key seed — declare a "
                        "module-level stream constant and seed from it",
                    ))
            elif dotted == "jax.random.fold_in":
                if len(node.args) >= 2 and _is_int_literal(node.args[1]):
                    findings.append(sf.finding(
                        RULE, node.lineno,
                        "inline literal fold_in stream tag — declare a "
                        "module-level *_STREAM constant",
                    ))
    return findings
