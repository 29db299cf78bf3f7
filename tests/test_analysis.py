"""The invariant linter (commefficient_tpu/analysis/), enforced in tier-1.

Three invariant families:

  1. the REAL package lints clean under all five rules (the gate — a new
     subsystem that violates traced-purity/rng-stream/collective-axis/
     registry-dispatch/exception-hygiene fails the suite);
  2. every rule actually FIRES on a violating fixture (the
     detects-violation discipline scripts/check_mode_dispatch.py
     established: a lint that cannot fail is a vacuous pass), including
     the call-graph fixture proving traced-purity follows helper-function
     indirection and builder closures;
  3. the pragma grammar round-trips: a reasoned pragma suppresses
     exactly its rule on exactly its lines, a reason-less or
     unknown-rule pragma is itself a violation, and the CLI keeps the
     gate-script JSON-summary contract on every exit path.

Fixtures are written to tmp_path as miniature packages and analyzed with
``run_analyzers(root=...)`` — pure AST, nothing is imported or executed.
"""

import json

from commefficient_tpu.analysis import run_analyzers
from commefficient_tpu.analysis.__main__ import main as cli_main


def _lint_dir(tmp_path, files, rules=None):
    """Write {relpath: source} under tmp_path/fixpkg and lint it."""
    root = tmp_path / "fixpkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    findings, _ = run_analyzers(root=root, rules=rules)
    return findings


def _by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# the gate: the real package is clean
# ---------------------------------------------------------------------------


def test_package_lints_clean():
    findings, _ = run_analyzers()
    assert not findings, (
        "the package must lint clean (fix the violation or pragma it "
        "with a reason):\n"
        + "\n".join(f.format(prefix="commefficient_tpu/") for f in findings)
    )


def test_list_rules_matches_analyzers():
    from commefficient_tpu.analysis import analyzer_registry

    reg = analyzer_registry()
    assert set(reg) == {
        "traced-purity", "rng-stream", "collective-axis",
        "registry-dispatch", "exception-hygiene",
    }
    for mod in reg.values():
        assert mod.DESCRIPTION  # --list-rules renders these


# ---------------------------------------------------------------------------
# traced-purity
# ---------------------------------------------------------------------------


def test_purity_detects_direct_violations(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "import numpy as np\n"
        "import jax\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    print(x)\n"
        "    n = np.random.default_rng().normal()\n"
        "    y = float(x)\n"
        "    z = x.item()\n"
        "    return t + n + y + z\n"
    )}, rules=["traced-purity"])
    lines = sorted(f.lineno for f in _by_rule(findings, "traced-purity"))
    assert lines == [7, 8, 9, 10, 11], findings


def test_purity_follows_helper_indirection(tmp_path):
    """The call-graph fixture: the banned call sits TWO hops from the
    root, reached through a plain helper call; an identical unreferenced
    twin must NOT be flagged (reachability, not grep)."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "import jax\n"
        "\n"
        "def deep():\n"
        "    return time.perf_counter()\n"
        "\n"
        "def helper(x):\n"
        "    return x + deep()\n"
        "\n"
        "def lonely(x):\n"
        "    return x + time.perf_counter()\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return helper(x)\n"
    )}, rules=["traced-purity"])
    hits = _by_rule(findings, "traced-purity")
    assert [f.lineno for f in hits] == [5], (
        "expected exactly the reachable deep() hit (line 5), not the "
        f"unreachable lonely() twin: {hits}"
    )


def test_purity_follows_builder_closure_and_shard_map(tmp_path):
    """The round.py shape: shard_map's body closes over a function the
    builder obtained from a maker (`grad_one = make_grad_one(...)`) —
    the alias hop plus the reference edge must connect it."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "from jax.experimental.shard_map import shard_map\n"
        "\n"
        "def make_grad():\n"
        "    def grad_one(x):\n"
        "        return x + time.time()\n"
        "    return grad_one\n"
        "\n"
        "def build(mesh):\n"
        "    grad_one = make_grad()\n"
        "    def body(x):\n"
        "        return grad_one(x)\n"
        "    return shard_map(body, mesh=mesh, in_specs=None,\n"
        "                     out_specs=None)\n"
    )}, rules=["traced-purity"])
    assert [f.lineno for f in _by_rule(findings, "traced-purity")] == [6]


def test_purity_unwraps_wrapper_and_builder_roots(tmp_path):
    """``jit(sentinel.wrap(f, tag))`` traces f just as surely as
    ``jit(f)`` (the parallel/api.py round_idx_fn shape), and
    ``jit(make_step(cfg))`` traces whatever nested def the builder
    returns — both must contribute call-graph roots."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "import jax\n"
        "\n"
        "def wrapped(x):\n"
        "    return x + time.time()\n"
        "\n"
        "def make_step(cfg):\n"
        "    def step(x):\n"
        "        return x + time.perf_counter()\n"
        "    return step\n"
        "\n"
        "def build(sentinel, cfg):\n"
        "    a = jax.jit(sentinel.wrap(wrapped, 'tag'))\n"
        "    b = jax.jit(make_step(cfg))\n"
        "    return a, b\n"
    )}, rules=["traced-purity"])
    lines = sorted(f.lineno for f in _by_rule(findings, "traced-purity"))
    assert lines == [5, 9], findings


def test_purity_pallas_root_and_method_resolution(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "from jax.experimental import pallas as pl\n"
        "\n"
        "class Enc:\n"
        "    def device_encode(self, x):\n"
        "        print('impure')\n"
        "        return x\n"
        "\n"
        "def kernel(ref, o_ref, enc):\n"
        "    o_ref[...] = enc.device_encode(ref[...])\n"
        "\n"
        "def run(x, enc):\n"
        "    return pl.pallas_call(kernel, out_shape=None)(x)\n"
    )}, rules=["traced-purity"])
    assert [f.lineno for f in _by_rule(findings, "traced-purity")] == [5]


def test_purity_resolves_defs_under_control_flow(tmp_path):
    """Definitions gated under control flow (``if cond: def f ... else:
    def f ...``) register in the enclosing scope, so the call graph
    follows them."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "import jax\n"
        "\n"
        "if hasattr(jax, 'new_api'):\n"
        "    def helper(x):\n"
        "        return x + time.time()\n"
        "else:\n"
        "    def helper(x):\n"
        "        return x + time.perf_counter()\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return helper(x)\n"
    )}, rules=["traced-purity"])
    lines = sorted(f.lineno for f in _by_rule(findings, "traced-purity"))
    # whichever branch defined `helper` last wins the name — but BOTH
    # defs are graph nodes, and at least the bound one must be reached
    assert lines and set(lines) <= {6, 9}, findings


def test_purity_follows_relative_imports_from_init(tmp_path):
    """``from . import helpers`` in an __init__.py anchors at the
    package itself (not one level up), so call-graph edges through
    relative imports resolve."""
    findings = _lint_dir(tmp_path, {
        "__init__.py": (
            "import jax\n"
            "from . import helpers\n"
            "\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return helpers.impure(x)\n"
        ),
        "helpers.py": (
            "import time\n"
            "\n"
            "def impure(x):\n"
            "    return x + time.time()\n"
        ),
    }, rules=["traced-purity"])
    assert [(f.path, f.lineno) for f in
            _by_rule(findings, "traced-purity")] == [("helpers.py", 4)]


def test_purity_ignores_host_code_and_static_coercions(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import time\n"
        "import jax\n"
        "\n"
        "def host_loop():  # never traced: free to use the wall clock\n"
        "    return time.time()\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    k = int(3)  # literal coercion: static, legal\n"
        "    return x * k\n"
    )}, rules=["traced-purity"])
    assert not findings, findings


# ---------------------------------------------------------------------------
# rng-stream
# ---------------------------------------------------------------------------


def test_rng_stream_detects_violations(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import numpy as np\n"
        "import jax\n"
        "\n"
        "def bad(seed):\n"
        "    a = np.random.default_rng()\n"
        "    b = np.random.default_rng(42)\n"
        "    c = np.random.default_rng((seed, 0x123))\n"
        "    d = jax.random.key(7)\n"
        "    e = jax.random.fold_in(d, 0x99)\n"
        "    f = np.random.normal(0, 1)\n"
        "    return a, b, c, e, f\n"
    )}, rules=["rng-stream"])
    lines = sorted(f.lineno for f in _by_rule(findings, "rng-stream"))
    assert lines == [5, 6, 7, 8, 9, 10], findings


def test_rng_stream_accepts_declared_streams(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import numpy as np\n"
        "import jax\n"
        "\n"
        "MY_STREAM = 0xFED51\n"
        "\n"
        "def good(seed, cfg, round_idx):\n"
        "    a = np.random.default_rng((seed, MY_STREAM, round_idx))\n"
        "    b = np.random.default_rng(seed)\n"
        "    c = jax.random.key(cfg.seed)\n"
        "    d = jax.random.fold_in(c, MY_STREAM)\n"
        "    return a, b, d\n"
    )}, rules=["rng-stream"])
    assert not findings, findings


def test_rng_stream_reuse_after_single_binding_and_in_lambda(tmp_path):
    """The textbook silent-correlation bug: bind a key once, consume it
    twice — the one initial assignment must not disable the check (only
    a rebinding BETWEEN the draws legalizes them). Lambda bodies are
    scopes too."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "def textbook(seed):\n"
        "    key = jax.random.key(seed)\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.uniform(key, (2,))\n"
        "    return a + b\n"
        "\n"
        "def in_lambda(key):\n"
        "    return lambda: (jax.random.normal(key, (2,))\n"
        "                    + jax.random.uniform(key, (2,)))\n"
    )}, rules=["rng-stream"])
    lines = sorted(f.lineno for f in _by_rule(findings, "rng-stream"))
    assert lines == [6, 11], findings


def test_rng_stream_literal_tag_inside_seedsequence(tmp_path):
    """A literal stream tag must not hide one call deeper — the
    SeedSequence idiom gets the same tuple-literal scan; derived-only
    entropy (the countsketch shape) stays legal."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import numpy as np\n"
        "\n"
        "def bad(seed):\n"
        "    return np.random.default_rng(\n"
        "        np.random.SeedSequence([seed, 0x123])\n"
        "    )\n"
        "\n"
        "def good(seed, row, purpose):\n"
        "    return np.random.default_rng(\n"
        "        np.random.SeedSequence([seed & 0x7FFF, row, purpose])\n"
        "    )\n"
    )}, rules=["rng-stream"])
    assert [f.lineno for f in _by_rule(findings, "rng-stream")] == [5], \
        findings


def test_rng_stream_branch_exclusive_draws_are_legal(tmp_path):
    """One draw per execution path is not reuse: if/else arms (statement
    and ternary) are mutually exclusive; a draw in the SAME arm as an
    earlier one still counts."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "def branched(key, flag):\n"
        "    if flag:\n"
        "        return jax.random.normal(key, (2,))\n"
        "    else:\n"
        "        return jax.random.uniform(key, (2,))\n"
        "\n"
        "def ternary(key, flag):\n"
        "    return (jax.random.normal(key, (2,)) if flag\n"
        "            else jax.random.uniform(key, (2,)))\n"
        "\n"
        "def same_arm(key, flag):\n"
        "    if flag:\n"
        "        a = jax.random.normal(key, (2,))\n"
        "        return a + jax.random.uniform(key, (2,))\n"
        "    return key\n"
    )}, rules=["rng-stream"])
    hits = _by_rule(findings, "rng-stream")
    assert [f.lineno for f in hits] == [16], hits


def test_rng_stream_detects_key_reuse_not_split(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "def reuse(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.normal(key, (2,))\n"
        "    return a + b\n"
        "\n"
        "def split_ok(rng):\n"
        "    rng, r = jax.random.split(rng)\n"
        "    a = jax.random.normal(r, (2,))\n"
        "    rng, r2 = jax.random.split(rng)\n"
        "    return a + jax.random.normal(r2, (2,))\n"
    )}, rules=["rng-stream"])
    hits = _by_rule(findings, "rng-stream")
    assert [f.lineno for f in hits] == [5], hits
    assert "reuse" in hits[0].message or "split" in hits[0].message


# ---------------------------------------------------------------------------
# collective-axis
# ---------------------------------------------------------------------------


def test_collective_axis_detects_literals(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "from functools import partial\n"
        "\n"
        "def attn(x):\n"
        "    return x\n"
        "\n"
        "def bad(x):\n"
        "    a = jax.lax.psum(x, 'workers')\n"
        "    b = jax.lax.all_gather(x, axis_name='workers')\n"
        "    c = jax.lax.psum(x, ('model', 'seq'))\n"
        "    d = partial(attn, axis_name='seq')\n"
        "    e = jax.lax.axis_index('workers')\n"
        "    return a, b, c, d, e\n"
    )}, rules=["collective-axis"])
    lines = sorted(f.lineno for f in _by_rule(findings, "collective-axis"))
    # line 10 carries TWO literals in the tuple
    assert lines == [8, 9, 10, 10, 11, 12], findings


def test_collective_axis_accepts_constants(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "WORKERS = 'workers'\n"
        "SEQ = 'seq'\n"
        "\n"
        "def good(x, axis_name):\n"
        "    a = jax.lax.psum(x, WORKERS)\n"
        "    b = jax.lax.psum(x, (WORKERS, SEQ))\n"
        "    c = jax.lax.all_gather(x, axis_name)\n"
        "    d = jax.lax.axis_index(axis_name=WORKERS)\n"
        "    return a, b, c, d\n"
    )}, rules=["collective-axis"])
    assert not findings, findings


def test_collective_axis_detects_hardcoded_perm_table(tmp_path):
    """ISSUE 14 satellite: integer literals in a ppermute perm table are
    baked device ids — valid for exactly one mesh size. Tables COMPUTED
    from the axis size (the recursive-halving butterfly, the ring shift —
    whose arithmetic constants live inside BinOps, not id slots) stay
    legal."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "WORKERS = 'workers'\n"
        "\n"
        "def bad(x):\n"
        "    return jax.lax.ppermute(\n"
        "        x, WORKERS, perm=[(0, 1), (1, 0)])\n"
        "\n"
        "def bad_positional(x):\n"
        "    return jax.lax.ppermute(x, WORKERS, [(3, 0)])\n"
        "\n"
        "def good(x, axis_size, bit):\n"
        "    butterfly = [(i, i ^ bit) for i in range(axis_size)]\n"
        "    a = jax.lax.ppermute(x, WORKERS, perm=butterfly)\n"
        "    ring = [(i, (i - 1) % axis_size) for i in range(axis_size)]\n"
        "    return jax.lax.ppermute(a, WORKERS, perm=ring)\n"
    )}, rules=["collective-axis"])
    hits = _by_rule(findings, "collective-axis")
    assert sorted(f.lineno for f in hits) == [7, 7, 7, 7, 10, 10], findings
    assert all("perm table" in f.message for f in hits), findings


# ---------------------------------------------------------------------------
# registry-dispatch (ported analyzer; the script shim is covered by
# tests/test_mode_dispatch.py)
# ---------------------------------------------------------------------------


def test_registry_dispatch_on_framework(tmp_path):
    findings = _lint_dir(tmp_path, {
        "train/loop.py": (
            "def f(cfg):\n"
            "    if cfg.mode == 'sketch':\n"
            "        pass\n"
            "    h = {'fixed': 1}[cfg.control_policy]\n"
        ),
        # the home package may dispatch on its own family
        "compress/registry.py": (
            "def g(cfg):\n"
            "    if cfg.mode == 'sketch':\n"
            "        pass\n"
        ),
    }, rules=["registry-dispatch"])
    hits = _by_rule(findings, "registry-dispatch")
    assert [(f.path, f.lineno) for f in hits] == [
        ("train/loop.py", 2), ("train/loop.py", 4),
    ], hits


# ---------------------------------------------------------------------------
# exception-hygiene
# ---------------------------------------------------------------------------


def test_exception_hygiene_detects_and_allows(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except (ImportError, AttributeError):\n"
        "        pass  # narrow swallow: author named what can happen\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        raise RuntimeError('ctx') from e\n"
    )}, rules=["exception-hygiene"])
    lines = sorted(f.lineno for f in _by_rule(findings, "exception-hygiene"))
    assert lines == [4, 8], findings


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------


def test_pragma_suppresses_with_reason(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # lint: allow[exception-hygiene] probe is best-effort\n"
        "    except Exception:\n"
        "        pass\n"
    )})
    assert not findings, findings


def test_pragma_without_reason_is_a_violation(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # lint: allow[exception-hygiene]\n"
        "    except Exception:\n"
        "        pass\n"
    )})
    rules = sorted(f.rule for f in findings)
    # the reason-less pragma is flagged AND does not suppress
    assert rules == ["exception-hygiene", "pragma"], findings


def test_pragma_unknown_rule_is_a_violation(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": (
        "X = 1  # lint: allow[not-a-rule] because reasons\n"
    )})
    assert [f.rule for f in findings] == ["pragma"], findings
    assert "not-a-rule" in findings[0].message


def test_pragma_scopes_to_rule_and_line(tmp_path):
    """A pragma for one rule must not silence another rule on the same
    line, nor the same rule elsewhere in the file."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "def f(x, key):\n"
        "    # lint: allow[collective-axis] wrong rule on purpose\n"
        "    a = jax.random.key(7)\n"
        "    b = jax.lax.psum(x, 'workers')\n"
        "    return a, b\n"
    )})
    rules = sorted(f.rule for f in findings)
    assert rules == ["collective-axis", "rng-stream"], findings


def test_trailing_pragma_does_not_leak_to_next_line(tmp_path):
    """A trailing pragma covers only its own line/statement: a
    violation inserted on the NEXT line must not silently inherit the
    exemption (only standalone comment-line pragmas cover downward)."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import jax\n"
        "\n"
        "def f(x):\n"
        "    a = jax.lax.psum(x, 'w')  "
        "# lint: allow[collective-axis] legacy axis\n"
        "    b = jax.lax.psum(x, 'w')\n"
        "    return a + b\n"
    )}, rules=["collective-axis"])
    assert [f.lineno for f in findings] == [5], findings


def test_pragma_covers_multiline_statement(tmp_path):
    """One pragma atop a multi-line call covers findings on its inner
    lines (the countsketch SeedSequence shape)."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        "import numpy as np\n"
        "\n"
        "def f(seed, row):\n"
        "    # lint: allow[rng-stream] deterministic spec-derived tag\n"
        "    rng = np.random.default_rng(\n"
        "        (seed,\n"
        "         0x123)\n"
        "    )\n"
        "    return rng\n"
    )})
    assert not findings, findings


def test_pragma_in_docstring_is_inert(tmp_path):
    """Quoting the grammar in a docstring/string (as the framework's own
    docs do) must neither suppress nor trip pragma hygiene."""
    findings = _lint_dir(tmp_path, {"mod.py": (
        '"""Docs: use # lint: allow[no-such-rule] here."""\n'
        "MSG = 'also inert: # lint: allow[zzz]'\n"
    )})
    assert not findings, findings


def test_parse_error_is_a_finding(tmp_path):
    findings = _lint_dir(tmp_path, {"mod.py": "def broken(:\n"})
    assert [f.rule for f in findings] == ["parse"], findings


def test_non_utf8_file_is_a_finding_not_a_crash(tmp_path):
    root = tmp_path / "fixpkg"
    root.mkdir()
    (root / "legacy.py").write_bytes(
        b"# -*- coding: latin-1 -*-\n# caf\xe9\nX = 1\n"
    )
    findings, _ = run_analyzers(root=root)
    assert [f.rule for f in findings] == ["parse"], findings
    assert "unreadable" in findings[0].message


# ---------------------------------------------------------------------------
# CLI: exit codes + the JSON summary contract on every exit path
# ---------------------------------------------------------------------------


def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_cli_clean_package(capsys):
    assert cli_main([]) == 0
    s = _last_json(capsys)
    assert s["kind"] == "invariant_lint" and s["clean"] is True
    assert s["findings"] == [] and len(s["rules"]) == 5


def test_cli_violations_exit_1(tmp_path, capsys):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'workers')\n"
    )
    assert cli_main(["--root", str(root)]) == 1
    s = _last_json(capsys)
    assert s["clean"] is False
    assert s["counts"] == {"collective-axis": 1}
    assert s["findings"][0]["path"] == "pkg/bad.py"
    assert s["findings"][0]["line"] == 3


def test_cli_rules_subset_and_json_flag(tmp_path, capsys):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'workers')\n"
    )
    # a subset NOT containing the violated rule passes...
    assert cli_main(["--root", str(root), "--rules", "rng-stream"]) == 0
    s = _last_json(capsys)
    assert s["rules"] == ["rng-stream"] and s["clean"] is True
    # ...and --json emits exactly one line (the summary)
    assert cli_main(["--root", str(root), "--json"]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    assert json.loads(out[0])["clean"] is False


def test_cli_duplicate_rules_run_once(tmp_path, capsys):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'workers')\n"
    )
    assert cli_main(["--root", str(root),
                     "--rules", "collective-axis,collective-axis"]) == 1
    s = _last_json(capsys)
    assert s["counts"] == {"collective-axis": 1}  # not doubled
    assert s["rules"] == ["collective-axis"]


def test_cli_usage_errors_keep_summary_contract(capsys):
    assert cli_main(["--rules", "bogus"]) == 2
    s = _last_json(capsys)
    assert s["kind"] == "invariant_lint" and "bogus" in s["error"]
    assert cli_main(["--root", "/nonexistent-dir-xyz"]) == 2
    s = _last_json(capsys)
    assert "error" in s
    # an empty selection (e.g. --rules "$UNSET_VAR") must be a usage
    # error, not a zero-analyzer vacuous pass
    assert cli_main(["--rules", ""]) == 2
    s = _last_json(capsys)
    assert "no rules" in s["error"]


def test_cli_root_dot_keeps_real_prefix(tmp_path, capsys, monkeypatch):
    """--root . resolves to the directory's real name, not a bare '/'
    prefix that reads as an absolute path."""
    root = tmp_path / "pkgdot"
    root.mkdir()
    (root / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'workers')\n"
    )
    monkeypatch.chdir(root)
    assert cli_main(["--root", ".", "--json"]) == 1
    s = _last_json(capsys)
    assert s["findings"][0]["path"] == "pkgdot/bad.py"


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("traced-purity", "rng-stream", "collective-axis",
                 "registry-dispatch", "exception-hygiene"):
        assert rule in out
    s = json.loads(out.strip().splitlines()[-1])
    assert s["listed"] is True and s["clean"] is True


def test_scripts_lint_shim_matches_module(tmp_path):
    """scripts/lint.py is the same entry point by path."""
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "lint.py"),
         "--json"],
        capture_output=True, text=True, cwd=repo, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    s = json.loads(r.stdout.strip().splitlines()[-1])
    assert s["kind"] == "invariant_lint" and s["clean"] is True


# ---------------------------------------------------------------------------
# the self-application is real: the package carries reasoned pragmas
# ---------------------------------------------------------------------------


def test_package_pragmas_all_carry_reasons():
    """Every pragma in the real package names a known rule and a reason
    (the clean gate implies this, but assert it directly so a pragma
    regression fails with a pointed message), and the known intentional
    exemptions are present — the trace-time sketch constants and the
    flight recorder's best-effort swallows (the run header's device
    identity is no longer one: telemetry/ledger.py fails without it)."""
    from commefficient_tpu.analysis import PackageIndex, analyzer_registry
    from commefficient_tpu.analysis.core import PACKAGE_ROOT

    index = PackageIndex(PACKAGE_ROOT)
    known = set(analyzer_registry())
    all_pragmas = [(f.rel, p) for f in index.files.values()
                   for p in f.pragmas]
    assert all_pragmas, "expected the package to carry lint pragmas"
    for rel, p in all_pragmas:
        assert p.rule in known, f"{rel}:{p.lineno}: unknown rule {p.rule}"
        assert p.reason, f"{rel}:{p.lineno}: pragma without a reason"
    by_file = {rel for rel, _ in all_pragmas}
    assert "ops/countsketch.py" in by_file  # seed-derived trace constants
    assert "telemetry/flight.py" in by_file  # best-effort crash dumps
    assert "telemetry/ledger.py" not in by_file  # identity is mandatory
