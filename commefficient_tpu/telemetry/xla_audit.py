"""Compiled-graph performance audit — measure the round from the artifact.

Everything perf-shaped the repo asserted before this module was *analytic*:
``CommLedger`` bytes come from ``bytes_per_round`` arithmetic, MFU
from a hand-maintained FLOPs model, and the PR-6 "no dense decode, every
all-gather <= W*k" discipline from a test-time HLO grep. FetchSGD's whole
claim is a communication/computation trade (arXiv:2007.07682), so the
system must be able to read that trade off the COMPILED round — what XLA
actually scheduled, moved, and allocated — and fail loudly when a future
PR regresses it. Three pieces live here:

  * ``CompiledRoundAudit`` — capture ``Compiled.cost_analysis()`` (FLOPs,
    bytes accessed, transcendentals) and ``memory_analysis()`` (argument/
    output/temp/alias bytes -> a derived peak-HBM figure) for the compiled
    round, walk its HLO for collectives, cross-check those against the
    CommLedger's analytic accounting + the PR-6 W*k bound, and write a
    versioned ``perf_report.json`` run artifact
    (scripts/check_telemetry_schema.py validates it; schema v3).
  * ``RetraceSentinel`` — a trace-time counter on the jitted round
    (``xla/retraces`` scalar; optional ``--max_retraces`` hard fail naming
    the offending argument-signature diff). Silent mid-run recompiles are
    the classic invisible perf killer: a weak-type or dtype drift in one
    argument recompiles a minutes-long XLA program with no visible signal
    but the wall clock.
  * ``chip_peak_flops`` / ``audited_mfu`` — the hardware peak table and
    the audited-FLOPs MFU. Nothing the driver runs reads either (the
    benchmark has its own ``benchmark/peaks.json``); ROADMAP D7.

Degradation contract: every analysis is optional per backend/jax version —
where jax 0.4.37 (this container) or the platform doesn't expose one, the
report carries nulls plus an ``unavailable_reason`` instead of crashing
(observability must never kill a run).

Accounting semantics of the collective cross-check: the ledger counts the
per-client *uplink* (client -> server link bytes); the compiled HLO's
collectives are the on-chip ICI realization of the same aggregation. For
sketch mode the two coincide (the psum moves exactly the [r, c] table each
link), so ``delta_bytes`` is near zero up to scalar psums — and the
sharded decode's KNOWN extra traffic (the zero-HH error-feedback re-sketch
psum + the <= W*k candidate gathers) is folded into ``tolerance_bytes``.
Modes whose device transmit is dense-shaped (local_topk/true_topk: the
compression is a *link* property the ICI psum doesn't model) report an
honestly large delta with ``within_tolerance`` false; the checker enforces
the invariant only where it is a design claim — the sketch sharded-decode
path.
"""

from __future__ import annotations

import json
import os
import re
from contextlib import contextmanager
from typing import Any, Dict, Optional

# Peak dense-matmul throughput (bf16 FLOP/s) per chip kind — the MFU
# denominator.
# A chip that is not in the table is an error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5": 459e12, "TPU v4": 275e12}

# scalar-collective slop for the ledger-vs-HLO cross-check: loss/aux/diag
# psums and the sharded threshold's bisection collectives are all scalars,
# a few bytes each — one page covers every observed round comfortably
# while staying far below any leaked d-sized collective.
SCALAR_COLLECTIVE_SLOP_BYTES = 4096


def chip_peak_flops() -> tuple:
    """(peak bf16 FLOP/s, device_kind) of the first device. Raises
    ValueError for a ``device_kind`` outside ``PEAK_FLOPS``: a utilization
    computed against another chip's peak is a wrong number, not a
    degraded one."""
    import jax

    kind = jax.devices()[0].device_kind
    # longest key first: "TPU v5" must not shadow "TPU v5 lite" (v5e)
    for name in sorted(PEAK_FLOPS, key=len, reverse=True):
        if name in kind:
            return PEAK_FLOPS[name], kind
    raise ValueError(
        f"no peak-FLOP/s entry for device_kind {kind!r} (known: "
        f"{sorted(PEAK_FLOPS)}); add the chip to PEAK_FLOPS with its source"
    )


def audited_mfu(flops_per_round: float, sec_per_round: float,
                peak_flops: float, n_chips: int = 1) -> float:
    """MFU from the COMPILED round's own FLOP count (cost_analysis), not
    the hand model. NB ``Compiled.cost_analysis()`` reports the PER-DEVICE
    SPMD module's FLOPs, so per-device figures pair with ``n_chips=1``
    and one chip's peak; pass ``n_chips`` only when
    ``flops_per_round`` is a whole-program total from some other source."""
    return flops_per_round / (sec_per_round * peak_flops * max(n_chips, 1))


# ---------------------------------------------------------------------------
# HLO collective audit
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u4": 1, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute")

# one HLO instruction line: "%name = <result shapes> <op>(" where the op
# may be the async -start form ( -done lines carry no shape work of their
# own and are skipped so async pairs aren't double-counted)
_COLL_LINE_RE = re.compile(
    r"=\s*(?P<lhs>[^=]*?)\s*(?P<op>" + "|".join(COLLECTIVE_OPS) +
    r")(?P<async>-start)?\("
)
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+[0-9]+[a-z0-9]*|pred)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(dt: str, dims: str) -> Optional[tuple]:
    """(n_elems, n_bytes) for one ``dtype[dims]`` result shape."""
    size = _DTYPE_BYTES.get(dt)
    if size is None:
        return None
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n, n * size


def collective_audit(hlo_text: str) -> Dict[str, Any]:
    """Walk a compiled module's text for collective ops.

    Returns ``{"ops": {op: {"count", "bytes"}}, "total_bytes",
    "max_all_gather_elems", "max_all_reduce_elems"}`` — bytes are the
    per-chip RESULT bytes of each collective (variadic/tuple-shaped
    all-reduces sum their components), counted once per static HLO
    occurrence; ``max_all_gather_elems`` is the largest single BUFFER any
    all-gather returns (None when the program has none) — the quantity
    the PR-6 ``<= W*k`` discipline bounds — and ``max_all_reduce_elems``
    its all-reduce twin, which the sparse-aggregate discipline bounds (a
    reduce-scatter of [D] is ALLOWED there: it moves O(D/W) per link and
    lands sharded, unlike an all-reduce's replicated [D] result). The
    maxima are per tuple component: XLA's combiner packs independent
    small reductions (the idx and val pair exchanges, the loss scalars)
    into one variadic op, and the discipline is about any one replicated
    buffer being d-sized, not about how many ride one launch.
    """
    ops: Dict[str, Dict[str, int]] = {
        op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS
    }
    max_ag: Optional[int] = None
    max_ar: Optional[int] = None
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        shapes = [
            parsed
            for sm in _SHAPE_RE.finditer(m.group("lhs"))
            if (parsed := _shape_bytes(sm.group("dt"), sm.group("dims")))
            is not None
        ]
        if m.group("async") and len(shapes) > 1:
            # async start ops return an (operand, output, [contexts...])
            # tuple on TPU — counting the operand alias would inflate the
            # bytes AND max_all_gather_elems past the W*k bound on a
            # perfectly clean sharded round; the transferred buffer is the
            # second component. collective-permute-start is pinned to
            # EXACTLY that component: its tuple trails u32[] context
            # scalars (source/target pair bookkeeping) that the shape
            # regex would otherwise parse as real 4-byte buffers and
            # double-count; the matching -done lines carry no "...(" op
            # call of their own, so the pair is counted once here
            shapes = (shapes[1:2] if op == "collective-permute"
                      else shapes[1:])
        line_elems = max((n for n, _ in shapes), default=0)
        line_bytes = sum(b for _, b in shapes)
        ops[op]["count"] += 1
        ops[op]["bytes"] += line_bytes
        if op == "all-gather":
            max_ag = line_elems if max_ag is None else max(max_ag, line_elems)
        elif op == "all-reduce":
            max_ar = line_elems if max_ar is None else max(max_ar, line_elems)
    return {
        "ops": {k: v for k, v in ops.items() if v["count"]},
        "total_bytes": sum(v["bytes"] for v in ops.values()),
        "max_all_gather_elems": max_ag,
        "max_all_reduce_elems": max_ar,
    }


def ledger_tolerance(upload_bytes: int, *, sharded: bool = False,
                     workers: int = 0, k: int = 0) -> int:
    """Accounting tolerance for the ledger-vs-HLO delta: scalar-collective
    slop, plus — on the sharded sketch decode — the path's KNOWN extra
    design traffic (one zero-HH error-feedback re-sketch psum of table
    size, and the idx+val candidate all-gathers of <= W*k pairs each)."""
    tol = SCALAR_COLLECTIVE_SLOP_BYTES
    if sharded:
        tol += int(upload_bytes) + 8 * int(workers) * int(k)
    return tol


def exposed_collective_ms(spans, audit=None) -> float:
    """The ``xla/exposed_collective_ms`` scalar: host-measured
    un-overlapped collective wait, cross-checked against the compiled
    artifact. The spans side (telemetry/spans.py
    ``collective_exposure_ms``) measures the union of collective-tagged
    span intervals NOT covered by any other span; the HLO side gates it —
    when the audited program contains no collectives at all (a 1-device
    run fences just as long on pure compute), the spans' number is host
    noise and the metric is pinned to 0.0. Without an audit (perf_audit
    off, or the analysis degraded) the spans measurement stands alone:
    an honest host-side reading beats a fake zero."""
    if spans is None:
        return 0.0
    if audit is not None and not audit.collectives_present:
        return 0.0
    return float(spans.collective_exposure_ms())


# ---------------------------------------------------------------------------
# cost / memory analyses (graceful per-backend degradation)
# ---------------------------------------------------------------------------

def _cost_analysis(compiled) -> Dict[str, Any]:
    out = {"flops": None, "bytes_accessed": None, "transcendentals": None,
           "unavailable_reason": None}
    try:
        raw = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001 — audit must never kill a run
        out["unavailable_reason"] = f"cost_analysis failed: {e}"[:200]
        return out
    if isinstance(raw, (list, tuple)):  # jax 0.4.x wraps per-executable
        raw = raw[0] if raw else None
    if not isinstance(raw, dict):
        out["unavailable_reason"] = (
            f"cost_analysis returned {type(raw).__name__}, not a dict"
        )
        return out
    for field, key in (("flops", "flops"), ("bytes_accessed", "bytes accessed"),
                       ("transcendentals", "transcendentals")):
        v = raw.get(key)
        if v is not None:
            out[field] = float(v)
    return out


def _memory_analysis(compiled) -> Dict[str, Any]:
    out = {"argument_bytes": None, "output_bytes": None, "temp_bytes": None,
           "alias_bytes": None, "generated_code_bytes": None,
           "peak_hbm_bytes": None, "unavailable_reason": None}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001
        out["unavailable_reason"] = f"memory_analysis failed: {e}"[:200]
        return out
    if ma is None:
        out["unavailable_reason"] = "memory_analysis returned None"
        return out
    try:
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["alias_bytes"] = int(ma.alias_size_in_bytes)
        out["generated_code_bytes"] = int(ma.generated_code_size_in_bytes)
        # derived peak: live arguments + outputs + temporaries, minus the
        # donated aliases counted on both sides (jax 0.4.37 exposes no
        # direct peak field; this is the standard upper bound)
        out["peak_hbm_bytes"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
            - out["alias_bytes"]
        )
    except Exception as e:  # noqa: BLE001
        out["unavailable_reason"] = f"memory stats unreadable: {e}"[:200]
    return out


# ---------------------------------------------------------------------------
# CompiledRoundAudit
# ---------------------------------------------------------------------------

class CompiledRoundAudit:
    """One compiled round function, audited.

    Build via ``from_compiled`` (any ``jax.stages.Compiled``) or through
    ``FederatedSession.audit_compiled_round`` (which supplies the session's
    ledger accounting and decode geometry). ``report()`` is the versioned
    ``perf_report.json`` payload; ``write()`` persists it; ``scalars()``
    are the ``xla/*`` metrics a train loop emits.
    """

    def __init__(self, *, cost: dict, memory: dict, collectives: dict,
                 engine: str = "replicated", mode: str = "",
                 sketch_decode: Optional[str] = None,
                 aggregate: Optional[str] = None, grad_size: int = 0,
                 workers_mesh: int = 1,
                 ledger_up_bytes: Optional[int] = None,
                 wk_bound: Optional[int] = None,
                 sparse_agg_bound: Optional[int] = None,
                 sparse_agg_exemption: Optional[str] = None,
                 tolerance_bytes: Optional[int] = None,
                 async_info: Optional[dict] = None,
                 overlap_info: Optional[dict] = None,
                 multihost_info: Optional[dict] = None,
                 hlo_unavailable_reason: Optional[str] = None):
        self.cost = cost
        self.memory = memory
        self.engine = engine
        self.mode = mode
        self.sketch_decode = sketch_decode
        # buffered-async audits (engine == "async") carry the overlap
        # geometry {buffer, concurrency, staleness_exponent}; None on
        # synchronous rounds (the v8 schema forbids the block there)
        self.async_info = dict(async_info) if async_info else None
        # collective-hiding state {collectives, double_buffer} — present
        # exactly when one of the hiding modes is ON (the v9 schema
        # forbids the block on a report whose config has both off), so a
        # wall-clock figure downstream can never be misattributed to the
        # wrong overlap setting
        self.overlap_info = dict(overlap_info) if overlap_info else None
        # host-axis topology {num_hosts, num_processes, host_id} — present
        # exactly when the audited round's mesh declares a hosts axis
        # (schema v12 forbids the block on single-host reports), so a
        # collective figure downstream always states which topology its
        # all-reduces spanned. On the mesh-faked twin num_processes is 1;
        # a real pod reports its jax.distributed process topology.
        self.multihost_info = dict(multihost_info) if multihost_info else None
        # resolved --aggregate path (None when the compressor has no sparse
        # aggregation capability): 'sparse' arms the checker's no-O(D)
        # all-reduce/all-gather enforcement against sparse_agg_bound
        self.aggregate = aggregate
        self.grad_size = int(grad_size)
        self.workers_mesh = int(workers_mesh)
        self.hlo_unavailable_reason = hlo_unavailable_reason
        coll = dict(collectives)
        coll["wk_bound"] = wk_bound
        coll["sparse_agg_bound"] = sparse_agg_bound
        # why (if at all) sparse_agg_bound exceeds the strict W*k-class
        # bound — 'client_state_writeback' when DEVICE-resident client
        # rows inflate it. A hosted store (--client_store host|mmap) never
        # sets it, and the schema checker REJECTS a host-store sparse
        # report carrying any exemption (satellite of ROADMAP item 3)
        coll["sparse_agg_exemption"] = sparse_agg_exemption
        coll["ledger_up_bytes"] = ledger_up_bytes
        if ledger_up_bytes is not None:
            delta = coll["total_bytes"] - int(ledger_up_bytes)
            tol = (tolerance_bytes if tolerance_bytes is not None
                   else SCALAR_COLLECTIVE_SLOP_BYTES)
            coll["delta_bytes"] = delta
            coll["tolerance_bytes"] = int(tol)
            coll["within_tolerance"] = abs(delta) <= int(tol)
        self.collectives = coll

    @property
    def collectives_present(self) -> bool:
        """Whether the compiled program contains ANY collective op — the
        HLO side of the ``exposed_collective_ms`` spans×HLO cross-check."""
        return any(v.get("count", 0) > 0
                   for v in self.collectives.get("ops", {}).values())

    @classmethod
    def from_compiled(cls, compiled, **kw) -> "CompiledRoundAudit":
        """Audit any ``Compiled``: cost + memory analyses and — when the
        backend can render the module text — the collective walk."""
        hlo_reason = None
        try:
            text = compiled.as_text()
        except Exception as e:  # noqa: BLE001
            text, hlo_reason = "", f"as_text failed: {e}"[:200]
        return cls(
            cost=_cost_analysis(compiled),
            memory=_memory_analysis(compiled),
            collectives=collective_audit(text),
            hlo_unavailable_reason=hlo_reason,
            **kw,
        )

    # -- outputs -----------------------------------------------------------
    def scalars(self) -> Dict[str, float]:
        """The drained ``xla/*`` scalars this audit contributes (only the
        available ones — a degraded analysis emits nothing rather than a
        fake zero)."""
        out: Dict[str, float] = {
            "xla/collective_bytes": float(self.collectives["total_bytes"]),
        }
        if self.collectives.get("delta_bytes") is not None:
            out["xla/ledger_delta_bytes"] = float(
                self.collectives["delta_bytes"]
            )
        if self.cost.get("flops") is not None:
            out["xla/audited_flops"] = float(self.cost["flops"])
        if self.memory.get("peak_hbm_bytes") is not None:
            out["xla/peak_hbm_bytes"] = float(self.memory["peak_hbm_bytes"])
        return out

    def report(self, *, generated_by: str, cfg=None,
               extra: Optional[dict] = None) -> dict:
        from commefficient_tpu.telemetry import SCHEMA_VERSION, jsonable_tree
        from commefficient_tpu.telemetry.ledger import run_metadata

        import jax

        # the device is always named; a chip outside the peak table (the
        # CPU test mesh) gets NO roofline floor rather than another chip's
        kind = jax.devices()[0].device_kind
        try:
            peak, _ = chip_peak_flops()
        except ValueError:
            peak = None
        predicted: Dict[str, Any] = {
            "peak_flops": peak, "device_kind": kind,
            # compute-bound roofline floor: the round can never beat its
            # audited FLOPs over the chip peak (bandwidth may bound it
            # higher — bytes_accessed / HBM BW — but peak BW varies per
            # part; the FLOP floor is the portable one)
            "compute_bound_sec_per_round": (
                self.cost["flops"] / peak
                if peak and self.cost.get("flops") is not None
                else None
            ),
        }
        rec = {
            "schema_version": SCHEMA_VERSION,
            "kind": "perf_report",
            "generated_by": generated_by,
            "engine": self.engine,
            "mode": self.mode,
            "sketch_decode": self.sketch_decode,
            "aggregate": self.aggregate,
            "grad_size": self.grad_size,
            "workers_mesh": self.workers_mesh,
            "cost": self.cost,
            "memory": self.memory,
            "collectives": self.collectives,
            "predicted": predicted,
            "hlo_unavailable_reason": self.hlo_unavailable_reason,
            "meta": run_metadata(cfg),
        }
        if self.async_info is not None:
            rec["async"] = dict(self.async_info)
        if self.overlap_info is not None:
            rec["overlap"] = dict(self.overlap_info)
        if self.multihost_info is not None:
            rec["multihost"] = dict(self.multihost_info)
        if extra:
            rec.update(extra)
        return jsonable_tree(rec)

    def write(self, logdir: str, *, generated_by: str, cfg=None,
              extra: Optional[dict] = None,
              filename: str = "perf_report.json") -> str:
        """Persist ``perf_report.json`` into ``logdir``; returns the path."""
        os.makedirs(logdir, exist_ok=True)
        path = os.path.join(logdir, filename)
        with open(path, "w") as f:
            json.dump(self.report(generated_by=generated_by, cfg=cfg,
                                  extra=extra),
                      f, indent=2, allow_nan=False)
        return path

    def describe(self) -> str:
        """One console line for the train-entry startup banner."""
        c, m = self.cost, self.memory
        flops = ("?" if c.get("flops") is None
                 else f"{c['flops'] / 1e9:.3f} GFLOP")
        hbm = ("?" if m.get("peak_hbm_bytes") is None
               else f"{m['peak_hbm_bytes'] / 2**20:.1f} MiB")
        coll = self.collectives
        ok = coll.get("within_tolerance")
        return (
            f"compiled-round audit [{self.engine}/{self.mode}]: "
            f"{flops}/round, peak HBM ~{hbm}, collectives "
            f"{coll['total_bytes']:,} B vs ledger "
            f"{coll.get('ledger_up_bytes', '?')} B"
            + ("" if ok is None else
               f" (delta {coll['delta_bytes']:+,} B, "
               f"{'within' if ok else 'OUTSIDE'} tolerance)")
        )


# ---------------------------------------------------------------------------
# Retrace sentinel
# ---------------------------------------------------------------------------

class RetraceError(RuntimeError):
    """The round fn retraced more than ``max_retraces`` times; the message
    names the argument-signature diff that caused the last retrace."""


def _describe_leaf(x) -> str:
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        weak = "(weak)" if getattr(aval, "weak_type", False) else ""
        return f"{aval.dtype}[{','.join(map(str, aval.shape))}]{weak}"
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{x.dtype}[{','.join(map(str, x.shape))}]"
    return f"py:{type(x).__name__}={x!r}"


def describe_signature(args, kwargs) -> Dict[str, str]:
    """{tree path: "dtype[shape]"} over every leaf of one call's
    arguments — the comparison key the sentinel diffs between traces.
    Runs at TRACE time (the leaves are tracers; their avals carry the
    shape/dtype/weak-type triple that keys the jit cache)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path((args, dict(kwargs)))[0]
    return {jax.tree_util.keystr(path): _describe_leaf(leaf)
            for path, leaf in flat}


def signature_diff(old: Dict[str, str], new: Dict[str, str]) -> str:
    """Human-readable diff between two trace signatures, naming the
    offending leaves (the thing a 3am perf post-mortem actually needs)."""
    lines = []
    for k in sorted(set(old) | set(new)):
        a, b = old.get(k), new.get(k)
        if a == b:
            continue
        if a is None:
            lines.append(f"  + {k}: {b}")
        elif b is None:
            lines.append(f"  - {k}: {a}")
        else:
            lines.append(f"  ~ {k}: {a} -> {b}")
    return "\n".join(lines) if lines else "  (pytree structure changed)"


class RetraceSentinel:
    """Counts traces of the session's jitted round programs and names what
    changed.

    Attach via the round builders' ``trace_hook=`` (the hook body runs at
    trace time only — a pure python counter, zero traced ops, so the
    compiled program is bit-identical with or without it). Signatures are
    tracked PER FUNCTION (a session may legitimately trace both its
    host-batch round and the device-resident index round — e.g. the AOT
    audit on one, training on the other — and neither first compile is a
    retrace); ``retraces`` sums ``traces - 1`` over each. With
    ``max_retraces`` set, exceeding the total raises ``RetraceError``
    naming the argument-signature diff. NB on this jax a ``lower()`` trace
    shares the call path's cache, so the session audit's AOT trace counts
    as that function's expected first trace (suspending it would leave the
    sentinel blind to the steady-state signature); ``suspended()`` exists
    for traces that must not be recorded at all.
    """

    def __init__(self, max_retraces: Optional[int] = None,
                 name: str = "round_fn"):
        self.max_retraces = max_retraces
        self.name = name
        # fn name -> [{path: desc}, ...] in trace order
        self.signatures: Dict[str, list] = {}
        self._suspended = 0
        self._last_retraced: Optional[str] = None

    @contextmanager
    def suspended(self):
        self._suspended += 1
        try:
            yield
        finally:
            self._suspended -= 1

    @property
    def traces(self) -> int:
        return sum(len(v) for v in self.signatures.values())

    @property
    def retraces(self) -> int:
        return sum(max(0, len(v) - 1) for v in self.signatures.values())

    def last_diff(self) -> str:
        name = self._last_retraced
        sigs = self.signatures.get(name, [])
        if len(sigs) < 2:
            return "(no retrace recorded)"
        return f"[{name}]\n" + signature_diff(sigs[-2], sigs[-1])

    def hook(self, *args, **kwargs) -> None:
        """Call at the top of the to-be-jitted round body (the default
        ``self.name`` stream); per-function streams via ``hook_for``."""
        self._note(self.name, args, kwargs)

    def hook_for(self, fn_name: str):
        """A trace hook recording into ``fn_name``'s own signature
        stream — for sessions with more than one jitted round program."""

        def hook(*args, **kwargs):
            self._note(fn_name, args, kwargs)

        return hook

    def _note(self, fn_name: str, args, kwargs) -> None:
        if self._suspended:
            return
        sigs = self.signatures.setdefault(fn_name, [])
        sigs.append(describe_signature(args, kwargs))
        if len(sigs) > 1:
            self._last_retraced = fn_name
        if self.max_retraces is not None and self.retraces > self.max_retraces:
            raise RetraceError(
                f"{fn_name} retraced — {self.retraces} retrace(s) total, "
                f"over the --max_retraces {self.max_retraces} budget. Every "
                "retrace recompiles the whole XLA round (minutes at GPT-2 "
                "scale) with no visible signal but the wall clock. "
                f"Offending argument-signature diff vs the previous trace:\n"
                f"{self.last_diff()}\n"
                "Typical causes: a python float/int where the steady state "
                "passes a jnp scalar (weak-type flip), a dtype drift in one "
                "batch, or a shape change (ragged tail batch reaching the "
                "round)."
            )

    def wrap(self, fn, fn_name: Optional[str] = None):
        """``fn`` with the hook prepended — for call sites that build their
        own traced function instead of passing ``trace_hook=``."""
        hook = self.hook_for(fn_name or getattr(fn, "__name__", "fn"))

        def wrapped(*args, **kwargs):
            hook(*args, **kwargs)
            return fn(*args, **kwargs)

        return wrapped
