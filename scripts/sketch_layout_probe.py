"""Chip probe (ISSUE 32): what a CountSketch row's two fixed permutations
cost around its one-hot product at the paper's GPT-2 geometry
(D = 124,444,417, 5 x 5M table: m 8,192, band 16, riffle factors 1, 97,
7603, ...), one row at a time: the riffle into chunk layout and back, the
product alone, the band's window view and its overlap-add, each in the
parent's form (written out below: ``reshape(f, G).T.reshape(L)``, a stack of
sixteen shifted slices merged to ``[nc, V]``) beside the tree's
(``ops/countsketch.py``: tiled moves, the band kept as two dimensions) and
the forms that lost (the band as a convolution over chunks, a gather-built
window, the 3-D view on the way in); then whole ``sketch_vec`` /
``estimate_all`` both ways and once with ``backend="pallas"``, a device
trace of each whole function (which compiler-made op belongs to which
helper), and the same whole functions at the ResNet-9 geometry.

    chiprun --timeout 3000 -- python scripts/sketch_layout_probe.py [--only a,b]

Host clock around ``block_until_ready``, the mean of ``--reps`` calls after
one warm call; one JSON line a reading, also appended to
``chiprun_out/sketch_layout_probe.jsonl``. Refuses without a TPU
(``--rehearse`` walks the control flow on the CPU at a small size and
writes nothing).
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from commefficient_tpu.ops import countsketch as cs

OUT = os.path.join("chiprun_out", "sketch_layout_probe.jsonl")
WRITE = True


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    if WRITE:
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "a") as f:
            f.write(line + "\n")


def timed(name, fn, *args, reps=5, **tags):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(f(*args))
    say(what=name, s=(time.perf_counter() - t0) / reps, first_s=first, **tags)
    return out


# ---- the parent's forms (commit 3abca0f), written out ------------------------

def old_to_layout(spec, x, row):
    f, L = spec._factor(row), spec._L_row(row)
    xp = jnp.pad(x, (0, L - spec.d_eff))
    if f > 1:
        xp = xp.reshape(f, L // f).T.reshape(L)
    return xp.reshape(L // spec.chunk_m, spec.chunk_m)


def old_from_layout(spec, x_chunks, row):
    f, L = spec._factor(row), spec._L_row(row)
    xp = x_chunks.reshape(L)
    if f > 1:
        xp = xp.reshape(L // f, f).T.reshape(L)
    return xp[: spec.d_eff]


def old_overlap_add(spec, O, row):
    nc, u, t = spec._nc_row(row), spec.u_row(row), spec.s_row(row)
    Or = O.reshape(nc, u, t)
    stack = jnp.stack(
        [jnp.pad(Or[:, i, :], ((i, u - 1 - i), (0, 0))) for i in range(u)]
    )
    return stack.sum(0).reshape((nc + u - 1) * t)


def old_overlap_gather(spec, row_vec, row):
    nc, u, t = spec._nc_row(row), spec.u_row(row), spec.s_row(row)
    acc = row_vec[: (nc + u - 1) * t].reshape(nc + u - 1, t)
    return jnp.stack([acc[i : i + nc] for i in range(u)], axis=1).reshape(nc, u * t)


def row_onehot(spec, row):
    """[m, V] static one-hot of the row's offset slots, the band merged."""
    slots = spec._offset_slots(row)
    return (slots[:, None] == jnp.arange(spec.V_row(row), dtype=jnp.int32)).astype(
        spec.dtype)


def product_sketch(spec, sv, row):
    return jnp.einsum("cm,ms->cs", sv, row_onehot(spec, row),
                      preferred_element_type=jnp.float32)


def product_estimate(spec, win, row):
    return jnp.einsum("cs,ms->cm", win, row_onehot(spec, row),
                      preferred_element_type=jnp.float32)


def old_sketch_row(spec, v_s, row):
    sv = old_to_layout(spec, v_s * spec._row_signs(row), row)
    out = old_overlap_add(spec, product_sketch(spec, sv, row), row)
    return jnp.pad(out, (0, spec.c_actual - out.shape[0]))


def old_estimate_row(spec, table_row, row):
    est = product_estimate(spec, old_overlap_gather(spec, table_row, row), row)
    return old_from_layout(spec, est, row) * spec._row_signs(row)


def old_sketch_vec(spec, v):
    v = cs._scramble(spec, v.astype(jnp.float32))
    return jnp.stack([old_sketch_row(spec, v, r) for r in range(spec.r)])


def old_estimate_all(spec, table):
    ests = jnp.stack([old_estimate_row(spec, table[r], r) for r in range(spec.r)])
    return cs._unscramble(spec, cs._median_rows(ests))


# ---- band forms, apart from the riffle -------------------------------------------

def _row_view(spec, table_row, row):
    nc, u, t = spec._nc_row(row), spec.u_row(row), spec.s_row(row)
    return table_row[: (nc + u - 1) * t].reshape(nc + u - 1, t)


def _band_conv(lhs, taps, padding):
    return jax.lax.conv_general_dilated(
        lhs[None], taps, window_strides=(1,), padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.float32)[0]


def _band_taps(spec, row, flip):
    """The one-hot as the u taps of a 1-D convolution over chunks: [u, s, m]
    (W, I, O) for the estimate, flipped and transposed for the sketch."""
    u, s = spec.u_row(row), spec.s_row(row)
    slots = spec._offset_slots(row)
    taps = jnp.arange(u * s, dtype=jnp.int32).reshape(u, s)
    if flip:
        return (slots[None, :, None] == taps[::-1, None, :]).astype(spec.dtype)
    return (taps[:, :, None] == slots[None, None, :]).astype(spec.dtype)


def conv_sketch(spec, sv, row):
    """ISSUE 32's first form: the band as a convolution over chunks (lost)."""
    u = spec.u_row(row)
    return _band_conv(sv, _band_taps(spec, row, True), [(u - 1, u - 1)])


def conv_estimate(spec, table_row, row):
    return _band_conv(_row_view(spec, table_row, row),
                      _band_taps(spec, row, False), "VALID")


def dot2_sketch(spec, sv, row):
    """The tree's form, sketch side: the product leaves ``[nc, u, s]`` (no
    ``[nc, V]`` reshape), then sixteen shifted adds."""
    u = spec.u_row(row)
    Or = jnp.einsum("cm,mus->cus", sv, cs._band_onehot(spec, row),
                    preferred_element_type=jnp.float32)
    return sum(jnp.pad(Or[:, i, :], ((i, u - 1 - i), (0, 0))) for i in range(u))


def dot2_estimate(spec, table_row, row):
    """The tree's form, estimate side: a dot contracting (u, s) over a
    ``[nc, u, s]`` stack of whole rows."""
    nc, u = spec._nc_row(row), spec.u_row(row)
    acc = _row_view(spec, table_row, row)
    win = jnp.stack([acc[i : i + nc] for i in range(u)], axis=1)
    return jnp.einsum("cus,mus->cm", win, cs._band_onehot(spec, row),
                      preferred_element_type=jnp.float32)


def dot2_estimate_gather(spec, table_row, row):
    """The same with the windows taken by one gather of whole rows."""
    nc, u = spec._nc_row(row), spec.u_row(row)
    acc = _row_view(spec, table_row, row)
    idx = jnp.arange(nc, dtype=jnp.int32)[:, None] + jnp.arange(u, dtype=jnp.int32)
    return jnp.einsum("cus,mus->cm", acc[idx], cs._band_onehot(spec, row),
                      preferred_element_type=jnp.float32)


# ---- the plain riffle on the way in through the vector's own [f, G/128, 128] tiles
# (the tree's _from_layout is this form on the way back)

def view3_to_layout(spec, x, row):
    f, L, m = spec._factor(row), spec._L_row(row), spec.chunk_m
    xp = jnp.pad(x, (0, L - spec.d_eff))
    if f > 1:
        xp = xp.reshape(f, L // f // 128, 128).transpose(1, 2, 0).reshape(L)
    return xp.reshape(L // m, m)


# ---- device trace: the longest ops of one jitted call --------------------------

def top_ops(name, fn, *args, calls=2, keep=14):
    from benchmark.reduce import load_xplane

    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                jax.block_until_ready(f(*args))
        paths = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")))
        trace = load_xplane(paths[-1])
    ops = next((o for o in trace["devices"].values() if o), [])
    by = {}
    for op, scope, _start, dur in ops:
        by.setdefault((op, scope), []).append(dur)
    rows = sorted(by.items(), key=lambda kv: -sum(kv[1]))[:keep]
    say(what=name + "_top_ops", calls=calls,
        ops=[dict(op=op, scope=scope[-60:], n=len(ds) // calls or len(ds),
                  s_per_call=sum(ds) / calls) for (op, scope), ds in rows])


def main():
    global WRITE
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=124_444_417)
    ap.add_argument("--c", type=int, default=5_000_000)
    ap.add_argument("--r", type=int, default=5)
    ap.add_argument("--rows", default="0,1,2")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", default="riffle,band,dot2,whole,pallas,trace_old,trace_new,resnet9,view3")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        if not a.rehearse:
            print("sketch_layout_probe: no TPU; seconds are only meaningful on the chip",
                  file=sys.stderr)
            sys.exit(1)
        WRITE = False
    only = set(a.only.split(","))
    reps = a.reps
    spec = cs.CountSketch(d=a.d, c=a.c, r=a.r, seed=42)
    say(what="device", platform=dev.platform, kind=dev.device_kind, d=a.d, c=a.c,
        r=a.r, m=spec.chunk_m, jax=jax.__version__,
        rows=[dict(f=spec._factor(r), nc=spec._nc_row(r), u=spec.u_row(r),
                   s=spec.s_row(r)) for r in range(spec.r)])
    key = jax.random.PRNGKey(32)
    v = jax.block_until_ready(jax.random.normal(key, (spec.d,), jnp.float32))
    v_s = jax.block_until_ready(jax.jit(lambda x: cs._scramble(spec, x))(v))
    table = None

    for row in (int(r) for r in a.rows.split(",")):
        f = spec._factor(row)
        tags = dict(row=row, f=f)
        sv = None
        if "riffle" in only:
            sv_old = timed("old_to_layout", lambda x: old_to_layout(spec, x, row), v_s,
                           reps=reps, **tags)
            sv = timed("to_layout", lambda x: cs._to_layout(spec, x, row), v_s,
                       reps=reps, **tags)
            say(what="to_layout_equal", equal=bool(jnp.array_equal(sv, sv_old)), **tags)
            del sv_old
            back_old = timed("old_from_layout", lambda x: old_from_layout(spec, x, row),
                             sv, reps=reps, **tags)
            back = timed("from_layout", lambda x: cs._from_layout(spec, x, row), sv,
                         reps=reps, **tags)
            say(what="from_layout_equal", equal=bool(jnp.array_equal(back, back_old)),
                inverse=bool(jnp.array_equal(back, v_s)), **tags)
            del back, back_old
        if "band" in only:
            if sv is None:
                sv = jax.jit(lambda x: cs._to_layout(spec, x, row))(v_s)
            win = timed("product_sketch", lambda x: product_sketch(spec, x, row), sv,
                        reps=reps, **tags)
            flat_old = timed("old_overlap_add", lambda x: old_overlap_add(spec, x, row),
                             win, reps=reps, **tags)
            del win
            flat = timed("conv_sketch", lambda x: conv_sketch(spec, x, row), sv,
                         reps=reps, **tags).reshape(-1)
            say(what="conv_sketch_vs_old", max_abs=float(jnp.abs(flat - flat_old).max()),
                row_max=float(jnp.abs(flat_old).max()), **tags)
            del flat_old
            win = timed("old_overlap_gather", lambda x: old_overlap_gather(spec, x, row),
                        flat, reps=reps, **tags)
            est_old = timed("product_estimate", lambda x: product_estimate(spec, x, row),
                            win, reps=reps, **tags)
            del win
            est = timed("conv_estimate", lambda x: conv_estimate(spec, x, row), flat,
                        reps=reps, **tags)
            say(what="conv_estimate_vs_old", max_abs=float(jnp.abs(est - est_old).max()),
                est_max=float(jnp.abs(est_old).max()), **tags)
            if "dot2" in only:
                d2 = timed("dot2_estimate", lambda x: dot2_estimate(spec, x, row), flat,
                           reps=reps, **tags)
                say(what="dot2_estimate_equal", equal=bool(jnp.array_equal(d2, est)), **tags)
                d3 = timed("dot2_estimate_gather",
                           lambda x: dot2_estimate_gather(spec, x, row), flat,
                           reps=reps, **tags)
                say(what="dot2_estimate_gather_equal", equal=bool(jnp.array_equal(d3, est)),
                    **tags)
                del d3
                d2 = timed("dot2_sketch", lambda x: dot2_sketch(spec, x, row), sv,
                           reps=reps, **tags).reshape(-1)
                say(what="dot2_sketch_vs_conv", max_abs=float(jnp.abs(d2 - flat).max()), **tags)
                del d2
            del est, est_old, flat
        del sv

    def whole(spec, tag):
        t_old = timed("old_sketch_vec", lambda x: old_sketch_vec(spec, x), v_, reps=reps,
                      geom=tag)
        t_new = timed("sketch_vec", lambda x: cs.sketch_vec(spec, x), v_, reps=reps,
                      geom=tag)
        say(what="sketch_vec_vs_old", max_abs=float(jnp.abs(t_new - t_old).max()),
            table_max=float(jnp.abs(t_old).max()), geom=tag)
        e_old = timed("old_estimate_all", lambda t: old_estimate_all(spec, t), t_old,
                      reps=reps, geom=tag)
        e_new = timed("estimate_all", lambda t: cs.estimate_all(spec, t), t_old,
                      reps=reps, geom=tag)
        say(what="estimate_all_vs_old", max_abs=float(jnp.abs(e_new - e_old).max()),
            equal=bool(jnp.array_equal(e_new, e_old)), geom=tag)
        return t_old

    v_ = v
    if "whole" in only:
        table = whole(spec, "gpt2")
    if "pallas" in only:
        pspec = spec._replace(backend="pallas")
        t_p = timed("sketch_vec_pallas", lambda x: cs.sketch_vec(pspec, x), v, reps=reps)
        timed("estimate_all_pallas", lambda t: cs.estimate_all(pspec, t), t_p, reps=reps)
        del t_p
    if "view3" in only:
        # the large factors' plain transpose through the vector's own
        # [f, G/128, 128] tiles: the tree takes it on the way back and not on
        # the way in; here each whole function with the other choice
        tree_to, tree_from = cs._to_layout, cs._from_layout
        for row in (2, 4):
            tags = dict(row=row, f=spec._factor(row))
            sv = timed("view3_to_layout", lambda x: view3_to_layout(spec, x, row), v_s,
                       reps=reps, **tags)
            say(what="view3_to_layout_equal", equal=bool(jnp.array_equal(
                sv, jax.jit(lambda x: tree_to(spec, x, row))(v_s))), **tags)
            back = timed("from_layout", lambda x: tree_from(spec, x, row), sv,
                         reps=reps, **tags)
            say(what="from_layout_inverse", equal=bool(jnp.array_equal(back, v_s)),
                **tags)
            del sv, back
        cs._to_layout = lambda spec, x, row: (
            tree_to if cs._riffle_tile(spec, row) else view3_to_layout)(spec, x, row)
        cs._from_layout = lambda spec, x, row: (
            tree_from if cs._riffle_tile(spec, row) else old_from_layout)(spec, x, row)
        try:
            t3 = timed("sketch_vec_view3_in", lambda x: cs.sketch_vec(spec, x), v,
                       reps=reps)
            timed("estimate_all_plain_back", lambda t: cs.estimate_all(spec, t), t3,
                  reps=reps)
            del t3
        finally:
            cs._to_layout, cs._from_layout = tree_to, tree_from
    if "trace_old" in only:
        if table is None:
            table = jax.jit(lambda x: old_sketch_vec(spec, x))(v)
        top_ops("old_sketch_vec", lambda x: old_sketch_vec(spec, x), v)
        top_ops("old_estimate_all", lambda t: old_estimate_all(spec, t), table)
    if "trace_new" in only:
        if table is None:
            table = jax.jit(lambda x: cs.sketch_vec(spec, x))(v)
        top_ops("sketch_vec", lambda x: cs.sketch_vec(spec, x), v, keep=40)
        top_ops("estimate_all", lambda t: cs.estimate_all(spec, t), table, keep=40)
    if "resnet9" in only:
        # the CV cells' geometry (PERF.md section 7, rows 2-3): d 6.57M, 5 x 500k
        small = cs.CountSketch(d=6_568_640 if not a.rehearse else a.d // 3,
                               c=500_000 if not a.rehearse else a.c // 3, r=5, seed=42)
        say(what="geometry", geom="resnet9", d=small.d, m=small.chunk_m,
            rows=[dict(f=small._factor(r), nc=small._nc_row(r), u=small.u_row(r),
                       s=small.s_row(r)) for r in range(small.r)])
        v_ = jax.random.normal(key, (small.d,), jnp.float32)
        whole(small, "resnet9")
        # every riffled row there both ways, whatever _riffle_tile picks
        for row in range(1, small.r):
            f, L = small._factor(row), small._L_row(row)
            x = jax.random.normal(key, (L,), jnp.float32)
            tags = dict(geom="resnet9", row=row, f=f)
            timed("plain_riffle", lambda x: x.reshape(f, L // f).T.reshape(
                L // small.chunk_m, small.chunk_m), x, reps=reps, **tags)
            timed("tiled_riffle", lambda x: cs._riffle(x, f, 128).reshape(
                L // small.chunk_m, small.chunk_m), x, reps=reps, **tags)
            y = x.reshape(L // small.chunk_m, small.chunk_m)
            timed("plain_unriffle", lambda y: y.reshape(L // f, f).T.reshape(L), y,
                  reps=reps, **tags)
            timed("tiled_unriffle", lambda y: cs._unriffle(y.reshape(L), f, 128), y,
                  reps=reps, **tags)
    stats = dev.memory_stats() or {}
    say(what="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        peak_bytes_reserved=stats.get("peak_bytes_reserved"))


if __name__ == "__main__":
    main()
