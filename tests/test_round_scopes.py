"""The names the compiled round carries (telemetry.trace.ROUND_SCOPES).

The round's layer boundaries are ``jax.named_scope``s: op metadata, no
operation. The benchmark's per-layer metrics read them back from a device
trace by pattern, so what is pinned here, on the program
``train_round_indices`` dispatches at telemetry level 0, is: every name a
mode traces is in its compiled round, every name it does not trace is not,
no name contains another (a substring pattern stays exact), the source
opens no scope the list does not hold, and forward and backward are told
apart by the wrapping alone.
"""

import os
import re

import jax.numpy as jnp
import pytest
from test_device_data import _mlp_loss, _toy_ds, augment_batch

import commefficient_tpu
from commefficient_tpu.data import FedSampler
from commefficient_tpu.parallel import FederatedSession, make_mesh
from commefficient_tpu.telemetry.trace import MODEL_SCOPES, ROUND_SCOPES
from commefficient_tpu.utils.config import Config

NAMES = tuple(name for name, _ in ROUND_SCOPES)
MODEL_NAMES = tuple(name for name, _ in MODEL_SCOPES)
BASE = dict(num_clients=16, num_workers=8, num_devices=1, local_batch_size=4,
            weight_decay=5e-4, max_grad_norm=1.0, seed=1)
MODES = {
    "sketch": dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                   k=64, num_rows=3, num_cols=2048, topk_method="threshold"),
    "uncompressed": dict(mode="uncompressed"),
    # per-client state: the transmit rule and the client-row scatter
    "local_topk": dict(mode="local_topk", error_type="local", k=64,
                       local_momentum=0.9, virtual_momentum=0.9,
                       topk_method="threshold"),
}
EVERY_ROUND = {"data_gather", "client_grad", "flat_grad_concat", "client_clip",
               "client_sum", "aggregate_tail", "server_decode_dense",
               "apply_update"}
TRACED = {
    "sketch": EVERY_ROUND | {"encode", "estimate_all", "topk_select",
                             "ef_resketch"},
    # device_encode is the identity and the server keeps no bank to decode;
    # on the leafwise path (parallel/round.py::make_leafwise_sum) nothing
    # sits between the one [D] concat of the summed leaves and the
    # aggregation tail's own concat of the psum payload, so the compiler
    # makes one concatenate of the two and keeps the tail's name (the
    # program still opens the scope: tests/test_leafwise_clients.py)
    "uncompressed": EVERY_ROUND - {"flat_grad_concat"},
    "local_topk": EVERY_ROUND | {"client_transmit", "topk_select"},
}


@pytest.fixture(scope="module")
def op_names():
    """mode -> the ``op_name`` metadata of its compiled index round."""
    out = {}
    for mode, kw in MODES.items():
        cfg = Config(**kw, **BASE)
        assert cfg.telemetry_level == 0
        params, loss_fn = _mlp_loss()
        ds = _toy_ds(num_clients=16)
        session = FederatedSession(cfg, params, loss_fn, mesh=make_mesh(1))
        sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1,
                             augment=augment_batch)
        session.attach_data(ds.data, augment_batch)
        ids, idx, plan = sampler.sample_round_indices(0)
        cids, idxd, pl = session.stage_round_indices(ids, idx, plan)
        text = session._round_idx_fn.lower(
            session.state, session._dev_data, jnp.asarray(cids), idxd, pl,
            jnp.float32(0.1), env=(),
        ).compile().as_text()
        out[mode] = set(re.findall(r'op_name="([^"]*)"', text))
    return out


def _carries(names, scope):
    rx = re.compile(r"\b" + scope + r"\b")
    return any(rx.search(n) for n in names)


@pytest.mark.parametrize("scope", NAMES)
@pytest.mark.parametrize("mode", sorted(MODES))
def test_round_carries_exactly_the_scopes_it_traces(op_names, mode, scope):
    assert _carries(op_names[mode], scope) == (scope in TRACED[mode]), (
        f"{mode}: {scope!r} is "
        f"{'missing from' if scope in TRACED[mode] else 'present in'} "
        "the compiled round")


@pytest.mark.parametrize("mode", sorted(MODES))
def test_forward_and_backward_are_told_apart_by_the_wrapping(op_names, mode):
    # benchmark/layers/model.bwd_s_per_round.json reads the second pattern
    assert any(re.search(r"client_grad\)?/jvp\(", n) for n in op_names[mode])
    assert any(re.search(r"client_grad\)?/transpose\(", n)
               for n in op_names[mode])


@pytest.mark.parametrize("mode", sorted(MODES))
def test_nothing_but_the_step_counter_is_left_unnamed(op_names, mode):
    """Every op the program traces sits under a name of the list, but for
    ``state.step + 1`` and, with per-client state, the gather of the
    participants' rows at the top of the round (the list has no name for
    it; ``round.unscoped_s_per_round`` is where a cell would read it).
    (Parameters and the reducers' own computations carry no ``jit(...)``
    path and are not the program's.)"""
    rx = re.compile("|".join(NAMES))
    bare = {n for n in op_names[mode]
            if n.startswith("jit(") and not rx.search(n)}
    allowed = {"jit(wrapped)/add"}
    if mode == "local_topk":
        allowed |= {"jit(wrapped)/gather", "jit(wrapped)/lt",
                    "jit(wrapped)/select_n"}
    assert bare <= allowed, bare


def test_decode_scopes_nest_under_the_decode_marker(op_names):
    inner = ("estimate_all", "topk_select", "ef_resketch")
    for n in op_names["sketch"]:
        if any(re.search(r"\b" + s + r"\b", n) for s in inner):
            assert "server_decode_dense/" in n, n


def test_resketch_compaction_is_named_ef_resketch_and_nothing_else(op_names):
    """The dense decode compacts the update's pairs inside ``ef_resketch``
    (``ops/topk.py::compact_nonzero_tree`` opens no scope of its own), so
    ``compress.resketch_s_per_round`` reads the compaction (its row
    counts, row gathers and in-row dot) with the scatter-add, and
    ``compress.topk_s_per_round`` reads none of it."""
    under = {n for n in op_names["sketch"] if re.search(r"\bef_resketch\b", n)}
    assert not [n for n in under if "topk_select" in n]
    for prim in ("reduce_sum", "gather", "dot_general", "scatter-add"):
        assert any(n.endswith("ef_resketch/" + prim) for n in under), prim


@pytest.mark.parametrize("name", NAMES + MODEL_NAMES)
def test_no_name_contains_another(name):
    assert re.fullmatch(r"[a-z][a-z0-9_]*", name)
    assert [o for o in NAMES + MODEL_NAMES if o != name and name in o] == []


def test_source_opens_only_scopes_of_the_list():
    root = os.path.dirname(commefficient_tpu.__file__)
    opened = set()
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    opened |= set(re.findall(
                        r'named_scope\(\s*"([^"]+)"\s*\)', fh.read()))
    opened |= {"sketch_decode_sharded", "sparse_aggregate_decode"}  # round.py
    # picks one of the two by the plan, through a variable
    assert opened == set(NAMES) | set(MODEL_NAMES)


def _lm_op_names(model):
    """The ``op_name`` metadata of the compiled index round of the LM entry
    at a tiny preset, built as ``lm_train.main`` builds it."""
    from commefficient_tpu.train import lm_train

    cfg = lm_train.parse_args(
        ["--model", model, "--max_seq_len", "128", "--num_clients", "8",
         "--num_workers", "2", "--num_devices", "1", "--mode", "uncompressed"],
        defaults=lm_train.DEFAULTS)
    train, _test, _lcfg, _model, params, loss_fn = lm_train.build_model_and_data(cfg)
    session, sampler = lm_train.build_session_and_sampler(cfg, train, params, loss_fn)
    ids, idx, plan = sampler.sample_round_indices(0)
    cids, idxd, pl = session.stage_round_indices(ids, idx, plan)
    text = session._round_idx_fn.lower(
        session.state, session._dev_data, jnp.asarray(cids), idxd, pl,
        jnp.float32(0.1), env=(),
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def laguna_op_names():
    return _lm_op_names("laguna_tiny")


@pytest.fixture(scope="module")
def keye_op_names():
    return _lm_op_names("keye_tiny")


@pytest.fixture(scope="module")
def sdar_op_names():
    return _lm_op_names("sdar_tiny")


# the scopes only an indexed-attention model opens, and the ones it never does;
# the same for a block-diffusion model
INDEXED_ONLY = ("attn_index", "attn_select", "attn_sparse")
DIFFUSION_ONLY = ("attn_blockdiff", "diffusion_streams", "diffusion_loss")
NEVER_INDEXED = ("attn_full", "attn_window", "mlp_dense") + DIFFUSION_ONLY
NEVER_DIFFUSION = ("attn_full", "attn_window", "mlp_dense") + INDEXED_ONLY


# the one model scope with no backward pass (the gradient is taken with
# respect to the split's output), and the three that the expert layer's mapped
# function opens, which therefore always sit under ``moe_loop``
NO_BACKWARD = ("param_unravel",)
IN_THE_EXPERT_LOOP = ("moe_dispatch", "moe_experts", "moe_combine")


def _under(names, scope):
    return [n for n in names if re.search(r"\b" + scope + r"\b", n)]


def _the_expert_loop_wraps(names, scope):
    """What ``make_routed_experts``' mapped functions open sits under
    ``moe_loop``: every op of ``moe_experts``; of ``moe_dispatch`` and
    ``moe_combine`` the rows' gather and scatter-add (the sort, the counts,
    the counters and ``y + routed_y`` are ``MoE.__call__``'s, outside the
    loop). ``moe_loop`` itself is opened twice: around the call (the loop's
    op, each client's slices and updates) and inside the mapped function (a
    loop's body is named from the scopes opened inside it down)."""
    under = _under(names, scope)
    if scope == "moe_experts":
        assert all("moe_loop" in n for n in under)
    elif scope in IN_THE_EXPERT_LOOP:
        assert any("moe_loop" in n for n in under)
    elif scope == "moe_loop":
        assert any(re.search(r"moe_loop/while/body/(dynamic_slice|dynamic_update_slice)$", n)
                   for n in under)
        assert any(re.search(r"moe_loop/while/body/.*moe_loop/", n) for n in under)


@pytest.mark.parametrize("scope", [n for n in MODEL_NAMES
                                   if n not in INDEXED_ONLY + DIFFUSION_ONLY])
def test_model_scopes_sit_under_client_grad_forward_and_backward(laguna_op_names, scope):
    """Each is in the round the LM entry compiles, always inside
    ``client_grad``, once wrapped by ``jvp(`` alone and once by
    ``transpose(``: the per-layer metrics read all of a scope's passes.
    (A sub-computation the compiler shares between call sites is lowered
    once, under its own relative path: no ``jit(`` prefix, the scope still
    in the name.)"""
    under = _under(laguna_op_names, scope)
    assert under
    outside = [n for n in under if "client_grad" not in n and n.startswith("jit(")]
    assert not outside, outside
    assert any("transpose(" in n for n in under) == (scope not in NO_BACKWARD)
    assert any("transpose(" not in n for n in under)
    _the_expert_loop_wraps(laguna_op_names, scope)


@pytest.mark.parametrize("scope", [n for n in MODEL_NAMES if n not in NEVER_INDEXED])
def test_model_scopes_of_an_indexed_model(keye_op_names, scope):
    """The same at ``keye_tiny``: its own three scopes beside the ones it
    shares with Laguna, and none of Laguna's attention kinds. The
    selection runs once, in the forward pass (its thresholds cross ``remat``
    as a residual), so ``attn_select`` has no backward wrapping (nor has
    ``param_unravel``), and it nests under ``attn_index``, whose projections
    are recomputed."""
    under = _under(keye_op_names, scope)
    assert under
    # (the chunked head is a loop, whose body the compiler names from the
    # scope down: ``jit(wrapped)/lm_head/...``; the loop's own op carries the
    # whole path, and a trace's union under ``client_grad`` holds its span;
    # what a trace holds under a model's name and outside ``client_grad`` is
    # ``model.outside_client_grad_s_per_round``, benchmark/layers/)
    assert not [n for n in under if "client_grad" not in n and n.startswith("jit(")
                and not n.startswith(f"jit(wrapped)/{scope}/")]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under) == (
        scope not in ("attn_select",) + NO_BACKWARD)
    _the_expert_loop_wraps(keye_op_names, scope)
    if scope == "attn_select":
        assert all("attn_index/" in n for n in under if n.startswith("jit("))


@pytest.mark.parametrize("scope", [n for n in MODEL_NAMES if n not in NEVER_DIFFUSION])
def test_model_scopes_of_a_block_diffusion_model(sdar_op_names, scope):
    """The same at ``sdar_tiny``: its own three scopes beside the expert
    layer's, the projections' and the head's (``lm_head`` nests under
    ``diffusion_loss``), forward and backward. ``diffusion_streams`` holds
    integer work in the forward pass (the noised ids, the join) and the
    split's slice, whose transpose is a pad in the backward pass."""
    under = _under(sdar_op_names, scope)
    assert under
    assert not [n for n in under if "client_grad" not in n and n.startswith("jit(")
                and not re.match(r"jit\(wrapped\)/(diffusion_loss|lm_head)/", n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under) == (scope not in NO_BACKWARD)
    _the_expert_loop_wraps(sdar_op_names, scope)
    if scope == "lm_head":
        assert all("diffusion_loss/" in n for n in under if n.startswith("jit("))


@pytest.mark.parametrize("scope", INDEXED_ONLY + DIFFUSION_ONLY)
def test_laguna_opens_no_scope_of_the_index_or_of_block_diffusion(laguna_op_names, scope):
    assert not _under(laguna_op_names, scope)


@pytest.mark.parametrize("scope", NEVER_DIFFUSION)
def test_a_block_diffusion_model_opens_no_other_attention_kind(sdar_op_names, scope):
    assert not _under(sdar_op_names, scope)


def test_the_noise_is_applied_under_data_gather(sdar_op_names):
    """The plan's comparison (``u < t``, the pad's) runs in the compiled
    round, beside the gather."""
    under = _under(sdar_op_names, "data_gather")
    assert any(n.endswith("/lt") for n in under) and any("gather" in n for n in under)


@pytest.mark.parametrize("scope", NEVER_INDEXED)
def test_an_indexed_model_opens_none_of_lagunas_attention_kinds(keye_op_names, scope):
    assert not _under(keye_op_names, scope)


@pytest.mark.parametrize("names", ["laguna_op_names", "keye_op_names", "sdar_op_names"])
def test_the_round_scopes_still_close_on_the_lm_round(names, request):
    rx = re.compile("|".join(NAMES))
    bare = {n for n in request.getfixturevalue(names)
            if n.startswith("jit(") and not rx.search(n)}
    # the chunked head's loop: a cast and two index broadcasts the compiler
    # lifts out of the body keep the model's scope and lose the round's
    # (a block-diffusion model's head is that loop under ``diffusion_loss``);
    # ``model.outside_client_grad_s_per_round`` reads such ops in a trace
    lifted = {n for n in bare if re.match(r"jit\(wrapped\)/(lm_head|diffusion_loss)/", n)}
    assert bare - lifted <= {"jit(wrapped)/add"}, bare
    assert len(lifted) <= 4 and (not lifted or names != "laguna_op_names"), lifted


# scalar bookkeeping and two call ops, by name with the layer's number out
# of it: the counters' maxima and sums over the layers (``LagunaLM``), the
# block's ``remat`` call itself (its body's ops carry their scopes), and the
# two sums ``ops/pallas/indexed_attention.py`` makes of an indexed layer's
# pair counts
UNNAMED_UNDER_CLIENT_GRAD = {
    "jvp(LagunaLM)/reduce_max",
    "jvp(LagunaLM)/reduce_sum",
    "transpose(jvp(LagunaLM))/vmap(client_grad)/jvp(LagunaLM)/remat2",
    "jvp(LagunaLM)/layer_N/attn/reduce_sum",
}


@pytest.mark.parametrize("names", ["laguna_op_names", "keye_op_names", "sdar_op_names"])
def test_the_model_scopes_close_on_client_grad(names, request):
    """Every op of the lowered tiny round whose name holds ``client_grad``
    holds a name of ``MODEL_SCOPES`` (``embed`` as a whole word: the module is
    named so too), but for the allow-list: what
    ``model.unnamed_s_per_round`` reads in a cell is these and the ops the
    compiler makes without a name, nothing the source left bare."""
    assert len(UNNAMED_UNDER_CLIENT_GRAD) <= 6
    rx = re.compile("|".join(r"\b" + n + r"\b" for n in MODEL_NAMES))
    bare = {re.sub(r"layer_\d+", "layer_N", n).split("vmap(client_grad)/", 1)[1]
            for n in request.getfixturevalue(names)
            if "client_grad" in n and not rx.search(n)}
    assert bare <= UNNAMED_UNDER_CLIENT_GRAD, bare - UNNAMED_UNDER_CLIENT_GRAD
