"""Multi-host execution (multihost/): topology, per-host data plane,
mesh-faked twins, and the REAL 2-process jax.distributed leg.

Two execution modes, one semantics:

* **real multi-process** — two OS processes x 4 virtual CPU devices
  joined through ``multihost.initialize_multihost`` (Gloo standing in for
  DCN), one 8-device ``(hosts, workers, model, seq)`` global mesh, and a
  federated sketch round whose psum crosses the process boundary. Runs
  wherever the probe says cross-process CPU collectives work (this
  container's jaxlib rejects them — a toolchain property, so the leg
  SKIPs here and runs on real pods).
* **mesh-faked twin** — ``num_hosts=2`` on ONE process over the suite's 8
  virtual devices: same 4-axis mesh, same tuple-axis collectives, no
  process boundary. The twin is pinned BIT-EQUAL (params array-equal,
  drained loss sequence identical) to the flat single-host run across
  modes, fedsim masking, and checkpoint resume — the CI-runnable proof
  that declaring the host axis re-shapes the mesh without changing a
  single reduction.

Plus the traffic pins: the compiled multihost sketch round lowers its
table psum to exactly ONE all-reduce whose replica group spans the pod,
and the two-level butterfly keeps log2(W) hops.
"""

import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

_CHILD = os.path.join(os.path.dirname(__file__), "multihost_child.py")


# ---------------------------------------------------------------------------
# real 2-process leg (probe-gated)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# minimal two-process jax.distributed bring-up: init + the cross-process
# replicated device_put the federated session does first (device_put with a
# multi-process sharding runs multihost_utils.assert_equal, whose
# broadcast_one_to_all psum is the op this container's jaxlib rejects with
# "Multiprocess computations aren't implemented on the CPU backend")
_PROBE = """
import sys
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:%d",
                           num_processes=2, process_id=int(sys.argv[1]))
import numpy as np
from jax.experimental import multihost_utils
multihost_utils.broadcast_one_to_all(np.zeros(1, np.float32))
print("PROBE_OK")
"""


@pytest.fixture(scope="module")
def multiprocess_cpu_probe():
    """Env probe: can THIS container run two-process jax.distributed
    collectives on CPU at all? Some jaxlib CPU builds (this container's
    0.4.37 among them) reject every cross-process computation with
    'Multiprocess computations aren't implemented on the CPU backend' —
    a toolchain property, not a regression in this repo. The probe runs
    the minimal init + one cross-process broadcast; on failure the real
    test SKIPs with the diagnosis (and still runs wherever distributed
    init works)."""
    port = _free_port()
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("JAX_", "XLA_"))
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE % port, str(i)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs, timed_out = [], False
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                timed_out = True
                out = "(probe timed out after 120s)"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    if timed_out or any(p.returncode != 0 for p in procs):
        tail = "\n".join(o[-400:] for o in outs)
        known = "Multiprocess computations aren't implemented" in tail
        pytest.skip(
            "two-process jax.distributed is broken in this environment: "
            + ("this jaxlib's CPU backend rejects cross-process "
               "computations ('Multiprocess computations aren't "
               "implemented on the CPU backend') — a container/toolchain "
               "limitation, not a repo regression"
               if known else
               f"probe failed with an unrecognized error:\n{tail}")
            + " — skipping the federated two-process round; it runs "
            "wherever distributed init works (e.g. real multi-host TPU)."
        )


def test_two_process_federated_round(multiprocess_cpu_probe):
    """The real leg: two processes bring up through multihost/
    (initialize_multihost + make_global_mesh + per-host data planes) and
    run sketch rounds over the pod mesh — both must report the SAME loss
    (the aggregation is global)."""
    port = _free_port()
    env = {
        k: v
        for k, v in os.environ.items()
        # the child builds its own jax env from scratch
        if not k.startswith(("JAX_", "XLA_"))
    }
    procs = [
        subprocess.Popen(
            [sys.executable, _CHILD, str(i), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=280)
            outs.append(out)
    finally:
        # a crashed child leaves its peer blocked in the cross-process
        # psum forever — never leak the pair past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
    losses = []
    for out in outs:
        m = re.search(r"MULTIHOST_OK pid=\d+ loss=([0-9.]+)", out)
        assert m, out[-2000:]
        losses.append(float(m.group(1)))
    assert losses[0] == losses[1], f"processes disagree: {losses}"


# ---------------------------------------------------------------------------
# everything below runs in-process on the suite's 8 virtual devices
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from commefficient_tpu.data import FedDataset, FedSampler  # noqa: E402
from commefficient_tpu.multihost import (  # noqa: E402
    HostDataPlane,
    assemble_cohort,
    assemble_rows,
    build_host_bank,
    build_topology,
    client_partition,
    global_client_ids,
    round_env_slice,
    slot_partition,
    validate_mesh_topology,
)
from commefficient_tpu.parallel import FederatedSession  # noqa: E402
from commefficient_tpu.parallel.mesh import (  # noqa: E402
    HOSTS,
    WORKERS,
    make_mesh,
    worker_axes,
    worker_axis_size,
)
from commefficient_tpu.utils.config import Config  # noqa: E402

from tests.test_round import BASE, _setup  # noqa: E402


# -- topology --------------------------------------------------------------

def test_partitions_tile_their_ranges():
    """Slot and client partitions are contiguous, host-major, and tile
    the global range exactly — every id owned by exactly one host."""
    assert slot_partition(8, 2, 0) == (0, 4)
    assert slot_partition(8, 2, 1) == (4, 8)
    with pytest.raises(ValueError, match="divisible"):
        slot_partition(8, 3, 0)
    with pytest.raises(ValueError, match="host_id"):
        slot_partition(8, 2, 2)
    # balanced-to-within-one client split, remainder to the first hosts
    for C, H in ((12, 2), (13, 2), (10, 4), (7, 4)):
        ranges = [client_partition(C, H, h) for h in range(H)]
        flat = [c for lo, hi in ranges for c in range(lo, hi)]
        assert flat == list(range(C)), (C, H, ranges)
        sizes = [hi - lo for lo, hi in ranges]
        assert max(sizes) - min(sizes) <= 1
    with pytest.raises(ValueError, match="host_id"):
        client_partition(12, 2, -1)


def test_build_topology_and_mesh_cross_check():
    cfg = Config(mode="uncompressed", num_hosts=2, **BASE)
    topos = [build_topology(cfg, host_id=h) for h in range(2)]
    for h, t in enumerate(topos):
        assert t.host_id == h
        assert t.chips_per_host == 4
        assert t.workers_per_host == 4
        assert t.slot_range == (4 * h, 4 * h + 4)
    t0 = topos[0]
    assert t0.owns_client(t0.client_range[0])
    assert not t0.owns_client(topos[1].client_range[0])
    assert t0.local_client(t0.client_range[0]) == 0
    with pytest.raises(ValueError, match="partition"):
        t0.local_client(topos[1].client_range[0])
    # host_id defaults to jax.process_index() (0 in this suite)
    assert build_topology(cfg).host_id == 0
    validate_mesh_topology(make_mesh(8, hosts=2), t0)
    with pytest.raises(ValueError, match="mesh declares"):
        validate_mesh_topology(make_mesh(8), t0)


# -- mesh hosts axis -------------------------------------------------------

def test_make_mesh_hosts_axis():
    """make_mesh(hosts=) declares the 4-axis mesh WITHOUT reordering
    devices (host h's rows are exactly its contiguous device block), and
    the 3-axis shape is untouched for every existing caller."""
    flat = make_mesh(8)
    assert flat.axis_names == (WORKERS, "model", "seq")
    assert flat.devices.shape == (8, 1, 1)
    assert worker_axes(flat) == WORKERS
    m = make_mesh(8, hosts=2)
    assert m.axis_names == (HOSTS, WORKERS, "model", "seq")
    assert m.devices.shape == (2, 4, 1, 1)
    assert worker_axes(m) == (HOSTS, WORKERS)
    assert worker_axis_size(m) == worker_axis_size(flat) == 8
    # identical flat device order: the 4-axis mesh is a reshape, not a
    # permutation — this is what makes the twin runs byte-comparable
    assert list(m.devices.reshape(-1)) == list(flat.devices.reshape(-1))
    # hosts=1 stays 3-axis (no degenerate axis for single-host runs)
    assert make_mesh(8, hosts=1).axis_names == flat.axis_names


def test_config_refuses_incompatible_multihost_knobs():
    base = dict(BASE)
    with pytest.raises(ValueError, match="power"):
        Config(mode="uncompressed", num_hosts=3, **{**base, "num_workers": 6,
                                                    "num_devices": 6})
    with pytest.raises(ValueError, match="num_hosts"):
        Config(mode="uncompressed", distributed=True, **base)
    with pytest.raises(ValueError, match="workers axis"):
        Config(mode="uncompressed", num_hosts=2, fsdp=True, **base)
    with pytest.raises(ValueError, match="workers axis"):
        Config(mode="uncompressed", num_hosts=2, model_axis=2,
               **{**base, "num_devices": 16, "num_workers": 16,
                  "num_clients": 32})
    with pytest.raises(ValueError, match="num_hosts"):
        Config(mode="uncompressed", num_hosts=16, **base)


# -- mesh-faked twin bit-equality (THE acceptance pin) ---------------------

def _twin_run(cfg, n_rounds=3, ckpt_at=None, tmp_path=None):
    """(losses, params_vec) after ``n_rounds`` — optionally killing the
    session at ``ckpt_at`` and resuming from its checkpoint."""
    from commefficient_tpu.utils.checkpoint import FedCheckpointer

    ds, params, loss_fn = _setup(cfg.num_clients)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    sess = FederatedSession(cfg, params, loss_fn)
    ckpt = FedCheckpointer(cfg) if ckpt_at is not None else None
    losses = []
    for r in range(n_rounds):
        ids, batch = sampler.sample_round(r)
        m = sess.train_round(ids, batch, lr=0.1 + 0.02 * r)
        losses.append(float(m["loss"]))
        if ckpt is not None:
            ckpt.maybe_save(sess, r + 1)
        if ckpt_at is not None and r + 1 == ckpt_at:
            # kill: fresh process state, restore, continue
            ckpt.close()
            ds2, params2, loss_fn2 = _setup(cfg.num_clients)
            sess = FederatedSession(cfg, params2, loss_fn2)
            ckpt = FedCheckpointer(cfg)
            assert ckpt.restore(sess) == ckpt_at
    if ckpt is not None:
        ckpt.close()
    return losses, np.asarray(sess.state.params_vec)


@pytest.mark.parametrize("mode,extra", [
    ("uncompressed", dict(error_type="none", virtual_momentum=0.0)),
    ("sketch", dict(error_type="virtual", virtual_momentum=0.9, k=40,
                    num_rows=3, num_cols=512)),
    ("local_topk", dict(error_type="local", local_momentum=0.9, k=30)),
])
def test_meshfaked_twin_bit_equal(mode, extra):
    """The central pin: the 2-virtual-host run (4-axis mesh, tuple-axis
    collectives) is BIT-equal to the flat single-host run on the same
    inputs — drained loss sequence identical, final params array-equal.
    The host axis may only re-shape the mesh, never change a sum."""
    losses1, params1 = _twin_run(Config(mode=mode, **extra, **BASE))
    losses2, params2 = _twin_run(
        Config(mode=mode, **extra, num_hosts=2, **BASE))
    assert losses1 == losses2, (losses1, losses2)
    np.testing.assert_array_equal(params1, params2)


def test_meshfaked_twin_bit_equal_fedsim_masking():
    """fedsim composition: the bernoulli dropout masks are a pure
    function of (seed, round), so the masked multihost round must stay
    bit-equal to its single-host twin — renormalization included."""
    extra = dict(error_type="virtual", virtual_momentum=0.9, k=40,
                 num_rows=3, num_cols=512, availability="bernoulli",
                 dropout_prob=0.3)
    losses1, params1 = _twin_run(Config(mode="sketch", **extra, **BASE))
    losses2, params2 = _twin_run(
        Config(mode="sketch", **extra, num_hosts=2, **BASE))
    assert losses1 == losses2
    np.testing.assert_array_equal(params1, params2)


def test_meshfaked_twin_bit_equal_checkpoint_resume(tmp_path):
    """Kill-and-resume on the 2-host mesh reproduces the uninterrupted
    single-host run bit-for-bit — the checkpoint round-trips the 4-axis
    shardings and the twin equality survives a process boundary."""
    extra = dict(error_type="virtual", virtual_momentum=0.9, k=40,
                 num_rows=3, num_cols=512)
    losses1, params1 = _twin_run(
        Config(mode="sketch", **extra, **BASE), n_rounds=4)
    cfg2 = Config(mode="sketch", **extra, num_hosts=2,
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
                  **BASE)
    losses2, params2 = _twin_run(cfg2, n_rounds=4, ckpt_at=2)
    assert losses1 == losses2
    np.testing.assert_array_equal(params1, params2)


# -- compiled traffic pins -------------------------------------------------

def test_hlo_multihost_sketch_single_cross_host_all_reduce():
    """The aggregation-plane pin: the compiled 2-host sketch round
    (dense decode, telemetry 0) lowers the table psum over the
    ``(hosts, workers)`` tuple axis to exactly ONE all-reduce, and its
    replica group spans the whole pod — one reduction, not one per
    level, and nothing left behind on the intra-host axis."""
    cfg = Config(mode="sketch", error_type="virtual", virtual_momentum=0.9,
                 k=40, num_rows=3, num_cols=512, sketch_decode="dense",
                 telemetry_level=0, num_hosts=2, **BASE)
    ds, params, loss_fn = _setup(cfg.num_clients)
    sess = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(ds, num_workers=cfg.num_workers,
                         local_batch_size=cfg.local_batch_size, seed=1)
    ids, batch = sampler.sample_round(0)
    args = [sess.state, jnp.asarray(ids),
            {k: jnp.asarray(v) for k, v in batch.items()}, jnp.float32(0.2)]
    text = sess.round_fn.lower(*args).compile().as_text()
    ars = [ln for ln in text.splitlines()
           if re.search(r"=\s*[^=]*all-reduce(-start)?\(", ln)]
    assert len(ars) == 1, (
        f"expected exactly ONE all-reduce in the multihost sketch round, "
        f"found {len(ars)}: "
        + "; ".join(ln.strip()[:100] for ln in ars)
    )
    m = re.search(r"replica_groups=\{\{([\d,]+)\}\}", ars[0])
    assert m, f"unparseable replica_groups: {ars[0].strip()[:200]}"
    group = sorted(int(x) for x in m.group(1).split(","))
    assert group == list(range(8)), (
        f"the table psum's replica group must span the pod, got {group}"
    )


def test_butterfly_two_level_hop_count_and_equivalence():
    """The two-level butterfly on the 4-axis mesh: intra-host hops over
    ``workers`` first, cross-host over ``hosts`` last — still exactly
    log2(W) hops total (2 ppermutes per hop: indices + values), and the
    result equals dense psum-then-slice."""
    from commefficient_tpu.ops.collectives.sparse_allreduce import (
        sparse_allreduce_sharded,
    )

    rng = np.random.default_rng(3)
    d, k, W, H = 512, 5, 8, 2
    dense = np.zeros((W, d), np.float32)
    for w in range(W):
        sup = rng.choice(d, size=k, replace=False)
        dense[w, sup] = rng.normal(size=k).astype(np.float32)
    mesh = make_mesh(W, hosts=H)
    f = jax.jit(jax.shard_map(
        lambda v: sparse_allreduce_sharded(
            v[0], k, (HOSTS, WORKERS), axis_size=W,
            axis_sizes=(H, W // H))[None],
        mesh=mesh, in_specs=(P((HOSTS, WORKERS)),),
        out_specs=P((HOSTS, WORKERS)),
    ))
    out = np.asarray(f(jnp.asarray(dense))).reshape(-1)
    np.testing.assert_allclose(out, dense.sum(axis=0), atol=1e-6)
    text = f.lower(
        jax.ShapeDtypeStruct((W, d), jnp.float32)).compile().as_text()
    hops = [ln for ln in text.splitlines()
            if re.search(r"=\s*[^=]*collective-permute(-start)?\(", ln)]
    n_hops = int(np.log2(W))
    assert len(hops) == 2 * n_hops, (
        f"two-level schedule must keep log2(W)={n_hops} hops "
        f"(2 ppermutes each), found {len(hops)} permutes"
    )
    assert "all-reduce" not in text
    assert "all-gather" not in text


def test_multihost_scalars_ride_level1_rounds():
    """Telemetry (schema v12): a num_hosts > 1 session's rounds carry the
    multihost/* topology scalars at level >= 1 — and single-host rounds
    carry none (constant key set per config)."""
    extra = dict(error_type="virtual", virtual_momentum=0.9, k=40,
                 num_rows=3, num_cols=512, telemetry_level=1)
    ds, params, loss_fn = _setup(12)
    sampler = FedSampler(ds, num_workers=8, local_batch_size=4, seed=1)
    ids, batch = sampler.sample_round(0)
    m2 = FederatedSession(
        Config(mode="sketch", num_hosts=2, **extra, **BASE),
        params, loss_fn).train_round(ids, batch, 0.2)
    assert m2["multihost/num_processes"] == 1.0  # mesh-faked twin
    assert m2["multihost/host_id"] == 0.0
    assert m2["multihost/cross_host_bytes"] >= 0.0
    assert m2["multihost/dcn_exposed_ms"] >= 0.0
    m1 = FederatedSession(
        Config(mode="sketch", **extra, **BASE),
        params, loss_fn).train_round(ids, batch, 0.2)
    assert not any(k.startswith("multihost/") for k in m1)


# -- per-host data plane ---------------------------------------------------

def _plane_fixture(num_hosts=2, num_clients=12, seed=7):
    cfg = Config(mode="uncompressed", num_hosts=num_hosts,
                 **{**BASE, "num_clients": num_clients})
    ds, _, _ = _setup(num_clients)
    planes = [
        HostDataPlane(ds, build_topology(cfg, host_id=h),
                      local_batch_size=cfg.local_batch_size, seed=seed)
        for h in range(num_hosts)
    ]
    return cfg, ds, planes


def test_dataplane_partitioned_draws_deterministic():
    """Each host draws its slots from its OWN client partition on its own
    stream: deterministic per (host, round), distinct ids within a draw,
    never a foreign client — and the global id vector is host-major."""
    cfg, _, planes = _plane_fixture()
    for rnd in range(3):
        for p in planes:
            ids = p.sample_clients(rnd)
            assert ids.shape == (4,)
            assert len(set(ids.tolist())) == 4
            lo, hi = p.topology.client_range
            assert ((ids >= lo) & (ids < hi)).all(), (ids, (lo, hi))
            np.testing.assert_array_equal(ids, p.sample_clients(rnd))
        np.testing.assert_array_equal(
            global_client_ids(planes, rnd),
            np.concatenate([p.sample_clients(rnd) for p in planes]))
    # different streams: the two hosts' round-0 LOCAL draws differ
    local = [p.sample_clients(0) - p.topology.client_range[0]
             for p in planes]
    assert not np.array_equal(local[0], local[1])
    # sample_round realizes the same draw it samples
    ids, batch = planes[0].sample_round(1)
    np.testing.assert_array_equal(ids, planes[0].sample_clients(1))
    assert batch["x"].shape[:2] == (4, cfg.local_batch_size)


def test_dataplane_refuses_mismatched_geometry():
    cfg, ds, _ = _plane_fixture()
    with pytest.raises(ValueError, match="clients"):
        HostDataPlane(ds, build_topology(cfg.replace(num_clients=20),
                                         host_id=0),
                      local_batch_size=4)
    # a partition smaller than its slot count cannot draw w/o replacement
    # (unreachable through a valid Config, which keeps num_clients >=
    # num_workers — pinned on a hand-built topology)
    from commefficient_tpu.multihost import HostTopology

    ds8, _, _ = _setup(8)
    starved = HostTopology(num_hosts=2, host_id=0, num_workers=8,
                           num_clients=8, chips_per_host=4,
                           slot_range=(0, 4), client_range=(0, 2))
    with pytest.raises(ValueError, match="distinct cohort slots"):
        HostDataPlane(ds8, starved, local_batch_size=4)


def test_assemble_rows_and_cohort():
    """assemble_rows lifts host-major slices into ONE worker-sharded
    global array (shards never straddle hosts); assemble_cohort is the
    twin's bridge from N planes to train_round inputs."""
    mesh = make_mesh(8, hosts=2)
    rows = {h: np.arange(4 * 3, dtype=np.float32).reshape(4, 3) + 100 * h
            for h in range(2)}
    arr = assemble_rows(mesh, rows, num_hosts=2)
    np.testing.assert_array_equal(
        np.asarray(arr), np.concatenate([rows[0], rows[1]]))
    assert arr.sharding.spec == P((HOSTS, WORKERS))
    with pytest.raises(ValueError, match="every host"):
        assemble_rows(mesh, {0: rows[0]}, num_hosts=2)
    with pytest.raises(ValueError, match="rows"):
        assemble_rows(mesh, {0: rows[0], 1: rows[1][:2]}, num_hosts=2)
    # cohort bridge over real per-host planes
    _, _, planes = _plane_fixture()
    parts = [p.sample_round(0) for p in planes]
    ids, batch = assemble_cohort(mesh, parts)
    np.testing.assert_array_equal(
        ids, np.concatenate([parts[0][0], parts[1][0]]))
    for k in parts[0][1]:
        np.testing.assert_array_equal(
            np.asarray(batch[k]),
            np.concatenate([parts[0][1][k], parts[1][1][k]]))


def test_round_env_slices_tile_the_global_env():
    """fedsim: every host realizes the same global RoundEnv and keeps its
    slot rows; live_count and stats stay GLOBAL on every slice."""
    from commefficient_tpu.fedsim import build_environment

    cfg = Config(mode="uncompressed", num_hosts=2,
                 availability="bernoulli", dropout_prob=0.4, **BASE)
    env = build_environment(cfg).round_env(0)
    topos = [build_topology(cfg, host_id=h) for h in range(2)]
    slices = [round_env_slice(env, t) for t in topos]
    np.testing.assert_array_equal(
        np.concatenate([s.live for s in slices]), env.live)
    np.testing.assert_array_equal(
        np.concatenate([s.corrupt for s in slices]), env.corrupt)
    for s in slices:
        assert s.live_count == env.live_count
        assert s.stats == env.stats


def test_host_bank_partition_sized_and_refuses_foreign_ids():
    """clientstore (the PR 17 remainder): each host's bank holds only its
    partition's rows, addressed by GLOBAL ids; a foreign id is a named
    error, not a silent wrong-row gather."""
    cfg = Config(mode="local_topk", error_type="local", k=30,
                 client_store="host", num_hosts=2, **BASE)
    topo = build_topology(cfg, host_id=1)
    bank = build_host_bank(cfg, topo, row_dim=16,
                           needs_vel=False, needs_err=True)
    assert bank is not None
    try:
        assert bank.err_array().shape == (topo.clients_per_host, 16)
        lo, hi = topo.client_range
        own = np.arange(lo, min(lo + 2, hi), dtype=np.int32)
        cohort = bank.gather(own)  # global ids translate through the topo
        assert cohort.err.shape[0] == own.size
        foreign = np.asarray([0], dtype=np.int32)  # host 0's client
        with pytest.raises(ValueError, match="partition"):
            bank.gather(foreign)
    finally:
        bank.close()
    # same construction gate as the single-host streamer
    dev_cfg = cfg.replace(client_store="device")
    assert build_host_bank(dev_cfg, topo, row_dim=16,
                           needs_vel=False, needs_err=True) is None
