"""Device-mesh helpers.

The reference's "cluster" is one host: N OS processes pinned to GPUs talking
through POSIX shared memory (SURVEY.md §2 "IPC backend"). The TPU-native
equivalent is a ``jax.sharding.Mesh``: the ``workers`` axis replaces worker
processes (gradient/sketch aggregation becomes ``lax.psum`` over ICI), and
two extra axes — ``model`` (tensor parallel) and ``seq`` (sequence parallel
for ring attention) — are capabilities the reference never had but fall out
naturally from the mesh formulation. Multi-host: build the same mesh over
``jax.devices()`` after ``jax.distributed.initialize()``; psum then spans
ICI within a slice and DCN across slices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HOSTS = "hosts"
WORKERS = "workers"
MODEL = "model"
SEQ = "seq"


def initialize_distributed() -> bool:
    """Multi-host bring-up (SURVEY.md §5 "Distributed communication
    backend" rebuild column — a capability the reference never had).

    Calls ``jax.distributed.initialize()`` when a coordinator is configured
    via the standard env (``JAX_COORDINATOR_ADDRESS`` + ``JAX_NUM_PROCESSES``
    + ``JAX_PROCESS_ID``, or a TPU pod runtime that auto-detects). After it,
    ``jax.devices()`` spans all hosts and ``make_mesh`` over the global
    device list gives psums that ride ICI within a slice and DCN across
    slices. No-op (returns False) single-host, so entry points can call it
    unconditionally.
    """
    import os

    multi_host_signals = (
        "JAX_COORDINATOR_ADDRESS",  # explicit jax.distributed coordinator
        "COORDINATOR_ADDRESS",
        "MEGASCALE_COORDINATOR_ADDRESS",  # multislice runtime
    )
    multi_host = any(os.environ.get(k) for k in multi_host_signals)
    # Cloud TPU pod metadata lists the slice's hosts; a single entry (e.g.
    # "localhost") is NOT a multi-host signal.
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    multi_host = multi_host or len([h for h in hostnames.split(",") if h]) > 1
    if not multi_host:
        return False  # single-host; don't touch the backend at all
    # NB: must not call jax.process_count()/jax.devices() first — that would
    # initialize the local backend and make distributed.initialize() raise.
    try:  # private, but the only no-side-effect way to detect prior init
        from jax._src import distributed as _dist

        if _dist.global_state.client is not None:
            return True  # already initialized
    except (ImportError, AttributeError):
        pass
    try:  # private API; if it moves, assume not-yet-initialized and proceed
        from jax._src import xla_bridge as _xb

        backend_up = _xb.backends_are_initialized()
    except (ImportError, AttributeError):
        backend_up = False
    if backend_up:
        # Too late to join the coordination service in this process (some
        # jax op already ran); proceed single-process rather than crash.
        import warnings

        warnings.warn(
            "multi-host coordinator configured but the XLA backend is "
            "already initialized; skipping jax.distributed.initialize()"
        )
        return False
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    nproc = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if addr and nproc and pid:  # empty strings fall through to auto-detect
        # explicit bring-up (e.g. CPU/GPU clusters, tests); TPU pod runtimes
        # auto-detect below instead
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=int(nproc),
            process_id=int(pid),
        )
    else:
        jax.distributed.initialize()
    return True


def make_mesh(
    num_workers_axis: int = 1,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    hosts: int = 1,
) -> Mesh:
    """A (workers, model, seq) mesh — or (hosts, workers, model, seq) when
    ``hosts > 1`` — over the available devices.

    ``num_workers_axis * model * seq`` must equal the device count used.
    With one device this still yields a valid 1x1x1 mesh, so every code path
    is mesh-shaped even single-chip (jit specializes the collectives away).

    ``hosts > 1`` splits the worker population's leading factor onto a
    declared host axis: the flat worker index ``w`` of the 3-axis mesh maps
    to ``(host=w // per_host, workers=w % per_host)`` on the 4-axis one, and
    because the device order is unchanged, ``P((HOSTS, WORKERS))`` places
    byte-identical shards to the 3-axis ``P(WORKERS)`` — the property the
    multi-host twin tests pin. ``jax.devices()`` is already process-major,
    so on a real pod the host axis coincides with process boundaries.
    """
    devices = list(devices if devices is not None else jax.devices())
    need = num_workers_axis * model * seq
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need])
    if hosts <= 1:
        return Mesh(arr.reshape(num_workers_axis, model, seq),
                    (WORKERS, MODEL, SEQ))
    if num_workers_axis % hosts:
        raise ValueError(
            f"hosts={hosts} must divide the worker axis ({num_workers_axis})"
        )
    return Mesh(
        arr.reshape(hosts, num_workers_axis // hosts, model, seq),
        (HOSTS, WORKERS, MODEL, SEQ),
    )


def worker_axes(mesh: Mesh):
    """The mesh axes a [W, ...] batch shards over: plain ``WORKERS`` on the
    3-axis mesh, the ``(HOSTS, WORKERS)`` tuple on a multi-host mesh. The
    tuple is what collectives take as ``axis_name`` so psums span both
    levels in one reduction."""
    return (HOSTS, WORKERS) if HOSTS in mesh.axis_names else WORKERS


def worker_axis_size(mesh: Mesh) -> int:
    """Total worker-slot count of the mesh (product over worker axes)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = worker_axes(mesh)
    if isinstance(axes, str):
        return sizes[axes]
    out = 1
    for a in axes:
        out *= sizes[a]
    return out


def worker_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over the worker axes (for [W, ...] batches)."""
    return NamedSharding(mesh, P(worker_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
