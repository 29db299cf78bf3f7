"""multihost/ — pod-scale distributed execution (ROADMAP item 1).

FetchSGD's server is a sum, and a sum over a pod is one cross-process
psum — so the multi-host story is a TOPOLOGY story, not an algorithm
story. This package owns the three planes of a distributed run:

* **topology** (``topology.py``): the global mesh grows a declared
  ``hosts`` axis (``(hosts, workers, model, seq)``; ``parallel/mesh.py
  make_mesh(hosts=)``), and :class:`HostTopology` derives each host's
  chip rows, worker-slot range, and client partition from the config —
  one source of truth every per-host component is built from.
* **data plane** (``dataplane.py``): each process realizes only its
  partition — its slots' sampler draws on its own rng stream, its rows
  of the (globally-deterministic) fedsim ``RoundEnv``, and a clientstore
  bank holding only its clients. ``assemble_rows`` lifts the slices into
  one globally-sharded array, so the round sources downstream (the
  plain loop, asyncfed) are unchanged.
* **aggregation plane**: no new code here by design — every worker-axis
  collective resolves its axis group through ``parallel.mesh
  .worker_axes(mesh)``, so the sketch-table psum and the dense fused
  all-reduce ride the ``(hosts, workers)`` tuple as ONE reduction
  (XLA lowers it to a single all-reduce whose replica groups span the
  pod), and the sparse-allreduce butterfly schedules its hops two-level:
  intra-host ppermutes over ``workers`` first, cross-host over ``hosts``
  last (``ops/collectives/sparse_allreduce.py``).

Two execution modes, one semantics (pinned bit-equal by
``tests/test_multihost.py``): **real multi-process** (``--distributed``;
``bringup.initialize_multihost`` joins the pod via jax.distributed, one
process per mesh host row) and **mesh-faked** (``--num_hosts N`` on one
process over virtual devices — N virtual hosts, N data planes, same
4-axis mesh; the CI twin that runs everywhere, since this container's
CPU jaxlib rejects cross-process collectives).
"""

from commefficient_tpu.multihost.bringup import (
    initialize_multihost,
    make_global_mesh,
)
from commefficient_tpu.multihost.dataplane import (
    MULTIHOST_STREAM,
    HostClientBank,
    HostDataPlane,
    assemble_cohort,
    assemble_rows,
    build_host_bank,
    global_client_ids,
    round_env_slice,
)
from commefficient_tpu.multihost.topology import (
    HostTopology,
    build_topology,
    client_partition,
    slot_partition,
    validate_mesh_topology,
)

__all__ = [
    "MULTIHOST_STREAM",
    "HostClientBank",
    "HostDataPlane",
    "HostTopology",
    "assemble_cohort",
    "assemble_rows",
    "build_host_bank",
    "build_topology",
    "client_partition",
    "global_client_ids",
    "initialize_multihost",
    "make_global_mesh",
    "round_env_slice",
    "slot_partition",
    "validate_mesh_topology",
]
