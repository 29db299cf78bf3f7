"""Per-host data plane — realize only what this host owns.

Single-host, one process realizes the whole round: the sampler's global
client draw + ``[W, B, ...]`` batch, the fedsim ``RoundEnv``'s ``[W]``
masks, and (hosted client state) the full ``[num_clients, D]`` banks. On a
pod that would make every host pay the whole population's DRAM and gather
bandwidth for rows it never feeds its chips. The data plane splits the
work along :class:`~commefficient_tpu.multihost.topology.HostTopology`'s
three partitions:

* **sampler** (:class:`HostDataPlane`): host ``h`` draws its
  ``W/num_hosts`` cohort slots from its OWN client partition on its own
  rng stream ``(seed, MULTIHOST_STREAM, host_id, round_idx)`` — separate
  realization streams, deterministic and resume-stable per host, and no
  host ever gathers another host's batch rows. ``sample_clients`` is the
  draw alone (cheap ints — any process can compute any host's ids, which
  is how the full ``[W]`` id vector exists everywhere without shipping
  data); ``sample_round`` additionally realizes the batch slice.
* **fedsim** (:func:`round_env_slice`): the ``RoundEnv`` is already a
  pure function of ``(seed, round_idx)``, so every host realizes it
  identically and keeps only its slot rows; ``live_count`` and the
  ``fedsim/*`` stats stay GLOBAL (the server renormalizes by the pod-wide
  live count).
* **clientstore** (:func:`build_host_bank`): the per-host bank stores
  rows for the host's client partition ONLY — global ids translate
  through the topology, and a foreign id is a named error, not a silent
  wrong-row gather (the PR 17 "per-host stores sharded by client
  partition" remainder).

:func:`assemble_rows` turns per-host row slices into ONE global
``jax.Array`` on the mesh's worker axes via ``make_array_from_callback``
— each process supplies data only for shards it addresses, so on a real
pod the non-owned rows never exist host-side, while on the mesh-faked
twin (all devices addressable by one process) the same call assembles all
virtual hosts' slices. The round sources downstream (the plain loop,
asyncfed) see an ordinary ``[W, ...]``-sharded array and are unchanged.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from commefficient_tpu.clientstore.streamer import build_streamer
from commefficient_tpu.fedsim.env import RoundEnv
from commefficient_tpu.multihost.topology import HostTopology
from commefficient_tpu.parallel.mesh import worker_sharding

# distinct rng stream tag: (seed, MULTIHOST_STREAM, host_id, round_idx)
# can never collide with the sampler's (seed, round_idx) or fedsim's
# (seed, FEDSIM_STREAM, round_idx) tuple seeds
MULTIHOST_STREAM = 0x40057


class HostDataPlane:
    """One host's slice of the sampler: partitioned draws + local batch
    realization, mirroring ``FedSampler``'s per-round contract at
    ``[W/num_hosts, B, ...]`` scale."""

    def __init__(self, dataset, topology: HostTopology, *,
                 local_batch_size: int, seed: int = 42, augment=None):
        if dataset.num_clients != topology.num_clients:
            raise ValueError(
                f"dataset has {dataset.num_clients} clients but the "
                f"topology was built for {topology.num_clients} — build "
                "both from the same config"
            )
        if topology.clients_per_host < topology.workers_per_host:
            raise ValueError(
                f"host {topology.host_id} owns "
                f"{topology.clients_per_host} clients but must draw "
                f"{topology.workers_per_host} distinct cohort slots per "
                "round — need num_clients >= num_workers per host "
                "partition (raise num_clients or lower num_hosts)"
            )
        self.dataset = dataset
        self.topology = topology
        self.local_batch_size = int(local_batch_size)
        self.seed = int(seed)
        self.augment = augment

    def _rng(self, round_idx: int) -> np.random.Generator:
        """This host's round stream — disjoint per host by construction
        (the host_id rides the tuple seed)."""
        return np.random.default_rng(
            (self.seed, MULTIHOST_STREAM, self.topology.host_id, round_idx)
        )

    def sample_clients(self, round_idx: int) -> np.ndarray:
        """GLOBAL client ids ``[W/num_hosts]`` for this host's slots —
        the draw alone, no batch realization (any process can afford to
        compute every host's ids from this)."""
        t = self.topology
        lo, hi = t.client_range
        rng = self._rng(round_idx)
        return (lo + rng.choice(hi - lo, size=t.workers_per_host,
                                replace=False)).astype(np.int32)

    def sample_round(
        self, round_idx: int
    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """(client_ids ``[Wl]`` global int32, batch ``{k: [Wl, B, ...]}``)
        — this host's realized slice of the round. The rng sequence is
        draw-then-batches on one generator, the ``FedSampler.sample_round``
        discipline, so realization is a pure function of
        ``(seed, host_id, round_idx)``."""
        t = self.topology
        lo, hi = t.client_range
        rng = self._rng(round_idx)
        clients = (lo + rng.choice(hi - lo, size=t.workers_per_host,
                                   replace=False)).astype(np.int32)
        B = self.local_batch_size
        shards = []
        for c in clients:
            b = self.dataset.client_batch(int(c), B, rng)
            if self.augment is not None:
                b = self.augment(b, rng)
            shards.append(b)
        batch = {k: np.stack([s[k] for s in shards]) for k in shards[0]}
        return clients, batch

    def steps_per_epoch(self) -> int:
        """GLOBAL rounds per epoch — every host must agree on the round
        schedule, so this uses the pod-wide cohort size (identical to the
        single-host ``FedSampler.steps_per_epoch``)."""
        t = self.topology
        per_round = t.num_workers * self.local_batch_size
        return max(1, len(self.dataset) // per_round)

    def epoch(self, epoch_idx: int):
        steps = self.steps_per_epoch()
        base = epoch_idx * steps
        for s in range(steps):
            yield self.sample_round(base + s)


def global_client_ids(planes: Sequence[HostDataPlane],
                      round_idx: int) -> np.ndarray:
    """The full ``[W]`` id vector from every host's draw, host-major —
    what the session's host-side row bookkeeping consumes. Draws are pure
    ints, so running all hosts' draws on one process is free; on a real
    pod each process calls this with planes for all hosts (only its own
    plane ever realizes batches)."""
    return np.concatenate([p.sample_clients(round_idx) for p in planes])


def round_env_slice(env: RoundEnv, topology: HostTopology) -> RoundEnv:
    """This host's rows of a globally-realized fedsim ``RoundEnv``.

    The masks slice to the host's slot range; ``live_count`` and the
    ``fedsim/*`` stats stay GLOBAL — the server renormalizes by pod-wide
    participation, and the stats ride every host's metric pack
    identically (constant key set, identical values)."""
    lo, hi = topology.slot_range
    return RoundEnv(
        live=env.live[lo:hi],
        corrupt=env.corrupt[lo:hi],
        live_count=env.live_count,
        stats=dict(env.stats),
    )


def assemble_rows(mesh, host_rows: Dict[int, np.ndarray], *,
                  num_hosts: int):
    """One global leading-axis-sharded ``jax.Array`` from per-host row
    slices.

    ``host_rows`` maps host_id -> that host's ``[W/num_hosts, ...]``
    slice; it must cover every host whose devices this process addresses
    (all of them on the mesh-faked twin, just itself on a real pod — the
    callback only runs for addressable shards, so foreign rows are never
    required host-side). Rows place in host-major order, matching
    ``P((HOSTS, WORKERS))``'s flat device order.
    """
    import jax

    per = None
    for h, rows in host_rows.items():
        if per is None:
            per = rows.shape[0]
        elif rows.shape[0] != per:
            raise ValueError(
                f"host {h}'s slice has {rows.shape[0]} rows, expected "
                f"{per} — every host owns num_workers/num_hosts slots"
            )
    if per is None:
        raise ValueError("host_rows is empty")
    sample = next(iter(host_rows.values()))
    shape = (per * num_hosts,) + sample.shape[1:]

    def cb(idx):
        r = idx[0]
        start = 0 if r.start is None else r.start
        stop = shape[0] if r.stop is None else r.stop
        h = start // per
        if h not in host_rows:
            raise ValueError(
                f"shard rows [{start}, {stop}) belong to host {h}, whose "
                "slice was not provided — a process must supply every "
                "host slice its addressable devices cover"
            )
        if stop > (h + 1) * per:
            raise ValueError(
                f"shard rows [{start}, {stop}) straddle a host boundary "
                f"(per-host rows={per}) — the worker axes must split the "
                "row dim host-major (is the mesh from make_mesh(hosts=)?)"
            )
        return host_rows[h][start - h * per:stop - h * per]

    return jax.make_array_from_callback(shape, worker_sharding(mesh), cb)


def assemble_cohort(mesh, parts: List[Tuple[np.ndarray, Dict[str, np.ndarray]]]):
    """(ids ``[W]`` host-side, batch ``{k: global jax.Array}``) from
    host-major per-plane ``sample_round`` outputs — the mesh-faked twin's
    one-call bridge from N virtual data planes to the session's
    ``train_round`` inputs."""
    ids = np.concatenate([p[0] for p in parts])
    n = len(parts)
    batch = {
        k: assemble_rows(mesh, {h: parts[h][1][k] for h in range(n)},
                         num_hosts=n)
        for k in parts[0][1]
    }
    return ids, batch


class _PartitionStoreCfg:
    """Duck-typed config shim handed to ``build_streamer``: identical
    store knobs, but ``num_clients`` is the PARTITION's row count and the
    mmap path carries the host id (two hosts on one filesystem must not
    share backing files)."""

    def __init__(self, cfg, topology: HostTopology):
        self.client_store = cfg.client_store
        self.client_state_hosted = cfg.client_state_hosted
        self.client_store_cache_rows = cfg.client_store_cache_rows
        self.client_store_path = (
            f"{cfg.client_store_path}.h{topology.host_id}"
            if cfg.client_store_path else ""
        )
        self.num_clients = topology.clients_per_host


class HostClientBank:
    """A ``CohortStreamer`` over ONE host's client partition, addressed
    by GLOBAL client ids — the translation (and the ownership check that
    makes a foreign id loud) lives here, so the streamer underneath is
    the stock single-host one."""

    def __init__(self, streamer, topology: HostTopology):
        self._streamer = streamer
        self.topology = topology

    def _local(self, cids) -> np.ndarray:
        cids = np.asarray(cids)
        lo, hi = self.topology.client_range
        if cids.size and (cids.min() < lo or cids.max() >= hi):
            bad = cids[(cids < lo) | (cids >= hi)]
            raise ValueError(
                f"client ids {bad.tolist()} are outside host "
                f"{self.topology.host_id}'s partition [{lo}, {hi}) — "
                "per-host banks only store the owning host's rows; draw "
                "cohorts through HostDataPlane (partitioned draws) or "
                "route the row to its owning host"
            )
        return (cids - lo).astype(cids.dtype)

    @property
    def has_vel(self) -> bool:
        return self._streamer.has_vel

    @property
    def has_err(self) -> bool:
        return self._streamer.has_err

    def gather(self, cids, trace_id=None):
        return self._streamer.gather(self._local(cids), trace_id=trace_id)

    def scatter(self, cids, new_vel, new_err, trace_id=None) -> None:
        self._streamer.scatter(self._local(cids), new_vel, new_err,
                               trace_id=trace_id)

    def flush(self) -> None:
        self._streamer.flush()

    def vel_array(self):
        self._streamer.flush()
        return self._streamer.vel_array()

    def err_array(self):
        self._streamer.flush()
        return self._streamer.err_array()

    def pop_round_stats(self) -> dict:
        return self._streamer.pop_round_stats()

    def close(self) -> None:
        self._streamer.close()


def build_host_bank(cfg, topology: HostTopology, row_dim: int, *,
                    needs_vel: bool, needs_err: bool,
                    stage_fn=None) -> Optional[HostClientBank]:
    """The per-host analog of ``clientstore.build_streamer``: same
    construction gate (None unless the config hosts client state and a
    bank is needed), but the store underneath holds only this host's
    client partition."""
    streamer = build_streamer(
        _PartitionStoreCfg(cfg, topology), row_dim,
        needs_vel=needs_vel, needs_err=needs_err, stage_fn=stage_fn,
    )
    if streamer is None:
        return None
    return HostClientBank(streamer, topology)
