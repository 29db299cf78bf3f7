"""Checkpoint / resume — a strict superset of the reference's persistence.

The reference can only ``save_pretrained`` final GPT-2 weights
(fed_aggregator.py ~L260-280); killed runs restart from scratch (SURVEY.md
§5 "Checkpoint/resume"). Here the FULL federated state checkpoints through
Orbax: ``FedState`` (params vector, server momentum/error — dense or sketch
tables — HBM client rows, round counter) plus the host-offloaded client
stores. The sampler needs no state: it is deterministic from
``(seed, round)`` (data/sampler.py), so restoring ``FedState.step`` IS the
full training clock — resume reproduces the uninterrupted run bit-for-bit
(pinned by tests/test_checkpoint.py).
"""

from __future__ import annotations

import hashlib
import json
import os
import warnings
from typing import Optional

import jax
import numpy as np

from commefficient_tpu.parallel.round import FedState
from commefficient_tpu.utils.config import Config


def _spec_fingerprint(spec) -> np.ndarray:
    """The sketch-layout identity a checkpointed [r, c] table depends on.
    Equal table SHAPES do not imply equal layouts (r4: the adaptive
    scramble block changed the seed-derived permutation while shapes stayed
    identical) — decoding a table with a different layout silently yields
    garbage estimates, so restore refuses on mismatch."""
    families = {"fmix32": 1, "poly4": 2}  # stable (str hash is per-process)
    return np.asarray(
        [
            spec.d, spec.c, spec.r, spec.num_blocks, spec.seed,
            spec.chunk_m, spec.sblock, spec.band, spec.d_eff,
            spec.c_actual, families.get(spec.hash_family, 0),
        ],
        np.int64,
    )


def _to_saveable(session) -> dict:
    st = session.state
    out = {
        "fed_state": {
            f: (() if isinstance(getattr(st, f), tuple) else np.asarray(getattr(st, f)))
            for f in st._fields
        },
        "grad_size": session.grad_size,
    }
    if session.spec is not None:
        out["sketch_layout"] = _spec_fingerprint(session.spec)
    if session.host_vel is not None:
        out["host_vel"] = session.host_vel
    if session.host_err is not None:
        out["host_err"] = session.host_err
    if getattr(session, "controller", None) is not None:
        # adaptive-communication controller state (control/): active rung,
        # switch count, byte spend, policy slots — restoring it is what
        # makes a resumed run reproduce the rung sequence bit-exactly
        # (drains happen before saves, so the blob reflects every drained
        # round <= this checkpoint's step)
        out["control"] = session.controller.state_blob()
    if getattr(session, "_client_blacklist", None) is not None:
        # resilience/ skip_clients blacklist: session-cumulative and
        # monotone, so a resumed run must keep masking the clients a
        # recovery already condemned — without this leaf a preempt/resume
        # cycle would silently re-admit them
        out["blacklist"] = np.asarray(session._client_blacklist, np.int64)
    return out


def _sha256_file(path: str) -> str:
    """Chunked file digest shared by manifest write and verify — one
    idiom, so a chunk-size or algorithm change can't desync the two."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def commit_fed_state(session, fs: dict, *, origin: str = "checkpoint") -> FedState:
    """Re-commit a host-side fed_state leaf dict to ``session``'s mesh
    shardings and return the new FedState — shared by checkpoint restore
    and the resilience RollbackVault (resilience/vault.py), so the two
    in-place state-replacement paths can never drift.

    FSDP leaves go back to their P(workers) shards (a plain asarray would
    park the full padded state on ONE device — the exact memory wall FSDP
    removes), replicated-round leaves to the replicated sharding (else the
    donated round_fn compiles a second program against the
    SingleDeviceSharding layout, see FederatedSession.__init__). A leaf
    absent from ``fs`` (pre-PR2 checkpoints: no ``comp``) keeps the
    session's freshly initialized value, with a warning naming ``origin``.
    """
    if session.cfg.fsdp:
        from commefficient_tpu.parallel.fsdp import fsdp_state_shardings

        shardings = fsdp_state_shardings(session.cfg, session.mesh)
    else:
        shardings = FedState(*[session._replicated] * len(FedState._fields))
    leaves = {}
    for f in FedState._fields:
        if f not in fs:
            # legacy source with no compressor warm state — keep the
            # session's freshly initialized leaf (legacy modes: (); a
            # powersgd session restores everything else and restarts its
            # Q warm-up cold).
            leaves[f] = getattr(session.state, f)
            if not isinstance(leaves[f], tuple):
                warnings.warn(
                    f"{origin} predates the compressor warm-state leaf "
                    f"{f!r}; restored everything else and re-initialized "
                    "it (powersgd warm start restarts cold — one extra "
                    "power iteration of subspace tracking)."
                )
            continue
        leaves[f] = (
            () if isinstance(fs[f], (tuple, list)) and len(fs[f]) == 0
            else jax.device_put(
                jax.numpy.asarray(fs[f]), getattr(shardings, f)
            )
        )
    return FedState(**leaves)


class FedCheckpointer:
    """Orbax-backed checkpoint manager honoring ``cfg.checkpoint_dir`` /
    ``checkpoint_every`` / ``resume`` (the three config fields the reference
    names but VERDICT r1 found dead)."""

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.mngr = None
        if cfg.checkpoint_dir:
            import orbax.checkpoint as ocp

            self.mngr = ocp.CheckpointManager(
                os.path.abspath(cfg.checkpoint_dir),
                options=ocp.CheckpointManagerOptions(max_to_keep=3),
            )

    @property
    def enabled(self) -> bool:
        return self.mngr is not None

    def will_save(self, round_idx: int, *, force: bool = False) -> bool:
        """True iff ``maybe_save(round_idx)`` would write a checkpoint —
        lets callers flush buffered logs BEFORE the state is persisted (a
        resume fast-forwards past these rounds, so anything unflushed at
        save time would be lost for good)."""
        if not self.enabled:
            return False
        every = self.cfg.checkpoint_every
        return force or (every > 0 and round_idx > 0 and round_idx % every == 0)

    def maybe_save(self, session, round_idx: int, *, force: bool = False) -> bool:
        """Save if ``checkpoint_every`` divides ``round_idx`` (or forced).
        A step already on disk is never re-saved (the runner's
        end-of-training force-save may land on a boundary the loop
        already wrote). Every save also writes an integrity manifest
        sidecar (sizes + sha256 per file) that ``restore`` verifies —
        a truncated/corrupted step is then rejected with its reason
        instead of restored as garbage."""
        if not self.will_save(round_idx, force=force):
            return False
        if self.mngr.latest_step() == round_idx:
            return False
        import orbax.checkpoint as ocp

        self.mngr.save(
            round_idx, args=ocp.args.StandardSave(_to_saveable(session))
        )
        self.mngr.wait_until_finished()
        self._write_manifest(round_idx)
        return True

    def latest_step(self) -> Optional[int]:
        return self.mngr.latest_step() if self.enabled else None

    def discard_steps_after(self, step: int) -> None:
        """Resilience rollback support: retained checkpoints ABOVE the
        rollback step were saved from the rolled-back trajectory. A
        ``retry`` replay reproduces them bit-identically, but ``demote``/
        ``skip_clients`` fork — leaving the old step on disk would make
        the replay's ``maybe_save`` at that boundary a silent no-op and a
        later ``--resume`` restore a PRE-recovery state (stale rung floor
        / blacklist). Delete them so the replay re-saves its own."""
        if not self.enabled:
            return
        for s in sorted(int(x) for x in (self.mngr.all_steps() or [])):
            if s > int(step):
                self.mngr.delete(s)
        self.mngr.wait_until_finished()
        self._gc_manifests()

    def resave(self, session, step: int) -> bool:
        """Persist the CURRENT session state at ``step``, replacing any
        retained checkpoint there. Used after a FORKING recovery
        (demote/skip_clients): the rollback restored round ``step``'s
        params, but the policy then mutated session state the retained
        blob predates (the demotion floor, the blacklist) — a crash
        before the next boundary would otherwise ``--resume`` without
        the fork. No-op when checkpointing is off."""
        if not self.enabled:
            return False
        if int(step) in {int(s) for s in (self.mngr.all_steps() or [])}:
            self.mngr.delete(int(step))
            self.mngr.wait_until_finished()
        return self.maybe_save(session, int(step), force=True)

    # -- integrity manifests (resilience: checkpoint fallback) -------------
    def _root(self) -> str:
        return os.path.abspath(self.cfg.checkpoint_dir)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._root(), str(int(step)))

    def _manifest_path(self, step: int) -> str:
        # sidecars live OUTSIDE the orbax step dirs (an extra file inside
        # one could be mistaken for an item); GC'd alongside rotation
        return os.path.join(self._root(), "manifests", f"{int(step)}.json")

    def _write_manifest(self, step: int) -> Optional[str]:
        """Hash every file of the committed step into
        ``<dir>/manifests/<step>.json`` (atomic write), and drop sidecars
        of rotated-away steps. Best-effort: a manifest failure must not
        kill the save (the checkpoint itself is already durable; restore
        just loses pre-verification for this step)."""
        try:
            step_dir = self._step_dir(step)
            files = {}
            for dirpath, _dirs, fnames in os.walk(step_dir):
                for fn in sorted(fnames):
                    p = os.path.join(dirpath, fn)
                    files[os.path.relpath(p, step_dir)] = {
                        "size": os.path.getsize(p),
                        "sha256": _sha256_file(p),
                    }
            path = self._manifest_path(step)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": int(step), "files": files}, f, indent=2)
            os.replace(tmp, path)
            self._gc_manifests()
            return path
        except Exception as e:  # noqa: BLE001 — observability, not data
            warnings.warn(
                f"checkpoint manifest for step {step} not written "
                f"({type(e).__name__}: {e}); restore will skip integrity "
                "verification for this step"
            )
            return None

    def _gc_manifests(self) -> None:
        mdir = os.path.join(self._root(), "manifests")
        if not os.path.isdir(mdir):
            return
        retained = {int(s) for s in (self.mngr.all_steps() or [])}
        for fn in os.listdir(mdir):
            stem, ext = os.path.splitext(fn)
            if ext == ".json" and stem.isdigit() and int(stem) not in retained:
                try:
                    os.remove(os.path.join(mdir, fn))
                except OSError:
                    pass

    def verify_step(self, step: int) -> Optional[str]:
        """Integrity-check the on-disk step against its manifest sidecar.
        Returns None when consistent (or when no sidecar exists — a
        legacy checkpoint has nothing to verify against), else a
        human-readable rejection reason naming the first mismatch."""
        path = self._manifest_path(step)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                manifest = json.load(f)
            files = manifest["files"]
        except Exception as e:  # noqa: BLE001 — a bad sidecar IS a reason
            return f"unreadable manifest sidecar ({type(e).__name__}: {e})"
        step_dir = self._step_dir(step)
        for rel, info in sorted(files.items()):
            p = os.path.join(step_dir, rel)
            if not os.path.exists(p):
                return f"missing file {rel!r}"
            size = os.path.getsize(p)
            if size != info["size"]:
                return (f"size mismatch at {rel!r} ({size} B on disk, "
                        f"manifest says {info['size']} B)")
            if _sha256_file(p) != info["sha256"]:
                return f"sha256 mismatch at {rel!r}"
        return None

    def _saved_lacks_sketch_layout(self, step: int, exc: Exception) -> bool:
        """True if the on-disk checkpoint at ``step`` predates the r4
        sketch-layout stamp. Probes the saved item structure (ADVICE r4:
        orbax's exception text is not a stable interface); only if the
        metadata probe itself fails does it fall back to matching the
        exception text — worst case the raw orbax error propagates, which
        still fails safe."""
        try:
            meta = self.mngr.item_metadata(step)
            # ADVICE r5 #2: orbax returns a Mapping here in some versions
            # and an iterable-of-keys view in others — normalize before
            # membership tests so the probe is not version-coupled.
            keys = set(meta.keys()) if hasattr(meta, "keys") else set(meta)
            if not {"fed_state", "grad_size"} <= keys:
                # every checkpoint this module ever wrote has these
                # siblings; their absence means the probe surfaced some
                # OTHER structure (or a corrupt item) — do not classify
                # the stamp's absence as "pre-stamp" from it.
                return "sketch_layout" in str(exc)
            return "sketch_layout" not in keys
        except Exception:  # noqa: BLE001 — probe is best-effort
            return "sketch_layout" in str(exc)

    @staticmethod
    def _rung_template_candidates(session) -> list:
        """Rung indices whose state template is worth attempting a restore
        under: the active rung first, then ONE representative of every
        other distinct (momentum, error, comp) shape signature. A k-only
        ladder has a single signature (rung switches don't change state
        shapes), so restore never retries; a num_cols/rank ladder retries
        once per distinct geometry until the template matches the rung the
        checkpoint was saved at (the controller blob then names it
        exactly). ``[None]`` for control-less sessions."""
        rungs = getattr(session, "rungs", None)
        if rungs is None or len(rungs) <= 1:
            return [None]

        def sig(i):
            st = session._rung_state_struct(rungs[i])
            return tuple(
                tuple(getattr(st, f).shape)
                if hasattr(getattr(st, f), "shape") else ()
                for f in ("momentum", "error", "comp")
            )

        out = [session.active_rung]
        seen = {sig(session.active_rung)}
        for i in range(len(rungs)):
            s = sig(i)
            if s not in seen:
                seen.add(s)
                out.append(i)
        return out

    def _attempt_restore(self, step: int, template: dict):
        """One StandardRestore attempt, absorbing the known
        template/saved key differences: pre-PR2 checkpoints lack the
        ``comp`` FedState leaf; pre-control checkpoints lack the
        ``control`` blob — each retried with the key dropped (the session
        keeps its fresh leaf/state). The mismatch is detected from the
        exception because ``item_metadata`` returns None on a freshly
        opened manager — no handler registry yet — so a pre-restore
        structure probe is not available."""
        import orbax.checkpoint as ocp

        template = {**template, "fed_state": dict(template["fed_state"])}
        for _ in range(4):  # at most: full, ±blacklist, -control, -comp
            try:
                return self.mngr.restore(
                    step, args=ocp.args.StandardRestore(template)
                )
            except ValueError as e:
                msg = str(e)
                # the installed orbax names each differing key under this
                # headline ("<key>: - Source: MISSING ...")
                if "tree structures do not match" not in msg:
                    raise
                if "blacklist" in msg:
                    if "blacklist" in template:
                        # checkpoint predates (or never had) a blacklist:
                        # the session keeps its own
                        template.pop("blacklist")
                    else:
                        # checkpoint CARRIES a blacklist this fresh
                        # session doesn't know yet — restore it (shape
                        # comes from the saved array)
                        template["blacklist"] = np.zeros(0, np.int64)
                    continue
                if "control" in template and "control" in msg:
                    # pre-control checkpoint into a controlled session:
                    # restore the rest; the controller starts at its
                    # initial rung (warned below, once restore succeeds)
                    template.pop("control")
                    continue
                if "comp" in template["fed_state"] and "comp" in msg:
                    # pre-PR2 checkpoint: retry with the 6-leaf template
                    template["fed_state"].pop("comp")
                    continue
                if "control" in msg and "control" not in template:
                    raise ValueError(
                        "checkpoint carries adaptive-control state "
                        "('control' blob) but this session was built "
                        "without a controller — restore with the same "
                        "control_policy/ladder the run was saved under "
                        f"(underlying: {e})"
                    ) from e
                raise
        raise ValueError("restore retries exhausted")  # unreachable

    def restore(self, session, step: Optional[int] = None) -> Optional[int]:
        """Restore into ``session`` in place; returns the restored round
        index (== FedState.step) or None if nothing to restore.

        Integrity fallback (resilience pillar 3): with ``step=None`` the
        walk starts at the latest retained step, pre-verifies it against
        its manifest sidecar, and on a mismatch — or ANY restore failure —
        falls back to the next older retained step with a warning naming
        the rejected step and the reason, only failing when the whole
        vault is exhausted (the final error chains every per-step
        failure). An EXPLICIT ``step`` is restored strictly: the caller
        named it, so a bad step raises instead of silently substituting
        an older one."""
        if not self.enabled:
            return None
        if step is not None:
            bad = self.verify_step(step)
            if bad is not None:
                raise ValueError(
                    f"checkpoint at step {step} failed integrity "
                    f"verification: {bad}"
                )
            return self._restore_step(session, step)
        steps = sorted((s for s in (self.mngr.all_steps() or [])),
                       reverse=True)
        if not steps:
            return None
        failures = []
        last_exc: Optional[Exception] = None
        for n, s in enumerate(steps):
            older = len(steps) - n - 1
            reason = self.verify_step(s)
            if reason is None:
                try:
                    return self._restore_step(session, s)
                except Exception as e:  # noqa: BLE001 — walk back
                    reason = f"{type(e).__name__}: {e}"
                    last_exc = last_exc or e
            failures.append((s, reason))
            warnings.warn(
                f"checkpoint at step {s} REJECTED ({reason})"
                + (f"; falling back to the next of {older} older retained "
                   "step(s)" if older else "; no older retained steps left")
            )
        raise ValueError(
            "restore failed at every retained checkpoint step — "
            + "; ".join(f"step {s}: {r}" for s, r in failures)
        ) from last_exc

    def _restore_step(self, session, step: int) -> int:
        """One step's restore (the pre-fallback restore semantics).

        Controlled sessions (control/ ladder): the checkpointed server
        state is laid out for the rung ACTIVE at save time, which a
        shape-changing ladder (num_cols/powersgd_rank) may make differ
        from the session's current template — restore walks the distinct
        rung layouts until one matches, then the restored ``control``
        blob re-activates the exact saved rung and policy state, so the
        resumed run reproduces the uninterrupted rung sequence."""
        candidates = self._rung_template_candidates(session)
        try:
            restored = None
            attempts = []  # (template label, exception) per failed layout
            for n, cand in enumerate(candidates):
                if cand is not None and cand != session.active_rung:
                    # rebuild the template in rung ``cand``'s layout; the
                    # migrated VALUES are irrelevant (overwritten on
                    # success) — only the shapes matter here
                    session.set_active_rung(cand, migrate=True)
                try:
                    restored = self._attempt_restore(
                        step, _to_saveable(session)
                    )
                    break
                except Exception as exc:  # noqa: BLE001 — try next layout
                    label = ("base template" if cand is None
                             else f"rung {cand} template")
                    attempts.append((label, exc))
                    if n == len(candidates) - 1:
                        if len(attempts) == 1:
                            raise
                        # every candidate failed: name EACH attempt and
                        # chain the FIRST (the active-rung template is
                        # tried first and is the likely save-time layout —
                        # a genuine corruption error there must not be
                        # masked by a later layout's shape mismatch)
                        raise ValueError(
                            "restore failed under every rung state "
                            "template — "
                            + "; ".join(
                                f"{lab}: {type(e).__name__}: {e}"
                                for lab, e in attempts
                            )
                            + " (the first attempt's failure is chained "
                            "as the cause)"
                        ) from attempts[0][1]
        except Exception as e:  # noqa: BLE001 — re-raise with provenance
            if session.spec is not None and self._saved_lacks_sketch_layout(
                step, e
            ):
                # NB the stamp's absence is the LIKELY cause, not a certain
                # one (review r5: a pre-stamp checkpoint can also fail for
                # an unrelated reason, e.g. a truncated write) — so the
                # original failure rides along in the message and as
                # __cause__.
                raise ValueError(
                    "restore failed and the checkpoint lacks the "
                    "sketch-layout stamp (pre-r4): its momentum/error "
                    "tables may have been written under a different "
                    "CountSketch layout (e.g. the pre-r4 scramble_block=8 "
                    "default) and cannot be safely decoded. Re-train, or "
                    "restore with a session whose "
                    "CountSketch(scramble_block=...) matches the run that "
                    "wrote the checkpoint. (If the layout is not the "
                    f"problem, the underlying failure was: {e})"
                ) from e
            raise
        if (getattr(session, "controller", None) is not None
                and "control" in restored):
            # activate the SAVED rung before the layout/shape checks below:
            # the restored leaves (and the sketch-layout stamp) are in that
            # rung's geometry, not necessarily the session's current one.
            # (Dispatch swap only — the leaves themselves load further
            # down; the controller's counters load after them.)
            saved_rung = int(np.asarray(restored["control"])[1])
            if 0 <= saved_rung < len(session.rungs):
                session.set_active_rung(saved_rung, migrate=False)
        if session.spec is not None and "sketch_layout" in restored:
            want = _spec_fingerprint(session.spec)
            got = np.asarray(restored["sketch_layout"])
            if not np.array_equal(want, got):
                raise ValueError(
                    "checkpoint sketch layout != this session's: the "
                    "[r, c] tables were written under a different "
                    f"CountSketch geometry (stamp {got.tolist()} vs "
                    f"{want.tolist()}; fields: d, c, r, num_blocks, seed, "
                    "chunk_m, sblock, band, d_eff, c_actual, "
                    "hash_family) — decoding them here would corrupt "
                    "training silently. Match the spec (e.g. pin "
                    "scramble_block) or re-train."
                )
        if restored["grad_size"] != session.grad_size:
            raise ValueError(
                f"checkpoint grad_size {restored['grad_size']} != model "
                f"{session.grad_size} — wrong model/config for this checkpoint"
            )
        fs = restored["fed_state"]
        # shared leaf-commit path (also the resilience RollbackVault's):
        # every leaf back onto its mesh sharding, missing legacy leaves
        # kept fresh with a warning
        session.state = commit_fed_state(
            session, fs, origin=f"checkpoint at step {step}"
        )
        if "host_vel" in restored:
            session.host_vel = np.asarray(restored["host_vel"])
        if "host_err" in restored:
            session.host_err = np.asarray(restored["host_err"])
        if getattr(session, "controller", None) is not None:
            if "control" in restored:
                # re-activates the saved rung (the restored leaves are
                # already in its layout — dispatch swap only, no
                # migration) + the policy's decision state, so the
                # resumed rung sequence is bit-identical to the
                # uninterrupted run's
                session.controller.load_state_blob(restored["control"])
            else:
                warnings.warn(
                    f"checkpoint at step {step} predates the adaptive-"
                    "communication controller; restored everything else — "
                    "the controller starts fresh (initial rung, zero byte "
                    "spend), so the resumed rung sequence is NOT the "
                    "uninterrupted run's"
                )
        if "blacklist" in restored:
            # resilience/ skip_clients: re-condemn the clients a recovery
            # blacklisted before the save — blacklist_clients validates
            # the session can actually mask them (fedsim), so a config
            # mismatch fails loudly instead of silently re-admitting them
            bl = np.asarray(restored["blacklist"], np.int64).ravel()
            if bl.size:
                session.blacklist_clients(bl)
        # the fedsim availability/chaos schedule keys off a host round
        # clock mirroring FedState.step — re-sync it so a resumed run
        # realizes the SAME masks the uninterrupted run would have
        session.sync_round_clock()
        return int(np.asarray(fs["step"]))

    def close(self):
        """Release the Orbax manager. Idempotent: the shared runner closes
        it in its ``finally`` block (crash paths included), and the train
        entries' own ``finally`` may close again — the second call is a
        no-op, not a double-close error."""
        if self.mngr is not None:
            self.mngr.close()
            self.mngr = None
