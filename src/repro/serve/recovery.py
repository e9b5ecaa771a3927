"""Elastic fault-tolerant serving: snapshot/restore, mesh resize on load,
and sync-journal crash recovery for the fleet LoD service.

A killed `LodService` process used to lose every client's temporal/manager
state and force a cold full-tree resync — exactly the bandwidth cliff the
paper's streaming reduction exists to avoid. This module wires the dormant
`repro.checkpoint.manager` (atomic rename, async-safe layout,
reshard-on-load) into the serving stack:

  * `snapshot_service` / `restore_service` — the full service round-trip:
    the `ServiceState` pytree (fleet slots, temporal/manager state, paging
    debt, sync counters), the host control-plane mirrors (slot occupancy,
    client ids, cameras, foveation taus, Δ-payload tenancy), the
    closed-loop bitrate-controller state (targets, allowances, tau scales,
    and the PREVIOUS sync's measured wire bytes — the one-sync-delayed
    feedback the controller replays from), and the static session config in
    the manifest extras. Survivors of save→kill→restore replay **bitwise**
    against an uninterrupted service (tests/test_fleet_recovery.py, with
    the churn-conformance harness as the oracle) across the vmapped,
    pooled-XLA, and pooled-Pallas sweep implementations.
  * restore onto a DIFFERENT `clients`×`slabs` mesh — bigger, smaller, or
    none: `restore_service(..., mesh=...)` builds the target's
    `sharding.fleet.fleet_shardings` and the checkpoint layer device_puts
    every leaf under it (reshard-on-load). This generalizes `maybe_shrink`
    from capacity to devices without dropping a client.
  * `SyncJournal` + `replay` + `RecoveryManager` — an append-only,
    CRC-framed journal of per-sync INPUTS (camera updates, admits/evicts,
    bandwidth re-tiers, NACK retransmit debt) with a snapshot-every-K
    policy: a crash between checkpoints recovers by restoring the newest
    intact snapshot and deterministically re-executing the journal tail.
    `recover` walks snapshots newest-first, so a torn/corrupt newest step
    falls back to the previous one instead of diverging.

Failure semantics (the fault-injection contract): every injected fault — a
save killed mid-write (`step_*.tmp` leftovers), a truncated leaf file, a
corrupt manifest, a torn or corrupted journal — ends in either a clean
restore from an earlier consistent point or a typed `RecoveryError`. Silent
divergence is never an outcome: restored snapshots cross-check the device
`FleetState` against the snapshotted host mirrors and the shared tree
against its saved fingerprint, and journal replay verifies record
contiguity and the determinism of re-executed admissions.

Journal-file semantics worth knowing: a record is one JSON line carrying
its own `seq` and a CRC32 over the canonical encoding of the rest. A bad
line with nothing but bad/empty lines after it is a TORN TAIL (the append
the crash interrupted) — truncated away, recovery proceeds from the valid
prefix. A bad line FOLLOWED by valid records is mid-file corruption — a
`RecoveryError`, because replaying around a hole would silently diverge.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.core.lod_tree import LodTree
from repro.core.pipeline import SessionConfig
from repro.serve import fleet as flt
from repro.serve.lod_service import (AdmissionDenied, LodService,
                                     ServiceStats, empty_stats)
from repro.sharding import fleet as shd

SNAPSHOT_FORMAT = "nebula-fleet-snapshot/2"   # /2: bit-plane slot state
JOURNAL_NAME = "journal.jsonl"
SNAPSHOT_DIRNAME = "snapshots"


class RecoveryError(RuntimeError):
    """A snapshot or journal cannot be used for a faithful restore: torn or
    truncated files, corrupt manifests, fingerprint/config mismatches,
    journal holes, or non-deterministic replay. The typed alternative to
    silently serving diverged state."""


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------


def tree_fingerprint(tree: LodTree) -> Dict[str, Any]:
    """Cheap identity of the shared city tree a snapshot was taken against:
    structural sizes plus a float64 sum over the Gaussian means. Restoring
    fleet state against a DIFFERENT tree would be silently catastrophic
    (every gid reindexed) — the fingerprint turns it into a typed error."""
    m = tree.meta
    mu = np.asarray(jax.device_get(tree.gaussians.mu))
    return {
        "n_pad": int(tree.n_pad), "T": int(m.T), "Ns": int(m.Ns),
        "S": int(m.S), "n_real": int(m.n_real),
        "mu_sum": float(mu.sum(dtype=np.float64)),
    }


def _host_mirrors(service: LodService) -> Dict[str, np.ndarray]:
    """The service's host control-plane state as a flat dict of arrays (the
    `host` half of the snapshot tree). `taus` is stored dense (cfg.tau fill
    when unset — the `has_taus` extras flag restores the None); the
    previous sync's measured wire bytes ride along so the bitrate
    controller's one-sync-delayed feedback loop replays bitwise."""
    cap = service.capacity
    taus = (np.asarray(service.taus, np.float32)
            if service.taus is not None
            else np.full((cap,), service.cfg.tau, np.float32))
    if service._last_stats is not None:
        last_bytes = np.asarray(
            jax.device_get(service._last_stats.sync_bytes), np.float32)
    else:
        last_bytes = np.zeros((cap,), np.float32)
    return {
        "active": np.asarray(service._active, bool),
        "allowance": np.asarray(service._allowance, np.int64),
        "bw_target": np.asarray(service._bw_target, np.float64),
        "client_ids": np.asarray(service._client_ids, np.int64),
        "delta_ids": np.asarray(service._delta_ids, np.int64),
        "last_sync_bytes": last_bytes,
        "slot_cams": np.asarray(service._slot_cams, np.float32),
        "stats_fresh": np.asarray(service._stats_fresh, bool),
        "tau_scale": np.asarray(service._tau_scale, np.float32),
        "taus": taus,
    }


def _host_like(capacity: int) -> Dict[str, np.ndarray]:
    """Shape/dtype skeleton of `_host_mirrors` for `ckpt.restore`."""
    return {
        "active": np.zeros((capacity,), bool),
        "allowance": np.zeros((capacity,), np.int64),
        "bw_target": np.zeros((capacity,), np.float64),
        "client_ids": np.zeros((capacity,), np.int64),
        "delta_ids": np.zeros((capacity,), np.int64),
        "last_sync_bytes": np.zeros((capacity,), np.float32),
        "slot_cams": np.zeros((capacity, 3), np.float32),
        "stats_fresh": np.zeros((capacity,), bool),
        "tau_scale": np.zeros((capacity,), np.float32),
        "taus": np.zeros((capacity,), np.float32),
    }


def snapshot_service(service: LodService, directory: str, step: int = 0, *,
                     journal_seq: int = 0,
                     scheduler_state: Optional[Dict[str, Any]] = None) -> str:
    """Atomically serialize `service` as checkpoint `step_<step>` under
    `directory` (`checkpoint.manager.save`: tmp dir + fsync + rename — a
    kill mid-write leaves a `.tmp` leftover, never a half checkpoint).

    The saved tree is {"state": ServiceState, "host": mirrors}; everything
    static — session config, scheduler mode, budgets, capacity, the shared
    tree's fingerprint, the mesh signature it was saved under, and
    `journal_seq` (how many journal records precede this snapshot) — rides
    in the manifest extras. The Δ payload itself is NOT serialized (it is a
    per-sync artifact with per-sync shapes); its tenancy vector is, so a
    restored service refuses stale decode requests instead of inventing
    rows.

    `scheduler_state` (a JSON-able dict — `DeadlineScheduler.state_dict()`)
    rides in the extras too, so a recovered service can rebuild its
    deadline scheduler with the fitted cost model and per-client deadlines
    it crashed with (repro.serve.scheduler)."""
    extras = {
        "format": SNAPSHOT_FORMAT,
        "capacity": int(service.capacity),
        "next_id": int(service._next_id),
        "has_taus": service.taus is not None,
        "has_last_stats": service._last_stats is not None,
        "journal_seq": int(journal_seq),
        "cfg": dataclasses.asdict(service.cfg),
        "service": {
            "focal": float(service.focal),
            "mode": service.mode,
            "sweep_impl": service.sweep_impl,
            "dedup": bool(service.dedup),
            "page_size": int(service.page_size),
            "delta_budget_arg": (None if service._delta_budget_arg is None
                                 else int(service._delta_budget_arg)),
            "max_clients": service.max_clients,
            "max_state_bytes": service.max_state_bytes,
        },
        "tree": tree_fingerprint(service.tree),
        "mesh": shd.mesh_signature(service.mesh),
    }
    if scheduler_state is not None:
        extras["scheduler"] = scheduler_state
    tree = {"state": service.state, "host": _host_mirrors(service)}
    return ckpt.save(directory, int(step), tree, extras)


def _zero_stats(capacity: int, sync_bytes: np.ndarray) -> ServiceStats:
    """A `ServiceStats` carrying only the restored per-slot wire bytes —
    the single column the rate controller's feedback loop reads."""
    return dataclasses.replace(empty_stats(capacity),
                               sync_bytes=jnp.asarray(sync_bytes,
                                                      jnp.float32))


def _read_extras(directory: str, step: int) -> Dict[str, Any]:
    try:
        extras = ckpt.read_extras(directory, step)
    except (OSError, ValueError, KeyError) as e:
        raise RecoveryError(
            f"snapshot step {step} manifest unreadable: {e}") from e
    if extras.get("format") != SNAPSHOT_FORMAT:
        raise RecoveryError(
            f"snapshot step {step} has format {extras.get('format')!r}, "
            f"expected {SNAPSHOT_FORMAT!r}")
    return extras


def restore_service(tree: LodTree, directory: str,
                    step: Optional[int] = None, mesh=None) -> LodService:
    """Rebuild a `LodService` from a snapshot, onto any target mesh.

    `tree` must be the SAME shared city tree the snapshot was taken against
    (fingerprint-checked). `mesh` is the TARGET layout — it need not match
    the saved one: every leaf is loaded full and device_put under the new
    mesh's `fleet_shardings` (reshard-on-load), so a fleet saved on a
    2×4 mesh restores onto 4×2, 1×1, or no mesh at all, clients intact.
    `step=None` restores the newest complete snapshot.

    Raises `RecoveryError` for anything that cannot restore faithfully:
    missing/torn snapshots, truncated leaf files, corrupt manifests, a
    mismatched tree, or snapshot halves that disagree."""
    svc, _ = _restore_with_extras(tree, directory, step, mesh)
    return svc


def _restore_with_extras(tree: LodTree, directory: str,
                         step: Optional[int], mesh
                         ) -> Tuple[LodService, Dict[str, Any]]:
    if step is None:
        step = ckpt.latest_step(directory)
        if step is None:
            raise RecoveryError(f"no complete snapshot in {directory}")
    extras = _read_extras(directory, int(step))
    saved_fp = extras.get("tree", {})
    fp = tree_fingerprint(tree)
    if saved_fp != fp:
        raise RecoveryError(
            f"snapshot step {step} was taken against a different tree: "
            f"saved {saved_fp}, have {fp}")
    try:
        cfg = SessionConfig(**extras["cfg"])
        srv = extras["service"]
        capacity = int(extras["capacity"])
        svc = LodService(
            tree, cfg, n_clients=0, focal=srv["focal"], mode=srv["mode"],
            dedup=srv["dedup"], sweep_impl=srv["sweep_impl"],
            delta_budget=srv["delta_budget_arg"], capacity=capacity,
            mesh=mesh, max_clients=srv["max_clients"],
            max_state_bytes=srv["max_state_bytes"],
            page_size=srv["page_size"])
    except (KeyError, TypeError, ValueError) as e:
        raise RecoveryError(
            f"snapshot step {step} has an unusable config: {e}") from e
    # NOTE: LodService(mesh=None) falls back to the ambient use_fleet_mesh
    # mesh; a restore is explicit about its target, so pin exactly `mesh`
    # (resize_mesh also re-places the slab tables under it)
    if svc.mesh is not mesh:
        svc.resize_mesh(mesh)
    like = {"state": svc.state, "host": _host_like(capacity)}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        shardings = {
            "state": shd.fleet_shardings(mesh, svc.state),
            "host": jax.tree_util.tree_map(
                lambda a: NamedSharding(mesh, PartitionSpec()),
                _host_like(capacity)),
        }
    else:
        shardings = None
    try:
        restored = ckpt.restore(directory, int(step), like, shardings)
    except (OSError, ValueError, KeyError, EOFError) as e:
        raise RecoveryError(
            f"snapshot step {step} unrestorable: {e}") from e
    svc.state = restored["state"]
    host = jax.tree_util.tree_map(
        lambda a: np.array(jax.device_get(a)), restored["host"])
    # cross-check: the device FleetState and the host mirror were saved
    # from one consistent service — restored, they must still agree
    dev_active, dev_ids, _ = flt.fleet_mirror(svc.state.fleet)
    if (not np.array_equal(dev_active, host["active"])
            or not np.array_equal(dev_ids.astype(np.int64),
                                  host["client_ids"].astype(np.int64))):
        raise RecoveryError(
            f"snapshot step {step}: device FleetState disagrees with the "
            f"snapshotted host mirror (active/client_ids)")
    svc._active = host["active"].copy()
    svc._client_ids = host["client_ids"].copy()
    svc._slot_cams = host["slot_cams"].copy()
    svc._delta_ids = host["delta_ids"].copy()
    svc._bw_target = host["bw_target"].copy()
    svc._allowance = host["allowance"].copy()
    svc._tau_scale = host["tau_scale"].copy()
    svc._stats_fresh = host["stats_fresh"].copy()
    svc._next_id = int(extras["next_id"])
    svc.taus = host["taus"].copy() if extras["has_taus"] else None
    svc._last_stats = (_zero_stats(capacity, host["last_sync_bytes"])
                       if extras["has_last_stats"] else None)
    svc.last_delta = None  # per-sync artifact; tenancy refuses stale reads
    return svc, extras


# ---------------------------------------------------------------------------
# sync journal
# ---------------------------------------------------------------------------


def _record_crc(rec: Dict[str, Any]) -> int:
    body = {k: v for k, v in rec.items() if k != "crc"}
    canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return zlib.crc32(canon.encode("utf-8")) & 0xFFFFFFFF


class SyncJournal:
    """Append-only CRC-framed JSONL journal of service inputs.

    One record per line: `{"seq": i, "kind": ..., ..., "crc": c}` with
    `seq` dense from 0 and `crc` a CRC32 over the canonical encoding of the
    other fields. Appends flush + fsync before returning, so a record the
    caller saw appended survives the process."""

    def __init__(self, path: str, seq: int = 0):
        self.path = path
        self.seq = int(seq)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def append(self, rec: Dict[str, Any]) -> int:
        rec = dict(rec, seq=self.seq)
        rec["crc"] = _record_crc(rec)
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        self.seq += 1
        return self.seq - 1

    @staticmethod
    def read(path: str, repair: bool = True) -> List[Dict[str, Any]]:
        """Validate and load every record. A bad line at the strict TAIL
        (the append a crash interrupted — possibly followed by more
        garbage, but never by a valid record) is truncated away when
        `repair`; a bad line FOLLOWED by a valid record, or a seq hole, is
        mid-file corruption → `RecoveryError`."""
        if not os.path.exists(path):
            return []
        with open(path, "rb") as f:
            raw = f.read()
        records: List[Dict[str, Any]] = []
        good_bytes = 0
        offset = 0
        bad_at: Optional[int] = None
        lines = raw.split(b"\n")
        for i, line in enumerate(lines):
            # the final split chunk has no trailing newline: an empty one is
            # the normal file end; a non-empty one is a torn partial append
            end = offset + len(line) + (1 if i < len(lines) - 1 else 0)
            if line.strip():
                rec = None
                try:
                    parsed = json.loads(line.decode("utf-8"))
                    if (isinstance(parsed, dict)
                            and parsed.get("crc") == _record_crc(parsed)):
                        rec = parsed
                except (ValueError, UnicodeDecodeError):
                    rec = None
                if rec is None:
                    if bad_at is None:
                        bad_at = len(records)
                elif bad_at is not None:
                    raise RecoveryError(
                        f"journal {path} corrupt at record {bad_at} with "
                        f"valid records after it — a hole, not a torn tail")
                elif rec.get("seq") != len(records):
                    raise RecoveryError(
                        f"journal {path} record {len(records)} carries "
                        f"seq {rec.get('seq')} — records are missing")
                else:
                    records.append(rec)
                    good_bytes = end
            offset = end
        if bad_at is not None and repair and good_bytes < len(raw):
            with open(path, "r+b") as f:
                f.truncate(good_bytes)
        return records


def _jsonable_cam(cam) -> Optional[List[float]]:
    if cam is None:
        return None
    # float32 → float64 → float32 is exact, so the journal round-trips the
    # service's camera dtype bitwise
    return [float(x) for x in np.asarray(cam, np.float32)]


def replay(service: LodService, records) -> int:
    """Re-execute journal `records` (in order) against `service`. Returns
    the number applied. The journal holds INPUTS only — every output
    (assigned client ids, shrink results) is recomputed and, where the
    journal recorded it, verified: a mismatch means the replay is not the
    trajectory the journal describes → `RecoveryError`."""
    n = 0
    for rec in records:
        kind = rec.get("kind")
        if kind == "sync":
            cams = rec.get("cams")
            part = rec.get("participate")
            service.sync(
                None if cams is None else {
                    int(c): np.asarray(v, np.float32)
                    for c, v in cams.items()},
                participate=None if part is None
                else [int(c) for c in part])
        elif kind == "admit":
            cid = service.admit(cam=rec.get("cam"), tau=rec.get("tau"),
                                bandwidth=rec.get("bandwidth"))
            if cid != rec["id"]:
                raise RecoveryError(
                    f"replay diverged: journal admit assigned id "
                    f"{rec['id']}, replay assigned {cid}")
        elif kind == "evict":
            service.evict(rec["id"])
        elif kind == "nack":
            service.nack_rows(rec["id"], rec.get("gids", []))
        elif kind == "bandwidth":
            service.set_bandwidth(rec["id"], rec.get("target"))
        elif kind == "shrink":
            service.maybe_shrink()
        else:
            raise RecoveryError(f"unknown journal record kind {kind!r} "
                                f"(seq {rec.get('seq')})")
        n += 1
    return n


# ---------------------------------------------------------------------------
# snapshot-every-K orchestration
# ---------------------------------------------------------------------------


class RecoveryManager:
    """Crash-recoverable wrapper around a live `LodService`: every mutating
    call is write-ahead journaled, and every `every` syncs the full service
    is snapshotted (keep-last-`keep` GC bounds disk; the journal bounds
    replay work to at most `every` syncs). Drive the service THROUGH this
    wrapper — a mutation that bypasses it is invisible to recovery.

    Layout under `directory`:
        snapshots/step_<seq>/   — snapshot taken after journal record seq-1
        journal.jsonl           — the full input history (seq 0 onward)

    `recover(tree, directory)` rebuilds the newest restorable snapshot and
    replays the journal tail — the service comes back bitwise at the exact
    sync the journal last recorded."""

    def __init__(self, service: LodService, directory: str, every: int = 8,
                 keep: int = 3, *, scheduler=None,
                 _resume_seq: Optional[int] = None):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.service = service
        # optional DeadlineScheduler whose state_dict() rides in every
        # snapshot's extras (restored via `recover(...).scheduler_state`)
        self.scheduler = scheduler
        self.directory = directory
        self.snapshot_dir = os.path.join(directory, SNAPSHOT_DIRNAME)
        self.every = int(every)
        self.keep = int(keep)
        os.makedirs(self.snapshot_dir, exist_ok=True)
        self.journal = SyncJournal(os.path.join(directory, JOURNAL_NAME),
                                   seq=0 if _resume_seq is None
                                   else _resume_seq)
        self._since_snapshot = 0
        if _resume_seq is None:
            # base snapshot: recovery always has a restore point even if
            # the process dies before the first snapshot interval elapses
            self._snapshot()

    # -- persistence ----------------------------------------------------------

    def _snapshot(self) -> None:
        snapshot_service(self.service, self.snapshot_dir,
                         step=self.journal.seq,
                         journal_seq=self.journal.seq,
                         scheduler_state=None if self.scheduler is None
                         else self.scheduler.state_dict())
        self._since_snapshot = 0
        self._gc()

    def _gc(self) -> None:
        for s in ckpt.valid_steps(self.snapshot_dir)[self.keep:]:
            shutil.rmtree(
                os.path.join(self.snapshot_dir, f"step_{s:08d}"),
                ignore_errors=True)

    def snapshot_now(self) -> None:
        """Force a snapshot at the current journal position (e.g. before a
        planned shutdown, so recovery replays nothing)."""
        self._snapshot()

    # -- journaled service API -------------------------------------------------

    def sync(self, cam_positions=None, participate=None) -> ServiceStats:
        if isinstance(cam_positions, dict):
            cams = {str(int(c)): _jsonable_cam(v)
                    for c, v in cam_positions.items()}
        elif cam_positions is not None:
            arr = np.asarray(cam_positions, np.float32)
            cams = {str(int(c)): _jsonable_cam(row)
                    for c, row in zip(self.service.active_ids, arr)}
        else:
            cams = None
        if participate is not None:
            # journal STABLE CLIENT IDS, not slot indices: replay may land
            # on a restored service whose slot layout shifted (shrink), but
            # ids name the same clients
            mask = self.service._participation_mask(participate)
            ids = sorted(int(c) for c in np.asarray(
                self.service._client_ids)[mask & self.service._active])
            part = ids
        else:
            part = None
        self.journal.append({"kind": "sync", "cams": cams,
                             "participate": part})
        stats = self.service.sync(
            None if cams is None else
            {int(c): np.asarray(v, np.float32) for c, v in cams.items()},
            participate=part)
        self._since_snapshot += 1
        if self._since_snapshot >= self.every:
            self._snapshot()
        return stats

    def admit(self, cam=None, tau=None, required: bool = True,
              bandwidth=None) -> Optional[int]:
        # pre-check admission so a DENIED admit never enters the journal
        # (replay would re-raise mid-recovery otherwise)
        denial = self.service._admission_denial()
        if denial is not None:
            if required:
                raise AdmissionDenied(denial)
            return None
        cid = int(self.service._next_id)
        self.journal.append({
            "kind": "admit", "id": cid, "cam": _jsonable_cam(cam),
            "tau": None if tau is None else float(tau),
            "bandwidth": (bandwidth if bandwidth is None
                          or isinstance(bandwidth, str)
                          else float(bandwidth))})
        got = self.service.admit(cam=cam, tau=tau, bandwidth=bandwidth)
        if got != cid:
            raise RecoveryError(
                f"admit assigned id {got}, journal predicted {cid}")
        return got

    def evict(self, client_id: int) -> None:
        self.service._slot_of(client_id)  # validate BEFORE journaling
        self.journal.append({"kind": "evict", "id": int(client_id)})
        self.service.evict(client_id)

    def nack(self, client_id: int, lost_pages) -> int:
        # journal the RESOLVED gids, not the page numbers: replay must not
        # depend on a payload that died with the crashed process
        gids = self.service.resolve_nack(client_id, lost_pages)
        self.journal.append({"kind": "nack", "id": int(client_id),
                             "gids": [int(g) for g in gids]})
        return self.service.nack_rows(client_id, gids)

    def set_bandwidth(self, client_id: int, bandwidth=None) -> None:
        self.service._slot_of(client_id)  # validate BEFORE journaling
        self.journal.append({
            "kind": "bandwidth", "id": int(client_id),
            "target": (bandwidth if bandwidth is None
                       or isinstance(bandwidth, str) else float(bandwidth))})
        self.service.set_bandwidth(client_id, bandwidth)

    def maybe_shrink(self) -> Optional[int]:
        self.journal.append({"kind": "shrink"})
        return self.service.maybe_shrink()


def recover(tree: LodTree, directory: str, mesh=None, every: int = 8,
            keep: int = 3) -> Tuple[RecoveryManager, int]:
    """Crash recovery: restore the newest intact snapshot under
    `directory` and deterministically re-execute the journal tail.

    Walks complete snapshots NEWEST-FIRST — a snapshot that turns out torn,
    truncated, or corrupt falls back to the one before it (its journal tail
    is longer, so nothing is lost but replay time). Leftover `step_*.tmp`
    dirs from killed saves are swept away. A torn journal tail (the append
    the crash interrupted) is truncated; a journal hole raises.

    `mesh` is the TARGET serving mesh (restore-onto-new-mesh works across
    a crash too). Returns `(manager, replayed)` — a `RecoveryManager`
    resumed at the journal head, and how many records were re-executed.
    Raises `RecoveryError` when no snapshot can be restored."""
    snap_dir = os.path.join(directory, SNAPSHOT_DIRNAME)
    if os.path.isdir(snap_dir):
        for name in os.listdir(snap_dir):
            if name.endswith(".tmp"):
                shutil.rmtree(os.path.join(snap_dir, name),
                              ignore_errors=True)
    records = SyncJournal.read(os.path.join(directory, JOURNAL_NAME),
                               repair=True)
    failures: List[str] = []
    for step in ckpt.valid_steps(snap_dir):
        try:
            svc, extras = _restore_with_extras(tree, snap_dir, step, mesh)
        except RecoveryError as e:
            failures.append(str(e))
            continue
        base = int(extras.get("journal_seq", 0))
        if base > len(records):
            failures.append(
                f"snapshot step {step} is ahead of the journal "
                f"({base} > {len(records)} records)")
            continue
        replayed = replay(svc, records[base:])
        manager = RecoveryManager(svc, directory, every=every, keep=keep,
                                  _resume_seq=len(records))
        # the snapshotted scheduler state (if any) — the caller rebuilds a
        # DeadlineScheduler around the recovered service and
        # load_state_dict()s this (the journal replays partial ticks, but
        # the fitted cost model / deadlines live scheduler-side)
        manager.scheduler_state = extras.get("scheduler")
        return manager, replayed
    detail = "; ".join(failures) if failures else "no complete snapshot"
    raise RecoveryError(f"cannot recover from {directory}: {detail}")
