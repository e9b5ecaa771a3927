"""Runtime Gaussian management (paper §4.3).

The cloud keeps a management table tracking which Gaussians the client holds;
per LoD-sync (every `w` frames) it transmits only:

  * the **Δcut** — Gaussians newly needed and not cached on the client
    (attribute payload, compressed by repro.core.compression);
  * the **cut-membership delta** — ids entering/leaving the render queue
    (ids only; Fig. 7 temporal similarity makes this ~1% of the cut).

Both sides then run the *same* reuse-window eviction rule (w_r* = 32 syncs by
default) on identical inputs, so no eviction traffic is needed and the two
tables stay consistent — the GC-like co-design of the paper. The client
renders its exact received cut between syncs (DESIGN.md §7: with the radial
LoD metric the cut is orientation-free, so head rotation needs no new data).

State is a dense bitmap over padded node ids (5 bytes/node on the cloud —
~5 MB per million Gaussians), sharded with the tree on the cloud mesh.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import tracing

ID_BYTES = 4          # plain 32-bit ids on the wire
ID_BYTES_DELTA = 2    # delta-coded ids (sorted ascending, varint-ish) — model
SYNC_HEADER_BYTES = 64
POSE_UPLINK_BYTES = 100  # client → cloud pose per frame (paper §2.1)
PAGE_HEADER_BYTES = 16  # per priority page of the paged multicast stream
#                         (page rank, row count, first gid, checksum)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ManagerState:
    """Cloud-side management table (the client mirrors it deterministically)."""

    client_has: jax.Array   # (N,) bool — which Gaussians the client stores
    last_used: jax.Array    # (N,) int32 — sync index when last in a cut
    cut_prev: jax.Array     # (N,) bool — previous cut (for membership deltas)

    @staticmethod
    def initial(n: int) -> "ManagerState":
        return ManagerState(
            client_has=jnp.zeros((n,), bool),
            last_used=jnp.full((n,), -(2**30), jnp.int32),
            cut_prev=jnp.zeros((n,), bool),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SyncPlan:
    """What one LoD sync transmits (masks over node ids + byte accounting)."""

    delta_data: jax.Array    # (N,) bool — Δcut: attribute payload to send
    cut_add: jax.Array       # (N,) bool — ids entering the render queue
    cut_remove: jax.Array    # (N,) bool — ids leaving the render queue
    evicted: jax.Array       # (N,) bool — dropped by the shared reuse rule
    n_delta: jax.Array       # () int32
    n_resident: jax.Array    # () int32 — client occupancy after the sync
    payload_bytes: jax.Array  # () float32 — given bytes/Gaussian (see below)

    def wire_bytes(self, bytes_per_gaussian: float) -> jax.Array:
        ids = (self.cut_add.sum() + self.cut_remove.sum()).astype(jnp.float32)
        return (self.n_delta.astype(jnp.float32) * bytes_per_gaussian
                + ids * ID_BYTES_DELTA + SYNC_HEADER_BYTES)


@functools.partial(jax.jit, static_argnames=())
def cloud_sync(state: ManagerState, cut_mask: jax.Array, t: jax.Array,
               w_star: jax.Array) -> Tuple[ManagerState, SyncPlan]:
    """One management-table update on the cloud (paper Fig. 9, left).

    t is the sync counter; w_star the shared reuse threshold (in syncs)."""
    delta_data = cut_mask & ~state.client_has
    cut_add = cut_mask & ~state.cut_prev
    cut_remove = state.cut_prev & ~cut_mask

    last_used = jnp.where(cut_mask, t, state.last_used)
    has = state.client_has | cut_mask
    evicted = has & ((t - last_used) > w_star)
    has = has & ~evicted

    new_state = ManagerState(client_has=has, last_used=last_used, cut_prev=cut_mask)
    plan = SyncPlan(
        delta_data=delta_data, cut_add=cut_add, cut_remove=cut_remove,
        evicted=evicted,
        n_delta=delta_data.sum().astype(jnp.int32),
        n_resident=has.sum().astype(jnp.int32),
        payload_bytes=jnp.float32(0.0),
    )
    return new_state, plan


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ClientState:
    """Client-side mirror: reconstructs the same table from the wire data only
    (Δcut ids + cut add/remove ids) — used to *prove* consistency in tests."""

    has: jax.Array
    last_used: jax.Array
    cut: jax.Array  # current render queue (bool mask)

    @staticmethod
    def initial(n: int) -> "ClientState":
        return ClientState(
            has=jnp.zeros((n,), bool),
            last_used=jnp.full((n,), -(2**30), jnp.int32),
            cut=jnp.zeros((n,), bool),
        )


@functools.partial(jax.jit, static_argnames=())
def client_sync(state: ClientState, delta_data: jax.Array, cut_add: jax.Array,
                cut_remove: jax.Array, t: jax.Array, w_star: jax.Array
                ) -> ClientState:
    """Apply one received sync. Inputs are exactly what came off the wire."""
    cut = (state.cut | cut_add) & ~cut_remove
    has = state.has | delta_data          # insert received Gaussians
    last_used = jnp.where(cut, t, state.last_used)
    has = has | cut                       # cut members are resident by definition
    keep = (t - last_used) <= w_star
    has = has & keep
    return ClientState(has=has, last_used=last_used, cut=cut)


def gather_payload(tree_gaussians, delta_mask: jax.Array, budget: int):
    """Compact Δcut ids (sorted, -1 padded) for payload gather/compression."""
    (ids,) = jnp.nonzero(delta_mask, size=budget, fill_value=-1)
    count = delta_mask.sum().astype(jnp.int32)
    return ids.astype(jnp.int32), count


# ---------------------------------------------------------------------------
# batched multi-client tables
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
@tracing.scoped("table.update")
def batched_cloud_sync(states: ManagerState, cut_masks: jax.Array,
                       ts: jax.Array, w_star: jax.Array
                       ) -> Tuple[ManagerState, SyncPlan]:
    """`cloud_sync` vmapped over B clients (one table per headset, one shared
    tree). `states` leaves are (B, N); cut_masks (B, N); ts (B,). The reuse
    window is shared. Returns batched (ManagerState, SyncPlan) — each client's
    slice is bit-identical to a sequential per-client `cloud_sync`."""
    return jax.vmap(cloud_sync, in_axes=(0, 0, 0, None))(
        states, cut_masks, ts, w_star)


def _pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis by halving it: elementwise adds in one fixed
    order whatever the leading axes' layout, so a fleet sharded over clients
    sums each client's floats exactly as one device does (a reduce may pick
    its order per program; on a TPU it did, per-device shape by shape)."""
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def batched_wire_bytes(plan: SyncPlan, bytes_per_gaussian: float, *,
                       shared_payload: bool = False,
                       active=None, delivered=None,
                       client_pages=None) -> jax.Array:
    """(B,) per-client downlink bytes for a batched SyncPlan.

    (`SyncPlan.wire_bytes` reduces over every axis and is only correct for the
    unbatched case.)

    shared_payload=False — the legacy unicast format: every client receives
    its own encoded Δcut stream (payload bytes ∝ its n_delta; Δ row ids are
    implicit, recomputable client-side from cut_add & ~has).

    shared_payload=True — the encode-once fleet format
    (repro.serve.delta_path): the union Δcut is multicast ONCE as
    [union gids + encoded rows]; clients filter the stream themselves, so the
    only per-client traffic stays the membership ids + header. Each shared
    row's cost (attributes + its id) is split evenly across the clients that
    requested it, so per-client figures still sum to the fleet total:
    Σ_b bytes_b = U·(bytes_per_gaussian + ID_BYTES_DELTA) + Σ_b(ids_b·2 + hdr)
    — downlink grows with *unique* Gaussians, not with B. Crossover: a row
    with a SINGLE requester costs ID_BYTES_DELTA more than on the unicast
    path (whose Δ ids are implicit), so a fully disjoint fleet pays a small
    id overhead; sharing by ≥2 clients is always a win.

    `delivered` is an optional (B, N) bool mask of the rows each client
    ACTUALLY ingested this sync (`DeltaBatch.delivered` from the paged
    stream, repro.serve.delta_path). Without it the shared split charges
    `plan.delta_data` — every requested row, INCLUDING rows a tight
    `delta_budget` paged out of the stream; pass it so deferred rows cost
    nothing until the sync that ships them (the silent-overcharge bug the
    paged stream fixes). `client_pages` ((B,) int32, same source) adds the
    per-page framing: PAGE_HEADER_BYTES for each priority page the client
    pulled rows from.

    `active` is an optional (B,) bool slot mask (ragged fleets,
    repro.serve.fleet): an inactive slot receives NOTHING — not even the
    sync header — so its row is exactly 0.0 bytes, and inactive slots are
    excluded from the shared-row requester split."""
    delta = plan.delta_data if delivered is None else delivered
    if active is not None:
        delta = delta & active[:, None]
    ids = (plan.cut_add.sum(axis=1) + plan.cut_remove.sum(axis=1)
           ).astype(jnp.float32)
    base = ids * ID_BYTES_DELTA + SYNC_HEADER_BYTES
    if not shared_payload:
        out = plan.n_delta.astype(jnp.float32) * bytes_per_gaussian + base
    else:
        share = delta.sum(axis=0)                            # (N,) requesters
        frac = _pairwise_sum(jnp.where(delta,
                                       1.0 / jnp.maximum(share, 1)[None, :],
                                       0.0))
        out = frac * (bytes_per_gaussian + ID_BYTES_DELTA) + base
        if client_pages is not None:
            out = out + client_pages.astype(jnp.float32) * PAGE_HEADER_BYTES
    if active is not None:
        out = jnp.where(active, out, 0.0)
    return out


# ---------------------------------------------------------------------------
# numpy reference (independent oracle for the property tests)
# ---------------------------------------------------------------------------


def reference_manager_np(cut_masks: np.ndarray, w_star: int):
    """Straight-line trace of the paper's table semantics over a cut sequence.

    cut_masks: (F, N) bool. Returns per-sync (delta_counts, resident_counts)."""
    f, n = cut_masks.shape
    has = np.zeros(n, bool)
    last = np.full(n, -(2**30), np.int64)
    deltas, residents = [], []
    for t in range(f):
        cut = cut_masks[t]
        deltas.append(int((cut & ~has).sum()))
        last[cut] = t
        has |= cut
        has &= (t - last) <= w_star
        residents.append(int(has.sum()))
    return np.asarray(deltas), np.asarray(residents)
