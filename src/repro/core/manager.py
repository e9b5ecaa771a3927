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

State is a set of bit planes over padded node ids (`repro.core.bitplane`):
which Gaussians the client holds, its previous cut, and each node's age in
syncs as eight planes of a saturating 8-bit counter — 12 bits per node on
the cloud, updated with word operations.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.serve import tracing

ID_BYTES = 4          # plain 32-bit ids on the wire
ID_BYTES_DELTA = 2    # delta-coded ids (sorted ascending, varint-ish) — model
SYNC_HEADER_BYTES = 64
POSE_UPLINK_BYTES = 100  # client → cloud pose per frame (paper §2.1)
PAGE_HEADER_BYTES = 16  # per priority page of the paged multicast stream
#                         (page rank, row count, first gid, checksum)


AGE_BITS = 8
AGE_MAX = (1 << AGE_BITS) - 1   # the age saturates here: "never, or long ago"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ManagerState:
    """Cloud-side management table (the client mirrors it deterministically),
    as bit planes over the padded node ids (`repro.core.bitplane`)."""

    client_has: jax.Array   # (W,) uint32 — which Gaussians the client stores
    age: Tuple[jax.Array, ...]  # 8 × (W,) uint32 — plane k holds bit k of
    #                         each node's age: syncs since it was last in a
    #                         cut, saturating at AGE_MAX (one byte a node,
    #                         kept as eight planes so that every operation
    #                         on it is a word operation)
    cut_prev: jax.Array     # (W,) uint32 — previous cut (membership deltas)

    @staticmethod
    def initial(n: int) -> "ManagerState":
        w = bp.words(n)
        return ManagerState(
            client_has=jnp.zeros((w,), jnp.uint32),
            age=tuple(jnp.full((w,), 0xFFFFFFFF, jnp.uint32)
                      for _ in range(AGE_BITS)),
            cut_prev=jnp.zeros((w,), jnp.uint32),
        )


def check_w_star(w_star: int) -> int:
    """The reuse window the saturating age can apply exactly: a node is
    evicted once its age passes `w_star`, and an age that saturated at
    AGE_MAX is read as "more than AGE_MAX - 1" — exact for w_star < AGE_MAX."""
    w_star = int(w_star)
    if not 0 <= w_star < AGE_MAX:
        raise ValueError(f"w_star {w_star} outside [0, {AGE_MAX}): the "
                         f"management table's {AGE_BITS}-bit age evicts "
                         f"exactly only below {AGE_MAX} syncs")
    return w_star


def age_step(age, cut: jax.Array):
    """One sync of the age planes: 0 where `cut` is set, else one more,
    saturating at AGE_MAX — a ripple-carry increment across the planes."""
    carry = ~functools.reduce(jnp.bitwise_and, age)
    out = []
    for plane in age:
        out.append((plane ^ carry) & ~cut)
        carry = plane & carry
    return tuple(out)


def age_above(age, w_star: jax.Array) -> jax.Array:
    """Plane of the nodes whose age exceeds `w_star` (a traced scalar in
    [0, AGE_MAX)): a comparison from the top plane down."""
    w = jnp.asarray(w_star, jnp.uint32)
    gt = jnp.zeros_like(age[0])
    eq = ~gt
    for k in range(AGE_BITS - 1, -1, -1):
        wk = jnp.uint32(0) - ((w >> k) & 1)     # all ones where bit k is set
        gt = gt | (eq & age[k] & ~wk)
        eq = eq & ~(age[k] ^ wk)
    return gt


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SyncPlan:
    """What one LoD sync transmits: planes over node ids (`bitplane`) and
    the counts the byte accounting needs."""

    cut: jax.Array           # (W,) uint32 — the sync's cut (render queue)
    delta_data: jax.Array    # (W,) uint32 — Δcut: attribute payload to send
    cut_add: jax.Array       # (W,) uint32 — ids entering the render queue
    cut_remove: jax.Array    # (W,) uint32 — ids leaving the render queue
    evicted: jax.Array       # (W,) uint32 — dropped by the shared reuse rule
    n_delta: jax.Array       # () int32
    n_ids: jax.Array         # () int32 — cut_add + cut_remove ids
    n_resident: jax.Array    # () int32 — client occupancy after the sync
    payload_bytes: jax.Array  # () float32 — given bytes/Gaussian (see below)

    def wire_bytes(self, bytes_per_gaussian: float) -> jax.Array:
        ids = self.n_ids.astype(jnp.float32)
        return (self.n_delta.astype(jnp.float32) * bytes_per_gaussian
                + ids * ID_BYTES_DELTA + SYNC_HEADER_BYTES)


@functools.partial(jax.jit, static_argnames=())
def cloud_sync(state: ManagerState, cut: jax.Array, w_star: jax.Array
               ) -> Tuple[ManagerState, SyncPlan]:
    """One management-table update on the cloud (paper Fig. 9, left), over
    words: `cut` is the sync's cut plane, w_star the shared reuse threshold
    (in syncs, below AGE_MAX: `check_w_star`). Every operation is per word,
    so leading (client) axes on the state and the cut batch it."""
    delta_data = cut & ~state.client_has
    cut_add = cut & ~state.cut_prev
    cut_remove = state.cut_prev & ~cut

    age = age_step(state.age, cut)
    has = state.client_has | cut
    evicted = has & age_above(age, w_star)
    has = has & ~evicted

    new_state = ManagerState(client_has=has, age=age, cut_prev=cut)
    plan = SyncPlan(
        cut=cut, delta_data=delta_data, cut_add=cut_add,
        cut_remove=cut_remove, evicted=evicted,
        n_delta=bp.count(delta_data),
        n_ids=bp.count(cut_add) + bp.count(cut_remove),
        n_resident=bp.count(has),
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


@functools.partial(jax.jit, static_argnames=("slab_width",))
@tracing.scoped("table.update")
def batched_cloud_sync(states: ManagerState, top_cut: jax.Array,
                       slab_cut: jax.Array, active: jax.Array,
                       w_star: jax.Array, *, slab_width: int
                       ) -> Tuple[ManagerState, SyncPlan]:
    """`cloud_sync` over B clients (one table per headset, one shared tree).

    The cut arrives as the search leaves it: `top_cut` (B, T) bool and the
    packed slab cuts `slab_cut` (B, Ns, words(slab_width)) uint32; it is
    joined into one (B, W) plane per client in global node order. A slot
    outside `active` ((B,) bool) takes an empty cut and keeps its table
    bitwise. Each client's slice is bit-identical to a sequential
    per-client `cloud_sync`."""
    with tracing.scope("table.update/pack"):
        on = bp.rows(active, 2)
        new, plan = cloud_sync(states, bp.join(top_cut, slab_cut, slab_width)
                               & on, w_star)
        new = jax.tree_util.tree_map(lambda n, o: jnp.where(on, n, o),
                                     new, states)
    return new, plan


def batched_wire_bytes(plan: SyncPlan, bytes_per_gaussian: float, *,
                       shared_rows=None, active=None,
                       client_pages=None) -> jax.Array:
    """(B,) per-client downlink bytes for a batched SyncPlan.

    Without `shared_rows` — the legacy unicast format: every client
    receives its own encoded Δcut stream (payload bytes ∝ its n_delta; Δ
    row ids are implicit, recomputable client-side from cut_add & ~has).

    With `shared_rows` — the encode-once fleet format
    (repro.serve.delta_path): the union Δcut is multicast ONCE as
    [union gids + encoded rows]; clients filter the stream themselves, so
    the only per-client traffic stays the membership ids + header. Each
    shared row's cost (attributes + its id) is split evenly across the
    clients that ingested it: `shared_rows` ((B,) float32,
    `DeltaBatch.shared_rows`) is, per client, the sum over the rows it
    ingested this sync of 1 / (clients that ingested the row). Rows a
    tight `delta_budget` paged out of the stream cost nothing until the
    sync that ships them. Per-client figures still sum to the fleet total:
    Σ_b bytes_b = U·(bytes_per_gaussian + ID_BYTES_DELTA) + Σ_b(ids_b·2 + hdr)
    — downlink grows with *unique* Gaussians, not with B. Crossover: a row
    with a SINGLE requester costs ID_BYTES_DELTA more than on the unicast
    path (whose Δ ids are implicit), so a fully disjoint fleet pays a small
    id overhead; sharing by ≥2 clients is always a win. `client_pages`
    ((B,) int32, same source) adds the per-page framing: PAGE_HEADER_BYTES
    for each priority page the client pulled rows from.

    `active` is an optional (B,) bool slot mask (ragged fleets,
    repro.serve.fleet): an inactive slot receives NOTHING — not even the
    sync header — so its row is exactly 0.0 bytes."""
    base = plan.n_ids.astype(jnp.float32) * ID_BYTES_DELTA + SYNC_HEADER_BYTES
    if shared_rows is None:
        out = plan.n_delta.astype(jnp.float32) * bytes_per_gaussian + base
    else:
        out = shared_rows * (bytes_per_gaussian + ID_BYTES_DELTA) + base
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
