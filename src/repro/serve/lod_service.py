"""Batched multi-client LoD service — the cloud half of paper Fig. 9/10 at
serving scale.

In the paper's collaborative split, the cloud runs the temporal-aware LoD
search and the Gaussian-management table per headset, and ships compressed
Δcuts downstream; the client only renders (Fig. 10 keeps the
motion-to-photon path entirely client-side). This module scales the cloud
half from one headset to B concurrent headsets against ONE shared city tree:

  * one `LodTree` + one scene codec are shared by every client (the codec is
    scene-level, so the client-side "codebook buffer" of §5 is identical for
    all users);
  * per-client state — `TemporalState` (LoD-search reuse), `ManagerState`
    (management table), sync counters — is stacked on a leading batch axis
    (`ServiceState`), exactly the functional-core layout of
    repro.core.pipeline scaled to B;
  * `service_sync_vmapped` runs the per-frame temporal LoD search vmapped
    across clients: one fused device program, bit-identical per client to the
    sequential single-client search;
  * `service_sync_pooled` is the production scheduler: the cheap exact
    top-tree sweep + staleness predicate runs vmapped for all clients, then
    the *stale (client, slab) pairs of every client are pooled into one
    power-of-two bucket* and swept by a single dispatch (each pair carries
    its own camera and τ). Pooling, compaction, and the pair gather all run
    ON DEVICE — the only host transfers on the steady-state path are two
    scalars, the stale-pool size and the Δ-union size, each picking a
    static pow2 bucket (bounded recompilation); the staleness and Δ masks
    themselves never leave the device. Wall-clock cost scales with TOTAL
    staleness in the fleet, not with client count;
  * the sync tail is **encode-once** (`repro.serve.delta_path`): the
    fleet-union Δcut is quantized/packed by ONE batched codec call and
    fanned out as (union-offset, mask) references, so downlink bytes and
    cloud encode FLOPs grow with the fleet's *unique* Gaussians, not with B
    — co-located viewers are nearly free.

Scheduling is double-buffered by construction: every sync is dispatched
asynchronously and only the bucket-size scalars are awaited, so while the
host schedules the pooled slab sweep of sync t the device is still executing
the management-table update + encode of sync t−1 (see
`service_sync_pooled`).

The fleet is RAGGED at runtime (repro.serve.fleet): clients are admitted
and evicted mid-session via `LodService.admit` / `LodService.evict`. State
lives in a slot array whose capacity grows on the shared
`lod_search.pow2_bucket` policy — admits/evicts *within* a capacity bucket
are jitted slot scatters (zero recompiles; the slot index is a traced
argument) and a bucket growth pads every leaf and retraces each jitted
path exactly once. Inactive slots are provably free: they contribute no
staleness to the pooled bucket, no rows to the Δ-union encode, no bytes to
the wire accounting (not even a header), and no tiles to the pooled fleet
rasterizer — and their per-slot state stays bitwise frozen at the reset
value, so a surviving client's trajectory is bitwise identical to a
fixed-size service of just the survivors (tests/test_fleet_churn.py).

The service runs MESH-SHARDED when given a `clients`×`slabs` serving mesh
(`LodService(mesh=...)` or the ambient
`repro.sharding.fleet.use_fleet_mesh`): per-slot state shards on its
leading slot axis over `clients` (each host owns a contiguous block of
slots — its staleness pool, tables, and wire accounting live with its
clients), the shared slab attribute tables and the union codec rows shard
over `slabs`, the pooled staleness compaction becomes per-client-shard
pow2 buckets (one per-shard count vector awaited instead of one scalar),
and the Δ-union payload replicates across client shards (the multicast
stream is broadcast to everyone anyway). With no mesh — or any indivisible
layout — every constraint falls back to replicate and the service is
bitwise the single-device one (tests/test_sharding_fleet.py).

Per-sync, per-client byte and work accounting (`ServiceStats`, now including
`unique_delta` / `dedup_bytes_saved`) feeds benchmarks/bench_multiclient.py,
benchmarks/bench_fleet_sync.py, benchmarks/bench_fleet_churn.py and
benchmarks/bench_fleet_shard.py (the multi-user analogs of the paper's
bandwidth figures); `repro.sharding.fleet.fleet_totals` psums the per-slot
columns to fleet scalars across client shards.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core import compression as comp
from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.core.gaussians import Gaussians
from repro.core.lod_tree import LodTree
from repro.core.pipeline import SessionConfig, session_wire_format
from repro.kernels import lod_cut as lc
from repro.serve import delta_path as dp
from repro.serve import fleet as flt
from repro.serve import tracing
from repro.sharding import fleet as shd
from repro import render as rnd


class AdmissionDenied(RuntimeError):
    """`LodService.admit` refused: the configured fleet budget (client count
    or state-byte budget) is exhausted — backpressure instead of unbounded
    capacity growth."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServiceState:
    """All per-client cloud state, batched on a leading (C, ...) SLOT axis.

    The leading axis is the fleet's slot CAPACITY, not its live client
    count: `fleet` (repro.serve.fleet.FleetState) records which slots hold a
    client. A fully-active fleet is exactly the legacy fixed-size service."""

    mgr: mgr.ManagerState       # bit planes, leaves (C, W) and (C, 8, W)
    temporal: ls.TemporalState  # leaves (C, Ns, ...); slab_cut0 packed
    cut_gids: jax.Array         # (C, cut_budget) int32, -1 padded
    sync_index: jax.Array       # (C,) int32 — per-slot syncs WHILE ACTIVE
    pending: jax.Array          # (C, W) uint32 plane (`repro.core.bitplane`)
    #                             — Δ rows owed to the slot from
    #                             earlier paged syncs (deferred by the
    #                             stream budget / row allowance); folded
    #                             into the next sync's union as forced-stale
    #                             membership until they ship. All-False for
    #                             inactive slots (an evicted slot drops its
    #                             debt; an admitted slot starts clean).
    fleet: flt.FleetState       # slot occupancy / client ids / generations

    @property
    def capacity(self) -> int:
        return self.sync_index.shape[0]

    @property
    def n_clients(self) -> int:
        """Slot capacity (kept for API compatibility — the legacy fixed
        service had n_clients == capacity; live count is `fleet.active`)."""
        return self.sync_index.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServiceStats:
    """Per-client accounting for one service sync (all leaves (C,), the
    slot capacity; inactive slots report all-zero rows — not even a sync
    header is charged to an empty slot)."""

    cut_size: jax.Array        # int32 — render-queue size
    delta_size: jax.Array      # int32 — Δcut Gaussians shipped to the client
    unique_delta: jax.Array    # int32 — Δ rows this client contributed to the
    #                            fleet union (first requester); sums to the
    #                            union size across clients
    sync_bytes: jax.Array      # float32 — downlink bytes (payload + ids)
    dedup_bytes_saved: jax.Array  # float32 — unicast-path bytes minus
    #                            encode-once bytes (0 when dedup is off;
    #                            slightly NEGATIVE for a sole requester —
    #                            the shared stream carries explicit union
    #                            ids the unicast format left implicit)
    nodes_touched: jax.Array   # int32 — LoD-search work attributed to client
    resweeps: jax.Array        # int32 — stale subtrees swept
    client_resident: jax.Array  # int32 — client store occupancy after sync
    overflow: jax.Array        # bool — cut exceeded cut_budget (queue truncated)
    delta_overflow: jax.Array  # bool — PER CLIENT: ≥1 of this client's Δ
    #                            rows was deferred to a later page this sync
    #                            (stream budget or row allowance; the rows
    #                            are carried over, never lost — always False
    #                            with dedup off or the default budget)
    delta_shipped: jax.Array   # int32 — union rows the client actually
    #                            ingested this sync (== delta_size unless
    #                            rows were deferred, by this sync or earlier)
    delta_deferred: jax.Array  # int32 — rows owed to the client AFTER this
    #                            sync (its carry-over into the next union;
    #                            0 once the paged stream has converged)
    pages: jax.Array           # int32 — priority pages the client pulled
    #                            rows from this sync (page-header framing)
    mtp_ms: jax.Array          # float32 — motion-to-photon latency this sync
    #                            closed for the client: ms from its oldest
    #                            unserved motion sample to this sync's
    #                            completion. Wall-clock is a HOST concept, so
    #                            the sync paths emit 0.0 and the deadline
    #                            scheduler (repro.serve.scheduler) stamps the
    #                            column on the stats it returns; 0.0 for
    #                            slots with no motion served this sync.
    deadline_miss: jax.Array   # bool — the served motion overran the
    #                            client's frame deadline (stamped by the
    #                            scheduler alongside mtp_ms; always False on
    #                            the raw lockstep sync paths)


def service_init(tree: LodTree, cfg: SessionConfig, n_clients: int,
                 capacity: Optional[int] = None) -> ServiceState:
    """Service state for `n_clients` live clients in a `capacity`-slot
    array (default: capacity == n_clients, the legacy fixed-size layout —
    pre-provision a pow2 capacity to admit clients without an early
    growth recompile)."""
    m = tree.meta
    cap = max(n_clients, 1) if capacity is None else int(capacity)
    if cap < max(n_clients, 1):
        raise ValueError(f"capacity {cap} < n_clients {n_clients}")
    return ServiceState(
        mgr=jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cap,) + a.shape),
            mgr.ManagerState.initial(tree.n_pad)),
        temporal=ls.TemporalState.initial_batched(m.Ns, m.S, cap),
        cut_gids=jnp.full((cap, cfg.cut_budget), -1, jnp.int32),
        sync_index=jnp.zeros((cap,), jnp.int32),
        pending=jnp.zeros((cap, bp.words(tree.n_pad)), jnp.uint32),
        fleet=flt.fleet_init(cap, n_clients),
    )


# ---------------------------------------------------------------------------
# fleet lifecycle: slot admission / eviction / capacity growth
# ---------------------------------------------------------------------------


def _fresh_slot_leaves(state: ServiceState):
    """(fresh ManagerState, fresh TemporalState, fresh cut row, fresh sync
    counter, fresh pending row) for one slot — shapes from the traced
    state, so usable in jit."""
    w = state.mgr.client_has.shape[1]
    ns, sw = state.temporal.slab_cut0.shape[1:]
    return (mgr.ManagerState.initial(bp.WORD * w),
            ls.TemporalState.initial(ns, bp.WORD * sw),
            jnp.full((state.cut_gids.shape[1],), -1, jnp.int32), jnp.int32(0),
            jnp.zeros((w,), jnp.uint32))


def _reset_slot(state: ServiceState, slot) -> ServiceState:
    f_mgr, f_tmp, f_cut, f_idx, f_pend = _fresh_slot_leaves(state)
    return ServiceState(
        mgr=flt.reset_slot(state.mgr, f_mgr, slot),
        temporal=flt.reset_slot(state.temporal, f_tmp, slot),
        cut_gids=state.cut_gids.at[jnp.asarray(slot, jnp.int32)].set(f_cut),
        sync_index=state.sync_index.at[jnp.asarray(slot, jnp.int32)].set(f_idx),
        pending=state.pending.at[jnp.asarray(slot, jnp.int32)].set(f_pend),
        fleet=state.fleet,
    )


@jax.jit
def service_admit_slot(state: ServiceState, slot, client_id) -> ServiceState:
    """Admit `client_id` into `slot`: reset every per-slot leaf to its fresh
    value (temporal fully unswept ⇒ the first sync is a cold sweep + cold
    Δcut) and mark the slot live. `slot`/`client_id` are TRACED — one trace
    per capacity bucket, zero recompiles per admit."""
    state = _reset_slot(state, slot)
    return dataclasses.replace(
        state, fleet=flt.fleet_admit_slot(state.fleet, slot, client_id))


@jax.jit
def service_nack_rows(state: ServiceState, slot, lost_rows) -> ServiceState:
    """Re-queue one slot's lost Δ rows as pending debt (the page-loss NACK
    path): the rows fold into the next sync's union exactly like
    budget-deferred pages, so the retransmit rides the normal priority
    stream — no special wire format, and convergence-to-oracle holds under
    loss for the same reason it holds under paging. `lost_rows` is a (W,)
    uint32 plane; `slot`/`lost_rows` are TRACED (one trace per capacity
    bucket). Inactive slots are a no-op (a NACK racing an eviction must not
    resurrect the slot's debt)."""
    slot = jnp.asarray(slot, jnp.int32)
    row = state.pending[slot] | jnp.where(state.fleet.active[slot],
                                          lost_rows, jnp.uint32(0))
    return dataclasses.replace(state, pending=state.pending.at[slot].set(row))


@jax.jit
def service_evict_slot(state: ServiceState, slot) -> ServiceState:
    """Evict the client in `slot`: free the slot AND reset its leaves
    immediately, so a recycled slot is bit-for-bit indistinguishable from a
    fresh one (and an inactive slot's state is exactly the fresh value —
    the invariant tests/test_fleet_churn.py pins)."""
    state = _reset_slot(state, slot)
    return dataclasses.replace(
        state, fleet=flt.fleet_evict_slot(state.fleet, slot))


def service_grow(tree: LodTree, cfg: SessionConfig, state: ServiceState,
                 new_capacity: int) -> ServiceState:
    """Pad every slot-axis leaf to `new_capacity` (new slots free + fresh).
    Host-side: growth (and its dual, `service_shrink`) are the lifecycle
    events that change compiled shapes, so each jitted sync path retraces
    exactly once afterwards."""
    f_mgr, f_tmp, f_cut, f_idx, f_pend = _fresh_slot_leaves(state)
    return ServiceState(
        mgr=flt.pad_slots(state.mgr, f_mgr, new_capacity),
        temporal=flt.pad_slots(state.temporal, f_tmp, new_capacity),
        cut_gids=flt.pad_slots(state.cut_gids, f_cut, new_capacity),
        sync_index=flt.pad_slots(state.sync_index, f_idx, new_capacity),
        pending=flt.pad_slots(state.pending, f_pend, new_capacity),
        fleet=flt.fleet_grow(state.fleet, new_capacity),
    )


@jax.jit
def service_shrink(state: ServiceState, perm) -> ServiceState:
    """Compact the fleet into the `len(perm)` slots named by `perm` (live
    slots first, in slot order, then free slots to fill the target
    capacity) — capacity SHRINK, the dual of `service_grow`.

    One gather per leaf (`fleet.take_slots`): survivors keep their exact
    per-slot state (their replay is bitwise — every sync computation is
    slot-parallel and the survivors' relative order is preserved), and the
    gathered free slots are bitwise fresh by the frozen-inactive invariant.
    The shape change retraces each jitted sync path exactly once — same
    contract as growth, downward."""
    return ServiceState(
        mgr=flt.take_slots(state.mgr, perm),
        temporal=flt.take_slots(state.temporal, perm),
        cut_gids=flt.take_slots(state.cut_gids, perm),
        sync_index=flt.take_slots(state.sync_index, perm),
        pending=flt.take_slots(state.pending, perm),
        fleet=flt.fleet_shrink(state.fleet, perm),
    )


def _select_bit(word: jax.Array, rank: jax.Array) -> jax.Array:
    """Index of the `rank`-th (0-based) set bit of each uint32 `word`: a
    binary search over halves by their popcounts."""
    pos = jnp.zeros_like(rank)
    for width in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            word & jnp.uint32((1 << width) - 1)).astype(jnp.int32)
        up = rank >= low
        rank = jnp.where(up, rank - low, rank)
        pos = jnp.where(up, pos + width, pos)
        word = jnp.where(up, word >> width, word)
    return pos


def _cut_queue(plane: jax.Array, budget: int):
    """One cut plane (W,) compacted to its first `budget` node ids in
    ascending order (-1 padded), and the cut's size: a popcount per word,
    an inclusive prefix sum over the words, the word that holds each queue
    position (a histogram of the prefix sums, summed again), then the
    position's bit within its word."""
    pc = jax.lax.population_count(plane).astype(jnp.int32)
    incl = jnp.cumsum(pc)
    count = incl[-1]
    hist = jnp.zeros((budget,), jnp.int32).at[incl].add(1, mode="drop")
    word = jnp.minimum(jnp.cumsum(hist), plane.shape[0] - 1)
    k = jnp.arange(budget, dtype=jnp.int32)
    pos = _select_bit(plane[word], k - (incl - pc)[word])
    return jnp.where(k < count, word * bp.WORD + pos, -1), count


@functools.partial(jax.jit, static_argnames=("budget", "mesh"))
@tracing.scoped("table.update")
def _batched_cut_gids(masks: jax.Array, budget: int, mesh=None):
    """Every slot's cut plane ((C, W) uint32) as its render queue: (C,
    budget) int32 node ids, ascending, -1 padded, and (C,) cut sizes — in
    chunks of slots (`bitplane.map_slots`)."""
    with tracing.scope("table.update/pack"):
        masks = shd.constrain_fleet(masks, ("clients", None), mesh)
        one = jax.vmap(functools.partial(_cut_queue, budget=budget))
        _, (gids, counts) = bp.map_slots(
            lambda _, m: (None, one(m)), masks,
            bp.slot_chunk(masks.shape[0], 4 * budget))
    gids = shd.constrain_fleet(gids, ("clients", None), mesh)
    counts = shd.constrain_fleet(counts, ("clients",), mesh)
    return gids, counts


def _finish_sync(tree: LodTree, cfg: SessionConfig, state: ServiceState,
                 temporal: ls.TemporalState, top_cut: jax.Array,
                 slab_cut: jax.Array,
                 nodes_touched: jax.Array, resweeps: jax.Array,
                 bytes_per_g: float, codec: Optional[comp.Codec] = None,
                 dedup: bool = False, delta_budget: Optional[int] = None,
                 priority=None, allowance=None,
                 page_size: Optional[int] = None,
                 participate=None, widths=(),
                 mesh=None) -> Tuple[ServiceState, ServiceStats,
                                     Optional[dp.DeltaBatch]]:
    """Shared tail of both sync paths: batched management-table update,
    per-client render queues, the encode-once Δcut payload, and accounting.
    The cut arrives as the search leaves it — `top_cut` (C, T) bool and the
    packed slab cuts `slab_cut` (C, Ns, words(S)) — and the tail works on
    bit planes from there on (`repro.core.bitplane`): no per-node byte
    array of the whole fleet is built.

    With `dedup`, the wire format is the shared multicast stream of
    repro.serve.delta_path (one codec call on the fleet union; `sync_bytes`
    uses the shared-payload split, charging only the rows that actually
    shipped plus the page-header framing) and the built `DeltaBatch` is
    returned; otherwise the legacy per-client unicast accounting applies and
    the third element is None.

    The union folds in `state.pending` — rows deferred by earlier paged
    syncs — and the new state's `pending` is this sync's deferred set MINUS
    rows the shared reuse rule evicted meanwhile (`plan.evicted`): a row the
    unbudgeted oracle's client would have dropped by now is debt nobody
    should pay, so dropping it keeps the paged stream bitwise convergent to
    the oracle. `priority` is the (N,) coarse-first rank key (default: the
    tree's `node_levels()`, computed here when not supplied — long-lived
    services pass their cached copy); `allowance` the optional (B,) int32
    per-client row cap (the bitrate controller's knob); `page_size` the
    priority-page granularity (default: one page per stream); `widths`
    the stream widths already built (`dp.build_delta_batch`).

    Ragged fleets: inactive slots (per `state.fleet.active`) are masked out
    of EVERYTHING here — cut masks (⇒ no Δ rows, no cut ids, fresh -1 cut
    queues), the management-table update (their table stays bitwise frozen),
    the wire accounting (0.0 bytes, header included), the Δ-union encode,
    and the per-slot sync counter (it only ticks while active, so a slot's
    counter always reads "syncs since this client was admitted").

    Partial-fleet syncs (`participate`, a (C,) bool slot mask): an ACTIVE
    slot left out of the tick is handled by the exact same frozen-slot
    machinery as an inactive one — no table update, no cut recompute, no
    union rows, 0.0 bytes, no sync-counter tick — EXCEPT that, unlike an
    inactive slot, it keeps what it already had: its render queue
    (`cut_gids`), its pending page debt, and its temporal state survive the
    tick bitwise (the frozen-inactive invariant only proves freshness
    because inactive state IS the reset value; here the preserved value is
    the slot's own). `participate=None` is the lockstep tick and compiles
    the exact pre-scheduler program.

    Sharded fleets (`mesh`): everything per-client here stays on its client
    shard (the table update, cut compaction, wire accounting — and the
    participation mask — are slot-parallel); the one cross-shard step is
    the Δ-union reduction, whose payload replicates
    (repro.serve.delta_path)."""
    active = state.fleet.active
    if participate is None:
        eff = active
    else:
        eff = active & shd.constrain_fleet(
            jnp.asarray(participate, bool), ("clients",), mesh)
    new_mgr, plan = mgr.batched_cloud_sync(state.mgr, top_cut, slab_cut, eff,
                                           jnp.int32(cfg.w_star),
                                           slab_width=tree.meta.S)
    gids, counts = _batched_cut_gids(plan.cut, cfg.cut_budget, mesh=mesh)
    if participate is not None:
        # a non-participating slot KEEPS its render queue (an inactive one's
        # stored queue is already the fresh -1 row, so this is a no-op for
        # it — and bitwise the lockstep value when everyone is selected)
        gids = jnp.where(eff[:, None], gids, state.cut_gids)
    unicast = mgr.batched_wire_bytes(plan, bytes_per_g, active=eff)
    batch = None
    zero = jnp.int32(0)
    zeros_i = jnp.zeros(counts.shape, jnp.int32)
    if dedup:
        if codec is None or delta_budget is None:
            raise ValueError("dedup sync needs a codec and a delta_budget")
        if priority is None:
            priority = tree.node_levels()
        batch = dp.build_delta_batch(tree.gaussians, codec, plan.delta_data,
                                     delta_budget, active=eff, mesh=mesh,
                                     pending=state.pending, priority=priority,
                                     allowance=allowance, page_size=page_size,
                                     widths=widths)
        sync_bytes = mgr.batched_wire_bytes(plan, bytes_per_g,
                                            shared_rows=batch.shared_rows,
                                            active=eff,
                                            client_pages=batch.client_pages)
        saved = unicast - sync_bytes
        delta_overflow = batch.client_overflow
        delta_shipped = bp.count(batch.delivered)
        # carry-over debt: deferred rows survive until they ship — unless
        # the shared reuse rule evicted them meanwhile (the oracle's client
        # would have dropped them too)
        pending = batch.deferred & ~plan.evicted & bp.rows(eff, 2)
        if participate is not None:
            # a slot that sat the tick out keeps its debt untouched (its
            # rows were masked out of this union, so `deferred` is blank
            # for it — wiping would silently lose its owed pages)
            pending = jnp.where(eff[:, None], pending, state.pending)
        delta_deferred = bp.count(pending)
        pages = batch.client_pages
    else:
        sync_bytes = unicast
        saved = jnp.zeros_like(unicast)
        delta_overflow = jnp.zeros(counts.shape, bool)
        delta_shipped = jnp.where(eff, plan.n_delta, zero)
        delta_deferred = zeros_i
        pages = zeros_i
        pending = state.pending
    new_state = ServiceState(
        mgr=new_mgr, temporal=temporal, cut_gids=gids,
        sync_index=state.sync_index + eff.astype(jnp.int32),
        pending=pending, fleet=state.fleet)
    stats = ServiceStats(
        cut_size=counts,
        delta_size=plan.n_delta,
        unique_delta=dp.first_owner_counts(plan.delta_data),
        sync_bytes=sync_bytes,
        dedup_bytes_saved=saved,
        nodes_touched=jnp.where(eff, nodes_touched.astype(jnp.int32), zero),
        resweeps=jnp.where(eff, resweeps.astype(jnp.int32), zero),
        client_resident=plan.n_resident,
        overflow=counts > cfg.cut_budget,
        delta_overflow=delta_overflow & eff,
        delta_shipped=delta_shipped,
        delta_deferred=delta_deferred,
        pages=jnp.where(eff, pages, zero),
        mtp_ms=jnp.zeros(counts.shape, jnp.float32),
        deadline_miss=jnp.zeros(counts.shape, bool))
    # pin the declared fleet layout on the outputs (no-op when meshless):
    # every ServiceState/ServiceStats leaf leads with the slot axis and
    # carries the client-shard NamedSharding the acceptance contract names
    new_state = shd.shard_service_state(mesh, new_state)
    stats = shd.shard_service_state(mesh, stats)
    return new_state, stats, batch


# ---------------------------------------------------------------------------
# closed-loop per-client bitrate control (heterogeneous bandwidth tiers)
# ---------------------------------------------------------------------------


BANDWIDTH_TIERS = {
    # per-SYNC downlink budgets (bytes) for heterogeneous clients — the
    # Voyager-style device classes: a phone on cellular, a standalone
    # headset on home Wi-Fi, a tethered headset on a link that is
    # effectively never the bottleneck
    "phone": 2.5e5,
    "headset": 1.5e6,
    "tethered": 1.6e7,
}


def rate_control_step(target_bytes, measured_bytes, allowance, tau_scale, *,
                      page_size: int, max_rows: int,
                      tau_step: float = 1.25, tau_scale_max: float = 8.0):
    """One update of the per-client closed-loop bitrate controller.

    Pure host-side numpy (it runs between syncs, on the previous sync's
    MEASURED per-client wire bytes — a one-sync-delayed feedback loop, the
    price of never forcing the in-flight sync). Two nested knobs per client:

      * `allowance` — rows the client may ingest per sync (its page
        allowance in the priority-ordered union stream). Multiplicative
        feedback: scaled by target/measured, clipped to [x0.5, x2.0] per
        sync so one noisy measurement cannot slam the loop, floored at one
        page (`page_size` — a client always makes progress) and capped at
        `max_rows` (the stream budget).
      * `tau_scale` — the fallback when the allowance alone cannot meet the
        target: a client pinned at the one-page floor and still over budget
        has its foveation threshold scaled up by `tau_step` per sync (coarser
        cut ⇒ fewer Δ rows at the source), up to `tau_scale_max`; once
        comfortably under target (measured < target/tau_step) the scale
        decays back toward 1.0 — the closed loop breathes both ways.

    `measured == 0` under a finite target is MAXIMAL headroom, not "no
    signal": an idle client (nothing shipped last sync) gets the full ×2.0
    allowance step and, if escalated, a τ relax — so one bursty sync can
    never pin a client coarse forever once it goes quiet.

    The allowance floor is `min(page_size, max_rows)`: a page wider than the
    stream budget (degenerate but allowed at the `build_delta_batch` layer,
    which clamps pages to the union width) must not invert the clip bounds —
    `np.clip` with min > max silently returns max everywhere, freezing the
    loop at a value the stream can never serve.

    Clients with a non-finite target (or a negative `allowance` sentinel)
    are uncontrolled and pass through untouched. Returns (allowance,
    tau_scale) as new arrays."""
    target = np.asarray(target_bytes, np.float64)
    measured = np.asarray(measured_bytes, np.float64)
    allowance = np.asarray(allowance, np.int64)
    tau_scale = np.asarray(tau_scale, np.float32)
    controlled = np.isfinite(target) & (allowance >= 0)
    ratio = np.where(controlled,
                     np.where(measured > 0.0,
                              target / np.maximum(measured, 1.0), np.inf),
                     1.0)
    step = np.clip(ratio, 0.5, 2.0)
    lo = min(int(page_size), int(max_rows))
    new_allow = np.where(
        controlled,
        np.clip(np.floor(allowance * step), lo, max_rows),
        allowance).astype(np.int64)
    at_floor = controlled & (new_allow <= lo) & (ratio < 1.0)
    new_tau = np.where(at_floor,
                       np.minimum(tau_scale * tau_step, tau_scale_max),
                       tau_scale)
    relaxed = controlled & ~at_floor & (ratio > tau_step) & (tau_scale > 1.0)
    new_tau = np.where(relaxed, np.maximum(new_tau / tau_step, 1.0), new_tau)
    return new_allow, new_tau.astype(np.float32)


def _bandwidth_bytes(bw) -> float:
    """One client's per-sync byte target: a `BANDWIDTH_TIERS` name, a
    number (bytes/sync), or None/inf for uncontrolled."""
    if bw is None:
        return float("inf")
    if isinstance(bw, str):
        try:
            return float(BANDWIDTH_TIERS[bw])
        except KeyError:
            raise ValueError(f"unknown bandwidth tier {bw!r} (have "
                             f"{sorted(BANDWIDTH_TIERS)})") from None
    return float(bw)


def _fleet_taus(cfg: SessionConfig, n_clients: int, taus) -> jnp.ndarray:
    """(B,) per-client LoD thresholds: cfg.tau everywhere unless a foveated
    per-client vector is given (ROADMAP "Quality": τ as a (B,) vector)."""
    if taus is None:
        return jnp.full((n_clients,), cfg.tau, jnp.float32)
    taus = jnp.asarray(taus, jnp.float32)
    if taus.shape != (n_clients,):
        raise ValueError(f"expected ({n_clients},) taus, got {taus.shape}")
    return taus


def service_sync_vmapped(tree: LodTree, cfg: SessionConfig,
                         state: ServiceState, cam_positions, focal,
                         bytes_per_g: float, taus=None,
                         codec: Optional[comp.Codec] = None,
                         dedup: bool = False,
                         delta_budget: Optional[int] = None,
                         priority=None, allowance=None,
                         page_size: Optional[int] = None,
                         participate=None, widths=(),
                         mesh=None) -> Tuple[ServiceState, ServiceStats,
                                             Optional[dp.DeltaBatch]]:
    """One LoD sync for every client, fully on-device (vmapped search).

    Exactness reference for the pooled scheduler; also the right path when
    nearly everything is stale (e.g. the fleet's first frame). `taus` is an
    optional (B,) per-client foveated threshold vector; `dedup` switches the
    sync tail to the encode-once fleet wire format (see `_finish_sync`).

    Ragged fleets: the fixed-shape vmapped sweep runs over every SLOT (that
    is the price of this path), but inactive slots' temporal state is
    frozen back to its reset value afterwards, so the resulting state is
    bitwise identical to the pooled scheduler's — which never touches them
    at all. `participate` (a (C,) bool slot mask; the deadline scheduler's
    per-tick selection) freezes non-selected ACTIVE slots the same way —
    except back to their own previous state, not the reset value (see
    `_finish_sync`).

    Sharded fleets: `mesh` (explicit, or the ambient
    `repro.sharding.fleet.use_fleet_mesh`) shards the whole search on the
    clients axis — the vmapped sweep is slot-parallel, so each client shard
    sweeps its own slots; results are bitwise the unsharded service's."""
    mesh = shd.resolve_mesh(mesh)
    cams = jnp.asarray(cam_positions, jnp.float32)
    tau_b = _fleet_taus(cfg, cams.shape[0], taus)
    eff = state.fleet.active
    if participate is not None:
        eff = eff & shd.constrain_fleet(
            jnp.asarray(participate, bool), ("clients",), mesh)
    cut, temporal = ls.batched_temporal_search(
        tree, state.temporal, cams, jnp.float32(focal), tau_b)
    temporal = flt.freeze_inactive(temporal, state.temporal, eff)
    # an eff slot's packed slab cut is its fresh one; any other slot's cut
    # is masked out of the tail
    return _finish_sync(tree, cfg, state, temporal, cut.top_cut,
                        temporal.slab_cut0,
                        cut.nodes_touched, cut.resweep.sum(axis=1),
                        bytes_per_g, codec=codec, dedup=dedup,
                        delta_budget=delta_budget, priority=priority,
                        allowance=allowance, page_size=page_size,
                        participate=participate, widths=widths,
                        mesh=mesh)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("guard", "mesh"))
@tracing.scoped("table.update")
def _apply_pooled_updates(slab_cut, root_expand, rho, cam0, sel_b, sel_s,
                          f_cut, f_rexp, f_rho, cam_sel, valid=None, *,
                          guard: bool = False, mesh=None):
    """Scatter pooled sweep results back into the batched temporal state.
    Repeat-padded (client, slab) pairs write identical values — harmless.
    `slab_cut` is packed; the sweep's cuts `f_cut` arrive as (K, S) bool
    from a bucket of one lane chunk and are packed here, or packed already
    from a chunked bucket (`_pooled_pair_sweep`).

    `guard` (static; only the sharded per-shard compaction sets it): a
    client shard with ZERO stale pairs pads its bucket lanes with a
    non-stale (slot 0, slab 0) pair — those lanes re-write the pair's
    CURRENT values (gather-then-scatter in the same program), so an empty
    shard's bucket is provably a no-op. The meshless global pool never pads
    with non-stale pairs (count > 0 is guaranteed), so the unguarded program
    is byte-identical to the pre-mesh service."""
    if f_cut.dtype == jnp.bool_:
        with tracing.scope("table.update/pack"):
            f_cut = bp.pack(f_cut)
    if guard:
        f_cut = jnp.where(valid[:, None], f_cut, slab_cut[sel_b, sel_s])
        f_rexp = jnp.where(valid, f_rexp, root_expand[sel_b, sel_s])
        f_rho = jnp.where(valid, f_rho, rho[sel_b, sel_s])
        cam_sel = jnp.where(valid[:, None], cam_sel, cam0[sel_b, sel_s])
    out = (slab_cut.at[sel_b, sel_s].set(f_cut),
           root_expand.at[sel_b, sel_s].set(f_rexp),
           rho.at[sel_b, sel_s].set(f_rho),
           cam0.at[sel_b, sel_s].set(cam_sel))
    if mesh is not None:
        out = tuple(shd.constrain_fleet(
            x, ("clients",) + (None,) * (x.ndim - 1), mesh) for x in out)
    return out


@functools.partial(jax.jit, static_argnames=("n_shards", "mesh"))
@tracing.scoped("lod.staleness")
def _shard_stale_counts(stale: jax.Array, n_shards: int, mesh=None):
    """(n_shards,) stale-pair counts, one per client shard — the ONE host
    transfer of a sharded pooled sync (each shard's count picks the shared
    per-shard pow2 bucket; their sum is the fleet pool size)."""
    counts = stale.reshape(n_shards, -1).sum(axis=1).astype(jnp.int32)
    return shd.constrain_fleet(counts, ("clients",), mesh)


@functools.partial(jax.jit, static_argnames=("bucket", "n_shards", "mesh"))
@tracing.scoped("lod.staleness")
def _compact_stale_pairs(stale: jax.Array, bucket: int, n_shards: int = 1,
                         mesh=None):
    """On-device compaction of the (B, Ns) staleness mask into per-client-
    shard power-of-two buckets of (client, slab) indices.

    Replaces the old host `np.nonzero(stale)` round-trip: the cumsum-based
    `jnp.nonzero(..., size=bucket)` runs inside the program, and each
    shard's bucket is repeat-padded with its earlier stale pairs
    (idx[i mod count], exactly the old `np.resize` cycle) so padded lanes
    rewrite identical values. Only the static `bucket` size — chosen from
    the per-shard count scalars — crosses to the host.

    With `n_shards` > 1 (a mesh whose `clients` axis divides the capacity)
    every shard compacts its OWN (C/k, Ns) block into its own bucket — the
    compaction is embarrassingly shard-parallel and no staleness mask ever
    crosses shards (the cross-host staleness pool). A shard with zero stale
    pairs marks its lanes invalid (`valid` false) so the scatter can skip
    them; `n_shards=1` reduces exactly to the old single global bucket.

    Returns (sel_b, sel_s, valid), each (n_shards * bucket,) with global
    slot indices."""
    b, ns = stale.shape
    flat = stale.reshape(n_shards, -1)           # (k, (B/k)*Ns)
    flat = shd.constrain_fleet(flat, ("clients", None), mesh)

    def one(f):
        count = f.sum()
        (idx,) = jnp.nonzero(f, size=bucket, fill_value=0)
        sel = idx[jnp.arange(bucket) % jnp.maximum(count, 1)]
        return sel, jnp.broadcast_to(count > 0, (bucket,))

    sel, valid = jax.vmap(one)(flat)             # (k, bucket) shard-local
    base = (jnp.arange(n_shards, dtype=sel.dtype)
            * (b // n_shards))[:, None]          # shard → first global slot
    sel_b = (base + sel // ns).reshape(-1)
    sel_s = (sel % ns).reshape(-1)
    valid = valid.reshape(-1)
    if mesh is not None:
        sel_b = shd.constrain_fleet(sel_b, ("clients",), mesh)
        sel_s = shd.constrain_fleet(sel_s, ("clients",), mesh)
        valid = shd.constrain_fleet(valid, ("clients",), mesh)
    return sel_b, sel_s, valid


# lanes of one pass of the pooled sweep: a larger bucket is swept in chunks
# of this many lanes, in a loop inside the program, so the sweep's
# temporaries stay those of one chunk whatever the pool
SWEEP_CHUNK = 1 << 16


def _sweep_lanes(tables: ls.SlabTables, rpe, cams, taus, sel_b, sel_s, focal,
                 impl: str, mesh):
    """Gather and sweep one set of pair lanes: (in_cut (K, S) bool,
    root_expand (K,), rho (K,))."""
    if impl == "pallas":
        with tracing.scope("lod.pair_sweep/gather"):
            tau_sel = taus[sel_b]
            gathered = (tables.mu[sel_s], tables.size[sel_s],
                        tables.end[sel_s], tables.is_leaf[sel_s],
                        tables.valid[sel_s], rpe[sel_b, sel_s],
                        cams[sel_b])
        gathered, tau_sel = shd.replicate_fleet(mesh, (gathered, tau_sel))
        return lc.lod_pair_sweep_pallas(*gathered, focal, tau_sel)
    with tracing.scope("lod.pair_sweep/gather"):
        tau_sel = taus[sel_b]
        gathered = (tables.mu[sel_s], tables.size[sel_s], tables.end[sel_s],
                    tables.is_leaf[sel_s], tables.valid[sel_s],
                    rpe[sel_b, sel_s], cams[sel_b])
    if mesh is not None:
        gathered = tuple(shd.constrain_fleet(
            g, ("clients",) + (None,) * (g.ndim - 1), mesh)
            for g in gathered)
        tau_sel = shd.constrain_fleet(tau_sel, ("clients",), mesh)
    return ls.sweep_slab_camera_pairs(*gathered, focal, tau_sel)


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "mesh"))
@tracing.scoped("lod.pair_sweep")
def _pooled_pair_sweep(tables: ls.SlabTables, rpe, cams, taus, sel_b, sel_s,
                       focal, *, impl: str, chunk: int = SWEEP_CHUNK,
                       mesh=None):
    """Gather the pooled pairs' slab attributes (means, sizes, DFS subtree
    ends, leaf and valid flags) from the device-resident tables and sweep
    them — ONE fused program (the gathers never detour through the host).
    `impl` picks the vmapped XLA sweep (`lod_search.sweep_slab_camera_pairs`)
    or the Pallas lod-cut kernel (`repro.kernels.lod_cut.lod_pair_sweep_pallas`,
    compiled on a TPU and interpreted on the CPU): twins that compute the
    same prefix max over subtree ends, bitwise equal on the cut, and both
    checked against the level-loop oracle (`repro.kernels.ref`).

    A bucket of at most `chunk` lanes is swept in one pass and returns its
    cuts as (K, S) bool. A larger one is swept `chunk` lanes at a time in a
    loop (the last pass repeat-padded), each pass packing its cuts, and
    returns them as (K, words(S)) uint32: the temporaries are one pass's
    whatever the pool.

    Sharded fleets: the pair axis is constrained onto the `clients` axis
    (each shard's bucket lanes sweep on that shard); the slab-table gathers
    cross the `slabs` axis, where the partitioner inserts the collectives —
    the XLA sweep partitions cleanly. The Pallas kernel is a single opaque
    dispatch the partitioner cannot split, so under a mesh its pair inputs
    are explicitly REPLICATED first (correct but not scaled — prefer
    impl='xla' on a mesh)."""
    k = sel_b.shape[0]
    if k <= chunk:
        return _sweep_lanes(tables, rpe, cams, taus, sel_b, sel_s, focal,
                            impl, mesh)
    passes = -(-k // chunk)
    pad = passes * chunk - k
    lanes = (jnp.concatenate([sel_b, sel_b[:pad]]),
             jnp.concatenate([sel_s, sel_s[:pad]]))

    def one_pass(_, lane):
        with tracing.scope("lod.pair_sweep/chunk"):
            cut, rexp, rho = _sweep_lanes(tables, rpe, cams, taus, *lane,
                                          focal, impl, mesh)
            return None, (bp.pack(cut), rexp, rho)

    _, out = bp.map_slots(one_pass, lanes, chunk)
    return tuple(x[:k] for x in out)


def service_sync_pooled(tree: LodTree, cfg: SessionConfig,
                        state: ServiceState, cam_positions, focal,
                        bytes_per_g: float, taus=None,
                        codec: Optional[comp.Codec] = None,
                        dedup: bool = False,
                        delta_budget: Optional[int] = None,
                        priority=None, allowance=None,
                        page_size: Optional[int] = None,
                        participate=None, widths=(),
                        tables: Optional[ls.SlabTables] = None,
                        sweep_impl: str = "xla",
                        mesh=None, account: Optional[dict] = None
                        ) -> Tuple[ServiceState, ServiceStats,
                                   Optional[dp.DeltaBatch]]:
    """One LoD sync for every client with cross-client slab pooling.

    The batched analog of `temporal_search_hybrid`, now device-scheduled:
    the vmapped top sweep marks every client's stale slabs, the (client,
    slab) pool is compacted ON DEVICE into a power-of-two bucket (bounded
    recompilation), and one dispatch sweeps the bucket — each pair with its
    own camera and τ — before scattering back. Bit-identical results to
    `service_sync_vmapped`.

    Host involvement per sync is scalar reads only (the pool size here —
    plus, with dedup, the Δ-union size in the sync tail — each selecting a
    static bucket); the staleness mask stays on device. Because
    everything else is dispatched asynchronously, the sweep of sync t is
    being scheduled while the device still executes the management-table
    update / encode tail of sync t−1 — the double-buffered pipeline the
    ROADMAP asked for.

    `tables` are the device-resident slab attribute tables
    (`ls.SlabTables.from_tree`); pass them from a long-lived service so the
    per-sync program starts at the pair gather instead of re-deriving the
    slab views. `sweep_impl` = "xla" | "pallas" picks the bucket sweep
    implementation (bit-parity tested).

    Partial-fleet ticks (`participate`, a (C,) bool slot mask): non-selected
    slots are masked out of the staleness pool itself, so the pooled sweep —
    and the pool-size scalars the host awaits — track only the SELECTED
    subset (this is the scheduler's actual work saving, not just an output
    mask); their temporal state, render queue, pending debt, and sync
    counter survive the tick bitwise (see `_finish_sync`).

    Sharded fleets (`mesh`, explicit or ambient): the staleness pool is
    PER CLIENT SHARD — each shard compacts its own slots' stale pairs into
    its own pow2 bucket (`_compact_stale_pairs(n_shards=k)`), the host
    awaits one (k,) per-shard count vector instead of one scalar (their max
    picks the shared bucket size, their sum is the fleet pool), and the
    bucketed sweep runs shard-parallel on the clients axis while its slab
    gathers cross the `slabs` axis. The participation mask is placed on the
    `clients` axis too (`shard_participation`), so partial-tick masking
    stays shard-local. Results are bitwise the unsharded service's:
    repeat-padding differs per shard but padded lanes rewrite identical
    values, and an empty shard's lanes are guarded no-ops.

    `account`, a dict when given, receives what the sync decided: `n_stale`
    (the stale-pair pool, the host count read above), `lanes` (the pair
    lanes the bucket swept: bucket × client shards, 0 when nothing was
    stale), `sweep_chunks` (the sweep's passes of at most `SWEEP_CHUNK`
    lanes) and `stale_causes` (the (3,) int32 device counts of
    `lod_search.stale_causes`, never read here).

    NOTE: like `temporal_search_hybrid`, the scatter donates the incoming
    `state.temporal` buffers (no (B, Ns, S) re-copy per sync). On backends
    that honor donation the input state is CONSUMED — keep using the
    returned state, never the argument."""
    m = tree.meta
    mesh = shd.resolve_mesh(mesh)
    cams = jnp.asarray(cam_positions, jnp.float32)
    tau_b = _fleet_taus(cfg, cams.shape[0], taus)
    active = state.fleet.active
    eff = active
    if participate is not None:
        eff = active & shd.shard_participation(
            mesh, jnp.asarray(participate, bool))
    if tables is None:
        tables = ls.SlabTables.from_tree(tree, mesh=mesh)
    # inactive slots report zero staleness, so they never enter the pool:
    # sweep work (and the pool-size scalars below) tracks the ACTIVE fleet
    # — and, on a partial tick, only its SELECTED subset
    top_cut, rpe, stale, causes = ls.batched_top_and_staleness(
        tree, state.temporal, cams, jnp.float32(focal), tau_b, eff,
        mesh=mesh)
    k = shd.client_shards(mesh, stale.shape[0])
    # the ONE host synchronization of the sync: pool-size scalars — global
    # for the meshless service, one per client shard under a mesh
    if k > 1:
        counts = _shard_stale_counts(stale, k, mesh=mesh)
        with tracing.span("svc.stale_count_read"):
            shard_counts = np.asarray(jax.device_get(counts))
        n_stale = int(shard_counts.sum())
    else:
        count = stale.sum()
        with tracing.span("svc.stale_count_read"):
            n_stale = int(jax.device_get(count))
    n_pairs = stale.shape[0] * stale.shape[1]

    tp = state.temporal
    slab_cut, root_expand, rho, cam0 = (tp.slab_cut0, tp.root_expand0,
                                        tp.rho, tp.cam0)
    bucket = chunks = 0
    if n_stale > 0:
        if k > 1:
            bucket = ls.pow2_bucket(int(shard_counts.max()), n_pairs // k)
        else:
            bucket = ls.pow2_bucket(n_stale, n_pairs)
        sel_b, sel_s, valid = _compact_stale_pairs(stale, bucket,
                                                   n_shards=k, mesh=mesh)
        f_cut, f_rexp, f_rho = _pooled_pair_sweep(
            tables, rpe, cams, tau_b, sel_b, sel_s, jnp.float32(focal),
            impl=sweep_impl, mesh=mesh)
        chunks = -(-bucket * k // SWEEP_CHUNK)
        slab_cut, root_expand, rho, cam0 = _apply_pooled_updates(
            slab_cut, root_expand, rho, cam0, sel_b, sel_s,
            f_cut, f_rexp, f_rho, cams[sel_b], valid, guard=k > 1,
            mesh=mesh)
    if account is not None:
        account.update(n_stale=n_stale, lanes=bucket * k,
                       sweep_chunks=chunks, stale_causes=causes)

    # the eff-masked scatter never touches a non-participating slot's
    # donated buffers; freeze the two non-donated leaves the same way so
    # inactive slots stay bitwise at their reset value (swept=False ⇒ still
    # cold) and sat-out slots keep their own previous temporal state
    temporal = ls.TemporalState(
        cam0=cam0, rho=rho,
        parent_expand0=jnp.where(eff[:, None], rpe, tp.parent_expand0),
        slab_cut0=slab_cut, root_expand0=root_expand,
        swept=jnp.where(eff[:, None], True, tp.swept))
    nodes_touched = m.T + stale.sum(axis=1).astype(jnp.int32) * m.S
    return _finish_sync(tree, cfg, state, temporal, top_cut, slab_cut,
                        nodes_touched,
                        stale.sum(axis=1), bytes_per_g, codec=codec,
                        dedup=dedup, delta_budget=delta_budget,
                        priority=priority, allowance=allowance,
                        page_size=page_size, participate=participate,
                        widths=widths, mesh=mesh)


# ---------------------------------------------------------------------------
# fleet render step (cloud-rendered fallback clients)
# ---------------------------------------------------------------------------


def _masked_queue(gaussians: Gaussians, gids: jax.Array) -> Gaussians:
    """One client's render queue from its cut ids (-1 padding → α=0 rows)."""
    queue = gaussians.slice_rows(jnp.clip(gids, 0))
    return dataclasses.replace(
        queue, opacity=jnp.where(gids >= 0, queue.opacity, 0.0))


def service_render_step(tree: LodTree, state: ServiceState, rigs,
                        rcfg: "rnd.RenderConfig", *, path: str = "vmap",
                        mesh=None):
    """Render EVERY client's current cut queue cloud-side in one batched
    stereo dispatch (the fallback tier of Fig. 10: headsets too weak to run
    the client rasterizer receive pixels, not Gaussians).

    Queues are gathered from the cloud's raw tree attributes (the cloud never
    holds the lossy client decode). `rigs` carries a leading client axis (see
    `repro.render.stack_rigs`); `path` picks the vmapped XLA renderer or the
    fleet-pooled Pallas bucket path. Returns (img_l (B,H,W,3), img_r,
    per-client `repro.render.StereoFrameStats`) — the frame-side accounting
    that sits alongside the sync-side `ServiceStats`.

    Ragged fleets: inactive slots' queues are empty (-1 cut everywhere) and
    their slots are masked out of the pooled occupied-tile bucket, so fleet
    rasterization work tracks live clients — inactive slots just return
    black frames.

    Sharded fleets: `mesh` (explicit or ambient) shards the queues and the
    returned fallback frames on the `clients` axis — each client shard
    rasterizes (and holds the pixels of) its own slots."""
    mesh = shd.resolve_mesh(mesh)
    queues = jax.vmap(lambda g: _masked_queue(tree.gaussians, g)
                      )(state.cut_gids)
    queues = shd.shard_service_state(mesh, queues)
    return rnd.batched_render_stereo(queues, rigs, rcfg, path=path,
                                     active=state.fleet.active, mesh=mesh)


class LodService:
    """Thin stateful wrapper: one shared tree/codec, a ragged client fleet.

    `sync(cam_positions)` advances every live client by one LoD sync and
    returns per-SLOT `ServiceStats` (inactive slot rows are all-zero); the
    encode-once fleet payload of the latest sync is kept on `last_delta`
    (`client_delta(cid)` decodes one client's slice). `mode` picks the
    scheduler: "pooled" (cross-client bucketed hybrid, device-compacted —
    the production path) or "vmapped" (always-sweep exactness reference).
    `sweep_impl` selects the pooled bucket sweep: "xla" (vmapped) or
    "pallas" (`repro.kernels.lod_cut.lod_pair_sweep_pallas`, compiled on a
    TPU backend and interpreted on the CPU — the backend decides, see
    `repro.kernels.resolve_interpret`). `dedup`
    toggles the encode-once wire format (on by default; `dedup=False`
    restores per-client unicast accounting and skips the codec). `taus`
    optionally gives every client its own foveated LoD threshold
    (n_clients,). `render_fallback(rigs)` rasterizes every live client's
    current queue cloud-side in one batched dispatch, with the static
    `RenderConfig` and stacked-rig pytree cached per (rig, fleet) signature.

    Fleet lifecycle: `admit(cam, tau)` returns a stable client id;
    `evict(client_id)` frees the slot. Clients live in a `capacity`-slot
    array (default: capacity == n_clients; pass `capacity=` to pre-provision
    a pow2 bucket). Admits/evicts within the capacity bucket are jitted
    slot scatters — zero recompiles; an admit that outgrows the bucket pads
    to `lod_search.pow2_bucket(capacity + 1)` and retraces each jitted path
    exactly once; `maybe_shrink()` is the downward dual (compact a sparse
    fleet into the smaller pow2 bucket — one retrace, survivors replay
    bitwise). `max_clients` / `max_state_bytes` switch growth to
    backpressure: a budget-exceeding `admit` raises `AdmissionDenied` (or
    returns None with `required=False`) and leaves the service untouched.
    Clients are addressed by their stable id everywhere
    (`sync` dicts, `client_cut`, `client_delta`, `client_tau`); for a
    never-churned service ids coincide with 0..B-1, so the legacy positional
    API keeps working unchanged.

    The Δ stream is PAGED (repro.serve.delta_path): a sync whose fleet
    Δ-union exceeds `delta_budget` ships the coarsest `page_size`-row
    priority pages now and carries the rest as per-slot debt
    (`ServiceState.pending`) — every Gaussian arrives within ⌈U/width⌉
    syncs, nothing is silently lost. As a backlog drains, the stream's
    width falls only to widths this service has built: a narrower one
    would compile anew on a served tick. `bandwidth` turns on the closed-loop
    per-client bitrate controller: pass a `BANDWIDTH_TIERS` name ("phone" /
    "headset" / "tethered"), a bytes-per-sync number, or a per-client
    sequence of either; each sync, the PREVIOUS sync's measured per-client
    `sync_bytes` multiplicatively adjusts that client's row allowance
    (floored at one page, so it always makes progress) and — when the floor
    alone still overshoots — its foveation τ (`rate_control_step`).
    `admit(bandwidth=...)` assigns a tier at admission; an evicted slot
    drops its deferred pages and its controller state.

    `mesh` installs the clients×slabs serving mesh (see the module
    docstring; `launch.make_fleet_mesh`) — sync, lifecycle, and fallback
    render all run sharded, bitwise-identical to the meshless service."""

    def __init__(self, tree: LodTree, cfg: SessionConfig, n_clients: int,
                 focal: float, mode: str = "pooled", taus=None,
                 dedup: bool = True, sweep_impl: str = "xla",
                 delta_budget: Optional[int] = None,
                 capacity: Optional[int] = None,
                 mesh=None, max_clients: Optional[int] = None,
                 max_state_bytes: Optional[float] = None,
                 bandwidth=None, page_size: Optional[int] = None):
        if mode not in ("pooled", "vmapped"):
            raise ValueError(f"unknown scheduler mode: {mode!r}")
        if sweep_impl not in ("xla", "pallas"):
            raise ValueError(f"unknown sweep_impl: {sweep_impl!r}")
        if sweep_impl == "pallas" and mode != "pooled":
            raise ValueError("sweep_impl='pallas' drives the pooled bucket "
                             "sweep; use mode='pooled'")
        mgr.check_w_star(cfg.w_star)
        self.tree = tree
        self.cfg = cfg
        # the serving mesh (explicit, else the ambient use_fleet_mesh one):
        # clients axis shards per-slot state, slabs axis shards the shared
        # slab tables + union codec rows; None = the single-device service
        self.mesh = shd.resolve_mesh(mesh)
        # admission control (backpressure): deny instead of growing past a
        # live-client count or a total state-byte budget
        self.max_clients = None if max_clients is None else int(max_clients)
        self.max_state_bytes = (None if max_state_bytes is None
                                else float(max_state_bytes))
        self.capacity = (max(int(n_clients), 1) if capacity is None
                         else int(capacity))
        if self.capacity < max(n_clients, 1):
            raise ValueError(f"capacity {self.capacity} < n_clients "
                             f"{n_clients}")
        self.focal = float(focal)
        self.mode = mode
        self.sweep_impl = sweep_impl
        self.dedup = bool(dedup)
        # host-side control-plane mirror of state.fleet (slot lookup and
        # validation without device round-trips; the device FleetState is
        # kept consistent by the jitted admit/evict steps)
        self._active = np.zeros(self.capacity, bool)
        self._active[:n_clients] = True
        self._client_ids = np.full(self.capacity, -1, np.int64)
        self._client_ids[:n_clients] = np.arange(n_clients)
        self._next_id = int(n_clients)
        self._slot_cams = np.zeros((self.capacity, 3), np.float32)
        # per-SLOT foveated thresholds; constructor taus address the initial
        # clients, admitted clients get theirs via admit(tau=...)
        if taus is None:
            self.taus = None
        else:
            per_client = np.asarray(_fleet_taus(cfg, n_clients, taus),
                                    np.float32)
            self.taus = np.full(self.capacity, cfg.tau, np.float32)
            self.taus[:n_clients] = per_client
        self.codec, self.bytes_per_g = session_wire_format(tree, cfg)
        # static union capacity of the encode-once stream: every client's
        # Δcut is bounded by its cut budget, so the fleet union is bounded
        # by min(capacity * cut_budget, N); recomputed on capacity growth
        # unless pinned by the caller
        self._delta_budget_arg = delta_budget
        self.delta_budget = (int(delta_budget) if delta_budget is not None
                             else min(tree.n_pad,
                                      cfg.cut_budget * self.capacity))
        # page_size=None → one 256-row page, clamped to the stream budget.
        # An EXPLICIT page wider than the budget is a config error: the
        # stream could never ship a full page per sync, and the rate
        # controller's allowance floor would sit above its own ceiling
        # (the np.clip(min > max) degenerate the PR 6 controller hit).
        if page_size is None:
            self.page_size = max(1, min(256, self.delta_budget))
        else:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if page_size > self.delta_budget:
                raise ValueError(
                    f"page_size {page_size} > delta_budget "
                    f"{self.delta_budget}: a page must fit the Δ-stream "
                    f"budget (pass a smaller page_size or raise "
                    f"delta_budget)")
            self.page_size = int(page_size)
        # coarse-first priority key of the paged union stream, derived once
        # over the bit planes' node axis (padding nodes never join a union)
        levels = tree.node_levels()
        self._priority = jnp.pad(
            levels, (0, bp.WORD * bp.words(tree.n_pad) - levels.shape[0]))
        # closed-loop bitrate controller state (host-side, like `taus`):
        # per-slot byte target (inf = uncontrolled), row allowance
        # (-1 sentinel = uncontrolled) and foveation fallback scale
        self._bw_target = np.full(self.capacity, np.inf, np.float64)
        self._allowance = np.full(self.capacity, -1, np.int64)
        self._tau_scale = np.ones(self.capacity, np.float32)
        self._last_stats: Optional[ServiceStats] = None
        # which rows of _last_stats are FRESH measurements (produced by the
        # immediately-previous sync): on a partial tick (`participate`) a
        # sat-out slot's stats row is its older measurement, and feeding it
        # to the multiplicative controller again would compound one
        # observation — the controller only commits where this mask is True
        self._stats_fresh = np.zeros(self.capacity, bool)
        if bandwidth is not None:
            if isinstance(bandwidth, (list, tuple, np.ndarray)):
                if len(bandwidth) != n_clients:
                    raise ValueError(f"expected {n_clients} bandwidth "
                                     f"entries, got {len(bandwidth)}")
                targets = [_bandwidth_bytes(bw) for bw in bandwidth]
            else:
                targets = [_bandwidth_bytes(bandwidth)] * n_clients
            for slot, target in enumerate(targets):
                self._set_bandwidth_slot(slot, target)
        # device-resident slab tables: gathered once, reused by every pooled
        # sweep (the per-sync program starts at the pair gather); the
        # vmapped reference path never reads them, so don't hold the copy.
        # Under a mesh the tables shard on the slabs axis at placement.
        self.tables = (ls.SlabTables.from_tree(tree, mesh=self.mesh)
                       if mode == "pooled" else None)
        self.state = shd.shard_service_state(
            self.mesh, service_init(tree, cfg, n_clients,
                                    capacity=self.capacity))
        self.last_delta: Optional[dp.DeltaBatch] = None
        self._delta_ids = np.full(self.capacity, -1, np.int64)
        # Δ stream widths built so far: a sync takes the narrowest that
        # holds its union, so a backlog that drains keeps the programs it
        # has and never compiles a narrower width on a served tick
        self._union_widths: set = set()
        # syncs run so far: the `tick` of every trace span of the next sync
        self.syncs = 0
        # what the last pooled sync decided (`service_sync_pooled`'s
        # `account`) and the state bytes per slot it left
        # (`slot_state_bytes`); empty after a vmapped sync, which pools
        # nothing
        self.last_account: dict = {}
        self._rcfg_cache = {}
        self._stack_cache = {}

    # -- fleet lifecycle ------------------------------------------------------

    @property
    def n_clients(self) -> int:
        """Number of LIVE clients (== capacity for a never-churned fleet)."""
        return int(self._active.sum())

    @property
    def active_ids(self):
        """Stable client ids of the live fleet, in slot order (the order
        `sync` expects array-form camera positions in)."""
        return [int(c) for c in self._client_ids[self._active]]

    def _slot_of(self, client_id: int) -> int:
        slots = np.flatnonzero(self._active
                               & (self._client_ids == int(client_id)))
        if slots.size == 0:
            raise KeyError(f"no live client with id {client_id}")
        return int(slots[0])

    def client_tau(self, client_id: int) -> float:
        """One live client's foveated LoD threshold (cfg.tau unless set at
        construction or admission; the bitrate controller's `tau_scale`
        multiplies on top of this base during sync)."""
        slot = self._slot_of(client_id)
        return float(self.cfg.tau if self.taus is None else self.taus[slot])

    def _set_bandwidth_slot(self, slot: int, target: float) -> None:
        """Seed one slot's controller state: its byte target and an initial
        row allowance of target/bytes-per-row (the loop refines it from
        measurements; uncontrolled slots carry the -1 sentinel)."""
        self._bw_target[slot] = target
        self._tau_scale[slot] = 1.0
        if np.isfinite(target):
            rows = int(target // max(self.bytes_per_g, 1.0))
            self._allowance[slot] = int(np.clip(rows, self.page_size,
                                                self.delta_budget))
        else:
            self._allowance[slot] = -1

    def set_bandwidth(self, client_id: int, bandwidth=None) -> None:
        """Re-tier a live client's downlink mid-session (a `BANDWIDTH_TIERS`
        name, bytes/sync, or None to turn control off): reseed its
        closed-loop controller exactly like `admit(bandwidth=...)` would —
        the loop re-converges from the seed allowance over the next syncs."""
        slot = self._slot_of(client_id)
        self._set_bandwidth_slot(slot, _bandwidth_bytes(bandwidth))

    def client_bandwidth(self, client_id: int):
        """One live client's (target_bytes, row_allowance, tau_scale)
        controller triple (target inf / allowance None when uncontrolled)."""
        slot = self._slot_of(client_id)
        allow = int(self._allowance[slot])
        return (float(self._bw_target[slot]),
                None if allow < 0 else allow, float(self._tau_scale[slot]))

    def _slot_state_bytes(self) -> float:
        """Per-slot device bytes of the service state (all slot-axis leaves
        of `ServiceState`, capacity-normalized) — the unit the admission
        byte budget is charged in."""
        total = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                    for a in jax.tree_util.tree_leaves(self.state)
                    if getattr(a, "ndim", 0) >= 1)
        return float(total) / self.capacity

    def _admission_denial(self) -> Optional[str]:
        """Why the next admit must be refused (None = admissible). Checked
        BEFORE any state mutation, so a denied admit is side-effect free."""
        if self.max_clients is not None \
                and self.n_clients + 1 > self.max_clients:
            return (f"live clients {self.n_clients} at the configured "
                    f"max_clients={self.max_clients}")
        if self.max_state_bytes is not None and not (~self._active).any():
            # a full fleet must GROW to admit — deny if the grown slot
            # array would blow the byte budget (in-bucket admits are free)
            grown = flt.fleet_capacity(self.capacity + 1)
            need = self._slot_state_bytes() * grown
            if need > self.max_state_bytes:
                return (f"growing {self.capacity}->{grown} slots needs "
                        f"{need:.0f} state bytes > max_state_bytes="
                        f"{self.max_state_bytes:.0f}")
        return None

    def admit(self, cam=None, tau: Optional[float] = None,
              required: bool = True, bandwidth=None) -> Optional[int]:
        """Admit one client; returns its stable id. The new slot starts
        fully stale, so the client's first sync is a cold full sweep and a
        cold Δcut. Within the current capacity bucket this is a jitted slot
        scatter (zero recompiles); on a full fleet the capacity grows to the
        next pow2 bucket first (one retrace of each jitted path). `cam`
        seeds the slot's camera (used until the next `sync` provides one);
        `tau` its foveated threshold (default cfg.tau).

        Admission control: with `max_clients` / `max_state_bytes`
        configured, an admit past the budget is DENIED instead of growing
        unboundedly — raising `AdmissionDenied` (`required=True`, the
        default) or returning None (`required=False`, for callers that
        queue and retry). A denied admit leaves the service untouched.

        `bandwidth` assigns the client's downlink tier (a `BANDWIDTH_TIERS`
        name or bytes/sync; default uncontrolled) — its closed-loop bitrate
        controller starts clean, like its pending-page debt."""
        denial = self._admission_denial()
        if denial is not None:
            if required:
                raise AdmissionDenied(denial)
            return None
        free = np.flatnonzero(~self._active)
        if free.size == 0:
            if self.capacity >= flt.MAX_CAPACITY:
                raise ValueError(f"fleet at MAX_CAPACITY ({flt.MAX_CAPACITY})")
            self._grow(flt.fleet_capacity(self.capacity + 1))
            free = np.flatnonzero(~self._active)
        slot = int(free[0])
        client_id = self._next_id
        self._next_id += 1
        self.state = shd.shard_service_state(
            self.mesh, service_admit_slot(self.state, slot, client_id))
        self._active[slot] = True
        self._client_ids[slot] = client_id
        self._slot_cams[slot] = (np.zeros(3, np.float32) if cam is None
                                 else np.asarray(cam, np.float32))
        if tau is not None and self.taus is None:
            self.taus = np.full(self.capacity, self.cfg.tau, np.float32)
        if self.taus is not None:
            self.taus[slot] = float(self.cfg.tau if tau is None else tau)
        self._set_bandwidth_slot(slot, _bandwidth_bytes(bandwidth))
        return client_id

    def evict(self, client_id: int) -> None:
        """Evict a live client. Its slot is freed AND reset in the same
        jitted step, so the next tenant of the slot is bit-for-bit
        indistinguishable from one landing on a never-used slot. No wire
        traffic results: both sides run the shared reuse rule, and the
        vacated slot contributes nothing to any later sync."""
        slot = self._slot_of(client_id)
        self.state = shd.shard_service_state(
            self.mesh, service_evict_slot(self.state, slot))
        self._active[slot] = False
        self._client_ids[slot] = -1
        self._slot_cams[slot] = 0.0
        if self.taus is not None:
            self.taus[slot] = self.cfg.tau
        # the slot's deferred pages died with its ServiceState.pending row
        # (service_evict_slot resets it); drop the controller state too
        self._bw_target[slot] = np.inf
        self._allowance[slot] = -1
        self._tau_scale[slot] = 1.0
        self._stats_fresh[slot] = False

    def _grow(self, new_capacity: int) -> None:
        """Pad every slot-axis array to `new_capacity` (host mirrors
        included). The stacked-rig / RenderConfig caches are dropped: their
        signatures include the capacity bucket, and the pinned pytrees have
        the old leading axis."""
        self.state = shd.shard_service_state(
            self.mesh, service_grow(self.tree, self.cfg, self.state,
                                    new_capacity))
        pad = new_capacity - self.capacity
        self._active = np.concatenate([self._active, np.zeros(pad, bool)])
        self._client_ids = np.concatenate(
            [self._client_ids, np.full(pad, -1, np.int64)])
        self._slot_cams = np.concatenate(
            [self._slot_cams, np.zeros((pad, 3), np.float32)])
        if self.taus is not None:
            self.taus = np.concatenate(
                [self.taus, np.full(pad, self.cfg.tau, np.float32)])
        # new slots have no slice in the latest payload (tenancy -1); the
        # pinned last_delta.ref_mask keeps its pre-growth leading dim — the
        # shrink remap and client_delta both handle the short payload
        self._delta_ids = np.concatenate(
            [self._delta_ids, np.full(pad, -1, np.int64)])
        self._bw_target = np.concatenate(
            [self._bw_target, np.full(pad, np.inf, np.float64)])
        self._allowance = np.concatenate(
            [self._allowance, np.full(pad, -1, np.int64)])
        self._tau_scale = np.concatenate(
            [self._tau_scale, np.ones(pad, np.float32)])
        self._stats_fresh = np.concatenate(
            [self._stats_fresh, np.zeros(pad, bool)])
        if self._last_stats is not None:
            # the feedback source keeps its pre-growth leading dim — pad
            # with zero rows (new slots are uncontrolled until admitted, and
            # a zero measurement is the no-op of the multiplicative loop)
            self._last_stats = jax.tree_util.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((new_capacity - a.shape[0],)
                                  + a.shape[1:], a.dtype)]),
                self._last_stats)
        self.capacity = new_capacity
        if self._delta_budget_arg is None:
            self.delta_budget = min(self.tree.n_pad,
                                    self.cfg.cut_budget * self.capacity)
        self._rcfg_cache.clear()
        self._stack_cache.clear()

    def maybe_shrink(self) -> Optional[int]:
        """Capacity SHRINK: if the live fleet fits a smaller pow2 bucket,
        compact the live slots to the front (slot order preserved) and
        truncate every slot-axis array to that bucket. Returns the new
        capacity, or None when already right-sized.

        One retrace: the shape change costs each jitted sync path exactly
        one new trace (the growth contract, downward). Survivors replay
        bitwise — every per-sync computation is slot-parallel and the
        survivors keep their relative order, so the pooled sweep, Δ-union
        stream, and first-requester byte split are unchanged. The latest
        encode-once payload's ref-mask rows are remapped through the same
        permutation, so `client_delta` keeps working across the shrink."""
        target = flt.fleet_capacity(max(self.n_clients, 1))
        if target >= self.capacity:
            return None
        live = np.flatnonzero(self._active)
        free = np.flatnonzero(~self._active)
        perm = np.concatenate([live, free])[:target].astype(np.int32)
        self.state = shd.shard_service_state(
            self.mesh, service_shrink(self.state, jnp.asarray(perm)))
        self._active = self._active[perm]
        self._client_ids = self._client_ids[perm]
        self._slot_cams = self._slot_cams[perm]
        if self.taus is not None:
            self.taus = self.taus[perm]
        self.capacity = target
        if self._delta_budget_arg is None:
            self.delta_budget = min(self.tree.n_pad,
                                    self.cfg.cut_budget * self.capacity)
        # client-leading device pytrees that may predate a capacity growth
        # (their leading dim = the capacity at their sync): slots beyond
        # them have no row — give those an all-zero one
        def _remap_rows(a):
            safe = np.minimum(perm, a.shape[0] - 1)
            keep = (perm < a.shape[0]).reshape((-1,) + (1,) *
                                               (a.ndim - 1))
            return jnp.where(keep, a[safe], jnp.zeros((), a.dtype))
        if self._last_stats is not None:
            # the rate controller's feedback source follows the slot
            # permutation like every other per-slot mirror
            self._last_stats = jax.tree_util.tree_map(_remap_rows,
                                                      self._last_stats)
        if self.last_delta is not None:
            # slots with no slice in the payload get an all-zero row (their
            # _delta_ids entry is -1, so client_delta already refuses them);
            # every client-leading leaf remaps through the same permutation
            self.last_delta = dataclasses.replace(
                self.last_delta,
                ref_mask=_remap_rows(self.last_delta.ref_mask),
                delivered=_remap_rows(self.last_delta.delivered),
                deferred=_remap_rows(self.last_delta.deferred),
                shared_rows=_remap_rows(self.last_delta.shared_rows),
                client_overflow=_remap_rows(self.last_delta.client_overflow),
                client_pages=_remap_rows(self.last_delta.client_pages))
        self._delta_ids = self._delta_ids[perm]
        self._bw_target = self._bw_target[perm]
        self._allowance = self._allowance[perm]
        self._tau_scale = self._tau_scale[perm]
        self._stats_fresh = self._stats_fresh[perm]
        self._rcfg_cache.clear()
        self._stack_cache.clear()
        return target

    # -- elasticity: live mesh resize + snapshot/restore ----------------------

    def resize_mesh(self, mesh) -> None:
        """Move the LIVE service onto a different `clients`×`slabs` serving
        mesh (bigger, smaller, or `None` for the single-device layout)
        without dropping a client: every `ServiceState` leaf (and the
        device-resident slab tables) is re-placed under the new mesh's fleet
        shardings — the in-memory analog of restore-onto-a-new-mesh. The
        traced signatures of the jitted sync paths include the static mesh,
        so the first sync after a resize retraces once (the same contract as
        a capacity change); results stay bitwise (the divisibility fallback
        replicates anything the new mesh cannot split)."""
        self.mesh = mesh
        if mesh is None:
            dev = jax.devices()[0]
            self.state = jax.device_put(self.state, dev)
            if self.tables is not None:
                self.tables = jax.device_put(self.tables, dev)
            if self.last_delta is not None:
                self.last_delta = jax.device_put(self.last_delta, dev)
        else:
            self.state = shd.shard_service_state(mesh, self.state)
            if self.tables is not None:
                self.tables = shd.shard_slab_tables(mesh, self.tables)
            if self.last_delta is not None:
                # mixed logical axes (union rows vs client slots): replicate
                # — always a correct placement for a broadcast stream
                from jax.sharding import NamedSharding, PartitionSpec
                self.last_delta = jax.device_put(
                    self.last_delta, NamedSharding(mesh, PartitionSpec()))
        self._rcfg_cache.clear()
        self._stack_cache.clear()

    def snapshot(self, directory: str, step: int = 0, *,
                 journal_seq: int = 0) -> str:
        """Atomically serialize the full service — `ServiceState` pytree,
        host control-plane mirrors, bitrate-controller state, and static
        config — as checkpoint `step_<step>` under `directory`
        (repro.serve.recovery.snapshot_service). Returns the final path."""
        from repro.serve import recovery
        return recovery.snapshot_service(self, directory, step=step,
                                         journal_seq=journal_seq)

    @classmethod
    def restore(cls, tree: LodTree, directory: str, step: Optional[int] = None,
                mesh=None) -> "LodService":
        """Rebuild a service from a `snapshot` directory against the SAME
        shared city tree (fingerprint-checked), optionally onto a different
        serving mesh (reshard-on-load; `mesh=None` restores single-device).
        Survivors replay bitwise vs the uninterrupted service
        (tests/test_fleet_recovery.py). Raises
        `repro.serve.recovery.RecoveryError` on any torn/corrupt/mismatched
        snapshot — never a silently divergent service."""
        from repro.serve import recovery
        return recovery.restore_service(tree, directory, step=step, mesh=mesh)

    # -- sync -----------------------------------------------------------------

    def _participation_mask(self, participate) -> Optional[np.ndarray]:
        """Normalize `sync`'s `participate` argument to a (capacity,) bool
        slot mask (None = lockstep): a bool array of capacity length passes
        through; anything else is an iterable of stable CLIENT IDS, each
        resolved to its live slot (unknown ids raise, before any state is
        touched)."""
        if participate is None:
            return None
        arr = np.asarray(participate)
        if arr.dtype == bool:
            if arr.shape != (self.capacity,):
                raise ValueError(f"participation mask shape {arr.shape} != "
                                 f"({self.capacity},)")
            return arr.copy()
        slots = [self._slot_of(int(c)) for c in np.atleast_1d(arr)]
        return flt.slots_mask(self.capacity, slots)

    def sync(self, cam_positions=None, participate=None) -> ServiceStats:
        """One fleet sync. Returns device-resident per-SLOT stats — they
        are NOT forced here, so back-to-back `sync` calls pipeline: the host
        dispatches sync t while the device finishes the table update and
        encode tail of sync t−1 (the only awaits per sync are the pooled
        scheduler's and the encoder's bucket-size scalars).

        `cam_positions` is either an (n_clients, 3) array addressing the
        live clients in slot order (`active_ids` order — the legacy form), a
        {client_id: position} dict updating a subset (others keep their last
        known position), or None (everyone keeps their last position). A
        dict with an unknown client id raises KeyError BEFORE any position
        is stored — a bad id never partially updates `_slot_cams`.

        `participate` makes this a PARTIAL-FLEET tick (the deadline
        scheduler's primitive, repro.serve.scheduler): a (capacity,) bool
        slot mask or an iterable of client ids — only those slots sync;
        everyone else's state (temporal, render queue, pending debt, sync
        counter, controller) survives the tick bitwise untouched, and
        returned stats rows for sat-out slots are zero. A mask selecting
        every live slot replays bitwise against the lockstep
        `participate=None` call (tests/test_scheduler.py).

        With bandwidth-controlled clients the PREVIOUS sync's stats are
        read back here to close the bitrate loop (one forced await per sync
        — only then; an uncontrolled fleet keeps the fully-async pipeline).
        Under partial ticks the controller only commits a slot's update
        when that slot's measurement is fresh (it participated in the
        previous sync) — a stale measurement is never fed through the
        multiplicative loop twice.

        The sync runs in the trace span `nebula.svc.sync` (`tracing`), with
        a span on each blocking read inside it."""
        with tracing.span("svc.sync", tick=self.syncs):
            stats = self._sync(cam_positions, participate)
        self.syncs += 1
        return stats

    def _sync(self, cam_positions, participate) -> ServiceStats:
        part_mask = self._participation_mask(participate)
        if isinstance(cam_positions, dict):
            updates = {self._slot_of(cid): np.asarray(pos, np.float32)
                       for cid, pos in cam_positions.items()}
            for slot, pos in updates.items():
                self._slot_cams[slot] = pos
        elif cam_positions is not None:
            cams = np.asarray(cam_positions, np.float32)
            if cams.shape != (self.n_clients, 3):
                raise ValueError(f"expected ({self.n_clients}, 3) camera "
                                 f"positions, got {cams.shape}")
            self._slot_cams[self._active] = cams
        allowance, taus_eff = None, self.taus
        if self.dedup and np.isfinite(self._bw_target).any():
            if self._last_stats is not None:
                with tracing.span("svc.rate_read"):
                    measured = np.asarray(self._last_stats.sync_bytes,
                                          np.float64)
                new_allow, new_tau = rate_control_step(
                    self._bw_target, measured, self._allowance,
                    self._tau_scale, page_size=self.page_size,
                    max_rows=self.delta_budget)
                commit = self._stats_fresh
                self._allowance = np.where(commit, new_allow,
                                           self._allowance)
                self._tau_scale = np.where(commit, new_tau,
                                           self._tau_scale
                                           ).astype(np.float32)
            allowance = np.where(self._allowance >= 0, self._allowance,
                                 self.delta_budget).astype(np.int32)
            base = (self.taus if self.taus is not None
                    else np.full(self.capacity, self.cfg.tau, np.float32))
            taus_eff = (base * self._tau_scale).astype(np.float32)
        kw = dict(taus=taus_eff, codec=self.codec, dedup=self.dedup,
                  delta_budget=self.delta_budget, priority=self._priority,
                  allowance=allowance, page_size=self.page_size,
                  participate=part_mask, widths=sorted(self._union_widths),
                  mesh=self.mesh)
        self.last_account = {}
        if self.mode == "pooled":
            self.state, stats, batch = service_sync_pooled(
                self.tree, self.cfg, self.state, self._slot_cams, self.focal,
                self.bytes_per_g, tables=self.tables,
                sweep_impl=self.sweep_impl, account=self.last_account, **kw)
            self.last_account["slot_state_bytes"] = self._slot_state_bytes()
        else:
            self.state, stats, batch = service_sync_vmapped(
                self.tree, self.cfg, self.state, self._slot_cams, self.focal,
                self.bytes_per_g, **kw)
        if batch is not None:
            self.last_delta = batch
            self._union_widths.add(int(batch.union_gids.shape[0]))
            # tenancy snapshot: which client each slot's ref_mask row is FOR
            # (guards client_delta against churn between sync and decode)
            self._delta_ids = self._client_ids.copy()
        # feedback source for the NEXT sync's rate-control step (device-
        # resident; only read back when a client is bandwidth-controlled).
        # A partial tick merges: each slot keeps its latest OBSERVED
        # measurement, and _stats_fresh marks which rows this tick renewed.
        if part_mask is None or self._last_stats is None:
            self._last_stats = stats
        else:
            pm = jnp.asarray(part_mask)
            self._last_stats = jax.tree_util.tree_map(
                lambda n, o: jnp.where(
                    pm.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                stats, self._last_stats)
        self._stats_fresh = (self._active.copy() if part_mask is None
                             else (self._active & part_mask))
        return stats

    def client_cut(self, client_id: int) -> jax.Array:
        """(cut_budget,) int32 render-queue ids of one live client (-1
        padded). Addressed by stable client id (== slot index for a
        never-churned fleet)."""
        return self.state.cut_gids[self._slot_of(client_id)]

    def client_delta(self, client_id: int):
        """Decode one client's Δcut slice of the latest encode-once payload:
        (ids (U,) int32 — -1 where the union row is not this client's — and
        the decoded union rows). Bitwise what the encode-per-client path
        would have delivered (tests/test_delta_path.py).

        The payload is a per-sync artifact: a client admitted (or a slot
        recycled) after the latest sync has no slice in it — that is an
        error, never a silent read of the previous tenant's row."""
        if self.last_delta is None:
            raise ValueError("no sync performed yet (or dedup=False)")
        slot = self._slot_of(client_id)
        if (slot >= len(self._delta_ids)
                or self._delta_ids[slot] != client_id):
            raise ValueError(f"latest payload predates client {client_id}'s "
                             f"admission — sync first")
        return dp.decode_client(self.codec, self.last_delta,
                                self.tree.gaussians.sh.shape[1], slot)

    def delta_checksums(self) -> np.ndarray:
        """(pages,) uint32 per-page checksums of the latest sync's shared
        stream — the values the wire serializer writes into each page header
        (`manager.PAGE_HEADER_BYTES` budgets the slot). A client re-derives
        each page's checksum from the rows it parsed and NACKs mismatches."""
        if self.last_delta is None:
            raise ValueError("no sync performed yet (or dedup=False)")
        return dp.page_checksums(self.last_delta)

    def resolve_nack(self, client_id: int, lost_pages) -> np.ndarray:
        """READ-ONLY half of the page-loss NACK: the ascending gids client
        `client_id` ingested from the named priority pages of the LATEST
        sync's stream — the rows a checksum-failed page costs it, resolved
        against the current payload. `nack` applies them; a journaling layer
        (repro.serve.recovery) records the resolved gids instead of the page
        numbers, so crash replay never depends on a payload that died with
        the process.

        Like `client_delta`, the NACK is a per-sync artifact: it must name
        pages of the latest payload, and a client admitted (or recycled)
        after that sync has no rows in it — that is an error, never a silent
        requeue of the previous tenant's rows."""
        if self.last_delta is None:
            raise ValueError("no sync performed yet (or dedup=False)")
        slot = self._slot_of(client_id)
        if (slot >= len(self._delta_ids)
                or self._delta_ids[slot] != client_id):
            raise ValueError(f"latest payload predates client {client_id}'s "
                             f"admission — nothing to NACK")
        n_pages = int(np.asarray(self.last_delta.pages))
        pages = sorted(set(int(p) for p in lost_pages))
        bad = [p for p in pages if not 0 <= p < n_pages]
        if bad:
            raise ValueError(f"NACK names pages {bad} outside the latest "
                             f"stream's {n_pages} pages")
        return np.flatnonzero(dp.lost_row_mask(self.last_delta, slot, pages))

    def nack_rows(self, client_id: int, gids) -> int:
        """Re-queue specific Gaussians as one live client's pending debt —
        the APPLY half of the NACK (and the form the sync journal replays):
        the rows fold into the next sync's union like budget-deferred pages
        and retransmit through the normal priority stream. Returns the
        number of rows queued."""
        slot = self._slot_of(client_id)
        g = np.asarray(list(gids), np.int64)
        if g.size and (g.min() < 0 or g.max() >= self.tree.n_pad):
            raise ValueError(f"NACK gids outside [0, {self.tree.n_pad})")
        mask = np.zeros((self.tree.n_pad,), bool)
        mask[g] = True
        self.state = shd.shard_service_state(
            self.mesh, service_nack_rows(self.state, slot,
                                         bp.pack(jnp.asarray(mask))))
        return int(mask.sum())

    def nack(self, client_id: int, lost_pages) -> int:
        """Client-reported page loss on the LATEST sync's stream: re-queue
        the rows `client_id` ingested from the named priority pages as
        `ServiceState.pending` debt (`resolve_nack` + `nack_rows`). Returns
        the number of rows re-queued."""
        return self.nack_rows(client_id,
                              self.resolve_nack(client_id, lost_pages))

    # -- fallback rendering ---------------------------------------------------

    def _fleet_key(self):
        """The fleet signature every render cache key must carry: the
        capacity bucket AND the live slot layout. Without it an evict (or a
        slot recycle) would serve a stacked-rig pytree whose slot alignment
        belongs to the previous fleet."""
        return (self.capacity, tuple(np.flatnonzero(self._active)))

    def _slot_aligned_rigs(self, rigs):
        """Expand an n_clients rig list (slot order) to a capacity-length
        slot list; free slots borrow the first rig purely as a shape/static
        placeholder — their queues are empty and the pooled path masks their
        tiles out entirely."""
        rigs = list(rigs)
        if self.n_clients == 0:
            raise ValueError("no live clients to render (fleet is empty)")
        if len(rigs) == self.capacity and self.n_clients == self.capacity:
            return rigs
        if len(rigs) != self.n_clients:
            raise ValueError(f"expected {self.n_clients} rigs (one per live "
                             f"client, slot order) or a slot-aligned stacked "
                             f"pytree, got {len(rigs)}")
        slot_rigs = [rigs[0]] * self.capacity
        for slot, rig in zip(np.flatnonzero(self._active), rigs):
            slot_rigs[int(slot)] = rig
        return slot_rigs

    def _fleet_render_config(self, rigs, tile, list_len, max_pairs):
        """Per-signature cache of the static RenderConfig + stacked rigs.

        Rebuilding the (frozen, hashable) RenderConfig each call re-traces
        nothing by itself, but `for_fleet` + `stack_rigs` walk every rig on
        the host per frame; repeated fleet renders (the steady state of the
        fallback tier) hit the caches instead. Both keys include the fleet
        signature (capacity bucket + live slots), so churn invalidates
        exactly the stale entries; the stack cache additionally keys on rig
        identity and pins the rig objects, so a hit can only mean the exact
        same rig pytrees in the exact same fleet."""
        fleet_key = self._fleet_key()
        static_sig = (tuple((r.left.width, r.left.height, float(r.left.focal),
                             r.left.near, r.left.far, r.baseline)
                            for r in rigs), tile, list_len, max_pairs,
                      fleet_key)
        rcfg = self._rcfg_cache.get(static_sig)
        if rcfg is None:
            rcfg = rnd.RenderConfig.for_fleet(rigs, tile=tile,
                                              list_len=list_len,
                                              max_pairs=max_pairs)
            self._rcfg_cache[static_sig] = rcfg
        stack_key = (tuple(id(r) for r in rigs), fleet_key)
        hit = self._stack_cache.get(stack_key)
        if hit is None:
            if len(self._stack_cache) >= 8:   # bound the pinned rigs
                self._stack_cache.clear()
            hit = (list(rigs), rnd.stack_rigs(rigs))
            self._stack_cache[stack_key] = hit
        return rcfg, hit[1]

    def render_fallback(self, rigs, *, tile: int = 16, list_len: int = 256,
                        max_pairs: int = 1 << 16, path: str = "vmap"):
        """Fleet render of every live client's queue → (img_l, img_r, stats)
        with a leading SLOT axis (inactive slots render black).

        `rigs` is a list of n_clients StereoRigs (shared resolution/
        baseline; slot order, like `sync`) or an already slot-aligned
        stacked rig pytree. The derived static `RenderConfig` (and, for rig
        lists, the stacked pytree) is cached per (rig, fleet) signature so
        repeated fleet renders skip the per-call host rebuild — and churn
        can never serve a stale stacked-rig pytree."""
        if isinstance(rigs, (list, tuple)):
            rcfg, rigs = self._fleet_render_config(
                self._slot_aligned_rigs(rigs), tile, list_len, max_pairs)
        else:
            from repro.core.stereo import n_categories
            focal = float(np.max(np.asarray(rigs.left.focal)))
            static_sig = (rigs.left.width, rigs.left.height, focal,
                          rigs.left.near, rigs.baseline, tile, list_len,
                          max_pairs, self._fleet_key())
            rcfg = self._rcfg_cache.get(static_sig)
            if rcfg is None:
                max_disp = focal * rigs.baseline / rigs.left.near
                rcfg = rnd.RenderConfig(
                    width=rigs.left.width, height=rigs.left.height, tile=tile,
                    list_len=list_len, max_pairs=max_pairs,
                    n_cat=n_categories(max_disp, tile))
                self._rcfg_cache[static_sig] = rcfg
        return service_render_step(self.tree, self.state, rigs, rcfg,
                                   path=path, mesh=self.mesh)


def empty_stats(capacity: int) -> ServiceStats:
    """The stats of a tick that served no slot: every row zero."""
    zi = jnp.zeros((capacity,), jnp.int32)
    zf = jnp.zeros((capacity,), jnp.float32)
    zb = jnp.zeros((capacity,), bool)
    return ServiceStats(
        cut_size=zi, delta_size=zi, unique_delta=zi, sync_bytes=zf,
        dedup_bytes_saved=zf, nodes_touched=zi, resweeps=zi,
        client_resident=zi, overflow=zb, delta_overflow=zb,
        delta_shipped=zi, delta_deferred=zi, pages=zi, mtp_ms=zf,
        deadline_miss=zb)
