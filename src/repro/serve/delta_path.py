"""Encode-once fleet Δcut delivery (cross-client payload dedup).

The per-client service path encodes and ships every client's Δcut
independently — B co-located viewers pay B× codec work and B× downlink for
the *same* Gaussians. This module rebuilds that data path around the fleet's
**unique** work:

  * `build_delta_batch` computes the fleet-union of Δcut gids for one sync
    (the batched `SyncPlan.delta_data` masks already expose the overlap),
    gathers the union rows from the shared tree ONCE, and runs the codec
    quantize/pack ONCE per distinct Gaussian — a single batched
    `compression.encode` regardless of client count;
  * per-client payloads are fanned out as *(union-offset, mask)* references
    (`DeltaBatch.ref_mask`): client b's Δcut is exactly the union rows where
    `ref_mask[b]` is set, in the same ascending-gid order the per-client
    path would have produced — so decode-side payloads are bitwise identical
    to encode-per-client (proven in tests/test_delta_path.py);
  * when the sync's union exceeds the stream budget the union is **paged**,
    never truncated: rows are ranked coarse-LoD-first (low tree depth, ties
    by fleet requester count, then gid), the top `budget` ranks ship this
    sync as `page_size`-row priority pages, and every row left behind is
    reported in `DeltaBatch.deferred` — the service carries it into the
    NEXT sync's union as forced-stale membership, so a client's store
    converges bitwise to the unbudgeted oracle in ≤ ⌈U/width⌉ syncs
    (tests/test_delta_path.py). Per-client `allowance` caps the rows a
    single client ingests per sync (the closed-loop bitrate controller in
    repro.serve.lod_service sets it from measured wire bytes);
  * the wire model is a shared multicast stream + thin per-client framing:

        shared   : page headers + union gids (delta-coded ids, ascending
                   within each page) + encoded attribute rows
        per-client: cut add/remove ids + sync header  (unchanged)

    A client filters the shared stream by itself: it knows its render cut
    (`cut_add`/`cut_remove` ids) and its own store, so its Δ membership
    (`needed & ~has`) is locally computable — no per-client row index list
    is ever transmitted. Shared-stream bytes therefore grow with the number
    of *unique* Gaussians in the sync, not with B.

`manager.batched_wire_bytes(..., shared_rows=...)` holds the byte
accounting for this format (each shared row's cost split across its
requesters, so per-client stats still sum to fleet totals) — it charges a
client only for rows it ingested this sync (`DeltaBatch.shared_rows`)
plus `PAGE_HEADER_BYTES` per priority page it pulled rows from.

`_union_refs` gathers the shipped rows (only those: a sync pays for what it
ships) and one `compression.encode` packs them; the single-client
`core.pipeline` path keeps the old unicast format via
`compression.encode_rows` (the same codec, B=1, no union stream).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core import compression as comp
from repro.core import lod_search as ls
from repro.core.gaussians import Gaussians
from repro.serve import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeltaBatch:
    """One sync's encode-once fleet payload (one page-set of the union).

    union_gids: (U,) int32 — ascending global ids of the rows SHIPPED this
                sync, -1 padded (U is the pow2 stream width ≤ the budget)
    n_union:    () int32 — TRUE union size this sync (shipped + deferred ==
                unique Gaussians wanted, including carried-over debt)
    n_shipped:  () int32 — rows actually in this sync's stream (≤ n_union;
                equal unless the union overflowed the budget)
    payload:    EncodedGaussians with U rows — the codec ran ONCE, on the
                shipped rows; rows past n_shipped are padding
    ref_mask:   (B, U) bool — stream rows client b INGESTS this sync (its
                wanted rows among the shipped set, clipped to its per-client
                row allowance), aligned with union_gids
    delivered:  (B, W) uint32 — node-indexed view of ref_mask as bit planes
                (`repro.core.bitplane`): what lands in client b's store
                this sync
    deferred:   (B, W) uint32 — planes of the rows client b wanted that did
                NOT ship to it this sync (union overflow or allowance) — the
                carry-over the service folds into the next sync's union
    shared_rows: (B,) float32 — client b's share of the stream: over the
                rows it ingests, the sum of 1 / (clients ingesting the
                row); drives the wire accounting
                (`manager.batched_wire_bytes`)
    client_overflow: (B,) bool — client b has ≥1 deferred row this sync
    client_pages: (B,) int32 — priority pages client b pulled rows from
                (page-header framing charge)
    pages:      () int32 — priority pages in this sync's shared stream
                (⌈n_shipped/page_size⌉)
    row_page:   (U,) int32 — the PRIORITY page each wire-order row shipped
                in (-1 for padding rows past n_shipped). Wire order is
                ascending-gid but pages are priority ranks, so a page's rows
                are interleaved through the stream — this map is what lets a
                client turn "page p failed its checksum" into the exact row
                set to NACK.
    overflow:   () bool — some row was deferred somewhere in the fleet (the
                old truncation flag, now recoverable instead of a silent
                loss)
    """

    union_gids: jax.Array
    n_union: jax.Array
    n_shipped: jax.Array
    payload: comp.EncodedGaussians
    ref_mask: jax.Array
    delivered: jax.Array
    deferred: jax.Array
    shared_rows: jax.Array
    client_overflow: jax.Array
    client_pages: jax.Array
    pages: jax.Array
    row_page: jax.Array
    overflow: jax.Array

    @property
    def n_clients(self) -> int:
        return self.ref_mask.shape[0]


_PRIO_PAD = 2**31 - 1  # non-members sort after every real row


@jax.jit
@tracing.scoped("delta.union")
def _union_mask(wanted: jax.Array, priority: jax.Array):
    """The size of the sync's union and every row's rank key: one int32
    ordering rows by (priority asc, requester count desc), non-members
    last. `wanted` is (B, W) bit planes, `priority` (32·W,) per node.
    Priorities are non-negative; those above ~2^31/(B+1) tie (tree levels
    stay ordered, and padding's level sentinel still sorts last)."""
    b, w = wanted.shape

    def count(req, chunk):
        return req + bp.spread(chunk).sum(axis=0, dtype=jnp.int32), None

    req, _ = bp.map_slots(count, wanted, bp.slot_chunk(b, bp.WORD * w),
                          init=jnp.zeros((bp.WORD, w), jnp.int32))
    prio = jnp.clip(bp.bit_major(priority.astype(jnp.int32)), 0,
                    (_PRIO_PAD - 1 - b) // (b + 1))
    union = req > 0
    key = jnp.where(union, prio * (b + 1) + (b - req), jnp.int32(_PRIO_PAD))
    return union.sum().astype(jnp.int32), bp.node_order(key)


@jax.jit
@tracing.scoped("delta.union")
def _rank_union(key: jax.Array, n_union: jax.Array, width: jax.Array):
    """Rank every row by its key, ties by gid (a stable sort of the gids),
    and lay the top min(n_union, width) ranks out in wire order (ascending
    gid). Width is traced: the N-row sort compiles once per table size, not
    once per pow2 stream width (on a TPU that compile takes tens of seconds
    at city scale). Returns (gids by rank, rank of each gid, shipped gids
    ascending then -1), all over the node axis of `key`."""
    n = key.shape[0]
    gid = jnp.arange(n, dtype=jnp.int32)
    _, by_rank = jax.lax.sort((key, gid), num_keys=1, is_stable=True)
    rank_of = jnp.zeros((n,), jnp.int32).at[by_rank].set(gid)
    shipped = rank_of < jnp.minimum(n_union, width)
    pos = jnp.cumsum(shipped.astype(jnp.int32)) - 1
    wire = jnp.full((n,), -1, jnp.int32).at[jnp.where(shipped, pos, n)].set(
        gid, mode="drop")
    return by_rank, rank_of, wire


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


# rank or stream positions a pass of the union's per-client work covers:
# a sync pays for the rows it ships, not for the width of its stream
UNION_BLOCK = 1 << 14


@functools.partial(jax.jit, static_argnames=("width", "page_size", "mesh"))
@tracing.scoped("delta.union")
def _union_refs(wanted: jax.Array, by_rank: jax.Array, rank_of: jax.Array,
                wire: jax.Array, n_union: jax.Array, allowance: jax.Array,
                gaussians: Gaussians, width: int, page_size: int, mesh=None):
    """Priority-ordered page selection of one sync's union.

    Union rows are ranked (`_rank_union`) by (tree depth asc, requester
    count desc, gid asc) — coarse LoD ships first, ties broken toward the
    most-shared rows — and the top `width` ranks ship. The stream itself
    stays ASCENDING by gid (delta-coded ids; each page is internally
    ascending), so the shipped subset decodes exactly like the unpaged
    format. `allowance` (B,) caps the rows each client ingests this sync,
    counted in priority order, so a bandwidth-tiered client takes the
    coarsest pages first and defers the rest: it ingests exactly its
    wanted rows ranked below a per-client reach. `wanted` is (B, W) bit
    planes.

    The per-client work runs in passes of `UNION_BLOCK` positions over the
    shipped ranks, then over the shipped stream rows, as many passes as the
    sync ships rows: a stream padded to a wide width costs no more than a
    narrow one. Returns everything the batch needs: the wire-order gids,
    the Gaussian rows to encode (row 0 in padding positions), the refs, the
    delivered/deferred planes, each client's share of the stream and the
    page accounting."""
    b, w = wanted.shape
    n_shipped = jnp.minimum(n_union, width).astype(jnp.int32)
    pages = ((n_shipped + page_size - 1) // page_size).astype(jnp.int32)
    block = page_size * max(1, min(width, UNION_BLOCK) // page_size)
    span = -(-width // block) * block
    passes = (n_shipped + block - 1) // block
    by_rank = jnp.pad(by_rank[:width], (0, span - width))
    gids = jnp.pad(wire[:width], (0, span - width), constant_values=-1)
    zeros = jnp.zeros((b,), jnp.int32)

    def rank_pass(k, carry):
        # in priority order: how far each client's allowance reaches, and
        # the pages it takes rows from
        seen, reach, client_pages = carry
        pos = k * block + jnp.arange(block, dtype=jnp.int32)
        take = jax.lax.dynamic_slice_in_dim(by_rank, k * block, block)
        want = bp.bit_at(wanted, take) & (pos < n_shipped)[None, :]
        within = (seen[:, None] + jnp.cumsum(want.astype(jnp.int32), axis=1)
                  <= allowance[:, None])
        hit = (want & within).reshape(b, block // page_size, page_size)
        return (seen + want.sum(axis=1, dtype=jnp.int32),
                reach + within.sum(axis=1, dtype=jnp.int32),
                client_pages + hit.any(axis=2).sum(axis=1, dtype=jnp.int32))

    _, reach, client_pages = jax.lax.fori_loop(0, passes, rank_pass,
                                               (zeros, zeros, zeros))
    reach = jnp.minimum(reach, n_shipped)

    # node-indexed planes: what landed (wanted, ranked below the reach),
    # what is owed
    rank_bits = bp.bit_major(rank_of)            # (32, W)

    def planes(_, chunk):
        want, lim = chunk
        delivered = want & bp.pack_bit_major(
            rank_bits[None] < lim[:, None, None])
        deferred = want & ~delivered
        return None, (delivered, deferred, jnp.any(deferred != 0, axis=1))

    _, (delivered, deferred, client_overflow) = bp.map_slots(
        planes, (wanted, reach), bp.slot_chunk(b, bp.WORD * w))

    def wire_pass(k, carry):
        # in wire order: each client's refs, each row's page and Gaussian
        # row, and each client's share of the rows (a row's cost splits
        # evenly over the clients that ingest it)
        ref, row_page, rows, shared = carry
        at = k * block
        g = jax.lax.dynamic_slice_in_dim(gids, at, block)
        on = g >= 0
        safe = jnp.maximum(g, 0)
        rank = rank_of[safe]
        r = (bp.bit_at(wanted, safe) & (rank[None, :] < reach[:, None])
             & on[None, :])
        inv = 1.0 / jnp.maximum(r.sum(axis=0, dtype=jnp.int32), 1)
        rows = jax.tree_util.tree_map(
            lambda buf, a: jax.lax.dynamic_update_slice_in_dim(
                buf, jnp.take(a, safe, axis=0), at, 0), rows, gaussians)
        return (jax.lax.dynamic_update_slice_in_dim(ref, r, at, 1),
                jax.lax.dynamic_update_slice_in_dim(
                    row_page, jnp.where(on, rank // page_size, -1), at, 0),
                rows, shared + _pairwise_sum(jnp.where(r, inv, 0.0)))

    rows0 = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[:1], (span,) + a.shape[1:]), gaussians)
    ref, row_page, rows, shared_rows = jax.lax.fori_loop(
        0, passes, wire_pass,
        (jnp.zeros((b, span), bool), jnp.full((span,), -1, jnp.int32), rows0,
         jnp.zeros((b,), jnp.float32)))
    gids, ref, row_page = gids[:width], ref[:, :width], row_page[:width]
    rows = jax.tree_util.tree_map(lambda a: a[:width], rows)
    if mesh is not None:
        from repro.sharding.fleet import constrain_fleet
        # the union row axis shards over `slabs` (codec work parallelism);
        # per-client leaves stay with their client shard
        gids = constrain_fleet(gids, ("union",), mesh)
        ref = constrain_fleet(ref, ("clients", "union"), mesh)
        row_page = constrain_fleet(row_page, ("union",), mesh)
        delivered = constrain_fleet(delivered, ("clients", None), mesh)
        deferred = constrain_fleet(deferred, ("clients", None), mesh)
        client_overflow = constrain_fleet(client_overflow, ("clients",), mesh)
        client_pages = constrain_fleet(client_pages, ("clients",), mesh)
        shared_rows = constrain_fleet(shared_rows, ("clients",), mesh)
    return (gids, rows, ref, delivered, deferred, shared_rows,
            client_overflow, client_pages, pages, n_shipped, row_page)


def build_delta_batch(gaussians: Gaussians, codec: comp.Codec,
                      delta_masks: jax.Array, budget: int,
                      active=None, mesh=None, *, pending=None, priority=None,
                      allowance=None, page_size=None,
                      widths: Sequence[int] = ()) -> DeltaBatch:
    """Encode one sync's fleet Δcut once, paged under the budget.

    delta_masks: (B, W) uint32 bit planes — the batched
    `SyncPlan.delta_data` (`repro.core.bitplane`).
    budget: static cap on the encoded stream (rows). A union larger than the
    budget is NOT truncated: the coarsest `budget` priority ranks ship now
    and the rest comes back in `deferred` for the caller to fold into the
    next sync (`overflow` flags that some row was deferred).
    pending: optional (B, W) uint32 carry-over debt from earlier syncs
    (rows deferred then) — unioned into this sync's wanted set, so a
    deferred Gaussian keeps competing for stream slots until it ships.
    priority: optional (N,) or (32·W,) int32 rank key, lower ships first,
    padded to the planes' node axis (the service passes
    `LodTree.node_levels()` — coarse LoD first); default 0 everywhere
    (requester count / gid order only).
    allowance: optional (B,) int32 per-client row cap for this sync (the
    closed-loop bitrate controller's knob); default unlimited.
    page_size: rows per priority page (accounting granularity for the
    per-page wire header); default one page spanning the whole stream.
    active: optional (B,) bool slot mask (ragged fleets, repro.serve.fleet)
    — an inactive slot contributes NO rows to the union (its `ref_mask` row
    stays all-False and no Gaussian is encoded on its behalf), so the
    encode-once stream and its pow2 width track the *active* fleet only.

    The encode width is pow2-bucketed on the ACTUAL union size (one scalar
    await — the same bounded-recompilation pattern as the pooled stale-slab
    scheduler), so codec quantize/pack FLOPs track the sync's unique
    Gaussians, not the static budget: a steady-state sync with a tiny union
    encodes a tiny bucket, never the whole budget. `widths` lists the widths
    a long-lived service has built: the stream takes the narrowest of them
    (up to the budget) that holds the bucket, so a draining backlog stays on
    compiled programs, and only a union wider than each builds a new width.
    A wider stream only pads: every row, reference and byte is the same.

    Sharded fleets (`mesh`, repro.sharding.fleet): the union `any` over
    clients is a CROSS-SHARD reduction — the union mask, its gids, and the
    encoded payload come back REPLICATED across client shards (the
    replicated-union fallback: every host holds the full multicast stream,
    which is the wire model anyway — the stream is broadcast to every
    client). Codec quantize/pack work is sharded along the union row axis
    over the `slabs` mesh axis when the pow2 width divides; an indivisible
    width replicates the encode (bitwise identical either way —
    tests/test_sharding_fleet.py)."""
    if active is not None:
        on = bp.rows(active, 2)
        delta_masks = delta_masks & on
        if pending is not None:
            pending = pending & on
    wanted = delta_masks if pending is None else delta_masks | pending
    b, w = wanted.shape
    n_rows = bp.WORD * w
    if priority is None:
        priority = jnp.zeros((n_rows,), jnp.int32)
    elif priority.shape[0] < n_rows:
        priority = jnp.pad(priority, (0, n_rows - priority.shape[0]))
    n_union, key = _union_mask(wanted, priority)
    with tracing.span("delta.union_size_read"):
        width = ls.pow2_bucket(int(jax.device_get(n_union)), budget)
    width = min((w for w in widths if width <= w <= budget), default=width)
    allow = (jnp.full((b,), width, jnp.int32) if allowance is None
             else jnp.asarray(allowance, jnp.int32))
    psize = width if page_size is None else max(1, min(int(page_size), width))
    by_rank, rank_of, wire = _rank_union(key, n_union, jnp.int32(width))
    (gids, rows, ref, delivered, deferred, shared_rows, client_overflow,
     client_pages, pages, n_shipped, row_page) = _union_refs(
        wanted, by_rank, rank_of, wire, n_union, allow, gaussians,
        width=width, page_size=psize, mesh=mesh)
    payload = comp.encode(codec, rows)
    if mesh is not None:
        from repro.sharding.fleet import constrain_fleet
        payload = jax.tree_util.tree_map(
            lambda a: constrain_fleet(
                a, ("union",) + (None,) * (a.ndim - 1), mesh), payload)
    return DeltaBatch(union_gids=gids, n_union=n_union, n_shipped=n_shipped,
                      payload=payload, ref_mask=ref, delivered=delivered,
                      deferred=deferred, shared_rows=shared_rows,
                      client_overflow=client_overflow,
                      client_pages=client_pages, pages=pages,
                      row_page=row_page, overflow=client_overflow.any())


def decode_client(codec: comp.Codec, batch: DeltaBatch, sh_k: int,
                  client: int) -> Tuple[jax.Array, Gaussians]:
    """One client's decoded Δcut from the shared stream.

    Returns (ids (U,) int32 — this client's gids, -1 where the union row is
    not referenced — and the decoded union rows (U,)). Scattering rows where
    ids >= 0 into the client store reproduces the encode-per-client path
    bit-for-bit (the codec is row-wise deterministic and union rows keep
    ascending-gid order).

    A client decodes on one device of its own, so a payload that a mesh
    spread over several devices is gathered to one first: a partitioned
    decode may round a row differently in the last ulp, and the rows a
    client decodes must not depend on the server's mesh."""
    payload = batch.payload
    devices = payload.pos_q.sharding.device_set
    if len(devices) > 1:
        payload = jax.device_put(payload, min(devices, key=lambda d: d.id))
    dec = comp.decode(codec, payload, sh_k)
    ids = jnp.where(batch.ref_mask[client], batch.union_gids, -1)
    return ids, dec


def encode_per_client(gaussians: Gaussians, codec: comp.Codec,
                      delta_masks: jax.Array, budget: int):
    """Reference path: encode every client's Δcut (`delta_masks`, (B, N)
    bool) independently (B codec calls). Returns per-client (ids (budget,)
    int32 -1 padded ascending, EncodedGaussians, overflow () bool).
    `overflow` is true when the client's Δ exceeded the budget and its
    unicast stream was TRUNCATED — parity fixtures must assert it false,
    otherwise dedup-vs-baseline comparisons can pass with both paths
    silently wrong (the bug this flag closes). Exists as the baseline the dedup path is proven against — and
    as the measuring stick for `dedup_bytes_saved`."""
    out = []
    for b in range(delta_masks.shape[0]):
        count = delta_masks[b].sum().astype(jnp.int32)
        (ids,) = jnp.nonzero(delta_masks[b], size=budget, fill_value=-1)
        ids = ids.astype(jnp.int32)
        out.append((ids, comp.encode_rows(codec, gaussians, ids),
                    count > jnp.int32(budget)))
    return out


# ---------------------------------------------------------------------------
# page integrity (loss detection + NACK retransmit)
# ---------------------------------------------------------------------------

# Knuth multiplicative hash constant — mixes each gid before the per-page
# sum so a swap of two gids between pages (same total) still flips both
# checksums; +1 makes the count of rows in the page part of the sum too
# (a dropped gid-0 row would otherwise hash to 0 and vanish).
_CKSUM_MIX = np.uint32(2654435761)


def page_checksums(batch: DeltaBatch) -> np.ndarray:
    """(pages,) uint32 — the per-page content checksum carried in each
    priority page's wire header (`manager.PAGE_HEADER_BYTES` already budgets
    the 4-byte slot). Host-side: checksums are wire framing, computed once
    per sync when the stream is serialized, never inside the jitted sync.

    A page's checksum covers the gids of its rows (order-independent
    wraparound sum of mixed gids), so a receiver that re-derives it over the
    rows it parsed detects any dropped/corrupted page without trusting the
    radio link's own CRC."""
    row_page = np.asarray(batch.row_page)
    gids = np.asarray(batch.union_gids)
    n_pages = int(np.asarray(batch.pages))
    out = np.zeros((max(n_pages, 1),), np.uint32)
    rows = row_page >= 0
    with np.errstate(over="ignore"):
        mix = gids[rows].astype(np.uint32) * _CKSUM_MIX + np.uint32(1)
    np.add.at(out, row_page[rows], mix)
    return out[:n_pages]


def lost_row_mask(batch: DeltaBatch, client: int, lost_pages) -> np.ndarray:
    """(32·W,) bool node mask of the rows slot `client` INGESTED this sync
    from the given priority pages — the retransmit set for a NACK naming
    pages whose checksum failed client-side. Rows of a lost page the client did
    not reference cost it nothing and are not re-queued."""
    row_page = np.asarray(batch.row_page)
    gids = np.asarray(batch.union_gids)
    ref = np.asarray(batch.ref_mask)[client]
    n = bp.WORD * batch.delivered.shape[1]
    lost = np.asarray(sorted(set(int(p) for p in lost_pages)), np.int64)
    rows = ref & np.isin(row_page, lost) & (gids >= 0)
    out = np.zeros((n,), bool)
    out[gids[rows]] = True
    return out


# ---------------------------------------------------------------------------
# dedup accounting
# ---------------------------------------------------------------------------


@jax.jit
@tracing.scoped("table.update")
def first_owner_counts(delta_masks: jax.Array) -> jax.Array:
    """(B,) int32 — per client, the number of its Δ rows for which it is the
    fleet's *first* requester (lowest client index). `delta_masks` is (B, W)
    bit planes. Partitions the union:
    `first_owner_counts(m).sum() == unique Gaussians this sync` — the
    `ServiceStats.unique_delta` column."""
    with tracing.scope("table.update/pack"):
        seen = jax.lax.associative_scan(jnp.bitwise_or, delta_masks, axis=0)
        before = jnp.concatenate([jnp.zeros_like(delta_masks[:1]), seen[:-1]])
        return bp.count(delta_masks & ~before)
