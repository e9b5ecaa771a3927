"""Fully-streaming + temporal-aware LoD search (paper §4.2), TPU-native.

Semantics (identical to HierGS-style traversal):
  proj(n)    = size(n) * focal / dist(cam, n)          (radial ⇒ rotation-free)
  expand(n)  = expand(parent(n)) AND proj(n) > τ        (root parent ≡ True)
  in_cut(n)  = expand(parent(n)) AND (proj(n) ≤ τ OR leaf(n))

*Fully-streaming traversal* — the tree is laid out as a replicated top-tree
plus fixed-size subtree slabs (see lod_tree.py). One frame = a level-major
sweep of the top-tree + a vmapped sweep of each slab. Slabs are in DFS
preorder, so a slab sweep reads ancestry from subtree ranges with one
prefix max along the slab (no gather, no loop over levels) — the TPU
analogue of the paper's shared-memory streaming.

*Temporal-aware search* — per subtree we maintain a provably-safe reuse bound:
after sweeping subtree s at camera position c0, ρ_s = min over its nodes of
|dist(c0, n) − r*(n)| with r*(n) = size(n)·focal/τ (the node's LoD-boundary
sphere radius). While the camera stays within ρ_s of c0 *and* the slab root's
parent-expand bit (recomputed exactly every frame from the cheap top sweep)
is unchanged, no comparison inside the subtree can flip, so the cached cut
slab is **bit-accurate**. This replaces the paper's previous-cut seeding with
an explicit invariant (same goal: skip untouched subtrees; DESIGN.md §2).

Two drivers are provided:
  * `temporal_search`        — fully jittable (vmap + select; exactness tests,
                               and composition into larger jitted pipelines);
  * `temporal_search_hybrid` — host-driven: gathers only the stale slabs and
                               sweeps them (bucketed shapes), delivering real
                               wall-clock savings proportional to staleness.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core.lod_tree import LodTree
from repro.serve import tracing

_EPS_DIST = 1e-6


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CutResult:
    """One frame's LoD cut.

    top_cut:  (T,)    bool — cut nodes inside the top-tree
    slab_cut: (Ns, S) bool — cut nodes inside each subtree slab
    root_expand: (Ns,) bool — expand flag of each slab root (diagnostics)
    resweep:  (Ns,)   bool — which slabs were actually swept this frame
    nodes_touched: () int32 — streaming work metric (top + swept slabs)
    """

    top_cut: jax.Array
    slab_cut: jax.Array
    root_expand: jax.Array
    resweep: jax.Array
    nodes_touched: jax.Array

    def mask(self, tree: LodTree) -> jax.Array:
        """(N_pad,) global cut mask."""
        return jnp.concatenate([self.top_cut, self.slab_cut.reshape(-1)])

    def count(self) -> jax.Array:
        return self.top_cut.sum() + self.slab_cut.sum()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TemporalState:
    """Per-subtree reuse state for temporal-aware search."""

    cam0: jax.Array            # (Ns, 3) camera at last sweep
    rho: jax.Array             # (Ns,)  safe radius
    parent_expand0: jax.Array  # (Ns,)  top parent-expand bit at last sweep
    slab_cut0: jax.Array       # (Ns, words(S)) uint32 — cached cut, packed
    #                            (`repro.core.bitplane`)
    root_expand0: jax.Array    # (Ns,)
    swept: jax.Array           # (Ns,)  ever swept

    @staticmethod
    def initial(Ns: int, S: int) -> "TemporalState":
        return TemporalState(
            cam0=jnp.zeros((Ns, 3), jnp.float32),
            rho=jnp.zeros((Ns,), jnp.float32),
            parent_expand0=jnp.zeros((Ns,), bool),
            slab_cut0=jnp.zeros((Ns, bp.words(S)), jnp.uint32),
            root_expand0=jnp.zeros((Ns,), bool),
            swept=jnp.zeros((Ns,), bool),
        )

    @staticmethod
    def initial_batched(Ns: int, S: int, B: int) -> "TemporalState":
        """B independent clients' states stacked on a leading batch axis.
        (`swept=False` everywhere, so every client's first search is a full
        sweep — identical to `full_search`.)"""
        base = TemporalState.initial(Ns, S)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (B,) + a.shape), base)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SlabTables:
    """Device-resident slab attribute tables, gathered once per tree.

    `tree.slab_mu()` / `tree.slab_size()` reshape the packed Gaussian arrays
    on every call; hot schedulers (repro.serve.lod_service) build these
    tables once at init and fuse the per-sync pair gather into the sweep
    program instead of re-deriving the views every sync."""

    mu: jax.Array        # (Ns, S, 3)
    size: jax.Array      # (Ns, S)
    is_leaf: jax.Array   # (Ns, S) bool
    valid: jax.Array     # (Ns, S) bool
    end: jax.Array       # (Ns, S) int32 — DFS subtree end

    @staticmethod
    def from_tree(tree: LodTree, mesh=None) -> "SlabTables":
        """`mesh` (a fleet mesh, repro.sharding.fleet) shards every table on
        its leading Ns axis over the `slabs` mesh axis — the city's attribute
        tables stop being bounded by one accelerator's HBM. Indivisible Ns
        (or no mesh) replicates: bitwise the single-device tables."""
        tables = SlabTables(
            mu=tree.slab_mu(), size=tree.slab_size(),
            is_leaf=tree.slab_is_leaf, valid=tree.slab_valid,
            end=tree.slab_end)
        if mesh is not None:
            from repro.sharding.fleet import shard_slab_tables
            tables = shard_slab_tables(mesh, tables)
        return tables


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------


def sq_dist(mu, cam_pos):
    """Squared distance over the last axis, summed x, y, z in that order:
    an explicit sum rather than a reduction, so every sweep adds in the same
    order whatever program it is fused into."""
    d = mu - cam_pos
    return d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1] + d[..., 2] * d[..., 2]


def lod_gt(size, dist2, focal, tau):
    """proj(n) > τ, decided without a divide or a square root:
    (size·focal)² > τ²·max(dist², ε²). Every sweep (XLA, the Pallas kernel,
    the numpy reference) evaluates this one expression, so their decisions
    agree bit for bit on any backend: a TPU's divide and square root may
    round differently under XLA and under Mosaic, its multiplies do not."""
    r = size * focal
    t = jnp.asarray(tau, jnp.float32)
    return r * r > (t * t) * jnp.maximum(dist2, _EPS_DIST * _EPS_DIST)


def top_sweep(tree: LodTree, cam_pos: jax.Array, focal, tau
              ) -> Tuple[jax.Array, jax.Array]:
    """Level-major sweep of the top-tree. Returns (expand, in_cut), both (T,)."""
    m = tree.meta
    mu = tree.top_mu()
    size = tree.top_size()
    gt = lod_gt(size, sq_dist(mu, cam_pos), focal, tau)

    expand = jnp.zeros((m.T,), bool)
    in_cut = jnp.zeros((m.T,), bool)
    offs = m.top_level_offsets
    for l in range(m.P):
        lo, hi = offs[l], offs[l + 1]
        if l == 0:
            pe = jnp.ones((hi - lo,), bool)
        else:
            pe = expand[tree.top_parent[lo:hi]]
        expand = expand.at[lo:hi].set(pe & gt[lo:hi])
        in_cut = in_cut.at[lo:hi].set(pe & (~gt[lo:hi] | tree.top_is_leaf[lo:hi]))
    return expand, in_cut


def _slab_sweep_one(mu, size, end, is_leaf, valid, root_parent_expand,
                    cam_pos, focal, tau):
    """Sweep a single (S,)-slab. Returns (in_cut, root_expand, rho).

    The slab is in DFS preorder, so node j's subtree is the lane range
    [j, end[j]) and j's ancestors are the valid nodes a < j with
    end[a] > j. j's parent expands iff the slab root's parent does and no
    ancestor stops (proj ≤ τ), i.e. iff the exclusive prefix max of
    end[a]·[valid(a) ∧ ¬gt(a)] over a < j is at most j: one cumulative max
    along the slab instead of a parent gather per level. The Pallas kernel
    (repro.kernels.lod_cut) computes the same prefix max; the level loop it
    replaces is the oracle `repro.kernels.ref.ref_lod_pair_sweep`."""
    dist2 = sq_dist(mu, cam_pos)
    gt = lod_gt(size, dist2, focal, tau)

    lane = jnp.arange(mu.shape[0], dtype=end.dtype)
    stop = jnp.where(valid & ~gt, end, 0)
    reach = jax.lax.cummax(jnp.pad(stop[:-1], (1, 0)))   # exclusive
    pexp = root_parent_expand & (reach <= lane)
    expand = pexp & gt & valid
    in_cut = pexp & (~gt | is_leaf) & valid

    # bit-accurate reuse bound: min distance-to-LoD-boundary over valid nodes
    # (a radius, not a decision: it may differ from the Pallas sweep's in
    # the last ulps)
    rstar = size * focal / tau
    dist = jnp.linalg.norm(mu - cam_pos, axis=-1)
    margin = jnp.where(valid, jnp.abs(dist - rstar), jnp.inf)
    rho = jnp.min(margin)
    return in_cut, expand[0], rho


def _slab_sweep_all(tree: LodTree, cam_pos, focal, tau, root_parent_expand):
    fn = functools.partial(_slab_sweep_one, cam_pos=cam_pos, focal=focal, tau=tau)
    return jax.vmap(fn)(
        tree.slab_mu(), tree.slab_size(), tree.slab_end, tree.slab_is_leaf,
        tree.slab_valid, root_parent_expand)


def _root_parent_expand(tree: LodTree, top_expand: jax.Array) -> jax.Array:
    """Exact parent-expand bit for every slab root (from the full top sweep)."""
    if tree.meta.P == 0:  # degenerate: whole tree is one slab rooted at level 0
        return jnp.ones((tree.meta.Ns,), bool)
    return top_expand[tree.slab_root_parent_top]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def full_search(tree: LodTree, cam_pos: jax.Array, focal: jax.Array,
                tau: jax.Array) -> Tuple[CutResult, TemporalState]:
    """Initial-frame fully-streaming traversal; also (re)initializes the
    temporal state (every subtree freshly swept)."""
    m = tree.meta
    cam_pos = jnp.asarray(cam_pos, jnp.float32)
    top_expand, top_cut = top_sweep(tree, cam_pos, focal, tau)
    rpe = _root_parent_expand(tree, top_expand)
    slab_cut, root_expand, rho = _slab_sweep_all(tree, cam_pos, focal, tau, rpe)

    cut = CutResult(
        top_cut=top_cut, slab_cut=slab_cut, root_expand=root_expand,
        resweep=jnp.ones((m.Ns,), bool),
        nodes_touched=jnp.asarray(m.T + m.Ns * m.S, jnp.int32),
    )
    state = TemporalState(
        cam0=jnp.broadcast_to(cam_pos, (m.Ns, 3)),
        rho=rho, parent_expand0=rpe, slab_cut0=bp.pack(slab_cut),
        root_expand0=root_expand, swept=jnp.ones((m.Ns,), bool),
    )
    return cut, state


@functools.partial(jax.jit, static_argnames=())
def temporal_search(tree: LodTree, state: TemporalState, cam_pos: jax.Array,
                    focal: jax.Array, tau: jax.Array
                    ) -> Tuple[CutResult, TemporalState]:
    """Temporal-aware search (jittable form). Bit-accurate vs full_search."""
    m = tree.meta
    cam_pos = jnp.asarray(cam_pos, jnp.float32)
    top_expand, top_cut = top_sweep(tree, cam_pos, focal, tau)
    rpe = _root_parent_expand(tree, top_expand)

    moved = jnp.linalg.norm(cam_pos - state.cam0, axis=-1)
    stale = (~state.swept) | (moved >= state.rho) | (rpe != state.parent_expand0)

    fresh_cut, fresh_root_expand, fresh_rho = _slab_sweep_all(
        tree, cam_pos, focal, tau, rpe)

    sel = stale[:, None]
    slab_cut = jnp.where(sel, fresh_cut, bp.unpack(state.slab_cut0, m.S))
    root_expand = jnp.where(stale, fresh_root_expand, state.root_expand0)

    new_state = TemporalState(
        cam0=jnp.where(sel, cam_pos[None, :], state.cam0),
        rho=jnp.where(stale, fresh_rho, state.rho),
        parent_expand0=rpe,
        slab_cut0=bp.pack(slab_cut),
        root_expand0=root_expand,
        swept=jnp.ones((m.Ns,), bool),
    )
    cut = CutResult(
        top_cut=top_cut, slab_cut=slab_cut, root_expand=root_expand,
        resweep=stale,
        nodes_touched=(m.T + stale.sum().astype(jnp.int32) * m.S).astype(jnp.int32),
    )
    return cut, new_state


# -- batched multi-client search (leading batch axis = clients) --------------


@functools.partial(jax.jit, static_argnames=())
def batched_temporal_search(tree: LodTree, states: TemporalState,
                            cam_positions: jax.Array, focal: jax.Array,
                            tau: jax.Array) -> Tuple[CutResult, TemporalState]:
    """`temporal_search` vmapped over B clients sharing one tree.

    states' leaves carry a leading (B, ...) axis (see
    `TemporalState.initial_batched`); cam_positions is (B, 3). `tau` may be a
    scalar (one threshold for everyone) or a (B,) per-client vector —
    foveated / gaze-dependent LoD: a client with a looser (larger) τ expands
    less of the tree and receives a strictly coarser, smaller cut. Returns a
    CutResult / TemporalState whose leaves are batched the same way — each
    client's slice is bit-identical to a sequential per-client
    `temporal_search` at its own τ. Shared-tree reads are broadcast, so the
    whole batch is one fused device program."""
    cam_positions = jnp.asarray(cam_positions, jnp.float32)
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32),
                            (cam_positions.shape[0],))
    return jax.vmap(temporal_search, in_axes=(None, 0, 0, None, 0))(
        tree, states, cam_positions, focal, taus)


def batched_cut_mask(cut: CutResult, tree: LodTree) -> jax.Array:
    """(B, N_pad) global cut masks from a batched CutResult.

    (`CutResult.mask` flattens all axes of slab_cut and is only correct for
    the unbatched case.)"""
    b = cut.top_cut.shape[0]
    return jnp.concatenate([cut.top_cut, cut.slab_cut.reshape(b, -1)], axis=1)


# -- host-driven variant (real wall-clock savings) ---------------------------


def pow2_bucket(n: int, cap: int) -> int:
    """Round `n` up to a power of two, clamped to [1, cap].

    The ONE bounded-recompilation bucket policy shared by every host-driven
    scheduler: the hybrid stale-slab sweep here, the service's pooled
    (client, slab) compaction and encode-once union width
    (repro.serve), the fleet occupied-tile pooling (repro.render), and the
    fleet capacity buckets of the lifecycle layer (repro.serve.fleet) —
    regression-pinned by tests/test_lod_search.py."""
    b = 1 << int(np.ceil(np.log2(max(n, 1))))
    return max(1, min(b, cap))


@functools.partial(jax.jit, static_argnames=())
def _sweep_selected(slab_mu, slab_size, slab_end, slab_is_leaf, slab_valid,
                    rpe_sel, cam_pos, focal, tau):
    fn = functools.partial(_slab_sweep_one, cam_pos=cam_pos, focal=focal, tau=tau)
    return jax.vmap(fn)(slab_mu, slab_size, slab_end, slab_is_leaf,
                        slab_valid, rpe_sel)


def _top_and_terms(tree: LodTree, state: TemporalState, cam_pos, focal, tau):
    """Top-tree sweep and the three terms of the staleness predicate, each
    (Ns,) bool: the slab was never swept, its root's parent-expand bit
    changed, the camera moved at least the reuse radius ρ."""
    top_expand, top_cut = top_sweep(tree, cam_pos, focal, tau)
    rpe = _root_parent_expand(tree, top_expand)
    moved = jnp.linalg.norm(cam_pos - state.cam0, axis=-1)
    return (top_cut, rpe, ~state.swept, rpe != state.parent_expand0,
            moved >= state.rho)


@functools.partial(jax.jit, static_argnames=())
def _top_and_staleness(tree: LodTree, state: TemporalState, cam_pos, focal, tau):
    top_cut, rpe, cold, parent, moved = _top_and_terms(tree, state, cam_pos,
                                                       focal, tau)
    return top_cut, rpe, cold | moved | parent


def stale_causes(cold, parent, moved) -> jax.Array:
    """(3,) int32 counts of stale pairs by first cause: never swept
    (cold), else parent expansion changed, else moved at least ρ. They sum
    to the stale pairs."""
    parent = parent & ~cold
    moved = moved & ~(cold | parent)
    return jnp.stack([cold.sum(), parent.sum(), moved.sum()]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("mesh",))
@tracing.scoped("lod.staleness")
def batched_top_and_staleness(tree: LodTree, states: TemporalState,
                              cam_positions: jax.Array, focal, tau,
                              active=None, *, mesh=None):
    """Per-client cheap phase of the hybrid search: exact top-tree sweep +
    per-subtree staleness predicate, vmapped over B clients. `tau` is a
    scalar or a (B,) per-client vector (foveated LoD).

    Returns (top_cut (B,T), rpe (B,Ns), stale (B,Ns), causes (3,) int32:
    the stale pairs counted by `stale_causes`). The expensive phase —
    sweeping only the stale (client, slab) pairs — is host-scheduled across
    clients by repro.serve.lod_service.

    `active` is an optional (B,) bool slot mask (the ragged-fleet lifecycle
    of repro.serve.fleet): inactive slots report ZERO staleness, so they add
    no pairs to the pooled sweep bucket and no pressure to the pool-size
    scalar the host awaits — sweep work tracks the fleet's *active*
    staleness, not its slot capacity.

    `mesh` (STATIC; a fleet mesh, repro.sharding.fleet) constrains the
    per-client outputs on the `clients` axis, so each client shard computes
    its own staleness rows — the cross-host staleness pool's cheap phase
    never gathers the fleet."""
    cam_positions = jnp.asarray(cam_positions, jnp.float32)
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32),
                            (cam_positions.shape[0],))
    top_cut, rpe, cold, parent, moved = jax.vmap(
        _top_and_terms, in_axes=(None, 0, 0, None, 0))(
        tree, states, cam_positions, focal, taus)
    if active is not None:
        on = active[:, None]
        cold, parent, moved = cold & on, parent & on, moved & on
    stale = cold | moved | parent
    causes = stale_causes(cold, parent, moved)
    if mesh is not None:
        from repro.sharding.fleet import constrain_fleet
        top_cut = constrain_fleet(top_cut, ("clients", None), mesh)
        rpe = constrain_fleet(rpe, ("clients", None), mesh)
        stale = constrain_fleet(stale, ("clients", None), mesh)
    return top_cut, rpe, stale, causes


@functools.partial(jax.jit, static_argnames=())
@tracing.scoped("lod.staleness")
def predicted_stale_counts(tree: LodTree, states: TemporalState,
                           cam_positions: jax.Array, focal, tau,
                           active=None) -> jax.Array:
    """(B,) int32 — how many slab subtrees each client WOULD resweep if it
    were synced right now, without touching any state.

    A pure read-only preview of the staleness predicate of
    `batched_top_and_staleness`: the same top sweep + per-subtree staleness
    test runs, but nothing is scattered back, so calling this between syncs
    is side-effect free. This is the feature the deadline scheduler's
    per-slot sync-cost model consumes (repro.serve.scheduler): predicted
    sweep cost is affine in the stale-pair count, so the scheduler can
    budget a tick's participation set before dispatching the real sync.
    Inactive slots (and slots masked out by `active`) predict zero."""
    _, _, stale = jax.vmap(
        _top_and_staleness, in_axes=(None, 0, 0, None, 0))(
        tree, states, jnp.asarray(cam_positions, jnp.float32), focal,
        jnp.broadcast_to(jnp.asarray(tau, jnp.float32),
                         (jnp.asarray(cam_positions).shape[0],)))
    if active is not None:
        stale = stale & active[:, None]
    return stale.sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=())
def sweep_slab_camera_pairs(slab_mu, slab_size, slab_end, slab_is_leaf,
                            slab_valid, rpe_sel, cam_sel, focal, tau):
    """Sweep K (slab, camera) pairs in one vmapped program.

    Unlike `_sweep_selected` (one shared camera), every pair carries its own
    camera position — and its own τ when `tau` is a (K,) vector (foveated
    fleets pool pairs of clients with different thresholds into the same
    bucket) — the primitive behind the cross-client pooled scheduler, where
    stale slabs of *different* clients share one bucketed dispatch.
    Returns (in_cut (K,S), root_expand (K,), rho (K,))."""
    k = slab_size.shape[0]
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (k,))

    def fn(mu, size, end, leaf, valid, rpe, cam, tau_k):
        return _slab_sweep_one(mu, size, end, leaf, valid, rpe, cam, focal,
                               tau_k)

    return jax.vmap(fn)(slab_mu, slab_size, slab_end, slab_is_leaf,
                        slab_valid, rpe_sel, cam_sel, taus)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _apply_slab_updates(slab_cut, root_expand, rho, cam0, sel, f_cut, f_rexp,
                        f_rho, cam_pos):
    """In-place (donated) state update — avoids re-copying the whole slab
    state every frame in the host-driven loop. `slab_cut` is packed; the
    swept cuts `f_cut` are packed here."""
    return (slab_cut.at[sel].set(bp.pack(f_cut)),
            root_expand.at[sel].set(f_rexp),
            rho.at[sel].set(f_rho),
            cam0.at[sel].set(cam_pos[None, :]))


def temporal_search_hybrid(tree: LodTree, state: TemporalState, cam_pos,
                           focal: float, tau: float
                           ) -> Tuple[CutResult, TemporalState]:
    """Host-driven temporal search: only stale slabs are gathered and swept.

    Shapes are bucketed to powers of two to bound recompilation. Returns the
    same bit-accurate result as `temporal_search`."""
    m = tree.meta
    cam_pos = jnp.asarray(cam_pos, jnp.float32)
    top_cut, rpe, stale = _top_and_staleness(tree, state, cam_pos, focal, tau)
    stale_np = np.asarray(stale)
    idx = np.nonzero(stale_np)[0]
    n_stale = len(idx)

    slab_cut = state.slab_cut0
    root_expand = state.root_expand0
    rho = state.rho
    cam0 = state.cam0

    if n_stale > 0:
        bucket = pow2_bucket(n_stale, m.Ns)
        pad = np.resize(idx, bucket)  # repeat-pad; duplicates are harmless
        sel = jnp.asarray(pad)
        f_cut, f_rexp, f_rho = _sweep_selected(
            tree.slab_mu()[sel], tree.slab_size()[sel], tree.slab_end[sel],
            tree.slab_is_leaf[sel], tree.slab_valid[sel], rpe[sel], cam_pos,
            jnp.float32(focal), jnp.float32(tau))
        slab_cut, root_expand, rho, cam0 = _apply_slab_updates(
            slab_cut, root_expand, rho, cam0, sel, f_cut, f_rexp, f_rho,
            cam_pos)

    new_state = TemporalState(
        cam0=cam0, rho=rho, parent_expand0=rpe, slab_cut0=slab_cut,
        root_expand0=root_expand, swept=jnp.ones((m.Ns,), bool))
    cut = CutResult(
        top_cut=top_cut, slab_cut=bp.unpack(slab_cut, m.S),
        root_expand=root_expand, resweep=stale,
        nodes_touched=jnp.asarray(m.T + n_stale * m.S, jnp.int32))
    return cut, new_state


# ---------------------------------------------------------------------------
# cut extraction
# ---------------------------------------------------------------------------


def cut_gids(cut: CutResult, tree: LodTree, budget: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the cut mask to (budget,) sorted global ids padded with -1.

    Returns (gids, count, overflow)."""
    mask = cut.mask(tree)
    count = mask.sum().astype(jnp.int32)
    (gids,) = jnp.nonzero(mask, size=budget, fill_value=-1)
    return gids.astype(jnp.int32), count, count > budget


# ---------------------------------------------------------------------------
# independent reference oracle (numpy) — ground truth for tests
# ---------------------------------------------------------------------------


def global_parent_np(tree: LodTree) -> np.ndarray:
    """(N_pad,) global parent ids (-1 root, -2 padding)."""
    m = tree.meta
    sp = np.asarray(tree.slab_parent)
    valid = np.asarray(tree.slab_valid)
    base = m.T + np.arange(m.Ns)[:, None] * m.S
    gp_slab = np.where(sp >= 0, base + sp,
                       np.asarray(tree.slab_root_parent_top)[:, None])
    gp_slab = np.where(valid, gp_slab, -2)
    return np.concatenate([np.asarray(tree.top_parent), gp_slab.reshape(-1)])


def global_level_np(tree: LodTree) -> np.ndarray:
    m = tree.meta
    top_level = np.zeros(m.T, np.int32)
    offs = m.top_level_offsets
    for l in range(m.P):
        top_level[offs[l]:offs[l + 1]] = l
    sl = np.asarray(tree.slab_level) + m.P
    sl = np.where(np.asarray(tree.slab_valid), sl, 2**30)
    return np.concatenate([top_level, sl.reshape(-1)])


def reference_search_np(tree: LodTree, cam_pos, focal: float, tau: float
                        ) -> np.ndarray:
    """Brute-force level-iteration over the whole tree. Returns (N_pad,) cut mask."""
    m = tree.meta
    mu = np.asarray(tree.gaussians.mu)
    size = np.asarray(tree.size)
    valid = np.asarray(tree.valid_mask())
    parent = global_parent_np(tree)
    level = global_level_np(tree)
    is_leaf = np.concatenate([np.asarray(tree.top_is_leaf),
                              np.asarray(tree.slab_is_leaf).reshape(-1)])

    d = mu - np.asarray(cam_pos, np.float32)
    dist2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] + d[:, 2] * d[:, 2]
    r = size * np.float32(focal)
    t = np.float32(tau)
    gt = r * r > (t * t) * np.maximum(dist2, np.float32(_EPS_DIST * _EPS_DIST))

    n = mu.shape[0]
    expand = np.zeros(n, bool)
    in_cut = np.zeros(n, bool)
    max_level = m.P + max(m.slab_max_depth, 0)
    for l in range(max_level + 1):
        at = (level == l) & valid
        pe = np.where(parent[at] < 0, l == 0, expand[np.clip(parent[at], 0, None)])
        expand[at] = pe & gt[at]
        in_cut[at] = pe & (~gt[at] | is_leaf[at])
    return in_cut
