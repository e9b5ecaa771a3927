"""Fleet-service sharding: client-axis × slab-axis partitioning of the
cloud LoD sync path (ROADMAP "shard ServiceState + tree on the cloud mesh").

The serving mesh has two logical axes:

  clients — shards every per-slot leaf of the service on its leading SLOT
            axis (`ServiceState` with its bit planes, (C, W) and (C, Ns,
            S/32) uint32 / `FleetState` / `ServiceStats` / per-client cut
            queues / fallback frames); a plane's word axis is never split,
            so a slot's words stay on one device. A host owns a
            contiguous block of slots: its staleness pool, management
            tables, Δ ref-mask rows, and wire accounting all live where its
            clients live.
  slabs   — shards the SHARED tree's slab attribute tables
            (`lod_search.SlabTables`, leading Ns axis) and the row axis of
            the encode-once union codec work, so one city's attribute
            tables need not fit a single accelerator's HBM.

Logical names are mapped to mesh axes by `fleet_axis_rules` (the default
mesh simply names its axes "clients"/"slabs" — `launch.make_fleet_mesh`),
through the SAME `partitioning.axes_for_dim` divisibility rule as the
weight/activation paths: an axis whose size does not divide the dimension
falls back to REPLICATED, never a partial split — so on a single device (or
any indivisible layout) every constraint is a no-op and the service is
bitwise the unsharded one.

The mesh is ambient (`use_fleet_mesh` / `current_fleet_mesh`):
`LodService(mesh=...)` installs it once and the functional sync paths pick
it up; plumbing-free callers can wrap any functional call themselves. The
jitted service kernels take the mesh as a STATIC argument (a `Mesh` is
hashable), so a meshed and an unmeshed service in one process can never
collide on a traced signature — the no-mesh traces stay byte-identical to
the pre-mesh code.

Cross-shard semantics worth knowing (tested in
tests/test_sharding_fleet.py):

  * the Δ-union `any` over clients is a cross-shard reduction; the union
    mask — and therefore the encode-once payload — comes back REPLICATED
    across client shards (the "replicated-union fallback": every host holds
    the full multicast stream, which is exactly the wire model — the stream
    is broadcast to everyone anyway);
  * `fleet_totals` reduces per-slot `ServiceStats` columns to fleet scalars
    with a `psum` over the clients axis (`shard_map`) when the mesh makes
    that meaningful, and a plain sum otherwise — int/bool totals are
    bit-identical either way; float columns may differ in the last ulp
    (per-shard partial sums reassociate the additions).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.partitioning import logical_to_pspec

# logical → mesh axes for the serving stack (remappable: a launcher that
# wants clients over an existing "data" axis passes its own rules)
FLEET_RULES: Dict[str, Tuple[str, ...]] = {
    "clients": ("clients",),   # leading slot axis of per-client state
    "slabs": ("slabs",),       # Ns axis of the shared slab tables
    "union": ("slabs",),       # row axis of the encode-once codec work
}


def fleet_axis_rules(mesh: Mesh,
                     rules: Optional[Dict[str, Tuple[str, ...]]] = None
                     ) -> Dict[str, Tuple[str, ...]]:
    """`FLEET_RULES` filtered to `mesh`'s axes, with `__sizes__` attached
    (the form `context.constrain`-style helpers consume)."""
    base = dict(FLEET_RULES if rules is None else rules)
    names = set(mesh.axis_names)
    out = {k: tuple(a for a in v if a in names)
           for k, v in base.items() if k != "__sizes__"}
    out["__sizes__"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    return out


# -- ambient mesh -----------------------------------------------------------

_FLEET_MESH: contextvars.ContextVar[Optional[Mesh]] = (
    contextvars.ContextVar("fleet_mesh", default=None))


def current_fleet_mesh() -> Optional[Mesh]:
    return _FLEET_MESH.get()


@contextlib.contextmanager
def use_fleet_mesh(mesh: Optional[Mesh]):
    """Install `mesh` as the ambient serving mesh: functional sync calls
    (`service_sync_vmapped` / `service_sync_pooled` / `service_render_step`)
    that are not given an explicit mesh pick it up here. `LodService`
    captures it at construction, so a long-lived service needs no `with`."""
    token = _FLEET_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _FLEET_MESH.reset(token)


def resolve_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Explicit mesh if given, else the ambient one (else None)."""
    return mesh if mesh is not None else _FLEET_MESH.get()


def mesh_signature(mesh: Optional[Mesh]):
    """JSON-able identity of a serving mesh — [[axis, size], ...] in axis
    order, or None for the meshless service. Recorded in snapshot manifests
    (repro.serve.recovery) so a restore can report the layout the state was
    SAVED under; restore itself is mesh-free (reshard-on-load device_puts
    every leaf under whatever target mesh the caller brings)."""
    if mesh is None:
        return None
    return [[str(a), int(s)]
            for a, s in zip(mesh.axis_names, mesh.devices.shape)]


def client_shards(mesh: Optional[Mesh], capacity: int) -> int:
    """How many client shards the slot axis actually splits into: the mesh's
    `clients` size when it divides `capacity`, else 1 (the replicate
    fallback — same divisibility rule as every constraint here)."""
    if mesh is None or "clients" not in mesh.axis_names:
        return 1
    k = int(mesh.shape["clients"])
    return k if k > 0 and capacity % k == 0 else 1


# -- constraints & placement ------------------------------------------------


def fleet_pspec(mesh: Mesh, logical: Tuple[Optional[str], ...],
                shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one leaf under the fleet rules (shape-checked —
    indivisible dims replicate; the same `logical_to_pspec` every other
    rule table goes through)."""
    return logical_to_pspec(logical, mesh, tuple(shape),
                            fleet_axis_rules(mesh))


def constrain_fleet(x: jax.Array, logical: Tuple[Optional[str], ...],
                    mesh: Optional[Mesh]) -> jax.Array:
    """`with_sharding_constraint` under the fleet rules; no-op when no mesh.
    Usable inside jit (the service kernels pass their static mesh arg)."""
    if mesh is None:
        return x
    spec = fleet_pspec(mesh, logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_participation(mesh: Optional[Mesh], mask) -> jax.Array:
    """Place a per-tick (C,) participation mask (the deadline scheduler's
    selected-slot set, `LodService.sync(participate=...)`) on the `clients`
    axis, like every other per-slot leaf: each client shard holds its own
    slots' bits, so the partial-sync masking (`active & participate`) stays
    shard-local and no mask ever crosses shards. No-op without a mesh."""
    mask = jnp.asarray(mask, bool)
    if mesh is None:
        return mask
    return jax.device_put(
        mask, NamedSharding(mesh, fleet_pspec(mesh, ("clients",),
                                              mask.shape)))


def _leading_axis_shardings(mesh: Mesh, tree: Any, axis_name: str):
    def one(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape:
            return NamedSharding(mesh, P())
        logical = (axis_name,) + (None,) * (len(shape) - 1)
        return NamedSharding(mesh, logical_to_pspec(
            logical, mesh, shape, fleet_axis_rules(mesh)))
    return jax.tree_util.tree_map(one, tree)


def fleet_shardings(mesh: Mesh, state: Any):
    """Tree of NamedShardings for any per-client pytree whose array leaves
    lead with the slot axis (`ServiceState`, `FleetState`, `ServiceStats`,
    stacked rigs, ...). Scalars replicate; an indivisible slot axis
    replicates (so a CPU/single-device run is a bitwise no-op) — the
    `partitioning.logical_to_pspec` fallback, not a second rule."""
    return _leading_axis_shardings(mesh, state, "clients")


def slab_shardings(mesh: Mesh, tables: Any):
    """NamedShardings for the shared tree's slab-axis pytrees
    (`lod_search.SlabTables`: every leaf leads with Ns)."""
    return _leading_axis_shardings(mesh, tables, "slabs")


def shard_service_state(mesh: Optional[Mesh], state: Any):
    """Pin `state`'s leaves to their fleet NamedShardings (device_put; the
    sync paths call this on every returned state so
    `state.leaf.sharding.spec` is always the declared layout, independent of
    what GSPMD propagation chose for the final jit output)."""
    if mesh is None:
        return state
    return jax.device_put(state, fleet_shardings(mesh, state))


def shard_slab_tables(mesh: Optional[Mesh], tables: Any):
    """Pin the shared slab attribute tables on the `slabs` axis."""
    if mesh is None:
        return tables
    return jax.device_put(tables, slab_shardings(mesh, tables))


def replicate_fleet(mesh: Optional[Mesh], tree: Any):
    """Replicate a pytree on every device of the fleet mesh — the opaque-
    kernel fallback: a Pallas dispatch the SPMD partitioner cannot split
    (the pooled lod-cut pair sweep, the pooled tile rasterizer) gets
    explicitly replicated inputs instead of shard-local garbage. Works
    inside jit (a constraint) and eagerly (device_put semantics); no-op
    without a mesh."""
    if mesh is None:
        return tree
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, rep), tree)


# -- cross-host reductions --------------------------------------------------


def fleet_totals(stats: Any, mesh: Optional[Mesh] = None):
    """Reduce per-slot stats columns ((C,) leaves) to fleet totals.

    With a mesh whose `clients` axis divides C, the reduction runs as a
    `shard_map` whose cross-shard half is an explicit `jax.lax.psum` over
    the clients axis — each host sums its own slots locally and one
    all-reduce combines them (the cross-host staleness-pool accounting).
    Otherwise it is a plain sum. Bool columns count (int32). Int/bool
    totals are bit-identical between the two paths (integer addition is
    associative); float columns (`sync_bytes`, `dedup_bytes_saved`) may
    differ in the last ulp once totals leave float32's exact-integer range
    — per-shard partial sums reassociate the additions."""
    mesh = resolve_mesh(mesh)

    def local(s):
        return jax.tree_util.tree_map(
            lambda a: (a.astype(jnp.int32) if a.dtype == jnp.bool_
                       else a).sum(axis=0), s)

    leaves = jax.tree_util.tree_leaves(stats)
    cap = leaves[0].shape[0] if leaves else 0
    k = client_shards(mesh, int(cap))
    if k <= 1:
        return local(stats)
    def shardwise(s):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, "clients"), local(s))

    in_specs = jax.tree_util.tree_map(
        lambda a: P(*(("clients",) + (None,) * (a.ndim - 1))), stats)
    out_specs = jax.tree_util.tree_map(lambda a: P(), stats)
    return jax.shard_map(shardwise, mesh=mesh, in_specs=(in_specs,),
                         out_specs=out_specs, check_vma=False)(stats)


def shard_resident_bytes(mesh: Optional[Mesh], *trees: Any) -> int:
    """Max per-shard resident bytes of the given pytrees under their fleet
    placement (analytic: each leaf's nbytes divided by the product of its
    spec's mesh axis sizes). With no mesh: the plain total."""
    total = 0.0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            nbytes = int(np.prod(leaf.shape, initial=1)
                         * jnp.dtype(leaf.dtype).itemsize)
            div = 1
            sharding = getattr(leaf, "sharding", None)
            if mesh is not None and sharding is not None \
                    and getattr(sharding, "spec", None) is not None:
                for entry in sharding.spec:
                    for ax in ((entry,) if isinstance(entry, str)
                               else (entry or ())):
                        div *= int(mesh.shape[ax])
            total += nbytes / div
    return int(total)
