"""Mesh-sharded fleet service: client-axis × slab-axis partitioning.

The load-bearing claims pinned here:

  * PARITY — on a forced 8-device host-platform CPU mesh (clients×slabs =
    4×2), the sharded service's cuts, per-slot stats (wire bytes included),
    decoded Δ payload rows, and pooled fallback frames are BITWISE identical
    to the single-device service across a randomized admit/evict/sync
    schedule, for both the pooled and the vmapped scheduler (subprocess —
    the parent process must keep seeing the single real device);
  * `ServiceState` leaves carry the declared client-axis NamedSharding
    (`leaf.sharding.spec == PartitionSpec('clients', ...)`), the slab
    tables the slab-axis one;
  * `fleet_totals` reduces per-slot stats identically via the shard_map
    psum path and the plain sum;
  * the ONE divisibility/replicate-fallback rule: `partitioning.axes_for_dim`
    is shared by `logical_to_pspec` AND `context.constrain` (regression-
    pinned by monkeypatch, like the pow2_bucket pin in test_lod_search);
  * capacity SHRINK compacts a sparse fleet into the smaller pow2 bucket
    and survivors replay bitwise vs a never-shrunk service;
  * admission control denies (AdmissionDenied / None) past the configured
    budgets and leaves a denied service untouched;
  * recompile guard mirroring test_fleet_churn.py: with no mesh installed
    the jitted sync entry points never retrace inside a capacity bucket —
    and a MESHED service running in the same process adds its own traces
    without invalidating or growing the meshless ones.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.serve import delta_path as dp
from repro.serve import fleet as flt
from repro.serve import lod_service as svc
from repro.sharding import context as shctx
from repro.sharding import fleet as shf
from repro.sharding import partitioning as shp

FOCAL = 1400.0
TAU = 32.0


def _fake_fleet_mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("clients", "slabs"))


# ---------------------------------------------------------------------------
# (a) the ONE shared divisibility / replicate-fallback rule
# ---------------------------------------------------------------------------


def test_axes_for_dim_semantics():
    rules = {"batch": ("pod", "data"), "heads": ("model",)}
    sizes = {"pod": 2, "data": 3, "model": 4}
    names = set(sizes)
    # full multi-axis product divides -> keep both axes
    assert shp.axes_for_dim("batch", 12, rules, names, sizes) == ("pod",
                                                                  "data")
    # full product (6) does not divide 8 -> the WHOLE dim replicates
    assert shp.axes_for_dim("batch", 8, rules, names, sizes) == ()
    # axes not on the mesh are dropped before the check
    assert shp.axes_for_dim("batch", 9, rules, {"data"}, {"data": 3}) == (
        "data",)
    # unknown sizes (mesh given as bare names): divisibility not enforced
    assert shp.axes_for_dim("batch", 7, rules, names, None) == ("pod", "data")
    # PARTIALLY known sizes: unknowable, keep (the old context.constrain
    # multiplied only the known axes and could drop a divisible split)
    assert shp.axes_for_dim("batch", 8, rules, names, {"data": 3}) == (
        "pod", "data")
    # unknown logical name / None -> replicate
    assert shp.axes_for_dim("nope", 8, rules, names, sizes) == ()
    assert shp.axes_for_dim(None, 8, rules, names, sizes) == ()


def test_constrain_and_pspec_share_the_helper(monkeypatch):
    """Both rule paths route EVERY dimension through axes_for_dim — the
    regression pin that keeps them from drifting apart again."""
    calls = []
    real = shp.axes_for_dim

    def spy(name, dim, rules, mesh_names=None, mesh_sizes=None):
        calls.append(("ctx" if rules.get("__tag__") else "pspec", name, dim))
        return real(name, dim, rules, mesh_names, mesh_sizes)

    monkeypatch.setattr(shp, "axes_for_dim", spy)
    monkeypatch.setattr(shctx, "axes_for_dim", spy)

    mesh = _fake_fleet_mesh()
    assert shp.logical_to_pspec(("clients", None), mesh, (4, 3),
                                shf.fleet_axis_rules(mesh)) == P("clients",
                                                                 None)
    rules = {"batch": ("clients",), "__sizes__": {"clients": 1, "slabs": 1},
             "__tag__": True}
    with mesh, shctx.use_rules(rules):
        shctx.constrain(jnp.zeros((4, 3)), ("batch", None))
    tags = {c[0] for c in calls}
    assert tags == {"pspec", "ctx"}
    # every logical dim went through the helper (None dims included)
    assert ("pspec", "clients", 4) in calls and ("ctx", "batch", 4) in calls


# ---------------------------------------------------------------------------
# (b) the fleet sharding builder (single-device: specs declared, layout no-op)
# ---------------------------------------------------------------------------


def test_fleet_shardings_builder(tiny_tree):
    mesh = _fake_fleet_mesh()
    state = svc.service_init(tiny_tree, svc.SessionConfig(tau=TAU), 4)
    sh = shf.fleet_shardings(mesh, state)
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(state)
    assert sh.sync_index.spec == P("clients")
    assert sh.temporal.slab_cut0.spec == P("clients", None, None)
    assert sh.fleet.next_id.spec == P()           # scalar -> replicated
    tables = ls.SlabTables.from_tree(tiny_tree)
    tsh = shf.slab_shardings(mesh, tables)
    assert tsh.mu.spec == P("slabs", None, None)
    # placement on the 1x1 mesh is a bitwise no-op
    placed = shf.shard_service_state(mesh, state)
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_axis_rules_filters_to_mesh():
    mesh = _fake_fleet_mesh()
    rules = shf.fleet_axis_rules(mesh)
    assert rules["clients"] == ("clients",)
    assert rules["union"] == ("slabs",)
    assert rules["__sizes__"] == {"clients": 1, "slabs": 1}
    # a mesh without the axes: every rule empties (total replicate fallback)
    lone = Mesh(np.array(jax.devices()[:1]), ("data",))
    rules = shf.fleet_axis_rules(lone)
    assert rules["clients"] == () and rules["slabs"] == ()


def test_client_shards_divisibility():
    mesh = _fake_fleet_mesh()
    assert shf.client_shards(mesh, 8) == 1     # size-1 axis -> 1 shard
    assert shf.client_shards(None, 8) == 1


def test_fleet_totals_meshless(tiny_tree):
    cfg = svc.SessionConfig(tau=TAU, cut_budget=2048)
    service = svc.LodService(tiny_tree, cfg, 3, focal=FOCAL)
    stats = service.sync(np.asarray([[5, 5, 2], [9, 7, 2], [20, 15, 3]],
                                    np.float32))
    tot = shf.fleet_totals(stats)
    assert int(tot.cut_size) == int(np.asarray(stats.cut_size).sum())
    assert float(tot.sync_bytes) == pytest.approx(
        float(np.asarray(stats.sync_bytes).sum()))
    assert tot.overflow.dtype == jnp.int32      # bools count


# ---------------------------------------------------------------------------
# (c) capacity SHRINK
# ---------------------------------------------------------------------------


def _mk(tree, n, cap, **kw):
    cfg = svc.SessionConfig(tau=TAU, cut_budget=2048)
    return svc.LodService(tree, cfg, n, focal=FOCAL, capacity=cap,
                          mode="pooled", dedup=True, **kw)


def test_maybe_shrink_compacts_and_survivors_replay_bitwise(tiny_tree):
    rng = np.random.default_rng(3)
    cams = rng.uniform([2, 2, 1], [28, 28, 6], (6, 3)).astype(np.float32)
    a = _mk(tiny_tree, 6, 8)
    b = _mk(tiny_tree, 6, 8)
    for s in (a, b):
        s.sync(cams)
        s.sync({cid: cams[i] + 2.0 for i, cid in enumerate(s.active_ids)})
    for cid in (0, 2, 4, 5):
        a.evict(cid)
        b.evict(cid)
    assert a.maybe_shrink() == 2 and a.capacity == 2
    assert a.maybe_shrink() is None              # already right-sized
    assert a.active_ids == b.active_ids == [1, 3]
    # pre-shrink payload stays addressable (ref-mask rows were remapped)
    ids_a, dec_a = a.client_delta(1)
    ids_b, dec_b = b.client_delta(1)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(dec_a.mu), np.asarray(dec_b.mu))
    # survivors replay bitwise vs the never-shrunk capacity-8 service
    for step in range(3):
        pos = {cid: cams[[1, 3].index(cid)] + 3.0 * (step + 1)
               for cid in (1, 3)}
        sa, sb = a.sync(dict(pos)), b.sync(dict(pos))
        for cid in (1, 3):
            ia, ib = a._slot_of(cid), b._slot_of(cid)
            for f in ("cut_size", "delta_size", "sync_bytes", "unique_delta",
                      "nodes_touched", "resweeps", "client_resident"):
                assert np.asarray(getattr(sa, f))[ia] == \
                    np.asarray(getattr(sb, f))[ib], (cid, f)
            np.testing.assert_array_equal(
                np.asarray(a.state.cut_gids[ia]),
                np.asarray(b.state.cut_gids[ib]), err_msg=f"cut {cid}")
            da, db = a.client_delta(cid), b.client_delta(cid)
            np.testing.assert_array_equal(np.asarray(da[0]),
                                          np.asarray(db[0]))


def test_shrink_gathered_free_slots_are_fresh(tiny_tree):
    service = _mk(tiny_tree, 5, 8)
    service.sync(np.tile(np.asarray([10, 10, 2], np.float32), (5, 1)))
    service.evict(3)
    service.evict(4)
    assert service.maybe_shrink() == 4           # 3 live -> pow2 bucket 4
    fresh = svc.service_init(tiny_tree, service.cfg, 0, capacity=4)
    # slot 3 (gathered from a FREE slot) must be bitwise the reset value
    for got, ref in zip(jax.tree_util.tree_leaves(
            (service.state.mgr, service.state.temporal,
             service.state.cut_gids, service.state.sync_index)),
            jax.tree_util.tree_leaves(
            (fresh.mgr, fresh.temporal, fresh.cut_gids, fresh.sync_index))):
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    assert not bool(service.state.fleet.active[3])
    # the freed slot is admissible again without growth
    cid = service.admit([1, 1, 1])
    assert service.capacity == 4 and service._slot_of(cid) == 3


def test_shrink_after_growth_with_stale_payload(tiny_tree):
    """A capacity growth between the last sync and a shrink must not break
    the payload remap (regression: `_grow` left `_delta_ids` at the old
    capacity — a later shrink indexed past it — and `ref_mask` rows predate
    the growth, so grown slots get an all-False row, never a wrong one)."""
    service = _mk(tiny_tree, 4, 4)
    service.sync(np.tile(np.asarray([10, 10, 2], np.float32), (4, 1)))
    cid = service.admit([11, 11, 2])        # grows 4 -> 8, no sync yet
    for c in (0, 1, 2, 3):
        service.evict(c)
    assert service.maybe_shrink() == 1 and service.active_ids == [cid]
    with pytest.raises(ValueError):         # payload predates cid's admit
        service.client_delta(cid)
    service.sync({cid: np.asarray([11, 11, 2], np.float32)})
    ids, _ = service.client_delta(cid)      # fresh payload addressable
    assert (np.asarray(ids) >= 0).any()


def test_take_slots_and_fleet_shrink_primitives():
    fleet = flt.fleet_init(4, 3)
    fleet = flt.fleet_evict_slot(fleet, 1)
    shrunk = flt.fleet_shrink(fleet, np.asarray([0, 2], np.int32))
    assert np.asarray(shrunk.active).tolist() == [True, True]
    assert np.asarray(shrunk.client_ids).tolist() == [0, 2]
    assert int(shrunk.next_id) == 3              # ids stay monotone
    batched = {"x": jnp.arange(12).reshape(4, 3)}
    out = flt.take_slots(batched, np.asarray([2, 0], np.int32))
    np.testing.assert_array_equal(np.asarray(out["x"]),
                                  [[6, 7, 8], [0, 1, 2]])


# ---------------------------------------------------------------------------
# (d) admission control
# ---------------------------------------------------------------------------


def test_admission_denied_max_clients(tiny_tree):
    service = _mk(tiny_tree, 2, 4, max_clients=2)
    service.sync(np.asarray([[5, 5, 2], [9, 7, 2]], np.float32))
    state_before = service.state
    with pytest.raises(svc.AdmissionDenied):
        service.admit([1, 1, 1])
    assert service.admit([1, 1, 1], required=False) is None
    # a denied admit is side-effect free
    assert service.n_clients == 2 and service.capacity == 4
    assert service.state is state_before
    service.evict(0)
    assert service.admit([1, 1, 1]) == 2         # room again -> admitted


def test_admission_denied_byte_budget(tiny_tree):
    service = _mk(tiny_tree, 2, 2)
    per_slot = service._slot_state_bytes()
    # budget covers the CURRENT 2 slots but not the pow2 growth to 4
    service.max_state_bytes = per_slot * 3
    with pytest.raises(svc.AdmissionDenied):
        service.admit([1, 1, 1])
    assert service.capacity == 2
    # an in-bucket admit (free slot, no growth) is always within budget
    service.evict(0)
    assert service.admit([1, 1, 1]) == 2


# ---------------------------------------------------------------------------
# (e) recompile guard (mirrors test_fleet_churn): meshless traces are
# unchanged by the sharding plumbing AND by a meshed service in-process
# ---------------------------------------------------------------------------


def _trace_counts():
    entries = {
        "top_and_staleness": ls.batched_top_and_staleness,
        "compact_stale_pairs": svc._compact_stale_pairs,
        "pooled_pair_sweep": svc._pooled_pair_sweep,
        "apply_pooled_updates": svc._apply_pooled_updates,
        "batched_cut_gids": svc._batched_cut_gids,
        "batched_cloud_sync": mgr.batched_cloud_sync,
        "union_mask": dp._union_mask,
        "union_refs": dp._union_refs,
        "admit_slot": svc.service_admit_slot,
        "evict_slot": svc.service_evict_slot,
    }
    return {name: fn._cache_size() for name, fn in entries.items()}


def test_meshless_recompile_guard_with_meshed_service_interleaved(tiny_tree):
    anchor = np.asarray([10.0, 10.0, 2.0], np.float32)
    plain = _mk(tiny_tree, 3, 4)
    plain.sync(np.tile(anchor, (3, 1)))
    plain.sync()
    cid = plain.admit(anchor)
    plain.sync()
    plain.evict(cid)
    plain.sync()
    base = _trace_counts()
    # a size-1x1 meshed service in the SAME process: its static mesh arg
    # keys separate cache entries, so it may add traces of its own...
    meshed = _mk(tiny_tree, 3, 4, mesh=_fake_fleet_mesh())
    meshed.sync(np.tile(anchor, (3, 1)))
    meshed.sync()
    with_mesh = _trace_counts()
    # ...but the meshless service keeps running trace-free either way
    for _ in range(6):
        plain.sync()
    cid = plain.admit(anchor)
    plain.sync()
    plain.evict(cid)
    plain.sync()
    assert _trace_counts() == with_mesh
    # and the meshed service's results agree with the meshless one
    np.testing.assert_array_equal(np.asarray(plain.state.fleet.active),
                                  np.asarray(meshed.state.fleet.active))


# ---------------------------------------------------------------------------
# (f) the 8-device parity subprocess (the acceptance contract)
# ---------------------------------------------------------------------------


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import lod_search as ls
from repro.core.camera import StereoRig, make_camera
from repro.core.gaussians import random_gaussians
from repro.core.lod_tree import build_lod_tree
from repro.launch.mesh import make_fleet_mesh
from repro.serve import lod_service as svc
from repro.sharding import fleet as shf

assert len(jax.devices()) == 8
STATS = ("cut_size", "delta_size", "sync_bytes", "unique_delta",
         "dedup_bytes_saved", "nodes_touched", "resweeps",
         "client_resident", "overflow", "delta_overflow",
         "delta_shipped", "delta_deferred", "pages")
GAUSS = ("mu", "log_scale", "quat", "opacity", "sh")

rng = np.random.default_rng(11)
leaves = random_gaussians(rng, 150, sh_degree=1, extent=30.0)
tree = build_lod_tree(leaves, branching=(2, 4), target_subtrees=8, seed=1)
cfg = svc.SessionConfig(tau=32.0, cut_budget=2048)
mesh = make_fleet_mesh(clients=4, slabs=2)

def mk(mode, m):
    return svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8,
                          mode=mode, dedup=True, mesh=m)

def rig_at(pos):
    cam = make_camera(list(np.asarray(pos, np.float32)),
                      list(np.asarray(pos, np.float32) + [10, 10, -0.2]),
                      focal_px=200.0, width=64, height=48, near=0.25)
    return StereoRig(left=cam, baseline=0.06)

def cmp_sync(tag, sb, ss, base, shrd):
    for f in STATS:
        np.testing.assert_array_equal(np.asarray(getattr(sb, f)),
                                      np.asarray(getattr(ss, f)),
                                      err_msg=f"{tag}:{f}")
    np.testing.assert_array_equal(np.asarray(base.state.cut_gids),
                                  np.asarray(shrd.state.cut_gids),
                                  err_msg=f"{tag}:cut_gids")
    for cid in base.active_ids:
        ib, dbv = base.client_delta(cid)
        is_, dsv = shrd.client_delta(cid)
        # a client decodes on one device, whatever the server's mesh
        assert len(dsv.mu.sharding.device_set) == 1, tag
        np.testing.assert_array_equal(np.asarray(ib), np.asarray(is_),
                                      err_msg=f"{tag}:ids:{cid}")
        sel = np.asarray(ib) >= 0
        for f in GAUSS:
            np.testing.assert_array_equal(
                np.asarray(getattr(dbv, f))[sel],
                np.asarray(getattr(dsv, f))[sel],
                err_msg=f"{tag}:rows:{f}:{cid}")

# randomized admit/evict/sync schedule (ids are monotone+deterministic, so
# the same host-side schedule drives every service)
def schedule(steps=7):
    r = np.random.default_rng(5)
    alive, nid = [0, 1, 2, 3], 4
    pos = {c: r.uniform([2, 2, 1], [28, 28, 6]).astype(np.float32)
           for c in alive}
    ev = []
    for t in range(steps):
        if len(alive) > 1 and r.random() < 0.35:
            c = alive.pop(int(r.integers(len(alive))))
            ev.append(("evict", c))
        if len(alive) < 6 and r.random() < 0.5:
            p = r.uniform([2, 2, 1], [28, 28, 6]).astype(np.float32)
            ev.append(("admit", nid, p)); pos[nid] = p
            alive.append(nid); nid += 1
        for c in alive:
            pos[c] = (pos[c] + r.normal(0, 3.0, 3)).astype(np.float32)
        ev.append(("sync", {c: pos[c].copy() for c in alive}))
    return ev

results = {}
for mode in ("pooled", "vmapped"):
    base, shrd = mk(mode, None), mk(mode, mesh)
    n_sync = 0
    for e in schedule():
        if e[0] == "admit":
            assert base.admit(e[2]) == e[1] and shrd.admit(e[2]) == e[1]
        elif e[0] == "evict":
            base.evict(e[1]); shrd.evict(e[1])
        else:
            cmp_sync(f"{mode}:{n_sync}", base.sync(dict(e[1])),
                     shrd.sync(dict(e[1])), base, shrd)
            n_sync += 1
    results[f"{mode}_syncs"] = n_sync

    # the declared client-axis NamedSharding on every slot-axis state leaf
    for leaf in jax.tree_util.tree_leaves(shrd.state):
        spec = leaf.sharding.spec
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == shrd.capacity:
            assert spec[0] == "clients", (leaf.shape, spec)
        else:
            assert spec == P(), (leaf.shape, spec)
    if mode == "pooled":
        assert shrd.tables.mu.sharding.spec[0] == "slabs"

    # fleet_totals: shard_map psum == plain sum, leafwise
    stats_s = shrd.sync()
    stats_b = base.sync()
    tot_p = shf.fleet_totals(stats_s, mesh)
    tot_r = shf.fleet_totals(stats_b, None)
    for a, b in zip(jax.tree_util.tree_leaves(tot_p),
                    jax.tree_util.tree_leaves(tot_r)):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            # per-shard partial sums reassociate float adds (documented)
            np.testing.assert_allclose(a, b, rtol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)

    # pooled fallback frames shard over clients and match bitwise
    rigs = [rig_at(p) for p in
            [base._slot_cams[base._slot_of(c)] for c in base.active_ids]]
    for path in ("vmap", "pooled"):
        il_b, ir_b, _ = base.render_fallback(rigs, list_len=128,
                                             max_pairs=1 << 15, path=path)
        il_s, ir_s, _ = shrd.render_fallback(rigs, list_len=128,
                                             max_pairs=1 << 15, path=path)
        np.testing.assert_array_equal(np.asarray(il_b), np.asarray(il_s),
                                      err_msg=f"{mode}:{path}:L")
        np.testing.assert_array_equal(np.asarray(ir_b), np.asarray(ir_s),
                                      err_msg=f"{mode}:{path}:R")
        assert il_s.sharding.spec[0] == "clients", (path, il_s.sharding)

# Pallas bucket sweep under the mesh: its pair inputs replicate (the
# kernel is opaque to the partitioner) and results stay bitwise
pb = svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8, mode="pooled",
                    sweep_impl="pallas", dedup=True)
ps = svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8, mode="pooled",
                    sweep_impl="pallas", dedup=True, mesh=mesh)
r = np.random.default_rng(9)
pos = r.uniform([2, 2, 1], [28, 28, 6], (4, 3)).astype(np.float32)
for t in range(2):
    cmp_sync(f"pallas:{t}", pb.sync(pos), ps.sync(pos), pb, ps)
    pos = (pos + r.normal(0, 3.0, (4, 3))).astype(np.float32)
results["pallas_ok"] = True

# SHRINK under the mesh: evict down to 2 and compact; survivors bitwise
for cid in list(base.active_ids)[:-2]:
    base.evict(cid); shrd.evict(cid)
assert base.maybe_shrink() == shrd.maybe_shrink() == 2
live = base.active_ids
pos = {c: np.asarray([12.0 + c, 9.0, 2.0], np.float32) for c in live}
cmp_sync("shrunk", base.sync(dict(pos)), shrd.sync(dict(pos)), base, shrd)
for leaf in jax.tree_util.tree_leaves(shrd.state):
    if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == 2:
        assert leaf.sharding.spec[0] in ("clients", None)

# paged Δ-stream under the mesh: a tight budget pages the cold union and
# the carried debt drains to bitwise equality with an un-budgeted fleet
am = svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8, mode="pooled",
                    dedup=True, mesh=mesh)
tp = svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8, mode="pooled",
                    dedup=True, delta_budget=32, page_size=16, mesh=mesh)
pos = np.asarray([[8.0, 8.0, 2.0], [20.0, 9.0, 2.5],
                  [10.0, 22.0, 3.0], [24.0, 24.0, 2.0]], np.float32)
st = tp.sync(pos); am.sync(pos)
assert int(np.asarray(st.delta_deferred).sum()) > 0
# overflow sync: width == budget (32), divisible by both mesh axes, so the
# declared union/clients layouts hold exactly
for leaf in (tp.last_delta.union_gids, tp.last_delta.payload.pos_q):
    assert leaf.sharding.spec[0] == "slabs", leaf.sharding
assert tp.last_delta.ref_mask.sharding.spec == P("clients", "slabs")
n_paged = 1
while np.asarray(tp.state.pending).any() and n_paged < 64:
    tp.sync(pos); am.sync(pos); n_paged += 1
assert not np.asarray(tp.state.pending).any()
np.testing.assert_array_equal(np.asarray(tp.state.mgr.client_has),
                              np.asarray(am.state.mgr.client_has))
results["paged_syncs"] = n_paged

# bounded recompilation with the mesh on: parked re-syncs add no traces
import repro.serve.lod_service as S
def counts():
    fns = (ls.batched_top_and_staleness, S._compact_stale_pairs,
           S._pooled_pair_sweep, S._apply_pooled_updates,
           S._batched_cut_gids)
    return [f._cache_size() for f in fns]
shrd.sync(); shrd.sync()
c0 = counts()
shrd.sync(); shrd.sync(); shrd.sync()
assert counts() == c0, (c0, counts())
results["ok"] = True
print(json.dumps(results))
"""


@pytest.mark.slow
def test_sharded_fleet_parity_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, timeout=900,
                         cwd=".")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert results["ok"] and results["pooled_syncs"] >= 5 \
        and results["vmapped_syncs"] >= 5
