"""Ragged fleet lifecycle: churn conformance + the capacity-bucket
recompile contract.

The load-bearing claims pinned here:

  * CONFORMANCE — after ANY admit/evict/sync schedule, every surviving
    client's cuts, decoded Δ payloads, and per-client accounting are bitwise
    identical to a fresh fixed-size service that replayed only that client's
    camera history (and, with the unicast wire format, the byte accounting
    too — the shared-payload split legitimately depends on who else shares a
    row, so its bitwise replay check runs with dedup off);
  * the three sweep paths (vmapped reference, pooled XLA, pooled Pallas)
    agree bitwise on the whole churn trajectory;
  * INACTIVE SLOTS ARE FREE — zero stats rows (header included), no union
    rows, per-slot state bitwise frozen at the reset value; an
    evicted-then-recycled slot is indistinguishable from a fresh one;
  * RECOMPILE BOUND — a 30-step churn schedule inside one pow2 capacity
    bucket never retraces any jitted sync entry point, and a capacity-bucket
    growth retraces each exactly once.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.core import pipeline as pl
from repro.serve import delta_path as dp
from repro.serve import fleet as flt
from repro.serve import lod_service as svc

FOCAL = 1400.0
TAU = 32.0

GAUSS_FIELDS = ("mu", "log_scale", "quat", "opacity", "sh")


def _rig_at(pos, width=64, height=48):
    from repro.core.camera import StereoRig, make_camera
    cam = make_camera(list(np.asarray(pos, np.float32)),
                      list(np.asarray(pos, np.float32) + [10, 10, -0.2]),
                      focal_px=200.0, width=width, height=height, near=0.25)
    return StereoRig(left=cam, baseline=0.06)


# ---------------------------------------------------------------------------
# schedule machinery
# ---------------------------------------------------------------------------


def _cam(rng):
    c = rng.uniform([5.0, 5.0, 1.5], [55.0, 55.0, 8.0]).astype(np.float32)
    return c


def _gen_schedule(rng, steps, start_clients, max_clients):
    """A randomized admit/evict/sync schedule. Client ids follow the
    service's monotone assignment, so events can name them directly.
    Returns a list of ("admit", cid, cam) | ("evict", cid) |
    ("sync", {cid: cam})."""
    alive = list(range(start_clients))
    next_id = start_clients
    pos = {cid: _cam(rng) for cid in alive}
    events = []
    for _ in range(steps):
        if len(alive) > 1 and rng.random() < 0.3:
            cid = alive[int(rng.integers(len(alive)))]
            alive.remove(cid)
            events.append(("evict", cid))
        if len(alive) < max_clients and rng.random() < 0.5:
            cam = _cam(rng)
            events.append(("admit", next_id, cam))
            pos[next_id] = cam
            alive.append(next_id)
            next_id += 1
        moves = {}
        for cid in alive:
            pos[cid] = (pos[cid] + rng.normal(0, 4.0, 3)).astype(np.float32)
            moves[cid] = pos[cid].copy()
        events.append(("sync", moves))
    return events


def _record(service, stats, cid, payload):
    """One client's view of one sync (everything host-side, copied)."""
    slot = service._slot_of(cid)
    rec = {
        "cut": np.asarray(service.state.cut_gids[slot]).copy(),
        "cut_size": int(stats.cut_size[slot]),
        "delta_size": int(stats.delta_size[slot]),
        "sync_bytes": float(stats.sync_bytes[slot]),
        "resident": int(stats.client_resident[slot]),
        "resweeps": int(stats.resweeps[slot]),
        "nodes": int(stats.nodes_touched[slot]),
    }
    if payload and service.dedup:
        ids, dec = service.client_delta(cid)
        ids = np.asarray(ids)
        sel = ids >= 0
        rec["delta_ids"] = ids[sel].copy()          # ascending by gid
        rec["delta_rows"] = {f: np.asarray(getattr(dec, f))[sel].copy()
                             for f in GAUSS_FIELDS}
    return rec


def _run_churn(mk_service, schedule, payload=True):
    """Drive one service through a schedule. Returns (service,
    {cid: [per-sync records]}, {cid: [per-sync cameras]})."""
    service = mk_service()
    log, hist = {}, {}
    for ev in schedule:
        if ev[0] == "admit":
            cid = service.admit(ev[2])
            assert cid == ev[1]  # ids are monotone and deterministic
            log.setdefault(cid, [])
            hist.setdefault(cid, [])
        elif ev[0] == "evict":
            service.evict(ev[1])
        else:
            stats = service.sync(dict(ev[1]))
            for cid in service.active_ids:
                log.setdefault(cid, []).append(
                    _record(service, stats, cid, payload))
                hist.setdefault(cid, []).append(ev[1][cid])
    return service, log, hist


def _assert_records_equal(a, b, ctx, skip=()):
    assert a.keys() == b.keys(), ctx
    for k in a:
        if k in skip:
            continue
        if k == "delta_rows":
            for f in GAUSS_FIELDS:
                np.testing.assert_array_equal(a[k][f], b[k][f],
                                              err_msg=f"{ctx}:{k}:{f}")
        elif isinstance(a[k], np.ndarray):
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"{ctx}:{k}")
        else:
            assert a[k] == b[k], (ctx, k, a[k], b[k])


def _replay_reference(tree, cfg, hist_cid, dedup, mode="pooled"):
    """A fresh single-client fixed-size service replaying one survivor's
    camera history; returns its per-sync records."""
    ref = svc.LodService(tree, cfg, 1, focal=FOCAL, mode=mode, dedup=dedup)
    out = []
    for cam in hist_cid:
        stats = ref.sync(np.asarray([cam], np.float32))
        out.append(_record(ref, stats, 0, payload=dedup))
    return out


# ---------------------------------------------------------------------------
# (a) churn conformance: survivors == fresh fixed-size replay, on all paths
# ---------------------------------------------------------------------------


def test_churn_conformance_across_paths(small_tree):
    """One randomized schedule (admits, evicts, growth past the capacity
    bucket) driven through all three sweep paths: the paths must agree
    bitwise sync-by-sync, and every surviving client must be bitwise
    indistinguishable from a fresh fixed-size service replaying only its own
    camera history (cuts, decoded Δ payload rows, per-client accounting —
    everything except the shared-payload byte split, which rightly depends
    on who else shares a union row; see the unicast test below)."""
    # seed chosen so the schedule reaches 5 concurrent clients (forcing one
    # capacity growth 4 -> 8), evicts three (recycling slots, including a
    # late admit), and leaves >= 2 survivors
    rng = np.random.default_rng(72)
    schedule = _gen_schedule(rng, steps=7, start_clients=2, max_clients=5)
    cfg = svc.SessionConfig(tau=TAU, cut_budget=8192)
    mk = {
        "pooled": lambda: svc.LodService(small_tree, cfg, 2, focal=FOCAL,
                                         capacity=4, mode="pooled"),
        "vmapped": lambda: svc.LodService(small_tree, cfg, 2, focal=FOCAL,
                                          capacity=4, mode="vmapped"),
        "pallas": lambda: svc.LodService(small_tree, cfg, 2, focal=FOCAL,
                                         capacity=4, mode="pooled",
                                         sweep_impl="pallas"),
    }
    runs = {name: _run_churn(f, schedule) for name, f in mk.items()}

    s_pool, log_pool, hist = runs["pooled"]
    assert s_pool.capacity == 8  # the 5th client forced one bucket growth

    # cross-path bitwise agreement, sync by sync, client by client
    for other in ("vmapped", "pallas"):
        _s, log_o, _h = runs[other]
        assert log_o.keys() == log_pool.keys()
        for cid in log_pool:
            assert len(log_o[cid]) == len(log_pool[cid])
            for k, (a, b) in enumerate(zip(log_pool[cid], log_o[cid])):
                _assert_records_equal(a, b, f"{other}/cid{cid}/sync{k}")
    # the two pooled schedulers share every state leaf bitwise
    s_pal = runs["pallas"][0]
    for a, b in zip(jax.tree_util.tree_leaves(s_pool.state),
                    jax.tree_util.tree_leaves(s_pal.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # conformance vs fresh fixed-size replay, for every survivor
    assert len(s_pool.active_ids) >= 2
    for cid in s_pool.active_ids:
        ref_log = _replay_reference(small_tree, cfg, hist[cid], dedup=True)
        assert len(ref_log) == len(log_pool[cid])
        for k, (got, want) in enumerate(zip(log_pool[cid], ref_log)):
            _assert_records_equal(got, want, f"replay/cid{cid}/sync{k}",
                                  skip=("sync_bytes",))


def test_churn_unicast_byte_accounting_matches_fresh_replay(small_tree):
    """With the unicast wire format, per-client bytes are independent of the
    rest of the fleet — so a survivor's byte accounting must replay bitwise
    too, header and all."""
    rng = np.random.default_rng(7)
    schedule = _gen_schedule(rng, steps=5, start_clients=2, max_clients=4)
    cfg = svc.SessionConfig(tau=TAU, cut_budget=8192)
    s, log, hist = _run_churn(
        lambda: svc.LodService(small_tree, cfg, 2, focal=FOCAL, capacity=4,
                               mode="pooled", dedup=False),
        schedule, payload=False)
    assert s.active_ids
    for cid in s.active_ids:
        ref_log = _replay_reference(small_tree, cfg, hist[cid], dedup=False)
        for k, (got, want) in enumerate(zip(log[cid], ref_log)):
            _assert_records_equal(got, want, f"unicast/cid{cid}/sync{k}")


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**20))
def test_property_churn_conformance(tiny_tree, seed):
    """Property form (hypothesis, or the seeded deterministic fallback):
    random schedules on the tiny tree, pooled path, unicast accounting —
    every survivor replays bitwise (cuts AND bytes)."""
    rng = np.random.default_rng(seed)
    schedule = _gen_schedule(rng, steps=4, start_clients=1, max_clients=4)
    cfg = svc.SessionConfig(tau=24.0, cut_budget=2048)
    s, log, hist = _run_churn(
        lambda: svc.LodService(tiny_tree, cfg, 1, focal=FOCAL, capacity=4,
                               mode="pooled", dedup=False),
        schedule, payload=False)
    for cid in s.active_ids:
        ref = svc.LodService(tiny_tree, cfg, 1, focal=FOCAL, mode="pooled",
                             dedup=False)
        for k, cam in enumerate(hist[cid]):
            stats = ref.sync(np.asarray([cam], np.float32))
            want = _record(ref, stats, 0, payload=False)
            _assert_records_equal(log[cid][k], want,
                                  f"prop/cid{cid}/sync{k}")


# ---------------------------------------------------------------------------
# (b) inactive slots are provably free; recycled slots are fresh
# ---------------------------------------------------------------------------


def _fresh_slot_reference(tree, cfg, capacity):
    return svc.service_init(tree, cfg, 0, capacity=capacity)


def _assert_slot_fresh(state, fresh, slot, ctx=""):
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(
            (state.mgr, state.temporal, state.cut_gids, state.sync_index,
             state.pending)),
            jax.tree_util.tree_leaves(
            (fresh.mgr, fresh.temporal, fresh.cut_gids, fresh.sync_index,
             fresh.pending))):
        np.testing.assert_array_equal(np.asarray(got_leaf[slot]),
                                      np.asarray(want_leaf[slot]),
                                      err_msg=ctx)


def test_inactive_slots_are_provably_free(small_tree):
    """Slots without a client must contribute NOTHING: all-zero stats rows
    (header included), no staleness resweeps, no Δ-union rows, and their
    per-slot state stays bitwise frozen at the reset value while the live
    fleet churns around them."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=8192)
    service = svc.LodService(small_tree, cfg, 3, focal=FOCAL, capacity=8,
                             mode="pooled", dedup=True)
    fresh = _fresh_slot_reference(small_tree, cfg, 8)
    rng = np.random.default_rng(3)
    cams = np.stack([_cam(rng) for _ in range(3)])
    for f in range(4):
        stats = service.sync(cams + rng.normal(0, 3.0, cams.shape
                                               ).astype(np.float32))
        inactive = ~service._active
        assert inactive.sum() == 5
        for name in ("cut_size", "delta_size", "unique_delta", "sync_bytes",
                     "dedup_bytes_saved", "nodes_touched", "resweeps",
                     "client_resident", "overflow", "delta_overflow",
                     "delta_shipped", "delta_deferred", "pages"):
            col = np.asarray(getattr(stats, name))
            assert not col[inactive].any(), (f, name)
        # no union rows on behalf of an inactive slot
        assert not np.asarray(service.last_delta.ref_mask)[inactive].any()
        # device fleet mask agrees with the host mirror
        np.testing.assert_array_equal(
            np.asarray(service.state.fleet.active), service._active)
        for slot in np.flatnonzero(inactive):
            _assert_slot_fresh(service.state, fresh, int(slot),
                               ctx=f"sync{f}/slot{slot}")
    # evict mid-run: the vacated slot is immediately frozen-fresh too
    victim = service.active_ids[1]
    v_slot = service._slot_of(victim)
    service.evict(victim)
    _assert_slot_fresh(service.state, fresh, v_slot, ctx="evicted")
    stats = service.sync()
    assert float(np.asarray(stats.sync_bytes)[v_slot]) == 0.0
    _assert_slot_fresh(service.state, fresh, v_slot, ctx="evicted+sync")


def test_recycled_slot_is_indistinguishable_from_fresh(small_tree):
    """Evict a heavily-used client and admit a new one into the recycled
    slot: the new tenant's first sync must equal a brand-new single-client
    service's first sync at the same camera, bit for bit."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=8192)
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, capacity=2,
                             mode="pooled", dedup=True)
    rng = np.random.default_rng(11)
    cams = np.stack([_cam(rng), _cam(rng)])
    for _ in range(3):
        service.sync(cams)
        cams = cams + rng.normal(0, 5.0, cams.shape).astype(np.float32)
    service.evict(0)
    cam_new = _cam(rng)
    cid = service.admit(cam_new)
    slot = service._slot_of(cid)
    assert slot == 0  # the recycled slot
    assert int(np.asarray(service.state.fleet.generation)[0]) == 2
    # the latest payload belongs to the PREVIOUS tenant of this slot —
    # reading it through the new client must fail, never silently alias
    with pytest.raises(ValueError, match="predates"):
        service.client_delta(cid)
    stats = service.sync({cid: cam_new})
    got = _record(service, stats, cid, payload=True)

    ref = svc.LodService(small_tree, cfg, 1, focal=FOCAL, mode="pooled",
                         dedup=True)
    want = _record(ref, ref.sync(np.asarray([cam_new])), 0, payload=True)
    _assert_records_equal(got, want, "recycled-first-sync",
                          skip=("sync_bytes",))
    assert got["sync_bytes"] > 0  # a cold cut is real traffic


def test_capacity_growth_follows_pow2_buckets(small_tree):
    """Admission beyond the slot array grows it on the shared pow2 policy;
    live clients' cuts survive the growth untouched."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=4096)
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, capacity=2,
                             mode="pooled")
    cams = {0: [30.0, 30.0, 2.0], 1: [40.0, 40.0, 2.0]}
    service.sync(cams)
    pre_cut = {cid: np.asarray(service.client_cut(cid)).copy()
               for cid in (0, 1)}
    assert service.capacity == 2
    service.admit([35.0, 35.0, 2.0])
    assert service.capacity == ls.pow2_bucket(3, flt.MAX_CAPACITY) == 4
    for _ in range(2):
        service.admit([20.0, 20.0, 2.0])
    assert service.capacity == 8 and service.n_clients == 5
    for cid in (0, 1):  # growth must not disturb live state
        np.testing.assert_array_equal(
            np.asarray(service.client_cut(cid)), pre_cut[cid])
    with pytest.raises(KeyError):
        service.evict(99)
    with pytest.raises(ValueError):
        svc.LodService(small_tree, cfg, 4, focal=FOCAL, capacity=2)


# ---------------------------------------------------------------------------
# (c) functional session-core admission/eviction primitives
# ---------------------------------------------------------------------------


def test_session_admit_evict_steps_reset_to_fresh(small_tree):
    """pipeline.admit_step / evict_step: after any amount of session
    history, both return exactly session_init's state (bitwise) — the
    single-client contract the fleet slot reset is built on."""
    cfg = pl.SessionConfig(tau=TAU, w=2, cut_budget=8192)
    codec, bpg = pl.session_wire_format(small_tree, cfg)
    state = pl.session_init(small_tree, cfg)
    pos = np.array([30.0, 30.0, 2.0], np.float32)
    for _ in range(5):
        state, _ = pl.session_step(small_tree, codec, cfg, state, pos,
                                   jnp.float32(FOCAL), bpg)
        pos = pos + 2.0
    assert int(state.sync_index) > 0
    fresh = pl.session_init(small_tree, cfg)
    for step in (pl.evict_step, pl.admit_step):
        got = step(state)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=step.__name__)
    # admit(evict(s)) == evict(s): a recycled slot is a fresh slot
    ev = pl.evict_step(state)
    re = pl.admit_step(ev)
    for a, b in zip(jax.tree_util.tree_leaves(ev),
                    jax.tree_util.tree_leaves(re)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (d) the recompile contract
# ---------------------------------------------------------------------------


def _trace_counts():
    """Compiled-signature counts of every jitted sync entry point on the
    churn path (jax's per-function pjit cache — one entry per static
    signature ever traced)."""
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


def test_recompile_bound_churn_within_and_across_buckets(small_tree):
    """The capacity-bucket recompile contract: after a warmup cycle that
    visits each static signature once, a 30-step admit/evict/sync churn
    schedule INSIDE one pow2 capacity bucket triggers ZERO new traces of any
    jitted sync entry point; the admit that grows the bucket triggers
    exactly ONE new trace of each."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=8192)
    anchor = np.asarray([30.0, 30.0, 2.0], np.float32)
    service = svc.LodService(small_tree, cfg, 5, focal=FOCAL, capacity=8,
                             mode="pooled", dedup=True)
    # warmup: one cycle through every signature the churn loop can hit —
    # all-cold first sync, parked steady sync, cold-admit sync, evict sync
    # (clients park at one anchor so data-dependent pow2 buckets — stale
    # pool, Δ-union width — repeat exactly across the loop)
    service.sync(np.tile(anchor, (5, 1)))
    service.sync()
    warm_cid = service.admit(anchor)
    service.sync()
    service.evict(warm_cid)
    service.sync()
    base = _trace_counts()

    alive = []
    for t in range(30):
        if t % 3 == 0 and service.n_clients < 8:
            alive.append(service.admit(anchor))
        elif t % 3 == 2 and alive:
            service.evict(alive.pop(0))
        service.sync()
    assert service.capacity == 8
    assert _trace_counts() == base  # zero retraces inside the bucket

    # fill the bucket one admit+sync at a time (still warm signatures)...
    while service.n_clients < 8:
        service.admit(anchor)
        service.sync()
    assert _trace_counts() == base
    pre = _trace_counts()
    # ...then the admit that outgrows it: capacity 8 -> 16, and exactly one
    # new trace per entry point on the next churn cycle (one cold sync for
    # the sync-path entries, one evict for the evict step — a second sync
    # would legitimately add the steady-state Δ-width signature too, which
    # is the bounded data-dependent bucketing, not a capacity retrace)
    grow_cid = service.admit(anchor)
    assert service.capacity == 16
    service.sync()
    service.evict(grow_cid)
    post = _trace_counts()
    assert {k: post[k] - pre[k] for k in pre} == {k: 1 for k in pre}


def test_render_fallback_fleet_cache_key(small_tree):
    """The render caches key on the fleet signature: an evict can't serve a
    stale stacked-rig pytree (a wrong-length rig list is rejected, the
    evicted slot renders black, live clients are unchanged), and re-using
    the same rigs after re-admission realigns cleanly."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=4096)
    service = svc.LodService(small_tree, cfg, 3, focal=FOCAL, capacity=4,
                             mode="pooled")
    cams = np.asarray([[30, 30, 2], [40, 32, 3], [26, 44, 2]], np.float32)
    service.sync(cams)
    rigs = [_rig_at(c) for c in cams]
    il0, ir0, _ = service.render_fallback(rigs, list_len=128,
                                          max_pairs=1 << 15)
    assert il0.shape[0] == 4  # slot axis, not client count
    service.evict(1)
    with pytest.raises(ValueError):
        service.render_fallback(rigs, list_len=128, max_pairs=1 << 15)
    il1, ir1, _ = service.render_fallback([rigs[0], rigs[2]], list_len=128,
                                          max_pairs=1 << 15)
    # evicted slot 1 renders black; surviving slots are bitwise unchanged
    assert not np.asarray(il1[1]).any() and not np.asarray(ir1[1]).any()
    for slot in (0, 2):
        np.testing.assert_array_equal(np.asarray(il1[slot]),
                                      np.asarray(il0[slot]))
        np.testing.assert_array_equal(np.asarray(ir1[slot]),
                                      np.asarray(ir0[slot]))
    # distinct fleet signatures live side by side in the caches
    assert len(service._rcfg_cache) == 2
    cid = service.admit(cams[1])
    il2, _, _ = service.render_fallback([rigs[0], _rig_at(cams[1]), rigs[2]],
                                        list_len=128, max_pairs=1 << 15)
    # the re-admitted client hasn't synced yet: empty queue, black frame
    assert not np.asarray(il2[service._slot_of(cid)]).any()


def test_pooled_render_masks_inactive_tiles(small_tree):
    """On the pooled Pallas render path, an inactive slot's tiles never
    reach the kernel even if its (placeholder) rig overlaps the scene —
    fleet rasterization work tracks live clients."""
    cfg = svc.SessionConfig(tau=TAU, cut_budget=2048)
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, capacity=4,
                             mode="pooled")
    cams = np.asarray([[30, 30, 2], [40, 32, 3]], np.float32)
    service.sync(cams)
    rigs = [_rig_at(c) for c in cams]
    il_v, ir_v, _ = service.render_fallback(rigs, list_len=128,
                                            max_pairs=1 << 15, path="vmap")
    il_p, ir_p, _ = service.render_fallback(rigs, list_len=128,
                                            max_pairs=1 << 15, path="pooled")
    assert not np.asarray(il_p[2:]).any() and not np.asarray(ir_p[2:]).any()
    for slot in (0, 1):  # live clients: pooled == vmapped (allclose — FMA)
        np.testing.assert_allclose(np.asarray(il_p[slot]),
                                   np.asarray(il_v[slot]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(ir_p[slot]),
                                   np.asarray(ir_v[slot]), atol=1e-5)
