"""Batched multi-client LoD serving: bit-accuracy of the vmapped search, the
cross-client pooled scheduler, and the functional session core."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane as bp
from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.core.camera import StereoRig, make_camera
from repro.core.pipeline import (CollaborativeSession, SessionConfig,
                                 cloud_sync_step, idle_step, session_init,
                                 session_step, session_wire_format)
from repro.serve import delta_path as dp
from repro.serve import lod_service as svc

FOCAL = 1400.0
TAU = 32.0


def _client_walks(rng, b, frames, start=(30.0, 30.0, 2.0), step_sigma=4.0):
    """(frames, B, 3) correlated random walks — one headset per column."""
    starts = np.asarray(start, np.float32) + rng.normal(0, 25.0, (b, 3))
    starts[:, 2] = np.abs(starts[:, 2]) + 1.0
    cams = [starts.astype(np.float32)]
    for _ in range(frames - 1):
        cams.append((cams[-1] + rng.normal(0, step_sigma, (b, 3))
                     ).astype(np.float32))
    return np.stack(cams)


# -- (a) vmapped multi-client search vs per-client search + oracle ------------


def test_batched_search_bit_accurate_vs_per_client(small_tree):
    rng = np.random.default_rng(0)
    b, frames = 4, 10
    walks = _client_walks(rng, b, frames)
    m = small_tree.meta
    states = ls.TemporalState.initial_batched(m.Ns, m.S, b)
    for f in range(frames):
        cut, states = ls.batched_temporal_search(
            small_tree, states, walks[f], jnp.float32(FOCAL), jnp.float32(TAU))
        masks = np.asarray(ls.batched_cut_mask(cut, small_tree))
        for i in range(b):
            full, _ = ls.full_search(small_tree, walks[f, i],
                                     jnp.float32(FOCAL), jnp.float32(TAU))
            assert (masks[i] == np.asarray(full.mask(small_tree))).all(), (f, i)
            ref = ls.reference_search_np(small_tree, walks[f, i], FOCAL, TAU)
            assert (masks[i] == ref).all(), (f, i)


def test_batched_search_clients_are_independent(small_tree):
    """A moving client must not disturb a parked client's reuse state."""
    m = small_tree.meta
    b = 2
    parked = np.array([40.0, 40.0, 2.0], np.float32)
    states = ls.TemporalState.initial_batched(m.Ns, m.S, b)
    cams = np.stack([parked, parked + 5.0])
    cut, states = ls.batched_temporal_search(
        small_tree, states, cams, jnp.float32(FOCAL), jnp.float32(TAU))
    rng = np.random.default_rng(1)
    for _ in range(6):
        cams = np.stack([parked, cams[1] + rng.normal(0, 12.0, 3).astype(np.float32)])
        cut, states = ls.batched_temporal_search(
            small_tree, states, cams, jnp.float32(FOCAL), jnp.float32(TAU))
        resweeps = np.asarray(cut.resweep)
        assert resweeps[0].sum() == 0  # parked client fully reuses its cut


# -- (b) cross-client pooled scheduler ≡ sequential hybrid per client ---------


@pytest.mark.parametrize("b", [1, 3, 5])
def test_pooled_scheduler_matches_sequential_hybrid(small_tree, b):
    rng = np.random.default_rng(2)
    frames = 8
    walks = _client_walks(rng, b, frames)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    state = svc.service_init(small_tree, cfg, b)
    seq_states = [ls.TemporalState.initial(small_tree.meta.Ns,
                                           small_tree.meta.S)
                  for _ in range(b)]
    for f in range(frames):
        state, stats, _delta = svc.service_sync_pooled(
            small_tree, cfg, state, walks[f], FOCAL, bytes_per_g=30.0)
        for i in range(b):
            cut, seq_states[i] = ls.temporal_search_hybrid(
                small_tree, seq_states[i], walks[f, i], FOCAL, TAU)
            mask_seq = np.asarray(cut.mask(small_tree))
            gids = np.asarray(state.cut_gids[i])
            mask_pool = np.zeros(small_tree.n_pad, bool)
            mask_pool[gids[gids >= 0]] = True
            assert (mask_pool == mask_seq).all(), (f, i)
            assert int(stats.resweeps[i]) == int(np.asarray(cut.resweep).sum())
            assert int(stats.nodes_touched[i]) == int(cut.nodes_touched)
        # pooled temporal state must equal the stacked sequential states
        for leaf, name in [(state.temporal.slab_cut0, "slab_cut0"),
                           (state.temporal.rho, "rho"),
                           (state.temporal.cam0, "cam0"),
                           (state.temporal.parent_expand0, "parent_expand0")]:
            stacked = np.stack([np.asarray(getattr(seq_states[i], name))
                                for i in range(b)])
            assert (np.asarray(leaf) == stacked).all(), (f, name)


def test_pooled_matches_vmapped_service(small_tree):
    rng = np.random.default_rng(3)
    b, frames = 4, 6
    walks = _client_walks(rng, b, frames)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    s_pool = svc.service_init(small_tree, cfg, b)
    s_vmap = svc.service_init(small_tree, cfg, b)
    for f in range(frames):
        s_pool, st_p, _dp = svc.service_sync_pooled(
            small_tree, cfg, s_pool, walks[f], FOCAL, bytes_per_g=30.0)
        s_vmap, st_v, _dv = svc.service_sync_vmapped(
            small_tree, cfg, s_vmap, walks[f], FOCAL, bytes_per_g=30.0)
        assert (np.asarray(s_pool.cut_gids) == np.asarray(s_vmap.cut_gids)).all()
        assert (np.asarray(st_p.sync_bytes) == np.asarray(st_v.sync_bytes)).all()
        assert (np.asarray(st_p.delta_size) == np.asarray(st_v.delta_size)).all()
        assert (np.asarray(st_p.client_resident)
                == np.asarray(st_v.client_resident)).all()
        # vmapped path sweeps everything; pooled must never touch more
        assert (np.asarray(st_p.nodes_touched)
                <= np.asarray(st_v.nodes_touched)).all()


def test_service_manager_matches_reference_trace(small_tree):
    """Per-client management tables of the batched service must follow the
    straight-line numpy oracle of the paper's table semantics."""
    rng = np.random.default_rng(4)
    b, frames = 3, 10
    walks = _client_walks(rng, b, frames, step_sigma=6.0)
    cfg = SessionConfig(tau=TAU, w_star=4, cut_budget=8192)
    state = svc.service_init(small_tree, cfg, b)
    masks_per_client = [[] for _ in range(b)]
    stats_log = []
    for f in range(frames):
        state, stats, _delta = svc.service_sync_pooled(
            small_tree, cfg, state, walks[f], FOCAL, bytes_per_g=30.0)
        stats_log.append(stats)
        for i in range(b):
            gids = np.asarray(state.cut_gids[i])
            mask = np.zeros(small_tree.n_pad, bool)
            mask[gids[gids >= 0]] = True
            masks_per_client[i].append(mask)
    for i in range(b):
        deltas, residents = mgr.reference_manager_np(
            np.stack(masks_per_client[i]), w_star=cfg.w_star)
        for f in range(frames):
            assert int(stats_log[f].delta_size[i]) == deltas[f], (f, i)
            assert int(stats_log[f].client_resident[i]) == residents[f], (f, i)


# -- (b2) on-device pooled scheduling + dedup + pallas sweep ------------------


def test_pooled_issues_no_host_nonzero(small_tree, monkeypatch):
    """The pooled scheduler must never pull the staleness mask to the host:
    compaction happens on device (the old path called np.nonzero on it)."""
    rng = np.random.default_rng(6)
    b = 3
    walks = _client_walks(rng, b, 5)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    state = svc.service_init(small_tree, cfg, b)

    real_nonzero = np.nonzero

    def _guarded(a, *rest, **k):
        # jax's tracer calls np.nonzero on small python lists internally;
        # only a bool ARRAY argument can be the staleness mask
        if getattr(a, "dtype", None) == np.bool_ and getattr(a, "ndim", 0):
            raise AssertionError("host np.nonzero on the pooled sync path")
        return real_nonzero(a, *rest, **k)

    monkeypatch.setattr(svc.np, "nonzero", _guarded)
    for f in range(5):
        state, stats, _delta = svc.service_sync_pooled(
            small_tree, cfg, state, walks[f], FOCAL, bytes_per_g=30.0)
    assert int(np.asarray(stats.cut_size).sum()) > 0


def test_pooled_dedup_matches_vmapped_dedup(small_tree):
    """With the encode-once tail on, pooled and vmapped schedulers must agree
    on the ENTIRE wire product: union gids, per-client references, encoded
    payload, and the shared-payload byte accounting."""
    rng = np.random.default_rng(7)
    b, frames = 4, 6
    walks = _client_walks(rng, b, frames)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    codec, bpg = session_wire_format(small_tree, cfg)
    budget = small_tree.n_pad
    s_pool = svc.service_init(small_tree, cfg, b)
    s_vmap = svc.service_init(small_tree, cfg, b)
    for f in range(frames):
        s_pool, st_p, d_p = svc.service_sync_pooled(
            small_tree, cfg, s_pool, walks[f], FOCAL, bytes_per_g=bpg,
            codec=codec, dedup=True, delta_budget=budget)
        s_vmap, st_v, d_v = svc.service_sync_vmapped(
            small_tree, cfg, s_vmap, walks[f], FOCAL, bytes_per_g=bpg,
            codec=codec, dedup=True, delta_budget=budget)
        assert (np.asarray(s_pool.cut_gids) == np.asarray(s_vmap.cut_gids)).all()
        assert int(d_p.n_union) == int(d_v.n_union)
        np.testing.assert_array_equal(np.asarray(d_p.union_gids),
                                      np.asarray(d_v.union_gids))
        np.testing.assert_array_equal(np.asarray(d_p.ref_mask),
                                      np.asarray(d_v.ref_mask))
        np.testing.assert_array_equal(np.asarray(d_p.payload.pos_q),
                                      np.asarray(d_v.payload.pos_q))
        np.testing.assert_array_equal(np.asarray(st_p.sync_bytes),
                                      np.asarray(st_v.sync_bytes))
        np.testing.assert_array_equal(np.asarray(st_p.unique_delta),
                                      np.asarray(st_v.unique_delta))
        np.testing.assert_array_equal(np.asarray(st_p.dedup_bytes_saved),
                                      np.asarray(st_v.dedup_bytes_saved))
        # union partition: first-owner counts sum to the union size
        assert int(np.asarray(st_p.unique_delta).sum()) == int(d_p.n_union)


def test_pallas_sweep_impl_bit_parity(small_tree):
    """LodService(sweep_impl="pallas") — the Pallas lod-cut pair kernel wired
    into the pooled bucket sweep — must be bit-identical to the XLA sweep
    AND to the always-sweep vmapped reference, sync after sync (foveated τ
    included)."""
    rng = np.random.default_rng(8)
    b, frames = 3, 6
    walks = _client_walks(rng, b, frames)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    taus = np.asarray([24.0, 48.0, 96.0], np.float32)
    mk = lambda **kw: svc.LodService(small_tree, cfg, b, focal=FOCAL,
                                     taus=taus, **kw)
    s_pal = mk(mode="pooled", sweep_impl="pallas")
    s_xla = mk(mode="pooled", sweep_impl="xla")
    s_ref = mk(mode="vmapped")
    for f in range(frames):
        s_pal.sync(walks[f]); s_xla.sync(walks[f]); s_ref.sync(walks[f])
        np.testing.assert_array_equal(np.asarray(s_pal.state.cut_gids),
                                      np.asarray(s_xla.state.cut_gids),
                                      err_msg=str(f))
        np.testing.assert_array_equal(np.asarray(s_pal.state.cut_gids),
                                      np.asarray(s_ref.state.cut_gids),
                                      err_msg=str(f))
        for name in ("slab_cut0", "rho", "cam0", "root_expand0"):
            np.testing.assert_array_equal(
                np.asarray(getattr(s_pal.state.temporal, name)),
                np.asarray(getattr(s_xla.state.temporal, name)),
                err_msg=f"{f} {name}")
    with pytest.raises(ValueError):
        mk(mode="vmapped", sweep_impl="pallas")


# the event JAX reports for every executable it builds, which the benchmark
# counts as `compiles_in_window`
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def test_pooled_ticks_at_a_known_bucket_compile_nothing(small_tree):
    """Once a pooled service has met a pair bucket and a Δ-union width, two
    more ticks at the same bucket and width build no executable: the pair
    sweep (and every other program of the tick) compiles once per pow2
    bucket, never inside a steady window."""
    cfg = SessionConfig(tau=TAU, cut_budget=4096)
    service = svc.LodService(small_tree, cfg, 3, focal=FOCAL, mode="pooled")
    here = np.asarray([[30, 30, 2], [40, 32, 3], [26, 44, 2]], np.float32)
    there = here + np.float32([14.0, -10.0, 1.0])

    def tick(cams):
        service.sync(cams)
        return (service.last_account["lanes"],
                service.last_delta.union_gids.shape[0])

    warm = [tick(c) for c in (here, there, here, there)]
    built = []

    def listen(event, duration, fun_name="", **_):
        if event == COMPILE_EVENT:
            built.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        steady = [tick(c) for c in (here, there)]
        jax.block_until_ready(service.state)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert steady == warm[2:], "the ticks met a new bucket or union width"
    assert all(lanes > 0 for lanes, _ in steady), "no pair was swept"
    assert built == []


def test_draining_union_reuses_built_widths(small_tree):
    """A coarse first sync builds a narrow Δ stream, a street-level sync a
    wide one; small moves then drain the union below the narrow width. The
    service goes back to the narrowest stream it has built that holds the
    union, so no narrower width compiles and the wide one is not kept, and
    it serves bitwise what a twin that sizes each stream to its own union
    serves (cuts, stats, every client's ids)."""
    cfg = SessionConfig(tau=TAU, cut_budget=4096)
    # a short focal length keeps the cut coarse, so each 6 m walk brings
    # new Δ rows (at FOCAL the first sync ships every leaf)
    service = svc.LodService(small_tree, cfg, 3, focal=300.0, mode="pooled")
    twin = svc.LodService(small_tree, cfg, 3, focal=300.0, mode="pooled")
    here = np.asarray([[30, 30, 2], [40, 32, 3], [26, 44, 2]], np.float32)
    high = here.copy()
    high[:, 2] = 160.0
    walk = [high] + [here + np.float32([6.0, 0.0, 0.0]) * k
                     for k in range(4)]
    widths, unions, refs_built = [], [], []
    for cams in walk:
        twin._union_widths.clear()      # the twin never keeps a width
        before = dp._union_refs._cache_size()
        st_s = service.sync(cams)
        refs_built.append(dp._union_refs._cache_size() - before)
        st_t = twin.sync(cams)
        widths.append(service.last_delta.union_gids.shape[0])
        unions.append(int(service.last_delta.n_union))
        np.testing.assert_array_equal(np.asarray(service.state.cut_gids),
                                      np.asarray(twin.state.cut_gids))
        for a, b in zip(jax.tree_util.tree_leaves(st_s),
                        jax.tree_util.tree_leaves(st_t)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        u = twin.last_delta.union_gids.shape[0]
        for cid in service.active_ids:
            ids_s = np.asarray(service.client_delta(cid)[0])
            np.testing.assert_array_equal(
                ids_s[:u], np.asarray(twin.client_delta(cid)[0]))
            assert (ids_s[u:] == -1).all()
    narrow, wide = widths[0], widths[1]
    assert narrow < wide, "the street-level sync built no wider stream"
    assert min(unions[2:]) > 0, "a walk brought no Δ row"
    assert all(ls.pow2_bucket(u, service.delta_budget) < narrow
               for u in unions[2:]), "the union never drained"
    assert widths[2:] == [narrow] * 3
    assert refs_built[2:] == [0] * 3, "a drained sync built a width"


def test_service_dedup_client_payload_roundtrip(small_tree):
    """End-to-end service check: each client's decode of the shared stream
    carries exactly its Δcut rows of this sync."""
    rng = np.random.default_rng(9)
    b = 3
    walks = _client_walks(rng, b, 3)
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    service = svc.LodService(small_tree, cfg, b, focal=FOCAL, dedup=True)
    n = small_tree.n_pad
    prev_has = np.asarray(bp.unpack(service.state.mgr.client_has, n))
    for f in range(3):
        stats = service.sync(walks[f])
        for i in range(b):
            ids, _dec = service.client_delta(i)
            got = np.sort(np.asarray(ids)[np.asarray(ids) >= 0])
            gids = np.asarray(service.state.cut_gids[i])
            cut = np.zeros(small_tree.n_pad, bool)
            cut[gids[gids >= 0]] = True
            want = np.where(cut & ~prev_has[i])[0]
            np.testing.assert_array_equal(got, want, err_msg=f"{f}/{i}")
            assert int(stats.delta_size[i]) == len(want)
        prev_has = np.asarray(bp.unpack(service.state.mgr.client_has, n))


# -- (c) functional session core ≡ legacy CollaborativeSession ----------------


def _rig_at(pos, focal_px=200.0):
    cam = make_camera(pos, np.asarray(pos) + [10, 10, -0.2],
                      focal_px=focal_px, width=64, height=48, near=0.2)
    return StereoRig(left=cam, baseline=0.06)


def test_functional_step_matches_legacy_session(small_tree):
    rng = np.random.default_rng(5)
    cfg = SessionConfig(tau=TAU, w=3, w_star=8, cut_budget=8192)
    rig0 = _rig_at([30.0, 30.0, 2.0])
    sess = CollaborativeSession(small_tree, cfg, rig0)
    codec, bytes_per_g = session_wire_format(small_tree, cfg)
    state = session_init(small_tree, cfg)

    pos = np.array([30.0, 30.0, 2.0], np.float32)
    focal = jnp.float32(rig0.left.focal)
    for f in range(12):
        rig = _rig_at(pos)
        legacy_stats, _ = sess.step(rig, render=False)
        state, st = session_step(small_tree, codec, cfg, state, pos, focal,
                                 bytes_per_g)
        assert bool(st.synced) == legacy_stats.synced, f
        assert int(st.cut_size) == legacy_stats.cut_size, f
        assert int(st.delta_size) == legacy_stats.delta_size, f
        assert float(st.sync_bytes) == legacy_stats.sync_bytes, f
        assert int(st.resweeps) == legacy_stats.resweeps, f
        assert int(st.nodes_touched) == legacy_stats.nodes_touched, f
        assert int(st.client_resident) == legacy_stats.client_resident, f
        assert (np.asarray(state.cut_gids)
                == np.asarray(sess.state.cut_gids)).all(), f
        pos = pos + rng.normal(0, 2.0, 3).astype(np.float32)


def test_functional_sync_cadence(small_tree):
    """cloud_sync_step/idle_step compose into the w-frame cadence and keep
    the client holding its full render queue."""
    cfg = SessionConfig(tau=TAU, w=4, cut_budget=8192)
    codec, bytes_per_g = session_wire_format(small_tree, cfg)
    state = session_init(small_tree, cfg)
    pos = np.array([40.0, 40.0, 2.0], np.float32)
    for f in range(9):
        if f % cfg.w == 0:
            state, st = cloud_sync_step(small_tree, codec, cfg, state, pos,
                                        jnp.float32(FOCAL), bytes_per_g)
            assert bool(st.synced)
        else:
            state, st = idle_step(state)
            assert not bool(st.synced)
            assert float(st.sync_bytes) == mgr.POSE_UPLINK_BYTES
        gids = np.asarray(state.cut_gids)
        has = np.asarray(state.client.has)
        assert has[gids[gids >= 0]].all(), f
        pos = pos + 1.0
    assert int(state.frame_index) == 9
    assert int(state.sync_index) == 3
