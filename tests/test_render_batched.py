"""The render/ subsystem: fleet-batched stereo rendering bit-accuracy, the
pooled Pallas bucket path, merge-overflow surfacing, per-client foveated τ,
and LoD-cut kernel parity with the vmapped service sweep."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np

from repro import render as rnd
from repro.core import lod_search as ls
from repro.core.binning import BinConfig, bin_left
from repro.core.camera import StereoRig, make_camera
from repro.core.gaussians import Gaussians, random_gaussians
from repro.core.pipeline import (SessionConfig, render_stereo,
                                 render_stereo_reference)
from repro.core.projection import depth_ranks, project
from repro.core.stereo import n_categories, stereo_lists
from repro.kernels import ops
from repro.serve import lod_service as svc

FOCAL = 200.0


def _rig_at(pos, target, focal=FOCAL, width=96, height=64, near=0.25):
    cam = make_camera(list(pos), list(target), focal_px=focal, width=width,
                      height=height, near=near)
    return StereoRig(left=cam, baseline=0.06)


def _fleet(b=4, n=200):
    """B distinct rigs (distinct pose, orientation, AND focal — chosen so the
    per-rig n_cat stays shared, the fleet-static requirement) + B queues."""
    queues = [random_gaussians(np.random.default_rng(i), n, sh_degree=1,
                               extent=6.0) for i in range(b)]
    rigs = [_rig_at((3 * i - 4, -16 + i, 2 + 0.3 * i), (i - 2, 2 - i, 0),
                    focal=FOCAL + 5 * i) for i in range(b)]
    return queues, rigs


# -- (a) batched_render_stereo ≡ single-client render_stereo ≡ reference ------


def test_batched_render_bitwise_vs_single_and_reference():
    b = 4
    queues, rigs = _fleet(b=b)
    cfg = rnd.RenderConfig.for_fleet(rigs, tile=16, list_len=128,
                                     max_pairs=1 << 15)
    for r in rigs:  # the fleet premise: one static widening covers everyone
        assert n_categories(r.max_disparity_px(), cfg.tile) == cfg.n_cat

    bl, br, stats = rnd.batched_render_stereo(
        rnd.stack_pytrees(queues), rnd.stack_rigs(rigs), cfg, path="vmap")
    assert not np.asarray(stats.overflow).any()
    for i in range(b):
        # bitwise vs the legacy single-client pipeline surface
        il, ir, (_s, ll, rl, _st) = render_stereo(
            queues[i], rigs[i], tile=16, list_len=128, max_pairs=1 << 15)
        assert not bool(ll.overflow) and not bool(rl.overflow)
        np.testing.assert_array_equal(np.asarray(bl[i]), np.asarray(il))
        np.testing.assert_array_equal(np.asarray(br[i]), np.asarray(ir))
        # and hence vs the fully independent per-eye reference
        ref_l, ref_r = render_stereo_reference(queues[i], rigs[i])
        np.testing.assert_array_equal(np.asarray(bl[i]), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(br[i]), np.asarray(ref_r))


def test_batched_stats_match_single_client():
    b = 3
    queues, rigs = _fleet(b=b, n=150)
    cfg = rnd.RenderConfig.for_fleet(rigs, tile=16, list_len=128,
                                     max_pairs=1 << 15)
    _bl, _br, stats = rnd.batched_render_stereo(
        rnd.stack_pytrees(queues), rnd.stack_rigs(rigs), cfg, path="vmap")
    for i in range(b):
        plan = rnd.build_plan(queues[i], rigs[i], cfg)
        _il, _ir, hits = rnd.render_stereo(plan, cfg)
        st = rnd.frame_stats(plan, hits)
        for name in ("shared_preprocess", "left_blends", "right_candidates",
                     "right_alpha_skipped", "overflow"):
            assert np.asarray(getattr(stats, name))[i] == np.asarray(
                getattr(st, name)), (i, name)


# -- (b) pooled Pallas bucket path --------------------------------------------


def test_pooled_bucket_path_matches_per_client_kernels():
    """Fleet-pooled occupied-tile rasterization must be bitwise equal to
    per-client Pallas dispatches, and allclose (FMA contraction) to the
    vmapped XLA path — with identical work accounting."""
    b = 3
    queues, rigs = _fleet(b=b, n=150)
    cfg = rnd.RenderConfig.for_fleet(rigs, tile=16, list_len=64,
                                     max_pairs=1 << 14)
    qs, rs = rnd.stack_pytrees(queues), rnd.stack_rigs(rigs)
    xl, xr, xstats = rnd.batched_render_stereo(qs, rs, cfg, path="vmap")
    pl_l, pl_r, pstats = rnd.batched_render_stereo(qs, rs, cfg, path="pooled")
    for i in range(b):
        plan = rnd.build_plan(queues[i], rigs[i], cfg)
        il, ir, _hits = rnd.rasterize(plan, cfg, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(pl_l[i]), np.asarray(il))
        np.testing.assert_array_equal(np.asarray(pl_r[i]), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(pl_l), np.asarray(xl),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pl_r), np.asarray(xr),
                               rtol=1e-5, atol=1e-6)
    for a, bb in zip(jax.tree_util.tree_leaves(xstats),
                     jax.tree_util.tree_leaves(pstats)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


# -- (c) merge overflow is surfaced, not silently truncated -------------------


def _epipolar_scene(n=60, list_len=48):
    """Splats along one epipolar line at many depths: their disparities fan
    the LEFT footprints across several tile columns (each left list small),
    but every right-eye footprint lands in the SAME right tile — the k-way
    merge, not the binning, is what overflows."""
    rig = _rig_at((0, 0, 2), (0, 10, 2))
    cam = rig.left
    rng = np.random.default_rng(0)
    disparity = np.linspace(5.0, 43.0, n)     # uniform fan over 3 columns
    depth = rig.baseline * FOCAL / disparity
    x_cam = np.full(n, rig.baseline)          # x_R ≡ cx for every depth
    y_cam = (-0.04 + rng.uniform(-0.005, 0.005, n)) * depth  # one tile row
    mu = (np.asarray(cam.pos)[None]
          + (np.asarray(cam.rot) @ np.stack([x_cam, y_cam, depth])).T)
    g = Gaussians(
        mu=jnp.asarray(mu, jnp.float32),
        log_scale=jnp.full((n, 3), -6.0, jnp.float32),
        quat=jnp.zeros((n, 4), jnp.float32).at[:, 0].set(1.0),
        opacity=jnp.full((n,), 0.9, jnp.float32),
        sh=jnp.asarray(rng.uniform(0.2, 0.8, (n, 1, 3)), jnp.float32))
    tile = 16
    n_cat = n_categories(rig.max_disparity_px(), tile)
    tiles_x_r = -(-cam.width // tile)
    wide = dc.replace(cam, width=(tiles_x_r + n_cat - 1) * tile)
    splats = project(g, rig, wide)
    ranks = depth_ranks(splats)
    cfg = BinConfig(tile=tile, max_pairs=1 << 14, list_len=list_len)
    left = bin_left(splats, wide.width, cam.height, cfg, ranks)
    return rig, splats, ranks, left, cfg, n_cat


def test_merge_overflow_surfaced_by_core_and_kernel():
    rig, splats, ranks, left, cfg, n_cat = _epipolar_scene()
    cam = rig.left
    assert not bool(left.overflow)            # binning is NOT the bottleneck
    merged = stereo_lists(left, splats, ranks, tile=cfg.tile,
                          width=cam.width, n_cat=n_cat)
    assert bool(merged.overflow)              # ...the merge is
    # the kernel surfaces the same flag (previously silent truncation)
    for use_pallas in (True, False):
        mk = ops.stereo_merge(left, splats, ranks, tile=cfg.tile,
                              width=cam.width, n_cat=n_cat,
                              use_pallas=use_pallas)
        assert bool(mk.overflow), use_pallas
        np.testing.assert_array_equal(np.asarray(mk.counts),
                                      np.asarray(merged.counts))


def test_merge_no_overflow_with_capacity():
    rig, splats, ranks, left, cfg, n_cat = _epipolar_scene(list_len=128)
    merged = stereo_lists(left, splats, ranks, tile=cfg.tile,
                          width=rig.left.width, n_cat=n_cat)
    assert not bool(merged.overflow)
    mk = ops.stereo_merge(left, splats, ranks, tile=cfg.tile,
                          width=rig.left.width, n_cat=n_cat, use_pallas=True)
    assert not bool(mk.overflow)
    np.testing.assert_array_equal(np.asarray(mk.lists),
                                  np.asarray(merged.lists))


# -- (d) per-client foveated τ ------------------------------------------------


def test_foveated_tau_fewer_cut_nodes(small_tree):
    """A client with a looser (larger) τ must receive strictly fewer cut
    nodes than a co-located client with a tight τ."""
    cfg = SessionConfig(tau=32.0, cut_budget=8192)
    cams = np.asarray([[30, 30, 2], [30, 30, 2]], np.float32)
    taus = np.asarray([32.0, 96.0], np.float32)
    state = svc.service_init(small_tree, cfg, 2)
    state, stats, _delta = svc.service_sync_vmapped(
        small_tree, cfg, state, cams, FOCAL, bytes_per_g=30.0, taus=taus)
    tight, loose = np.asarray(stats.cut_size)
    assert loose < tight, (tight, loose)


def test_foveated_tau_bitwise_vs_scalar_search(small_tree):
    """Each client of a mixed-τ batch must match the scalar-τ search run at
    its own threshold — for the vmapped AND the pooled scheduler."""
    b = 3
    taus = np.asarray([24.0, 48.0, 96.0], np.float32)
    cams = np.asarray([[30, 30, 2], [34, 31, 2], [28, 36, 2]], np.float32)
    m = small_tree.meta
    states = ls.TemporalState.initial_batched(m.Ns, m.S, b)
    cut, _ = ls.batched_temporal_search(small_tree, states, cams,
                                        jnp.float32(FOCAL), jnp.asarray(taus))
    masks = np.asarray(ls.batched_cut_mask(cut, small_tree))
    for i in range(b):
        ref, _ = ls.full_search(small_tree, cams[i], jnp.float32(FOCAL),
                                jnp.float32(taus[i]))
        assert (masks[i] == np.asarray(ref.mask(small_tree))).all(), i

    cfg = SessionConfig(tau=1.0, cut_budget=8192)  # cfg.tau must be ignored
    rng = np.random.default_rng(0)
    s_pool = svc.service_init(small_tree, cfg, b)
    s_vmap = svc.service_init(small_tree, cfg, b)
    walk = cams.copy()
    for _ in range(4):
        s_pool, _st, _d = svc.service_sync_pooled(
            small_tree, cfg, s_pool, walk, FOCAL, bytes_per_g=30.0, taus=taus)
        s_vmap, _sv, _d2 = svc.service_sync_vmapped(
            small_tree, cfg, s_vmap, walk, FOCAL, bytes_per_g=30.0, taus=taus)
        assert (np.asarray(s_pool.cut_gids)
                == np.asarray(s_vmap.cut_gids)).all()
        walk = walk + rng.normal(0, 6.0, walk.shape).astype(np.float32)


# -- (e) LoD-cut kernel parity with the vmapped service sweep -----------------


def test_lod_cut_kernel_parity_with_vmapped_service_sweep(small_tree):
    """Interpret-mode `kernels.lod_cut` vs the vmapped XLA sweep that
    `lod_service` runs: per client (own camera, own foveated τ), the kernel
    must reproduce the service's fresh slab cuts bit-for-bit."""
    b = 3
    cams = np.asarray([[250, 250, 120], [40, 40, 2], [120, 80, 10]],
                      np.float32)
    taus = np.asarray([48.0, 64.0, 32.0], np.float32)
    m = small_tree.meta
    states = ls.TemporalState.initial_batched(m.Ns, m.S, b)
    # first frame ⇒ every slab freshly swept by the vmapped XLA path
    cut, _ = ls.batched_temporal_search(small_tree, states, cams,
                                        jnp.float32(FOCAL), jnp.asarray(taus))
    _top, rpe, _stale, _causes = ls.batched_top_and_staleness(
        small_tree, states, cams, jnp.float32(FOCAL), jnp.asarray(taus))
    for i in range(b):
        cut_p, rexp_p, _rho = ops.lod_slab_sweep(
            small_tree, jnp.asarray(cams[i]), jnp.float32(FOCAL),
            jnp.float32(taus[i]), rpe[i], use_pallas=True)
        np.testing.assert_array_equal(np.asarray(cut_p),
                                      np.asarray(cut.slab_cut[i]), err_msg=str(i))
        np.testing.assert_array_equal(np.asarray(rexp_p),
                                      np.asarray(cut.root_expand[i]))
    # and the pooled primitive (mixed clients in one dispatch) agrees too
    sel_b = np.repeat(np.arange(b), m.Ns)
    sel_s = np.tile(np.arange(m.Ns), b)
    f_cut, f_rexp, _f_rho = ls.sweep_slab_camera_pairs(
        small_tree.slab_mu()[sel_s], small_tree.slab_size()[sel_s],
        small_tree.slab_end[sel_s], small_tree.slab_is_leaf[sel_s],
        small_tree.slab_valid[sel_s], rpe[sel_b, sel_s],
        jnp.asarray(cams)[sel_b], jnp.float32(FOCAL),
        jnp.asarray(taus)[sel_b])
    np.testing.assert_array_equal(
        np.asarray(f_cut).reshape(b, m.Ns, m.S), np.asarray(cut.slab_cut))


# -- (f) fleet render step in the service -------------------------------------


def test_service_render_step_matches_direct_render(small_tree):
    cfg = SessionConfig(tau=32.0, cut_budget=4096)
    b = 3
    cams = np.asarray([[30, 30, 2], [40, 32, 3], [26, 44, 2]], np.float32)
    service = svc.LodService(small_tree, cfg, b, focal=FOCAL, mode="pooled")
    service.sync(cams)
    rigs = [_rig_at(c, np.asarray(c) + [10, 10, -0.2], width=64, height=48)
            for c in cams]
    il, ir, stats = service.render_fallback(rigs, list_len=128,
                                            max_pairs=1 << 15)
    assert il.shape == (b, 48, 64, 3) and ir.shape == (b, 48, 64, 3)
    rcfg = rnd.RenderConfig.for_fleet(rigs, tile=16, list_len=128,
                                      max_pairs=1 << 15)
    for i in range(b):
        gids = service.client_cut(i)
        queue = small_tree.gaussians.slice_rows(jnp.clip(gids, 0))
        queue = dc.replace(queue, opacity=jnp.where(gids >= 0, queue.opacity,
                                                    0.0))
        plan = rnd.build_plan(queue, rigs[i], rcfg)
        ref_l, ref_r, _ = rnd.render_stereo(plan, rcfg)
        np.testing.assert_array_equal(np.asarray(il[i]), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(ir[i]), np.asarray(ref_r))
    assert (np.asarray(stats.shared_preprocess) > 0).all()


def test_render_fallback_caches_config_and_stack(small_tree):
    """Repeated fleet renders must reuse the cached RenderConfig + stacked
    rig pytree (no per-call for_fleet/stack_rigs rebuild) and still produce
    identical frames; a new rig signature gets its own config."""
    cfg = SessionConfig(tau=32.0, cut_budget=4096)
    b = 2
    cams = np.asarray([[30, 30, 2], [40, 32, 3]], np.float32)
    service = svc.LodService(small_tree, cfg, b, focal=FOCAL, mode="pooled")
    service.sync(cams)
    rigs = [_rig_at(c, np.asarray(c) + [10, 10, -0.2], width=64, height=48)
            for c in cams]
    il0, ir0, _ = service.render_fallback(rigs, list_len=128,
                                          max_pairs=1 << 15)
    assert len(service._rcfg_cache) == 1 and len(service._stack_cache) == 1
    (rcfg0,) = service._rcfg_cache.values()
    (stack0,) = service._stack_cache.values()
    il1, ir1, _ = service.render_fallback(rigs, list_len=128,
                                          max_pairs=1 << 15)
    # same signature: both caches hit (same objects, no growth)
    assert len(service._rcfg_cache) == 1 and len(service._stack_cache) == 1
    assert next(iter(service._rcfg_cache.values())) is rcfg0
    assert next(iter(service._stack_cache.values())) is stack0
    np.testing.assert_array_equal(np.asarray(il0), np.asarray(il1))
    np.testing.assert_array_equal(np.asarray(ir0), np.asarray(ir1))
    # a different static signature (resolution) adds a second entry
    rigs2 = [_rig_at(c, np.asarray(c) + [10, 10, -0.2], width=32, height=32)
             for c in cams]
    service.render_fallback(rigs2, list_len=128, max_pairs=1 << 15)
    assert len(service._rcfg_cache) == 2 and len(service._stack_cache) == 2
