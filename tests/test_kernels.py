"""Per-kernel interpret-mode validation vs pure-jnp oracles.

Each kernel is swept over shapes/dtypes per the deliverable: Pallas
(interpret=True on CPU) must allclose (mostly bit-equal) the ref.py oracle."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lod_search as ls
from repro.core.binning import BinConfig, bin_left
from repro.core.camera import StereoRig, make_camera
from repro.core.compression import vq_assign_ref
from repro.core.gaussians import Gaussians, random_gaussians
from repro.core.lod_tree import LodTree, TreeMeta, subtree_end
from repro.core.projection import depth_ranks, project
from repro.core.raster import render_tiles
from repro.core.stereo import n_categories, stereo_lists
from repro.kernels import ops, ref as kref, resolve_interpret
from repro.kernels.lod_cut import lod_pair_sweep_pallas
from repro.kernels.preprocess import preprocess_pallas
from repro.kernels.stereo_shift import stereo_merge_pallas


def _scene(n=300, seed=0, width=96, height=64, focal=200.0):
    rng = np.random.default_rng(seed)
    g = random_gaussians(rng, n, sh_degree=1, extent=5.0)
    cam = make_camera([0, -15, 2], [0, 0, 0], focal_px=focal,
                      width=width, height=height, near=0.25)
    rig = StereoRig(left=cam, baseline=0.06)
    tile = 16
    n_cat = n_categories(rig.max_disparity_px(), tile)
    tiles_x_r = -(-cam.width // tile)
    wide = dataclasses.replace(cam, width=(tiles_x_r + n_cat - 1) * tile)
    splats = project(g, rig, wide)
    ranks = depth_ranks(splats)
    cfg = BinConfig(tile=tile, max_pairs=1 << 14, list_len=64)
    lists = bin_left(splats, wide.width, cam.height, cfg, ranks)
    return g, rig, wide, splats, ranks, lists, cfg


# -- rasterize ---------------------------------------------------------------


@pytest.mark.parametrize("n,seed", [(100, 0), (300, 1), (800, 2)])
def test_rasterize_kernel_vs_oracle(n, seed):
    _g, _rig, wide, splats, ranks, lists, cfg = _scene(n=n, seed=seed)
    entries, counts = ops.gather_entries(lists, splats, "left")
    from repro.kernels.rasterize import rasterize_tiles_pallas
    img_p, hit_p = rasterize_tiles_pallas(entries, counts, tile=cfg.tile,
                                          tiles_x=lists.tiles_x, eps_t=0.0)
    img_r, hit_r = kref.ref_rasterize(entries, counts, tile=cfg.tile,
                                      tiles_x=lists.tiles_x, eps_t=0.0)
    np.testing.assert_array_equal(np.asarray(img_p), np.asarray(img_r))
    np.testing.assert_array_equal(np.asarray(hit_p), np.asarray(hit_r))


def test_rasterize_kernel_matches_core_renderer():
    """Cross-compilation comparison: same math, different program structure —
    XLA CPU FMA contraction differs, so allclose (≤ few ulp), not bitwise.
    (Bitwise equality is asserted kernel-vs-oracle above, where the program
    structure is identical.)"""
    _g, rig, wide, splats, ranks, lists, cfg = _scene()
    cam = rig.left
    img_core, hits_core = render_tiles(lists, splats, width=cam.width,
                                       height=cam.height, tile=cfg.tile, eye="left")
    img_k, hits_k = ops.rasterize(lists, splats, width=cam.width,
                                  height=cam.height, tile=cfg.tile, eye="left")
    np.testing.assert_allclose(np.asarray(img_k), np.asarray(img_core),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(hits_k), np.asarray(hits_core))


def test_rasterize_early_termination_bounded():
    """eps_t early-exit may only perturb pixels by ≤ eps_t in color."""
    _g, rig, wide, splats, ranks, lists, cfg = _scene(n=800, seed=3)
    cam = rig.left
    img0, _ = ops.rasterize(lists, splats, width=cam.width, height=cam.height,
                            tile=cfg.tile, eye="left", eps_t=0.0)
    img1, _ = ops.rasterize(lists, splats, width=cam.width, height=cam.height,
                            tile=cfg.tile, eye="left", eps_t=1e-3)
    assert np.abs(np.asarray(img0) - np.asarray(img1)).max() <= 1e-3 + 1e-6


# -- vq ------------------------------------------------------------------------


@pytest.mark.parametrize("m,kc,d", [(64, 16, 9), (500, 256, 24), (1000, 128, 45)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_vq_kernel(m, kc, d, dtype):
    rng = np.random.default_rng(m + kc)
    x = jnp.asarray(rng.normal(size=(m, d)), dtype)
    cb = jnp.asarray(rng.normal(size=(kc, d)), dtype)
    idx_p = ops.vq_assign(x, cb, use_pallas=True)
    idx_r = vq_assign_ref(x, cb)
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_r))


# -- preprocess -----------------------------------------------------------------


@pytest.mark.parametrize("n,sh_degree", [(64, 0), (300, 1), (200, 2)])
def test_preprocess_kernel(n, sh_degree):
    rng = np.random.default_rng(n)
    g = random_gaussians(rng, n, sh_degree=sh_degree, extent=5.0)
    cam = make_camera([0, -15, 2], [0, 0, 0], focal_px=200.0, width=96,
                      height=64, near=0.25)
    rig = StereoRig(left=cam, baseline=0.06)
    wide = dataclasses.replace(cam, width=160)
    s_ref = project(g, rig, wide)
    s_ker = ops.preprocess(g, rig, wide, use_pallas=True)
    for name in ("mean2d", "depth", "conic", "ext", "color_l", "color_r",
                 "opacity", "disparity"):
        np.testing.assert_allclose(np.asarray(getattr(s_ker, name)),
                                   np.asarray(getattr(s_ref, name)),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    np.testing.assert_array_equal(np.asarray(s_ker.visible),
                                  np.asarray(s_ref.visible))


# -- LoD sweep -------------------------------------------------------------------


def test_lod_sweep_kernel(small_tree):
    cam = np.array([250, 250, 120], np.float32)
    top_expand, _ = ls.top_sweep(small_tree, jnp.asarray(cam), jnp.float32(1400.0),
                                 jnp.float32(64.0))
    rpe = top_expand[small_tree.slab_root_parent_top]
    cut_p, rexp_p, rho_p = ops.lod_slab_sweep(
        small_tree, jnp.asarray(cam), jnp.float32(1400.0), jnp.float32(64.0), rpe,
        use_pallas=True)
    cut_r, rexp_r, rho_r = kref.ref_lod_slab_sweep(
        small_tree.slab_mu(), small_tree.slab_size(), small_tree.slab_parent,
        small_tree.slab_level, small_tree.slab_is_leaf, small_tree.slab_valid,
        rpe, jnp.asarray(cam), jnp.float32(1400.0), jnp.float32(64.0),
        max_depth=small_tree.meta.slab_max_depth)
    np.testing.assert_array_equal(np.asarray(cut_p), np.asarray(cut_r))
    np.testing.assert_array_equal(np.asarray(rexp_p), np.asarray(rexp_r))
    np.testing.assert_allclose(np.asarray(rho_p), np.asarray(rho_r), rtol=1e-6)


# -- LoD sweep: the prefix max against the level loop --------------------------

SWEEP_LANES = 40   # not a multiple of 128: the kernel pads lanes
SWEEP_PAIRS = 20   # not a multiple of 8: the kernel pads pairs
SWEEP_FOCAL = 400.0


def _random_dfs_slabs(rng, n_slabs, s, depth):
    """(parent, level, is_leaf, valid), each (n_slabs, s), of random
    DFS-preorder slabs: valid nodes first, then padding lanes; slab 0
    reaches `depth` and no slab goes deeper."""
    parent = np.full((n_slabs, s), -1, np.int32)
    level = np.full((n_slabs, s), 2**30, np.int32)
    for k in range(n_slabs):
        n = int(rng.integers(depth + 1, s + 1))
        par, lev = [-1], [0]

        def grow(j, spine):
            if lev[j] == depth:
                return
            for c in range(int(rng.integers(1 if spine else 0, 4))):
                if len(par) == n:
                    return
                par.append(j)
                lev.append(lev[j] + 1)
                grow(len(par) - 1, spine and c == 0)

        grow(0, k == 0)
        parent[k, :len(par)], level[k, :len(lev)] = par, lev
    valid = level < 2**30
    has_child = np.zeros_like(valid)
    rows = np.broadcast_to(np.arange(n_slabs)[:, None], parent.shape)
    child = valid & (parent >= 0)
    has_child[rows[child], parent[child]] = True
    return parent, level, valid & ~has_child, valid


def _slab_forest(rng, n_slabs, depth):
    """A P = 0 tree (no top-tree; every slab root is a tree root) of random
    DFS slabs. Node sizes shrink by 0.6 per level; padding lanes carry random
    means and sizes, which every sweep must ignore."""
    s = SWEEP_LANES
    parent, level, is_leaf, valid = _random_dfs_slabs(rng, n_slabs, s, depth)
    centre = rng.uniform(-50.0, 50.0, (n_slabs, 1, 3))
    mu = (centre + rng.normal(0.0, 4.0, (n_slabs, s, 3))).astype(np.float32)
    size = np.where(valid, 6.0 * 0.6 ** np.minimum(level, 8), 3.0)
    size = (size * rng.uniform(0.6, 1.4, (n_slabs, s))).astype(np.float32)
    n = n_slabs * s
    meta = TreeMeta(T=0, Ns=n_slabs, S=s, P=0, depth=depth,
                    n_real=int(valid.sum()), n_leaves=int(is_leaf.sum()),
                    top_level_offsets=(0,), slab_max_depth=depth)
    return LodTree(
        gaussians=Gaussians(
            mu=jnp.asarray(mu.reshape(n, 3)),
            log_scale=jnp.zeros((n, 3), jnp.float32),
            quat=jnp.zeros((n, 4), jnp.float32).at[:, 0].set(1.0),
            opacity=jnp.full((n,), 0.5, jnp.float32),
            sh=jnp.zeros((n, 1, 3), jnp.float32)),
        size=jnp.asarray(size.reshape(n)),
        top_parent=jnp.zeros((0,), jnp.int32),
        top_is_leaf=jnp.zeros((0,), bool),
        slab_parent=jnp.asarray(parent), slab_is_leaf=jnp.asarray(is_leaf),
        slab_valid=jnp.asarray(valid), slab_level=jnp.asarray(level),
        slab_end=jnp.asarray(subtree_end(parent, level, valid, depth)),
        slab_root_parent_top=jnp.full((n_slabs,), -1, jnp.int32),
        meta=meta)


@pytest.mark.parametrize("n_slabs", [1, 6])
@pytest.mark.parametrize("tau", ["scalar", "per_pair"])
@pytest.mark.parametrize("rpe", ["on", "mixed"])
@pytest.mark.parametrize("depth", range(5))
def test_slab_sweeps_match_the_level_loop(depth, rpe, tau, n_slabs):
    """The XLA sweep and the Pallas kernel (interpreted here) read ancestry
    from DFS subtree ends by a prefix max; the level-loop oracle reads slab
    parents level by level, and `reference_search_np` walks the whole tree.
    All agree bitwise on the cut and the root expand bits, on random DFS
    slabs with padding lanes, at every slab depth, with the slab roots'
    parent-expand bits all on or mixed, and one τ or one per pair.
    `n_slabs=1` is the degenerate P = 0 tree that is one slab."""
    rng = np.random.default_rng(1000 * depth + 10 * n_slabs
                                + (rpe == "on") + 2 * (tau == "scalar"))
    tree = _slab_forest(rng, n_slabs, depth)
    sel = np.arange(SWEEP_PAIRS) % n_slabs
    # half the cameras inside their slab's cluster, half up to 150 m away
    radius = np.where(np.arange(SWEEP_PAIRS) % 2 == 0, 8.0, 150.0)[:, None]
    cams = (np.asarray(tree.slab_mu())[sel, 0]
            + rng.uniform(-1.0, 1.0, (SWEEP_PAIRS, 3)) * radius
            ).astype(np.float32)
    rpe_k = (rng.random(SWEEP_PAIRS) < 0.5) | (rpe == "on")
    rpe_k[0] = True   # slab 0, the deepest, seen from nearby
    taus = (np.full(SWEEP_PAIRS, 24.0, np.float32) if tau == "scalar"
            else rng.uniform(1.0, 40.0, SWEEP_PAIRS).astype(np.float32))
    tau_arg = jnp.float32(taus[0]) if tau == "scalar" else jnp.asarray(taus)
    focal = jnp.float32(SWEEP_FOCAL)
    mu, size = tree.slab_mu()[sel], tree.slab_size()[sel]
    leaf, valid = tree.slab_is_leaf[sel], tree.slab_valid[sel]
    want = kref.ref_lod_pair_sweep(
        mu, size, tree.slab_parent[sel], tree.slab_level[sel], leaf, valid,
        jnp.asarray(rpe_k), jnp.asarray(cams), focal, tau_arg,
        max_depth=depth)
    got = {
        "xla": ls.sweep_slab_camera_pairs(
            mu, size, tree.slab_end[sel], leaf, valid, jnp.asarray(rpe_k),
            jnp.asarray(cams), focal, tau_arg),
        "pallas": lod_pair_sweep_pallas(
            mu, size, tree.slab_end[sel], leaf, valid, jnp.asarray(rpe_k),
            jnp.asarray(cams), focal, tau_arg),
    }
    want = [np.asarray(x) for x in want]
    for name, out in got.items():
        cut, rexp, rho = (np.asarray(x) for x in out)
        np.testing.assert_array_equal(cut, want[0], err_msg=name)
        np.testing.assert_array_equal(rexp, want[1], err_msg=name)
        np.testing.assert_allclose(rho, want[2], rtol=1e-6, err_msg=name)
    np.testing.assert_array_equal(np.asarray(got["xla"][2]), want[2])

    for k in range(SWEEP_PAIRS):
        full = ls.reference_search_np(tree, cams[k], SWEEP_FOCAL, taus[k])
        row = full.reshape(n_slabs, SWEEP_LANES)[sel[k]] if rpe_k[k] else 0
        np.testing.assert_array_equal(want[0][k], row, err_msg=str(k))
    level = np.asarray(tree.slab_level)[sel]
    assert (want[0] & (level == depth)).any(), "no cut at the deepest level"
    if depth > 0:
        assert (want[0] & (level == 0)).any(), "no cut at the slab roots"

    # the whole-tree search sweeps every slab from one camera the same way
    cut, _ = ls.full_search(tree, jnp.asarray(cams[0]), focal,
                            jnp.float32(taus[0]))
    np.testing.assert_array_equal(
        np.asarray(cut.mask(tree)),
        ls.reference_search_np(tree, cams[0], SWEEP_FOCAL, taus[0]))


# -- stereo merge ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stereo_merge_kernel(seed):
    g, rig, wide, splats, ranks, lists, cfg = _scene(n=400, seed=seed)
    cam = rig.left
    n_cat = n_categories(rig.max_disparity_px(), cfg.tile)
    right_core = stereo_lists(lists, splats, ranks, tile=cfg.tile,
                              width=cam.width, n_cat=n_cat)
    right_p = ops.stereo_merge(lists, splats, ranks, tile=cfg.tile,
                               width=cam.width, n_cat=n_cat, use_pallas=True)
    right_r = ops.stereo_merge(lists, splats, ranks, tile=cfg.tile,
                               width=cam.width, n_cat=n_cat, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(right_p.lists), np.asarray(right_core.lists))
    np.testing.assert_array_equal(np.asarray(right_r.lists), np.asarray(right_core.lists))
    np.testing.assert_array_equal(np.asarray(right_p.counts), np.asarray(right_core.counts))
    # the merge kernel surfaces its overflow flag (matching TileLists.overflow)
    assert bool(right_p.overflow) == bool(right_core.overflow)
    assert bool(right_r.overflow) == bool(right_core.overflow)


# -- flash attention ----------------------------------------------------------------


@pytest.mark.parametrize("b,h,hkv,lq,lk,d", [
    (1, 4, 4, 64, 64, 32),
    (2, 8, 2, 128, 128, 16),   # GQA
    (1, 4, 1, 96, 96, 32),     # MQA
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, h, hkv, lq, lk, d, causal, window, dtype):
    rng = np.random.default_rng(h * lq + window)
    q = jnp.asarray(rng.normal(size=(b, h, lq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, lk, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, lk, d)), dtype)
    out_p = ops.flash_attention(q, k, v, causal=causal, window=window,
                                use_pallas=True)
    out_r = kref.ref_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32), rtol=tol, atol=tol)


# -- interpret decision ---------------------------------------------------------


def test_interpret_follows_the_backend():
    """One decision: interpret on the CPU backend unless told otherwise."""
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False and resolve_interpret(True) is True


@pytest.mark.parametrize("kernel,args", [
    (stereo_merge_pallas, (jnp.zeros((8, 2, 128), jnp.int32),
                           jnp.zeros((8, 2, 128), jnp.int32))),
    (preprocess_pallas, (jnp.zeros((256, 3)), jnp.zeros((256, 3)),
                         jnp.zeros((256, 4)), jnp.zeros((256,)),
                         jnp.zeros((256, 4, 3)), jnp.zeros((26,)))),
])
def test_unlowered_kernels_refuse_compiled_calls(kernel, args):
    """Kernels Mosaic cannot lower raise when asked to compile, instead of
    silently running the interpreter on a TPU."""
    with pytest.raises(NotImplementedError, match="Mosaic"):
        kernel(*args, interpret=False)
