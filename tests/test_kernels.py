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
from repro.core.gaussians import random_gaussians
from repro.core.projection import depth_ranks, project
from repro.core.raster import render_tiles
from repro.core.stereo import n_categories, stereo_lists
from repro.kernels import ops, ref as kref, resolve_interpret
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
