"""Jit'd public wrappers around the Pallas kernels.

Every op takes `use_pallas`; the fallback is the pure-jnp oracle path, so
callers can flip between the accelerator kernel and XLA. `interpret=None`
lets the backend decide (`repro.kernels.resolve_interpret`): the kernels
compile on a TPU and run in the Pallas interpreter on the CPU."""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.binning import TileLists
from repro.core.projection import Splats
from repro.render.common import eye_views
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lod_cut import lod_slab_sweep_pallas
from repro.kernels.preprocess import OUT_COLS, pack_camera, preprocess_pallas
from repro.kernels.rasterize import rasterize_tiles_pallas
from repro.kernels.stereo_shift import stereo_merge_pallas
from repro.kernels.vq_assign import vq_assign_pallas

_INF32 = jnp.int32(2**30)


# -- rasterize ---------------------------------------------------------------


def gather_entries(lists: TileLists, s: Splats, eye: str
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pre-gather per-tile entry slabs (the Fig. 14 attribute broadcast)."""
    means, colors = eye_views(s, eye)
    idx = lists.lists
    g = jnp.clip(idx, 0, s.m - 1)
    valid = idx >= 0
    ent = jnp.concatenate([
        means[g], s.conic[g], colors[g],
        jnp.where(valid, s.opacity[g], 0.0)[..., None],
    ], axis=-1)
    return ent.astype(jnp.float32), lists.counts


def rasterize(lists: TileLists, s: Splats, *, width: int, height: int,
              tile: int, eye: str, eps_t: float = 0.0, use_pallas: bool = True,
              interpret=None) -> Tuple[jax.Array, jax.Array]:
    """Tile raster → (image (H, W, 3), α-hit flags (n_tiles, L))."""
    entries, counts = gather_entries(lists, s, eye)
    if use_pallas:
        tiles_img, hits = rasterize_tiles_pallas(
            entries, counts, tile=tile, tiles_x=lists.tiles_x, eps_t=eps_t,
            interpret=interpret)
    else:
        tiles_img, hits = kref.ref_rasterize(entries, counts, tile=tile,
                                             tiles_x=lists.tiles_x, eps_t=eps_t)
    ty, tx = lists.tiles_y, lists.tiles_x
    img = tiles_img.reshape(ty, tx, tile, tile, 3)
    img = img.transpose(0, 2, 1, 3, 4).reshape(ty * tile, tx * tile, 3)
    return img[:height, :width], hits


# -- vq ----------------------------------------------------------------------


def vq_assign(x: jax.Array, codebook: jax.Array, *, use_pallas: bool = True,
              interpret=None) -> jax.Array:
    if use_pallas:
        return vq_assign_pallas(x, codebook, interpret=interpret)
    return kref.ref_vq_assign(x, codebook)


# -- preprocessing ------------------------------------------------------------


def preprocess(g, rig, wide, *, use_pallas: bool = True,
               interpret=None) -> Splats:
    """Kernelized repro.core.projection.project (same Splats output)."""
    if not use_pallas:
        from repro.core.projection import project
        return project(g, rig, wide)
    cam = pack_camera(rig, wide)
    out = preprocess_pallas(g.mu, g.log_scale, g.quat, g.opacity, g.sh, cam,
                            interpret=interpret)
    return Splats(
        mean2d=out[:, 0:2], depth=out[:, 2], conic=out[:, 3:6], ext=out[:, 6:8],
        color_l=out[:, 8:11], color_r=out[:, 11:14], opacity=out[:, 14],
        disparity=out[:, 15], visible=out[:, 16] > 0.5)


# -- LoD sweep ----------------------------------------------------------------


def lod_slab_sweep(tree, cam_pos, focal, tau, root_parent_expand, *,
                   use_pallas: bool = True, interpret=None):
    if use_pallas:
        return lod_slab_sweep_pallas(
            tree.slab_mu(), tree.slab_size(), tree.slab_end,
            tree.slab_is_leaf, tree.slab_valid, root_parent_expand, cam_pos,
            focal, tau, interpret=interpret)
    args = (tree.slab_mu(), tree.slab_size(), tree.slab_parent, tree.slab_level,
            tree.slab_is_leaf, tree.slab_valid, root_parent_expand)
    return kref.ref_lod_slab_sweep(*args, cam_pos, focal, tau,
                                   max_depth=tree.meta.slab_max_depth)


# -- stereo merge --------------------------------------------------------------


def build_merge_sources(left: TileLists, s: Splats, ranks: jax.Array, *,
                        tile: int, width: int, n_cat: int):
    """SRU front-end: per right tile, the n_cat include-filtered, compacted,
    depth-sorted source rows (what the line buffer holds)."""
    tiles_x_r = -(-width // tile)
    tiles_y = left.tiles_y
    tiles_x_w = left.tiles_x
    l_len = left.lists.shape[1]
    m = s.m
    wide = left.lists.reshape(tiles_y, tiles_x_w, l_len)

    def per_cx(cx):
        cols = jnp.clip(cx + jnp.arange(n_cat), 0, tiles_x_w - 1)
        src = wide[:, cols, :]
        ok = (cx + jnp.arange(n_cat)) < tiles_x_w
        return jnp.where(ok[None, :, None], src, -1)

    src = jax.vmap(per_cx, out_axes=1)(jnp.arange(tiles_x_r))
    src = src.reshape(tiles_y * tiles_x_r, n_cat, l_len)

    from repro.core.binning import corner_r2
    g = jnp.clip(src, 0, m - 1)
    valid = src >= 0
    x_r = s.mean2d[g, 0] - s.disparity[g]
    ext_x = s.ext[g, 0]
    cx_of = (jnp.arange(tiles_y * tiles_x_r) % tiles_x_r)
    cy_of = (jnp.arange(tiles_y * tiles_x_r) // tiles_x_r)
    lo = (cx_of * tile).astype(jnp.float32)[:, None, None]
    include = valid & (x_r + ext_x >= lo) & (x_r - ext_x <= lo + tile)
    r2 = corner_r2(s.conic, s.opacity)[g]
    y_r = s.mean2d[g, 1]
    ylo = (cy_of * tile).astype(jnp.float32)[:, None, None]
    dx = jnp.maximum(jnp.maximum(lo - x_r, x_r - (lo + tile)), 0.0)
    dy = jnp.maximum(jnp.maximum(ylo - y_r, y_r - (ylo + tile)), 0.0)
    include = include & (dx * dx + dy * dy <= r2)

    ranks_src = jnp.where(include, ranks[g], _INF32)
    ids_src = jnp.where(include, g, -1)
    # compact each row (entries are sorted; excluded → INF sink to the end)
    order = jnp.argsort(ranks_src, axis=-1, stable=True)
    return (jnp.take_along_axis(ranks_src, order, axis=-1),
            jnp.take_along_axis(ids_src, order, axis=-1))


def stereo_merge(left: TileLists, s: Splats, ranks: jax.Array, *, tile: int,
                 width: int, n_cat: int, use_pallas: bool = True,
                 interpret=None) -> TileLists:
    """Kernelized stereo.stereo_lists (same TileLists output)."""
    src_ranks, src_ids = build_merge_sources(left, s, ranks, tile=tile,
                                             width=width, n_cat=n_cat)
    l_len = left.lists.shape[1]
    if use_pallas:
        out, counts, ovf = stereo_merge_pallas(src_ranks, src_ids,
                                               interpret=interpret)
        merge_overflow = ovf.any()
    else:
        out, counts = kref.ref_stereo_merge(src_ranks, src_ids)
        merge_overflow = (counts > l_len).any()
    tiles_x_r = -(-width // tile)
    return TileLists(lists=out, counts=jnp.minimum(counts, l_len),
                     overflow=left.overflow | merge_overflow,
                     tiles_x=tiles_x_r, tiles_y=left.tiles_y)


# -- attention -----------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool = True, interpret=None):
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=interpret)
    return kref.ref_attention(q, k, v, causal=causal, window=window)
