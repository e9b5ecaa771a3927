"""Explicit client-render stages: project → bin_shared → stereo_merge →
rasterize (paper Fig. 13/§4.4), over a static `RenderConfig`.

The stages are pure functions of pytrees, so the same code serves three
callers with identical math:
  * the legacy single-client `repro.core.pipeline.render_stereo` (builds a
    plan, rasterizes, returns the historical tuple);
  * `render_stereo(plan)` here — one call from plan to pixels;
  * `repro.render.batched.batched_render_stereo` — the whole chain vmapped on
    a leading client axis (bit-identical per client, proven in tests).

`render_tiles` / `render_reference` (the XLA rasterizers, formerly in
repro.core.raster) live here so the render subsystem is self-contained;
repro.core.raster re-exports them for existing imports.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import projection as proj
from repro.core.binning import TileLists, bin_left
from repro.core.camera import StereoRig
from repro.core.gaussians import Gaussians
from repro.core.projection import ALPHA_MAX, ALPHA_MIN, Splats, depth_ranks
from repro.core.stereo import stereo_lists
from repro.render.common import eye_views, pixel_alpha
from repro.render.config import RenderConfig
from repro.render.plan import RenderPlan


# ---------------------------------------------------------------------------
# XLA rasterizers (oracle-consistent; moved from repro.core.raster)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("width", "height", "tile", "eye",
                                             "alpha_min", "alpha_max"))
def render_tiles(lists: TileLists, s: Splats, *, width: int, height: int,
                 tile: int, eye: str, alpha_min: float = ALPHA_MIN,
                 alpha_max: float = ALPHA_MAX) -> Tuple[jax.Array, jax.Array]:
    """Render from per-tile lists. Returns (image (H,W,3), alpha_hit (n_tiles, L)).

    alpha_hit[t, i] — entry i of tile t passed the α-check at ≥1 pixel; this is
    exactly what the paper's SRU forwards to the stereo buffer."""
    means, colors = eye_views(s, eye)
    tiles_x, tiles_y = lists.tiles_x, lists.tiles_y

    ty, tx = jnp.meshgrid(jnp.arange(tiles_y), jnp.arange(tiles_x), indexing="ij")
    origins = jnp.stack([tx.reshape(-1) * tile, ty.reshape(-1) * tile], -1)

    yy, xx = jnp.meshgrid(jnp.arange(tile), jnp.arange(tile), indexing="ij")
    px_local = jnp.stack([xx + 0.5, yy + 0.5], -1)   # (T, T, 2) pixel centers

    def tile_fn(list_row, origin):
        px = px_local + origin.astype(jnp.float32)

        def step(carry, idx):
            color_acc, t_acc = carry
            valid = idx >= 0
            g = jnp.clip(idx, 0, s.m - 1)
            a = pixel_alpha(px, means[g], s.conic[g], s.opacity[g],
                            alpha_min=alpha_min, alpha_max=alpha_max)
            a = jnp.where(valid, a, 0.0)
            contrib = t_acc * a
            color_acc = color_acc + contrib[..., None] * colors[g]
            t_acc = t_acc * (1.0 - a)
            return (color_acc, t_acc), (a > 0.0).any()

        init = (jnp.zeros((tile, tile, 3), jnp.float32),
                jnp.ones((tile, tile), jnp.float32))
        (color, _t), hit = jax.lax.scan(step, init, list_row)
        return color, hit

    colors_t, hits = jax.vmap(tile_fn)(lists.lists, origins)   # (n_tiles, T, T, 3)
    img = colors_t.reshape(tiles_y, tiles_x, tile, tile, 3)
    img = img.transpose(0, 2, 1, 3, 4).reshape(tiles_y * tile, tiles_x * tile, 3)
    return img[:height, :width], hits


@functools.partial(jax.jit, static_argnames=("width", "height", "eye",
                                             "alpha_min", "alpha_max"))
def render_reference(s: Splats, *, width: int, height: int, eye: str,
                     alpha_min: float = ALPHA_MIN,
                     alpha_max: float = ALPHA_MAX) -> jax.Array:
    """Oracle: per-pixel blend of every splat in global depth order (no tiles)."""
    means, colors = eye_views(s, eye)
    key = jnp.where(s.visible, s.depth, jnp.inf)
    order = jnp.argsort(key, stable=True)

    yy, xx = jnp.meshgrid(jnp.arange(height), jnp.arange(width), indexing="ij")
    px = jnp.stack([xx + 0.5, yy + 0.5], -1).astype(jnp.float32)

    def step(carry, g):
        color_acc, t_acc = carry
        a = pixel_alpha(px, means[g], s.conic[g], s.opacity[g],
                        alpha_min=alpha_min, alpha_max=alpha_max)
        a = jnp.where(s.visible[g], a, 0.0)
        contrib = t_acc * a
        color_acc = color_acc + contrib[..., None] * colors[g]
        t_acc = t_acc * (1.0 - a)
        return (color_acc, t_acc), None

    init = (jnp.zeros((height, width, 3), jnp.float32),
            jnp.ones((height, width), jnp.float32))
    (img, _), _ = jax.lax.scan(step, init, order)
    return img


# ---------------------------------------------------------------------------
# pipeline stages
# ---------------------------------------------------------------------------


def project(queue: Gaussians, rig: StereoRig, cfg: RenderConfig
            ) -> Tuple[Splats, jax.Array]:
    """Shared stereo preprocessing: one EWA projection on the widened-left
    plane + one depth sort serve BOTH eyes. Returns (splats, ranks)."""
    splats = proj.project(queue, rig, cfg.widened(rig.left))
    return splats, depth_ranks(splats)


def bin_shared(splats: Splats, ranks: jax.Array, cfg: RenderConfig
               ) -> TileLists:
    """Depth-ordered tile binning on the widened grid (left eye; the right
    eye's lists derive from these via the shift-merge)."""
    return bin_left(splats, cfg.wide_width, cfg.height, cfg.bin_config(),
                    ranks)


def stereo_merge(splats: Splats, ranks: jax.Array, left: TileLists,
                 cfg: RenderConfig, *, use_pallas: bool = False) -> TileLists:
    """Right-eye lists via the SRU/line-buffer k-way shift-merge (no re-sort,
    no re-bin). `use_pallas` switches to the merge kernel (same output)."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.stereo_merge(left, splats, ranks, tile=cfg.tile,
                                 width=cfg.width, n_cat=cfg.n_cat)
    return stereo_lists(left, splats, ranks, tile=cfg.tile, width=cfg.width,
                        n_cat=cfg.n_cat)


def build_plan(queue: Gaussians, rig: StereoRig, cfg: RenderConfig, *,
               use_pallas_merge: bool = False) -> RenderPlan:
    """project → bin_shared → stereo_merge, composed."""
    splats, ranks = project(queue, rig, cfg)
    left = bin_shared(splats, ranks, cfg)
    right = stereo_merge(splats, ranks, left, cfg, use_pallas=use_pallas_merge)
    return RenderPlan(splats=splats, ranks=ranks, left=left, right=right)


def rasterize(plan: RenderPlan, cfg: RenderConfig, *, use_pallas: bool = False
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rasterize both eyes from a plan → (img_l, img_r, left α-hit flags).

    XLA path by default; `use_pallas` dispatches the tile kernel per eye
    (allclose vs XLA — FMA contraction differs across program structures)."""
    if use_pallas:
        if (cfg.alpha_min, cfg.alpha_max) != (ALPHA_MIN, ALPHA_MAX):
            raise NotImplementedError(
                "the Pallas rasterizer assumes the default α thresholds; "
                f"got ({cfg.alpha_min}, {cfg.alpha_max})")
        from repro.kernels import ops as kops
        img_l, hits = kops.rasterize(plan.left, plan.splats, width=cfg.width,
                                     height=cfg.height, tile=cfg.tile,
                                     eye="left", eps_t=cfg.eps_t)
        img_r, _ = kops.rasterize(plan.right, plan.splats, width=cfg.width,
                                  height=cfg.height, tile=cfg.tile,
                                  eye="right", eps_t=cfg.eps_t)
        return img_l, img_r, hits
    img_l, hits = render_tiles(plan.left, plan.splats, width=cfg.width,
                               height=cfg.height, tile=cfg.tile, eye="left",
                               alpha_min=cfg.alpha_min,
                               alpha_max=cfg.alpha_max)
    img_r, _ = render_tiles(plan.right, plan.splats, width=cfg.width,
                            height=cfg.height, tile=cfg.tile, eye="right",
                            alpha_min=cfg.alpha_min, alpha_max=cfg.alpha_max)
    return img_l, img_r, hits


def render_stereo(plan: RenderPlan, cfg: RenderConfig, *,
                  use_pallas: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One call from plan to pixels: (img_l, img_r, left α-hit flags)."""
    return rasterize(plan, cfg, use_pallas=use_pallas)


def render_stereo_reference(queue: Gaussians, rig: StereoRig,
                            cfg: RenderConfig = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """Two fully independent untiled eye renders (the BASE baseline of
    Fig. 16) from the same projected splats."""
    if cfg is None:
        cfg = RenderConfig.for_rig(rig)
    splats, _ranks = project(queue, rig, cfg)
    img_l = render_reference(splats, width=cfg.width, height=cfg.height,
                             eye="left", alpha_min=cfg.alpha_min,
                             alpha_max=cfg.alpha_max)
    img_r = render_reference(splats, width=cfg.width, height=cfg.height,
                             eye="right", alpha_min=cfg.alpha_min,
                             alpha_max=cfg.alpha_max)
    return img_l, img_r
