"""Fleet-batched stereo rendering (ROADMAP "client-side Pallas stereo
batching"): render B clients' queues in one shot.

Two paths, identical math:

  * `path="vmap"` — the whole project→bin→merge→rasterize chain vmapped on a
    leading client axis: one fused device program, bit-identical per client
    to the single-client `repro.core.pipeline.render_stereo` (proven in
    tests/test_render_batched.py).
  * `path="pooled"` — the Pallas bucket path, mirroring the stale-slab
    pooling of repro.serve.lod_service: plans are built vmapped, then the
    OCCUPIED (client, eye, tile) slabs of the whole fleet are pooled,
    rounded up to a power-of-two bucket (bounded recompilation), and
    rasterized by ONE origin-based kernel dispatch
    (repro.kernels.rasterize.rasterize_slabs_pallas). Empty tiles never
    reach the kernel, so fleet rasterization work scales with total occupied
    tiles, not clients × tiles. Bit-identical to the per-client Pallas
    rasterizer; allclose (FMA contraction) vs the XLA path.

Rigs are batched as pytrees: stack per-client rigs with `stack_rigs` (static
fields — resolution, near/far, baseline — must agree; pose and focal are
leaves and vary per client).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lod_search as ls
from repro.core.camera import StereoRig
from repro.core.gaussians import Gaussians
from repro.render.config import RenderConfig
from repro.render.plan import RenderPlan, StereoFrameStats, frame_stats
from repro.render.stages import build_plan, render_stereo


def stack_pytrees(items: Sequence) -> object:
    """Stack a list of identically-shaped pytrees on a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)


def stack_rigs(rigs: Sequence[StereoRig]) -> StereoRig:
    """Stack rigs on a leading client axis. Static fields must agree — they
    define the compiled program; per-client pose/focal stay leaves."""
    rigs = list(rigs)
    r0 = rigs[0]
    key = (r0.left.width, r0.left.height, r0.left.near, r0.left.far,
           r0.left.cx, r0.left.cy, r0.baseline)
    for r in rigs[1:]:
        k = (r.left.width, r.left.height, r.left.near, r.left.far,
             r.left.cx, r.left.cy, r.baseline)
        if k != key:
            raise ValueError(f"rig static fields differ: {key} vs {k}")
    return stack_pytrees(rigs)


def batched_build_plans(queues: Gaussians, rigs: StereoRig, cfg: RenderConfig
                        ) -> RenderPlan:
    """Build every client's RenderPlan vmapped (leaves gain a leading B)."""
    return jax.vmap(lambda q, r: build_plan(q, r, cfg))(queues, rigs)


def _single_frame(queue, rig, cfg):
    plan = build_plan(queue, rig, cfg)
    img_l, img_r, hits = render_stereo(plan, cfg)
    return img_l, img_r, frame_stats(plan, hits)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _vmapped_frames_jit(queues, rigs, cfg):
    return jax.vmap(lambda q, r: _single_frame(q, r, cfg))(queues, rigs)


def batched_render_stereo(queues: Gaussians, rigs: StereoRig,
                          cfg: RenderConfig, *, path: str = "vmap",
                          jit: bool = False, active=None, mesh=None
                          ) -> Tuple[jax.Array, jax.Array, StereoFrameStats]:
    """Render B clients → (img_l (B,H,W,3), img_r (B,H,W,3), per-client
    StereoFrameStats). `queues`/`rigs` carry a leading client axis (see
    `stack_pytrees`/`stack_rigs`).

    `jit=True` wraps the vmap path in one whole-fleet jit: measurably faster,
    but whole-program fusion reassociates FMAs, so results are allclose — not
    bitwise — vs the single-client path. Leave it off where the bit-accuracy
    guarantee matters.

    `active` is an optional (B,) bool slot mask (ragged fleets,
    repro.serve.fleet). On the pooled path an inactive slot's tiles NEVER
    enter the occupied-tile bucket — fleet rasterization work tracks live
    clients, not slot capacity — and its frames come back black. The fixed
    -shape vmap path ignores the mask (an inactive slot's queue is empty, so
    it renders black anyway at unavoidable vmap cost).

    `mesh` (a fleet mesh, repro.sharding.fleet) shards the returned frames
    and per-client stats on the `clients` axis — on both paths each client
    shard's fallback pixels live with its slots (plan building and the XLA
    rasterization are slot-parallel; the pooled path's single Pallas bucket
    dispatch itself stays replicated — its tile pooling is still global)."""
    if path == "vmap":
        if jit:
            out = _vmapped_frames_jit(queues, rigs, cfg)
        else:
            out = jax.vmap(lambda q, r: _single_frame(q, r, cfg))(queues,
                                                                  rigs)
        return _constrain_frames(out, mesh)
    if path == "pooled":
        return _constrain_frames(
            _pooled_render(queues, rigs, cfg, active=active, mesh=mesh), mesh)
    raise ValueError(f"unknown batched render path: {path!r}")


def _constrain_frames(out, mesh):
    """Pin (img_l, img_r, stats) on the `clients` axis (no-op meshless)."""
    if mesh is None:
        return out
    from repro.sharding.fleet import shard_service_state
    return shard_service_state(mesh, out)


# ---------------------------------------------------------------------------
# Pallas bucket path: pool occupied tiles fleet-wide, one kernel dispatch
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _gather_fleet_slabs(plans: RenderPlan, cfg: RenderConfig):
    """(entries, counts, origins) for every (client, eye, tile) slab, flat.

    Left slabs live on the widened grid (they must all be rasterized — even
    columns later cropped out feed the α-hit forwarding); right slabs on the
    output grid. Origins are pixel-space tile corners, so the kernel needs no
    grid shape."""
    from repro.kernels.ops import gather_entries

    def per_client(plan):
        ent_l, cnt_l = gather_entries(plan.left, plan.splats, "left")
        ent_r, cnt_r = gather_entries(plan.right, plan.splats, "right")
        return ent_l, cnt_l, ent_r, cnt_r

    ent_l, cnt_l, ent_r, cnt_r = jax.vmap(per_client)(plans)
    b = cnt_l.shape[0]
    t = cfg.tile

    def grid_origins(tiles_x, n_tiles):
        idx = jnp.arange(n_tiles, dtype=jnp.int32)
        return jnp.stack([(idx % tiles_x) * t, (idx // tiles_x) * t], -1)

    org_l = jnp.broadcast_to(grid_origins(cfg.tiles_x_wide, cnt_l.shape[1]),
                             (b,) + (cnt_l.shape[1], 2))
    org_r = jnp.broadcast_to(grid_origins(cfg.tiles_x, cnt_r.shape[1]),
                             (b,) + (cnt_r.shape[1], 2))
    entries = jnp.concatenate([ent_l.reshape(-1, *ent_l.shape[2:]),
                               ent_r.reshape(-1, *ent_r.shape[2:])])
    counts = jnp.concatenate([cnt_l.reshape(-1), cnt_r.reshape(-1)])
    origins = jnp.concatenate([org_l.reshape(-1, 2), org_r.reshape(-1, 2)])
    return entries, counts, origins


@functools.partial(jax.jit, static_argnames=("n_slabs", "tile", "l_len"))
def _scatter_slabs(sel, tiles_img, hits, *, n_slabs: int, tile: int,
                   l_len: int):
    """Scatter pooled kernel outputs back to the dense fleet slab array.
    Repeat-padded slabs write identical values — harmless."""
    imgs = jnp.zeros((n_slabs, tile, tile, 3), jnp.float32)
    flags = jnp.zeros((n_slabs, l_len), jnp.bool_)
    return imgs.at[sel].set(tiles_img), flags.at[sel].set(hits)


def _assemble(tiles_img, tiles_y, tiles_x, tile, height, width):
    img = tiles_img.reshape(-1, tiles_y, tiles_x, tile, tile, 3)
    img = img.transpose(0, 1, 3, 2, 4, 5).reshape(
        -1, tiles_y * tile, tiles_x * tile, 3)
    return img[:, :height, :width]


def _pooled_render(queues, rigs, cfg: RenderConfig, *, active=None,
                   mesh=None):
    from repro.kernels.rasterize import rasterize_slabs_pallas

    plans = batched_build_plans(queues, rigs, cfg)
    if mesh is not None:
        # the pooling tail (slab gather → ONE Pallas bucket dispatch →
        # scatter/assemble) is cross-client by design and its kernel is
        # opaque to the SPMD partitioner — running it on client-sharded
        # plans computes shard-local garbage. Replicate the built plans
        # (one all-gather; plan BUILDING above stays sharded over clients)
        # so the tail is exactly the single-device program, then
        # `_constrain_frames` re-shards the assembled frames over clients.
        from repro.sharding.fleet import replicate_fleet
        plans = replicate_fleet(mesh, plans)
    entries, counts, origins = _gather_fleet_slabs(plans, cfg)
    b = plans.ranks.shape[0]
    n_l = b * cfg.tiles_x_wide * cfg.tiles_y      # left slabs, then right
    n_slabs = int(counts.shape[0])

    occ_mask = np.asarray(counts) > 0
    if active is not None:
        # ragged fleet: an inactive slot's slabs never reach the kernel
        act = np.asarray(active, bool)
        occ_mask &= np.concatenate([
            np.repeat(act, cfg.tiles_x_wide * cfg.tiles_y),
            np.repeat(act, cfg.tiles_x * cfg.tiles_y)])
    occupied = np.nonzero(occ_mask)[0]
    if occupied.size:
        bucket = ls.pow2_bucket(occupied.size, n_slabs)
        sel = jnp.asarray(np.resize(occupied, bucket))
        tiles_img, hits = rasterize_slabs_pallas(
            entries[sel], counts[sel], origins[sel], tile=cfg.tile,
            eps_t=cfg.eps_t)
        all_img, all_hits = _scatter_slabs(
            sel, tiles_img, hits, n_slabs=n_slabs, tile=cfg.tile,
            l_len=cfg.list_len)
    else:
        all_img = jnp.zeros((n_slabs, cfg.tile, cfg.tile, 3), jnp.float32)
        all_hits = jnp.zeros((n_slabs, cfg.list_len), jnp.bool_)

    img_l = _assemble(all_img[:n_l], cfg.tiles_y, cfg.tiles_x_wide, cfg.tile,
                      cfg.height, cfg.width)
    img_r = _assemble(all_img[n_l:], cfg.tiles_y, cfg.tiles_x, cfg.tile,
                      cfg.height, cfg.width)
    left_hits = all_hits[:n_l].reshape(b, -1, cfg.list_len)
    stats = jax.vmap(frame_stats)(plans, left_hits)
    return img_l, img_r, stats
