"""Pallas TPU kernel: tile rasterization (the paper's VRC, §5).

Dataflow mirrors GSCore's volume rendering core: per grid cell = one tile
slab; the slab's depth-ordered Gaussian entries are streamed through SMEM and
broadcast to all T×T "rendering units" (vector lanes); each lane α-checks and
front-to-back blends (the α test itself is the shared definition in
repro.render.common — one expression for every rasterization path). Early
termination stops the entry loop once every lane's transmittance is exhausted
(eps_t) — set eps_t=0.0 for the bitwise mode used by the stereo bit-accuracy
proofs.

The kernel is ORIGIN-BASED: each slab carries its own pixel-space tile corner,
so the grid needs no image-shape knowledge. That is what lets
repro.render.batched pool the occupied slabs of a whole client fleet — mixed
clients, mixed eyes, mixed grid positions — into one dispatch
(`rasterize_slabs_pallas`); the classic one-image entry point
(`rasterize_tiles_pallas`) derives origins from the tile grid and calls the
same kernel.

Entry layout (pre-gathered by ops.gather_entries from RenderPlan slabs — the
attribute broadcast of Fig. 14): entries[t, i] = [mean_x, mean_y, conic_a,
conic_b, conic_c, r, g, b, opacity]; invalid slots carry opacity = 0.

Layout: per grid cell one flat SMEM record holds the slab's (x, y) origin,
its count and its (L, 9) entries — the entry loop reads one entry's nine
scalars per step and broadcasts them over the T×T pixel tile in VMEM; the
outputs are the (3, T, T) channel-major tile image and the (1, L) int32
α-hit flags (the SRU feed; bool and (T, T, 3) views are restored outside).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.render.common import entry_alpha

_HEAD = 128  # per-slab SMEM record header: [origin x, origin y, count, 0...]
_RECORD_TILE = 1024  # HBM tiling of a 1-D f32 array: records are multiples


def _raster_kernel(rec_ref, img_ref, hit_ref, *, tile: int, eps_t: float):
    pix = lambda axis: jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), axis).astype(jnp.float32)
    px = pix(1) + rec_ref[0] + 0.5
    py = pix(0) + rec_ref[1] + 0.5
    count = rec_ref[2].astype(jnp.int32)
    entry_id = jax.lax.broadcasted_iota(jnp.int32, hit_ref.shape[1:], 1)

    def cond(state):
        i, *_color, t_acc, _hits = state
        return (i < count) & (jnp.max(t_acc) > eps_t)

    def body(state):
        i, red, green, blue, t_acc, hits = state
        e = [rec_ref[_HEAD + i * 9 + c] for c in range(9)]
        a = entry_alpha(px, py, e)
        contrib = t_acc * a
        red = red + contrib * e[5]
        green = green + contrib * e[6]
        blue = blue + contrib * e[7]
        t_acc = t_acc * (1.0 - a)
        hit = (jnp.max(a) > 0.0).astype(jnp.int32)
        hits = jnp.where(entry_id == i, hit, hits)
        return i + 1, red, green, blue, t_acc, hits

    zero = jnp.zeros((tile, tile), jnp.float32)
    init = (jnp.int32(0), zero, zero, zero, jnp.ones((tile, tile), jnp.float32),
            jnp.zeros(hit_ref.shape[1:], jnp.int32))
    _, red, green, blue, _t, hits = jax.lax.while_loop(cond, body, init)
    img_ref[0, 0] = red
    img_ref[0, 1] = green
    img_ref[0, 2] = blue
    hit_ref[0] = hits


@functools.partial(jax.jit, static_argnames=("tile", "eps_t", "interpret"))
def rasterize_slabs_pallas(entries: jax.Array, counts: jax.Array,
                           origins: jax.Array, *, tile: int,
                           eps_t: float = 0.0, interpret=None):
    """Rasterize arbitrary tile slabs — each with its own pixel origin.

    entries: (n_slabs, L, 9) f32; counts: (n_slabs,) int32;
    origins: (n_slabs, 2) int32 pixel-space tile corners (x, y).
    Returns (tile_images (n_slabs, T, T, 3), hits (n_slabs, L) bool).

    This is the fleet-pooled entry point: slabs may come from different
    clients, eyes, and grid positions (repro.render.batched pools occupied
    slabs into power-of-two buckets and makes ONE dispatch here).
    `interpret=None` compiles on a TPU and interprets on the CPU
    (`repro.kernels.resolve_interpret`)."""
    n_slabs, l_max, _ = entries.shape
    body = -(-(_HEAD + l_max * 9) // _RECORD_TILE) * _RECORD_TILE - _HEAD
    head = jnp.zeros((n_slabs, _HEAD), jnp.float32)
    head = head.at[:, 0:2].set(jnp.asarray(origins, jnp.float32))
    head = head.at[:, 2].set(jnp.asarray(counts, jnp.float32))
    flat = jnp.asarray(entries, jnp.float32).reshape(n_slabs, l_max * 9)
    records = jnp.concatenate(
        [head, jnp.pad(flat, ((0, 0), (0, body - l_max * 9)))], axis=1)
    kernel = functools.partial(_raster_kernel, tile=tile, eps_t=eps_t)
    img, hits = pl.pallas_call(
        kernel,
        grid=(n_slabs,),
        in_specs=[pl.BlockSpec((_HEAD + body,), lambda t: (t,),
                               memory_space=pltpu.SMEM)],
        out_specs=[
            pl.BlockSpec((1, 3, tile, tile), lambda t: (t, 0, 0, 0)),
            pl.BlockSpec((1, 1, l_max), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_slabs, 3, tile, tile), jnp.float32),
            jax.ShapeDtypeStruct((n_slabs, 1, l_max), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(records.reshape(-1))
    return img.transpose(0, 2, 3, 1), hits[:, 0] != 0


@functools.partial(jax.jit, static_argnames=("tile", "tiles_x", "eps_t", "interpret"))
def rasterize_tiles_pallas(entries: jax.Array, counts: jax.Array, *, tile: int,
                           tiles_x: int, eps_t: float = 0.0, interpret=None):
    """One-image entry point: entries: (n_tiles, L, 9) f32 laid out on a
    row-major (tiles_y, tiles_x) grid; counts: (n_tiles,) int32.
    Returns (tile_images (n_tiles, T, T, 3), hits (n_tiles, L))."""
    n_tiles = entries.shape[0]
    idx = jnp.arange(n_tiles, dtype=jnp.int32)
    origins = jnp.stack([(idx % tiles_x) * tile, (idx // tiles_x) * tile], -1)
    return rasterize_slabs_pallas(entries, counts, origins, tile=tile,
                                  eps_t=eps_t, interpret=interpret)
