"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Where a core module already implements the math in pure jnp, the oracle
reuses it (the core path is itself tested against independent references —
e.g. raster vs the untiled per-pixel renderer). The LoD slab sweep's oracle
is the level loop, kept here apart from the prefix max that both the XLA
sweep and the Pallas kernel compute; attention gets an independent naive
softmax."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lod_search as _ls
from repro.core.compression import vq_assign_ref as ref_vq_assign  # noqa: F401
from repro.render.common import entry_alpha


def ref_rasterize_slabs(entries: jax.Array, counts: jax.Array,
                        origins: jax.Array, *, tile: int, eps_t: float = 0.0):
    """Oracle for rasterize.rasterize_slabs_pallas: origin-based tile slabs
    (the fleet-pooled entry layout)."""
    n_slabs, l_max, _ = entries.shape

    yy, xx = jnp.meshgrid(jnp.arange(tile), jnp.arange(tile), indexing="ij")

    def tile_fn(origin, ent, count):
        ox = origin[0]
        oy = origin[1]
        px = xx.astype(jnp.float32) + ox + 0.5
        py = yy.astype(jnp.float32) + oy + 0.5

        def step(carry, i):
            color, t_acc, hits, alive = carry
            e = ent[i]
            a = entry_alpha(px, py, e)
            active = alive & (i < count)
            a = jnp.where(active, a, 0.0)
            contrib = t_acc * a
            color = color + contrib[..., None] * e[5:8]
            t_acc = t_acc * (1.0 - a)
            hits = hits.at[i].set(active & jnp.any(a > 0.0))
            alive = alive & (jnp.max(t_acc) > eps_t)
            return (color, t_acc, hits, alive), None

        init = (jnp.zeros((tile, tile, 3), jnp.float32),
                jnp.ones((tile, tile), jnp.float32),
                jnp.zeros((l_max,), jnp.bool_),
                jnp.bool_(True))
        (color, _t, hits, _a), _ = jax.lax.scan(step, init, jnp.arange(l_max))
        return color, hits

    return jax.vmap(tile_fn)(origins, entries, counts)


def ref_rasterize(entries: jax.Array, counts: jax.Array, *, tile: int,
                  tiles_x: int, eps_t: float = 0.0):
    """Oracle for rasterize.rasterize_tiles_pallas (same entry layout)."""
    n_tiles = entries.shape[0]
    idx = jnp.arange(n_tiles, dtype=jnp.int32)
    origins = jnp.stack([(idx % tiles_x) * tile, (idx // tiles_x) * tile], -1)
    return ref_rasterize_slabs(entries, counts, origins, tile=tile,
                               eps_t=eps_t)


def _level_loop_sweep(mu, size, parent, level, is_leaf, valid,
                      root_parent_expand, cam_pos, focal, tau, max_depth: int):
    """One (S,)-slab swept level by level: each level reads its parents'
    expand bits with a gather, as the traversal's definition states
    (expand(n) = expand(parent(n)) ∧ proj(n) > τ). Independent of the DFS
    subtree ranges the served sweeps read. Returns (in_cut, root_expand,
    rho)."""
    gt = _ls.lod_gt(size, _ls.sq_dist(mu, cam_pos), focal, tau)
    s = mu.shape[0]
    expand = jnp.zeros((s,), bool)
    pexp = jnp.zeros((s,), bool)
    for l in range(max_depth + 1):
        at = level == l
        pe_l = jnp.where(parent < 0, root_parent_expand,
                         expand[jnp.clip(parent, 0, s - 1)])
        pexp = jnp.where(at, pe_l, pexp)
        expand = jnp.where(at, pe_l & gt, expand)
    expand = expand & valid
    in_cut = pexp & (~gt | is_leaf) & valid

    rstar = size * focal / tau
    dist = jnp.linalg.norm(mu - cam_pos, axis=-1)
    margin = jnp.where(valid, jnp.abs(dist - rstar), jnp.inf)
    return in_cut, expand[0], jnp.min(margin)


def ref_lod_pair_sweep(pair_mu, pair_size, pair_parent, pair_level,
                       pair_is_leaf, pair_valid, root_parent_expand, cam_pos,
                       focal, tau, *, max_depth: int):
    """Oracle for the pair sweeps (`lod_search.sweep_slab_camera_pairs`,
    lod_cut.lod_pair_sweep_pallas): K (slab, camera) pairs, each with its
    own (K, 3) camera and a scalar or (K,) τ, swept by the level loop from
    slab-local parents and levels. Returns (in_cut (K,S), root_expand (K,),
    rho (K,))."""
    k = pair_size.shape[0]
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (k,))

    def fn(mu, size, parent, level, leaf, valid, rpe, cam, tau_k):
        return _level_loop_sweep(mu, size, parent, level, leaf, valid, rpe,
                                 cam, focal, tau_k, max_depth)

    return jax.vmap(fn)(pair_mu, pair_size, pair_parent, pair_level,
                        pair_is_leaf, pair_valid, root_parent_expand,
                        jnp.asarray(cam_pos, jnp.float32), taus)


def ref_lod_slab_sweep(slab_mu, slab_size, slab_parent, slab_level,
                       slab_is_leaf, slab_valid, root_parent_expand,
                       cam_pos, focal, tau, *, max_depth: int):
    """`ref_lod_pair_sweep` of every slab from one camera."""
    cams = jnp.broadcast_to(jnp.asarray(cam_pos, jnp.float32).reshape(1, 3),
                            (slab_size.shape[0], 3))
    return ref_lod_pair_sweep(slab_mu, slab_size, slab_parent, slab_level,
                              slab_is_leaf, slab_valid, root_parent_expand,
                              cams, focal, tau, max_depth=max_depth)


def ref_stereo_merge(src_ranks: jax.Array, src_ids: jax.Array):
    """Vectorized merge oracle: stable sort by rank, drop INF and duplicates."""
    n_tiles, n_cat, l_len = src_ranks.shape
    r = src_ranks.reshape(n_tiles, -1)
    g = src_ids.reshape(n_tiles, -1)
    order = jnp.argsort(r, axis=1, stable=True)
    sr = jnp.take_along_axis(r, order, axis=1)
    sg = jnp.take_along_axis(g, order, axis=1)
    dup = jnp.concatenate([jnp.zeros((n_tiles, 1), bool),
                           sr[:, 1:] == sr[:, :-1]], axis=1)
    keep = (sr < 2**30) & ~dup
    comp_key = jnp.where(keep, jnp.arange(sr.shape[1])[None, :], 2**30)
    comp_order = jnp.argsort(comp_key, axis=1)
    out = jnp.take_along_axis(jnp.where(keep, sg, -1), comp_order, axis=1)
    return out[:, :l_len].astype(jnp.int32), keep.sum(1).astype(jnp.int32)


def ref_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Naive (materialized-scores) GQA attention oracle."""
    b, h, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = h // hkv
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / (d ** 0.5)
    row = jnp.arange(lq)[:, None]
    col = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask = mask & (col <= row)
    if window > 0:
        mask = mask & (col > row - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)
