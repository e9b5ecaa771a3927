"""Pallas TPU kernel: fully-streaming LoD slab sweep (paper §4.2).

One grid step sweeps `PAIRS` (client, slab) pairs — one pair per sublane
row, the slab's S nodes on lanes (S padded to a multiple of 128) — resident
in VMEM for the whole sweep: the TPU analogue of the paper's "blocks small
enough to fully reside in GPU shared memory".

Slabs are laid out in DFS preorder (repro.core.lod_tree), so node j's
subtree is the contiguous lane range [j, end[j]). That replaces the level
loop's parent gather (which Mosaic cannot lower across vregs) with a prefix
max: node j's parent-expand bit is false iff some valid node a < j that does
not expand on its own (proj ≤ τ) still covers it, i.e. iff
max_{a<j} end[a]·[valid(a) ∧ ¬gt(a)] > j. The prefix max is log2(S) lane
rotations. Its twin is the XLA sweep (`lod_search.sweep_slab_camera_pairs`),
which computes the same prefix max with a cumulative max; both are bitwise
the level-loop oracle (`repro.kernels.ref.ref_lod_pair_sweep`) on the cut.
Also emits the per-subtree temporal reuse radius ρ."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lod_search import lod_gt
from repro.kernels import resolve_interpret

PAIRS = 8     # pairs per grid step: one sublane tile
_LANES = 128
_INF = float("inf")  # plain literal — jnp constants would be captured as consts


def _pair_kernel(focal_ref, cam_ref, tau_ref, rpe_ref, mu_ref, size_ref,
                 end_ref, leaf_ref, valid_ref, cut_ref, rexp_ref, rho_ref):
    focal = focal_ref[0]
    tau = tau_ref[...]                                  # (P, 1)
    d = mu_ref[...] - cam_ref[...]                      # (3, P, S)
    dist2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]     # (P, S)
    size = size_ref[...]
    gt = lod_gt(size, dist2, focal, tau)
    valid = valid_ref[...] != 0
    leaf = leaf_ref[...] != 0

    s = gt.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, gt.shape, 1)
    stop = jnp.where(valid & ~gt, end_ref[...], 0)
    reach = jnp.where(lane >= 1, pltpu.roll(stop, 1, 1), 0)   # exclusive
    shift = 1
    while shift < s:
        reach = jnp.maximum(
            reach, jnp.where(lane >= shift, pltpu.roll(reach, shift, 1), 0))
        shift *= 2
    pexp = (rpe_ref[...] != 0) & (reach <= lane)
    expand = pexp & gt & valid
    cut_ref[...] = (pexp & (~gt | leaf) & valid).astype(jnp.int32)
    rexp_ref[...] = expand[:, 0:1].astype(jnp.int32)

    rstar = size * focal / tau
    dist = jnp.sqrt(jnp.sum(d * d, axis=0))
    margin = jnp.where(valid, jnp.abs(dist - rstar), _INF)
    rho_ref[...] = jnp.min(margin, axis=1, keepdims=True)


def _pad2(x, rows: int, cols: int):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1]))
                   + ((0, 0),) * (x.ndim - 2))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lod_pair_sweep_pallas(pair_mu, pair_size, pair_end, pair_is_leaf,
                          pair_valid, root_parent_expand, cam_pos, focal, tau,
                          *, interpret=None):
    """Sweep K pooled (client, slab) pairs — each with its OWN camera and τ —
    in one kernel dispatch. Inputs are the gathered pair tables ((K, S, 3)
    means, (K, S) sizes / DFS subtree ends / leaf / valid flags, (K,)
    root-parent-expand bits, (K, 3) cameras, scalar or (K,) taus); returns
    (in_cut (K,S) bool, root_expand (K,) bool, rho (K,) f32). Bit-parity
    with `lod_search.sweep_slab_camera_pairs` — the sweep behind
    `LodService(sweep_impl="pallas")`. `interpret=None` compiles on a TPU
    and interprets on the CPU (`repro.kernels.resolve_interpret`)."""
    k, s = pair_size.shape
    kp = -(-k // PAIRS) * PAIRS
    sp = -(-s // _LANES) * _LANES
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (k,))
    col = lambda x, dt: _pad2(jnp.asarray(x, dt).reshape(k, 1), kp, 1)
    cams = _pad2(jnp.asarray(cam_pos, jnp.float32), kp, 3).T[:, :, None]
    mu = jnp.moveaxis(_pad2(jnp.asarray(pair_mu, jnp.float32), kp, sp), -1, 0)
    row = lambda x: _pad2(x, kp, sp)
    block = lambda i: (i, 0)
    tile = pl.BlockSpec((PAIRS, sp), block)
    scalar = pl.BlockSpec((PAIRS, 1), block)
    cut, rexp, rho = pl.pallas_call(
        _pair_kernel,
        grid=(kp // PAIRS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((3, PAIRS, 1), lambda i: (0, i, 0)),
            scalar, scalar,
            pl.BlockSpec((3, PAIRS, sp), lambda i: (0, i, 0)),
            tile, tile, tile, tile,
        ],
        out_specs=[tile, scalar, scalar],
        out_shape=[
            jax.ShapeDtypeStruct((kp, sp), jnp.int32),
            jax.ShapeDtypeStruct((kp, 1), jnp.int32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(focal, jnp.float32).reshape(1), cams, col(taus, jnp.float32),
      col(root_parent_expand, jnp.int32), mu,
      row(jnp.asarray(pair_size, jnp.float32)),
      row(jnp.asarray(pair_end, jnp.int32)),
      row(pair_is_leaf.astype(jnp.int32)), row(pair_valid.astype(jnp.int32)))
    return cut[:k, :s] != 0, rexp[:k, 0] != 0, rho[:k, 0]


def lod_slab_sweep_pallas(slab_mu, slab_size, slab_end, slab_is_leaf,
                          slab_valid, root_parent_expand, cam_pos, focal, tau,
                          *, interpret=None):
    """Sweep all (Ns, S) slabs from ONE camera: the pair kernel with the
    camera and τ broadcast to every slab. Returns (in_cut (Ns,S) bool,
    root_expand (Ns,), rho (Ns,)); matches
    repro.core.lod_search._slab_sweep_one bit-for-bit on the cut."""
    ns = slab_size.shape[0]
    cams = jnp.broadcast_to(jnp.asarray(cam_pos, jnp.float32).reshape(1, 3),
                            (ns, 3))
    return lod_pair_sweep_pallas(slab_mu, slab_size, slab_end, slab_is_leaf,
                                 slab_valid, root_parent_expand, cams, focal,
                                 tau, interpret=interpret)
