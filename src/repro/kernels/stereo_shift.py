"""Pallas TPU kernel: SRU re-projection + line-buffer k-way merge (paper §5).

One grid cell = one right-eye tile. Inputs are the n_cat pre-compacted source
sequences (left columns cx..cx+n_cat−1 after the SRU's x-overlap include
test), each already depth-sorted. The kernel is a faithful merge unit: it
repeatedly selects the minimum-rank head among the n_cat circular-buffer rows
(INF when exhausted), emits it, advances that head, and drops duplicate ranks
(the same Gaussian arriving from two source columns)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

_INF = 2**30  # plain literal — jnp constants would be captured as consts


def _merge_kernel(ranks_ref, ids_ref, out_ref, cnt_ref, ovf_ref, *, n_cat: int,
                  l_len: int, out_len: int):
    ranks = ranks_ref[0]      # (n_cat, L) int32, INF-padded, each row sorted
    ids = ids_ref[0]          # (n_cat, L) int32

    def head_rank(ptrs):
        return jax.vmap(lambda row, p: jnp.where(p < l_len, row[jnp.minimum(p, l_len - 1)], _INF)
                        )(ranks, ptrs)

    def body(i, state):
        ptrs, out, count, prev = state
        hr = head_rank(ptrs)
        c = jnp.argmin(hr).astype(jnp.int32)
        r = hr[c]
        valid = r < _INF
        dup = r == prev
        emit = valid & ~dup
        write = emit & (count < out_len)   # capacity full → count only (overflow)
        gid = jax.vmap(lambda row, p: row[jnp.minimum(p, l_len - 1)])(ids, ptrs)[c]
        out = jnp.where(write, out.at[jnp.minimum(count, out_len - 1)].set(gid), out)
        count = count + emit.astype(jnp.int32)
        ptrs = ptrs.at[c].add(jnp.where(valid, 1, 0))
        prev = jnp.where(valid, r, prev)
        return ptrs, out, count, prev

    init = (jnp.zeros((n_cat,), jnp.int32),
            jnp.full((out_len,), -1, jnp.int32),
            jnp.int32(0),
            -jnp.ones((), jnp.int32))
    _, out, count, _ = jax.lax.fori_loop(0, n_cat * l_len, body, init)
    out_ref[0] = out
    cnt_ref[0] = count
    ovf_ref[0] = count > out_len


@functools.partial(jax.jit, static_argnames=("interpret",))
def stereo_merge_pallas(src_ranks: jax.Array, src_ids: jax.Array, *,
                        interpret=None):
    """src_ranks/src_ids: (n_tiles, n_cat, L) — per right tile, the n_cat
    include-filtered sorted source rows (INF/-1 padded).
    Returns (merged ids (n_tiles, L), counts (n_tiles,), overflow (n_tiles,)).

    `overflow[t]` flags a merge that produced more unique entries than the
    output capacity — the write loop drops the tail, so a True flag means
    tile t's list is TRUNCATED (counts still reports the untruncated total;
    callers surface the flag on the merged TileLists instead of silently
    clamping).

    Interpret-only: the per-row head gathers span several vregs, which
    Mosaic cannot lower, so a compiled call (a TPU backend, or
    `interpret=False`) raises instead of silently interpreting — use the
    XLA merge (`ops.stereo_merge(use_pallas=False)`) there."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "stereo_merge_pallas does not lower to Mosaic; use the XLA merge")
    n_tiles, n_cat, l_len = src_ranks.shape
    kernel = functools.partial(_merge_kernel, n_cat=n_cat, l_len=l_len,
                               out_len=l_len)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, n_cat, l_len), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, n_cat, l_len), lambda t: (t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l_len), lambda t: (t, 0)),
            pl.BlockSpec((1,), lambda t: (t,)),
            pl.BlockSpec((1,), lambda t: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, l_len), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.bool_),
        ],
        interpret=True,
    )(src_ranks, src_ids)
