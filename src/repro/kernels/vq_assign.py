"""Pallas TPU kernel: vector-quantization codeword assignment (paper §4.3).

argmin_k ||x − c_k||² = argmin_k (||c_k||² − 2·x·c_kᵀ) — the dominant term is
a (Bm, D) × (D, Bk) matmul that maps straight onto the MXU. The codebook is
tiled over the minor grid axis with a running (best_val, best_idx) carried in
the output block (revisited sequentially per TPU grid semantics)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _vq_kernel(x_ref, cb_ref, c2_ref, val_ref, idx_ref, *, block_k: int):
    kb = pl.program_id(1)
    x = x_ref[...]                     # (Bm, D)
    cb = cb_ref[...]                   # (Bk, D)
    c2 = c2_ref[...]                   # (Bk,)
    scores = c2[None, :] - 2.0 * jnp.dot(x, cb.T,
                                         preferred_element_type=jnp.float32)
    local_idx = jnp.argmin(scores, axis=1).astype(jnp.int32)
    local_val = jnp.min(scores, axis=1)
    global_idx = local_idx + kb * block_k

    @pl.when(kb == 0)
    def _init():
        val_ref[...] = local_val
        idx_ref[...] = global_idx

    @pl.when(kb > 0)
    def _accum():
        better = local_val < val_ref[...]
        val_ref[...] = jnp.where(better, local_val, val_ref[...])
        idx_ref[...] = jnp.where(better, global_idx, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def vq_assign_pallas(x: jax.Array, codebook: jax.Array, *, block_m: int = 256,
                     block_k: int = 128, interpret=None) -> jax.Array:
    """(M, D) × (Kc, D) → (M,) nearest codeword indices."""
    m, d = x.shape
    kc = codebook.shape[0]
    block_m = min(block_m, m)
    block_k = min(block_k, kc)
    grid = (pl.cdiv(m, block_m), pl.cdiv(kc, block_k))
    c2 = jnp.sum(codebook * codebook, axis=-1)
    kernel = functools.partial(_vq_kernel, block_k=block_k)
    val, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, k: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, k: (k, 0)),
            pl.BlockSpec((block_k,), lambda i, k: (k,)),
        ],
        out_specs=[
            pl.BlockSpec((block_m,), lambda i, k: (i,)),
            pl.BlockSpec((block_m,), lambda i, k: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, codebook, c2)
    del val
    return idx
