"""Pallas TPU kernel: flash attention (online softmax) for the LM framework.

Grid (batch, q_heads, q_blocks); GQA is handled zero-copy by the K/V
BlockSpec index maps (head h reads kv head h // group). The kv loop streams
(block_k, head_dim) chunks through VMEM with the usual running
(max, denom, acc) carry. Supports causal and sliding-window (local) masking —
the two patterns the assigned architectures need. The dry-run path uses the
pure-JAX chunked implementation in repro.models.attention (this kernel is the
TPU hot-spot realization, validated in interpret mode)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                  seq_k: int, causal: bool, window: int, scale: float):
    qb = pl.program_id(2)
    q = q_ref[0, 0] * scale                       # (Bq, D)
    row = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    nk = pl.cdiv(seq_k, block_k)

    def body(kb, carry):
        m_i, l_i, acc = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[0, 0], kb * block_k, block_k, 0)
        v = jax.lax.dynamic_slice_in_dim(v_ref[0, 0], kb * block_k, block_k, 0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (Bq, Bk)
        col = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = col < seq_k
        if causal:
            mask = mask & (col <= row)
        if window > 0:
            mask = mask & (col > row - window)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((block_q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    m_i, l_i, acc = jax.lax.fori_loop(0, nk, body, init)
    o_ref[0, 0] = (acc / jnp.maximum(l_i, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret=None) -> jax.Array:
    """q: (B, H, Lq, D); k, v: (B, Hkv, Lk, D) with H % Hkv == 0."""
    b, h, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                               seq_k=lk, causal=causal, window=window,
                               scale=scale)
    grid = (b, h, pl.cdiv(lq, block_q))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, hh, qq: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda bb, hh, qq: (bb, hh // group, 0, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda bb, hh, qq: (bb, hh // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bb, hh, qq: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
