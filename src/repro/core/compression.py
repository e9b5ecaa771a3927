"""Δcut compression (paper §4.3 "Compression" — the paper claims no novelty
here and neither do we; this follows Compact3DGS-style attribute coding).

  * SH: DC band kept at fp16; AC bands vector-quantized against a k-means
    codebook fit offline on the scene (the client holds the codebook — the
    hardware decoder's "codebook buffer" of §5).
  * position / log-scale: 16-bit fixed point over the scene range;
  * quaternion: 16-bit per component in [-1, 1];
  * opacity: 16-bit in [0, 1].

Everything is jittable; the VQ assignment hot spot also exists as a Pallas
kernel (repro.kernels.vq_assign) with this module as its oracle-consistent
fallback.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gaussians import Gaussians
from repro.serve import tracing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Codec:
    codebook: jax.Array     # (Kc, D) f32, D = (K-1)*3 SH AC dims (Kc>=1)
    pos_lo: jax.Array       # (3,)
    pos_hi: jax.Array       # (3,)
    scale_lo: jax.Array     # ()
    scale_hi: jax.Array     # ()

    @property
    def k_codes(self) -> int:
        return self.codebook.shape[0]

    def code_bytes(self) -> int:
        return max(1, int(np.ceil(np.log2(max(self.k_codes, 2)) / 8)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EncodedGaussians:
    dc: jax.Array        # (M, 3) f16
    code: jax.Array      # (M,) int32 — VQ index (wire width = codec.code_bytes())
    pos_q: jax.Array     # (M, 3) uint16
    scale_q: jax.Array   # (M, 3) uint16
    quat_q: jax.Array    # (M, 4) int16
    opa_q: jax.Array     # (M,) uint16

    @property
    def m(self) -> int:
        return self.dc.shape[0]


def wire_bytes_per_gaussian(codec: Codec) -> int:
    """16-bit attrs + fp16 DC + VQ code index (paper §4.3 layout)."""
    return 3 * 2 + codec.code_bytes() + 3 * 2 + 3 * 2 + 4 * 2 + 2


# ---------------------------------------------------------------------------
# k-means codebook (offline)
# ---------------------------------------------------------------------------


def vq_assign_ref(x: jax.Array, codebook: jax.Array) -> jax.Array:
    """(M, D) × (Kc, D) → (M,) nearest-codeword indices (pure jnp oracle)."""
    # argmin ||x - c||² = argmin (||c||² − 2 x·c)
    c2 = jnp.sum(codebook * codebook, axis=-1)
    scores = c2[None, :] - 2.0 * (x @ codebook.T)
    return jnp.argmin(scores, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("iters",))
def _kmeans(x: jax.Array, init: jax.Array, iters: int) -> jax.Array:
    def body(codebook, _):
        idx = vq_assign_ref(x, codebook)
        k = codebook.shape[0]
        sums = jax.ops.segment_sum(x, idx, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), idx,
                                   num_segments=k)
        new = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts[:, None], 1.0),
                        codebook)
        return new, None

    cb, _ = jax.lax.scan(body, init, None, length=iters)
    return cb


def fit_codec(g: Gaussians, k_codes: int = 256, iters: int = 8,
              seed: int = 0, sample: int = 65536) -> Codec:
    """Fit the codec on scene statistics (offline; cloud side)."""
    rng = np.random.default_rng(seed)
    n, k = g.sh.shape[0], g.sh.shape[1]
    d = max((k - 1) * 3, 1)
    if k > 1:
        ac = np.asarray(g.sh[:, 1:, :].reshape(n, -1))
    else:
        ac = np.zeros((n, 1), np.float32)
    take = rng.choice(n, size=min(sample, n), replace=False)
    xs = jnp.asarray(ac[take])
    init = jnp.asarray(ac[rng.choice(n, size=min(k_codes, n), replace=False)])
    if init.shape[0] < k_codes:  # tiny scenes: tile
        reps = int(np.ceil(k_codes / init.shape[0]))
        init = jnp.tile(init, (reps, 1))[:k_codes]
        init = init + 1e-4 * jnp.asarray(rng.normal(size=init.shape), jnp.float32)
    codebook = _kmeans(xs, init, iters)

    mu = np.asarray(g.mu)
    ls = np.asarray(g.log_scale)
    pad = 1e-3
    return Codec(
        codebook=codebook.reshape(k_codes, d),
        pos_lo=jnp.asarray(mu.min(0) - pad),
        pos_hi=jnp.asarray(mu.max(0) + pad),
        scale_lo=jnp.asarray(np.float32(ls.min() - pad)),
        scale_hi=jnp.asarray(np.float32(ls.max() + pad)),
    )


# ---------------------------------------------------------------------------
# encode / decode (jittable)
# ---------------------------------------------------------------------------


def _quant16(x, lo, hi):
    q = (x - lo) / jnp.maximum(hi - lo, 1e-12) * 65535.0
    return jnp.clip(jnp.round(q), 0, 65535).astype(jnp.uint16)


def _dequant16(q, lo, hi):
    return q.astype(jnp.float32) / 65535.0 * (hi - lo) + lo


@jax.jit
@tracing.scoped("delta.union")
def encode(codec: Codec, g: Gaussians) -> EncodedGaussians:
    n, k = g.sh.shape[0], g.sh.shape[1]
    if k > 1:
        ac = g.sh[:, 1:, :].reshape(n, -1)
        code = vq_assign_ref(ac, codec.codebook)
    else:
        code = jnp.zeros((n,), jnp.int32)
    quat = g.quat / (jnp.linalg.norm(g.quat, axis=-1, keepdims=True) + 1e-12)
    return EncodedGaussians(
        dc=g.sh[:, 0, :].astype(jnp.float16),
        code=code,
        pos_q=_quant16(g.mu, codec.pos_lo, codec.pos_hi),
        scale_q=_quant16(g.log_scale, codec.scale_lo, codec.scale_hi),
        quat_q=jnp.clip(jnp.round(quat * 32767.0), -32767, 32767).astype(jnp.int16),
        opa_q=_quant16(g.opacity, 0.0, 1.0),
    )


@functools.partial(jax.jit, static_argnames=("sh_k",))
def decode(codec: Codec, e: EncodedGaussians, sh_k: int) -> Gaussians:
    m = e.m
    dc = e.dc.astype(jnp.float32)
    if sh_k > 1:
        ac = jnp.take(codec.codebook, e.code, axis=0).reshape(m, sh_k - 1, 3)
        sh = jnp.concatenate([dc[:, None, :], ac], axis=1)
    else:
        sh = dc[:, None, :]
    quat = e.quat_q.astype(jnp.float32) / 32767.0
    quat = quat / (jnp.linalg.norm(quat, axis=-1, keepdims=True) + 1e-12)
    return Gaussians(
        mu=_dequant16(e.pos_q, codec.pos_lo, codec.pos_hi),
        log_scale=_dequant16(e.scale_q, codec.scale_lo, codec.scale_hi),
        quat=quat,
        opacity=_dequant16(e.opa_q, 0.0, 1.0),
        sh=sh,
    )


def encode_rows(codec: Codec, g: Gaussians, ids: jax.Array
                ) -> EncodedGaussians:
    """Gather rows `ids` (-1 padded → row 0) from a Gaussian table and encode
    them: the gather + quantize/pack helper of the single-client pipeline's
    unicast Δcut and the per-client reference encoder (the fleet union
    gathers its shipped rows itself, repro.serve.delta_path)."""
    return encode(codec, g.slice_rows(jnp.clip(ids, 0)))


def roundtrip(codec: Codec, g: Gaussians) -> Gaussians:
    return decode(codec, encode(codec, g), g.sh.shape[1])


def max_position_error(codec: Codec) -> float:
    """Worst-case quantization error in meters (half an LSB per axis)."""
    rng = np.asarray(codec.pos_hi) - np.asarray(codec.pos_lo)
    return float(np.linalg.norm(rng / 65535.0 / 2.0))
