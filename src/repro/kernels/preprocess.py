"""Pallas TPU kernel: shared stereo EWA preprocessing (paper Fig. 13 left).

One pass per Gaussian block: world→cam transform, perspective Jacobian,
2D covariance + conic, conservative α-extent, per-eye SH color, disparity.
Pure VPU vector math over (B,) lanes; blocks stream HBM→VMEM. Camera is a
packed (P,) parameter vector (pos, rot, focal, principal point, near/far,
baseline, eye positions)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from repro.core.gaussians import SH_C0, SH_C1
from repro.core.projection import ALPHA_MIN, COV_BLUR

# packed camera params layout
_P_POS = 0          # 3
_P_ROT = 3          # 9 (row-major world→cam)
_P_FOCAL = 12
_P_CX = 13
_P_CY = 14
_P_NEAR = 15
_P_FAR = 16
_P_BASE = 17
_P_LPOS = 18        # 3 left eye pos
_P_RPOS = 21        # 3 right eye pos
_P_W = 24           # widened width
_P_H = 25
P_LEN = 26


def pack_camera(rig, wide) -> jax.Array:
    w2c = wide.rot.T
    return jnp.concatenate([
        wide.pos.reshape(3), w2c.reshape(9),
        jnp.asarray([wide.focal, wide.cx, wide.cy, wide.near, wide.far,
                     rig.baseline], jnp.float32),
        rig.left.pos.reshape(3), rig.right.pos.reshape(3),
        jnp.asarray([wide.width, wide.height], jnp.float32),
    ]).astype(jnp.float32)


def _sh_color(sh, dirs, k: int):
    c = SH_C0 * sh[:, 0, :]
    if k >= 4:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        c = c - SH_C1 * y * sh[:, 1, :] + SH_C1 * z * sh[:, 2, :] - SH_C1 * x * sh[:, 3, :]
    if k >= 9:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        xx, yy, zz, xy, yz, xz = x * x, y * y, z * z, x * y, y * z, x * z
        c = (c + 1.0925484305920792 * xy * sh[:, 4, :]
             - 1.0925484305920792 * yz * sh[:, 5, :]
             + 0.31539156525252005 * (2.0 * zz - xx - yy) * sh[:, 6, :]
             - 1.0925484305920792 * xz * sh[:, 7, :]
             + 0.5462742152960396 * (xx - yy) * sh[:, 8, :])
    return jnp.maximum(c + 0.5, 0.0)


def _preprocess_kernel(cam_ref, mu_ref, ls_ref, quat_ref, opa_ref, sh_ref,
                       out_ref, *, sh_k: int):
    prm = cam_ref[...]
    pos = prm[_P_POS:_P_POS + 3]
    w2c = prm[_P_ROT:_P_ROT + 9].reshape(3, 3)
    f = prm[_P_FOCAL]
    cx, cy = prm[_P_CX], prm[_P_CY]
    near, far = prm[_P_NEAR], prm[_P_FAR]
    baseline = prm[_P_BASE]
    lpos = prm[_P_LPOS:_P_LPOS + 3]
    rpos = prm[_P_RPOS:_P_RPOS + 3]
    width, height = prm[_P_W], prm[_P_H]

    mu = mu_ref[...]
    t = (mu - pos[None, :]) @ w2c.T                      # world→cam
    z = t[:, 2]
    inv_z = 1.0 / jnp.maximum(z, 1e-6)
    mx = f * t[:, 0] * inv_z + cx
    my = f * t[:, 1] * inv_z + cy

    # R S S R^T from quaternion
    q = quat_ref[...]
    q = q / (jnp.sqrt(jnp.sum(q * q, -1, keepdims=True)) + 1e-12)
    w_, x_, y_, z_ = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    r00 = 1 - 2 * (y_ * y_ + z_ * z_); r01 = 2 * (x_ * y_ - w_ * z_); r02 = 2 * (x_ * z_ + w_ * y_)
    r10 = 2 * (x_ * y_ + w_ * z_); r11 = 1 - 2 * (x_ * x_ + z_ * z_); r12 = 2 * (y_ * z_ - w_ * x_)
    r20 = 2 * (x_ * z_ - w_ * y_); r21 = 2 * (y_ * z_ + w_ * x_); r22 = 1 - 2 * (x_ * x_ + y_ * y_)
    rot = jnp.stack([jnp.stack([r00, r01, r02], -1),
                     jnp.stack([r10, r11, r12], -1),
                     jnp.stack([r20, r21, r22], -1)], -2)  # (B,3,3)
    s = jnp.exp(ls_ref[...])
    rs = rot * s[:, None, :]
    cov3 = rs @ jnp.swapaxes(rs, -1, -2)

    zero = jnp.zeros_like(z)
    j = jnp.stack([
        jnp.stack([f * inv_z, zero, -f * t[:, 0] * inv_z * inv_z], -1),
        jnp.stack([zero, f * inv_z, -f * t[:, 1] * inv_z * inv_z], -1),
    ], -2)                                               # (B,2,3)
    jw = j @ jnp.broadcast_to(w2c, (j.shape[0], 3, 3))
    cov2 = jw @ cov3 @ jnp.swapaxes(jw, -1, -2)
    a = cov2[:, 0, 0] + COV_BLUR
    b = cov2[:, 0, 1]
    c = cov2[:, 1, 1] + COV_BLUR
    det = jnp.maximum(a * c - b * b, 1e-12)

    opa = opa_ref[...]
    tau2 = 2.0 * jnp.log(jnp.maximum(opa, ALPHA_MIN) / ALPHA_MIN)
    ext_x = jnp.sqrt(jnp.maximum(tau2, 0.0) * a)
    ext_y = jnp.sqrt(jnp.maximum(tau2, 0.0) * c)

    sh = sh_ref[...].reshape(mu.shape[0], sh_k, 3)
    dl = mu - lpos[None, :]
    dr = mu - rpos[None, :]
    dl = dl / (jnp.sqrt(jnp.sum(dl * dl, -1, keepdims=True)) + 1e-12)
    dr = dr / (jnp.sqrt(jnp.sum(dr * dr, -1, keepdims=True)) + 1e-12)
    col_l = _sh_color(sh, dl, sh_k)
    col_r = _sh_color(sh, dr, sh_k)

    disparity = baseline * f * inv_z
    visible = ((z > near) & (z < far) & (opa > ALPHA_MIN)
               & (mx + ext_x >= 0.0) & (mx - ext_x <= width)
               & (my + ext_y >= 0.0) & (my - ext_y <= height))

    out = jnp.stack([
        mx, my, z, c / det, -b / det, a / det, ext_x, ext_y,
        col_l[:, 0], col_l[:, 1], col_l[:, 2],
        col_r[:, 0], col_r[:, 1], col_r[:, 2],
        opa, disparity, visible.astype(jnp.float32),
    ], axis=-1)
    out_ref[...] = out


OUT_COLS = 17


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def preprocess_pallas(mu, log_scale, quat, opacity, sh, cam_params, *,
                      block: int = 256, interpret=None) -> jax.Array:
    """Returns (M, 17): [mean2d(2), depth, conic(3), ext(2), color_l(3),
    color_r(3), opacity, disparity, visible].

    Interpret-only: the (B, 3)-wide blocks and per-Gaussian 3×3 matmuls do
    not lower to Mosaic, so a compiled call (a TPU backend, or
    `interpret=False`) raises instead of silently interpreting — use the
    XLA projection (`ops.preprocess(use_pallas=False)`) there."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "preprocess_pallas does not lower to Mosaic; use the XLA projection")
    m = mu.shape[0]
    sh_k = sh.shape[1]
    block = min(block, m)
    grid = (pl.cdiv(m, block),)
    kernel = functools.partial(_preprocess_kernel, sh_k=sh_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((P_LEN,), lambda i: (0,)),
            pl.BlockSpec((block, 3), lambda i: (i, 0)),
            pl.BlockSpec((block, 3), lambda i: (i, 0)),
            pl.BlockSpec((block, 4), lambda i: (i, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block, sh_k * 3), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, OUT_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, OUT_COLS), jnp.float32),
        interpret=True,
    )(cam_params, mu, log_scale, quat, opacity, sh.reshape(m, sh_k * 3))
