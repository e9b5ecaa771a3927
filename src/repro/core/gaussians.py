"""Gaussian primitive container + procedural city-scale scene generation.

The scene generator stands in for the Urban/Mega/HierGS captures (not shipped
offline). It produces leaf Gaussians with city statistics: a ground plane, a
grid of buildings (walls/roofs), and street clutter, with view-dependent color
via spherical harmonics. Scale is a parameter — tests use hundreds of leaves,
benchmarks use up to millions.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# SH constants (degree <= 3 supported; default degree 1 keeps tests light).
SH_C0 = 0.28209479177387814
SH_C1 = 0.4886025119029199


def sh_dim(degree: int) -> int:
    return (degree + 1) ** 2


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Gaussians:
    """Structure-of-arrays Gaussian container (the smallest rendering primitive).

    mu:        (N, 3) float32 world-space centers
    log_scale: (N, 3) float32 per-axis log std-dev
    quat:      (N, 4) float32 rotation quaternion (w, x, y, z), normalized
    opacity:   (N,)   float32 in (0, 1)
    sh:        (N, K, 3) float32 spherical-harmonic color coefficients
    """

    mu: jax.Array
    log_scale: jax.Array
    quat: jax.Array
    opacity: jax.Array
    sh: jax.Array

    @property
    def n(self) -> int:
        return self.mu.shape[0]

    @property
    def sh_degree(self) -> int:
        return int(np.sqrt(self.sh.shape[1])) - 1

    def __getitem__(self, idx) -> "Gaussians":
        return Gaussians(
            mu=self.mu[idx],
            log_scale=self.log_scale[idx],
            quat=self.quat[idx],
            opacity=self.opacity[idx],
            sh=self.sh[idx],
        )

    def slice_rows(self, idx: jax.Array) -> "Gaussians":
        """Gather rows by (possibly traced) index array."""
        return Gaussians(
            mu=jnp.take(self.mu, idx, axis=0),
            log_scale=jnp.take(self.log_scale, idx, axis=0),
            quat=jnp.take(self.quat, idx, axis=0),
            opacity=jnp.take(self.opacity, idx, axis=0),
            sh=jnp.take(self.sh, idx, axis=0),
        )

    @staticmethod
    def concat(parts: Tuple["Gaussians", ...]) -> "Gaussians":
        return Gaussians(
            mu=jnp.concatenate([p.mu for p in parts], axis=0),
            log_scale=jnp.concatenate([p.log_scale for p in parts], axis=0),
            quat=jnp.concatenate([p.quat for p in parts], axis=0),
            opacity=jnp.concatenate([p.opacity for p in parts], axis=0),
            sh=jnp.concatenate([p.sh for p in parts], axis=0),
        )

    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (self.mu, self.log_scale, self.quat, self.opacity, self.sh))


def bytes_per_gaussian(sh_degree: int, raw: bool = True) -> int:
    """Uncompressed storage per Gaussian in float32 (mu3+ls3+q4+op1 + sh)."""
    k = sh_dim(sh_degree)
    return 4 * (3 + 3 + 4 + 1 + 3 * k)


def quat_to_rotmat(q: jax.Array) -> jax.Array:
    """(…, 4) wxyz quaternion → (…, 3, 3) rotation matrix. A numpy input
    stays in numpy, so offline host code (the LoD tree build) dispatches
    nothing to the accelerator."""
    xp = np if isinstance(q, np.ndarray) else jnp
    q = q / (xp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    r = xp.stack(
        [
            1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y),
            2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x),
            2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y),
        ],
        axis=-1,
    )
    return r.reshape(q.shape[:-1] + (3, 3))


def covariance(g: Gaussians) -> jax.Array:
    """(N, 3, 3) world-space covariance R S S^T R^T."""
    rot = quat_to_rotmat(g.quat)
    s = jnp.exp(g.log_scale)
    rs = rot * s[..., None, :]
    return rs @ jnp.swapaxes(rs, -1, -2)


def eval_sh(sh: jax.Array, dirs: jax.Array) -> jax.Array:
    """Evaluate SH color along unit view directions.

    sh:   (..., K, 3), dirs: (..., 3) unit vectors → (..., 3) RGB (clipped >= 0).
    Supports K in {1, 4, 9, 16}; higher bands of the basis are standard real SH.
    """
    k = sh.shape[-2]
    c = SH_C0 * sh[..., 0, :]
    if k >= 4:
        x, y, z = dirs[..., 0:1], dirs[..., 1:2], dirs[..., 2:3]
        c = c - SH_C1 * y * sh[..., 1, :] + SH_C1 * z * sh[..., 2, :] - SH_C1 * x * sh[..., 3, :]
    if k >= 9:
        x, y, z = dirs[..., 0:1], dirs[..., 1:2], dirs[..., 2:3]
        xx, yy, zz = x * x, y * y, z * z
        xy, yz, xz = x * y, y * z, x * z
        c = (c
             + 1.0925484305920792 * xy * sh[..., 4, :]
             + (-1.0925484305920792) * yz * sh[..., 5, :]
             + 0.31539156525252005 * (2.0 * zz - xx - yy) * sh[..., 6, :]
             + (-1.0925484305920792) * xz * sh[..., 7, :]
             + 0.5462742152960396 * (xx - yy) * sh[..., 8, :])
    c = c + 0.5
    return jnp.maximum(c, 0.0)


# ---------------------------------------------------------------------------
# Procedural city scene
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CityConfig:
    """Procedural city parameters (world units are meters)."""

    blocks_x: int = 4
    blocks_y: int = 4
    block_size: float = 40.0
    street_width: float = 12.0
    max_height: float = 45.0
    leaf_density: float = 0.6       # Gaussians per square meter of surface
    sh_degree: int = 1
    seed: int = 0

    @property
    def extent(self) -> Tuple[float, float]:
        pitch = self.block_size + self.street_width
        return (self.blocks_x * pitch, self.blocks_y * pitch)


def _surface_points(rng: np.random.Generator, n: int, origin, u_vec, v_vec) -> np.ndarray:
    """Sample n points on a parallelogram surface patch."""
    uv = rng.random((n, 2))
    return (np.asarray(origin)[None, :]
            + uv[:, :1] * np.asarray(u_vec)[None, :]
            + uv[:, 1:] * np.asarray(v_vec)[None, :])


def generate_city(cfg: CityConfig) -> Gaussians:
    """Generate leaf Gaussians for a procedural city (numpy; offline step)."""
    rng = np.random.default_rng(cfg.seed)
    pitch = cfg.block_size + cfg.street_width
    pts, scales, colors = [], [], []

    def add_patch(origin, u_vec, v_vec, base_color, scale_m):
        area = np.linalg.norm(np.cross(u_vec, v_vec))
        n = max(4, int(area * cfg.leaf_density))
        p = _surface_points(rng, n, origin, u_vec, v_vec)
        pts.append(p)
        scales.append(np.full((n, 3), scale_m) * rng.uniform(0.6, 1.6, (n, 3)))
        col = np.clip(base_color + rng.normal(0, 0.08, (n, 3)), 0.02, 0.98)
        colors.append(col)

    # Ground plane per block cell (streets included)
    ex, ey = cfg.extent
    n_ground = max(16, int(ex * ey * cfg.leaf_density * 0.08))
    gp = rng.random((n_ground, 2)) * np.array([ex, ey])
    pts.append(np.concatenate([gp, np.zeros((n_ground, 1))], axis=1))
    scales.append(np.full((n_ground, 3), 1.2) * rng.uniform(0.7, 1.4, (n_ground, 3)))
    colors.append(np.clip(0.35 + rng.normal(0, 0.05, (n_ground, 3)), 0.05, 0.9))

    for bx in range(cfg.blocks_x):
        for by in range(cfg.blocks_y):
            x0 = bx * pitch + cfg.street_width / 2
            y0 = by * pitch + cfg.street_width / 2
            w = cfg.block_size * rng.uniform(0.5, 0.95)
            d = cfg.block_size * rng.uniform(0.5, 0.95)
            h = cfg.max_height * rng.uniform(0.15, 1.0)
            base = np.clip(rng.uniform(0.25, 0.8, 3), 0, 1)
            sc = 0.8
            # four walls + roof
            add_patch([x0, y0, 0], [w, 0, 0], [0, 0, h], base, sc)
            add_patch([x0, y0 + d, 0], [w, 0, 0], [0, 0, h], base * 0.9, sc)
            add_patch([x0, y0, 0], [0, d, 0], [0, 0, h], base * 0.95, sc)
            add_patch([x0 + w, y0, 0], [0, d, 0], [0, 0, h], base * 0.85, sc)
            add_patch([x0, y0, h], [w, 0, 0], [0, d, 0], base * 1.1, sc)

    mu = np.concatenate(pts, axis=0).astype(np.float32)
    scale = np.concatenate(scales, axis=0).astype(np.float32)
    col = np.concatenate(colors, axis=0).astype(np.float32)
    n = mu.shape[0]

    quat = rng.normal(size=(n, 4)).astype(np.float32)
    quat /= np.linalg.norm(quat, axis=1, keepdims=True)
    opacity = rng.uniform(0.35, 0.95, n).astype(np.float32)

    k = sh_dim(cfg.sh_degree)
    sh = np.zeros((n, k, 3), dtype=np.float32)
    sh[:, 0, :] = (col - 0.5) / SH_C0  # DC term reproduces base color
    if k > 1:
        # view dependence is LOW-RANK in real captures (a few material/BRDF
        # prototypes per scene) — sample from a small dictionary + jitter.
        # This is also the property Compact3DGS-style VQ exploits.
        n_mat = 32
        protos = rng.normal(0, 0.12, (n_mat, k - 1, 3))
        mat = rng.integers(0, n_mat, n)
        sh[:, 1:, :] = protos[mat] + rng.normal(0, 0.015, (n, k - 1, 3))

    return Gaussians(
        mu=jnp.asarray(mu),
        log_scale=jnp.asarray(np.log(np.maximum(scale, 1e-4))),
        quat=jnp.asarray(quat),
        opacity=jnp.asarray(opacity),
        sh=jnp.asarray(sh),
    )


def random_gaussians(rng: np.random.Generator, n: int, sh_degree: int = 1,
                     extent: float = 10.0) -> Gaussians:
    """Uniform random Gaussians — used by unit tests and kernels sweeps."""
    k = sh_dim(sh_degree)
    quat = rng.normal(size=(n, 4)).astype(np.float32)
    quat /= np.linalg.norm(quat, axis=1, keepdims=True) + 1e-12
    return Gaussians(
        mu=jnp.asarray(rng.uniform(-extent, extent, (n, 3)).astype(np.float32)),
        log_scale=jnp.asarray(np.log(rng.uniform(0.05, 0.6, (n, 3))).astype(np.float32)),
        quat=jnp.asarray(quat),
        opacity=jnp.asarray(rng.uniform(0.2, 0.95, n).astype(np.float32)),
        sh=jnp.asarray(rng.normal(0, 0.35, (n, k, 3)).astype(np.float32)),
    )
