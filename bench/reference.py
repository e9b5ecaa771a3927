"""Plain reference of what a served client must end up with, and the
comparison that decides `correct`.

It imports nothing of the program. Its inputs are the scene, as plain
arrays of the LoD tree's nodes (`Scene`: positions, sizes, parent links,
leaf and padding flags, and the raw attributes), and what the harness
captured of the timed path: each checked client's pose and threshold, the
cut the service holds for it, and the Δ rows it decoded from the shared
stream.

The semantics it checks, as stated by the configuration:

* LoD cut (Kerbl et al. 2024; Nebula §4.2). A node's projected size is
  size·focal/distance. A node is expanded when its parent is expanded (the
  root's parent counts as expanded) and its projected size exceeds τ; it is
  in the cut when its parent is expanded and it is not (or is a leaf). The
  configuration states the test in float32, decided without a divide or a
  square root: (size·focal)² > τ²·max(dx²+dy²+dz², 10⁻¹²), each operation
  rounded to float32 in that order. The reference evaluates that, level by
  level. A node whose two sides lie within `TIE` of each other is a tie:
  another program may round it either way (XLA may fuse a multiply and an
  add), so a cut that differs from the reference only in a tie node and
  the nodes below it agrees with it. Every other node must agree.
* Δ rows. Every row a client decodes is its node's raw row, quantised as
  the wire format states: positions and log-scales in 16-bit fixed point
  over the scene's range, opacity in 16-bit over [0, 1], the normalised
  quaternion in signed 16-bit, the SH DC band in float16. A row's gap is
  its worst attribute error in units of that attribute's quantisation
  step (the SH AC bands are vector-quantised against a codebook fitted to
  the scene and are not compared).
* Residency. The client's store is replayed from the rows it decoded, with
  the reuse window's eviction rule (a row leaves the store once it has
  been out of the cut for more than w* of the client's syncs). After each
  sync every node of the client's cut is in its store or among the rows
  the stream still owes it; no row arrives that the client holds already,
  and none that no recent cut asked for.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

Q16 = 65535.0
Q_QUAT16 = 32767.0
TIE = 2.0 ** -18   # relative gap of a tie: 64 float32 ulps, far above the
#                    few ulps by which two roundings of the test can differ


@dataclasses.dataclass
class Scene:
    """The LoD tree as plain arrays over node ids (padding rows included)."""

    mu: np.ndarray        # (N, 3) float32
    size: np.ndarray      # (N,) float32 bounding radius
    parent: np.ndarray    # (N,) int64, -1 for a root, ignored for padding
    is_leaf: np.ndarray   # (N,) bool
    valid: np.ndarray     # (N,) bool, False for padding rows
    log_scale: np.ndarray  # (N, 3) float32
    quat: np.ndarray      # (N, 4) float32
    opacity: np.ndarray   # (N,) float32
    dc: np.ndarray        # (N, 3) float32 SH DC band

    def __post_init__(self):
        self.n = self.mu.shape[0]
        # depth of every valid node by pointer jumping; nodes of one level
        # only read expand bits of the level above
        depth = np.zeros(self.n, np.int64)
        p = np.where(self.valid, self.parent, -1)
        while np.any(p >= 0):
            live = p >= 0
            depth[live] += 1
            p = np.where(live, np.where(self.valid[np.maximum(p, 0)],
                                        self.parent[np.maximum(p, 0)], -1), -1)
        ids = np.nonzero(self.valid)[0]
        ids = ids[np.argsort(depth[ids], kind="stable")]
        bounds = np.searchsorted(depth[ids], np.arange(depth.max() + 2))
        self.levels = [ids[bounds[l]:bounds[l + 1]]
                       for l in range(len(bounds) - 1)]
        self.levels = [lv for lv in self.levels if lv.size]
        # the wire format's quantisation steps, from the scene itself
        self.pos_step = np.maximum(self.mu.max(0).astype(np.float64)
                                   - self.mu.min(0), 1e-12) / Q16
        ls = self.log_scale.astype(np.float64)
        self.scale_step = max(ls.max() - ls.min(), 1e-12) / Q16


def lod_cut(scene: Scene, cam, focal: float, tau: float,
            dtype=np.float32):
    """(sorted node ids of the LoD cut at camera position `cam`, bool mask
    of the tie nodes), every operation of the test rounded to `dtype`
    (float32, as the configuration states; a lower precision is the
    control)."""
    mu = scene.mu.astype(dtype, copy=False)
    d = mu - np.asarray(cam, np.float32).astype(dtype)
    dist2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] + d[:, 2] * d[:, 2]
    r = scene.size.astype(dtype, copy=False) * dtype(focal)
    t = dtype(tau)
    lhs, rhs = r * r, (t * t) * np.maximum(dist2, dtype(1e-12))
    gt = np.asarray(lhs > rhs, bool)
    lhs, rhs = lhs.astype(np.float64), rhs.astype(np.float64)
    tie = np.abs(lhs - rhs) <= TIE * np.maximum(lhs, rhs)
    expand = np.zeros(scene.n, bool)
    cut = np.zeros(scene.n, bool)
    for ids in scene.levels:
        par = scene.parent[ids]
        pe = np.where(par < 0, True, expand[np.maximum(par, 0)])
        expand[ids] = pe & gt[ids]
        cut[ids] = pe & (~gt[ids] | scene.is_leaf[ids])
    return np.nonzero(cut)[0], tie


def cut_mismatch(scene: Scene, got_ids: np.ndarray, want_ids: np.ndarray,
                 tie: np.ndarray, budget: int) -> int:
    """Nodes in one cut and not the other that are neither a tie nor below
    one. A cut larger than the service's budget is held to its first
    `budget` ids, as the service stores it."""
    got = np.unique(got_ids[got_ids >= 0])
    want = want_ids[:budget]
    node = np.setxor1d(got, want, assume_unique=True)
    excused = np.zeros(node.size, bool)
    while node.size:
        excused |= tie[node]
        up = scene.parent[node]
        if np.all(up < 0):
            break
        node = np.where(up >= 0, up, node)
    return int((~excused).sum())


def row_gap(scene: Scene, gids: np.ndarray, rows: Dict[str, np.ndarray]
            ) -> float:
    """Worst attribute error of decoded rows against the raw rows of nodes
    `gids`, in quantisation steps of the wire format (see module doc)."""
    if gids.size == 0:
        return 0.0
    g = gids.astype(np.int64)
    gaps = [np.abs(rows["mu"] - scene.mu[g]) / scene.pos_step,
            np.abs(rows["log_scale"] - scene.log_scale[g]) / scene.scale_step,
            np.abs(rows["opacity"] - scene.opacity[g]) * Q16]
    q = scene.quat[g].astype(np.float64)
    q = q / (np.linalg.norm(q, axis=1, keepdims=True) + 1e-12)
    gaps.append(np.abs(rows["quat"] - q) * Q_QUAT16)
    dc = scene.dc[g]
    ulp = np.spacing(np.abs(dc).astype(np.float16)).astype(np.float64)
    gaps.append(np.abs(rows["dc"] - dc) / ulp)
    return float(max(np.max(x) for x in gaps))


def control_rows(scene: Scene, gids: np.ndarray) -> Dict[str, np.ndarray]:
    """The reference's rows at the next precision down: 8-bit fixed point
    where the wire format states 16, and float8 (e4m3) where it states
    float16. The control of `row_gap`."""
    import ml_dtypes

    g = gids.astype(np.int64)
    mu, ls = scene.mu[g].astype(np.float64), scene.log_scale[g]
    lo_mu, lo_ls = scene.mu.min(0), scene.log_scale.min()
    k = Q16 / 255.0

    def q8(x, lo, step):
        return np.clip(np.round((x - lo) / (step * k)), 0, 255) * step * k + lo

    q = scene.quat[g].astype(np.float64)
    q = q / (np.linalg.norm(q, axis=1, keepdims=True) + 1e-12)
    return {"mu": q8(mu, lo_mu, scene.pos_step),
            "log_scale": q8(ls, lo_ls, scene.scale_step),
            "opacity": q8(scene.opacity[g], 0.0, 1.0 / Q16),
            "quat": np.round(q * 127.0) / 127.0,
            "dc": scene.dc[g].astype(ml_dtypes.float8_e4m3fn).astype(
                np.float64)}


@dataclasses.dataclass
class Update:
    """One served update of one client, as the harness captured it."""

    client: int
    tick: int
    cam: np.ndarray          # (3,) the pose the tick served
    tau: float               # the threshold the service used for the client
    cut: np.ndarray          # the service's cut ids (-1 padded)
    delivered: np.ndarray    # ids of the Δ rows the client decoded
    rows: Optional[Dict[str, np.ndarray]] = None  # decoded rows of `row_ids`
    row_ids: Optional[np.ndarray] = None
    owed: int = 0            # rows the stream says it still owes the client
    checked: bool = False    # in the sample whose cut and rows are compared


def residency_gap(history: List[Update], n: int, w_star: int) -> int:
    """Replay one client's store over its syncs (oldest first, from its
    admission): the worst count, over syncs, of cut nodes neither held nor
    owed, plus rows delivered that it held already or that no cut within the
    reuse window asked for."""
    has = np.zeros(n, bool)
    last = np.full(n, -(2 ** 40), np.int64)
    worst = 0
    for t, u in enumerate(history):
        got = u.delivered[u.delivered >= 0]
        cut = u.cut[u.cut >= 0]
        dup = int(has[got].sum())
        has[got] = True
        last[cut] = t
        unwanted = int(((t - last[got]) > w_star).sum())
        has &= (t - last) <= w_star
        missing = int((~has[cut]).sum()) - int(u.owed)
        worst = max(worst, dup + unwanted + max(missing, 0))
    return worst


@dataclasses.dataclass
class Readings:
    """The numbers compared: each the worst over what was checked."""

    cut_mismatch: int = 0
    row_gap: float = 0.0
    residency_gap: int = 0
    updates_checked: int = 0
    rows_checked: int = 0
    failed_updates: int = 0


def compare(scene: Scene, updates: List[Update],
            histories: Dict[int, List[Update]], *, focal: float,
            cut_budget: int, w_star: int, limits: Dict[str, float],
            control: bool = False) -> Readings:
    """Run the reference over the checked updates and the watched clients'
    histories. With `control`, the reference's own lower-precision results
    take the program's place: the cut in bfloat16 where the configuration
    states float32, and the rows at 8 bits where the wire format states 16."""
    out = Readings()
    for u in updates:
        if not u.checked:
            continue
        want, tie = lod_cut(scene, u.cam, focal, u.tau)
        if control:
            import ml_dtypes
            got, _ = lod_cut(scene, u.cam, focal, u.tau,
                             dtype=ml_dtypes.bfloat16)
            rows = control_rows(scene, u.row_ids)
        else:
            got, rows = u.cut, u.rows
        mis = cut_mismatch(scene, got, want, tie, cut_budget)
        gap = row_gap(scene, u.row_ids, rows)
        out.cut_mismatch = max(out.cut_mismatch, mis)
        out.row_gap = max(out.row_gap, gap)
        out.updates_checked += 1
        out.rows_checked += int(u.row_ids.size)
        out.failed_updates += int(mis > limits["cut_mismatch"]
                                  or gap > limits["row_gap"])
    if not control:
        for hist in histories.values():
            gap = residency_gap(hist, scene.n, w_star)
            out.residency_gap = max(out.residency_gap, gap)
            out.failed_updates += int(gap > limits["residency_gap"])
    return out
