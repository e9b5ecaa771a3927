"""End-to-end collaborative rendering session (paper Fig. 9 / Fig. 10).

Cloud side (per LoD sync, every `w` frames):
  temporal-aware LoD search → cut → management-table sync → Δcut compression.
Client side (every frame):
  decode Δcut into the local store → render queue = received cut →
  shared stereo preprocessing → left raster → triangulation shift-merge →
  right raster. Only client-side work is on the motion-to-photon path.

The session is a **pure functional core** — `SessionState` is a pytree and
`cloud_sync_step` / `client_render_step` / `session_step` are pure functions
(state in, state out) — so one cloud can hold many sessions side by side:
`repro.serve.lod_service` stacks `SessionState`-style leaves on a leading
batch axis and vmaps the temporal LoD search across clients.
`CollaborativeSession` remains as a thin stateful wrapper over the core for
API compatibility (examples, benchmarks, older tests).

The session also keeps full byte/work accounting so the benchmarks can
reproduce the paper's bandwidth/speedup figures."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core import compression as comp
from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.core.camera import StereoRig
from repro.core.gaussians import Gaussians
from repro.core.lod_tree import LodTree
from repro.core.stereo import alpha_skip_stats
from repro import render as rnd


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    tau: float = 48.0            # LoD threshold τ* in pixels
    w: int = 4                   # LoD sync interval in frames (paper default)
    w_star: int = 32             # reuse window w_r* in syncs (paper default)
    cut_budget: int = 4096
    tile: int = 16
    list_len: int = 256
    max_pairs: int = 1 << 16
    k_codes: int = 256
    use_compression: bool = True


@dataclasses.dataclass
class FrameStats:
    frame: int
    synced: bool
    cut_size: int
    delta_size: int
    sync_bytes: float
    nodes_touched: int
    resweeps: int
    client_resident: int
    stereo: Optional[object] = None


# ---------------------------------------------------------------------------
# functional core
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SessionState:
    """Complete per-client session state as a single pytree.

    mgr_state:    cloud-side management table
    client:       client-side mirror (reconstructed from wire data only)
    temporal:     per-subtree LoD-search reuse state
    client_store: client-side decoded attribute store (codec error included)
    cut_gids:     (cut_budget,) int32 current render queue, -1 padded
    sync_index:   () int32 — LoD syncs performed so far
    frame_index:  () int32 — frames stepped so far
    """

    mgr_state: mgr.ManagerState
    client: mgr.ClientState
    temporal: ls.TemporalState
    client_store: Gaussians
    cut_gids: jax.Array
    sync_index: jax.Array
    frame_index: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StepStats:
    """One frame's accounting, as arrays (pytree — safe to vmap/stack)."""

    synced: jax.Array          # () bool
    cut_size: jax.Array        # () int32
    delta_size: jax.Array      # () int32
    sync_bytes: jax.Array      # () float32
    nodes_touched: jax.Array   # () int32
    resweeps: jax.Array        # () int32
    client_resident: jax.Array  # () int32


def session_init(tree: LodTree, cfg: SessionConfig) -> SessionState:
    """Fresh session state. The initial TemporalState has `swept=False`
    everywhere, so the first `cloud_sync_step` performs a full sweep —
    bit-identical to `ls.full_search` (no special first-frame case)."""
    mgr.check_w_star(cfg.w_star)
    m = tree.meta
    n = tree.n_pad
    z = tree.gaussians
    store = Gaussians(
        mu=jnp.zeros_like(z.mu), log_scale=jnp.zeros_like(z.log_scale),
        quat=jnp.zeros_like(z.quat).at[:, 0].set(1.0),
        opacity=jnp.zeros_like(z.opacity), sh=jnp.zeros_like(z.sh))
    return SessionState(
        mgr_state=mgr.ManagerState.initial(n),
        client=mgr.ClientState.initial(n),
        temporal=ls.TemporalState.initial(m.Ns, m.S),
        client_store=store,
        cut_gids=jnp.full((cfg.cut_budget,), -1, jnp.int32),
        sync_index=jnp.int32(0),
        frame_index=jnp.int32(0),
    )


def session_wire_format(tree: LodTree, cfg: SessionConfig
                        ) -> Tuple[comp.Codec, float]:
    """(codec, bytes-per-Gaussian) shared by cloud and client. The codec is
    scene-level — one fit serves every client of the tree."""
    codec = comp.fit_codec(tree.gaussians, k_codes=cfg.k_codes, iters=6)
    bytes_per_g = (comp.wire_bytes_per_gaussian(codec)
                   if cfg.use_compression
                   else 4 * (3 + 3 + 4 + 1 + 3 * tree.gaussians.sh.shape[1]))
    return codec, float(bytes_per_g)


def cloud_sync_step(tree: LodTree, codec: comp.Codec, cfg: SessionConfig,
                    state: SessionState, cam_pos, focal,
                    bytes_per_g: float) -> Tuple[SessionState, StepStats]:
    """One LoD sync: temporal-aware search → management sync → Δcut payload →
    client mirror + store update. Pure (composed of jitted pieces)."""
    cam_pos = jnp.asarray(cam_pos, jnp.float32)
    cut, temporal = ls.temporal_search(tree, state.temporal, cam_pos,
                                       jnp.float32(focal), jnp.float32(cfg.tau))
    mask = cut.mask(tree)
    t = state.sync_index
    mgr_state, planes = mgr.cloud_sync(state.mgr_state, bp.pack(mask),
                                       jnp.int32(cfg.w_star))
    # the single client's plan as node masks: its payload and its mirror
    n = mask.shape[0]
    delta_data, cut_add, cut_remove = (
        bp.unpack(p, n) for p in (planes.delta_data, planes.cut_add,
                                  planes.cut_remove))
    # wire: Δcut payload (compressed) + cut membership deltas
    # single-client shim: the legacy unicast wire format (one per-client
    # stream, implicit Δ ids) — the fleet service dedups this per sync via
    # repro.serve.delta_path, through the same codec
    ids, n_delta = mgr.gather_payload(tree.gaussians, delta_data,
                                      cfg.cut_budget)
    sh_k = tree.gaussians.sh.shape[1]
    if cfg.use_compression:
        enc = comp.encode_rows(codec, tree.gaussians, ids)
        dec = comp.decode(codec, enc, sh_k)
    else:
        dec = tree.gaussians.slice_rows(jnp.clip(ids, 0))
    # client applies the sync
    client = mgr.client_sync(state.client, delta_data, cut_add, cut_remove,
                             t, jnp.int32(cfg.w_star))
    client_store = _apply_payload(state.client_store, ids, dec)
    gids, count, _overflow = ls.cut_gids(cut, tree, cfg.cut_budget)
    new_state = SessionState(
        mgr_state=mgr_state, client=client, temporal=temporal,
        client_store=client_store, cut_gids=gids,
        sync_index=t + 1, frame_index=state.frame_index + 1)
    stats = StepStats(
        synced=jnp.asarray(True),
        cut_size=count,
        delta_size=n_delta,
        sync_bytes=planes.wire_bytes(bytes_per_g),
        nodes_touched=cut.nodes_touched,
        resweeps=cut.resweep.sum().astype(jnp.int32),
        client_resident=planes.n_resident)
    return new_state, stats


def idle_step(state: SessionState) -> Tuple[SessionState, StepStats]:
    """A non-sync frame: the client renders its cached cut; the only uplink
    traffic is the pose."""
    new_state = dataclasses.replace(state, frame_index=state.frame_index + 1)
    stats = StepStats(
        synced=jnp.asarray(False),
        cut_size=(state.cut_gids >= 0).sum().astype(jnp.int32),
        delta_size=jnp.int32(0),
        sync_bytes=jnp.float32(mgr.POSE_UPLINK_BYTES),
        nodes_touched=jnp.int32(0),
        resweeps=jnp.int32(0),
        client_resident=state.client.has.sum().astype(jnp.int32))
    return new_state, stats


def session_step(tree: LodTree, codec: comp.Codec, cfg: SessionConfig,
                 state: SessionState, cam_pos, focal, bytes_per_g: float
                 ) -> Tuple[SessionState, StepStats]:
    """Advance one VR frame (host-driven sync cadence: every cfg.w frames)."""
    if int(state.frame_index) % cfg.w == 0:
        return cloud_sync_step(tree, codec, cfg, state, cam_pos, focal,
                               bytes_per_g)
    return idle_step(state)


@jax.jit
def _fresh_session_like(state: SessionState) -> SessionState:
    """A freshly-initialized SessionState with `state`'s leaf shapes —
    bitwise identical to `session_init` for the same tree/config, but
    jittable (shapes come from the traced state, not host objects)."""
    n = state.client.has.shape[0]
    ns, sw = state.temporal.slab_cut0.shape
    store = state.client_store
    return SessionState(
        mgr_state=mgr.ManagerState.initial(n),
        client=mgr.ClientState.initial(n),
        temporal=ls.TemporalState.initial(ns, bp.WORD * sw),
        client_store=Gaussians(
            mu=jnp.zeros_like(store.mu),
            log_scale=jnp.zeros_like(store.log_scale),
            quat=jnp.zeros_like(store.quat).at[:, 0].set(1.0),
            opacity=jnp.zeros_like(store.opacity),
            sh=jnp.zeros_like(store.sh)),
        cut_gids=jnp.full_like(state.cut_gids, -1),
        sync_index=jnp.int32(0),
        frame_index=jnp.int32(0),
    )


def admit_step(state: SessionState) -> SessionState:
    """Functional client admission for the session core (the single-client
    primitive behind the fleet lifecycle of repro.serve.fleet): returns the
    freshly-admitted session occupying this state's slot. The temporal state
    is fully unswept, so the admitted client's FIRST sync is a cold full
    sweep and a cold Δcut — no special first-frame case anywhere."""
    return _fresh_session_like(state)


def evict_step(state: SessionState) -> SessionState:
    """Functional client eviction: clear the session back to its fresh
    value. Eviction and admission reset to the SAME state by construction —
    `admit_step(evict_step(s)) == evict_step(s)` bitwise — which is the
    contract that makes a recycled fleet slot indistinguishable from a
    brand-new one (tests/test_fleet_churn.py)."""
    return _fresh_session_like(state)


def client_render_step(cfg: SessionConfig, state: SessionState,
                       rig: StereoRig):
    """Render the client's current queue from its *decoded* store (pure)."""
    gids = state.cut_gids
    queue = state.client_store.slice_rows(jnp.clip(gids, 0))
    # mask out padding rows by zero opacity
    queue = dataclasses.replace(
        queue, opacity=jnp.where(gids >= 0, queue.opacity, 0.0))
    return render_stereo(queue, rig, tile=cfg.tile, list_len=cfg.list_len,
                         max_pairs=cfg.max_pairs)


def _apply_payload(store: Gaussians, ids: jax.Array, dec: Gaussians
                   ) -> Gaussians:
    """Scatter decoded Δcut rows into the client store (-1 ids are padding)."""
    valid = (ids >= 0)[:, None]
    safe_ids = jnp.clip(ids, 0)
    return Gaussians(
        mu=store.mu.at[safe_ids].set(jnp.where(valid, dec.mu, store.mu[safe_ids])),
        log_scale=store.log_scale.at[safe_ids].set(
            jnp.where(valid, dec.log_scale, store.log_scale[safe_ids])),
        quat=store.quat.at[safe_ids].set(
            jnp.where(valid, dec.quat, store.quat[safe_ids])),
        opacity=store.opacity.at[safe_ids].set(
            jnp.where(valid[:, 0], dec.opacity, store.opacity[safe_ids])),
        sh=store.sh.at[safe_ids].set(
            jnp.where(valid[:, :, None], dec.sh, store.sh[safe_ids])),
    )


# ---------------------------------------------------------------------------
# stateful wrapper (API compatibility)
# ---------------------------------------------------------------------------


class CollaborativeSession:
    """Thin stateful wrapper over the functional core (single client)."""

    def __init__(self, tree: LodTree, cfg: SessionConfig, rig_template: StereoRig):
        self.tree = tree
        self.cfg = cfg
        self.codec, self.bytes_per_g = session_wire_format(tree, cfg)
        self.rig_template = rig_template
        self.state = session_init(tree, cfg)

    # legacy attribute views ---------------------------------------------------

    @property
    def mgr_state(self) -> mgr.ManagerState:
        return self.state.mgr_state

    @property
    def client(self) -> mgr.ClientState:
        return self.state.client

    @property
    def temporal(self) -> ls.TemporalState:
        return self.state.temporal

    @property
    def client_store(self) -> Gaussians:
        return self.state.client_store

    @property
    def sync_index(self) -> int:
        return int(self.state.sync_index)

    @property
    def frame_index(self) -> int:
        return int(self.state.frame_index)

    @property
    def current_cut_ids(self) -> Optional[jax.Array]:
        return self.state.cut_gids if self.sync_index > 0 else None

    # -- client ----------------------------------------------------------------

    def render(self, rig: StereoRig, gids: jax.Array):
        cfg = self.cfg
        queue = self.state.client_store.slice_rows(jnp.clip(gids, 0))
        queue = dataclasses.replace(
            queue, opacity=jnp.where(gids >= 0, queue.opacity, 0.0))
        return render_stereo(queue, rig, tile=cfg.tile, list_len=cfg.list_len,
                             max_pairs=cfg.max_pairs)

    # -- frame loop ------------------------------------------------------------

    def step(self, rig: StereoRig, render: bool = True):
        """Advance one VR frame. LoD sync happens every cfg.w frames."""
        frame = int(self.state.frame_index)
        focal = jnp.float32(self.rig_template.left.focal)
        self.state, st = session_step(
            self.tree, self.codec, self.cfg, self.state,
            np.asarray(rig.left.pos), focal, self.bytes_per_g)
        stats = FrameStats(
            frame=frame, synced=bool(st.synced),
            cut_size=int(st.cut_size), delta_size=int(st.delta_size),
            sync_bytes=float(st.sync_bytes),
            nodes_touched=int(st.nodes_touched), resweeps=int(st.resweeps),
            client_resident=int(st.client_resident))
        out = client_render_step(self.cfg, self.state, rig) if render else None
        return stats, out


def render_stereo(queue: Gaussians, rig: StereoRig, *, tile: int = 16,
                  list_len: int = 256, max_pairs: int = 1 << 16):
    """Client stereo pipeline: shared preprocessing → left raster →
    triangulation shift-merge → right raster. Returns (left, right, stats).

    Legacy single-client surface over the `repro.render` subsystem: builds a
    `RenderConfig` + `RenderPlan` and rasterizes — the same stages
    `repro.render.batched.batched_render_stereo` vmaps across a fleet
    (bit-identical per client, proven in tests)."""
    cfg = rnd.RenderConfig.for_rig(rig, tile=tile, list_len=list_len,
                                   max_pairs=max_pairs)
    plan = rnd.build_plan(queue, rig, cfg)
    img_l, img_r, hits = rnd.render_stereo(plan, cfg)
    stats = alpha_skip_stats(plan.left, plan.right, hits, plan.splats)
    return img_l, img_r, (plan.splats, plan.left, plan.right, stats)


def render_stereo_reference(queue: Gaussians, rig: StereoRig):
    """Two fully independent eye renders (the BASE baseline of Fig. 16)."""
    return rnd.render_stereo_reference(queue, rig)
