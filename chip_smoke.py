"""Bring-up smoke run of the fleet LoD service on a TPU.

    python chip_smoke.py              # one chip: the served path + references
    python chip_smoke.py --chips 4    # four chips: the sharded service only

One chip, in order: a city scene of ~2M leaf Gaussians at SH degree 3; 32
clients of mixed bandwidth tiers served through `DeadlineScheduler` ticks
(partial ticks, admits in waves, an evict); 3 lockstep syncs of the pooled
service against the vmapped reference (bitwise); the same syncs with the
compiled Pallas pair sweep against the XLA sweep (bitwise); and the
fallback stereo render on both batched paths (allclose). `--chips 4`
serves the same fleet on the `clients`x`slabs` meshes 4x1 and 2x2 and
checks them bitwise against a single-device service, on the 8x8-block city
unless `--blocks` says otherwise: at 32x32 the single-device service alone
peaked at ~12.8 GB on one chip, and its device 0 also holds a shard of
each meshed service.

Each phase prints what it found; any failed check exits non-zero. Without
a TPU the script exits non-zero before doing any work. The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import lod_search as ls  # noqa: E402
from repro.core.camera import StereoRig, make_camera  # noqa: E402
from repro.core.gaussians import CityConfig, generate_city  # noqa: E402
from repro.core.lod_tree import build_lod_tree  # noqa: E402
from repro.core.pipeline import SessionConfig  # noqa: E402
from repro.kernels import lod_cut, resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.serve.lod_service import LodService  # noqa: E402
from repro.serve.scheduler import (CostModel, DeadlineScheduler,  # noqa: E402
                                   bursty_motion_path)

TIERS = ("phone", "headset", "tethered")
FOCAL = 400.0       # px: ~100° horizontal FOV on a 960-px-wide eye
TAU = 48.0          # px: the session default LoD threshold
CUT_BUDGET = 262144
EYE_HEIGHT = 1.7
# On a TPU v5e the pooled (Mosaic) and vmapped (XLA) renders were bitwise
# equal and the Pallas sweep's ρ equalled the XLA sweep's exactly; the
# checks allow only float noise (np.allclose defaults, a few ulps of ρ).
# Loosen them only for a measured difference.
RHO_ULPS = 4
_T0 = time.perf_counter()


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    """One result line, stamped with seconds since the script started."""
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def report_memory(phase: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"{phase}: peak_bytes_in_use={'n/a' if peak is None else int(peak)}")


def device_gate(chips: int = 1):
    """The first device must be a TPU, and `chips` of them must be there."""
    devices = jax.devices()
    d = devices[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {d.platform}")
    if len(devices) < chips:
        raise SmokeFailure(f"{chips} chips asked for, {len(devices)} present")
    return d


def build_scene(blocks: int = 32, seed: int = 0):
    """Procedural city at SH degree 3; `target_subtrees` follows the block
    count so the slab width S stays near 2K (S sets the pair-sweep
    footprint). Returns (city config, tree)."""
    t0 = time.perf_counter()
    city = CityConfig(blocks_x=blocks, blocks_y=blocks, leaf_density=0.5,
                      sh_degree=3, seed=seed)
    leaves = generate_city(city)
    tree = build_lod_tree(leaves, target_subtrees=max(16, blocks * blocks),
                          slab_pad_to=128, seed=seed)
    m = tree.meta
    say(f"scene: blocks={blocks}x{blocks} leaves={leaves.n} "
        f"n_real={m.n_real} n_pad={tree.n_pad} Ns={m.Ns} S={m.S} "
        f"sh_degree=3 build_s={time.perf_counter() - t0:.1f}")
    check(m.S <= 4096, f"slab width S={m.S} > 4096")
    return city, tree


def _starts(city: CityConfig, n: int, rng) -> np.ndarray:
    ex, ey = city.extent
    xy = rng.uniform([0.1 * ex, 0.1 * ey], [0.9 * ex, 0.9 * ey], (n, 2))
    return np.concatenate([xy, np.full((n, 1), EYE_HEIGHT)], 1).astype(
        np.float32)


def served_phase(city, tree, *, n_clients: int = 32, wave: int = 8,
                 ticks: int = 10, seed: int = 0, cut_budget=CUT_BUDGET):
    """32 clients through `DeadlineScheduler`: the first wave starts with
    the service, one more wave is admitted per tick until the fleet is
    full (a cold sync sweeps every (client, slab) pair of the newcomers,
    so joins are spread over ticks), odd ticks are partial (half the fleet
    moved), and one client is evicted mid-run.

    The scheduler's cost model is seeded at 1 ms + 1e-4 ms per stale pair
    so that every wave passes the predicted-cost admission gate before the
    model has refit (8 measured ticks): this drives admission, it does not
    test the gate. What the refit model would decide for one more join is
    printed at the end."""
    rng = np.random.default_rng(seed)
    cfg = SessionConfig(tau=TAU, cut_budget=cut_budget)
    starts = _starts(city, n_clients, rng)
    paths = [bursty_motion_path(rng, ticks, speed=0.5, start=s)
             for s in starts]
    svc = LodService(tree, cfg, wave, FOCAL, mode="pooled", sweep_impl="xla",
                     capacity=n_clients,
                     bandwidth=[TIERS[i % 3] for i in range(wave)])
    sched = DeadlineScheduler(svc, default_deadline_ms=100.0,
                              cost_model=CostModel(alpha_ms=1.0,
                                                   beta_ms=1e-4))
    path_of = {cid: i for i, cid in enumerate(svc.active_ids)}
    joined, evicted, partial_ticks = wave, None, 0
    ns = tree.meta.Ns
    for t in range(ticks):
        admitted = []
        while t > 0 and joined < n_clients and len(admitted) < wave:
            cid = sched.admit(cam=starts[joined],
                              bandwidth=TIERS[joined % 3])
            path_of[cid] = joined
            admitted.append(cid)
            joined += 1
        if t == ticks - 3:
            older = [c for c in svc.active_ids if c not in admitted]
            evicted = older[len(older) // 2]
            sched.evict(evicted)
        live = svc.active_ids
        movers = (live if t % 2 == 0 else
                  [c for c in live if rng.random() < 0.5] or live[:1])
        for cid in movers:
            sched.observe_motion(cid, paths[path_of[cid]][t])
        t0 = time.perf_counter()
        stats = sched.tick()
        wall_ms = (time.perf_counter() - t0) * 1e3
        check(bool(np.asarray(stats.mtp_ms).any()),
              f"tick {t}: nothing was synced")
        served = sorted(set(movers) | set(admitted))
        slots = np.array([svc._slot_of(c) for c in served])
        stale = int(np.asarray(stats.resweeps).sum())
        sync_bytes = np.asarray(stats.sync_bytes)[slots]
        cut = np.asarray(stats.cut_size)[slots]
        batch = svc.last_delta
        partial_ticks += len(served) < len(live)
        say(f"tick {t}: live={len(live)} participants={len(served)} "
            f"admitted={len(admitted)} "
            f"evicted={evicted if t == ticks - 3 else '-'} "
            f"stale_pairs={stale} "
            f"lanes={svc.last_account['lanes']} "
            f"union={int(batch.n_union)} pages={int(batch.pages)} "
            f"bytes_per_client_mean={float(sync_bytes.mean()):.0f} "
            f"max={float(sync_bytes.max()):.0f} cut_mean={float(cut.mean()):.0f} "
            f"cut_overflow={int(np.asarray(stats.overflow)[slots].sum())} "
            f"wall_ms={wall_ms:.1f}")
        check(bool(np.all(cut > 0)), f"tick {t}: an empty cut was served")
        check(bool(np.all(np.isfinite(sync_bytes)) and np.all(sync_bytes > 0)),
              f"tick {t}: non-finite or zero wire bytes")
    cost = sched.cost
    say(f"admission gate: cost model alpha_ms={cost.alpha:.1f} "
        f"beta_ms={cost.beta:.3g} from {len(cost.samples)} measured ticks; "
        f"a cold join ({ns} stale pairs) predicts {cost.predict(ns):.1f} ms; "
        f"next admit: {sched.predicted_admission_denial() or 'admitted'}")
    records = sched.recorder.drain()
    mtp = np.concatenate([r["wait_ms"] + r["service_ms"] for r in records])
    missed = np.concatenate([r["missed"] for r in records])
    say(f"served: clients={svc.n_clients} joined={joined} "
        f"partial_ticks={partial_ticks} "
        f"mtp_p50_ms={np.percentile(mtp, 50):.1f} "
        f"mtp_p99_ms={np.percentile(mtp, 99):.1f} "
        f"deadline_miss_rate={missed.mean():.3f}")
    check(joined == n_clients, f"only {joined} of {n_clients} clients joined")
    check(partial_ticks > 0, "no partial tick ran")
    return svc


def _lockstep_poses(city, n_clients: int, syncs: int, seed: int):
    rng = np.random.default_rng(seed + 1)
    starts = _starts(city, n_clients, rng)
    walks = [bursty_motion_path(rng, syncs, speed=2.0, start=s)
             for s in starts]
    return np.stack(walks, axis=1)          # (syncs, clients, 3)


def _client_view(svc: LodService, cid: int):
    """Host copies of one client's render queue and decoded Δ rows — taken
    right after a sync, before the next one consumes the donated state."""
    ids, rows = svc.client_delta(cid)
    return ([np.asarray(svc.client_cut(cid)), np.asarray(ids)]
            + [np.asarray(x) for x in jax.tree_util.tree_leaves(rows)])


def _same(x, y) -> bool:
    return (x.shape == y.shape and x.dtype == y.dtype
            and np.array_equal(x, y, equal_nan=True))


def _within_ulps(x, y, ulps: int) -> bool:
    """Equal (inf included) or at most `ulps` float spacings apart."""
    with np.errstate(invalid="ignore"):
        return bool(np.all((x == y) | (np.abs(x - y)
                                       <= ulps * np.spacing(np.abs(y)))))


def _assert_same(a, b, what: str) -> None:
    check(len(a) == len(b), f"{what}: different leaf counts")
    for i, (x, y) in enumerate(zip(a, b)):
        check(_same(x, y), f"{what}: leaf {i} differs")


def lockstep(services, poses, label: str) -> None:
    """Sync every service on the same poses; after each sync every
    service's cuts and decoded Δ rows must equal the first's bitwise.

    The reuse radius ρ and the per-client resweep counts are reported, not
    checked: ρ is computed with a sqrt and a divide that differently fused
    programs may round apart, and a ρ off by an ulp changes a staleness
    decision only if a client moved within that ulp of it."""
    for s, cams in enumerate(poses):
        views, rhos, resweeps = [], [], []
        for svc in services:
            stats = svc.sync(cams)
            views.append([_client_view(svc, c) for c in svc.active_ids])
            rhos.append(np.asarray(svc.state.temporal.rho))
            resweeps.append(np.asarray(stats.resweeps))
        for other in views[1:]:
            for cid, (x, y) in enumerate(zip(views[0], other)):
                _assert_same(x, y, f"{label} sync {s} client {cid}")
        finite = np.isfinite(rhos[0])
        rho_diff = max(float(np.abs(r - rhos[0])[finite].max(initial=0.0))
                       for r in rhos[1:])
        rho_off = sum(int((r != rhos[0]).sum()) for r in rhos[1:])
        same_work = all(np.array_equal(r, resweeps[0]) for r in resweeps[1:])
        cuts = [int((v[0] >= 0).sum()) for v in views[0]]
        say(f"{label} sync {s}: bitwise equal; cut sizes {cuts}; "
            f"rho pairs differing {rho_off} of {rhos[0].size} "
            f"(max |diff| {rho_diff:.3g}); resweeps "
            f"{resweeps[0].tolist()} equal={same_work}")


def reference_phase(city, tree, *, n_clients: int = 4, syncs: int = 3,
                    seed: int = 0, cut_budget=CUT_BUDGET):
    """Pooled production service vs the vmapped always-sweep reference:
    same poses, no bandwidth control. Returns the pooled service and the
    clients' last poses."""
    cfg = SessionConfig(tau=TAU, cut_budget=cut_budget)
    pooled = LodService(tree, cfg, n_clients, FOCAL, mode="pooled")
    vmapped = LodService(tree, cfg, n_clients, FOCAL, mode="vmapped")
    poses = _lockstep_poses(city, n_clients, syncs, seed)
    lockstep([pooled, vmapped], poses, "pooled-vs-vmapped")
    return pooled, poses[-1]


def pallas_phase(city, tree, *, n_clients: int = 4, syncs: int = 3,
                 seed: int = 0, cut_budget=CUT_BUDGET) -> None:
    """The Pallas pair sweep against the XLA sweep: the kernel alone on
    every slab from one camera, then the same lockstep syncs as the
    reference phase. On a TPU the kernel must be compiled, not
    interpreted."""
    tables = ls.SlabTables.from_tree(tree)
    ns = tree.meta.Ns
    cam = jnp.asarray(_lockstep_poses(city, 1, 1, seed)[0, 0])
    top_expand, _ = ls.top_sweep(tree, cam, jnp.float32(FOCAL),
                                 jnp.float32(TAU))
    rpe = top_expand[tree.slab_root_parent_top]
    cams = jnp.broadcast_to(cam, (ns, 3))
    args = (tables.mu, tables.size, tables.end, tables.is_leaf, tables.valid,
            rpe, cams, jnp.float32(FOCAL), jnp.float32(TAU))
    text = lod_cut.lod_pair_sweep_pallas.lower(*args).compile().as_text()
    compiled = "tpu_custom_call" in text
    say(f"pallas: interpret={resolve_interpret()} "
        f"tpu_custom_call={compiled}")
    check(compiled or resolve_interpret(),
          "the Pallas sweep did not compile to a TPU kernel")
    got = lod_cut.lod_pair_sweep_pallas(*args)
    want = ls.sweep_slab_camera_pairs(
        tables.mu, tables.size, tables.end, tables.is_leaf, tables.valid,
        rpe, cams, jnp.float32(FOCAL), jnp.float32(TAU))
    got, want = ([np.asarray(x) for x in out] for out in (got, want))
    _assert_same(got[:2], want[:2], "pallas kernel vs XLA sweep (cut)")
    finite = np.isfinite(want[2])
    rho_diff = float(np.abs(got[2] - want[2])[finite].max(initial=0.0))
    check(_within_ulps(got[2], want[2], RHO_ULPS),
          f"pallas kernel vs XLA sweep: rho differs by {rho_diff} "
          f"(> {RHO_ULPS} ulps)")
    say(f"pallas kernel: {ns} slabs, cut bitwise equal to the XLA sweep "
        f"(cut nodes {int(got[0].sum())}); max |rho diff| = {rho_diff:.3g}")
    cfg = SessionConfig(tau=TAU, cut_budget=cut_budget)
    xla = LodService(tree, cfg, n_clients, FOCAL, mode="pooled")
    pallas = LodService(tree, cfg, n_clients, FOCAL, mode="pooled",
                        sweep_impl="pallas")
    lockstep([xla, pallas], _lockstep_poses(city, n_clients, syncs, seed),
             "pallas-vs-xla")


def render_phase(svc: LodService, positions, *, width: int = 960,
                 height: int = 1080, max_pairs: int = 1 << 21) -> None:
    """Fallback stereo frames of every live client's current queue (client
    i at `positions[i]`) on the vmapped XLA path and the pooled Pallas
    path; they must agree."""
    rigs = []
    for pos in np.asarray(positions, np.float32):
        cam = make_camera(pos, pos + np.array([30.0, 30.0, -0.5], np.float32),
                          focal_px=FOCAL, width=width, height=height,
                          near=0.25)
        rigs.append(StereoRig(left=cam, baseline=0.064))
    frames = {}
    for path in ("vmap", "pooled"):
        t0 = time.perf_counter()
        img_l, img_r, stats = svc.render_fallback(rigs, path=path,
                                                  max_pairs=max_pairs)
        jax.block_until_ready((img_l, img_r))
        frames[path] = (np.asarray(img_l), np.asarray(img_r))
        say(f"render {path}: {len(rigs)} clients {width}x{height}/eye "
            f"merge_overflow={np.asarray(stats.overflow).tolist()} "
            f"left_blends={np.asarray(stats.left_blends).tolist()} "
            f"wall_s={time.perf_counter() - t0:.2f}")
    for eye, a, b in zip(("left", "right"), frames["vmap"], frames["pooled"]):
        diff = np.abs(a - b)
        say(f"render {eye}: max |vmap - pooled| = {float(diff.max()):.3g}, "
            f"{int((diff > 1e-5).sum())} of {diff.size} values off by > 1e-5")
        check(bool(np.all(np.isfinite(b))), f"render {eye}: non-finite pixels")
        check(bool(np.allclose(a, b)),
              f"render {eye}: vmap and pooled frames differ")


def sharded_phase(city, tree, *, n_clients: int = 32, wave: int = 16,
                  syncs: int = 4, seed: int = 0,
                  cut_budget=CUT_BUDGET) -> None:
    """The same fleet on the 4x1 and 2x2 `clients`x`slabs` meshes and on a
    single device, in lockstep: a wave joins on each early sync, one client
    is evicted and its slot recycled, and after every sync the cuts, wire
    bytes and decoded Δ rows of every live client match bitwise."""
    rng = np.random.default_rng(seed)
    cfg = SessionConfig(tau=TAU, cut_budget=cut_budget)
    starts = _starts(city, n_clients + 1, rng)
    paths = [bursty_motion_path(rng, syncs, speed=0.5, start=s)
             for s in starts]
    meshes = {"single": None,
              "4x1": make_fleet_mesh(clients=4, slabs=1),
              "2x2": make_fleet_mesh(clients=2, slabs=2)}
    services = {name: LodService(tree, cfg, wave, FOCAL, mode="pooled",
                                 capacity=n_clients, mesh=mesh,
                                 bandwidth=[TIERS[i % 3] for i in range(wave)])
                for name, mesh in meshes.items()}
    path_of = {cid: cid for cid in range(wave)}
    joined = wave
    for s in range(syncs):
        events = []
        if 0 < s and joined < n_clients:
            for svc in services.values():
                ids = [svc.admit(cam=starts[j], bandwidth=TIERS[j % 3])
                       for j in range(joined, min(joined + wave, n_clients))]
            for j, cid in zip(range(joined, n_clients), ids):
                path_of[cid] = j
            events.append(f"admitted {len(ids)}")
            joined += len(ids)
        elif s == syncs - 1:
            victim = services["single"].active_ids[1]
            for svc in services.values():
                svc.evict(victim)
                cid = svc.admit(cam=starts[n_clients], bandwidth="headset")
            path_of[cid] = n_clients
            events.append(f"evicted {victim}, admitted {cid}")
        views = {}
        for name, svc in services.items():
            live = svc.active_ids
            stats = svc.sync({c: paths[path_of[c]][s] for c in live})
            client = [_client_view(svc, c) for c in live]
            views[name] = {"wire bytes": [np.asarray(stats.sync_bytes)],
                           "cuts": [v[0] for v in client],
                           "Δ rows": [x for v in client for x in v[1:]]}
        for name in ("4x1", "2x2"):
            # every part is compared before failing, so one run names them all
            bad = [part for part, want in views["single"].items()
                   if not all(map(_same, want, views[name][part]))]
            check(not bad, f"mesh {name} sync {s}: {', '.join(bad)} differ")
        total = float(views["single"]["wire bytes"][0].sum())
        say(f"sharded sync {s}: {'; '.join(events) or 'steady'}; "
            f"live={services['single'].n_clients} meshes 4x1 and 2x2 "
            f"bitwise equal to one device (cuts, wire bytes, Δ rows); "
            f"fleet bytes={total:.0f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a four-chip host")
    ap.add_argument("--blocks", type=int, default=None,
                    help="city blocks per side (32: ~2M leaf Gaussians); "
                         "default 32 on one chip, 8 with --chips 4")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = device_gate(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    say(f"compilation cache: {enable_compilation_cache()}")
    blocks = args.blocks or (8 if args.chips == 4 else 32)
    city, tree = build_scene(blocks, args.seed)
    report_memory("scene")
    if args.chips == 4:
        sharded_phase(city, tree, seed=args.seed)
        report_memory("sharded")
    else:
        served_phase(city, tree, seed=args.seed)
        report_memory("served")
        pooled, positions = reference_phase(city, tree, seed=args.seed)
        report_memory("reference")
        pallas_phase(city, tree, seed=args.seed)
        report_memory("pallas")
        render_phase(pooled, positions)
        report_memory("render")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
