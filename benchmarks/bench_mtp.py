"""Motion-to-photon latency: deadline scheduler vs lockstep baseline.

The paper's headline serving claim is a 2.7× motion-to-photon speedup from
not making every client wait on the whole fleet. This bench prices that on
a STRAGGLER-LADEN fleet: most clients are tight-deadline headsets with
bursty head motion; a few are stragglers that teleport across the city
every few frames, forcing near-full slab resweeps. Under lockstep `sync()`
every frame that contains a straggler teleport is slow for EVERYONE; the
deadline scheduler (`repro.serve.scheduler`) gives stragglers loose
deadlines, so their expensive resweeps run in their own ticks while the
tight-deadline majority keeps syncing in small fast ticks.

Swept axes (ISSUE: arrival rate × motion burstiness × bandwidth tier):

  * motion arrival rate — per-frame Poisson intensity of head-pose
    deliveries per normal client (sparser arrivals → idle clients the
    scheduler can skip, lockstep cannot);
  * motion burstiness — probability of a saccade (large jump) per
    delivered pose (`scheduler.bursty_motion_path`);
  * bandwidth tier — uncontrolled vs a `BANDWIDTH_TIERS` preset driving
    the closed-loop rate controller under the scheduler.

Per row, BOTH modes replay the IDENTICAL motion schedule (same rng seed)
and report p50/p99 motion-to-photon latency (motion delivery → completion
of the sync that served it, wall clock) and the deadline-miss rate.
Deadlines are calibrated from a measured warm lockstep tick so the rows
are machine-independent: tight = 3×, straggler = 60× the warm tick.

Set NEBULA_BENCH_SMOKE=1 for the CI trajectory run (small scene, fewer
frames, one rate×burst×tier row — the lockstep-vs-deadline p99 comparison
still lands in BENCH_mtp.json).
"""

import os
import time

import numpy as np

from benchmarks.common import city_scene, emit
from repro.core.pipeline import SessionConfig
from repro.serve import lod_service as svc
from repro.serve.scheduler import (DeadlineScheduler, bursty_motion_path,
                                   straggler_path)

FOCAL, TAU = 260.0, 48.0


def _smoke() -> bool:
    return os.environ.get("NEBULA_BENCH_SMOKE", "") not in ("", "0")


def _motion_schedule(rng, n_normal, n_straggler, frames, rate, burst,
                     extent):
    """frames × clients motion deliveries (None = no pose this frame).
    Normals: Poisson(rate)-thinned bursty walks; stragglers: teleporting
    paths delivered every frame (they are head-tracked too — just mostly
    still between teleports)."""
    n = n_normal + n_straggler
    paths = []
    for i in range(n_normal):
        paths.append(bursty_motion_path(
            rng, frames, speed=0.8, burst_prob=burst, burst_scale=12.0,
            start=rng.uniform(-extent / 4, extent / 4, 3)))
    for i in range(n_straggler):
        paths.append(straggler_path(rng, frames, teleport_every=4,
                                    extent=extent))
    deliver = np.ones((frames, n), bool)
    deliver[:, :n_normal] = rng.poisson(rate, (frames, n_normal)) > 0
    return paths, deliver


def _build(tree, cfg, n, tier):
    return svc.LodService(tree, cfg, n, focal=FOCAL, mode="pooled",
                          dedup=True, bandwidth=tier)


def _run_lockstep(tree, cfg, n, tier, paths, deliver):
    """Lockstep baseline with the scheduler's MTP bookkeeping: every frame
    syncs EVERY live client; a client's sample is its oldest undelivered
    pose → sync completion."""
    service = _build(tree, cfg, n, tier)
    ids = service.active_ids
    oldest = {c: None for c in ids}
    cams = {c: np.asarray(paths[i][0], np.float32)
            for i, c in enumerate(ids)}
    samples = []
    service.sync(cams)  # warm/compile sync outside the measured window
    for f in range(deliver.shape[0]):
        now = time.monotonic()
        moved = False
        for i, c in enumerate(ids):
            if deliver[f, i]:
                cams[c] = np.asarray(paths[i][f], np.float32)
                if oldest[c] is None:
                    oldest[c] = now
                moved = True
        if not moved:
            continue
        stats = service.sync(cams)
        np.asarray(stats.sync_bytes)  # block: completion = photon time
        done = time.monotonic()
        for c in ids:
            if oldest[c] is not None:
                samples.append((done - oldest[c]) * 1e3)
                oldest[c] = None
    return np.asarray(samples)


def _run_deadline(tree, cfg, n_normal, n_straggler, tier, paths, deliver,
                  tight_ms, loose_ms, budget_ms):
    service = _build(tree, cfg, n_normal + n_straggler, tier)
    ids = service.active_ids
    sched = DeadlineScheduler(service, default_deadline_ms=tight_ms,
                              tick_budget_ms=budget_ms)
    for i, c in enumerate(ids):
        sched.set_deadline(c, loose_ms if i >= n_normal else tight_ms)
        sched.observe_motion(c, paths[i][0])
    sched.tick()  # warm/compile tick outside the measured window
    sched.recorder.drain()
    for f in range(deliver.shape[0]):
        for i, c in enumerate(ids):
            if deliver[f, i]:
                sched.observe_motion(c, paths[i][f])
        sched.tick()
    # drain: motion the budget deferred still gets served (and counted)
    for _ in range(16):
        if not np.asarray(sched.tick().mtp_ms).any():   # served nobody
            break
    records = sched.recorder.drain()
    mtp = np.concatenate([r["wait_ms"] + r["service_ms"] for r in records])
    miss = np.concatenate([r["missed"] for r in records])
    return mtp, miss, sched


def run():
    scale = "small" if _smoke() else "medium"
    frames = 40 if _smoke() else 80
    n_normal, n_straggler = (5, 2) if _smoke() else (9, 3)
    rates = (1.0,) if _smoke() else (0.4, 1.0)
    bursts = (0.2,) if _smoke() else (0.0, 0.3)
    tiers = (None,) if _smoke() else (None, "headset")
    _cfg, _leaves, tree = city_scene(scale)
    hi = np.asarray(tree.gaussians.mu).max(axis=0)
    extent = float(max(hi[0], hi[1]))
    cfg = SessionConfig(tau=TAU, cut_budget=4096)
    n = n_normal + n_straggler
    emit("mtp/scene", 0.0,
         f"scale={scale} B={n} stragglers={n_straggler} frames={frames}")

    # calibrate deadlines off a measured warm lockstep tick: machine-
    # independent rows, and the scheduler is never handed a deadline the
    # hardware could not hold even for an empty fleet
    calib = _build(tree, cfg, n, None)
    walk = np.asarray(bursty_motion_path(np.random.default_rng(9), 4))
    calib.sync(np.tile(walk[0], (n, 1)))
    ts = []
    for i in range(1, 4):
        t0 = time.monotonic()
        np.asarray(calib.sync(np.tile(walk[i], (n, 1))).sync_bytes)
        ts.append(time.monotonic() - t0)
    warm_ms = float(np.median(ts) * 1e3)
    tight_ms, loose_ms = 3.0 * warm_ms, 60.0 * warm_ms
    budget_ms = 2.0 * warm_ms
    del calib
    emit("mtp/calibration", warm_ms * 1e3,
         f"warm_tick={warm_ms:.2f}ms tight={tight_ms:.1f}ms "
         f"loose={loose_ms:.1f}ms")

    for rate in rates:
        for burst in bursts:
            for tier in tiers:
                rng = np.random.default_rng(11)
                paths, deliver = _motion_schedule(
                    rng, n_normal, n_straggler, frames, rate, burst, extent)
                lock = _run_lockstep(tree, cfg, n, tier, paths, deliver)
                mtp, miss, sched = _run_deadline(
                    tree, cfg, n_normal, n_straggler, tier, paths, deliver,
                    tight_ms, loose_ms, budget_ms)
                tname = tier if isinstance(tier, str) else "uncapped"
                key = f"mtp/r{int(rate * 100):03d}/bst{int(burst * 100):03d}/{tname}"
                lp50, lp99 = (float(np.percentile(lock, 50)),
                              float(np.percentile(lock, 99)))
                dp50, dp99 = (float(np.percentile(mtp, 50)),
                              float(np.percentile(mtp, 99)))
                emit(f"{key}/lockstep", lp99 * 1e3,
                     f"p50={lp50:.2f}ms p99={lp99:.2f}ms n={lock.size}")
                emit(f"{key}/deadline", dp99 * 1e3,
                     f"p50={dp50:.2f}ms p99={dp99:.2f}ms "
                     f"miss={float(miss.mean()) * 100:.1f}% n={mtp.size}")
                emit(f"{key}/p99_speedup", 0.0,
                     f"lockstep_p99/deadline_p99={lp99 / max(dp99, 1e-9):.2f}x "
                     f"cost_model=a{sched.cost.alpha:.2f}+b{sched.cost.beta:.4f}")
    emit("mtp/summary", 0.0,
         "deadline scheduler: straggler resweeps leave the tight-deadline "
         "majority's ticks, p99 MTP drops below the lockstep baseline")


if __name__ == "__main__":
    run()
