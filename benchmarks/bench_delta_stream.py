"""Paged union stream: budget pressure × bandwidth tier × fleet size.

Sweeps the paged encode-once Δcut stream (repro.serve.delta_path) along the
three axes that shape it:

  * B ∈ {2, 8} concurrent headsets on a half-overlapping walk (the
    bench_fleet_sync fleet geometry);
  * budget pressure: `delta_budget` as a fraction of the fleet's COLD union
    (measured by an un-budgeted probe) — 1.0 is the ample baseline, smaller
    fractions force the stream to page and carry debt across syncs;
  * bandwidth tier: uncontrolled vs the `BANDWIDTH_TIERS` presets, driving
    the closed-loop per-client rate controller.

Reported per (B, pressure, tier):
  * per-client wire bytes (mean / p95 across clients × syncs) — under
    pressure these are bytes of pages actually SHIPPED, never of deferred
    rows;
  * pages per sync (fleet stream) and pages pulled per client;
  * deferred-row backlog while moving, and syncs-to-drain once the fleet
    goes static — the convergence claim (`pending` empties; a finite number
    proves no Gaussian is silently lost);
  * fleet sync latency (host wall-clock).

Set NEBULA_BENCH_SMOKE=1 for the CI trajectory run (small scene, fewer
syncs, B=2 only → every (pressure, tier) row still lands in
BENCH_delta_stream.json).
"""

import os
import time

import numpy as np

from benchmarks.bench_fleet_sync import _fleet_walk
from benchmarks.common import city_scene, emit
from repro.core import bitplane as bp
from repro.core.pipeline import SessionConfig
from repro.serve import lod_service as svc

FOCAL, TAU = 260.0, 48.0
OVERLAP = 0.5
PRESSURES = (1.0, 0.25, 0.0625)
# uncontrolled / a 4KB-per-sync trickle that binds at ANY scene scale (the
# controller must pace + eventually escalate τ) / the named phone preset
TIERS = (None, 4.0e3, "phone")
MAX_DRAIN = 64


def _smoke() -> bool:
    return os.environ.get("NEBULA_BENCH_SMOKE", "") not in ("", "0")


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def run():
    scale = "small" if _smoke() else "medium"
    syncs = 4 if _smoke() else 10
    batches = (2,) if _smoke() else (2, 8)
    page = 64 if _smoke() else 256
    _cfg, _leaves, tree = city_scene(scale)
    hi = np.asarray(tree.gaussians.mu).max(axis=0)
    extent = (float(hi[0]), float(hi[1]))
    cfg = SessionConfig(tau=TAU, cut_budget=16384)
    emit("delta_stream/scene", 0.0,
         f"scale={scale} nodes={tree.meta.n_real} page={page} syncs={syncs}")

    for b in batches:
        walks = _fleet_walk(b, syncs, OVERLAP, extent)
        # un-budgeted probe: the cold union the pressure axis is relative to
        probe = svc.LodService(tree, cfg, b, focal=FOCAL, mode="pooled",
                               dedup=True)
        u0 = int(np.asarray(probe.sync(walks[0]).unique_delta).sum())
        del probe
        emit(f"delta_stream/b{b}/cold_union", 0.0, f"rows={u0}")

        for press in PRESSURES:
            # pow2 budgets keep the stream-width retrace set bounded
            budget = max(2 * page, _pow2_ceil(int(u0 * press)))
            for tier in TIERS:
                service = svc.LodService(
                    tree, cfg, b, focal=FOCAL, mode="pooled", dedup=True,
                    delta_budget=budget, page_size=page, bandwidth=tier)
                t0 = time.perf_counter()
                first = service.sync(walks[0])
                np.asarray(first.sync_bytes)  # force the first (compile) sync
                t_first = time.perf_counter() - t0

                times, rows = [], [first]
                for f in range(1, syncs):
                    t0 = time.perf_counter()
                    stats = service.sync(walks[f])
                    np.asarray(stats.sync_bytes)
                    times.append(time.perf_counter() - t0)
                    rows.append(stats)

                # fleet stops moving: the carried debt must drain to zero
                drain = 0
                while (np.asarray(service.state.pending).any()
                       and drain < MAX_DRAIN):
                    service.sync(walks[-1])
                    drain += 1
                leftover = int(np.asarray(bp.count(service.state.pending)).sum())

                by = np.stack([np.asarray(s.sync_bytes) for s in rows])
                pages = np.stack([np.asarray(s.pages) for s in rows])
                stream_pages = np.stack(
                    [np.asarray(s.delta_shipped).max() for s in rows])
                backlog = np.stack(
                    [np.asarray(s.delta_deferred).sum() for s in rows])
                tname = ("uncapped" if tier is None else
                         tier if isinstance(tier, str) else
                         f"{int(tier)}B")
                key = (f"delta_stream/b{b}/p{int(press * 1000):04d}/{tname}")
                emit(f"{key}/sync_us", float(np.median(times) * 1e6)
                     if times else 0.0,
                     f"budget={budget} t_first={t_first * 1e3:.0f}ms")
                emit(f"{key}/bytes_per_client", float(by.mean()),
                     f"mean={by.mean() / 1024:.2f}KiB "
                     f"p95={np.percentile(by, 95) / 1024:.2f}KiB")
                emit(f"{key}/pages_per_sync", float(pages.mean()),
                     f"client_mean={pages.mean():.2f} "
                     f"shipped_rows_max={int(stream_pages.max())}")
                emit(f"{key}/deferred_backlog", float(backlog.mean()),
                     f"peak={int(backlog.max())} drain_syncs={drain} "
                     f"leftover={leftover}")
    emit("delta_stream/summary", 0.0,
         "paged stream: tight budgets bound per-sync bytes, carried debt "
         "drains once the fleet goes static — no Gaussian silently lost")


if __name__ == "__main__":
    run()
