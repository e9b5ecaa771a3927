"""Mesh-sharded fleet service: per-client sync cost and per-shard state
residency as the serving mesh widens (ROADMAP "shard ServiceState + tree on
the cloud mesh").

Sweep: fleet size B ∈ {4, 16, 64} × mesh {1, 2, 4, 8} virtual CPU devices
(the `clients` axis of `launch.make_fleet_mesh`; mesh 1 is the unsharded
baseline service). Every cell runs in its OWN subprocess with
`JAX_PLATFORMS=cpu` and `--xla_force_host_platform_device_count=8` — XLA's
device count is fixed at first import, so the parent bench process (which
must keep seeing its own device) cannot host the meshes itself. These rows
are CPU rehearsals; the sharded path on real chips is
`chip_smoke.py --chips 4`.

Reported per cell:
  * `us_per_call` — steady-state pooled sync wall time / B (per-client cost;
    on host-platform virtual devices this measures partitioning OVERHEAD,
    not speedup — the 8 "devices" share one CPU. The number that must not
    regress is mesh-1);
  * `derived` — fleet sync µs, max per-shard resident bytes of the
    slot-axis service state under its client-axis placement
    (`sharding.fleet.shard_resident_bytes` — the HBM-per-host figure the
    sharding exists to bound) and the same figure unsharded.

Set NEBULA_BENCH_SMOKE=1 for the CI trajectory run (small scene,
B ∈ {4, 16}, mesh ∈ {1, 2}, fewer syncs → every row still present in
BENCH_fleet_shard.json).
"""

import json
import os
import subprocess
import sys

from benchmarks.common import emit

FOCAL, TAU = 260.0, 48.0


def _smoke() -> bool:
    return os.environ.get("NEBULA_BENCH_SMOKE", "") not in ("", "0")


_SUBPROC = r"""
import os, json, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import numpy as np, jax
cfg_in = json.loads(sys.argv[1])
B, shards, smoke = cfg_in["B"], cfg_in["shards"], cfg_in["smoke"]

from repro.core.gaussians import CityConfig, generate_city
from repro.core.lod_tree import build_lod_tree
from repro.launch.mesh import make_fleet_mesh
from repro.serve import lod_service as svc
from repro.sharding import fleet as shf

city = CityConfig(blocks_x=2 if smoke else 4, blocks_y=2 if smoke else 4,
                  leaf_density=0.10 if smoke else 0.25, seed=2)
leaves = generate_city(city)
tree = build_lod_tree(leaves, target_subtrees=16 if smoke else 64, seed=0)
cfg = svc.SessionConfig(tau=%(tau)r, cut_budget=8192)
mesh = None if shards == 1 else make_fleet_mesh(clients=shards, slabs=1)
service = svc.LodService(tree, cfg, B, focal=%(focal)r, mode="pooled",
                         dedup=True, mesh=mesh)

rng = np.random.default_rng(0)
lo = np.asarray([0.15 * city.blocks_x * 50, 0.15 * city.blocks_y * 50, 1.5])
hi = np.asarray([0.85 * city.blocks_x * 50, 0.85 * city.blocks_y * 50, 8.0])
pos = rng.uniform(lo, hi, (B, 3)).astype(np.float32)

def one_sync():
    global pos
    pos = np.clip(pos + rng.normal(0, 3.0, (B, 3)), lo, hi).astype(np.float32)
    stats = service.sync(pos)
    np.asarray(stats.sync_bytes)   # force

for _ in range(2):
    one_sync()                     # warmup/compile
ts = []
for _ in range(3 if smoke else 6):
    t0 = time.perf_counter()
    one_sync()
    ts.append(time.perf_counter() - t0)

shard_bytes = shf.shard_resident_bytes(mesh, service.state)
flat_bytes = shf.shard_resident_bytes(None, service.state)
print(json.dumps({
    "fleet_us": float(np.median(ts) * 1e6),
    "shard_bytes": int(shard_bytes),
    "flat_bytes": int(flat_bytes),
    "devices": len(jax.devices()),
}))
""" % {"tau": TAU, "focal": FOCAL}


def run():
    smoke = _smoke()
    fleets = (4, 16) if smoke else (4, 16, 64)
    meshes = (1, 2) if smoke else (1, 2, 4, 8)
    for b in fleets:
        for d in meshes:
            if b % d:
                # clients axis must divide the slot capacity (== B here) or
                # every constraint replicates — the row would silently
                # re-measure the unsharded program under a mesh8 label
                print(f"# skip fleet_shard_B{b}_mesh{d}: {d} does not "
                      f"divide B={b} (replicate fallback)", flush=True)
                continue
            payload = json.dumps({"B": b, "shards": d, "smoke": smoke})
            # the children measure virtual-device partitioning on the CPU
            # by design: never let one reach for an accelerator the parent
            # process may hold
            out = subprocess.run([sys.executable, "-c", _SUBPROC, payload],
                                 capture_output=True, text=True, timeout=1800,
                                 env={**os.environ, "JAX_PLATFORMS": "cpu"})
            if out.returncode != 0:
                raise RuntimeError(
                    f"bench_fleet_shard B={b} mesh={d} failed:\n"
                    f"{out.stderr[-2000:]}")
            row = json.loads(out.stdout.strip().splitlines()[-1])
            emit(f"fleet_shard_B{b}_mesh{d}", row["fleet_us"] / b,
                 f"fleet_us={row['fleet_us']:.0f} "
                 f"shard_state_bytes={row['shard_bytes']} "
                 f"flat_state_bytes={row['flat_bytes']}")


if __name__ == "__main__":
    run()
