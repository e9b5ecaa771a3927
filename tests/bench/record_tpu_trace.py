"""Record the short TPU trace kept at `data/tpu_trace_pruned.json`.

    python3 tests/bench/record_tpu_trace.py <trace_dir> <out.json>

On one chip it serves the tiny test city (`data/tiny-city.json`) to its
four slots through a pooled, bandwidth-controlled `LodService` under the
`DeadlineScheduler`, every client stepping about 2 m each tick. After 8
ticks that compile every shape, it traces 3 ticks inside `bench.window`,
each in `bench.tick`, into `<trace_dir>`, and writes the events the scoped
reduction reads (`bench/scoped_trace.prune`) to `<out.json>`.
"""

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import scene, scoped_trace  # noqa: E402
from repro.core.pipeline import SessionConfig  # noqa: E402
from repro.serve.lod_service import LodService  # noqa: E402
from repro.serve.scheduler import CostModel, DeadlineScheduler  # noqa: E402


def main(trace_dir: str, out: str) -> None:
    with open(HERE / "data" / "tiny-city.json") as f:
        cfg = json.load(f)
    host, info = scene.load(ROOT, cfg["name"], cfg, cache=False)
    n, tiers = int(cfg["fleet_slots"]), cfg["tiers"]
    svc = LodService(scene.to_device(host),
                     SessionConfig(tau=float(cfg["tau_px"]),
                                   cut_budget=int(cfg["cut_budget"]),
                                   w_star=int(cfg["w_star"])),
                     n, float(cfg["focal_px"]), mode="pooled",
                     bandwidth=[tiers[i % len(tiers)] for i in range(n)])
    sched = DeadlineScheduler(svc, cost_model=CostModel(0.0, 0.0))
    rng = np.random.default_rng(0)
    ext = np.float32(info["extent"])
    pos = np.concatenate([rng.uniform(0.2, 0.8, (n, 2)) * ext,
                          np.full((n, 1), cfg["eye_height_m"])], 1)

    def tick():
        nonlocal pos
        pos = pos + rng.normal(size=pos.shape) * [2.0, 2.0, 0.0]
        for cid in svc.active_ids:
            sched.observe_motion(cid, pos[svc._slot_of(cid)])
        jax.block_until_ready((sched.tick(), svc.last_delta))

    for _ in range(8):
        tick()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                tick()
    jax.profiler.stop_trace()
    scoped_trace.write_pruned(trace_dir, out)


if __name__ == "__main__":
    main(*sys.argv[1:3])
