"""Benchmark harness — one module per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV rows.

    python -m benchmarks.run [--only bench_fleet_sync ...] [--json PATH]

``--only`` restricts the run to the named modules (short names accepted);
``--json PATH`` additionally writes every emitted row to a machine-readable
trajectory file (the ``BENCH_<name>.json`` convention — CI emits
``BENCH_fleet_sync.json`` each run so the perf trajectory accumulates).
"""

import argparse
import json
import platform
import sys
import traceback

from benchmarks import common
from repro.launch.compile_cache import enable_compilation_cache

MODULES = [
    "benchmarks.bench_memory",       # Figs. 2/6
    "benchmarks.bench_lod_search",   # Figs. 7/20
    "benchmarks.bench_multiclient",  # multi-user cloud serving (ROADMAP)
    "benchmarks.bench_fleet_sync",   # encode-once fleet sync (dedup × B)
    "benchmarks.bench_fleet_churn",  # ragged fleet lifecycle (admit/evict)
    "benchmarks.bench_fleet_recovery",  # snapshot/restore + journal replay
    "benchmarks.bench_fleet_shard",  # mesh-sharded fleet (clients × slabs)
    "benchmarks.bench_delta_stream",  # paged Δ stream (pressure × tier)
    "benchmarks.bench_mtp",          # deadline scheduler vs lockstep MTP
    "benchmarks.bench_bandwidth",    # Figs. 5/17(bw)/24
    "benchmarks.bench_stereo",       # Figs. 8/21
    "benchmarks.bench_stereo_batched",  # fleet-batched client rendering
    "benchmarks.bench_quality",      # Figs. 16/17(quality)
    "benchmarks.bench_e2e",          # Figs. 18/19/22
    "benchmarks.bench_tile_size",    # Figs. 23/25
    "benchmarks.bench_kernels",      # per-kernel sweeps
    "benchmarks.bench_lm",           # framework LM throughput
]


def _select(only):
    if not only:
        return list(MODULES)
    picked = []
    for name in only:
        matches = [m for m in MODULES
                   if m == name or m.split(".")[-1] == name]
        if not matches:
            raise SystemExit(f"unknown benchmark module: {name!r} "
                             f"(choose from {[m.split('.')[-1] for m in MODULES]})")
        picked.extend(matches)
    return picked


def write_json(path: str, modules, failed) -> None:
    """Write the collected rows as one trajectory point."""
    doc = {
        "schema": "nebula-bench-rows/1",
        "platform": platform.platform(),
        "python": platform.python_version(),
        "modules": modules,
        "failed": failed,
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for (n, us, d) in common.ROWS],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# wrote {len(common.ROWS)} rows -> {path}", flush=True)


def main(argv=None) -> None:
    enable_compilation_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", metavar="MODULE",
                    help="run only this module (repeatable; short name ok)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write emitted rows to PATH as JSON")
    args = ap.parse_args(argv)
    modules = _select(args.only)

    print("name,us_per_call,derived")
    failed = []
    for mod_name in modules:
        print(f"# --- {mod_name} ---", flush=True)
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
    if args.json:
        write_json(args.json, modules, failed)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
