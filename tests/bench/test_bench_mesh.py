"""A configuration that asks for a 4x1 `clients` x `slabs` mesh runs through
the harness on four virtual CPU devices (in a subprocess, which owns its
own device count)."""

import json
import os
import subprocess
import sys

import bench_support as bs

SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import bench_support as bs
s = bs.tiny_spec(config="tests/bench/data/tiny-city-4x1.json", mixes=["walk"])
s["workloads"][-1]["chips"] = 4
r = bs.run_tiny("tiny.walk", spec_=s)
print(json.dumps({{"correct": r["correct"], "device": r["device"],
                  "attempted": r["attempted"]}}))
"""


def test_4x1_config_runs_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(bs.ROOT), src=str(bs.ROOT / "src"),
                         here=str(bs.HERE))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.splitlines()[-1])
    assert got["device"]["count"] == 4
    assert got["correct"] is True and got["attempted"] > 0
