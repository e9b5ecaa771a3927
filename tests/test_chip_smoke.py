"""chip_smoke.py's phases on the CPU at a tiny scene (kernels interpreted),
and its refusal to run without a TPU. The full-size run needs the chip.

The sharded phase needs four devices, so it runs in a process of its own,
started before the first test so that it overlaps the in-process phases."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()
BUDGET = 1024  # cut budget: a tiny scene's unions saturate one stream width

_SHARDED = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)
city, tree = cs.build_scene(blocks=1, seed=0)
cs.sharded_phase(city, tree, n_clients=4, wave=2, syncs=3, cut_budget=1024)
"""


@pytest.fixture(scope="module", autouse=True)
def sharded_run():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.Popen([sys.executable, "-c", _SHARDED], cwd=ROOT,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    yield proc
    if proc.poll() is None:
        proc.kill()
        proc.communicate()


@pytest.fixture(scope="module")
def scene():
    return cs.build_scene(blocks=1, seed=0)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_served_phase_ticks_partial_admit_evict(scene, capsys):
    city, tree = scene
    svc = cs.served_phase(city, tree, n_clients=3, wave=2, ticks=5,
                          cut_budget=BUDGET)
    out = capsys.readouterr().out
    assert svc.n_clients == 2          # 3 joined, 1 evicted
    assert out.count("tick ") == 5 and "admitted=1" in out
    assert "partial_ticks=0" not in out
    assert "admission gate: cost model" in out and "next admit: " in out


def test_reference_and_pallas_phases_bitwise(scene, capsys):
    city, tree = scene
    pooled, positions = cs.reference_phase(city, tree, n_clients=2, syncs=2,
                                           cut_budget=BUDGET)
    cs.pallas_phase(city, tree, n_clients=2, syncs=2, cut_budget=BUDGET)
    out = capsys.readouterr().out
    assert out.count("bitwise equal") == 5
    assert positions.shape == (2, 3) and pooled.n_clients == 2


def test_render_phase_paths_agree(scene):
    city, tree = scene
    pooled, positions = cs.reference_phase(city, tree, n_clients=2, syncs=1,
                                           cut_budget=BUDGET)
    cs.render_phase(pooled, positions, width=16, height=16,
                    max_pairs=1 << 10)


def test_failed_check_raises():
    with pytest.raises(cs.SmokeFailure):
        cs._assert_same([np.zeros(2)], [np.ones(2)], "probe")


def test_sharded_phase_on_four_virtual_devices(sharded_run):
    stdout, stderr = sharded_run.communicate(timeout=600)
    assert sharded_run.returncode == 0, stderr[-3000:]
    assert stdout.count("bitwise equal to one device") == 3
    assert "admitted 2" in stdout and "evicted" in stdout
