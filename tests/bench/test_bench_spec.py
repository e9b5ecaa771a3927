"""`BENCHMARK.json` keeps to the benchmark's contract; the harness finds a
configuration, a traffic mix and a metric by name, so a new cell needs new
files and entries only; and nothing is measured without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_support as bs

from bench import harness, roofline, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_keeps_to_the_contract():
    s = bs.spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert (bs.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= s["run_seconds"] <= 51 and isinstance(s["run_seconds"], int)
    for p in s["paths"]:
        assert (bs.ROOT / p).is_dir() and not p.startswith("/")
    assert s["command"] == ["python3", "bench/run.py"]
    cfg_names = [c["name"] for c in s["configs"]]
    used = {w["config"] for w in s["workloads"]}
    assert set(cfg_names) == used and len(set(cfg_names)) == len(cfg_names)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        cfg = json.load(open(bs.ROOT / c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = set()
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert traffic.mix_path(bs.ROOT, w["traffic"]).exists()
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (bs.ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    names += [w["name"] for w in s["workloads"]] + cfg_names
    assert len(names) == len(set(names))
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_finds_config_traffic_and_metric_by_name():
    s = bs.spec()
    cell, entry = harness.find_cell(s, "city32-walk")
    cfg = harness.load_config(bs.ROOT, entry)
    assert cfg["fleet_slots"] == 32 and cell["traffic"] == "walk"
    mix = traffic.load_mix(traffic.mix_path(bs.ROOT, cell["traffic"]))
    assert mix["groups"][0]["motion"]["kind"] == "waypoint"
    read = harness.metric_reader(bs.ROOT, "stale_pairs_per_tick")
    assert callable(read)
    with pytest.raises(harness.BenchError):
        harness.find_cell(s, "no-such-cell")


def test_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A configuration, a traffic mix and a per-layer metric are added as
    files plus BENCHMARK.json entries; the harness itself is untouched."""
    shutil.copytree(bs.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".scene_cache", ".trace",
                                                  "__pycache__"))
    cfg = json.load(open(bs.ROOT / bs.TINY_CONFIG))
    cfg.update(name="tiny-plaza", fleet_slots=3, wave=3)
    (tmp_path / "bench/configs/tiny-plaza.json").write_text(json.dumps(cfg))
    mix = json.load(open(traffic.mix_path(bs.ROOT, "crowd")))
    mix["groups"][0]["spawn"]["count"] = 2
    (tmp_path / "bench/traffic/pair.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/ticks_in_window.py").write_text(
        "def read(rec):\n    return float(len(rec.window_ticks))\n")
    s = bs.spec()
    s["configs"].append({"name": "tiny-plaza", "source": "a test",
                         "file": "bench/configs/tiny-plaza.json",
                         "reduced": [], "why": "a test"})
    s["workloads"].append({"name": "tiny-plaza.pair", "config": "tiny-plaza",
                           "traffic": "pair", "chips": 1, "why": "a test"})
    s["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                           "better": "higher", "source": "program_counter",
                           "layer": "host control plane",
                           "moves": "updates_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    result = bs.run_tiny("tiny-plaza.pair", trace=True,
                         spec_=harness.load_spec(tmp_path), root=tmp_path)
    assert result["correct"] is True
    assert result["metrics"]["ticks_in_window"]["value"] >= 1


def _run_bench(cwd, *args, env=None):
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "city32-walk",
         "--seed", "3", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _result_lines(stdout: str):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_refuses_to_time_without_a_tpu():
    done = _run_bench(bs.ROOT)
    assert done.returncode != 0
    assert not _result_lines(done.stdout)
    assert "no TPU" in done.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    s = bs.spec()
    shutil.copy(bs.ROOT / "BENCHMARK.json", tmp_path)
    for p in s["paths"]:
        shutil.copytree(bs.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".scene_cache",
                                                      ".trace",
                                                      "__pycache__"))
    done = _run_bench(tmp_path)
    assert done.returncode != 0
    assert not _result_lines(done.stdout)


def test_roofline_counts_needed_work_against_known_peaks():
    ops, nbytes = roofline.pair_sweep_work(1000, 1536)
    assert ops == 1000 * 1536 * roofline.NODE_OPS
    assert nbytes == 1000 * (1536 * 23 + roofline.PAIR_BYTES)
    peak = roofline.peaks(bs.ROOT, "TPU v5 lite")
    seconds, bound = roofline.least_time_s((ops, nbytes), peak)
    assert bound == "hbm" and seconds == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks(bs.ROOT, "TPU v4")
