"""Shared set-up of the benchmark's tests: the checkout on `sys.path`, and a
spec with a tiny city for each of the benchmark's traffic mixes."""

import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = "tests/bench/data/tiny-city.json"


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def tiny_spec(config: str = TINY_CONFIG, mixes=None) -> dict:
    """BENCHMARK.json plus a tiny-city configuration and one tiny cell
    (`tiny.<mix>`) per traffic mix of the real cells."""
    s = copy.deepcopy(spec())
    name = pathlib.Path(config).stem
    s["configs"].append({"name": name, "file": config})
    mixes = mixes or sorted({w["traffic"] for w in s["workloads"]})
    for mix in mixes:
        s["workloads"].append({"name": f"tiny.{mix}", "config": name,
                               "traffic": mix, "chips": 1})
    return s


def run_tiny(workload: str, *, seed: int = 2**31 + 7, seconds: float = 1.0,
             trace: bool = False, control: bool = False, spec_=None,
             root=ROOT):
    """One run of a tiny cell through the harness, without the look for a
    chip, the scene cache or JAX's persistent compilation cache."""
    from bench import harness
    s = spec_ if spec_ is not None else tiny_spec()
    return harness.Run(root, s, workload, seed, seconds, trace=trace,
                       control=control, gate=False, cache=False).execute()
