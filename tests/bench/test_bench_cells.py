"""Every cell of the benchmark runs one short window through the harness on
the CPU, at a tiny city, without the look for a chip; its result line
carries the contract's keys."""

import io
import json

import pytest

import bench_support as bs

from bench import harness

CELLS = [(w["name"], w["traffic"]) for w in bs.spec()["workloads"]]
# the mixes in the tree that no cell runs yet, kept for the cells to come
SPARE_MIXES = sorted({p.stem for p in (bs.ROOT / "bench" / "traffic")
                      .glob("*.json")} - {mix for _, mix in CELLS})


@pytest.fixture(scope="module")
def spec():
    return bs.tiny_spec(mixes=sorted({mix for _, mix in CELLS})
                        + SPARE_MIXES)


def _one_window(mix, spec):
    result = bs.run_tiny(f"tiny.{mix}", spec_=spec)
    out, err = io.StringIO(), io.StringIO()
    harness.emit(result, out, err)
    line = json.loads(out.getvalue().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in
            harness.cell_metrics(spec, f"tiny.{mix}", "end_to_end")}
    assert set(line["metrics"]) == want
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        # at the tiny city a window may pass with no Δ row to ship
        assert m["value"] >= 0 if name == "downlink_bytes_per_update" \
            else m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    tail = err.getvalue().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "(limit " in t for t in tail)


@pytest.mark.parametrize("cell,mix", CELLS)
def test_cell_runs_one_window(cell, mix, spec):
    _one_window(mix, spec)


@pytest.mark.parametrize("mix", SPARE_MIXES)
def test_spare_mix_runs_one_window(mix, spec):
    _one_window(mix, spec)


def test_traced_run_reports_per_layer_metrics(spec):
    result = bs.run_tiny("tiny.walk", spec_=spec, trace=True)
    names = {m["name"] for m in spec["per_layer"]}
    assert result["correct"] is True
    assert set(result["metrics"]) <= names
    # counters need no device; the CPU's trace holds no device plane, so
    # the readers of device time find nothing and the metrics are left out
    assert {"compiles_in_window", "stale_pairs_per_tick"} \
        <= set(result["metrics"])
    assert "device_idle_share" not in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs(spec):
    from bench import traffic
    mix = traffic.load_mix(traffic.mix_path(bs.ROOT, "teleport"))
    a, b = (traffic.Layout(mix, 12, (500.0, 400.0), 1.7, 2**31 + 5)
            for _ in range(2))
    assert (a.spawn == b.spawn).all() and (a.order == b.order).all()
    assert (a.phase == b.phase).all()
    pa, pb = traffic.PoseStream(a), traffic.PoseStream(b)
    for c in range(12):
        pa.add(c, 0.0)
        pb.add(c, 0.0)
    # the same poses however the calls are spaced
    got_a = pa.due(0.5) + pa.due(3.0)
    got_b = pb.due(3.0)
    assert len(got_a) == len(got_b) > 12 * 72 * 3 - 12
    for (ca, ta, xa), (cb, tb, xb) in zip(sorted(got_a, key=lambda x: x[:2]),
                                          sorted(got_b, key=lambda x: x[:2])):
        assert ca == cb and ta == tb and (xa == xb).all()


def test_every_seed_walks_the_same_walks_in_another_order():
    """The mix fixes the walks, so every seed does the same work; the seed
    draws the order of joining and the phase of each client's poses."""
    from bench import traffic
    mix = traffic.load_mix(traffic.mix_path(bs.ROOT, "walk"))
    a, b = (traffic.Layout(mix, 16, (1664.0, 1664.0), 1.7, seed)
            for seed in (2**31 + 5, 2**33 + 9))
    assert (a.spawn == b.spawn).all()
    assert (a.order != b.order).any() and (a.phase != b.phase).any()
    path_a = traffic._Path(a, 3).upto(720)
    path_b = traffic._Path(b, 3).upto(720)
    assert (path_a == path_b).all()


def test_walkers_keep_pace_and_eye_height():
    """A waypoint walker covers at most speed x time, keeps its eyes within
    the head bob of the configured height, and pauses only at targets."""
    import numpy as np
    from bench import traffic
    mix = traffic.load_mix(traffic.mix_path(bs.ROOT, "walk"))
    m = mix["groups"][0]["motion"]
    layout = traffic.Layout(mix, 8, (1664.0, 1664.0), 1.7, 2**33 + 1)
    stream = traffic.PoseStream(layout)
    for c in range(8):
        stream.add(c, 0.0)
    poses = {}
    for c, _, pose in stream.due(30.0):
        poses.setdefault(c, []).append(pose)
    for c, path in poses.items():
        p = np.asarray(path, np.float64)
        step = np.linalg.norm(np.diff(p[:, :2], axis=0), axis=1)
        # positions are float32: a metre-scale coordinate rounds to ~1e-4 m
        assert step.max() <= m["speed_mps"] / mix["pose_hz"] + 2e-4
        # walking at least the share of time not spent pausing
        assert step.sum() >= m["speed_mps"] * 30.0 * 0.5
        assert np.abs(p[:, 2] - 1.7).max() <= m["bob_m"] + 1e-6
        assert (p[:, :2] >= 0).all() and (p[:, :2] <= 1664.0).all()


def test_stream_bytes_counts_every_page_a_client_pulls_whole():
    import numpy as np
    stream_bytes = harness._jitted()[0]
    rng = np.random.default_rng(5)
    u, ps, shipped, row = 16, 4, 11, 33
    ranks = rng.permutation(shipped)               # wire order != rank order
    row_page = np.full(u, -1, np.int32)
    row_page[:shipped] = ranks // ps
    ref = rng.random((3, u)) < 0.3
    ref[:, shipped:] = False
    want = []
    for b in range(3):
        pages = set(row_page[ref[b]].tolist())
        want.append(sum(int((row_page == p).sum()) * row
                        + harness.PAGE_HEADER_BYTES for p in pages))
    got = np.asarray(stream_bytes(ref, row_page, row, ps))
    assert got.tolist() == want
