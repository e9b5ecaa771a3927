"""The reduction from a profiler trace to busy and idle time, per-program
device time and host self time, on hand-made events with known answers."""

import pytest

import bench_support  # noqa: F401  (puts the checkout on sys.path)

from bench import trace


def _hand_made():
    # window 0..100; device busy 10..30 (two overlapping ops) and 50..60
    ops = [[(10.0, 25.0), (20.0, 30.0), (50.0, 60.0), (95.0, 120.0)]]
    modules = [[(10.0, 30.0, "jit__pooled_pair_sweep(3)"),
                (50.0, 55.0, "jit__union_refs(9)"),
                (55.0, 60.0, "jit_encode(12)"),
                (95.0, 120.0, "jit__union_refs(9)")]]
    spans = [(0.0, 100.0, "bench.window"), (0.0, 10.0, "bench.ingest"),
             (10.0, 70.0, "bench.tick"), (70.0, 100.0, "bench.check")]
    return trace.Trace(window=(0.0, 100.0), ops=ops, modules=modules,
                       spans=spans)


def test_busy_idle_and_program_time_of_hand_made_events():
    t = _hand_made()
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(35e-9)       # 20 + 10 + 5 in window
    assert t.program_ns([r"_pooled_pair_sweep"]) == pytest.approx(20.0)
    assert t.program_ns([r"_union_refs"]) == pytest.approx(10.0)
    # patterns see the name without the id JAX appends, so an anchored
    # pattern matches a module of that name whatever its id
    assert t.program_ns([r"^jit_encode$"]) == pytest.approx(5.0)
    assert t.program_ns([r"^jit_encode\(12\)$"]) is None
    assert t.program_ns([r"no_such_program"]) is None
    # the tick span 10..70 holds 30 ns of device work: 30 ns of host time
    assert t.host_ms_per_span("bench.tick") == pytest.approx(30e-6)
    gaps = t.idle_gaps()
    assert gaps[0] == ("check", pytest.approx(35e-9))   # 60..95
    assert ("tick", pytest.approx(20e-9)) in gaps        # 30..50
    assert ("ingest", pytest.approx(10e-9)) in gaps      # 0..10
    b = t.breakdown()
    assert b["device_ops"][0] == ["jit__pooled_pair_sweep",
                                  pytest.approx(20e-9)]


def test_union_merges_and_clips():
    assert trace.union([(5, 8), (1, 3), (2, 4), (7, 12)], 0, 10) == \
        [(1, 4), (5, 10)]
    assert trace.covered([(1, 4), (5, 10)], 3, 6) == 2
