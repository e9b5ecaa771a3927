"""The scoped reduction of a profiler trace (`bench/scoped_trace.py`), on
hand-made events with known answers: device time per stage scope and in no
stage, device-idle time inside the program's read spans, idle gaps labelled
by the innermost covering span, and every existing metric reader reading
the same with and without the program's extra events.

`data/tpu_trace_pruned.json` is a trace recorded on one TPU v5 lite by
`record_tpu_trace.py` (three ticks of the tiny city through the served
path), pruned to the events the reduction reads; it holds every stage
scope and every span the program writes."""

import collections
import json

import pytest

import bench_support as bs

from bench import harness, scoped_trace, trace
from repro.serve import tracing

STAGES = ("lod.staleness", "lod.pair_sweep", "lod.pair_sweep/gather",
          "table.update", "delta.union")
EXISTING = sorted(m["name"] for m in bs.spec()["per_layer"])


def _plain():
    # window 0..100: the events of tests/bench/test_bench_trace.py
    ops = [[(10.0, 25.0), (20.0, 30.0), (50.0, 60.0), (95.0, 120.0)]]
    modules = [[(10.0, 30.0, "jit__pooled_pair_sweep(3)"),
                (50.0, 55.0, "jit__union_refs(9)"),
                (55.0, 60.0, "jit_encode(12)"),
                (95.0, 120.0, "jit__union_refs(9)")]]
    spans = [(0.0, 100.0, "bench.window"), (0.0, 10.0, "bench.ingest"),
             (10.0, 70.0, "bench.tick"), (70.0, 100.0, "bench.check")]
    return ops, modules, spans


PATHS = [["jit(_pooled_pair_sweep)/lod.pair_sweep/gather/gather",
          "jit(_pooled_pair_sweep)/lod.pair_sweep/while",
          "jit(_union_refs)/delta.union/cumsum",
          "jit(_take)/gather"]]
NEBULA = [(10.0, 69.0, "nebula.sched.tick"),
          (12.0, 18.0, "nebula.sched.select"),
          (13.0, 17.0, "nebula.sched.preview_read"),
          (18.0, 64.0, "nebula.svc.sync"),
          (31.0, 45.0, "nebula.svc.stale_count_read"),
          (64.0, 69.0, "nebula.sched.wait")]


def _scoped():
    ops, modules, spans = _plain()
    return scoped_trace.ScopedTrace(window=(0.0, 100.0), ops=ops,
                                    modules=modules, spans=spans + NEBULA,
                                    op_paths=PATHS)


def test_stage_time_goes_to_the_innermost_operation():
    t = _scoped()
    # 10..20 gather alone, 20..25 both open: the later-started `while`
    # holds them, 25..30 the while alone
    assert t.scope_ns("lod.pair_sweep/gather") == pytest.approx(10.0)
    assert t.scope_ns("lod.pair_sweep") == pytest.approx(20.0)
    assert t.scope_ns("delta.union") == pytest.approx(10.0)
    assert t.scope_ns("table.update") is None
    # the eager take (95..100 in the window) is in no stage
    assert t.unscoped_ns(STAGES) == pytest.approx(5.0)
    # stages and the unscoped rest partition the busy time
    total = sum(t.scope_ns(s) or 0.0 for s in
                ("lod.pair_sweep", "delta.union")) + t.unscoped_ns(STAGES)
    assert total == pytest.approx(t.busy_s() * 1e9)


def test_a_stage_matches_whole_path_parts_in_a_row():
    assert scoped_trace.path_has("jit(f)/lod.pair_sweep/gather/x",
                                 "lod.pair_sweep/gather")
    assert not scoped_trace.path_has("jit(f)/lod.pair_sweep/x/gather",
                                     "lod.pair_sweep/gather")
    assert not scoped_trace.path_has("jit(f)/lod.pair_sweeps/x",
                                     "lod.pair_sweep")


def test_idle_time_inside_the_read_spans():
    t = _scoped()
    # preview read 13..17 is busy throughout; the stale-count read 31..45
    # idles from 31 to 45 (busy again at 50); the wait 64..69 idles
    assert t.idle_in_spans(["sched.preview_read"]) == pytest.approx(0.0)
    assert t.idle_in_spans(["svc.stale_count_read"]) == pytest.approx(14.0)
    assert t.idle_in_spans(["sched.preview_read", "svc.stale_count_read",
                            "sched.wait"]) == pytest.approx(19.0)
    assert t.idle_in_spans(["delta.union_size_read"]) is None


def test_gaps_take_the_innermost_covering_span():
    gaps = dict((label, s) for label, s in _scoped().idle_gaps())
    # 60..95: the wait covers 5 of 35 ns, the check 30: the check holds it
    assert gaps["check"] == pytest.approx(35e-9)
    # 30..50: the stale-count read covers 14 of 20 ns, inside svc.sync
    assert gaps["nebula.svc.stale_count_read"] == pytest.approx(20e-9)
    assert gaps["ingest"] == pytest.approx(10e-9)


def test_without_program_events_the_gaps_are_the_plain_reduction():
    ops, modules, spans = _plain()
    plain = trace.Trace(window=(0.0, 100.0), ops=ops, modules=modules,
                        spans=spans)
    bare = scoped_trace.ScopedTrace(window=(0.0, 100.0), ops=ops,
                                    modules=modules, spans=spans,
                                    op_paths=[["", "", "", ""]])
    assert bare.idle_gaps() == plain.idle_gaps()
    assert bare.breakdown() == plain.breakdown()
    assert bare.scope_ns("lod.pair_sweep") is None
    assert bare.unscoped_ns(STAGES) is None
    assert bare.idle_in_spans(["sched.wait"]) is None


def _record(t):
    ticks = [harness.Tick(start=10.0, done=70.0, served=4,
                          latencies_ms=[1.0, 2.0], stale_pairs=40,
                          in_window=True)]
    return harness.Record(ticks=ticks, compiles_in_window=0,
                          memory_peak_bytes=1 << 30, slab_width=128,
                          device_kind="TPU v5 lite", root=bs.ROOT, trace=t)


@pytest.mark.parametrize("name", EXISTING)
def test_existing_reader_reads_the_same_with_program_events(name):
    ops, modules, spans = _plain()
    plain = trace.Trace(window=(0.0, 100.0), ops=ops, modules=modules,
                        spans=spans)
    read = harness.metric_reader(bs.ROOT, name)
    assert read(_record(_scoped())) == read(_record(plain))


def test_prune_round_trips():
    t = _scoped()
    again = scoped_trace.from_json(scoped_trace.prune(t))
    assert again.scope_ns("lod.pair_sweep") == t.scope_ns("lod.pair_sweep")
    assert again.idle_gaps() == t.idle_gaps()
    assert scoped_trace.prune(again) == scoped_trace.prune(t)



def test_unnamed_ops_take_the_path_their_program_run_shares():
    ops = [[(0.0, 10.0), (10.0, 12.0), (12.0, 20.0), (30.0, 40.0)]]
    modules = [[(0.0, 20.0, "jit__pooled_pair_sweep(1)"),
                (30.0, 40.0, "jit__where(2)")]]
    paths = [["jit(_pooled_pair_sweep)/lod.pair_sweep/gather/gather",
              "",                         # a copy the compiler added
              "jit(_pooled_pair_sweep)/lod.pair_sweep/while",
              "a"]]                       # a parameter, in an eager op
    t = scoped_trace.ScopedTrace(
        window=(0.0, 50.0), ops=ops, modules=modules,
        spans=[(0.0, 50.0, "bench.window")], op_paths=paths)
    assert t.scope_ns("lod.pair_sweep") == pytest.approx(20.0)
    assert t.scope_ns("lod.pair_sweep/gather") == pytest.approx(10.0)
    assert t.unscoped_ns(STAGES) == pytest.approx(10.0)


@pytest.fixture(scope="module")
def tpu():
    with open(bs.HERE / "data" / "tpu_trace_pruned.json") as f:
        return scoped_trace.from_json(json.load(f))


@pytest.mark.parametrize("stage", tracing.STAGES)
def test_tpu_trace_holds_every_stage(tpu, stage):
    assert tpu.scope_ns(stage) > 0


def test_tpu_trace_holds_every_span_of_each_tick(tpu):
    names = collections.Counter(n for _, _, n in tpu.spans)
    ticks = names["bench.tick"]
    assert ticks == 3
    program = {n[len(tracing.SPAN_PREFIX):]: c for n, c in names.items()
               if n.startswith(tracing.SPAN_PREFIX)}
    assert program == {n: ticks for n in (
        "sched.tick", "sched.select", "svc.sync", *tracing.READ_SPANS)}
    assert tpu.idle_in_spans(tracing.READ_SPANS) > 0


def test_tpu_trace_stages_and_the_rest_partition_busy_time(tpu):
    top = [s for s in tracing.STAGES if "/" not in s]
    total = sum(tpu.scope_ns(s) for s in top) + tpu.unscoped_ns(top)
    assert total == pytest.approx(tpu.busy_s() * 1e9)
    plain = trace.Trace(window=tpu.window, ops=tpu.ops,
                        modules=tpu.modules,
                        spans=[x for x in tpu.spans
                               if x[2].startswith(trace.SPAN_PREFIX)])
    assert tpu.busy_s() == plain.busy_s()
    assert tpu.program_ns([r"_pooled_pair_sweep"]) == \
        plain.program_ns([r"_pooled_pair_sweep"])
