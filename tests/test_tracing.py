"""Tracing of the fleet sync (`repro.serve.tracing`), on the CPU.

  * every jitted program of a pooled sync carries its stage scope in the
    `op_name` locations of its lowering (this guards scope placement
    without a chip);
  * one scheduler tick under the profiler writes the `nebula.*` host spans
    nested as documented, all with the tick's index, and the blocking-read
    spans number five on a bandwidth-controlled fleet;
  * the counters: `stale_causes` sums to the resweeps, `lanes` is the pow2
    bucket the sync swept, and each client's queue wait plus service time
    is its stamped `mtp_ms`;
  * tracing changes no number: a tick with the profiler on gives state,
    stats and Δ batch bitwise equal to one with it off.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import compression as comp
from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.serve import delta_path as dp
from repro.serve import lod_service as svc
from repro.serve import scheduler as sch
from repro.serve import tracing

FOCAL = 1400.0
TAU = 32.0

# every jitted program of the served path, by module, and its stage
PROGRAMS = [
    (ls, "batched_top_and_staleness", "lod.staleness"),
    (ls, "predicted_stale_counts", "lod.staleness"),
    (svc, "_compact_stale_pairs", "lod.staleness"),
    (svc, "_pooled_pair_sweep", "lod.pair_sweep"),
    (mgr, "batched_cloud_sync", "table.update"),
    (svc, "_batched_cut_gids", "table.update"),
    (svc, "_apply_pooled_updates", "table.update"),
    (dp, "first_owner_counts", "table.update"),
    (dp, "_union_mask", "delta.union"),
    (dp, "_rank_union", "delta.union"),
    (dp, "_union_refs", "delta.union"),
    (comp, "encode", "delta.union"),
]

# the documented nesting: span -> its parent
PARENT = {
    "sched.select": "sched.tick",
    "sched.preview_read": "sched.select",
    "svc.sync": "sched.tick",
    "svc.rate_read": "svc.sync",
    "svc.stale_count_read": "svc.sync",
    "delta.union_size_read": "svc.sync",
    "sched.wait": "sched.tick",
}


class _Clock:
    """Scripted monotonic clock: +1 ms per read."""

    def __init__(self, t0: float = 100.0, step: float = 1e-3):
        self.t, self.step = float(t0), float(step)

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def _service(tree, n=4, **kw):
    cfg = svc.SessionConfig(tau=TAU, cut_budget=2048)
    return svc.LodService(tree, cfg, n, focal=FOCAL, mode="pooled",
                          dedup=True, **kw)


def _moves(rng, n):
    return rng.uniform([2, 2, 1], [28, 28, 6], (n, 3)).astype(np.float32)


def _scheduled(tree, n=4, seed=3):
    """A bandwidth-controlled fleet that has synced once (so the rate
    controller reads back the last sync's bytes), under a scheduler with a
    scripted clock, and a pose for every client."""
    service = _service(tree, n, bandwidth=["phone", "headset"] * (n // 2))
    rng = np.random.default_rng(seed)
    service.sync(_moves(rng, n))
    sched = sch.DeadlineScheduler(service, default_deadline_ms=1e6,
                                  clock=_Clock())
    for cid in service.active_ids:
        sched.observe_motion(cid, _moves(rng, 1)[0])
    return service, sched


def _struct(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
    return x


@pytest.fixture(scope="module")
def lowered(tiny_tree):
    """Debug-info text of each program's lowering at the arguments one
    scheduler tick called it with."""
    calls = {}
    mp = pytest.MonkeyPatch()
    for mod, name, _ in PROGRAMS:
        real = getattr(mod, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            calls.setdefault(_name, (_real, jax.tree.map(
                _struct, (args, kwargs))))
            return _real(*args, **kwargs)

        mp.setattr(mod, name, spy)
    try:
        _, sched = _scheduled(tiny_tree)
        sched.tick()
    finally:
        mp.undo()
    text = {}
    for name, (real, (args, kwargs)) in calls.items():
        text[name] = real.lower(*args, **kwargs).as_text(debug_info=True)
    real, (args, kwargs) = calls["_pooled_pair_sweep"]
    text["_pooled_pair_sweep/pallas"] = real.lower(
        *args, **dict(kwargs, impl="pallas")).as_text(debug_info=True)
    stale = jax.ShapeDtypeStruct((4, tiny_tree.meta.Ns), bool)
    text["_shard_stale_counts"] = svc._shard_stale_counts.lower(
        stale, 2).as_text(debug_info=True)
    return text


@pytest.mark.parametrize("name,stage", [(n, s) for _, n, s in PROGRAMS]
                         + [("_shard_stale_counts", "lod.staleness")])
def test_program_lowers_inside_its_stage_scope(lowered, name, stage):
    assert name in lowered, f"one tick never called {name}"
    assert f"/{stage}/" in lowered[name]


@pytest.mark.parametrize("name", ["_pooled_pair_sweep",
                                  "_pooled_pair_sweep/pallas"])
def test_pair_sweep_gathers_lower_inside_the_gather_scope(lowered, name):
    assert "/lod.pair_sweep/gather/" in lowered[name]


def test_unknown_stage_is_refused():
    with pytest.raises(AssertionError):
        tracing.scope("lod.no_such_stage")


def _spans(trace_dir):
    """(start, end, name, tick) of every `nebula.*` host span, by start."""
    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.SPAN_PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name[len(tracing.SPAN_PREFIX):],
                                dict(e.stats).get("tick")))
    return sorted(out)


def _traced_tick(sched, trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        stats = sched.tick()
        jax.block_until_ready((stats, sched.service.last_delta))
    finally:
        jax.profiler.stop_trace()
    return stats


def test_tick_spans_nest_as_documented(tiny_tree, tmp_path):
    service, sched = _scheduled(tiny_tree)
    index = service.syncs
    _traced_tick(sched, tmp_path)
    spans = _spans(tmp_path)
    names = collections.Counter(n for _, _, n, _ in spans)
    assert names == collections.Counter(["sched.tick", *PARENT])
    assert {t for _, _, _, t in spans} == {index}
    at = {n: (s, e) for s, e, n, _ in spans}
    for child, parent in PARENT.items():
        (cs, ce), (ps, pe) = at[child], at[parent]
        assert ps <= cs and ce <= pe, (child, parent)
    # the five blocking device->host reads of a bandwidth-controlled tick
    assert sum(names[n] for n in tracing.READ_SPANS) == 5


def test_raw_sync_spans_carry_the_sync_index(tiny_tree, tmp_path):
    service = _service(tiny_tree)
    service.sync(_moves(np.random.default_rng(0), 4))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        service.sync(_moves(np.random.default_rng(1), 4))
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    # an uncontrolled fleet reads no bytes back for rate control
    assert [n for _, _, n, _ in spans] == [
        "svc.sync", "svc.stale_count_read", "delta.union_size_read"]
    assert {t for _, _, _, t in spans} == {1} and service.syncs == 2


def test_stale_causes_sum_to_resweeps_and_lanes_are_the_bucket(tiny_tree):
    n = 4
    service = _service(tiny_tree, n)
    cap = service.capacity * tiny_tree.meta.Ns
    rng = np.random.default_rng(5)
    cams = _moves(rng, n)
    seen = np.zeros(3, np.int64)
    # small steps carry some clients past ρ; a climb of 3 km stops the
    # top tree expanding, which flips the slab roots' parent bits
    steps = [None, 0.3, 0.3, 3000.0]
    for step, d in enumerate(steps):
        if d is not None:
            cams = cams + (np.float32([0, 0, d]) if d > 100 else
                           (rng.normal(size=cams.shape) * d
                            ).astype(np.float32))
        stats = service.sync(cams)
        acct = service.last_account
        causes = np.asarray(acct["stale_causes"])
        resweeps = int(np.asarray(stats.resweeps).sum())
        assert causes.dtype == np.int32 and causes.shape == (3,)
        assert causes.sum() == resweeps == acct["n_stale"]
        if step == 0:     # a fresh fleet: every pair is cold
            assert causes[0] == n * tiny_tree.meta.Ns
        else:
            assert causes[0] == 0
        bucket = ls.pow2_bucket(resweeps, cap) if resweeps else 0
        assert acct["lanes"] == bucket >= resweeps
        seen += causes
    assert seen[1] > 0 and seen[2] > 0


def test_recorder_splits_each_mtp_into_wait_and_service(tiny_tree):
    service, sched = _scheduled(tiny_tree)
    stats = sched.tick()
    sched.set_deadline(1, 1e-6)
    sched.observe_motion(1, [3.0, 3.0, 2.0])
    stats2 = sched.tick()
    records = sched.recorder.drain()
    assert [r["tick"] for r in records] == [1, 2]
    for rec, st in zip(records, (stats, stats2)):
        slots = [service._slot_of(c) for c in rec["client"]]
        np.testing.assert_allclose(rec["wait_ms"] + rec["service_ms"],
                                   np.asarray(st.mtp_ms)[slots], rtol=1e-6)
        assert (rec["wait_ms"] > 0).all() and (rec["service_ms"] > 0).all()
        np.testing.assert_array_equal(rec["missed"],
                                      np.asarray(st.deadline_miss)[slots])
        # the device counters came back with the drain, as host arrays
        assert isinstance(rec["stale_causes"], np.ndarray)
        assert rec["stale_causes"].sum() == rec["n_stale"] \
            == int(np.asarray(st.resweeps).sum())
        assert rec["lanes"] >= rec["n_stale"]
    assert list(records[1]["client"]) == [1] and records[1]["missed"].all()
    assert len(sched.recorder) == 0 and sched.recorder.drain() == []


def test_recorder_keeps_the_last_ticks():
    rec = tracing.Recorder(maxlen=2)
    for i in range(3):
        rec.add(tick=i, lanes=jnp.int32(i))
    assert len(rec) == 2
    assert [(r["tick"], int(r["lanes"])) for r in rec.drain()] == \
        [(1, 1), (2, 2)]


def test_profiler_on_changes_nothing(tiny_tree, tmp_path):
    runs = []
    for traced in (False, True):
        service, sched = _scheduled(tiny_tree)
        stats = (_traced_tick(sched, tmp_path) if traced else sched.tick())
        runs.append((service.state, stats, service.last_delta,
                     service.last_account))
    for a, b in zip(*runs):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
