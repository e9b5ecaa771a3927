"""Deadline-driven MTP scheduler + the partial-fleet sync primitive.

The load-bearing claims pinned here:

  * PARTICIPATE-ALL PARITY — a sync whose participation mask selects every
    live slot replays BITWISE against the lockstep `participate=None` call
    (state AND stats), on all three sweep paths (vmapped XLA, pooled XLA,
    pooled Pallas) and, in the slow subprocess leg, on a forced 8-device
    clients×slabs mesh;
  * ISOLATION — a partial tick leaves every sat-out slot's state (temporal,
    manager, cut_gids, pending debt, sync counter) bitwise untouched and
    its stats rows zero, reusing the frozen-inactive-slot invariant; the
    controller freshness mask only re-commits measurements from slots that
    actually synced;
  * bad participation input (unknown client id, wrong mask shape) raises
    BEFORE any state is touched;
  * `sync(cam_positions=...)` array and dict forms agree bitwise on a
    churned fleet with non-contiguous live slots, and a dict naming an
    unknown client raises cleanly without corrupting `_slot_cams`;
  * the scheduler itself: EDF selection under deadlines + the greedy cost
    budget (the most urgent candidate is never starved), MTP/deadline-miss
    stamping on the served slots only, online cost-model refit,
    predicted-cost admission denial that leaves the service untouched,
    JSON-able state_dict round-trip, and snapshot/recovery carriage;
  * the workload generators are deterministic and shaped as documented.
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.serve import lod_service as svc
from repro.serve import recovery as rec
from repro.serve import scheduler as sch

FOCAL = 1400.0
TAU = 32.0


def _mk(tree, n, **kw):
    cfg = svc.SessionConfig(tau=TAU, cut_budget=2048)
    kw.setdefault("mode", "pooled")
    return svc.LodService(tree, cfg, n, focal=FOCAL, dedup=True, **kw)


def _cams(rng, n):
    return rng.uniform([2, 2, 1], [28, 28, 6], (n, 3)).astype(np.float32)


def _leaves_equal(a, b, tag=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=tag)


class _Clock:
    """Scripted monotonic clock: +1ms per read."""

    def __init__(self, t0: float = 100.0, step: float = 1e-3):
        self.t, self.step = float(t0), float(step)

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# (a) participate-everyone == lockstep, bitwise, on all three sweep paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,impl", [("vmapped", "xla"), ("pooled", "xla"),
                                       ("pooled", "pallas")])
def test_participate_everyone_replays_lockstep_bitwise(tiny_tree, mode, impl):
    a = _mk(tiny_tree, 4, mode=mode, sweep_impl=impl)
    b = _mk(tiny_tree, 4, mode=mode, sweep_impl=impl)
    rng = np.random.default_rng(3)
    pos = _cams(rng, 4)
    for t in range(3):
        # alternate the two participation spellings (client ids, bool mask)
        part = (b.active_ids if t % 2 == 0
                else np.ones(b.capacity, bool))
        sa = a.sync(pos)
        sb = b.sync(pos, participate=part)
        _leaves_equal(sa, sb, f"{mode}/{impl}:stats:{t}")
        _leaves_equal(a.state, b.state, f"{mode}/{impl}:state:{t}")
        pos = (pos + rng.normal(0, 2.5, (4, 3))).astype(np.float32)


# ---------------------------------------------------------------------------
# (b) partial-tick isolation: sat-out slots are provably untouched
# ---------------------------------------------------------------------------


def _satout_rows_unchanged(new, old, touched, capacity):
    touched = set(touched)
    others = [s for s in range(capacity) if s not in touched]
    for x, y in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        x, y = np.asarray(x), np.asarray(y)
        if x.ndim >= 1 and x.shape[0] == capacity:
            np.testing.assert_array_equal(x[others], y[others])
        else:
            np.testing.assert_array_equal(x, y)


def test_partial_tick_satout_slots_bitwise_untouched(tiny_tree):
    service = _mk(tiny_tree, 5, capacity=8)
    rng = np.random.default_rng(7)
    service.sync(_cams(rng, 5))
    service.evict(1)
    service.evict(3)                      # live slots 0, 2, 4 — ragged
    service.sync()                        # settle post-churn
    before = jax.device_get(service.state)
    idx0 = np.asarray(service.state.sync_index).copy()
    slot0 = service._slot_of(0)

    stats = service.sync({0: np.asarray([25.0, 25.0, 4.0], np.float32)},
                         participate=[0])
    _satout_rows_unchanged(service.state, before, {slot0}, service.capacity)
    # the tick only advanced the participant's sync counter
    idx1 = np.asarray(service.state.sync_index)
    assert idx1[slot0] == idx0[slot0] + 1
    # sat-out stats rows are zero (active AND inactive alike)
    others = [s for s in range(service.capacity) if s != slot0]
    for f in ("cut_size", "delta_size", "sync_bytes", "resweeps",
              "nodes_touched", "unique_delta"):
        assert not np.asarray(getattr(stats, f))[others].any(), f
    # the controller freshness mask marks exactly the participant
    fresh = np.zeros(service.capacity, bool)
    fresh[slot0] = True
    np.testing.assert_array_equal(service._stats_fresh, fresh)


def test_bad_participation_raises_before_state_is_touched(tiny_tree):
    service = _mk(tiny_tree, 3)
    service.sync(_cams(np.random.default_rng(0), 3))
    state = service.state
    with pytest.raises(KeyError):
        service.sync(participate=[99])
    with pytest.raises(ValueError):
        service.sync(participate=np.ones(service.capacity + 1, bool))
    assert service.state is state         # nothing ran


# ---------------------------------------------------------------------------
# (c) sync camera forms: array vs dict on a churned fleet, unknown ids
# ---------------------------------------------------------------------------


def test_sync_array_and_dict_forms_agree_on_churned_fleet(tiny_tree):
    a = _mk(tiny_tree, 6, capacity=8)
    b = _mk(tiny_tree, 6, capacity=8)
    rng = np.random.default_rng(5)
    pos = _cams(rng, 6)
    for s in (a, b):
        s.sync(pos)
        s.evict(0)
        s.evict(4)                        # live slots 1,2,3,5 — ragged
    ids = a.active_ids
    assert ids == b.active_ids
    for t in range(2):
        # array form addresses live clients in slot order == active_ids
        cams = _cams(rng, len(ids))
        sa = a.sync(cams)
        sb = b.sync({cid: cams[k] for k, cid in enumerate(ids)})
        _leaves_equal(sa, sb, f"stats:{t}")
        _leaves_equal(a.state, b.state, f"state:{t}")
        np.testing.assert_array_equal(a._slot_cams, b._slot_cams)


def test_sync_dict_unknown_client_raises_without_corruption(tiny_tree):
    service = _mk(tiny_tree, 3)
    rng = np.random.default_rng(1)
    service.sync(_cams(rng, 3))
    cams_before = service._slot_cams.copy()
    state_before = service.state
    with pytest.raises(KeyError):
        service.sync({0: [9.0, 9.0, 2.0], 99: [1.0, 1.0, 1.0]})
    # the bad id aborted BEFORE any position was stored or any sync ran
    np.testing.assert_array_equal(service._slot_cams, cams_before)
    assert service.state is state_before
    service.sync({0: [9.0, 9.0, 2.0]})    # the service is still healthy
    assert np.allclose(service._slot_cams[service._slot_of(0)],
                       [9.0, 9.0, 2.0])


# ---------------------------------------------------------------------------
# (d) the scheduler: selection, MTP stamping, cost model, admission
# ---------------------------------------------------------------------------


def test_tick_serves_only_unserved_motion_and_stamps_mtp(tiny_tree):
    service = _mk(tiny_tree, 4)
    rng = np.random.default_rng(2)
    service.sync(_cams(rng, 4))
    sched = sch.DeadlineScheduler(service, default_deadline_ms=1e6,
                                  clock=_Clock())
    sched.observe_motion(0, [20.0, 20.0, 3.0])
    sched.observe_motion(2, [4.0, 22.0, 2.0])
    assert set(sched.select()) == {0, 2}
    stats = sched.tick()
    mtp = np.asarray(stats.mtp_ms)
    served = [service._slot_of(0), service._slot_of(2)]
    others = [service._slot_of(1), service._slot_of(3)]
    assert (mtp[served] > 0.0).all() and not mtp[others].any()
    assert not np.asarray(stats.deadline_miss).any()
    syncs = service.syncs
    idle = sched.tick()                   # motion served — an idle tick
    assert service.syncs == syncs         # syncs nothing, serves nobody
    assert not any(np.asarray(leaf).any()
                   for leaf in jax.tree_util.tree_leaves(idle))
    # a deadline the clock cannot hold stamps a miss for that client only
    sched.set_deadline(0, 1e-6)
    sched.observe_motion(0, [21.0, 21.0, 3.0])
    stats = sched.tick()
    miss = np.asarray(stats.deadline_miss)
    assert bool(miss[service._slot_of(0)]) and miss.sum() == 1
    records = sched.recorder.drain()
    assert [len(r["client"]) for r in records] == [2, 1]
    missed = np.concatenate([r["missed"] for r in records])
    mtp = np.concatenate([r["wait_ms"] + r["service_ms"] for r in records])
    assert missed.size == 3 and missed.sum() == 1
    assert (mtp > 0.0).all() and len(sched.recorder) == 0


def test_select_edf_orders_by_slack_and_budget_never_starves_head(tiny_tree):
    service = _mk(tiny_tree, 3)
    service.sync(np.tile(np.asarray([10.0, 10.0, 2.0], np.float32), (3, 1)))
    sched = sch.DeadlineScheduler(service, default_deadline_ms=1000.0,
                                  clock=_Clock())
    sched.set_deadline(1, 10.0)           # the tightest deadline
    for cid in (0, 1, 2):
        # teleport: every candidate prices at a full resweep
        sched.observe_motion(cid, [25.0 - cid, 3.0 + cid, 5.0])
    sel = sched.select()
    assert sel[0] == 1 and set(sel) == {0, 1, 2}
    # a budget one candidate exhausts still selects the head of the queue
    sched.cost.alpha, sched.cost.beta = 0.0, 1.0
    sched.tick_budget_ms = 1.0
    assert sched.select() == [1]
    stats = sched.tick()
    assert int(np.asarray(stats.resweeps)[service._slot_of(1)]) > 0
    # the deferred candidates are still pending, served by later ticks
    sched.tick_budget_ms = None
    assert set(sched.select()) == {0, 2}


def test_cost_model_refits_from_measured_ticks():
    cm = sch.CostModel(alpha_ms=50.0, beta_ms=5.0, min_samples=6)
    for pairs in (0, 2, 4, 8, 16, 32, 64):
        cm.observe(pairs, 3.0 + 0.25 * pairs)
    assert cm.alpha == pytest.approx(3.0, abs=1e-6)
    assert cm.beta == pytest.approx(0.25, abs=1e-6)
    assert cm.predict(100) == pytest.approx(28.0, abs=1e-4)
    # a constant-pairs window re-estimates alpha only (no beta signal)
    cm2 = sch.CostModel(alpha_ms=1.0, beta_ms=0.5, min_samples=2)
    for _ in range(4):
        cm2.observe(4, 7.0)
    assert cm2.alpha == pytest.approx(7.0) and cm2.beta == 0.5
    # a degenerate fit never predicts negative (free) work
    cm3 = sch.CostModel(min_samples=2)
    for pairs, ms in ((0, 10.0), (10, 1.0), (20, 0.5)):
        cm3.observe(pairs, ms)
    assert cm3.beta == 0.0 and cm3.predict(1000) >= 0.0


def test_predicted_cost_admission_denial_leaves_service_untouched(tiny_tree):
    service = _mk(tiny_tree, 2, capacity=4)
    service.sync(_cams(np.random.default_rng(4), 2))
    sched = sch.DeadlineScheduler(service, default_deadline_ms=50.0,
                                  clock=_Clock())
    # the newcomer's cold full resweep is predicted over its deadline
    sched.cost.alpha, sched.cost.beta = 1000.0, 0.0
    state = service.state
    with pytest.raises(svc.AdmissionDenied, match="cold first sync"):
        sched.admit([5.0, 5.0, 2.0])
    assert sched.admit([5.0, 5.0, 2.0], required=False) is None
    assert service.n_clients == 2 and service.state is state
    # aggregate utilization gate: cheap ticks, but fleet demand > one lane
    # (deadline = 2·Ns·beta ms, so each client needs half the sync lane —
    # three of them cannot fit, while any one cold sync still could)
    sched.cost.alpha, sched.cost.beta = 0.0, 1.0
    d = 2.0 * sched._ns
    for cid in service.active_ids:
        sched.set_deadline(cid, d)
    with pytest.raises(svc.AdmissionDenied, match="utilization"):
        sched.admit([5.0, 5.0, 2.0], deadline_ms=d)
    with pytest.raises(svc.AdmissionDenied, match="not positive"):
        sched.admit([5.0, 5.0, 2.0], deadline_ms=0.0)
    # with sane costs the admit lands and its first pose is scheduled
    sched.cost.beta = 0.001
    cid = sched.admit([5.0, 5.0, 2.0], deadline_ms=40.0)
    assert service.n_clients == 3 and sched.deadline(cid) == 40.0
    assert cid in sched.select()
    sched.evict(cid)
    assert cid not in sched._clients and service.n_clients == 2


def test_scheduler_state_dict_json_roundtrip(tiny_tree):
    service = _mk(tiny_tree, 2)
    service.sync(_cams(np.random.default_rng(6), 2))
    clock = _Clock()
    sched = sch.DeadlineScheduler(service, default_deadline_ms=25.0,
                                  tick_budget_ms=12.0, clock=clock)
    sched.set_deadline(1, 75.0)
    sched.observe_motion(0, [20.0, 20.0, 3.0])
    sched.observe_motion(0, [21.0, 20.0, 3.0])   # → nonzero velocity EWMA
    sched.tick()
    blob = json.dumps(sched.state_dict())        # JSON-able by contract

    other = _mk(tiny_tree, 2)
    other.sync(_cams(np.random.default_rng(6), 2))
    sched2 = sch.DeadlineScheduler(other, clock=_Clock())
    sched2.load_state_dict(json.loads(blob))
    assert sched2.default_deadline_ms == 25.0
    assert sched2.tick_budget_ms == 12.0
    assert sched2.deadline(1) == 75.0
    assert sched2.cost.alpha == sched.cost.alpha
    assert sched2.cost.beta == sched.cost.beta
    for cid in (0, 1):
        a, b = sched._clients[cid], sched2._clients[cid]
        assert b.velocity == a.velocity and b.ewma_pairs == a.ewma_pairs
    assert sched._clients[0].velocity > 0.0


def test_recovery_journals_partial_ticks_and_carries_scheduler_state(
        tiny_tree, tmp_path):
    service = _mk(tiny_tree, 3)
    rng = np.random.default_rng(8)
    sched = sch.DeadlineScheduler(service, default_deadline_ms=42.0,
                                  clock=_Clock())
    man = rec.RecoveryManager(service, str(tmp_path), every=16,
                              scheduler=sched)
    pos = _cams(rng, 3)
    man.sync(pos)
    # partial ticks through the journal (stable ids, replayed on recover)
    man.sync({0: pos[0] + 2.0}, participate=[0])
    man.sync({1: pos[1] + 2.0, 2: pos[2] + 1.0}, participate=[1, 2])
    man.snapshot_now()                    # scheduler extras ride along
    man.sync({0: pos[0] + 4.0}, participate=[0])   # journal tail to replay

    man2, replayed = rec.recover(tiny_tree, str(tmp_path))
    assert replayed == 1
    _leaves_equal(man2.service.state, man.service.state, "recovered state")
    assert man2.scheduler_state is not None
    sched2 = sch.DeadlineScheduler(man2.service, clock=_Clock())
    sched2.load_state_dict(man2.scheduler_state)
    assert sched2.default_deadline_ms == 42.0
    assert sched2.cost.alpha == sched.cost.alpha


# ---------------------------------------------------------------------------
# (e) workload generators
# ---------------------------------------------------------------------------


def test_workload_generators_deterministic_and_shaped():
    a = sch.poisson_arrivals(np.random.default_rng(0), 2.0, 256)
    b = sch.poisson_arrivals(np.random.default_rng(0), 2.0, 256)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (256,) and a.dtype == np.int64
    assert 1.5 < a.mean() < 2.5

    calm = sch.bursty_motion_path(np.random.default_rng(1), 128,
                                  speed=0.5, burst_prob=0.0)
    again = sch.bursty_motion_path(np.random.default_rng(1), 128,
                                   speed=0.5, burst_prob=0.0)
    np.testing.assert_array_equal(calm, again)
    assert calm.shape == (128, 3) and calm.dtype == np.float32
    steps = np.linalg.norm(np.diff(calm, axis=0), axis=1)
    np.testing.assert_allclose(steps, 0.5, rtol=1e-5)   # no bursts: |step|==speed
    wild = sch.bursty_motion_path(np.random.default_rng(1), 128,
                                  speed=0.5, burst_prob=0.5, burst_scale=10.0)
    assert np.linalg.norm(np.diff(wild, axis=0), axis=1).max() > 2.0

    strag = sch.straggler_path(np.random.default_rng(2), 200,
                               teleport_every=5, extent=30.0)
    assert strag.shape == (200, 3)
    assert np.abs(strag).max() <= 30.0
    jumps = np.linalg.norm(np.diff(strag, axis=0), axis=1)
    assert (jumps == 0.0).mean() > 0.5    # mostly stationary...
    assert (jumps > 5.0).sum() >= 10      # ...punctuated by teleports


# ---------------------------------------------------------------------------
# (f) the 8-device mesh leg (the acceptance contract)
# ---------------------------------------------------------------------------


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "src")
import numpy as np, jax
from repro.core.gaussians import random_gaussians
from repro.core.lod_tree import build_lod_tree
from repro.launch.mesh import make_fleet_mesh
from repro.serve import lod_service as svc

assert len(jax.devices()) == 8
rng = np.random.default_rng(11)
leaves = random_gaussians(rng, 150, sh_degree=1, extent=30.0)
tree = build_lod_tree(leaves, branching=(2, 4), target_subtrees=8, seed=1)
cfg = svc.SessionConfig(tau=32.0, cut_budget=2048)
mesh = make_fleet_mesh(clients=4, slabs=2)

def mk(m):
    return svc.LodService(tree, cfg, 4, focal=1400.0, capacity=8,
                          mode="pooled", dedup=True, mesh=m)

def eq(a, b, tag):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=tag)

lock, part, plain = mk(mesh), mk(mesh), mk(None)
pos = np.random.default_rng(5).uniform(
    [2, 2, 1], [28, 28, 6], (4, 3)).astype(np.float32)
for t in range(3):
    mask = part.active_ids if t % 2 == 0 else np.ones(8, bool)
    sl = lock.sync(pos)
    sp = part.sync(pos, participate=mask)
    s0 = plain.sync(pos, participate=np.ones(8, bool))
    eq(sl, sp, f"stats:{t}")
    eq(sl, s0, f"stats-vs-plain:{t}")
    eq(lock.state, part.state, f"state:{t}")
    eq(lock.state, plain.state, f"state-vs-plain:{t}")
    pos = (pos + np.random.default_rng(t).normal(0, 2.0, (4, 3))
           ).astype(np.float32)

# a PARTIAL tick under the mesh: sat-out slots bitwise untouched, and the
# mask rides the clients axis without disturbing the declared shardings
before = jax.device_get(part.state)
sp = part.sync({0: pos[0] + 5.0}, participate=[0])
for x, y in zip(jax.tree_util.tree_leaves(part.state),
                jax.tree_util.tree_leaves(before)):
    x, y = np.asarray(x), np.asarray(y)
    if x.ndim >= 1 and x.shape[0] == 8:
        np.testing.assert_array_equal(x[1:], y[1:])
assert not np.asarray(sp.resweeps)[1:].any()
assert not np.asarray(sp.sync_bytes)[1:].any()
for leaf in jax.tree_util.tree_leaves(part.state):
    spec = leaf.sharding.spec
    if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == 8:
        assert spec[0] == "clients", (leaf.shape, spec)
print(json.dumps({"ok": True}))
"""


@pytest.mark.slow
def test_partial_sync_mesh_parity_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, timeout=900,
                         cwd=".")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
