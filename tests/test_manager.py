"""Runtime Gaussian management: cloud/client consistency, eviction, Δ minimality."""

import jax.numpy as jnp
import pytest
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import bitplane as bp
from repro.core import manager as mgr


def _random_cut_sequence(rng, n, frames, churn=0.05):
    """Cut sequences with paper-like temporal similarity (~95-99% overlap)."""
    cut = rng.random(n) < 0.3
    seq = [cut.copy()]
    for _ in range(frames - 1):
        flip = rng.random(n) < churn
        cut = np.where(flip, ~cut, cut)
        seq.append(cut.copy())
    return np.stack(seq)


def _dense(plane, n):
    """A packed plane of the table as its (n,) bool node mask."""
    return np.asarray(bp.unpack(plane, n))


def _ages(cloud):
    """The table's age planes as one int age per node."""
    return sum(np.asarray(bp.unpack(plane)).astype(np.int64) << k
               for k, plane in enumerate(cloud.age))


def _sync(cloud, cut, w_star):
    """One table update with a dense cut mask."""
    return mgr.cloud_sync(cloud, bp.pack(jnp.asarray(cut)), jnp.int32(w_star))


def _drive(cuts, w_star):
    n = cuts.shape[1]
    cloud = mgr.ManagerState.initial(n)
    client = mgr.ClientState.initial(n)
    stats = []
    for t, cut in enumerate(cuts):
        cloud, plan = _sync(cloud, cut, w_star)
        client = mgr.client_sync(client, _dense(plan.delta_data, n),
                                 _dense(plan.cut_add, n),
                                 _dense(plan.cut_remove, n), jnp.int32(t),
                                 jnp.int32(w_star))
        stats.append((plan, cloud, client, cut))
    return stats


def test_cloud_client_tables_identical():
    rng = np.random.default_rng(0)
    cuts = _random_cut_sequence(rng, 512, 40)
    for t, (plan, cloud, client, cut) in enumerate(_drive(cuts, w_star=8)):
        np.testing.assert_array_equal(_dense(cloud.client_has, 512),
                                      np.asarray(client.has), err_msg=str(t))
        assert (np.asarray(client.cut) == cut).all(), t


def test_client_always_holds_current_cut():
    rng = np.random.default_rng(1)
    cuts = _random_cut_sequence(rng, 256, 30)
    for plan, cloud, client, cut in _drive(cuts, w_star=4):
        has = np.asarray(client.has)
        assert has[cut].all()  # never render a Gaussian we don't hold


def test_delta_minimality():
    """Δcut must contain exactly the cut members the client lacked."""
    rng = np.random.default_rng(2)
    cuts = _random_cut_sequence(rng, 256, 20)
    n = cuts.shape[1]
    cloud = mgr.ManagerState.initial(n)
    prev_has = np.zeros(n, bool)
    for t, cut in enumerate(cuts):
        cloud, plan = _sync(cloud, cut, 8)
        expect = cut & ~prev_has
        assert (_dense(plan.delta_data, n) == expect).all()
        prev_has = _dense(cloud.client_has, n)


def test_eviction_after_reuse_window():
    n = 8
    cloud = mgr.ManagerState.initial(n)
    cut0 = np.zeros(n, bool); cut0[0] = True
    empty = np.zeros(n, bool)
    cloud, _ = _sync(cloud, cut0, 3)
    for t in range(1, 4):
        cloud, _ = _sync(cloud, empty, 3)
        assert _dense(cloud.client_has, n)[0]  # within window
    cloud, plan = _sync(cloud, empty, 3)
    assert not _dense(cloud.client_has, n)[0]  # evicted exactly past w_r*
    assert _dense(plan.evicted, n)[0]


def test_matches_reference_trace():
    rng = np.random.default_rng(3)
    cuts = _random_cut_sequence(rng, 300, 25, churn=0.1)
    ref_delta, ref_res = mgr.reference_manager_np(cuts, w_star=5)
    n = cuts.shape[1]
    cloud = mgr.ManagerState.initial(n)
    for t, cut in enumerate(cuts):
        cloud, plan = _sync(cloud, cut, 5)
        assert int(plan.n_delta) == ref_delta[t]
        assert int(plan.n_resident) == ref_res[t]


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    w_star=st.integers(1, 12),
    churn=st.floats(0.0, 0.4),
)
def test_property_consistency_and_residency(seed, w_star, churn):
    rng = np.random.default_rng(seed)
    cuts = _random_cut_sequence(rng, 128, 15, churn=churn)
    for plan, cloud, client, cut in _drive(cuts, w_star):
        assert (_dense(cloud.client_has, 128) == np.asarray(client.has)).all()
        assert np.asarray(client.has)[cut].all()
        # resident set is bounded by everything used within the window
        assert int(plan.n_resident) <= 128


def test_wire_bytes_accounting():
    n = 64
    cloud = mgr.ManagerState.initial(n)
    cut = np.zeros(n, bool); cut[:10] = True
    cloud, plan = _sync(cloud, cut, 8)
    b = float(plan.wire_bytes(bytes_per_gaussian=30.0))
    assert b == 10 * 30.0 + 10 * mgr.ID_BYTES_DELTA + mgr.SYNC_HEADER_BYTES


@pytest.mark.parametrize("w_star", [0, 1, 254])
def test_packed_table_matches_reference_through_the_age_cap(w_star):
    """The word-level table (bit planes, a saturating 8-bit age) against
    the numpy oracle, sync by sync, over random cuts on 100 nodes (padding
    bits in the last word). Node 0 is cut only at sync 0 and then ages
    past 255, where its age saturates; node 1 returns at sync 260."""
    rng = np.random.default_rng(w_star)
    n, syncs = 100, 300
    cuts = _random_cut_sequence(rng, n, syncs, churn=0.05)
    cuts[:, :2] = False
    cuts[0, :2] = True
    cuts[260, 1] = True
    ref_delta, ref_res = mgr.reference_manager_np(cuts, w_star=w_star)
    cloud = mgr.ManagerState.initial(n)
    client = mgr.ClientState.initial(n)
    last = np.full(n, -(2**30), np.int64)
    for t, cut in enumerate(cuts):
        cloud, plan = _sync(cloud, cut, w_star)
        client = mgr.client_sync(client, _dense(plan.delta_data, n),
                                 _dense(plan.cut_add, n),
                                 _dense(plan.cut_remove, n), jnp.int32(t),
                                 jnp.int32(w_star))
        assert int(plan.n_delta) == ref_delta[t], t
        assert int(plan.n_resident) == ref_res[t], t
        np.testing.assert_array_equal(_dense(cloud.client_has, n),
                                      np.asarray(client.has), err_msg=str(t))
        last[cut] = t
        np.testing.assert_array_equal(
            _ages(cloud)[:n],
            np.minimum(t - last, mgr.AGE_MAX), err_msg=str(t))
    assert _ages(cloud)[0] == mgr.AGE_MAX


def test_reuse_window_past_the_age_cap_is_refused():
    assert mgr.check_w_star(254) == 254
    for bad in (255, 300, -1):
        with pytest.raises(ValueError, match="w_star"):
            mgr.check_w_star(bad)
