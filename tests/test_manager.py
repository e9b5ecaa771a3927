"""Runtime Gaussian management: cloud/client consistency, eviction, Δ minimality."""

import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

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


def _drive(cuts, w_star):
    n = cuts.shape[1]
    cloud = mgr.ManagerState.initial(n)
    client = mgr.ClientState.initial(n)
    stats = []
    for t, cut in enumerate(cuts):
        cloud, plan = mgr.cloud_sync(cloud, jnp.asarray(cut), jnp.int32(t),
                                     jnp.int32(w_star))
        client = mgr.client_sync(client, plan.delta_data, plan.cut_add,
                                 plan.cut_remove, jnp.int32(t), jnp.int32(w_star))
        stats.append((plan, cloud, client, cut))
    return stats


def test_cloud_client_tables_identical():
    rng = np.random.default_rng(0)
    cuts = _random_cut_sequence(rng, 512, 40)
    for t, (plan, cloud, client, cut) in enumerate(_drive(cuts, w_star=8)):
        assert (np.asarray(cloud.client_has) == np.asarray(client.has)).all(), t
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
        cloud, plan = mgr.cloud_sync(cloud, jnp.asarray(cut), jnp.int32(t),
                                     jnp.int32(8))
        expect = cut & ~prev_has
        assert (np.asarray(plan.delta_data) == expect).all()
        prev_has = np.asarray(cloud.client_has)


def test_eviction_after_reuse_window():
    n = 8
    cloud = mgr.ManagerState.initial(n)
    cut0 = np.zeros(n, bool); cut0[0] = True
    empty = np.zeros(n, bool)
    cloud, _ = mgr.cloud_sync(cloud, jnp.asarray(cut0), jnp.int32(0), jnp.int32(3))
    for t in range(1, 4):
        cloud, _ = mgr.cloud_sync(cloud, jnp.asarray(empty), jnp.int32(t), jnp.int32(3))
        assert bool(cloud.client_has[0])  # within window
    cloud, plan = mgr.cloud_sync(cloud, jnp.asarray(empty), jnp.int32(4), jnp.int32(3))
    assert not bool(cloud.client_has[0])  # evicted exactly past w_r*
    assert bool(plan.evicted[0])


def test_matches_reference_trace():
    rng = np.random.default_rng(3)
    cuts = _random_cut_sequence(rng, 300, 25, churn=0.1)
    ref_delta, ref_res = mgr.reference_manager_np(cuts, w_star=5)
    n = cuts.shape[1]
    cloud = mgr.ManagerState.initial(n)
    for t, cut in enumerate(cuts):
        cloud, plan = mgr.cloud_sync(cloud, jnp.asarray(cut), jnp.int32(t),
                                     jnp.int32(5))
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
        assert (np.asarray(cloud.client_has) == np.asarray(client.has)).all()
        assert np.asarray(client.has)[cut].all()
        # resident set is bounded by everything used within the window
        assert int(plan.n_resident) <= 128


def test_wire_bytes_accounting():
    n = 64
    cloud = mgr.ManagerState.initial(n)
    cut = np.zeros(n, bool); cut[:10] = True
    cloud, plan = mgr.cloud_sync(cloud, jnp.asarray(cut), jnp.int32(0), jnp.int32(8))
    b = float(plan.wire_bytes(bytes_per_gaussian=30.0))
    assert b == 10 * 30.0 + 10 * mgr.ID_BYTES_DELTA + mgr.SYNC_HEADER_BYTES
