"""Streaming + temporal-aware LoD search: bit-accuracy vs the numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import lod_search as ls

FOCAL = 1400.0


def _run_full(tree, cam, tau):
    cut, state = ls.full_search(tree, np.asarray(cam, np.float32),
                                jnp.float32(FOCAL), jnp.float32(tau))
    return np.asarray(cut.mask(tree)), state


@pytest.mark.parametrize("tau", [2.0, 16.0, 64.0, 256.0])
@pytest.mark.parametrize("cam", [[20, 20, 1.7], [300, 300, 150], [-100, 50, 30]])
def test_full_search_matches_oracle(small_tree, tau, cam):
    got, _ = _run_full(small_tree, cam, tau)
    ref = ls.reference_search_np(small_tree, np.asarray(cam, np.float32), FOCAL, tau)
    assert (got == ref).all()


def test_cut_is_antichain_and_maximal(small_tree):
    """No cut node is an ancestor of another; every root-leaf path crosses the
    cut exactly once (fundamental property of an LoD cut)."""
    cam = np.array([250, 250, 120], np.float32)
    got, _ = _run_full(small_tree, cam, 64.0)
    parent = ls.global_parent_np(small_tree)
    valid = np.asarray(small_tree.valid_mask())
    # walk up from every cut node: no ancestor may be in the cut
    idxs = np.where(got)[0]
    for i in idxs[:: max(1, len(idxs) // 64)]:
        p = parent[i]
        while p >= 0:
            assert not got[p]
            p = parent[p]
    # walk up from every leaf: exactly one cut crossing
    level = ls.global_level_np(small_tree)
    is_leaf = np.concatenate([
        np.asarray(small_tree.top_is_leaf),
        np.asarray(small_tree.slab_is_leaf).reshape(-1)])
    leaves = np.where(is_leaf & valid)[0]
    for i in leaves[:: max(1, len(leaves) // 64)]:
        crossings, p = int(got[i]), parent[i]
        while p >= 0:
            crossings += int(got[p])
            p = parent[p]
        assert crossings == 1


def test_temporal_bit_accurate_walk(small_tree):
    rng = np.random.default_rng(0)
    cam = np.array([20, 20, 1.7], np.float32)
    _, state = _run_full(small_tree, cam, 24.0)
    for _ in range(25):
        cam = cam + rng.normal(0, 0.05, 3).astype(np.float32)
        cut, state = ls.temporal_search(small_tree, state, cam,
                                        jnp.float32(FOCAL), jnp.float32(24.0))
        ref = ls.reference_search_np(small_tree, cam, FOCAL, 24.0)
        assert (np.asarray(cut.mask(small_tree)) == ref).all()


def test_temporal_bit_accurate_flyout(small_tree):
    """Fly from street level to altitude — crosses LoD boundaries, forcing
    resweeps; accuracy must hold on the resweep path too."""
    cam = np.array([40, 40, 2], np.float32)
    cut, state = ls.full_search(small_tree, cam, jnp.float32(FOCAL), jnp.float32(64.0))
    total_resweeps = 0
    for _ in range(40):
        cam = cam + np.array([4, 4, 60], np.float32)
        cut, state = ls.temporal_search(small_tree, state, cam,
                                        jnp.float32(FOCAL), jnp.float32(64.0))
        ref = ls.reference_search_np(small_tree, cam, FOCAL, 64.0)
        assert (np.asarray(cut.mask(small_tree)) == ref).all()
        total_resweeps += int(np.asarray(cut.resweep).sum())
    assert total_resweeps > 0  # the reuse bound must actually have been crossed


def test_hybrid_matches_jit_variant(small_tree):
    rng = np.random.default_rng(1)
    cam = np.array([30, 30, 2], np.float32)
    _, s1 = _run_full(small_tree, cam, 48.0)
    _, s2 = _run_full(small_tree, cam, 48.0)
    for _ in range(12):
        cam = cam + rng.normal(0, 8.0, 3).astype(np.float32)
        c1, s1 = ls.temporal_search(small_tree, s1, cam,
                                    jnp.float32(FOCAL), jnp.float32(48.0))
        c2, s2 = ls.temporal_search_hybrid(small_tree, s2, cam, FOCAL, 48.0)
        assert (np.asarray(c1.mask(small_tree)) == np.asarray(c2.mask(small_tree))).all()


def test_nodes_touched_monotonicity(small_tree):
    """Temporal search must touch no more nodes than the full sweep."""
    cam = np.array([20, 20, 1.7], np.float32)
    cut_full, state = ls.full_search(small_tree, cam, jnp.float32(FOCAL),
                                     jnp.float32(24.0))
    cut_t, _ = ls.temporal_search(small_tree, state, cam + 0.01,
                                  jnp.float32(FOCAL), jnp.float32(24.0))
    assert int(cut_t.nodes_touched) <= int(cut_full.nodes_touched)


def test_cut_gids_compaction(small_tree):
    cam = np.array([250, 250, 120], np.float32)
    cut, _ = ls.full_search(small_tree, cam, jnp.float32(FOCAL), jnp.float32(64.0))
    n = int(cut.count())
    gids, count, overflow = ls.cut_gids(cut, small_tree, budget=n + 8)
    assert int(count) == n and not bool(overflow)
    g = np.asarray(gids)
    assert (g[:n] >= 0).all() and (g[n:] == -1).all()
    assert (np.diff(g[:n]) > 0).all()  # sorted unique
    mask = np.asarray(cut.mask(small_tree))
    assert mask[g[:n]].all()


@settings(max_examples=15, deadline=None)
@given(
    tau=st.floats(4.0, 512.0),
    x=st.floats(-200.0, 400.0),
    y=st.floats(-200.0, 400.0),
    z=st.floats(1.0, 500.0),
)
def test_property_full_search_matches_oracle(tiny_tree, tau, x, y, z):
    cam = np.array([x, y, z], np.float32)
    cut, _ = ls.full_search(tiny_tree, cam, jnp.float32(FOCAL), jnp.float32(tau))
    ref = ls.reference_search_np(tiny_tree, cam, FOCAL, tau)
    assert (np.asarray(cut.mask(tiny_tree)) == ref).all()


# -- the shared bounded-recompilation bucket policy ---------------------------


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


@settings(max_examples=40, deadline=None)
@given(n=st.integers(0, 1 << 20), cap=st.integers(1, 1 << 20))
def test_property_pow2_bucket(n, cap):
    """pow2_bucket is the ONE bucket policy every host-driven scheduler
    shares; pin its algebra: the result is the next power of two (clamped to
    the cap), covers n whenever the cap allows, is monotone in n, and is a
    fixed point of itself (re-bucketing a bucket never grows it)."""
    b = ls.pow2_bucket(n, cap)
    want = min(_pow2_ceil(max(n, 1)), cap)
    assert b == max(1, want)
    # power of two unless the (possibly non-pow2) cap clamped it
    assert (b & (b - 1)) == 0 or b == cap
    assert 1 <= b <= max(cap, 1)
    if _pow2_ceil(max(n, 1)) <= cap:
        assert b >= n  # the bucket really holds n items
    # monotone in n
    assert ls.pow2_bucket(max(n - 1, 0), cap) <= b
    assert b <= ls.pow2_bucket(n + 1, cap)
    # idempotent
    assert ls.pow2_bucket(b, cap) == b


def test_pow2_bucket_is_the_policy_of_all_host_schedulers(
        small_tree, monkeypatch):
    """Regression-pin the SHARED policy: the four host-driven schedulers —
    the hybrid stale-slab sweep, the service's pooled (client, slab)
    compaction, the Δ-union encode width, and the fleet occupied-tile
    render pooling — must all route their bucket choice through
    ls.pow2_bucket (and dispatch exactly the bucket it returns)."""
    import jax

    from repro.core.pipeline import SessionConfig
    from repro.serve import delta_path as dp
    from repro.serve import lod_service as svc
    from repro import render as rnd
    from repro.render import batched as rb

    calls = []
    real = ls.pow2_bucket

    def recording(n, cap):
        b = real(n, cap)
        calls.append((int(n), int(cap), int(b)))
        return b

    monkeypatch.setattr(ls, "pow2_bucket", recording)
    cam = np.array([30.0, 30.0, 2.0], np.float32)

    # (1) host-driven hybrid search (lod_search module-global lookup)
    _, state = ls.full_search(small_tree, cam, jnp.float32(FOCAL),
                              jnp.float32(48.0))
    calls.clear()
    cut, _ = ls.temporal_search_hybrid(small_tree, state, cam + 50.0,
                                       FOCAL, 48.0)
    n_stale = int(np.asarray(cut.resweep).sum())
    assert n_stale > 0 and calls == [(n_stale, small_tree.meta.Ns,
                                      real(n_stale, small_tree.meta.Ns))]

    # (2) pooled (client, slab) compaction + (3) Δ-union encode width
    cfg = SessionConfig(tau=32.0, cut_budget=4096)
    codec, bpg = svc.session_wire_format(small_tree, cfg)
    st = svc.service_init(small_tree, cfg, 2)
    calls.clear()
    st, stats, batch = svc.service_sync_pooled(
        small_tree, cfg, st, np.stack([cam, cam + 3.0]), FOCAL,
        bytes_per_g=bpg, codec=codec, dedup=True,
        delta_budget=small_tree.n_pad)
    pool_n = int(np.asarray(stats.resweeps).sum())
    union_n = int(batch.n_union)
    assert (pool_n, 2 * small_tree.meta.Ns,
            real(pool_n, 2 * small_tree.meta.Ns)) in calls
    assert (union_n, small_tree.n_pad,
            real(union_n, small_tree.n_pad)) in calls
    assert len(calls) == 2

    # (4) fleet occupied-tile pooling on the pooled render path
    from repro.core.camera import StereoRig, make_camera
    from repro.core.gaussians import random_gaussians
    rig = StereoRig(left=make_camera([0, -16, 2], [0, 0, 0], focal_px=200.0,
                                     width=48, height=32, near=0.25),
                    baseline=0.06)
    queues = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, a]),
        random_gaussians(np.random.default_rng(0), 64, sh_degree=1,
                         extent=10.0))
    rigs = rnd.stack_rigs([rig, rig])
    rcfg = rnd.RenderConfig.for_rig(rig, tile=16, list_len=64,
                                    max_pairs=1 << 12)
    calls.clear()
    rb.batched_render_stereo(queues, rigs, rcfg, path="pooled")
    assert len(calls) == 1
    occ, cap, got = calls[0]
    assert occ > 0 and got == real(occ, cap)
