"""The fleet service on bit planes: per-slot state packed 32 nodes a word,
a sync tail that works on words, and a pair sweep that runs in lane chunks.

  * a 16-slot crowd around two plazas, through `LodService` with the slot
    loops forced into several chunks, equals the per-client oracle: every
    cut is the brute-force LoD cut, every Δ is the cut minus the client's
    store, and the table replays `manager.reference_manager_np`;
  * the per-slot state costs at most ⌈N/32⌉·16 + N + 4·cut_budget bytes
    plus the per-slab scalars, under 10 MB at the city32 scene;
  * no program of the sync tail holds a (C, N) array of a byte or wider;
  * a pool wider than one lane chunk sweeps bitwise as one pass and as the
    level-loop oracle, and a bucket of one chunk compiles no loop;
  * a packed snapshot restores bitwise.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane as bp
from repro.core import lod_search as ls
from repro.core import manager as mgr
from repro.core.gaussians import CityConfig, generate_city
from repro.core.lod_tree import build_lod_tree
from repro.core.pipeline import SessionConfig
from repro.kernels import ref as kref
from repro.serve import delta_path as dp
from repro.serve import lod_service as svc

FOCAL = 400.0
TAU = 24.0


@pytest.fixture(scope="module")
def word_tree():
    """A small city whose slabs fill whole words (the city trees' layout:
    the joined cut plane is one shifted stream)."""
    city = generate_city(CityConfig(blocks_x=2, blocks_y=2, leaf_density=0.15,
                                    seed=4))
    tree = build_lod_tree(city, target_subtrees=12, slab_pad_to=32, seed=0)
    assert tree.meta.S % 32 == 0 and tree.meta.T % 32 != 0
    return tree


def _crowd(rng, n, steps):
    """(steps, n, 3) eye positions: two plazas, 10 clients at one and 6 at
    the other, each client walking a short random path."""
    plazas = np.asarray([[30.0, 30.0, 1.7], [70.0, 60.0, 1.7]], np.float32)
    home = plazas[(np.arange(n) >= 10).astype(int)]
    start = home + rng.normal(0.0, 4.0, (n, 3)).astype(np.float32) * [1, 1, 0]
    steps_xy = rng.normal(0.0, 1.5, (steps, n, 3)).astype(np.float32)
    steps_xy[..., 2] = 0.0
    return start[None] + np.cumsum(steps_xy, axis=0)


def test_crowd_matches_the_per_client_oracle(word_tree, monkeypatch):
    # a tiny chunk budget: every slot loop of the tail runs several passes
    monkeypatch.setattr(bp, "CHUNK_ELEMS", 2 * bp.WORD * bp.words(
        word_tree.n_pad))
    n, steps = 16, 5
    cfg = SessionConfig(tau=TAU, cut_budget=4096, w_star=2)
    service = svc.LodService(word_tree, cfg, n, focal=FOCAL, mode="pooled")
    walks = _crowd(np.random.default_rng(0), n, steps)
    n_pad = word_tree.n_pad
    cuts = np.zeros((steps, n, n_pad), bool)
    prev_has = np.zeros((n, n_pad), bool)
    for f in range(steps):
        stats = service.sync(walks[f])
        assert 1 <= service.last_account["sweep_chunks"]
        gids = np.asarray(service.state.cut_gids)
        for c in range(n):
            want = ls.reference_search_np(word_tree, walks[f, c], FOCAL, TAU)
            cuts[f, c] = want
            got = np.zeros(n_pad, bool)
            got[gids[c][gids[c] >= 0]] = True
            np.testing.assert_array_equal(got, want, err_msg=f"{f}/{c}")
            ids, _ = service.client_delta(c)
            ids = np.asarray(ids)
            np.testing.assert_array_equal(
                np.sort(ids[ids >= 0]), np.flatnonzero(want & ~prev_has[c]),
                err_msg=f"delta {f}/{c}")
        assert int(np.asarray(stats.cut_size).sum()) == int(cuts[f].sum())
        assert int(np.asarray(stats.unique_delta).sum()) == int(
            (cuts[f] & ~prev_has).any(axis=0).sum())
        prev_has = np.asarray(bp.unpack(service.state.mgr.client_has, n_pad))
    for c in range(n):
        deltas, residents = mgr.reference_manager_np(cuts[:, c], cfg.w_star)
        np.testing.assert_array_equal(
            residents[-1], int(prev_has[c].sum()), err_msg=str(c))
        assert deltas.sum() >= residents[-1] > 0
    assert not np.asarray(service.state.pending).any()


def test_slot_state_is_packed(word_tree):
    cfg = SessionConfig(tau=TAU, cut_budget=4096)
    service = svc.LodService(word_tree, cfg, 4, focal=FOCAL)
    n, m = word_tree.n_pad, word_tree.meta
    per_slab = 12 + 4 + 1 + 1 + 1      # cam0, rho and three flags
    bound = bp.words(n) * 16 + n + 4 * cfg.cut_budget + per_slab * m.Ns + 64
    assert service._slot_state_bytes() <= bound
    st = service.state
    for plane in (st.mgr.client_has, st.mgr.cut_prev, st.pending,
                  *st.mgr.age):
        assert plane.shape == (4, bp.words(n)) and plane.dtype == jnp.uint32
    assert st.temporal.slab_cut0.shape == (4, m.Ns, m.S // 32)
    service.sync(np.full((4, 3), 40.0, np.float32))
    assert service.last_account["slot_state_bytes"] == \
        service._slot_state_bytes()


def test_city_scale_slot_state_fits_ten_megabytes():
    """The city32 scene's per-slot state, from shapes alone: n_pad
    5,120,326, Ns 3,333, S 1,536, cut_budget 262,144."""
    tree = types.SimpleNamespace(
        n_pad=5_120_326, meta=types.SimpleNamespace(Ns=3333, S=1536))
    cfg = SessionConfig(tau=48.0, cut_budget=262144, w_star=32)
    state = jax.eval_shape(lambda: svc.service_init(tree, cfg, 4))
    fake = types.SimpleNamespace(state=state, capacity=4)
    per_slot = svc.LodService._slot_state_bytes(fake)
    assert 8.5e6 < per_slot <= 10e6, per_slot


def test_lodservice_refuses_a_window_past_the_age_cap(word_tree):
    with pytest.raises(ValueError, match="w_star"):
        svc.LodService(word_tree, SessionConfig(tau=TAU, w_star=255), 2,
                       focal=FOCAL)


TAIL = [(mgr, "batched_cloud_sync"), (svc, "_batched_cut_gids"),
        (dp, "first_owner_counts"), (dp, "_union_mask"),
        (dp, "_rank_union"), (dp, "_union_refs"),
        (svc, "_apply_pooled_updates")]


def test_no_fleet_wide_byte_array_in_the_tail(word_tree, monkeypatch):
    """Every program of a pooled sync's tail, lowered at the arguments the
    sync gave it: no array holds a byte or more per node for every slot."""
    monkeypatch.setattr(bp, "CHUNK_ELEMS", 2 * bp.WORD * bp.words(
        word_tree.n_pad))
    calls = {}
    for mod, name in TAIL:
        real = getattr(mod, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            calls.setdefault(_name, (_real, args, kwargs))
            return _real(*args, **kwargs)

        monkeypatch.setattr(mod, name, spy)
    c = 8
    cfg = SessionConfig(tau=TAU, cut_budget=2048)
    service = svc.LodService(word_tree, cfg, c, focal=FOCAL,
                             delta_budget=1024)
    service.sync(_crowd(np.random.default_rng(1), c, 1)[0])
    assert set(calls) == {name for _, name in TAIL}
    n = word_tree.n_pad
    shape = re.compile(r"tensor<((?:\d+x)+)([a-z]+\d*)>")
    for name, (real, args, kwargs) in calls.items():
        text = real.lower(*args, **kwargs).as_text()
        for dims, _ in set(shape.findall(text)):
            dims = [int(d) for d in dims.rstrip("x").split("x")]
            per_slot = int(np.prod(dims[1:], dtype=np.int64))
            assert not (dims[0] == c and per_slot >= n), (name, dims)


def _pairs(tree, rng, k, b):
    return (jnp.asarray(rng.integers(0, b, k), jnp.int32),
            jnp.asarray(rng.integers(0, tree.meta.Ns, k), jnp.int32))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_sweep_matches_one_pass_and_the_level_loop(small_tree,
                                                           impl):
    rng = np.random.default_rng(5)
    b, k, chunk = 3, 40, 16                   # 3 passes, the last padded
    tables = ls.SlabTables.from_tree(small_tree)
    rpe = jnp.asarray(rng.random((b, small_tree.meta.Ns)) < 0.8)
    cams = jnp.asarray(rng.uniform(10, 90, (b, 3)), jnp.float32)
    taus = jnp.asarray([24.0, 32.0, 48.0], jnp.float32)
    sel_b, sel_s = _pairs(small_tree, rng, k, b)
    args = (tables, rpe, cams, taus, sel_b, sel_s, jnp.float32(FOCAL))
    one = svc._pooled_pair_sweep(*args, impl=impl)
    chunked = svc._pooled_pair_sweep(*args, impl=impl, chunk=chunk)
    assert one[0].dtype == jnp.bool_ and chunked[0].dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(chunked[0]),
                                  np.asarray(bp.pack(one[0])))
    for a, b_ in zip(one[1:], chunked[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    oracle = kref.ref_lod_pair_sweep(
        small_tree.slab_mu()[sel_s], small_tree.slab_size()[sel_s],
        small_tree.slab_parent[sel_s], small_tree.slab_level[sel_s],
        small_tree.slab_is_leaf[sel_s], small_tree.slab_valid[sel_s],
        rpe[sel_b, sel_s], cams[sel_b], jnp.float32(FOCAL), taus[sel_b],
        max_depth=small_tree.meta.slab_max_depth)
    np.testing.assert_array_equal(
        np.asarray(bp.unpack(chunked[0], small_tree.meta.S)),
        np.asarray(oracle[0]))
    np.testing.assert_array_equal(np.asarray(chunked[1]),
                                  np.asarray(oracle[1]))
    # one chunk's bucket is the one-pass program: no loop in it
    lowered = svc._pooled_pair_sweep.lower(*args, impl="xla").as_text()
    assert "stablehlo.while" not in lowered
    assert "stablehlo.while" in svc._pooled_pair_sweep.lower(
        *args, impl="xla", chunk=chunk).as_text()


def test_packed_snapshot_restores_bitwise(word_tree, tmp_path):
    cfg = SessionConfig(tau=TAU, cut_budget=4096)
    service = svc.LodService(word_tree, cfg, 6, focal=FOCAL,
                             delta_budget=256, page_size=64)
    walks = _crowd(np.random.default_rng(2), 6, 3)
    for f in range(2):
        service.sync(walks[f])
    assert np.asarray(service.state.pending).any()   # debt rides along
    service.snapshot(str(tmp_path), step=1)
    restored = svc.LodService.restore(word_tree, str(tmp_path))
    for a, b in zip(jax.tree_util.tree_leaves(service.state),
                    jax.tree_util.tree_leaves(restored.state)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    service.sync(walks[2])
    restored.sync(walks[2])
    for a, b in zip(jax.tree_util.tree_leaves(service.state),
                    jax.tree_util.tree_leaves(restored.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
