"""Encode-once fleet Δcut dedup (repro.serve.delta_path): per-client decoded
payloads must be bitwise identical to the encode-per-client path across
overlap factors and ragged per-client Δ sizes, codec work must be one batched
encode per sync, and fleet bytes must grow with unique Gaussians, not B."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitplane as bp
from repro.core import compression as comp
from repro.core.pipeline import SessionConfig, session_wire_format
from repro.serve import delta_path as dp
from repro.serve import lod_service as svc

FOCAL = 1400.0
TAU = 32.0


def _planes(masks) -> jax.Array:
    """(B, N) bool masks as the bit planes the union takes."""
    return bp.pack(jnp.asarray(masks))


def _nodes(planes, n=None) -> np.ndarray:
    """Bit planes of a batch or a service as (..., n) bool node masks."""
    return np.asarray(bp.unpack(planes, n))


def _masks_for_overlap(n: int, b: int, overlap: float, rng,
                       sizes=(600, 350, 150)) -> np.ndarray:
    """(B, N) bool Δ masks with a controlled shared fraction and RAGGED
    per-client sizes (client i requests sizes[i % len] rows, of which
    ~overlap are drawn from one shared pool)."""
    masks = np.zeros((b, n), bool)
    pool = rng.permutation(n)
    shared_pool = pool[: n // 2]
    private_pool = pool[n // 2 :]
    p_off = 0
    for i in range(b):
        k = sizes[i % len(sizes)]
        k_shared = int(round(k * overlap))
        own = shared_pool[:k_shared].tolist()
        own += private_pool[p_off : p_off + (k - k_shared)].tolist()
        p_off += k - k_shared
        masks[i, own] = True
    return masks


@pytest.mark.parametrize("overlap", [0.0, 0.5, 1.0])
def test_dedup_decode_bitwise_matches_per_client(small_tree, overlap):
    rng = np.random.default_rng(11)
    b, n = 3, small_tree.n_pad
    sizes = (600, 600, 600) if overlap == 1.0 else (600, 350, 150)
    if overlap == 1.0:  # identical masks: the fully co-located sync
        one = _masks_for_overlap(n, 1, 1.0, rng, sizes=(600,))
        masks = np.repeat(one, b, axis=0)
    else:
        masks = _masks_for_overlap(n, b, overlap, rng, sizes=sizes)
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    sh_k = small_tree.gaussians.sh.shape[1]
    budget = int(masks.any(axis=0).sum()) + 32

    batch = dp.build_delta_batch(small_tree.gaussians, codec,
                                 _planes(masks), budget)
    assert not bool(batch.overflow)
    assert int(batch.n_union) == int(masks.any(axis=0).sum())
    assert int(batch.n_shipped) == int(batch.n_union)  # ample: no paging
    assert not _nodes(batch.deferred).any()
    ref = dp.encode_per_client(small_tree.gaussians, codec,
                               jnp.asarray(masks), budget)

    for i in range(b):
        ids_u, dec_u = dp.decode_client(codec, batch, sh_k, i)
        ids_u = np.asarray(ids_u)
        sel_u = ids_u >= 0
        ids_r, enc_r, ovf_r = ref[i]
        # a truncated reference stream would make the parity below
        # meaningless — the budget must have been ample for BOTH paths
        assert not bool(ovf_r), f"client {i} reference stream truncated"
        ids_r = np.asarray(ids_r)
        sel_r = ids_r >= 0
        # same rows, same ascending-gid order
        np.testing.assert_array_equal(ids_u[sel_u], ids_r[sel_r], err_msg=str(i))
        # encoded representation: union rows referenced by this client vs its
        # own unicast stream — bitwise equal, field by field
        enc_u = batch.payload
        for field in ("dc", "code", "pos_q", "scale_q", "quat_q", "opa_q"):
            np.testing.assert_array_equal(
                np.asarray(getattr(enc_u, field))[sel_u],
                np.asarray(getattr(enc_r, field))[sel_r],
                err_msg=f"client {i} field {field}")
        # and so is the decode the client store would ingest
        dec_r = comp.decode(codec, enc_r, sh_k)
        for field in ("mu", "log_scale", "quat", "opacity", "sh"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dec_u, field))[sel_u],
                np.asarray(getattr(dec_r, field))[sel_r],
                err_msg=f"client {i} field {field}")


def test_all_clients_idle_sync(small_tree):
    """The all-idle sync (no client needs anything) must produce an empty,
    well-formed batch."""
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    masks = jnp.zeros((4, small_tree.n_pad), bool)
    batch = dp.build_delta_batch(small_tree.gaussians, codec, _planes(masks),
                                 64)
    assert int(batch.n_union) == 0
    assert not bool(batch.overflow)
    assert not np.asarray(batch.ref_mask).any()
    ids, _dec = dp.decode_client(codec, batch,
                                 small_tree.gaussians.sh.shape[1], 2)
    assert (np.asarray(ids) == -1).all()
    assert np.asarray(dp.first_owner_counts(_planes(masks))).sum() == 0


def test_union_overflow_flagged(small_tree):
    rng = np.random.default_rng(3)
    masks = _masks_for_overlap(small_tree.n_pad, 2, 0.0, rng,
                               sizes=(100, 80))
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    batch = dp.build_delta_batch(small_tree.gaussians, codec,
                                 _planes(masks), 64)
    assert bool(batch.overflow)
    # ... but nothing is lost: exactly budget rows shipped, the rest is
    # reported as per-client deferred carry-over
    assert int(batch.n_shipped) == 64
    assert int(batch.n_union) == 180
    delivered = _nodes(batch.delivered, small_tree.n_pad)
    deferred = _nodes(batch.deferred, small_tree.n_pad)
    np.testing.assert_array_equal(delivered | deferred, masks)
    assert not (delivered & deferred).any()
    assert deferred.any(axis=1).all()  # both clients lost rows to paging
    assert np.asarray(batch.client_overflow).all()


def test_paged_stream_ships_coarse_rows_first(small_tree):
    """With a priority key, the shipped subset must be exactly the lowest-
    priority-ranked union rows, and the stream must stay ascending by gid."""
    rng = np.random.default_rng(9)
    masks = _masks_for_overlap(small_tree.n_pad, 3, 0.3, rng)
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    prio = np.asarray(small_tree.node_levels())
    batch = dp.build_delta_batch(small_tree.gaussians, codec,
                                 _planes(masks), 128,
                                 priority=small_tree.node_levels())
    union = masks.any(axis=0)
    gids = np.asarray(batch.union_gids)
    shipped = gids[gids >= 0]
    assert shipped.size == 128 == int(batch.n_shipped)
    assert (np.diff(shipped) > 0).all()          # ascending, delta-codable
    # priority cut: every shipped row ranks <= every deferred row under
    # (level, -requesters, gid) lexicographic order
    req = masks.sum(axis=0)
    rank = sorted((int(prio[g]), -int(req[g]), int(g))
                  for g in np.flatnonzero(union))
    want = {g for _, _, g in rank[:128]}
    assert set(shipped.tolist()) == want


def test_union_ranking_compiles_once_across_stream_widths(small_tree):
    """The N-row priority sort is keyed on the table size only: unions that
    land in different pow2 stream widths reuse one compiled ranking (on a
    TPU each compile of that sort takes tens of seconds at city scale)."""
    rng = np.random.default_rng(3)
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    widths, traced = set(), None
    for budget in (64, 256, 1024):
        masks = _masks_for_overlap(small_tree.n_pad, 3, 0.5, rng)
        batch = dp.build_delta_batch(small_tree.gaussians, codec,
                                     _planes(masks), budget,
                                     priority=small_tree.node_levels())
        widths.add(batch.union_gids.shape[0])
        traced = traced or dp._rank_union._cache_size()
    assert widths == {64, 256, 1024}
    assert dp._rank_union._cache_size() == traced


@pytest.mark.parametrize("budget,widths,page", [
    (4096, (), None),              # nothing built: the union's own bucket
    (4096, (16,), None),           # every built width too narrow: new bucket
    (4096, (2048, 4096), None),    # the narrowest built width that holds it
    (4096, (4096, 1024), 64),      # priority pages, widths in any order
    (4096, (1 << 20,), 64),        # a built width above the budget: unused
    (128, (1024,), 32),            # paged overflow: the budget caps both
])
def test_built_widths_only_pad_the_stream(small_tree, budget, widths, page):
    """A stream widened to a width a service has built only pads it: every
    shipped row, reference, deferral, page count and decoded row is the
    unpadded stream's, and the width is the narrowest built width within
    the budget that holds the union's pow2 bucket, else that bucket."""
    rng = np.random.default_rng(13)
    masks = _planes(_masks_for_overlap(small_tree.n_pad, 3, 0.5, rng))
    codec, _ = session_wire_format(small_tree, SessionConfig(tau=TAU))
    sh_k = small_tree.gaussians.sh.shape[1]
    kw = dict(priority=small_tree.node_levels(), page_size=page)
    base = dp.build_delta_batch(small_tree.gaussians, codec, masks, budget,
                                **kw)
    wide = dp.build_delta_batch(small_tree.gaussians, codec, masks, budget,
                                widths=widths, **kw)
    u = base.union_gids.shape[0]
    assert wide.union_gids.shape[0] == min(
        [w for w in widths if u <= w <= budget], default=u)
    for name in ("n_union", "n_shipped", "delivered", "deferred",
                 "client_overflow", "client_pages", "pages", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(wide, name)),
                                      np.asarray(getattr(base, name)),
                                      err_msg=name)
    gids = np.asarray(wide.union_gids)
    np.testing.assert_array_equal(gids[:u], np.asarray(base.union_gids))
    assert (gids[u:] == -1).all()
    ref = np.asarray(wide.ref_mask)
    np.testing.assert_array_equal(ref[:, :u], np.asarray(base.ref_mask))
    assert not ref[:, u:].any()
    np.testing.assert_array_equal(np.asarray(wide.row_page)[:u],
                                  np.asarray(base.row_page))
    for c in range(masks.shape[0]):
        ids_b, rows_b = dp.decode_client(codec, base, sh_k, c)
        ids_w, rows_w = dp.decode_client(codec, wide, sh_k, c)
        ids_b, ids_w = np.asarray(ids_b), np.asarray(ids_w)
        np.testing.assert_array_equal(ids_w[:u], ids_b)
        on = ids_b >= 0
        for leaf_b, leaf_w in zip(jax.tree_util.tree_leaves(rows_b),
                                  jax.tree_util.tree_leaves(rows_w)):
            np.testing.assert_array_equal(np.asarray(leaf_w)[:u][on],
                                          np.asarray(leaf_b)[on])


def test_first_owner_counts_partition_union(small_tree):
    rng = np.random.default_rng(5)
    masks = _masks_for_overlap(small_tree.n_pad, 4, 0.5, rng)
    u = np.asarray(dp.first_owner_counts(_planes(masks)))
    assert u.sum() == masks.any(axis=0).sum()
    assert (u <= masks.sum(axis=1)).all()


# -- service-level: one codec call per sync, bytes grow with unique ----------


def _count_encodes(monkeypatch):
    calls = {"n": 0}
    real = comp.encode

    def counting_encode(codec, g):
        calls["n"] += 1
        return real(codec, g)

    monkeypatch.setattr(comp, "encode", counting_encode)
    return calls


def test_service_encodes_once_per_sync(small_tree, monkeypatch):
    """B co-located clients: the dedup service runs the codec ONCE per sync;
    the per-client reference path runs it B times."""
    b = 6
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.broadcast_to(np.asarray([40.0, 40.0, 2.0], np.float32),
                           (b, 3)).copy()
    service = svc.LodService(small_tree, cfg, b, focal=FOCAL, mode="pooled",
                             dedup=True)
    calls = _count_encodes(monkeypatch)
    service.sync(cams)
    assert calls["n"] == 1
    service.sync(cams + 1.0)
    assert calls["n"] == 2  # still one per sync, B-independent

    masks = _nodes(service.state.mgr.cut_prev, small_tree.n_pad)
    calls["n"] = 0
    dp.encode_per_client(small_tree.gaussians, service.codec,
                         jnp.asarray(masks), 256)
    assert calls["n"] == b

    off = svc.LodService(small_tree, cfg, b, focal=FOCAL, mode="pooled",
                         dedup=False)
    calls["n"] = 0
    off.sync(cams)
    assert calls["n"] == 0  # unicast accounting path never touches the codec


def test_colocated_fleet_bytes_grow_with_unique_not_b(small_tree):
    """Identical cameras: fleet downlink = one shared payload + B thin
    framings — total sync_bytes for B clients must equal the single-client
    total plus (B-1) framings, NOT B× the single-client total."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cam = np.asarray([[40.0, 40.0, 2.0]], np.float32)
    b = 8

    s1 = svc.LodService(small_tree, cfg, 1, focal=FOCAL, dedup=True)
    st1 = s1.sync(cam)
    sb = svc.LodService(small_tree, cfg, b, focal=FOCAL, dedup=True)
    stb = sb.sync(np.repeat(cam, b, axis=0))

    total1 = float(np.asarray(st1.sync_bytes).sum())
    totalb = float(np.asarray(stb.sync_bytes).sum())
    ids = float(np.asarray(st1.cut_size)[0])  # first sync: cut_add == cut
    # co-located clients pull from the same priority pages, so per-client
    # framing = membership ids + sync header + page headers
    pages = float(np.asarray(stb.pages)[0])
    assert pages == float(np.asarray(st1.pages)[0])
    framing = ids * 2 + 64 + pages * 16
    # ID_BYTES_DELTA * ids + SYNC_HEADER_BYTES + pages * PAGE_HEADER_BYTES
    assert np.isclose(totalb, total1 + (b - 1) * framing, rtol=1e-5), \
        (totalb, total1, framing)
    # payload part is O(unique): far below B x the unicast accounting
    assert totalb < 0.35 * b * total1
    assert int(np.asarray(stb.unique_delta).sum()) == int(sb.last_delta.n_union)
    assert float(np.asarray(stb.dedup_bytes_saved).sum()) > 0.0


def test_service_surfaces_delta_overflow(small_tree):
    """A too-small delta_budget pages the encode-once stream — the service
    must surface that PER CLIENT in ServiceStats (exactly the clients with
    deferred rows), not as a fleet-wide broadcast."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [41.0, 40.0, 2.0]], np.float32)
    tight = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                           delta_budget=64)
    st = tight.sync(cams)
    deferred = _nodes(tight.last_delta.deferred).any(axis=1)
    np.testing.assert_array_equal(np.asarray(st.delta_overflow), deferred)
    assert deferred.all()  # both clients' Δs dwarf 64 rows here
    assert bool(tight.last_delta.overflow)
    # shipped + owed partitions each client's Δ; bytes charge only shipped
    shipped = np.asarray(st.delta_shipped)
    owed = np.asarray(st.delta_deferred)
    np.testing.assert_array_equal(shipped + owed, np.asarray(st.delta_size))
    assert (shipped <= 64).all()
    ok = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True)
    st = ok.sync(cams)  # default budget bounds the union — never defers
    assert not np.asarray(st.delta_overflow).any()
    assert not np.asarray(st.delta_deferred).any()


def test_tight_budget_bytes_charge_only_shipped_rows(small_tree):
    """Regression (the silent-overcharge bug): with a tight delta_budget,
    per-client sync_bytes must count only the union rows actually shipped
    this sync plus the page/sync framing — NOT the full requested Δ."""
    from repro.core import manager as mgr
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [41.0, 40.0, 2.0]], np.float32)
    tight = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                           delta_budget=64, page_size=16)
    st = tight.sync(cams)
    batch = tight.last_delta
    delivered = _nodes(batch.delivered)
    share = delivered.sum(axis=0)
    ids = np.asarray(st.cut_size)  # first sync: cut_add == cut, no removes
    want = np.empty(2)
    for b in range(2):
        frac = (1.0 / np.maximum(share[delivered[b]], 1)).sum()
        want[b] = (frac * (tight.bytes_per_g + mgr.ID_BYTES_DELTA)
                   + ids[b] * mgr.ID_BYTES_DELTA + mgr.SYNC_HEADER_BYTES
                   + int(np.asarray(batch.client_pages)[b])
                   * mgr.PAGE_HEADER_BYTES)
    np.testing.assert_allclose(np.asarray(st.sync_bytes), want, rtol=1e-5)
    # the old accounting would have charged every requested row:
    assert np.asarray(st.sync_bytes).sum() < (
        np.asarray(st.delta_size, np.float64).sum() * tight.bytes_per_g)


# -- paging convergence: tight budgets defer, never lose ---------------------


def _converge(service, cams, oracle_delivered, budget):
    """Drive `service` at static `cams` until its pending debt drains;
    assert bitwise convergence to `oracle_delivered` within the page bound.
    Returns the number of syncs taken."""
    u = int(oracle_delivered.any(axis=0).sum())
    max_syncs = -(-u // budget)  # ceil: one full-width page-set per sync
    got = np.zeros_like(oracle_delivered)
    for k in range(max_syncs):
        service.sync(cams)
        got |= _nodes(service.last_delta.delivered)
        if not np.asarray(service.state.pending).any():
            break
    assert not np.asarray(service.state.pending).any(), \
        f"debt left after {max_syncs} syncs"
    np.testing.assert_array_equal(got, oracle_delivered)
    return k + 1


@pytest.mark.parametrize("mode,impl", [("vmapped", "xla"), ("pooled", "xla"),
                                       ("pooled", "pallas")])
def test_paged_syncs_converge_bitwise_to_unbudgeted_oracle(small_tree, mode,
                                                           impl):
    """delta_budget < true union: every client's store must converge
    BITWISE to the unbudgeted baseline in <= ceil(U/width) syncs — rows
    arrive later, never never. All three sweep paths."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [46.0, 41.0, 2.5],
                       [38.0, 47.0, 3.0]], np.float32)
    kw = dict(focal=FOCAL, mode=mode, sweep_impl=impl, dedup=True)
    base = svc.LodService(small_tree, cfg, 3, **kw)
    base.sync(cams)
    oracle = _nodes(base.last_delta.delivered)
    assert not np.asarray(base.state.pending).any()  # ample: no debt, ever

    budget = 128
    tight = svc.LodService(small_tree, cfg, 3, delta_budget=budget,
                           page_size=64, **kw)
    n_syncs = _converge(tight, cams, oracle, budget)
    assert n_syncs > 1  # the budget actually paged the stream


def _store_scatter(store, ids, dec):
    sel = np.asarray(ids) >= 0
    gids = np.asarray(ids)[sel]
    for f in ("mu", "log_scale", "quat", "opacity", "sh"):
        store.setdefault(f, {})
        rows = np.asarray(getattr(dec, f))[sel]
        for g, row in zip(gids.tolist(), rows):
            store[f][g] = row
    return store


def test_paged_decoded_store_bitwise_equals_oracle_store(small_tree):
    """The decode-side proof: accumulate one client's per-sync decoded Δ
    slices from the paged stream and compare every row bitwise against the
    single unbudgeted sync."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [44.0, 43.0, 2.5]], np.float32)
    base = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True)
    base.sync(cams)
    want = _store_scatter({}, *base.client_delta(0))

    budget = 128
    tight = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                           delta_budget=budget, page_size=32)
    got, syncs = {}, 0
    while True:
        tight.sync(cams)
        got = _store_scatter(got, *tight.client_delta(0))
        syncs += 1
        if not np.asarray(tight.state.pending).any():
            break
        assert syncs < 64, "paged stream failed to drain"
    assert syncs > 1
    for f in want:
        assert got[f].keys() == want[f].keys(), f
        for g in want[f]:
            np.testing.assert_array_equal(got[f][g], want[f][g],
                                          err_msg=f"{f}/gid{g}")


def test_paged_convergence_under_churn(small_tree):
    """Churn safety: an evicted slot DROPS its deferred pages (no debt ever
    reattaches to the slot's next tenant), an admitted client starts clean,
    and survivors still converge bitwise to their unbudgeted replay."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cam_a = np.asarray([40.0, 40.0, 2.0], np.float32)
    cam_b = np.asarray([46.0, 42.0, 2.5], np.float32)
    cam_c = np.asarray([38.0, 47.0, 3.0], np.float32)
    budget = 128
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                             capacity=4, delta_budget=budget, page_size=64)
    service.sync(np.stack([cam_a, cam_b]))
    assert np.asarray(service.state.pending).any()  # tight budget: debt

    # evict the indebted client 1: its slot's debt must vanish immediately
    slot_b = service._slot_of(1)
    assert np.asarray(service.state.pending)[slot_b].any()
    service.evict(1)
    assert not np.asarray(service.state.pending)[slot_b].any()

    # admit a newcomer (recycles the slot) — starts with zero debt
    cid_c = service.admit(cam_c)
    slot_c = service._slot_of(cid_c)
    assert not np.asarray(service.state.pending)[slot_c].any()

    # drive to convergence for the survivors
    for _ in range(32):
        service.sync({0: cam_a, cid_c: cam_c})
        if not np.asarray(service.state.pending).any():
            break
    assert not np.asarray(service.state.pending).any()

    # each survivor's store == a fresh ample single-client replay's store
    for cid, cam in ((0, cam_a), (cid_c, cam_c)):
        ref = svc.LodService(small_tree, cfg, 1, focal=FOCAL, dedup=True)
        ref.sync(cam[None])
        slot = service._slot_of(cid)
        np.testing.assert_array_equal(
            np.asarray(service.state.mgr.client_has[slot]),
            np.asarray(ref.state.mgr.client_has[0]), err_msg=f"cid{cid}")


# -- closed-loop bitrate control ---------------------------------------------


def test_rate_control_step_unit():
    """The controller's pure update rule, pinned: multiplicative tracking
    clipped to [x0.5, x2], one-page floor, tau escalation only at the floor,
    decay once comfortably under target, uncontrolled slots untouched."""
    target = np.asarray([1e4, 1e4, np.inf, 1e4])
    allowance = np.asarray([1000, 64, -1, 1000])
    tau = np.ones(4, np.float32)
    # client 0 overshoots 4x -> clipped halving; client 1 at the floor ->
    # tau escalates; client 2 uncontrolled; client 3 on target -> unchanged
    measured = np.asarray([4e4, 4e4, 123.0, 1e4])
    allow2, tau2 = svc.rate_control_step(target, measured, allowance, tau,
                                         page_size=64, max_rows=4096)
    assert allow2.tolist() == [500, 64, -1, 1000]
    assert tau2[0] == 1.0 and tau2[1] == pytest.approx(1.25)
    assert tau2[2] == 1.0 and tau2[3] == 1.0
    # undershoot far below target: allowance doubles (clip x2), and an
    # escalated tau decays back toward 1.0
    measured = np.asarray([1e3, 1e3, 0.0, 1e3])
    allow3, tau3 = svc.rate_control_step(target, measured, allow2, tau2,
                                         page_size=64, max_rows=4096)
    assert allow3.tolist() == [1000, 128, -1, 2000]
    assert tau3[1] == 1.0  # 1.25 / 1.25, floored at 1.0
    # idle sync (0 measured bytes) leaves the controlled state alone
    assert allow3[2] == -1 and tau3[2] == 1.0


def test_rate_control_idle_client_relaxes_escalation():
    """Regression (burst-then-idle): a client that bursts to the floor and
    escalates tau, then goes IDLE, must be released — `measured == 0` under
    a finite target is maximal headroom, not "no signal". Pre-fix the
    update forced ratio to 1.0 at zero measurement, so an idle client's
    allowance froze at the floor and its escalated tau never decayed: one
    bursty sync pinned it coarse forever."""
    target = np.asarray([1e4])
    allow = np.asarray([64])
    tau = np.asarray([2.0], np.float32)
    # the burst: 8x over target at the one-page floor -> tau escalates
    allow, tau = svc.rate_control_step(target, [8e4], allow, tau,
                                       page_size=64, max_rows=4096)
    assert allow.tolist() == [64] and tau[0] == pytest.approx(2.5)
    # first idle sync: full x2 allowance step AND a tau relax
    allow, tau = svc.rate_control_step(target, [0.0], allow, tau,
                                       page_size=64, max_rows=4096)
    assert allow.tolist() == [128] and tau[0] == pytest.approx(2.0)
    # sustained idle drains the escalation completely and re-opens the
    # allowance to the stream budget
    for _ in range(8):
        allow, tau = svc.rate_control_step(target, [0.0], allow, tau,
                                           page_size=64, max_rows=4096)
    assert tau[0] == 1.0 and allow[0] == 4096


def test_page_size_budget_degenerate_config(small_tree):
    """Regression: `page_size > delta_budget` used to invert the
    controller's `np.clip(..., page_size, max_rows)` bounds — numpy
    silently returns the max everywhere, freezing the loop at an allowance
    the stream can never serve. The config is now a typed error at
    construction, the default page adapts to small budgets, and the
    controller floor is `min(page_size, max_rows)` so the bounds can never
    invert."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    with pytest.raises(ValueError, match="page_size"):
        svc.LodService(small_tree, cfg, 1, focal=FOCAL, dedup=True,
                       delta_budget=64, page_size=256)
    with pytest.raises(ValueError, match="page_size"):
        svc.LodService(small_tree, cfg, 1, focal=FOCAL, dedup=True,
                       delta_budget=64, page_size=0)
    service = svc.LodService(small_tree, cfg, 1, focal=FOCAL, dedup=True,
                             delta_budget=64)
    assert service.page_size == 64        # default clamps to the budget
    # the pure update rule floors at the EFFECTIVE page (min with the
    # budget): an overshooting client lands exactly on the serveable floor
    # and the tau fallback still engages there
    allow, tau = svc.rate_control_step(
        [1e4], [4e4], [64], np.ones(1, np.float32),
        page_size=512, max_rows=128)
    assert allow.tolist() == [128] and tau[0] == pytest.approx(1.25)


def test_bandwidth_tiers_shape_the_stream(small_tree):
    """Heterogeneous bandwidth on one fleet: the narrow client is paced
    (rows deferred, allowance tightened by the loop) while the uncapped
    client drinks the full stream — and once the fleet goes static, every
    deferred row still arrives (rate control never loses data)."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    rng = np.random.default_rng(17)
    cams = np.asarray([[40.0, 40.0, 2.0], [41.0, 40.5, 2.2]], np.float32)
    narrow = 2e3  # bytes/sync — far below any cold Δcut
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                             bandwidth=[narrow, 1e9], page_size=64)
    assert service.client_bandwidth(0)[0] == narrow
    seed_allow = service.client_bandwidth(0)[1]
    # the uncapped client's allowance saturates at the stream budget
    assert service.client_bandwidth(1)[1] == service.delta_budget

    narrow_bytes, wide_bytes, narrow_deferred = [], [], 0
    for _ in range(6):
        st = service.sync(cams)
        narrow_bytes.append(float(np.asarray(st.sync_bytes)[0]))
        wide_bytes.append(float(np.asarray(st.sync_bytes)[1]))
        narrow_deferred += int(np.asarray(st.delta_deferred)[0] > 0)
        cams = cams + rng.uniform(1.0, 3.0, cams.shape).astype(np.float32)
    # the cold sync's union dwarfs the narrow client's row allowance...
    assert narrow_deferred > 0
    # ...so it is paced far below the uncapped client
    assert narrow_bytes[0] < wide_bytes[0]
    # the loop reacts to the overshoot: allowance never exceeds its seed,
    # and the tau fallback only ever escalates (scale >= 1)
    assert service.client_bandwidth(0)[1] <= seed_allow
    assert service.client_bandwidth(0)[2] >= 1.0
    assert service.client_bandwidth(1)[1] == service.delta_budget

    # stop moving: the narrow client's debt must fully drain (paged, never
    # lost) — the acceptance claim under rate control
    for _ in range(64):
        service.sync(cams)
        if not np.asarray(service.state.pending).any():
            break
    assert not np.asarray(service.state.pending).any()

    # tier names resolve through BANDWIDTH_TIERS at admission too
    cid = service.admit(cams[0], bandwidth="phone")
    assert service.client_bandwidth(cid)[0] == svc.BANDWIDTH_TIERS["phone"]


# -- page checksums + NACK retransmit ----------------------------------------


def test_page_checksums_and_row_page_wellformed(small_tree):
    """The wire-framing checksum layer on a genuinely paged stream:
    `row_page` maps every shipped wire row to a valid priority page with
    per-page populations bounded by page_size, and `page_checksums` is an
    order-independent per-page digest that a receiver can re-derive from
    the rows it parsed — and that flips when a row is dropped or migrates
    between pages."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [46.0, 41.0, 2.5]], np.float32)
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                             delta_budget=128, page_size=32)
    service.sync(cams)
    batch = service.last_delta
    row_page = np.asarray(batch.row_page)
    gids = np.asarray(batch.union_gids)
    n_shipped = int(np.asarray(batch.n_shipped))
    n_pages = int(np.asarray(batch.pages))
    assert n_pages > 1  # the budget actually paged the stream

    # well-formedness: shipped rows carry a real page id, padding carries -1
    shipped = row_page >= 0
    assert int(shipped.sum()) == n_shipped
    assert (gids[shipped] >= 0).all()
    assert row_page[shipped].max() == n_pages - 1
    counts = np.bincount(row_page[shipped], minlength=n_pages)
    assert (counts > 0).all() and (counts <= service.page_size).all()
    # per-client page pulls can never exceed the stream's page count
    assert (np.asarray(batch.client_pages) <= n_pages).all()

    # receiver-side recompute, in shuffled order: bitwise the header values
    want = service.delta_checksums()
    assert want.shape == (n_pages,) and want.dtype == np.uint32
    rng = np.random.default_rng(0)
    got = np.zeros_like(want)
    for i in rng.permutation(np.flatnonzero(shipped)):
        with np.errstate(over="ignore"):
            got[row_page[i]] += (np.uint32(gids[i]) * dp._CKSUM_MIX
                                 + np.uint32(1))
    np.testing.assert_array_equal(got, want)

    # a dropped row flips exactly its page's checksum...
    import dataclasses as _dc
    drop = int(np.flatnonzero(shipped)[0])
    mangled = row_page.copy()
    mangled[drop] = -1
    broken = _dc.replace(batch, row_page=jnp.asarray(mangled))
    diff = dp.page_checksums(broken) != want
    assert diff[row_page[drop]] and diff.sum() == 1
    # ...and a row migrating between pages flips both (same gid total)
    src, dst = int(row_page[drop]), (int(row_page[drop]) + 1) % n_pages
    moved = row_page.copy()
    moved[drop] = dst
    diff2 = dp.page_checksums(
        _dc.replace(batch, row_page=jnp.asarray(moved))) != want
    assert diff2[src] and diff2[dst] and diff2.sum() == 2


def test_lost_row_mask_is_clients_refs_in_lost_pages(small_tree):
    """`lost_row_mask` re-queues exactly the rows the client INGESTED from
    the named pages — never another client's rows, never rows of intact
    pages."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [46.0, 41.0, 2.5]], np.float32)
    service = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                             delta_budget=128, page_size=32)
    service.sync(cams)
    batch = service.last_delta
    row_page = np.asarray(batch.row_page)
    gids = np.asarray(batch.union_gids)
    n_pages = int(np.asarray(batch.pages))
    for slot in (0, 1):
        ref = np.asarray(batch.ref_mask)[slot]
        lost = [0, n_pages - 1]
        mask = dp.lost_row_mask(batch, slot, lost)
        rows = ref & np.isin(row_page, lost) & (gids >= 0)
        want = np.zeros_like(mask)
        want[gids[rows]] = True
        np.testing.assert_array_equal(mask, want, err_msg=f"slot{slot}")
        # a NACK for every page is exactly this sync's delivered set
        all_mask = dp.lost_row_mask(batch, slot, range(n_pages))
        np.testing.assert_array_equal(
            all_mask, _nodes(batch.delivered)[slot],
            err_msg=f"slot{slot}:all")


def test_nack_retransmit_converges_under_seeded_loss(small_tree):
    """The loss loop end-to-end: every sync, each priority page of the
    paged stream is independently lost with ~10% probability (seeded); the
    client ingests only intact pages and NACKs the rest. The accumulated
    store must converge BITWISE to the lossless unbudgeted oracle — page
    loss costs retransmit syncs, never data."""
    cfg = SessionConfig(tau=TAU, cut_budget=8192)
    cams = np.asarray([[40.0, 40.0, 2.0], [44.0, 43.0, 2.5]], np.float32)
    base = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True)
    base.sync(cams)
    want = _store_scatter({}, *base.client_delta(0))

    lossy = svc.LodService(small_tree, cfg, 2, focal=FOCAL, dedup=True,
                           delta_budget=128, page_size=32)
    rng = np.random.default_rng(23)
    got, losses, syncs = {}, 0, 0
    for syncs in range(1, 64 + 1):
        lossy.sync(cams)
        batch = lossy.last_delta
        n_pages = int(np.asarray(batch.pages))
        lost = [p for p in range(n_pages) if rng.random() < 0.10]
        losses += len(lost)
        # the client keeps only rows of pages whose checksum verified
        ids, dec = lossy.client_delta(0)
        keep = np.asarray(ids) >= 0
        if lost:
            keep &= ~np.isin(np.asarray(batch.row_page), lost)
        kept_ids = np.where(keep, np.asarray(ids), -1)
        got = _store_scatter(got, kept_ids, dec)
        if lost:
            assert lossy.nack(0, lost) >= 0  # re-queue as pending debt
        if not np.asarray(lossy.state.pending).any() and not lost:
            break
    assert losses > 0, "seed never dropped a page — test is vacuous"
    assert not np.asarray(lossy.state.pending).any()
    for f in want:
        assert got[f].keys() == want[f].keys(), f
        for g in want[f]:
            np.testing.assert_array_equal(got[f][g], want[f][g],
                                          err_msg=f"{f}/gid{g}")
