"""The correctness check fails a run whose timed path is broken, and its
control (the reference one precision down) reads over every limit it has.

Each fault drives the rest of a run on the CPU at a tiny city, with the
look for a chip skipped, under the `jump` mix (every client teleports about
every 50 ms, so every tick moves every cut). A one-chip cell has no
exchange between chips, so that fault has no case here."""

import dataclasses
import shutil

import jax.numpy as jnp
import pytest

import bench_support as bs

from repro.serve import delta_path, lod_service


@pytest.fixture(scope="module")
def jump(tmp_path_factory):
    """A checkout whose traffic mixes include tests/bench/data/jump.json."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(bs.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".scene_cache", ".trace",
                                                  "__pycache__"))
    shutil.copy(bs.HERE / "data" / "jump.json", root / "bench" / "traffic")
    (root / "tests" / "bench" / "data").mkdir(parents=True)
    shutil.copy(bs.ROOT / bs.TINY_CONFIG, root / bs.TINY_CONFIG)
    spec = bs.tiny_spec(mixes=["jump"])
    return lambda **kw: bs.run_tiny("tiny.jump", spec_=spec, root=root, **kw)


def test_sound_run_is_correct_and_control_fails(jump):
    result = jump(control=True)
    assert result["correct"] is True, result["checks"]
    # the control goes through the same verdict and comes out not correct;
    # it has to fail one of the numbers, not each
    control = result["control"]
    assert control["correct"] is False, control
    assert any(v["value"] > v["limit"] for v in control["checks"].values())


def test_state_left_unchanged(jump, monkeypatch):
    real = lod_service.service_sync_pooled

    def unchanged(tree, cfg, state, *a, **kw):
        # everything a client sees stays as it was (the temporal search
        # state is the new one only because the sync consumed the old)
        old = dict(cut_gids=state.cut_gids, mgr=state.mgr,
                   pending=state.pending, sync_index=state.sync_index)
        new, stats, batch = real(tree, cfg, state, *a, **kw)
        return dataclasses.replace(new, **old), stats, batch

    monkeypatch.setattr(lod_service, "service_sync_pooled", unchanged)
    result = jump()
    assert result["correct"] is False
    assert result["checks"]["cut_mismatch"]["value"] > \
        result["checks"]["cut_mismatch"]["limit"]


def test_half_of_the_batch_left_out(jump, monkeypatch):
    real = lod_service.LodService.sync

    def half(self, cams=None, participate=None):
        if participate is not None:
            participate = list(participate)[: max(1, len(participate) // 2)]
        return real(self, cams, participate=participate)

    monkeypatch.setattr(lod_service.LodService, "sync", half)
    assert jump()["correct"] is False


def test_answer_altered_where_produced(jump, monkeypatch):
    real = lod_service._batched_cut_gids

    def altered(masks, budget, mesh=None):
        gids, counts = real(masks, budget, mesh=mesh)
        return jnp.where(gids > 0, gids - 1, gids), counts

    monkeypatch.setattr(lod_service, "_batched_cut_gids", altered)
    result = jump()
    assert result["correct"] is False
    assert result["checks"]["cut_mismatch"]["value"] > \
        result["checks"]["cut_mismatch"]["limit"]


def test_decoded_row_altered(jump, monkeypatch):
    real = delta_path.decode_client

    def altered(codec, batch, sh_k, client):
        ids, rows = real(codec, batch, sh_k, client)
        return ids, dataclasses.replace(rows, mu=rows.mu + 0.5)

    monkeypatch.setattr(delta_path, "decode_client", altered)
    result = jump()
    assert result["window"]["rows_checked"] > 0
    assert result["correct"] is False
    assert result["checks"]["row_gap"]["value"] > \
        result["checks"]["row_gap"]["limit"]
