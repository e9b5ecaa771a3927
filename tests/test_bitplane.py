"""Bit planes (`repro.core.bitplane`): packing round-trips, both bit layouts,
the join of a tree's top-tree flags and packed slabs into one plane, and
the slot-chunk loop, against plain numpy on seeded random flags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane as bp


@pytest.mark.parametrize("n", [1, 31, 32, 33, 100, 257])
def test_pack_round_trips(n):
    rng = np.random.default_rng(n)
    bits = rng.random((3, 2, n)) < 0.4
    planes = bp.pack(jnp.asarray(bits))
    assert planes.dtype == jnp.uint32 and planes.shape == (3, 2, bp.words(n))
    np.testing.assert_array_equal(np.asarray(bp.unpack(planes, n)), bits)
    # the padding bits past n are zero, so counts are exact
    assert not np.asarray(bp.unpack(planes))[..., n:].any()
    np.testing.assert_array_equal(np.asarray(bp.count(planes)),
                                  bits.sum(axis=-1))
    # node g is bit g % 32 of word g // 32
    g = np.flatnonzero(bits[0, 0])
    w = np.asarray(planes[0, 0])
    assert all((int(w[i // 32]) >> (i % 32)) & 1 for i in g)


def test_bit_major_layout_round_trips():
    rng = np.random.default_rng(1)
    bits = rng.random((4, 96)) < 0.5
    planes = bp.pack(jnp.asarray(bits))
    spread = np.asarray(bp.spread(planes))                 # (4, 32, 3)
    np.testing.assert_array_equal(np.asarray(bp.node_order(spread)), bits)
    np.testing.assert_array_equal(np.asarray(bp.bit_major(bits)), spread)
    np.testing.assert_array_equal(
        np.asarray(bp.pack_bit_major(jnp.asarray(spread))),
        np.asarray(planes))
    idx = jnp.asarray(rng.integers(0, 96, 17), jnp.int32)
    np.testing.assert_array_equal(np.asarray(bp.bit_at(planes, idx)),
                                  bits[:, np.asarray(idx)])


@pytest.mark.parametrize("t,seg_bits", [(838 % 97, 128), (64, 64),
                                        (7, 40), (0, 24), (33, 32)])
def test_join_is_the_global_node_order(t, seg_bits):
    """Top-tree flags then slab after slab, whether the slabs fill whole
    words (one shifted stream) or not (widened and packed again)."""
    rng = np.random.default_rng(t + seg_bits)
    ns = 5
    top = rng.random((2, t)) < 0.5
    slabs = rng.random((2, ns, seg_bits)) < 0.5
    got = bp.join(jnp.asarray(top), bp.pack(jnp.asarray(slabs)), seg_bits)
    want = np.concatenate([top, slabs.reshape(2, -1)], axis=1)
    assert got.shape == (2, bp.words(want.shape[1]))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(bp.pack(jnp.asarray(want))))


def test_rows_masks_whole_slots():
    planes = bp.pack(jnp.ones((3, 40), bool))
    on = bp.rows(jnp.asarray([True, False, True]), 2)
    np.testing.assert_array_equal(np.asarray(bp.count(planes & on)),
                                  [40, 0, 40])


@pytest.mark.parametrize("chunk", [1, 2, 4, 8])
def test_map_slots_matches_one_pass(chunk):
    """The chunk loop gives the unlooped result, carry included."""
    rng = np.random.default_rng(chunk)
    x = jnp.asarray(rng.integers(0, 1 << 30, (8, 5)), jnp.uint32)

    def fn(total, xs):
        return total + bp.count(xs).sum(), xs ^ jnp.uint32(7)

    total, ys = jax.jit(lambda x: bp.map_slots(fn, x, chunk,
                                               init=jnp.int32(0)))(x)
    assert int(total) == int(bp.count(x).sum())
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(x ^ 7))
    assert bp.slot_chunk(256, bp.CHUNK_ELEMS // 4) == 4
    assert bp.slot_chunk(6, 1) == 2 and bp.slot_chunk(5, 1) == 1
