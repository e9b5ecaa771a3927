"""Bit planes: one flag per tree node, packed 32 nodes to a uint32 word.

Node g is bit `g % 32` of word `g // 32`, so a plane over N nodes has
`words(N)` words and every padding bit past N is zero. The per-slot state
of the fleet service (which nodes a client holds, its last cut, its owed
rows, its cached slab cuts) is kept in this form, and the sync tail works
on words — AND, OR, ANDN and popcount — instead of one byte per node.

Two layouts of the unpacked bits appear here: node order (`unpack`,
`pack`: the trailing axis is the node axis) and bit-major order
(`spread`, `pack_bit_major`, `bit_major`, `node_order`: a trailing
(32, W) pair whose element [k, w] is node 32w + k). The second keeps the
word axis minor, which is how a TPU wants a long axis laid out; the sync
tail uses it wherever it lays a per-node array against a plane.

`map_slots` runs a per-slot-chunk function over a leading slot axis in a
loop, so a program that must widen planes to one value per node holds
that widening for one chunk of slots at a time.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

WORD = 32
# per-chunk element budget of `slot_chunk`: a chunk of slots widened to one
# value per node of a city tree (5.1M nodes) stays at a few hundred MB
CHUNK_ELEMS = 1 << 25

_SHIFTS = jnp.arange(WORD, dtype=jnp.uint32)


def words(n: int) -> int:
    """Words of a plane over `n` nodes."""
    return -(-int(n) // WORD)


def pack(bits: jax.Array) -> jax.Array:
    """(..., n) bool → (..., words(n)) uint32, node order."""
    n = bits.shape[-1]
    w = words(n)
    pad = [(0, 0)] * (bits.ndim - 1) + [(0, w * WORD - n)]
    b = jnp.pad(bits.astype(jnp.uint32), pad).reshape(
        bits.shape[:-1] + (w, WORD))
    return (b << _SHIFTS).sum(axis=-1, dtype=jnp.uint32)


def unpack(planes: jax.Array, n=None) -> jax.Array:
    """(..., W) uint32 → (..., n) bool in node order (n defaults to 32·W)."""
    w = planes.shape[-1]
    bits = ((planes[..., None] >> _SHIFTS) & 1).astype(bool)
    bits = bits.reshape(planes.shape[:-1] + (w * WORD,))
    return bits if n is None else bits[..., :n]


def count(planes: jax.Array) -> jax.Array:
    """Set bits of each plane: (..., W) uint32 → (...,) int32."""
    return jax.lax.population_count(planes).astype(jnp.int32).sum(axis=-1)


def spread(planes: jax.Array) -> jax.Array:
    """(..., W) uint32 → (..., 32, W) bool, bit-major."""
    return ((planes[..., None, :] >> _SHIFTS[:, None]) & 1).astype(bool)


def pack_bit_major(bits: jax.Array) -> jax.Array:
    """(..., 32, W) bool, bit-major → (..., W) uint32."""
    return (bits.astype(jnp.uint32) << _SHIFTS[:, None]).sum(
        axis=-2, dtype=jnp.uint32)


def bit_major(x: jax.Array) -> jax.Array:
    """A per-node array (..., 32·W) in node order → (..., 32, W)."""
    w = x.shape[-1] // WORD
    return x.reshape(x.shape[:-1] + (w, WORD)).swapaxes(-1, -2)


def node_order(x: jax.Array) -> jax.Array:
    """(..., 32, W) bit-major → (..., 32·W) in node order."""
    return x.swapaxes(-1, -2).reshape(x.shape[:-2] + (-1,))


def bit_at(planes: jax.Array, idx: jax.Array) -> jax.Array:
    """The bits of nodes `idx` ((k,) int32, each in range) in every plane:
    (..., W) → (..., k) bool."""
    word = jnp.take(planes, idx >> 5, axis=-1)
    return ((word >> (idx & 31).astype(jnp.uint32)) & 1).astype(bool)


def rows(mask: jax.Array, ndim: int) -> jax.Array:
    """A (C,) bool slot mask as all-ones / all-zero words, broadcastable
    against (C, ...) planes of `ndim` dimensions."""
    full = jnp.where(mask, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return full.reshape(mask.shape + (1,) * (ndim - 1))


def join(prefix: jax.Array, segments: jax.Array, seg_bits: int) -> jax.Array:
    """One plane of `prefix` (..., T) bool followed by Ns packed segments
    (..., Ns, words(seg_bits)) of `seg_bits` nodes each: the tree's global
    node order (top-tree nodes, then slab after slab).

    When a segment fills whole words (the city trees pad slabs to a
    multiple of 128) the segments are one contiguous bit stream, and the
    plane is that stream shifted by T mod 32 bits, word by word. Otherwise
    the segments are widened to bools and packed again."""
    t = prefix.shape[-1]
    ns, sw = segments.shape[-2:]
    lead = prefix.shape[:-1]
    if seg_bits % WORD:
        bits = unpack(segments, seg_bits).reshape(lead + (ns * seg_bits,))
        return pack(jnp.concatenate([prefix, bits], axis=-1))
    stream = segments.reshape(lead + (ns * sw,))
    head = pack(prefix)
    q, off = divmod(t, WORD)
    if off == 0:
        return jnp.concatenate([head, stream], axis=-1)
    zero = jnp.zeros(lead + (1,), jnp.uint32)
    hi = jnp.concatenate([stream << off, zero], axis=-1)
    lo = jnp.concatenate([zero, stream >> (WORD - off)], axis=-1)
    shifted = hi | lo
    first = shifted[..., :1] | head[..., q:q + 1]
    return jnp.concatenate([head[..., :q], first, shifted[..., 1:]], axis=-1)


def slot_chunk(slots: int, row_elems: int) -> int:
    """Slots per chunk for `map_slots`: the largest power of two dividing
    `slots` whose chunk holds at most `CHUNK_ELEMS` elements of
    `row_elems` each (at least one slot)."""
    k = 1
    while slots % (2 * k) == 0 and 2 * k * row_elems <= CHUNK_ELEMS:
        k *= 2
    return k


def map_slots(fn: Callable, xs, chunk: int, init=None):
    """`fn(carry, xs_chunk) -> (carry, ys_chunk)` over consecutive chunks of
    `chunk` slots of the leading slot axis of every leaf of `xs`, in a
    loop; returns (carry, ys). The chunks are read with dynamic slices and
    ys written with dynamic updates, so nothing is transposed or copied
    whole. One chunk covering every slot runs `fn` once, unlooped."""
    b = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if chunk >= b:
        return fn(init, xs)

    def take(i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk), xs)

    _, y_shapes = jax.eval_shape(
        fn, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), init),
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((chunk,) + a.shape[1:], a.dtype),
            xs))
    ys = jax.tree_util.tree_map(
        lambda s: jnp.zeros((b,) + s.shape[1:], s.dtype), y_shapes)

    def body(i, state):
        carry, bufs = state
        carry, y = fn(carry, take(i))
        return carry, jax.tree_util.tree_map(
            lambda a, v: jax.lax.dynamic_update_slice_in_dim(a, v, i * chunk,
                                                             0), bufs, y)

    return jax.lax.fori_loop(0, b // chunk, body, (init, ys))
