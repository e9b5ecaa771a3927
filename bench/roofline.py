"""The operations and bytes the pair sweep's algorithm needs, and the least
time a chip could take for them.

One stale (client, slab) pair sweeps the S nodes of its slab at the
client's camera: it reads each node's position (3 x f32), size (f32), one
topology word (i32: parent or subtree end) and its leaf and padding flags
(1 byte each), and writes the node's cut bit (1 byte); per pair it reads
the camera (3 x f32), τ (f32) and the root's parent-expand bit, and writes
the root's expand bit and the reuse radius ρ (f32). Per node it computes
the squared distance (3 subtractions, 3 multiplies, 2 adds), the LoD test
(3 multiplies, a max, a compare) and its share of ρ (a square root, a
multiply, a divide, a subtraction, an absolute value and a min): 17
operations. Counted on the real stale pairs, never the padded bucket.
"""

from __future__ import annotations

import json
import pathlib
from typing import Tuple

NODE_READ_BYTES = 12 + 4 + 4 + 1 + 1
NODE_WRITE_BYTES = 1
PAIR_BYTES = 12 + 4 + 1 + 1 + 4
NODE_OPS = 17


def pair_sweep_work(pairs: int, slab_width: int) -> Tuple[float, float]:
    """(operations, bytes) of sweeping `pairs` stale pairs of S nodes."""
    nodes = float(pairs) * float(slab_width)
    ops = nodes * NODE_OPS
    nbytes = nodes * (NODE_READ_BYTES + NODE_WRITE_BYTES) \
        + float(pairs) * PAIR_BYTES
    return ops, nbytes


def peaks(root, device_kind: str) -> dict:
    """The chip's published peaks; a device not in `bench/peaks.json` is an
    error, never a default."""
    with open(pathlib.Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def least_time_s(work: Tuple[float, float], peak: dict) -> Tuple[float, str]:
    """(seconds, binding bound): the larger of operations over peak
    operation rate and bytes over peak memory bandwidth."""
    ops, nbytes = work
    compute = ops / float(peak["bf16_flops_per_s"])
    memory = nbytes / float(peak["hbm_bytes_per_s"])
    return (memory, "hbm") if memory >= compute else (compute, "compute")
