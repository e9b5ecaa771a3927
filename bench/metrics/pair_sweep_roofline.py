"""Pair sweep kernel: the least time the chip needs for the sweep's work
over its measured device time, in percent. The work is what the algorithm
needs for the real stale pairs (bench/roofline.py), not the pow2 bucket
the program sweeps, so padding shows as a lower share."""

from bench import roofline

PATTERNS = [r"_pooled_pair_sweep"]


def read(rec):
    if rec.trace is None:
        return None
    ns = rec.trace.program_ns(PATTERNS)
    pairs = sum(t.stale_pairs for t in rec.window_ticks)
    if not ns or not pairs:
        return None
    least_s, _ = roofline.least_time_s(
        roofline.pair_sweep_work(pairs, rec.slab_width),
        roofline.peaks(rec.root, rec.device_kind))
    return 100.0 * least_s / (ns / 1e9)
