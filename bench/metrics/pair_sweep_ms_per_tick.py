"""Pair sweep: device ms per window tick of the pooled pair-sweep program,
whichever implementation (XLA or the Pallas kernel) runs inside it."""

PATTERNS = [r"_pooled_pair_sweep"]


def read(rec):
    return rec.program_ms_per_tick(PATTERNS)
