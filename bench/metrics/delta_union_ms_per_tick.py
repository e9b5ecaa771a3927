"""Δ union: device ms per window tick of the union mask, its ranking, the
page references and the encode of the shipped rows."""

PATTERNS = [r"_union_mask", r"_rank_union", r"_union_refs", r"^jit_encode$"]


def read(rec):
    return rec.program_ms_per_tick(PATTERNS)
