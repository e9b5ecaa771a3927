"""Table update: device ms per window tick of the compaction of every served
client's cut into its render queue (`_batched_cut_gids`) alone."""

PATTERNS = [r"_batched_cut_gids"]


def read(rec):
    return rec.program_ms_per_tick(PATTERNS)
