"""LoD search: device ms per window tick of the top sweep, the staleness
test (the scheduler's preview included) and the stale-pair compaction."""

PATTERNS = [r"batched_top_and_staleness", r"predicted_stale_counts",
            r"_compact_stale_pairs", r"_shard_stale_counts"]


def read(rec):
    return rec.program_ms_per_tick(PATTERNS)
