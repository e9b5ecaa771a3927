"""Table update: device ms per window tick of the management-table update,
the per-client cut ids and the pooled scatter back into the temporal
state."""

PATTERNS = [r"batched_cloud_sync", r"_batched_cut_gids",
            r"_apply_pooled_updates", r"first_owner_counts"]


def read(rec):
    return rec.program_ms_per_tick(PATTERNS)
