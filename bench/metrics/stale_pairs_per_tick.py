"""LoD search: stale (client, slab) pairs swept per window tick (the sum of
`ServiceStats.resweeps`)."""


def read(rec):
    ticks = rec.window_ticks
    if not ticks:
        return None
    return sum(t.stale_pairs for t in ticks) / len(ticks)
