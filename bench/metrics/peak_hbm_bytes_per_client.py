"""Device: `peak_bytes_in_use` of the fullest chip after the window over the
mean number of clients served per window tick — what one served client
costs the chip."""


def read(rec):
    ticks = rec.window_ticks
    served = sum(t.served for t in ticks)
    if not rec.memory_peak_bytes or not served:
        return None
    return float(rec.memory_peak_bytes) * len(ticks) / served
