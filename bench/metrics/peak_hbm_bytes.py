"""Device: `peak_bytes_in_use` of the fullest chip after the window."""


def read(rec):
    return float(rec.memory_peak_bytes) if rec.memory_peak_bytes else None
