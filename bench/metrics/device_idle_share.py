"""Device: percent of the traced window in which the device ran no
operation (mean over the chips used)."""


def read(rec):
    if rec.trace is None or rec.trace.busy_s() is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s())
