"""Host control plane: ms per window tick in which the device ran nothing
(the tick's host span minus the device-busy time inside it)."""


def read(rec):
    if rec.trace is None:
        return None
    return rec.trace.host_ms_per_span("bench.tick")
