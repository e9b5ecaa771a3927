"""Reduction of a profiler trace of the measured window to device busy and
idle time, per-program device time, host self time and the breakdown.

It reads the `.xplane.pb` that `jax.profiler` writes, with nothing but
`jax.profiler.ProfileData`. Device planes are `/device:TPU:<i>`; their
`XLA Ops` line holds one event per operation run on the chip, and their
`XLA Modules` line one per program (a jitted function's name, such as
`jit__pooled_pair_sweep(12)`). The harness's own spans (`bench.window`,
`bench.ingest`, `bench.tick`, `bench.check`) are host events of the same
trace, on the same clock.

Only time inside the `bench.window` span counts. Busy time is the union
of the operation intervals of a device; with several devices it is their
mean. An idle gap is a stretch of the window in which a device runs
nothing; it is labelled by the harness span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """A program's name without the run-specific id JAX appends."""
    return _PROGRAM_ID.sub("", event_name.strip())


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted, merged intervals clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclasses.dataclass
class Trace:
    """Events of one traced window, times in ns on one clock."""

    window: Interval
    ops: List[List[Interval]]                 # per device
    modules: List[List[Tuple[float, float, str]]]  # per device
    spans: List[Tuple[float, float, str]]     # harness spans

    def __post_init__(self):
        lo, hi = self.window
        self.busy = [union(d, lo, hi) for d in self.ops]

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> Optional[float]:
        """Device-busy seconds in the window, the mean over devices (None
        when the trace holds no device)."""
        if not self.busy:
            return None
        return sum(covered(b, *self.window) for b in self.busy) \
            / len(self.busy) / 1e9

    def program_ns(self, patterns: Sequence[str]) -> Optional[float]:
        """Device ns in the window of programs whose name, without the id
        JAX appends, matches any of the regular expressions `patterns`
        (mean over devices); None when none matched."""
        regs = [re.compile(p) for p in patterns]
        lo, hi = self.window
        total, hit = 0.0, False
        for dev in self.modules:
            for s, e, name in dev:
                if any(r.search(program_name(name)) for r in regs):
                    hit = True
                    total += max(0.0, min(e, hi) - max(s, lo))
        if not hit:
            return None
        return total / max(len(self.modules), 1)

    def spans_named(self, name: str) -> List[Interval]:
        return [(s, e) for s, e, n in self.spans if n == name]

    def host_ms_per_span(self, name: str) -> Optional[float]:
        """Mean ms per span `name` in which the devices ran nothing."""
        spans = self.spans_named(name)
        if not spans or not self.busy:
            return None
        idle = 0.0
        for s, e in spans:
            busy = sum(covered(b, s, e) for b in self.busy) / len(self.busy)
            idle += (e - s) - busy
        return idle / len(spans) / 1e6

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of device 0 in the window, longest first, labelled
        by the harness span covering most of it."""
        if not self.busy:
            return []
        lo, hi = self.window
        gaps, at = [], lo
        for s, e in self.busy[0] + [(hi, hi)]:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        inner = [(s, e, n) for s, e, n in self.spans if n != "bench.window"]
        out = []
        for s, e in gaps:
            best, label = 0.0, "outside spans"
            for ss, se, n in inner:
                cover = min(e, se) - max(s, ss)
                if cover > best:
                    best, label = cover, n[len(SPAN_PREFIX):]
            out.append((label, (e - s) / 1e9))
        return sorted(out, key=lambda x: -x[1])

    def program_totals(self) -> Dict[str, float]:
        """Device seconds in the window per program (mean over devices)."""
        lo, hi = self.window
        totals: Dict[str, float] = {}
        for dev in self.modules:
            for s, e, name in dev:
                key = program_name(name)
                totals[key] = totals.get(key, 0.0) + max(
                    0.0, min(e, hi) - max(s, lo)) / 1e9
        n = max(len(self.modules), 1)
        return {k: v / n for k, v in totals.items()}

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.program_totals().items(), key=lambda x: -x[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:top]]}


def from_profile(pd) -> Trace:
    """Build a `Trace` from a `jax.profiler.ProfileData`."""
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mod_line = lines.get(MODULES_LINE)
            op_line = lines.get(OPS_LINE, mod_line)
            if op_line is None:
                continue
            ops.append([(e.start_ns, e.start_ns + e.duration_ns)
                        for e in op_line.events])
            modules.append([] if mod_line is None else
                           [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in mod_line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    windows = [(s, e) for s, e, n in spans if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    return Trace(window=windows[0], ops=ops, modules=modules, spans=spans)


def load(trace_dir) -> Trace:
    """The newest `.xplane.pb` under `trace_dir`, reduced."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))
