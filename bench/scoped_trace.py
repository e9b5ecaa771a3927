"""The reduction of a profiler trace by what the program itself puts in it:
device time per stage scope, device time in no stage, the device's idle
time inside the program's host spans, and idle gaps labelled by the
innermost span that covers them.

`ScopedTrace` extends `bench.trace.Trace`, whose methods return on the same
events what they return there, with two things the program's tracing
(`repro.serve.tracing`) writes into the trace:

  * the scope path of each device operation: the `op_name` of its HLO
    instruction (`jit(<program>)/<stage>/.../<op>:`), which a TPU trace
    keeps in the `tf_op` stat of the operation's event metadata (seen by
    hand in a TPU v5 lite trace; `jax.profiler.ProfileData` does not
    expose event metadata, so `from_xspace` reads the `.xplane.pb`
    protobuf itself);
  * the program's host spans, `nebula.*`, beside the harness's `bench.*`.

An operation the compiler adds without an `op_name` of the program's own
(a copy, a layout change, loop control, a parameter named after its
argument) takes the scope path that all the program's named operations
share in that run of it: a program wholly in one stage is then wholly in
that stage, as its program-name pattern has it. Device time is attributed
per instant to the innermost operation running (an operation that holds
others, such as a loop, keeps only the time its inner operations leave),
so the stages, the harness's own `bench` scope and the unscoped rest
partition the busy time. Stage names are given by the
caller, as program-name patterns are; nothing here imports the program.
Against a program with no scopes and no spans the new methods return None.

`prune` keeps only the events these methods read, as JSON, and `from_json`
reads them back: the form of a recorded trace kept among the tests.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace

PATH_STAT = "tf_op"       # event-metadata stat holding an op's op_name
SPAN_PREFIXES = ("bench.", "nebula.")
BENCH_SCOPE = "bench"
WINDOW = "bench.window"

Segment = Tuple[float, float, str]    # start, end, the op's scope path


def path_has(path: str, stage: str) -> bool:
    """Whether the `/`-separated scope path holds the stage's parts in a
    row (`lod.pair_sweep/gather` in `jit(f)/lod.pair_sweep/gather/take`)."""
    parts, want = path.split("/"), stage.split("/")
    return any(parts[i:i + len(want)] == want
               for i in range(len(parts) - len(want) + 1))


def innermost(ops: Sequence[Tuple[float, float, str]]) -> List[Segment]:
    """Disjoint segments covering the union of the operations, each
    labelled with the path of the innermost operation running then (the
    one started last among those open); operations of no length add
    nothing."""
    ops = [op for op in ops if op[1] > op[0]]
    points = sorted([(s, 1, i) for i, (s, e, _) in enumerate(ops)]
                    + [(e, 0, i) for i, (s, e, _) in enumerate(ops)])
    open_: List[int] = []
    out: List[Segment] = []
    prev = None
    for t, starts, i in points:
        if open_ and t > prev:
            out.append((prev, t, ops[open_[-1]][2]))
        prev = t
        if starts:
            open_.append(i)
        else:
            open_.remove(i)
    return out


def named(path: str) -> bool:
    """Whether an op_name is the program's own (`jit(<program>)/...`),
    not a parameter's or the compiler's."""
    return path.startswith("jit(")


def inherit(ops: Sequence[Tuple[float, float]], paths: Sequence[str],
            modules: Sequence[Tuple[float, float, str]]) -> List[str]:
    """Each operation's scope path, an unnamed one taking the longest
    path prefix that the named operations of its program run share."""
    out = list(paths)
    runs = sorted(modules)
    order = sorted(range(len(ops)), key=lambda i: ops[i][0])
    m, members = 0, {}
    for i in order:
        s = ops[i][0]
        while m < len(runs) and runs[m][1] <= s:
            m += 1
        if m < len(runs) and runs[m][0] <= s:
            members.setdefault(m, []).append(i)
    for idx in members.values():
        parts = [out[i].split("/") for i in idx if named(out[i])]
        if not parts:
            continue
        common = parts[0]
        for p in parts[1:]:
            n = 0
            while n < min(len(common), len(p)) and common[n] == p[n]:
                n += 1
            common = common[:n]
        for i in idx:
            if not named(out[i]):
                out[i] = "/".join(common)
    return out


@dataclasses.dataclass
class ScopedTrace(trace.Trace):
    """A `Trace` with each operation's scope path (`op_paths`, per device,
    aligned with `ops`) and host spans of both prefixes in `spans`."""

    op_paths: List[List[str]] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        super().__post_init__()
        lo, hi = self.window
        self.segments: List[List[Segment]] = []
        for dev, paths, mods in zip(self.ops, self.op_paths, self.modules):
            paths = inherit(dev, paths, mods)
            segs = innermost([(s, e, p) for (s, e), p in zip(dev, paths)])
            self.segments.append([(max(s, lo), min(e, hi), p)
                                  for s, e, p in segs if e > lo and s < hi])

    def _ns(self, keep) -> Optional[float]:
        """Mean device ns in the window of segments whose path `keep`
        accepts; None when no segment is accepted."""
        total, hit = 0.0, False
        for segs in self.segments:
            for s, e, p in segs:
                if keep(p):
                    hit = True
                    total += e - s
        return total / len(self.segments) if hit else None

    def scope_ns(self, stage: str) -> Optional[float]:
        """Device ns in the window inside scope `stage` (mean over
        devices); None when no operation carries it."""
        return self._ns(lambda p: path_has(p, stage))

    def unscoped_ns(self, stages: Sequence[str]) -> Optional[float]:
        """Device ns in the window in none of `stages` and not in the
        harness's `bench` scope; None when no operation carries a stage
        (a program without scopes: everything would be unscoped)."""
        if all(self.scope_ns(st) is None for st in stages):
            return None
        return self._ns(lambda p: not any(
            path_has(p, st) for st in (*stages, BENCH_SCOPE))) or 0.0

    def idle_in_spans(self, names: Sequence[str]) -> Optional[float]:
        """Device-idle ns inside the host spans `nebula.<name>` for each of
        `names`, clipped to the window (mean over devices); None when the
        trace holds none of them."""
        want = {"nebula." + n for n in names}
        spans = [(s, e) for s, e, n in self.spans if n in want]
        if not spans or not self.busy:
            return None
        lo, hi = self.window
        idle = 0.0
        for s, e in trace.union(spans, lo, hi):
            busy = sum(trace.covered(b, s, e) for b in self.busy)
            idle += (e - s) - busy / len(self.busy)
        return idle

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of device 0 in the window, longest first. Its
        label is the innermost span (`bench.*` or `nebula.*`) that covers
        at least half of it, else the span covering most of it; `bench.`
        is left off the harness's names."""
        if not self.busy:
            return []
        lo, hi = self.window
        gaps, at = [], lo
        for s, e in self.busy[0] + [(hi, hi)]:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        inner = [x for x in self.spans if x[2] != WINDOW]
        out = []
        for s, e in gaps:
            cover = [(min(e, se) - max(s, ss), se - ss, n)
                     for ss, se, n in inner if min(e, se) > max(s, ss)]
            half = [c for c in cover if 2 * c[0] >= e - s]
            if half:
                label = min(half, key=lambda c: c[1])[2]
            elif cover:
                label = max(cover, key=lambda c: c[0])[2]
            else:
                label = "outside spans"
            if label.startswith(trace.SPAN_PREFIX):
                label = label[len(trace.SPAN_PREFIX):]
            out.append((label, (e - s) / 1e9))
        return sorted(out, key=lambda x: -x[1])


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _map(entry: bytes) -> Tuple[int, bytes]:
    kv = dict(_fields(entry))
    return kv.get(1, 0), kv.get(2, b"")


def _plane(raw: bytes):
    """(name, lines, event names, event paths) of one XPlane: each line as
    (name, [(start ns, end ns, metadata id)]), with the names and `tf_op`
    paths of the event metadata by id."""
    name, lines, metas, stat_names = "", [], {}, {}
    for f, v in _fields(raw):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, meta = _map(v)
            metas[k] = meta
        elif f == 5:
            k, meta = _map(v)
            stat_names[k] = dict(_fields(meta)).get(2, b"").decode()
    names, paths = {}, {}
    for k, meta in metas.items():
        for f, v in _fields(meta):
            if f == 2:
                names[k] = v.decode(errors="replace")
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    paths[k] = stat.get(5, b"").decode().rstrip(":")
    out = []
    for raw_line in lines:
        line_name, t0, events = "", 0, []
        for f, v in _fields(raw_line):
            if f == 2:
                line_name = v.decode()
            elif f == 3:
                t0 = v
            elif f == 4:
                ev = dict(_fields(v))
                # whole ns, as `jax.profiler.ProfileData` gives them
                start = float(t0 + ev.get(2, 0) // 1000)
                events.append((start, start + ev.get(3, 0) // 1000,
                               ev.get(1, 0)))
        out.append((line_name, events))
    return name, out, names, paths


def from_xspace(raw: bytes) -> ScopedTrace:
    """Build a `ScopedTrace` from the bytes of an `.xplane.pb` (an XSpace
    protobuf): the same events `bench.trace.from_profile` reads, with each
    operation's scope path beside it."""
    ops, paths, modules, spans = [], [], [], []
    for f, raw_plane in _fields(raw):
        if f != 1:
            continue
        name, lines, names, op_paths = _plane(raw_plane)
        lines = dict(lines) if trace.DEVICE_PLANE.match(name) else lines
        if trace.DEVICE_PLANE.match(name):
            mod_line = lines.get(trace.MODULES_LINE)
            op_line = lines.get(trace.OPS_LINE, mod_line)
            if op_line is None:
                continue
            ops.append([(s, e) for s, e, _ in op_line])
            paths.append([op_paths.get(k, "") for _, _, k in op_line])
            modules.append([(s, e, names.get(k, ""))
                            for s, e, k in mod_line or []])
        elif name.startswith("/host:"):
            for _, events in lines:
                spans += [(s, e, names[k]) for s, e, k in events
                          if names.get(k, "").startswith(SPAN_PREFIXES)]
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    return ScopedTrace(window=windows[0], ops=ops, modules=modules,
                       spans=spans, op_paths=paths)


def load(trace_dir) -> ScopedTrace:
    """The newest `.xplane.pb` under `trace_dir`, reduced."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return from_xspace(files[-1].read_bytes())


def prune(t: ScopedTrace) -> Dict:
    """The events the reduction reads, times in ns relative to the window's
    start and scope paths listed once."""
    lo = t.window[0]
    table: Dict[str, int] = {}

    def rel(x):
        return round(x - lo)

    return {
        "window": [0, rel(t.window[1])],
        "spans": [[rel(s), rel(e), n] for s, e, n in t.spans],
        "devices": [{
            "ops": [[rel(s), rel(e), table.setdefault(p, len(table))]
                    for (s, e), p in zip(dev, paths)],
            "modules": [[rel(s), rel(e), n] for s, e, n in mods],
        } for dev, paths, mods in zip(t.ops, t.op_paths, t.modules)],
        "paths": list(table),
    }


def from_json(d: Dict) -> ScopedTrace:
    """A `ScopedTrace` of events kept by `prune`."""
    paths = d["paths"]
    devs = d["devices"]
    return ScopedTrace(
        window=tuple(d["window"]),
        ops=[[(s, e) for s, e, _ in dev["ops"]] for dev in devs],
        modules=[[tuple(m) for m in dev["modules"]] for dev in devs],
        spans=[tuple(x) for x in d["spans"]],
        op_paths=[[paths[i] for _, _, i in dev["ops"]] for dev in devs])


def write_pruned(trace_dir, out) -> None:
    """Prune the newest trace under `trace_dir` into the JSON file `out`."""
    with open(out, "w") as f:
        json.dump(prune(load(trace_dir)), f, separators=(",", ":"))
