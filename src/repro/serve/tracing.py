"""Tracing of the fleet sync: stage scopes on the device, spans on the host,
and a per-tick account of the work a tick decided.

Three instruments, one module:

  * `scope(stage)` names a device stage inside a jitted program
    (`jax.named_scope`; `scoped(stage)` wraps a whole jitted function in
    it): every operation the stage lowers to carries the
    stage in its `op_name` path (`jit(<program>)/lod.pair_sweep/...`), so a
    profiler trace attributes device time to stages, not to program names.
    A scope reaches only what is traced inside a jitted program; an eager
    operation runs as its own cached executable and stays unscoped.
  * `span(name)` is a host span (`jax.profiler.TraceAnnotation`)
    named `nebula.<name>`. Spans land in the profiler's own trace, on the
    clock of the device planes, so the device's idle gaps can be laid
    against what the host was doing. With no trace running a span costs
    about a microsecond. Every span carries `tick=<index>`, the sync
    counter of the service (`LodService.syncs`), so all spans of one tick
    share an identifier; a span opened without one takes its enclosing
    span's.
  * `Recorder` keeps a bounded per-tick account. Records hold host numbers
    and device arrays that nothing reads during the tick; `drain()` reads
    every device value with one `jax.device_get`.

The spans of one scheduler tick, nested as they run:

    sched.tick
      sched.select
        sched.preview_read        staleness preview read
      svc.sync
        svc.rate_read             last sync's bytes, for rate control
        svc.stale_count_read      stale-pair pool size
        delta.union_size_read     Δ-union size
      sched.wait                  the tick's block and resweep read

`READ_SPANS` are the five whose host time is a blocking device→host read:
while the host waits in one, the device may run out of work.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from collections import deque
from typing import Iterator, List, Optional

import jax

STAGES = (
    # top-tree sweep, staleness test (the scheduler's preview included) and
    # the stale-pair compaction; twin of `top_staleness_ms_per_tick`
    "lod.staleness",
    # the pooled (client, slab) pair sweep, either implementation; twin of
    # `pair_sweep_ms_per_tick`
    "lod.pair_sweep",
    # inside it: the slab-table, parent-expand, camera and τ gathers of the
    # bucket's lanes (no program-name twin: they run in the sweep program)
    "lod.pair_sweep/gather",
    # management-table update, per-client cut ids, the pooled scatter back
    # into the temporal state, first-owner counts; twin of
    # `table_update_ms_per_tick`
    "table.update",
    # union mask, its ranking, the page references and the encode of the
    # shipped rows; twin of `delta_union_ms_per_tick`
    "delta.union",
)

# finer scopes inside two stages; the TPU trace the benchmark's tests keep
# (tests/bench/data/tpu_trace_pruned.json) was recorded before them, so
# they stand apart from STAGES
DETAIL_STAGES = (
    # inside `table.update`: the word-level table update over bit planes,
    # the packing of swept slab cuts, the first-owner counts and the
    # compaction of cut planes into render queues
    "table.update/pack",
    # inside `lod.pair_sweep`: one pass of a bucket swept in lane chunks
    # (buckets wider than `lod_service.SWEEP_CHUNK` only)
    "lod.pair_sweep/chunk",
)

SPAN_PREFIX = "nebula."

READ_SPANS = ("sched.preview_read", "svc.rate_read", "svc.stale_count_read",
              "delta.union_size_read", "sched.wait")

_TICK: contextvars.ContextVar = contextvars.ContextVar("nebula_tick",
                                                       default=None)


def scope(stage: str):
    """`jax.named_scope` of a device stage, for use inside jitted code. A
    child stage (`parent/child`) is opened inside its parent's scope and
    names only its own part."""
    assert stage in STAGES + DETAIL_STAGES, \
        f"unknown stage {stage!r} (see tracing.STAGES)"
    return jax.named_scope(stage.rsplit("/", 1)[-1])


def scoped(stage: str):
    """Decorator: the whole body of a function in `scope(stage)`. Put it
    under `jax.jit`; the program keeps the function's name."""
    scope(stage)   # an unknown stage fails at import, not at first trace

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(stage):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def span(name: str, tick: Optional[int] = None) -> Iterator[None]:
    """Host span `nebula.<name>` in the profiler's trace, carrying the stat
    `tick` (default: the enclosing span's)."""
    tick = _TICK.get() if tick is None else int(tick)
    args = {} if tick is None else {"tick": tick}
    token = _TICK.set(tick)
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args):
            yield
    finally:
        _TICK.reset(token)


class Recorder:
    """Per-tick account of a fleet, bounded to the last `maxlen` ticks.

    The deadline scheduler adds one record per tick it runs
    (`DeadlineScheduler.recorder`): the tick index, the stale-pair pool
    (`n_stale`), the pair lanes its bucket swept (`lanes`: bucket × client
    shards, so `n_stale / lanes` is the bucket's occupancy), why pairs went
    stale (`stale_causes`, a (3,) int32 device array: never swept, parent
    expansion changed, moved at least ρ; `lod_search.stale_causes`),
    and per served client its id, queue wait (`wait_ms`: oldest unserved
    pose to the tick's sync start), service time (`service_ms`: sync start
    to the tick's stats ready) and whether its deadline was `missed`.
    `wait_ms + service_ms` is the client's stamped `ServiceStats.mtp_ms`.

    Nothing is read from the device while a record is added, so keeping the
    account adds no blocking read to a tick; an operator drains it when the
    numbers are wanted."""

    def __init__(self, maxlen: int = 1024):
        self._records: deque = deque(maxlen=int(maxlen))

    def __len__(self) -> int:
        return len(self._records)

    def add(self, **record) -> None:
        self._records.append(record)

    def drain(self) -> List[dict]:
        """Every record, oldest first, device values read to the host in
        one transfer; the recorder is left empty."""
        records = list(self._records)
        self._records.clear()
        return jax.device_get(records)
