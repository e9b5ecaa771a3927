"""The cell benchmark of the fleet LoD service: set-up, the measured window,
the correctness check and the result line.

One command serves every cell:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` names the cell's configuration (`bench/configs/*.json`),
its traffic mix (`bench/traffic/<mix>.json`) and the per-layer metrics,
each read by `bench/metrics/<metric>.py`; nothing here is specific to one
cell.

The window drives the served path: `DeadlineScheduler.tick()` over a
`LodService` in pooled mode. Before each tick the harness feeds every pose
that has come due through `observe_motion(t=due)`; each served client's
update is timed from the due time of its oldest unserved pose to the
moment the tick's stats and Δ batch are ready on the device. The window
starts ticks for `--seconds` seconds and closes when the last of them is
complete; rates are over that whole time.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import reference, scene as scene_mod, traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ID_BYTES = 4            # a plain 32-bit id per streamed row
PAGE_HEADER_BYTES = 16  # page rank, row count, first id, checksum
ROWS_PER_CHECK = 1024   # decoded rows compared per checked update


class BenchError(RuntimeError):
    """The run cannot measure (no accelerator, a bad spec)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_spec(root: pathlib.Path) -> dict:
    path = pathlib.Path(root) / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str):
    """(workload entry, config entry) of a cell named in the spec."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def load_config(root: pathlib.Path, entry: dict) -> dict:
    with open(pathlib.Path(root) / entry["file"]) as f:
        cfg = json.load(f)
    if int(cfg["chips"]) != int(cfg["mesh_clients"]) * int(cfg["mesh_slabs"]):
        raise BenchError(f"{entry['file']}: mesh does not cover its chips")
    return cfg


def metric_reader(root: pathlib.Path, name: str):
    """The `read(record)` function of `bench/metrics/<name>.py`."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, workload: str, kind: str) -> List[dict]:
    """The `end_to_end` or `per_layer` metrics this cell reports."""
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# device side helpers
# ---------------------------------------------------------------------------


def device_gate(chips: int):
    """The devices to run on: JAX's first device must be a TPU, and there
    must be `chips` of them. Otherwise nothing is measured."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"{chips} chips asked for, {len(devices)} present")
    return devices[:chips]


@functools.lru_cache(maxsize=None)
def _jitted():
    """The harness's own device programs, built on first use."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("page_size",))
    def stream_bytes(ref_mask, row_page, row_bytes, page_size):
        """(B,) bytes each client pulls: every page it takes a row from,
        whole (rows x (row + id) bytes + page header). Page p holds the
        shipped rows of priority ranks [p*page_size, (p+1)*page_size), so
        ordering the columns by page lays each page out contiguously."""
        b, u = ref_mask.shape
        n_pages = -(-u // page_size)
        order = jnp.argsort(jnp.where(row_page >= 0, row_page, n_pages),
                            stable=True)
        hit = jnp.pad(ref_mask[:, order],
                      ((0, 0), (0, n_pages * page_size - u)))
        hit = hit.reshape(b, n_pages, page_size).any(axis=2)
        shipped = (row_page >= 0).sum()
        rows = jnp.clip(shipped - jnp.arange(n_pages) * page_size, 0,
                        page_size)
        size = rows * row_bytes + jnp.where(rows > 0, PAGE_HEADER_BYTES, 0)
        return jnp.where(hit, size[None, :], 0).sum(axis=1)

    @functools.partial(jax.jit, static_argnames=("rows",))
    def capture(cut_gids, batch, slot, key, rows):
        """One client's cut, its ids over the stream (-1 where it takes no
        row), and the stream's payload, ids and reference mask cut down to
        `rows` positions: those of rows it takes first, in an order drawn
        from `key`."""
        ids = jnp.where(batch.ref_mask[slot], batch.union_gids, -1)
        score = jnp.where(ids >= 0, jax.random.uniform(key, ids.shape), 2.0)
        pick = jnp.argsort(score)[:rows]
        return cut_gids[slot], ids, {
            "payload": jax.tree.map(lambda x: x[pick], batch.payload),
            "union_gids": batch.union_gids[pick],
            "ref_mask": batch.ref_mask[:, pick]}

    @jax.jit
    def take_rows(rows):
        return {"mu": rows.mu, "log_scale": rows.log_scale,
                "quat": rows.quat, "opacity": rows.opacity,
                "dc": rows.sh[:, 0, :]}

    return stream_bytes, capture, take_rows


def _row_bytes(payload, codebook_rows: int) -> int:
    """Wire bytes of one encoded row plus its id: every attribute at its
    stored width, except the VQ index, which takes the bytes its codebook
    needs."""
    total = ID_BYTES + max(1, int(np.ceil(np.log2(max(codebook_rows, 2))
                                          / 8)))
    for name, leaf in vars(payload).items():
        if name != "code":
            total += int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
    return total


class CompileCounter:
    """Counts the executables JAX builds or loads while it is installed."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.names: List[str] = []

    def __call__(self, event, duration, fun_name="", **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += float(duration)
            self.names.append(str(fun_name))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Tick:
    start: float
    done: float
    served: int
    latencies_ms: List[float]
    stale_pairs: int
    in_window: bool
    stream_bytes: float = 0.0   # filled in once the window has closed


@dataclasses.dataclass
class Record:
    """What the metric readers read: counters per tick, the trace, and the
    run's device facts."""

    ticks: List[Tick]
    compiles_in_window: int
    memory_peak_bytes: int
    slab_width: int
    device_kind: str
    root: pathlib.Path
    trace: Optional[object] = None   # bench.trace.Trace of the window

    @property
    def window_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.in_window]

    def program_ms_per_tick(self, patterns) -> Optional[float]:
        """Device ms per window tick of the programs whose names match any
        of `patterns`; None when there is no trace or nothing matched."""
        if self.trace is None or not self.window_ticks:
            return None
        ns = self.trace.program_ns(patterns)
        if ns is None:
            return None
        return ns / 1e6 / len(self.window_ticks)


class Run:
    """One run of one cell. `gate=False` skips the look for a TPU (the
    tests drive the rest of a run on the CPU); `cache=False` leaves JAX's
    persistent compilation cache and the scene cache off."""

    def __init__(self, root, spec, workload: str, seed: int, seconds: float,
                 trace: bool = False, control: bool = False,
                 gate: bool = True, cache: bool = True,
                 t_process: Optional[float] = None):
        self.root = pathlib.Path(root)
        self.spec = spec
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.gate = gate
        self.cache = cache
        self.t_process = (time.perf_counter() if t_process is None
                          else t_process)
        self.cell, self.config_entry = find_cell(spec, workload)
        self.cfg = load_config(self.root, self.config_entry)
        self.mix = traffic.load_mix(traffic.mix_path(self.root,
                                                     self.cell["traffic"]))
        with open(self.root / "bench" / "checks.json") as f:
            self.limits = {k: float(v["limit"])
                           for k, v in json.load(f).items()}
        self.clock = time.perf_counter

    # -- set-up ---------------------------------------------------------------

    def _devices(self):
        import jax
        if self.gate:
            return device_gate(int(self.cfg["chips"]))
        devices = jax.devices()
        if len(devices) < int(self.cfg["chips"]):
            raise BenchError(f"{self.cfg['chips']} devices asked for, "
                             f"{len(devices)} present")
        return devices[:int(self.cfg["chips"])]

    def _service(self, tree):
        from repro.core.pipeline import SessionConfig
        from repro.launch.mesh import make_fleet_mesh
        from repro.serve.lod_service import LodService
        from repro.serve.scheduler import CostModel, DeadlineScheduler
        cfg = self.cfg
        mesh = None
        if int(cfg["chips"]) > 1:
            mesh = make_fleet_mesh(clients=int(cfg["mesh_clients"]),
                                   slabs=int(cfg["mesh_slabs"]))
        first = self.waves[0]
        svc = LodService(
            tree, SessionConfig(tau=float(cfg["tau_px"]),
                                cut_budget=int(cfg["cut_budget"]),
                                w_star=int(cfg["w_star"])),
            len(first), float(cfg["focal_px"]), mode="pooled",
            capacity=int(cfg["fleet_slots"]), mesh=mesh,
            bandwidth=[self._tier(i) for i in first])
        # the admission gate is not what these cells measure: a cost model
        # seeded at zero admits every wave of set-up, which ends before the
        # model refits from measured ticks
        sched = DeadlineScheduler(
            svc, default_deadline_ms=float(cfg["deadline_ms"]),
            cost_model=CostModel(alpha_ms=0.0, beta_ms=0.0),
            clock=self.clock)
        self.cid_of = {int(i): cid for i, cid in zip(first, svc.active_ids)}
        return svc, sched

    def _tier(self, client: int) -> str:
        tiers = self.cfg["tiers"]
        return tiers[int(client) % len(tiers)]

    # -- one tick -------------------------------------------------------------

    def _ingest(self, stream, pending: Dict[int, float]) -> None:
        now = self.clock()
        for c, due, pose in stream.due(now):
            cid = self.cid_of.get(c)
            if cid is None:
                continue
            self.sched.observe_motion(cid, pose, t=due)
            self.last_pose[c] = pose
            pending.setdefault(c, due)

    def _tick(self, pending: Dict[int, float], in_window: bool):
        import jax
        start = self.clock()
        stats = self.sched.tick()
        batch = self.svc.last_delta
        jax.block_until_ready((stats, batch))
        done = self.clock()
        host = jax.device_get(stats)
        # the clients the scheduler stamped are those it served; any other
        # keeps its oldest unserved pose for a later tick
        stamped = np.asarray(host.mtp_ms) > 0
        served = [c for c in sorted(pending)
                  if stamped[self.svc._slot_of(self.cid_of[c])]]
        lat = [(done - pending.pop(c)) * 1e3 for c in served]
        slots = [self.svc._slot_of(self.cid_of[c]) for c in served]
        stream_bytes = _jitted()[0]
        if self.row_bytes is None:
            self.row_bytes = _row_bytes(
                batch.payload, int(self.svc.codec.codebook.shape[0]))
        width = int(batch.union_gids.shape[0])
        stale = int(np.asarray(host.resweeps).sum())
        self._say(f"tick {self.tick_index} "
                  f"{'window' if in_window else 'set-up'}"
                  f": {(done - start) * 1e3:.1f} ms, {len(served)} served, "
                  f"{stale} stale pairs, "
                  f"union {int(batch.n_union)} in width {width}")
        tick = Tick(start=start, done=done, served=len(served),
                    latencies_ms=lat, stale_pairs=stale, in_window=in_window)
        # the bytes are read once the window has closed
        self.bytes_due.append((tick, slots, stream_bytes(
            batch.ref_mask, batch.row_page, self.row_bytes,
            min(int(self.svc.page_size), width))))
        return tick, served, host

    def _step(self, stream, pending, ticks, profiler=None,
              in_window: bool = False) -> None:
        """Ingest, tick, capture: one turn of the loop."""
        with _span(profiler, "bench.ingest"):
            self._ingest(stream, pending)
        with _span(profiler, "bench.tick"):
            tick, served, host = self._tick(pending, in_window)
        ticks.append(tick)
        with _span(profiler, "bench.check"):
            self._capture(served, host, in_window)
        self.tick_index += 1

    def _capture(self, served, host, in_window: bool) -> None:
        """Device copies of what the checked clients got this tick: the cut
        the service holds and `ROWS_PER_CHECK` of the Δ rows they decode
        from the shared stream (`delta_path.decode_client`). Nothing is
        read back here: the copies are only dispatched, and the host reads
        them once the window has closed (`_collect`). Watched clients are
        captured on every sync since they joined, with every id they take;
        in the window, `check_per_tick` more are drawn from the seed."""
        import jax
        import jax.numpy as jnp
        from repro.serve import delta_path
        _, capture, take_rows = _jitted()
        rng = traffic.stream_rng(self.seed, traffic.STREAM_CHECK,
                                 self.tick_index)
        pool = [c for c in served if c not in self.watched]
        k = min(int(self.mix["check_per_tick"]), len(pool)) if in_window \
            else 0
        sampled = set(int(c) for c in rng.choice(pool, size=k,
                                                 replace=False)) if k else set()
        for c in served:
            if c not in self.watched and c not in sampled:
                continue
            cid = self.cid_of[c]
            slot = self.svc._slot_of(cid)
            cut, ids, sub = capture(
                self.svc.state.cut_gids, self.svc.last_delta,
                np.int32(slot), jax.random.key(int(rng.integers(2 ** 31))),
                rows=ROWS_PER_CHECK)
            row_ids, rows = delta_path.decode_client(
                self.svc.codec, dataclasses.replace(self.svc.last_delta,
                                                    **sub),
                self.sh_k, jnp.int32(slot))
            _, _, scale = self.svc.client_bandwidth(cid)
            self.captured.append(dict(
                client=c, tick=self.tick_index,
                cam=np.asarray(self.last_pose[c], np.float64),
                tau=float(np.float32(self.svc.client_tau(cid))
                          * np.float32(scale)),
                owed=int(np.asarray(host.delta_deferred)[slot]),
                checked=in_window, watched=c in self.watched,
                cut=cut, ids=ids if c in self.watched else None,
                row_ids=row_ids, rows=take_rows(rows)))

    def _collect(self) -> None:
        """Read back what the window dispatched: each tick's downlink bytes
        and the captured updates, into `updates` and `histories`."""
        import jax
        for tick, slots, per_client in self.bytes_due:
            tick.stream_bytes = float(np.asarray(per_client)[slots].sum())
        self.bytes_due.clear()
        for cap in jax.device_get(self.captured):
            row_ids = np.asarray(cap["row_ids"])
            on = row_ids >= 0
            cut = np.asarray(cap["cut"])
            u = reference.Update(
                client=cap["client"], tick=cap["tick"], cam=cap["cam"],
                tau=cap["tau"], cut=cut,
                delivered=(np.asarray(cap["ids"]) if cap["watched"]
                           else row_ids),
                rows={k: np.asarray(v, np.float64)[on]
                      for k, v in cap["rows"].items()},
                row_ids=row_ids[on], owed=cap["owed"],
                checked=cap["checked"])
            if cap["watched"]:
                self.histories[cap["client"]].append(u)
            if cap["checked"]:
                self.updates.append(u)
        self.captured.clear()

    # -- the whole run --------------------------------------------------------

    def execute(self) -> dict:
        import jax
        devices = self._devices()
        if self.cache:
            from repro.launch.compile_cache import enable_compilation_cache
            enable_compilation_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
        cfg = self.cfg
        host_tree, info = scene_mod.load(self.root, self.config_entry["name"],
                                         cfg, cache=self.cache)
        self._say(f"scene: {info['leaves']} leaves, n_pad "
                  f"{host_tree.meta.T + host_tree.meta.Ns * host_tree.meta.S}"
                  f", Ns {host_tree.meta.Ns}, S {host_tree.meta.S}")
        self.scene = scene_mod.reference_scene(host_tree)
        tree = scene_mod.to_device(host_tree)
        del host_tree
        self.sh_k = int(tree.gaussians.sh.shape[1])
        n = int(cfg["fleet_slots"])
        self.layout = traffic.Layout(self.mix, n, info["extent"],
                                     float(cfg["eye_height_m"]), self.seed)
        self.waves = self.layout.waves(int(cfg["wave"]))
        self.svc, self.sched = self._service(tree)
        self.row_bytes = None    # read off the first tick's payload
        wrng = traffic.stream_rng(self.seed, traffic.STREAM_WATCH)
        self.watched = set(int(c) for c in wrng.choice(
            n, size=min(int(self.mix["watched"]), n), replace=False))
        self.histories = {c: [] for c in self.watched}
        self.updates: List[reference.Update] = []
        self.captured: List[dict] = []   # device copies, read after the window
        self.bytes_due: List[tuple] = []
        self.last_pose: Dict[int, np.ndarray] = {}
        self.tick_index = 0
        ticks: List[Tick] = []
        pending: Dict[int, float] = {}

        # fleet come-up in waves, then warm-up; each client's path runs on
        # from its admission through the window
        poses = traffic.PoseStream(self.layout)
        for c in self.waves[0]:
            poses.add(int(c), self.clock())
        for w, wave in enumerate(self.waves):
            if w > 0:
                for c in wave:
                    c = int(c)
                    self.cid_of[c] = self.sched.admit(
                        cam=self.layout.spawn[c], bandwidth=self._tier(c))
                    self.last_pose[c] = self.layout.spawn[c]
                    pending.setdefault(c, self.clock())
                    poses.add(c, self.clock())
            self._step(poses, pending, ticks)
        # warm up until `warmup_ticks` ticks in a row build no executable:
        # every pow2 bucket and stream width the traffic reaches is then
        # compiled, capped at four times as many ticks
        quiet, need = 0, int(self.mix["warmup_ticks"])
        with CompileCounter() as built:
            for _ in range(4 * need):
                before = built.count
                self._step(poses, pending, ticks)
                quiet = quiet + 1 if built.count == before else 0
                if quiet >= need:
                    break

        self._say(f"fleet up and warm after {len(ticks)} ticks")

        # the measured window, on its own stream
        trace_dir = self.root / "bench" / ".trace" / self.workload
        profiler = None
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
            profiler = jax.profiler
        raised = 0
        with CompileCounter() as compiles:
            t0 = self.clock()
            setup_s = t0 - self.t_process
            t_end = t0 + self.seconds
            with _span(profiler, "bench.window"):
                while self.clock() < t_end:
                    try:
                        self._step(poses, pending, ticks, profiler,
                                   in_window=True)
                    except Exception:  # a failed tick fails its updates
                        import traceback
                        traceback.print_exc()
                        raised += max(len(pending), 1)
                        break
        if profiler is not None:
            profiler.stop_trace()
        if compiles.names:
            print(f"compiled in the window: {', '.join(compiles.names)}",
                  file=sys.stderr, flush=True)
        window = [t for t in ticks if t.in_window]
        window_s = (window[-1].done - t0) if window else self.seconds
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        self._collect()

        # the program's state is freed before the reference runs
        tree_meta = tree.meta
        del self.svc, self.sched, tree
        gc.collect()
        t_ref = self.clock()
        readings = reference.compare(
            self.scene, self.updates, self.histories,
            focal=float(cfg["focal_px"]), cut_budget=int(cfg["cut_budget"]),
            w_star=int(cfg["w_star"]), limits=self.limits)
        self._say(f"reference: {readings.updates_checked} updates, "
                  f"{len(self.histories)} histories checked in "
                  f"{self.clock() - t_ref:.1f} s")
        control = None
        if self.control:
            control = reference.compare(
                self.scene, self.updates, self.histories,
                focal=float(cfg["focal_px"]),
                cut_budget=int(cfg["cut_budget"]),
                w_star=int(cfg["w_star"]), limits=self.limits, control=True)
        correct, failed, checks = verdict(readings, self.limits,
                                          bool(window), raised)
        attempted = sum(t.served for t in window) + raised
        record = Record(ticks=ticks, compiles_in_window=compiles.count,
                        memory_peak_bytes=peak,
                        slab_width=int(tree_meta.S),
                        device_kind=devices[0].device_kind, root=self.root)
        result = {"correct": correct, "attempted": int(attempted),
                  "failed": int(failed)}
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        if self.trace:
            from bench import trace as trace_mod
            record.trace = trace_mod.load(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            metrics = {}
            for m in cell_metrics(self.spec, self.workload, "per_layer"):
                value = metric_reader(self.root, m["name"])(record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = record.trace.busy_s() or 0.0
            device["window_s"] = record.trace.window_s()
            result["breakdown"] = record.trace.breakdown()
        else:
            metrics = self._end_to_end(window, window_s, setup_s)
        result["metrics"] = metrics
        result["device"] = device
        result["window"] = {"ticks": len(window), "seconds": window_s,
                            "compiles": compiles.count,
                            "updates_checked": readings.updates_checked,
                            "rows_checked": readings.rows_checked,
                            "watched": sorted(self.watched)}
        if control is not None:
            # the control takes the program's place in the same verdict
            c_correct, _, c_checks = verdict(control, self.limits,
                                             bool(window), raised,
                                             residency=False)
            result["control"] = {"correct": c_correct, "checks": c_checks}
            self._say(f"control: correct {c_correct}, " + ", ".join(
                f"{k} {v['value']!r}" for k, v in c_checks.items()))
        result["checks"] = checks
        return result

    def _say(self, msg: str) -> None:
        print(f"[{self.clock() - self.t_process:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def _end_to_end(self, window: List[Tick], window_s: float,
                    setup_s: float) -> dict:
        lat = np.array([x for t in window for x in t.latencies_ms])
        updates = sum(t.served for t in window)
        values = {
            "pose_to_update_p50_ms": float(np.percentile(lat, 50))
            if lat.size else None,
            "pose_to_update_p95_ms": float(np.percentile(lat, 95))
            if lat.size else None,
            "updates_per_s": updates / window_s if window_s > 0 else None,
            "downlink_bytes_per_update": (sum(t.stream_bytes for t in window)
                                          / updates if updates else None),
            "setup_s": setup_s,
        }
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell_metrics(self.spec, self.workload, "end_to_end")
                if values.get(m["name"]) is not None}


def verdict(readings, limits: Dict[str, float], ticked: bool, raised: int,
            residency: bool = True):
    """(correct, failed updates, each number compared beside its limit) of
    one set of readings. The control has no store to replay, so it is
    judged without `residency_gap`."""
    names = ["cut_mismatch", "row_gap"] + (["residency_gap"] if residency
                                           else [])
    checks = {k: {"value": getattr(readings, k), "limit": limits[k]}
              for k in names}
    failed = raised + readings.failed_updates
    correct = (ticked and failed == 0 and readings.updates_checked > 0
               and all(v["value"] <= v["limit"] for v in checks.values()))
    return correct, failed, checks


def _span(profiler, name: str):
    """A host span in the profiler's trace (nothing when not tracing)."""
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


def emit(result: dict, out=None, err=None) -> None:
    """The end of a run's output: each number compared beside its limit as
    the last lines of stderr, and the result as the last line of stdout."""
    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)


def main(argv=None, t_process: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also read the control (the reference at the "
                         "next precision down) on the same captures")
    args = ap.parse_args(argv)
    try:
        spec = load_spec(ROOT)
        run = Run(ROOT, spec, args.workload, args.seed, args.seconds,
                  trace=bool(args.trace), control=bool(args.control),
                  t_process=t_process)
        result = run.execute()
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0
