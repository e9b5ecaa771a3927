"""The one generator of head-pose traffic: an open loop of poses per client.

A traffic mix is a data file under `bench/traffic/<mix>.json`; this module
reads it and nothing else decides what clients do. Each client posts a pose
at the mix's headset rate on its own phase, whatever the service is doing:
the harness feeds every pose that is due before each tick, and the
scheduler keeps the newest one. So the offered load is the fleet size and
its motion, not a request rate.

What clients do is fixed by the mix: the spawn points, plazas and walks
come from the mix's own `walks_seed`, so every `--seed` serves the same
set of walks and does the same work. The run's seed draws the order in
which the clients join, the phase of each client's poses and what the
correctness check samples. Every number comes from
`stream_rng(seed, stream, ...)`, so one seed gives the same inputs whatever
the timing of a run. A client's path starts at its spawn point when it
joins and runs on through set-up and the measured window.

Two motion models, kept here so that a change to the program cannot move
the traffic:

* `waypoint`, a walking user: the random-waypoint model (Johnson & Maltz
  1996; Camp, Boleng & Davies 2002). The client walks in a straight line at
  a fixed speed to a target drawn from its area (the whole city, or around
  its plaza), pauses there for a uniform time, and draws the next target.
  Its eyes stay at the configuration's height plus a vertical head bob at
  the step rate while it walks.
* `teleport`, a copy of the program's `straggler_path`: stationary, then a
  jump to a uniform point of the city at eye height.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Sequence

import numpy as np

CHUNK = 4096  # poses drawn per client at a time

# stream ids of the seeded draws (never reuse one for another purpose)
STREAM_LAYOUT = 0   # walks_seed: spawn points, plazas; seed: order, phases
STREAM_PATHS = 1    # walks_seed: each client's targets, pauses and jumps
STREAM_CHECK = 3    # which updates and rows the correctness check samples
STREAM_WATCH = 4    # which clients the check follows from their admission


def stream_rng(seed: int, *ids: int) -> np.random.Generator:
    """The generator of one stream of draws of one seed (any integer)."""
    return np.random.default_rng([int(seed) % 2**64] + [int(i) for i in ids])


def load_mix(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("pose_hz", "walks_seed", "groups", "check_per_tick",
                "watched", "warmup_ticks"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix has no {key!r}")
    shares = sum(float(g["share"]) for g in mix["groups"])
    if abs(shares - 1.0) > 1e-6:
        raise ValueError(f"{path}: group shares sum to {shares}, not 1")
    return mix


def _group_sizes(groups: Sequence[dict], n: int) -> List[int]:
    """Clients per group: rounded shares, the remainder to the last group."""
    sizes = [int(round(float(g["share"]) * n)) for g in groups[:-1]]
    sizes.append(n - sum(sizes))
    if min(sizes) < 0:
        raise ValueError(f"group shares do not fit {n} clients")
    return sizes


class Layout:
    """Who the clients are: group, spawn point, tier, wave and pose phase.

    `extent` is the scene's (x, y) footprint in metres; clients live at
    `eye_height` above the ground."""

    def __init__(self, mix: dict, n_clients: int, extent, eye_height: float,
                 seed: int):
        self.walks_seed = int(mix["walks_seed"])
        rng = stream_rng(self.walks_seed, STREAM_LAYOUT)
        self.mix = mix
        self.n = int(n_clients)
        self.extent = np.asarray(extent, np.float64)
        self.eye_height = float(eye_height)
        self.group = np.repeat(np.arange(len(mix["groups"])),
                               _group_sizes(mix["groups"], self.n))
        self.spawn = np.zeros((self.n, 3), np.float32)
        # the centre and spread of each client's area (a plaza), or NaN
        # where its area is the whole city
        self.home = np.full((self.n, 2), np.nan)
        self.spread = np.zeros(self.n)
        for gi, g in enumerate(mix["groups"]):
            idx = np.nonzero(self.group == gi)[0]
            self.spawn[idx] = self._spawn(g["spawn"], idx, rng)
        run = stream_rng(seed, STREAM_LAYOUT)
        self.phase = run.random(self.n)          # pose phase, in periods
        self.order = run.permutation(self.n)     # admission order (waves)

    def inside(self, margin: float, n: int, rng) -> np.ndarray:
        lo, hi = margin * self.extent, (1.0 - margin) * self.extent
        return rng.uniform(lo, hi, (n, 2))

    def _spawn(self, spec: dict, idx: np.ndarray, rng) -> np.ndarray:
        kind, n = spec["kind"], len(idx)
        if kind == "uniform":
            xy = self.inside(float(spec["margin"]), n, rng)
        elif kind == "plazas":
            k = int(spec["count"])
            centres = self.inside(float(spec["margin"]), k, rng)
            pop = 1.0 / np.arange(1, k + 1) ** float(spec["zipf"])
            pick = rng.choice(k, size=n, p=pop / pop.sum())
            self.home[idx] = centres[pick]
            self.spread[idx] = float(spec["sigma_m"])
            xy = self.around(self.home[idx], self.spread[idx], rng)
        else:
            raise ValueError(f"unknown spawn kind {kind!r}")
        z = np.full((n, 1), self.eye_height)
        return np.concatenate([xy, z], 1).astype(np.float32)

    def around(self, centre, sigma, rng) -> np.ndarray:
        """Gaussian points around `centre`, clipped to the city."""
        centre = np.asarray(centre, np.float64).reshape(-1, 2)
        xy = centre + rng.normal(0.0, 1.0, centre.shape) \
            * np.asarray(sigma, np.float64).reshape(-1, 1)
        return np.clip(xy, 0.0, self.extent)

    def target(self, client: int, rng) -> np.ndarray:
        """A walking target in the client's area: around its plaza, or a
        uniform point of the city inside its group's margin. Two draws
        either way."""
        if np.isnan(self.home[client, 0]):
            margin = float(self.mix["groups"][self.group[client]]["spawn"]
                           .get("margin", 0.0))
            return self.inside(margin, 1, rng)[0]
        return self.around(self.home[client], self.spread[client], rng)[0]

    def waves(self, size: int) -> List[np.ndarray]:
        """Client indices in admission order, `size` to a wave."""
        return [self.order[i:i + size] for i in range(0, self.n, size)]


class _Path:
    """One client's pose path from its spawn point, drawn lazily in chunks
    from its own stream of the mix's walks."""

    def __init__(self, layout: Layout, client: int):
        self.rng = stream_rng(layout.walks_seed, STREAM_PATHS, client)
        self.motion = layout.mix["groups"][layout.group[client]]["motion"]
        self.hz = float(layout.mix["pose_hz"])
        self.layout = layout
        self.client = client
        self.pos = layout.spawn[client].astype(np.float64)
        self.poses = np.zeros((0, 3), np.float32)
        # waypoint legs: depart, arrive and leave times, start and target
        self.legs = np.zeros((0, 3))
        self.ends = np.zeros((0, 2, 2))
        self.bob_phase = 2.0 * np.pi * self.rng.random()

    def _leg(self) -> None:
        """Append the next leg: walk to a new target, then pause there."""
        m = self.motion
        t0 = self.legs[-1, 2] if len(self.legs) else 0.0
        p0 = self.ends[-1, 1] if len(self.ends) else self.pos[:2]
        p1 = self.layout.target(self.client, self.rng)
        pause = float(m["pause_max_s"]) * self.rng.random()
        t1 = t0 + float(np.linalg.norm(p1 - p0)) / float(m["speed_mps"])
        self.legs = np.concatenate([self.legs, [[t0, t1, t1 + pause]]])
        self.ends = np.concatenate([self.ends, [[p0, p1]]])

    def _waypoint(self, j0: int, n: int) -> np.ndarray:
        # random waypoint at speed_mps with pauses of U[0, pause_max_s];
        # pose j is the position at time j/hz
        m = self.motion
        t = (j0 + np.arange(n)) / self.hz
        while not len(self.legs) or self.legs[-1, 2] <= t[-1]:
            self._leg()
        k = np.searchsorted(self.legs[:, 2], t, side="right")
        t0, t1 = self.legs[k, 0], self.legs[k, 1]
        frac = np.clip((t - t0) / np.maximum(t1 - t0, 1e-9), 0.0, 1.0)
        p0, p1 = self.ends[k, 0], self.ends[k, 1]
        out = np.empty((n, 3))
        out[:, :2] = p0 + frac[:, None] * (p1 - p0)
        walking = t < t1
        out[:, 2] = self.layout.eye_height + walking * float(m["bob_m"]) \
            * np.sin(2.0 * np.pi * float(m["step_hz"]) * t + self.bob_phase)
        return out

    def _teleport(self, n: int) -> np.ndarray:
        # straggler_path: stationary, then a jump to a uniform point of the
        # city at probability 1/(every_s*hz) per pose
        m = self.motion
        jump = self.rng.random(n) < 1.0 / (float(m["every_s"]) * self.hz)
        targets = self.layout.inside(float(m["margin"]), n, self.rng)
        out = np.empty((n, 3))
        out[:, 2] = self.layout.eye_height
        last = np.maximum.accumulate(np.where(jump, np.arange(n), -1))
        out[:, :2] = np.where((last >= 0)[:, None],
                              targets[np.maximum(last, 0)], self.pos[:2])
        return out

    def upto(self, j: int) -> np.ndarray:
        """Poses 0..j (at least), extending the path as needed."""
        while self.poses.shape[0] <= j:
            kind = self.motion["kind"]
            if kind == "waypoint":
                chunk = self._waypoint(self.poses.shape[0], CHUNK)
            elif kind == "teleport":
                chunk = self._teleport(CHUNK)
            else:
                raise ValueError(f"unknown motion kind {kind!r}")
            self.pos = chunk[-1]
            self.poses = np.concatenate([self.poses,
                                         chunk.astype(np.float32)])
        return self.poses


class PoseStream:
    """The poses of the clients that have joined: client i, added at time
    t_i, posts pose j of its path at t_i + (j - phase_i)/hz, so its first
    pose is due at or before t_i. `due(now)` yields (client, due time,
    pose) for every pose that has come due since the last call, in client
    order."""

    def __init__(self, layout: Layout):
        self.layout = layout
        self.hz = float(layout.mix["pose_hz"])
        self.paths: Dict[int, _Path] = {}
        self.start: Dict[int, float] = {}
        self.next: Dict[int, int] = {}

    def add(self, client: int, t: float) -> None:
        """Start client `client`'s poses at time `t` (it joined the fleet)."""
        self.paths[client] = _Path(self.layout, client)
        self.start[client] = float(t)
        self.next[client] = 0

    def due(self, now: float):
        out = []
        for c, path in self.paths.items():
            j0, t0 = self.next[c], self.start[c]
            j1 = int(np.floor((now - t0) * self.hz
                              + self.layout.phase[c])) + 1
            if j1 <= j0:
                continue
            poses = path.upto(j1 - 1)
            for j in range(j0, j1):
                out.append((c, t0 + (j - self.layout.phase[c]) / self.hz,
                            poses[j]))
            self.next[c] = j1
        return out


def mix_path(root: pathlib.Path, name: str) -> pathlib.Path:
    return pathlib.Path(root) / "bench" / "traffic" / f"{name}.json"
