"""The scene of a configuration: the procedural city and its LoD tree,
built once per checkout and then loaded from a cache file.

The city is fixed by the configuration, like a deployment's asset, so the
first run of a configuration in a checkout builds it with the program's
offline build functions (`generate_city`, `build_lod_tree`) and pickles the host
arrays to `bench/.scene_cache/<config>-<key>.pkl`; later runs load that
file. The key hashes the scene's settings and the source of the two
build functions, so a change to either builds afresh. The file is written under a
fixed temporary name and renamed into place, so a run that is cut never
leaves a half-written cache behind.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import pathlib
import pickle

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

SCENE_KEYS = ("city_blocks", "leaf_density", "sh_degree", "scene_seed",
              "target_subtrees", "slab_pad_to")


def _key(cfg: dict) -> str:
    from repro.core import gaussians, lod_tree
    h = hashlib.sha256(json.dumps({k: cfg[k] for k in SCENE_KEYS},
                                  sort_keys=True).encode())
    for mod in (gaussians, lod_tree):
        h.update(inspect.getsource(mod).encode())
    return h.hexdigest()[:16]


def _build(cfg: dict):
    from repro.core.gaussians import CityConfig, generate_city
    from repro.core.lod_tree import build_lod_tree
    blocks = int(cfg["city_blocks"])
    city = CityConfig(blocks_x=blocks, blocks_y=blocks,
                      leaf_density=float(cfg["leaf_density"]),
                      sh_degree=int(cfg["sh_degree"]),
                      seed=int(cfg["scene_seed"]))
    leaves = generate_city(city)
    tree = build_lod_tree(leaves, target_subtrees=int(cfg["target_subtrees"]),
                          slab_pad_to=int(cfg["slab_pad_to"]),
                          seed=int(cfg["scene_seed"]))
    host = jax.tree_util.tree_map(np.asarray, tree)
    return host, {"leaves": int(leaves.n), "extent": list(city.extent)}


def load(root: pathlib.Path, name: str, cfg: dict, cache: bool = True):
    """(host tree with numpy leaves, info dict). Reads the cache file when
    there is one for these settings; otherwise builds, and with `cache`
    writes it."""
    directory = pathlib.Path(root) / "bench" / ".scene_cache"
    path = directory / f"{name}-{_key(cfg)}.pkl"
    if cache and path.exists():
        with open(path, "rb") as f:
            return pickle.load(f)
    host, info = _build(cfg)
    if cache:
        directory.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "wb") as f:
            pickle.dump((host, info), f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    return host, info


def to_device(host_tree):
    return jax.tree_util.tree_map(jnp.asarray, host_tree)


def reference_scene(host_tree) -> reference.Scene:
    """The tree's nodes as plain arrays over global node ids: top-tree rows
    first, then slab s's local node j at T + s*S + j."""
    m = host_tree.meta
    t, ns, s = m.T, m.Ns, m.S
    base = t + np.arange(ns)[:, None] * s
    sp = np.asarray(host_tree.slab_parent, np.int64)
    root_parent = (np.asarray(host_tree.slab_root_parent_top, np.int64)
                   if t > 0 else np.full(ns, -1, np.int64))
    slab_parent = np.where(sp >= 0, base + sp, root_parent[:, None])
    g = host_tree.gaussians
    return reference.Scene(
        mu=np.asarray(g.mu, np.float32),
        size=np.asarray(host_tree.size, np.float32),
        parent=np.concatenate([np.asarray(host_tree.top_parent, np.int64),
                               slab_parent.reshape(-1)]),
        is_leaf=np.concatenate([np.asarray(host_tree.top_is_leaf, bool),
                                np.asarray(host_tree.slab_is_leaf,
                                           bool).reshape(-1)]),
        valid=np.concatenate([np.ones(t, bool),
                              np.asarray(host_tree.slab_valid,
                                         bool).reshape(-1)]),
        log_scale=np.asarray(g.log_scale, np.float32),
        quat=np.asarray(g.quat, np.float32),
        opacity=np.asarray(g.opacity, np.float32),
        dc=np.asarray(g.sh[:, 0, :], np.float32))
