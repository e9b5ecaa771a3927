"""Quickstart: build a procedural city, run the LoD search, render a stereo
frame with the bit-accurate shared pipeline, and verify it against two
independent per-eye renders.

    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses as dc

import jax.numpy as jnp
import numpy as np

from repro.core import lod_search as ls
from repro.core.camera import StereoRig, make_camera
from repro.core.gaussians import CityConfig, generate_city
from repro.core.lod_tree import build_lod_tree
from repro.core.pipeline import render_stereo, render_stereo_reference
from repro.launch.compile_cache import enable_compilation_cache


def main():
    enable_compilation_cache()
    print("== building city scene ==")
    leaves = generate_city(CityConfig(blocks_x=3, blocks_y=3, leaf_density=0.2))
    tree = build_lod_tree(leaves, target_subtrees=32)
    print(f"   {leaves.n} leaf gaussians → LoD tree: {tree.meta.n_real} nodes, "
          f"{tree.meta.Ns} subtrees of {tree.meta.S} slots, depth {tree.meta.depth}")

    cam = make_camera([30, 30, 1.7], [80, 80, 1.5], focal_px=300.0,
                      width=192, height=108, near=0.25)
    rig = StereoRig(left=cam, baseline=0.06)

    print("== LoD search (fully-streaming) ==")
    cut, state = ls.full_search(tree, np.asarray(cam.pos),
                                jnp.float32(cam.focal), jnp.float32(48.0))
    n_cut = int(cut.count())
    print(f"   cut = {n_cut} gaussians "
          f"({n_cut / tree.meta.n_real * 100:.1f}% of the scene)")

    gids, _cnt, _ovf = ls.cut_gids(cut, tree, budget=16384)
    queue = tree.gaussians.slice_rows(jnp.clip(gids, 0))
    queue = dc.replace(queue, opacity=jnp.where(gids >= 0, queue.opacity, 0.0))

    print("== stereo rendering (shared preprocessing + triangulation) ==")
    left, right, (_s, _ll, _rl, stats) = render_stereo(
        queue, rig, tile=16, list_len=256, max_pairs=1 << 17)
    ref_l, ref_r = render_stereo_reference(queue, rig)
    exact = bool((np.asarray(left) == np.asarray(ref_l)).all()
                 and (np.asarray(right) == np.asarray(ref_r)).all())
    print(f"   bit-accurate vs independent per-eye renders: {exact}")
    print(f"   work sharing: {stats.shared_preprocess} splats preprocessed once, "
          f"{stats.right_alpha_skipped}/{stats.right_candidates} right-eye "
          f"candidates prunable via left α-checks")

    out = np.concatenate([np.asarray(left), np.asarray(right)], axis=1)
    path = "/tmp/nebula_quickstart_stereo.npy"
    np.save(path, out)
    print(f"   stereo pair saved to {path} (shape {out.shape})")


if __name__ == "__main__":
    main()
