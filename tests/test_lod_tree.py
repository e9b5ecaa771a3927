"""LoD tree construction invariants."""


import numpy as np
import pytest

from repro.core.gaussians import random_gaussians
from repro.core.lod_tree import build_lod_tree, subtree_end
from repro.core.lod_search import global_level_np, global_parent_np


def _check_invariants(tree):
    m = tree.meta
    parent = global_parent_np(tree)
    level = global_level_np(tree)
    valid = np.asarray(tree.valid_mask())
    size = np.asarray(tree.size)

    # exactly one root, at level 0 in the top-tree (or slab 0 if P==0)
    roots = np.where((parent == -1) & valid)[0]
    assert len(roots) == 1 and roots[0] == 0

    # parent levels are exactly one less
    ch = np.where(valid & (parent >= 0))[0]
    assert (level[ch] == level[parent[ch]] + 1).all()

    # bounding-sphere monotonicity: parent sphere contains child sphere
    mu = np.asarray(tree.gaussians.mu)
    d = np.linalg.norm(mu[ch] - mu[parent[ch]], axis=1)
    assert (d + size[ch] <= size[parent[ch]] + 1e-3).all()

    # every real node is counted once
    assert valid.sum() == m.n_real
    # slab roots have their parent in the top-tree
    rpt = np.asarray(tree.slab_root_parent_top)
    assert ((rpt >= 0) & (rpt < m.T)).all()
    # slab-local parents precede their children (DFS preorder)
    sp = np.asarray(tree.slab_parent)
    sv = np.asarray(tree.slab_valid)
    jj = np.broadcast_to(np.arange(m.S), (m.Ns, m.S))
    has_local = sv & (sp >= 0)
    assert (sp[has_local] < jj[has_local]).all()


@pytest.mark.parametrize("n,branching", [(50, (2, 4)), (400, (3, 7)), (1500, (2, 8))])
def test_tree_invariants(n, branching):
    rng = np.random.default_rng(n)
    leaves = random_gaussians(rng, n, sh_degree=1, extent=50.0)
    tree = build_lod_tree(leaves, branching=branching, target_subtrees=8, seed=2)
    _check_invariants(tree)


def test_city_tree_invariants(small_tree):
    _check_invariants(small_tree)


def test_leaf_count_preserved(small_city, small_tree):
    leafs = np.asarray(small_tree.top_is_leaf).sum() + (
        np.asarray(small_tree.slab_is_leaf) & np.asarray(small_tree.slab_valid)).sum()
    assert leafs == small_city.n == small_tree.meta.n_leaves


def test_padding_is_inert(small_tree):
    sv = np.asarray(small_tree.slab_valid)
    size = np.asarray(small_tree.slab_size())
    assert (size[~sv] == 0).all()


def _subtree_end_brute(tree):
    """One past each node's last descendant, by walking parent pointers."""
    parent = np.asarray(tree.slab_parent)
    valid = np.asarray(tree.slab_valid)
    end = np.where(valid, np.arange(tree.meta.S)[None, :] + 1, 0)
    for s in range(tree.meta.Ns):
        for j in np.flatnonzero(valid[s]):
            a = parent[s, j]
            while a >= 0:
                end[s, a] = max(end[s, a], j + 1)
                a = parent[s, a]
    return end


@pytest.mark.parametrize("fixture", ["small_tree", "tiny_tree"])
def test_slabs_are_dfs_preorder(fixture, request):
    """Each node's subtree is the slab range [j, end[j]) — the layout the
    slab sweeps read ancestry from, as the tree carries it (`slab_end`)."""
    tree = request.getfixturevalue(fixture)
    np.testing.assert_array_equal(
        subtree_end(tree.slab_parent, tree.slab_level, tree.slab_valid,
                    tree.meta.slab_max_depth), _subtree_end_brute(tree))
    np.testing.assert_array_equal(np.asarray(tree.slab_end),
                                  _subtree_end_brute(tree))


def test_subtree_end_rejects_level_order_slabs(small_tree):
    """A slab re-laid in level (BFS) order breaks the contiguous-subtree
    contract; the check refuses it instead of feeding the kernel."""
    parent = np.asarray(small_tree.slab_parent)
    level = np.asarray(small_tree.slab_level)
    valid = np.asarray(small_tree.slab_valid)
    new_p, new_l, new_v = parent.copy(), level.copy(), valid.copy()
    for s in range(small_tree.meta.Ns):
        order = np.lexsort((np.arange(small_tree.meta.S), level[s]))
        inv = np.empty_like(order)
        inv[order] = np.arange(order.size)
        p = parent[s][order]
        new_p[s] = np.where(p >= 0, inv[np.clip(p, 0, None)], -1)
        new_l[s], new_v[s] = level[s][order], valid[s][order]
    with pytest.raises(ValueError, match="DFS preorder"):
        subtree_end(new_p, new_l, new_v, small_tree.meta.slab_max_depth)
