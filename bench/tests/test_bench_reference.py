"""The plain references and the device graph generator."""
import os
import sys

import numpy as np
import pytest

from bench import graphgen
from bench.reference import algorithms as ref
from bench.reference import compare
from bench.reference import rounds

GRAPH500 = dict(edge_factor=16, a=0.57, b=0.19, c=0.19)


def test_generator_is_seeded_and_clean():
    a = graphgen.rmat_edges(10, seed=2**33 + 5, **GRAPH500)
    b = graphgen.rmat_edges(10, seed=2**33 + 5, **GRAPH500)
    c = graphgen.rmat_edges(10, seed=5, **GRAPH500)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])       # seeds past 2**32 differ
    src, dst, w = a
    assert src.dtype == dst.dtype == np.int32 and w.dtype == np.float32
    assert (src != dst).all() and src.max() < 1024 and dst.max() < 1024
    assert w.min() >= 0.0 and w.max() < 1.0     # Graph500 kernel 3
    assert 15 * 1024 < src.shape[0] <= 16 * 1024
    # RMAT skew without a label permutation: the low ids are the hubs
    deg = np.bincount(np.concatenate([src, dst]), minlength=1024)
    assert deg[0] == deg.max()


def test_a_structure_seed_gives_one_graph_up_to_its_labels():
    cfg = dict(GRAPH500, generator="rmat", scale=10, weight_min=0.0,
               weight_max=1.0, structure_seed=21)
    a = graphgen.generate(cfg, seed=5)
    b = graphgen.generate(cfg, seed=2**33 + 5)
    assert not np.array_equal(a[0], b[0])            # labels differ
    dists = []
    for src, dst, w in (a, b):
        deg = compare.degrees(1024, src, dst)
        root = int(np.argmax(deg))
        dists.append(np.sort(ref.sssp(ref.csr(1024, src, dst, w),
                                      [root])[0]))
        np.testing.assert_array_equal(np.sort(deg), np.sort(
            compare.degrees(1024, *a[:2])))
    np.testing.assert_array_equal(dists[0], dists[1])  # the same work


def test_sssp_takes_the_min_over_parallel_edges():
    src = np.array([0, 0, 1], np.int32)
    dst = np.array([1, 1, 2], np.int32)
    w = np.array([5.0, 2.0, 1.0], np.float32)
    g = ref.csr(4, src, dst, w)
    d = ref.sssp(g, [0])[0]
    np.testing.assert_array_equal(d, [0.0, 2.0, 3.0, np.inf])
    np.testing.assert_array_equal(ref.bfs(g, [2])[0], [2, 1, 0, -1])
    np.testing.assert_array_equal(ref.wcc(g), [0, 0, 0, 3])


def test_plane_bytes_on_a_path():
    # 0 -1.0- 1 -2.0- 2 (undirected); SSSP from 0: frontiers {0}, {1}, {2}
    src = np.array([0, 1], np.int32)
    dst = np.array([1, 2], np.int32)
    w = np.array([1.0, 2.0], np.float32)
    out, k, slots = rounds.run_rounds(3, src, dst, w, root=0)
    np.testing.assert_array_equal(out, [0.0, 1.0, 3.0])
    assert k == 3 and list(slots) == [1, 2, 1]
    # 4 slots x (4 B id + 4 B weight + 4 B value) + 3 rounds x 3 x 4 B x 2
    assert rounds.plane_bytes(3, slots, edge_props=1) == 4 * 12 + 3 * 24
    # no edge property: 8 B a slot
    assert rounds.plane_bytes(3, slots, edge_props=0) == 4 * 8 + 3 * 24


def test_references_agree_with_the_bring_up_smoke():
    """Built from the benchmark's own edge list, the references give what
    chip_smoke.py's references give from the program's PropertyGraph."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    try:
        import chip_smoke
    finally:
        sys.path.remove(root)
    from repro.core.graph import from_edges
    src, dst, w = graphgen.rmat_edges(10, seed=7, **GRAPH500)
    g = from_edges(src, dst, 1024, edge_props={"weight": w},
                   directed=False)
    theirs = chip_smoke._csr(g)
    ours = ref.csr(1024, src, dst, w)
    r = int(np.argmax(g.out_degree))
    np.testing.assert_allclose(ref.sssp(ours, [r])[0],
                               chip_smoke.ref_sssp(theirs, r))
    np.testing.assert_array_equal(ref.bfs(ours, [r])[0],
                                  chip_smoke.ref_bfs(theirs, r))
    np.testing.assert_array_equal(ref.wcc(ours), chip_smoke.ref_wcc(theirs))
    np.testing.assert_allclose(ref.pagerank(ours, 20),
                               chip_smoke.ref_pagerank(theirs, 20),
                               rtol=1e-12)


@pytest.mark.parametrize("dtype,rtol", [("float32", 1e-6),
                                         ("bfloat16", 2e-2)])
def test_rounds_reach_the_reference_fixpoint(dtype, rtol):
    src, dst, w = graphgen.rmat_edges(9, seed=3, **GRAPH500)
    g = ref.csr(512, src, dst, w)
    out, k, _ = rounds.run_rounds(512, src, dst, w, root=0, dtype=dtype)
    np.testing.assert_allclose(out.astype(np.float64), ref.sssp(g, [0])[0],
                               rtol=rtol)
    assert 2 <= k < rounds.MAX_ROUNDS


def test_sssp_errors_are_relative_and_count_reachability():
    want = np.array([0.0, 0.5, 2.0, np.inf])
    rel, reach = compare.sssp_errors(np.array([0.0, 0.5, 2.0, np.inf]), want)
    assert rel == 0.0 and reach == 0
    # relative where the reference is positive, absolute where it is 0
    rel, _ = compare.sssp_errors(np.array([0.0, 0.5005, 2.0, np.inf]), want)
    assert rel == pytest.approx(1e-3)
    rel, _ = compare.sssp_errors(np.array([1e-4, 0.5, 2.0, np.inf]), want)
    assert rel == pytest.approx(1e-4)
    _, reach = compare.sssp_errors(np.array([0.0, np.inf, 2.0, 7.0]), want)
    assert reach == 2
    lim = compare.limits({"limits": {"sssp_rel_err": 1e-5}},
                         {"sssp_rel_err": 3e-6})
    assert lim == {"sssp_rel_err": {"value": 3e-6, "limit": 1e-5}}
