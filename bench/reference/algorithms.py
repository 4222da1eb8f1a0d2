"""Plain references over the benchmark's own generated edge list.

Nothing here imports the system under test or reads anything it made:
the CSR is built from the (src, dst, weight) arrays that `bench.graphgen`
generated. Undirected graphs store both directions of every generated
edge, parallel edges kept: a shortest-path search relaxes every stored
entry, so parallel edges act as their minimum.

Semantics follow the operators they check: SSSP and BFS from one root
(unreachable = inf / -1), WCC labels every vertex with the smallest
vertex id of its component, PageRank keeps the uniform start in round 1
and then runs num_iters - 1 synchronous updates, dropping dangling mass.
"""
from __future__ import annotations

import numpy as np


def both_directions(src, dst, w=None):
    """The stored edge slots of an undirected edge list (no self loops)."""
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    return s, d, (None if w is None else np.concatenate([w, w]))


def csr(num_vertices: int, src, dst, w=None, undirected: bool = True):
    """Out-edge CSR (float64 weights, 1.0 where unweighted) with every
    stored slot kept, parallel edges included."""
    import scipy.sparse as sp
    if undirected:
        src, dst, w = both_directions(src, dst, w)
    order = np.argsort(src)
    indptr = np.zeros(num_vertices + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=num_vertices), out=indptr[1:])
    data = (np.ones(order.shape[0]) if w is None
            else w[order].astype(np.float64))
    return sp.csr_matrix((data, dst[order], indptr),
                         shape=(num_vertices, num_vertices))


def sssp(graph, roots):
    """[len(roots), V] float64 distances (inf where unreachable)."""
    from scipy.sparse import csgraph
    return np.atleast_2d(csgraph.dijkstra(graph, directed=True,
                                          indices=np.asarray(roots)))


def bfs(graph, roots):
    """[len(roots), V] int64 hop depths (-1 where unreachable)."""
    from scipy.sparse import csgraph
    d = np.atleast_2d(csgraph.shortest_path(graph, method="D", directed=True,
                                            unweighted=True,
                                            indices=np.asarray(roots)))
    return np.where(np.isfinite(d), d, -1).astype(np.int64)


def wcc(graph):
    """[V] smallest vertex id of each vertex's weakly connected component."""
    from scipy.sparse import csgraph
    n = graph.shape[0]
    _, comp = csgraph.connected_components(graph, directed=True,
                                           connection="weak")
    low = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(low, comp, np.arange(n))
    return low[comp]


def pagerank(graph, num_iters: int = 20, damping: float = 0.85):
    import scipy.sparse as sp
    n = graph.shape[0]
    ones = sp.csr_matrix((np.ones_like(graph.data), graph.indices,
                          graph.indptr), shape=graph.shape)
    inv_deg = 1.0 / np.maximum(np.diff(graph.indptr), 1)
    rank = np.full(n, 1.0 / n)
    for _ in range(num_iters - 1):
        rank = (1.0 - damping) / n + damping * (ones.T @ (rank * inv_deg))
    return rank
