"""The plain reference: temporal k-core component search, straight from
its definition, with nothing of the system under test.

For a query ``(u, ts, te, k)`` on an undirected temporal multigraph:
project the edges whose day lies in ``[ts, te]`` (both ends included),
collapse parallel edges so that a degree counts distinct neighbours, peel
every vertex of degree below ``k`` until none is left (the k-core), and
answer the vertices connected to ``u`` in what remains, or nothing when
``u`` is not in the k-core. COUNT answers the number of those vertices.

``distinct_neighbours=False`` is the control: degree counts parallel
edges, a shortcut that skips the collapse and breaks the guarantee.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def component(g, u: int, ts: int, te: int, k: int, *,
              distinct_neighbours: bool = True) -> frozenset:
    """Vertex set of ``u``'s k-core component in ``g`` over ``[ts, te]``."""
    lo, hi = np.searchsorted(g.t, [ts, te + 1])
    a = g.src[lo:hi].astype(np.int64)
    b = g.dst[lo:hi].astype(np.int64)
    if distinct_neighbours:
        pairs = np.unique(np.minimum(a, b) * g.n + np.maximum(a, b))
        a, b = pairs // g.n, pairs % g.n
    while a.size:
        deg = np.bincount(a, minlength=g.n) + np.bincount(b, minlength=g.n)
        keep = (deg[a] >= k) & (deg[b] >= k)
        if keep.all():
            break
        a, b = a[keep], b[keep]
    if not ((a == u).any() or (b == u).any()):
        return frozenset()
    adj = coo_matrix((np.ones(a.size, np.int8), (a, b)), shape=(g.n, g.n))
    _, label = connected_components(adj, directed=False)
    touched = np.zeros(g.n, bool)
    touched[a] = touched[b] = True
    return frozenset(np.nonzero(touched & (label == label[u]))[0].tolist())
