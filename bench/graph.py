"""The benchmark's graphs: heterogeneous graphs at published statistics.

A configuration's ``graph`` block gives the node counts, the raw feature
widths, each relation with its published edge count and the name of its
reverse, and the metapaths.  Edges are drawn as in the repository's
``data/synthetic.py`` (uniform sources, Pareto(1.3) destination popularity,
deduplicated) and features are N(0, 0.01).  The graph is a fixed data set:
it comes from the block's own ``seed``, not from a run's ``--seed``, so
every run of a cell serves the same graph, as a deployment does.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np
import scipy.sparse as sp

Relation = Tuple[str, str, str]


@dataclass
class Graph:
    target: str
    counts: Dict[str, int]
    feats: Dict[str, np.ndarray]  # type -> [n, F] float32
    relations: Dict[Relation, sp.csr_matrix]  # (s, r, d) -> [n_s, n_d]
    metapaths: List[List[str]]

    def rel(self, src: str, dst: str) -> sp.csr_matrix:
        for (s, _, d), a in self.relations.items():
            if s == src and d == dst:
                return a
        raise KeyError(f"no relation {src}->{dst}")


def _bipartite(n_src: int, n_dst: int, n_edges: int,
               rng: np.random.Generator) -> sp.csr_matrix:
    w = rng.pareto(1.3, size=n_dst) + 1.0
    m = int(n_edges * 1.3) + 16
    src = rng.integers(0, n_src, size=m)
    dst = rng.choice(n_dst, size=m, p=w / w.sum())
    _, idx = np.unique(src.astype(np.int64) * n_dst + dst, return_index=True)
    idx = idx[:n_edges]
    return sp.csr_matrix((np.ones(len(idx), np.float32), (src[idx], dst[idx])),
                         shape=(n_src, n_dst))


def make_graph(spec: Dict) -> Graph:
    rng = np.random.default_rng(spec["seed"])
    counts = {t: int(n) for t, n in spec["counts"].items()}
    relations: Dict[Relation, sp.csr_matrix] = {}
    for s, r, d, n_edges, rev in spec["relations"]:
        a = _bipartite(counts[s], counts[d], int(n_edges), rng)
        relations[(s, r, d)] = a
        relations[(d, rev, s)] = a.T.tocsr()
    feats = {t: rng.standard_normal((counts[t], int(spec["dims"][t])),
                                    dtype=np.float32) * np.float32(0.1)
             for t in counts}
    return Graph(spec["target"], counts, feats, relations,
                 [list(p) for p in spec.get("metapaths", [])])


def metapath_adjacency(g: Graph, path: List[str]) -> sp.csr_matrix:
    """Binary ``[n_first, n_last]`` reachability along ``path``, with the
    self loop that HAN adds to every metapath graph."""
    acc = g.rel(path[0], path[1]).astype(np.float32)
    for a, b in zip(path[1:-1], path[2:]):
        acc = acc @ g.rel(a, b).astype(np.float32)
    acc = (acc + sp.eye(acc.shape[0], acc.shape[1], format="csr")).tocsr()
    acc.data = np.ones_like(acc.data)
    acc.eliminate_zeros()
    return acc


def in_adjacency(g: Graph, key: Relation) -> sp.csr_matrix:
    """``[n_d, n_s]``: row ``u`` of type ``d`` lists its in-neighbors of
    type ``s`` under relation ``key = (s, r, d)``."""
    return g.relations[key].T.tocsr()
