"""The benchmark's own copy of the instance generators.

Copied from the program's graph generators and Jaccard signing, so that a
later change there cannot move the yardstick. Each function returns numpy
arrays; the benchmark hands only these to the program.

  * ``collaboration_like``: Barabasi-Albert stand-in for the SNAP ca-*
    collaboration graphs of the paper's CC-LP experiments.
  * ``signed_instance``: Wang et al. non-linear Jaccard signing with the
    +-offset of Veldt et al., giving the dense CC-LP input (dissim, weights).
"""

from __future__ import annotations

import networkx as nx
import numpy as np


def _largest_component_adjacency(g: nx.Graph) -> np.ndarray:
    nodes = max(nx.connected_components(g), key=len)
    return nx.to_numpy_array(g.subgraph(nodes), dtype=np.float64) > 0


def collaboration_like(n: int, m: int = 3, seed: int = 0) -> np.ndarray:
    """Adjacency of the largest component of a BA(n, m) graph."""
    return _largest_component_adjacency(
        nx.barabasi_albert_graph(n, m, seed=seed)
    )


def signed_instance(
    adj: np.ndarray, delta: float = 0.05, offset_eps: float = 0.01
) -> tuple[np.ndarray, np.ndarray]:
    """(dissim, weights): dissim in {0, 1}, weights > 0, both (n, n) with a
    meaningful strict upper triangle."""
    a = adj.astype(np.float64)
    np.fill_diagonal(a, 1.0)  # closed neighbourhoods
    inter = a @ a.T
    deg = a.sum(axis=1)
    union = deg[:, None] + deg[None, :] - inter
    with np.errstate(divide="ignore", invalid="ignore"):
        j = np.where(union > 0, inter / union, 0.0)
    np.fill_diagonal(j, 0.0)
    s = np.log((1.0 + j - delta) / (1.0 - j + delta))
    s = s + np.where(s >= 0, offset_eps, -offset_eps)
    n = adj.shape[0]
    iu = np.triu(np.ones((n, n), bool), 1)
    dissim = np.where(iu & (s < 0), 1.0, 0.0)
    weights = np.maximum(np.where(iu, np.abs(s), 1.0), 1e-6)
    return dissim, weights


def make_graph(kind: str, n: int, seed: int, **params) -> np.ndarray:
    """Adjacency by generator name, as a configuration file names it."""
    gens = {"collaboration_like": collaboration_like}
    return gens[kind](n, seed=seed, **params)
