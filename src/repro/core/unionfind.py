"""Disjoint sets with a Jaccard lower-bound guarantee (paper §6).

Faithful implementation of the paper's extended union-find: every tree
carries ``min_score`` — the minimum triangle-inequality lower bound on
Jaccard similarity between the root and any leaf.  A union of two trees is
admitted only when the implied leaf-to-leaf bound

    leaf_to_leaf = x.min_score + y.min_score + sim(xRoot, yRoot) - 2

stays >= ``tree_threshold`` (paper §6.4).  This guarantees that *every*
pair of documents inside one cluster has exact Jaccard >= tree_threshold
without evaluating all pairs.

Also provides ``connected_components``: a parallel pointer-doubling
connected-components solver in pure JAX (lax.while_loop) — the
TPU-friendly alternative for the scalable path (DESIGN.md §2).
"""
from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp


class ThresholdUnionFind:
    """Paper §6.4 extended disjoint sets (host-side, numpy-backed)."""

    def __init__(self, n: int, tree_threshold: float):
        self.parent = np.arange(n, dtype=np.int64)
        self.rank = np.zeros(n, dtype=np.int32)
        # min lower bound on Jaccard between node (as root) and its leaves.
        self.min_score = np.ones(n, dtype=np.float64)
        self.tree_threshold = float(tree_threshold)
        self.n_unions = 0
        self.n_rejected = 0
        # Root-representative tracking (retention layer, DESIGN.md §7):
        # every doc starts as the root of its own tree and loses
        # roothood AT MOST ONCE — ``parent[d]`` changes away from ``d``
        # only inside ``union`` where ``d`` is the losing root (path
        # compression only rewires already-deposed nodes).  With
        # ``track_deposed`` on, each union logs the deposed root, so an
        # eviction policy can discover newly non-representative docs
        # incrementally (O(unions drained), never an O(all docs) scan).
        self.track_deposed = False
        self.deposed: list[int] = []

    def grow(self, n: int) -> None:
        """Extend the forest to cover ``n`` docs (new ids are singletons).

        Incremental ingest (``core.session.DedupSession``) allocates doc
        ids chunk by chunk; growing keeps every existing root, rank, and
        ``min_score`` untouched, so clustering state accumulated so far
        is preserved exactly.
        """
        old = len(self.parent)
        if n <= old:
            return
        self.parent = np.concatenate(
            [self.parent, np.arange(old, n, dtype=np.int64)])
        self.rank = np.concatenate(
            [self.rank, np.zeros(n - old, dtype=np.int32)])
        self.min_score = np.concatenate(
            [self.min_score, np.ones(n - old, dtype=np.float64)])

    def find(self, x: int) -> int:
        root = x
        while self.parent[root] != root:
            root = self.parent[root]
        # Path compression (does not change root min_score semantics:
        # min_score is only meaningful at roots).
        while self.parent[x] != root:
            self.parent[x], x = root, self.parent[x]
        return int(root)

    def find_many(self, xs: np.ndarray) -> np.ndarray:
        """Roots of ``xs`` (int64 array) in one array pass: every lane
        steps to its parent until no lane moves; then ``xs`` point
        straight at their roots.  Roots are ``find``'s, lane for lane."""
        parent = self.parent
        roots = parent[xs]
        while True:
            up = parent[roots]
            if np.array_equal(up, roots):
                break
            roots = up
        parent[xs] = roots
        return roots

    def union(self, x: int, y: int, sim: float) -> bool:
        """Union by rank, guarded by the lower-bound threshold property.

        ``sim`` must be the *exact* (or verified-estimate) Jaccard
        similarity between the two current roots' documents — the paper
        computes sim(xRoot, yRoot) at union time.
        Returns True iff the union was performed.
        """
        x_root, y_root = self.find(x), self.find(y)
        if x_root == y_root:
            return False
        leaf_to_leaf = (
            self.min_score[x_root] + self.min_score[y_root] + sim - 2.0
        )
        if leaf_to_leaf < self.tree_threshold:
            self.n_rejected += 1
            return False
        if self.rank[x_root] < self.rank[y_root]:
            x_root, y_root = y_root, x_root
        # Attach y under x.
        self.parent[y_root] = x_root
        if self.track_deposed:
            self.deposed.append(int(y_root))
        if self.rank[x_root] == self.rank[y_root]:
            self.rank[x_root] += 1
        self.min_score[x_root] = min(
            self.min_score[x_root], self.min_score[y_root] - (1.0 - sim)
        )
        self.n_unions += 1
        return True

    def drain_deposed(self) -> list[int]:
        """Return (and clear) the roots deposed since the last drain.

        Each doc appears at most once across ALL drains (roothood is
        lost at most once), so a retention sweep can treat the drained
        list as the exact set of newly eviction-eligible documents.
        Requires ``track_deposed`` to have been on while the unions ran.
        """
        out, self.deposed = self.deposed, []
        return out

    def components(self) -> np.ndarray:
        """Root label for every node (fully compressed)."""
        return np.array([self.find(i) for i in range(len(self.parent))])

    def clusters(self, min_size: int = 2) -> list[list[int]]:
        roots = self.components()
        groups: dict[int, list[int]] = {}
        for i, r in enumerate(roots):
            groups.setdefault(int(r), []).append(i)
        return [v for v in groups.values() if len(v) >= min_size]


# ---------------------------------------------------------------------------
# Parallel connected components (pointer doubling) — pure JAX
# ---------------------------------------------------------------------------

import functools


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def connected_components(
    edges: jnp.ndarray, mask: jnp.ndarray, num_nodes: int
) -> jnp.ndarray:
    """Label connected components given an edge list.

    edges: (E, 2) int32, mask: (E,) bool (invalid edges ignored).
    Returns (num_nodes,) int32 labels — the minimum node id reachable.

    Algorithm: iterative min-label propagation (hooking) + pointer
    doubling (shortcutting), O(log N) rounds inside lax.while_loop.
    TPU-friendly: only scatter-min / gather ops, static shapes.
    """
    u = jnp.where(mask, edges[:, 0], 0).astype(jnp.int32)
    v = jnp.where(mask, edges[:, 1], 0).astype(jnp.int32)
    labels0 = jnp.arange(num_nodes, dtype=jnp.int32)

    def cond(state):
        labels, changed, it = state
        return changed & (it < 64)

    def body(state):
        labels, _, it = state
        lu = labels[u]
        lv = labels[v]
        m = jnp.minimum(lu, lv)
        new = labels
        # Hook: each endpoint's label drops to the edge minimum.
        new = new.at[u].min(jnp.where(mask, m, jnp.int32(2**31 - 1)))
        new = new.at[v].min(jnp.where(mask, m, jnp.int32(2**31 - 1)))
        # Shortcut: pointer double twice.
        new = new[new]
        new = new[new]
        changed = jnp.any(new != labels)
        return new, changed, it + 1

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.array(True), jnp.int32(0))
    )
    return labels


def cluster_min_score_audit(
    labels: np.ndarray,
    edges: np.ndarray,
    sims: np.ndarray,
    tree_threshold: float,
) -> dict:
    """Post-hoc audit of the lower-bound property for parallel CC output.

    Builds a spanning tree per cluster from the verified edges and checks
    the triangle-inequality bound along tree paths (DESIGN.md §2: the
    guarantee is audited rather than enforced in the parallel path).
    Returns {n_clusters, n_audited_pairs, min_bound, property_holds}.
    """
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(len(labels)))
    for (a, b), s in zip(edges, sims):
        a, b = int(a), int(b)
        if a != b:
            if not g.has_edge(a, b) or g[a][b]["sim"] < s:
                g.add_edge(a, b, sim=float(s), dist=1.0 - float(s))
    min_bound = 1.0
    n_pairs = 0
    holds = True
    for comp in nx.connected_components(g):
        comp = list(comp)
        if len(comp) < 2:
            continue
        sub = g.subgraph(comp)
        # Max-similarity spanning tree gives the tightest bound.
        tree = nx.minimum_spanning_tree(sub, weight="dist")
        ecc_dist = dict(
            nx.all_pairs_dijkstra_path_length(tree, weight="dist")
        )
        for a in comp:
            for b in comp:
                if a < b:
                    bound = 1.0 - ecc_dist[a][b]
                    min_bound = min(min_bound, bound)
                    n_pairs += 1
                    if bound < tree_threshold - 1e-9:
                        holds = False
    return {
        "n_clusters": sum(
            1 for c in nx.connected_components(g) if len(c) >= 2
        ),
        "n_audited_pairs": n_pairs,
        "min_bound": min_bound,
        "property_holds": holds,
    }
