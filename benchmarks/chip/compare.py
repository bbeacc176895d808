"""The comparisons that decide a run's ``correct``.

Each function takes what the timed path produced, recomputes it with
the plain reference (``reference.py``) and returns named counts of
disagreements.  Every count is an exact comparison: a sound run reads 0.

Clusters are the disjoint sets of the paper's §6.4: a union joins the
trees of a candidate pair whose sim exceeds the edge threshold, scored
between the trees' current roots, and is refused when the bound it
implies on the Jaccard of any two leaves,
``min_score(x) + min_score(y) + sim - 2``, falls under the tree
threshold.  Which pairs get scored depends on the order of unions, so
the reference does not replay that order; it checks properties every
such clustering has:

* ``unsupported``: every cluster lies inside one connected component of
  the graph of candidate pairs whose sim exceeds the edge threshold (a
  union edge is such a pair; in exact mode it shares a band with
  probability above 1 - 1e-18);
* ``split``: every candidate pair above the threshold whose two notes
  sit in different clusters has a witness, a pair the program scored
  between those two clusters that could have kept them apart: one
  whose sim is at most the edge threshold, or one whose union the tree
  threshold could have refused.  The pair (a, b) was met with its
  notes under two roots, one in each final cluster, and that root pair
  was scored then or earlier; had it joined them, a and b would share
  a cluster.  A tree's ``min_score`` starts at 1 and each union takes
  at most ``1 - sim`` of one of its pairs off it, so it is at least
  ``1 - slack``, where a cluster's slack is the sum of its ``size - 1``
  largest ``1 - sim`` over the pairs scored inside it above the edge
  threshold; a refusal needs ``sim - slack(A) - slack(B)`` under the
  tree threshold.  Witnesses' sims are checked against the reference.
"""
from __future__ import annotations

import numpy as np

import reference as ref

SLACK_EPS = 1e-9


def _components_split(labels: np.ndarray, comp: np.ndarray) -> int:
    """Clusters (by ``labels``) that span two components of ``comp``."""
    n = len(labels)
    order = np.lexsort((comp, labels))
    lab, cmp_ = labels[order], comp[order]
    same_cluster = np.zeros(n, dtype=bool)
    same_cluster[1:] = lab[1:] == lab[:-1]
    split = same_cluster & np.concatenate([[False], cmp_[1:] != cmp_[:-1]])
    return len(np.unique(lab[split]))


def _slack(labels: np.ndarray, pairs: np.ndarray, sims: np.ndarray,
           edge_threshold: float) -> np.ndarray:
    """Per cluster root: the sum of its ``size - 1`` largest ``1 - sim``
    over the scored pairs inside it above the edge threshold."""
    n = len(labels)
    size = np.bincount(labels, minlength=n)
    inside = (labels[pairs[:, 0]] == labels[pairs[:, 1]]) & \
        (sims > np.float32(edge_threshold))
    root = labels[pairs[inside, 0]]
    loss = 1.0 - sims[inside].astype(np.float64)
    order = np.lexsort((-loss, root))
    root, loss = root[order], loss[order]
    first = np.searchsorted(root, root, side="left")
    rank = np.arange(len(root)) - first
    keep = rank < size[root] - 1
    return np.bincount(root[keep], weights=loss[keep], minlength=n)


def label_faults(labels: np.ndarray, edges: np.ndarray, pairs: np.ndarray,
                 sims: np.ndarray, edge_threshold: float,
                 tree_threshold: float) -> dict:
    """``labels``: cluster root per note; ``edges``: every candidate
    pair above the edge threshold (reference); ``pairs`` / ``sims``:
    every pair the program scored and the sim it used.  Returns the two
    counts of the module docstring and the indices of the pairs that
    serve as witnesses (their sims must be checked)."""
    labels = np.asarray(labels, dtype=np.int64)
    n = len(labels)
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    unsupported = _components_split(labels, ref.components(n, edges))

    cross = edges[labels[edges[:, 0]] != labels[edges[:, 1]]]
    la, lb = labels[cross[:, 0]], labels[cross[:, 1]]
    want = np.unique(np.minimum(la, lb) * np.int64(n) + np.maximum(la, lb))
    pa, pb = labels[pairs[:, 0]], labels[pairs[:, 1]]
    key = np.minimum(pa, pb) * np.int64(n) + np.maximum(pa, pb)
    witness = np.flatnonzero((pa != pb) & np.isin(key, want))
    slack = _slack(labels, pairs, sims, edge_threshold)
    s = sims[witness].astype(np.float64)
    can_refuse = (s <= np.float32(edge_threshold)) | (
        s - slack[pa[witness]] - slack[pb[witness]]
        < tree_threshold + SLACK_EPS)
    explained = np.unique(key[witness[can_refuse]])
    split = int(np.sum(~np.isin(want, explained)))
    return {"unsupported": int(unsupported), "split": split,
            "witnesses": witness}


def batch_checks(texts: list[str], cfg: dict, prog_bands: np.ndarray,
                 prog_labels: np.ndarray, prog_pairs: list,
                 first_checked: int, sample: int,
                 rng: np.random.Generator) -> dict:
    """The batch cell: the notes ``first_checked..`` (the window's) are
    checked for band values, a seeded sample of the pairs the window
    scored for their sims, and every note for its cluster."""
    tok = ref.Tokens(texts, do_stem=cfg["stem"])
    seeds = ref.minhash_seeds(cfg["num_hashes"], cfg["seed_key"])
    bands = ref.band_values(ref.signatures(tok, cfg["ngram"], seeds),
                            cfg["rows_per_band"])
    # Notes the program never clustered count as wrong everywhere.
    n = len(texts)
    lost = max(0, n - len(prog_labels))
    prog_labels = np.concatenate([np.asarray(prog_labels, dtype=np.int64)[:n],
                                  np.arange(n - lost, n)])
    prog_bands = np.concatenate([prog_bands[:n], np.zeros(
        (max(0, n - len(prog_bands)),) + bands.shape[1:], dtype=np.uint32)])
    window = slice(first_checked, n)
    band_rows_wrong = int(np.sum(np.any(
        prog_bands[window] != bands[window], axis=(1, 2))))

    grams = ref.GramSets(tok, cfg["ngram"])
    pairs = np.array([(a, b) for a, b, _ in prog_pairs], dtype=np.int64
                     ).reshape(-1, 2)
    sims = np.array([s for _, _, s in prog_pairs], dtype=np.float32)
    # A pair naming a note the run never had is a fault of its own.
    alien = (pairs.min(axis=1, initial=0) < 0) | (pairs.max(
        axis=1, initial=0) >= n) if len(pairs) else np.zeros(0, bool)
    pairs, sims = pairs[~alien], sims[~alien]

    edges, _ = grams.similar_pairs(cfg["edge_threshold"],
                                   keys=ref.band_keys(bands))
    lf = label_faults(prog_labels, edges, pairs, sims, cfg["edge_threshold"],
                      cfg["tree_threshold"])

    in_window = np.flatnonzero(pairs.max(axis=1, initial=0) >= first_checked
                               ) if len(pairs) else np.zeros(0, np.int64)
    pick = (rng.choice(in_window, size=sample, replace=False)
            if len(in_window) > sample else in_window)
    pick = np.union1d(pick, lf["witnesses"]).astype(np.int64)
    want = grams.jaccard(pairs[pick])
    sims_wrong = int(np.sum(want.view(np.uint32)
                            != sims[pick].view(np.uint32))) + int(alien.sum())
    return {
        "band_rows_wrong": band_rows_wrong,
        "sims_wrong": sims_wrong,
        "label_faults": lf["unsupported"] + lf["split"] + lost,
        "_sims_checked": int(len(pick)),
        "_witnesses": int(len(lf["witnesses"])),
        "_pairs_scored": int(len(in_window)),
        "_edges": int(len(edges)),
        "_unsupported": lf["unsupported"],
        "_split": lf["split"],
    }
