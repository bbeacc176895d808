"""The comparisons that decide ``correct`` in a signature-estimate cell.

The same named counts as ``compare.batch_checks``, against
``reference_estimate.py``: band values of the window's notes, the
window's sims bit for bit against the reference estimate, and every
note's cluster through ``compare.label_faults``, whose reference edges
are the candidate pairs (band collisions) above the edge threshold and
the scored pairs above it that a candidate pair roots
(``rooted_edges``).  A scored pair that no candidate pair roots is a
label fault of its own.
"""
from __future__ import annotations

import numpy as np

import compare
import reference as ref
import reference_estimate as refest


def _component_pair(comp: np.ndarray, pairs: np.ndarray) -> np.ndarray:
    """One int64 key per pair: its two ends' components, unordered."""
    ca, cb = comp[pairs[:, 0]], comp[pairs[:, 1]]
    return np.minimum(ca, cb) * np.int64(len(comp)) + np.maximum(ca, cb)


def rooted_edges(n: int, cand: np.ndarray, cand_above: np.ndarray,
                 scored: np.ndarray, scored_above: np.ndarray
                 ) -> tuple[np.ndarray, int, int]:
    """The reference edges, the scored pairs above the threshold that
    joined them, and the scored pairs that no candidate pair roots.

    The disjoint sets score the current roots of a candidate pair's two
    trees, not the pair itself, and at 8 rows a band a pair above 0.75
    can share no band (at 0.76, in about one case in five), so a union
    may rest on a pair that is no candidate.  Every pair the program
    scores still joins the trees of a candidate pair (a, b): one end in
    a's tree, the other in b's, trees that earlier unions built.  So,
    starting from the candidate pairs above the threshold, a scored
    pair above it becomes an edge when some candidate pair has one end
    in each of its ends' components, and this repeats until no such
    pair is left.  Afterwards every scored pair, above the threshold or
    not, must have its ends in the components of some candidate pair's
    two ends; one that does not is a pair the program scored without a
    band collision to root it, and counts as unrooted.

    ``cand``: every candidate pair, ``cand_above`` the mask of those
    above the threshold; ``scored`` / ``scored_above`` likewise for the
    pairs the program scored, by the reference's own estimate.
    """
    edges = cand[cand_above]
    is_cand = np.isin(scored[:, 0] * np.int64(n) + scored[:, 1],
                      cand[:, 0] * np.int64(n) + cand[:, 1])
    pending = scored[scored_above & ~is_cand]
    admitted = 0
    while True:
        comp = ref.components(n, edges)
        roots = np.unique(_component_pair(comp, cand))
        ok = np.isin(_component_pair(comp, pending), roots)
        if not ok.any():
            break
        edges = np.concatenate([edges, pending[ok]])
        admitted += int(ok.sum())
        pending = pending[~ok]
    unrooted = int(np.sum(~np.isin(_component_pair(comp, scored), roots)))
    return ref.unique_pairs(edges, n), admitted, unrooted


def estimate_checks(texts: list[str], cfg: dict, prog_bands: np.ndarray,
                    prog_labels: np.ndarray, prog_ab: np.ndarray,
                    prog_sims: np.ndarray, first_checked: int, sample: int,
                    rng: np.random.Generator) -> dict:
    """``prog_ab`` / ``prog_sims``: every pair the program scored and its
    sim.  The notes ``first_checked..`` (the window's) are checked for
    band values, a seeded sample of the pairs the window scored and
    every witness for their sims, and every note for its cluster."""
    sig, bands = refest.arrays(texts, cfg)
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

    pairs = np.asarray(prog_ab, dtype=np.int64).reshape(-1, 2)
    sims = np.asarray(prog_sims, dtype=np.float32).reshape(-1)
    # A pair naming a note the run never had is a fault of its own.
    alien = (pairs.min(axis=1, initial=0) < 0) | (pairs.max(
        axis=1, initial=0) >= n) if len(pairs) else np.zeros(0, bool)
    pairs, sims = pairs[~alien], sims[~alien]

    cand = refest.candidate_pairs(bands)
    above = np.float32(cfg["edge_threshold"])
    cand_above = refest.estimate(sig, cand) > above
    edges, admitted, unrooted = rooted_edges(
        n, cand, cand_above, np.sort(pairs, axis=1),
        refest.estimate(sig, pairs) > above)
    lf = compare.label_faults(prog_labels, edges, pairs, sims,
                              cfg["edge_threshold"], cfg["tree_threshold"])
    # How many clusters the candidate pairs alone would not support.
    cand_only = compare._components_split(
        prog_labels, ref.components(n, cand[cand_above]))

    in_window = np.flatnonzero(pairs.max(axis=1, initial=0) >= first_checked
                               ) if len(pairs) else np.zeros(0, np.int64)
    pick = (rng.choice(in_window, size=sample, replace=False)
            if len(in_window) > sample else in_window)
    pick = np.union1d(pick, lf["witnesses"]).astype(np.int64)
    want = refest.estimate(sig, pairs[pick])
    sims_wrong = int(np.sum(want.view(np.uint32)
                            != sims[pick].view(np.uint32))) + int(alien.sum())
    return {
        "band_rows_wrong": band_rows_wrong,
        "sims_wrong": sims_wrong,
        "label_faults": lf["unsupported"] + lf["split"] + unrooted + lost,
        "_sims_checked": int(len(pick)),
        "_witnesses": int(len(lf["witnesses"])),
        "_pairs_scored": int(len(in_window)),
        "_candidates": int(len(cand)),
        "_edges": int(len(edges)),
        "_unsupported": lf["unsupported"],
        "_split": lf["split"],
        "_unrooted": unrooted,
        "_admitted": admitted,
        "_unsupported_by_candidates_alone": cand_only,
    }
