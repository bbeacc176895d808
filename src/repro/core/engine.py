"""The staged dedup engine: CandidateSource -> BatchVerifier -> UnionFind.

This is the single implementation of the paper's §6.5
``find_candidate_pairs`` procedure that all three execution paths drive:

* host in-memory      — ``pipeline.DedupPipeline`` (``BandMatrixSource``)
* out-of-core / streaming — ``streaming.StreamingDedup``
  (``StoreBandSource`` over a Design-1/2 band store)
* sharded (shard_map) — ``dist_lsh`` prescreens edges on-device with a
  signature-prefix compare inside the all_to_all, then its host-side
  merge drives this engine over a ``ShardedEdgeSource`` with a
  full-signature ``ShardedEdgeVerifier`` (``dist_lsh.cluster_step_output``),
  so thresholds and verify semantics match the other paths exactly.

For each band the engine walks equal-value runs, path-compresses run
members to their current union-find roots, and collects not-yet-evaluated
root pairs into a batch buffer that is flushed through the verifier in
device-sized dispatches — the scalar ``similarity_fn(a, b)`` inner loop
of the previous three copies is gone.

``batch`` granularity:

* ``"run"``  (default) — flush at every run boundary.  Bit-identical to
  the historical scalar loop: unions from one run are visible to the
  next run's root compression, so the exclusion statistics (paper
  Table 5) and the union-find lower-bound guarantee are unchanged.
* ``"band"`` — flush at band boundaries (or when the buffer reaches
  ``max_batch_pairs``).  With disjoint sets the runs between two
  flushes are compressed and paired in array passes
  (``ClusterAccumulator._feed_arrays``), with the loop's results bit
  for bit.  Larger dispatches, maximum throughput; pairs
  that a same-band union would have excluded may be evaluated, and a
  union's ``sim`` is the one measured against collection-time roots, so
  the tree-threshold guarantee becomes approximate (audit with
  ``unionfind.cluster_min_score_audit`` if it matters).
"""
from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass
from itertools import chain, islice

import numpy as np

from repro.core import spans
from repro.core.candidates import CandidateSource
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import as_verifier


@dataclass
class ClusterStats:
    """Engine counters (superset of the paper's Table 5 accounting)."""

    pairs_generated: int = 0
    pairs_evaluated: int = 0
    pairs_excluded: int = 0  # skipped Jaccard computations (paper Table 5)
    pairs_above_edge: int = 0
    unions_done: int = 0
    unions_rejected: int = 0
    verify_batches: int = 0
    verify_seconds: float = 0.0

    @property
    def verify_pairs_per_second(self) -> float:
        if self.verify_seconds <= 0:
            return 0.0
        return self.pairs_evaluated / self.verify_seconds

    def add(self, other: "ClusterStats") -> "ClusterStats":
        """Accumulate another pass's counters (multi-source clustering)."""
        for f in (
            "pairs_generated", "pairs_evaluated", "pairs_excluded",
            "pairs_above_edge", "unions_done", "unions_rejected",
            "verify_batches", "verify_seconds",
        ):
            setattr(self, f, getattr(self, f) + getattr(other, f))
        return self


def _sort_keys(ab: np.ndarray) -> np.ndarray:
    """One int64 per (a, b) that sorts as the tuple does (ids < 2**31)."""
    return (ab[:, 0] << 32) | ab[:, 1]


def _in_sorted(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """``keys`` found in the sorted array ``sorted_keys``, lane for lane."""
    if not len(sorted_keys):
        return np.zeros(len(keys), bool)
    at = np.searchsorted(sorted_keys, keys)
    return sorted_keys[np.minimum(at, len(sorted_keys) - 1)] == keys


def _merge_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Two sorted key arrays as one sorted array."""
    return np.insert(a, np.searchsorted(a, b), b)


def _pairs_of(keys: list[np.ndarray]) -> np.ndarray:
    """The (P, 2) pairs of a list of ``_sort_keys`` arrays, in order."""
    keys = np.concatenate(keys) if keys else np.zeros(0, np.int64)
    return np.stack([keys >> 32, keys & 0xFFFFFFFF], axis=-1)


def _run_root_pairs(uf: ThresholdUnionFind, docs: np.ndarray,
                    starts: np.ndarray, ends: np.ndarray):
    """Every run ``docs[s:e]`` compressed to its sorted unique roots,
    and their pairs in row-major order, runs in order: the pairs the
    scalar loop walks, as ``_sort_keys`` and each pair's run index."""
    m = ends - starts
    run = np.repeat(np.arange(len(m)), m)
    at = np.arange(len(run)) + np.repeat(starts - (np.cumsum(m) - m), m)
    roots = uf.find_many(docs[at])
    order = np.lexsort((roots, run))
    run, roots = run[order], roots[order]
    uniq = np.ones(len(roots), bool)
    uniq[1:] = (run[1:] != run[:-1]) | (roots[1:] != roots[:-1])
    run, roots = run[uniq], roots[uniq]
    # Each root pairs with the roots after it in its run: k - 1 - its
    # place among the run's k roots.
    k = np.bincount(run, minlength=len(m))
    after = np.repeat(np.cumsum(k), k) - 1 - np.arange(len(roots))
    left = np.repeat(np.arange(len(roots)), after)
    right = (left + 1 + np.arange(len(left))
             - np.repeat(np.cumsum(after) - after, after))
    return (roots[left] << 32) | roots[right], run[left]


class PairList(Sequence):
    """Verified ``(a, b, sim)`` triples sorted by ``(a, b)``: an
    immutable sequence over two columns, which it makes read-only.

    ``ab`` is (P, 2) int64 and ``sim`` (P,) float64, so each sim is the
    Python float the verified-sim cache holds, bit for bit.  Reading
    yields ``(int, int, float)`` tuples built on demand; slicing gives a
    ``PairList`` view; ``==`` compares with another ``PairList``, a list
    or a tuple as the list of those tuples would.
    """

    __slots__ = ("ab", "sim", "_keys")
    __hash__ = None

    def __init__(self, ab: np.ndarray, sim: np.ndarray):
        ab.setflags(write=False)
        sim.setflags(write=False)
        self.ab = ab
        self.sim = sim
        self._keys = None

    @property
    def keys(self) -> np.ndarray:
        """``_sort_keys`` of the pairs, sorted (made on first use)."""
        if self._keys is None:
            self._keys = _sort_keys(self.ab)
            self._keys.setflags(write=False)
        return self._keys

    @classmethod
    def empty(cls) -> "PairList":
        return cls(np.zeros((0, 2), np.int64), np.zeros(0, np.float64))

    def merged(self, ab: np.ndarray, sim: np.ndarray) -> "PairList":
        """A new list holding these pairs and ``(ab, sim)`` (keys not
        already present, any order).  ``self`` is left as it was."""
        if not len(ab):
            return self
        if ab.min() < 0 or ab.max() >= 1 << 31:
            raise ValueError("pair ids must lie in [0, 2**31)")
        ab = np.concatenate([self.ab, ab])
        # Stable sort of one sorted run and an unsorted tail: timsort
        # merges the two runs, so the old pairs cost O(P), not O(P log P).
        order = np.argsort(_sort_keys(ab), kind="stable")
        return PairList(ab[order], np.concatenate([self.sim, sim])[order])

    def __len__(self) -> int:
        return len(self.sim)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return PairList(self.ab[i], self.sim[i])
        a, b = self.ab[i].tolist()
        return a, b, self.sim[i].item()

    def __iter__(self):
        return zip(self.ab[:, 0].tolist(), self.ab[:, 1].tolist(),
                   self.sim.tolist())

    def __eq__(self, other):
        if isinstance(other, PairList):
            return (np.array_equal(self.ab, other.ab)
                    and np.array_equal(self.sim, other.sim))
        if isinstance(other, (list, tuple)):
            return len(other) == len(self) and type(other)(self) == other
        return NotImplemented

    def __repr__(self) -> str:
        head = ", ".join(map(repr, self[:3]))
        return f"PairList([{head}{', ...' if len(self) > 3 else ''}])"


class ClusterAccumulator:
    """Incremental multi-source clustering: one union-find, shared caches.

    ``feed`` drives one candidate source through batched verification
    into the accumulator's union-find; feeding several sources in
    sequence is the engine-level mechanism behind the sharded path's
    *streamed* host merge — ``dist_lsh`` emits one edge buffer per
    band-group and ``cluster_step_output`` feeds each group as it
    arrives off the device, so the merge of group g overlaps the device
    shuffle of group g+1.  The verified-sim cache carries across feeds:
    a pair evaluated while merging group g is counted as *excluded*
    (never re-verified) when group g+1 — or the overflow fallback pass —
    emits it again, exactly like re-occurrences within a single source.

    ``stats`` holds the totals across every feed; each ``feed`` call
    also returns that source's own ``ClusterStats``.

    ``grow`` extends the union-find to cover newly allocated doc ids —
    the incremental-ingest mechanism behind ``core.session.DedupSession``
    (docs arrive chunk by chunk, one accumulator clusters them all) —
    and ``feed(source, verifier=...)`` lets one accumulator mix
    verification strategies per feed (e.g. device-registered scores for
    the sharded step's own edges, the plain host estimator for
    cross-step candidates against retained signatures) while the
    verified-sim cache and union-find stay shared.
    """

    def __init__(
        self,
        num_docs: int,
        verifier,
        edge_threshold: float,
        tree_threshold: float,
        *,
        use_disjoint_sets: bool = True,
        batch: str = "run",
        max_batch_pairs: int = 8192,
        uf: ThresholdUnionFind | None = None,
    ):
        if batch not in ("run", "band"):
            raise ValueError(f"unknown batch granularity {batch!r}")
        self.verifier = as_verifier(verifier)
        if uf is None:
            uf = ThresholdUnionFind(num_docs, tree_threshold)
        else:
            if len(uf.parent) < num_docs:
                raise ValueError(
                    f"existing uf covers {len(uf.parent)} docs, source "
                    f"has {num_docs}")
            if uf.tree_threshold != tree_threshold:
                raise ValueError(
                    f"tree_threshold {tree_threshold} does not match the "
                    f"existing uf's {uf.tree_threshold}; unions are "
                    "guarded by the uf's own threshold")
        self.uf = uf
        self.edge_threshold = float(edge_threshold)
        self.use_disjoint_sets = bool(use_disjoint_sets)
        self.batch = batch
        self.max_batch_pairs = int(max_batch_pairs)
        self.stats = ClusterStats()
        self.evaluated: dict[tuple[int, int], float] = {}
        # ``evaluated``'s first len(_sorted) entries, sorted.  Keys are
        # only ever added (here and by ``merge_cluster_rounds``' shared
        # sim cache), so its insertion-order tail is what is new.
        self._sorted = PairList.empty()

    @property
    def n_sorted(self) -> int:
        """Evaluated pairs already folded into the sorted ``pairs``."""
        return len(self._sorted)

    @property
    def pairs(self) -> PairList:
        """Every evaluated (a, b, sim), sorted, across all feeds.

        Folds in only the pairs evaluated since the last read; a list
        returned earlier keeps its contents."""
        ab = self._unsorted()
        if len(ab):
            sim = np.fromiter(
                islice(self.evaluated.values(), len(self._sorted), None),
                dtype=np.float64, count=len(ab))
            self._sorted = self._sorted.merged(ab, sim)
        return self._sorted

    def _unsorted(self) -> np.ndarray:
        """(n, 2) evaluated pairs not in the sorted list yet, in order."""
        start = len(self._sorted)
        n = len(self.evaluated) - start
        return np.fromiter(
            chain.from_iterable(islice(self.evaluated, start, None)),
            dtype=np.int64, count=2 * n).reshape(n, 2)

    @property
    def num_docs(self) -> int:
        return len(self.uf.parent)

    def grow(self, num_docs: int) -> None:
        """Extend the union-find to cover ``num_docs`` ids (no-op if it
        already does).  New ids start as singletons."""
        self.uf.grow(num_docs)

    def feed(self, source: CandidateSource,
             verifier=None) -> ClusterStats:
        """Cluster one source into the accumulator; returns its stats.

        ``verifier`` overrides the accumulator's verifier for THIS feed
        only (same shared sim cache / union-find / stats).
        """
        if len(self.uf.parent) < source.num_docs:
            raise ValueError(
                f"accumulator covers {len(self.uf.parent)} docs, source "
                f"has {source.num_docs}")
        with spans.span("merge") as sp:
            uf = self.uf
            verifier = (self.verifier if verifier is None
                        else as_verifier(verifier))
            evaluated = self.evaluated
            # Snapshot the verifier's lifetime counters so stats report
            # THIS feed's batches/seconds even when the verifier instance
            # is reused (e.g. re-clustering at a second threshold).
            batches0, seconds0 = verifier.n_batches, verifier.seconds
            stats = ClusterStats()

            def flush(pairs: np.ndarray) -> bool:
                """Verify (P, 2) pending pairs, record them in order and
                union those above the edge threshold; True if any
                union went through."""
                if not len(pairs):
                    return False
                sims = np.asarray(verifier(pairs), np.float64)
                evaluated.update(zip(
                    zip(pairs[:, 0].tolist(), pairs[:, 1].tolist()),
                    sims.tolist()))
                stats.pairs_evaluated += len(pairs)
                above = np.flatnonzero(sims > self.edge_threshold)
                stats.pairs_above_edge += len(above)
                if not self.use_disjoint_sets:
                    return False
                unions0 = uf.n_unions
                for i in above.tolist():
                    before = uf.n_unions
                    uf.union(int(pairs[i, 0]), int(pairs[i, 1]),
                             sims[i].item())
                    if uf.n_unions > before:
                        stats.unions_done += 1
                    else:
                        stats.unions_rejected += 1
                return uf.n_unions > unions0

            if self.batch == "band" and self.use_disjoint_sets:
                groups, recompressed = self._feed_arrays(
                    source, stats, flush)
            else:
                groups, recompressed = self._feed_loop(
                    source, stats, flush)

            stats.verify_batches = verifier.n_batches - batches0
            stats.verify_seconds = verifier.seconds - seconds0
            # The verify calls inside this feed, as a stat of the span
            # rather than spans of their own: with ``batch="run"`` there
            # is one call per band run, too many to trace.
            sp.count(verify_ns=round(stats.verify_seconds * 1e9),
                     groups=groups, recompressed=recompressed)
        self.stats.add(stats)
        return stats

    def _feed_loop(self, source, stats, flush) -> tuple[int, int]:
        """The scalar merge: one run at a time, in Python.  Serves
        ``batch="run"`` (a flush after every run) and a merge without
        disjoint sets.  Returns (runs of >= 2 members, 0)."""
        uf = self.uf
        evaluated = self.evaluated
        pending: list[tuple[int, int]] = []
        pending_set: set[tuple[int, int]] = set()
        groups = 0

        def flush_pending():
            flush(np.array(pending, dtype=np.int64).reshape(-1, 2))
            pending.clear()
            pending_set.clear()

        for band_runs in source.iter_bands():
            for members in band_runs.iter_groups():
                groups += 1
                m = len(members)
                stats.pairs_generated += m * (m - 1) // 2
                if self.use_disjoint_sets:
                    # "replace D with D.find()" — compress to roots.
                    uniq = np.unique([uf.find(int(d)) for d in members])
                else:
                    uniq = np.sort(members)
                k = len(uniq)
                if k < 2:
                    # All members already co-clustered: all excluded.
                    stats.pairs_excluded += m * (m - 1) // 2
                    continue
                # Pairs collapsed by prior clustering are excluded too.
                stats.pairs_excluded += (m * (m - 1) // 2
                                         - k * (k - 1) // 2)
                for ii in range(k):
                    for jj in range(ii + 1, k):
                        key = (int(uniq[ii]), int(uniq[jj]))
                        if key in evaluated or key in pending_set:
                            stats.pairs_excluded += 1
                            continue
                        pending.append(key)
                        pending_set.add(key)
                if self.batch == "run" or \
                        len(pending) >= self.max_batch_pairs:
                    flush_pending()
            if self.batch == "band":
                flush_pending()
        flush_pending()
        return groups, 0

    def _feed_arrays(self, source, stats, flush) -> tuple[int, int]:
        """``_feed_loop`` at band granularity with disjoint sets, in
        array passes: the same pending blocks, flushes, unions and
        counts, bit for bit.

        Between two flushes the roots and the evaluated set are fixed,
        so a stretch of runs compresses in one pass: the roots of every
        member (``find_many``), each run's sorted unique roots, their
        pairs in the loop's row-major order, and which pairs are new
        (neither evaluated nor pending, nor met earlier in the stretch).
        The band is cut where the loop flushes: after the first run at
        which the pending pairs reach ``max_batch_pairs``.  A flush that unions changes roots, so the
        runs after its cut are compressed again.  A stretch holds about
        twice the runs that could fill the block, so that work stays
        bounded.  Returns (runs of >= 2 members, runs compressed again).
        """
        uf = self.uf
        cap = self.max_batch_pairs
        # A pair evaluated or pending is in ``old`` (the sorted list) or
        # in ``recent`` (evaluated since it was sorted, or pending or
        # evaluated in this feed): folding ``recent`` into the sorted
        # list would re-sort every pair on each feed.
        old = self._sorted.keys
        recent = np.sort(_sort_keys(self._unsorted()))
        pending: list[np.ndarray] = []
        n_pending = groups = recompressed = 0

        for band_runs in source.iter_bands():
            starts, ends = band_runs.run_starts, band_runs.run_ends
            multi = ends - starts >= 2
            starts, ends = starts[multi], ends[multi]
            groups += len(starts)
            m = ends - starts
            # Pairs the runs before each run generate (the run's, too,
            # at the end): an upper bound on the new pairs.
            n_pairs = np.concatenate([[0], np.cumsum(m * (m - 1) // 2)])
            g = 0
            while g < len(starts):
                # The stretch [g, w): enough runs to fill the block twice
                # over if no pair were excluded.
                w = int(np.searchsorted(
                    n_pairs, n_pairs[g] + 2 * (cap - n_pending)))
                w = min(max(w, g + 1), len(starts))
                keys, run = _run_root_pairs(
                    uf, band_runs.sorted_docs, starts[g:w], ends[g:w])
                new = ~(_in_sorted(old, keys) | _in_sorted(recent, keys))
                first = np.zeros(len(keys), bool)
                first[np.unique(keys, return_index=True)[1]] = True
                new &= first
                n_new = np.concatenate([[0], np.cumsum(
                    np.bincount(run[new], minlength=w - g))])
                # Commit runs [c, e) a block at a time: e - 1 is the run
                # at which the pending pairs reach ``cap``, or the last.
                c = 0
                while c < w - g:
                    e = int(np.searchsorted(n_new,
                                            n_new[c] + cap - n_pending))
                    full = e <= w - g
                    e = min(e, w - g)
                    lo, hi = np.searchsorted(run, [c, e])
                    took = keys[lo:hi][new[lo:hi]]
                    generated = int(n_pairs[g + e] - n_pairs[g + c])
                    stats.pairs_generated += generated
                    stats.pairs_excluded += generated - len(took)
                    pending.append(took)
                    n_pending += len(took)
                    recent = _merge_sorted(recent, np.sort(took))
                    c = e
                    if full:
                        unioned = flush(_pairs_of(pending))
                        pending, n_pending = [], 0
                        if unioned:
                            recompressed += w - g - c
                            break
                g += c
            flush(_pairs_of(pending))
            pending, n_pending = [], 0
        return groups, recompressed


def cluster_source(
    source: CandidateSource,
    verifier,
    edge_threshold: float,
    tree_threshold: float,
    *,
    use_disjoint_sets: bool = True,
    batch: str = "run",
    max_batch_pairs: int = 8192,
    uf: ThresholdUnionFind | None = None,
) -> tuple[ThresholdUnionFind, ClusterStats, PairList]:
    """Run the staged engine over a candidate source.

    ``verifier`` is a ``verify.BatchVerifier`` or a scalar
    ``fn(a, b) -> float`` (wrapped via ``verify.as_verifier``).
    Returns (union-find, stats, evaluated pairs) — the same contract
    the historical ``cluster_bands`` had, the pairs as a sorted
    ``PairList`` of (a, b, sim).

    With ``use_disjoint_sets=False`` every candidate pair is evaluated
    (the paper's non-clustered baseline behind Table 5's "6388 pairs").

    Passing an existing ``uf`` accumulates this source's clustering into
    it instead of starting fresh — the retry path for the sharded step's
    overflow fallback: docs already co-clustered by a previous pass are
    excluded up front, only the remainder is re-verified.  For feeding
    several sources with a shared verified-sim cache (the streamed
    per-band-group merge), use ``ClusterAccumulator`` directly.
    """
    acc = ClusterAccumulator(
        source.num_docs, verifier, edge_threshold, tree_threshold,
        use_disjoint_sets=use_disjoint_sets, batch=batch,
        max_batch_pairs=max_batch_pairs, uf=uf)
    stats = acc.feed(source)
    return acc.uf, stats, acc.pairs


def merge_cluster_rounds(
    uf: ThresholdUnionFind,
    verifier,
    edge_threshold: float,
    *,
    max_batch_pairs: int = 8192,
    roots=None,
    candidate_pairs=None,
    sim_cache: dict | None = None,
) -> int:
    """Paper §10's second clustering round, batch-verified.

    Compares cluster REPRESENTATIVES and merges clusters whose reps are
    highly similar (fixes the over-partitioning the disjoint-set pass can
    produce — Table 7's 56 'diff-set high-similarity' pairs).  The (i, j)
    sweep is processed in blocks of ``max_batch_pairs``: each block's
    still-distinct current-root pairs go through the verifier in one
    dispatch, then the block's merges are applied in sweep order (rare
    pairs whose roots changed mid-block fall back to a singleton
    dispatch).  The verified-sim cache (``sim_at``) is shared across
    blocks: a doc pair's similarity is deterministic, so a root pair
    that re-appears in a later block — mid-sweep unions redirect
    ``find`` onto roots scored earlier — reuses the cached value instead
    of a redundant singleton dispatch.  Semantics match the historical
    O(roots^2) scalar loop — sims are always between *current* roots at
    union time — with O(block) memory for the batch buffer.  Returns
    #merges.

    Incremental-session hooks (``DedupSession.refine``, DESIGN.md §7):

    * ``roots`` — explicit representative candidates (any docs; each is
      compressed to its current root).  Skips the O(all docs) root scan
      — the retention layer already knows the live root set.
    * ``candidate_pairs`` — (E, 2) doc-id pairs to sweep INSTEAD of the
      full (i, j) cross product (e.g. band collisions among re-banded
      representatives); each endpoint is compressed to its current root
      at processing time, so chained merges behave exactly like the
      full sweep restricted to those pairs.
    * ``sim_cache`` — external ``{(a, b): sim}`` dict shared with the
      caller (the accumulator's verified-sim cache): sims the session
      already verified are never re-dispatched, and sims this round
      computes become visible to later feeds.
    """
    verifier = as_verifier(verifier)
    if candidate_pairs is not None:
        cand = np.asarray(candidate_pairs, dtype=np.int64).reshape(-1, 2)
        if len(cand) == 0:
            return 0
        sweep = [(int(a), int(b)) for a, b in cand]
    else:
        if roots is None:
            roots = range(len(uf.parent))
        roots = sorted({uf.find(int(r)) for r in roots})
        if len(roots) < 2:
            return 0
        sweep = None  # generated lazily below (O(R^2) pairs)

    def blocks():
        block = []
        if sweep is not None:
            for a, b in sweep:
                block.append((a, b))
                if len(block) >= max_batch_pairs:
                    yield block
                    block = []
        else:
            for i in range(len(roots)):
                for j in range(i + 1, len(roots)):
                    block.append((roots[i], roots[j]))
                    if len(block) >= max_batch_pairs:
                        yield block
                        block = []
        if block:
            yield block

    merges = 0
    sim_at = sim_cache if sim_cache is not None else {}
    for block in blocks():
        want = []
        want_set = set()
        for x, y in block:
            a, b = uf.find(x), uf.find(y)
            key = (min(a, b), max(a, b))
            if a != b and key not in sim_at and key not in want_set:
                want_set.add(key)
                want.append(key)
        if want:
            for key, s in zip(want, verifier(np.array(want,
                                                      dtype=np.int64))):
                sim_at[key] = float(s)
        for x, y in block:
            a, b = uf.find(x), uf.find(y)
            if a == b:
                continue
            key = (min(a, b), max(a, b))
            sim = sim_at.get(key)
            if sim is None:
                # Roots changed due to a union earlier in this block.
                sim = float(verifier(np.array([key], dtype=np.int64))[0])
                sim_at[key] = sim
            if sim > edge_threshold and uf.union(a, b, sim):
                merges += 1
    return merges
