"""Incremental multi-step ingest: one ``DedupSession`` over every path.

The paper's pipeline is batch-shaped (shingle -> MinHash -> LSH ->
verify -> disjoint sets) but the corpus it targets is continuously fed:
10M+ notes arrive in chunks.  ``DedupSession`` owns the long-lived
clustering state —

* ONE ``engine.ClusterAccumulator`` (union-find + verified-sim cache +
  cumulative ``ClusterStats``),
* global doc-id allocation (``DocIdAllocator`` — the single home of the
  ``doc_id_base`` / ``doc_offsets`` arithmetic the three drivers used
  to re-implement by hand),
* retained per-doc signature rows (one growing verifier), and
* a retained band index for cross-step candidate generation,

and exposes host, streaming, and sharded **backends** behind the same
``ingest(chunk) -> ClusterSnapshot`` API (DESIGN.md §6).  Each chunk
contributes two candidate families:

* *within-chunk*: the backend's native source — host band matrix,
  Design-2 store scan, or the sharded step's prescreened edge buffers;
* *cross-step*: band collisions of the chunk's band values against the
  retained index (same doc re-shingled, near-dups split across chunks)
  become explicit edges verified through the same engine.

The candidate-pair SET over N chunks equals the one-shot run over the
concatenated corpus (band collision is chunk-independent); only the
feed order differs, and ``ClusterAccumulator`` is order-invariant over
an edge set (pinned by the hypothesis test in
``tests/test_staged_engine.py``), so snapshot-after-every-chunk ends at
the one-shot clustering with bit-identical per-edge sims.

The sharded backend feeds several ``make_streamed_dedup_step``
invocations into the one accumulator; ``ingest_stream`` keeps a
one-chunk lookahead so the host merge of step t overlaps the device
shuffle of step t+1 (the same overlap the band groups give WITHIN a
step, lifted across steps).

The historical drivers are thin adapters over this layer:
``pipeline.DedupPipeline.run`` is a one-shot host ingest,
``streaming.StreamingDedup.cluster`` snapshots a session over its own
band store, and ``dist_lsh.cluster_step_output`` is the one-step
sharded merge (both call ``dist_lsh.feed_step_groups``).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field, replace
from typing import Iterable, Iterator

import numpy as np
import jax.numpy as jnp

from repro.core import lsh, minhash, shingle, spans
from repro.core.bandstore import SqliteBandStore
from repro.core.candidates import BandMatrixSource, ShardedEdgeSource
from repro.core.engine import (
    ClusterAccumulator,
    ClusterStats,
    PairList,
    merge_cluster_rounds,
)
from repro.core.pipeline import DedupConfig
from repro.core.retention import (
    BandBloomFilter,
    RetentionManager,
    RetentionPolicy,
)
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import (
    BatchVerifier,
    DeviceScoredEdgeVerifier,
    ExactJaccardVerifier,
    SignatureVerifier,
    as_verifier,
)

BACKENDS = ("host", "streaming", "sharded")


class DocIdAllocator:
    """Global doc-id allocation for chunked ingest (one home for the
    ``doc_id_base`` arithmetic).

    ``allocate(n)`` hands out the next contiguous block and returns its
    base; ``device_offsets(base, d_loc, n_dev)`` is the per-device
    ``doc_offsets`` convention of the sharded step (device i's first
    row is ``base + i * d_loc``).  Padding rows a backend appends for
    divisibility live ABOVE the allocated block (ids >= base + n), so
    they can never alias a later chunk's ids — they are range-filtered
    before any of them reaches the engine.
    """

    def __init__(self, base: int = 0):
        self.base = int(base)
        self.next = int(base)

    @property
    def n_docs(self) -> int:
        """Exclusive upper bound of allocated ids (gap ids included)."""
        return self.next

    def allocate(self, n: int) -> int:
        base = self.next
        self.next += int(n)
        return base

    @staticmethod
    def device_offsets(base: int, d_loc: int, n_dev: int) -> np.ndarray:
        return np.uint32(base) + np.uint32(d_loc) * np.arange(
            n_dev, dtype=np.uint32)


class BandIndex:
    """Retained band values of every ingested doc, keyed for collision.

    ``match_then_insert`` is the cross-step candidate generator: the
    chunk's band values are looked up against the retained state —
    every (band, value) hit against an EARLIER chunk emits an
    (old_doc, new_doc) edge — and then inserted, so a later chunk can
    collide with this one.  Same-chunk collisions are never emitted
    (the backend's within-chunk source owns those); old-vs-old pairs
    were emitted when the old chunk arrived.

    Bounded retained state (DESIGN.md §7): with ``track_entries`` the
    index keeps a per-doc reverse map so ``evict`` can rewrite an
    evicted doc's bucket entries onto its cluster root — membership
    hits keep producing candidate pairs against *retained* docs, and
    the engine compresses to roots anyway, so eviction alone changes no
    clustering outcome.  The unbounded dimension is the KEY count
    (every unique band value ever seen); ``key_budget`` caps it per
    band by compacting the least-recently-HIT keys into a per-band
    ``BandBloomFilter`` (hits refresh recency — a true LRU, so a hot
    key recurring every chunk is never compacted).  A later hit on a
    compacted key is counted in
    ``filter_only_hits`` — the value was seen before, but by a doc the
    index can no longer name, so the pair cannot be re-verified (the
    LSHBloom recall trade).
    """

    def __init__(self, num_bands: int, *, key_budget: int | None = None,
                 bloom_bits: int = 1 << 17, bloom_hashes: int = 4,
                 track_entries: bool = False):
        # A bucket of one doc holds the bare id, a larger one a list:
        # with a list per key, every full garbage collection walked
        # one list per (band, note), a pause that grew with the index.
        self._maps: list[dict[tuple[int, int], int | list[int]]] = [
            {} for _ in range(num_bands)]
        self._key_budget = key_budget
        self._bloom_bits = int(bloom_bits)
        self._bloom_hashes = int(bloom_hashes)
        self._filters: list[BandBloomFilter | None] = [None] * num_bands
        self._entries: dict[int, list] | None = (
            {} if track_entries else None)
        self.filter_only_hits = 0
        self.compacted_keys = 0

    @property
    def num_bands(self) -> int:
        return len(self._maps)

    def _filter(self, j: int) -> BandBloomFilter:
        if self._filters[j] is None:
            self._filters[j] = BandBloomFilter(
                self._bloom_bits, self._bloom_hashes)
        return self._filters[j]

    def match_then_insert(self, bands: np.ndarray,
                          doc_id_base: int) -> np.ndarray:
        """(C, b, 2) chunk bands -> (E, 2) int64 cross-step edges."""
        bands = np.asarray(bands)
        if bands.ndim != 3 or bands.shape[1] != self.num_bands:
            raise ValueError(
                f"expected (C, {self.num_bands}, 2) bands, "
                f"got {bands.shape}")
        edges: list[tuple[int, int]] = []
        for j, m in enumerate(self._maps):
            col = bands[:, j, :]
            flt = self._filters[j]
            for i in range(len(col)):
                key = (int(col[i, 0]), int(col[i, 1]))
                new_id = doc_id_base + i
                # A hit is popped and put back: the budget sweep pops
                # from the FRONT of the dict, so a hit must move its
                # key to the end or a HOT key (a duplicate recurring
                # every chunk) would be compacted by insertion age and
                # break the within-window parity invariant.
                olds = m.pop(key, None)
                if olds is not None:
                    if not isinstance(olds, list):
                        olds = [olds]
                    edges.extend((old, new_id) for old in olds
                                 if old < doc_id_base)
                    olds.append(new_id)
                    m[key] = olds
                else:
                    if flt is not None and key in flt:
                        # Seen before, partner compacted away: the pair
                        # can no longer be exactly re-verified.
                        self.filter_only_hits += 1
                    m[key] = new_id
                if self._entries is not None:
                    self._entries.setdefault(new_id, []).append((j, key))
            if self._key_budget is not None:
                while len(m) > self._key_budget:
                    old_key = next(iter(m))
                    del m[old_key]
                    self._filter(j).add(old_key)
                    self.compacted_keys += 1
        if not edges:
            return np.zeros((0, 2), dtype=np.int64)
        return np.array(edges, dtype=np.int64)

    def evict(self, doc_ids, root_of) -> None:
        """Rewrite evicted docs' bucket entries onto their cluster root.

        ``root_of`` maps a doc id to its current union-find root (the
        retained representative).  The root inherits the evicted doc's
        (band, key) entries — re-homed in the reverse map so a later
        eviction of a deposed root keeps working — and is inserted into
        the bucket at most once, so bucket lists shrink onto the
        retained set instead of growing with cluster size.
        """
        if self._entries is None:
            raise ValueError(
                "BandIndex was built without track_entries; eviction "
                "needs the per-doc reverse map")
        for d in doc_ids:
            d = int(d)
            for j, key in self._entries.pop(d, ()):
                olds = self._maps[j].get(key)
                if olds is None:
                    continue               # key already compacted
                if not isinstance(olds, list):
                    if olds == d:          # the doc's own bucket of one
                        r = int(root_of(d))
                        self._maps[j][key] = r
                        self._entries.setdefault(r, []).append((j, key))
                    continue
                try:
                    olds.remove(d)
                except ValueError:
                    continue               # key was compacted + re-seen
                r = int(root_of(d))
                if r not in olds:
                    olds.append(r)
                    self._entries.setdefault(r, []).append((j, key))

    def export_maps(self) -> tuple:
        """Frozen per-band bucket maps for a ``SessionView``.

        Each band's ``{(hi, lo): [doc ids]}`` dict is copied with its
        bucket lists frozen to tuples, so a published view's probe
        results can never be changed by a later ``match_then_insert``
        or ``evict`` (DESIGN.md §9).  Pure read — recency (the LRU
        compaction order) is NOT refreshed.
        """
        return tuple({k: tuple(v) if isinstance(v, list) else (v,)
                      for k, v in m.items()}
                     for m in self._maps)

    def export_filters(self) -> tuple:
        """Frozen per-band Bloom filters for a ``SessionView`` (copies;
        a concurrent compaction's ``add`` cannot flip bits mid-probe)."""
        return tuple(f.copy() if f is not None else None
                     for f in self._filters)

    def stats(self) -> dict:
        """Memory/recall accounting for reports and the soak benchmark."""
        return {
            "n_keys": sum(len(m) for m in self._maps),
            "n_entries": sum(len(v) if isinstance(v, list) else 1
                             for m in self._maps for v in m.values()),
            "n_docs_tracked": (len(self._entries)
                               if self._entries is not None else 0),
            "compacted_keys": self.compacted_keys,
            "filter_only_hits": self.filter_only_hits,
            "bloom_bytes": sum(f.memory_bytes for f in self._filters
                               if f is not None),
        }


@dataclass(frozen=True)
class ClusterSnapshot:
    """Cluster state after an ``ingest`` call — a pure VALUE object.

    Every public field is a copy or a frozen value (``labels`` is frozen
    read-only, ``stats`` is a counter copy, ``pairs`` is a ``PairList``
    over frozen sorted columns that later ingests extend into new
    arrays) or an immutable scalar: holding a snapshot
    never pins live session state, and later ingests cannot change what
    a snapshot already reported.  The LIVE handles moved off the public
    surface in PR 7 — ``DedupSession.uf`` is the live union-find, and
    the read path goes through the immutable ``SessionView``
    (``DedupSession.view``, DESIGN.md §9).  The deprecated ``uf``
    property still serves old call sites via the private ``_uf`` handle.
    """

    n_docs: int                 # docs ingested so far (id upper bound)
    labels: np.ndarray          # (n_docs,) cluster root per doc (frozen)
    stats: ClusterStats         # cumulative engine counters (a copy)
    pairs: PairList             # every evaluated (a, b, sim) so far (frozen)
    overflow: int = 0           # sharded: device buffer overflow so far
    retried: int = 0            # sharded: overflow fallback passes run
    device_scored: int = 0      # sharded stage2=device: pass-throughs
    host_rescored: int = 0      # sharded stage2=device: host re-scores
    row_overflow: int = 0       # sharded: cross-shard row-buffer overflow
    # Retained-state view (bounded-memory sessions, DESIGN.md §7):
    retained_rows: int = 0      # live verifier rows (== n_docs unevicted)
    evicted: int = 0            # rows released by the retention policy
    filter_only_hits: int = 0   # band hits whose partner was compacted
    refine_merges: int = 0      # second-round merges so far
    representatives: np.ndarray | None = None  # retained roots (sorted)
    _uf: ThresholdUnionFind | None = field(default=None, repr=False,
                                           compare=False)

    @property
    def uf(self) -> ThresholdUnionFind | None:
        """Deprecated: the LIVE union-find (not part of the snapshot's
        value semantics).  Use ``DedupSession.uf`` for live clustering
        state, or ``labels`` for the frozen per-doc roots."""
        warnings.warn(
            "ClusterSnapshot.uf is deprecated: snapshots are pure value "
            "objects; use DedupSession.uf for the live union-find or "
            "ClusterSnapshot.labels for the frozen roots",
            DeprecationWarning, stacklevel=2)
        return self._uf

    @property
    def num_clusters(self) -> int:
        """Duplicate clusters, i.e. components of size >= 2."""
        _, counts = np.unique(self.labels, return_counts=True)
        return int((counts >= 2).sum())

    @property
    def num_duplicates(self) -> int:
        """Docs that are non-representative members of some cluster."""
        return self.n_docs - len(set(self.labels.tolist()))

    def clusters(self, min_size: int = 2) -> list[list[int]]:
        groups: dict[int, list[int]] = {}
        for i, r in enumerate(self.labels):
            groups.setdefault(int(r), []).append(i)
        return [v for v in groups.values() if len(v) >= min_size]


@dataclass(frozen=True)
class ExactRowsView:
    """Frozen exact-verifier rows inside a ``SessionView`` (host
    exact-verification sessions).

    ``vocab`` is shared with the live verifier BY REFERENCE: interning
    is append-only (an n-gram's id never changes once assigned), so
    read-only lookups stay valid across later ingests; the read path
    must only ever ``get`` from it, never ``setdefault``.
    """

    ids: np.ndarray             # (R, lmax) padded sorted n-gram id rows
    lengths: np.ndarray         # (R,) real row lengths
    slot_of: dict | None        # doc -> row (eviction layout; None = id)
    vocab: dict                 # n-gram -> id (append-only, shared)
    ngram: int

    def row_for(self, doc: int) -> np.ndarray:
        slot = doc if self.slot_of is None else self.slot_of[doc]
        return self.ids[slot][: int(self.lengths[slot])]


@dataclass(frozen=True)
class SessionView:
    """Immutable read-path handle over a ``DedupSession`` (DESIGN.md §9).

    Published atomically (one attribute swap on the session) at the end
    of an ingest: a query running against a view can never race a
    concurrent ingest or retention sweep, because everything it touches
    is either a frozen copy (labels, band maps, Bloom filters, the
    eviction-mode row matrix) or an append-only buffer whose visible
    rows are never rewritten (the unevicted signature/token matrices —
    see ``SignatureVerifier.frozen_rows``).  Two consecutive views share
    those append-only buffers, so publication is O(band-index entries),
    not O(corpus).

    ``core.query`` implements probe/verify over a view;
    ``serving.dedup_service.DedupQueryService`` serves it.
    """

    version: int                # monotone publication counter
    n_docs: int                 # docs covered (labels bound)
    edge_threshold: float       # the engine's duplicate threshold
    num_bands: int
    rows_per_band: int
    labels: np.ndarray          # (n_docs,) cluster root per doc (frozen)
    band_maps: tuple            # per band: {(hi, lo): (doc ids,)}
    band_filters: tuple         # per band: BandBloomFilter | None
    signatures: np.ndarray      # retained rows (estimate sessions)
    slot_of: dict | None        # doc -> signature row (eviction layout)
    exact: ExactRowsView | None = None   # exact-verification sessions
    # Disk-tier sessions (DedupConfig.store="sqlite", DESIGN.md §12):
    # the live SqliteBandStore the read path delegates probes to (its
    # ``probe_keys`` is a pure Bloom-first read) instead of exporting
    # the whole on-disk index into host dicts per publication.
    # ``band_maps``/``band_filters`` are empty then.  The trade: probe
    # results reflect the store at QUERY time, so a stale view held
    # across later ingests can see newer entries (bounded to its own
    # ``n_docs`` coverage by the probe's id filter) — the memory tier
    # keeps strict frozen-at-publication semantics.
    band_store: SqliteBandStore | None = None
    # Device-probe index cache (``core.query``): derived read-only from
    # the frozen band maps, built lazily on the first large query batch
    # and reused for the view's lifetime.  Excluded from eq/repr — it
    # is a cache, not state.
    _probe_cache: dict = field(default_factory=dict, repr=False,
                               compare=False)

    @property
    def mode(self) -> str:
        return "exact" if self.exact is not None else "estimate"

    def root_of(self, doc: int) -> int:
        return int(self.labels[doc])

    def slot_index(self, ids: np.ndarray) -> np.ndarray:
        """Global doc ids -> physical signature rows (eviction-aware)."""
        ids = np.asarray(ids, dtype=np.int64)
        if self.slot_of is None:
            return ids
        so = self.slot_of
        return np.fromiter((so[int(i)] for i in ids.ravel()),
                           dtype=np.int64,
                           count=ids.size).reshape(ids.shape)

    def rows_for(self, doc_ids) -> np.ndarray:
        """Retained signature rows for ``doc_ids`` at publication time."""
        ids = np.asarray(doc_ids, dtype=np.int64)
        if ids.size == 0:
            return np.zeros((0,) + self.signatures.shape[1:],
                            dtype=self.signatures.dtype)
        return self.signatures[self.slot_index(ids)]


class DedupSession:
    """Long-lived incremental dedup over host/streaming/sharded backends.

    ``ingest(chunk)`` clusters one chunk of documents into the session
    and returns a cumulative ``ClusterSnapshot``; ``ingest_stream``
    pipelines a sequence of chunks (sharded backend: the host merge of
    step t overlaps the device shuffle of step t+1).

    Backends:

    * ``"host"`` — in-memory band matrix per chunk; verification is
      exact Jaccard or the signature estimate per
      ``config.exact_verification`` (same semantics as
      ``DedupPipeline``).
    * ``"streaming"`` — chunks are written to a Design-2 band store
      (``StreamingDedup`` phase 1); each ingest re-scans the store
      band-major (the paper's phase 2) through the accumulator, whose
      verified-sim cache makes the re-scan cheap (no pair is ever
      re-verified).
    * ``"sharded"`` — each chunk runs one
      ``dist_lsh.make_streamed_dedup_step`` invocation with
      ``doc_offsets`` from the allocator; the band-group buffers feed
      the session accumulator via ``dist_lsh.feed_step_groups``, and
      ``stage2="device"`` scores (incl. cross-shard, via the exchanged
      row buffers) register with the session's long-lived
      ``DeviceScoredEdgeVerifier``.

    All backends share the cross-step ``BandIndex`` pass except
    streaming, whose store re-scan already covers cross-chunk
    collisions (the store IS the retained state there).
    """

    def __init__(
        self,
        config: DedupConfig | None = None,
        backend: str = "host",
        *,
        dist_config=None,
        mesh=None,
        store_path: str = ":memory:",
        chunk_docs: int = 512,
        doc_id_base: int = 0,
        verifier: BatchVerifier | None = None,
        stream: bool | None = None,
        retention: RetentionPolicy | None = None,
        _adopt_streaming=None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"one of {BACKENDS}")
        self.config = config or DedupConfig()
        self.backend = backend
        self.allocator = DocIdAllocator(doc_id_base)
        self._verifier = as_verifier(verifier) if verifier is not None \
            else None
        self._external_verifier = verifier is not None
        self.acc = ClusterAccumulator(
            int(doc_id_base), _NullVerifier(), self.config.edge_threshold,
            self.config.tree_threshold,
            use_disjoint_sets=self.config.use_disjoint_sets,
            batch=self.config.verify_batch)
        self.retention = (RetentionManager(retention)
                          if retention is not None else None)
        if self.retention is not None:
            # Incremental root-representative tracking: each union logs
            # its deposed root so eviction sweeps never scan all docs.
            self.acc.uf.track_deposed = True
        # Cross-step band index tier (DESIGN.md §12): "memory" keeps the
        # host dict index; "sqlite" retains it disk-resident behind
        # Bloom-first lookups (same match/insert/evict semantics — the
        # cross-tier parity pins depend on it).  The streaming backend's
        # retained state is its band STORE, so its (unused) index stays
        # in memory regardless.
        index_cls = (SqliteBandStore
                     if self.config.store == "sqlite"
                     and backend != "streaming" else BandIndex)
        index_kw = {"path": store_path} if index_cls is SqliteBandStore \
            else {}
        self.band_index = index_cls(
            num_bands=self.config.num_bands,
            key_budget=(retention.band_key_budget
                        if retention is not None else None),
            bloom_bits=(retention.bloom_bits if retention is not None
                        else 1 << 17),
            bloom_hashes=(retention.bloom_hashes
                          if retention is not None else 4),
            track_entries=retention is not None, **index_kw)
        self.seeds = minhash.default_seeds(self.config.num_hashes)
        self.overflow = 0
        self.retried = 0
        self.row_overflow = 0
        self.steps_ingested = 0
        self.refine_merges = 0
        self.refines_run = 0
        # Docs whose merge has completed — snapshots cover these.  With
        # ingest_stream's one-chunk lookahead the allocator runs ahead
        # of the merges, so the two counters differ transiently.
        self.n_merged = int(doc_id_base)
        self._finalized = False
        # Read-path publication state (SessionView, DESIGN.md §9).
        self._view_cache: SessionView | None = None
        self._view_key = None
        self._view_version = 0
        if backend == "host":
            self._impl = _HostBackend(self)
        elif backend == "streaming":
            self._impl = _StreamingBackend(self, store_path=store_path,
                                           chunk_docs=chunk_docs,
                                           adopt=_adopt_streaming)
        else:
            self._impl = _ShardedBackend(self, dist_config=dist_config,
                                         mesh=mesh, stream=stream)

    @classmethod
    def over_store(cls, sd, *, config: DedupConfig | None = None,
                   verifier: BatchVerifier | None = None) -> "DedupSession":
        """Adopt an already-populated ``StreamingDedup`` (store + sig
        cache) and cluster its contents as one pre-ingested step.

        This is the adapter behind ``StreamingDedup.cluster``: the
        band-major phase-2 scan runs through a session accumulator, and
        the returned session stays live — further ``ingest`` calls
        append to the same store and union-find.  ``sd.n_docs`` may
        exceed the contiguous allocation (resumed-ingest gaps); gap ids
        have no store rows, so they stay singletons.
        """
        sess = cls(config=config or sd.config, backend="streaming",
                   verifier=verifier, _adopt_streaming=sd)
        sess.allocator.next = sd.n_docs
        sess.n_merged = sd.n_docs
        if verifier is None and sd.n_ingested:
            # Full (n_docs, M) global-id matrix, gap rows zero — keeps
            # "row i == doc i" for the adopted docs and later ingests.
            sess._verifier = sd.default_verifier()
        sess.acc.grow(sd.n_docs)
        sess.acc.feed(sd.candidate_source(), verifier=sess._verifier)
        sess.steps_ingested += 1
        return sess

    # -- state -------------------------------------------------------------

    @property
    def n_docs(self) -> int:
        """Docs fully ingested (merged) so far — snapshot coverage."""
        return self.n_merged

    @property
    def stats(self) -> ClusterStats:
        return self.acc.stats

    @property
    def uf(self) -> ThresholdUnionFind:
        return self.acc.uf

    @property
    def verifier(self) -> BatchVerifier | None:
        return self._verifier

    @property
    def signatures(self) -> np.ndarray:
        """The retained signature matrix, row i == doc i until the
        retention policy evicts a row (``verifier.rows_for`` is the
        eviction-aware accessor).

        Owned by the session's verifier (one copy, grown in place);
        empty for exact-mode or external-verifier sessions, which do
        not verify through signatures.
        """
        sig = getattr(self._verifier, "signatures", None)
        if sig is None:
            return np.zeros((0, self.config.num_hashes), dtype=np.uint32)
        return sig

    def snapshot(self) -> ClusterSnapshot:
        with spans.span("snapshot") as sp:
            v = self._verifier
            retained = getattr(v, "n_live_rows", None)
            labels = self.uf.components()[: self.n_docs]
            labels.setflags(write=False)
            n_sorted = self.acc.n_sorted
            pairs = self.acc.pairs
            snap = ClusterSnapshot(
                n_docs=self.n_docs,
                labels=labels,
                stats=replace(self.acc.stats),
                pairs=pairs,
                _uf=self.uf,
                overflow=self.overflow,
                retried=self.retried,
                device_scored=getattr(v, "n_passthrough", 0),
                host_rescored=getattr(v, "n_rescored", 0),
                row_overflow=self.row_overflow,
                retained_rows=(retained if retained is not None
                               else self.n_docs),
                evicted=(self.retention.n_evicted
                         if self.retention is not None else 0),
                filter_only_hits=self.band_index.filter_only_hits,
                refine_merges=self.refine_merges,
                representatives=(np.array(self.retention.representatives(),
                                          dtype=np.int64)
                                 if self.retention is not None else None),
            )
            sp.count(pairs=len(pairs), sorted=len(pairs) - n_sorted)
        return snap

    # -- read path (SessionView publication, DESIGN.md §9) -------------------

    def _view_state_key(self) -> tuple:
        """Covers every mutation that can change a view's contents."""
        return (self.steps_ingested, self.n_merged, self.refines_run,
                self.acc.stats.unions_done,
                self.retention.n_evicted if self.retention is not None
                else 0,
                self.band_index.compacted_keys)

    def view(self) -> SessionView:
        """The current immutable read-path handle over this session.

        Built on first read after a mutation and cached — the cache
        swap is the atomic publication, and the publication key covers
        every state-mutating counter (ingest steps, merges, unions,
        refines, evictions, band compaction), so the SAME object comes
        back until the session actually changes.  Queries holding an
        older view keep working unchanged across later ingests: their
        frozen copies never see them (see ``SessionView``).

        The streaming backend keeps its retained state in the band
        store, not the cross-step ``BandIndex``, so it has nothing to
        probe; use a host or sharded session for the query service.
        """
        if self.backend == "streaming":
            raise ValueError(
                "SessionView needs a backend that maintains the "
                "cross-step BandIndex (host or sharded); the streaming "
                "backend's retained state is its band store")
        key = self._view_state_key()
        if self._view_cache is not None and self._view_key == key:
            return self._view_cache
        labels = self.uf.components()[: self.n_docs]
        labels.setflags(write=False)
        cfg = self.config
        v = self._verifier
        empty_sig = np.zeros((0, cfg.num_hashes), dtype=np.uint32)
        exact = None
        sig, slot_of = empty_sig, None
        if isinstance(v, ExactJaccardVerifier):
            if v._vocab is None or v._ngram is None:
                raise ValueError(
                    "exact verifier was built from raw id rows (no "
                    "vocab/ngram); the read path cannot intern query "
                    "documents — build it with from_token_lists")
            ids, lengths, slot = v.frozen_rows()
            exact = ExactRowsView(ids=ids, lengths=lengths, slot_of=slot,
                                  vocab=v._vocab, ngram=v._ngram)
        elif isinstance(v, SignatureVerifier):
            sig, slot_of = v.frozen_rows()
        elif v is not None and self.n_docs > self.allocator.base:
            raise ValueError(
                "SessionView needs retained signature or token rows; "
                "external callback verifiers keep neither — pass a "
                "SignatureVerifier/ExactJaccardVerifier instead")
        if isinstance(self.band_index, SqliteBandStore):
            # Disk tier: don't haul the whole on-disk index into host
            # dicts per publication — the view probes the store's pure
            # Bloom-first read path instead (see SessionView.band_store).
            band_maps, band_filters = (), ()
            band_store = self.band_index
        else:
            band_maps = self.band_index.export_maps()
            band_filters = self.band_index.export_filters()
            band_store = None
        view = SessionView(
            version=self._view_version + 1,
            n_docs=self.n_docs,
            edge_threshold=cfg.edge_threshold,
            num_bands=cfg.num_bands,
            rows_per_band=cfg.rows_per_band,
            labels=labels,
            band_maps=band_maps,
            band_filters=band_filters,
            signatures=sig,
            slot_of=slot_of,
            exact=exact,
            band_store=band_store,
        )
        # The one sanctioned read-path mutation: this cache swap IS the
        # atomic single-writer publication protocol (DESIGN.md §9) —
        # same key, same object; queries never observe a half-built view.
        # repro-lint: disable=RPR002
        self._view_version = view.version
        self._view_cache, self._view_key = view, key  # repro-lint: disable=RPR002
        return view

    # -- ingest ------------------------------------------------------------

    def _check_live(self):
        if self._finalized:
            raise ValueError(
                "this session was finalized by a one-shot ingest "
                "(DedupPipeline.run adapter) and skipped the cross-step "
                "index; start a fresh DedupSession for chunked ingest")

    def ingest(self, texts: Iterable[str]) -> ClusterSnapshot:
        """Cluster one chunk of documents; returns a cumulative snapshot."""
        self._check_live()
        with spans.span("ingest"):
            pending = self._impl.dispatch(list(texts))
            self._impl.merge(pending)
            self._post_merge()
            return self.snapshot()

    def ingest_tokens(self,
                      token_lists: list[list[str]]) -> ClusterSnapshot:
        """``ingest`` over pre-tokenized documents."""
        self._check_live()
        with spans.span("ingest"):
            pending = self._impl.dispatch(list(token_lists),
                                          tokenized=True)
            self._impl.merge(pending)
            self._post_merge()
            return self.snapshot()

    def ingest_stream(
        self, chunks: Iterable[list], *, tokenized: bool = False,
    ) -> Iterator[ClusterSnapshot]:
        """Pipelined multi-chunk ingest: one-chunk dispatch lookahead.

        Chunk t+1's device work (sharded backend: signature compute +
        every band-group's all_to_all shuffle) is dispatched BEFORE
        chunk t's host merge runs, so the merge of step t overlaps the
        shuffle of step t+1.  Yields the cumulative snapshot after each
        chunk, in order; results are identical to sequential ``ingest``
        calls (dispatch only allocates ids and launches device work —
        the merges still run in chunk order against the same
        accumulator and retained index).

        ``tokenized=True`` streams pre-tokenized chunks (lists of token
        lists) — the flag is threaded through to the backend dispatch
        so already-tokenized documents are never re-tokenized.
        """
        self._check_live()
        pending = None
        for chunk in chunks:
            nxt = self._impl.dispatch(list(chunk), tokenized=tokenized)
            if pending is not None:
                self._impl.merge(pending)
                self._post_merge()
                yield self.snapshot()
            pending = nxt
        if pending is not None:
            self._impl.merge(pending)
            self._post_merge()
            yield self.snapshot()

    def _merge_precomputed(self, token_lists, sig,
                           bands) -> ClusterSnapshot:
        """Host-backend ingest of a chunk whose tokenize/signature/band
        stages the caller already ran (the ``DedupPipeline.run`` timing
        adapter).  One-shot by construction: the cross-step band index
        is skipped entirely (a single chunk has no earlier chunk to
        collide with, and indexing every (doc, band) would be pure
        overhead at corpus scale), so the session is finalized — it
        cannot accept further chunks."""
        if self.backend != "host":
            raise ValueError("precomputed ingest is a host-backend path")
        if self._finalized:
            raise ValueError("one-shot session already finalized")
        base = self.allocator.allocate(len(token_lists))
        self._impl.merge((base, token_lists, np.asarray(sig),
                          np.asarray(bands), None), index=False)
        self._finalized = True
        return self.snapshot()

    # -- bounded retained state (DESIGN.md §7) ------------------------------

    def _post_merge(self) -> None:
        """Retention sweep + auto-refine cadence after a chunk merge."""
        if self.retention is None:
            return
        self.retention.sweep(self)
        every = self.retention.policy.refine_every
        if every and self.steps_ingested % every == 0:
            self.refine()

    def _release_rows(self, doc_ids) -> None:
        """Evict docs' rows from the session verifier (retention hook).

        External verifiers without a ``release_rows`` API keep their
        rows (the policy still bounds the band index and logs roots).
        """
        v = self._verifier
        if v is not None and hasattr(v, "release_rows"):
            v.release_rows(doc_ids)

    def _compact_band_store(self, doc_ids, root_of) -> None:
        """Rewrite evicted docs' band-STORE rows onto their cluster
        roots (retention hook; streaming backend only — the other
        backends' retained band state is the ``band_index``, which the
        sweep's ``evict`` call already rewrote).  Keeps the phase-1
        store bounded instead of growing with evicted history (the
        ROADMAP "retention completeness" fix); clustering-neutral, see
        ``bandstore.Design2Store.compact``.
        """
        compact = getattr(self._impl, "compact_store", None)
        if compact is not None:
            compact(doc_ids, root_of)

    def _representatives(self) -> list[int]:
        """Sorted current union-find roots (the retained-rep view).

        Gap ids below the session's base (``doc_id_base`` sessions)
        are excluded: they have no real document behind them — their
        verifier rows are blank, so re-banding them would collide every
        gap with every other gap at a bogus similarity of 1.0.
        """
        if self.retention is not None:
            self.retention.sweep(self)   # sync roots with recent unions
            return self.retention.representatives()
        base = self.allocator.base
        lab = self.uf.components()[: self.n_docs]
        return sorted({int(r) for r in lab[base:]} if base else
                      {int(r) for r in lab})

    def _rep_band_pairs(self, reps: list[int],
                        est: SignatureVerifier) -> np.ndarray:
        """Re-band representatives, return their collision pairs.

        The second clustering round's candidate generator: band values
        are deterministic in the signature rows, so representative
        collisions are exactly the original LSH collisions restricted
        to the current root set — no O(reps^2) sweep.
        """
        rows = est.rows_for(reps)
        bands = np.asarray(lsh.band_values(
            jnp.asarray(rows), self.config.rows_per_band))
        pairs: list[tuple[int, int]] = []
        for j in range(bands.shape[1]):
            seen: dict[tuple[int, int], list[int]] = {}
            col = bands[:, j, :]
            for i, rep in enumerate(reps):
                key = (int(col[i, 0]), int(col[i, 1]))
                olds = seen.get(key)
                if olds is None:
                    seen[key] = [rep]
                else:
                    pairs.extend((old, rep) for old in olds)
                    olds.append(rep)
        if not pairs:
            return np.zeros((0, 2), dtype=np.int64)
        return np.array(pairs, dtype=np.int64)

    def refine(self) -> ClusterSnapshot:
        """Incremental second clustering round (paper §10) over the
        retained representatives.

        Re-bands only the current cluster representatives and drives
        their collision pairs through ``engine.merge_cluster_rounds``
        with the accumulator's verified-sim cache — sims the session
        already verified are served from cache, and second-round sims
        become visible to later feeds.  Merges clusters whose
        representatives clear ``edge_threshold`` (the over-partitioning
        fix the paper runs as a batch pass; here it is incremental and
        auto-triggered every ``RetentionPolicy.refine_every`` steps).

        Verifiers without retained signatures (exact / callback
        sessions) fall back to the full representative-pair sweep.
        """
        self._check_live()
        reps = self._representatives()
        merges = 0
        if len(reps) >= 2 and self._verifier is not None:
            est = self._estimate_verifier()
            cand = None
            if isinstance(est, SignatureVerifier) and \
                    est.signatures.size:
                cand = self._rep_band_pairs(reps, est)
            merges = merge_cluster_rounds(
                self.uf, est, self.config.edge_threshold,
                roots=reps, candidate_pairs=cand,
                sim_cache=self.acc.evaluated)
        self.refine_merges += merges
        self.refines_run += 1
        if self.retention is not None and merges:
            # Second-round unions deposed roots; evict their rows.
            self.retention.sweep(self)
        return self.snapshot()

    # -- shared backend plumbing -------------------------------------------

    def _retain(self, token_lists, sig: np.ndarray, sig_dev=None) -> None:
        """Grow the session verifier with one chunk's docs.

        The verifier owns the retained state ("row i == doc i"): the
        first chunk builds it — padded with blank rows for any ids
        below the chunk's base (``doc_id_base`` sessions; those ids
        have no band rows, so they can never become candidates) — and
        later chunks extend it in place.  ``sig_dev``, the chunk's
        signatures still on the device, goes into a device verifier's
        store without a copy from the host.
        """
        if self._external_verifier:
            return
        with spans.span("retain"):
            sig = np.asarray(sig)
            cfg = self.config
            if self._verifier is None:
                gap = self.n_merged  # ids below the first chunk's base
                if self._wants_exact():
                    self._verifier = ExactJaccardVerifier.from_token_lists(
                        [[]] * gap + list(token_lists), cfg.ngram)
                    return
                cls = (DeviceScoredEdgeVerifier
                       if self.backend == "sharded"
                       and self._impl.stage2 == "device"
                       else SignatureVerifier)
                self._verifier = cls(
                    np.zeros((gap, sig.shape[1]), dtype=sig.dtype),
                    backend=cfg.resolved_backend(),
                    capacity=cfg.sig_store_capacity)
                self._verifier.extend_signatures(sig, device_rows=sig_dev)
            elif self._wants_exact():
                self._verifier.extend_token_lists(token_lists)
            else:
                self._verifier.extend_signatures(sig, device_rows=sig_dev)

    def _wants_exact(self) -> bool:
        return self.backend == "host" and self.config.exact_verification

    def _estimate_verifier(self) -> BatchVerifier:
        """Plain signature-estimate view for cross-step host edges.

        For ``stage2="device"`` sessions the main verifier counts
        registry pass-throughs vs host re-scores; host-generated
        cross-step edges must not inflate ``n_rescored`` (the
        overflow-only pin), so they verify through a shared plain
        estimator over the same retained matrix — bit-identical scores,
        same accumulator cache.
        """
        if not isinstance(self._verifier, DeviceScoredEdgeVerifier):
            return self._verifier
        if not hasattr(self, "_est_verifier"):
            self._est_verifier = SignatureVerifier(
                np.zeros((0, self.config.num_hashes), dtype=np.uint32),
                backend=self.config.resolved_backend())
        # Re-adopt buffer, slot layout and device store every use: chunk
        # extensions regrow the matrix and retention sweeps rewrite rows
        # in place.
        self._est_verifier.adopt_layout(self._verifier)
        return self._est_verifier

    def _feed_cross_step(self, bands: np.ndarray, base: int) -> None:
        """Cross-step candidates: chunk bands vs the retained index."""
        with spans.span("band_index"):
            edges = self.band_index.match_then_insert(bands, base)
        if len(edges):
            self.acc.feed(
                ShardedEdgeSource(edges, num_docs=self.n_docs),
                verifier=self._estimate_verifier())


class _NullVerifier(BatchVerifier):
    """Placeholder until the first chunk builds the real verifier (the
    accumulator is constructed before any signatures exist)."""

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        raise RuntimeError("session verifier not initialised — "
                           "ingest a chunk first")


class _HostBackend:
    """In-memory per-chunk band matrix (the ``DedupPipeline`` shape)."""

    def __init__(self, sess: DedupSession):
        self.sess = sess
        from repro.core.pipeline import DedupPipeline

        self.pipe = DedupPipeline(sess.config)
        self.pipe.seeds = sess.seeds

    def dispatch(self, chunk, tokenized: bool = False):
        sess = self.sess
        if sess.config.byte_ingest:
            # Zero-copy path: raw UTF-8 bytes go to device untokenized.
            # Pre-tokenized chunks re-join with spaces — tokens are
            # alnum-only, so the byte tokenizer recovers them exactly.
            docs = ([" ".join(t) for t in chunk] if tokenized
                    else list(chunk))
            base = sess.allocator.allocate(len(docs))
            if not docs:
                return (base, docs, None, None, None)
            pad = shingle.pow2_bucket(
                max(len(d.encode("utf-8")) for d in docs) + 1)
            # The signatures stay on the device too, for the store of a
            # device verify backend.
            return (base, docs) + self.pipe.compute_arrays_bytes(
                docs, pad_len=pad, keep_device=True)
        toks = chunk if tokenized else self.pipe.tokenize(chunk)
        base = sess.allocator.allocate(len(toks))
        if not toks:
            return (base, toks, None, None, None)
        # Fused-ingest configs compute both arrays in one Pallas pass.
        # The token dim buckets to a power of two so repeated chunked
        # ingests reuse a bounded jit-compile set instead of paying one
        # recompile per novel max-document-length (the PR 7 serving
        # bug, on the write path); signatures are padding-invariant.
        pad = shingle.pow2_bucket(max((len(t) for t in toks), default=1))
        sig, bands = self.pipe.compute_arrays(toks, pad_len=pad)
        return (base, toks, sig, bands, None)

    def merge(self, pending, index: bool = True):
        base, toks, sig, bands, sig_dev = pending
        if sig is None:
            return
        sess = self.sess
        sess._retain(toks, sig, sig_dev)
        sess.n_merged = base + len(toks)
        sess.acc.grow(sess.n_docs)
        sess.acc.feed(BandMatrixSource(bands, doc_id_base=base),
                      verifier=sess._verifier)
        if index:
            sess._feed_cross_step(bands, base)
        sess.steps_ingested += 1


class _StreamingBackend:
    """Design-2 band store phase 1 + band-major re-scan phase 2.

    Owns (or adopts) a ``streaming.StreamingDedup`` for the store
    writes and signature cache; each merge re-scans the store through
    the session accumulator — the verified-sim cache turns the re-scan
    into pure candidate re-enumeration (no re-verification), which is
    the paper's "repeat phase 2" made incremental.  The store is the
    retained state here, so no separate ``BandIndex`` is kept.
    """

    def __init__(self, sess: DedupSession, *, store_path: str,
                 chunk_docs: int, adopt=None):
        self.sess = sess
        self._owned = adopt is None
        if adopt is not None:
            self.sd = adopt
        else:
            from repro.core.streaming import StreamingDedup

            self.sd = StreamingDedup(sess.config, store_path=store_path,
                                     chunk_docs=chunk_docs,
                                     doc_id_base=sess.allocator.base)
            self.sd.seeds = sess.seeds

    def dispatch(self, chunk, tokenized: bool = False):
        # The store write is host-side work with nothing to overlap, so
        # it happens at merge time — a lookahead dispatch must not leak
        # chunk t+1's rows into the band-major scan that merges chunk t.
        if self.sess.config.byte_ingest:
            # Byte configs buffer raw texts; StreamingDedup._flush
            # routes them through the bytes_to_bands kernel.
            toks = ([" ".join(t) for t in chunk] if tokenized
                    else list(chunk))
        else:
            toks = chunk if tokenized else [shingle.tokenize(t)
                                            for t in chunk]
        return (self.sess.allocator.allocate(len(toks)), toks)

    def merge(self, pending):
        base, toks = pending
        sess = self.sess
        assert base == self.sd.n_docs, (base, self.sd.n_docs)
        if toks:
            self.sd.ingest_tokens(toks)
            if hasattr(self.sd.store, "put_signatures"):
                # Disk tier (DedupConfig.store="sqlite"): the flush
                # already wrote the chunk's signature rows into the
                # store — the session verifies straight off disk
                # through the store's LRU-cached row gather, so there
                # is no host matrix to grow and nothing cached to pop.
                if sess._verifier is None and \
                        not sess._external_verifier:
                    sess._verifier = self.sd.default_verifier()
            else:
                sig = np.stack([self.sd._sig_cache[base + i]
                                for i in range(len(toks))])
                sess._retain(toks, sig)
                if self._owned:
                    # The rows now live in the session verifier;
                    # keeping them in the phase-1 cache too would store
                    # every signature twice.  (Adopted StreamingDedups
                    # keep their cache — ``default_verifier`` may
                    # rebuild from it.)
                    for i in range(len(toks)):
                        self.sd._sig_cache.pop(base + i, None)
        sess.n_merged = max(sess.n_merged, base + len(toks))
        sess.acc.grow(sess.n_docs)
        sess.acc.feed(self.sd.candidate_source(),
                      verifier=sess._verifier)
        sess.steps_ingested += 1

    def compact_store(self, doc_ids, root_of):
        """Retention hook: drop evicted docs' band-store rows on
        rewrite (``DedupSession._compact_band_store``)."""
        self.sd.store.compact(doc_ids, root_of)


class _ShardedBackend:
    """One streamed ``dist_lsh`` step invocation per chunk, one
    accumulator across all of them."""

    def __init__(self, sess: DedupSession, *, dist_config, mesh,
                 stream: bool | None):
        from repro.core.dist_lsh import DistLSHConfig, docs_mesh

        self.sess = sess
        cfg = sess.config
        self.dcfg = dist_config or DistLSHConfig(
            ngram=cfg.ngram, num_hashes=cfg.num_hashes,
            rows_per_band=cfg.rows_per_band,
            edge_threshold=cfg.edge_threshold,
            fused_ingest=cfg.fused_ingest,
            byte_ingest=cfg.byte_ingest)
        # The session's retained state (seeds, signature width, band
        # index shape) is derived from DedupConfig while the device
        # step runs the DistLSHConfig — they must describe the same
        # hash space or the first dispatch/merge corrupts the session.
        # ``byte_ingest`` joins the check because it flips the step's
        # INPUT contract (uint8 byte matrix vs uint32 token matrix).
        for f in ("ngram", "num_hashes", "rows_per_band", "byte_ingest"):
            if getattr(cfg, f) != getattr(self.dcfg, f):
                raise ValueError(
                    f"DedupConfig.{f}={getattr(cfg, f)} does not match "
                    f"DistLSHConfig.{f}={getattr(self.dcfg, f)}; the "
                    "session's retained signatures/bands must share the "
                    "sharded step's hash parameters")
        self.mesh = mesh if mesh is not None else docs_mesh()
        self.stream = stream
        self._step = None
        self.n_dev = int(np.prod([self.mesh.shape[a]
                                  for a in self.mesh.axis_names]))
        # Chunk inputs go straight to their shards (and the seeds to
        # every device) instead of landing whole on the default device.
        from jax.sharding import NamedSharding, PartitionSpec

        self._rows = NamedSharding(self.mesh,
                                   PartitionSpec(self.mesh.axis_names[0]))
        self._replicated = NamedSharding(self.mesh, PartitionSpec())

    @property
    def stage2(self) -> str:
        return self.dcfg.stage2

    def _get_step(self):
        if self._step is None:
            from repro.core.dist_lsh import make_streamed_dedup_step

            self._step = make_streamed_dedup_step(self.dcfg, self.mesh)
        return self._step

    def _run_step(self, data, lengths, offsets):
        import jax

        rows = self._rows
        return self._get_step()(
            jax.device_put(data, rows), jax.device_put(lengths, rows),
            jax.device_put(self.sess.seeds, self._replicated),
            jax.device_put(offsets, rows))

    def dispatch(self, chunk, tokenized: bool = False):
        sess = self.sess
        if self.dcfg.byte_ingest:
            return self._dispatch_bytes(chunk, tokenized)
        toks = chunk if tokenized else [shingle.tokenize(t)
                                        for t in chunk]
        n_real = len(toks)
        base = sess.allocator.allocate(n_real)
        if n_real == 0:
            return (base, toks, 0, None)
        # Pad for device-count divisibility; pad ids live above the
        # allocated block and are range-filtered at the merge.
        pad = (-n_real) % self.n_dev
        padded = toks + [["pad"]] * pad
        packed = shingle.pack_documents(padded)
        d_loc = len(padded) // self.n_dev
        offsets = DocIdAllocator.device_offsets(base, d_loc, self.n_dev)
        out = self._run_step(packed.tokens, packed.lengths, offsets)
        return (base, toks, n_real, out)

    def _dispatch_bytes(self, chunk, tokenized: bool):
        """Byte-ingest dispatch: ship raw UTF-8 bytes, not token ids.

        Same step contract otherwise; the padding doc is the literal
        text ``"pad"`` so its signature matches the token path's
        ``["pad"]`` row bit-for-bit (it is range-filtered regardless).
        """
        sess = self.sess
        docs = ([" ".join(t) for t in chunk] if tokenized
                else list(chunk))
        n_real = len(docs)
        base = sess.allocator.allocate(n_real)
        if n_real == 0:
            return (base, docs, 0, None)
        pad = (-n_real) % self.n_dev
        padded = docs + ["pad"] * pad
        blen = shingle.pow2_bucket(
            max(len(d.encode("utf-8")) for d in padded) + 1)
        packed = shingle.pack_bytes(padded, blen)
        d_loc = len(padded) // self.n_dev
        offsets = DocIdAllocator.device_offsets(base, d_loc, self.n_dev)
        out = self._run_step(packed.data, packed.lengths, offsets)
        return (base, docs, n_real, out)

    def merge(self, pending):
        from repro.core.dist_lsh import feed_step_groups

        base, toks, n_real, out = pending
        if out is None:
            return
        sess = self.sess
        sig = np.asarray(out["sig"])[:n_real]
        sess._retain(toks, sig)
        sess.n_merged = base + n_real
        sess.acc.grow(sess.n_docs)
        on_group = None
        if sess.retention is not None:
            # Intra-step eviction between band-group merges: a giant
            # chunk's own rows are shielded (protect_from=base) — the
            # remaining groups and the sig-row-exchange re-score path
            # only ever touch this chunk's rows and retained roots.
            on_group = lambda: sess.retention.sweep(
                sess, protect_from=base)
        feed = feed_step_groups(
            sess.acc, out, self.dcfg, num_docs=base + n_real,
            edge_offset=0, verifier=sess._verifier, stream=self.stream,
            on_group_merged=on_group)
        sess.overflow += feed.overflow
        sess.row_overflow += feed.row_overflow
        bands = np.asarray(lsh.band_values(jnp.asarray(sig),
                                           self.dcfg.rows_per_band))
        if feed.overflow > 0:
            # Device buffers dropped prescreened edges for THIS chunk:
            # re-derive its candidates on the host and accumulate them
            # through the same engine (cross-step edges are host-side
            # and unbounded, so only the within-chunk family can lose).
            sess.retried += 1
            sess.acc.feed(BandMatrixSource(bands, doc_id_base=base),
                          verifier=sess._estimate_verifier())
        sess._feed_cross_step(bands, base)
        sess.steps_ingested += 1
