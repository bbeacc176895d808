"""Streaming (out-of-core) dedup — the paper's §12 production mode.

The 10M-note corpus never fits memory: the paper streams notes, writes
band signatures to Cassandra (75 h), then reads band-major and clusters
(24 h).  This module reproduces that *two-phase* shape:

  Phase 1 (write): stream document chunks -> signatures (JAX/Pallas) ->
    band values -> a Design-2 band store (sqlite stand-in; on the pod
    this is the all_to_all reshard in core.dist_lsh).
  Phase 2 (read): band-major scan over the store via the staged engine
    (``candidates.StoreBandSource`` -> batched ``verify`` ->
    ``ThresholdUnionFind``; see ``core.engine``).

Incremental by construction: Phase 1 can be appended to (new notes
arrive), and Phase 2 can be re-run at different edge thresholds without
recomputing signatures — exactly the property the paper calls out
("the second step ... can be repeated for different edge thresholds").

Also implements the paper's §10 suggestion of a SECOND clustering round:
merge clusters whose representatives are highly similar (the disjoint-set
pass can over-partition; see Table 7's 56 diff-set-high pairs) — batched
through the same verifier layer (``engine.merge_cluster_rounds``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable

import numpy as np
import jax.numpy as jnp

from repro.core import lsh, minhash, shingle
from repro.core.bandstore import DiskSignatureVerifier, make_store
from repro.core.candidates import StoreBandSource
from repro.core.engine import merge_cluster_rounds as _merge_rounds
from repro.core.pipeline import DedupConfig
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import BatchVerifier, SignatureVerifier, as_verifier


@dataclass
class StreamingDedup:
    """Two-phase streaming dedup over a Design-2 band store.

    ``doc_id_base`` assigns global doc ids starting at that base —
    resumed ingest of a chunked corpus (the ``doc_offsets`` convention
    of the sharded path) writes non-contiguous per-part id ranges into
    the store, which the Design-2 schema persists explicitly.
    """

    config: DedupConfig = field(default_factory=DedupConfig)
    store_path: str = ":memory:"
    chunk_docs: int = 512
    doc_id_base: int = 0

    def __post_init__(self):
        # The store tier comes from the config (DESIGN.md §12):
        # "memory" is the historical Design-2 blob store, "sqlite" the
        # key-level disk tier with Bloom-first lookups and
        # disk-resident signature rows.
        self.store = make_store(self.config.store, self.store_path,
                                part_size=self.chunk_docs,
                                num_bands=self.config.num_bands)
        self.seeds = minhash.default_seeds(self.config.num_hashes)
        self.n_docs = int(self.doc_id_base)
        self.n_ingested = 0
        self._sig_cache: dict[int, np.ndarray] = {}
        self._seeds_dev = None
        self._seeds_src = None

    def _device_seeds(self) -> jnp.ndarray:
        """Seeds as a cached device array (one upload per assignment,
        not one per flushed chunk)."""
        if self._seeds_dev is None or self._seeds_src is not self.seeds:
            self._seeds_dev = jnp.asarray(self.seeds)
            self._seeds_src = self.seeds
        return self._seeds_dev

    # -- phase 1 -----------------------------------------------------------

    def ingest(self, texts: Iterable[str], keep_signatures: bool = True):
        """Stream documents into the band store, chunk by chunk."""
        if self.config.byte_ingest:
            # Zero-copy phase 1: texts are buffered raw and shipped to
            # the device as UTF-8 bytes — no host tokenize pass.
            self.ingest_tokens(texts, keep_signatures)
            return
        self.ingest_tokens(
            (shingle.tokenize(t) for t in texts), keep_signatures)

    def ingest_tokens(self, token_lists: Iterable[list[str]],
                      keep_signatures: bool = True):
        """Ingest pre-tokenized documents (avoids re-tokenizing when the
        caller already has token lists, e.g. to build an exact verifier)."""
        buf: list[list[str]] = []
        for toks in token_lists:
            buf.append(toks)
            if len(buf) == self.chunk_docs:
                self._flush(buf, keep_signatures)
                buf = []
        if buf:
            self._flush(buf, keep_signatures)
        self.store.commit()

    def _flush(self, token_lists, keep_signatures):
        # Bucket the padded token dim to a power of two: full chunks
        # share one jit compile regardless of each chunk's longest
        # document, instead of recompiling the fused/staged stages per
        # novel (D, L) (signatures are padding-invariant).
        if self.config.byte_ingest:
            # Byte configs buffer raw texts (see ``ingest``): pack their
            # UTF-8 bytes and run the whole chain on device.
            from repro.kernels.byte_shingle import bytes_to_bands

            pad_len = shingle.pow2_bucket(
                max((len(t if isinstance(t, bytes) else
                         t.encode("utf-8")) for t in token_lists),
                    default=0) + 1)
            packed_b = shingle.pack_bytes(token_lists, pad_len)
            sig_j, bands_j, _ = bytes_to_bands(
                jnp.asarray(packed_b.data), jnp.asarray(packed_b.lengths),
                self._device_seeds(), n=self.config.ngram,
                r=self.config.rows_per_band)
            self._store_chunk(np.asarray(sig_j), np.asarray(bands_j),
                              len(token_lists), keep_signatures)
            return
        pad_len = shingle.pow2_bucket(
            max((len(t) for t in token_lists), default=1))
        packed = shingle.pack_documents(token_lists, pad_len)
        if self.config.fused_ingest:
            # Phase 1 on the fused device pass: signatures AND band
            # values come back from one Pallas dispatch (bit-identical
            # to the staged chain below).
            from repro.kernels.fused_ingest import fused_ingest

            sig_j, bands_j, _ = fused_ingest(
                jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                self._device_seeds(), n=self.config.ngram,
                r=self.config.rows_per_band)
            sig, bands = np.asarray(sig_j), np.asarray(bands_j)
        else:
            ng, valid = shingle.ngram_hashes(
                jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                n=self.config.ngram)
            sig = np.asarray(minhash.signatures(ng, valid,
                                                self._device_seeds()))
            bands = np.asarray(lsh.band_values(
                jnp.asarray(sig), self.config.rows_per_band))
        self._store_chunk(sig, bands, len(token_lists), keep_signatures)

    def _store_chunk(self, sig, bands, n, keep_signatures):
        """Write one flushed chunk's band rows (+ signature rows) to the
        store.  Signature routing is the tier split: stores with
        disk-resident signature rows take them directly (the
        ``DiskSignatureVerifier`` path); the memory tier keeps the
        host-side phase-1 cache."""
        for i in range(n):
            self.store.insert_document(self.n_docs + i, bands[i])
        if keep_signatures:
            if hasattr(self.store, "put_signatures"):
                self.store.put_signatures(
                    np.arange(self.n_docs, self.n_docs + n), sig[:n])
            else:
                for i in range(n):
                    self._sig_cache[self.n_docs + i] = sig[i]
        self.n_docs += n
        self.n_ingested += n

    # -- phase 2 -----------------------------------------------------------

    def candidate_source(self) -> StoreBandSource:
        """The staged-engine candidate source over the band store."""
        return StoreBandSource(self.store, self.config.num_bands,
                               self.n_docs)

    def default_verifier(self) -> BatchVerifier:
        """Signature-agreement verifier over the phase-1 rows.

        Disk-tier stores hold their signature rows on disk, so the
        verifier gathers rows through the store's LRU row cache
        (``bandstore.DiskSignatureVerifier`` — same estimate expression,
        bit-identical sims).  The memory tier builds the full matrix
        from the host cache, indexed by global doc id (rows below
        ``doc_id_base`` or inside a resumed-ingest gap stay zero — those
        ids have no band-store rows, so they can never reach the
        verifier as candidates).
        """
        if hasattr(self.store, "put_signatures"):
            if self.store.n_signatures() < self.n_ingested:
                raise ValueError(
                    f"store holds {self.store.n_signatures()} of "
                    f"{self.n_ingested} ingested docs' signature rows — "
                    "ingest with keep_signatures=True or pass an "
                    "explicit similarity_fn / verifier to cluster()")
            return DiskSignatureVerifier(self.store,
                                         self.config.num_hashes)
        if len(self._sig_cache) < self.n_ingested:
            raise ValueError(
                f"signature cache holds {len(self._sig_cache)} of "
                f"{self.n_ingested} ingested docs — ingest with "
                "keep_signatures=True or pass an explicit "
                "similarity_fn / verifier to cluster()")
        sig = np.zeros((self.n_docs, self.config.num_hashes),
                       dtype=np.uint32)
        for i, row in self._sig_cache.items():
            sig[i] = row
        return SignatureVerifier(
            sig, backend=self.config.resolved_backend(),
            capacity=self.config.sig_store_capacity)

    def cluster(self, edge_threshold: float | None = None,
                tree_threshold: float | None = None,
                similarity_fn: Callable[[int, int], float]
                | BatchVerifier | None = None):
        """Band-major read -> candidates -> batched verify -> union-find.

        A thin adapter over ``session.DedupSession.over_store``: the
        phase-2 scan runs through a session accumulator (one union-find
        + verified-sim cache), which is the same machinery incremental
        multi-chunk ingest uses — ``cluster`` is simply the one-shot
        snapshot of it.  ``similarity_fn`` may be a
        ``verify.BatchVerifier`` or a scalar callable; it defaults to
        batched signature agreement over the phase-1 cache.
        Re-runnable at different thresholds without re-hashing (paper
        §12).
        """
        from dataclasses import replace

        from repro.core.session import DedupSession

        cfg = self.config
        edge_t = edge_threshold if edge_threshold is not None else \
            cfg.edge_threshold
        tree_t = tree_threshold if tree_threshold is not None else \
            cfg.tree_threshold
        verifier = (None if similarity_fn is None
                    else as_verifier(similarity_fn))
        sess = DedupSession.over_store(
            self, config=replace(cfg, edge_threshold=edge_t,
                                 tree_threshold=tree_t),
            verifier=verifier)
        snap = sess.snapshot()
        return sess.uf, {"pairs_evaluated": snap.stats.pairs_evaluated,
                         "pairs_excluded": snap.stats.pairs_excluded,
                         "verify_batches": snap.stats.verify_batches,
                         "verify_seconds": snap.stats.verify_seconds}


def merge_cluster_rounds(
    uf: ThresholdUnionFind,
    similarity_fn: Callable[[int, int], float] | BatchVerifier,
    edge_threshold: float,
) -> int:
    """Paper §10's second clustering round (see
    ``engine.merge_cluster_rounds``): root-pair similarities are computed
    in one batched dispatch instead of an O(roots^2) scalar loop.
    Returns #merges performed."""
    return _merge_rounds(uf, similarity_fn, edge_threshold)
