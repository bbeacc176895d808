"""End-to-end deduplication pipeline (the paper, assembled).

text docs -> tokenize/stem -> pack -> n-gram hashes -> minhash signatures
-> band matrix -> candidate pairs -> verified similarities -> threshold
union-find clusters -> keep-list (one representative per cluster).

Execution styles, all thin drivers over the staged engine
(``CandidateSource -> BatchVerifier -> ThresholdUnionFind``, see
``core.engine``):

* ``DedupPipeline.run`` — host-orchestrated, paper-faithful; candidate
  generation via ``candidates.BandMatrixSource``, verification via the
  batched ``verify`` layer (exact Jaccard or signature estimate on a
  selectable ``numpy`` / ``jnp`` / ``pallas`` backend).
* ``StreamingDedup`` in ``core.streaming`` — out-of-core two-phase mode
  over a band store (``candidates.StoreBandSource``), same engine.
* ``dedup_step`` in ``core.dist_lsh`` — sharded step for the production
  mesh: on-device candidate shuffle + prefix prescreen, then the host
  merge (``dist_lsh.cluster_step_output``) drives this same engine.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

from repro.core import lsh
from repro.core import minhash
from repro.core import shingle
from repro.core import spans
from repro.core.engine import ClusterStats, PairList
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import ExactJaccardVerifier, SignatureVerifier


def _h2d(*host_arrays: np.ndarray) -> dict:
    """Span count of one device-ingest call: the bytes its host arrays
    copy to the device."""
    return {"h2d_bytes": sum(a.nbytes for a in host_arrays)}


@dataclass(frozen=True)
class DedupConfig:
    """Paper defaults: n=8, M=100, r=2 (=> b=50), thresholds from §9-10."""

    ngram: int = 8
    num_hashes: int = 100
    rows_per_band: int = 2
    edge_threshold: float = 0.75
    tree_threshold: float = 0.40
    use_disjoint_sets: bool = True
    exact_verification: bool = True  # exact Jaccard vs signature estimate
    use_pallas: bool = False  # route signature computation through kernels
    fused_ingest: bool = False  # one-pass Pallas shingle->minhash->fold
    byte_ingest: bool = False  # device bytes->bands (no-stem, zero-copy)
    verify_backend: str = "auto"  # estimate mode: numpy | jnp | pallas
    verify_batch: str = "run"  # engine batch granularity: run | band
    # Rows of the device signature store of the jnp/pallas verify
    # backends (``verify.SignatureStore``): a deployment sizes it for
    # its corpus so the store never changes shape; 0 grows it by
    # doubling.
    sig_store_capacity: int = 0
    seed: int = 0x5EED
    # Band-store tier (core.bandstore, DESIGN.md §12): "memory" keeps
    # the historical in-RAM layout; "sqlite" puts band rows + signature
    # rows on disk behind Bloom-first lookups.  Identical clusters and
    # bit-identical per-edge sims either way (pinned in tests); the env
    # default lets the CI store matrix flip the whole suite per cell.
    store: str = field(default_factory=lambda: os.environ.get(
        "REPRO_STORE_BACKEND", "memory"))

    def __post_init__(self):
        if self.byte_ingest and self.exact_verification:
            raise ValueError(
                "byte_ingest never materializes host token lists, so "
                "exact Jaccard verification is impossible; set "
                "exact_verification=False (signature-estimate mode)")
        if self.store not in ("memory", "sqlite"):
            raise ValueError(
                f"unknown store backend {self.store!r}; "
                "one of ('memory', 'sqlite')")

    @property
    def num_bands(self) -> int:
        return self.num_hashes // self.rows_per_band

    def resolved_backend(self) -> str:
        if self.verify_backend != "auto":
            return self.verify_backend
        return "pallas" if self.use_pallas else "numpy"


@dataclass
class DedupResult:
    labels: np.ndarray  # (D,) cluster root per doc
    keep_mask: np.ndarray  # (D,) bool — True for cluster representatives
    pairs: PairList  # evaluated (a, b, sim), sorted
    stats: ClusterStats
    uf: ThresholdUnionFind
    signatures: np.ndarray  # (D, M) uint32
    bands: np.ndarray  # (D, b, 2) uint32
    timings: dict = field(default_factory=dict)

    @property
    def num_clusters(self) -> int:
        """Number of duplicate clusters, i.e. components of size >= 2."""
        _, counts = np.unique(self.labels, return_counts=True)
        return int((counts >= 2).sum())

    @property
    def num_duplicates_removed(self) -> int:
        return int((~self.keep_mask).sum())


class DedupPipeline:
    def __init__(self, config: DedupConfig | None = None):
        self.config = config or DedupConfig()
        self.seeds = minhash.default_seeds(self.config.num_hashes)
        self._seeds_dev = None
        self._seeds_src = None
        # Per-stage host seconds of the LAST call of each stage
        # (``tokenize_s``, ``signature_s``, ``bands_s``), taken by the
        # stage's ``spans.span``; device stages end on the transfer
        # back to numpy, so every backend times its device work alike.
        self.stage_timings: dict[str, float] = {}

    def device_seeds(self) -> jnp.ndarray:
        """The seed vector as a cached device array.

        Uploaded once per ``seeds`` assignment instead of re-running
        ``jnp.asarray`` on every chunk (the old per-chunk host->device
        copy was pure overhead in multi-step sessions).
        """
        if self._seeds_dev is None or self._seeds_src is not self.seeds:
            self._seeds_dev = jnp.asarray(self.seeds)
            self._seeds_src = self.seeds
        return self._seeds_dev

    # -- stages ------------------------------------------------------------

    def tokenize(self, texts: list[str]) -> list[list[str]]:
        with spans.span("tokenize") as s:
            toks = [shingle.tokenize(t) for t in texts]
        self.stage_timings["tokenize_s"] = s.seconds
        return toks

    def compute_signatures(self, token_lists: list[list[str]],
                           pad_len: int | None = None) -> np.ndarray:
        with spans.span("pack") as pack:
            packed = shingle.pack_documents(token_lists, pad_len)
        with spans.span("device_ingest", **_h2d(packed.tokens,
                                                packed.lengths)) as dev:
            if self.config.use_pallas or self.config.fused_ingest:
                from repro.kernels import ops as kops

                if self.config.fused_ingest:
                    sig, _, _ = kops.fused_ingest(
                        jnp.asarray(packed.tokens),
                        jnp.asarray(packed.lengths),
                        self.device_seeds(),
                        n=self.config.ngram,
                        r=self.config.rows_per_band,
                    )
                else:
                    ng, valid = kops.ngram_hashes(
                        jnp.asarray(packed.tokens),
                        jnp.asarray(packed.lengths),
                        n=self.config.ngram,
                    )
                    sig = kops.minhash_signatures(ng, valid,
                                                  self.device_seeds())
            else:
                ng, valid = shingle.ngram_hashes(
                    jnp.asarray(packed.tokens),
                    jnp.asarray(packed.lengths),
                    n=self.config.ngram,
                )
                sig = minhash.signatures(ng, valid, self.device_seeds())
            # np.asarray blocks on the device work, so the kops/fused
            # paths record the same wall semantics as the numpy path.
            sig = np.asarray(sig)
        self.stage_timings["signature_s"] = pack.seconds + dev.seconds
        return sig

    def compute_bands(self, sig: np.ndarray) -> np.ndarray:
        with spans.span("device_ingest", h2d_bytes=sig.nbytes) as dev:
            bands = np.asarray(
                lsh.band_values(jnp.asarray(sig), self.config.rows_per_band)
            )
        self.stage_timings["bands_s"] = dev.seconds
        return bands

    def compute_arrays(
        self, token_lists: list[list[str]],
        pad_len: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """One chunk's (signatures, band values) — the ingest hot path.

        With ``config.fused_ingest`` both arrays come out of ONE
        device-resident Pallas pass (no intermediate n-gram/signature
        HBM round-trip and no separate band dispatch); otherwise the
        staged ``compute_signatures`` -> ``compute_bands`` chain runs.
        Outputs are bit-identical either way.

        ``pad_len`` (>= the longest document) widens the packed token
        matrix; signatures are invariant to padding (the validity mask
        comes from real lengths), so callers with many small batches —
        the query service — bucket shapes to bound jit recompiles.

        Named ``compute_*`` (not ``ingest_*``) per the public naming
        scheme (``repro.core`` docstring): this is a pure stage
        computation — only ``ingest*`` entry points add documents to
        long-lived dedup state.
        """
        if not self.config.fused_ingest:
            sig = self.compute_signatures(token_lists, pad_len)
            return sig, self.compute_bands(sig)
        from repro.kernels import ops as kops

        with spans.span("pack") as pack:
            packed = shingle.pack_documents(token_lists, pad_len)
        with spans.span("device_ingest", **_h2d(packed.tokens,
                                                packed.lengths)) as dev:
            sig, bands, _ = kops.fused_ingest(
                jnp.asarray(packed.tokens),
                jnp.asarray(packed.lengths),
                self.device_seeds(),
                n=self.config.ngram,
                r=self.config.rows_per_band,
            )
            sig, bands = np.asarray(sig), np.asarray(bands)
        self.stage_timings["signature_s"] = pack.seconds + dev.seconds
        self.stage_timings["bands_s"] = 0.0  # fused into the one pass
        return sig, bands

    def compute_arrays_bytes(
        self, docs: list[str | bytes],
        pad_len: int | None = None,
        *,
        keep_device: bool = False,
    ) -> tuple:
        """One chunk's (signatures, band values) straight from UTF-8 bytes.

        The ``byte_ingest`` hot path: tokenization never happens on the
        host — raw bytes are the only host->device transfer (uint8, ~4x
        less traffic than the padded int32 token matrix) and the
        ``bytes_to_bands`` kernel chain produces both arrays in one
        device-resident sweep.  Bit-identical to
        ``compute_arrays(tokenize(text, do_stem=False))``.

        ``pad_len`` buckets the byte-matrix width (must exceed the
        longest document's byte length; see ``shingle.pack_bytes``).
        With ``keep_device`` the device array of the signatures comes
        back third, for a verifier that keeps its rows on the device.
        """
        from repro.kernels import ops as kops

        with spans.span("pack") as pack:
            packed = shingle.pack_bytes(docs, pad_len)
        with spans.span("device_ingest", **_h2d(packed.data,
                                                packed.lengths)) as dev:
            sig_dev, bands, _ = kops.bytes_to_bands(
                jnp.asarray(packed.data),
                jnp.asarray(packed.lengths),
                self.device_seeds(),
                n=self.config.ngram,
                r=self.config.rows_per_band,
            )
            sig, bands = np.asarray(sig_dev), np.asarray(bands)
        self.stage_timings["signature_s"] = pack.seconds + dev.seconds
        self.stage_timings["bands_s"] = 0.0  # fused into the one pass
        return (sig, bands, sig_dev) if keep_device else (sig, bands)

    def ingest_arrays(
        self, token_lists: list[list[str]]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Deprecated spelling of :meth:`compute_arrays`.

        The old name collided with the session-layer ``ingest*`` verbs,
        which add documents to long-lived dedup state; this method never
        did (it is a pure stage computation).
        """
        import warnings

        warnings.warn(
            "DedupPipeline.ingest_arrays is deprecated; use "
            "compute_arrays (same signature, same outputs). 'ingest*' "
            "names are reserved for entry points that add documents to "
            "long-lived dedup state.",
            DeprecationWarning, stacklevel=2)
        return self.compute_arrays(token_lists)

    def make_verifier(self, token_lists: list[list[str]],
                      sig: np.ndarray):
        """The batched pair verifier for this config (``verify`` layer)."""
        cfg = self.config
        if cfg.exact_verification:
            return ExactJaccardVerifier.from_token_lists(
                token_lists, cfg.ngram)
        return SignatureVerifier(sig, backend=cfg.resolved_backend(),
                                 capacity=cfg.sig_store_capacity)

    # -- end to end ----------------------------------------------------------

    def run(self, texts: list[str]) -> DedupResult:
        """One-shot host dedup — a single-chunk ``DedupSession`` ingest.

        The session layer (``core.session``) owns the engine wiring;
        this adapter keeps the paper-shaped stage timings and the
        ``DedupResult`` contract (including the explicit verifier
        choice of ``make_verifier``).
        """
        from repro.core.session import DedupSession

        cfg = self.config
        timings = {}
        if cfg.byte_ingest:
            # Zero-copy path: no host tokenize; the engine only needs
            # per-doc placeholders (estimate mode never reads tokens).
            token_lists = [[] for _ in texts]
            timings["tokenize_s"] = 0.0
            pad_len = shingle.pow2_bucket(
                max((len(t.encode("utf-8")) for t in texts), default=0) + 1)
            sig, bands = self.compute_arrays_bytes(texts, pad_len)
        else:
            token_lists = self.tokenize(texts)
            timings["tokenize_s"] = self.stage_timings["tokenize_s"]
            sig, bands = self.compute_arrays(token_lists)
        timings["signatures_s"] = self.stage_timings["signature_s"]
        timings["bands_s"] = self.stage_timings["bands_s"]

        with spans.span("verifier_build") as s:
            verifier = self.make_verifier(token_lists, sig)
        timings["verifier_build_s"] = s.seconds

        with spans.span("cluster") as s:
            sess = DedupSession(cfg, backend="host", verifier=verifier)
            snap = sess._merge_precomputed(token_lists, sig, bands)
            uf, stats, pairs = sess.uf, snap.stats, snap.pairs
        timings["cluster_s"] = s.seconds
        timings["verify_s"] = stats.verify_seconds

        labels = snap.labels
        keep = np.zeros(len(texts), dtype=bool)
        seen: set[int] = set()
        for i, r in enumerate(labels):
            if int(r) not in seen:
                seen.add(int(r))
                keep[i] = True
        return DedupResult(
            labels=labels,
            keep_mask=keep,
            pairs=pairs,
            stats=stats,
            uf=uf,
            signatures=sig,
            bands=bands,
            timings=timings,
        )
