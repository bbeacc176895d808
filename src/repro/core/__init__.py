"""Core library: MinHash-LSH deduplication (the paper's contribution).

The dedup hot path is a staged engine (``engine.cluster_source``)::

    CandidateSource  ->  BatchVerifier  ->  ThresholdUnionFind
    (candidates.py)      (verify.py)        (unionfind.py)

with three thin drivers: ``DedupPipeline`` (host, in-memory),
``StreamingDedup`` (out-of-core band store) and ``dist_lsh`` (sharded,
on-device) — all adapters over ``DedupSession`` (``session.py``), the
long-lived incremental-ingest layer (one accumulator, global doc-id
allocation, retained signatures; chunked corpora cluster across steps).

Public API surface (PR 7)
-------------------------
This package IS the blessed import surface — ``from repro.core import
DedupSession, DedupConfig, ...`` — deep module paths stay importable
but are not API-stable.  The blessed names:

* write path — ``DedupSession`` (+ ``DedupConfig``, ``DistLSHConfig``,
  ``RetentionPolicy``), returning pure-value ``ClusterSnapshot``s;
* read path — ``SessionView`` (``DedupSession.view()``),
  ``QueryResult`` / ``query_view`` (``core.query``), and the serving
  shell ``DedupQueryService`` (``serving.dedup_service``, re-exported
  here lazily: ``dedup_service`` imports this package, so a plain
  import would be a cycle).

Naming scheme for ingest-shaped entry points: a method is named
``ingest*`` iff it ADDS DOCUMENTS to long-lived dedup state —
``DedupSession.ingest`` / ``ingest_tokens`` / ``ingest_stream`` and
``StreamingDedup.ingest`` (its store is retained state).  Pure stage
computations are ``compute_*`` (``DedupPipeline.compute_signatures`` /
``compute_bands`` / ``compute_arrays``); reads are ``query*`` / ``view``
and never mutate.  Old spellings (``DedupPipeline.ingest_arrays``,
``ClusterSnapshot.uf``) survive as ``DeprecationWarning`` shims.

Running the linter
------------------
The scheme above — plus the uint32 bit-parity discipline, read-path
purity, jit shape bucketing, and Pallas BlockSpec/VMEM budgets — is
machine-checked by the repo's own static-analysis pass::

    PYTHONPATH=src python -m repro.analysis            # text report
    PYTHONPATH=src python -m repro.analysis --format json

Rules RPR001-RPR005 are documented in DESIGN.md §10; grandfathered
findings live in ``.repro-lint-baseline.json`` and intentional
exceptions carry ``# repro-lint: disable=RPR00x`` comments.  CI runs
the pass (plus ruff) as the ``lint`` job before tier-1.  Set
``REPRO_SANITIZE=1`` for the runtime tripwires (``core.sanitize``):
``jax_debug_nans`` and the SessionView mutation check in query paths.
"""
from repro.core import sanitize as _sanitize
from repro.core.pipeline import DedupConfig, DedupPipeline, DedupResult
from repro.core.lsh import LSHParams, candidate_probability
from repro.core.unionfind import ThresholdUnionFind, connected_components
from repro.core.dist_lsh import (
    DistLSHConfig,
    ShardedClusterResult,
    StepFeed,
    cluster_step_output,
    docs_mesh,
    feed_step_groups,
    make_dedup_step,
    make_streamed_dedup_step,
)
from repro.core.retention import (
    BandBloomFilter,
    RetentionManager,
    RetentionPolicy,
)
from repro.core.session import (
    BandIndex,
    ClusterSnapshot,
    DedupSession,
    DocIdAllocator,
    SessionView,
)
from repro.core.query import QueryResult, query_view
from repro.core.candidates import (
    BandMatrixSource,
    CandidateSource,
    EdgeStreamSource,
    ShardedEdgeSource,
    StoreBandSource,
    candidate_pairs,
)
from repro.core.engine import (
    ClusterAccumulator,
    ClusterStats,
    PairList,
    cluster_source,
)
from repro.core.verify import (
    BatchVerifier,
    CallbackVerifier,
    DeviceScoredEdgeVerifier,
    ExactJaccardVerifier,
    ShardedEdgeVerifier,
    SignatureVerifier,
)

__all__ = [
    "DedupConfig",
    "DedupPipeline",
    "DedupResult",
    "LSHParams",
    "candidate_probability",
    "ThresholdUnionFind",
    "connected_components",
    "DistLSHConfig",
    "ShardedClusterResult",
    "StepFeed",
    "cluster_step_output",
    "feed_step_groups",
    "make_dedup_step",
    "make_streamed_dedup_step",
    "docs_mesh",
    "BandBloomFilter",
    "RetentionManager",
    "RetentionPolicy",
    "BandIndex",
    "ClusterSnapshot",
    "DedupSession",
    "DedupQueryService",
    "DocIdAllocator",
    "SessionView",
    "QueryResult",
    "query_view",
    "BandMatrixSource",
    "CandidateSource",
    "EdgeStreamSource",
    "ShardedEdgeSource",
    "StoreBandSource",
    "candidate_pairs",
    "ClusterAccumulator",
    "ClusterStats",
    "PairList",
    "cluster_source",
    "BatchVerifier",
    "CallbackVerifier",
    "DeviceScoredEdgeVerifier",
    "ExactJaccardVerifier",
    "ShardedEdgeVerifier",
    "SignatureVerifier",
]

# REPRO_SANITIZE=1 flips jax_debug_nans once, at import (the view
# tripwire in core.query reads the env per call and needs no install).
_sanitize.maybe_install()


def __getattr__(name: str):
    # Lazy re-export: repro.serving.dedup_service imports repro.core
    # submodules, so a plain import here would be a cycle. Importing
    # repro.serving first would run this package's __init__ while
    # dedup_service is half-initialised and fail to find
    # DedupQueryService; resolving it on first access breaks the cycle.
    if name == "DedupQueryService":
        from repro.serving.dedup_service import DedupQueryService

        return DedupQueryService
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
