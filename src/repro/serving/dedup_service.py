"""Online dedup query service over a warm ``DedupSession``.

``DedupQueryService`` is the serving shell around the ``core.query``
read path (DESIGN.md §9): it holds a long-lived session, publishes its
immutable ``SessionView`` per ingest, and answers

    query(texts) -> [QueryResult(is_duplicate, cluster_root,
                                 best_sim, matched_doc)]

without mutating session state, plus ``admit(texts)`` to actually
ingest documents (the write path — after which the next query sees a
fresh view).

Two calling styles:

* **Synchronous** — ``query(texts)`` runs one batch end to end.
* **Microbatched** — ``submit`` / ``step`` / ``run_until_drained``,
  the same slot/queue shape as ``serving.engine.ServeEngine``'s
  continuous batching: callers enqueue single documents, each ``step``
  drains up to ``max_batch`` of them and executes ONE fused-ingest +
  probe + ONE batched device verify for the whole microbatch.  Per-
  query work is dominated by fixed dispatch overheads, so batching N
  queries costs far less than N sequential calls — that is the QPS
  story ``benchmarks/serving_dedup.py`` measures — while results are
  bit-identical to sequential queries (pinned by
  ``tests/test_query_service.py``).

The per-view verifier is cached by view version, so the device-
resident retained signature rows upload once per publication, not once
per query.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

from repro.core import shingle
from repro.core.pipeline import DedupPipeline
from repro.core.query import (
    ExactViewVerifier,
    QueryResult,
    ViewVerifier,
    query_view,
)
from repro.core.session import ClusterSnapshot, DedupSession, SessionView


@dataclass
class QueryRequest:
    """One enqueued query document (microbatched path)."""

    rid: int
    tokens: list[str]
    result: QueryResult | None = None
    enqueued_at: float = 0.0
    latency_s: float = 0.0
    done: bool = False


@dataclass
class QueryServiceStats:
    queries: int = 0
    microbatches: int = 0
    batch_occupancy_sum: float = 0.0
    admitted: int = 0
    duplicates_found: int = 0

    @property
    def mean_occupancy(self) -> float:
        """Mean microbatch fill fraction (of ``max_batch``)."""
        return self.batch_occupancy_sum / max(1, self.microbatches)


class DedupQueryService:
    """Low-latency "is this note a duplicate?" API over a warm session.

    ``session`` must use a backend that maintains the cross-step
    ``BandIndex`` (host or sharded — ``DedupSession.view`` enforces
    this).  ``backend`` picks the verify estimator for estimate-mode
    sessions (``numpy`` / ``jnp`` / ``pallas``; default: the session
    config's ``resolved_backend()``); exact-mode sessions always verify
    with the exact merge-count Jaccard.
    """

    def __init__(self, session: DedupSession, *, backend: str | None = None,
                 max_batch: int = 64):
        self.session = session
        self.backend = backend or session.config.resolved_backend()
        self.max_batch = int(max_batch)
        # The query-side stage pipeline: same config, same seeds as the
        # session, so a query's signatures/bands are bit-identical to
        # what ingesting the same document would compute.
        self.pipe = DedupPipeline(session.config)
        self.pipe.seeds = session.seeds
        self.queue: deque[QueryRequest] = deque()
        self.stats = QueryServiceStats()
        self._rid = 0
        self._verifier = None
        self._verifier_version = -1

    # -- read path -----------------------------------------------------------

    def view(self) -> SessionView:
        """The session's current published view (cached until ingest)."""
        return self.session.view()

    def _verifier_for(self, view: SessionView):
        if self._verifier is not None and \
                self._verifier_version == view.version:
            return self._verifier
        if view.mode == "exact":
            self._verifier = ExactViewVerifier(view)
        else:
            self._verifier = ViewVerifier(view, backend=self.backend)
        self._verifier_version = view.version
        return self._verifier

    def query(self, texts: list[str]) -> list[QueryResult]:
        """Answer one batch of query documents synchronously."""
        if self.session.config.byte_ingest:
            # Byte sessions tokenize on device (no-stem); the host
            # tokenizer below would stem and miss the ingested rows.
            return self.query_bytes(texts)
        return self.query_tokens(self.pipe.tokenize(texts))

    def query_bytes(self, texts: list[str | bytes]) -> list[QueryResult]:
        """``query`` straight from UTF-8 bytes — the zero-copy read path.

        Signatures/bands come out of the device-resident
        ``bytes_to_bands`` chain (no host tokenize), bit-identical to
        querying ``tokenize(text, do_stem=False)`` tokens, so results
        match ``byte_ingest`` sessions exactly.  Exact-mode views have
        no byte route (exact Jaccard needs host token lists).
        """
        if not texts:
            return []
        view = self.view()
        if view.mode == "exact":
            raise ValueError(
                "query_bytes serves estimate-mode views only; exact "
                "Jaccard verification needs host token lists — use "
                "query()/query_tokens() against this session")
        n = len(texts)
        raw = [t if isinstance(t, bytes) else t.encode("utf-8")
               for t in texts]
        # Same pow2 bucketing as _bucketed_arrays, on byte widths (the
        # +1 keeps the final-token emission column; see pack_bytes).
        lb = shingle.pow2_bucket(max(len(b) for b in raw) + 1)
        db = shingle.pow2_bucket(n, floor=8)
        padded = raw + [b"pad"] * (db - n)
        sig, bands = self.pipe.compute_arrays_bytes(padded, pad_len=lb)
        sig, bands = sig[:n], bands[:n]
        results = query_view(view, bands, sig=sig,
                             verifier=self._verifier_for(view))
        self.stats.queries += len(results)  # repro-lint: disable=RPR002
        self.stats.duplicates_found += sum(  # repro-lint: disable=RPR002
            r.is_duplicate for r in results)
        return results

    def query_tokens(
        self, token_lists: list[list[str]]
    ) -> list[QueryResult]:
        """``query`` over pre-tokenized documents."""
        if not token_lists:
            return []
        view = self.view()
        sig, bands = self._bucketed_arrays(token_lists)
        results = query_view(view, bands, sig=sig,
                             token_lists=token_lists,
                             verifier=self._verifier_for(view))
        # Telemetry counters only — no query ever reads them, so the
        # purity contract (RPR002) holds for everything queries observe.
        self.stats.queries += len(results)  # repro-lint: disable=RPR002
        self.stats.duplicates_found += sum(  # repro-lint: disable=RPR002
            r.is_duplicate for r in results)
        return results

    def _bucketed_arrays(self, token_lists):
        """Query-batch (sig, bands) with power-of-two shape bucketing.

        Serving sees a stream of tiny batches whose shapes all differ,
        and every new shape is a jit recompile.  Signatures are
        invariant to padding (validity is masked by real lengths), so
        both dimensions are padded up to power-of-two buckets via the
        shared ``shingle.pow2_bucket`` helper — a bounded compile set,
        amortized to zero — and the pad rows are dropped before
        verification.
        """
        n = len(token_lists)
        lb = shingle.pow2_bucket(max(len(t) for t in token_lists))
        db = shingle.pow2_bucket(n, floor=8)
        padded = list(token_lists) + [["pad"]] * (db - n)
        sig, bands = self.pipe.compute_arrays(padded, pad_len=lb)
        return sig[:n], bands[:n]

    # -- write path ----------------------------------------------------------

    def admit(self, texts: list[str]) -> ClusterSnapshot:
        """Ingest documents into the session (the write path).

        The next ``view()`` read publishes a fresh ``SessionView``
        covering them; queries already holding the old view keep their
        frozen state (DESIGN.md §9).
        """
        snap = self.session.ingest(list(texts))
        self.stats.admitted = snap.n_docs
        return snap

    # -- microbatching (continuous-batching shape) ---------------------------

    def submit(self, text: str) -> int:
        """Enqueue one query document; returns its request id."""
        self._rid += 1
        # Byte sessions match the device tokenizer (no-stem); the
        # token-path signatures over those tokens are bit-identical to
        # the bytes_to_bands chain, so microbatched results agree with
        # query_bytes exactly.
        toks = shingle.tokenize(
            text, do_stem=not self.session.config.byte_ingest)
        self.queue.append(QueryRequest(
            self._rid, toks, enqueued_at=time.perf_counter()))
        return self._rid

    def step(self) -> int:
        """Serve one microbatch: drain up to ``max_batch`` queued
        queries, run ONE fused ingest + probe + batched verify for all
        of them.  Returns the number of queries served."""
        if not self.queue:
            return 0
        batch: list[QueryRequest] = []
        while self.queue and len(batch) < self.max_batch:
            batch.append(self.queue.popleft())
        results = self.query_tokens([r.tokens for r in batch])
        now = time.perf_counter()
        for req, res in zip(batch, results):
            req.result = res
            req.latency_s = now - req.enqueued_at
            req.done = True
        self.stats.microbatches += 1
        self.stats.batch_occupancy_sum += len(batch) / self.max_batch
        return len(batch)

    def run_until_drained(self,
                          max_steps: int = 10_000) -> list[QueryRequest]:
        """Step until the queue is empty; returns finished requests."""
        finished: list[QueryRequest] = []
        pending: dict[int, QueryRequest] = {r.rid: r for r in self.queue}
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
            for rid, r in list(pending.items()):
                if r.done:
                    finished.append(r)
                    del pending[rid]
        return finished
