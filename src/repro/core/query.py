"""Online dedup read path: probe + verify over a ``SessionView``.

The batch pipeline answers "which notes in the corpus are duplicates";
the north-star workload also needs the online form — given ONE incoming
note, is it a (near-)duplicate of anything already ingested, and of
which cluster?  This module is that read path (DESIGN.md §9), built
entirely over the immutable ``core.session.SessionView``:

    query texts -> fused ingest (signatures + band values, the SAME
    ``DedupPipeline.compute_arrays`` stage the write path runs)
    -> band probe against the view's frozen bucket maps (LSHBloom-style:
    a compacted key still answers "seen before" via the Bloom filter)
    -> batched verify of (retained doc, query) candidate pairs
    -> threshold at the engine's edge threshold.

Estimator parity is load-bearing: the verify step reuses the engine's
exact estimators bit-for-bit (``(a == b).mean`` in float32 for
signature sessions — host numpy, or the fused
``kernels.sigjaccard.indexed_pair_counts`` gather kernel on device with
the /M in numpy —
and the merge-count exact Jaccard for exact sessions), so querying an
already-ingested document reproduces the session's recorded pair sims
exactly.  Queries NEVER mutate session state: probes run over the
view's frozen copies, and exact-mode interning only ``get``s from the
shared append-only vocab.

``serving.dedup_service.DedupQueryService`` wraps this over a warm
session and adds the microbatching loop.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core import sanitize
from repro.core.hashing import GOLDEN32, U32_MAX, fmix32_np
from repro.core.session import SessionView
from repro.core.shingle import pow2_bucket

# Query batches at least this large probe on device (sorted-band-key
# searchsorted) instead of walking the host band dicts; smaller batches
# stay on the host, where the dict walk wins on latency.
PROBE_DEVICE_MIN_BATCH = 32


@dataclass(frozen=True)
class QueryResult:
    """Verdict for one query document against a ``SessionView``.

    ``is_duplicate`` uses the engine's edge semantics
    (``sim > edge_threshold``); ``cluster_root`` / ``matched_doc`` are
    ``None`` for novel documents.  ``candidates`` keeps every verified
    (retained doc, sim) pair, best first, for callers that want the
    full ranking; ``filter_only_hits`` counts band keys that hit a
    compacted Bloom filter — "seen before, but by a doc the index can
    no longer name" (the LSHBloom recall trade, DESIGN.md §7).
    """

    is_duplicate: bool
    cluster_root: int | None
    best_sim: float
    matched_doc: int | None
    n_candidates: int = 0
    filter_only_hits: int = 0
    candidates: tuple = ()

    @property
    def novel(self) -> bool:
        return not self.is_duplicate


def _band_key32(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Mix a band's (hi, lo) 2-lane value into one 32-bit probe key.

    x64 is disabled on the accelerator, so the device index stores one
    mixed uint32 per (hi, lo) pair instead of the 64-bit concatenation.
    A collision only ever costs a confirming host ``dict.get`` (the
    probe is one-sided: every true key is found).
    """
    with np.errstate(over="ignore"):
        x = (fmix32_np(hi.astype(np.uint32)) ^ lo.astype(np.uint32))
        return fmix32_np((x * GOLDEN32).astype(np.uint32))


_PROBE_JIT = None


def _get_probe_jit():
    global _PROBE_JIT
    if _PROBE_JIT is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def probe(keys, counts, qkeys):
            # keys (b, K) sorted uint32 (U32_MAX padded); counts (b,)
            # int32 real sizes; qkeys (b, Q) uint32.
            idx = jax.vmap(jnp.searchsorted)(keys, qkeys)
            idx_c = jnp.minimum(idx, keys.shape[1] - 1)
            found = jnp.take_along_axis(keys, idx_c, axis=1) == qkeys
            return found & (idx < counts[:, None])

        _PROBE_JIT = probe
    return _PROBE_JIT


def _device_probe_index(view: SessionView):
    """Lazily build (and cache on the view) the device band-key index.

    Per band: the sorted unique mixed keys of every dict entry, padded
    with ``U32_MAX`` to one shared pow2 width.  The view is immutable,
    so the index is valid for its whole lifetime.  Returns ``None``
    when the view has no band entries (nothing to probe on device).
    """
    cached = view._probe_cache.get("band_keys")
    if cached is not None:
        return cached
    import jax.numpy as jnp

    per_band = []
    n_max = 0
    for m in view.band_maps:
        if m:
            ks = np.array(list(m.keys()), dtype=np.uint32)  # (n, 2)
            uniq = np.unique(_band_key32(ks[:, 0], ks[:, 1]))
        else:
            uniq = np.zeros((0,), dtype=np.uint32)
        per_band.append(uniq)
        n_max = max(n_max, len(uniq))
    if n_max == 0:
        return None
    k_bucket = pow2_bucket(n_max, floor=128)
    keys = np.full((len(per_band), k_bucket), U32_MAX, dtype=np.uint32)
    counts = np.zeros((len(per_band),), dtype=np.int32)
    for j, uniq in enumerate(per_band):
        keys[j, : len(uniq)] = uniq
        counts[j] = len(uniq)
    index = (jnp.asarray(keys), jnp.asarray(counts))
    view._probe_cache["band_keys"] = index
    return index


def _probe_device(view: SessionView, bands: np.ndarray,
                  index) -> tuple[list[np.ndarray], list[int]]:
    """Device-resident band probe, dict-walk parity by construction.

    The searchsorted membership test has no false negatives (every true
    key's mix is in the sorted index), so a device miss IS a dict miss;
    device hits are confirmed against the host dict, so 32-bit mix
    collisions cannot add candidates.  Bloom fall-through for misses
    matches the walk exactly.
    """
    import jax.numpy as jnp

    keys_dev, counts_dev = index
    q = len(bands)
    qkeys = _band_key32(bands[:, :, 0], bands[:, :, 1])  # (Q, b)
    # Bucket the query dim so repeated batch sizes share jit compiles.
    q_bucket = pow2_bucket(q, floor=PROBE_DEVICE_MIN_BATCH)
    qk = np.zeros((q_bucket, qkeys.shape[1]), dtype=np.uint32)
    qk[:q] = qkeys
    hits = np.asarray(_get_probe_jit()(
        keys_dev, counts_dev, jnp.asarray(qk.T))).T[:q]  # (Q, b)
    cands: list[set[int]] = [set() for _ in range(q)]
    filter_hits = [0] * q
    for j, m in enumerate(view.band_maps):
        col = bands[:, j, :]
        flt = view.band_filters[j]
        hj = hits[:, j]
        for i in range(q):
            key = (int(col[i, 0]), int(col[i, 1]))
            if hj[i]:
                olds = m.get(key)
                if olds is not None:
                    cands[i].update(olds)
                    continue
            if flt is not None and key in flt:
                filter_hits[i] += 1
    out = [np.array(sorted(s), dtype=np.int64) for s in cands]
    return out, filter_hits


def probe_candidates(
    view: SessionView, bands: np.ndarray, *,
    device_min_batch: int = PROBE_DEVICE_MIN_BATCH,
) -> tuple[list[np.ndarray], list[int]]:
    """Band-probe query band values against a view's frozen maps.

    ``bands`` is the (Q, b, 2) query band matrix (same layout the write
    path inserts).  Returns per-query sorted unique candidate doc-id
    arrays plus per-query compacted-key (Bloom-only) hit counts.  Pure
    read: unlike ``BandIndex.match_then_insert`` nothing is inserted
    and no LRU recency moves — which is exactly why it runs over the
    view's exported copies rather than the live index.

    Batches of ``device_min_batch`` or more route through a
    device-resident sorted-band-key ``searchsorted`` probe (the index
    is built once per view and cached); results are identical to the
    host dict walk — device hits are dict-confirmed, and the probe has
    no false negatives (see ``_probe_device``).
    """
    bands = np.asarray(bands)
    if bands.ndim != 3 or bands.shape[1] != view.num_bands:
        raise ValueError(
            f"expected (Q, {view.num_bands}, 2) bands, got {bands.shape}")
    q = len(bands)
    if view.band_store is not None:
        # Disk-tier view (DESIGN.md §12): delegate to the store's pure
        # Bloom-first probe — a primary-filter miss never touches disk,
        # a hit pays one batched SELECT.  Candidates are clipped to the
        # view's publication coverage so docs ingested after this view
        # was published stay invisible to it.
        cands, filter_hits = view.band_store.probe_keys(bands)
        return [c[c < view.n_docs] for c in cands], filter_hits
    if q >= device_min_batch:
        index = _device_probe_index(view)
        if index is not None:
            return _probe_device(view, bands, index)
    cands: list[set[int]] = [set() for _ in range(q)]
    filter_hits = [0] * q
    for j, m in enumerate(view.band_maps):
        col = bands[:, j, :]
        flt = view.band_filters[j]
        for i in range(q):
            key = (int(col[i, 0]), int(col[i, 1]))
            olds = m.get(key)
            if olds is not None:
                cands[i].update(olds)
            elif flt is not None and key in flt:
                filter_hits[i] += 1
    out = [np.array(sorted(s), dtype=np.int64) for s in cands]
    return out, filter_hits


class ViewVerifier:
    """Batched (retained doc, query) estimator over one view.

    The signature-session analogue of ``verify.SignatureVerifier``,
    specialised to mixed operands: one side gathers from the view's
    frozen retained rows, the other from the query batch.  Backends
    match the write path — ``numpy`` host estimate, or ``jnp`` /
    ``pallas`` via the fused gather kernel over a device-resident
    ``[retained rows; query rows]`` stack (the view's rows upload ONCE
    per verifier and are reused across every microbatch; only the
    small query block re-uploads).  All backends produce bit-identical
    float32 sims (pinned by the engine's backend-parity tests), so the
    query pin — sims bit-equal to the session's recorded pairs — holds
    on any backend.
    """

    batch_pairs = 8192

    def __init__(self, view: SessionView, backend: str = "numpy"):
        if backend not in ("numpy", "jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if view.mode != "estimate":
            raise ValueError("ViewVerifier needs an estimate-mode view; "
                             "use ExactViewVerifier for exact sessions")
        self.view = view
        self.backend = backend
        self._dev_sig = None           # retained rows, uploaded once
        self.n_pairs = 0
        self.n_batches = 0

    def _device_retained(self):
        import jax.numpy as jnp

        if self._dev_sig is None:
            self._dev_sig = jnp.asarray(self.view.signatures)
        return self._dev_sig

    def sims(self, q_sigs: np.ndarray, cand_ids: np.ndarray,
             q_idx: np.ndarray) -> np.ndarray:
        """sims[p] = estimate(retained row of cand_ids[p], q_sigs[q_idx[p]])."""
        cand_ids = np.asarray(cand_ids, dtype=np.int64)
        q_idx = np.asarray(q_idx, dtype=np.int64)
        if cand_ids.size == 0:
            return np.zeros((0,), dtype=np.float32)
        out = np.empty(len(cand_ids), dtype=np.float32)
        for s in range(0, len(cand_ids), self.batch_pairs):
            c = cand_ids[s : s + self.batch_pairs]
            qi = q_idx[s : s + self.batch_pairs]
            out[s : s + len(c)] = self._sims_batch(q_sigs, c, qi)
            self.n_batches += 1
        self.n_pairs += len(cand_ids)
        return out

    def _sims_batch(self, q_sigs, cand_ids, q_idx) -> np.ndarray:
        view = self.view
        if self.backend == "numpy":
            a = view.rows_for(cand_ids)
            b = np.asarray(q_sigs)[q_idx]
            return (a == b).mean(axis=-1, dtype=np.float32)
        import jax.numpy as jnp

        retained = self._device_retained()
        n_ret = retained.shape[0]
        stack = jnp.concatenate([retained, jnp.asarray(q_sigs)], axis=0)
        from repro.core.verify import device_estimate

        # device_estimate pads the indices to its pair buckets.
        return device_estimate(self.backend, stack,
                               view.slot_index(cand_ids), n_ret + q_idx)


class ExactViewVerifier:
    """Exact-Jaccard query verifier over a view's frozen token rows.

    Query n-grams are interned READ-ONLY against the session's shared
    vocab (``dict.get`` only — the write path's ``setdefault`` is what
    assigns new ids, and queries must not mutate session state).  A
    query n-gram the vocab has never seen cannot intersect any stored
    row, so it contributes to the union count only; intersections are
    exact merge-counts against the stored sorted id rows, and the final
    ``inter / union`` is computed with the same float64-divide +
    float32-cast as ``verify.ExactJaccardVerifier`` for bit parity.
    """

    def __init__(self, view: SessionView):
        if view.exact is None:
            raise ValueError("view has no exact token rows; "
                             "use ViewVerifier for estimate sessions")
        self.view = view
        self.n_pairs = 0
        self.n_batches = 0

    def intern_queries(
        self, token_lists: list[list[str]]
    ) -> tuple[list[np.ndarray], np.ndarray]:
        """Per-query (known-id row, total n-gram count incl. unknown)."""
        from repro.core.shingle import ngram_set

        ex = self.view.exact
        vocab = ex.vocab
        rows, totals = [], []
        for toks in token_lists:
            grams = ngram_set(toks, ex.ngram)
            ids = [vocab.get(g) for g in grams]
            known = np.sort(np.array(
                [i for i in ids if i is not None], dtype=np.int64))
            rows.append(known)
            totals.append(len(grams))
        return rows, np.asarray(totals, dtype=np.int64)

    def sims(self, q_rows: list[np.ndarray], q_totals: np.ndarray,
             cand_ids: np.ndarray, q_idx: np.ndarray) -> np.ndarray:
        ex = self.view.exact
        cand_ids = np.asarray(cand_ids, dtype=np.int64)
        q_idx = np.asarray(q_idx, dtype=np.int64)
        if cand_ids.size == 0:
            return np.zeros((0,), dtype=np.float32)
        inter = np.empty(len(cand_ids), dtype=np.int64)
        la = np.empty(len(cand_ids), dtype=np.int64)
        for p, (doc, qi) in enumerate(zip(cand_ids, q_idx)):
            stored = ex.row_for(int(doc))
            la[p] = len(stored)
            inter[p] = np.intersect1d(
                stored, q_rows[int(qi)], assume_unique=True).size
        union = la + q_totals[q_idx] - inter
        self.n_pairs += len(cand_ids)
        self.n_batches += 1
        # Two empty sets have Jaccard 1.0 (matches ExactJaccardVerifier).
        return np.where(
            union > 0, inter / np.maximum(union, 1), 1.0
        ).astype(np.float32)


def _flatten(cands: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Per-query candidate lists -> flat (cand_ids, q_idx) pair arrays."""
    if not any(len(c) for c in cands):
        e = np.zeros((0,), dtype=np.int64)
        return e, e
    cand_ids = np.concatenate([c for c in cands if len(c)])
    q_idx = np.concatenate([np.full(len(c), i, dtype=np.int64)
                            for i, c in enumerate(cands) if len(c)])
    return cand_ids, q_idx


def query_view(
    view: SessionView,
    bands: np.ndarray,
    *,
    sig: np.ndarray | None = None,
    token_lists: list[list[str]] | None = None,
    backend: str = "numpy",
    verifier=None,
) -> list[QueryResult]:
    """Probe + verify one query batch against a view.

    ``bands`` (Q, b, 2) drives the probe; verification needs ``sig``
    (Q, M) for estimate-mode views or ``token_lists`` for exact-mode
    views (both come out of the same write-path stages —
    ``DedupPipeline.compute_arrays`` / ``tokenize``).  Pass a cached
    ``ViewVerifier`` / ``ExactViewVerifier`` via ``verifier`` to reuse
    its device-resident retained rows across calls (the service does).

    With ``REPRO_SANITIZE=1`` the view's arrays are fingerprinted and
    re-checked on entry and exit (``sanitize.SessionViewMutated`` on
    drift) — the dynamic half of the RPR002 purity contract.
    """
    sanitize.check_view(view, "query entry")
    cands, filter_hits = probe_candidates(view, bands)
    cand_ids, q_idx = _flatten(cands)
    if view.mode == "estimate":
        if sig is None:
            raise ValueError("estimate-mode query needs sig (Q, M)")
        v = verifier if verifier is not None else ViewVerifier(
            view, backend=backend)
        sims = v.sims(sig, cand_ids, q_idx)
    else:
        if token_lists is None:
            raise ValueError("exact-mode query needs token_lists")
        v = verifier if verifier is not None else ExactViewVerifier(view)
        q_rows, q_totals = v.intern_queries(token_lists)
        sims = v.sims(q_rows, q_totals, cand_ids, q_idx)

    out: list[QueryResult] = []
    start = 0
    for i, c in enumerate(cands):
        s = sims[start : start + len(c)]
        start += len(c)
        if len(c) == 0:
            out.append(QueryResult(
                is_duplicate=False, cluster_root=None, best_sim=0.0,
                matched_doc=None, n_candidates=0,
                filter_only_hits=filter_hits[i]))
            continue
        order = np.lexsort((c, -s.astype(np.float64)))
        ranked = tuple((int(c[k]), float(s[k])) for k in order)
        best_doc, best_sim = ranked[0]
        # Engine edge semantics: an edge merges iff sim > threshold
        # (float32 sim against the raw config float, same promotion as
        # ClusterAccumulator's flush).
        dup = bool(s[order[0]] > view.edge_threshold)
        out.append(QueryResult(
            is_duplicate=dup,
            cluster_root=view.root_of(best_doc) if dup else None,
            best_sim=best_sim,
            matched_doc=best_doc if dup else None,
            n_candidates=len(c),
            filter_only_hits=filter_hits[i],
            candidates=ranked))
    sanitize.check_view(view, "query exit")
    return out
