"""Batched pair verification layer of the staged dedup engine.

Staged-engine architecture (see also ``candidates.py`` and
``engine.py``)::

    CandidateSource  ->  BatchVerifier  ->  ThresholdUnionFind

A ``BatchVerifier`` maps a (P, 2) int array of candidate doc pairs to a
(P,) float32 similarity vector in device-sized batches, replacing the
per-pair Python ``similarity_fn(a, b)`` callbacks the three execution
paths used to carry.  Backends:

===================  =====================================================
verifier             computes
===================  =====================================================
SignatureVerifier    signature-agreement estimate m/M (paper §3.4) over
                     gathered signature rows; backend ``numpy`` (host),
                     ``jnp`` (gather + count under jit) or ``pallas``
                     (``kernels.sigjaccard.indexed_pair_counts``); the
                     device counts, numpy divides by M
ExactJaccardVerifier exact set Jaccard (paper §2.1) vectorized over
                     pre-sorted n-gram id arrays (merge-count, no
                     Python set ops on the hot path)
ShardedEdgeVerifier  full-signature re-verify of the ``dist_lsh``
                     prefix-prescreen survivors (stage 2 of the sharded
                     path's two-stage verify); same estimator/backends
                     as SignatureVerifier by construction
DeviceScoredEdge-    pass-through for the device-resident stage-2 mode:
Verifier             serves scores the ``kernels.sigjaccard`` shard_map
                     kernel already computed, re-scores only cross-shard
                     stragglers
CallbackVerifier     compat shim around a scalar ``fn(a, b) -> float``
===================  =====================================================

All verifiers record ``n_batches`` / ``n_pairs`` / ``seconds`` so
drivers and benchmarks can report batched-verification throughput.

The device backends of ``SignatureVerifier`` keep the retained rows in
a ``SignatureStore``: one device buffer that new rows are written into
in place, so a chunk copies only its own rows to the device.
"""
from __future__ import annotations

import functools
import time
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.shingle import pow2_bucket


class BatchVerifier:
    """Base class: ``verifier(pairs (P, 2)) -> sims (P,) float32``.

    Subclasses implement ``_verify_batch``; ``__call__`` handles
    batching, empty input, and throughput accounting.
    """

    batch_pairs: int = 8192

    def __init__(self):
        self.n_batches = 0
        self.n_pairs = 0
        self.seconds = 0.0

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def __call__(self, pairs: np.ndarray) -> np.ndarray:
        pairs = np.asarray(pairs)
        if pairs.size == 0:
            return np.zeros((0,), dtype=np.float32)
        pairs = pairs.reshape(-1, 2)
        t0 = time.perf_counter()
        out = np.empty(len(pairs), dtype=np.float32)
        for s in range(0, len(pairs), self.batch_pairs):
            chunk = pairs[s : s + self.batch_pairs]
            out[s : s + len(chunk)] = np.asarray(
                self._verify_batch(chunk), dtype=np.float32
            )[: len(chunk)]
            self.n_batches += 1
        self.n_pairs += len(pairs)
        self.seconds += time.perf_counter() - t0
        return out

    @property
    def pairs_per_second(self) -> float:
        return self.n_pairs / self.seconds if self.seconds > 0 else 0.0


class CallbackVerifier(BatchVerifier):
    """Wrap a scalar ``similarity_fn(a, b) -> float`` (compat path)."""

    def __init__(self, fn: Callable[[int, int], float]):
        super().__init__()
        self.fn = fn

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        return np.array(
            [self.fn(int(a), int(b)) for a, b in pairs], dtype=np.float32
        )


@functools.partial(jax.jit, donate_argnums=0)
def _store_block(buf, rows, start):
    """``rows`` written into ``buf`` from row ``start`` on, in place."""
    return jax.lax.dynamic_update_slice(buf, rows.astype(buf.dtype),
                                        (start, 0))


@functools.partial(jax.jit, donate_argnums=0)
def _store_slots(buf, rows, slots):
    """Row i of ``rows`` written into row ``slots[i]`` of ``buf``."""
    return buf.at[slots, :rows.shape[1]].set(rows.astype(buf.dtype))


@functools.partial(jax.jit, static_argnums=1)
def _store_grown(buf, capacity):
    """``buf``'s rows at the top of a zero buffer of ``capacity`` rows."""
    return jax.lax.dynamic_update_slice(
        jnp.zeros((capacity, buf.shape[1]), buf.dtype), buf, (0, 0))


LANES = 128


class SignatureStore:
    """Retained signature rows on the device: one (capacity, W) uint32
    buffer, zero past the rows written, whose rows hold the ``width``
    hashes of a signature padded to whole 128-lane tiles (W = 128 for
    M = 112).  The TPU lays such a buffer out row-major, as a row
    gather reads it; a (capacity, 112) buffer it lays out column-major,
    and every gather from it would first copy the whole store.

    ``write`` copies rows into the buffer in place (the old buffer is
    donated), from the host or from a device array that already holds
    them, inside a ``dedup.sig_store`` span whose ``h2d_bytes`` counts
    what it copied to the device: the rows when they come from the
    host, and the row offset or the slot indices.  With a fixed
    ``capacity`` the buffer never changes shape, so a verify program
    over it compiles once per batch shape; with capacity 0 it doubles
    when a write passes its end, copying the old rows on the device.
    """

    def __init__(self, width: int, capacity: int = 0):
        self.fixed = capacity > 0
        self.width = int(width)
        lanes = -(-self.width // LANES) * LANES
        self.buf = jnp.zeros((int(capacity), lanes), jnp.uint32)

    @property
    def capacity(self) -> int:
        return self.buf.shape[0]

    def _reserve(self, rows: int) -> None:
        if rows <= self.capacity:
            return
        if self.fixed:
            raise ValueError(
                f"the signature store holds {self.capacity} rows and "
                f"{rows} are needed; raise DedupConfig.sig_store_capacity")
        self.buf = _store_grown(self.buf, max(rows, 2 * self.capacity))

    def write(self, rows, *, start: int | None = None, slots=None,
              device_rows=None) -> None:
        """Write row i of ``rows`` at ``start + i``, or at ``slots[i]``.

        ``device_rows``, when given, is a device array holding the same
        rows; nothing of them is copied from the host then.
        """
        with spans.span("sig_store") as sp:
            h2d = 0
            if device_rows is None:
                rows = np.asarray(rows, dtype=np.uint32)
                device_rows = jax.device_put(rows)
                h2d += rows.nbytes
            if slots is None:
                self._reserve(start + device_rows.shape[0])
                offset = np.int32(start)
                self.buf = _store_block(self.buf, device_rows, offset)
                h2d += offset.nbytes
            else:
                slots = np.asarray(slots, dtype=np.int32)
                self._reserve(int(slots.max()) + 1)
                self.buf = _store_slots(self.buf, device_rows, slots)
                h2d += slots.nbytes
            sp.count(h2d_bytes=h2d)


class SignatureVerifier(BatchVerifier):
    """Signature-agreement estimate over gathered signature rows.

    ``backend``:
      * ``"numpy"`` — host vectorized ``(sig[a] == sig[b]).mean(-1)``.
      * ``"jnp"``   — jitted gather + agreement count on device.
      * ``"pallas"`` — ``kernels.sigjaccard.indexed_pair_counts`` TPU
        kernel (interpret mode on CPU).
      Both device backends divide the counts by M in numpy
      (``device_estimate``), bit-identical to ``"numpy"``.  They keep
      the retained rows in a ``SignatureStore`` of ``capacity`` rows
      (0: grown by doubling) beside the host matrix, and write each
      extension into it.  Over a fixed capacity the verify program's
      shapes are the pair buckets of ``device_estimate`` up to
      ``batch_pairs``, and the store's creation compiles every one, so
      a later batch of a size set-up never met compiles nothing.
    """

    def __init__(self, signatures: np.ndarray, backend: str = "numpy",
                 batch_pairs: int = 8192, capacity: int = 0):
        super().__init__()
        if backend not in ("numpy", "jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.batch_pairs = int(batch_pairs)
        self.capacity = int(capacity)
        self._set_signatures(np.asarray(signatures))

    def _set_signatures(self, sig: np.ndarray, device_rows=None):
        # The matrix is adopted as the growth buffer; extensions write
        # past ``_n_rows`` after a capacity-doubling copy, so repeated
        # chunk appends are amortized O(chunk).  The device backends
        # write the same rows into their store.  Row i holds doc i
        # until the first ``release_rows`` call, which switches the
        # verifier to an explicit doc -> slot map with a free-slot pool
        # (retention layer, DESIGN.md §7).
        self._buf = sig
        self._n_rows = len(sig)
        self.signatures = sig
        self._slot_of: dict[int, int] | None = None
        self._free: list[int] = []
        self._n_docs = len(sig)
        self._store: SignatureStore | None = None
        if len(sig):
            self._device_write(sig, device_rows, start=0)

    def _device_write(self, rows: np.ndarray, device_rows, *,
                      start: int | None = None, slots=None) -> None:
        if self.backend == "numpy":
            return
        if self._store is None:
            self._store = SignatureStore(rows.shape[1], self.capacity)
            if self._store.fixed:
                self._compile_buckets()
        self._store.write(rows, start=start, slots=slots,
                          device_rows=device_rows)

    def _compile_buckets(self) -> None:
        """Run the verify program once at every pair bucket up to
        ``batch_pairs`` over the (still empty) store."""
        bucket = pow2_bucket(1)
        while True:
            zeros = np.zeros(bucket, dtype=np.int32)
            device_estimate(self.backend, self._store.buf, zeros, zeros,
                            self._store.width)
            if bucket >= self.batch_pairs:
                return
            bucket *= 2

    # -- retention (free-slot pool) ----------------------------------------

    @property
    def n_live_rows(self) -> int:
        """Rows currently holding a retained document's signature."""
        if self._slot_of is None:
            return self._n_rows
        return len(self._slot_of)

    def _slot_index(self, ids: np.ndarray) -> np.ndarray:
        """Translate global doc ids to physical row slots."""
        if self._slot_of is None:
            return ids
        so = self._slot_of
        try:
            return np.fromiter((so[int(i)] for i in ids.ravel()),
                               dtype=np.int64,
                               count=ids.size).reshape(ids.shape)
        except KeyError as e:
            raise KeyError(
                f"doc {e.args[0]} has no retained signature row (evicted "
                "by the retention policy); only union-find roots and the "
                "LRU window are verifiable") from None

    def release_rows(self, doc_ids) -> int:
        """Evict docs' signature rows into the free-slot pool.

        The first call switches the verifier from the implicit
        ``row i == doc i`` layout to an explicit doc -> slot map; freed
        slots are reused by later ``extend_signatures`` calls, so the
        matrix stops growing once eviction keeps pace with ingest
        (memory O(live rows), not O(docs ever ingested)).  Releasing an
        unknown / already-released doc raises.
        """
        if self._slot_of is None:
            self._slot_of = {i: i for i in range(self._n_rows)}
        released = 0
        for d in doc_ids:
            d = int(d)
            try:
                slot = self._slot_of.pop(d)
            except KeyError:
                raise KeyError(f"doc {d} has no retained row to release")
            self._free.append(slot)
            released += 1
        return released

    def adopt_layout(self, other: "SignatureVerifier") -> None:
        """Share ``other``'s retained matrix, slot layout and device
        store (zero-copy).

        The session keeps a plain-estimator view over a
        ``DeviceScoredEdgeVerifier``'s matrix for host-generated edges;
        eviction mutates rows in place, so the view must re-adopt the
        owner's buffer/slot state before each use.  The owner writes
        its extensions and slot rewrites into the one device store both
        read, so nothing is copied to the device for the view.
        """
        if (self.backend == "numpy") != (other.backend == "numpy"):
            raise ValueError(
                "adopt_layout shares the device store: both verifiers "
                "need a host backend or both a device backend")
        if self.signatures is not other.signatures:
            self._buf = other._buf
            self._n_rows = other._n_rows
            self.signatures = other.signatures
        self._slot_of = other._slot_of
        self._free = other._free
        self._n_docs = other._n_docs
        self._store = other._store

    def rows_for(self, doc_ids) -> np.ndarray:
        """Retained signature rows for ``doc_ids`` (eviction-aware)."""
        ids = np.asarray(doc_ids, dtype=np.int64)
        if ids.size == 0:
            return np.zeros((0,) + self.signatures.shape[1:],
                            dtype=self.signatures.dtype)
        return self.signatures[self._slot_index(ids)]

    def frozen_rows(self) -> tuple[np.ndarray, dict | None]:
        """(signatures, doc->slot) safe against later session mutation.

        Read-path snapshot for ``core.session.SessionView``.  In the
        append-only layout later extensions only ever write past this
        view's row bound or reallocate into a fresh buffer, so the
        current row-slice object is already immutable — shared
        zero-copy.  In the eviction layout (``_slot_of`` set) freed
        slots are rewritten in place by later chunks, so the live rows
        — bounded O(clusters + LRU window) by the retention invariant —
        are copied together with the doc->slot map.
        """
        if self._slot_of is None:
            return self.signatures, None
        return self.signatures.copy(), dict(self._slot_of)

    def extend_signatures(self, rows: np.ndarray, device_rows=None) -> None:
        """Append signature rows for newly ingested docs.

        Incremental ingest (``core.session.DedupSession``) allocates
        global doc ids chunk by chunk; the verifier's row i must stay
        doc i's signature, so each chunk's rows are appended in
        allocation order.  Throughput counters (and, for
        ``DeviceScoredEdgeVerifier``, the registered device scores)
        survive the extension — the session keeps ONE verifier alive
        across every chunk.  ``device_rows``, a device array holding
        the same rows, spares the device backends the copy from the
        host.
        """
        rows = np.asarray(rows)
        if rows.size == 0:
            return
        if self.signatures.size == 0:
            self._set_signatures(rows, device_rows)
            return
        if rows.shape[-1] != self.signatures.shape[-1]:
            raise ValueError(
                f"signature width {rows.shape[-1]} != existing "
                f"{self.signatures.shape[-1]}")
        if self._slot_of is not None:
            # Retention mode: fill freed slots before growing the
            # matrix — new docs take the next sequential global ids.
            n_append = max(0, len(rows) - len(self._free))
            n_new = self._n_rows + n_append
            if n_new > len(self._buf):
                cap = max(n_new, 2 * max(1, len(self._buf)))
                buf = np.empty((cap, self._buf.shape[1]),
                               dtype=self._buf.dtype)
                buf[: self._n_rows] = self._buf[: self._n_rows]
                self._buf = buf
            slots = []
            for row in rows:
                if self._free:
                    slot = self._free.pop()
                else:
                    slot = self._n_rows
                    self._n_rows += 1
                self._buf[slot] = row
                self._slot_of[self._n_docs] = slot
                self._n_docs += 1
                slots.append(slot)
            self.signatures = self._buf[: self._n_rows]
            self._device_write(rows, device_rows, slots=slots)
            return
        n0 = self._n_rows
        n_new = n0 + len(rows)
        if n_new > len(self._buf):
            cap = max(n_new, 2 * max(1, len(self._buf)))
            buf = np.empty((cap, self._buf.shape[1]),
                           dtype=self._buf.dtype)
            buf[:n0] = self._buf[:n0]
            self._buf = buf
        self._buf[n0:n_new] = rows
        self._n_rows = n_new
        self._n_docs = n_new
        self.signatures = self._buf[: self._n_rows]
        self._device_write(rows, device_rows, start=n0)

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        pairs = self._slot_index(np.asarray(pairs))
        a_idx, b_idx = pairs[:, 0], pairs[:, 1]
        if self.backend == "numpy":
            a = self.signatures[a_idx]
            b = self.signatures[b_idx]
            return (a == b).mean(axis=-1, dtype=np.float32)
        return device_estimate(self.backend, self._store.buf, a_idx,
                               b_idx, self._store.width)


@functools.partial(jax.jit, static_argnums=3)
def _gather_counts_jit(sig, a_idx, b_idx, width=None):
    """Fused gather + agreement counts over each row's leading ``width``
    hashes (one dispatch per bucket)."""
    return jnp.sum((sig[a_idx][:, :width] == sig[b_idx][:, :width]
                    ).astype(jnp.float32), axis=-1)


def device_estimate(backend: str, sig_dev, a_idx, b_idx,
                    width: int | None = None) -> np.ndarray:
    """m/M estimates of (a, b) row pairs, agreement counted on device
    over each row's leading ``width`` hashes (M; default every column).

    The host index arrays are padded with row 0 to their power-of-two
    bucket (at least 256), so the verify program compiles once per
    bucket, not once per batch size.  The device returns exact counts
    and numpy divides by M, so the ``jnp`` and ``pallas`` backends are
    bit-identical to the ``numpy`` estimator (a division on the device
    is not correctly rounded).
    """
    p = len(a_idx)
    pad = (0, pow2_bucket(p) - p)
    a_idx = jnp.asarray(np.pad(np.asarray(a_idx), pad))
    b_idx = jnp.asarray(np.pad(np.asarray(b_idx), pad))
    width = sig_dev.shape[1] if width is None else width
    if backend == "jnp":
        counts = _gather_counts_jit(sig_dev, a_idx, b_idx, width)
    else:
        from repro.kernels import ops as kops

        counts = kops.indexed_pair_counts(sig_dev, a_idx, b_idx,
                                          width=width)
    return np.asarray(counts)[:p] / np.float32(width)


class ShardedEdgeVerifier(SignatureVerifier):
    """Stage 2 of the sharded path's two-stage verify (``dist_lsh``).

    Stage 1 is the cheap on-device prescreen inside the all_to_all: each
    band run compares only the exchanged ``verify_k``-prefix of the
    signatures and keeps edges whose prefix estimate clears
    ``edge_threshold - prescreen_margin``.  The surviving edges land in
    per-device buffers; this verifier re-scores them on the host side
    against the **full** (D, M) signature matrix using the exact same
    estimator and backends (numpy / jnp / ``kernels.sigjaccard``) as the
    host path's ``SignatureVerifier`` — so edge thresholds and estimate
    semantics cannot drift between the sharded and host engines.

    Build it from a dedup-step output with ``from_step_output`` (the step
    returns the signatures it computed, keeping device and host views
    bit-identical).
    """

    @classmethod
    def from_step_output(cls, out, backend: str = "numpy",
                         batch_pairs: int = 8192) -> "ShardedEdgeVerifier":
        return cls(np.asarray(out["sig"]), backend=backend,
                   batch_pairs=batch_pairs)

    def drift_count(self, pairs: np.ndarray,
                    reference: BatchVerifier) -> int:
        """#pairs whose estimate differs from ``reference``'s (expect 0)."""
        pairs = np.asarray(pairs).reshape(-1, 2)
        if pairs.size == 0:
            return 0
        return int(np.sum(self(pairs) != reference(pairs)))


class DeviceScoredEdgeVerifier(ShardedEdgeVerifier):
    """Pass-through stage 2 for the device-resident verify mode.

    When ``dist_lsh`` runs its stage-2 verify on the accelerator
    (``stage2="device"``: the ``kernels.sigjaccard`` fused gather +
    full-M-estimate kernel under ``shard_map``), edges whose two
    endpoints live on one device's signature shard arrive at the host
    merge already fully scored.  ``add_scores`` registers those scores;
    ``_verify_batch`` then serves a pair from the registry when present
    and falls back to the parent full-signature re-verify only for the
    *cross-shard stragglers* (edge endpoints on different shards) and
    for root pairs the engine synthesizes after unions.

    The device kernel computes the identical estimator (full-M
    agreement, float32 division), so registry hits and host re-scores
    are bit-interchangeable — drift stays 0 by construction.

    ``n_passthrough`` / ``n_rescored`` count how the split landed.
    """

    def __init__(self, signatures: np.ndarray, backend: str = "numpy",
                 batch_pairs: int = 8192, capacity: int = 0):
        super().__init__(signatures, backend=backend,
                         batch_pairs=batch_pairs, capacity=capacity)
        self._scores: dict[tuple[int, int], float] = {}
        self.n_passthrough = 0
        self.n_rescored = 0

    def add_scores(self, pairs: np.ndarray, sims: np.ndarray):
        """Register device-computed full-signature scores for pairs.

        ``pairs`` (P, 2) int doc ids in any order; keys are canonicalized
        to (min, max) to match the engine's root-pair convention.
        """
        pairs = np.asarray(pairs).reshape(-1, 2).astype(np.int64)
        sims = np.asarray(sims).reshape(-1)
        for (a, b), s in zip(pairs, sims):
            a, b = int(a), int(b)
            self._scores[(min(a, b), max(a, b))] = float(s)

    @property
    def num_scores(self) -> int:
        return len(self._scores)

    def clear_scores(self) -> None:
        """Drop the device-score registry (counters survive).

        A registered edge is dead once its step's buffers have been fed:
        every raw edge either landed in the engine's verified-sim cache
        or its endpoints were already co-clustered (and unions never
        split, so the pair can never reach the verifier again).
        ``dist_lsh.feed_step_groups`` clears after each step so a
        long-lived incremental session doesn't accumulate one registry
        entry per device-scored edge forever.
        """
        self._scores.clear()

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        out = np.empty(len(pairs), dtype=np.float32)
        missing = []
        missing_at = []
        for i, (a, b) in enumerate(pairs):
            s = self._scores.get((int(a), int(b)))
            if s is None:
                missing.append((int(a), int(b)))
                missing_at.append(i)
            else:
                out[i] = s
        self.n_passthrough += len(pairs) - len(missing)
        if missing:
            self.n_rescored += len(missing)
            out[missing_at] = super()._verify_batch(
                np.array(missing, dtype=np.int64))
        return out


class ExactJaccardVerifier(BatchVerifier):
    """Vectorized exact Jaccard over pre-sorted n-gram id arrays.

    Each document's n-gram set is interned to integer ids once
    (``from_token_lists``); a batch of P pairs is then verified by
    concatenating the two padded id rows, sorting each row, and counting
    adjacent equal values — |A ∩ B| by merge, no Python set ops.  Padding
    slots carry globally unique sentinels so they can never collide.
    Matches ``jaccard.exact_jaccard`` on n-gram sets exactly (interning
    is collision-free by construction).
    """

    def __init__(self, id_rows: list[np.ndarray], batch_pairs: int = 2048,
                 *, _vocab: dict | None = None, _ngram: int | None = None):
        super().__init__()
        self.batch_pairs = int(batch_pairs)
        self._rows: list[np.ndarray] = [
            np.asarray(r, dtype=np.int64) for r in id_rows]
        self._vocab = _vocab        # n-gram -> id (None: raw-id rows only)
        self._ngram = _ngram
        # Retention: None = implicit "row i == doc i"; first
        # release_rows switches to a doc -> slot map + free pool (same
        # protocol as SignatureVerifier).
        self._slot_of: dict[int, int] | None = None
        self._free: list[int] = []
        self._n_docs = len(self._rows)
        self._rebuild()

    def _pad_rows(self, rows: list[np.ndarray], row0: int,
                  lmax: int) -> np.ndarray:
        """Pad id rows to (len(rows), lmax).

        Pad slot (row0 + i, j) carries the globally unique NEGATIVE
        sentinel ``-(1 + (row0 + i) * lmax + j)``: real interned ids
        are >= 0, so pads can never match a real id nor another pad —
        and, unlike a max-id-derived sentinel base, they stay valid
        when later chunks grow the vocab, which is what makes
        ``extend_id_rows`` append-only.
        """
        d = len(rows)
        out = -(1 + np.int64(row0) * lmax
                + np.arange(d * lmax, dtype=np.int64).reshape(d, lmax))
        for i, row in enumerate(rows):
            out[i, : len(row)] = row
        return out

    def _rebuild(self):
        self._n_rows = len(self._rows)
        self._len_buf = np.array([len(r) for r in self._rows],
                                 dtype=np.int64)
        self._lmax = int(max(1, self._len_buf.max(initial=1)))
        self._ids_buf = self._pad_rows(self._rows, 0, self._lmax)
        self.lengths = self._len_buf
        self.ids = self._ids_buf

    def extend_id_rows(self, id_rows: list[np.ndarray]) -> None:
        """Append pre-interned sorted id rows for newly ingested docs.

        Ids must come from the same interning namespace as the existing
        rows (intersection counts — and therefore exact Jaccard values —
        depend only on id equality, so chunked interning with a shared
        vocab is bit-identical to one-shot interning).  Appending is
        amortized O(chunk) — capacity-doubling row buffers, like
        ``SignatureVerifier.extend_signatures`` — while the new rows
        fit the current row width; only a chunk containing a longer
        document than any before re-pads the whole matrix.  In
        retention mode (after a ``release_rows`` call) freed slots are
        reused before the buffers grow.
        """
        if not id_rows:
            return
        new = [np.asarray(r, dtype=np.int64) for r in id_rows]
        if self._slot_of is not None:
            self._extend_into_slots(new)
            return
        n0 = self._n_rows
        n1 = n0 + len(new)
        self._rows.extend(new)
        self._n_docs = n1
        if max((len(r) for r in new), default=1) > self._lmax:
            self._rebuild()
            return
        if n1 > len(self._ids_buf):
            cap = max(n1, 2 * max(1, len(self._ids_buf)))
            ids_buf = np.empty((cap, self._lmax), dtype=np.int64)
            ids_buf[:n0] = self._ids_buf[:n0]
            len_buf = np.empty((cap,), dtype=np.int64)
            len_buf[:n0] = self._len_buf[:n0]
            self._ids_buf, self._len_buf = ids_buf, len_buf
        self._ids_buf[n0:n1] = self._pad_rows(new, n0, self._lmax)
        self._len_buf[n0:n1] = [len(r) for r in new]
        self._n_rows = n1
        self.ids = self._ids_buf[:n1]
        self.lengths = self._len_buf[:n1]

    def _extend_into_slots(self, new: list[np.ndarray]) -> None:
        """Retention-mode extension: fill freed slots, then append."""
        slots = []
        for row in new:
            if self._free:
                slot = self._free.pop()
                self._rows[slot] = row
            else:
                slot = len(self._rows)
                self._rows.append(row)
            slots.append(slot)
            self._slot_of[self._n_docs] = slot
            self._n_docs += 1
        if max((len(r) for r in new), default=1) > self._lmax:
            self._rebuild()            # one full re-pad at the new width
            return
        n1 = len(self._rows)
        if n1 > len(self._ids_buf):
            n0 = self._n_rows
            cap = max(n1, 2 * max(1, len(self._ids_buf)))
            ids_buf = np.empty((cap, self._lmax), dtype=np.int64)
            ids_buf[:n0] = self._ids_buf[:n0]
            len_buf = np.empty((cap,), dtype=np.int64)
            len_buf[:n0] = self._len_buf[:n0]
            self._ids_buf, self._len_buf = ids_buf, len_buf
        for slot, row in zip(slots, new):
            self._ids_buf[slot] = self._pad_rows([row], slot,
                                                 self._lmax)[0]
            self._len_buf[slot] = len(row)
        self._n_rows = n1
        self.ids = self._ids_buf[:n1]
        self.lengths = self._len_buf[:n1]

    # -- retention (free-slot pool) ----------------------------------------

    @property
    def n_live_rows(self) -> int:
        """Rows currently holding a retained document's n-gram ids."""
        if self._slot_of is None:
            return self._n_rows
        return len(self._slot_of)

    def _slot_index(self, ids: np.ndarray) -> np.ndarray:
        if self._slot_of is None:
            return ids
        so = self._slot_of
        try:
            return np.fromiter((so[int(i)] for i in ids.ravel()),
                               dtype=np.int64,
                               count=ids.size).reshape(ids.shape)
        except KeyError as e:
            raise KeyError(
                f"doc {e.args[0]} has no retained token row (evicted by "
                "the retention policy); only union-find roots and the "
                "LRU window are verifiable") from None

    def release_rows(self, doc_ids) -> int:
        """Evict docs' interned-id rows into the free-slot pool.

        Frees the per-doc id array immediately (the dominant token-store
        memory); the fixed-width padded row is reused by the next
        extension.
        """
        if self._slot_of is None:
            self._slot_of = {i: i for i in range(self._n_rows)}
        released = 0
        for d in doc_ids:
            d = int(d)
            try:
                slot = self._slot_of.pop(d)
            except KeyError:
                raise KeyError(f"doc {d} has no retained row to release")
            self._rows[slot] = np.zeros((0,), dtype=np.int64)
            self._len_buf[slot] = 0
            self._free.append(slot)
            released += 1
        return released

    def frozen_rows(self) -> tuple[np.ndarray, np.ndarray, dict | None]:
        """(ids, lengths, doc->slot) safe against later session mutation
        (same snapshot protocol as ``SignatureVerifier.frozen_rows``:
        zero-copy while append-only, copied under the eviction layout
        where slot reuse rewrites rows in place)."""
        if self._slot_of is None:
            return self.ids, self.lengths, None
        return self.ids.copy(), self.lengths.copy(), dict(self._slot_of)

    def extend_token_lists(self, token_lists: list[list[str]]) -> None:
        """Intern + append new documents using the persistent vocab.

        Only verifiers built with ``from_token_lists`` /
        ``from_ngram_sets`` carry the vocab needed to intern new docs.
        """
        if self._vocab is None or self._ngram is None:
            raise ValueError(
                "verifier was built from raw id rows (no vocab); use "
                "extend_id_rows with consistently interned rows")
        self.extend_id_rows(
            _intern_rows(self._vocab,
                         (_ngram_set_of(toks, self._ngram)
                          for toks in token_lists)))

    @classmethod
    def from_token_lists(cls, token_lists: list[list[str]], n: int = 8,
                         batch_pairs: int = 2048) -> "ExactJaccardVerifier":
        """Intern every document's n-gram set to sorted int64 id rows."""
        vocab: dict[tuple, int] = {}
        rows = _intern_rows(
            vocab, (_ngram_set_of(toks, n) for toks in token_lists))
        return cls(rows, batch_pairs=batch_pairs, _vocab=vocab, _ngram=n)

    @classmethod
    def from_ngram_sets(cls, ngram_sets: list[set], batch_pairs: int = 2048,
                        n: int | None = None) -> "ExactJaccardVerifier":
        """Intern pre-built n-gram sets.  Pass ``n`` (the width the sets
        were built with) to enable ``extend_token_lists``; without it
        the verifier cannot know the width and extension by token lists
        is refused rather than silently mixing n-gram widths."""
        vocab: dict = {}
        rows = _intern_rows(vocab, ngram_sets)
        return cls(rows, batch_pairs=batch_pairs, _vocab=vocab, _ngram=n)

    def _verify_batch(self, pairs: np.ndarray) -> np.ndarray:
        pairs = self._slot_index(np.asarray(pairs))
        a_idx, b_idx = pairs[:, 0], pairs[:, 1]
        merged = np.concatenate(
            [self.ids[a_idx], self.ids[b_idx]], axis=1
        )
        merged.sort(axis=1)
        inter = np.sum(merged[:, 1:] == merged[:, :-1], axis=1)
        la = self.lengths[a_idx]
        lb = self.lengths[b_idx]
        union = la + lb - inter
        # Two empty sets have Jaccard 1.0 (matches jaccard.exact_jaccard).
        return np.where(
            union > 0, inter / np.maximum(union, 1), 1.0
        ).astype(np.float32)


def _ngram_set_of(toks: list[str], n: int):
    from repro.core.shingle import ngram_set

    return ngram_set(toks, n)


def _intern_rows(vocab: dict, ngram_sets) -> list[np.ndarray]:
    """Intern n-gram sets to sorted int64 id rows via a shared vocab."""
    rows = []
    for s in ngram_sets:
        ids = {vocab.setdefault(g, len(vocab)) for g in s}
        rows.append(np.sort(np.fromiter(ids, dtype=np.int64,
                                        count=len(ids))))
    return rows


def as_verifier(obj) -> BatchVerifier:
    """Coerce a BatchVerifier or scalar ``fn(a, b)`` into a verifier."""
    if isinstance(obj, BatchVerifier):
        return obj
    if callable(obj):
        return CallbackVerifier(obj)
    raise TypeError(f"not a verifier or similarity fn: {obj!r}")
