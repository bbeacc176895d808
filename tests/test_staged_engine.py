"""Staged dedup engine: candidate sources agree, batched verifiers match
the per-pair numpy oracle, and the engine reproduces the scalar loop."""
import dataclasses
import functools

import numpy as np
import pytest

from repro.core import DedupSession, jaccard, lsh, shingle
from repro.core.bandstore import Design1Store, Design2Store
from repro.core.candidates import (
    BandMatrixSource, EdgeStreamSource, ShardedEdgeSource, StoreBandSource,
    candidate_pairs,
)
from repro.core.cluster import cluster_bands
from repro.core.engine import (
    ClusterAccumulator, cluster_source, merge_cluster_rounds,
)
from repro.core.pipeline import DedupConfig, DedupPipeline, DedupResult
from repro.core.streaming import StreamingDedup
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import (
    BatchVerifier, CallbackVerifier, DeviceScoredEdgeVerifier, ExactJaccardVerifier,
    ShardedEdgeVerifier, SignatureVerifier,
)
from repro.data import inject_near_duplicates, make_i2b2_like


def _corpus(n=60, dups=40, seed=0):
    notes = make_i2b2_like(n, seed=seed)
    notes, _ = inject_near_duplicates(notes, dups, seed=seed + 1)
    return notes


def _random_pairs(rng, d, p):
    a = rng.randint(0, d, size=p)
    b = (a + 1 + rng.randint(0, d - 1, size=p)) % d
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    return np.stack([lo, hi], axis=-1).astype(np.int64)


# -- verify layer ----------------------------------------------------------

def test_signature_verifier_backends_match_per_pair_oracle():
    rng = np.random.RandomState(0)
    sig = rng.randint(0, 50, size=(40, 100)).astype(np.uint32)
    pairs = _random_pairs(rng, 40, 500)
    oracle = np.array(
        [(sig[a] == sig[b]).mean() for a, b in pairs], dtype=np.float32)
    for backend in ("numpy", "jnp", "pallas"):
        v = SignatureVerifier(sig, backend=backend, batch_pairs=128)
        np.testing.assert_allclose(v(pairs), oracle, atol=1e-6,
                                   err_msg=backend)
        assert v.n_pairs == len(pairs)
        assert v.n_batches == -(-len(pairs) // 128)


def test_exact_verifier_matches_per_pair_oracle():
    notes = _corpus()
    toks = [shingle.tokenize(t) for t in notes]
    sets = [shingle.ngram_set(t, 8) for t in toks]
    rng = np.random.RandomState(1)
    pairs = _random_pairs(rng, len(notes), 400)
    oracle = np.array(
        [jaccard.exact_jaccard(sets[a], sets[b]) for a, b in pairs],
        dtype=np.float32)
    v = ExactJaccardVerifier.from_token_lists(toks, 8, batch_pairs=64)
    np.testing.assert_allclose(v(pairs), oracle, atol=1e-6)


def test_exact_verifier_empty_and_short_docs():
    v = ExactJaccardVerifier.from_token_lists(
        [[], [], ["a", "b"], ["a", "b"], ["c"]], n=8)
    sims = v(np.array([[0, 1], [0, 2], [2, 3], [2, 4]]))
    # empty vs empty = 1.0 (matches jaccard.exact_jaccard), empty vs
    # non-empty = 0, identical short docs = 1, disjoint = 0.
    np.testing.assert_allclose(sims, [1.0, 0.0, 1.0, 0.0], atol=1e-6)


# -- candidate layer -------------------------------------------------------

def test_three_candidate_sources_identical_pairs():
    notes = _corpus()
    pipe = DedupPipeline(DedupConfig())
    bands = pipe.compute_bands(
        pipe.compute_signatures(pipe.tokenize(notes)))
    d, b, _ = bands.shape

    mem_pairs = candidate_pairs(BandMatrixSource(bands))
    assert len(mem_pairs), "corpus with injected dups must have candidates"

    s1, s2 = Design1Store(), Design2Store(part_size=16)
    for i in range(d):
        s1.insert_document(i, bands[i])
        s2.insert_document(i, bands[i])
    s1.commit()
    s2.commit()
    p1 = candidate_pairs(StoreBandSource(s1, b, d))
    p2 = candidate_pairs(StoreBandSource(s2, b, d))

    sd = StreamingDedup(DedupConfig(), chunk_docs=16)
    sd.ingest(notes)
    p3 = candidate_pairs(sd.candidate_source())

    np.testing.assert_array_equal(mem_pairs, p1)
    np.testing.assert_array_equal(mem_pairs, p2)
    np.testing.assert_array_equal(mem_pairs, p3)
    # legacy entry points delegate to the same layer
    np.testing.assert_array_equal(mem_pairs, lsh.all_candidate_pairs(bands))


# -- engine ----------------------------------------------------------------

def test_engine_batched_matches_scalar_callback():
    notes = _corpus()
    pipe = DedupPipeline(DedupConfig())
    toks = pipe.tokenize(notes)
    bands = pipe.compute_bands(pipe.compute_signatures(toks))
    sets = [shingle.ngram_set(t, 8) for t in toks]

    uf_cb, st_cb, pairs_cb = cluster_bands(
        bands, lambda a, b: jaccard.exact_jaccard(sets[a], sets[b]),
        0.75, 0.40, True)
    uf_bv, st_bv, pairs_bv = cluster_bands(
        bands, ExactJaccardVerifier.from_token_lists(toks, 8),
        0.75, 0.40, True)

    np.testing.assert_array_equal(uf_cb.components(), uf_bv.components())
    assert st_cb.pairs_evaluated == st_bv.pairs_evaluated
    assert st_cb.pairs_excluded == st_bv.pairs_excluded
    assert st_cb.unions_done == st_bv.unions_done
    assert [(a, b) for a, b, _ in pairs_cb] == \
        [(a, b) for a, b, _ in pairs_bv]
    np.testing.assert_allclose(
        [s for _, _, s in pairs_cb], [s for _, _, s in pairs_bv],
        atol=1e-6)


def test_engine_band_batch_mode_still_clusters():
    notes = make_i2b2_like(40, seed=9)
    notes = notes + [notes[0]] * 3
    pipe = DedupPipeline(DedupConfig())
    toks = pipe.tokenize(notes)
    sig = pipe.compute_signatures(toks)
    bands = pipe.compute_bands(sig)
    uf, st, _ = cluster_source(
        BandMatrixSource(bands), SignatureVerifier(sig),
        0.75, 0.40, batch="band", max_batch_pairs=64)
    labels = uf.components()
    assert labels[40] == labels[0] == labels[41] == labels[42]
    # band mode may evaluate pairs the strict mode excludes, never fewer
    _, st_run, _ = cluster_source(
        BandMatrixSource(bands), SignatureVerifier(sig), 0.75, 0.40)
    assert st.pairs_evaluated >= st_run.pairs_evaluated


def test_streaming_cluster_uses_batched_verifier():
    notes = _corpus(40, 20, seed=3)
    sd = StreamingDedup(DedupConfig(), chunk_docs=8)
    sd.ingest(notes)
    uf_b, stats = sd.cluster()
    assert stats["verify_batches"] >= 1
    # scalar-callback compat path gives the identical clustering.
    # Rows come from wherever the configured tier keeps them: the host
    # phase-1 cache (memory) or the store's sigs table (sqlite).
    if hasattr(sd.store, "get_signature"):
        row = sd.store.get_signature
    else:
        row = sd._sig_cache.__getitem__
    uf_s, _ = sd.cluster(
        similarity_fn=lambda a, b: float(
            (row(a) == row(b)).mean()))
    np.testing.assert_array_equal(uf_b.components(), uf_s.components())


def test_merge_cluster_rounds_batched_matches_scalar():
    rng = np.random.RandomState(5)
    sims = {(a, b): float(rng.uniform(0.5, 1.0))
            for a in range(8) for b in range(8) if a < b}

    def build():
        uf = ThresholdUnionFind(8, 0.3)
        for a, b in ((0, 1), (2, 3), (4, 5), (6, 7)):
            uf.union(a, b, 0.95)
        return uf

    def fn(a, b):
        return sims[(min(a, b), max(a, b))]

    uf_scalar = build()
    m1 = merge_cluster_rounds(uf_scalar, fn, 0.75)
    uf_batched = build()
    m2 = merge_cluster_rounds(uf_batched, CallbackVerifier(fn), 0.75)
    assert m1 == m2
    np.testing.assert_array_equal(
        uf_scalar.components(), uf_batched.components())


# -- sharded path layers (host-side units; device path in
# tests/test_distributed.py) -----------------------------------------------

def test_sharded_edge_source_pairs_mask_and_pad_filtering():
    # Two device buffers of capacity 3 (num_shards=2): invalid slots,
    # masked-out slots, and edges touching pad docs (id >= num_docs)
    # must all be dropped.
    inv = np.uint32(0xFFFFFFFF)
    edges = np.array([
        [0, 1], [2, 3], [inv, inv],     # device 0: two valid, one unused
        [4, 9], [4, 5], [inv, inv],     # device 1: [4, 9] touches a pad
    ], dtype=np.uint32)
    mask = np.array([1, 1, 0, 1, 1, 0], dtype=bool)
    src = ShardedEdgeSource(edges, mask, num_docs=8, num_shards=2)
    assert src.num_docs == 8
    assert src.num_bands == 2
    assert src.num_edges == 3
    np.testing.assert_array_equal(
        candidate_pairs(src), [[0, 1], [2, 3], [4, 5]])
    # every run is a two-member group
    groups = [g.tolist() for br in src.iter_bands()
              for g in br.iter_groups()]
    assert groups == [[0, 1], [2, 3], [4, 5]]


def test_sharded_edge_verifier_matches_host_estimator():
    rng = np.random.RandomState(7)
    sig = rng.randint(0, 50, size=(40, 100)).astype(np.uint32)
    pairs = _random_pairs(rng, 40, 300)
    host = SignatureVerifier(sig, backend="numpy")
    oracle = host(pairs)
    for backend in ("numpy", "jnp", "pallas"):
        v = ShardedEdgeVerifier(sig, backend=backend, batch_pairs=128)
        np.testing.assert_allclose(v(pairs), oracle, atol=1e-6,
                                   err_msg=backend)
        # bit-identical to the host verifier on the SAME backend (pallas
        # multiplies by 1/M instead of dividing, so cross-backend
        # estimates agree only to float tolerance)
        assert v.drift_count(
            pairs, SignatureVerifier(sig, backend=backend)) == 0
    # from_step_output builds from the step's returned signatures
    v = ShardedEdgeVerifier.from_step_output({"sig": sig})
    np.testing.assert_allclose(v(pairs), oracle, atol=1e-6)


def test_sharded_edges_through_engine_match_band_source():
    # Star edges of every band run, fed through ShardedEdgeSource, must
    # cluster identically to the host BandMatrixSource on the engine.
    notes = _corpus()
    pipe = DedupPipeline(DedupConfig())
    sig = pipe.compute_signatures(pipe.tokenize(notes))
    bands = pipe.compute_bands(sig)
    uf_h, _, pairs_h = cluster_source(
        BandMatrixSource(bands), SignatureVerifier(sig), 0.75, 0.40)
    edges = []
    for br in BandMatrixSource(bands).iter_bands():
        for g in br.iter_groups():
            edges += [(g[0], m) for m in g[1:]]   # member -> run head
    src = ShardedEdgeSource(np.array(edges, dtype=np.int64),
                            num_docs=len(notes))
    uf_s, _, pairs_s = cluster_source(
        src, ShardedEdgeVerifier(sig), 0.75, 0.40)
    np.testing.assert_array_equal(uf_h.components(), uf_s.components())
    sims_h = dict(((a, b), s) for a, b, s in pairs_h)
    shared = [(a, b, s) for a, b, s in pairs_s if (a, b) in sims_h]
    assert shared
    assert all(s == sims_h[(a, b)] for a, b, s in shared)


def test_cluster_source_accumulates_into_existing_uf():
    # Overflow-retry shape: a partial edge source first, then the full
    # band source into the SAME union-find recovers the full clustering.
    notes = _corpus()
    pipe = DedupPipeline(DedupConfig())
    sig = pipe.compute_signatures(pipe.tokenize(notes))
    bands = pipe.compute_bands(sig)
    uf_full, _, _ = cluster_source(
        BandMatrixSource(bands), SignatureVerifier(sig), 0.75, 0.40)

    edges = []
    for br in BandMatrixSource(bands).iter_bands():
        for g in br.iter_groups():
            edges += [(g[0], m) for m in g[1:]]
    partial = ShardedEdgeSource(
        np.array(edges[: len(edges) // 3], dtype=np.int64),
        num_docs=len(notes))
    verifier = SignatureVerifier(sig)
    uf, st1, _ = cluster_source(partial, verifier, 0.75, 0.40)
    uf2, st2, _ = cluster_source(
        BandMatrixSource(bands), verifier, 0.75, 0.40, uf=uf)
    assert uf2 is uf
    np.testing.assert_array_equal(uf.components(), uf_full.components())
    # the retry pass re-verifies at most what a fresh run would
    _, st_fresh, _ = cluster_source(
        BandMatrixSource(bands), SignatureVerifier(sig), 0.75, 0.40)
    assert st2.pairs_evaluated <= st_fresh.pairs_evaluated


# -- doc-id integrity regressions ------------------------------------------

def test_design2_store_noncontiguous_doc_ids_round_trip():
    """Regression: Design 2 must persist explicit per-part doc ids.

    The historical blob stored only the values and *reconstructed* ids
    as arange(doc0, doc0 + d) — silently wrong whenever a part holds a
    non-contiguous id range (ragged chunks, resumed ingest with
    doc_offsets-style global ids, ids >= 2^31).
    """
    rng = np.random.RandomState(0)
    ids = [3, 100, 2**31 + 7, 11, 2**31 + 5]
    bands = {i: rng.randint(0, 2**31, size=(4, 2)).astype(np.uint32)
             for i in ids}
    s1, s2 = Design1Store(), Design2Store(part_size=3)
    for i in ids:
        s1.insert_document(i, bands[i])
        s2.insert_document(i, bands[i])
    s1.commit()
    s2.commit()
    for j in range(4):
        d2, v2 = s2.read_band(j)
        assert sorted(d2.tolist()) == sorted(ids)
        assert d2.dtype == np.int64
        for doc, val in zip(d2, v2):
            np.testing.assert_array_equal(val, bands[int(doc)][j])
        # both designs agree row-for-row
        d1, v1 = s1.read_band(j)
        o1, o2 = np.argsort(d1), np.argsort(d2)
        np.testing.assert_array_equal(d1[o1], d2[o2])
        np.testing.assert_array_equal(v1[o1], v2[o2])


def test_design2_store_reads_legacy_v1_blobs():
    """Pre-existing stores (raw value blobs) stay readable via doc0."""
    rng = np.random.RandomState(1)
    vals = rng.randint(0, 2**31, size=(5, 2)).astype(np.uint32)
    s2 = Design2Store()
    s2.conn.execute("INSERT INTO band2 VALUES (?,?,?,?)",
                    (0, 0, 10, vals.tobytes()))
    docs, got = s2.read_band(0)
    np.testing.assert_array_equal(docs, np.arange(10, 15))
    np.testing.assert_array_equal(got, vals)


def test_streaming_resumed_ingest_noncontiguous_ids():
    """Resumed ingest writes non-contiguous ids inside one band part.

    chunk A (ids 0..4) and chunk B (ids 42..46) share a part of size 8,
    so the part's id range is non-contiguous; the round-trip must keep
    the explicit ids and cluster a cross-chunk duplicate pair.
    """
    notes_a = make_i2b2_like(5, seed=11)
    notes_a[3] = notes_a[1]                 # in-chunk duplicate
    notes_b = make_i2b2_like(5, seed=12)
    notes_b[0] = notes_a[1]                 # cross-chunk duplicate (id 42)
    cfg = DedupConfig()
    sd = StreamingDedup(cfg, chunk_docs=8)
    sd.ingest(notes_a)
    sd.n_docs = 42                          # resume after a corpus gap
    sd.ingest(notes_b)
    assert sd.n_docs == 47
    docs0, _ = sd.store.read_band(0)
    assert sorted(docs0.tolist()) == [0, 1, 2, 3, 4, 42, 43, 44, 45, 46]

    # default verifier: signature matrix indexed by global id (gap rows
    # zero; gap ids have no store rows so they never become candidates)
    uf, _ = sd.cluster()
    labels = uf.components()
    assert labels[1] == labels[3] == labels[42], labels

    # doc_id_base makes resumed ingest first-class (fresh store).
    sd2 = StreamingDedup(cfg, chunk_docs=8, doc_id_base=1000)
    sd2.ingest(notes_a)
    docs0, _ = sd2.store.read_band(0)
    assert sorted(docs0.tolist()) == [1000, 1001, 1002, 1003, 1004]
    uf2, _ = sd2.cluster()                # default verifier works too
    labels2 = uf2.components()
    assert labels2[1001] == labels2[1003]


def test_pair_enumeration_int64_global_ids():
    """Regression: doc ids >= 2^31 must survive pair enumeration.

    The historical int32 downcast wrapped exactly the global ids that
    chunked corpora with doc_offsets produce.
    """
    big = 2**31
    vals = np.array([[1, 1], [1, 1], [2, 2], [2, 2]], dtype=np.uint32)
    docs = np.array([big + 9, big + 5, 7, big + 3], dtype=np.int64)
    from repro.core.candidates import make_band_runs, pairs_in_runs

    runs = make_band_runs(0, vals, docs)
    pairs = pairs_in_runs(runs.sorted_vals, runs.sorted_docs)
    assert pairs.dtype == np.int64
    assert sorted(map(tuple, pairs.tolist())) == \
        [(7, big + 3), (big + 5, big + 9)]
    # the source-agnostic dedup path and legacy entry point agree
    lp = lsh.enumerate_pairs_in_runs(runs.sorted_vals, runs.sorted_docs)
    assert lp.dtype == np.int64
    np.testing.assert_array_equal(np.sort(lp, axis=0),
                                  np.sort(pairs, axis=0))

    class _OneBand:
        num_docs = 0
        num_bands = 1

        def iter_bands(self):
            yield runs

    cp = candidate_pairs(_OneBand())
    assert cp.dtype == np.int64
    assert sorted(map(tuple, cp.tolist())) == \
        [(7, big + 3), (big + 5, big + 9)]


def test_merge_cluster_rounds_dispatch_count_pin():
    """The verified-sim cache is shared across blocks: a root pair that
    re-appears after a mid-sweep union is served from cache, never
    re-dispatched (historically each block re-verified it singleton)."""
    sims = {(0, 2): 0.9}

    def fn(a, b):
        return sims.get((min(a, b), max(a, b)), 0.6)

    def build():
        uf = ThresholdUnionFind(8, 0.3)
        for a, b in ((0, 1), (2, 3), (4, 5), (6, 7)):
            uf.union(a, b, 0.95)
        return uf

    uf = build()
    v = CallbackVerifier(fn)
    merges = merge_cluster_rounds(uf, v, 0.75, max_batch_pairs=2)
    assert merges == 1
    # 4 roots -> 6 root pairs in the sweep, but only 4 distinct pairs of
    # *current* roots exist once (0, 2) merges; every one is verified
    # exactly once.
    assert v.n_pairs == 4
    uf_big = build()
    merge_cluster_rounds(uf_big, fn, 0.75)  # single block reference
    np.testing.assert_array_equal(uf.components(), uf_big.components())


# -- band-group streaming layers (host-side; device path in
# tests/test_distributed.py) -----------------------------------------------

def test_edge_stream_source_lazy_groups_match_sharded_source():
    inv = np.uint32(0xFFFFFFFF)
    g1 = np.array([[0, 1], [2, 3], [inv, inv]], dtype=np.uint32)
    m1 = np.array([1, 1, 0], dtype=bool)
    g2 = np.array([[4, 9], [4, 5], [0, 2]], dtype=np.uint32)
    consumed = []

    def groups():
        consumed.append("g1")
        yield g1, m1
        consumed.append("g2")
        yield g2, None

    seen_cb = []
    src = EdgeStreamSource(groups(), num_docs=8, num_shards=1,
                           on_group=lambda g, e, m: seen_cb.append(g))
    it = src.iter_bands()
    first = next(it)
    assert consumed == ["g1"]       # group 2 not materialized yet
    assert [g.tolist() for g in first.iter_groups()] == [[0, 1], [2, 3]]
    rest = list(it)
    assert consumed == ["g1", "g2"] and seen_cb == [0, 1]
    groups_all = [g.tolist() for br in [first] + rest
                  for g in br.iter_groups()]
    assert groups_all == [[0, 1], [2, 3], [4, 5], [0, 2]]  # pad edge dropped
    assert src.num_edges == 4 and src.groups_consumed == 2

    # engine result == one ShardedEdgeSource over the concatenation
    sig = np.random.RandomState(3).randint(
        0, 4, size=(8, 100)).astype(np.uint32)
    uf_a, _, pairs_a = cluster_source(
        EdgeStreamSource([(g1, m1), (g2, None)], num_docs=8),
        SignatureVerifier(sig), 0.75, 0.40)
    uf_b, _, pairs_b = cluster_source(
        ShardedEdgeSource(np.concatenate([g1, g2]),
                          np.concatenate([m1, np.ones(3, bool)]),
                          num_docs=8),
        SignatureVerifier(sig), 0.75, 0.40)
    np.testing.assert_array_equal(uf_a.components(), uf_b.components())
    assert pairs_a == pairs_b


def test_cluster_accumulator_excludes_cross_feed_pairs():
    """A pair verified while feeding group g is excluded in group g+1."""
    sig = np.random.RandomState(4).randint(
        0, 50, size=(10, 100)).astype(np.uint32)   # all sims ~ tiny
    edges = np.array([[0, 1], [2, 3], [4, 5]], dtype=np.int64)
    verifier = SignatureVerifier(sig)
    acc = ClusterAccumulator(10, verifier, 0.75, 0.40)
    st1 = acc.feed(ShardedEdgeSource(edges, num_docs=10))
    assert st1.pairs_evaluated == 3
    st2 = acc.feed(ShardedEdgeSource(edges, num_docs=10))
    assert st2.pairs_evaluated == 0          # served from the shared cache
    assert st2.pairs_excluded == 3
    assert acc.stats.pairs_evaluated == 3
    assert len(acc.pairs) == 3


def test_device_scored_verifier_passthrough_and_stragglers():
    rng = np.random.RandomState(7)
    sig = rng.randint(0, 50, size=(40, 100)).astype(np.uint32)
    pairs = _random_pairs(rng, 40, 200)
    host = SignatureVerifier(sig, backend="numpy")
    oracle = host(pairs)
    v = DeviceScoredEdgeVerifier(sig, backend="numpy")
    # register device scores for the first half, swapped order included
    half = pairs[:100][:, ::-1]
    v.add_scores(half, oracle[:100])
    keys = {(min(a, b), max(a, b)) for a, b in half.tolist()}
    assert v.num_scores == len(keys)
    np.testing.assert_array_equal(v(pairs), oracle)
    served = sum(1 for a, b in pairs.tolist() if (a, b) in keys)
    assert v.n_passthrough == served > 0
    assert v.n_rescored == len(pairs) - served > 0


def test_masked_indexed_pair_estimate_matches_host():
    """Deterministic kernel check (the hypothesis sweep is CI-only):
    full-M agreement where valid — bit-identical to numpy — else 0."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    rng = np.random.RandomState(9)
    sig = rng.randint(0, 4, size=(30, 100)).astype(np.uint32)
    a = rng.randint(-30, 60, size=(500,)).astype(np.int32)
    b = rng.randint(-30, 60, size=(500,)).astype(np.int32)
    valid = (a >= 0) & (a < 30) & (b >= 0) & (b < 30)
    got = np.asarray(kops.masked_indexed_pair_estimate(
        jnp.asarray(sig), jnp.asarray(a), jnp.asarray(b),
        jnp.asarray(valid)))
    want = np.where(
        valid,
        (sig[np.clip(a, 0, 29)] == sig[np.clip(b, 0, 29)]).mean(
            axis=-1, dtype=np.float32),
        np.float32(0.0)).astype(np.float32)
    assert np.array_equal(got, want)


def test_streamed_step_single_device_matches_end_of_step():
    """Band-group streaming (G=2, 5) and the device-resident stage 2
    reproduce the end-of-step path exactly on a 1-device mesh (where
    every edge is same-shard, so stage 2 passes fully through)."""
    import jax.numpy as jnp

    from repro.core import minhash
    from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                     docs_mesh, make_dedup_step,
                                     make_streamed_dedup_step)

    rng = np.random.RandomState(0)
    vocab = [f"t{i}" for i in range(300)]
    docs = [list(rng.choice(vocab, size=48)) for _ in range(24)]
    docs[5] = docs[3]
    docs[17] = docs[3][:44] + docs[17][:4]
    packed = shingle.pack_documents(docs)
    seeds = jnp.asarray(minhash.default_seeds(20))

    def run(cfg, step_factory, **kw):
        step = step_factory(cfg, docs_mesh(), **kw)
        out = step(jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                   seeds)
        return cluster_step_output(out, cfg, tree_threshold=0.40,
                                   num_docs=24, overflow_fallback=False)

    base = dict(ngram=4, num_hashes=20, verify_k=8, edge_capacity=256,
                edge_threshold=0.5, bucket_slack=16.0)
    ref = run(DistLSHConfig(**base), make_dedup_step)
    assert ref.num_edges > 0 and ref.overflow == 0
    sims = {(a, b): s for a, b, s in ref.pairs}
    for G in (2, 5):
        for stage2 in ("host", "device"):
            res = run(DistLSHConfig(**base, band_groups=G),
                      make_streamed_dedup_step, stage2=stage2)
            assert len(res.group_stats) == G
            np.testing.assert_array_equal(res.labels(), ref.labels())
            shared = [(a, b, s) for a, b, s in res.pairs
                      if (a, b) in sims]
            assert shared
            assert all(s == sims[(a, b)] for a, b, s in shared), stage2
            if stage2 == "device":
                # 1-device mesh: all first-evaluation pairs pass through
                assert res.device_scored > 0


# -- DedupResult.num_clusters (clusters of size >= 2) ----------------------

def test_num_clusters_counts_only_multidoc_clusters():
    labels = np.array([0, 0, 1, 2, 2, 2, 3])  # sizes 2, 1, 3, 1
    res = DedupResult(
        labels=labels,
        keep_mask=np.array([1, 0, 1, 1, 0, 0, 1], dtype=bool),
        pairs=[], stats=None, uf=None,
        signatures=np.zeros((7, 1), np.uint32),
        bands=np.zeros((7, 1, 2), np.uint32))
    assert res.num_clusters == 2
    assert res.num_duplicates_removed == 3


# -- the array merge at band granularity against the scalar loop -----------

class _TableVerifier(BatchVerifier):
    """``sims[a, b]`` from a fixed table, whatever the batches."""

    def __init__(self, sims):
        super().__init__()
        self.sims = sims

    def _verify_batch(self, pairs):
        return self.sims[pairs[:, 0], pairs[:, 1]]


def _sim_table(rng, n, lo=0.3):
    s = rng.uniform(lo, 1.0, size=(n, n)).astype(np.float32)
    return np.triu(s) + np.triu(s, 1).T


def _runs_source(rng, n, num_bands=6):
    """Each band splits the docs into runs of 1-9 members."""
    bands = np.zeros((n, num_bands, 2), np.uint32)
    for j in range(num_bands):
        sizes = rng.randint(1, 10, size=n)
        group = np.repeat(np.arange(n), sizes)[:n]
        bands[rng.permutation(n), j, 0] = group
    return BandMatrixSource(bands)


def _edges_source(rng, n, num_edges=300):
    """Random edges, each repeated, reversed or both, in two shards."""
    e = rng.randint(0, n, size=(num_edges, 2))
    e = np.concatenate([e, e[: num_edges // 3], e[::4, ::-1]])
    return ShardedEdgeSource(e[rng.permutation(len(e))], num_docs=n,
                             num_shards=2)


def _band_merge(feeds, n, sims, *, cap, tree, rounds, edge, scalar):
    verifier = _TableVerifier(sims)
    acc = ClusterAccumulator(n, verifier, edge, tree, batch="band",
                             max_batch_pairs=cap)
    if scalar:
        acc._feed_arrays = acc._feed_loop
    stats = []
    for i, source in enumerate(feeds):
        st = acc.feed(source)
        stats.append(dataclasses.replace(st, verify_seconds=0.0))
        if rounds and i == 0:
            merge_cluster_rounds(acc.uf, verifier, edge,
                                 sim_cache=acc.evaluated)
    return dict(labels=acc.uf.components().tolist(), pairs=acc.pairs,
                stats=stats, batches=verifier.n_batches,
                evaluated=list(acc.evaluated.items()),
                min_score=acc.uf.min_score.tolist())


BAND_CASES = {
    **{f"{kind}-cap{cap}": (kind, cap, 0.40, False, 0.75)
       for kind in ("runs", "edges") for cap in (1, 3, 8192)},
    "tree-rejects": ("runs", 3, 0.65, False, 0.75),
    "two-feeds-rounds": ("both", 3, 0.40, True, 0.75),
    # float32(0.8) lies above 0.8: sims on the threshold are edges
    # only when compared in float64, as the scalar loop compares them.
    "sims-at-threshold": ("runs", 3, 0.40, False, 0.8),
}


@pytest.mark.parametrize("case", list(BAND_CASES))
def test_band_arrays_match_scalar_loop(case):
    """At ``batch="band"`` the array merge gives the scalar loop's
    labels, pairs and sims, counts, verify batches and sim-cache order."""
    kind, cap, tree, rounds, edge = BAND_CASES[case]
    rng = np.random.RandomState(sorted(BAND_CASES).index(case))
    n = 48
    sims = _sim_table(rng, n)
    if edge == 0.8:
        sims = np.round(sims * 5).astype(np.float32) / np.float32(5)
    feeds = {"runs": [_runs_source(rng, n)],
             "edges": [_edges_source(rng, n)],
             "both": [_runs_source(rng, n), _edges_source(rng, n)]}[kind]
    run = functools.partial(_band_merge, feeds, n, sims, cap=cap,
                            tree=tree, rounds=rounds, edge=edge)
    ref, got = run(scalar=True), run(scalar=False)
    assert sum(st.unions_done for st in ref["stats"]) > 0
    if tree > 0.5:
        assert sum(st.unions_rejected for st in ref["stats"]) > 0
    if cap < 8192:   # cuts inside a band, with unions between them
        assert ref["batches"] > sum(s.num_bands for s in feeds)
    assert got == ref
    if not rounds:   # edges by a float64 compare, as ``float(sim) >``
        sim = np.array([v for _, v in got["evaluated"]])
        assert sum(st.pairs_above_edge for st in got["stats"]) == \
            np.count_nonzero(sim > edge)


@pytest.mark.parametrize("cap", [8192, 16])
def test_band_arrays_match_scalar_loop_in_session(cap, monkeypatch):
    """The paper's settings in a session over templated notes: the
    within-chunk bands and the cross-step edges through both merges."""
    notes, _ = inject_near_duplicates(make_i2b2_like(72, seed=11), 48,
                                      seed=12)
    cfg = DedupConfig(ngram=8, num_hashes=100, rows_per_band=2,
                      edge_threshold=0.75, tree_threshold=0.40,
                      use_disjoint_sets=True, exact_verification=True,
                      verify_batch="band")

    def ingest():
        sess = DedupSession(cfg, backend="host")
        sess.acc.max_batch_pairs = cap
        for i in range(0, len(notes), 40):
            snap = sess.ingest(notes[i:i + 40])
        return (snap.labels.tolist(), snap.pairs,
                dataclasses.replace(snap.stats, verify_seconds=0.0))

    got = ingest()
    with monkeypatch.context() as m:
        m.setattr(ClusterAccumulator, "_feed_arrays",
                  ClusterAccumulator._feed_loop)
        ref = ingest()
    assert ref[2].unions_done > 0 and ref[2].pairs_evaluated > 0
    assert got == ref


def _merge_span_counts(tmp_path, source, sims, cap):
    import jax

    from repro.core import spans

    acc = ClusterAccumulator(source.num_docs, _TableVerifier(sims), 0.75,
                             0.0, batch="band", max_batch_pairs=cap)
    spans.take()
    jax.profiler.start_trace(str(tmp_path))
    try:
        acc.feed(source)
    finally:
        jax.profiler.stop_trace()
    (counts,) = [c for name, *_, c in spans.take() if name == "dedup.merge"]
    return counts


def test_merge_span_counts_groups_and_recompressed(tmp_path):
    """``groups`` counts the runs of two or more members; ``recompressed``
    the runs compressed again after a flush that unioned."""
    rng = np.random.RandomState(3)
    source = _runs_source(rng, 40)
    n_groups = sum(1 for b in source.iter_bands() for _ in b.iter_groups())
    low = np.full((40, 40), 0.5, np.float32)     # no pair is an edge
    counts = _merge_span_counts(tmp_path / "low", source, low, 1)
    assert counts["groups"] == n_groups and counts["recompressed"] == 0

    # One band of three edges, a flush after each: the first unions 0
    # and 1, so the two runs after it are compressed again.
    chain3 = ShardedEdgeSource(np.array([[0, 1], [1, 2], [0, 2]]),
                               num_docs=3)
    counts = _merge_span_counts(tmp_path / "high", chain3,
                                np.full((3, 3), 0.9, np.float32), 1)
    assert counts["groups"] == 3 and counts["recompressed"] > 0
