"""DedupSession: incremental multi-step ingest over every backend.

Pins the session contract: snapshot-after-every-chunk converges on the
one-shot clustering with bit-identical per-edge sims, across the host,
streaming, and (single-device here; multi-device in
tests/test_distributed.py) sharded backends — plus the growth
primitives it stands on (uf.grow, verifier extension, BandIndex,
DocIdAllocator).
"""
import numpy as np
import pytest

from repro.core import DedupConfig, DedupPipeline, DedupSession
from repro.core.engine import ClusterAccumulator
from repro.core.session import BandIndex, DocIdAllocator
from repro.core.streaming import StreamingDedup
from repro.core.unionfind import ThresholdUnionFind
from repro.core.verify import (
    CallbackVerifier, ExactJaccardVerifier, SignatureVerifier,
)
from repro.data import inject_near_duplicates, make_i2b2_like


def _corpus(n=60, dups=40, seed=0):
    notes = make_i2b2_like(n, seed=seed)
    notes, _ = inject_near_duplicates(notes, dups, seed=seed + 1)
    return notes


def _chunks(notes, k):
    return [[notes[i] for i in idx]
            for idx in np.array_split(np.arange(len(notes)), k)]


def _assert_matches_reference(snap, ref_labels, ref_pairs):
    np.testing.assert_array_equal(snap.labels, ref_labels)
    sims = {(a, b): s for a, b, s in ref_pairs}
    shared = [(a, b, s) for a, b, s in snap.pairs if (a, b) in sims]
    assert shared, "paths must evaluate overlapping pairs"
    assert all(s == sims[(a, b)] for a, b, s in shared)


# -- host backend ----------------------------------------------------------

@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("n_chunks", [1, 3])
def test_host_session_chunked_matches_one_shot(exact, n_chunks):
    notes = _corpus()
    cfg = DedupConfig(exact_verification=exact)
    ref = DedupPipeline(cfg).run(notes)
    sess = DedupSession(cfg, backend="host")
    for i, chunk in enumerate(_chunks(notes, n_chunks)):
        snap = sess.ingest(chunk)
        assert snap.n_docs == sum(
            len(c) for c in _chunks(notes, n_chunks)[: i + 1])
    _assert_matches_reference(snap, ref.labels, ref.pairs)
    assert snap.num_duplicates == ref.num_duplicates_removed
    assert snap.num_clusters == ref.num_clusters
    assert sess.steps_ingested == n_chunks


def test_host_session_snapshots_are_cumulative_and_isolated():
    notes = _corpus(40, 20, seed=3)
    sess = DedupSession(DedupConfig(exact_verification=False),
                        backend="host")
    snap1 = sess.ingest(notes[:20])
    snap2 = sess.ingest(notes[20:])
    assert snap2.n_docs == len(notes) > snap1.n_docs
    assert snap2.stats.pairs_evaluated >= snap1.stats.pairs_evaluated
    # snapshot stats are copies: later ingest must not mutate snap1
    before = snap1.stats.pairs_evaluated
    sess.ingest(notes[:5])
    assert snap1.stats.pairs_evaluated == before


def test_host_ingest_stream_equals_sequential_ingest():
    notes = _corpus(40, 20, seed=5)
    cfg = DedupConfig(exact_verification=False)
    chunks = _chunks(notes, 4)
    seq = DedupSession(cfg, backend="host")
    seq_snaps = [seq.ingest(c) for c in chunks]
    stream = DedupSession(cfg, backend="host")
    stream_snaps = list(stream.ingest_stream(chunks))
    assert len(stream_snaps) == len(seq_snaps)
    for a, b in zip(seq_snaps, stream_snaps):
        assert a.n_docs == b.n_docs
        np.testing.assert_array_equal(a.labels, b.labels)
    assert seq_snaps[-1].pairs == stream_snaps[-1].pairs


def test_snapshot_pairs_stay_frozen_across_later_ingests():
    notes = _corpus(60, 40, seed=7)
    sess = DedupSession(DedupConfig(exact_verification=False),
                        backend="host")
    chunks = _chunks(notes, 3)
    snap1 = sess.ingest(chunks[0])
    held = list(snap1.pairs)
    for c in chunks[1:]:
        last = sess.ingest(c)
    assert len(last.pairs) > len(held) > 0
    assert len(snap1.pairs) == len(held) and snap1.pairs == held
    for col in (snap1.pairs.ab, snap1.pairs.sim):
        assert not col.flags.writeable


@pytest.mark.parametrize("refine_every", [None, 2])
def test_snapshots_sort_each_pair_once(tmp_path, refine_every):
    """The ``sorted`` counts of the ``dedup.snapshot`` spans add up to
    the pairs of the last snapshot: no pair is sorted twice, whether
    ``feed`` or a refine round wrote it."""
    import jax

    from repro.core import RetentionPolicy, spans

    retention = (None if refine_every is None else
                 RetentionPolicy.preset("none", refine_every=refine_every))
    sess = DedupSession(DedupConfig(exact_verification=False),
                        backend="host", retention=retention)
    spans.take()
    jax.profiler.start_trace(str(tmp_path))
    try:
        snaps = [sess.ingest(c) for c in _chunks(_corpus(48, 32, seed=9), 4)]
        snaps.append(sess.refine())
    finally:
        jax.profiler.stop_trace()
    kept = [s[4] for s in spans.take() if s[0] == "dedup.snapshot"]
    # An auto-refine inside ``ingest`` takes a snapshot of its own.
    assert len(kept) == len(snaps) + (0 if refine_every is None else 2)
    sizes = [k["pairs"] for k in kept]
    assert sizes[-1] == len(snaps[-1].pairs)
    assert [k["sorted"] for k in kept] == [
        b - a for a, b in zip([0] + sizes, sizes)]
    assert sum(k["sorted"] for k in kept) == len(snaps[-1].pairs) > 0


@pytest.mark.parametrize("exact", [True, False])
def test_host_session_doc_id_base_resumed_ingest(exact):
    """Regression: a doc_id_base > 0 session must verify through global
    ids (the first verifier build once covered only the chunk's rows,
    so global ids indexed past the matrix — IndexError on numpy, silent
    clamped-gather sims on jnp/pallas)."""
    notes = _corpus(30, 20, seed=13)
    base = 100
    sess = DedupSession(DedupConfig(exact_verification=exact),
                        backend="host", doc_id_base=base)
    snap1 = sess.ingest(notes[:15])
    snap = sess.ingest(notes[15:] + [notes[0]])   # cross-chunk dup
    assert snap.n_docs == base + len(notes) + 1
    ref = DedupPipeline(DedupConfig(exact_verification=exact)).run(
        notes + [notes[0]])
    np.testing.assert_array_equal(snap.labels[base:] - base, ref.labels)
    assert (snap.labels[:base] == np.arange(base)).all()  # gap singletons
    sims = {(a, b): s for a, b, s in ref.pairs}
    shared = [(a - base, b - base, s) for a, b, s in snap.pairs
              if (a - base, b - base) in sims]
    assert shared
    assert all(s == sims[(a, b)] for a, b, s in shared)
    assert snap1.stats.pairs_evaluated <= snap.stats.pairs_evaluated


# -- streaming backend -----------------------------------------------------

@pytest.mark.parametrize("n_chunks", [1, 3])
def test_streaming_session_chunked_matches_one_shot(n_chunks):
    notes = _corpus()
    cfg = DedupConfig(exact_verification=False)
    ref = DedupPipeline(cfg).run(notes)
    sess = DedupSession(cfg, backend="streaming", chunk_docs=16)
    for chunk in _chunks(notes, n_chunks):
        snap = sess.ingest(chunk)
    _assert_matches_reference(snap, ref.labels, ref.pairs)
    # the store-rescan cache never re-verifies a pair
    assert snap.stats.pairs_evaluated <= ref.stats.pairs_evaluated + \
        snap.stats.pairs_above_edge


def test_streaming_cluster_adapter_session_stays_live():
    """StreamingDedup.cluster == session over_store snapshot, and the
    underlying machinery keeps accepting chunks afterwards."""
    notes = _corpus(40, 20, seed=7)
    sd = StreamingDedup(DedupConfig(), chunk_docs=8)
    sd.ingest(notes)
    uf, stats = sd.cluster()
    from repro.core.session import DedupSession as DS

    sess = DS.over_store(sd)
    np.testing.assert_array_equal(uf.components(),
                                  sess.uf.components())
    # live continuation: a duplicate of doc 0 ingested later joins it
    snap = sess.ingest([notes[0]])
    assert snap.n_docs == len(notes) + 1
    assert snap.labels[len(notes)] == snap.labels[0]


# -- sharded backend (single-device mesh; 8-device in
# tests/test_distributed.py) ------------------------------------------------

@pytest.mark.parametrize("stage2", ["host", "device"])
def test_sharded_session_single_device_matches_host(stage2):
    from repro.core.dist_lsh import DistLSHConfig

    rng = np.random.RandomState(0)
    vocab = [f"t{i}" for i in range(300)]
    docs = [" ".join(rng.choice(vocab, size=48)) for _ in range(24)]
    docs[5] = docs[3]
    docs[21] = docs[3]                        # cross-chunk duplicate
    cfg = DedupConfig(ngram=4, num_hashes=20, edge_threshold=0.5,
                      exact_verification=False)
    ref = DedupPipeline(cfg).run(docs)
    dcfg = DistLSHConfig(ngram=4, num_hashes=20, verify_k=8,
                         edge_capacity=256, edge_threshold=0.5,
                         bucket_slack=16.0, band_groups=2,
                         stage2=stage2)
    sess = DedupSession(cfg, backend="sharded", dist_config=dcfg)
    for chunk in _chunks(docs, 2):
        snap = sess.ingest(chunk)
    _assert_matches_reference(snap, ref.labels, ref.pairs)
    assert snap.overflow == 0
    lab = snap.labels
    assert lab[3] == lab[5] == lab[21]
    if stage2 == "device":
        # 1-device mesh: every within-chunk edge is same-shard
        assert snap.device_scored > 0


# -- growth primitives -----------------------------------------------------

def test_unionfind_grow_preserves_state():
    uf = ThresholdUnionFind(4, 0.3)
    uf.union(0, 1, 0.9)
    roots_before = uf.components().copy()
    ms_before = uf.min_score.copy()
    uf.grow(8)
    assert len(uf.parent) == 8
    np.testing.assert_array_equal(uf.components()[:4], roots_before)
    np.testing.assert_array_equal(uf.min_score[:4], ms_before)
    assert all(uf.find(i) == i for i in range(4, 8))
    uf.grow(6)                                # no-op shrink attempt
    assert len(uf.parent) == 8
    uf.union(2, 7, 0.95)
    assert uf.find(2) == uf.find(7)


def test_accumulator_grow_and_per_feed_verifier_override():
    from repro.core.candidates import ShardedEdgeSource

    sims_a = {(0, 1): 0.9}
    sims_b = {(2, 3): 0.8}
    acc = ClusterAccumulator(
        2, CallbackVerifier(lambda a, b: sims_a[(a, b)]), 0.75, 0.3)
    acc.feed(ShardedEdgeSource(np.array([[0, 1]]), num_docs=2))
    acc.grow(4)
    assert acc.num_docs == 4
    acc.feed(ShardedEdgeSource(np.array([[2, 3]]), num_docs=4),
             verifier=CallbackVerifier(lambda a, b: sims_b[(a, b)]))
    assert acc.evaluated == {(0, 1): np.float32(0.9),
                             (2, 3): np.float32(0.8)}
    assert acc.uf.find(0) == acc.uf.find(1)
    assert acc.uf.find(2) == acc.uf.find(3)


def test_signature_verifier_extension_matches_full_build():
    rng = np.random.RandomState(2)
    sig = rng.randint(0, 50, size=(30, 100)).astype(np.uint32)
    pairs = np.array([(a, b) for a in range(0, 30, 3)
                      for b in range(a + 1, 30, 7)], dtype=np.int64)
    full = SignatureVerifier(sig)
    for backend in ("numpy", "jnp"):
        v = SignatureVerifier(sig[:10], backend=backend)
        v.extend_signatures(sig[10:20])
        v.extend_signatures(sig[20:])
        np.testing.assert_array_equal(v(pairs), full(pairs))
    with pytest.raises(ValueError):
        full.extend_signatures(np.zeros((2, 7), dtype=np.uint32))


def test_exact_verifier_extension_matches_full_build():
    notes = _corpus(30, 15, seed=9)
    toks = [n.split() for n in notes]
    full = ExactJaccardVerifier.from_token_lists(toks, 8)
    v = ExactJaccardVerifier.from_token_lists(toks[:10], 8)
    v.extend_token_lists(toks[10:20])
    v.extend_token_lists(toks[20:])
    pairs = np.array([(a, b) for a in range(0, 30, 3)
                      for b in range(a + 1, 30, 7)], dtype=np.int64)
    np.testing.assert_array_equal(v(pairs), full(pairs))
    raw = ExactJaccardVerifier([np.array([1, 2, 3])])
    with pytest.raises(ValueError):
        raw.extend_token_lists([["a"]])       # no vocab to intern with


def test_doc_id_allocator_and_device_offsets():
    al = DocIdAllocator(100)
    assert al.allocate(8) == 100
    assert al.allocate(4) == 108
    assert al.n_docs == 112
    np.testing.assert_array_equal(
        DocIdAllocator.device_offsets(108, 2, 4),
        np.uint32([108, 110, 112, 114]))


def test_band_index_cross_step_edges():
    idx = BandIndex(2)
    b1 = np.array([[[1, 1], [9, 9]],
                   [[2, 2], [8, 8]]], dtype=np.uint32)   # docs 0, 1
    assert len(idx.match_then_insert(b1, 0)) == 0        # nothing retained
    # doc 2 collides with doc 0 in band 0 and doc 1 in band 1;
    # doc 3 collides with doc 0 in band 0 — its same-chunk collision
    # with doc 2 is NOT emitted (the within-chunk source owns those)
    b2 = np.array([[[1, 1], [8, 8]],
                   [[1, 1], [7, 7]]], dtype=np.uint32)   # docs 2, 3
    edges = idx.match_then_insert(b2, 2)
    assert sorted(map(tuple, edges.tolist())) == \
        [(0, 2), (0, 3), (1, 2)]
    # ...but doc 2 IS retained: a third chunk colliding with it matches
    b3 = np.array([[[1, 1], [0, 0]]], dtype=np.uint32)   # doc 4
    edges = idx.match_then_insert(b3, 4)
    assert sorted(map(tuple, edges.tolist())) == \
        [(0, 4), (2, 4), (3, 4)]
    with pytest.raises(ValueError):
        idx.match_then_insert(np.zeros((1, 3, 2), np.uint32), 9)


def test_band_index_holds_no_list_per_single_doc_bucket():
    """A bucket of one doc is the bare id, so the index adds no object
    a garbage collection walks per (band, doc): such a collection's
    pause no longer grows with the index."""
    import gc

    rng = np.random.default_rng(0)
    bands = rng.integers(0, 2**32, size=(4000, 14, 2),
                         dtype=np.uint64).astype(np.uint32)
    bands[3000] = bands[3]                   # collides in every band
    idx = BandIndex(14)
    gc.collect()
    before = len(gc.get_objects())
    assert len(idx.match_then_insert(bands[:2000], 0)) == 0
    edges = idx.match_then_insert(bands[2000:], 2000)
    gc.collect()
    assert len(gc.get_objects()) - before < 200     # not 14 x 4,000
    assert edges.tolist() == [[3, 3000]] * 14
    assert idx.stats()["n_entries"] == 14 * 4000
    key = (int(bands[3, 0, 0]), int(bands[3, 0, 1]))
    maps = idx.export_maps()
    assert maps[0][key] == (3, 3000)
    other = (int(bands[7, 0, 0]), int(bands[7, 0, 1]))
    assert maps[0][other] == (7,)


def test_band_index_evicts_a_single_doc_bucket_onto_its_root():
    idx = BandIndex(1, track_entries=True)
    idx.match_then_insert(np.array([[[1, 1]], [[2, 2]]], np.uint32), 0)
    idx.evict([1], lambda d: 0)              # doc 1 deposed under 0
    assert idx.export_maps()[0][(2, 2)] == (0,)
    edges = idx.match_then_insert(np.array([[[2, 2]]], np.uint32), 2)
    assert edges.tolist() == [[0, 2]]
    idx.evict([0], lambda d: 2)              # the re-homed entry moves on
    assert idx.export_maps()[0][(1, 1)] == (2,)
    assert idx.export_maps()[0][(2, 2)] == (2,)


# -- order invariance of ClusterAccumulator --------------------------------

def _run_order_invariance(seed: int, n_docs: int, n_edges: int,
                          order_seed: int):
    """Same edge multiset, shuffled feed partitions/orders -> identical
    clusters, and identical sims for every pair either order evaluates.

    Doc-pair sims are deterministic and bimodal (exact duplicates at
    1.0 vs clear non-dups below 0.5), the regime the session's
    chunk-vs-one-shot equivalence relies on: the union guard never
    fires mid-band, so clustering is pure thresholded connectivity and
    must not depend on how the engine's feeds partition the edges.
    """
    from repro.core.candidates import ShardedEdgeSource

    rng = np.random.RandomState(seed)
    group_of = rng.randint(0, max(2, n_docs // 3), size=n_docs)

    def sim(a, b):
        return 1.0 if group_of[a] == group_of[b] else \
            0.1 + 0.4 * ((a * 31 + b * 17) % 10) / 10.0

    edges = rng.randint(0, n_docs, size=(n_edges, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]

    def cluster(order_rng):
        e = edges[order_rng.permutation(len(edges))]
        acc = ClusterAccumulator(n_docs, CallbackVerifier(sim),
                                 0.75, 0.3)
        n_parts = order_rng.randint(1, 5)
        for part in np.array_split(e, n_parts):
            acc.feed(ShardedEdgeSource(part, num_docs=n_docs))
        first = {}
        canon = [first.setdefault(int(r), i)
                 for i, r in enumerate(acc.uf.components())]
        return canon, dict(acc.evaluated)

    canon_a, eval_a = cluster(np.random.RandomState(order_seed))
    canon_b, eval_b = cluster(np.random.RandomState(order_seed + 1))
    assert canon_a == canon_b
    common = set(eval_a) & set(eval_b)
    assert all(eval_a[k] == eval_b[k] for k in common)
    # every edge pair with sim > threshold was clustered in both
    for a, b in edges:
        if sim(int(a), int(b)) > 0.75:
            assert canon_a[a] == canon_a[b]


@pytest.mark.parametrize("seed", range(6))
def test_cluster_accumulator_order_invariance_deterministic(seed):
    """Deterministic sweep (the hypothesis exploration is CI-only)."""
    _run_order_invariance(seed, n_docs=10 + seed, n_edges=24,
                          order_seed=seed * 7 + 1)


def test_cluster_accumulator_order_invariance_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=40)
    @given(seed=st.integers(0, 2**20), n_docs=st.integers(4, 16),
           n_edges=st.integers(1, 40), order_seed=st.integers(0, 2**20))
    def prop(seed, n_docs, n_edges, order_seed):
        _run_order_invariance(seed, n_docs, n_edges, order_seed)

    prop()
