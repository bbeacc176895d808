"""Streaming dedup (paper §12 two-phase mode)."""

from repro.core.pipeline import DedupConfig, DedupPipeline
from repro.core.streaming import StreamingDedup, merge_cluster_rounds
from repro.data import make_i2b2_like


def test_streaming_matches_batch_pipeline():
    notes = make_i2b2_like(80, seed=0)
    notes = notes + [notes[0]] * 3 + [notes[5]] * 2

    batch = DedupPipeline(DedupConfig()).run(notes)

    sd = StreamingDedup(DedupConfig(), chunk_docs=16)
    sd.ingest(notes)
    assert sd.n_docs == len(notes)
    uf, stats = sd.cluster()
    # identical exact-dup clusters
    sl = uf.components()
    assert (sl[80] == sl[0]) and (sl[81] == sl[0]) and (sl[82] == sl[0])
    assert (sl[83] == sl[5]) and (sl[84] == sl[5])
    # same number of duplicates found
    n_stream = len(notes) - len(set(sl.tolist()))
    assert n_stream == batch.num_duplicates_removed


def test_streaming_incremental_ingest_and_rethreshold():
    notes = make_i2b2_like(40, seed=1)
    sd = StreamingDedup(DedupConfig(), chunk_docs=8)
    sd.ingest(notes)
    n0 = sd.n_docs
    # late-arriving duplicates (the production stream case)
    sd.ingest([notes[3], notes[7]])
    assert sd.n_docs == n0 + 2
    uf, _ = sd.cluster()
    labels = uf.components()
    assert labels[n0] == labels[3]
    assert labels[n0 + 1] == labels[7]
    # phase 2 re-run at a different threshold without re-hashing
    uf2, _ = sd.cluster(edge_threshold=0.95)
    assert len(set(uf2.components().tolist())) >= len(
        set(labels.tolist()))


def test_second_round_merging():
    """Paper §10: a second round merges over-partitioned clusters."""
    from repro.core.unionfind import ThresholdUnionFind

    # 4 docs, all pairwise sim 0.9, but round 1 only saw edges (0,1), (2,3).
    sims = {(a, b): 0.9 for a in range(4) for b in range(4) if a < b}
    uf = ThresholdUnionFind(4, tree_threshold=0.4)
    uf.union(0, 1, 0.9)
    uf.union(2, 3, 0.9)
    assert uf.find(0) != uf.find(2)
    merges = merge_cluster_rounds(
        uf, lambda a, b: sims[(min(a, b), max(a, b))],
        edge_threshold=0.75)
    assert merges == 1
    assert uf.find(0) == uf.find(2)
