"""Multi-device integration (subprocesses with forced host devices):
the distributed LSH path against the one-device host path."""
import pytest

from tests.conftest import run_with_devices


@pytest.mark.slow
def test_dist_lsh_cross_shard_duplicates():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, networkx as nx
        from repro.core.dist_lsh import (DistLSHConfig, docs_mesh,
                                         make_dedup_step)
        from repro.core import shingle, minhash
        rng = np.random.RandomState(0)
        vocab = [f"t{i}" for i in range(400)]
        docs = [list(rng.choice(vocab, size=64)) for _ in range(64)]
        docs[5] = docs[3]; docs[41] = docs[3]
        docs[9] = docs[3][:60] + docs[9][:4]
        packed = shingle.pack_documents(docs)
        cfg = DistLSHConfig(edge_capacity=256, edge_threshold=0.5)
        step = make_dedup_step(cfg, docs_mesh())
        out = step(jnp.asarray(packed.tokens),
                   jnp.asarray(packed.lengths),
                   jnp.asarray(minhash.default_seeds(cfg.num_hashes)))
        em = np.asarray(out["edge_mask"])
        edges = np.asarray(out["edges"])[em]
        g = nx.Graph(); g.add_edges_from(map(tuple, edges.tolist()))
        comp = nx.node_connected_component(g, 3)
        assert {3, 5, 41} <= comp, comp
        assert 9 in comp
        print("dist lsh ok")
    """, n_devices=8)


@pytest.mark.slow
def test_sharded_engine_matches_host_pipeline():
    """Ported sharded path == host path on the shared engine.

    dist_lsh prescreened edges + ShardedEdgeSource -> cluster_source
    must produce the same clusters as DedupPipeline (estimate mode) on
    the same corpus, with identical per-edge similarity estimates for
    every pair both paths evaluate (both verify against the full
    signature matrix with the same estimator).
    """
    run_with_devices("""
        from collections import defaultdict
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step)
        from repro.core.pipeline import DedupConfig, DedupPipeline
        from repro.core import shingle, minhash
        from repro.data import make_i2b2_like, inject_near_duplicates
        # Clean similarity margin: near-exact dups (J >= ~0.93) vs
        # template notes (J <= ~0.8); threshold 0.88 sits in the gap so
        # the verify_k=32 prefix prescreen (recall margin 0.15) cannot
        # drop a true edge.
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        host = DedupPipeline(DedupConfig(
            edge_threshold=0.88, exact_verification=False,
            verify_backend="numpy")).run(notes)

        token_lists = [shingle.tokenize(t) for t in notes]
        packed = shingle.pack_documents(token_lists)
        # bucket_slack sized so no device bucket overflows: the pure
        # sharded edge path (no host fallback) must match on its own.
        cfg = DistLSHConfig(edge_capacity=4096, edge_threshold=0.88,
                            bucket_slack=16.0)
        step = make_dedup_step(cfg, docs_mesh())
        out = step(jnp.asarray(packed.tokens),
                   jnp.asarray(packed.lengths),
                   jnp.asarray(minhash.default_seeds(cfg.num_hashes)))
        # device and host signature matrices are bit-identical
        assert np.array_equal(np.asarray(out["sig"]), host.signatures)
        res = cluster_step_output(out, cfg, tree_threshold=0.40,
                                  num_docs=len(notes),
                                  overflow_fallback=False)
        assert res.overflow == 0, res.overflow
        assert res.num_edges > 0

        # identical per-edge similarity estimates on shared pairs
        host_sims = {(a, b): s for a, b, s in host.pairs}
        shared = [(a, b, s) for a, b, s in res.pairs
                  if (a, b) in host_sims]
        assert shared, "paths must evaluate overlapping pairs"
        assert all(s == host_sims[(a, b)] for a, b, s in shared)

        def comps(labels):
            d = defaultdict(list)
            for i, l in enumerate(labels):
                d[int(l)].append(i)
            return {frozenset(v) for v in d.values() if len(v) >= 2}
        assert comps(res.labels()) == comps(host.labels)
        print("sharded engine == host ok")
    """, n_devices=8)


@pytest.mark.slow
def test_dist_lsh_doc_offsets_chunked():
    """Regression: chunked invocations must not alias global doc ids.

    The historical ``dev * d_loc + arange(d_loc)`` assignment restarted
    at 0 for every step invocation, so edges from a second corpus chunk
    collided with chunk-one ids.  ``doc_offsets`` pins the global base.
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, docs_mesh,
                                         make_dedup_step)
        from repro.core import shingle, minhash
        rng = np.random.RandomState(0)
        vocab = [f"t{i}" for i in range(300)]
        docs = [list(rng.choice(vocab, size=48)) for _ in range(8)]
        docs[7] = docs[0]          # duplicate pair inside chunk B
        packed = shingle.pack_documents(docs)
        cfg = DistLSHConfig(edge_capacity=256, edge_threshold=0.5,
                            bucket_slack=16.0)
        step = make_dedup_step(cfg, docs_mesh())
        seeds = jnp.asarray(minhash.default_seeds(cfg.num_hashes))
        args = (jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                seeds)
        # Default offsets: contiguous row ids (the old behaviour).
        out_a = step(*args)
        em = np.asarray(out_a["edge_mask"])
        ids_a = set(np.asarray(out_a["edges"])[em].flatten().tolist())
        assert ids_a and max(ids_a) < 8, ids_a
        # Chunk B of a larger corpus, global docs 16..23: every edge id
        # must land in [16, 24) — the old scheme returned 0..7 and
        # silently collided with chunk A.
        out_b = step(*args,
                     jnp.uint32(16) + jnp.arange(8, dtype=jnp.uint32))
        em = np.asarray(out_b["edge_mask"])
        ids_b = set(np.asarray(out_b["edges"])[em].flatten().tolist())
        assert ids_b and all(16 <= i < 24 for i in ids_b), ids_b
        assert {16, 23} <= ids_b   # the injected duplicate pair
        # The host merge composes with offsets: doc_id_base shifts the
        # global edge ids back onto the chunk-local signature rows.
        from repro.core.dist_lsh import cluster_step_output
        res = cluster_step_output(out_b, cfg, tree_threshold=0.4,
                                  num_docs=8, doc_id_base=16)
        assert res.num_edges > 0
        labels = res.labels()
        assert labels[0] == labels[7], labels   # global docs 16 and 23
        print("doc offsets ok")
    """, n_devices=8)


@pytest.mark.slow
def test_band_group_streaming_matches_end_of_step():
    """Band-group streaming == the PR 2 end-of-step path, any G.

    The streamed step emits one bounded verified-edge buffer per
    band-group and cluster_step_output consumes them incrementally
    (host merge of group g overlaps the device shuffle of group g+1);
    clusters and per-edge full-signature sims must be identical to the
    single end-of-step gather, with edge drift 0.
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step,
                                         make_streamed_dedup_step)
        from repro.core import shingle, minhash
        from repro.data import make_i2b2_like, inject_near_duplicates
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        packed = shingle.pack_documents(
            [shingle.tokenize(t) for t in notes])
        seeds = jnp.asarray(minhash.default_seeds(100))
        args = (jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                seeds)
        base = dict(edge_capacity=4096, edge_threshold=0.88,
                    bucket_slack=16.0)
        ref_step = make_dedup_step(DistLSHConfig(**base), docs_mesh())
        ref = cluster_step_output(ref_step(*args), DistLSHConfig(**base),
                                  tree_threshold=0.40, num_docs=len(notes),
                                  overflow_fallback=False)
        assert ref.overflow == 0 and ref.num_edges > 0
        sims = {(a, b): s for a, b, s in ref.pairs}
        for G in (2, 5, 10):
            cfg = DistLSHConfig(**base, band_groups=G)
            step = make_streamed_dedup_step(cfg, docs_mesh())
            res = cluster_step_output(step(*args), cfg,
                                      tree_threshold=0.40,
                                      num_docs=len(notes),
                                      overflow_fallback=False)
            assert res.overflow == 0
            assert res.num_edges == ref.num_edges, (G, res.num_edges)
            assert len(res.group_stats) == G
            np.testing.assert_array_equal(res.labels(), ref.labels())
            shared = [(a, b, s) for a, b, s in res.pairs
                      if (a, b) in sims]
            assert shared, G
            drift = sum(1 for a, b, s in shared if s != sims[(a, b)])
            assert drift == 0, (G, drift)
        print("band-group streaming ok")
    """, n_devices=8)


@pytest.mark.slow
def test_device_stage2_passthrough_and_stragglers():
    """Device-resident stage 2 == host stage 2, bit for bit.

    Same-shard edges are fully scored on the accelerator (the fused
    sigjaccard kernel under shard_map); cross-shard edges are scored
    there too via the bounded signature-row exchange inside the
    all_to_all (``sig_row_capacity``), so with ample capacity the host
    re-score path is pinned to ZERO — and with the exchange disabled
    (capacity 0) the historical straggler re-score recovers the same
    result.  Both kinds of edges are planted; clusters and per-edge
    sims must match the end-of-step host-verified path exactly
    (drift 0).
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step,
                                         make_streamed_dedup_step)
        from repro.core import shingle, minhash
        rng = np.random.RandomState(0)
        vocab = [f"t{i}" for i in range(400)]
        docs = [list(rng.choice(vocab, size=64)) for _ in range(64)]
        # 8 docs/device: same-shard dups (1,5) on dev0 and (17,20) on
        # dev2; near-dup (17,22) on dev2; cross-shard dup (3,41).
        docs[5] = docs[1]; docs[20] = docs[17]; docs[41] = docs[3]
        docs[22] = docs[17][:60] + docs[22][:4]
        packed = shingle.pack_documents(docs)
        seeds = jnp.asarray(minhash.default_seeds(100))
        args = (jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                seeds)
        base = dict(edge_capacity=4096, edge_threshold=0.5,
                    bucket_slack=16.0)
        ref_step = make_dedup_step(DistLSHConfig(**base), docs_mesh())
        ref = cluster_step_output(ref_step(*args), DistLSHConfig(**base),
                                  num_docs=64, overflow_fallback=False)
        sims = {(a, b): s for a, b, s in ref.pairs}

        def run(rc):
            cfg = DistLSHConfig(**base, band_groups=5, stage2="device",
                                sig_row_capacity=rc)
            step = make_streamed_dedup_step(cfg, docs_mesh())
            out = step(*args)
            assert all("device_match_counts" in g for g in out["groups"])
            res = cluster_step_output(out, cfg, num_docs=64,
                                      overflow_fallback=False)
            assert res.overflow == 0
            np.testing.assert_array_equal(res.labels(), ref.labels())
            lab = res.labels()
            assert lab[1] == lab[5] and lab[17] == lab[20] == lab[22]
            assert lab[3] == lab[41]
            shared = [(a, b, s) for a, b, s in res.pairs
                      if (a, b) in sims]
            assert shared
            drift = sum(1 for a, b, s in shared if s != sims[(a, b)])
            assert drift == 0, drift
            assert res.device_scored > 0, "no edge served from device"
            return res

        # Exchange on: the cross-shard dup (3, 41) is scored on-device
        # by dev0 against dev5's exchanged row — host re-scores pinned
        # to row-buffer overflow, which is zero here.
        res = run(rc=1024)
        assert res.row_overflow == 0
        assert res.host_rescored == 0, res.host_rescored
        # Exchange off: historical straggler behaviour, same clusters.
        res = run(rc=0)
        assert res.host_rescored > 0, "straggler fallback not exercised"
        print("device stage2 ok")
    """, n_devices=8)


@pytest.mark.slow
def test_device_stage2_row_buffer_overflow_falls_back_to_host():
    """Cross-shard row exchange overflow: counted, host-recovered.

    With several cross-shard duplicate pairs whose member rows live on
    one device and ``sig_row_capacity=1``, the publisher cannot fit all
    straggler rows; the overflowed edges stay uncovered, the counter
    reports them, and the host re-score path restores exactly the
    end-of-step clustering (drift 0).
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step,
                                         make_streamed_dedup_step)
        from repro.core import shingle, minhash
        rng = np.random.RandomState(3)
        vocab = [f"t{i}" for i in range(400)]
        docs = [list(rng.choice(vocab, size=64)) for _ in range(64)]
        # 8 docs/device: heads 1..3 on dev0, members 41..43 on dev5 —
        # three distinct member rows compete for dev5's exchange buffer.
        docs[41] = docs[1]; docs[42] = docs[2]; docs[43] = docs[3]
        packed = shingle.pack_documents(docs)
        seeds = jnp.asarray(minhash.default_seeds(100))
        args = (jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                seeds)
        base = dict(edge_capacity=4096, edge_threshold=0.5,
                    bucket_slack=16.0)
        ref_step = make_dedup_step(DistLSHConfig(**base), docs_mesh())
        ref = cluster_step_output(ref_step(*args), DistLSHConfig(**base),
                                  num_docs=64, overflow_fallback=False)
        cfg = DistLSHConfig(**base, stage2="device", sig_row_capacity=1)
        step = make_streamed_dedup_step(cfg, docs_mesh())
        res = cluster_step_output(step(*args), cfg, num_docs=64,
                                  overflow_fallback=False)
        assert res.overflow == 0
        assert res.row_overflow > 0, "row buffer should have overflowed"
        assert res.host_rescored > 0, "overflowed edges must re-score"
        np.testing.assert_array_equal(res.labels(), ref.labels())
        lab = res.labels()
        assert lab[1] == lab[41] and lab[2] == lab[42] \\
            and lab[3] == lab[43]
        sims = {(a, b): s for a, b, s in ref.pairs}
        shared = [(a, b, s) for a, b, s in res.pairs if (a, b) in sims]
        assert shared
        assert all(s == sims[(a, b)] for a, b, s in shared)
        print("row overflow ok")
    """, n_devices=8)


@pytest.mark.slow
def test_session_multistep_sharded_matches_single_step():
    """N-step chunked ingest through ONE DedupSession == single-step.

    The session feeds N streamed step invocations (chunked corpus,
    allocator-assigned ``doc_offsets``) into one ClusterAccumulator,
    generating cross-chunk candidates from the retained band index;
    clusters and per-edge sims must be identical / bit-identical to the
    PR 3 single-step path over the concatenated corpus, for N in
    {2, 4}, with and without the device-resident stage 2.  With the
    cross-shard row exchange on and no overflow anywhere, the device
    path's host re-scores stay pinned at zero (overflow-only).
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DedupConfig, DedupSession
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step)
        from repro.core import shingle, minhash
        from repro.data import make_i2b2_like, inject_near_duplicates
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        packed = shingle.pack_documents(
            [shingle.tokenize(t) for t in notes])
        base = dict(edge_capacity=4096, edge_threshold=0.88,
                    bucket_slack=16.0)
        ref_step = make_dedup_step(DistLSHConfig(**base), docs_mesh())
        out = ref_step(jnp.asarray(packed.tokens),
                       jnp.asarray(packed.lengths),
                       jnp.asarray(minhash.default_seeds(100)))
        ref = cluster_step_output(out, DistLSHConfig(**base),
                                  tree_threshold=0.40,
                                  num_docs=len(notes),
                                  overflow_fallback=False)
        assert ref.overflow == 0 and ref.num_edges > 0
        sims = {(a, b): s for a, b, s in ref.pairs}
        cfg = DedupConfig(edge_threshold=0.88, exact_verification=False)
        for stage2 in ("host", "device"):
            for n_steps in (2, 4):
                dcfg = DistLSHConfig(**base, band_groups=5,
                                     stage2=stage2)
                sess = DedupSession(cfg, backend="sharded",
                                    dist_config=dcfg)
                chunks = [[notes[i] for i in idx] for idx in
                          np.array_split(np.arange(len(notes)),
                                         n_steps)]
                snaps = list(sess.ingest_stream(chunks))
                assert len(snaps) == n_steps
                assert [s.n_docs for s in snaps] == list(
                    np.cumsum([len(c) for c in chunks]))
                snap = snaps[-1]
                assert snap.overflow == 0 and snap.row_overflow == 0
                np.testing.assert_array_equal(snap.labels,
                                              ref.labels())
                shared = [(a, b, s) for a, b, s in snap.pairs
                          if (a, b) in sims]
                assert shared, (stage2, n_steps)
                drift = sum(1 for a, b, s in shared
                            if s != sims[(a, b)])
                assert drift == 0, (stage2, n_steps, drift)
                if stage2 == "device":
                    # cross-shard exchange on, nothing overflowed:
                    # host re-scores are overflow-only == 0.
                    assert snap.host_rescored == 0, snap.host_rescored
        print("session multistep ok")
    """, n_devices=8)


@pytest.mark.slow
def test_session_fused_ingest_matches_staged_sharded():
    """Fused one-pass device ingest == staged, through a sharded session.

    The fused shingle->minhash->band-fold kernel feeds the all_to_all
    shuffle in ``local_prepare``; with bit-identical signatures and band
    values the whole downstream pipeline (candidate shuffle, prescreen,
    stage 2, host merge) must produce identical clusters and
    bit-identical per-edge sims vs the staged chain — N-step ingest,
    stage2 host AND device, with the device path's host re-scores
    pinned at zero (overflow-only).  The device-stage2 cell runs once
    (n_steps=2, band_groups=1): interpret-mode device scoring costs
    minutes per session, and ingest parity is stage2-independent.
    """
    run_with_devices("""
        import numpy as np
        from repro.core import DedupConfig, DedupSession
        from repro.core.dist_lsh import DistLSHConfig
        from repro.data import make_i2b2_like, inject_near_duplicates
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        base = dict(edge_capacity=4096, edge_threshold=0.88,
                    bucket_slack=16.0)
        cfg = DedupConfig(edge_threshold=0.88, exact_verification=False)
        for stage2, n_steps, groups in [("host", 1, 5), ("host", 3, 5),
                                        ("device", 2, 1)]:
            chunks = [[notes[i] for i in idx] for idx in
                      np.array_split(np.arange(len(notes)), n_steps)]
            snaps = {}
            for fused in (False, True):
                dcfg = DistLSHConfig(**base, stage2=stage2,
                                     band_groups=groups,
                                     fused_ingest=fused)
                sess = DedupSession(cfg, backend="sharded",
                                    dist_config=dcfg)
                for snap in sess.ingest_stream(chunks):
                    pass
                assert snap.overflow == 0
                snaps[fused] = snap
            a, b = snaps[False], snaps[True]
            np.testing.assert_array_equal(a.labels, b.labels)
            pa = {(x, y): s for x, y, s in a.pairs}
            pb = {(x, y): s for x, y, s in b.pairs}
            assert pa and pa == pb, (stage2, n_steps)
            if stage2 == "device":
                assert b.host_rescored == 0, b.host_rescored
        print("fused sharded parity ok")
    """, n_devices=8)


@pytest.mark.slow
def test_session_byte_ingest_matches_token_sharded():
    """Device bytes->bands == host no-stem tokenize, sharded N-step.

    A ``byte_ingest`` sharded session consumes raw UTF-8 texts (the
    zero-copy path: uint8 bytes are the only host->device transfer and
    tokenize/shingle/minhash/band-fold run in ``local_prepare`` on
    device); a fused token session consumes the matching
    ``tokenize(do_stem=False)`` lists.  Bit-identical signatures and
    band values mean the whole downstream pipeline must agree: labels
    identical, per-edge sims bit-identical, and the device-stage2
    cell's host re-scores pinned at zero (overflow-only), across
    N-step ingest.  Same cell set as the fused-vs-staged pin.
    """
    run_with_devices("""
        import numpy as np
        from repro.core import DedupConfig, DedupSession
        from repro.core.dist_lsh import DistLSHConfig
        from repro.core import shingle
        from repro.data import make_i2b2_like, inject_near_duplicates
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        base = dict(edge_capacity=4096, edge_threshold=0.88,
                    bucket_slack=16.0)
        for stage2, n_steps, groups in [("host", 1, 5), ("host", 3, 5),
                                        ("device", 2, 1)]:
            idx_chunks = np.array_split(np.arange(len(notes)), n_steps)
            snaps = {}
            for byte in (False, True):
                dcfg = DistLSHConfig(**base, stage2=stage2,
                                     band_groups=groups,
                                     fused_ingest=not byte,
                                     byte_ingest=byte)
                cfg = DedupConfig(edge_threshold=0.88,
                                  exact_verification=False,
                                  byte_ingest=byte)
                sess = DedupSession(cfg, backend="sharded",
                                    dist_config=dcfg)
                if byte:
                    chunks = [[notes[i] for i in idx]
                              for idx in idx_chunks]
                    stream = sess.ingest_stream(chunks)
                else:
                    chunks = [[shingle.tokenize(notes[i], do_stem=False)
                               for i in idx] for idx in idx_chunks]
                    stream = sess.ingest_stream(chunks, tokenized=True)
                for snap in stream:
                    pass
                assert snap.overflow == 0 and snap.row_overflow == 0
                snaps[byte] = snap
            a, b = snaps[False], snaps[True]
            np.testing.assert_array_equal(a.labels, b.labels)
            pa = {(x, y): s for x, y, s in a.pairs}
            pb = {(x, y): s for x, y, s in b.pairs}
            assert pa and pa == pb, (stage2, n_steps)
            if stage2 == "device":
                assert b.host_rescored == 0, b.host_rescored
        print("byte sharded parity ok")
    """, n_devices=8)


@pytest.mark.slow
def test_session_eviction_multidevice_keeps_parity_and_device_scoring():
    """Bounded retention on the 8-device sharded backend.

    A tight LRU window evicts retained rows between steps (and between
    band-group merges, via the feed hook); clusters and per-edge sims
    must stay identical to the append-only session, and with
    stage2="device" + the sig-row exchange the host re-score path must
    stay pinned at ZERO on the no-overflow path — eviction never evicts
    a row the device-scoring merge still needs.
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DedupConfig, DedupSession, RetentionPolicy
        from repro.core.dist_lsh import DistLSHConfig, docs_mesh
        from repro.data import make_i2b2_like, inject_near_duplicates
        notes = make_i2b2_like(56, seed=0)
        notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                          frac_high=0.005, seed=1)
        # Interleave so duplicate pairs complete in EARLY chunks —
        # their deposed roots age out of the LRU window and evict.
        order = np.random.RandomState(2).permutation(len(notes))
        notes = [notes[i] for i in order]
        cfg = DedupConfig(edge_threshold=0.88, exact_verification=False)
        base = dict(edge_capacity=4096, edge_threshold=0.88,
                    bucket_slack=16.0, band_groups=2)
        # Two equal-size chunks: one compiled step shape, four feeds.
        chunks = [[notes[i] for i in idx] for idx in
                  np.array_split(np.arange(len(notes)), 2)]
        for stage2 in ("host", "device"):
            dcfg = DistLSHConfig(**base, stage2=stage2)
            plain = DedupSession(cfg, backend="sharded",
                                 dist_config=dcfg)
            for c in chunks:
                ref = plain.ingest(c)
            sess = DedupSession(cfg, backend="sharded",
                                dist_config=dcfg,
                                retention=RetentionPolicy(lru_window=8))
            for c in chunks:
                snap = sess.ingest(c)
            assert snap.overflow == 0 and snap.row_overflow == 0
            assert snap.evicted > 0, "eviction never ran"
            np.testing.assert_array_equal(snap.labels, ref.labels)
            assert snap.pairs == ref.pairs
            if stage2 == "device":
                assert snap.device_scored > 0
                assert snap.host_rescored == 0, snap.host_rescored
        print("session eviction multidevice ok")
    """, n_devices=8)


@pytest.mark.slow
def test_dist_lsh_overflow_retry_through_engine():
    """Device buffer overflow falls back through the same engine.

    With a tiny edge buffer the device step drops prescreened edges
    (counted, never silent); cluster_step_output must detect the
    overflow and recover the full clustering by re-deriving candidates
    on the host from the step's own signatures.
    """
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.dist_lsh import (DistLSHConfig, cluster_step_output,
                                         docs_mesh, make_dedup_step)
        from repro.core import shingle, minhash
        rng = np.random.RandomState(1)
        vocab = [f"t{i}" for i in range(300)]
        docs = [list(rng.choice(vocab, size=48)) for _ in range(32)]
        for i in range(1, 10):
            docs[i] = docs[0]      # 10-way duplicate group
        packed = shingle.pack_documents(docs)
        cfg = DistLSHConfig(edge_capacity=2, edge_threshold=0.5,
                            bucket_slack=16.0)
        step = make_dedup_step(cfg, docs_mesh())
        out = step(jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
                   jnp.asarray(minhash.default_seeds(cfg.num_hashes)))
        res = cluster_step_output(out, cfg, tree_threshold=0.4,
                                  num_docs=32)
        assert res.overflow > 0 and res.retried
        labels = res.labels()
        assert len({int(labels[i]) for i in range(10)}) == 1, labels[:10]
        # without the fallback the dropped edges fragment the cluster
        res_no = cluster_step_output(out, cfg, tree_threshold=0.4,
                                     num_docs=32, overflow_fallback=False)
        assert not res_no.retried
        assert res_no.stats.unions_done <= res.stats.unions_done
        print("overflow retry ok")
    """, n_devices=8)
