"""Dedup driver: host, streaming (out-of-core), or sharded execution.

All three modes drive ONE ``core.session.DedupSession`` — the corpus is
split into ``--steps`` chunks and ingested incrementally (the sharded
backend pipelines: the host merge of step t overlaps the device shuffle
of step t+1) — and report cumulative session stats through one shared
helper.

  PYTHONPATH=src python -m repro.launch.dedup --notes 500 --dups 300
  PYTHONPATH=src python -m repro.launch.dedup --backend jnp --batch band
  PYTHONPATH=src python -m repro.launch.dedup --streaming --chunk 128
  PYTHONPATH=src python -m repro.launch.dedup --sharded --devices 8
  PYTHONPATH=src python -m repro.launch.dedup --sharded --steps 4
  PYTHONPATH=src python -m repro.launch.dedup --estimate --query 8
"""
from __future__ import annotations

import argparse
import os
import time


def report_session(mode: str, snap, seconds: float, extra: str = ""):
    """The one cumulative report every execution mode prints.

    ``snap`` is a ``core.session.ClusterSnapshot``; the line carries the
    session-level counters (docs ingested, duplicate clusters,
    duplicates, verify throughput) so the three modes are comparable at
    a glance.
    """
    retain = ""
    if snap.evicted or snap.refine_merges or snap.filter_only_hits:
        retain = (f", {snap.retained_rows} rows retained "
                  f"({snap.evicted} evicted, "
                  f"{snap.filter_only_hits} filter-only hits, "
                  f"{snap.refine_merges} refine merges)")
    print(f"{mode}: {snap.n_docs} docs ingested, "
          f"{snap.num_clusters} clusters, "
          f"{snap.num_duplicates} duplicates, "
          f"{snap.stats.pairs_evaluated} pairs verified "
          f"({snap.stats.pairs_excluded} excluded) in "
          f"{snap.stats.verify_batches} batches "
          f"({snap.stats.verify_pairs_per_second:.0f} pairs/s)"
          f"{extra}{retain}, {seconds:.2f}s total")


def run_query_demo(sess, notes, n: int):
    """Read-path demo: re-query ``n`` ingested notes + one novel note.

    Stands up a ``DedupQueryService`` over the warm session and prints
    one summary line.  Queries never mutate the session — the snapshot
    the caller just reported stays valid.  Modes whose session cannot
    publish a ``SessionView`` (streaming: no cross-step band index;
    stage2=device: external verifier callback) are reported and
    skipped rather than failed.
    """
    from repro.serving.dedup_service import DedupQueryService

    try:
        view = sess.view()
    except ValueError as e:
        print(f"query demo skipped: {e}")
        return
    svc = DedupQueryService(sess)
    n = min(n, len(notes))
    novel = "entirely unrelated query text " * 12
    t0 = time.perf_counter()
    results = svc.query(list(notes[:n]) + [novel])
    dt = time.perf_counter() - t0
    hits = sum(r.is_duplicate for r in results[:n])
    best = max((r.best_sim for r in results[:n]), default=0.0)
    print(f"query[view v{view.version}]: {hits}/{n} re-queried notes "
          f"matched their clusters (best sim {best:.2f}), novel note "
          f"{'came back novel' if results[-1].novel else 'MATCHED (!)'}"
          f", {n + 1} queries in {dt * 1e3:.1f} ms")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--notes", type=int, default=500)
    ap.add_argument("--dups", type=int, default=300)
    ap.add_argument("--edge-threshold", type=float, default=0.75)
    ap.add_argument("--tree-threshold", type=float, default=0.40)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--fused-ingest", action="store_true",
                    help="one-pass device ingest: shingle -> minhash -> "
                         "band fold in a single fused Pallas kernel "
                         "(bit-identical to the staged path)")
    ap.add_argument("--byte-ingest", action="store_true",
                    help="zero-copy device ingest: raw UTF-8 bytes are "
                         "the only host->device transfer; tokenize + "
                         "shingle + minhash + band fold all run on "
                         "device (no-stem tokenization; implies "
                         "--estimate, since no host token lists exist)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "numpy", "jnp", "pallas"),
                    help="estimate-mode verification backend")
    ap.add_argument("--batch", default="run", choices=("run", "band"),
                    help="engine batch granularity (band = max throughput)")
    ap.add_argument("--estimate", action="store_true",
                    help="signature-estimate verification (vs exact)")
    ap.add_argument("--streaming", action="store_true",
                    help="two-phase out-of-core mode over a band store")
    ap.add_argument("--chunk", type=int, default=128,
                    help="streaming ingest chunk size")
    ap.add_argument("--store", default=None,
                    choices=("memory", "sqlite"),
                    help="band-store tier: memory (in-RAM index / "
                         "Design-2 blob store) or sqlite (disk-resident "
                         "band + signature rows behind Bloom-first "
                         "lookups; identical clusters either way). "
                         "Default: $REPRO_STORE_BACKEND or memory")
    ap.add_argument("--store-path", default=":memory:",
                    help="sqlite database path for the store tier "
                         "(default :memory:)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the shard_map dedup step")
    ap.add_argument("--devices", type=int, default=0,
                    help="sharded mode: run on the first N devices "
                         "(with JAX_PLATFORMS=cpu, N forced host devices)")
    ap.add_argument("--band-groups", type=int, default=1,
                    help="stream the sharded step's verified-edge "
                         "buffers per band-group (G bounded buffers of "
                         "b/G bands; host merge overlaps device shuffle)")
    ap.add_argument("--stage2", default="host", choices=("host", "device"),
                    help="full-signature verify placement: host merge "
                         "or TPU-resident (fused sigjaccard kernel "
                         "under shard_map; cross-shard edges scored "
                         "via the exchanged row buffers, host "
                         "re-scores only on row-buffer overflow)")
    ap.add_argument("--steps", type=int, default=1,
                    help="split the corpus into N chunks and ingest "
                         "them incrementally through one DedupSession "
                         "(sharded mode pipelines: merge of step t "
                         "overlaps the shuffle of step t+1)")
    ap.add_argument("--retain-budget", default="none",
                    choices=("none", "small", "medium", "unlimited"),
                    help="retained-state eviction policy: evict "
                         "signature/token rows down to cluster "
                         "representatives + an LRU window and compact "
                         "old band-index keys into per-band Bloom "
                         "filters (none = PR 4 append-only retention)")
    ap.add_argument("--refine-every", type=int, default=0,
                    help="auto-run the incremental second clustering "
                         "round (DedupSession.refine) every K ingest "
                         "steps (0 = off)")
    ap.add_argument("--query", type=int, default=0, metavar="N",
                    help="after ingest, stand up a DedupQueryService "
                         "over the warm session and re-query N ingested "
                         "notes plus one novel note (read path demo; "
                         "host/sharded modes only — streaming has no "
                         "band index to publish a view over)")
    args = ap.parse_args(argv)

    if args.sharded and args.devices and \
            os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        # The flag only creates CPU host devices; it must precede the
        # first jax import.  Accelerators are taken as they are.
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={args.devices}"]))

    import numpy as np
    import jax
    from repro.core import DedupConfig, DedupSession, RetentionPolicy
    from repro.data import inject_near_duplicates, make_i2b2_like
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    retention = None
    if args.retain_budget != "none" or args.refine_every:
        # "none" + --refine-every keeps rows append-only (no eviction)
        # while still tracking roots for the auto-refine cadence.
        retention = RetentionPolicy.preset(
            args.retain_budget, refine_every=args.refine_every)

    notes = make_i2b2_like(args.notes)
    notes, prov = inject_near_duplicates(notes, args.dups)
    print(f"corpus: {len(notes)} notes ({args.dups} injected near-dups), "
          f"{args.steps} ingest step(s)")

    bounds = np.linspace(0, len(notes), max(1, args.steps) + 1).astype(int)
    chunks = [notes[a:b] for a, b in zip(bounds, bounds[1:])]

    cfg = DedupConfig(
        edge_threshold=args.edge_threshold,
        tree_threshold=args.tree_threshold,
        use_pallas=args.use_pallas,
        fused_ingest=args.fused_ingest,
        byte_ingest=args.byte_ingest,
        exact_verification=not (args.estimate or args.byte_ingest),
        verify_backend=args.backend,
        verify_batch=args.batch,
        # None falls back to the field default ($REPRO_STORE_BACKEND).
        **({"store": args.store} if args.store else {}))

    if args.sharded:
        from repro.core import DistLSHConfig
        from repro.core.dist_lsh import docs_mesh

        devices = jax.devices()
        if args.devices:
            if len(devices) < args.devices:
                raise SystemExit(
                    f"--devices {args.devices}: only {len(devices)} "
                    f"{devices[0].platform} device(s) present")
            devices = devices[:args.devices]
        ndev = len(devices)
        dcfg = DistLSHConfig(edge_threshold=args.edge_threshold,
                             edge_capacity=8192,
                             band_groups=args.band_groups,
                             stage2=args.stage2,
                             fused_ingest=args.fused_ingest,
                             byte_ingest=args.byte_ingest)
        from dataclasses import replace

        # Sharded verification is estimate-shaped by construction; the
        # session's verifier is the same full-signature estimator the
        # host path uses (or the device-score registry for stage2
        # device).
        sess = DedupSession(replace(cfg, exact_verification=False),
                            backend="sharded", dist_config=dcfg,
                            mesh=docs_mesh(devices),
                            store_path=args.store_path,
                            retention=retention)
        t0 = time.perf_counter()
        for snap in sess.ingest_stream(chunks):
            pass
        dt = time.perf_counter() - t0
        extra = (f", {snap.overflow} overflow"
                 f"{' (host fallback ran)' if snap.retried else ''}")
        if args.stage2 == "device":
            extra += (f", stage2=device {snap.device_scored} "
                      f"device-scored / {snap.host_rescored} "
                      f"host-rescored / {snap.row_overflow} row-overflow")
        report_session(
            f"sharded[{ndev} devices x {dcfg.band_groups} band-group(s) "
            f"x {args.steps} step(s)]", snap, dt, extra)
        if args.query:
            run_query_demo(sess, notes, args.query)
        return

    if args.streaming:
        from repro.core.shingle import tokenize
        from repro.core.verify import ExactJaccardVerifier

        verifier = None
        if cfg.byte_ingest:
            # Byte configs stream raw texts — tokenization happens on
            # device, so there is nothing to pre-tokenize (and no token
            # lists for an exact verifier; config validation enforces
            # estimate mode).
            stream_chunks = (notes[a:b]
                             for a, b in zip(bounds, bounds[1:]))
            tokenized = False
        else:
            # Tokenize once; the chunks are ingested pre-tokenized so
            # the exact verifier (built over the same token lists — the
            # streaming backend's native verifier is the signature
            # estimate, so exact_verification is honoured explicitly)
            # does not pay a second tokenize pass.
            toks = [tokenize(t) for t in notes]
            if cfg.exact_verification:
                verifier = ExactJaccardVerifier.from_token_lists(
                    toks, cfg.ngram)
            stream_chunks = (toks[a:b]
                             for a, b in zip(bounds, bounds[1:]))
            tokenized = True
        sess = DedupSession(cfg, backend="streaming",
                            chunk_docs=args.chunk, verifier=verifier,
                            store_path=args.store_path,
                            retention=retention)
        t0 = time.perf_counter()
        # Pre-tokenized chunks stream with the tokenized flag threaded
        # through, so nothing downstream re-tokenizes or re-stores them.
        for snap in sess.ingest_stream(stream_chunks,
                                       tokenized=tokenized):
            pass
        dt = time.perf_counter() - t0
        report_session(f"streaming[{args.steps} step(s)]", snap, dt)
        if args.query:
            run_query_demo(sess, notes, args.query)
        return

    sess = DedupSession(cfg, backend="host",
                        store_path=args.store_path, retention=retention)
    t0 = time.perf_counter()
    for chunk in chunks:
        snap = sess.ingest(chunk)
    dt = time.perf_counter() - t0
    report_session(f"host[{args.steps} step(s)]", snap, dt)
    if args.query:
        run_query_demo(sess, notes, args.query)


if __name__ == "__main__":
    main()
