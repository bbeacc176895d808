"""Batch entry: the paper's deployment, raw-text chunks into one session.

Set-up ingests the retained base in chunks of the window's size (the
same work as one chunk of the base's size, and it warms the one ingest
shape the window uses).  The window calls ``DedupSession.ingest`` on
further chunks of the same mix while time remains; the rate is all notes
of the completed chunks over the time from the window's start to the
return of the last one.  The check covers the window's band values, a
seeded sample of the pairs it scored, and every note's cluster.
"""
from __future__ import annotations

import numpy as np

import compare
from traffic import clinical_notes as cn


def program_bands(view, n_docs: int, num_bands: int) -> np.ndarray:
    """Every note's band values, read back from the view's band maps."""
    out = np.zeros((n_docs, num_bands, 2), dtype=np.uint32)
    for j, buckets in enumerate(view.band_maps):
        for key, docs in buckets.items():
            out[list(docs), j] = key
    return out


def run(ctx) -> dict:
    from repro.core import DedupConfig, DedupSession

    cfg, tr = ctx.config, ctx.workload["traffic"]
    dedup = dict(cfg["dedup"])
    if ctx.control:
        # The control: the program's own signature-estimate verifier in
        # place of exact Jaccard, the cheaper path a change could take.
        dedup["exact_verification"] = False
    size = tr["chunk_notes"]
    rng = ctx.rng("corpus")
    fresh: list[str] = []

    def chunk():
        return cn.corpus_chunk(fresh, size, tr["dup_share"],
                               tr["frac_low"], tr["frac_high"], rng)

    base = [chunk() for _ in range(cfg["corpus_notes"] // size)]
    window_chunks = [chunk() for _ in range(tr["window_chunk_cap"])]
    base_n = size * len(base)

    sess = DedupSession(DedupConfig(**dedup), backend="host")
    snap = None
    with ctx.span("setup_ingest"):
        for ch in base:
            snap = sess.ingest(ch)
    before = snap.stats
    done = 0
    with ctx.window() as w:
        while done < len(window_chunks) and w.elapsed() < ctx.seconds:
            with ctx.span("ingest"):
                snap = sess.ingest(window_chunks[done])
            done += 1
        w.close()
    ctx.read_memory()
    notes = done * size
    after = snap.stats
    ctx.counters.update(
        notes=notes, window_s=w.seconds,
        verify_s=after.verify_seconds - before.verify_seconds,
        pairs_evaluated=after.pairs_evaluated - before.pairs_evaluated,
        pairs_generated=after.pairs_generated - before.pairs_generated)

    texts = [t for ch in base + window_chunks[:done] for t in ch]
    bands = program_bands(sess.view(), snap.n_docs,
                          cfg["dedup"]["num_hashes"]
                          // cfg["dedup"]["rows_per_band"])
    labels = np.asarray(snap.labels)
    pairs = snap.pairs
    del sess, snap
    checks = compare.batch_checks(
        texts, ctx.reference_config(), bands, labels, pairs,
        first_checked=base_n, sample=ctx.workload["check"]["sims_sample"],
        rng=ctx.rng("check"))
    return {
        "end_to_end": {"notes_per_s": notes / w.seconds},
        "attempted": notes, "failed": 0, "checks": checks,
        "log": [f"window: {done} chunks of {size} notes in "
                f"{w.seconds:.3f} s onto {base_n} retained notes"],
    }
