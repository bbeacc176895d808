"""Bulk-admission entry: raw-byte chunks into one signature-estimate
session whose retained signatures live in a store on the chip.

Set-up ingests the retained base in chunks of the window's size, which
warms every shape the window uses: the byte ingest, the store write and
the verify program.  The window calls ``DedupSession.ingest`` on
further chunks of the same mix while time remains and chunks are left;
the rate is all notes of the completed chunks over the time from the
window's start to the return of the last one, as in ``batch_ingest``.
The check (``compare_estimate.py``) covers the window's band values, a
seeded sample of the pairs it scored, every witness, and every note's
cluster.  ``--control`` rounds the sims the check sees to float16, the
nearest precision below the float32 the configuration states.
"""
from __future__ import annotations

import numpy as np

import compare_estimate
from entries.batch_ingest import program_bands
from traffic import clinical_notes as cn


def run(ctx) -> dict:
    from repro.core import DedupConfig, DedupSession

    cfg, tr = ctx.config, ctx.workload["traffic"]
    # First, so a program without this deployment's options fails at once.
    dedup = DedupConfig(**cfg["dedup"],
                        sig_store_capacity=cfg["sig_store_capacity"])
    size = tr["chunk_notes"]
    need = cfg["corpus_notes"] // size * size + tr["window_chunk_cap"] * size
    if dedup.sig_store_capacity < need:
        raise ValueError(f"sig_store_capacity {dedup.sig_store_capacity} "
                         f"holds fewer than the {need} notes the cell can "
                         "admit")
    rng = ctx.rng("corpus")
    fresh: list[str] = []

    def chunk():
        return cn.corpus_chunk(fresh, size, tr["dup_share"],
                               tr["frac_low"], tr["frac_high"], rng)

    base = [chunk() for _ in range(cfg["corpus_notes"] // size)]
    window_chunks = [chunk() for _ in range(tr["window_chunk_cap"])]
    base_n = size * len(base)

    sess = DedupSession(dedup, backend="host")
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
    ended = ("at the chunk cap" if done == len(window_chunks)
             else f"at {ctx.seconds:g} s")

    texts = [t for ch in base + window_chunks[:done] for t in ch]
    bands = program_bands(sess.view(), snap.n_docs,
                          cfg["dedup"]["num_hashes"]
                          // cfg["dedup"]["rows_per_band"])
    labels = np.asarray(snap.labels)
    ab, sims = snap.pairs.ab, np.asarray(snap.pairs.sim, dtype=np.float32)
    if ctx.control:
        sims = sims.astype(np.float16).astype(np.float32)
    del sess, snap
    checks = compare_estimate.estimate_checks(
        texts, ctx.reference_config(), bands, labels, ab, sims,
        first_checked=base_n, sample=ctx.workload["check"]["sims_sample"],
        rng=ctx.rng("check"))
    return {
        "end_to_end": {"notes_per_s": notes / w.seconds},
        "attempted": notes, "failed": 0, "checks": checks,
        "log": [f"window: {done} chunks of {size} notes in "
                f"{w.seconds:.3f} s onto {base_n} retained notes; the "
                f"window ended {ended} ({tr['window_chunk_cap']} chunks)"],
    }
