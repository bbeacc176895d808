"""Paper §12 (production run) — scalability extrapolation.

Measures signature+banding throughput at growing corpus sizes, fits the
linear rate, and extrapolates to the paper's 10M-note corpus; reports
cluster statistics analogous to §12 on the largest size that fits CI.
"""
from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, section
from repro.core import lsh, minhash, shingle
from repro.core.pipeline import DedupConfig, DedupPipeline
from repro.data import inject_near_duplicates, make_i2b2_like


def run():
    section("§12: throughput scaling + 10M-note extrapolation")
    rates = []
    for n in (250, 500, 1000, 2000):
        notes = make_i2b2_like(n, seed=4)
        token_lists = [shingle.tokenize(t) for t in notes]
        packed = shingle.pack_documents(token_lists)
        t0 = time.perf_counter()
        ng, valid = shingle.ngram_hashes(
            jnp.asarray(packed.tokens), jnp.asarray(packed.lengths), n=8)
        sig = minhash.signatures(
            ng, valid, jnp.asarray(minhash.default_seeds(100)))
        _bands = np.asarray(lsh.band_values(sig, 2))
        dt = time.perf_counter() - t0
        rates.append(n / dt)
        emit(f"scale_signatures_n{n}", dt * 1e6 / n,
             f"notes_per_s={n/dt:.0f}")
    rate = np.median(rates)
    hours_10m = 10e6 / rate / 3600
    emit("scale_extrapolate_10M_hours", 0.0,
         f"{hours_10m:.2f}h_single_CPU(paper:75h_signatures)")

    section("§12-style cluster stats (4k-note corpus w/ heavy duplication)")
    notes = make_i2b2_like(1500, seed=5)
    notes, _ = inject_near_duplicates(notes, 1500, frac_low=0.0,
                                      frac_high=0.2, seed=6)
    t0 = time.perf_counter()
    res = DedupPipeline(DedupConfig(edge_threshold=0.75)).run(notes)
    dt = time.perf_counter() - t0
    sizes = {}
    for l in res.labels:
        sizes[int(l)] = sizes.get(int(l), 0) + 1
    clusters = [v for v in sizes.values() if v >= 2]
    exact = sum(1 for a, b, s in res.pairs if s > 0.999)
    emit("scale_cluster_run", dt * 1e6,
         f"notes={len(notes)};clusters={len(clusters)};"
         f"largest={max(clusters) if clusters else 0};"
         f"pairs={len(res.pairs)};exact_pairs={exact};"
         f"removed={res.num_duplicates_removed}")


if __name__ == "__main__":
    run()
