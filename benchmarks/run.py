"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only accuracy,kernels
  PYTHONPATH=src python -m benchmarks.run --smoke --json BENCH_smoke.json
  PYTHONPATH=src python -m benchmarks.run --compare BENCH_smoke.json
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: accuracy,designs,"
                         "clustering,scale,kernels,serving,roofline "
                         "(roofline: the measured ingest roofline, "
                         "staged vs fused and tokens vs bytes)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size CI smoke: sharded-vs-host parity, "
                         "verify throughput, band-group merge overlap; "
                         "writes BENCH_smoke.json at the repo root "
                         "unless --json overrides")
    ap.add_argument("--json", default=None,
                    help="also write emitted rows to this JSON file "
                         "(the BENCH_*.json perf-trajectory artifact)")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="run the smoke set and diff it against a "
                         "committed BENCH_*.json baseline: exits "
                         "nonzero on a >2x slowdown of any comparable "
                         "row, a >2x peak_rss_mb memory regression, "
                         "or any derived drift != 0 / "
                         "same_clusters != 1 field (the bench-smoke "
                         "CI regression gate)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    t0 = time.perf_counter()

    if args.smoke or args.compare:
        import json

        from benchmarks import designs
        from benchmarks.common import ROWS, compare_rows, write_json

        baseline = None
        if args.compare:
            with open(args.compare) as f:
                baseline = json.load(f)

        designs.run_sharded(n_notes=96, n_dups=32)
        designs.run_band_group_overlap(n_notes=96, n_dups=32)
        # PR 10 disk tier: Bloom-first probe throughput + FP rate vs
        # the in-memory dict walk (drift must stay 0).
        designs.run_band_probe(n_notes=96, n_queries=48)
        from benchmarks import kernels, roofline

        # Fused-ingest perf gate: drift must stay 0 (bit parity with
        # the staged chain) and the fused wall must not regress >2x.
        # byte_ingest holds the same contract for the bytes->bands
        # path vs host tokenize + fused; the ingest roofline also
        # emits the measured host->device transfer row.
        kernels.run_fused_ingest()
        kernels.run_byte_ingest()
        roofline.run_ingest_roofline()
        from benchmarks import serving_dedup

        # Online query service gate: p50/p99 latency + QPS rows, with
        # the microbatch==sequential parity canary (same_clusters).
        serving_dedup.run_smoke()
        # The smoke artifact is committed at the repo root so the perf
        # trajectory accumulates in-tree, not only as a CI artifact.
        write_json(args.json or os.path.join(REPO_ROOT,
                                             "BENCH_smoke.json"))
        print(f"\n# benchmarks completed in {time.perf_counter()-t0:.1f}s")
        if baseline is not None:
            failures = compare_rows(baseline, ROWS)
            if failures:
                print(f"# REGRESSION vs {args.compare}:")
                for msg in failures:
                    print(f"#   {msg}")
                sys.exit(1)
            print(f"# no regression vs {args.compare} "
                  f"({len(baseline)} baseline rows)")
        return

    if want("accuracy"):
        from benchmarks import accuracy
        accuracy.run()
        accuracy.run_time_vs_bands()
    if want("designs"):
        from benchmarks import designs
        designs.run()
        designs.run_memory()
        designs.run_band_probe()
        designs.run_sharded()
    if want("clustering"):
        from benchmarks import clustering
        clustering.run()
        clustering.run_verify_throughput()
        clustering.run_engine_end_to_end()
        clustering.run_louvain()
    if want("scale"):
        from benchmarks import scale
        scale.run()
    if want("kernels"):
        from benchmarks import kernels
        kernels.run()
    if want("roofline"):
        from benchmarks import roofline
        roofline.run_ingest_roofline()
    if want("serving"):
        from benchmarks import serving_dedup
        serving_dedup.run()

    if args.json:
        from benchmarks.common import write_json

        write_json(args.json)
    print(f"\n# benchmarks completed in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()
