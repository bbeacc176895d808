#!/usr/bin/env python3
"""Run the dedup system's main path on a TPU and check it bit for bit.

    python chip_smoke.py [--seed 0]      # one chip: the four phases below
    python chip_smoke.py --chips 4       # the sharded session only

Everything runs in this one process: a chip belongs to one process, so
nothing here starts a child that touches JAX.  The corpus is generated
from ``--seed`` with ``repro.data.make_i2b2_like`` plus
``inject_near_duplicates`` (the paper's §10 protocol).  The script exits
nonzero and prints no result when JAX finds no TPU, when the repo's
``src/`` is not next to this file, or when any check fails.  Its last
line of output is the JSON result, printed only when every check held.

One chip, through ``DedupSession`` / ``DedupQueryService``:

1. paper configuration — the ``DedupConfig`` defaults (n=8, M=100, r=2,
   thresholds 0.75 / 0.40, exact verification) with ``fused_ingest``, in
   8,192-note chunks.  Each chunk's signatures and bands equal the numpy
   oracle; the final labels and pair sims equal those of a session whose
   arrays come from the oracle.
2. raw bytes — the same corpus with ``byte_ingest``, estimate mode and
   the pallas verifier.  Signatures and bands equal the oracle over
   ``tokenize(do_stem=False)``; every pair sim equals the numpy
   verifier's.
3. query service — microbatches of known notes, near-duplicates and
   novel notes against the warm byte session; the pallas verifier's
   results equal the numpy verifier's.
4. ingest-only sweep — 262,144 notes through ``compute_arrays`` and
   ``compute_arrays_bytes`` in 8,192-note chunks; the first and last
   chunk equal the oracle.

``--chips 4`` runs the sharded session (``dist_lsh`` over a 4-device
mesh) with 32,768-note chunks, for both ingest kernels and both stage-2
placements, against the one-device host session: identical labels and
signatures, every pair sim equal to the numpy verifier's, and no host
re-score on the device-scored path.

Times printed are smoke readings from one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import replace

CHUNK = 8192          # notes per ingest chunk (a 256-token pow2 width)
NOTES = 16_384        # session corpus (see PAPER_NOTES)
SWEEP_NOTES = 262_144
SHARD_CHUNK = 32_768  # notes per sharded step: 8,192 per chip
PAPER_NOTES = 10_000_000  # the paper's corpus: "more than 10M notes"
# One verify call per band, not per bucket run: on this template-heavy
# corpus "run" makes ~1.7M tiny verify calls for 16,384 notes, each a
# device dispatch on the pallas backend.  Same sims either way.
VERIFY_BATCH = "band"
# The sharded step verifies star edges (run member -> run head) that
# survive a 32-row prefix prescreen, so it finds the host path's
# clusters only where the similarity margin is clean: near-exact
# duplicates against an edge threshold above the template notes'
# similarity (tests/test_distributed.py uses the same 0.88).  Buffers
# are sized so that no edge or exchanged row overflows at 8,192 notes
# per chip on this corpus.
SHARD_EDGE_THRESHOLD = 0.88
SHARD_EDGE_CAPACITY = 65_536
SHARD_ROW_CAPACITY = 8192


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Sums the backend compile seconds JAX reports."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.count += 1


class Phase:
    """Logs a phase's wall time and the compile time inside it."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.n0 = self.clock.seconds, self.clock.count
        log(f"== {self.name}")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"== {self.name}: {time.perf_counter() - self.t0:.2f} s "
                f"wall, {self.clock.count - self.n0} compiles "
                f"({self.clock.seconds - self.c0:.2f} s)")
        return False


def corpus(n: int, seed: int, frac_high: float = 0.2) -> list[str]:
    """``n`` clinical-style notes, an eighth of them near-duplicates with
    up to ``frac_high`` of their words changed."""
    from repro.data import inject_near_duplicates, make_i2b2_like

    n_dups = n // 8
    notes, _ = inject_near_duplicates(
        make_i2b2_like(n - n_dups, seed=seed), n_dups,
        frac_high=frac_high, seed=seed + 1)
    return notes


def partition(labels) -> set:
    """Clusters (size >= 2) as a set of frozensets of doc ids."""
    groups: dict[int, list[int]] = {}
    for i, r in enumerate(labels):
        groups.setdefault(int(r), []).append(i)
    return {frozenset(g) for g in groups.values() if len(g) >= 2}


def oracle_arrays(token_lists, seeds, cfg):
    """(signatures, bands) from the numpy oracles."""
    from repro.core import lsh, minhash, shingle

    packed = shingle.pack_documents(token_lists)
    ng, valid = shingle.ngram_hashes_np(packed.tokens, packed.lengths,
                                        n=cfg.ngram)
    sig = minhash.signatures_np(ng, valid, seeds)
    return sig, lsh.band_values_np(sig, cfg.rows_per_band)


def pipelines():
    """Pipeline subclasses a host session can be given: one records the
    arrays the session computes, one computes them with the oracle."""
    from repro.core import DedupPipeline

    class Recorder(DedupPipeline):
        def __init__(self, config):
            super().__init__(config)
            self.arrays = []

        def compute_arrays(self, token_lists, pad_len=None):
            out = super().compute_arrays(token_lists, pad_len)
            self.arrays.append((pad_len,) + out)
            return out

        def compute_arrays_bytes(self, docs, pad_len=None):
            out = super().compute_arrays_bytes(docs, pad_len)
            self.arrays.append((pad_len,) + out)
            return out

    class Oracle(DedupPipeline):
        def compute_arrays(self, token_lists, pad_len=None):
            return oracle_arrays(token_lists, self.seeds, self.config)

    return Recorder, Oracle


def host_session(cfg, pipeline_cls):
    """A host ``DedupSession`` whose backend runs ``pipeline_cls``."""
    from repro.core import DedupSession

    sess = DedupSession(cfg, backend="host")
    pipe = pipeline_cls(cfg)
    pipe.seeds = sess.seeds
    sess._impl.pipe = pipe
    return sess, pipe


def pair_arrays(snap):
    """A snapshot's evaluated pairs as ((P, 2) ids, (P,) float32 sims)."""
    import numpy as np

    p = snap.pairs
    ids = np.array([(a, b) for a, b, _ in p], dtype=np.int64).reshape(-1, 2)
    return ids, np.array([s for _, _, s in p], dtype=np.float32)


def check_sims_numpy(snap, signatures, what: str) -> int:
    """Every pair sim of ``snap`` equals the numpy verifier's, bit for bit."""
    import numpy as np
    from repro.core import SignatureVerifier

    ids, sims = pair_arrays(snap)
    want = SignatureVerifier(signatures, backend="numpy")(ids)
    check(np.array_equal(sims, want), f"{what}: pair sims != numpy verifier")
    return len(ids)


# -- one chip ----------------------------------------------------------------

def paper_phase(notes, chunks, clock):
    import numpy as np
    from repro.core import DedupConfig
    from repro.core.shingle import tokenize

    Recorder, Oracle = pipelines()
    cfg = DedupConfig(fused_ingest=True, verify_batch=VERIFY_BATCH)
    check((cfg.ngram, cfg.num_hashes, cfg.rows_per_band) == (8, 100, 2)
          and (cfg.edge_threshold, cfg.tree_threshold) == (0.75, 0.40)
          and cfg.exact_verification, "DedupConfig defaults moved")
    oracle, toks = [], []
    with Phase("paper configuration: fused_ingest, exact verification",
               clock):
        sess, rec = host_session(cfg, Recorder)
        for i, chunk in enumerate(chunks):
            t0 = time.perf_counter()
            snap = sess.ingest(chunk)
            dt = time.perf_counter() - t0
            pad, sig, bands = rec.arrays[-1]
            toks.append([tokenize(t) for t in chunk])
            o_sig, o_bands = oracle_arrays(toks[-1], sess.seeds, cfg)
            oracle.append((o_sig, o_bands))
            check(np.array_equal(sig, o_sig), f"chunk {i}: signatures")
            check(np.array_equal(bands, o_bands), f"chunk {i}: bands")
            log(f"  chunk {i}: {len(chunk)} notes, token width {pad}, "
                f"ingest {dt:.2f} s; signatures {sig.shape} and bands "
                f"{bands.shape} == numpy oracle")
        ref, _ = host_session(cfg, Oracle)
        for t in toks:
            ref_snap = ref.ingest_tokens(t)
        check(np.array_equal(snap.labels, ref_snap.labels),
              "labels != oracle-array session")
        check(snap.pairs == ref_snap.pairs, "pair sims != oracle session")
        check(snap.num_clusters > 0, "no duplicate clusters found")
        log(f"  {snap.n_docs} notes: {snap.num_clusters} clusters, "
            f"{snap.num_duplicates} duplicates, "
            f"{snap.stats.pairs_evaluated} pairs verified; labels and "
            f"{len(snap.pairs)} pair sims == oracle-array session")
    return oracle, toks


def bytes_phase(chunks, clock):
    import numpy as np
    from repro.core import DedupConfig
    from repro.core.shingle import tokenize

    Recorder, _ = pipelines()
    cfg = DedupConfig(byte_ingest=True, exact_verification=False,
                      verify_backend="pallas", verify_batch=VERIFY_BATCH)
    oracle = []
    with Phase("raw bytes: byte_ingest, estimate mode, pallas verifier",
               clock):
        sess, rec = host_session(cfg, Recorder)
        for i, chunk in enumerate(chunks):
            t0 = time.perf_counter()
            snap = sess.ingest(chunk)
            dt = time.perf_counter() - t0
            pad, sig, bands = rec.arrays[-1]
            o_sig, o_bands = oracle_arrays(
                [tokenize(t, do_stem=False) for t in chunk], sess.seeds,
                cfg)
            oracle.append((o_sig, o_bands))
            check(np.array_equal(sig, o_sig), f"chunk {i}: signatures")
            check(np.array_equal(bands, o_bands), f"chunk {i}: bands")
            log(f"  chunk {i}: {len(chunk)} notes, byte width {pad}, "
                f"ingest {dt:.2f} s; signatures and bands == numpy "
                f"oracle over tokenize(do_stem=False)")
        check(sess.verifier.backend == "pallas", "verifier is not pallas")
        n = check_sims_numpy(
            snap, np.concatenate([o for o, _ in oracle]), "bytes")
        log(f"  {snap.n_docs} notes: {snap.num_clusters} clusters, "
            f"{snap.stats.verify_batches} pallas verify batches; "
            f"{n} pair sims == numpy verifier")
    return sess, oracle


def novel_notes(n: int, rng) -> list[str]:
    """Notes of random lowercase words: no 8-gram in common with the
    corpus."""
    letters = list("abcdefghijklmnopqrstuvwxyz")
    return [" ".join("".join(rng.choice(letters, rng.randint(3, 9)))
                     for _ in range(rng.randint(120, 200)))
            for _ in range(n)]


def query_phase(sess, notes, seed, clock):
    import numpy as np
    from repro.core import DedupQueryService
    from repro.data.corpus import perturb

    rng = np.random.RandomState(seed + 7)
    known = [notes[i] for i in rng.choice(len(notes), 64, replace=False)]
    near = [perturb(notes[i], 0.01, rng)
            for i in rng.choice(len(notes), 64, replace=False)]
    novel = novel_notes(64, rng)
    kind = ["known"] * 64 + ["near"] * 64 + ["novel"] * 64
    order = rng.permutation(len(kind))
    texts = [(known + near + novel)[i] for i in order]
    kinds = [kind[i] for i in order]
    with Phase("query service: pallas vs numpy verifier", clock):
        svc = DedupQueryService(sess, backend="pallas", max_batch=64)
        ref = DedupQueryService(sess, backend="numpy", max_batch=64)
        results = []
        for b in range(0, len(texts), 64):
            batch = texts[b:b + 64]
            t0 = time.perf_counter()
            got = svc.query(batch)
            dt = time.perf_counter() - t0
            check(got == ref.query(batch),
                  f"microbatch {b // 64}: pallas != numpy results")
            results += got
            log(f"  microbatch {b // 64}: {len(batch)} queries in "
                f"{dt * 1e3:.1f} ms, == numpy verifier")
        # The submit/step microbatch route (host no-stem tokens) agrees.
        for t in texts[:64]:
            svc.submit(t)
        done = svc.run_until_drained()
        check([r.result for r in sorted(done, key=lambda r: r.rid)]
              == results[:64], "submit/step results != query results")
        by = {k: [r for r, kk in zip(results, kinds) if kk == k]
              for k in ("known", "near", "novel")}
        check(all(r.is_duplicate and r.best_sim == 1.0
                  for r in by["known"]), "a known note was not matched")
        check(not any(r.is_duplicate for r in by["novel"]),
              "a novel note was matched")
        log(f"  known {sum(r.is_duplicate for r in by['known'])}/64, "
            f"near-duplicates {sum(r.is_duplicate for r in by['near'])}/64,"
            f" novel {sum(r.is_duplicate for r in by['novel'])}/64 flagged"
            f" as duplicates")


def sweep_phase(chunks, toks, tok_oracle, byte_oracle, clock):
    import numpy as np
    from repro.core import DedupConfig, DedupPipeline
    from repro.core.shingle import pow2_bucket

    n_chunks = SWEEP_NOTES // CHUNK
    last = n_chunks - 1
    paths = [
        ("tokens (compute_arrays, fused_ingest)",
         DedupPipeline(DedupConfig(fused_ingest=True)), tok_oracle,
         lambda p, c: p.compute_arrays(
             toks[c], pow2_bucket(max(len(t) for t in toks[c])))),
        ("bytes (compute_arrays_bytes)",
         DedupPipeline(DedupConfig(byte_ingest=True,
                                   exact_verification=False)),
         byte_oracle,
         lambda p, c: p.compute_arrays_bytes(
             chunks[c], pow2_bucket(max(len(t.encode("utf-8"))
                                        for t in chunks[c]) + 1))),
    ]
    with Phase(f"ingest-only sweep: {SWEEP_NOTES} notes in {n_chunks} "
               f"chunks of {CHUNK} (the {len(chunks)} corpus chunks in "
               f"turn)", clock):
        for name, pipe, oracle, run in paths:
            run(pipe, 0)  # warm: compile outside the timed loop
            t0 = time.perf_counter()
            docs = 0
            for k in range(n_chunks):
                c = k % len(chunks)
                sig, bands = run(pipe, c)
                docs += len(sig)
                if k in (0, last):
                    check(np.array_equal(sig, oracle[c][0])
                          and np.array_equal(bands, oracle[c][1]),
                          f"sweep {name} chunk {k} != oracle")
            dt = time.perf_counter() - t0
            log(f"  {name}: {docs} notes, {docs / dt:.0f} docs/s (smoke "
                f"reading, not a benchmark number; host packing "
                f"included); first and last chunk == oracle")


def one_chip(args, clock):
    with Phase("corpus", clock):
        notes = corpus(NOTES, args.seed)
        chunks = [notes[i:i + CHUNK] for i in range(0, len(notes), CHUNK)]
        log(f"  {len(notes)} notes in {len(chunks)} chunks of {CHUNK}: a "
            f"cut of the paper's {PAPER_NOTES:,}+ notes by "
            f"{PAPER_NOTES // len(notes)}x, so that the host merge keeps "
            f"the run within its time limit")
    tok_oracle, toks = paper_phase(notes, chunks, clock)
    sess_b, byte_oracle = bytes_phase(chunks, clock)
    query_phase(sess_b, notes, args.seed, clock)
    sweep_phase(chunks, toks, tok_oracle, byte_oracle, clock)


# -- four chips --------------------------------------------------------------

def four_chips(args, clock):
    import jax
    import numpy as np
    from repro.core import DedupConfig, DedupSession, DistLSHConfig
    from repro.core import docs_mesh
    from repro.core.shingle import tokenize

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    mesh = docs_mesh(devices[:4])
    with Phase("corpus", clock):
        notes = corpus(SHARD_CHUNK, args.seed, frac_high=0.005)
        toks = [tokenize(t, do_stem=False) for t in notes]
        log(f"  {len(notes)} notes in one {SHARD_CHUNK}-note chunk "
            f"({SHARD_CHUNK // 4} per chip), near-duplicates with at "
            f"most 0.5% of words changed")
    cfg = DedupConfig(exact_verification=False, verify_batch=VERIFY_BATCH,
                      edge_threshold=SHARD_EDGE_THRESHOLD)
    with Phase("one-device host session (fused_ingest, no-stem tokens)",
               clock):
        ref = DedupSession(replace(cfg, fused_ingest=True), backend="host")
        ref_snap = ref.ingest_tokens(toks)
        ref_parts = partition(ref_snap.labels)
        log(f"  {ref_snap.num_clusters} clusters, "
            f"{ref_snap.stats.pairs_evaluated} pairs verified")
    # Both ingest kernels see the same no-stem tokens (raw bytes for
    # byte_ingest), so one host session is the reference for all four.
    for ingest in ("fused_ingest", "byte_ingest"):
        for stage2 in ("host", "device"):
            dcfg = DistLSHConfig(
                stage2=stage2, edge_threshold=SHARD_EDGE_THRESHOLD,
                edge_capacity=SHARD_EDGE_CAPACITY,
                sig_row_capacity=SHARD_ROW_CAPACITY, **{ingest: True})
            with Phase(f"sharded session on 4 chips, {ingest}, stage2 "
                       f"{stage2}", clock):
                sess = DedupSession(replace(cfg, **{ingest: True}),
                                    backend="sharded", dist_config=dcfg,
                                    mesh=mesh)
                snap = (sess.ingest(notes) if ingest == "byte_ingest"
                        else sess.ingest_tokens(toks))
                check(np.array_equal(sess.signatures, ref.signatures),
                      "sharded signatures != host session")
                check(partition(snap.labels) == ref_parts,
                      "sharded clusters != host session")
                n = check_sims_numpy(snap, ref.signatures, "sharded")
                check(snap.overflow == 0 and not snap.retried,
                      f"device buffers overflowed ({snap.overflow}): the "
                      f"host fallback did the chip's work")
                if stage2 == "device":
                    check(snap.host_rescored == 0,
                          f"{snap.host_rescored} pairs re-scored on host")
                log(f"  clusters == host session, {n} pair sims == numpy "
                    f"verifier; overflow {snap.overflow}, device-scored "
                    f"{snap.device_scored}, host-rescored "
                    f"{snap.host_rescored}, row overflow "
                    f"{snap.row_overflow}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded session on 4 chips")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    from repro.kernels.common import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    check(resolve_interpret(None) is False,
          "kernels would run in interpret mode")
    log(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}"
        f", jax {jax.__version__}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args, clock)
        else:
            one_chip(args, clock)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"total {time.perf_counter() - t0:.2f} s wall, {clock.count} "
        f"compiles ({clock.seconds:.2f} s); device memory: peak "
        f"{stats.get('peak_bytes_in_use', 'n/a')} of "
        f"{stats.get('bytes_limit', 'n/a')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
