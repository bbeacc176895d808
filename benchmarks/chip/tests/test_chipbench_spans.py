"""The program's own spans, recorded by the profiler on the CPU and kept
by the program in its process, reduced by ``program_spans.py``: two
chunks of the cell's traffic through ``DedupSession`` at the cell's
configuration, with the verifier called once per band (the cell's) and
once per band run."""
from __future__ import annotations

import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import program_spans  # noqa: E402
from traffic import clinical_notes as cn  # noqa: E402

CHUNK = 256
# Each span of one chunk and the span it is opened inside.
PARENT = {"dedup.tokenize": "dedup.ingest", "dedup.pack": "dedup.ingest",
          "dedup.device_ingest": "dedup.ingest",
          "dedup.retain": "dedup.ingest", "dedup.merge": "dedup.ingest",
          "dedup.band_index": "dedup.ingest",
          "dedup.snapshot": "dedup.ingest"}


@pytest.fixture(scope="module", params=["band", "run"])
def traced(request, tmp_path_factory):
    """Two chunks ingested under the profiler: (spans in the trace,
    spans the program kept, chunks, snaps)."""
    import jax

    from repro.core import DedupConfig, DedupSession, spans

    with open(os.path.join(HERE, "configs", "paper_exact.json")) as f:
        cfg = json.load(f)["dedup"]
    cfg["verify_batch"] = request.param
    rng = cn.rng_for(24, "corpus")
    pool: list[str] = []
    chunks = [cn.corpus_chunk(pool, CHUNK, 0.125, 0.0, 0.2, rng)
              for _ in range(2)]
    sess = DedupSession(DedupConfig(**cfg), backend="host")
    spans.take()
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        snaps = [sess.ingest(ch) for ch in chunks]
    finally:
        jax.profiler.stop_trace()
    # Nothing is kept outside a profiler session.
    sess.ingest(chunks[0][:8])
    kept = spans.take()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    return program_spans.from_profile(data), kept, chunks, snaps


def _inside(inner, outer) -> bool:
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_every_span_present_and_nested(traced):
    t, _, _, _ = traced
    spans = t.spans
    roots = [s for s in spans if s[0] == "dedup.ingest"]
    assert len(roots) == 2
    for name, parent in PARENT.items():
        mine = [s for s in spans if s[0] == name]
        assert mine, name
        outers = [s for s in spans if s[0] == parent]
        for s in mine:
            assert any(_inside(s, o) for o in outers), (name, s)
    # A chunk opens one span per stage and two merges (within the chunk
    # and against the index), whatever the verify batching: no span per
    # note, pair, band run or verify call.
    assert len(spans) <= 2 * 9


def test_counts_on_the_spans(traced):
    from repro.core import shingle

    t, _, chunks, snaps = traced
    ingest = [s for s in t.spans if s[0] == "dedup.device_ingest"]
    assert len(ingest) == 2
    for sp, ch in zip(ingest, chunks):
        toks = [shingle.tokenize(x) for x in ch]
        packed = shingle.pack_documents(
            toks, shingle.pow2_bucket(max(len(x) for x in toks)))
        assert sp[4]["h2d_bytes"] == (packed.tokens.nbytes
                                      + packed.lengths.nbytes)
    snap_spans = [s for s in t.spans if s[0] == "dedup.snapshot"]
    assert [s[4]["pairs"] for s in snap_spans] == [
        len(s.pairs) for s in snaps]


def test_merge_spans_carry_the_verify_clock(traced):
    t, _, _, snaps = traced
    merges = [s for s in t.spans if s[0] == "dedup.merge"]
    verify_s = sum(s[4]["verify_ns"] for s in merges) * 1e-9
    assert snaps[-1].stats.verify_seconds > 0
    assert verify_s == pytest.approx(snaps[-1].stats.verify_seconds,
                                     abs=1e-9 * len(merges))
    # The verify time lies inside the merge spans that carry it.
    assert verify_s <= sum(e - s for _, s, e, _, _ in merges) * 1e-9


def test_kept_spans_match_the_trace(traced):
    """The spans the program kept in its process are those of the trace:
    same names, order, stats and nesting, durations within 5%."""
    t, kept, _, _ = traced
    key = lambda s: s[1]  # noqa: E731
    in_trace, mine = sorted(t.spans, key=key), sorted(kept, key=key)
    assert [s[0] for s in mine] == [s[0] for s in in_trace]
    assert [s[4] for s in mine] == [s[4] for s in in_trace]
    for name, parent in PARENT.items():
        for s in (x for x in mine if x[0] == name):
            assert any(_inside(s, o) for o in mine if o[0] == parent)
    ingest_trace = sum(e - s for n, s, e, _, _ in in_trace
                       if n == "dedup.ingest")
    ingest_kept = sum(e - s for n, s, e, _, _ in mine if n == "dedup.ingest")
    assert ingest_kept == pytest.approx(ingest_trace, rel=0.05)
    kept_sp = program_spans.Spans(kept)
    for name in {s[0] for s in kept}:
        assert kept_sp.self_s(name) == pytest.approx(
            t.self_s(name), rel=0.05, abs=2e-4), name


def test_traced_cell_reports_every_span_metric():
    """A ``--trace 1`` run of the cell, at a size the CPU holds, reads
    each per-layer metric that the program's spans feed."""
    import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if m["source"] == "program_span"
                 or m["name"] in ("h2d_bytes_per_note.batch",
                                  "snapshot_pairs_per_note.batch")]
    small = {"config": {"corpus_notes": 512},
             "workload": {"traffic": {"chunk_notes": CHUNK,
                                      "window_chunk_cap": 2},
                          "check": {"sims_sample": 1024}}}
    result = run.run(["--workload", "paper_exact.templated_batch",
                      "--seed", "3000000017", "--seconds", "60",
                      "--trace", "1"], overrides=small, allow_cpu=True)
    assert result["correct"]
    assert set(names) <= set(result["metrics"])
    assert result["metrics"]["h2d_bytes_per_note.batch"]["value"] == 1028.0
    shares = sum(result["metrics"][n]["value"] for n in names
                 if n.endswith("_share.batch"))
    assert 0 < shares <= 100
