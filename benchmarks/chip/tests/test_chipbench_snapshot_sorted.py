"""``snapshot_sorted_per_note.batch``: the ``sorted`` stat of the
program's ``dedup.snapshot`` spans per window note, on a stub context
and in a traced run of the cell at a size the CPU holds."""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import program_spans  # noqa: E402

NAME = "snapshot_sorted_per_note.batch"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_snapshot_sorted",
        os.path.join(HERE, "metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(kept):
    return SimpleNamespace(trace=object(), counters={"notes": 4},
                           program_spans=program_spans.Spans(kept))


@pytest.mark.parametrize("stats, want", [
    ([{"pairs": 6, "sorted": 6}, {"pairs": 10, "sorted": 4}], 2.5),
    ([{"pairs": 6, "sorted": 6}, {"pairs": 6, "sorted": 0}], 1.5),
    # A program whose snapshots re-sort everything carries no ``sorted``.
    ([{"pairs": 6}, {"pairs": 10}], None),
    ([], None),
])
def test_reader_on_a_stub_context(stats, want):
    kept = [("dedup.snapshot", 10 * i, 10 * i + 5, 1, st)
            for i, st in enumerate(stats)]
    assert _reader()(_ctx(kept)) == want
    assert _reader()(SimpleNamespace(trace=None,
                                     counters={"notes": 4})) is None


def test_traced_cell_sorts_each_verified_pair_once():
    """In a traced run, the pairs the window's snapshots sorted are the
    pairs the window verified: none is sorted twice."""
    import run

    small = {"config": {"corpus_notes": 512},
             "workload": {"traffic": {"chunk_notes": 128,
                                      "window_chunk_cap": 2},
                          "check": {"sims_sample": 1024}}}
    result = run.run(["--workload", "paper_exact.templated_batch",
                      "--seed", "3000000019", "--seconds", "60",
                      "--trace", "1"], overrides=small, allow_cpu=True)
    assert result["correct"]
    got = result["metrics"]
    assert got[NAME]["value"] > 0
    assert got[NAME]["value"] == pytest.approx(
        got["verified_pairs_per_note.batch"]["value"], rel=1e-12)
