"""A whole batch-cell run on the CPU at a small size, past the harness's
look for a chip: sound, it reads correct; with the timed path broken
underneath, or with the control in the program's place, it does not."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import run  # noqa: E402

CELL = "paper_exact.templated_batch"
# The cell's own traffic (duplicates with 0-20% of words changed) at a
# size interpret-mode kernels run in seconds.  On this seed a window
# note is an edge of a note already in a cluster of two, so a merge
# that leaves it out has something to leave out.
BASE = 1024
SEED = "24"
SMALL = {"config": {"corpus_notes": BASE},
         "workload": {"traffic": {"chunk_notes": 256, "window_chunk_cap": 2},
                      "check": {"sims_sample": 4096}}}


def _run(*extra):
    return run.run(["--workload", CELL, "--seed", SEED,
                    "--seconds", "60", "--trace", "0", *extra],
                   overrides=SMALL, allow_cpu=True)


def _break(monkeypatch, fault):
    from repro.core import DedupSession, engine, unionfind, verify
    from repro.kernels import ops

    if fault == "unchanged":
        monkeypatch.setattr(engine.ClusterAccumulator, "feed",
                            lambda self, source, verifier=None:
                            engine.ClusterStats())
    elif fault == "half_left_out":
        ingest = DedupSession.ingest
        monkeypatch.setattr(DedupSession, "ingest", lambda self, texts:
                            ingest(self, list(texts)[: len(texts) // 2]))
    elif fault == "band_altered":
        fused = ops.fused_ingest

        def altered(*a, **k):
            sig, bands, rest = fused(*a, **k)
            return sig, bands.at[0, 0, 0].set(bands[0, 0, 0] ^ 1), rest

        monkeypatch.setattr(ops, "fused_ingest", altered)
    elif fault == "sim_altered":
        batch = verify.ExactJaccardVerifier._verify_batch

        def altered(self, pairs):
            out = np.array(batch(self, pairs), dtype=np.float32)
            out[0] = np.nextafter(out[0], np.float32(2))
            return out

        monkeypatch.setattr(verify.ExactJaccardVerifier, "_verify_batch",
                            altered)
    elif fault == "left_out_of_cluster":
        # A merge that stops adding window notes to clusters the
        # retained notes already formed.
        union = unionfind.ThresholdUnionFind.union
        fired = []

        def refusing(self, x, y, sim):
            rx, ry = self.find(x), self.find(y)
            old, new = min(rx, ry), max(rx, ry)
            if old != new and old < BASE <= new and sum(
                    self.find(i) == old for i in range(BASE)) > 1:
                fired.append((x, y))
                self.n_rejected += 1
                return False
            return union(self, x, y, sim)

        monkeypatch.setattr(unionfind.ThresholdUnionFind, "union", refusing)
        return fired


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True
    assert set(res["metrics"]) == {"notes_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out",
                                   "band_altered", "sim_altered",
                                   "left_out_of_cluster"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fired = _break(monkeypatch, fault)
    res = _run()
    if fired is not None:
        assert fired
    assert res["correct"] is False, res["checks"]


def test_control_is_not_correct():
    res = _run("--control")
    assert res["correct"] is False
    assert res["checks"]["sims_wrong"]["value"] > 0
