"""The bulk-admission cell on the CPU at a small size, past the
harness's look for a chip: the byte session checked against the
estimate reference reads correct; with a band row, a sim or a note's
cluster broken underneath, or with the sims in float16 (the control),
it does not.  Also the new per-layer readers, on hand-built traces and
on a traced run."""
from __future__ import annotations

import dataclasses
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import kernel_bytes  # noqa: E402
import reference as ref  # noqa: E402
import reference_estimate as refest  # noqa: E402
import run  # noqa: E402

CELL = "admission_bytes.bulk_admit"
BASE = 512
SEED = "3000000019"
# The cell's traffic and store at a size interpret-mode kernels run in
# seconds.
SMALL = {"config": {"corpus_notes": BASE, "sig_store_capacity": 1024},
         "workload": {"traffic": {"chunk_notes": 256, "window_chunk_cap": 2},
                      "check": {"sims_sample": 4096}}}
NEW_METRICS = ("byte_ingest_ms_per_1k_notes.bulk",
               "byte_shingle_hbm_share.bulk",
               "sigjaccard_ms_per_1k_notes.bulk",
               "sigjaccard_hbm_share.bulk",
               "sig_store_h2d_bytes_per_note.bulk")
# The host merge's counters for this cell.
HOST_METRICS = ("verified_pairs_per_note.bulk",
                "candidate_pairs_per_note.bulk")


def _run(*extra, trace="0"):
    return run.run(["--workload", CELL, "--seed", SEED,
                    "--seconds", "60", "--trace", trace, *extra],
                   overrides=SMALL, allow_cpu=True)


def _break(monkeypatch, fault):
    from repro.core import DedupSession, verify
    from repro.kernels import ops

    if fault == "band_row_flipped":
        bytes_to_bands = ops.bytes_to_bands

        def flipped(*a, **k):
            sig, bands, rest = bytes_to_bands(*a, **k)
            return sig, bands.at[0, 0, 0].set(bands[0, 0, 0] ^ 1), rest

        monkeypatch.setattr(ops, "bytes_to_bands", flipped)
    elif fault == "sim_off_by_one_count":
        estimate = verify.device_estimate

        def off(backend, sig_dev, a_idx, b_idx, width):
            out = np.array(estimate(backend, sig_dev, a_idx, b_idx, width))
            m = np.float32(width)
            out[0] = np.float32(np.rint(out[0] * m) + 1) / m
            return out

        monkeypatch.setattr(verify, "device_estimate", off)
    elif fault == "pair_without_collision":
        # The band index also hands the merge a pair of notes that share
        # no band: note 0 against the window's first note.
        from repro.core.session import BandIndex

        match = BandIndex.match_then_insert

        def extra(self, bands, doc_id_base):
            edges = match(self, bands, doc_id_base)
            if doc_id_base == BASE:
                edges = np.concatenate([edges, [[0, BASE]]]).astype(np.int64)
            return edges

        monkeypatch.setattr(BandIndex, "match_then_insert", extra)
    elif fault == "note_out_of_cluster":
        # The last snapshot moves one window note of a two-note cluster
        # out of it.
        snapshot = DedupSession.snapshot
        moved = []

        def moving(self):
            snap = snapshot(self)
            labels = np.array(snap.labels)
            sizes = np.bincount(labels, minlength=len(labels))
            for (a, b), s in zip(snap.pairs.ab, snap.pairs.sim):
                if b >= BASE and s > 0.75 and labels[a] == labels[b] \
                        and sizes[labels[b]] == 2:
                    labels[b] = b
                    moved[:] = [int(b)]
                    break
            return dataclasses.replace(snap, labels=labels)

        monkeypatch.setattr(DedupSession, "snapshot", moving)
        return moved


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"notes_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("fault,check", [
    ("band_row_flipped", "band_rows_wrong"),
    ("sim_off_by_one_count", "sims_wrong"),
    ("note_out_of_cluster", "label_faults"),
    ("pair_without_collision", "label_faults")])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, check):
    moved = _break(monkeypatch, fault)
    res = _run()
    if moved is not None:
        assert moved
    assert res["correct"] is False
    assert res["checks"][check]["value"] > 0, res["checks"]


def test_float16_control_is_not_correct():
    res = _run("--control")
    assert res["correct"] is False
    assert res["checks"]["sims_wrong"]["value"] > 0
    assert res["checks"]["band_rows_wrong"]["value"] == 0


def test_traced_run_reports_the_store_copy():
    res = _run(trace="1")
    assert res["correct"] is True
    # The CPU trace holds no TPU ops, so only the program's counters
    # read: rows come from the device, and only the row offset crosses.
    assert set(res["metrics"]) == {"sig_store_h2d_bytes_per_note.bulk",
                                   *HOST_METRICS}
    got = {m: v["value"] for m, v in res["metrics"].items()}
    assert got["sig_store_h2d_bytes_per_note.bulk"] == pytest.approx(4 / 256)
    assert 0 < got["verified_pairs_per_note.bulk"] \
        <= got["candidate_pairs_per_note.bulk"]


def test_reference_estimate_counts_then_divides():
    texts = ["the patient is a 64 year old male with chest pain today",
             "The patient is a 64 year old male with chest pain TODAY!",
             "the patient is a 71 year old female with a cough today",
             "no known drug allergies"]
    cfg = {"stem": False, "seed_key": 24301, "ngram": 5, "num_hashes": 112,
           "rows_per_band": 8}
    sig, bands = refest.arrays(texts, cfg)
    assert sig.shape == (4, 112) and bands.shape == (4, 14, 2)
    np.testing.assert_array_equal(sig[0], sig[1])  # case and punctuation
    pairs = np.array([[0, 1], [0, 2], [2, 3]])
    est = refest.estimate(sig, pairs)
    count = np.sum(sig[pairs[:, 0]] == sig[pairs[:, 1]], axis=1)
    assert est.dtype == np.float32
    np.testing.assert_array_equal(
        est, count.astype(np.float32) / np.float32(112))
    assert est[0] == 1.0
    cand = refest.candidate_pairs(bands)
    keys = ref.band_keys(bands)
    want = [(a, b) for a in range(4) for b in range(a + 1, 4)
            if np.any(keys[a] == keys[b])]
    assert [tuple(p) for p in cand] == want


# -- the per-layer readers on hand-built traces ---------------------------

SHINGLE = ('%byte_shingle.2 = (u32[2304,8192]{1,0:T(8,128)}, '
           's32[2304,8192]{1,0:T(8,128)}, u32[1,8192]{1,0:T(1,128)}, '
           's32[1,8192]{1,0:T(1,128)}) custom-call(u8[2304,8192]'
           '{1,0:T(32,128)(4,1)} %pad.1, s32[1,8192]{1,0:T(1,128)} '
           '%bitcast.3), custom_call_target="tpu_custom_call", '
           'backend_config={"u32[9,9]": 1}')
COUNTS = ('%sigjaccard_counts.1 = f32[8192,1]{1,0:T(8,128)} custom-call('
          'u32[8192,112]{1,0:T(8,128)} %gather.1, u32[8192,112]{1,0:T(8,128)}'
          ' %gather.2, s32[8192,1]{1,0:T(8,128)} %broadcast.4), '
          'custom_call_target="tpu_custom_call"')


def test_kernel_bytes_from_op_names():
    assert kernel_bytes.op_name_matches(SHINGLE, "byte_shingle")
    assert kernel_bytes.op_name_matches("byte_shingle = f32[1]", "byte_shingle")
    assert not kernel_bytes.op_name_matches(COUNTS, "byte_shingle")
    assert not kernel_bytes.op_name_matches("%copy.1 = u8[4]{0} copy("
                                            "byte_shingle.2)", "byte_shingle")
    results, operands = kernel_bytes.op_shapes(COUNTS)
    assert results == [("f32", (8192, 1))]
    assert operands == [("u32", (8192, 112)), ("u32", (8192, 112)),
                        ("s32", (8192, 1))]
    # byte_shingle's model equals every result and operand of the op.
    res, ops = kernel_bytes.op_shapes(SHINGLE)
    width = {"u8": 1, "s32": 4, "u32": 4}
    assert kernel_bytes.byte_shingle_op_bytes(SHINGLE) == sum(
        width[t] * int(np.prod(d)) for t, d in res + ops)
    # A verify call: the gathered rows, two index vectors, the counts.
    assert kernel_bytes.sigjaccard_op_bytes(COUNTS) == \
        2 * 8192 * 112 * 4 + 3 * 8192 * 4
    assert kernel_bytes.byte_shingle_op_bytes(SHINGLE) == \
        2304 * 8192 * 9 + 3 * 8192 * 4
    assert kernel_bytes.op_shapes("%copy.1 = u8[4]{0} copy(%p)") is None


def _trace_ctx(ops, modules, notes=8192):
    """A context whose trace is a hand-built profile with ``ops`` and
    ``modules`` as (name, start us, duration us) on one TPU, and a
    benchmark window [0, 10) ms."""
    import jax

    names = list(dict.fromkeys(n for n, _, _ in ops + modules))
    mid = {n: i + 1 for i, n in enumerate(names)}

    def events(evs):
        return "".join(f" events {{ metadata_id: {mid[n]} offset_ps: "
                       f"{s * 1000000} duration_ps: {d * 1000000} }}"
                       for n, s, d in evs)

    def quoted(n):
        return n.replace("\\", "\\\\").replace('"', '\\"')

    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{quoted(n)}" }} }}' for n, i in mid.items())
    text = (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0'
            f'{events(ops)} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0'
            f'{events(modules)} }}{meta} }} '
            'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
            'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
            'duration_ps: 10000000000 } } event_metadata { key: 1 value { '
            'id: 1 name: "bench.window" } } }')
    bench_trace = run.load_file("trace.py", "bench_trace")
    t = bench_trace.Trace(jax.profiler.ProfileData.from_text_proto(text))
    return SimpleNamespace(trace=t, counters={"notes": notes})


def _reader(name):
    return run.load("metrics", name)


def test_readers_on_a_hand_built_trace(monkeypatch):
    import jax

    # Two byte_shingle ops of 400 and 600 us inside 3 ms of
    # bytes_to_bands; one 20 us sigjaccard_counts op in 40 us of verify.
    ops = [(SHINGLE, 0, 400), (SHINGLE, 1000, 600), (COUNTS, 4000, 20),
           ("%fusion.3 = u32[8]{0} fusion()", 5000, 5)]
    modules = [("jit_bytes_to_bands(12)", 0, 3000),
               ("jit_indexed_pair_counts(7)", 4000, 40)]
    ctx = _trace_ctx(ops, modules, notes=16384)
    monkeypatch.setattr(jax, "devices",
                        lambda: [SimpleNamespace(device_kind="TPU v5 lite")])
    bw = 819e9
    got = {m: _reader(m).read(ctx) for m in NEW_METRICS[:4]}
    assert got["byte_ingest_ms_per_1k_notes.bulk"] == pytest.approx(
        3.0 / 16.384)
    assert got["sigjaccard_ms_per_1k_notes.bulk"] == pytest.approx(
        0.04 / 16.384)
    assert got["byte_shingle_hbm_share.bulk"] == pytest.approx(
        100 * 2 * (2304 * 8192 * 9 + 3 * 8192 * 4) / (1.0e-3 * bw))
    # Over the verify program's 40 us, not the kernel's 20 us.
    assert got["sigjaccard_hbm_share.bulk"] == pytest.approx(
        100 * (2 * 8192 * 112 * 4 + 3 * 8192 * 4) / (40e-6 * bw))


def test_readers_read_nothing_without_their_op_or_program():
    ctx = _trace_ctx([("%fusion.3 = u32[8]{0} fusion()", 0, 5)],
                     [("jit_fused_ingest(1)", 0, 9)])
    for m in NEW_METRICS[:4]:
        assert _reader(m).read(ctx) is None, m
    untraced = SimpleNamespace(trace=None, counters={"notes": 8})
    for m in NEW_METRICS:
        assert _reader(m).read(untraced) is None, m


def test_rooted_edges_admit_only_pairs_a_candidate_roots():
    import compare_estimate

    # Candidates: 0-1 and 2-3 above the threshold, 1-2 below it.  The
    # scored 0-3 (trees {0, 1} and {2, 3}, joined by the candidate 1-2)
    # shares no band and is admitted; 4-5 is rooted by no candidate.
    cand = np.array([[0, 1], [1, 2], [2, 3]])
    scored = np.array([[0, 1], [1, 2], [0, 3], [4, 5]])
    edges, admitted, unrooted = compare_estimate.rooted_edges(
        6, cand, np.array([True, False, True]), scored,
        np.array([True, False, True, True]))
    assert edges.tolist() == [[0, 1], [0, 3], [2, 3]]
    assert (admitted, unrooted) == (1, 1)
    # Nothing scored outside the candidates' trees: nothing unrooted.
    edges, admitted, unrooted = compare_estimate.rooted_edges(
        6, cand, np.array([True, False, True]), scored[:3],
        np.array([True, False, True]))
    assert (admitted, unrooted) == (1, 0)
    # Without the candidate 1-2, 0-3 joins trees no candidate joins.
    edges, admitted, unrooted = compare_estimate.rooted_edges(
        6, cand[[0, 2]], np.array([True, True]), scored[[0, 2]],
        np.array([True, True]))
    assert edges.tolist() == [[0, 1], [2, 3]]
    assert (admitted, unrooted) == (0, 1)


def test_host_readers_on_stub_contexts():
    counters = {"notes": 100, "pairs_evaluated": 3, "pairs_generated": 12}
    ctx = SimpleNamespace(trace=None, counters=counters)
    got = {m: _reader(m).read(ctx) for m in HOST_METRICS}
    assert got == pytest.approx({"verified_pairs_per_note.bulk": 0.03,
                                 "candidate_pairs_per_note.bulk": 0.12})
    # Counters read nothing without their count or without notes; the
    # idle share nothing without a trace.
    for c in ({"notes": 100}, {"notes": 0, "pairs_evaluated": 3,
                               "pairs_generated": 12}):
        ctx = SimpleNamespace(trace=None, counters=c)
        assert all(_reader(m).read(ctx) is None for m in HOST_METRICS)
    assert _reader("device_idle_share.bulk").read(ctx) is None


def test_device_idle_reader_on_a_hand_built_trace():
    # 400 + 600 us of ops in a 10 ms window.
    ctx = _trace_ctx([(SHINGLE, 0, 400), (SHINGLE, 1000, 600)],
                     [("jit_bytes_to_bands(12)", 0, 3000)])
    assert _reader("device_idle_share.bulk").read(ctx) == pytest.approx(90.0)


def test_store_reader_on_kept_spans():
    import program_spans

    spans = [("dedup.sig_store", 0, 10, 1, {"h2d_bytes": 4}),
             ("dedup.sig_store", 20, 30, 1, {"h2d_bytes": 4}),
             ("dedup.retain", 0, 40, 1, {})]
    ctx = SimpleNamespace(trace=object(), counters={"notes": 16},
                          program_spans=program_spans.Spans(spans))
    reader = _reader("sig_store_h2d_bytes_per_note.bulk")
    assert reader.read(ctx) == pytest.approx(0.5)
    ctx.program_spans = program_spans.Spans(spans[2:])
    assert reader.read(ctx) is None
