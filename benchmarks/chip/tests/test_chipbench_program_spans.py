"""The reduction of the program's ``dedup.*`` spans (``program_spans.py``)
and the per-layer readers built on it, against numbers worked out by
hand; and the trace reduction's own numbers on a trace that carries such
spans."""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import program_spans  # noqa: E402


def _load(relpath: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load("trace.py", "bench_trace")
HAND_BUILT = _load(os.path.join("tests", "test_chipbench_trace.py"),
                   "bench_test_trace").HAND_BUILT


def _with_program_spans(spans) -> str:
    """HAND_BUILT with program spans (name, start us, end us, stats) on
    its host line."""
    names = sorted({n for n, *_ in spans})
    stat_keys = sorted({k for *_, st in spans for k in st})
    mid = {n: 10 + i for i, n in enumerate(names)}
    sid = {k: 1 + i for i, k in enumerate(stat_keys)}
    events = "".join(
        f" events {{ metadata_id: {mid[n]} offset_ps: {round(s * 1e6)} "
        f"duration_ps: {round((e - s) * 1e6)} "
        + "".join(f"stats {{ metadata_id: {sid[k]} int64_value: {v} }} "
                  for k, v in st.items()) + "}"
        for n, s, e, st in spans)
    meta = "".join(
        f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in mid.items())
    meta += "".join(
        f' stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
        for k, i in sid.items())
    head, host = HAND_BUILT.split('name: "/host:CPU"')
    host = host.replace("duration_ps: 5000000 }",
                        "duration_ps: 5000000 }" + events, 1)
    host = host.replace('name: "bench.step" } }',
                        'name: "bench.step" } }' + meta, 1)
    return head + 'name: "/host:CPU"' + host


def _profile(text: str):
    import jax

    return jax.profiler.ProfileData.from_text_proto(text)


# dedup.ingest > dedup.merge > two dedup.verify; dedup.snapshot inside
# the ingest; a second dedup.snapshot after the window [0, 10) us.
NESTED = [
    ("dedup.ingest", 0.2, 9.3, {}),
    ("dedup.merge", 1.5, 5.7, {"verify_ns": 1700}),
    ("dedup.verify", 2.0, 3.0, {}),
    ("dedup.verify", 4.4, 5.1, {}),
    ("dedup.snapshot", 7.6, 9.0, {"pairs": 3}),
    ("dedup.snapshot", 10.5, 11.0, {"pairs": 100}),
]


def test_program_spans_leave_the_trace_numbers_unchanged():
    plain = trace.Trace(_profile(HAND_BUILT))
    spanned = trace.Trace(_profile(_with_program_spans(NESTED)))
    assert spanned.busy_s() == plain.busy_s()
    assert spanned.window_s() == plain.window_s()
    for names in (("fused_ingest",), ("probe", "fused")):
        assert spanned.program_s(names) == plain.program_s(names)
    assert spanned.top_ops(10) == plain.top_ops(10)
    assert spanned.idle_gaps(k=10) == plain.idle_gaps(k=10)
    assert program_spans.from_profile(_profile(HAND_BUILT)).spans == []


def test_profile_spans_reduce_to_hand_numbers():
    data = _profile(_with_program_spans(NESTED))
    sp = program_spans.from_profile(
        data, window=trace.Trace(data).window_ns())
    assert [s[0] for s in sp.spans] == [n for n, *_ in NESTED]
    assert [s[4] for s in sp.spans] == [st for *_, st in NESTED]
    # Self time: each span's time in the window minus its children's.
    assert sp.self_s("dedup.ingest") == pytest.approx(3.5e-6)  # 9.1-4.2-1.4
    assert sp.self_s("dedup.merge") == pytest.approx(2.5e-6)  # 4.2-1.0-0.7
    assert sp.self_s("dedup.verify") == pytest.approx(1.7e-6)
    assert sp.self_s("dedup.snapshot") == pytest.approx(1.4e-6)
    assert sp.self_s("dedup.tokenize") == 0.0
    # Counts and stats cover the spans that start in the window only.
    assert sp.count("dedup.verify") == 2
    assert sp.count("dedup.snapshot") == 1
    assert sp.count("dedup.tokenize") == 0
    assert sp.stat_sum("dedup.merge", "verify_ns") == 1700
    assert sp.stat_sum("dedup.snapshot", "pairs") == 3
    assert sp.stat_sum("dedup.verify", "verify_ns") == 0
    assert sp.stat_sum("dedup.ingest", "pairs") == 0


def test_spans_on_another_line_are_not_children():
    # Thread 1 runs an ingest over [0, 10) ns; thread 2 a merge over
    # [2, 6) ns.  Without a window the whole record counts.
    sp = program_spans.Spans([
        ("dedup.ingest", 0, 10, 1, {}),
        ("dedup.merge", 2, 6, 2, {"verify_ns": 1}),
    ])
    assert sp.self_s("dedup.ingest") == pytest.approx(10e-9)
    assert sp.self_s("dedup.merge") == pytest.approx(4e-9)
    assert sp.count("dedup.merge") == 1


# One chunk's stages in the window [0, 10) us of HAND_BUILT, two notes,
# as the program keeps them (ns on one thread).
STAGES = [
    ("dedup.ingest", 0.0, 8.0, {}),
    ("dedup.tokenize", 0.0, 1.0, {}),
    ("dedup.pack", 1.0, 1.2, {}),
    ("dedup.device_ingest", 1.2, 1.5, {"h2d_bytes": 2056}),
    ("dedup.retain", 1.5, 2.0, {}),
    ("dedup.merge", 2.0, 5.0, {"verify_ns": 1000}),
    ("dedup.band_index", 5.0, 5.5, {}),
    ("dedup.snapshot", 5.5, 7.0, {"pairs": 9}),
]
READERS = {  # reader: its value on STAGES with two notes
    "tokenize_share.batch": 10.0,
    "pack_share.batch": 2.0,
    "retain_share.batch": 5.0,
    "merge_share.batch": 20.0,  # 30% minus the 1 us verify inside it
    "band_index_share.batch": 5.0,
    "snapshot_share.batch": 15.0,
    "h2d_bytes_per_note.batch": 1028.0,
    "snapshot_pairs_per_note.batch": 4.5,
}


def _ctx(kept, trace_text=HAND_BUILT):
    ctx = SimpleNamespace(trace=trace.Trace(_profile(trace_text)),
                          counters={"notes": 2})
    if kept is not None:
        ctx.program_spans = program_spans.Spans(kept)
    return ctx


@pytest.mark.parametrize("name", sorted(READERS))
def test_span_reader_on_a_stub_context(name, monkeypatch):
    read = _load(os.path.join("metrics", name + ".py"),
                 "bench_metric_" + name.replace(".", "_")).read
    kept = [(n, round(s * 1e3), round(e * 1e3), 7, st)
            for n, s, e, st in STAGES]
    assert read(_ctx(kept)) == pytest.approx(READERS[name])
    # No trace, or a program that kept none of the reader's spans: no
    # reading.
    assert read(SimpleNamespace(trace=None, counters={"notes": 2})) is None
    assert read(_ctx([])) is None
    # A program without the span helper (the code before it): no
    # reading, and no error.
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert read(_ctx(None)) is None
