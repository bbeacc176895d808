"""The trace reduction on a hand-built trace, against numbers worked out
by hand."""
from __future__ import annotations

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_trace", os.path.join(HERE, "trace.py"))
trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace)

# Device ops [1, 3) and [2, 4) and [6, 7) us; one module [1, 4) us; the
# benchmark's window span [0, 10) us with a step span [0, 5) us.
HAND_BUILT = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fused_ingest(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_probe(9)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
}
'''


def test_hand_built_trace_reduces_to_hand_numbers():
    import jax

    t = trace.Trace(jax.profiler.ProfileData.from_text_proto(HAND_BUILT))
    assert t.devices == [0]
    assert t.window_s() == pytest.approx(10e-6)
    # Union of [1, 3), [2, 4), [6, 7): 4 us busy of 10.
    assert t.busy_s() == pytest.approx(4e-6)
    assert t.program_s(("fused_ingest",)) == pytest.approx(3e-6)
    assert t.program_s(("probe", "fused")) == pytest.approx(4e-6)
    with pytest.raises(LookupError):
        t.program_s(("sigjaccard",))
    assert t.top_ops(1) == [["fusion.1", pytest.approx(3e-6)]]
    gaps = t.idle_gaps(k=3)
    # Idle [7, 10), [4, 6) and [0, 1) us; the first's middle lies in the
    # window span alone, the others' in the step span too.
    assert [round(g[1] * 1e6, 6) for g in gaps] == [3.0, 2.0, 1.0]
    assert [g[0].split("@")[0] for g in gaps] == ["window", "step", "step"]
