"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  The device
planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per operation that ran and the ``XLA Modules`` line one event per
program (jitted function or Pallas kernel call) that ran.  Host planes
hold the benchmark's own spans (``TraceAnnotation`` names starting with
``bench.``), on the same clock.

* busy time of a device: the union of its operation intervals;
* a program's device time: the summed durations of the ``XLA Modules``
  events whose name contains one of a metric's name fragments (each
  metric file keeps its own table of fragments);
* idle gaps: the stretches between busy intervals, each named by the
  innermost benchmark span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _intervals(events) -> np.ndarray:
    """(name, start_ns, duration_ns) events -> sorted [start, end) rows."""
    iv = np.array([(s, s + d) for _, s, d in events],
                  dtype=np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")] if len(iv) else iv


def union(iv: np.ndarray) -> np.ndarray:
    """Merge sorted [start, end) intervals into disjoint ones."""
    if not len(iv):
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


class Trace:
    """One traced window, reduced."""

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax

        return cls(jax.profiler.ProfileData.from_file(path))

    def __init__(self, data):
        """``data``: a ``jax.profiler.ProfileData``."""
        self.ops: dict[int, list] = {}
        self.modules: dict[int, list] = {}
        self.spans: list[tuple[str, float, float]] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    self.ops[int(m.group(2))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
                elif m and line.name == MODULES_LINE:
                    self.modules[int(m.group(2))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
                elif not m:
                    self.spans.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
        self.devices = sorted(self.ops)

    def _busy(self, dev: int) -> np.ndarray:
        return union(_intervals(self.ops[dev]))

    def busy_s(self) -> float:
        """Busy seconds inside the window span, averaged over the
        devices that ran anything."""
        if not self.devices:
            return 0.0
        lo, hi = self.window_ns()
        per = []
        for d in self.devices:
            b = np.clip(self._busy(d), lo, hi)
            per.append(np.sum(b[:, 1] - b[:, 0]) if len(b) else 0.0)
        return float(np.mean(per)) * 1e-9

    def window_s(self) -> float:
        lo, hi = self.window_ns()
        return (hi - lo) * 1e-9

    def program_s(self, fragments) -> float:
        """Device seconds of the programs whose name holds a fragment,
        summed over devices.  A trace in which programs ran but none
        matches raises: a metric's name table that no longer meets the
        program's names must fail the run, not drop the metric."""
        hits = [d for evs in self.modules.values() for n, _, d in evs
                if any(f in n for f in fragments)]
        if not hits and any(self.modules.values()):
            raise LookupError(f"no program in the trace matches {fragments}")
        return sum(hits) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        acc: dict[str, float] = {}
        for evs in self.ops.values():
            for n, _, d in evs:
                acc[n] = acc.get(n, 0.0) + d
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]

    def idle_gaps(self, window: tuple[float, float] | None = None,
                  k: int = 10) -> list:
        """The ``k`` longest idle stretches of the first device inside
        the benchmark's window span, each as [name, seconds]."""
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        if window is None:
            window = self.window_ns()
        lo, hi = window
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = [(max(s, lo), min(e, hi)) for s, e in edges
                if min(e, hi) > max(s, lo)]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            out.append([f"{self.span_at((s + e) / 2)}@{(s - lo) * 1e-9:.3f}s",
                        (e - s) * 1e-9])
        return out

    def span_at(self, t: float) -> str:
        """The innermost benchmark span covering time ``t``."""
        best, width = "none", np.inf
        for name, s, e in self.spans:
            if s <= t <= e and e - s < width:
                best, width = name[len(SPAN_PREFIX):], e - s
        return best

    def window_ns(self) -> tuple[float, float]:
        """The ``bench.window`` span, else the extent of the device ops."""
        for name, s, e in self.spans:
            if name == SPAN_PREFIX + "window":
                return s, e
        iv = np.concatenate([_intervals(v) for v in self.ops.values()]
                            or [np.zeros((0, 2))])
        return (float(iv[:, 0].min()), float(iv[:, 1].max())) if len(iv) \
            else (0.0, 0.0)

