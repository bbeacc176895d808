"""The program's own spans (``repro.core.spans``, names ``dedup.*``),
reduced to the per-layer numbers that read them.

A span is ``(name, start_ns, end_ns, line, stats)``: ``line`` tells the
host threads apart (a span's children lie on its own line) and ``stats``
holds the counts the program put on it.  Two sources give the same
spans:

* ``of_run(ctx)``: the spans the program kept in its own process while
  the window's profiler session was on (``repro.core.spans.take``), on
  ``time.perf_counter_ns``'s clock.  The metric readers use this one.
* ``from_profile(data)``: the ``dedup.`` events on the host planes of a
  ``jax.profiler.ProfileData`` (a kept ``.xplane.pb``), with their stats,
  on the profiler's clock beside the device operations.

A span's self time is its duration less what its child spans cover: the
time in which it is the innermost program span on its line.
"""
from __future__ import annotations

import re

PREFIX = "dedup."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


class Spans:
    """The program spans of one window."""

    def __init__(self, spans, window: tuple[float, float] | None = None):
        """``window``: count only what lies in [lo, hi) on the spans'
        clock (the whole record when None)."""
        self.spans = list(spans)
        self.window = window
        self._pieces = None

    def _innermost_pieces(self) -> list:
        """Every line's span time cut into (start, end, name) pieces,
        each owned by the innermost span open over it."""
        if self._pieces is not None:
            return self._pieces
        lines: dict = {}
        for name, s, e, line, _ in self.spans:
            lines.setdefault(line, []).append((s, -e, name))
        pieces = []
        for evs in lines.values():
            evs.sort()  # by start, outer (later end) first
            stack: list = []  # (end, name), innermost last
            t = None
            for s, neg_e, name in evs:
                while stack and stack[-1][0] <= s:
                    end, owner = stack.pop()
                    pieces.append((t, end, owner))
                    t = end
                if stack:
                    pieces.append((t, s, stack[-1][1]))
                stack.append((-neg_e, name))
                t = s
            while stack:
                end, owner = stack.pop()
                pieces.append((t, end, owner))
                t = end
        self._pieces = [p for p in pieces if p[1] > p[0]]
        return self._pieces

    def self_s(self, name: str) -> float:
        """Seconds in which ``name`` is the innermost span on its line."""
        lo, hi = self.window or (float("-inf"), float("inf"))
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for s, e, owner in self._innermost_pieces()
                   if owner == name) * 1e-9

    def _named(self, name: str) -> list:
        lo, hi = self.window or (float("-inf"), float("inf"))
        return [sp for sp in self.spans if sp[0] == name and lo <= sp[1] < hi]

    def count(self, name: str) -> int:
        """Spans named ``name`` that start in the window."""
        return len(self._named(name))

    def stat_sum(self, name: str, key: str) -> float:
        """A stat summed over the ``name`` spans that start in the window
        (spans without it count 0)."""
        return sum(sp[4].get(key, 0) for sp in self._named(name))


def from_profile(data, window: tuple[float, float] | None = None) -> Spans:
    """The ``dedup.`` events of a ``jax.profiler.ProfileData``."""
    out = []
    for p, plane in enumerate(data.planes):
        if DEVICE_PLANE.match(plane.name):
            continue
        for ln, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        (p, ln), dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIX))
    return Spans(out, window)


def of_run(ctx) -> Spans | None:
    """The spans the program kept during a traced run's window, taken
    once and shared by every reader of the run; None in an untraced run
    or for a program that keeps none."""
    if ctx.trace is None:
        return None
    got = getattr(ctx, "program_spans", None)
    if got is None:
        try:
            from repro.core.spans import take
        except ImportError:
            return None
        got = ctx.program_spans = Spans(take())
    return got


def share(ctx, name: str) -> float | None:
    """Self time of ``name`` as a percentage of the traced window."""
    sp = of_run(ctx)
    if sp is None or not sp.count(name):
        return None
    return 100.0 * sp.self_s(name) / ctx.trace.window_s()


def per_note(ctx, name: str, key: str) -> float | None:
    """The ``key`` stat of the ``name`` spans over the window's notes."""
    sp, notes = of_run(ctx), ctx.counters.get("notes", 0)
    if sp is None or notes <= 0 or not sp.count(name):
        return None
    return sp.stat_sum(name, key) / notes
