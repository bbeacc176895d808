"""Named host spans around the dedup path's stages, on the profiler's clock.

``span(name, **counts)`` opens ``jax.profiler.TraceAnnotation("dedup." +
name)``: under a profiler session the span lands in the same
``.xplane.pb`` as the device operations, on the same clock, and its
counts land as the event's stats, so an operator reading the trace can
name each idle stretch of the device by the host stage that held it and
turn the counts into ratios.  With no profiler session a span costs
about a microsecond, so spans sit at chunk-stage and feed granularity
only, never inside a loop over notes, pairs, verify batches or
union-find operations.

While a profiler session is on, each span that closes is also kept in
this process (``take``), so the process that ran the work can reduce its
own spans without reading the ``.xplane.pb`` back.  With no session
nothing is kept.

The span is also the dedup path's host clock for its stages:
``seconds`` holds the elapsed host time once the span has closed.
Counts known only once the work is done go in through ``count``.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "dedup."

# (name, start_ns, end_ns, thread, counts) of the spans closed under a
# profiler session, on ``time.perf_counter_ns``'s clock.
_kept: list[tuple[str, int, int, int, dict]] = []


def take() -> list[tuple[str, int, int, int, dict]]:
    """The spans kept since the last ``take``, oldest first; clears them."""
    out = _kept[:]
    del _kept[:len(out)]
    return out


class span:
    """``with span("pack") as s: ...; s.count(h2d_bytes=n); s.seconds``."""

    __slots__ = ("_ann", "_name", "_counts", "_t0", "seconds")

    def __init__(self, name: str, **counts):
        self._ann = TraceAnnotation(PREFIX + name, **counts)
        self._name = name
        self._counts = counts
        self.seconds = 0.0

    def count(self, **counts) -> None:
        """Attach counts to the span (its stats in the trace)."""
        self._ann.set_metadata(**counts)
        self._counts.update(counts)

    def __enter__(self) -> "span":
        # The host clock reads outside the annotation, so a kept span
        # covers its event in the trace, the profiler's own cost included.
        self._t0 = time.perf_counter_ns()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) * 1e-9
        if TraceAnnotation.is_enabled():
            _kept.append((PREFIX + self._name, self._t0, t1,
                          threading.get_ident(), self._counts))
