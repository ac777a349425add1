"""Time a traced run's requests spent in the program's own spans.

The program names its stages with ``jax.profiler.TraceAnnotation`` spans
(``repro.core.spans``), recorded on the profiler's clock beside the
harness's ``chipbench.request`` span around each request.  A reader takes,
for every request of one kind, the union of the named spans' intervals
clipped to that request's span, and reports the median over the requests.
A trace of a program that records no such span reads None.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Iterable, List, Optional, Sequence, Tuple

from chipbench import trace as tracing

REQUEST_SPAN = "chipbench.request"  # the harness's span around one request


class _Spans:
    """Host events of some names, sorted by start, for overlap queries."""

    def __init__(self, host: Sequence[Tuple[str, str, int, int]], names: Iterable[str]):
        names = set(names)
        evs = sorted((s, e) for _, n, s, e in host if n in names)
        self.starts = [s for s, _ in evs]
        self.events = [("", s, e) for s, e in evs]
        self.longest = max((e - s for s, e in evs), default=0)

    def within(self, lo: int, hi: int) -> List[tracing.Event]:
        """The events that overlap [lo, hi], unclipped."""
        i = bisect.bisect_left(self.starts, lo - self.longest)
        j = bisect.bisect_left(self.starts, hi)
        return [ev for ev in self.events[i:j] if ev[2] > lo]


def request_spans(run) -> Optional[List[Tuple[int, int]]]:
    """(start, end) of each request's span in the traced window, in the
    order of ``run.requests``; None when they do not pair one to one."""
    t = run.trace
    reqs = sorted((s, e) for _, n, s, e in t.host
                  if n == REQUEST_SPAN and s >= t.lo and e <= t.hi)
    return reqs if len(reqs) == len(run.requests) else None


def span_ms(run, kind: str, names: Iterable[str],
            minus: Iterable[str] = ()) -> Optional[float]:
    """Median over the answered requests of ``kind`` of the time, in ms,
    covered by the spans ``names`` inside each request's span; with
    ``minus``, of that time less what the spans ``minus`` cover.  None
    without a trace, or when no request holds a span of ``names`` (or of
    ``minus``, when given)."""
    if run.trace is None:
        return None
    reqs = request_spans(run)
    if reqs is None:
        return None
    minus = tuple(minus)
    a, b = _Spans(run.trace.host, names), _Spans(run.trace.host, minus)
    wanted = {id(r) for r in run.done(kind)}
    xs: List[int] = []
    seen_a, seen_b = False, not minus
    for (lo, hi), r in zip(reqs, run.requests):
        if id(r) not in wanted:
            continue
        in_a, in_b = a.within(lo, hi), b.within(lo, hi)
        seen_a, seen_b = seen_a or bool(in_a), seen_b or bool(in_b)
        xs.append(tracing.busy_ns(in_a + in_b, lo, hi) - tracing.busy_ns(in_b, lo, hi))
    if not xs or not seen_a or not seen_b:
        return None
    return statistics.median(xs) / 1e6
