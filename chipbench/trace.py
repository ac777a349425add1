"""Reduction of a JAX profiler trace to device busy time, kernel time, the
longest device idle gaps and what the host was doing in them.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain event
lists, and everything after that is arithmetic on ``(name, start_ns,
end_ns)`` tuples, so it can be checked on synthetic traces.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)

DEVICE_LINES = ("XLA Ops",)  # per-operation lines of a TPU plane
WINDOW_SPAN = "chipbench.window"  # the harness's host span around the window


@dataclass
class Trace:
    device: List[Event]                      # operations on the chip used
    host: List[Tuple[str, str, int, int]]    # (thread, name, start, end)
    lo: int                                  # traced window, ns
    hi: int
    layout: Dict[str, Dict[str, int]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


def load(logdir: str, device_index: int = 0) -> Trace:
    """Events of the newest ``.xplane.pb`` under ``logdir``: operations of
    TPU ``device_index`` and every host thread's events, clipped to the
    harness's window span."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    device: List[Event] = []
    host: List[Tuple[str, str, int, int]] = []
    layout: Dict[str, Dict[str, int]] = {}
    dev_plane = f"/device:TPU:{device_index}"
    for plane in data.planes:
        lines = layout.setdefault(plane.name, {})
        is_dev = plane.name == dev_plane
        is_host = plane.name.startswith("/host:")
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = len(evs)
            if is_dev and line.name in DEVICE_LINES:
                device.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                              for e in evs)
            elif is_host:
                host.extend((line.name, e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)) for e in evs)
    spans = [(s, e) for _, n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = spans[-1]
    return Trace(device=device, host=host, lo=lo, hi=hi, layout=layout)


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def merge(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Union of the events' intervals, as sorted disjoint intervals."""
    out: List[Tuple[int, int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Iterable[Event], lo: int, hi: int) -> int:
    """Time within [lo, hi] in which at least one operation ran."""
    return sum(e - s for s, e in merge(clip(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals within [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for s, e in merge(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def kernel_ns(events: Iterable[Event], name: str, lo: int, hi: int) -> Tuple[int, int]:
    """(total device time, count) of the operations whose name contains
    ``name`` within [lo, hi]."""
    hits = [(n, s, e) for n, s, e in clip(events, lo, hi) if name in n]
    return sum(e - s for _, s, e in hits), len(hits)


def short_name(name: str) -> str:
    """An operation's HLO text cut to its name and result shape:
    ``%fusion.9 = f32[1024,2816]``."""
    head, sep, rest = name.partition(" = ")
    return f"{head} = {rest.split('{')[0].split(' ')[0]}" if sep else name


def top_ops(events: Iterable[Event], lo: int, hi: int, n: int = 10) -> List[list]:
    """The ``n`` operations (by short name) that took most device time:
    [[name, s]]."""
    tot: Dict[str, int] = {}
    for name, s, e in clip(events, lo, hi):
        key = short_name(name)
        tot[key] = tot.get(key, 0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def host_activity(host: Sequence[Tuple[str, str, int, int]], s: int, e: int,
                  exclude: Tuple[str, ...] = (WINDOW_SPAN,)) -> str:
    """What the host was doing during [s, e]: the shortest host event that
    covers at least half of it, else the one overlapping it most, named
    ``thread/event``; "no host event" when nothing overlaps."""
    best, best_key = None, None
    for thread, name, hs, he in host:
        if name in exclude:
            continue
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        covers = 2 * ov >= (e - s)
        key = (0, he - hs) if covers else (1, -ov)
        if best_key is None or key < best_key:
            best, best_key = f"{thread}/{name}", key
    return best or "no host event"


def longest_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` longest device idle gaps in the window, each named by the
    host's activity in it: [[name, seconds]], longest first."""
    gaps = sorted(idle_gaps(trace.device, trace.lo, trace.hi),
                  key=lambda g: g[0] - g[1])[:n]
    return [[host_activity(trace.host, s, e), (e - s) / 1e9] for s, e in gaps]


def summary(trace: Trace) -> Dict:
    """``busy_s``, ``window_s`` and the ``breakdown`` the result line carries."""
    return {
        "busy_s": busy_ns(trace.device, trace.lo, trace.hi) / 1e9,
        "window_s": trace.window_s,
        "breakdown": {
            "device_ops": top_ops(trace.device, trace.lo, trace.hi),
            "idle_gaps": longest_gaps(trace),
        },
    }


def device_busy_s(trace: Optional[Trace]) -> Optional[float]:
    if trace is None:
        return None
    return busy_ns(trace.device, trace.lo, trace.hi) / 1e9
