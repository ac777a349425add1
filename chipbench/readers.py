"""Arithmetic the metric readers (``metrics/<name>.py``) share.  Each
reader returns None where its run holds nothing to read."""
from __future__ import annotations

import statistics
from typing import Optional

from chipbench import costs, stats, trace as tracing


def ttft_ms(run, kind: str, q: float) -> Optional[float]:
    """The ``q``-th percentile of client-side time to first token, in ms,
    over every answered request of ``kind`` in the window; None when the
    sample leaves fewer than ten requests beyond it."""
    xs = [r.ttft_s * 1e3 for r in run.done(kind)]
    if not xs:
        return None
    if q == 50:
        return statistics.median(xs)
    return stats.supported_percentile(xs, q)


def median_stat_ms(run, kind: str, field: str) -> Optional[float]:
    """Median of a per-request restore statistic, in ms."""
    xs = [getattr(r, field) for r in run.done(kind)]
    xs = [x * 1e3 for x in xs if x is not None]
    return statistics.median(xs) if xs else None


def mfu(run, kind: str) -> Optional[float]:
    """Model FLOPs of the answered prefills over the window, as % of the
    chip's bf16 peak."""
    n = len(run.done(kind))
    if not n or len(run.done()) != n:
        return None
    achieved = stats.rate(n, run.window_s) * run.flops_per_request
    return 100.0 * achieved / run.peaks()["bf16_flops_per_s"]


def idle_share(run, kind: str) -> Optional[float]:
    """% of the traced window in which no operation ran on the chip."""
    if run.trace is None or not run.done(kind) or len(run.done()) != len(run.done(kind)):
        return None
    return 100.0 * (1.0 - tracing.device_busy_s(run.trace) / run.trace.window_s)


def roofline(run, kernel: str) -> Optional[float]:
    """Least time of the kernel's calls (their page-plan bytes at the HBM
    peak) over the device time of its operations in the trace, in %."""
    if run.trace is None or not run.overlay_plans:
        return None
    dev_ns, n = tracing.kernel_ns(run.trace.device, kernel, run.trace.lo, run.trace.hi)
    if not n or not dev_ns:
        return None
    least = sum(costs.overlay_bytes(k, pb) for k, pb in run.overlay_plans)
    least_s = least / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / (dev_ns / 1e9)
