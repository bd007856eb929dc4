"""End-to-end arithmetic over a window: every request, every second."""
from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies_ms(requests, miss_ms: float) -> List[float]:
    """Due-to-result latency of every request, in ms. A request that was
    refused, failed or never answered counts as ``miss_ms``, which is
    longer than any limit the run could meet (the whole window)."""
    return [1000.0 * (r.done - r.due) if r.status == "ok" and r.done is not None
            else miss_ms for r in requests]


def images_per_s(window) -> float:
    """Images answered inside the window over the window's length."""
    return len(window.completed()) / window.seconds


def failed(requests) -> int:
    return sum(1 for r in requests if r.status != "ok")


def lateness_ms(requests) -> Optional[List[float]]:
    """How late the generator submitted each request after it was due."""
    return [1000.0 * (r.submitted - r.due) for r in requests]
