"""Rates and percentiles over a window, failures counted as misses."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loops, stats  # noqa: E402


def req(due, done, status="ok"):
    return loops.Request(index=0, due=due, submitted=due, done=done, status=status)


@pytest.mark.parametrize("q,expect", [(50, 5), (95, 10), (100, 10), (1, 1)])
def test_nearest_rank_percentile(q, expect):
    assert stats.percentile(list(range(10, 0, -1)), q) == expect


def test_failed_and_refused_requests_miss_every_limit():
    rs = [req(0.0, 0.010), req(0.0, 0.020), req(0.0, None, "refused"),
          req(0.0, 0.5, "failed")]
    lat = stats.latencies_ms(rs, miss_ms=2000.0)
    assert lat[:2] == pytest.approx([10.0, 20.0]) and lat[2:] == [2000.0, 2000.0]
    assert stats.percentile(lat, 50) == pytest.approx(20.0)
    assert stats.percentile(lat, 95) == 2000.0
    assert stats.failed(rs) == 2


def test_images_per_s_counts_answers_inside_the_window():
    w = loops.Window(start=10.0, end=12.0)
    w.requests = [req(10.0, 11.0), req(10.0, 12.0), req(10.0, 12.5),
                  req(10.0, 11.5, "failed")]
    assert stats.images_per_s(w) == pytest.approx(1.0)
