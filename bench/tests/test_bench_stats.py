"""Percentile and rate arithmetic of the end-to-end metrics: latency is
timed from the due time, so a stall counts against every request that
was due behind it."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import stats  # noqa: E402


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_nearest_rank_percentile(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_stall_counts_against_requests_due_behind_it():
    # requests due every 0.1 s; the server stalls from 0.1 s to 1.0 s and
    # then serves everything at once: each request waited from its due time
    recs = [NS(due=0.1 * i, times=[1.0 if i else 0.05, 1.2]) for i in range(5)]
    t = stats.ttfts(recs, seconds=2.0, gave_up=3.0)
    assert t == pytest.approx([0.05, 0.9, 0.8, 0.7, 0.6])
    # had it been timed from submission (all submitted at 1.0 s, after
    # the stall), the same run would read a TTFT of zero for four of them
    assert stats.percentile(t, 90) == pytest.approx(0.9)


def test_request_never_served_counts_until_given_up():
    recs = [NS(due=0.5, times=[]), NS(due=0.2, times=[0.3])]
    assert stats.ttfts(recs, 1.0, gave_up=4.0) == pytest.approx([3.5, 0.1])


def test_requests_due_after_the_window_are_left_out():
    recs = [NS(due=0.5, times=[0.6]), NS(due=1.5, times=[1.6])]
    assert stats.ttfts(recs, 1.0, gave_up=9.0) == pytest.approx([0.1])


def test_gaps_and_tokens_in_window():
    # a segment of 4 reaches the client at once: three zero gaps, then one
    recs = [NS(due=0.0, times=[0.1, 0.5, 0.5, 0.5, 0.5, 0.9, 0.9, 1.3])]
    g = stats.gaps(recs, seconds=1.0)
    assert g == pytest.approx([0.4, 0.0, 0.0, 0.0, 0.4, 0.0])
    assert stats.percentile(g, 95) == pytest.approx(0.4)
    assert stats.tokens_in_window(recs, 1.0) == 7
