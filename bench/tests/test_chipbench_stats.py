"""Percentiles, spreads and the end-to-end arithmetic of the harness."""

import statistics

import numpy as np
import pytest

from bench import harness, peaks, stats


def test_percentile_matches_numpy_linear():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 10, 50, 90, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 95) == 4.0


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


def test_rate_refuses_an_empty_window():
    assert stats.rate(30, 2.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


class _Req:
    def __init__(self):
        self.admitted_at = None


def _rec(sched, times, failed=False):
    return harness.Rec(req=_Req(), item=None, turn=0, sched=sched,
                       times=list(times), failed=failed)


def test_end_to_end_counts_tokens_in_window_and_times_from_schedule():
    recs = [_rec(0.0, [0.1, 0.2, 0.3]), _rec(0.5, [0.9, 1.1, 1.3]),
            _rec(2.5, [2.6])]                     # sent after the window
    rec = {"t0": 0.0, "end": 1.0, "seconds": 1.0, "recs": recs}
    e2e = harness.end_to_end(rec, setup_s=3.0)
    assert e2e["output_tokens_per_s"] == 4.0      # 0.1 .2 .3 .9
    ttft = [100.0, 400.0]
    assert e2e["ttft_p50_ms"] == pytest.approx(np.percentile(ttft, 50))
    assert e2e["ttft_p90_ms"] == pytest.approx(np.percentile(ttft, 90))
    itl = [100.0, 100.0, 200.0, 200.0]
    assert e2e["itl_p95_ms"] == pytest.approx(np.percentile(itl, 95))
    assert e2e["setup_s"] == 3.0


def test_failed_request_misses_every_limit():
    recs = [_rec(0.0, [0.1]), _rec(0.0, [], failed=True)]
    rec = {"t0": 0.0, "end": 1.0, "seconds": 1.0, "recs": recs}
    e2e = harness.end_to_end(rec, setup_s=0.0)
    # the failed request waits until the drain gives up
    horizon_ms = (1.0 + harness.DRAIN_S) * 1e3
    assert e2e["ttft_p90_ms"] == pytest.approx(
        np.percentile([100.0, horizon_ms], 90))


def test_peak_table_refuses_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownChip):
        peaks.peaks("TPU v9 imaginary")
