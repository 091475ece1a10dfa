"""Window arithmetic, byte counts, and shares of the peaks, against values
worked out by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import peakshare  # noqa: E402
import workcount  # noqa: E402
from loader import load_module  # noqa: E402
from windowstats import RoundRecord, Window, nearest_rank  # noqa: E402


def window():
    return Window(start=10.0, rounds=[
        RoundRecord(10.0, 14.0, {"queries": 2}, {"query": [1.5, 2.5]}),
        RoundRecord(14.5, 20.0, {"queries": 2}, {"query": [4.0, 1.5]}),
    ])


def test_rate_counts_whole_rounds_over_their_wall_time():
    w = window()
    assert w.seconds == 10.0
    assert w.rate("queries") == 0.4
    assert w.rate("rows") == 0.0
    assert Window(start=0.0, rounds=[]).rate("queries") is None


@pytest.mark.parametrize("values,pct,want", [
    ([3, 1, 2], 50, 2), ([3, 1, 2], 95, 3), (list(range(1, 21)), 95, 19),
    (list(range(1, 101)), 95, 95), (list(range(1, 41)), 95, 38),
    ([7.0], 95, 7.0), ([], 95, None)])
def test_nearest_rank_percentile(values, pct, want):
    assert nearest_rank(values, pct) == want


def test_segment_reduce_bytes():
    assert workcount.segment_reduce_bytes(3, 100, 4) == 1648


@pytest.mark.parametrize("ids,want", [
    ([0, 0, 1, 2, 2, -1, -1, -1], (5, 3)),      # padded rows are -1
    ([0] * 6 + [-1, -1], (6, 1)),                # a global aggregate
    ([-1, -1], (0, 0)),
    (list(range(5)), (5, 5))])
def test_live_rows_and_segments_leave_out_the_padding(ids, want):
    assert workcount.live_rows_and_segments(np.asarray(ids, np.int32)) \
        == want


def run_with(trace=None, counters=None):
    return SimpleNamespace(
        window=window(), trace=trace, counters=counters or {},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        setup_s=12.5)


def test_shares_of_the_peaks():
    run = run_with(trace={"busy_s": 1.0, "window_s": 4.0})
    assert peakshare.idle_share(run) == 75.0
    assert peakshare.roofline_share(run, 2e9, 1e6, 0.004) == 50.0
    assert peakshare.roofline_share(run, 1e3, 2e6, 0.004) == 50.0
    assert peakshare.roofline_share(run, 1e9, 1e6, 0.0) is None
    assert peakshare.idle_share(run_with()) is None


def read(metric, run):
    return load_module(CHIP / "metrics" / f"{metric}.py").read(run)


def test_readers_return_nothing_without_a_trace():
    run = run_with()
    for metric in ("device_idle_share.query",
                   "segment_reduce_roofline.query"):
        assert read(metric, run) is None


def test_segment_reduce_roofline_reads_bytes_over_kernel_time():
    trace = {"busy_s": 1.0, "window_s": 4.0,
             "modules": {"jit__segment_reduce_2d": 0.002,
                         "jit_other": 1.0}}
    run = run_with(trace=trace, counters={"segment_reduce_bytes": 1e6})
    # 1e6 bytes at 1e9 bytes/s need 1 ms of the kernel's 2 ms.
    assert read("segment_reduce_roofline.query", run) == 50.0


def test_host_clock_and_counter_readers():
    run = run_with(counters={"plan_cache_misses": 0,
                             "interpreted_fallbacks": 2})
    assert read("queries_per_s", run) == 0.4
    assert read("query_p95_s", run) == 4.0
    assert read("setup_s", run) == 12.5
    assert read("plan_cache_misses.query", run) == 0
    assert read("interpreted_fallbacks.query", run) == 2
