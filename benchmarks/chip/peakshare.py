"""Arithmetic shared by the metric readers: shares of the chip's peaks."""
from __future__ import annotations


def idle_share(run):
    """Percent of the traced window in which no operation ran on the
    device."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def roofline_share(run, flops: float, nbytes: float, seconds: float):
    """Percent of ``seconds`` that the chip's peaks say the work needs at
    least: the larger of operations over peak FLOP/s and bytes over peak
    bandwidth. None where nothing was timed or counted."""
    if not seconds or not (flops or nbytes):
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
