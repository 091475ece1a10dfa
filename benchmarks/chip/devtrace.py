"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time by operation and by program, and idle gaps by host span.

The harness brackets its window and every round with host annotations
whose names start with ``bench:``; the reduction clips every device
event to the ``bench:window`` span. Busy time is the union of the
intervals in which an operation ran on a device (the ``XLA Ops`` line of
its plane), averaged over the chips used. An idle gap is a stretch of
the window in which no operation ran; it is named after the innermost
``bench:`` span around its middle, which says what the host was doing.
On a v5e the trace's device timestamps run about 1 ms ahead of the
host's (a program shows on the device 0.9-1.1 ms before the host call
that launched it), so a gap shorter than that may carry the name of the
span next to it.

Named ``devtrace`` and not ``trace`` so that it never shadows the
standard library's module of that name.
"""
from __future__ import annotations

import collections
import glob
import os

PREFIX = "bench:"
WINDOW = PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TPU_PLANE = "/device:TPU:"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_planes(profile, chips: int):
    """The chips' planes, ``/device:TPU:0`` first (a TPU trace also holds
    ``/device:CUSTOM:...`` planes, which are not chips)."""
    planes = [p for p in profile.planes if p.name.startswith(TPU_PLANE)]
    planes.sort(key=lambda p: int(p.name[len(TPU_PLANE):]))
    return planes[:chips]


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[32,1,2048]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 = bf16[32,1,2048]``: the op and its result shape, which
    is how the trace names an operation, without layout and operands."""
    head = hlo.split(" = ", 1)
    if len(head) == 2:
        return head[0] + " = " + head[1].split("{", 1)[0].split(" ", 1)[0]
    return hlo


def host_spans(profile) -> list:
    """Every ``bench:`` annotation on the host: (name, start, end)."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce_profile(profile, chips: int = 1, top: int = 10) -> dict:
    """Busy and window seconds, device seconds by op and by program, and
    the longest idle gaps named by host span."""
    spans = host_spans(profile)
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    _, w0, w1 = windows[0]
    planes = device_planes(profile, chips)
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    ops = collections.Counter()
    modules = collections.Counter()
    busy = 0.0
    gaps = []
    inner = sorted((s for s in spans if s[0] != WINDOW),
                   key=lambda s: s[2] - s[1])
    for plane in planes:
        intervals = []
        for name, s, e in _events(plane, OPS_LINE):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                intervals.append((s, e))
                ops[op_name(name)] += (e - s) / 1e9 / len(planes)
        for name, s, e in _events(plane, MODULES_LINE):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                modules[name.split("(", 1)[0]] += (e - s) / 1e9 / len(planes)
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) / 1e9 / len(planes)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                name = next((n for n, a, b in inner if a <= mid <= b),
                            WINDOW)
                gaps.append((name[len(PREFIX):], (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy,
        "window_s": (w1 - w0) / 1e9,
        "ops": dict(ops),
        "modules": dict(modules),
        "breakdown": {
            "device_ops": [[n, s] for n, s in ops.most_common(top)],
            "idle_gaps": [[n, s] for n, s in gaps[:top]],
        },
    }


def reduce_file(path: str, chips: int = 1, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), chips, top)


def device_seconds(trace: dict, program: str) -> float:
    """Device seconds of the programs whose name contains ``program``."""
    return sum(s for name, s in trace["modules"].items() if program in name)
