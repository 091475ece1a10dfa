"""The trace reduction: busy time as the union of device operations inside
the window, device time by operation and program, idle gaps named by the
innermost host span, on a hand-built profile and on a small trace
recorded on a TPU v5e."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402

RECORDED = CHIP / "tests" / "data" / "v5e_small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench:window", 100, 1000),
        ev("bench:round", 100, 500),
        ev("bench:ServingEngine.serve", 150, 300),
        ev("bench:round", 650, 450),
        ev("other", 0, 5000),
    ])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("fusion.1", 50, 100),      # clipped to [100, 150)
            ev("fusion.1", 200, 100),     # [200, 300)
            ev("copy.2", 250, 100),       # overlaps: busy to 350
            ev("fusion.1", 1000, 500),    # clipped to [1000, 1100)
        ]),
        NS(name="XLA Modules", events=[ev("jit_decode_step(3)", 200, 150),
                                       ev("jit_other(4)", 1000, 50)]),
    ])
    return NS(planes=[host, device,
                      NS(name="/device:TPU:1", lines=[])])


def test_busy_window_ops_and_programs():
    r = devtrace.reduce_profile(profile(), chips=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    # Union of [100,150), [200,350), [1000,1100): 300 ns.
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(250e-9)
    assert r["ops"]["copy.2"] == pytest.approx(100e-9)
    assert devtrace.device_seconds(r, "decode_step") == pytest.approx(150e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = devtrace.reduce_profile(profile(), chips=1)["breakdown"][
        "idle_gaps"]
    # [350,1000) mid 675 lies in the second round; [150,200) mid 175 in
    # ServingEngine.serve.
    assert gaps[0] == ["round", pytest.approx(650e-9)]
    assert gaps[1] == ["ServingEngine.serve", pytest.approx(50e-9)]
    assert len(gaps) == 2


def test_a_trace_without_a_window_or_device_is_refused():
    p = profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    with pytest.raises(RuntimeError, match="bench:window"):
        devtrace.reduce_profile(p)
    p = profile()
    p.planes = p.planes[:1]
    with pytest.raises(RuntimeError, match="device plane"):
        devtrace.reduce_profile(p)


def test_recorded_v5e_trace():
    """Three rounds, each a small jitted program named ``decode_step``
    inside a ``ServingEngine.serve`` span, a 5 ms sleep and a
    ``segment_reduce`` call, with 10 ms sleeps between rounds."""
    r = devtrace.reduce_file(str(RECORDED))
    assert r["window_s"] == pytest.approx(0.055584547)
    assert 0.0004 < r["busy_s"] < 0.0006
    assert set(r["modules"]) == {"jit_decode_step",
                                 "jit__segment_reduce_2d", "jit_ravel"}
    assert devtrace.device_seconds(r, "segment_reduce") \
        == pytest.approx(0.000442788)
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "%_segment_reduce_2d.1 = f32[3,128]"
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) == 10
    assert {g[0] for g in gaps} <= {"window", "round",
                                    "ServingEngine.serve"}
    assert gaps[0][0] == "window" and 0.010 < gaps[0][1] < 0.013
    assert [g[0] for g in gaps[3:6]] == ["round"] * 3
