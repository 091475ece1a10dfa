"""The per-layer host times read from the program's spans: the reduction
on records worked out by hand, and a whole window of each mix at a size
a CPU holds."""
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import run  # noqa: E402
import spanstats  # noqa: E402
from windowstats import RoundRecord, Window  # noqa: E402

DATA = CHIP / "tests" / "data"
READERS = ["plan_ms.query", "schedule_ms.query", "store_ms.query",
           "codec_ms.query", "assemble_ms.query", "host_ops_ms.query",
           "device_wait_ms.query", "finalize_ms.query", "h2d_mb.query",
           "span_coverage.query"]
MS = 1_000_000


def rec(id, parent, name, start, end, **attrs):
    """A closed span; times in ms."""
    return SimpleNamespace(id=id, parent=parent, name=name,
                           start_ns=start * MS, end_ns=end * MS, attrs=attrs)


def serve_call(base: int, t0: int) -> list:
    """One serve call of 100 ms starting at ``t0``, in closing order:
    plan 10 (a 2 ms read inside), schedule 80 with one fragment of 70
    (read 5, decode 10, assemble 4, ops 40 with a 25 ms device call,
    put 5), and finalize 6; serve itself keeps 4 ms, the fragment 6."""
    b, t = base, t0
    return [
        rec(b + 2, b + 1, "engine.store.get", t + 1, t + 3, tier="object"),
        rec(b + 1, b, "engine.plan", t, t + 10),
        rec(b + 5, b + 4, "engine.store.get", t + 12, t + 17),
        rec(b + 6, b + 4, "engine.codec.decode", t + 17, t + 27),
        rec(b + 11, b + 4, "engine.fragment.assemble", t + 27, t + 31),
        rec(b + 8, b + 7, "engine.device", t + 34, t + 59,
            h2d_bytes=2_000_000),
        rec(b + 7, b + 4, "engine.ops", t + 31, t + 71),
        rec(b + 9, b + 4, "engine.store.put", t + 71, t + 76),
        rec(b + 4, b + 3, "engine.fragment", t + 12, t + 82),
        rec(b + 3, b, "engine.schedule", t + 10, t + 90),
        rec(b + 10, b, "engine.finalize", t + 90, t + 96),
        rec(b, None, "engine.serve", t, t + 100, requests=1),
    ]


def test_self_time_by_layer_of_one_call():
    s = spanstats.reduce_spans(serve_call(1, 1000), 0, 1000 * MS, 1100 * MS)
    want = {"plan": 8, "schedule": 10, "store": 12, "codec": 10,
            "assemble": 4, "host_ops": 15, "device_wait": 25, "finalize": 6}
    assert s["layers_s"] == pytest.approx({k: v / 1e3
                                           for k, v in want.items()})
    assert s["rest_s"] == pytest.approx(0.010)
    assert s["serve_s"] == pytest.approx(0.1)
    assert sum(s["layers_s"].values()) + s["rest_s"] \
        == pytest.approx(s["serve_s"])
    assert s["h2d_bytes"] == 2_000_000
    assert s["calls"] == 1 and s["spans"] == 12


def test_only_calls_inside_the_window_count():
    records = (serve_call(1, 500) + serve_call(101, 1000)
               + serve_call(201, 1200) + serve_call(301, 1350)
               + [rec(401, None, "engine.store.get", 1210, 1220)])
    s = spanstats.reduce_spans(records, 0, 1000 * MS, 1300 * MS)
    assert s["calls"] == 2 and s["spans"] == 24
    assert s["serve_s"] == pytest.approx(0.2)
    assert s["layers_s"]["store"] == pytest.approx(0.024)


def test_readers_divide_by_the_window_queries(monkeypatch):
    from repro.core import spans
    records = serve_call(1, 1000) + serve_call(101, 1150)
    monkeypatch.setattr(spans, "RING", SimpleNamespace(
        snapshot=lambda: (records, 0)))
    window = Window(start=1.0, rounds=[
        RoundRecord(1.0, 1.12, {"queries": 1}, {}),
        RoundRecord(1.14, 1.26, {"queries": 1}, {})])
    r = run.Run(setup_s=1.0, window=window, counters={}, trace={},
                peaks={})
    got = {name: run.load_module(CHIP / "metrics" / f"{name}.py").read(r)
           for name in READERS}
    assert got == pytest.approx({
        "plan_ms.query": 8.0, "schedule_ms.query": 10.0,
        "store_ms.query": 12.0, "codec_ms.query": 10.0,
        "assemble_ms.query": 4.0, "host_ops_ms.query": 15.0, "device_wait_ms.query": 25.0,
        "finalize_ms.query": 6.0, "h2d_mb.query": 2.0,
        "span_coverage.query": 90.0})


def test_records_of_the_window_pushed_out_refuse_the_reading():
    records = serve_call(101, 1000)
    with pytest.raises(spanstats.DroppedSpans):
        spanstats.reduce_spans(records[3:], 3, 1000 * MS, 1100 * MS)
    # Drops that all closed before the window began are harmless.
    older = serve_call(1, 500)[5:] + records
    s = spanstats.reduce_spans(older, 5, 1000 * MS, 1100 * MS)
    assert s["calls"] == 1 and s["serve_s"] == pytest.approx(0.1)


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    import repro.core
    from repro.core import spans
    records = serve_call(1, 1000)
    monkeypatch.setattr(spans, "RING", SimpleNamespace(
        snapshot=lambda: (records, 0)))
    window = Window(start=1.0, rounds=[RoundRecord(1.0, 1.1,
                                                   {"queries": 1}, {})])
    r = run.Run(setup_s=1.0, window=window, counters={}, trace={}, peaks={})
    readers = [run.load_module(CHIP / "metrics" / f"{name}.py")
               for name in READERS]
    assert None not in [reader.read(r) for reader in readers]
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert [reader.read(r) for reader in readers] == [None] * len(READERS)


@pytest.mark.parametrize("mix", ["q1_q6", "q12"])
def test_a_tiny_window_reads_every_layer(mix):
    from repro.core import spans
    cell = run.make_cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                         f"tpch_sf1.{mix}", DATA / "tpch_tiny.json",
                         CHIP / "traffic" / f"{mix}.json")
    ctx = run.Context(cell, 2 ** 33 + 17, tracing=False)
    state = cell.driver.setup(ctx, jax.devices())
    try:
        window = cell.kind.window(ctx, state, 0.5, ctx.rounds("window"))
    finally:
        state.spy.remove()
    r = run.Run(setup_s=1.0, window=window, counters={}, trace={}, peaks={})
    got = {name: run.load_module(CHIP / "metrics" / f"{name}.py").read(r)
           for name in READERS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert got["h2d_mb.query"] > 0 and got["span_coverage.query"] > 50
    s = spanstats.window_stats(r)
    assert s["calls"] == s["queries"] == window.total("queries")
    assert sum(s["layers_s"].values()) + s["rest_s"] \
        == pytest.approx(s["serve_s"], rel=0.01)
    records, _ = spans.RING.snapshot()
    by_id = {x.id: x for x in records}
    start, end = round(window.start * 1e9), round(window.end * 1e9)
    puts_in_fragments = [
        x for x in records if x.name == "engine.store.put"
        and start <= x.start_ns <= end
        and getattr(by_id.get(x.parent), "name", "") == "engine.fragment"]
    # Each query writes its answer; a q12 also shuffles its scans' rows
    # into 8 partitions.
    assert len(puts_in_fragments) >= s["queries"]
    if mix == "q12":
        assert len(puts_in_fragments) >= 8 * s["queries"]
