"""The TPCx-BB Q3 cell at a size a CPU holds, with the chip check
skipped: a sound run is correct; sessions cut inside a fragment, or a
window of six views, are not; its metric readers read the program's
spans and counters, and read nothing from a program without them; and a
program whose query builder lacks the cell's parameters fails before it
generates any data."""
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import run  # noqa: E402

DATA = CHIP / "tests" / "data"
CELL = "tpcxbb_sf1.bb_q3"


def tiny_cell() -> run.Cell:
    return run.make_cell(run.load_json(run.ROOT / "BENCHMARK.json"), CELL,
                         DATA / "tpcxbb_tiny.json",
                         CHIP / "traffic" / "bb_q3.json")


@pytest.fixture
def no_disk_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")


def test_sound_run_is_correct(no_disk_cache):
    result = run.run_cell(tiny_cell(), 2 ** 33 + 5, 0.5, False,
                          require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    assert result["checks"]["max_abs_err"]["value"] == 0
    assert result["checks"]["wrong_keys"]["value"] == 0


def test_sessions_cut_inside_a_fragment_are_caught(monkeypatch,
                                                   no_disk_cache):
    """Each session fragment's rows run through the UDF in two halves,
    as sessions cut at object boundaries would."""
    from repro.engine import compile as engine_compile
    from repro.engine.columnar import ColumnBatch
    inner = engine_compile._run_udf

    def halves(batch, op):
        n = batch.num_rows // 2
        return ColumnBatch.concat([
            inner(ColumnBatch({k: v[:n] for k, v in batch.items()}), op),
            inner(ColumnBatch({k: v[n:] for k, v in batch.items()}), op)])

    monkeypatch.setattr(engine_compile, "_run_udf", halves)
    result = run.run_cell(tiny_cell(), 2 ** 33 + 9, 0.5, False,
                          require_chip=False)
    assert not result["correct"]
    checks = result["checks"]
    assert checks["max_abs_err"]["value"] > 0 \
        or checks["wrong_keys"]["value"] > 0


def test_a_window_of_six_views_is_caught(monkeypatch, no_disk_cache):
    cell = tiny_cell()
    inner = cell.driver.logical_query

    def six_views(params, *args):
        return inner({**params, "views": 6}, *args)

    monkeypatch.setattr(cell.driver, "logical_query", six_views)
    result = run.run_cell(cell, 2 ** 33 + 11, 0.5, False,
                          require_chip=False)
    assert not result["correct"]
    checks = result["checks"]
    assert checks["max_abs_err"]["value"] > 0 \
        or checks["wrong_keys"]["value"] > 0


def test_reference_matches_the_program_reference():
    """The independent walk and the program's own numpy reference agree
    on the same rows."""
    from repro.engine import queries
    from repro.engine.columnar import ColumnBatch
    cell = tiny_cell()
    tables = cell.driver.generate_tables(cell.config, 3)
    sessions = cell.driver.REF.Sessions(tables["clickstreams"])
    cats = tables["item"]["i_category_id"]
    for target in (1, 8):
        params = {"target_category": target, "views": 5, "seconds": 864000}
        want = queries.bb_q3_reference(ColumnBatch(tables["clickstreams"]),
                                       ColumnBatch(tables["item"]), target)
        got = sessions.bb_q3(cats, params)
        assert dict(zip(got["viewed_item"].tolist(),
                        got["views"].tolist())) == want
        assert want


def test_bfloat16_control_fails_the_limits():
    """The reference with its timestamps in bfloat16 in the program's
    place is not correct."""
    cell = tiny_cell()
    tables = cell.driver.generate_tables(cell.config, 11)
    ref = cell.driver.REF
    exact = ref.Sessions(tables["clickstreams"])
    control = ref.Sessions(tables["clickstreams"], ref.bfloat16)
    cats = tables["item"]["i_category_id"]
    wrong = worst = 0
    for target in range(10):
        params = {"target_category": target, "views": 5, "seconds": 864000}
        err = cell.driver.compare(control.bb_q3(cats, params),
                                  exact.bb_q3(cats, params))
        if err is None:
            wrong += 1
        else:
            worst = max(worst, err)
    limits = cell.config["check"]["limits"]
    assert wrong > limits["wrong_keys"] or worst > limits["max_abs_err"]


def test_a_builder_without_the_window_parameters_fails_at_once(
        monkeypatch, no_disk_cache):
    from repro.engine import queries

    def old_builder(item_table_key, target_category=3, window=5,
                    shuffle_partitions=8):
        raise AssertionError("not reached")

    monkeypatch.setattr(queries, "bb_q3_logical", old_builder)
    cell = tiny_cell()
    generated = []
    monkeypatch.setattr(cell.driver, "generate_tables",
                        lambda *a: generated.append(a))
    ctx = run.Context(cell, 5, tracing=False)
    with pytest.raises(TypeError):
        cell.driver.setup(ctx, jax.devices())
    assert generated == []


def test_session_window_bytes_count_rows_and_calls():
    driver = tiny_cell().driver
    assert driver.session_window_bytes(0, 0, 100) == 0
    assert driver.session_window_bytes(2, 1000, 100) \
        == 13 * 1000 + 8 * 100 * 2


# ---------------------------------------------------------------------------
# Metric readers
# ---------------------------------------------------------------------------

READERS = ["udf_ms.query", "exchange_mb.query",
           "session_window_roofline.query"]


def _readers():
    return {name: run.load_module(CHIP / "metrics" / f"{name}.py")
            for name in READERS}


def test_a_tiny_window_reads_every_new_metric(no_disk_cache):
    cell = tiny_cell()
    ctx = run.Context(cell, 2 ** 33 + 17, tracing=False)
    state = cell.driver.setup(ctx, jax.devices())
    c0 = state.counters()
    window = cell.kind.window(ctx, state, 0.5, ctx.rounds("window"))
    c1 = state.counters()
    counters = {k: c1[k] - c0[k] for k in c1}
    trace = {"modules": {"jit_session_window(12)": 0.002, "jit_other": 1.0}}
    r = run.Run(setup_s=1.0, window=window, counters=counters, trace=trace,
                peaks={"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9})
    got = {name: reader.read(r) for name, reader in _readers().items()}
    queries = window.total("queries")
    assert counters["session_window_calls"] == 8 * queries
    assert counters["session_window_rows"] == 40000 * queries
    assert counters["exchange_bytes"] > 0
    assert got["exchange_mb.query"] == pytest.approx(
        counters["exchange_bytes"] / 1e6 / queries)
    assert got["udf_ms.query"] > 0
    assert got["session_window_roofline.query"] == pytest.approx(
        100 * counters["session_window_bytes"] / 819e9 / 0.002)


def _serve_call(attrs: dict):
    ms = 1_000_000

    def rec(id, parent, name, start, end, **a):
        return SimpleNamespace(id=id, parent=parent, name=name,
                               start_ns=start * ms, end_ns=end * ms, attrs=a)

    return [rec(4, 3, "engine.device", 1030, 1040, kernel="session_window"),
            rec(3, 2, "engine.udf", 1020, 1050, udf="clicks_before_purchase"),
            rec(2, 1, "engine.fragment", 1010, 1060, **attrs),
            rec(1, None, "engine.serve", 1000, 1100)]


def test_readers_on_records_worked_out_by_hand(monkeypatch):
    from repro.core import spans
    from windowstats import RoundRecord, Window
    records = _serve_call({"exchange_bytes": 3_000_000})
    monkeypatch.setattr(spans, "RING", SimpleNamespace(
        snapshot=lambda: (records, 0)))
    window = Window(start=1.0, rounds=[RoundRecord(1.0, 1.2,
                                                   {"queries": 2}, {})])
    r = run.Run(setup_s=1.0, window=window, counters={}, trace=None,
                peaks={})
    readers = _readers()
    # udf self time 30 - 10 ms over 2 queries; 3 MB over 2 queries
    assert readers["udf_ms.query"].read(r) == pytest.approx(10.0)
    assert readers["exchange_mb.query"].read(r) == pytest.approx(1.5)
    assert readers["session_window_roofline.query"].read(r) is None
    # A program whose spans carry no counters reads nothing.
    monkeypatch.delattr(spans, "count")
    assert readers["exchange_mb.query"].read(r) is None


def test_a_program_without_udf_spans_reads_nothing(monkeypatch):
    from repro.core import spans
    from windowstats import RoundRecord, Window
    records = [x for x in _serve_call({}) if x.name != "engine.udf"]
    monkeypatch.setattr(spans, "RING", SimpleNamespace(
        snapshot=lambda: (records, 0)))
    window = Window(start=1.0, rounds=[RoundRecord(1.0, 1.2,
                                                   {"queries": 1}, {})])
    r = run.Run(setup_s=1.0, window=window, counters={},
                trace={"modules": {}}, peaks={})
    readers = _readers()
    assert readers["udf_ms.query"].read(r) is None
    assert readers["session_window_roofline.query"].read(r) is None
    assert readers["exchange_mb.query"].read(r) == 0.0
    assert np.isfinite(readers["exchange_mb.query"].read(r))


def test_exchange_reads_on_a_tiny_q12_window():
    """exchange_mb.query also reads on tpch_sf1.q12, whose join shuffles
    both scans."""
    cell = run.make_cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                         "tpch_sf1.q12", DATA / "tpch_tiny.json",
                         CHIP / "traffic" / "q12.json")
    assert "exchange_mb.query" in {m["name"]
                                   for m in cell.metrics["per_layer"]}
    ctx = run.Context(cell, 2 ** 33 + 19, tracing=False)
    state = cell.driver.setup(ctx, jax.devices())
    try:
        window = cell.kind.window(ctx, state, 0.5, ctx.rounds("window"))
    finally:
        state.spy.remove()
    r = run.Run(setup_s=1.0, window=window, counters={}, trace={},
                peaks={})
    got = _readers()["exchange_mb.query"].read(r)
    assert isinstance(got, float) and got > 0
