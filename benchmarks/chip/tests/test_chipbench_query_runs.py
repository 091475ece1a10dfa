"""A whole run of the query-engine cell at a size a CPU holds, with the
chip check skipped: a sound run is correct, and a run whose answers are
altered where they are produced, or whose answers come from the
reference computed in bfloat16 (the control), is not."""
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import run  # noqa: E402

DATA = CHIP / "tests" / "data"


def tiny_cell(mix: str) -> run.Cell:
    return run.make_cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                         f"tpch_sf1.{mix}", DATA / "tpch_tiny.json",
                         CHIP / "traffic" / f"{mix}.json")


@pytest.fixture
def no_disk_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")


@pytest.mark.parametrize("mix,metrics", [
    ("q1_q6", {"queries_per_s", "query_p95_s", "setup_s"}),
    ("q12", {"queries_per_s", "setup_s"})], ids=["q1_q6", "q12"])
def test_sound_run_is_correct(mix, metrics, no_disk_cache):
    result = run.run_cell(tiny_cell(mix), 2 ** 33 + 5, 0.5, False,
                          require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == metrics
    assert result["checks"]["max_rel_err"]["value"] <= 1e-6
    assert result["checks"]["wrong_keys"]["value"] == 0


def test_every_answer_of_the_window_is_checked(monkeypatch, no_disk_cache):
    cell = tiny_cell("q1_q6")
    compared = []
    compare = cell.driver.compare

    def counted(got, want, keys):
        compared.append(keys)
        return compare(got, want, keys)

    monkeypatch.setattr(cell.driver, "compare", counted)
    result = run.run_cell(cell, 12, 1.0, False, require_chip=False)
    assert result["correct"] and result["attempted"] >= 4
    assert len(compared) == result["attempted"]


def test_kernel_bytes_leave_out_the_padding(no_disk_cache):
    """The tiny lineitem's Q6 reduces a row count that is no power of
    two into one segment; the count is of those rows, not the padded
    call."""
    from repro.engine import compile as engine_compile
    cell = tiny_cell("q1_q6")
    calls = []
    inner = engine_compile.segment_reduce

    def seen(vals, ids, *, num_segments, **kw):
        calls.append((np.shape(vals), np.asarray(ids).copy(), num_segments))
        return inner(vals, ids, num_segments=num_segments, **kw)

    states = []
    check = cell.driver.TpchCell.check

    def keep_state(self):
        states.append(self)
        return check(self)

    engine_compile.segment_reduce = seen
    cell.driver.TpchCell.check = keep_state
    try:
        result = run.run_cell(cell, 3, 0.5, False, require_chip=False)
    finally:
        engine_compile.segment_reduce = inner
        cell.driver.TpchCell.check = check
    assert result["correct"] and calls
    padded = sum(4 * (s[0] * s[1] + s[1] + s[0] * n) for s, _, n in calls)
    live = sum(4 * (s[0] * int((i >= 0).sum()) + int((i >= 0).sum())
                    + s[0] * (int(i.max()) + 1)) for s, i, _ in calls)
    assert live < padded
    assert any((i < 0).any() for _, i, _ in calls)
    assert states[0].spy.bytes == live


def test_answer_altered_in_the_kernel_is_caught(monkeypatch, no_disk_cache):
    from repro.engine import compile as engine_compile
    inner = engine_compile.segment_reduce

    def off_by_a_little(*args, **kwargs):
        return np.asarray(inner(*args, **kwargs)) * (1 + 1e-5)

    monkeypatch.setattr(engine_compile, "segment_reduce", off_by_a_little)
    result = run.run_cell(tiny_cell("q1_q6"), 7, 0.5, False,
                          require_chip=False)
    assert not result["correct"]
    assert result["checks"]["max_rel_err"]["value"] > 1e-6


@pytest.mark.parametrize("mix", ["q1_q6", "q12"])
def test_half_of_each_table_object_left_out_is_caught(mix, monkeypatch,
                                                      no_disk_cache):
    """The scan reads only the first half of every table object's rows."""
    from repro.engine import columnar
    inner = columnar.deserialize

    def first_half(data, columns=None):
        batch = inner(data, columns)
        if bytes(data[:4]) == columnar.FRAME_MAGIC:
            return batch
        return columnar.ColumnBatch({k: v[: len(v) // 2]
                                     for k, v in batch.items()})

    monkeypatch.setattr(columnar, "deserialize", first_half)
    result = run.run_cell(tiny_cell(mix), 21, 0.5, False,
                          require_chip=False)
    assert not result["correct"]
    checks = result["checks"]
    assert checks["max_rel_err"]["value"] > 0.1 \
        or checks["wrong_keys"]["value"] > 0


def test_bfloat16_control_fails_the_limit():
    cell = tiny_cell("q1_q6")
    ref = cell.driver.REF
    tables = cell.driver.generate_tables(cell.config, ["lineitem"], 11)
    limit = cell.config["check"]["limits"]["max_rel_err"]
    worst = 0.0
    for name, params in (("q1", {"delta_days": 90}),
                         ("q6", {"year": 1994, "discount": 0.06,
                                 "quantity": 24})):
        keys = cell.config["queries"][name]["group_keys"]
        got = ref.QUERIES[name](tables, params, ref.BFloat16)
        want = ref.QUERIES[name](tables, params)
        err = cell.driver.compare(got, want, keys)
        worst = max(worst, np.inf if err is None else err)
    assert worst > 3 * limit


def test_reference_matches_the_program_reference_copy():
    """The copied generators and answers agree with the program's own
    numpy reference on the same rows (both are plain numpy)."""
    from repro.engine import queries
    from repro.engine.columnar import ColumnBatch
    cell = tiny_cell("q12")
    ref = cell.driver.REF
    tables = cell.driver.generate_tables(cell.config,
                                         ["lineitem", "orders"], 3)
    li, orders = ColumnBatch(tables["lineitem"]), ColumnBatch(tables["orders"])
    want = queries.q12_reference(li, orders, ref.first_of_year(1995))
    got = ref.q12(tables, {"year": 1995})
    assert cell.driver.compare(got, dict(want), ["l_shipmode"]) == 0.0
    want = queries.q1_reference(li, 75)
    got = ref.q1(tables, {"delta_days": 75})
    assert cell.driver.compare(got, dict(want), ["l_returnflag",
                                                 "l_linestatus"]) < 1e-12
    assert ref.first_of_year(1994) == 731 and ref.first_of_year(1997) == 1827
