"""BENCHMARK.json resolves by name to the files that implement it, keeps
to the benchmark's contract, and the command refuses to run where it
cannot measure the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell to
    # compile and 1200 s spare must fit 43,200 s with 24 cells.
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for kind, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        for m in BENCH[kind]:
            assert set(m) - {"workloads"} == keys
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len({m["name"] for k in ("end_to_end", "per_layer")
                for m in BENCH[k]}) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in CELLS:
        mine = {n for n, m in e2e.items() if cell in m.get("workloads",
                                                            CELLS)}
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in BENCH["per_layer"]
                  if cell in m.get("workloads", CELLS)]
        assert layers
        for m in layers:
            assert m["moves"] in mine, (cell, m["name"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.resolve(cell)
    assert c.chips == 1 and callable(c.driver.setup)
    assert callable(c.kind.rounds) and callable(c.kind.window)
    names = [m["name"] for kind in c.metrics.values() for m in kind]
    for name in names:
        reader = run.load_module(CHIP / "metrics" / f"{name}.py")
        assert callable(reader.read)


def test_every_file_is_named_by_the_benchmark():
    metrics = {m["name"] for k in ("end_to_end", "per_layer")
               for m in BENCH[k]}
    assert {p.stem for p in (CHIP / "metrics").glob("*.py")} == metrics
    traffics = {w["traffic"] for w in BENCH["workloads"]}
    assert {p.stem for p in (CHIP / "traffic").glob("*.json")} == traffics
    kinds = {json.loads((CHIP / "traffic" / f"{t}.json").read_text())["kind"]
             for t in traffics}
    assert {p.stem for p in (CHIP / "traffic").glob("*.py")} == kinds
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert (CHIP / "configs" / f"{c['name']}.py").is_file()


def _run_command(cwd: Path, workload: str, env_extra: dict):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_refuses_a_cpu_platform():
    p = _run_command(ROOT, "tpch_sf1.q1_q6", {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path, "tpch_sf1.q1_q6", {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_an_unknown_workload():
    p = _run_command(ROOT, "tpch_sf1.nothing", {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and "no workload" in p.stderr
