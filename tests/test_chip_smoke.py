"""The phases of ``chip_smoke.py`` at tiny sizes on the CPU, and its
refusal to run anywhere but on a TPU."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.registry import ARCHS

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_engine_phase_matches_references(chip_smoke):
    records = chip_smoke.engine_phase(seed=0, lineitem_rows=50_000,
                                      orders_rows=12_500,
                                      clickstream_rows=40_000)
    assert [r["query"] for r in records] == ["q1", "q6", "q12", "bb_q3"]
    for r in records:
        assert r["max_rel_err"] <= chip_smoke.PARITY_RTOL
        assert r["interpreted_fallbacks"] == 0


def test_model_phase_within_bf16_tolerance(chip_smoke):
    cfg = dataclasses.replace(ARCHS["internlm2-1.8b"].reduced(),
                              dtype="bfloat16")
    r = chip_smoke.model_phase(cfg, seed=0, batch=2, prompt_len=16,
                               new_tokens=4, max_len=24)
    assert r["new_tokens"] == 8
    assert r["logits_max_abs_err"] <= r["logits_tol"]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_refuses_without_a_tpu(tmp_path, where):
    """With no TPU (or with no program beside it) the script exits nonzero
    and prints no result line."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if where == "repo":
        assert "'cpu'" in out.stderr
