"""Runtime-variability model (core.variability) and simulation clock
(core.simulation): Table 5 golden MR/CoV values, hash-salt-independent
sampling (subprocess sweep over PYTHONHASHSEED), the shared cov->sigma
conversion the adaptive speculation barrier reuses, and SimClock event
ordering semantics."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import variability
from repro.core.simulation import SimClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# variability: cov_sigma + Table 5 goldens
# ---------------------------------------------------------------------------

def test_cov_sigma_roundtrip():
    # sigma = sqrt(ln(1 + cov^2)); a lognormal with that sigma has
    # exactly the requested coefficient of variation.
    for cov in (5.0, 22.65, 50.0):
        sigma = variability.cov_sigma(cov)
        realized = math.sqrt(math.exp(sigma ** 2) - 1.0)
        assert realized == pytest.approx(cov / 100.0, rel=1e-12)
    assert variability.cov_sigma(22.65) == pytest.approx(0.22367, abs=1e-4)


# Golden values for table5(runs=32, seed=0) under the crc32-stable
# sampler; regenerate with
#   PYTHONPATH=src python -c "from repro.core import variability; \
#       print(variability.table5())"
TABLE5_GOLDEN = {
    "us-east-1": {"cold_mr": 1.0, "cold_cov": 22.58,
                  "warm_mr": 1.0, "warm_cov": 5.79},
    "eu-west-1": {"cold_mr": 1.5365, "cold_cov": 4.67,
                  "warm_mr": 1.5015, "warm_cov": 9.93},
    "ap-northeast-1": {"cold_mr": 0.9774, "cold_cov": 6.96,
                       "warm_mr": 0.9532, "warm_cov": 6.16},
}


def test_table5_matches_goldens():
    table = variability.table5()
    assert set(table) == set(TABLE5_GOLDEN)
    for region, want in TABLE5_GOLDEN.items():
        got = table[region]
        for k, v in want.items():
            assert got[k] == pytest.approx(v, rel=0.01), (region, k)
    # The modeled CoVs stay within a sane band of the paper's Table 5
    # inputs (sampled statistics wander around the configured CoV).
    profs = {r: p for r, p in variability.REGIONS.items()}
    for region, got in table.items():
        assert got["cold_cov"] == pytest.approx(
            profs[region].cold_cov, rel=0.35)


def test_sampling_is_hash_salt_independent():
    """``sample_suite_runtimes`` once seeded its per-(region, cold)
    stream with Python's salted ``hash``; the crc32 stream must yield
    identical draws in any process."""
    code = ("from repro.core import variability\n"
            "import json\n"
            "t = variability.table5(runs=8, seed=3)\n"
            "print(json.dumps(t, sort_keys=True, default=float))\n")
    seen = set()
    for seed in ("0", "1", "1234"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH="src" + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr
        seen.add(out.stdout.strip())
    assert len(seen) == 1


def test_streams_differ_by_region_and_temperature():
    a = variability.sample_suite_runtimes("us-east-1", cold=True, runs=16)
    b = variability.sample_suite_runtimes("us-east-1", cold=False, runs=16)
    c = variability.sample_suite_runtimes("eu-west-1", cold=True, runs=16)
    assert not np.allclose(a, b) and not np.allclose(a, c)
    # Same arguments, same draws.
    np.testing.assert_array_equal(
        a, variability.sample_suite_runtimes("us-east-1", cold=True,
                                             runs=16))


# ---------------------------------------------------------------------------
# simulation: SimClock semantics
# ---------------------------------------------------------------------------

def test_simclock_runs_events_in_time_order():
    clock = SimClock()
    fired = []
    clock.at(2.0, lambda: fired.append("b"))
    clock.at(1.0, lambda: fired.append("a"))
    clock.after(3.0, lambda: fired.append("c"))
    assert clock.pending() == 3
    assert clock.peek() == 1.0
    clock.run()
    assert fired == ["a", "b", "c"]
    assert clock.now() == 3.0
    assert clock.pending() == 0 and clock.peek() is None


def test_simclock_run_until_stops_and_resumes():
    clock = SimClock()
    fired = []
    for t in (1.0, 2.0, 3.0):
        clock.at(t, lambda t=t: fired.append(t))
    clock.run(until=2.0)
    assert fired == [1.0, 2.0]
    assert clock.pending() == 1
    clock.run()
    assert fired == [1.0, 2.0, 3.0]


def test_simclock_rejects_past_events():
    clock = SimClock()
    clock.advance(5.0)
    with pytest.raises(ValueError):
        clock.at(4.0, lambda: None)


def test_simclock_fifo_tie_order():
    clock = SimClock()
    fired = []
    for name in ("first", "second", "third"):
        clock.at(1.0, lambda n=name: fired.append(n))
    clock.run()
    assert fired == ["first", "second", "third"]


def test_simclock_events_can_schedule_events():
    clock = SimClock()
    fired = []

    def chain():
        fired.append(clock.now())
        if clock.now() < 3.0:
            clock.after(1.0, chain)

    clock.after(1.0, chain)
    clock.run()
    assert fired == [1.0, 2.0, 3.0]
