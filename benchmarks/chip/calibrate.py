"""Readings that a cell's limits are set from, on the chip at the cell's
own sizes: the program's compared numbers over many seeds, and the
control's, which computes the plain reference one precision below what
the configuration states and puts it in the program's place.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds <a,b,...> --control-seeds <c,d,...> --seconds <s>

Each seed is a whole run of the harness, set-up, window and check, in one
process. The program's readings are the run's own check; the control's
answers come from the float64 reference's bfloat16 twin, on every query
that the same window answered. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def control_readings(cell, state) -> dict:
    """The bfloat16 reference in the program's place, on every answered
    query of the window, compared as the check compares the program."""
    driver = cell.driver
    worst, wrong = 0.0, 0
    for name, params, _ in driver.answered(state.served_items):
        got = driver.REF.QUERIES[name](state.tables, params,
                                       driver.REF.BFloat16)
        want = driver.REF.QUERIES[name](state.tables, params)
        keys = cell.config["queries"][name]["group_keys"]
        err = driver.compare(got, want, keys)
        if err is None:
            wrong += 1
        else:
            worst = max(worst, err)
    return {"control_max_rel_err": worst, "control_wrong_keys": wrong}


def readings(cell, seeds, control_seeds, seconds: float) -> None:
    driver = cell.driver
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        captured = {}
        original = driver.TpchCell.check

        def check(self):
            captured["state"] = self
            return original(self)

        driver.TpchCell.check = check
        try:
            result = run.run_cell(cell, seed, seconds, trace=False)
        finally:
            driver.TpchCell.check = original
        rec = {"seed": seed, "queries": result["attempted"],
               "correct": result["correct"],
               "program_max_rel_err":
                   result["checks"]["max_rel_err"]["value"],
               "program_wrong_keys": result["checks"]["wrong_keys"]["value"]}
        if seed in control_seeds:
            rec.update(control_readings(cell, captured["state"]))
        rec["seconds"] = time.perf_counter() - t0
        run.emit(rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True,
                    help="window of each run")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    readings(run.resolve(args.workload), seeds, control, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
