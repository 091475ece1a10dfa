"""On-chip benchmark: run one cell of ``BENCHMARK.json`` and print its result.

    python3 benchmarks/chip/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name: the cell's configuration file and its driver
(``configs/<config>.json`` and ``configs/<config>.py``), its traffic mix
(``traffic/<mix>.json``) and the module of the mix's kind, which
generates its rounds and drives the window (``traffic/<kind>.py``), and
one reader per metric (``metrics/<metric>.py``). A run

1. refuses any platform but the TPU, and fewer chips than the cell asks;
2. sets up: data from the seed, the system under test, and a
   warm-up of exactly the shapes the window will use (``setup_s``);
3. drives the window with the kind's loop for ``--seconds`` on the host
   clock, with the profiler on when ``--trace 1``;
4. reads the device's peak memory, frees the system, and checks what the
   window produced against the plain reference.

Earlier stdout lines give the set-up split, the work sent, served and
failed, and what compiled inside the window; the last line is the result
as one JSON object. The numbers the check compared go to stderr as its
last lines, and under ``checks`` as the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
from loader import load_module  # noqa: E402
from windowstats import Window  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(Exception):
    """A run that cannot produce a result: exit nonzero, print none."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One resolved cell: its entries in BENCHMARK.json and its files."""
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: object           # traffic/<kind>.py: rounds() and window()
    driver: object
    metrics: dict          # metric name -> its BENCHMARK.json entry, by kind


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, bench_path: Path = ROOT / "BENCHMARK.json"
            ) -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in {bench_path.name}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return make_cell(bench, workload, ROOT / configs[w["config"]]["file"],
                     HERE / "traffic" / f"{w['traffic']}.json", w["chips"])


def make_cell(bench: dict, workload: str, config_path: Path,
              traffic_path: Path, chips: int = 1) -> Cell:
    """A cell from its files; the driver is named by the workload's
    configuration (``<config>.<mix>``), the traffic's loop by the mix's
    ``kind``, the metrics by the workload."""
    driver = workload.split(".", 1)[0]
    traffic = load_json(traffic_path)
    return Cell(
        name=workload, chips=chips, config=load_json(config_path),
        traffic=traffic,
        kind=load_module(HERE / "traffic" / f"{traffic['kind']}.py"),
        driver=load_module(HERE / "configs" / f"{driver}.py"),
        metrics={kind: [m for m in bench[kind] if _applies(m, workload)]
                 for kind in ("end_to_end", "per_layer")})


def read_metrics(entries: list, run) -> dict:
    out = {}
    for m in entries:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    setup_s: float
    window: Window
    counters: dict          # program counters, delta over the window
    trace: dict | None      # devtrace.reduce_profile of the window
    peaks: dict             # peaks.json row of this device kind


class Context:
    """What a configuration's driver is handed to set itself up."""

    def __init__(self, cell: Cell, seed: int, tracing: bool):
        self.config = cell.config
        self.traffic = cell.traffic
        self.kind = cell.kind
        self.seed = seed
        self.tracing = tracing
        self.split: dict = {}
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_dir = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.split[name] = self.split.get(name, 0.0) \
                + time.perf_counter() - t0

    def rounds(self, stream: str):
        """Endless rounds of the cell's mix from one stream of the seed."""
        return self.kind.rounds(self.traffic, self.config, self.seed, stream)

    def span(self, name: str):
        """A host span in the trace, around a call into a layer."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(devtrace.PREFIX + name)

    def on_event(self, event: str, seconds: float, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += seconds
            self.compiles += event == COMPILE_EVENTS[0]


def check_device(chips: int):
    import jax
    platform = jax.default_backend()
    devices = jax.devices()
    if platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} TPU chips; found {len(devices)}")
    return devices


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if None in peaks else max(peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the program is not in this checkout "
                         f"({ROOT / 'src'} is missing)")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    ctx = Context(cell, seed, trace)
    ctx.split["process_start"] = time.perf_counter() - T_START
    with ctx.phase("jax_init"):
        import jax
        devices = check_device(cell.chips) if require_chip else jax.devices()
        from repro.launch import compile_cache
        ctx.cache_dir = compile_cache.enable()
    kind = devices[0].device_kind
    peaks = load_json(HERE / "peaks.json")
    if require_chip and kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    jax.monitoring.register_event_duration_secs_listener(ctx.on_event)
    try:
        return _measure(ctx, cell, devices[:cell.chips], seconds,
                        peaks.get(kind, {}))
    finally:
        jax.monitoring.unregister_event_duration_listener(ctx.on_event)


def _measure(ctx: Context, cell: Cell, devices, seconds: float,
             peaks: dict) -> dict:
    import jax
    state = cell.driver.setup(ctx, devices)
    setup_s = time.perf_counter() - T_START
    setup_compiles = ctx.compiles
    emit({"setup_split_s": ctx.split, "setup_compile_s": ctx.compile_s,
          "setup_compiles": setup_compiles, "setup_s": setup_s,
          "compile_cache": ctx.cache_dir})

    window_rounds = ctx.rounds("window")
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
        if ctx.tracing else None
    c0 = state.counters()
    try:
        if log_dir:
            jax.profiler.start_trace(log_dir)
        try:
            window = cell.kind.window(ctx, state, seconds, window_rounds)
        finally:
            if log_dir:
                jax.profiler.stop_trace()
        c1 = state.counters()
        window_compiles = ctx.compiles - setup_compiles
        reduced = devtrace.reduce_file(devtrace.find_xplane(log_dir),
                                       cell.chips) if log_dir else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    counters = {k: c1[k] - c0.get(k, 0) for k in c1}
    memory_peak = peak_bytes(devices)
    served = state.served()
    emit({"window_s": window.seconds, "rounds": len(window.rounds),
          "round_s": [r.end - r.start for r in window.rounds],
          "window_compiles": window_compiles, **served,
          "counters": counters})
    t0 = time.perf_counter()
    checks = state.check()
    emit({"check_s": time.perf_counter() - t0})
    correct = served["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    run = Run(setup_s=setup_s, window=window, counters=counters,
              trace=reduced, peaks=peaks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": served["attempted"],
              "failed": served["failed"],
              "metrics": read_metrics(
                  cell.metrics["per_layer" if reduced else "end_to_end"],
                  run),
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
