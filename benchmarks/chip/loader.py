"""Load a benchmark file by its path: configuration drivers, references
and metric readers are found by name, not imported as a package."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_module(path: Path):
    name = "chipbench_" + "_".join(
        Path(path).resolve().relative_to(HERE).with_suffix("").parts
    ).replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module
