"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``."""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs.registry import ARCHS
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.serve.engine import Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published config (default is the "
                         "reduced smoke config)")
    args = ap.parse_args()

    compile_cache.enable()
    cfg = ARCHS[args.arch] if args.full_config else ARCHS[args.arch].reduced()
    mesh = make_mesh(1, 1)
    engine = ServingEngine(cfg, mesh, batch_size=args.batch_size,
                           max_prompt=16, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, rng.integers(4, 16)),
                    max_new_tokens=args.max_new_tokens)
            for i in range(args.requests)]
    t0 = time.time()
    done = engine.serve(reqs)
    wall = time.time() - t0
    for r in done:
        print(f"req {r.request_id}: {r.completion.tolist()}")
    print(f"{len(done)} requests, {wall:.2f}s,",
          engine.cost_report(wall, len(done)))


if __name__ == "__main__":
    main()
