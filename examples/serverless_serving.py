"""End-to-end serving driver: batched requests through prefill + decode on
a small model, with per-request latency and elastic-vs-reserved cost
break-even (the paper's Table-6 economics at serve time).

    PYTHONPATH=src python examples/serverless_serving.py
"""
import time

import numpy as np

from repro.configs.registry import ARCHS
from repro.launch.mesh import make_mesh
from repro.serve.engine import Request, ServingEngine


def main() -> None:
    cfg = ARCHS["internlm2-1.8b"].reduced()
    mesh = make_mesh(1, 1)
    engine = ServingEngine(cfg, mesh, batch_size=4, max_prompt=16,
                           max_len=32)

    rng = np.random.default_rng(0)
    requests = [
        Request(i, rng.integers(0, cfg.vocab_size, rng.integers(4, 16)),
                max_new_tokens=8)
        for i in range(10)
    ]
    t0 = time.time()
    done = engine.serve(requests)
    wall = time.time() - t0

    for r in done[:5]:
        print(f"req {r.request_id}: prompt[{len(r.prompt)}] -> "
              f"completion {r.completion.tolist()} "
              f"({r.latency_s:.2f}s batch latency)")
    print(f"{len(done)} requests in {wall:.2f}s "
          f"({len(done) / wall:.1f} req/s)")
    print("cost:", engine.cost_report(wall, len(done)))


if __name__ == "__main__":
    main()
