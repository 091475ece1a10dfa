"""Smoke run on the TPU: the query engine's main path and one full-width model.

    python chip_smoke.py [--seed N]       # one chip: phases engine + model
    python chip_smoke.py --four-chips     # four chips: sharded model only

Phase ``engine`` generates TPC-H/TPCx-BB-shaped tables from the seed
(lineitem 6M rows in 4 partitions, orders 1.5M, clickstreams 4M, item to
match), serves TPC-H Q1, Q6, Q12 and TPCx-BB Q3 through ``QueryServer``
on the default ``jit`` backend, and checks every result against the
query's numpy reference under the float contract of ``docs/BACKENDS.md``
(exact group keys, aggregates at rtol=1e-6) with no interpreted join
fallback. Phase ``model`` serves internlm2-1.8b at its published widths
in bfloat16 through ``ServingEngine`` and checks its prefill logits
against a float32 forward of the same weights. ``--four-chips`` serves
the same model on a (data=1, model=4) mesh against one device, checks
that the parameters are split four ways, and compares one training step
on a (data=2, model=2) mesh with one device.

One line per measurement goes to stdout; the last line is a JSON object
naming the device. The script exits nonzero, without that line, when
JAX finds no TPU or any check fails. It runs in one process: a second
process could not reach a chip this one holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

PARITY_RTOL = 1e-6                # docs/BACKENDS.md aggregate contract
BF16_UNIT_ROUNDOFF = 2.0 ** -8
# bf16 roundings per layer on the way to the residual stream, counted in
# models/: 2 norm outputs, q/k/v projections (3), rotary q and k (2),
# softmax probabilities, attention output, its projection, gate, up,
# sigmoid, silu product, gated product, down projection, 2 residual adds.
ROUNDINGS_PER_LAYER = 18
# Chance that the largest logit error of a run exceeds the bound when the
# errors are as large as the model predicts.
EXCEEDANCE = 1e-3
INIT_LOGIT_STD = 0.02             # embed_init: std of the lm_head entries


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def peak_device_bytes(devices=None):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in (devices or jax.devices()[:1])]
    return None if None in peaks else max(peaks)


# ---------------------------------------------------------------------------
# Phase engine
# ---------------------------------------------------------------------------

def _compare(name: str, got, want, keys: list[str]) -> float:
    """Exact group keys and aggregates at ``PARITY_RTOL``; returns the
    worst relative error, raises on a breach."""
    if sorted(got) != sorted(want) or got.num_rows != want.num_rows:
        raise AssertionError(
            f"{name}: result shape {sorted(got)} x {got.num_rows} rows, "
            f"reference {sorted(want)} x {want.num_rows} rows")
    og = np.lexsort([np.asarray(got[k]) for k in reversed(keys)]) \
        if keys else np.arange(got.num_rows)
    ow = np.lexsort([np.asarray(want[k]) for k in reversed(keys)]) \
        if keys else np.arange(want.num_rows)
    for k in keys:
        if not np.array_equal(np.asarray(got[k])[og],
                              np.asarray(want[k])[ow]):
            raise AssertionError(f"{name}: group keys {k!r} differ")
    worst = 0.0
    for col in want:
        if col in keys:
            continue
        g = np.asarray(got[col], np.float64)[og]
        w = np.asarray(want[col], np.float64)[ow]
        diff = np.abs(g - w)
        if np.any(diff > PARITY_RTOL * np.abs(w)):
            i = int(np.argmax(diff - PARITY_RTOL * np.abs(w)))
            raise AssertionError(
                f"{name}: {col!r} = {g[i]!r}, reference {w[i]!r}: relative "
                f"error {diff[i] / abs(w[i]) if w[i] else math.inf:.3g} "
                f"> rtol {PARITY_RTOL}")
        rel = diff / np.where(w == 0, 1.0, np.abs(w))
        worst = max(worst, float(rel.max(initial=0.0)))
    return worst


def engine_phase(seed: int, lineitem_rows: int = 6_000_000,
                 orders_rows: int = 1_500_000,
                 clickstream_rows: int = 4_000_000,
                 partitions: int = 4) -> list[dict]:
    """Serve Q1, Q6, Q12 and BB-Q3 twice each (cold, then warm) and check
    every result against its reference. Returns one record per query."""
    from repro.core.storage_service import ObjectStore
    from repro.engine import columnar, datagen, queries
    from repro.engine import compile as engine_compile
    from repro.engine.columnar import ColumnBatch
    from repro.serve.query_server import QueryRequest, QueryServer

    t0 = time.perf_counter()
    store = ObjectStore()
    n_items = max(8, clickstream_rows // 128)     # gen_clickstreams default
    keys = {
        "lineitem": datagen.load_table(store, "lineitem", lineitem_rows,
                                       partitions, seed=seed),
        "orders": datagen.load_table(store, "orders", orders_rows, 1,
                                     seed=seed + 1),
        "clickstreams": datagen.load_table(store, "clickstreams",
                                           clickstream_rows, partitions,
                                           seed=seed + 2),
        "item": datagen.load_table(store, "item", n_items, 1, seed=seed + 3),
    }
    parts = {t: [columnar.deserialize(store.get(k)) for k in ks]
             for t, ks in keys.items()}
    full = {t: ColumnBatch.concat(ps) for t, ps in parts.items()}
    emit({"phase": "engine", "step": "datagen", "rows": {
        t: b.num_rows for t, b in full.items()},
        "seconds": time.perf_counter() - t0})

    bb_views = queries.bb_q3_reference(full["clickstreams"], full["item"])
    items = np.asarray(sorted(bb_views), np.int64)
    cases = [
        ("q1", queries.q1_logical(), ["l_returnflag", "l_linestatus"],
         lambda: queries.q1_reference(full["lineitem"]),
         full["lineitem"].num_rows),
        ("q6", queries.q6_logical(), [],
         lambda: ColumnBatch({"revenue": np.asarray(
             [queries.q6_reference(full["lineitem"])])}),
         full["lineitem"].num_rows),
        ("q12", queries.q12_logical(), ["l_shipmode"],
         lambda: queries.q12_reference(full["lineitem"], full["orders"]),
         full["lineitem"].num_rows + full["orders"].num_rows),
        ("bb_q3", queries.bb_q3_logical(keys["item"][0]), ["viewed_item"],
         lambda: ColumnBatch({"viewed_item": items, "views": np.asarray(
             [bb_views[int(i)] for i in items], np.float64)}),
         full["clickstreams"].num_rows),
    ]
    server = QueryServer(store, result_cache=False)
    for table in ("lineitem", "orders", "clickstreams"):
        server.register_table(table, keys[table])

    records = []
    for name, query, group_keys, reference, rows in cases:
        fallbacks = engine_compile.FALLBACK_STATS["int32_join"]
        walls, results = [], []
        for _ in range(2):                    # cold (compiles), then warm
            t0 = time.perf_counter()
            served = server.serve([QueryRequest(query)]).queries[0]
            walls.append(time.perf_counter() - t0)
            if served.result.failure is not None:
                raise AssertionError(f"{name}: {served.result.failure}")
            results.append(served.result.result)
        fallbacks = engine_compile.FALLBACK_STATS["int32_join"] - fallbacks
        want = reference()
        err = max(_compare(name, got, want, group_keys) for got in results)
        record = {"phase": "engine", "query": name, "rows": rows,
                  "cold_s": walls[0], "warm_s": walls[1],
                  "compile_s": walls[0] - walls[1],
                  "max_rel_err": err, "rtol": PARITY_RTOL,
                  "interpreted_fallbacks": fallbacks,
                  "peak_device_bytes": peak_device_bytes()}
        emit(record)
        if fallbacks:
            raise AssertionError(
                f"{name}: {fallbacks} compiled join(s) fell back to the "
                "interpreted path")
        records.append(record)
    return records


# ---------------------------------------------------------------------------
# Phase model
# ---------------------------------------------------------------------------

def logits_tolerance(num_layers: int, ref: np.ndarray) -> float:
    """Largest max-abs logit error that bf16 rounding explains.

    A bf16 rounding perturbs a value by a relative error spread evenly
    over +-u (u = 2^-8), RMS u/sqrt(3). ``ROUNDINGS_PER_LAYER`` of them
    per layer reach the residual stream, and independent errors add in
    quadrature, so the final hidden state, and with it every logit,
    carries a relative RMS error eps = u/sqrt(3) * sqrt(R L). Of N
    Gaussian errors, the largest exceeds sqrt(2 ln(2N / a)) RMS errors
    with a chance of about a (``EXCEEDANCE``)."""
    eps = _relative_rms_error(num_layers)
    rms = float(np.sqrt(np.mean(np.square(ref, dtype=np.float64))))
    return math.sqrt(2 * math.log(2 * ref.size / EXCEEDANCE)) * eps * rms


def _relative_rms_error(num_layers: int) -> float:
    return BF16_UNIT_ROUNDOFF / math.sqrt(3) \
        * math.sqrt(ROUNDINGS_PER_LAYER * num_layers)


def reference_logits(params, cfg, prompts: np.ndarray, cache_len: int):
    """Last-position prefill logits of a float32 forward of ``params``
    (upcast) at the highest matmul precision."""
    from repro.models import transformer as tfm
    from repro.models.common import clear_activation_rules

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    clear_activation_rules()          # no mesh constraints in the reference

    def forward(p, tokens):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return tfm.forward_prefill(p32, cfg32, {"tokens": tokens},
                                   cache_len)[0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(params, jnp.asarray(prompts)))


def _prompts(cfg, seed: int, batch: int, prompt_len: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                        dtype=np.int32)


def _serve(engine, prompts: np.ndarray, new_tokens: int):
    """Serve one request per prompt; returns (tokens (B, T), seconds)."""
    from repro.serve.engine import Request
    reqs = [Request(i, p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    seconds = time.perf_counter() - t0
    return np.stack([r.completion for r in sorted(
        done, key=lambda r: r.request_id)]), seconds


def model_phase(cfg, seed: int, batch: int = 4, prompt_len: int = 128,
                new_tokens: int = 16, max_len: int = 160) -> dict:
    """Serve ``batch`` full-length prompts through ``ServingEngine`` on one
    device, then check the prefill logits against the float32 forward."""
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServingEngine

    t0 = time.perf_counter()
    engine = ServingEngine(cfg, make_mesh(1, 1, devices=jax.devices()[:1]),
                           batch_size=batch, max_prompt=prompt_len,
                           max_len=max_len, seed=seed)
    jax.block_until_ready(engine.params)
    init_s = time.perf_counter() - t0
    prompts = _prompts(cfg, seed, batch, prompt_len)
    cold, cold_s = _serve(engine, prompts, new_tokens)
    warm, warm_s = _serve(engine, prompts, new_tokens)
    if not np.array_equal(cold, warm):
        raise AssertionError("model: two greedy runs of the same prompts "
                             "gave different tokens")
    logits, _ = engine.prefill(engine.params,
                               {"tokens": jnp.asarray(prompts)})
    logits = np.asarray(logits, np.float64)
    ref = np.asarray(reference_logits(engine.params, cfg, prompts, max_len),
                     np.float64)
    err = float(np.max(np.abs(logits - ref)))
    tol = logits_tolerance(cfg.num_layers, ref)
    record = {"phase": "model", "arch": cfg.name, "layers": cfg.num_layers,
              "d_model": cfg.d_model, "dtype": cfg.dtype,
              "requests": batch, "prompt_tokens": batch * prompt_len,
              "new_tokens": int(cold.size), "init_s": init_s,
              "cold_s": cold_s, "warm_s": warm_s,
              "compile_s": cold_s - warm_s,
              "decode_tokens_per_s": cold.size / warm_s,
              "logits_max_abs_err": err, "logits_tol": tol,
              "logits_rms": float(np.sqrt(np.mean(ref ** 2))),
              "top1_agreement": float(np.mean(
                  logits.argmax(-1) == ref.argmax(-1))),
              "peak_device_bytes": peak_device_bytes()}
    emit(record)
    if not np.isfinite(logits).all() or err > tol:
        raise AssertionError(f"model: prefill logits off the float32 "
                             f"forward by {err:.4g} > tolerance {tol:.4g}")
    return record


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def _forced_logits(engine, prompts: np.ndarray, tokens: np.ndarray):
    """Logits of every greedy step when ``tokens`` are fed back instead of
    the engine's own choices: (T, B, V)."""
    logits, caches = engine.prefill(engine.params,
                                    {"tokens": jnp.asarray(prompts)})
    out = [np.asarray(logits)]
    for t in range(tokens.shape[1] - 1):
        logits, caches = engine.decode(
            engine.params, jnp.asarray(tokens[:, t:t + 1]), caches,
            jnp.asarray(prompts.shape[1] + t, jnp.int32))
        out.append(np.asarray(logits))
    return np.stack(out).astype(np.float64)


def _param_shares(params, devices) -> list[float]:
    """Fraction of all parameter bytes that each device holds."""
    held = {d: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] / total for d in devices]


def four_chip_serving(cfg, seed: int, batch: int = 4, prompt_len: int = 128,
                      new_tokens: int = 16, max_len: int = 160) -> dict:
    """Serve on (data=1, model=4) and on one device; greedy tokens must
    agree up to near-ties that the logits tolerance explains."""
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServingEngine

    devices = jax.devices()[:4]
    engines = {}
    for name, mesh in (("one", make_mesh(1, 1, devices=devices[:1])),
                       ("model4", make_mesh(1, 4, devices=devices))):
        engines[name] = ServingEngine(cfg, mesh, batch_size=batch,
                                      max_prompt=prompt_len, max_len=max_len,
                                      seed=seed)
    shares = _param_shares(engines["model4"].params, devices)
    prompts = _prompts(cfg, seed, batch, prompt_len)
    tokens, seconds = {}, {}
    for name, engine in engines.items():
        _serve(engine, prompts, new_tokens)                 # compile
        tokens[name], seconds[name] = _serve(engine, prompts, new_tokens)
    # Teacher-forced on the one-device tokens, both engines see the same
    # prefix at every step, so their logits are directly comparable.
    forced = {name: _forced_logits(e, prompts, tokens["one"])
              for name, e in engines.items()}
    err = float(np.max(np.abs(forced["model4"] - forced["one"])))
    tol = logits_tolerance(cfg.num_layers, forced["one"][0])
    # Up to a request's first differing token both engines saw the same
    # prefix, so there the sharded engine's logits are forced["model4"].
    # Its argmax can differ from the one-device argmax only if the
    # one-device logits of the two candidates are within 2 * err.
    divergences = []
    for i in range(batch):
        diff = np.flatnonzero(tokens["one"][i] != tokens["model4"][i])
        if diff.size:
            t = int(diff[0])
            step = forced["one"][t, i]
            gap = step[tokens["one"][i, t]] - step[tokens["model4"][i, t]]
            divergences.append({"request": i, "step": t, "gap": float(gap)})
    unexplained = [d for d in divergences if d["gap"] > 2 * err]
    record = {"phase": "four_chip_serving", "arch": cfg.name,
              "mesh": "data=1,model=4", "param_shares": shares,
              "tokens_equal": bool(np.array_equal(tokens["one"],
                                                  tokens["model4"])),
              "tokens_equal_frac": float(np.mean(tokens["one"]
                                                 == tokens["model4"])),
              "first_divergences": divergences,
              "logits_max_abs_err": err, "logits_tol": tol,
              "warm_s": seconds,
              "peak_device_bytes": peak_device_bytes(devices)}
    emit(record)
    if any(abs(s - 0.25) > 0.01 for s in shares):
        raise AssertionError(f"parameters not split four ways: {shares}")
    if err > tol:
        raise AssertionError(f"sharded logits off one device by {err:.4g} "
                             f"> tolerance {tol:.4g}")
    if unexplained:
        raise AssertionError(f"greedy tokens differ beyond near-ties: "
                             f"{unexplained}")
    return record


def four_chip_training(cfg, seed: int, layers: int = 2, batch: int = 8,
                       seq_len: int = 256) -> dict:
    """One training step of ``cfg`` cut to ``layers`` layers on
    (data=2, model=2) and on one device; the losses must agree."""
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import init_params, make_train_step
    from repro.train.optimizer import AdamWConfig, init_opt_state

    cfg = dataclasses.replace(cfg, num_layers=layers, microbatches=1)
    devices = jax.devices()[:4]
    data = TokenPipeline(DataConfig(seq_len=seq_len, global_batch=batch,
                                    seed=seed, vocab_size=cfg.vocab_size))
    batch_np = data.batch_at(0)
    losses, seconds = {}, {}
    for name, mesh in (("one", make_mesh(1, 1, devices=devices[:1])),
                       ("data2_model2", make_mesh(2, 2, devices=devices))):
        step, _ = make_train_step(cfg, mesh, AdamWConfig(), donate=False,
                                  global_batch=batch)
        params = init_params(cfg, mesh, seed)
        opt = init_opt_state(params, AdamWConfig())
        t0 = time.perf_counter()
        params, opt, metrics = step(params, opt, {
            k: jnp.asarray(v) for k, v in batch_np.items()})
        losses[name] = float(metrics["loss"])
        seconds[name] = time.perf_counter() - t0
        if not all(bool(jnp.isfinite(p).all())
                   for p in jax.tree.leaves(params)):
            raise AssertionError(f"training on {name}: non-finite params")
    # Loss = mean(logsumexp - gold logit): each term moves by at most the
    # logits' bf16 error, eps(L) times the logit spread, which at
    # initialisation is INIT_LOGIT_STD * sqrt(d_model) (unit-RMS normed
    # hidden state times the lm_head).
    tol = 2 * _relative_rms_error(layers) * INIT_LOGIT_STD \
        * math.sqrt(cfg.d_model)
    diff = abs(losses["one"] - losses["data2_model2"])
    record = {"phase": "four_chip_training", "arch": cfg.name,
              "layers": layers, "mesh": "data=2,model=2", "losses": losses,
              "loss_abs_diff": diff, "loss_tol": tol,
              "first_step_s": seconds,
              "peak_device_bytes": peak_device_bytes(devices)}
    emit(record)
    if diff > tol:
        raise AssertionError(f"training loss on 2x2 differs from one "
                             f"device by {diff:.4g} > {tol:.4g}")
    return record


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving and training checks "
                         "on four chips")
    args = ap.parse_args(argv)

    platform = jax.default_backend()
    devices = jax.devices()
    if platform != "tpu" or not devices:
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips; found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)

    from repro.configs.registry import ARCHS
    from repro.launch import compile_cache
    emit({"compile_cache": compile_cache.enable()})
    cfg = ARCHS["internlm2-1.8b"]
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_serving(cfg, args.seed)
        four_chip_training(cfg, args.seed)
    else:
        engine_phase(args.seed)
        model_phase(cfg, args.seed)
    emit({"total_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
