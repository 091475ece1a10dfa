"""Substrate tests: optimizer, data pipeline, serving engine, HLO analyzer,
optimized model paths (blocked/local attention, chunked scans)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.data.pipeline import DataConfig, TokenPipeline, pack_sequences
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
from repro.models import transformer as tfm
from repro.models.common import split_tree
from repro.train import optimizer as opt


# -- optimizer ---------------------------------------------------------------

def test_adamw_decreases_quadratic_loss():
    cfg = opt.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                          weight_decay=0.0, moment_dtype="float32")
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init_opt_state(params, cfg)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, _ = opt.apply_updates(params, g, state, cfg)
    assert float(loss(params)) < 0.5


def test_schedule_warmup_and_cosine():
    cfg = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    assert float(opt.schedule(jnp.asarray(0), cfg)) == 0.0
    assert float(opt.schedule(jnp.asarray(10), cfg)) == pytest.approx(1.0)
    assert float(opt.schedule(jnp.asarray(100), cfg)) == pytest.approx(0.1)


def test_grad_clip_bounds_update():
    cfg = opt.AdamWConfig(lr=0.1, grad_clip=1.0, warmup_steps=0,
                          weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init_opt_state(params, cfg)
    huge = {"w": jnp.asarray([1e9, -1e9, 1e9])}
    p2, _, m = opt.apply_updates(params, huge, state, cfg)
    assert float(m["grad_norm"]) > 1e8
    assert bool(jnp.all(jnp.isfinite(p2["w"])))


# -- data pipeline ------------------------------------------------------------

def test_pipeline_deterministic_and_sharded():
    cfg = DataConfig(seq_len=16, global_batch=8, seed=3, vocab_size=100)
    a = TokenPipeline(cfg).batch_at(5)
    b = TokenPipeline(cfg).batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    s0 = TokenPipeline(cfg, shard=0, num_shards=2).batch_at(5)
    s1 = TokenPipeline(cfg, shard=1, num_shards=2).batch_at(5)
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_pipeline_prefetch_plan_within_burst():
    cfg = DataConfig(seq_len=4096, global_batch=256)
    plan = TokenPipeline(cfg).prefetch_plan(workers=8)
    assert plan["within_burst"] == 1.0


def test_pack_sequences_lossless():
    docs = [np.arange(1, 6), np.arange(10, 13), np.arange(20, 30)]
    rows, segs = pack_sequences(docs, seq_len=8)
    flat = rows[segs > 0]
    np.testing.assert_array_equal(np.sort(flat),
                                  np.sort(np.concatenate(docs)))
    assert rows.shape[1] == 8


# -- optimized model paths -----------------------------------------------------

@pytest.mark.parametrize("arch", ["deepseek-7b", "recurrentgemma-2b"])
def test_blocked_impl_matches_reference_loss(arch, rng):
    cfg = ARCHS[arch].reduced()
    params, _ = split_tree(tfm.init_model(jax.random.PRNGKey(0), cfg))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 33)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    l_ref, _ = tfm.forward_train(params, cfg, batch, impl="reference")
    l_blk, _ = tfm.forward_train(params, cfg, batch, impl="blocked")
    assert float(l_ref) == pytest.approx(float(l_blk), rel=1e-4)


def test_chunked_block_scan_matches(rng):
    cfg = ARCHS["recurrentgemma-2b"].reduced()
    cfg2 = dataclasses.replace(
        cfg, recurrent=dataclasses.replace(cfg.recurrent,
                                           scan_impl="chunked_block",
                                           chunk=8))
    params, _ = split_tree(tfm.init_model(jax.random.PRNGKey(0), cfg))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 33)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    l1, _ = tfm.forward_train(params, cfg, batch)
    l2, _ = tfm.forward_train(params, cfg2, batch)
    assert float(l1) == pytest.approx(float(l2), rel=1e-4)


# -- serving engine -------------------------------------------------------------

def test_serving_engine_completes_requests():
    from repro.serve.engine import Request, ServingEngine
    cfg = ARCHS["musicgen-medium"].reduced()
    mesh = make_mesh(1, 1)
    eng = ServingEngine(cfg, mesh, batch_size=2, max_prompt=8, max_len=16)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 6), max_new_tokens=4)
            for i in range(3)]
    done = eng.serve(reqs)
    assert len(done) == 3
    for r in done:
        assert r.completion.shape == (4,)
        assert (r.completion >= 0).all()
        assert (r.completion < cfg.vocab_size).all()
    rep = eng.cost_report(1.0, 3)
    assert rep["per_request_usd"] > 0


# -- HLO analyzer ----------------------------------------------------------------

HLO_SAMPLE = """
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
}
%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}
ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_hlo_analysis_trip_counts():
    s = hlo_analysis.analyze(HLO_SAMPLE, total_devices=4)
    # dot: 2 * 64 * 8 = 1024 flops, x5 trips
    assert s.dot_flops == pytest.approx(5 * 2 * 8 * 8 * 8)
    assert s.collective_counts["all-reduce"] == 5
    # all-reduce of 256B, group 4, ring: 2*256*(3/4) per execution
    assert s.collective_wire_bytes == pytest.approx(5 * 2 * 256 * 0.75)
    assert s.while_trip_counts == [5]


def test_hlo_analysis_trip_count_from_condition():
    txt = HLO_SAMPLE.replace(
        ', backend_config={"known_trip_count":{"n":"5"}}', "")
    s = hlo_analysis.analyze(txt, total_devices=4)
    assert s.while_trip_counts == [5]      # parsed from %cond constant


# -- dry-run artifacts (when present) ---------------------------------------------

def test_dryrun_artifacts_complete():
    from pathlib import Path
    import json
    art = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated")
    for mesh in ("16x16", "2x16x16"):
        cells = [json.loads(f.read_text())
                 for f in art.glob(f"*__{mesh}.json")]
        cells = [c for c in cells if not c.get("tag")]
        if not cells:
            pytest.skip(f"no {mesh} artifacts")
        assert len(cells) == 40, mesh
        status = {c["status"] for c in cells}
        assert status <= {"ok", "n/a"}, mesh
        assert sum(c["status"] == "ok" for c in cells) == 32, mesh
