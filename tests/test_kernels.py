"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles,
across shapes and dtypes, plus hypothesis property tests on invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypo_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_hmajor
from repro.kernels.moe_gmm import gmm as gmm_kernel
from repro.kernels.rglru_scan import rglru_scan_blocked
from repro.kernels.rwkv6_scan import rwkv6_scan_hmajor


def _rand(rng, shape, dtype, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# -- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,s,h,hkv,d,bq,bk", [
    (1, 64, 2, 2, 16, 16, 16),
    (2, 128, 4, 2, 32, 32, 64),
    (1, 256, 8, 1, 16, 64, 32),    # MQA
    (2, 96, 6, 3, 8, 32, 32),      # non-pow2 heads
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 48), (False, 0)])
def test_flash_attention_sweep(rng, dtype, tol, b, s, h, hkv, d, bq, bk,
                               causal, window):
    q = _rand(rng, (b, s, h, d), dtype)
    k = _rand(rng, (b, s, hkv, d), dtype)
    v = _rand(rng, (b, s, hkv, d), dtype)
    got = flash_attention_hmajor(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=True).transpose(0, 2, 1, 3)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_rows_sum_to_convex_combination(rng):
    # softmax(QK)V outputs lie within per-column min/max of V rows.
    b, s, h, d = 1, 64, 2, 8
    q = _rand(rng, (b, s, h, d), jnp.float32)
    k = _rand(rng, (b, s, h, d), jnp.float32)
    v = _rand(rng, (b, s, h, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    assert float(out.max()) <= float(v.max()) + 1e-4
    assert float(out.min()) >= float(v.min()) - 1e-4


# -- grouped matmul ----------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("e,c,d,f,bc,bf,bd", [
    (2, 16, 32, 24, 8, 8, 16),
    (8, 64, 64, 48, 32, 16, 32),
    (1, 128, 16, 128, 128, 128, 16),
])
def test_gmm_sweep(rng, dtype, tol, e, c, d, f, bc, bf, bd):
    x = _rand(rng, (e, c, d), dtype)
    w = _rand(rng, (e, d, f), dtype)
    got = gmm_kernel(x, w, block_c=bc, block_f=bf, block_d=bd,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.gmm_ref(x, w), np.float32),
                               rtol=tol, atol=tol * 8)


@settings(max_examples=15, deadline=None)
@given(e=st.integers(1, 4), c=st.sampled_from([8, 16]),
       d=st.sampled_from([8, 32]), f=st.sampled_from([8, 16]))
def test_gmm_property_linear(e, c, d, f):
    """gmm is linear: gmm(a x, w) == a gmm(x, w)."""
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32)
    y1 = gmm_kernel(x * 2.0, w, block_c=8, block_f=8, block_d=8,
                    interpret=True)
    y2 = gmm_kernel(x, w, block_c=8, block_f=8, block_d=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2) * 2.0,
                               rtol=1e-4, atol=1e-4)


# -- RWKV-6 chunked scan ------------------------------------------------------

@pytest.mark.parametrize("b,s,h,k,chunk", [
    (1, 32, 1, 8, 8),
    (2, 96, 2, 16, 32),
    (1, 128, 4, 16, 64),
])
def test_rwkv6_kernel_vs_sequential(rng, b, s, h, k, chunk):
    r = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    kk = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    v = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    lw = -jnp.exp(_rand(rng, (b, s, h, k), jnp.float32, 0.5) - 2.0)
    u = _rand(rng, (h, k), jnp.float32, 0.3)
    s0 = _rand(rng, (b, h, k, k), jnp.float32, 0.1)
    o_seq, s_seq = ref.rwkv6_step_ref(r, kk, v, lw, u, s0)
    tr = lambda a: a.transpose(0, 2, 1, 3)
    o_ker, s_ker = rwkv6_scan_hmajor(tr(r), tr(kk), tr(v), tr(lw), u, s0,
                                     chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(tr(o_ker)), np.asarray(o_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_ker), np.asarray(s_seq),
                               rtol=2e-4, atol=2e-4)


def test_rwkv6_chunk_invariance(rng):
    """Chunk size must not change the result (associativity of the scan)."""
    b, s, h, k = 1, 64, 2, 8
    r = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    kk = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    v = _rand(rng, (b, s, h, k), jnp.float32, 0.5)
    lw = -jnp.exp(_rand(rng, (b, s, h, k), jnp.float32, 0.3) - 2.0)
    u = _rand(rng, (h, k), jnp.float32, 0.3)
    s0 = jnp.zeros((b, h, k, k), jnp.float32)
    o8, st8 = ref.rwkv6_chunked_ref(r, kk, v, lw, u, s0, chunk=8)
    o32, st32 = ref.rwkv6_chunked_ref(r, kk, v, lw, u, s0, chunk=32)
    np.testing.assert_allclose(np.asarray(o8), np.asarray(o32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st8), np.asarray(st32),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(decay=st.floats(0.5, 6.0))
def test_rwkv6_strong_decay_state_bounded(decay):
    """Stronger decay shrinks the carried state (contraction property) —
    exercised through the exact pairwise chunked reference."""
    rng = np.random.default_rng(7)
    b, s, h, k = 1, 32, 1, 8
    r = jnp.asarray(rng.standard_normal((b, s, h, k)) * .5, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((b, s, h, k)) * .5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, k)) * .5, jnp.float32)
    lw = jnp.full((b, s, h, k), -decay, jnp.float32)
    u = jnp.zeros((h, k), jnp.float32)
    s0 = jnp.ones((b, h, k, k), jnp.float32)
    _, s_out = ref.rwkv6_chunked_ref(r, kk, v, lw, u, s0, chunk=16)
    _, s_seq = ref.rwkv6_step_ref(r, kk, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(s_seq),
                               rtol=1e-3, atol=1e-3)


# -- RG-LRU scan --------------------------------------------------------------

@pytest.mark.parametrize("b,s,w,chunk,bw", [
    (1, 32, 16, 8, 8),
    (2, 100, 64, 16, 32),
    (1, 256, 32, 64, 32),
])
def test_rglru_kernel_vs_sequential(rng, b, s, w, chunk, bw):
    la = -jnp.exp(_rand(rng, (b, s, w), jnp.float32))
    b_in = _rand(rng, (b, s, w), jnp.float32)
    h0 = _rand(rng, (b, w), jnp.float32)
    want_all, want_last = ref.rglru_scan_ref(la, b_in, h0)
    pad = (-s) % chunk
    la_p = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))
    b_p = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
    got_all, got_last = rglru_scan_blocked(la_p, b_p, h0, chunk=chunk,
                                           block_w=bw, interpret=True)
    np.testing.assert_allclose(np.asarray(got_all[:, :s]),
                               np.asarray(want_all), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last),
                               rtol=1e-5, atol=1e-5)


def test_rglru_strong_decay_is_exact(rng):
    """Sequential kernel is exact even for violently strong decays, where a
    naive exp(+cumsum) parallel form would overflow (docstring claim)."""
    b, s, w = 1, 64, 8
    la = jnp.full((b, s, w), -40.0)     # decay to ~0 each step
    b_in = _rand(rng, (b, s, w), jnp.float32)
    h0 = jnp.full((b, w), 1e6, jnp.float32)
    got_all, got_last = rglru_scan_blocked(la, b_in, h0, chunk=16,
                                           block_w=8, interpret=True)
    want_all, want_last = ref.rglru_scan_ref(la, b_in, h0)
    assert bool(jnp.all(jnp.isfinite(got_all)))
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_rglru_associative_scan_matches_sequential(seed):
    rng = np.random.default_rng(seed)
    from repro.models.rglru import linear_scan
    la = -jnp.exp(jnp.asarray(rng.standard_normal((1, 24, 8)), jnp.float32))
    b_in = jnp.asarray(rng.standard_normal((1, 24, 8)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((1, 8)), jnp.float32)
    got_all, got_last = linear_scan(la, b_in, h0)
    want_all, want_last = ref.rglru_scan_ref(la, b_in, h0)
    np.testing.assert_allclose(np.asarray(got_all), np.asarray(want_all),
                               rtol=1e-4, atol=1e-4)


# -- blocked attention (the dry-run flash stand-in) ---------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
def test_blocked_sdpa_matches_reference(rng, causal, window):
    from repro.models.attention import _blocked_sdpa, _sdpa
    q = _rand(rng, (2, 128, 4, 16), jnp.float32)
    k = _rand(rng, (2, 128, 2, 16), jnp.float32)
    v = _rand(rng, (2, 128, 2, 16), jnp.float32)
    a = _sdpa(q, k, v, causal=causal, window=window)
    b = _blocked_sdpa(q, k, v, causal=causal, window=window, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


# -- sorted-probe (hash join) -------------------------------------------------

from repro.kernels.hash_join import (prepare_buckets, sorted_probe,
                                     sorted_probe_np, sorted_probe_range,
                                     sorted_probe_range_np)


@pytest.mark.parametrize("n,s,lo,hi", [
    (1000, 400, 0, 600),          # partial match, dense keys
    (5000, 1, 0, 4),              # single-row build
    (257, 4096, -500, 9000),      # negative keys, probe wider than build
    (64, 1000, 10**6, 10**9),     # sparse keys, wide span
])
def test_sorted_probe_matches_oracle(rng, n, s, lo, hi):
    build = np.sort(rng.choice(np.arange(lo, hi), size=s, replace=False)
                    ).astype(np.int32)
    keys = rng.integers(lo - 10, hi + 10, n).astype(np.int32)
    pos, match = sorted_probe(build, keys)
    ref_pos, ref_match = sorted_probe_np(build, keys)
    np.testing.assert_array_equal(np.asarray(match), ref_match)
    np.testing.assert_array_equal(np.asarray(pos)[ref_match],
                                  ref_pos[ref_match])


def test_sorted_probe_duplicate_build_keys_lower_bound(rng):
    """With duplicate build keys the probe returns the FIRST occurrence
    (lower bound), which the engine relies on to detect duplicates."""
    build = np.sort(rng.integers(0, 50, 300)).astype(np.int32)
    keys = np.arange(-5, 60, dtype=np.int32)
    pos, match = sorted_probe(build, keys)
    ref_pos, ref_match = sorted_probe_np(build, keys)
    np.testing.assert_array_equal(np.asarray(match), ref_match)
    np.testing.assert_array_equal(np.asarray(pos)[ref_match],
                                  ref_pos[ref_match])


@pytest.mark.parametrize("n,s,kmax,dup_frac", [
    (2000, 400, 600, 0.5),            # half the keys duplicated
    (500, 300, 50, 1.0),              # every build key duplicated, dense
    (257, 4096, 2**30, 0.1),          # sparse wide span, light dups
])
def test_sorted_probe_range_matches_oracle(rng, n, s, kmax, dup_frac):
    """The duplicate-key range probe: matched keys report the exact
    [lo, hi) run; absent keys report multiplicity 0 (their lo/hi are
    bucket-local and intentionally unspecified beyond hi - lo == 0)."""
    base = rng.integers(0, kmax, s).astype(np.int32)
    dups = rng.choice(base, int(s * dup_frac))
    build = np.sort(np.concatenate([base, dups])).astype(np.int32)
    keys = rng.integers(-10, kmax + 10, n).astype(np.int32)
    lo, hi, match = sorted_probe_range(build, keys)
    lo, hi, match = np.asarray(lo), np.asarray(hi), np.asarray(match)
    ref_lo, ref_hi, ref_match = sorted_probe_range_np(build, keys)
    np.testing.assert_array_equal(match, ref_match)
    np.testing.assert_array_equal(lo[ref_match], ref_lo[ref_match])
    np.testing.assert_array_equal(hi[ref_match], ref_hi[ref_match])
    assert np.all((hi - lo)[~ref_match] == 0)


def test_prepare_buckets_depth_covers_skew(rng):
    """The static search depth must cover the most populated bucket even
    under heavy key skew."""
    build = np.sort(np.concatenate([
        np.zeros(5000, np.int32),                       # one huge bucket
        rng.integers(1, 2**30, 100).astype(np.int32)])).astype(np.int32)
    scal, starts, iters = prepare_buckets(build)
    keys = np.concatenate([np.zeros(10, np.int32),
                           rng.integers(0, 2**30, 100).astype(np.int32)])
    pos, match = sorted_probe(build, keys, scalars=scal, starts=starts,
                              iters=iters)
    ref_pos, ref_match = sorted_probe_np(build, keys)
    np.testing.assert_array_equal(np.asarray(match), ref_match)
    np.testing.assert_array_equal(np.asarray(pos)[ref_match],
                                  ref_pos[ref_match])


# -- segmented reduction -----------------------------------------------------

from repro.kernels.segment_reduce import segment_reduce  # noqa: E402


@pytest.mark.parametrize("mode", ["sum", "count", "min", "max"])
def test_segment_reduce_any_row_order(mode):
    """Rows may reach the kernel in any order: the same rows under a
    random permutation reduce to the same sums within 1e-6, and to the
    same min, max and count. 10,000 rows over 300 segments take two row
    blocks in interpret mode, so the permutation also moves rows
    across the blocks' Kahan accumulation."""
    rng = np.random.default_rng(17)
    n, s = 10_000, 300
    ids = rng.integers(0, s, n).astype(np.int32)
    vals = rng.uniform(1, 100, n).astype(np.float32)
    perm = rng.permutation(n)
    got = [np.asarray(segment_reduce(v, i, num_segments=s, mode=mode,
                                     interpret=True))
           for v, i in ((vals, ids), (vals[perm], ids[perm]))]
    if mode == "sum":
        np.testing.assert_allclose(got[1], got[0], rtol=1e-6)
    else:
        np.testing.assert_array_equal(got[1], got[0])
