"""Pallas kernel validation: interpret=True vs the pure-jnp oracles,
swept over shapes and dtypes (per-kernel allclose against ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't error
from hypothesis import given, settings, strategies as st

from repro.configs.speed_tig import TIG, TIG_MXU
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_flush import fused_flush_fwd
from repro.kernels.fused_gru import fused_gru
from repro.kernels.rwkv6_scan import rwkv6_chunked
from repro.kernels.temporal_attn import temporal_attn


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# -------------------------------------------------------------- fused GRU

@pytest.mark.parametrize("b,d_in,d_h", [
    (8, 16, 16), (64, 48, 32), (100, 112, 64), (256, 128, 128), (3, 7, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_gru_matches_ref(b, d_in, d_h, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = rand(ks[0], (b, d_in), dtype)
    h = rand(ks[1], (b, d_h), dtype)
    wx = rand(ks[2], (d_in, 3 * d_h), dtype, 0.3)
    wh = rand(ks[3], (d_h, 3 * d_h), dtype, 0.3)
    bx = rand(ks[4], (3 * d_h,), dtype, 0.1)
    bh = rand(ks[5], (3 * d_h,), dtype, 0.1)
    got = fused_gru(x, h, wx, wh, bx, bh, interpret=True, block_b=32)
    want = ref.gru_ref(x, h, wx, wh, bx, bh)
    # bf16: the kernel accumulates gates in f32 (preferred_element_type)
    # while the jnp oracle matmuls in bf16 — allow bf16-rounding slack.
    tol = 1e-5 if dtype == jnp.float32 else 1.5e-1
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 70), d=st.sampled_from([8, 24, 40]),
       seed=st.integers(0, 100))
def test_fused_gru_property(b, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = rand(ks[0], (b, d))
    h = rand(ks[1], (b, d))
    wx = rand(ks[2], (d, 3 * d), scale=0.3)
    wh = rand(ks[3], (d, 3 * d), scale=0.3)
    bx = rand(ks[4], (3 * d,), scale=0.1)
    bh = rand(ks[5], (3 * d,), scale=0.1)
    got = fused_gru(x, h, wx, wh, bx, bh, interpret=True, block_b=16)
    want = ref.gru_ref(x, h, wx, wh, bx, bh)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # GRU output is a convex mix of candidate (|.|<=1) and h
    assert np.all(np.abs(got) <= np.maximum(np.abs(h), 1.0) + 1e-5)


# ------------------------------------------------------ temporal attention

@pytest.mark.parametrize("b,k,h,d", [
    (16, 4, 2, 8), (64, 10, 2, 16), (33, 20, 4, 32), (5, 1, 1, 4),
])
def test_temporal_attn_matches_ref(b, k, h, d):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = rand(ks[0], (b, h, d))
    kk = rand(ks[1], (b, k, h, d))
    v = rand(ks[2], (b, k, h, d))
    mask = jax.random.uniform(ks[3], (b, k)) > 0.3
    got = temporal_attn(q, kk, v, mask, interpret=True, block_b=16)
    want = ref.temporal_attention_ref(q, kk, v, mask)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


def test_temporal_attn_empty_rows_zero():
    b, k, h, d = 8, 5, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (b, h, d))
    kk = rand(ks[1], (b, k, h, d))
    v = rand(ks[2], (b, k, h, d))
    mask = np.zeros((b, k), bool)
    mask[0, :] = True  # only row 0 has neighbors
    got = np.asarray(temporal_attn(q, kk, v, jnp.asarray(mask),
                                   interpret=True))
    assert np.abs(got[1:]).max() == 0.0
    assert np.abs(got[0]).max() > 0.0


@pytest.mark.parametrize("cfg", [TIG, TIG_MXU], ids=["tig", "tig_mxu"])
def test_temporal_attention_paper_widths_match_ref(cfg):
    """The padded, head-folded launch at the widths the chip runs (3B
    query rows, K neighbors, n_heads x dim/n_heads) equals the oracle."""
    b, k, h = 3 * cfg.batch_size, cfg.num_neighbors, cfg.n_heads
    d = cfg.dim // h
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = rand(ks[0], (b, h, d))
    kk = rand(ks[1], (b, k, h, d))
    v = rand(ks[2], (b, k, h, d))
    mask = jax.random.uniform(ks[3], (b, k)) > 0.3
    mask = mask.at[:7].set(False)          # rows with no neighbor
    got = ops.temporal_attention(q, kk, v, mask, backend="interpret")
    want = ref.temporal_attention_ref(q, kk, v, mask)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    g = rand(ks[4], (b, h, d))
    loss = lambda f: lambda *a: jnp.sum(f(*a, mask) * g)  # noqa: E731
    got_g = jax.grad(loss(lambda *a: ops.temporal_attention(
        *a, backend="interpret")), argnums=(0, 1, 2))(q, kk, v)
    want_g = jax.grad(loss(ref.temporal_attention_ref),
                      argnums=(0, 1, 2))(q, kk, v)
    for a, w in zip(got_g, want_g):
        np.testing.assert_allclose(a, w, atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- fused flush

def flush_args(key, n, rows, dm, d, id_hi=None):
    ks = jax.random.split(key, 8)
    ids = jax.random.randint(ks[0], (rows,), 0,
                             (id_hi or n) + 1).astype(jnp.int32)
    return (ids,
            rand(ks[1], (rows, dm)),
            jax.random.uniform(ks[2], (rows,)) * 5.0,
            rand(ks[3], (n + 1, d)),
            jax.random.uniform(ks[4], (n + 1,)),
            rand(ks[5], (dm, 3 * d), scale=0.3),
            rand(ks[6], (d, 3 * d), scale=0.3),
            rand(ks[7], (3 * d,), scale=0.1),
            jnp.zeros((3 * d,)))


# (deterministic fused-flush parity sweeps live in test_kernel_grads.py,
# which has no optional-dep guard and runs everywhere tier-1 runs; only
# the hypothesis property test stays here)

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), rows=st.sampled_from([4, 16, 30]),
       n=st.sampled_from([5, 40]))
def test_fused_flush_property(seed, rows, n):
    args = flush_args(jax.random.PRNGKey(seed), n, rows, 8, 8)
    got = fused_flush_fwd(*args, interpret=True)
    want = ref.flush_ref(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("cfg", [TIG, TIG_MXU], ids=["tig", "tig_mxu"])
def test_fused_flush_paper_widths_match_ref(cfg):
    """2B pending rows at the model's (msg_dim, dim), on a table whose row
    count is a multiple of neither the 8-row tile nor the 1024-element
    ``last`` chunk."""
    n, rows = 5_000, 2 * cfg.batch_size
    args = list(flush_args(jax.random.PRNGKey(6), n, rows, cfg.msg_dim,
                           cfg.dim, id_hi=n // 3))
    args[0] = args[0].at[-5:].set(n)           # padding rows -> dump row
    got = ops.fused_flush(*args, backend="interpret")
    want = ref.flush_ref(*args)
    # the gate pre-activations sum ~600 f32 terms of magnitude ~7 here, in
    # a different order than the oracle: rounding reaches ~1e-5
    for name, a, b in zip(("mem", "last", "mbar"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-5, err_msg=name)


# --------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,h,s,d", [
    (1, 1, 128, 16), (2, 2, 256, 32), (1, 4, 512, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, h, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (b, h, s, d), dtype)
    k = rand(ks[1], (b, h, s, d), dtype)
    v = rand(ks[2], (b, h, s, d), dtype)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 64, 200])
def test_flash_attention_sliding_window(window):
    b, h, s, d = 1, 2, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (b, h, s, d))
    k = rand(ks[1], (b, h, s, d))
    v = rand(ks[2], (b, h, s, d))
    got = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_flash_attention_noncausal():
    b, h, s, d = 1, 1, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (rand(ki, (b, h, s, d)) for ki in ks)
    got = flash_attention(q, k, v, causal=False, interpret=True,
                          block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------- RWKV6 WKV

def wkv_inputs(key, b, h, s, dk, dv, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    r = rand(ks[0], (b, h, s, dk), dtype)
    k = rand(ks[1], (b, h, s, dk), dtype)
    v = rand(ks[2], (b, h, s, dv), dtype)
    # decay in (~0.7, 1.0): the regime trained RWKV models live in
    w = jnp.exp(-jnp.exp(
        rand(ks[3], (b, h, s, dk)) * 0.5 - 2.0)).astype(dtype)
    u = rand(ks[4], (h, dk))
    return r, k, v, w, u


@pytest.mark.parametrize("b,h,s,dk,dv,chunk", [
    (1, 1, 64, 16, 16, 16), (2, 2, 128, 32, 32, 32),
    (1, 2, 256, 64, 64, 64), (1, 1, 128, 8, 24, 64),
])
def test_rwkv6_chunked_matches_scan(b, h, s, dk, dv, chunk):
    r, k, v, w, u = wkv_inputs(jax.random.PRNGKey(6), b, h, s, dk, dv)
    got_o, got_s = rwkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    want_o, want_s = ref.rwkv6_ref(r, k, v, w, u, return_state=True)
    np.testing.assert_allclose(got_o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)


def test_rwkv6_initial_state_continuation():
    """Processing [first half] then [second half | state] == full sequence."""
    b, h, s, dk, dv = 1, 2, 128, 16, 16
    r, k, v, w, u = wkv_inputs(jax.random.PRNGKey(7), b, h, s, dk, dv)
    full_o, full_s = rwkv6_chunked(r, k, v, w, u, chunk=32, interpret=True)
    half = s // 2
    o1, s1 = rwkv6_chunked(r[:, :, :half], k[:, :, :half], v[:, :, :half],
                           w[:, :, :half], u, chunk=32, interpret=True)
    o2, s2 = rwkv6_chunked(r[:, :, half:], k[:, :, half:], v[:, :, half:],
                           w[:, :, half:], u, state=s1, chunk=32,
                           interpret=True)
    np.testing.assert_allclose(np.concatenate([o1, o2], axis=2), full_o,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s2, full_s, atol=2e-4, rtol=2e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 50), chunk=st.sampled_from([8, 16, 32]))
def test_rwkv6_property_random(seed, chunk):
    b, h, s, dk, dv = 1, 1, 64, 8, 8
    r, k, v, w, u = wkv_inputs(jax.random.PRNGKey(seed), b, h, s, dk, dv)
    got_o, _ = rwkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    want_o = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(got_o, want_o, atol=3e-4, rtol=3e-4)
