"""Gradient parity for the Pallas backward kernels (and the fused flush).

The fused backward kernels (interpret mode on CPU) must reproduce the XLA
oracle gradients: through the raw ops, through ``flush_pending``, and
through a full ``step_loss`` training step for the GRU flavors.  This
module deliberately has no optional-dep guard — it runs everywhere
tier-1 runs.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.fused_flush import fused_flush_fwd
from repro.kernels.fused_gru import fused_gru_bwd
from repro.kernels.temporal_attn import temporal_attn_bwd
from repro.tig.batching import build_batches
from repro.tig.data import synthetic_tig
from repro.tig.models import (
    TIGConfig,
    flush_pending,
    init_params,
    init_state,
    step_loss,
)
from repro.tig.train import graph_as_stream

TOL = 1e-5


def rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape) * scale


def assert_tree_close(got, want, tol=TOL, label=""):
    flat_g, _ = jax.tree.flatten(got)
    flat_w, _ = jax.tree.flatten(want)
    assert len(flat_g) == len(flat_w)
    for i, (a, b) in enumerate(zip(flat_g, flat_w)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=tol, rtol=tol,
            err_msg=f"{label} leaf {i}")


# ----------------------------------------------------------------- raw ops

@pytest.mark.parametrize("b,d_in,d_h", [
    (8, 16, 16), (100, 48, 32), (33, 7, 5),     # incl. ragged last block
])
def test_gru_fused_bwd_matches_oracle(b, d_in, d_h):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    args = (rand(ks[0], (b, d_in)), rand(ks[1], (b, d_h)),
            rand(ks[2], (d_in, 3 * d_h), 0.3),
            rand(ks[3], (d_h, 3 * d_h), 0.3),
            rand(ks[4], (3 * d_h,), 0.1), rand(ks[5], (3 * d_h,), 0.1))
    g = rand(ks[6], (b, d_h))
    want = jax.grad(
        lambda *a: jnp.sum(ref.gru_ref(*a) * g), argnums=(0, 1, 2, 3, 4, 5)
    )(*args)
    got = jax.grad(
        lambda *a: jnp.sum(
            ops.gru(*a, backend="interpret", bwd="fused") * g),
        argnums=(0, 1, 2, 3, 4, 5))(*args)
    assert_tree_close(got, want, label="gru")
    # the raw backward kernel agrees too (block boundary crossed: block_b=16)
    raw = fused_gru_bwd(g, *args, block_b=16, interpret=True)
    assert_tree_close(raw, want, label="gru raw kernel")


@pytest.mark.parametrize("b,k,h,d", [(16, 4, 2, 8), (33, 5, 1, 4)])
def test_temporal_attn_fused_bwd_matches_oracle(b, k, h, d):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q, kk, v = (rand(ks[0], (b, h, d)), rand(ks[1], (b, k, h, d)),
                rand(ks[2], (b, k, h, d)))
    mask = jax.random.uniform(ks[3], (b, k)) > 0.3
    mask = mask.at[0].set(False)        # a zero-neighbor row
    g = rand(ks[4], (b, h, d))
    want = jax.grad(
        lambda *a: jnp.sum(ref.temporal_attention_ref(*a, mask) * g),
        argnums=(0, 1, 2))(q, kk, v)
    got = jax.grad(
        lambda *a: jnp.sum(ops.temporal_attention(
            *a, mask, backend="interpret", bwd="fused") * g),
        argnums=(0, 1, 2))(q, kk, v)
    assert_tree_close(got, want, label="attn")
    raw = temporal_attn_bwd(g, q, kk, v, mask, block_b=16, interpret=True)
    assert_tree_close(raw, want, label="attn raw kernel")
    # zero-neighbor rows get exactly zero input gradients
    assert np.abs(np.asarray(raw[0][0])).max() == 0.0


def test_gru_oracle_bwd_mode_still_works():
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    args = (rand(ks[0], (12, 8)), rand(ks[1], (12, 8)),
            rand(ks[2], (8, 24), 0.3), rand(ks[3], (8, 24), 0.3),
            rand(ks[4], (24,), 0.1), rand(ks[5], (24,), 0.1))
    want = jax.grad(lambda *a: jnp.sum(ref.gru_ref(*a)),
                    argnums=(0, 1))(*args)
    got = jax.grad(
        lambda *a: jnp.sum(ops.gru(*a, backend="interpret", bwd="oracle")),
        argnums=(0, 1))(*args)
    assert_tree_close(got, want, label="gru oracle bwd")


# -------------------------------------------------------------- fused flush

def flush_inputs(seed=3, n=40, rows=24, dm=20, d=16, dup_heavy=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    hi = n // 4 if dup_heavy else n     # force duplicate ids
    ids = jax.random.randint(ks[0], (rows,), 0, hi + 1).astype(jnp.int32)
    ids = ids.at[-2:].set(n)            # padding rows -> dump row
    return (ids,
            rand(ks[1], (rows, dm)),
            jax.random.uniform(ks[2], (rows,)) * 10,
            rand(ks[3], (n + 1, d)),
            jax.random.uniform(ks[4], (n + 1,)),
            rand(ks[5], (dm, 3 * d), 0.3),
            rand(ks[6], (d, 3 * d), 0.3),
            rand(ks[7], (3 * d,), 0.1),
            jnp.zeros((3 * d,)))


def test_fused_flush_forward_matches_oracle():
    args = flush_inputs()
    want = ref.flush_ref(*args)
    got = fused_flush_fwd(*args, interpret=True)
    for name, a, b in zip(("mem", "last", "mbar"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6, err_msg=name)
    # untouched memory rows are bit-identical (aliased in place)
    touched = set(np.asarray(args[0]).tolist())
    mem_in, mem_out = np.asarray(args[3]), np.asarray(got[0])
    for r in range(mem_in.shape[0] - 1):
        if r not in touched:
            np.testing.assert_array_equal(mem_out[r], mem_in[r])


@pytest.mark.parametrize("n,rows,dm,d", [
    (30, 16, 12, 8), (100, 64, 48, 32), (9, 24, 20, 16),  # heavy duplicates
])
def test_fused_flush_forward_shape_sweep(n, rows, dm, d):
    args = flush_inputs(seed=10, n=n, rows=rows, dm=dm, d=d,
                        dup_heavy=False)
    got = fused_flush_fwd(*args, interpret=True)
    want = ref.flush_ref(*args)
    for name, a, b in zip(("mem", "last", "mbar"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6, err_msg=name)


def test_fused_flush_all_padding_is_noop():
    n, rows, dm, d = 20, 8, 12, 8
    args = list(flush_inputs(seed=11, n=n, rows=rows, dm=dm, d=d))
    args[0] = jnp.full((rows,), n, jnp.int32)      # every row -> dump
    mem_out, last_out, mbar = fused_flush_fwd(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(mem_out[:-1]),
                                  np.asarray(args[3][:-1]))
    assert np.abs(np.asarray(mem_out[-1])).max() == 0.0
    assert np.abs(np.asarray(last_out[-1])).max() == 0.0
    assert np.abs(np.asarray(mbar)).max() == 0.0


def test_fused_flush_grads_match_oracle():
    args = flush_inputs(seed=4)

    def loss(f):
        def inner(msg, mem, wx, wh, bx, bh):
            m, l, mb = f(args[0], msg, args[2], mem, args[4],
                         wx, wh, bx, bh)
            return jnp.sum(m * m) + jnp.sum(l) + jnp.sum(mb)
        return inner

    diff = (args[1], args[3], args[5], args[6], args[7], args[8])
    want = jax.grad(loss(ref.flush_ref), argnums=tuple(range(6)))(*diff)
    got = jax.grad(
        loss(lambda *a: ops.fused_flush(*a, backend="interpret")),
        argnums=tuple(range(6)))(*diff)
    assert_tree_close(got, want, label="flush")


def test_flush_pending_pallas_matches_xla_path():
    """Whole flush_pending: fused kernel vs the inline XLA aggregation."""
    for flavor in ("tgn", "tige"):
        cfg_x = TIGConfig(flavor=flavor, dim=16, dim_time=8, dim_edge=16,
                          dim_node=16, num_neighbors=4, batch_size=8)
        cfg_p = TIGConfig(flavor=flavor, dim=16, dim_time=8, dim_edge=16,
                          dim_node=16, num_neighbors=4, batch_size=8,
                          use_pallas=True, kernel_backend="interpret")
        params = init_params(jax.random.PRNGKey(0), cfg_x)
        state = init_state(cfg_x, 30)
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        state["mem"] = rand(ks[0], state["mem"].shape)
        state["pend_ids"] = jax.random.randint(
            ks[1], state["pend_ids"].shape, 0, 31).astype(jnp.int32)
        state["pend_raw"] = rand(ks[2], state["pend_raw"].shape)
        state["pend_t"] = jnp.linspace(0.0, 1.0, 16)
        out_x, fresh_x = flush_pending(params, cfg_x, dict(state))
        out_p, fresh_p = flush_pending(params, cfg_p, dict(state))
        for key in ("mem", "mem2", "last"):
            np.testing.assert_allclose(
                np.asarray(out_p[key]), np.asarray(out_x[key]),
                atol=1e-6, rtol=1e-6, err_msg=f"{flavor}/{key}")
        for key in fresh_x:
            np.testing.assert_allclose(
                np.asarray(fresh_p[key]), np.asarray(fresh_x[key]),
                atol=1e-6, rtol=1e-6, err_msg=f"{flavor}/fresh {key}")


# ------------------------------------------------------- full training step

def _step_setup(flavor):
    cfg_kw = dict(flavor=flavor, dim=16, dim_time=8, dim_edge=16,
                  dim_node=16, num_neighbors=4, batch_size=32,
                  message_fn="mlp", dim_msg=24)
    cfg_x = TIGConfig(**cfg_kw)
    g = synthetic_tig("tiny", seed=7)
    stream, tables = graph_as_stream(g)
    batches = build_batches(stream, cfg_x, np.random.default_rng(0))
    params = init_params(jax.random.PRNGKey(0), cfg_x)
    state = init_state(cfg_x, g.num_nodes)
    tables_j = {k: jnp.asarray(v) for k, v in tables.items()}
    bjs = [{k: jnp.asarray(v) for k, v in b.items() if k != "labels"}
           for b in batches[:2]]
    return cfg_kw, params, state, tables_j, bjs


def _two_step_grads(cfg, params, state, tables_j, bjs):
    def loss(p):
        s, total = state, 0.0
        for bj in bjs:           # 2 steps: flush sees real pending messages
            l, (s, _) = step_loss(p, s, bj, tables_j, cfg)
            total = total + l
        return total
    return jax.grad(loss)(params)


@pytest.mark.parametrize("flavor", ["tgn", "tige"])
def test_step_loss_grad_parity_fused_bwd(flavor):
    cfg_kw, params, state, tables_j, bjs = _step_setup(flavor)
    want = _two_step_grads(TIGConfig(**cfg_kw), params, state, tables_j,
                           bjs)
    got = _two_step_grads(
        TIGConfig(**cfg_kw, use_pallas=True, kernel_backend="interpret"),
        params, state, tables_j, bjs)
    assert_tree_close(got, want, tol=TOL, label=f"step_loss {flavor}")


def test_step_loss_grad_parity_oracle_bwd(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BWD", "oracle")
    cfg_kw, params, state, tables_j, bjs = _step_setup("tgn")
    want = _two_step_grads(TIGConfig(**cfg_kw), params, state, tables_j,
                           bjs)
    got = _two_step_grads(
        TIGConfig(**cfg_kw, use_pallas=True, kernel_backend="interpret"),
        params, state, tables_j, bjs)
    assert_tree_close(got, want, tol=TOL, label="step_loss oracle bwd")


def test_bwd_env_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BWD", raising=False)
    assert ops.default_bwd() == "fused"
    monkeypatch.setenv("REPRO_KERNEL_BWD", "oracle")
    assert ops.default_bwd() == "oracle"
    monkeypatch.setenv("REPRO_KERNEL_BWD", "bogus")
    with pytest.raises(ValueError):
        ops.default_bwd()
