"""Fused GRU cell — Pallas TPU kernels (forward and backward).

The TIG memory update (paper Fig.6 UPD module) applies a GRU to every node
touched by a batch: rows (B, d_in) x (B, d_h).  Unfused, XLA emits two gate
matmuls plus ~10 elementwise HBM round-trips over (B, 3*d_h) intermediates.
The forward kernel keeps the gate activations in VMEM: one pass over HBM
for x, h and the weights, one write for h'.

The backward kernel is flash-attention-style: no gate activations are
saved as residuals — r/z/n are recomputed in VMEM from (x, h, weights),
so the backward pass reads each operand from HBM exactly once and writes
each gradient exactly once.  Weight/bias gradients are accumulated across
the row-block grid in a VMEM-resident output block (TPU grids execute
sequentially, making the revisited block a legal carry).

Tiling: grid over row blocks of ``block_b``; both weight matrices are small
(d <= 512 in TIG models) and are resident in VMEM for every grid step.
d_h is padded to a multiple of 128 lanes by the wrapper (ops.py), so the
(d_in, 3*d_h) matmuls hit the MXU with aligned shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fused_gru", "fused_gru_bwd"]


def _gru_kernel(x_ref, h_ref, wx_ref, wh_ref, bx_ref, bh_ref, out_ref):
    x = x_ref[...]
    h = h_ref[...]
    gx = jnp.dot(x, wx_ref[...],
                 preferred_element_type=jnp.float32) + bx_ref[...]
    gh = jnp.dot(h, wh_ref[...],
                 preferred_element_type=jnp.float32) + bh_ref[...]
    d_h = h.shape[-1]
    rx, zx, nx = gx[:, :d_h], gx[:, d_h:2 * d_h], gx[:, 2 * d_h:]
    rh, zh, nh = gh[:, :d_h], gh[:, d_h:2 * d_h], gh[:, 2 * d_h:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    out_ref[...] = ((1.0 - z) * n + z * h).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_gru(x, h, wx, wh, bx, bh, *, block_b: int = 128,
              interpret: bool = False):
    """h' = GRU(x, h).  Shapes: x (B, d_in), h (B, d_h), wx (d_in, 3*d_h),
    wh (d_h, 3*d_h), bx/bh (3*d_h,)."""
    b, d_in = x.shape
    d_h = h.shape[-1]
    block_b = min(block_b, b)
    grid = (pl.cdiv(b, block_b),)
    return pl.pallas_call(
        _gru_kernel,
        name="fused_gru",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d_in), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d_h), lambda i: (i, 0)),
            pl.BlockSpec((d_in, 3 * d_h), lambda i: (0, 0)),
            pl.BlockSpec((d_h, 3 * d_h), lambda i: (0, 0)),
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, d_h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d_h), h.dtype),
        interpret=interpret,
    )(x, h, wx, wh, bx, bh)


def _gru_bwd_kernel(g_ref, x_ref, h_ref, wx_ref, wh_ref, bx_ref, bh_ref,
                    dx_ref, dh_ref, dwx_ref, dwh_ref, dbx_ref, dbh_ref, *,
                    n_rows, block_b):
    i = pl.program_id(0)
    f32 = jnp.float32
    g = g_ref[...].astype(f32)
    x = x_ref[...].astype(f32)
    h = h_ref[...].astype(f32)
    # rows past n_rows are block padding: mask them out of the weight/bias
    # accumulators (their dx/dh writes are dropped by the block machinery)
    row = i * block_b + jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)
    valid = row < n_rows
    x = jnp.where(valid, x, 0.0)
    h = jnp.where(valid, h, 0.0)
    g = jnp.where(valid, g, 0.0)

    # in-VMEM recompute of the gates from the (x, h, weights) residuals
    gx = jnp.dot(x, wx_ref[...].astype(f32),
                 preferred_element_type=f32) + bx_ref[...]
    gh = jnp.dot(h, wh_ref[...].astype(f32),
                 preferred_element_type=f32) + bh_ref[...]
    d_h = h.shape[-1]
    rx, zx, nx = gx[:, :d_h], gx[:, d_h:2 * d_h], gx[:, 2 * d_h:]
    rh, zh, nh = gh[:, :d_h], gh[:, d_h:2 * d_h], gh[:, 2 * d_h:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)

    # out = (1-z)*n + z*h
    dn = g * (1.0 - z)
    dz = g * (h - n)
    dpre_n = dn * (1.0 - n * n)
    dpre_r = (dpre_n * nh) * r * (1.0 - r)
    dpre_z = dz * z * (1.0 - z)
    dgx = jnp.concatenate([dpre_r, dpre_z, dpre_n], axis=-1)
    dgh = jnp.concatenate([dpre_r, dpre_z, dpre_n * r], axis=-1)

    t_dims = (((1,), (1,)), ((), ()))      # contract gate axis: dg @ w.T
    a_dims = (((0,), (0,)), ((), ()))      # contract row axis:  op.T @ dg
    dx_ref[...] = jax.lax.dot_general(
        dgx, wx_ref[...].astype(f32), t_dims,
        preferred_element_type=f32).astype(dx_ref.dtype)
    dh_ref[...] = (jax.lax.dot_general(
        dgh, wh_ref[...].astype(f32), t_dims,
        preferred_element_type=f32) + g * z).astype(dh_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dwx_ref[...] = jnp.zeros_like(dwx_ref)
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        dbx_ref[...] = jnp.zeros_like(dbx_ref)
        dbh_ref[...] = jnp.zeros_like(dbh_ref)

    dwx_ref[...] += jax.lax.dot_general(
        x, dgx, a_dims, preferred_element_type=f32).astype(dwx_ref.dtype)
    dwh_ref[...] += jax.lax.dot_general(
        h, dgh, a_dims, preferred_element_type=f32).astype(dwh_ref.dtype)
    dbx_ref[...] += jnp.sum(dgx, axis=0).astype(dbx_ref.dtype)
    dbh_ref[...] += jnp.sum(dgh, axis=0).astype(dbh_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_gru_bwd(g, x, h, wx, wh, bx, bh, *, block_b: int = 128,
                  interpret: bool = False):
    """One-pass GRU backward: (dx, dh, dwx, dwh, dbx, dbh) from the output
    cotangent ``g`` and the forward residuals (inputs only — gates are
    recomputed in VMEM)."""
    b, d_in = x.shape
    d_h = h.shape[-1]
    block_b = min(block_b, b)
    grid = (pl.cdiv(b, block_b),)
    kernel = functools.partial(_gru_bwd_kernel, n_rows=b, block_b=block_b)
    row_spec = lambda cols: pl.BlockSpec((block_b, cols), lambda i: (i, 0))
    full = lambda rows, cols: pl.BlockSpec((rows, cols), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        name="fused_gru_bwd",
        grid=grid,
        in_specs=[
            row_spec(d_h),                               # g
            row_spec(d_in),                              # x
            row_spec(d_h),                               # h
            full(d_in, 3 * d_h),                         # wx
            full(d_h, 3 * d_h),                          # wh
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),    # bx
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),    # bh
        ],
        out_specs=[
            row_spec(d_in),                              # dx
            row_spec(d_h),                               # dh
            full(d_in, 3 * d_h),                         # dwx (accumulated)
            full(d_h, 3 * d_h),                          # dwh (accumulated)
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),    # dbx (accumulated)
            pl.BlockSpec((3 * d_h,), lambda i: (0,)),    # dbh (accumulated)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, d_in), x.dtype),
            jax.ShapeDtypeStruct((b, d_h), h.dtype),
            jax.ShapeDtypeStruct(wx.shape, wx.dtype),
            jax.ShapeDtypeStruct(wh.shape, wh.dtype),
            jax.ShapeDtypeStruct(bx.shape, bx.dtype),
            jax.ShapeDtypeStruct(bh.shape, bh.dtype),
        ],
        interpret=interpret,
    )(g, x, h, wx, wh, bx, bh)
