"""Temporal neighbor attention — Pallas TPU kernels (forward and backward).

The TGN/TIGE embedding module attends from each node over its K sampled
temporal neighbors (K is small, 10-32).  XLA handles the einsums fine but
round-trips the (B, H, K) score tensor and the (B, K, H, D) projections
through HBM; with K this small the whole per-row working set fits VMEM, so
we fuse QK^T -> mask -> softmax -> AV into one kernel.

The backward kernel is flash-attention-style: scores and the softmax are
recomputed in VMEM from (q, k, v, mask) — nothing but the inputs is saved
as residuals — so the backward pass makes one HBM read per operand and one
write per gradient instead of round-tripping the (B, H, K) attention
tensor and its cotangent chain through HBM.

Tiling: grid over row blocks (block_b); K and the head dims live entirely in
VMEM.  The mask handles both empty slots and rows with zero neighbors
(output exactly 0 — matching the oracle and the model semantics for
never-seen nodes).

Layout: Mosaic lowers batched contractions with ONE leading batch dim only
(the per-head form ``"bhd,bkhd->bhk"`` carries two and is refused).  So
the jitted wrappers below fold the head axis into the neighbor axis — a
reshape of k/v from (B, K, H, D) to (B, J = K*H, D), slot j = k*H + h,
that XLA fuses into the producing projection — and the kernels contract ``"bhd,bjd->bhj"``: every head
scores every slot, and a head-diagonal mask (j % H == h) drops the H-1
cross-head columns before the softmax.  At the H <= 2 of the TIG models
that doubles the score FLOPs of a kernel whose cost is its HBM reads.

The kernel itself is shape-generic, but the public wrapper
(``kernels/ops.py``) pads the head dim D to a multiple of 128 lanes and K
to a multiple of 8 sublanes before calling it, so the QK^T/AV contractions
here always see MXU-aligned tiles.  Padded K slots arrive with
``mask=False`` (they never contribute); the padded tail of D is zeros on
both q and k, with q pre-scaled so the 1/sqrt(D_padded) below equals the
raw 1/sqrt(D) — the wrapper's padding is value-invariant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["temporal_attn", "temporal_attn_bwd"]


def _softmax(q, k, mask):
    """Head-diagonal masked softmax over the folded slots: q (b, H, D),
    k (b, J, D), mask (b, J) int32 -> (b, H, J), zero for rows with no
    valid neighbor and for every cross-head slot."""
    h, j = q.shape[1], k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bhd,bjd->bhj", q, k,
                        preferred_element_type=jnp.float32) * scale
    head = jax.lax.broadcasted_iota(jnp.int32, (1, h, j), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, h, j), 2)
    valid = jnp.logical_and(mask[:, None, :] > 0, slot % h == head)
    scores = jnp.where(valid, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    att = e / jnp.sum(e, axis=-1, keepdims=True)
    any_nbr = jnp.max(mask, axis=-1, keepdims=True)[:, :, None] > 0
    return jnp.where(any_nbr, att, 0.0)


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (b, H, D)
    k = k_ref[...].astype(jnp.float32)          # (b, J, D)
    v = v_ref[...].astype(jnp.float32)
    att = _softmax(q, k, mask_ref[...])
    ctx = jnp.einsum("bhj,bjd->bhd", att, v,
                     preferred_element_type=jnp.float32)
    out_ref[...] = ctx.astype(out_ref.dtype)


def _fold(k, v, mask):
    """(B, K, H, D) k/v and (B, K) mask -> the kernels' (B, K*H, D) slots
    and (B, K*H) int32 mask (slot j = k*H + h)."""
    b, kk, h, d = k.shape
    m = jnp.repeat(mask.astype(jnp.int32), h, axis=1)
    return k.reshape(b, kk * h, d), v.reshape(b, kk * h, d), m


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def temporal_attn(q, k, v, mask, *, block_b: int = 64,
                  interpret: bool = False):
    """Masked attention over sampled neighbors.

    q: (B, H, D); k, v: (B, K, H, D); mask: (B, K) bool -> (B, H, D).
    """
    b, h, d = q.shape
    k2, v2, m2 = _fold(k, v, mask)
    j = k2.shape[1]
    block_b = min(block_b, b)
    row3 = lambda n: pl.BlockSpec((block_b, n, d), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _attn_kernel,
        name="temporal_attn",
        grid=(pl.cdiv(b, block_b),),
        in_specs=[row3(h), row3(j), row3(j),
                  pl.BlockSpec((block_b, j), lambda i: (i, 0))],
        out_specs=row3(h),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(q, k2, v2, m2)


def _attn_bwd_kernel(g_ref, q_ref, k_ref, v_ref, mask_ref,
                     dq_ref, dk_ref, dv_ref):
    f32 = jnp.float32
    g = g_ref[...].astype(f32)                   # (b, H, D)
    q = q_ref[...].astype(f32)
    k = k_ref[...].astype(f32)                   # (b, J, D)
    v = v_ref[...].astype(f32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], f32))

    # in-VMEM softmax recompute (identical math to the forward kernel)
    att = _softmax(q, k, mask_ref[...])

    # masked and cross-head slots have att == 0, so the softmax-backward
    # formula below already routes zero gradient to them (and to
    # zero-neighbor rows)
    dv = jnp.einsum("bhj,bhd->bjd", att, g, preferred_element_type=f32)
    datt = jnp.einsum("bhd,bjd->bhj", g, v, preferred_element_type=f32)
    ds = att * (datt - jnp.sum(att * datt, axis=-1, keepdims=True))
    dq = jnp.einsum("bhj,bjd->bhd", ds, k, preferred_element_type=f32)
    dk = jnp.einsum("bhj,bhd->bjd", ds, q, preferred_element_type=f32)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def temporal_attn_bwd(g, q, k, v, mask, *, block_b: int = 64,
                      interpret: bool = False):
    """One-pass attention backward: (dq, dk, dv) from the output cotangent
    ``g`` and the forward inputs (softmax recomputed in VMEM)."""
    b, h, d = q.shape
    k2, v2, m2 = _fold(k, v, mask)
    j = k2.shape[1]
    block_b = min(block_b, b)
    row3 = lambda n: pl.BlockSpec((block_b, n, d), lambda i: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        _attn_bwd_kernel,
        name="temporal_attn_bwd",
        grid=(pl.cdiv(b, block_b),),
        in_specs=[row3(h), row3(h), row3(j), row3(j),
                  pl.BlockSpec((block_b, j), lambda i: (i, 0))],
        out_specs=[row3(h), row3(j), row3(j)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k2.shape, k.dtype),
            jax.ShapeDtypeStruct(v2.shape, v.dtype),
        ],
        interpret=interpret,
    )(g, q, k2, v2, m2)
    return dq, dk.reshape(k.shape), dv.reshape(v.shape)
