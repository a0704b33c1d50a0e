"""Fused TGN message-pipeline kernel — Pallas TPU.

``flush_pending`` (repro.tig.models) applies the previous batch's stashed
messages to node memory: segment-mean aggregation of the (R=2B, d_msg)
pending messages per touched node, a GRU update of those nodes' memory
rows, and a scatter of the new ``mem``/``last`` values.  With (N+1, d_msg)
aggregation tables (scatter-add sums + counts, as ``ref.flush_ref`` builds
them) that is O(N) HBM traffic per step for work that only touches 2B
rows.  TGL (Zhou et al., 2022) identifies exactly this
mailbox/memory-update scatter as the step-time bottleneck at scale.

This kernel does the whole pipeline in one ``pallas_call`` with O(R) HBM
traffic:

  * Mosaic moves HBM rows only in whole (8, 128)-tiles, so the grid runs
    over the distinct 8-row TILES of ``mem`` that the R ids touch (sorted,
    in scalar-prefetch SMEM), and the BlockSpec index maps gather tile
    ``tiles[i]`` of ``mem`` — and the 1024-element chunk of ``last`` it
    falls in — straight into VMEM and scatter the results back; no
    aggregation tables, no O(N) pass;
  * the segment mean is an equality-mask matmul of the tile's 8 node ids
    against the VMEM-resident (R, d_msg) message block, so duplicate ids
    need no special casing: each node's row is written once, by the one
    grid step that owns its tile;
  * gate math (the GRU) runs in VMEM on the 8 gathered rows, untouched
    rows of the tile are written back unchanged;
  * ``mem``/``last`` are input/output-aliased, so untouched tiles are
    untouched in HBM.

The tile list is padded to a static length by repeating its last entry.
Pallas keeps a block resident (no re-fetch, no write-back) while
consecutive steps map to the same block, so a repeated step would only
redo the same work; it is skipped.  Sorting makes every ``last`` chunk's
steps consecutive for the same reason.  The dump row (``mem.shape[0]-1``)
is always among the tiles (the wrapper appends it) and is re-zeroed, as
in ``ref.flush_ref``.

MXU alignment: the public wrapper (``kernels/ops.py``) pads ONLY the
d_msg side (message columns + the wx rows) to a multiple of 128 lanes
before calling this kernel.  The memory table is aliased in place and
keeps its raw width — padding d_mem would force an O(N) copy and defeat
the O(R)-traffic point of the kernel (Mosaic slices the unaligned gate
blocks itself).  Padded message columns feed zero weight rows, so
mem/last/mbar on the raw columns are unchanged by the padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_flush_fwd"]

TILE = 8        # rows of a 2-D f32 HBM tile: the unit Mosaic moves
CHUNK = 1024    # elements of a 1-D f32 HBM tile (the ``last`` block)
_HI = jax.lax.Precision.HIGHEST   # one-hot sums must not round messages


def _flush_kernel(tiles_ref, ids_row_ref, ids_col_ref, ts_col_ref, msg_ref,
                  mem_ref, last_ref, wx_ref, wh_ref, bx_ref, bh_ref,
                  mem_out_ref, last_out_ref, mbar_ref, *, n_dump, tb, lb):
    f32 = jnp.float32
    i = pl.program_id(0)
    t = tiles_ref[i]
    prev = tiles_ref[jnp.maximum(i - 1, 0)]
    ids_row = ids_row_ref[...]                        # (1, R) int32
    live_row = ids_row < n_dump

    def segment_mean(nodes):
        """Mean pending message of each node in the (n, 1) column."""
        eq = jnp.logical_and(nodes == ids_row, live_row).astype(f32)
        cnt = jnp.sum(eq, axis=1, keepdims=True)
        sums = jnp.dot(eq, msg_ref[...].astype(f32), precision=_HI,
                       preferred_element_type=f32)
        return sums / jnp.maximum(cnt, 1.0), cnt

    @pl.when(i == 0)
    def _mbar():
        mbar, _ = segment_mean(ids_col_ref[...])
        mbar_ref[...] = mbar.astype(mbar_ref.dtype)

    @pl.when(jnp.logical_or(i == 0, t != prev))
    def _tile():
        node = t * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        mbar, cnt = segment_mean(node)
        s_old = mem_ref[...].astype(f32)              # (tb, d)
        d = s_old.shape[-1]
        gx = jnp.dot(mbar, wx_ref[...].astype(f32),
                     preferred_element_type=f32) + bx_ref[...]
        gh = jnp.dot(s_old, wh_ref[...].astype(f32),
                     preferred_element_type=f32) + bh_ref[...]
        r = jax.nn.sigmoid(gx[:, :d] + gh[:, :d])
        z = jax.nn.sigmoid(gx[:, d:2 * d] + gh[:, d:2 * d])
        n = jnp.tanh(gx[:, 2 * d:] + r * gh[:, 2 * d:])
        s_new = jnp.where(cnt > 0, (1.0 - z) * n + z * s_old, s_old)
        mem_out_ref[...] = jnp.where(node == n_dump, 0.0,
                                     s_new).astype(mem_out_ref.dtype)

    chunk = (t * tb) // lb

    @pl.when(jnp.logical_or(i == 0, chunk != (prev * tb) // lb))
    def _last():
        node = chunk * lb + jax.lax.broadcasted_iota(jnp.int32, (1, lb), 1)
        ids_col = ids_col_ref[...]                    # (R, 1)
        hit = jnp.logical_and(ids_col == node, ids_col < n_dump)
        tmax = jnp.max(jnp.where(hit, ts_col_ref[...], -3.4e38), axis=0,
                       keepdims=True)                 # (1, lb)
        last_out_ref[...] = jnp.where(
            (node == n_dump).reshape(lb), 0.0,
            jnp.maximum(last_ref[...], tmax.reshape(lb))
        ).astype(last_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_flush_fwd(ids, msg, ts, mem, last, wx, wh, bx, bh, *,
                    interpret: bool = False):
    """Segment-mean + GRU + scatter in one launch.

    ids: (R,) int32; msg: (R, dm); ts: (R,); mem: (N+1, d); last: (N+1,);
    GRU weights as in ``ref.gru_ref``.  Returns ``(mem', last', mbar)``
    matching ``ref.flush_ref``.
    """
    n_rows, dm = msg.shape
    n1, d = mem.shape
    n_dump = n1 - 1
    tb, lb = min(TILE, n1), min(CHUNK, n1)

    # append the dump row (so its tile is always re-zeroed) and pad the
    # rows to a sublane multiple; padding rows are dead (id = dump)
    rp = -(-(n_rows + 1) // 8) * 8
    ids_p = jnp.concatenate([ids.astype(jnp.int32),
                             jnp.full((rp - n_rows,), n_dump, jnp.int32)])
    msg_p = jnp.pad(msg, ((0, rp - n_rows), (0, 0)))
    ts_p = jnp.pad(ts.astype(last.dtype), (0, rp - n_rows))
    tiles = ids_p // tb
    tiles = jnp.unique(tiles, size=rp, fill_value=jnp.max(tiles))

    kernel = functools.partial(_flush_kernel, n_dump=n_dump, tb=tb, lb=lb)
    whole = lambda shape: pl.BlockSpec(shape, lambda i, t: (0, 0))
    mem_blk = pl.BlockSpec((tb, d), lambda i, t: (t[i], 0))
    last_blk = pl.BlockSpec((lb,), lambda i, t: ((t[i] * tb) // lb,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rp,),
        in_specs=[
            whole((1, rp)),                    # ids (row vector)
            whole((rp, 1)),                    # ids (column vector)
            whole((rp, 1)),                    # ts  (column vector)
            whole((rp, dm)),                   # msg
            mem_blk,                           # mem tile
            last_blk,                          # last chunk
            whole((dm, 3 * d)),                # wx
            whole((d, 3 * d)),                 # wh
            whole((1, 3 * d)),                 # bx
            whole((1, 3 * d)),                 # bh
        ],
        out_specs=[mem_blk, last_blk, whole((rp, dm))],
    )
    mem_out, last_out, mbar = pl.pallas_call(
        kernel,
        name="fused_flush",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n1, d), mem.dtype),
            jax.ShapeDtypeStruct((n1,), last.dtype),
            jax.ShapeDtypeStruct((rp, dm), msg.dtype),
        ],
        # inputs count the scalar-prefetch arg: 5 = mem, 6 = last
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tiles, ids_p[None, :], ids_p[:, None], ts_p[:, None], msg_p,
      mem, last, wx, wh, bx[None, :], bh[None, :])
    return mem_out, last_out, mbar[:n_rows]
