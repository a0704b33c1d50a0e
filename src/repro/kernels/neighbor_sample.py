"""Device-side temporal neighbor sampling kernel — Pallas TPU.

Host planning (``ChronoNeighborIndex.sample`` inside ``build_batch_program``)
pre-samples every batch's (B, K) neighbor grids on the CPU and ships them to
the device — a serial planner stage plus O(steps x B x K) H2D traffic per
epoch.  This kernel moves the sampling step onto the device: the T-CSR
(``ChronoNeighborIndex.device_export``) lives in HBM once per stream, the
scanned step hands over only raw edge records, and each query is answered
in-kernel.

Per grid step (a group of ``ROWS`` = 8 query rows, one at a time):

  * each query's segment bounds ``[start, stop)``, its batch-boundary
    search key and its window shift ride in scalar-prefetch SMEM (the
    bounds are a cheap XLA gather of ``indptr`` in the wrapper);
  * the event arrays stay in HBM (``memory_space=ANY``).  Mosaic moves a
    1-D HBM array only in whole 1024-element tiles, so every read is a
    tile-aligned ``CHUNK`` DMA into SMEM: the binary search (bisect_left
    on the per-event key ``batch + 1``, history = 0) loads the ``bat``
    chunk its probe falls in — only when that differs from the chunk
    already loaded, so a segment inside one chunk costs one DMA — giving
    the first event of a stream batch >= the boundary;
  * the K-wide window ``[end - (w+1)K, end - wK)`` of neighbor ids /
    times / edge rows (w = the row's window shift; 0 = the trailing K,
    the multi-layer fold asks for older windows per layer) spans at most
    two chunks, which are DMA'd for all three arrays concurrently; the
    window is in-bounds by construction because the export front-pads the
    buffers by K x depth and shifts ``indptr``;
  * slots before ``start`` are masked to the -1 / -1.0 padding as the
    scalars are written to the (ROWS, K) SMEM output blocks.

The event arrays must hold a whole number of chunks so every chunk DMA
stays in bounds.  ``export_length`` is that layout: the length
``ChronoNeighborIndex.device_export`` back-pads to and PAC's sharded event
cap is sized by.  The wrapper refuses any other length rather than copy
the arrays inside the scanned step.

HBM traffic is O(R x (search chunks + 2) x CHUNK) elements instead of the
host path's O(R x 3K) *transferred* elements — the reads hit memory that
is already device-resident, so the epoch's H2D volume shrinks to the raw
edge stream plus one T-CSR upload (see ``roofline.kernel_bytes``).

The pure-jnp oracle is ``ref.sample_ref``; parity is bit-exact (both
reproduce the host index's ``searchsorted`` semantics).  Sampling happens
before the differentiated section of the step (it produces integer ids and
already-materialized times), so no custom VJP is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["neighbor_sample_fwd", "export_length", "CHUNK"]

CHUNK = 1024    # elements of a 1-D 32-bit HBM tile: the unit Mosaic moves
ROWS = 8        # query rows per grid step (sublane height of the outputs)


def export_length(n_events: int, k: int, depth: int = 1) -> int:
    """Length of a ``device_export`` event array: the ``k * depth`` front
    pad plus ``n_events``, rounded up to whole ``CHUNK``s — the unit the
    sampling kernel DMAs, so its reads stay in bounds with no copy."""
    return -(-(n_events + k * depth) // CHUNK) * CHUNK


def _sample_kernel(start_ref, stop_ref, key_ref, win_ref,
                   bat_hbm, nbr_hbm, t_hbm, e_hbm,
                   ids_out, t_out, e_out,
                   bat_s, nbr_s, t_s, e_s, sem, *, iters, k, n_chunks):
    g = pl.program_id(0)

    def chunk_copy(hbm, c, dst, slot):
        at = pl.multiple_of(c * CHUNK, CHUNK)
        return pltpu.make_async_copy(
            hbm.at[pl.ds(at, CHUNK)], dst.at[pl.ds(slot * CHUNK, CHUNK)],
            sem)

    def row(j, carry):
        i = g * ROWS + j
        start = start_ref[i]
        key = key_ref[i]
        win = win_ref[i]

        def probe(_, c):
            lo, hi, loaded = c
            active = lo < hi
            mid = jax.lax.div(lo + hi, 2)
            want = mid // CHUNK

            @pl.when(jnp.logical_and(active, want != loaded))
            def _load():
                cp = chunk_copy(bat_hbm, want, bat_s, 0)
                cp.start()
                cp.wait()

            loaded = jnp.where(active, want, loaded)
            go = jnp.logical_and(active, bat_s[mid % CHUNK] < key)
            return (jnp.where(go, mid + 1, lo),
                    jnp.where(jnp.logical_and(active, ~go), mid, hi),
                    loaded)

        end, _, _ = jax.lax.fori_loop(0, iters, probe,
                                      (start, stop_ref[i], jnp.int32(-1)))

        # window ``win`` gathers [end-(win+1)k, end-win*k): in-bounds for
        # any win < export depth (the export front-pads the event arrays by
        # k*depth); the max(., 0) guards callers passing deeper windows,
        # whose out-of-segment slots the validity mask already kills
        w = jnp.maximum(end - (win + 1) * k, 0)
        c0 = w // CHUNK
        two = jnp.logical_and((w + k - 1) // CHUNK != c0, c0 + 1 < n_chunks)
        arrays = ((nbr_hbm, nbr_s), (t_hbm, t_s), (e_hbm, e_s))
        for hbm, dst in arrays:
            chunk_copy(hbm, c0, dst, 0).start()

        @pl.when(two)
        def _start_second():
            for hbm, dst in arrays:
                chunk_copy(hbm, c0 + 1, dst, 1).start()

        for hbm, dst in arrays:
            chunk_copy(hbm, c0, dst, 0).wait()

        @pl.when(two)
        def _wait_second():
            for hbm, dst in arrays:
                chunk_copy(hbm, c0 + 1, dst, 1).wait()

        off = w - c0 * CHUNK
        for s in range(k):
            valid = w + s >= start
            ids_out[j, s] = jnp.where(valid, nbr_s[off + s], -1)
            t_out[j, s] = jnp.where(valid, t_s[off + s], jnp.float32(-1.0))
            e_out[j, s] = jnp.where(valid, e_s[off + s], -1)
        return carry

    jax.lax.fori_loop(0, ROWS, row, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def neighbor_sample_fwd(indptr, nbr, t, eidx, bat, nodes, batch_of, *,
                        k: int, interpret: bool = False, window=None):
    """K most recent neighbors of ``nodes`` as of batch ``batch_of``.

    indptr: (N+1,) int32; nbr / t / eidx / bat: (pad + total,) event arrays
    from ``ChronoNeighborIndex.device_export``; nodes: (R,) int32;
    batch_of: scalar or (R,) int32; window: None (= 0, most recent),
    scalar, or (R,) int32 per-row K-window shift (multi-layer grids).
    Returns ((R, k) int32 ids, (R, k) float32 times, (R, k) int32 edge
    rows) matching ``ref.sample_ref``.
    """
    if k > CHUNK:
        raise ValueError(f"k={k} exceeds the {CHUNK}-event DMA chunk")
    r = nodes.shape[0]
    total = nbr.shape[0]
    if total % CHUNK:
        raise ValueError(f"event arrays of length {total} are not whole "
                         f"{CHUNK}-element chunks; pad them to "
                         "export_length (ChronoNeighborIndex.device_export "
                         "does)")
    nodes = nodes.astype(jnp.int32)
    # pad the queries to whole row groups: padded rows have empty segments
    rp = -(-r // ROWS) * ROWS
    padq = lambda a: jnp.pad(a, (0, rp - r))  # noqa: E731
    start = padq(indptr[nodes])
    stop = padq(indptr[nodes + 1])
    key = padq(jnp.broadcast_to(jnp.asarray(batch_of, jnp.int32) + 1, (r,)))
    window = 0 if window is None else window
    win = padq(jnp.broadcast_to(jnp.asarray(window, jnp.int32), (r,)))

    kernel = functools.partial(
        _sample_kernel, iters=max(1, int(total).bit_length()), k=k,
        n_chunks=total // CHUNK)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.BlockSpec((ROWS, k), lambda g, *_: (g, 0),
                       memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(rp // ROWS,),
        in_specs=[hbm, hbm, hbm, hbm],               # bat, nbr, t, eidx
        out_specs=[out, out, out],
        scratch_shapes=[
            pltpu.SMEM((CHUNK,), jnp.int32),         # bat probe chunk
            pltpu.SMEM((2 * CHUNK,), jnp.int32),     # nbr window chunks
            pltpu.SMEM((2 * CHUNK,), jnp.float32),   # t window chunks
            pltpu.SMEM((2 * CHUNK,), jnp.int32),     # eidx window chunks
            pltpu.SemaphoreType.DMA,
        ],
    )
    ids, tms, eix = pl.pallas_call(
        kernel,
        name="neighbor_sample",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rp, k), jnp.int32),
            jax.ShapeDtypeStruct((rp, k), jnp.float32),
            jax.ShapeDtypeStruct((rp, k), jnp.int32),
        ],
        interpret=interpret,
    )(start, stop, key, win, bat, nbr, t, eidx)
    return ids[:r], tms[:r], eix[:r]
