"""Public kernel entry points with backend dispatch.

Each op picks its execution path:
  * ``backend="pallas"``     — pl.pallas_call targeting real TPUs,
  * ``backend="interpret"``  — the same kernel body executed in Python on
                               CPU (correctness validation; what tests use),
  * ``backend="xla"``        — the pure-jnp oracle from ``ref.py`` (what the
                               models use without ``use_pallas``).

``default_backend()`` resolves "auto": pallas on TPU, xla elsewhere; a
failure to find any device is raised, never read as "CPU".  The
``REPRO_KERNEL_BACKEND`` environment variable overrides the "auto"
resolution (e.g. ``REPRO_KERNEL_BACKEND=interpret`` exercises the Pallas
kernel bodies on CPU without touching any config).

Backward passes: the differentiable ops (``gru``, ``temporal_attention``,
``fused_flush``) carry custom VJPs.  For gru/attention the default
backward is a real Pallas kernel (flash-style in-kernel recompute from the
input residuals — one HBM pass per operand); ``bwd="oracle"`` (or
``REPRO_KERNEL_BWD=oracle``) falls back to differentiating the pure-jnp
oracle from ``ref.py``, which is the parity reference and what the
``"xla"`` backend uses implicitly.  ``fused_flush`` always differentiates
through its oracle (``ref.flush_ref``); ``models.flush_pending`` calls it
under ``stop_gradient`` as the table write and takes the gradient through
the 2B updated rows instead, so training never runs that backward.

MXU alignment: the f32 TPU tile is (8, 128) and the MXU is 128x128, so
kernels fed unaligned feature dims waste tile columns.  The Pallas-bound
ops below lane-pad their feature dims to multiples of 128 (and the
neighbor axis to 8 sublanes) HERE, once, in plain differentiable jnp —
before the custom-VJP wrappers, so autodiff transposes pad -> slice for
free — and slice the results back.  The kernels themselves stay
shape-generic, and the ``ref.py`` oracles stay UNPADDED: parity tests
against them prove the padding is value-invariant.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _fa_pallas
from repro.kernels.fused_flush import fused_flush_fwd as _flush_pallas
from repro.kernels.fused_gru import fused_gru as _gru_pallas
from repro.kernels.fused_gru import fused_gru_bwd as _gru_bwd_pallas
from repro.kernels.neighbor_sample import neighbor_sample_fwd as _ns_pallas
from repro.kernels.rwkv6_scan import rwkv6_chunked as _wkv_pallas
from repro.kernels.temporal_attn import temporal_attn as _tattn_pallas
from repro.kernels.temporal_attn import temporal_attn_bwd as _tattn_bwd_pallas

__all__ = ["default_backend", "default_bwd", "gru", "temporal_attention",
           "fused_flush", "neighbor_sample", "flash_attention", "rwkv6",
           "lane_pad", "LANES", "SUBLANES"]


@functools.cache
def default_backend() -> str:
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"


def _resolve(backend: str | None) -> str:
    if backend not in (None, "auto"):
        return backend
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in ("xla", "pallas", "interpret", "scan"):
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r}: expected one of "
                "xla / pallas / interpret / scan")
        return env
    return default_backend()


def default_bwd() -> str:
    env = os.environ.get("REPRO_KERNEL_BWD")
    if env:
        if env not in ("fused", "oracle"):
            raise ValueError(
                f"REPRO_KERNEL_BWD={env!r}: expected fused / oracle")
        return env
    return "fused"


def _resolve_bwd(bwd: str | None) -> str:
    return bwd if bwd not in (None, "auto") else default_bwd()


# ----------------------------------------------------------- MXU alignment

LANES = 128      # last-dim tile width (f32) — MXU columns
SUBLANES = 8     # second-to-last-dim tile height (f32)


def _pad_to(n: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``n``."""
    return -(-n // m) * m


def lane_pad(n: int) -> int:
    """Lane-aligned width of a feature dim: what the MXU tier actually
    launches for a raw dim ``n`` (compiled-program cache keys hash this)."""
    return _pad_to(n, LANES)


def _pad_axis(x, target: int, axis: int = -1):
    """Zero-pad ``x`` along ``axis`` up to length ``target`` (no-op when
    already there).  Plain jnp: under autodiff this transposes to a slice,
    keeping the custom-VJP kernels downstream oblivious to padding."""
    n = x.shape[axis]
    if n == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis % x.ndim] = (0, target - n)
    return jnp.pad(x, pad)


def _pad_gates(w, d_h: int, d_p: int, axis: int = -1):
    """Pad a GRU [r|z|n] gate matrix/bias from 3*d_h to 3*d_p along
    ``axis``, padding each gate block separately so kernels (and the
    oracle) that split gates at thirds keep addressing the right block."""
    if d_h == d_p:
        return w
    blocks = jnp.split(w, 3, axis=axis)
    return jnp.concatenate([_pad_axis(b, d_p, axis) for b in blocks],
                           axis=axis)


# The TIG training scan differentiates through the fused kernels, but raw
# ``pallas_call`` has no transpose rule.  Fix: custom VJP.  The default
# backward (``bwd="fused"``) is a real Pallas kernel that recomputes the
# gates/softmax in VMEM from the input residuals; ``bwd="oracle"`` keeps
# the original fallback — differentiate the pure-jnp oracle (ref.py),
# recomputing the forward through XLA.  Both produce gradients identical
# to the XLA path (the kernels are validated against the oracles).

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _gru_fused(x, h, wx, wh, bx, bh, interpret, bwd):
    return _gru_pallas(x, h, wx, wh, bx, bh, interpret=interpret)


def _gru_fused_fwd(x, h, wx, wh, bx, bh, interpret, bwd):
    return (_gru_fused(x, h, wx, wh, bx, bh, interpret, bwd),
            (x, h, wx, wh, bx, bh))


def _gru_fused_bwd(interpret, bwd, res, g):
    if bwd == "oracle":
        _, vjp = jax.vjp(ref.gru_ref, *res)
        return vjp(g)
    return _gru_bwd_pallas(g, *res, interpret=interpret)


_gru_fused.defvjp(_gru_fused_fwd, _gru_fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tattn_fused(q, k, v, mask, interpret, bwd):
    return _tattn_pallas(q, k, v, mask, interpret=interpret)


def _tattn_fused_fwd(q, k, v, mask, interpret, bwd):
    return _tattn_fused(q, k, v, mask, interpret, bwd), (q, k, v, mask)


def _tattn_fused_bwd(interpret, bwd, res, g):
    q, k, v, mask = res
    if bwd == "oracle":
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ref.temporal_attention_ref(q_, k_, v_, mask),
            q, k, v)
        return (*vjp(g), None)
    return (*_tattn_bwd_pallas(g, q, k, v, mask, interpret=interpret), None)


_tattn_fused.defvjp(_tattn_fused_fwd, _tattn_fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _flush_fused(ids, msg, ts, mem, last, wx, wh, bx, bh, interpret):
    return _flush_pallas(ids, msg, ts, mem, last, wx, wh, bx, bh,
                         interpret=interpret)


def _flush_fused_fwd(ids, msg, ts, mem, last, wx, wh, bx, bh, interpret):
    return (_flush_fused(ids, msg, ts, mem, last, wx, wh, bx, bh, interpret),
            (ids, msg, ts, mem, last, wx, wh, bx, bh))


def _flush_fused_bwd(interpret, res, g):
    ids = res[0]
    _, vjp = jax.vjp(
        lambda *diff: ref.flush_ref(ids, *diff), *res[1:])
    return (None, *vjp(g))


_flush_fused.defvjp(_flush_fused_fwd, _flush_fused_bwd)


def gru(x, h, wx, wh, bx, bh, *, backend: str | None = None,
        bwd: str | None = None):
    b = _resolve(backend)
    if b in ("xla", "scan"):   # "scan" only exists for rwkv6 -> oracle here
        return ref.gru_ref(x, h, wx, wh, bx, bh)
    # MXU tier: pad d_in and d_h up to 128 lanes.  Padded h columns are 0,
    # padded gate columns see zero pre-activations (r = z = 0.5, n = 0), so
    # padded outputs are (1-z)*0 + z*0 = 0 and real columns are unchanged.
    d_in, d_h = x.shape[-1], h.shape[-1]
    d_in_p, d_h_p = _pad_to(d_in, LANES), _pad_to(d_h, LANES)
    if (d_in_p, d_h_p) != (d_in, d_h):
        x = _pad_axis(x, d_in_p)
        h = _pad_axis(h, d_h_p)
        wx = _pad_gates(_pad_axis(wx, d_in_p, axis=0), d_h, d_h_p)
        wh = _pad_gates(_pad_axis(wh, d_h_p, axis=0), d_h, d_h_p)
        bx = _pad_gates(bx, d_h, d_h_p)
        bh = _pad_gates(bh, d_h, d_h_p)
    out = _gru_fused(x, h, wx, wh, bx, bh, b == "interpret",
                     _resolve_bwd(bwd))
    return out[..., :d_h]


def temporal_attention(q, k, v, mask, *, backend: str | None = None,
                       bwd: str | None = None):
    b = _resolve(backend)
    if b in ("xla", "scan"):
        return ref.temporal_attention_ref(q, k, v, mask)
    # MXU tier: pad the head dim D to 128 lanes and the neighbor axis K to
    # 8 sublanes (padded slots masked False).  Kernel and oracle both scale
    # scores by 1/sqrt(D of their input), so q is pre-scaled by
    # sqrt(D_p/D): the padded launch then computes the raw 1/sqrt(D)
    # scores exactly (zero-padded D columns add nothing to q.k).
    d, kn = q.shape[-1], k.shape[1]
    d_p, k_p = _pad_to(d, LANES), _pad_to(kn, SUBLANES)
    if d_p != d:
        q = q * jnp.sqrt(jnp.float32(d_p) / jnp.float32(d))
        q = _pad_axis(q, d_p)
        k = _pad_axis(k, d_p)
        v = _pad_axis(v, d_p)
    if k_p != kn:
        k = _pad_axis(k, k_p, axis=1)
        v = _pad_axis(v, k_p, axis=1)
        mask = _pad_axis(mask, k_p, axis=1)
    out = _tattn_fused(q, k, v, mask, b == "interpret", _resolve_bwd(bwd))
    return out[..., :d]


def fused_flush(ids, msg, ts, mem, last, wx, wh, bx, bh, *,
                backend: str | None = None):
    """The whole ``flush_pending`` message pipeline (segment-mean + GRU +
    mem/last scatter) as one kernel; ``(mem', last', mbar)``.  Backward is
    always the ``ref.flush_ref`` oracle VJP."""
    b = _resolve(backend)
    if b in ("xla", "scan"):
        return ref.flush_ref(ids, msg, ts, mem, last, wx, wh, bx, bh)
    # MXU tier: pad ONLY the message (d_msg) side — msg columns plus the
    # matching wx rows (zero rows contribute nothing to the gate matmul).
    # The (N+1, d) memory table is aliased in place; padding d_h would
    # reintroduce O(N) HBM traffic the kernel exists to avoid.
    dm = msg.shape[-1]
    dm_p = _pad_to(dm, LANES)
    if dm_p != dm:
        msg = _pad_axis(msg, dm_p)
        wx = _pad_axis(wx, dm_p, axis=0)
    mem2, last2, mbar = _flush_fused(ids, msg, ts, mem, last, wx, wh,
                                     bx, bh, b == "interpret")
    return mem2, last2, mbar[..., :dm]


def neighbor_sample(tcsr, nodes, batch_of, k, *, backend: str | None = None,
                    window=None):
    """K most recent temporal neighbors from a device-resident T-CSR.

    ``tcsr`` is the staged dict from ``ChronoNeighborIndex.device_export``
    (keys indptr / nbr / t / eidx / bat); nodes: (R,) int32; batch_of:
    scalar or (R,) int32 batch index (events of stream batches >= batch_of
    are excluded, history always included); window: None (= 0), scalar or
    (R,) int32 K-window shift — window w returns events
    ``[end-(w+1)K, end-wK)``, the multi-layer fold's per-layer grids
    (requires an export with depth > w).  Returns ((R, k) ids, times,
    edge rows), -1 / -1.0 front-padded, oldest -> newest — bit-identical
    to ``ChronoNeighborIndex.sample``.

    Forward-only: sampling produces integer ids and already-materialized
    times before the differentiated section of the step, so there is no
    VJP to define.
    """
    b = _resolve(backend)
    args = (tcsr["indptr"], tcsr["nbr"], tcsr["t"], tcsr["eidx"],
            tcsr["bat"], nodes, batch_of)
    if b in ("xla", "scan"):
        return ref.sample_ref(*args, k, 0 if window is None else window)
    return _ns_pallas(*args, k=k, interpret=(b == "interpret"),
                      window=window)


def flash_attention(q, k, v, *, causal=True, window=None,
                    backend: str | None = None, block_q=128, block_k=128):
    b = _resolve(backend)
    if b in ("xla", "scan"):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa_pallas(q, k, v, causal=causal, window=window,
                      block_q=block_q, block_k=block_k,
                      interpret=(b == "interpret"))


def rwkv6(r, k, v, w, u, *, state=None, chunk=64,
          backend: str | None = None, return_state=True):
    b = _resolve(backend)
    if b == "xla":
        # chunked XLA path (falls back to the token scan for short/ragged
        # sequences) — §Perf iteration B1: ~chunk-fold fewer state carries.
        o, s = ref.rwkv6_chunked_xla(r, k, v, w, u, state=state,
                                     chunk=chunk, return_state=True)
    elif b == "scan":
        o, s = ref.rwkv6_ref(r, k, v, w, u, state=state, return_state=True)
    else:
        o, s = _wkv_pallas(r, k, v, w, u, state=state, chunk=chunk,
                           interpret=(b == "interpret"))
    return (o, s) if return_state else o
