"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: kernels are validated against them in
``tests/test_kernels.py`` over shape/dtype sweeps (interpret=True on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "gru_ref",
    "temporal_attention_ref",
    "flush_ref",
    "sample_ref",
    "flash_attention_ref",
    "rwkv6_ref",
    "rwkv6_chunked_xla",
]


def gru_ref(x, h, wx, wh, bx, bh):
    """Fused GRU cell oracle.

    x: (B, d_in), h: (B, d_h); wx: (d_in, 3*d_h), wh: (d_h, 3*d_h);
    biases (3*d_h,).  Gate order: [reset, update, candidate] (matches
    ``repro.tig.modules.gru``).
    """
    gx = x @ wx + bx
    gh = h @ wh + bh
    rx, zx, nx = jnp.split(gx, 3, axis=-1)
    rh, zh, nh = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    return (1.0 - z) * n + z * h


def temporal_attention_ref(q, k, v, mask):
    """Masked neighbor attention oracle.

    q: (B, H, D); k, v: (B, K, H, D); mask: (B, K) bool.
    Rows with no valid neighbor yield exactly zero context.
    """
    scores = jnp.einsum("bhd,bkhd->bhk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1)
    att = jnp.where(mask.any(-1)[:, None, None], att, 0.0)
    return jnp.einsum("bhk,bkhd->bhd", att, v)


def flush_ref(ids, msg, ts, mem, last, wx, wh, bx, bh):
    """Message-pipeline oracle: segment-mean aggregation of the pending
    messages + GRU memory update + scatter of ``mem``/``last``.

    It computes the table write of ``repro.tig.models.flush_pending`` for
    the GRU flavors (the fused Pallas kernel in ``fused_flush.py`` is
    validated against it, and its custom VJP recomputes through it).

    ids: (R,) int32 touched rows (dump row ``mem.shape[0]-1`` = padding);
    msg: (R, dm) post-MSG messages; ts: (R,) event times; mem: (N+1, d);
    last: (N+1,); wx/wh/bx/bh: GRU gate parameters.
    Returns ``(mem', last', mbar)`` with ``mbar`` the (R, dm) per-row
    aggregated messages (consumed by TIGE's second-memory update).
    """
    n_dump = mem.shape[0] - 1
    live = ids < n_dump
    zeros = jnp.zeros((n_dump + 1, msg.shape[-1]), msg.dtype)
    sums = zeros.at[ids].add(jnp.where(live[:, None], msg, 0.0))
    cnt = jnp.zeros((n_dump + 1,), msg.dtype).at[ids].add(
        live.astype(msg.dtype))
    mbar_tbl = sums / jnp.clip(cnt, 1.0)[:, None]
    mbar = mbar_tbl[ids]
    s_new = gru_ref(mbar, mem[ids], wx, wh, bx, bh)
    mem = mem.at[ids].set(s_new).at[n_dump].set(0.0)
    last = last.at[ids].max(jnp.where(live, ts, 0.0)).at[n_dump].set(0.0)
    return mem, last, mbar


def sample_ref(indptr, nbr, t, eidx, bat, nodes, batch_of, k, window=0):
    """Device-side temporal neighbor sampling oracle over an exported T-CSR.

    Mirrors ``ChronoNeighborIndex.sample`` bit-for-bit on device: for each
    queried node a branchless binary search over the node's time-sorted
    event segment finds the first event of stream batch >= ``batch_of``
    (events carry the key ``batch + 1`` with history pinned to 0), then the
    K-wide window ``[end-(w+1)k, end-wk)`` before it is gathered, -1
    front-padded, oldest -> newest (w = ``window``, default 0 = most
    recent; the multi-layer fold passes per-row windows).

    indptr: (N+1,) int32 and nbr / t / eidx / bat: (pad + total,) arrays
    from ``ChronoNeighborIndex.device_export`` (front-padded by k*depth,
    so every window w < depth never underflows); nodes: (R,) int32 node
    ids; batch_of: scalar or (R,) int32 batch index — events of stream
    batches >= batch_of are excluded, history always included; window:
    scalar or (R,) int32.  Returns ((R, k) int32 ids, (R, k) float32
    times, (R, k) int32 edge rows).
    """
    total = nbr.shape[0]
    nodes = nodes.astype(jnp.int32)
    start = indptr[nodes]
    stop = indptr[nodes + 1]
    key = jnp.broadcast_to(
        jnp.asarray(batch_of, jnp.int32) + 1, nodes.shape)
    win = jnp.broadcast_to(jnp.asarray(window, jnp.int32), nodes.shape)
    # branchless bisect_left for `key` within [start, stop); the iteration
    # count is static (log2 of the buffer covers any segment length)
    lo, hi = start, stop
    for _ in range(max(1, int(total).bit_length())):
        mid = (lo + hi) // 2
        v = bat[jnp.minimum(mid, total - 1)]
        active = lo < hi
        go = active & (v < key)
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)
    end = lo
    idx = (end[:, None] - (win[:, None] + 1) * k
           + jnp.arange(k, dtype=jnp.int32)[None, :])
    valid = idx >= start[:, None]
    # in-bounds even if a caller passes window >= export depth (those
    # slots are already masked invalid); a no-op at window = 0
    idx = jnp.maximum(idx, 0)
    ids = jnp.where(valid, nbr[idx], -1)
    tms = jnp.where(valid, t[idx], jnp.float32(-1.0))
    eix = jnp.where(valid, eidx[idx], -1)
    return ids, tms, eix


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """Dense attention oracle (the thing flash attention must equal).

    q, k, v: (B, H, S, D).  ``window``: sliding-window size (#tokens each
    query may look back, incl. itself); None = unbounded.
    """
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(
        jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    m = jnp.ones((s, s), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    logits = jnp.where(m, logits, -1e30)
    att = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", att,
                      v.astype(jnp.float32)).astype(q.dtype)


def rwkv6_ref(r, k, v, w, u, *, state=None, return_state=False):
    """RWKV6 (Finch) WKV recurrence oracle — token-by-token scan.

    r, k, w: (B, H, S, Dk); v: (B, H, S, Dv); u: (H, Dk).
    ``w`` is the per-channel decay in (0, 1) (data-dependent in v6).
    state: optional (B, H, Dk, Dv) initial state.

        o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    b, h, s, dk = r.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    r, k, v, w = (x.astype(f32) for x in (r, k, v, w))
    u = u.astype(f32)
    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)

    def step(S, inp):
        rt, kt, vt, wt = inp      # (B,H,Dk) x3, (B,H,Dv)
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,Dk,Dv)
        o = jnp.einsum("bhk,bhkv->bhv", rt,
                       S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, o

    inputs = (jnp.moveaxis(r, 2, 0), jnp.moveaxis(k, 2, 0),
              jnp.moveaxis(v, 2, 0), jnp.moveaxis(w, 2, 0))
    state, o = jax.lax.scan(step, state, inputs)
    o = jnp.moveaxis(o, 0, 2)     # (B, H, S, Dv)
    if return_state:
        return o, state
    return o


def rwkv6_chunked_xla(r, k, v, w, u, *, state=None, chunk: int = 64,
                      return_state: bool = False):
    """Chunked WKV6 in pure XLA — the same matmul reformulation as the
    Pallas kernel (see rwkv6_scan.py for the math), used as the production
    XLA path: the token-by-token scan round-trips the (B,H,Dk,Dv) state
    through HBM S times; chunking turns that into S/C state carries plus
    three MXU matmuls per chunk (§Perf iteration B1)."""
    b, h, s, dk = r.shape
    dv = v.shape[-1]
    if s % chunk or s <= chunk:
        return rwkv6_ref(r, k, v, w, u, state=state,
                         return_state=return_state)
    nc = s // chunk
    f32 = jnp.float32
    rr, kk, vv, ww = (jnp.reshape(x.astype(f32), (b, h, nc, chunk, -1))
                      for x in (r, k, v, w))
    u = u.astype(f32)
    lw = jnp.log(jnp.clip(ww, 1e-38, 1.0))           # (B,H,NC,C,Dk)
    c = jnp.cumsum(lw, axis=-2)
    c_prev = c - lw
    c_tot = c[..., -1:, :]                            # (B,H,NC,1,Dk)
    z = 0.5 * c_tot

    r_dec = rr * jnp.exp(c_prev - z)
    k_dec = kk * jnp.exp(z - c)
    scores = jnp.einsum("bhnid,bhnjd->bhnij", r_dec, k_dec)
    ti = jnp.arange(chunk)
    scores = jnp.where(ti[None, :] < ti[:, None], scores, 0.0)
    intra = jnp.einsum("bhnij,bhnjd->bhnid", scores, vv)
    bonus = jnp.sum(rr * u[None, :, None, None, :] * kk,
                    axis=-1, keepdims=True) * vv

    # inter-chunk: sequential state carry (S/C steps instead of S)
    r_in = rr * jnp.exp(c_prev)                       # (B,H,NC,C,Dk)
    k_carry = kk * jnp.exp(c_tot - c)
    decay_tot = jnp.exp(c_tot[..., 0, :])             # (B,H,NC,Dk)
    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)

    def step(s0, inp):
        r_c, kc_c, v_c, dec = inp                     # per-chunk blocks
        inter = jnp.einsum("bhid,bhdv->bhiv", r_c, s0)
        s1 = dec[..., None] * s0 + jnp.einsum("bhjd,bhjv->bhdv", kc_c, v_c)
        return s1, inter

    xs = (jnp.moveaxis(r_in, 2, 0), jnp.moveaxis(k_carry, 2, 0),
          jnp.moveaxis(vv, 2, 0), jnp.moveaxis(decay_tot, 2, 0))
    state, inter = jax.lax.scan(step, state, xs)
    inter = jnp.moveaxis(inter, 0, 2)                 # (B,H,NC,C,Dv)

    o = (intra + bonus + inter).reshape(b, h, s, dv).astype(r.dtype)
    if return_state:
        return o, state
    return o
