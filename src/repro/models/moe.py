"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Design (see DESIGN.md §5): instead of the GShard one-hot dispatch einsum
(whose (T, E, C) tensors dwarf the useful compute), tokens are routed by
*sorting* the flattened (token, expert) assignments by expert id and
scattering into a capacity-bucketed (E, C+1, d) buffer (slot C is the
overflow dump).  The expert matmuls are then plain batched GEMMs — the only
O(T·k·d·d_ff) compute — and the combine is a weighted scatter-add.  Experts
shard over the mesh "model" axis (expert parallelism); XLA inserts the
token exchange collectives from the shardings.

Router aux loss: the standard load-balance term E * sum_e f_e * P_e
(Switch/GShard), returned alongside so PAC... the LM loss can add it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compat
from repro.models.layers import _act, linear_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, d: int, d_ff: int, n_experts: int, act: str) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    def e_init(k, din, dout):
        return jax.random.normal(k, (n_experts, din, dout), jnp.float32) \
            * (din ** -0.5)
    p = {
        "router": linear_init(k1, d, n_experts),
        "wi": e_init(k2, d, d_ff),
        "wo": e_init(k4, d_ff, d),
    }
    if act in ("swiglu", "geglu"):
        p["wg"] = e_init(k3, d, d_ff)
    return p


def moe_apply(p: dict, x: jnp.ndarray, *, top_k: int, act: str,
              capacity_factor: float = 1.25, dropless: bool = False):
    """x: (T, d) -> (y: (T, d), aux_loss: scalar).

    Tokens beyond an expert's capacity C = ceil(T * top_k / E * cf) are
    dropped (contribute zero), the standard capacity-based behaviour.
    ``dropless=True`` sets C = T (serving: one token must never be dropped,
    and decode batches are small enough that the buffer stays cheap).
    """
    t, d = x.shape
    e = p["wi"].shape[0]
    logits = x.astype(jnp.float32) @ p["router"]["w"]        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)               # (T, k)
    # renormalize the chosen gates (Qwen/Mixtral convention)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary (Switch eq.4-6) ----
    me = probs.mean(axis=0)                                   # (E,)
    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)      # (T, k, E)
    ce = onehot.sum(axis=(0, 1)) / (t * top_k)                # fraction
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    cap = t if dropless else int(max(1, -(-t * top_k // e)
                                     * capacity_factor))
    flat_e = top_i.reshape(-1)                                # (T*k,)
    flat_t = jnp.arange(t * top_k) // top_k                   # (T*k,) token
    flat_w = top_p.reshape(-1).astype(x.dtype)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * top_k) - starts[se]                  # rank in expert
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                          # cap = dump

    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[se, slot].set(x[st], mode="drop")

    h = _act(act, jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(x.dtype)))
    if "wg" in p:
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype))
    yb = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))

    contrib = yb[se, slot] * sw[:, None] * keep[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    return y, aux


def moe_apply_sharded(p: dict, x: jnp.ndarray, *, top_k: int, act: str,
                      capacity_factor: float, token_axes,
                      expert_axis: str = "model"):
    """Expert-parallel MoE via shard_map (§Perf iteration A1).

    Under plain pjit the sort-based dispatch crosses the data<->model
    sharding boundary, so GSPMD materializes and all-reduces the global
    (E, C, d) dispatch buffer — ~1000s of collective time per step for the
    235B config.  Here each (data, model) device instead:

      1. routes ITS token shard with the (replicated, tiny) router,
      2. keeps only assignments to ITS local experts (everything else goes
         to a dump expert slot), sorts locally, capacity cap/shard,
      3. runs its local expert GEMMs,
      4. psum's the combined output over the expert axis — the ONLY
         collective: O(T_loc * d) per layer instead of O(E * C * d).

    Per-expert capacity is ceil(T_loc*k/E*cf) per data shard, which sums to
    the same global capacity as the pjit path (drop pattern differs
    per-shard, the standard behaviour of distributed capacity MoE).
    """
    t, d = x.shape
    e_total = p["wi"].shape[0]
    has_gate = "wg" in p

    def body(router_w, wi, wo, wg_or_none, xs):
        x_loc = xs                                    # (T_loc, d)
        t_loc = x_loc.shape[0]
        e_loc = wi.shape[0]
        m = jax.lax.axis_index(expert_axis)
        logits = x_loc.astype(jnp.float32) @ router_w   # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

        # load-balance aux (identical on every expert shard; mean over data)
        me = probs.mean(axis=0)
        onehot = jax.nn.one_hot(top_i, e_total, dtype=jnp.float32)
        ce = onehot.sum(axis=(0, 1)) / (t_loc * top_k)
        aux = e_total * jnp.sum(me * ce)
        if token_axes is not None:
            aux = jax.lax.pmean(aux, token_axes)

        # local dispatch: only MY experts; everything else -> dump expert
        my_lo = m * e_loc
        sel = (top_i >= my_lo) & (top_i < my_lo + e_loc)
        flat_e = jnp.where(sel, top_i - my_lo, e_loc).reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc), top_k)
        flat_w = (top_p * sel.astype(top_p.dtype)).reshape(-1).astype(
            x_loc.dtype)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        cap = int(max(1, -(-t_loc * top_k // e_total) * capacity_factor))
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[se].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * top_k) - starts[se]
        keep = (pos < cap) & (se < e_loc)
        slot = jnp.where(keep, pos, cap)
        ebuf = jnp.where(keep, se, 0)

        buf = jnp.zeros((e_loc, cap + 1, d), x_loc.dtype)
        buf = buf.at[ebuf, slot].set(
            jnp.where(keep[:, None], x_loc[st], 0), mode="drop")
        h = _act(act, jnp.einsum("ecd,edf->ecf", buf, wi.astype(x_loc.dtype)))
        if has_gate:
            h = h * jnp.einsum("ecd,edf->ecf", buf,
                               wg_or_none.astype(x_loc.dtype))
        yb = jnp.einsum("ecf,efd->ecd", h, wo.astype(x_loc.dtype))
        contrib = yb[ebuf, slot] * sw[:, None] * keep[:, None].astype(
            x_loc.dtype)
        y_loc = jnp.zeros((t_loc, d), x_loc.dtype).at[st].add(contrib)
        # the only collective: combine expert shards' outputs
        y_loc = jax.lax.psum(y_loc, expert_axis)
        return y_loc, aux

    from jax.sharding import PartitionSpec as P

    tok = P(token_axes, None)
    wspec = P(expert_axis, None, None)
    wg = p.get("wg", p["wi"][:, :0, :0])   # dummy when ungated
    out = compat.shard_map(
        body,
        in_specs=(P(None, None), wspec, wspec, wspec, tok),
        out_specs=(tok, P()),
    )(p["router"]["w"], p["wi"], p["wo"], wg, x)
    return out
