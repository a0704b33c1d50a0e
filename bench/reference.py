"""Plain reference of the first training steps of a TIG model (TGN or
JODIE, the SPEED paper's Fig. 6 template), in straightforward jax.numpy.

It imports nothing of the program.  It batches the chronological train
split itself, samples each batch's K most recent temporal neighbors itself
(events of earlier batches only), and follows the message-store training
step: flush the previous batch's raw messages into memory (mean per node,
then a GRU or tanh-RNN update), embed src/dst/negative nodes (temporal
attention over the sampled neighbors for TGN; JODIE's time projection),
decode pos/neg pairs with a 2-layer MLP, take the mean binary
cross-entropy, stash this batch's raw messages, and apply AdamW with
global-norm clipping; after the followed steps, AdamW runs on zero
gradients to the epoch's end, as the program does over a plan whose later
steps are invalid.  Only the memory rows a step touches are updated;
no (N, d)-sized temporary is built, so the reference fits beside a table
of millions of rows.

Products run at ``precision``: "highest" is float32, as the
configurations state; "high" gives the control, three bfloat16 passes
(hi*hi + hi*lo + lo*hi of each operand's bfloat16 split), written out so
that it computes the same on any backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------------ data

def time_scale(t: np.ndarray) -> float:
    """Mean gap between consecutive events: timestamps are in these units."""
    if len(t) < 2:
        return 1.0
    m = float(np.diff(np.sort(t)).mean())
    return m if m > 0 else 1.0


def batches(src, dst, t, eidx, batch_size: int, steps: int) -> dict:
    """The first ``steps`` chronological batches, padded with -1 / invalid."""
    b = batch_size
    n = len(src)

    def grid(x, fill, dtype):
        out = np.full(steps * b, fill, dtype)
        m = min(n, steps * b)
        out[:m] = x[:m]
        return out.reshape(steps, b)

    return {"src": grid(src, -1, np.int32), "dst": grid(dst, -1, np.int32),
            "t": grid(t, 0.0, np.float32), "eidx": grid(eidx, -1, np.int32),
            "valid": grid(np.ones(n, bool), False, bool)}


def recent_neighbors(src, dst, t, eidx, batch_size: int, k: int,
                     query, batch_of):
    """For each query node and batch index: its ``k`` most recent events
    among those of strictly earlier batches (ties in time broken src-side
    first, then by edge order).  Returns (ids, times, edge rows), each
    (Q, k), -1 where a node has fewer events."""
    n = len(src)
    e = np.arange(n, dtype=np.int64)
    node = np.concatenate([src, dst]).astype(np.int64)
    other = np.concatenate([dst, src]).astype(np.int64)
    tt = np.concatenate([t, t])
    ee = np.concatenate([eidx, eidx]).astype(np.int64)
    bat = np.concatenate([e // batch_size, e // batch_size])
    side = np.concatenate([np.zeros(n, np.int64), np.ones(n, np.int64)])
    edge = np.concatenate([e, e])
    order = np.lexsort((edge, side, tt, bat, node))
    node, other, tt, ee, bat = (x[order] for x in (node, other, tt, ee, bat))
    n_bat = int(bat.max()) + 2 if len(bat) else 1
    key = node * n_bat + bat
    q = np.asarray(query, np.int64)
    end = np.searchsorted(key, q * n_bat + np.asarray(batch_of, np.int64))
    begin = np.searchsorted(node, q)
    idx = end[:, None] - k + np.arange(k)[None, :]
    ok = idx >= begin[:, None]
    idx = np.where(ok, idx, 0)
    pick = lambda x, fill: np.where(ok, x[idx] if len(x) else fill, fill)
    return pick(other, -1), pick(tt, -1.0), pick(ee, -1)


# ----------------------------------------------------------------- model

def products(precision: str):
    """``einsum(spec, a, b)`` at the given precision."""
    if precision == "highest":
        return lambda spec, a, b: jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def three_pass(spec, a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        dot = lambda x, y: jnp.einsum(spec, x, y,
                                      preferred_element_type=jnp.float32)
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)

    return three_pass


def _dense(p, x, mm):
    return mm("...i,io->...o", x, p["w"]) + p["b"]


def _mlp(p, x, mm):
    for i in range(len(p)):
        x = _dense(p[f"l{i}"], x, mm)
        if i + 1 < len(p):
            x = jax.nn.relu(x)
    return x


def _gru(p, x, h, mm):
    gx, gh = _dense(p["xz"], x, mm), _dense(p["hz"], h, mm)
    rx, zx, nx = jnp.split(gx, 3, axis=-1)
    rh, zh, nh = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    return (1.0 - z) * n + z * h


def _rnn(p, x, h, mm):
    return jnp.tanh(_dense(p["x"], x, mm) + _dense(p["h"], h, mm))


def _phi(p, dt):
    return jnp.cos(dt[..., None] * p["w"] + p["b"])


def step_loss(params, mem, last, pend, batch, nbr, tables, cfg, mm):
    """Loss of one step and the state after it."""
    n = mem.shape[0] - 1                                    # dump row
    pid, praw, pt = pend
    live = pid < n
    same = (pid[:, None] == pid[None, :]) & live[None, :]
    cnt = jnp.maximum(same.sum(1), 1).astype(jnp.float32)
    mbar = mm("ij,jd->id", same.astype(jnp.float32), praw) / cnt[:, None]
    upd = _gru if cfg["flavor"] == "tgn" else _rnn
    h_new = upd(params["upd"], mbar, mem[pid], mm)
    t_new = jnp.max(jnp.where(same, pt[None, :], -jnp.inf), axis=1)
    last_new = jnp.maximum(last[pid], jnp.where(live, t_new, 0.0))

    def read(q):
        hit = (q[:, None] == pid[None, :]) & live[None, :]
        j = jnp.argmax(hit, axis=1)
        any_hit = hit.any(1)
        return (jnp.where(any_hit[:, None], h_new[j], mem[q]),
                jnp.where(any_hit, last_new[j], last[q]))

    valid = batch["valid"]
    remap = lambda x: jnp.where((x >= 0) & valid, x, n)
    ids_s, ids_d, ids_n = remap(batch["src"]), remap(batch["dst"]), \
        remap(batch["neg"])
    b = ids_s.shape[0]
    ids = jnp.concatenate([ids_s, ids_d, ids_n])
    t3 = jnp.tile(batch["t"], 3)
    s, s_last = read(ids)
    nf = tables["nfeat"][ids]
    e_dump = tables["efeat"].shape[0] - 1
    if cfg["flavor"] == "jodie":
        base = _dense(params["emb"], jnp.concatenate([s, nf], -1), mm)
        dt = jnp.log1p(jnp.maximum(t3 - s_last, 0.0))
        emb = (1.0 + dt[:, None] * params["jodie_w"]) * base
    else:
        n_id, n_t, n_e = nbr
        mask = n_id >= 0
        k = n_id.shape[1]
        s_nbr, _ = read(jnp.where(mask, n_id, n).reshape(-1))
        s_nbr = s_nbr.reshape(3 * b, k, -1)
        e_nbr = tables["efeat"][jnp.where(n_e >= 0, n_e, e_dump)]
        phi_nbr = _phi(params["time"], jnp.where(mask, t3[:, None] - n_t, 0.0))
        phi_0 = _phi(params["time"], jnp.zeros_like(t3))
        q_in = jnp.concatenate([s, nf, phi_0], -1)
        kv = jnp.concatenate([s_nbr, e_nbr, phi_nbr], -1)
        a = params["attn"]
        h = cfg["n_heads"]
        q = _dense(a["q"], q_in, mm).reshape(3 * b, h, -1)
        kk = _dense(a["k"], kv, mm).reshape(3 * b, k, h, -1)
        vv = _dense(a["v"], kv, mm).reshape(3 * b, k, h, -1)
        sc = mm("bhd,bkhd->bhk", q, kk) / np.sqrt(q.shape[-1])
        sc = jnp.where(mask[:, None, :], sc, -1e30)
        att = jax.nn.softmax(sc, axis=-1)
        att = jnp.where(mask.any(1)[:, None, None], att, 0.0)
        ctx = mm("bhk,bkhd->bhd", att, vv).reshape(3 * b, -1)
        emb = _dense(a["o"], jnp.concatenate([q_in, ctx], -1), mm)
    es, ed, en = emb[:b], emb[b:2 * b], emb[2 * b:]
    logits = _mlp(params["dec"], jnp.concatenate([
        jnp.concatenate([es, ed], -1), jnp.concatenate([es, en], -1)]), mm)
    logits = logits[:, 0]
    v = valid.astype(jnp.float32)
    loss = ((jax.nn.softplus(-logits[:b]) + jax.nn.softplus(logits[b:])) * v
            ).sum() / (2.0 * jnp.maximum(v.sum(), 1.0))

    # state after the step: flushed rows written, this batch's messages
    rows = jnp.where(live, pid, n)
    mem2 = mem.at[rows].set(h_new).at[n].set(0.0)
    last2 = last.at[rows].max(jnp.where(live, t_new, 0.0)).at[n].set(0.0)
    (si, li), (sj, lj) = read(ids_s), read(ids_d)
    ef = tables["efeat"][jnp.where(batch["eidx"] >= 0, batch["eidx"], e_dump)]
    raw_i = jnp.concatenate(
        [si, sj, _phi(params["time"], batch["t"] - li), ef], -1)
    raw_j = jnp.concatenate(
        [sj, si, _phi(params["time"], batch["t"] - lj), ef], -1)
    pend2 = (jnp.concatenate([ids_s, ids_d]),
             jnp.concatenate([raw_i, raw_j]),
             jnp.concatenate([batch["t"], batch["t"]]))
    return loss, (mem2, last2, jax.lax.stop_gradient(pend2))


def adamw_step(params, grads, mu, nu, count, lr, max_norm,
               b1=0.9, b2=0.999, eps=1e-8):
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c = count.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, mu, nu)
    return params, mu, nu, count


def follow(params, tables, n_nodes: int, stream: dict, negatives,
           cfg: dict, steps: int, *, lr: float, max_grad_norm: float,
           precision: str = "highest", epoch_steps: int = 0):
    """The first ``steps`` training steps from a fresh memory, then
    AdamW on zero gradients up to ``epoch_steps`` steps in all.  Returns
    the losses of the followed steps, the chronological batches it made,
    and the weights and AdamW's second moment at the end.

    ``stream`` holds the train split (src, dst, eidx; ``t`` in mean-gap
    units as float32); ``negatives`` the (>= steps, B) negative ids.
    """
    if (cfg["flavor"], cfg["n_layers"], cfg["message_fn"]) not in (
            ("tgn", 1, "id"), ("jodie", 1, "id")):
        raise ValueError(f"the reference follows one-layer TGN and JODIE "
                         f"with id messages, not {cfg}")
    b, k = cfg["batch_size"], cfg["num_neighbors"]
    bt = batches(stream["src"], stream["dst"], stream["t"], stream["eidx"],
                 b, steps)
    bt["neg"] = np.asarray(negatives[:steps], np.int32)
    if cfg["flavor"] == "jodie":
        nbrs = None
    else:
        q, bo = [], []
        for role in ("src", "dst", "neg"):
            q.append(bt[role])
            bo.append(np.broadcast_to(np.arange(steps)[:, None], (steps, b)))
        q = np.concatenate(q, 1)                 # (steps, 3B)
        alive = (q >= 0) & np.tile(bt["valid"], 3)
        ids, tms, eds = recent_neighbors(
            stream["src"], stream["dst"], stream["t"], stream["eidx"], b, k,
            np.where(alive, q, 0).ravel(), np.concatenate(bo, 1).ravel())
        dead = ~alive.ravel()[:, None]
        nbrs = (np.where(dead, -1, ids).reshape(steps, 3 * b, k)
                .astype(np.int32),
                tms.reshape(steps, 3 * b, k).astype(np.float32),
                np.where(dead, -1, eds).reshape(steps, 3 * b, k)
                .astype(np.int32))
    raw_dim = 2 * cfg["dim"] + cfg["dim_time"] + tables["efeat"].shape[1]
    n = n_nodes
    mem = jnp.zeros((n + 1, cfg["dim"]), jnp.float32)
    last = jnp.zeros((n + 1,), jnp.float32)
    pend = (jnp.full((2 * b,), n, jnp.int32),
            jnp.zeros((2 * b, raw_dim), jnp.float32),
            jnp.zeros((2 * b,), jnp.float32))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mm = products(precision)

    def one(carry, xs):
        params, mu, nu, count, mem, last, pend = carry
        batch, nbr = xs
        lfn = lambda p: step_loss(p, mem, last, pend, batch, nbr, tables,
                                  cfg, mm)
        (loss, (mem, last, pend)), grads = jax.value_and_grad(
            lfn, has_aux=True)(params)
        params, mu, nu, count = adamw_step(params, grads, mu, nu, count, lr,
                                           max_grad_norm)
        return (params, mu, nu, count, mem, last, pend), loss

    def coast(carry, _):
        params, mu, nu, count = carry
        return adamw_step(params, zeros, mu, nu, count, lr,
                          max_grad_norm), None

    @jax.jit
    def run(params, mem, last, pend, xs):
        carry = (params, zeros, zeros, jnp.zeros((), jnp.int32), mem, last,
                 pend)
        carry, losses = jax.lax.scan(one, carry, xs)
        (params, _mu, nu, _count), _ = jax.lax.scan(
            coast, carry[:4], None, length=max(epoch_steps - steps, 0))
        return losses, params, nu

    xs = ({kk: jnp.asarray(v) for kk, v in bt.items()},
          None if nbrs is None else tuple(jnp.asarray(v) for v in nbrs))
    losses, params, nu = run(params, mem, last, pend, xs)
    return {"losses": np.asarray(losses, np.float64), "rows": bt,
            "params": params, "nu": nu}
