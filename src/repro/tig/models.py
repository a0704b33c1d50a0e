"""TIG models as instances of one general architecture (paper Fig.6).

The paper trains four backbones — Jodie [1], DyRep [2], TGN [4], TIGE [5] —
through a single Encoder-Decoder template: Memory, Message (MSG),
Aggregation, State Update (UPD), Embedding, and a link Decoder.  Each flavor
selects concrete modules:

    flavor   MSG            AGG    UPD        Embedding
    jodie    id-concat      mean   RNN        time projection
    dyrep    id-concat      mean   RNN        identity (memory read-out)
    tgn      id-concat/MLP  mean   GRU        temporal graph attention
    tige     id-concat/MLP  mean   GRU+RNN    temporal graph attention over
                                   (dual mem) the dual-memory mean

Training semantics follow TGN's *message store*: the raw messages produced by
batch n are **stashed** and only applied to memory at the start of batch n+1,
right before embeddings are computed — so the loss at batch n+1 backpropagates
through the UPD/MSG modules (otherwise they would receive no gradient).
TIGE's published restart mechanism is simplified to its dual-memory reading
(see DESIGN.md §3 — changed assumptions).

All functions are pure; state is a pytree:

    state = {
      "mem":      (N+1, d)   node memory M (row N = dump row for padding),
      "mem2":     (N+1, d)   second memory (TIGE only; zeros otherwise),
      "last":     (N+1,)     last-update timestamps,
      "pend_ids": (2B,)      node rows touched by the previous batch,
      "pend_raw": (2B, dr)   their raw (pre-MSG) messages,
      "pend_t":   (2B,)      their event times,
    }

Batches are fixed-shape with a validity mask; invalid ids are remapped to the
dump row, which is re-zeroed after every update.

The tables are data with no gradient.  ``flush_pending`` hands the step its
2B updated rows separately, and every memory read inside the step takes a
row from them where the id is a live pending id, so a step's gradient
never builds a value with one row per node.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.tig.modules import (
    attn_init,
    dense,
    dense_init,
    gru,
    gru_init,
    mlp,
    mlp_init,
    rnn,
    rnn_init,
    stacked_attn_init,
    stacked_temporal_attention,
    temporal_attention,
)
from repro.tig.time_encode import init_time_encoder, time_encode

__all__ = ["TIGConfig", "init_params", "init_state", "step_loss",
           "flush_pending", "embed_nodes", "FLAVORS"]

FLAVORS = ("jodie", "dyrep", "tgn", "tige")


@dataclasses.dataclass(frozen=True)
class TIGConfig:
    """Hyper-parameters of the general TIG architecture."""

    flavor: str = "tgn"
    dim: int = 64              # memory == embedding dim
    dim_time: int = 32
    dim_edge: int = 16
    dim_node: int = 16
    num_neighbors: int = 10    # K most-recent temporal neighbors
    n_heads: int = 2
    message_fn: str = "id"     # "id" (concat) or "mlp"
    dim_msg: int = 64          # MSG output dim when message_fn == "mlp"
    batch_size: int = 200
    n_classes: int = 0         # >0 enables the node-classification head
    use_pallas: bool = False   # route UPD/attention through Pallas kernels
    kernel_backend: str = "auto"  # with use_pallas: "auto" | "pallas" |
                                  # "interpret" (CPU-testable Pallas path)
    # NOTE: new fields append at the END — cache keys use astuple(cfg) and
    # tests index into it positionally.
    n_layers: int = 1          # attention layers (lax.scan over a stacked
                               # layer block when > 1; TGN/TIGE only)

    def __post_init__(self):
        assert self.flavor in FLAVORS, self.flavor
        assert self.kernel_backend in ("auto", "pallas", "interpret"), \
            self.kernel_backend
        assert self.n_layers >= 1, self.n_layers

    @property
    def backend(self) -> str:
        """Kernel backend for this config ("xla" unless use_pallas)."""
        return self.kernel_backend if self.use_pallas else "xla"

    @property
    def raw_msg_dim(self) -> int:
        # [s_self ; s_other ; Phi(dt) ; e_ij]
        return 2 * self.dim + self.dim_time + self.dim_edge

    @property
    def msg_dim(self) -> int:
        return self.dim_msg if self.message_fn == "mlp" else self.raw_msg_dim

    @property
    def uses_attention(self) -> bool:
        return self.flavor in ("tgn", "tige")

    @property
    def updater(self) -> str:
        return "rnn" if self.flavor in ("jodie", "dyrep") else "gru"


# --------------------------------------------------------------------- init

def init_params(key, cfg: TIGConfig) -> dict:
    ks = list(jax.random.split(key, 12))
    p: dict = {"time": init_time_encoder(cfg.dim_time)}
    if cfg.message_fn == "mlp":
        p["msg"] = mlp_init(ks[0], [cfg.raw_msg_dim, cfg.msg_dim, cfg.msg_dim])
    if cfg.updater == "gru":
        p["upd"] = gru_init(ks[1], cfg.msg_dim, cfg.dim)
    else:
        p["upd"] = rnn_init(ks[1], cfg.msg_dim, cfg.dim)
    if cfg.flavor == "tige":
        p["upd2"] = rnn_init(ks[2], cfg.msg_dim, cfg.dim)

    if cfg.uses_attention:
        d_q = cfg.dim + cfg.dim_node + cfg.dim_time
        d_kv = cfg.dim + cfg.dim_edge + cfg.dim_time
        if cfg.n_layers == 1:
            p["attn"] = attn_init(ks[3], d_q, d_kv, cfg.dim, cfg.n_heads)
        else:
            # stacked layer block: every leaf carries a leading (L,) axis so
            # embed_nodes can lax.scan over ONE compiled layer
            p["attn"] = stacked_attn_init(ks[3], cfg.n_layers, d_q, d_kv,
                                          cfg.dim, cfg.n_heads)
    elif cfg.flavor == "jodie":
        p["jodie_w"] = jnp.zeros((cfg.dim,), jnp.float32)
        p["emb"] = dense_init(ks[3], cfg.dim + cfg.dim_node, cfg.dim)
    else:  # dyrep
        p["emb"] = dense_init(ks[3], cfg.dim + cfg.dim_node, cfg.dim)

    p["dec"] = mlp_init(ks[4], [2 * cfg.dim, cfg.dim, 1])
    if cfg.n_classes > 0:
        p["cls"] = mlp_init(ks[5], [cfg.dim, cfg.dim, cfg.n_classes])
    return p


def init_state(cfg: TIGConfig, num_local_nodes: int) -> dict:
    """A fresh node memory (the host span ``tig.reset`` in a trace)."""
    n, b, d = num_local_nodes, cfg.batch_size, cfg.dim
    with jax.profiler.TraceAnnotation("tig.reset"):
        return {
            "mem": jnp.zeros((n + 1, d), jnp.float32),
            "mem2": jnp.zeros((n + 1, d), jnp.float32),
            "last": jnp.zeros((n + 1,), jnp.float32),
            "pend_ids": jnp.full((2 * b,), n, jnp.int32),
            "pend_raw": jnp.zeros((2 * b, cfg.raw_msg_dim), jnp.float32),
            "pend_t": jnp.zeros((2 * b,), jnp.float32),
        }


# ---------------------------------------------------------------- memory ops

def _first_match(q, ids, live):
    """Whether each id of ``q`` (any shape) equals a live id of ``ids``
    (R,), and the index of its first match (0 where none): an (..., R)
    compare, O(|q| R), never a table with one row per node."""
    hit = (q[..., None] == ids) & live
    return hit.any(-1), jnp.argmax(hit, axis=-1)


def _pending_mean(ids, live, msg):
    """Mean pending message of each row's node, (R, dm): the scatter mean
    of ``ref.flush_ref`` summed into R slots (each node's first live row)
    instead of (N+1) rows, in the same order, so the values are the same.
    Dead rows get 0, as the dump row's mean is."""
    _, first = _first_match(ids, ids, live)
    sums = jnp.zeros_like(msg).at[first].add(
        jnp.where(live[:, None], msg, 0.0))
    cnt = jnp.zeros(ids.shape, msg.dtype).at[first].add(
        live.astype(msg.dtype))
    mean = sums / jnp.clip(cnt, 1.0)[:, None]
    return jnp.where(live[:, None], mean[first], 0.0)


def _read_memory(cfg: TIGConfig, state: dict, fresh: dict, q):
    """Memory rows of the node ids ``q`` (any shape): the flush's fresh rows
    where ``q`` is a live pending id, the written table otherwise.  The
    table is data: the gradient reaches the parameters only through the
    (R, d) fresh rows."""
    n_dump = state["mem"].shape[0] - 1
    hit, j = _first_match(q, fresh["ids"], fresh["ids"] < n_dump)

    def read(rows, table):
        return jnp.where(hit[..., None], rows[j],
                         jax.lax.stop_gradient(table)[q])

    if cfg.flavor == "tige":
        return 0.5 * (read(fresh["rows"], state["mem"])
                      + read(fresh["rows2"], state["mem2"]))
    return read(fresh["rows"], state["mem"])


def flush_pending(params: dict, cfg: TIGConfig,
                  state: dict) -> tuple[dict, dict]:
    """Apply the stashed messages of the previous batch to memory (the
    differentiable half of the TGN message-store trick), then clear them.

    Returns ``(state, fresh)``.  ``state`` holds the written tables, which
    carry no gradient.  ``fresh = {"ids", "rows"[, "rows2"]}`` holds the R
    updated rows ``upd(mean message, mem[ids])`` (TIGE: and its second
    memory's), the only path from the parameters into memory; reads inside
    the step take them through ``_read_memory``."""
    sg = jax.lax.stop_gradient
    mem_in, mem2_in = sg(state["mem"]), sg(state["mem2"])
    n_dump = mem_in.shape[0] - 1
    ids = state["pend_ids"]
    raw = state["pend_raw"]
    ts = state["pend_t"]
    live = ids < n_dump

    msg = mlp(params["msg"], raw) if cfg.message_fn == "mlp" else raw
    # mean-aggregate messages per node (paper: "simply mean message")
    mbar = _pending_mean(ids, live, msg)
    # the rows that carry the gradient take XLA's updater on every backend,
    # so they follow the configured matmul precision: the Pallas GRU
    # kernels' gate matmuls do not, and on a TPU v5e that rounding alone
    # moved the first steps' gradient by 1.7e-4 of the median leaf
    upd_fn = gru if cfg.updater == "gru" else rnn
    rows = upd_fn(params["upd"], mbar, mem_in[ids])

    if cfg.updater == "gru" and cfg.use_pallas:
        from repro.kernels import ops
        # the table write: segment-mean + GRU + mem/last scatter in one
        # Pallas launch, in place, O(2B) HBM traffic
        w = sg(params["upd"])
        mem, last, _ = ops.fused_flush(
            ids, sg(msg), ts, mem_in, state["last"], w["xz"]["w"],
            w["hz"]["w"], w["xz"]["b"], w["hz"]["b"],
            backend=cfg.kernel_backend)
    else:
        mem = mem_in.at[ids].set(sg(rows)).at[n_dump].set(0.0)
        last = state["last"].at[ids].max(jnp.where(live, ts, 0.0))
        last = last.at[n_dump].set(0.0)

    fresh = {"ids": ids, "rows": rows}
    mem2 = mem2_in
    if cfg.flavor == "tige":
        fresh["rows2"] = rnn(params["upd2"], mbar, mem2_in[ids])
        mem2 = mem2_in.at[ids].set(sg(fresh["rows2"])).at[n_dump].set(0.0)

    b2 = ids.shape[0]
    return {
        "mem": mem,
        "mem2": mem2,
        "last": last,
        "pend_ids": jnp.full((b2,), n_dump, jnp.int32),
        "pend_raw": jnp.zeros_like(raw),
        "pend_t": jnp.zeros_like(ts),
    }, fresh


def _stash_messages(cfg: TIGConfig, state: dict, ids_s, ids_d, t, efeat,
                    valid, time_params) -> dict:
    """Compute raw messages for the current batch and stash them (consumed by
    ``flush_pending`` at the start of the next step)."""
    n_dump = state["mem"].shape[0] - 1
    s_i = state["mem"][ids_s]
    s_j = state["mem"][ids_d]
    dt_i = t - state["last"][ids_s]
    dt_j = t - state["last"][ids_d]
    phi_i = time_encode(time_params, dt_i)
    phi_j = time_encode(time_params, dt_j)
    raw_i = jnp.concatenate([s_i, s_j, phi_i, efeat], axis=-1)
    raw_j = jnp.concatenate([s_j, s_i, phi_j, efeat], axis=-1)
    ids = jnp.concatenate([ids_s, ids_d])
    ids = jnp.where(jnp.concatenate([valid, valid]), ids, n_dump)
    return {
        **state,
        "pend_ids": ids.astype(jnp.int32),
        "pend_raw": jnp.concatenate([raw_i, raw_j]),
        "pend_t": jnp.concatenate([t, t]),
    }


# ----------------------------------------------------------------- embedding

def embed_nodes(
    params: dict,
    cfg: TIGConfig,
    state: dict,
    fresh: dict,             # flush_pending's fresh rows
    tables: dict,            # {"efeat": (E+1, d_e), "nfeat": (N+1, d_n)}
    ids: jnp.ndarray,        # (B,) local ids (dump row for padding)
    t: jnp.ndarray,          # (B,)
    nbr_ids: jnp.ndarray,    # (B, K) — -1 for empty slots
    nbr_t: jnp.ndarray,      # (B, K)
    nbr_eidx: jnp.ndarray,   # (B, K) — -1 for empty slots
) -> jnp.ndarray:
    """The Embedding module: emb_i(t) from current memory + temporal
    neighborhood (paper Fig.6, right)."""
    n_dump = state["mem"].shape[0] - 1
    s = _read_memory(cfg, state, fresh, ids)
    nf = tables["nfeat"][ids]
    dt = t - state["last"][ids]

    if cfg.flavor == "jodie":
        # time-projected embedding: (1 + dt*w) ⊙ W[s ; v].  dt enters through
        # log1p so long gaps cannot blow the projection up (timestamps are
        # already mean-gap-normalized upstream).
        base = dense(params["emb"], jnp.concatenate([s, nf], axis=-1))
        dt_n = jnp.log1p(jnp.maximum(dt, 0.0))
        return (1.0 + dt_n[:, None] * params["jodie_w"]) * base
    if cfg.flavor == "dyrep":
        return dense(params["emb"], jnp.concatenate([s, nf], axis=-1))

    # TGN / TIGE: temporal graph attention over K recent neighbors.  The
    # neighbor grids are (B, K) for a single layer or (L, B, K) for the
    # multi-layer fold (one grid per layer; layer l's grid holds the
    # (L-1-l)-th most-recent K-window so the LAST applied layer sees the
    # freshest neighbors — exact n_layers=1 semantics at L=1).
    mask = nbr_ids >= 0
    nids = jnp.where(mask, nbr_ids, n_dump)
    eids = jnp.where(nbr_eidx >= 0, nbr_eidx, tables["efeat"].shape[0] - 1)
    s_nbr = _read_memory(cfg, state, fresh, nids)
    e_nbr = tables["efeat"][eids]
    # t is (B,): (B, 1) broadcasts against both (B, K) and (L, B, K)
    phi_nbr = time_encode(params["time"],
                          jnp.where(mask, t[:, None] - nbr_t, 0.0))
    phi_self = time_encode(params["time"], jnp.zeros_like(t))
    kv_in = jnp.concatenate([s_nbr, e_nbr, phi_nbr], axis=-1)
    extra = jnp.concatenate([nf, phi_self], axis=-1)
    if nbr_ids.ndim == 3:
        # scan over the stacked layer block: ONE compiled layer, carried
        # query refined per layer (q_in = [h ; nf ; Phi(0)], h0 = memory)
        return stacked_temporal_attention(
            params["attn"], s, extra, kv_in, mask,
            n_heads=cfg.n_heads, backend=cfg.backend)
    q_in = jnp.concatenate([s, extra], axis=-1)
    h = temporal_attention(params["attn"], q_in, kv_in, mask,
                           n_heads=cfg.n_heads, backend=cfg.backend)
    return h


# -------------------------------------------------------------------- step

def _fresh_read_share(cfg: TIGConfig, fresh: dict, n_dump: int, ids,
                      nbr_ids):
    """Share of the embedding's valid memory reads (query ids, and neighbor
    slots where the flavor attends) that the fresh rows serve."""
    q = [ids]
    if cfg.uses_attention:
        q.append(jnp.where(nbr_ids >= 0, nbr_ids, n_dump).reshape(-1))
    q = jnp.concatenate(q)
    hit, _ = _first_match(q, fresh["ids"], fresh["ids"] < n_dump)
    reads = jnp.sum(q < n_dump)
    return jnp.sum(hit) / jnp.maximum(reads, 1)


def step_loss(
    params: dict,
    state: dict,
    batch: dict,
    tables: dict,
    cfg: TIGConfig,
) -> tuple[jnp.ndarray, tuple[dict, dict]]:
    """One training step body: flush pending -> embed -> decode -> loss,
    then stash this batch's messages.  Returns (loss, (new_state, aux)).

    ``batch`` keys: src, dst, neg (B,) int32 local ids (-1 = padding);
    t (B,) f32; efeat (B, d_e); valid (B,) bool; and per role r in
    {src, dst, neg}: nbr_{r} (B,K) ids, nbrt_{r} (B,K) times,
    nbre_{r} (B,K) edge idx — or (L,B,K) each when cfg.n_layers > 1
    (roles concatenate on axis=-2 either way).  Optional: labels (B,)
    int64 (-1 unlabeled).

    ``aux`` holds the logits, the src/dst embeddings, ``valid`` and
    ``fresh_read_share`` (``_fresh_read_share``).
    """
    n_dump = state["mem"].shape[0] - 1
    valid = batch["valid"]
    remap = lambda x: jnp.where((x >= 0) & valid, x, n_dump).astype(jnp.int32)
    ids_s, ids_d, ids_n = map(remap, (batch["src"], batch["dst"],
                                      batch["neg"]))
    e_dump = tables["efeat"].shape[0] - 1
    efeat = tables["efeat"][jnp.where(batch["eidx"] >= 0,
                                      batch["eidx"], e_dump)]

    # 1) apply previous batch's messages (grads flow into MSG/UPD here,
    # through the fresh rows only)
    with jax.named_scope("tig.memory.flush"):
        state, fresh = flush_pending(params, cfg, state)

    # 2) embeddings at time t from the just-updated memory — the three
    # roles share one (3B,)-fused embed call (one attention launch instead
    # of three; row-wise identical math)
    b = ids_s.shape[0]
    ids_all = jnp.concatenate([ids_s, ids_d, ids_n])
    nbr_all = jnp.concatenate([batch["nbr_src"], batch["nbr_dst"],
                               batch["nbr_neg"]], axis=-2)
    with jax.named_scope("tig.embed"):
        emb_all = embed_nodes(
            params, cfg, state, fresh, tables, ids_all,
            jnp.tile(batch["t"], 3), nbr_all,
            jnp.concatenate([batch["nbrt_src"], batch["nbrt_dst"],
                             batch["nbrt_neg"]], axis=-2),
            jnp.concatenate([batch["nbre_src"], batch["nbre_dst"],
                             batch["nbre_neg"]], axis=-2),
        )
    embeds = {"src": emb_all[:b], "dst": emb_all[b:2 * b],
              "neg": emb_all[2 * b:]}

    # 3) self-supervised link prediction loss (paper §II-C decoder g) —
    # pos and neg pairs stacked into ONE (2B, 2d) decoder launch
    with jax.named_scope("tig.decode"):
        dec_in = jnp.concatenate([
            jnp.concatenate([embeds["src"], embeds["dst"]], axis=-1),
            jnp.concatenate([embeds["src"], embeds["neg"]], axis=-1)])
        logits = mlp(params["dec"], dec_in)[:, 0]
        pos_logit, neg_logit = logits[:b], logits[b:]
        v = valid.astype(jnp.float32)
        nv = jnp.clip(v.sum(), 1.0)
        bce_pos = jax.nn.softplus(-pos_logit)
        bce_neg = jax.nn.softplus(neg_logit)
        loss = ((bce_pos + bce_neg) * v).sum() / (2.0 * nv)

    # 4) stash this batch's raw messages for the next step
    with jax.named_scope("tig.memory.stash"):
        new_state = _stash_messages(cfg, state, ids_s, ids_d, batch["t"],
                                    efeat, valid, params["time"])

    aux = {
        "pos_logit": pos_logit,
        "neg_logit": neg_logit,
        "src_embed": embeds["src"],
        "dst_embed": embeds["dst"],
        "valid": valid,
        "fresh_read_share": _fresh_read_share(cfg, fresh, n_dump, ids_all,
                                              nbr_all),
    }
    return loss, (new_state, aux)
