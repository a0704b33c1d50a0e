"""Single-device TIG training & evaluation (the paper's non-partitioned
baseline — 'Single-GPU' / 'w/o Partitioning' rows of Tab.III/IV).

Epochs run through the device-resident streaming engine
(``repro.tig.engine``): host planning pre-stages the whole chronological
stream as one (steps, ...) batch pytree, and a single jitted ``lax.scan``
executes the epoch on device.  The distributed PAC trainer
(``repro.tig.distributed``) drives the same scan program.

Split and evaluation logic lives in ``repro.tig.protocol`` — chronological
70/15/15 splits are zero-copy stream views, and the val/test scoring of
every trainer (this module's ``train_single`` / ``train_sharded`` and the
PAC path) goes through the same ``run_protocol`` driver.
"""

from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.optim import adamw, Optimizer
from repro.tig.batching import (
    LocalStream,
    build_batch_program,
    make_tables,
)
from repro.tig.engine import make_eval_epoch, make_train_epoch
from repro.tig.graph import TemporalGraph
from repro.tig.models import TIGConfig, init_params, init_state, step_loss
from repro.tig.protocol import (
    DEFAULT_CHUNK_EDGES,
    ProtocolSplits,
    device_batches,
    run_protocol,
    score_stream,
    split_views,
    time_scale_of,
    train_classifier_head,
)
from repro.tig.sampler import ChronoNeighborIndex
from repro.tig.stream import EpochPrefetcher


def _stage_tcsr(index: ChronoNeighborIndex, depth: int = 1) -> dict:
    """Stage a stream's T-CSR (``device_export``) as device arrays — done
    ONCE per run; every epoch's scanned program samples from these buffers
    instead of receiving pre-sampled (steps, B, 3, K) neighbor grids.
    ``depth`` = the model's ``n_layers`` (multi-layer folds gather one
    K-window per layer, so the export front-pads by k*depth)."""
    return {k: jnp.asarray(v)
            for k, v in index.device_export(depth=depth).items()}

__all__ = [
    "graph_as_stream",
    "make_train_step",
    "make_eval_step",
    "train_epoch",
    "evaluate_stream",
    "evaluate_params",
    "train_single",
    "train_sharded",
    "train_classifier_head",
    "time_scale_of",
    "epoch_rng",
]

# the protocol layer owns stream scoring; the old name stays importable
evaluate_stream = score_stream


def epoch_rng(seed: int, epoch: int, role: int = 0) -> np.random.Generator:
    """Independent generator per (seed, epoch, role) — epoch plans drawn
    from dedicated streams, so prefetched (out-of-order) planning produces
    bit-identical draws to serial planning."""
    return np.random.default_rng(
        np.random.SeedSequence([seed, role, epoch]))


def graph_as_stream(g: TemporalGraph) -> tuple[LocalStream, dict]:
    """Treat the whole graph as one device-local stream (ids unchanged).

    Timestamps are rescaled to mean-gap units (see ``time_scale_of``)."""
    scale = time_scale_of(g.t)
    stream = LocalStream(
        src=g.src.astype(np.int64),
        dst=g.dst.astype(np.int64),
        t=g.t / scale,
        eidx=np.arange(g.num_edges, dtype=np.int64),
        num_local_nodes=g.num_nodes,
        labels=g.labels,
    )
    return stream, make_tables(g.edge_feat, g.node_feat)


def make_train_step(cfg: TIGConfig, opt: Optimizer):
    """jit'd per-batch step (params, opt_state, state, batch, tables) ->
    updated + loss.  The epoch hot path uses ``engine.make_train_epoch``;
    this single-step variant remains for debugging and parity tests."""

    @jax.jit
    def step(params, opt_state, state, batch, tables):
        (loss, (new_state, _aux)), grads = jax.value_and_grad(
            step_loss, has_aux=True
        )(params, state, batch, tables, cfg)
        params, opt_state = opt.apply(grads, opt_state, params)
        return params, opt_state, new_state, loss

    return step


def make_eval_step(cfg: TIGConfig):
    """jit'd forward-only step: returns (new_state, aux) with logits."""

    @jax.jit
    def step(params, state, batch, tables):
        _loss, (new_state, aux) = step_loss(params, state, batch, tables, cfg)
        return new_state, aux

    return step


def train_epoch(params, opt_state, state, batches, tables_j, epoch_fn,
                tcsr=None):
    """One pass over prepared batches as a single scanned device program.

    ``batches`` is a (steps, ...) pytree (or a legacy list of per-batch
    dicts); ``epoch_fn`` comes from ``engine.make_train_epoch``.  With
    ``tcsr`` (a staged ``ChronoNeighborIndex.device_export`` dict) the
    batches are a raw-edge ``plan="device"`` program and the scan samples
    neighbor grids on device.

    Returns ``(params, opt_state, state, mean_loss)``: the epoch's updated
    carry and its mean loss over steps as a host float.  In a profiler
    trace, ``tig.dispatch`` is the call that enqueues the epoch program and
    ``tig.fetch`` the loss fetch, where the host blocks until the device
    has run the epoch.
    """
    bj = device_batches(batches)
    kw = {} if tcsr is None else {"tcsr": tcsr}
    with jax.profiler.TraceAnnotation("tig.dispatch"):
        params, opt_state, state, losses = epoch_fn(
            params, opt_state, state, bj, tables_j, **kw)
    with jax.profiler.TraceAnnotation("tig.fetch"):
        mean_loss = float(jnp.mean(losses))
    return params, opt_state, state, mean_loss


@dataclasses.dataclass
class ShardedResult:
    losses: list[float]
    epoch_seconds: list[float]
    params: dict
    state: dict
    cfg: TIGConfig
    metrics: Optional[dict] = None      # run_protocol output (protocol=True)
    best_epoch: Optional[int] = None
    val_curve: list[float] = dataclasses.field(default_factory=list)


def train_sharded(
    shards,
    cfg: TIGConfig,
    *,
    epochs: int = 2,
    lr: float = 1e-3,
    seed: int = 0,
    prefetch: bool = True,
    depth: int = 1,
    protocol: bool = False,
    patience: int = 2,
    eval_node_class: bool = False,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    plan: str = "device",
) -> ShardedResult:
    """Out-of-core training over a ``tig-shards-v1`` stream.

    The full data plane is chunked: id columns materialize at 8 bytes/edge,
    the edge-feature table is staged shard-by-shard into a donated device
    buffer (the host never holds all rows), the temporal neighbor index is
    built with the chunked T-CSR merge, and epoch plans are prefetched on
    a worker thread while the previous epoch's scan runs (``depth`` epoch
    plans may run ahead on the host; device staging stays single-slot, and
    any depth is bit-identical — disable with ``prefetch=False`` /
    ``depth=0`` when debugging).  With
    ``plan="device"`` (the default) the chunk-built T-CSR is additionally
    exported to device once and epochs ship raw-edge programs — neighbor
    grids are sampled inside the scan; ``plan="host"`` pre-samples them on
    the host (the bit-parity oracle).

    With ``protocol=False`` (the legacy fast path) the whole stream is the
    train split and no evaluation runs.  With ``protocol=True`` the quality
    path runs end-to-end from shards: the 70/15/15 chronological split
    becomes zero-copy row-range views (``protocol.split_views``), training
    sees only the train rows, each epoch scores the val split from the
    epoch-end memory, the best-val parameters (with their epoch-end memory)
    are kept via ``repro.checkpoint`` (patience-based early stop), and the
    final metrics come from ``protocol.run_protocol`` with the restored
    best params —
    identical code (and identical numbers, given identical plans) to
    ``evaluate_params`` on the equivalent in-memory graph.

    ``ckpt_every=k`` additionally writes a periodic fault-tolerance
    checkpoint ``{params, opt_state, state}`` every k epochs (atomic
    tmp+rename; needs ``ckpt_dir``).
    """
    from repro.tig.stream import stage_device_tables

    if plan not in ("host", "device"):
        raise ValueError(f"plan={plan!r}: expected 'host' or 'device'")
    splits: Optional[ProtocolSplits] = None
    if protocol:
        splits = split_views(shards)
        stream = splits.train

        def scaled_chunks():
            for lo in range(0, stream.num_edges, DEFAULT_CHUNK_EDGES):
                hi = min(lo + DEFAULT_CHUNK_EDGES, stream.num_edges)
                yield (stream.src[lo:hi], stream.dst[lo:hi],
                       stream.t[lo:hi], stream.eidx[lo:hi])

        neg_pool = splits.neg_pool
    else:
        src = shards.column("src")
        dst = shards.column("dst")
        t = shards.column("t")
        scale = time_scale_of(t)
        stream = LocalStream(
            src=src.astype(np.int64),
            dst=dst.astype(np.int64),
            t=t / scale,
            eidx=np.arange(len(src), dtype=np.int64),
            num_local_nodes=shards.num_nodes,
            labels=None,
        )

        def scaled_chunks():
            for c_src, c_dst, c_t, c_eidx in shards.edge_chunks():
                yield c_src, c_dst, c_t / scale, c_eidx

        neg_pool = np.unique(stream.dst)

    # index is epoch-invariant (same stream, no history): chunked build once
    index = ChronoNeighborIndex.from_chunks(
        scaled_chunks, shards.num_nodes, cfg.num_neighbors, cfg.batch_size)

    tables_j = stage_device_tables(shards)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw(lr=lr, max_grad_norm=1.0)
    opt_state = opt.init(params)
    epoch_fn = make_train_epoch(cfg, opt)
    eval_fn = make_eval_epoch(cfg)
    train_hist = index.final_snapshot() if protocol else None
    val_mask = splits.inductive_edge_mask(splits.val) if protocol else None

    # device planning: the chunk-built T-CSR (and, under protocol, the val
    # continuation index) is exported/staged once; epochs reuse it
    tcsr_tr = _stage_tcsr(index, cfg.n_layers) \
        if plan == "device" else None
    val_index, tcsr_val = None, None
    if plan == "device" and protocol:
        val_index = ChronoNeighborIndex(
            splits.val.src, splits.val.dst, splits.val.t, splits.val.eidx,
            shards.num_nodes, cfg.num_neighbors, cfg.batch_size,
            history=train_hist)
        tcsr_val = _stage_tcsr(val_index, cfg.n_layers)

    own_tmp = None
    if protocol and ckpt_dir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="tig_ckpt_")
        ckpt_dir = own_tmp.name

    pf = EpochPrefetcher(
        lambda ep: build_batch_program(
            stream, cfg, epoch_rng(seed, ep, 1), neg_pool=neg_pool,
            index=index, plan=plan)[0],
        epochs,
        to_device=device_batches,
        enabled=prefetch,
        depth=depth,
    )
    losses, epoch_secs, val_curve = [], [], []
    state = None
    best_val, best_epoch, bad = -np.inf, None, 0
    try:
        with pf:
            for ep in range(epochs):
                t0 = time.perf_counter()
                batches = pf.get(ep)
                state = init_state(cfg, shards.num_nodes)
                params, opt_state, state, loss = train_epoch(
                    params, opt_state, state, batches, tables_j, epoch_fn,
                    tcsr=tcsr_tr)
                epoch_secs.append(time.perf_counter() - t0)
                losses.append(loss)
                if ckpt_dir and ckpt_every and (ep + 1) % ckpt_every == 0:
                    # periodic fault-tolerance snapshot: a superset of the
                    # best-val pair (opt state included), written with the
                    # same atomic tmp+rename protocol
                    save_checkpoint(ckpt_dir, ep,
                                    {"params": params,
                                     "opt_state": opt_state,
                                     "state": state},
                                    metadata={"epoch": ep})

                if not protocol:
                    continue
                # validation continues the epoch-end memory + train history
                val_batches, _ = build_batch_program(
                    splits.val, cfg, epoch_rng(seed, ep, 2),
                    history=None if plan == "device" else train_hist,
                    neg_pool=neg_pool, index=val_index, plan=plan)
                res_val = score_stream(params, cfg, state, val_batches,
                                       tables_j, eval_fn,
                                       inductive_edge_mask=val_mask,
                                       tcsr=tcsr_val)
                val_curve.append(res_val["ap"])
                if res_val["ap"] > best_val:
                    best_val, best_epoch, bad = res_val["ap"], ep, 0
                    # params AND their epoch-end memory: the restored pair
                    # is a consistent training point, not best params +
                    # later state
                    save_checkpoint(ckpt_dir, ep,
                                    {"params": params,
                                     "opt_state": opt_state,
                                     "state": state},
                                    metadata={"val_ap": float(res_val["ap"])})
                else:
                    bad += 1
                    if bad >= patience:
                        pf.close()  # drop the in-flight next-epoch plan
                        break

        metrics = None
        if protocol:
            # best_epoch is None when no epoch ran or val AP was NaN
            # throughout (e.g. a degenerate val split) — keep last params
            if best_epoch is not None:
                restored = restore_checkpoint(
                    ckpt_dir, best_epoch,
                    {"params": params, "state": state})
                params, state = restored["params"], restored["state"]
            metrics = run_protocol(
                params, cfg, splits, tables_j, seed=seed,
                eval_node_class=eval_node_class, prefetch=prefetch,
                depth=depth)
    finally:
        if own_tmp is not None:
            own_tmp.cleanup()

    return ShardedResult(
        losses=losses,
        epoch_seconds=epoch_secs,
        params=params,
        state=state,
        cfg=cfg,
        metrics=metrics,
        best_epoch=best_epoch,
        val_curve=val_curve,
    )


def evaluate_params(
    g: TemporalGraph,
    cfg: TIGConfig,
    params: dict,
    *,
    seed: int = 0,
    eval_node_class: bool = False,
) -> dict:
    """Evaluate (PAC-)trained parameters on the standard protocol: replay the
    train split to build memory (no parameter updates), then score val/test
    link prediction (+ optional node classification).  This is how the
    partition-trained rows of Tab.IV/V are produced.

    Thin wrapper over ``protocol.run_protocol`` on zero-copy split views —
    the same driver the sharded quality path reports through."""
    splits = split_views(g)
    tables = make_tables(g.edge_feat, g.node_feat)
    tables_j = {k: jnp.asarray(v) for k, v in tables.items()}
    return run_protocol(params, cfg, splits, tables_j, seed=seed,
                        eval_node_class=eval_node_class)


@dataclasses.dataclass
class SingleResult:
    val_ap: float
    test_ap: float
    test_ap_inductive: float
    node_auroc: float
    epoch_seconds: list[float]
    losses: list[float]
    params: dict
    state: dict
    cfg: TIGConfig


def train_single(
    g: TemporalGraph,
    cfg: TIGConfig,
    *,
    epochs: int = 3,
    lr: float = 1e-3,
    seed: int = 0,
    eval_node_class: bool = False,
    prefetch: bool = True,
    depth: int = 1,
    plan: str = "device",
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
) -> SingleResult:
    """The paper's single-device baseline trainer: chronological 70/15/15
    split, memory reset per epoch, val/test continue the epoch-end memory.

    Splits are the protocol layer's zero-copy stream views (no materialized
    sub-graphs).  Each epoch is one host-planning pass (vectorized neighbor
    index + batch grid) followed by one scanned device program.  With
    ``prefetch`` (the default) epoch e+1's plan is built — and moved to
    device — on a worker thread while epoch e's scan runs (``depth`` host
    plans may run ahead; device staging stays single-slot); per-epoch RNG
    streams make the result bit-identical to serial planning at any depth.

    ``plan="device"`` (the default) stages each split's T-CSR once and
    ships raw-edge programs — the scanned step samples its own neighbor
    grids on device (``kernels.ops.neighbor_sample``), shrinking per-epoch
    H2D traffic to the edge records.  ``plan="host"`` keeps the pre-sampled
    grids (the bit-parity oracle: identical metrics, losses, and memory).

    ``ckpt_dir`` + ``ckpt_every=k`` writes a periodic fault-tolerance
    checkpoint ``{params, opt_state, state}`` every k epochs (atomic
    tmp+rename, ``repro.checkpoint``)."""
    if plan not in ("host", "device"):
        raise ValueError(f"plan={plan!r}: expected 'host' or 'device'")
    splits = split_views(g)
    tables = make_tables(g.edge_feat, g.node_feat)
    tables_j = {k: jnp.asarray(v) for k, v in tables.items()}
    tr_stream, val_stream, test_stream = splits.views

    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw(lr=lr, max_grad_norm=1.0)
    opt_state = opt.init(params)
    epoch_fn = make_train_epoch(cfg, opt)
    eval_fn = make_eval_epoch(cfg)
    eval_fn_test = make_eval_epoch(cfg, collect_embeddings=True) \
        if eval_node_class else eval_fn

    neg_pool = splits.neg_pool
    epoch_secs, losses = [], []
    best = {"val_ap": -1.0}

    # device planning: indexes are epoch-invariant (train sees no history;
    # val/test continue fixed snapshots), so each split's T-CSR is built
    # and staged exactly once — val/test lazily, from the train/val
    # end-of-stream snapshots.
    tr_index = None
    tcsr = {}
    if plan == "device":
        tr_index = ChronoNeighborIndex(
            tr_stream.src, tr_stream.dst, tr_stream.t, tr_stream.eidx,
            g.num_nodes, cfg.num_neighbors, cfg.batch_size)
        tcsr["train"] = _stage_tcsr(tr_index, cfg.n_layers)
    idx = {}

    # double-buffered host planning: epoch e+1's train plan is built and
    # device-put on a worker thread while epoch e's scan executes.
    with EpochPrefetcher(
        lambda ep: build_batch_program(
            tr_stream, cfg, epoch_rng(seed, ep, 1), neg_pool=neg_pool,
            index=tr_index, plan=plan),
        epochs,
        to_device=lambda pr: (device_batches(pr[0]), pr[1]),
        enabled=prefetch,
        depth=depth,
    ) as pf:
        for ep in range(epochs):
            t0 = time.perf_counter()
            tr_batches, hist = pf.get(ep)
            state = init_state(cfg, g.num_nodes)  # Alg.2: reset at start
            params, opt_state, state, loss = train_epoch(
                params, opt_state, state, tr_batches, tables_j, epoch_fn,
                tcsr=tcsr.get("train"))
            epoch_secs.append(time.perf_counter() - t0)
            losses.append(loss)
            if ckpt_dir and ckpt_every and (ep + 1) % ckpt_every == 0:
                # periodic fault-tolerance snapshot (atomic tmp+rename)
                save_checkpoint(ckpt_dir, ep,
                                {"params": params, "opt_state": opt_state,
                                 "state": state},
                                metadata={"epoch": ep})

            # validation continues from epoch-end memory + neighbor index
            if plan == "device" and "val" not in idx:
                idx["val"] = ChronoNeighborIndex(
                    val_stream.src, val_stream.dst, val_stream.t,
                    val_stream.eidx, g.num_nodes, cfg.num_neighbors,
                    cfg.batch_size, history=hist)
                tcsr["val"] = _stage_tcsr(idx["val"], cfg.n_layers)
            val_batches, hist_val = build_batch_program(
                val_stream, cfg, epoch_rng(seed, ep, 2),
                history=None if plan == "device" else hist,
                neg_pool=neg_pool, index=idx.get("val"), plan=plan)
            res_val = score_stream(params, cfg, state, val_batches,
                                   tables_j, eval_fn, tcsr=tcsr.get("val"))
            if res_val["ap"] > best["val_ap"]:
                if plan == "device" and "test" not in idx:
                    idx["test"] = ChronoNeighborIndex(
                        test_stream.src, test_stream.dst, test_stream.t,
                        test_stream.eidx, g.num_nodes, cfg.num_neighbors,
                        cfg.batch_size, history=hist_val)
                    tcsr["test"] = _stage_tcsr(idx["test"], cfg.n_layers)
                test_batches, _ = build_batch_program(
                    test_stream, cfg, epoch_rng(seed, ep, 3),
                    history=None if plan == "device" else hist_val,
                    neg_pool=neg_pool, index=idx.get("test"), plan=plan)
                res_test = score_stream(
                    params, cfg, res_val["state"], test_batches, tables_j,
                    eval_fn_test,
                    inductive_edge_mask=splits.inductive_edge_mask(
                        test_stream),
                    collect_embeddings=eval_node_class,
                    tcsr=tcsr.get("test"),
                )
                best = {
                    "val_ap": res_val["ap"],
                    "test_ap": res_test["ap"],
                    "test_ap_inductive": res_test.get("ap_inductive",
                                                      float("nan")),
                    "test_res": res_test,
                }

    node_auroc = float("nan")
    if eval_node_class and g.labels is not None:
        res_test = best["test_res"]
        if res_test.get("embeddings") is not None \
                and res_test.get("labels") is not None:
            n_classes = int(g.labels[g.labels >= 0].max()) + 1
            node_auroc = train_classifier_head(
                res_test["embeddings"], res_test["labels"],
                max(n_classes, 2))

    return SingleResult(
        val_ap=best["val_ap"],
        test_ap=best["test_ap"],
        test_ap_inductive=best["test_ap_inductive"],
        node_auroc=node_auroc,
        epoch_seconds=epoch_secs,
        losses=losses,
        params=params,
        state=state,
        cfg=cfg,
    )
