"""Device-resident streaming epoch engine — ONE scan-based step program.

The single source of truth for the TIG hot path.  Both the single-device
baseline (``repro.tig.train``) and the PAC distributed trainer
(``repro.tig.distributed``) drive their epochs through the scanned programs
here instead of dispatching one jitted call per batch from a Python loop:
the whole epoch — flush pending messages, embed, decode, loss, grads,
optimizer — runs as one ``lax.scan`` on device over a pre-staged
(steps, ...) batch pytree, with buffer donation so params/optimizer/memory
update in place.

``scan_train_epoch`` is written once and parameterized by:

  * ``axis``          — ``None`` for single-device; a mapped axis name for
                        DDP (gradients are ``pmean``'d over it before the
                        update), under either ``jax.vmap`` simulation or
                        ``jax.shard_map`` SPMD;
  * ``cycle_length``  — ``None`` for a plain chronological pass; an int
                        array for the paper's Alg.2 loop-within-epoch
                        semantics (reset node memory at each data-cycle
                        start, back it up at each cycle end, restore the
                        last complete backup at epoch end);
  * ``wrap_steps``    — transfer-minimal Alg.2 wrap-around ON DEVICE: the
                        host ships only the ``cycle_length`` *real* batches
                        (at ``wrap_offset`` in a flat shared grid) and the
                        scan gathers batch ``offset + s % cycle_length``
                        with ``lax.dynamic_index_in_dim`` for each of the
                        ``wrap_steps`` lockstep steps, instead of the host
                        replaying the stream to the global lockstep length.

Kernel routing (``cfg.use_pallas`` / ``cfg.kernel_backend``) happens inside
``models.step_loss``: the neighbor-aggregation attention and the GRU memory
update go through ``repro.kernels`` Pallas kernels, with the XLA path as
fallback.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.optim import Optimizer
from repro.tig.cache import lru_get
from repro.tig.models import TIGConfig, init_state, step_loss

__all__ = [
    "sample_batch_neighbors",
    "scan_train_epoch",
    "scan_eval_stream",
    "make_train_epoch",
    "make_eval_epoch",
    "donate_args",
]


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def donate_args(*argnums: int) -> tuple[int, ...]:
    """Buffer donation saves one params+opt+memory copy per epoch (and
    lets the PAC scan-only program consume its per-epoch plan buffers in
    place), but CPU jit only warns that donation is unimplemented — keep
    test logs clean."""
    return argnums if jax.default_backend() != "cpu" else ()


_donate_args = donate_args    # internal alias (pre-PR 9 name)


def sample_batch_neighbors(batch, tcsr, batch_of, cfg: TIGConfig):
    """Augment a raw-edge batch with device-sampled neighbor grids.

    ``batch`` is one (B,)-shaped raw batch (a ``plan="device"`` program
    row); ``tcsr`` the staged ``ChronoNeighborIndex.device_export`` dict;
    ``batch_of`` this row's batch index within its stream.  Adds the nine
    ``nbr_* / nbrt_* / nbre_*`` keys exactly as the host planner would:
    one fused (3B,) sample over src ++ dst ++ neg, with dead rows (padding
    / invalid) redirected to node 0 and their ids/edge rows re-masked to
    -1 afterwards — times are left as sampled, matching the host grid
    bit-for-bit.

    With ``cfg.n_layers > 1`` the grids come back (L, B, K): still ONE
    fused (L*3B,) launch, with per-row windows so layer l's grid holds
    the (L-1-l)-th most-recent K-window (the staged export must have
    ``depth >= n_layers``).  Row l = L-1 (window 0) is bit-identical to
    the single-layer grid.
    """
    k = cfg.num_neighbors
    b = batch["src"].shape[0]
    ids3 = jnp.concatenate([batch["src"], batch["dst"], batch["neg"]])
    alive = (ids3 >= 0) & jnp.tile(batch["valid"], 3)
    clean = jnp.where(alive, ids3, 0).astype(jnp.int32)
    n_l = cfg.n_layers
    if n_l > 1:
        win = jnp.repeat(jnp.arange(n_l - 1, -1, -1, dtype=jnp.int32),
                         3 * b)
        nb, nt, ne = ops.neighbor_sample(
            tcsr, jnp.tile(clean, n_l), batch_of, k,
            backend=cfg.backend, window=win)
        nb = jnp.where(alive[:, None], nb.reshape(n_l, 3 * b, k), -1)
        nt = nt.reshape(n_l, 3 * b, k)
        ne = jnp.where(alive[:, None], ne.reshape(n_l, 3 * b, k), -1)
        out = dict(batch)
        for j, role in enumerate(("src", "dst", "neg")):
            rows = slice(j * b, (j + 1) * b)
            out[f"nbr_{role}"] = nb[:, rows]
            out[f"nbrt_{role}"] = nt[:, rows]
            out[f"nbre_{role}"] = ne[:, rows]
        return out
    nb, nt, ne = ops.neighbor_sample(
        tcsr, clean, batch_of, k, backend=cfg.backend)
    nb = jnp.where(alive[:, None], nb, -1)
    ne = jnp.where(alive[:, None], ne, -1)
    out = dict(batch)
    for j, role in enumerate(("src", "dst", "neg")):
        rows = slice(j * b, (j + 1) * b)
        out[f"nbr_{role}"] = nb[rows]
        out[f"nbrt_{role}"] = nt[rows]
        out[f"nbre_{role}"] = ne[rows]
    return out


# ----------------------------------------------------------------- training

def scan_train_epoch(
    params,
    opt_state,
    state,
    batches,                 # pytree of (steps, ...) arrays
    tables,                  # {"efeat": (E+1, d_e), "nfeat": (N+1, d_n)}
    *,
    cfg: TIGConfig,
    opt: Optimizer,
    axis: Optional[str] = None,
    cycle_length=None,       # () int array or None
    wrap_steps: Optional[int] = None,
    wrap_offset=0,           # () int array — batch-grid start row
    tcsr=None,               # staged device_export dict or None
):
    """One training epoch as a single scan (traced; jit/vmap/shard_map it).

    Returns ``(params, opt_state, state, losses)`` with ``losses`` of shape
    (steps,).  With ``cycle_length`` set, ``state`` is the backup taken at
    the end of the last *complete* data cycle (paper Alg.2 lines 10-11);
    otherwise it is simply the post-stream state.

    With ``wrap_steps`` (requires ``cycle_length``), ``batches`` holds only
    the REAL batches — this device's ``cycle_length`` rows starting at
    ``wrap_offset`` of a flat grid shared across devices — and the scan
    runs ``wrap_steps`` lockstep steps, gathering batch
    ``wrap_offset + s % cycle_length`` on device.  Identical semantics to
    handing in a host-replayed (wrap_steps, ...) grid, at
    O(cycle_length) instead of O(wrap_steps) host/transfer bytes.  The
    pod-scale row-range-sharded layout (``plan_epoch(layout="sharded")``)
    reuses this path with ``wrap_offset == 0``: each device holds only
    its OWN zero-padded (rows_cap, ...) grid slab, and since
    ``s % cycle_length < cycle_length`` the gather never reads a padding
    row.

    With ``tcsr`` (a staged ``ChronoNeighborIndex.device_export`` dict),
    ``batches`` is a raw-edge program (``plan="device"``) and each step
    samples its neighbor grids on device at its batch index — ``s`` for a
    plain pass, ``s % cycle_length`` under replay/wrap-around (each
    replayed row re-samples as of its REAL batch, exactly like the host
    planner's grid for that row).
    """
    cycling = cycle_length is not None
    if wrap_steps is not None and not cycling:
        raise ValueError("wrap_steps requires cycle_length")
    fresh = init_state(cfg, state["mem"].shape[0] - 1)

    def step_body(params, opt_state, state, batch, b_of):
        if tcsr is not None:
            with jax.named_scope("tig.sample"):
                batch = sample_batch_neighbors(batch, tcsr, b_of, cfg)
        (loss, (state, _aux)), grads = jax.value_and_grad(
            step_loss, has_aux=True
        )(params, state, batch, tables, cfg)
        if axis is not None:
            grads = jax.lax.pmean(grads, axis)
        with jax.named_scope("tig.optimizer"):
            params, opt_state = opt.apply(grads, opt_state, params)
        return params, opt_state, state, loss

    if not cycling:
        steps = jax.tree.leaves(batches)[0].shape[0]

        def scan_step(carry, xs):
            batch, s = xs
            params, opt_state, state = carry
            params, opt_state, state, loss = step_body(
                params, opt_state, state, batch, s)
            return (params, opt_state, state), loss

        (params, opt_state, state), losses = jax.lax.scan(
            scan_step, (params, opt_state, state),
            (batches, jnp.arange(steps, dtype=jnp.int32)))
        return params, opt_state, state, losses

    n_cycle = jnp.asarray(cycle_length, jnp.int32)

    if wrap_steps is not None:
        offset = jnp.asarray(wrap_offset, jnp.int32)

        def wrap_step(carry, s):
            params, opt_state, state, backup = carry
            batch = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, offset + s % n_cycle, 0, keepdims=False),
                batches)
            is_start = (s % n_cycle) == 0
            state = _tree_where(is_start, fresh, state)
            params, opt_state, state, loss = step_body(
                params, opt_state, state, batch, s % n_cycle)
            is_end = ((s + 1) % n_cycle) == 0
            backup = _tree_where(is_end, state, backup)
            return (params, opt_state, state, backup), loss

        (params, opt_state, _state, backup), losses = jax.lax.scan(
            wrap_step, (params, opt_state, state, fresh),
            jnp.arange(wrap_steps, dtype=jnp.int32))
        return params, opt_state, backup, losses

    def scan_step(carry, batch):
        params, opt_state, state, backup, s = carry
        # Alg.2 lines 6-7: reset memory at each data-cycle start
        is_start = (s % n_cycle) == 0
        state = _tree_where(is_start, fresh, state)
        params, opt_state, state, loss = step_body(
            params, opt_state, state, batch, s % n_cycle)
        # Alg.2 lines 10-11: back up memory at each data-cycle end
        is_end = ((s + 1) % n_cycle) == 0
        backup = _tree_where(is_end, state, backup)
        return (params, opt_state, state, backup, s + 1), loss

    carry0 = (params, opt_state, state, fresh, jnp.zeros((), jnp.int32))
    (params, opt_state, _state, backup, _), losses = jax.lax.scan(
        scan_step, carry0, batches)
    # epoch end: restore the latest complete-cycle memory (Alg.2)
    return params, opt_state, backup, losses


def _named_partial(fn, **bound):
    """``functools.partial`` that keeps ``fn``'s name, so the compiled
    program is ``jit_<fn>`` (a bare partial compiles as ``jit__unknown``)
    in HLO dumps and profiler traces."""
    out = functools.partial(fn, **bound)
    out.__name__ = fn.__name__
    return out


def make_train_epoch(cfg: TIGConfig, opt: Optimizer):
    """jit'd single-device epoch ``jit_scan_train_epoch``: (params,
    opt_state, state, batches, tables) -> (params, opt_state, state,
    losses), donating the carried buffers."""
    fn = _named_partial(scan_train_epoch, cfg=cfg, opt=opt)
    return jax.jit(fn, donate_argnums=_donate_args(0, 1, 2))


# --------------------------------------------------------------- evaluation

def scan_eval_stream(
    params,
    state,
    batches,                 # pytree of (steps, ...) arrays
    tables,
    *,
    cfg: TIGConfig,
    collect_embeddings: bool = False,
    tcsr=None,
):
    """Forward-only scan over a chronological stream (memory keeps
    updating, params frozen).

    Returns ``(state, aux)`` with ``aux`` holding (steps, B)-stacked
    ``pos_logit`` / ``neg_logit``, plus (steps, B, d) ``src_embed`` when
    ``collect_embeddings`` (off by default — the stack is steps*B*d floats,
    only the node-classification protocol needs it).

    With ``tcsr`` (staged ``device_export`` dict) ``batches`` is a
    raw-edge program and each step samples its neighbor grids on device.
    """
    steps = jax.tree.leaves(batches)[0].shape[0]

    def scan_step(state, xs):
        batch, s = xs
        if tcsr is not None:
            with jax.named_scope("tig.sample"):
                batch = sample_batch_neighbors(batch, tcsr, s, cfg)
        _loss, (state, aux) = step_loss(params, state, batch, tables, cfg)
        out = {"pos_logit": aux["pos_logit"],
               "neg_logit": aux["neg_logit"]}
        if collect_embeddings:
            out["src_embed"] = aux["src_embed"]
            # dst too: the restarter's embedding bank needs coverage of
            # nodes that only ever appear as destinations (bipartite TIGs)
            out["dst_embed"] = aux["dst_embed"]
        return state, out

    return jax.lax.scan(scan_step, state,
                        (batches, jnp.arange(steps, dtype=jnp.int32)))


_EVAL_PROGRAMS: dict = {}
_EVAL_PROGRAMS_MAX = 32          # bounded LRU: evict least-recently-USED,
                                 # don't pin every compiled program for
                                 # process lifetime


def make_eval_epoch(cfg: TIGConfig, *, collect_embeddings: bool = False):
    """jit'd eval-stream program ``jit_scan_eval_stream``: (params, state,
    batches, tables) -> (state, stacked aux).

    Programs are cached per (cfg, collect_embeddings) with LRU eviction
    (hits move to the back of the dict, the front is evicted): per-epoch
    validation during training, the protocol driver's train replay, and
    final scoring all reuse one compiled scan instead of re-tracing a
    fresh ``jax.jit`` wrapper on every call, and an alternating
    train/val/protocol workload cycling through >32 configs can't thrash
    a program it keeps coming back to.

    No buffer donation here: callers legitimately reuse the input state
    (e.g. train_single evaluates val from the epoch-end memory it also
    keeps for the returned result).

    The returned program accepts an optional ``tcsr=`` keyword for
    device-planned (raw-edge) batch programs; passing it traces a second
    variant under the same jit wrapper."""
    # astuple(cfg) already covers every field (n_layers included — it is
    # appended LAST so positional consumers stay valid); the lane-padded
    # dims the MXU tier actually launches are keyed explicitly so a
    # padding-rule change can never alias two different executables
    key = (dataclasses.astuple(cfg),
           (cfg.n_layers, ops.lane_pad(cfg.dim), ops.lane_pad(cfg.msg_dim)),
           collect_embeddings)
    # the key is by VALUE: close over a defensive copy so in-place
    # mutation of the caller's cfg can't desync a cached program
    return lru_get(
        _EVAL_PROGRAMS, key, _EVAL_PROGRAMS_MAX,
        lambda: jax.jit(_named_partial(
            scan_eval_stream, cfg=dataclasses.replace(cfg),
            collect_embeddings=collect_embeddings)))
