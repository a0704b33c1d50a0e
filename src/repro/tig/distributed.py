"""PAC — distributed parallel training of TIG models (paper §II-C, Alg.2).

The device half of the Parallel Acceleration Component.  One *device epoch*
is a single jitted program per device — the scanned step program of
``repro.tig.engine`` (shared with the single-device baseline) with DDP
gradient ``pmean`` over the "part" axis and Alg.2 cycle semantics
(``cycle_length``), followed here by the PAC-specific epilogue:

    scan over lockstep global steps s in [0, steps_per_epoch):
      1. if s is my cycle start:  reset node memory (Alg.2 line 6-7)
      2. batch = my_batches[s % my_num_batches]   (wrap-around loop)
      3. loss, grads = step_loss(batch)           (TIG model, models.py)
      4. grads = pmean(grads, axis="part")        (DDP gradient sync)
      5. params, opt_state = adamw(...)           (replicated update)
      6. if s is my cycle end:    backup memory   (Alg.2 line 10-11)
    epoch end:
      7. memory <- backup                         (restore complete state)
      8. shared-node sync: all_gather shared rows over "part", each device
         adopts the replica with the largest last-update timestamp
         ("latest", the paper's choice) or the mean.

The SAME function runs under two executors:
  * ``jax.vmap(..., axis_name="part")``  — single-host simulation (tests,
    CPU benchmarks; collectives become batched ops, semantics identical);
  * ``jax.shard_map(..., mesh)``         — real multi-device SPMD (the
    production path; also used by the dry-run on 512 host devices).

Host-side epoch planning (partition -> super-partitions -> localized padded
streams) lives here too, built on ``repro.core.pac``.

§Perf C3 — transfer-minimal batch plane.  The Alg.2 wrap-around (step 2
above) runs ON DEVICE: ``plan_epoch`` emits each device's *real* batch grid
only, concatenated into one flat pytree plus per-device row offsets, and
the scanned epoch gathers batch ``offset + s % n_batches`` with
``lax.dynamic_index_in_dim``.  The previous host-side scheme — replaying
every grid to the global lockstep length with ``v[replay]`` — shipped
``N_dev * steps_per_epoch`` batch rows per epoch; the flat plan ships
``sum_k real_batches_k``, an ``N*steps/sum(real)``-fold reduction in host
grid bytes and host->device traffic that grows with partition imbalance.
The replay layout is kept as the bit-exact parity oracle
(``host_replay=True``).  ``plan_epoch`` also localizes directly from
``ShardedStream`` row-range chunks (one shard of ids+features in host
memory at a time), so ``pac_train`` runs end-to-end without a materialized
``TemporalGraph``.

§Perf C4 — pod-scale row-range sharding.  ``layout="sharded"`` re-cuts the
same plan by per-device row ranges: the grid becomes a zero-padded
(N_dev, rows_cap, ...) stack and the T-CSR export stays per-device
(unoffset ``indptr`` + padded per-device event rows), so ``make_pac_epoch``
can PARTITION both over the mesh's "part" axis instead of replicating
them — per-device H2D drops from O(sum all devices) to O(own rows).  On a
process-spanning mesh (``launch.mesh.make_tig_mesh`` over
``jax.process_count() * local_device_count`` devices) ``pac_train`` plans
only the local devices' rows per host (``local_ranks``) and stages them
with ``make_array_from_process_local_data`` (``stream.stage_partitioned``),
so HOST grid bytes also stay O(local devices); the Alg.2 shared-node
memory sync (all_gather/psum over "part") then genuinely spans hosts.
The replicated flat layout remains the single-host bit-parity oracle
(``grid_layout="replicated"``).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Literal, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.core.pac import (
    CycleSchedule,
    build_subgraph,
    cycle_schedule,
    make_local_indices,
    shuffle_combine,
    subgraph_mask,
)
from repro.core.sep import PartitionResult
from repro.kernels.neighbor_sample import export_length
from repro.optim import Optimizer
from repro.tig.batching import (
    LocalStream,
    build_batch_program,
    concat_batch_programs,
    pad_batch_programs,
)
from repro.tig.cache import lru_get
from repro.tig.engine import donate_args as _donate, scan_train_epoch
from repro.tig.graph import TemporalGraph
from repro.tig.models import TIGConfig, init_params, init_state
from repro.tig.protocol import time_scale_of
from repro.tig.sampler import ChronoNeighborIndex
from repro.tig.stream import (
    EpochPrefetcher,
    ShardedStream,
    stage_partitioned,
    stage_replicated,
)
from repro.faults import FaultInjector, HostLossError, is_host_loss
from repro.tig.train import epoch_rng

__all__ = ["EpochPlan", "plan_epoch", "make_pac_epoch", "make_pac_sync",
           "sync_shared_memory", "pac_train", "PACResult",
           "globalize_memory"]

StreamSource = Union[TemporalGraph, ShardedStream]


# ======================================================================
# host-side epoch planning
# ======================================================================

@dataclasses.dataclass
class EpochPlan:
    """Everything one epoch of PAC needs.

    Default (transfer-minimal) layout: ``batches`` is a FLAT pytree of
    (sum_k n_batches_k, ...) arrays — each device's real batch grid only,
    concatenated — and ``offsets`` holds each device's start row; the
    device epoch gathers batch ``offsets[k] + s % n_batches[k]`` on device
    (Alg.2 wrap-around without host replay).  With ``host_replay=True``
    (the parity oracle) ``batches`` is the legacy (N_dev, steps, ...)
    stack, replayed to the lockstep length on the host, and ``offsets`` is
    ``None``.

    ``layout="sharded"`` (pod scale) re-cuts the flat grid by per-device
    row ranges: ``batches`` is a zero-padded (N_held, rows_cap, ...) stack
    (rows_cap = global max n_batches_k, a shard_map uniform-block
    requirement), ``offsets`` is all-zero, and a device plan's ``tcsr``
    keeps per-device UNOFFSET ``indptr`` rows plus per-device padded event
    rows — every array mappable over the "part" axis.  With
    ``local_ranks`` only those devices' rows are materialized (N_held =
    len(local_ranks)); the scalar schedule (``n_batches``, ``offsets``,
    ``steps``, capacities) stays GLOBAL so every process plans the same
    lockstep epoch.
    """

    batches: dict                 # flat (sum real, ...) / (N_dev, steps, ...)
                                  # / sharded (N_held, rows_cap, ...)
    n_batches: np.ndarray         # (N_dev,) real batches per device
    nfeat_local: np.ndarray       # (N_held, cap+1, d_n)
    efeat_local: np.ndarray       # (N_held, e_cap+1, d_e) — per-device edge
                                  # features (§Perf C2: sharded, never the
                                  # full replicated table)
    shared_local: np.ndarray      # (N_dev, S) local rows of shared nodes
    node_lists: list[np.ndarray]  # global ids per device
    capacity: int                 # padded local node count
    edge_capacity: int            # padded local edge count
    steps: int
    edges_per_device: np.ndarray  # (N_dev,)
    offsets: Optional[np.ndarray] = None   # (N_dev,) flat-grid start rows
    host_replay: bool = False
    tcsr: Optional[dict] = None   # device plan: {"indptr": (N_dev, cap+1),
                                  # "nbr"/"t"/"eidx"/"bat": flat events} —
                                  # or all (N_held, ...) when sharded
    layout: str = "replicated"    # "replicated" | "sharded"
    local_ranks: Optional[np.ndarray] = None  # devices materialized here

    def grid_bytes(self) -> int:
        """Host bytes of the batch grids (what the epoch must transfer)."""
        return int(sum(np.asarray(v).nbytes for v in self.batches.values()))

    def tcsr_bytes(self) -> int:
        """Host bytes of the exported T-CSR (0 for host-sampled plans)."""
        if self.tcsr is None:
            return 0
        return int(sum(np.asarray(v).nbytes for v in self.tcsr.values()))

    def plan_bytes(self) -> int:
        """Total host->device plan bytes: batch grids + (device plan only)
        the T-CSR the sampler reads instead of pre-sampled grids."""
        return self.grid_bytes() + self.tcsr_bytes()

    def device_input_bytes(self) -> int:
        """Grid + T-CSR bytes ONE device receives over H2D.

        Replicated layouts ship the full flat grid (and flat event
        buffer) to every device; the sharded and host-replay layouts map
        the leading axis over devices, so each device receives only its
        own (uniform, padded) row."""
        if self.layout == "sharded" or self.host_replay:
            held = len(np.asarray(next(iter(self.batches.values()))))
            return self.plan_bytes() // max(held, 1)
        return self.plan_bytes()


def _localize_in_memory(
    g: TemporalGraph,
    node_lists: list[np.ndarray],
    local,
    cap: int,
    time_scale: float,
    ranks: list[int],
):
    """Per-device localized streams + feature gathers from a materialized
    ``TemporalGraph`` (the original in-memory path).

    ``ranks`` selects which devices' streams/features to MATERIALIZE (a
    host in a multi-process run builds only its own devices' rows; edge
    COUNTS stay global so the lockstep schedule agrees everywhere).
    Streams/indexes are ``None`` for unmaterialized devices; feature rows
    hold ``len(ranks)`` entries in rank order."""
    n_dev = len(node_lists)
    held = set(ranks)
    streams: list[Optional[LocalStream]] = []
    indexes: list[Optional[ChronoNeighborIndex]] = []
    edges_per_device = np.zeros(n_dev, dtype=np.int64)
    edge_globals: dict[int, np.ndarray] = {}
    for k, (nodes, li) in enumerate(zip(node_lists, local)):
        eidx = build_subgraph(g.src, g.dst, nodes, g.num_nodes)
        edges_per_device[k] = len(eidx)
        if k not in held:
            streams.append(None)
            indexes.append(None)
            continue
        edge_globals[k] = eidx
        streams.append(
            LocalStream(
                src=li.to_local[g.src[eidx]].astype(np.int64),
                dst=li.to_local[g.dst[eidx]].astype(np.int64),
                t=g.t[eidx] / time_scale,
                # LOCAL edge ids into the device's own feature table
                # (§Perf C2: the paper keeps edge data per GPU, so do we)
                eidx=np.arange(len(eidx), dtype=np.int64),
                num_local_nodes=cap,
                labels=None if g.labels is None else g.labels[eidx],
            )
        )
        indexes.append(None)   # build_batch_program's one-shot build

    nfeat_local = np.zeros((len(ranks), cap + 1, g.dim_node), np.float32)
    for row, k in enumerate(ranks):
        li = local[k]
        real_ids = li.globals_[: li.num_real]
        nfeat_local[row, : li.num_real] = g.node_feat[real_ids]

    e_cap = int(edges_per_device.max()) if n_dev else 0
    efeat_local = np.zeros((len(ranks), e_cap + 1, g.dim_edge), np.float32)
    for row, k in enumerate(ranks):
        eg = edge_globals[k]
        efeat_local[row, : len(eg)] = g.edge_feat[eg]
    return streams, indexes, edges_per_device, nfeat_local, efeat_local


def _localize_sharded(
    shards: ShardedStream,
    node_lists: list[np.ndarray],
    local,
    cap: int,
    cfg: TIGConfig,
    time_scale: float,
    ranks: list[int],
):
    """Per-device localized streams + feature gathers straight from
    ``tig-shards-v1`` row-range chunks — the graph is never materialized.

    One chunked pass over ``edge_chunks(features=True)`` classifies each
    shard's edges against every device's membership (vectorized
    ``subgraph_mask``), localizes ids, and gathers that shard's feature
    rows; host memory holds one shard of ids+features plus the per-device
    localized streams (O(E_k) ids + O(E_k) feature rows — the working set
    the device needs anyway, never the global table).  The per-device
    temporal neighbor index is built with the chunked two-pass T-CSR
    (``ChronoNeighborIndex.from_chunks``) over the same localized pieces —
    arrays identical to the one-shot build on the concatenated stream.

    ``ranks`` as in ``_localize_in_memory``: per-device streams, features
    and indexes materialize only for those devices (the chunk pass still
    CLASSIFIES every device's edges — the counts drive the global
    schedule — but unmaterialized devices never accumulate id/feature
    pieces, keeping the host working set O(local devices)).
    """
    n_dev = len(node_lists)
    held = set(ranks)
    members = [li.to_local >= 0 for li in local]
    pieces: list[list[tuple]] = [[] for _ in range(n_dev)]
    feat_parts: list[list[np.ndarray]] = [[] for _ in range(n_dev)]
    cursors = np.zeros(n_dev, dtype=np.int64)

    for src, dst, t, _eidx, efeat in shards.edge_chunks(features=True):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        for k, li in enumerate(local):
            keep = subgraph_mask(members[k], src, dst)
            m = int(keep.sum())
            if m == 0:
                continue
            # LOCAL edge ids into the device's own feature table: rows are
            # appended in stream order, so ids are the running cursor
            eidx_local = np.arange(cursors[k], cursors[k] + m,
                                   dtype=np.int64)
            cursors[k] += m
            if k not in held:
                continue
            pieces[k].append((
                li.to_local[src[keep]].astype(np.int64),
                li.to_local[dst[keep]].astype(np.int64),
                np.asarray(t, np.float64)[keep] / time_scale,
                eidx_local,
            ))
            feat_parts[k].append(efeat[keep])

    streams: list[Optional[LocalStream]] = [None] * n_dev
    indexes: list[Optional[ChronoNeighborIndex]] = [None] * n_dev
    edges_per_device = cursors.copy()
    e_cap = int(edges_per_device.max()) if n_dev else 0
    efeat_local = np.zeros((len(ranks), e_cap + 1, shards.dim_edge),
                           np.float32)
    for row, k in enumerate(ranks):
        chunks = pieces[k]
        cat = lambda i: (  # noqa: E731
            np.concatenate([c[i] for c in chunks]) if chunks
            else np.zeros(0, np.int64 if i != 2 else np.float64))
        streams[k] = LocalStream(
            src=cat(0), dst=cat(1), t=cat(2), eidx=cat(3),
            num_local_nodes=cap, labels=None,
        )
        # an edge-less device degenerates to one padding batch whose index
        # the one-shot build handles (from_chunks would report 0 batches)
        indexes[k] = (ChronoNeighborIndex.from_chunks(
            chunks, cap, cfg.num_neighbors, cfg.batch_size)
            if chunks else None)
        if feat_parts[k]:
            efeat_local[row, : edges_per_device[k]] = \
                np.concatenate(feat_parts[k])
        # release this device's chunk pieces eagerly: the concatenated
        # stream + T-CSR index own fresh arrays, keeping the originals
        # alive would double the id-column working set
        feat_parts[k] = []
        pieces[k] = []

    nfeat_local = np.zeros((len(ranks), cap + 1, shards.dim_node),
                           np.float32)
    nfeat = shards.node_feat()          # memory-mapped (or zeros)
    for row, k in enumerate(ranks):
        li = local[k]
        real_ids = li.globals_[: li.num_real]
        nfeat_local[row, : li.num_real] = np.asarray(nfeat[real_ids],
                                                     np.float32)
    return streams, indexes, edges_per_device, nfeat_local, efeat_local


def plan_epoch(
    source: StreamSource,
    node_lists: list[np.ndarray],
    shared_nodes: np.ndarray,
    cfg: TIGConfig,
    rng: np.random.Generator,
    *,
    steps_override: Optional[int] = None,
    time_scale: Optional[float] = None,
    host_replay: bool = False,
    plan: str = "host",
    layout: str = "replicated",
    local_ranks=None,
) -> EpochPlan:
    """Localize each device's sub-graph and pre-build its batch stream.

    ``source`` is an in-memory ``TemporalGraph`` or an out-of-core
    ``ShardedStream`` (row-range localization, the graph never
    materializes).  By default the plan is transfer-minimal: only real
    batches are emitted (flat grid + per-device offsets; Alg.2 wrap-around
    happens on device).  ``host_replay=True`` reproduces the legacy
    host-side replay up to ``steps_per_epoch`` — kept as the bit-exact
    parity oracle.

    ``plan="device"`` additionally drops the pre-sampled neighbor grids:
    each device ships only its localized RAW edge stream and the scanned
    step samples neighbors on device from a per-device T-CSR.  The
    per-device ``device_export``s compose into ONE flat event buffer
    (each device's ``indptr`` offset by the preceding devices' lengths),
    so ``EpochPlan.tcsr`` carries a mapped (N_dev, cap+1) ``indptr``
    plus unmapped flat ``nbr`` / ``t`` / ``eidx`` / ``bat`` arrays — no
    per-device padding to the largest partition.  ``plan="host"`` (the
    default) is the bit-parity oracle; ``host_replay`` implies it.

    ``layout="sharded"`` (pod scale) cuts the same plan by per-device row
    ranges instead: the grid is a zero-padded (N_held, rows_cap, ...)
    stack, the T-CSR stays per-device (unoffset ``indptr``, events padded
    to the largest export) — both mappable over "part" so each device
    transfers only its own rows.  ``local_ranks`` (sharded only) limits
    materialization to this process's devices: batch programs, features
    and T-CSRs are built for those ranks only, while edge counts and the
    per-device RNG seeds are drawn for ALL ranks so every process derives
    the identical global schedule.  Batch-program negatives draw from
    per-device child seeds (split upfront from ``rng``) — device k's
    stream is reproducible no matter which subset of devices a host
    plans.
    """
    if plan not in ("host", "device"):
        raise ValueError(f"plan={plan!r}: expected 'host' or 'device'")
    if host_replay and plan == "device":
        raise ValueError(
            "host_replay is the host-planned parity oracle; it cannot be "
            "combined with plan='device'")
    if layout not in ("replicated", "sharded"):
        raise ValueError(
            f"layout={layout!r}: expected 'replicated' or 'sharded'")
    if host_replay and layout == "sharded":
        raise ValueError(
            "host_replay IS the legacy replicated-schedule oracle; use "
            "layout='sharded' without it")
    n_dev = len(node_lists)
    if local_ranks is not None:
        if layout != "sharded":
            raise ValueError(
                "local_ranks requires layout='sharded' (the replicated "
                "flat grid needs every device's rows)")
        ranks = [int(r) for r in np.asarray(local_ranks).ravel()]
        if ranks != sorted(set(ranks)) or not ranks \
                or ranks[0] < 0 or ranks[-1] >= n_dev:
            raise ValueError(f"local_ranks={ranks}: expected sorted unique "
                             f"ranks within [0, {n_dev})")
    else:
        ranks = list(range(n_dev))
    local = make_local_indices(node_lists, source.num_nodes)
    cap = local[0].capacity if local else 0

    # one child seed per device, split upfront: device k's batch stream
    # (negative draws) is a pure function of (rng, k), independent of
    # which devices this process materializes
    seeds = rng.integers(0, 2**63, size=n_dev) if n_dev else []

    if isinstance(source, ShardedStream):
        if time_scale is None:
            # one 8-byte/edge column pass — the same cost every consumer
            # of a sharded stream already pays (protocol.split_views)
            time_scale = time_scale_of(source.column("t"))
        streams, indexes, edges_per_device, nfeat_local, efeat_local = \
            _localize_sharded(source, node_lists, local, cap, cfg,
                              time_scale, ranks)
    else:
        time_scale = time_scale or time_scale_of(source.t)
        streams, indexes, edges_per_device, nfeat_local, efeat_local = \
            _localize_in_memory(source, node_lists, local, cap, time_scale,
                                ranks)

    sched = cycle_schedule(edges_per_device, cfg.batch_size)
    steps = steps_override or sched.steps_per_epoch

    programs = []                  # aligned with ranks
    exports: list[dict] = []       # aligned with ranks (device plan)
    for k in ranks:
        stream = streams[k]
        idx = indexes[k]
        if plan == "device" and idx is None:
            # the host path defers to build_batch_program's one-shot build;
            # the device plan needs the index itself to export its T-CSR
            # (an edge-less stream yields the empty index: all -1 samples)
            idx = ChronoNeighborIndex(
                stream.src, stream.dst, stream.t, stream.eidx,
                cap, cfg.num_neighbors, cfg.batch_size)
        if plan == "device":
            exports.append(idx.device_export(depth=cfg.n_layers))
        real, _ = build_batch_program(
            stream, cfg, np.random.default_rng(int(seeds[k])),
            # an empty stream pads to one batch, which the zero-batch
            # index would fail shape validation against
            index=idx if (idx is not None and stream.num_edges) else None,
            plan=plan)
        # labels are host-side only (classification head trained post-hoc)
        real.pop("labels", None)
        programs.append(real)

    # real batch counts are GLOBAL (the lockstep schedule): recover the
    # unmaterialized devices' counts from the cycle schedule and check the
    # built programs agree with it
    real_batches = np.asarray(sched.batches, dtype=np.int64)
    for row, k in enumerate(ranks):
        assert len(programs[row]["src"]) == real_batches[k], \
            (k, len(programs[row]["src"]), real_batches[k])
    n_batches = np.minimum(real_batches, steps).astype(np.int32)

    tcsr = None
    if plan == "device":
        if layout == "sharded":
            # per-device rows, UNOFFSET indptr: each device addresses its
            # own event segment, padded to the largest export so shard_map
            # can map the leading axis (pad rows are never addressed —
            # indptr bounds stay within the real segment)
            # GLOBAL event cap, derivable from edge counts alone (export
            # length = 2 endpoint events per edge + K*depth front pad,
            # chunk-aligned), so a host planning only its own ranks pads
            # identically
            ev_len = [export_length(2 * int(n), cfg.num_neighbors,
                                    cfg.n_layers) for n in edges_per_device]
            ev_cap = max(ev_len)
            for k, e in zip(ranks, exports):
                assert len(e["nbr"]) == ev_len[k], (k, len(e["nbr"]))
            pad = lambda v: np.pad(v, (0, ev_cap - len(v)))  # noqa: E731
            tcsr = {
                "indptr": np.stack([e["indptr"] for e in exports]),
                **{key: np.stack([pad(e[key]) for e in exports])
                   for key in ("nbr", "t", "eidx", "bat")},
            }
        else:
            lens = [len(e["nbr"]) for e in exports]
            bases = np.cumsum([0] + lens)[:-1]
            tcsr = {
                "indptr": np.stack([e["indptr"] + np.int32(b)
                                    for e, b in zip(exports, bases)]),
                **{key: np.concatenate([e[key] for e in exports])
                   for key in ("nbr", "t", "eidx", "bat")},
            }

    if host_replay:
        # legacy Alg.2 wrap-around ON HOST: replay from the start; the
        # neighbor index is implicitly reset each cycle because replayed
        # batches reuse the first-cycle samples.
        per_dev = [{kk: v[np.arange(steps) % len(p["src"])]
                    for kk, v in p.items()} for p in programs]
        batches = {kk: np.stack([d[kk] for d in per_dev])
                   for kk in per_dev[0]}
        offsets = None
    else:
        # ship ONLY the real batches (trimmed to the lockstep length when
        # steps_override cuts an epoch short); the device gathers
        # offsets[k] + s % n_batches[k] inside the scan.
        trimmed = [{kk: v[: n_batches[k]] for kk, v in p.items()}
                   for k, p in zip(ranks, programs)]
        if layout == "sharded":
            # row-range-sharded: every device owns row k of a padded
            # stack — offsets are all zero and the grid maps over "part"
            rows_cap = int(n_batches.max()) if n_dev else 0
            batches = pad_batch_programs(trimmed, rows_cap)
            offsets = np.zeros(n_dev, np.int32)
        else:
            batches, offsets = concat_batch_programs(trimmed)

    shared_local = np.zeros((n_dev, len(shared_nodes)), np.int32)
    for k, li in enumerate(local):
        rows = li.to_local[shared_nodes] if len(shared_nodes) else \
            np.zeros(0, np.int32)
        if len(shared_nodes) and (rows < 0).any():
            raise ValueError(
                "shared nodes must be present on every device "
                "(Alg.1 line 20 shared_to_all)")
        shared_local[k] = rows

    e_cap = int(edges_per_device.max()) if n_dev else 0
    return EpochPlan(
        batches=batches,
        n_batches=n_batches,
        nfeat_local=nfeat_local,
        efeat_local=efeat_local,
        shared_local=shared_local,
        node_lists=list(node_lists),
        capacity=cap,
        edge_capacity=e_cap,
        steps=steps,
        edges_per_device=edges_per_device,
        offsets=offsets,
        host_replay=host_replay,
        tcsr=tcsr,
        layout=layout,
        local_ranks=None if local_ranks is None
        else np.asarray(ranks, np.int64),
    )


# ======================================================================
# the device-epoch program
# ======================================================================

def device_epoch(
    params,
    opt_state,
    batches,        # flat (sum real, ...) pytree — or (steps, ...) replayed
    offset,         # () int32 — this device's start row in the flat grid
    n_batches,      # () int32 — real batches (cycle length)
    nfeat_local,    # (cap+1, d_n)
    efeat,          # (E+1, d_e) replicated
    shared_local,   # (S,) int32
    tcsr_indptr=None,   # (cap+1,) int32 — this device's T-CSR row bounds
    tcsr_events=None,   # flat event arrays (shared across devices)
    *,
    cfg: TIGConfig,
    opt: Optimizer,
    steps: int,
    capacity: int,
    sync_mode: Literal["latest", "mean"] = "latest",
    axis: str = "part",
    host_replay: bool = False,
    sync_epilogue: bool = True,
):
    """One epoch on one device (runs under vmap or shard_map over ``axis``).

    The scan itself is the shared engine program (``engine.scan_train_epoch``
    with ``cycle_length`` = this device's real batch count and DDP gradient
    sync over ``axis``); the PAC-specific shared-node memory sync runs as
    the ``sync_shared_memory`` epilogue.  ``sync_epilogue=False`` returns
    the PRE-sync epoch-end state instead — the scan-only half of the
    overlap boundary, whose caller dispatches ``make_pac_sync`` separately
    so the collectives drain behind the next epoch.

    Default mode is the transfer-minimal plan: ``batches`` holds only real
    batches and the scan gathers ``offset + s % n_batches`` for each of the
    ``steps`` lockstep steps (Alg.2 wrap-around ON DEVICE).  With
    ``host_replay`` (the parity oracle) ``batches`` is this device's grid
    already replayed to ``steps`` rows on the host.

    With ``tcsr_indptr`` / ``tcsr_events`` (a device-sampled plan,
    ``plan_epoch(plan="device")``) the batch grid carries raw edge records
    and the scanned step samples its neighbor grids on device: the
    device's ``indptr`` window addresses its own segment of the shared
    flat event buffer (replicated layout — per-device exports are
    concatenated with offset ``indptr``s) or, with the row-range-sharded
    layout, its OWN padded event rows with unoffset ``indptr`` (the
    executor maps both over the device axis, so either way this function
    sees one device's ``(cap+1,)`` indptr + the events it may address).
    """
    tables = {"efeat": efeat, "nfeat": nfeat_local}
    fresh = init_state(cfg, capacity)
    tcsr = None
    if tcsr_indptr is not None:
        tcsr = {"indptr": tcsr_indptr, **tcsr_events}

    if host_replay:
        # stream length is carried by the batches pytree itself
        params, opt_state, state, losses = scan_train_epoch(
            params, opt_state, fresh, batches, tables,
            cfg=cfg, opt=opt, axis=axis, cycle_length=n_batches, tcsr=tcsr)
    else:
        params, opt_state, state, losses = scan_train_epoch(
            params, opt_state, fresh, batches, tables,
            cfg=cfg, opt=opt, axis=axis, cycle_length=n_batches,
            wrap_steps=steps, wrap_offset=offset, tcsr=tcsr)

    if sync_epilogue:
        state = sync_shared_memory(state, shared_local,
                                   sync_mode=sync_mode, axis=axis)

    return params, opt_state, state, losses


def sync_shared_memory(
    state,
    shared_local,   # (S,) int32 — this device's rows of the shared nodes
    *,
    sync_mode: Literal["latest", "mean"] = "latest",
    axis: str = "part",
):
    """Shared-node memory synchronization (paper §II-C) for ONE device's
    epoch-end state — runs under vmap or shard_map over ``axis``.

    §Perf iteration C1: instead of all-gathering the full (N_dev, S, d)
    replica rows (O(N*S*d) link bytes), gather only the (N_dev, S)
    timestamps, compute the argmax winner, and combine rows with a
    winner-masked psum — O(N*S + S*d) bytes, ~d-fold less traffic.

    Factored out of ``device_epoch`` so the overlap boundary can dispatch
    it as a SEPARATE program (``make_pac_sync``) right after the scan-only
    epoch program: the cross-host collectives then drain while the next
    epoch stages and dispatches, instead of serializing inside one fused
    program.  The fused path (``device_epoch(sync_epilogue=True)``) calls
    this same function, so the two boundaries share the sync math.
    """
    if shared_local.shape[0] == 0:
        return state
    rows_m = state["mem"][shared_local]          # (S, d)
    rows_m2 = state["mem2"][shared_local]
    rows_t = state["last"][shared_local]         # (S,)
    if sync_mode == "latest":
        all_t = jax.lax.all_gather(rows_t, axis)     # (N_dev, S)
        win = jnp.argmax(all_t, axis=0)              # (S,)
        me = jax.lax.axis_index(axis)
        mine = (win == me)[:, None].astype(rows_m.dtype)
        new_m = jax.lax.psum(rows_m * mine, axis)
        new_m2 = jax.lax.psum(rows_m2 * mine, axis)
        new_t = jnp.max(all_t, axis=0)
    else:
        n = jax.lax.psum(1, axis)
        new_m = jax.lax.psum(rows_m, axis) / n
        new_m2 = jax.lax.psum(rows_m2, axis) / n
        new_t = jax.lax.psum(rows_t, axis) / n
    return {
        **state,
        "mem": state["mem"].at[shared_local].set(new_m),
        "mem2": state["mem2"].at[shared_local].set(new_m2),
        "last": state["last"].at[shared_local].set(new_t),
    }


def make_pac_epoch(
    cfg: TIGConfig,
    opt: Optimizer,
    steps: int,
    capacity: int,
    *,
    mesh: Optional[Mesh] = None,
    sync_mode: Literal["latest", "mean"] = "latest",
    host_replay: bool = False,
    device_plan: bool = False,
    grid_layout: str = "replicated",
    sync_epilogue: bool = True,
):
    """Build the jitted epoch executor.

    mesh=None  -> vmap simulation over the leading device axis (single host
                  device; used by CPU tests/benchmarks).
    mesh given -> shard_map over mesh axis "part" (real SPMD; the dry-run
                  compiles this exact program for the production mesh; the
                  mesh may SPAN PROCESSES — ``launch.mesh.make_tig_mesh``
                  — in which case the grid/feature in_specs place each
                  device's rows on its owning host and the shared-node
                  sync collectives run across hosts).

    ``grid_layout="replicated"`` (the single-host oracle): the flat batch
    grid is UNMAPPED (vmap ``in_axes=None`` / shard_map replicated) —
    every device holds the ``sum_k n_batches_k`` real rows and gathers its
    own window; still far smaller than a replayed ``N_dev * steps`` grid
    whenever partitions are imbalanced.  ``grid_layout="sharded"`` (pod
    scale) instead maps the (N_dev, rows_cap, ...) padded grid — and a
    device plan's per-device T-CSR events — over "part": per-device H2D
    is O(own rows) and no host ever needs another host's rows
    (``plan_epoch(layout="sharded")`` emits this layout).  With
    ``host_replay`` the legacy per-device replayed grids are mapped over
    the device axis.

    With ``device_plan`` the executor takes two extra operands — the
    (N_dev, cap+1) mapped T-CSR ``indptr`` and the event arrays (flat
    replicated, or per-device mapped when sharded) — and the scanned step
    samples neighbor grids on device (``plan_epoch(plan="device")`` emits
    both).  Note the vmap simulation then routes sampling through
    whatever backend ``cfg`` selects; the Pallas path is written for the
    per-device shard_map/SPMD layout.

    ``sync_epilogue=False`` builds the SCAN-ONLY half of the async epoch
    boundary: the program returns the pre-sync epoch-end states (the
    caller dispatches ``make_pac_sync`` on them separately so the
    shared-node collectives drain behind the next epoch), and its
    per-epoch plan operands — batch grids, feature tables, T-CSR — are
    DONATED (non-CPU backends): the staging path re-materializes them
    every epoch, so XLA may reuse their device buffers in place.  The
    fused single-program path (``sync_epilogue=True``, the default) is
    the bit-parity oracle for the split boundary.
    """
    if grid_layout not in ("replicated", "sharded"):
        raise ValueError(f"grid_layout={grid_layout!r}")
    if host_replay and grid_layout == "sharded":
        raise ValueError("host_replay implies the replicated schedule")
    sharded = grid_layout == "sharded"
    grid_mapped = host_replay or sharded
    kernel = functools.partial(
        device_epoch, cfg=cfg, opt=opt, steps=steps, capacity=capacity,
        sync_mode=sync_mode, host_replay=host_replay,
        sync_epilogue=sync_epilogue,
    )
    # donated plan buffers (scan-only boundary): batches=2, nfeat=5,
    # efeat=6 (+ the T-CSR operands, 8/9) are re-staged every epoch and
    # consumed exactly once; shared_local (7) is NOT donated — the
    # separate sync program reads it after the scan.  The fused oracle
    # keeps its operands intact.
    donate = () if sync_epilogue else _donate(
        2, 5, 6, *((8, 9) if device_plan else ()))

    if mesh is None:
        in_axes = [None, None, 0 if grid_mapped else None, 0, 0, 0, 0, 0]
        if device_plan:
            # indptr always mapped; events mapped only when sharded
            in_axes += [0, 0 if sharded else None]
        vmapped = jax.vmap(
            kernel,
            in_axes=tuple(in_axes),
            out_axes=(0, 0, 0, 0),
            axis_name="part",
        )

        def run(params, opt_state, batches, offsets, n_batches,
                nfeat_local, efeat, shared_local, *tcsr_args):
            p, o, state, losses = vmapped(
                params, opt_state, batches, offsets, n_batches,
                nfeat_local, efeat, shared_local, *tcsr_args)
            # params/opt_state identical across devices (pmean'd grads)
            p0 = jax.tree.map(lambda x: x[0], p)
            o0 = jax.tree.map(lambda x: x[0], o)
            return p0, o0, state, losses

        return jax.jit(run, donate_argnums=donate)

    part = P("part")
    rep = P()

    def body(params, opt_state, batches, offsets, n_batches, nfeat_local,
             efeat, shared_local, *tcsr_args):
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
        extra = ()
        if tcsr_args:
            extra = (squeeze(tcsr_args[0]),
                     squeeze(tcsr_args[1]) if sharded else tcsr_args[1])
        p, o, state, losses = kernel(
            params, opt_state,
            squeeze(batches) if grid_mapped else batches,
            squeeze(offsets), squeeze(n_batches),
            squeeze(nfeat_local), squeeze(efeat), squeeze(shared_local),
            *extra)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return p, o, expand(state), expand(losses)

    in_specs = (rep, rep, part if grid_mapped else rep,
                part, part, part, part, part)
    if device_plan:
        in_specs += (part, part if sharded else rep)
    smapped = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(rep, rep, part, part),
    )
    return jax.jit(smapped, donate_argnums=donate)


def make_pac_sync(
    *,
    sync_mode: Literal["latest", "mean"] = "latest",
    mesh: Optional[Mesh] = None,
):
    """Build the standalone jitted shared-node sync program —
    ``(states, shared_local) -> states`` over stacked (N_dev, ...) inputs.

    The separable half of the async epoch boundary: ``pac_train`` with
    ``epoch_boundary="overlap"`` dispatches this right after the
    scan-only epoch program and does NOT block on it, so the cross-host
    ``all_gather``/``psum`` collectives drain while the worker thread
    stages epoch e+1's plan and the main thread dispatches its scan.
    Executors mirror ``make_pac_epoch``: vmap simulation (``mesh=None``)
    or shard_map over the mesh's "part" axis.  The math is the same
    ``sync_shared_memory`` the fused oracle runs.
    """
    kernel = functools.partial(sync_shared_memory, sync_mode=sync_mode)
    if mesh is None:
        return jax.jit(jax.vmap(kernel, in_axes=(0, 0), out_axes=0,
                                axis_name="part"))

    part = P("part")

    def body(state, shared_local):
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
        out = kernel(squeeze(state), squeeze(shared_local))
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(part, part), out_specs=part))


# ======================================================================
# full training driver
# ======================================================================

def globalize_memory(
    states,
    plan: EpochPlan,
    num_nodes: int,
    cfg: TIGConfig,
    *,
    time_rescale: float = 1.0,
) -> dict:
    """Merge PAC's stacked (N_dev, ...) post-sync memories into one
    global-row state suitable for the evaluation protocol.

    Each device contributes its real local rows (local id = rank in the
    sorted node list, as ``make_local_indices`` assigns them); a node
    hosted by several devices resolves by the paper's "latest" rule — the
    replica with the largest last-update time wins (first host wins ties).
    ``time_rescale`` converts the plan-scale "last" timestamps into the
    consumer's units (train-split scale -> protocol full-stream scale).
    Pending-message buffers are not carried over: PAC's cycle-end backup
    already treats (mem, mem2, last) as the state of record.
    """
    d = int(np.asarray(states["mem"]).shape[-1])
    mem = np.zeros((num_nodes + 1, d), np.float32)
    mem2 = np.zeros((num_nodes + 1, d), np.float32)
    last = np.zeros((num_nodes + 1,), np.float32)
    written = np.zeros(num_nodes + 1, dtype=bool)
    for k, nodes in enumerate(plan.node_lists):
        nodes = np.sort(np.asarray(nodes, np.int64))
        n = len(nodes)
        m = np.asarray(states["mem"][k][:n])
        m2 = np.asarray(states["mem2"][k][:n])
        l = np.asarray(states["last"][k][:n]) * np.float32(time_rescale)
        take = (~written[nodes]) | (l > last[nodes])
        tgt = nodes[take]
        mem[tgt], mem2[tgt], last[tgt] = m[take], m2[take], l[take]
        written[tgt] = True
    fresh = init_state(cfg, num_nodes)
    return {**fresh, "mem": jnp.asarray(mem), "mem2": jnp.asarray(mem2),
            "last": jnp.asarray(last)}


@dataclasses.dataclass
class PACResult:
    params: dict
    memory_states: dict           # stacked (N_dev, ...) post-sync states
    losses: list                  # per epoch: (N_dev, steps_e) arrays
    derived_speedup: float
    edges_per_device: np.ndarray
    plan: EpochPlan
    metrics: Optional[dict] = None   # run_protocol output (eval_graph given)

    def mean_loss_per_epoch(self) -> np.ndarray:
        return np.array([float(l.mean()) for l in self.losses])


def stage_replicated_tree(tree, mesh):
    """Replicate every leaf of a pytree across all devices of ``mesh`` —
    cross-process safe (params/optimizer state at the start of a
    multi-process PAC run; epoch outputs then keep the placement)."""
    return jax.tree.map(lambda x: stage_replicated(x, mesh), tree)


_PAC_PROGRAMS_MAX = 8    # per-call LRU of compiled epoch executors

# Module-level LRU of the multihost host-read gather (jit identity that
# reshards fully replicated).  One wrapper per MESH, persistent across
# ``pac_train`` calls: rebuilding it per call discarded its trace cache,
# so every call re-traced per distinct loss shape (``steps`` varies
# across epochs) — the same retrace leak the epoch-program LRU fixes.
_GATHER_PROGRAMS: dict = {}
_GATHER_PROGRAMS_MAX = 8


def _replicating_gather(mesh: Mesh):
    return lru_get(
        _GATHER_PROGRAMS, mesh, _GATHER_PROGRAMS_MAX,
        lambda: jax.jit(lambda t: t,
                        out_shardings=NamedSharding(mesh, P())))


def pac_train(
    g_train: StreamSource,
    partition: PartitionResult,
    cfg: TIGConfig,
    *,
    num_devices: int,
    epochs: int = 3,
    lr: float = 1e-3,
    seed: int = 0,
    shuffle_parts: bool = True,
    sync_mode: Literal["latest", "mean"] = "latest",
    mesh: Optional[Mesh] = None,
    prefetch: bool = True,
    depth: int = 1,
    epoch_boundary: Literal["overlap", "serial"] = "overlap",
    host_replay: bool = False,
    plan: str = "device",
    grid_layout: Optional[str] = None,
    eval_graph: Optional[StreamSource] = None,
    eval_node_class: bool = False,
    eval_warm: Literal["memory", "replay", "restart"] = "memory",
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    resume: bool = False,
    faults: Optional[FaultInjector] = None,
) -> PACResult:
    """Train a TIG model with SEP partitions + PAC (the paper's pipeline).

    ``g_train`` is the train split — an in-memory ``TemporalGraph`` or an
    out-of-core ``ShardedStream`` (per-device localization then runs
    straight off the row-range shards; the graph never materializes).

    ``partition`` may have more parts than devices (|P| > N): parts are then
    shuffle-combined into N super-partitions before every epoch (Fig.7).

    With ``prefetch`` (the default) cycle e+1's host planning — shuffle-
    combine, localization, batch grids — and its host->device transfer run
    on a worker thread while cycle e's scan executes (``depth`` host plans
    may run ahead; device staging stays single-slot); per-epoch RNG
    streams keep results bit-identical to serial planning.
    ``host_replay=True`` selects the legacy host-side wrap-around replay
    plan (the parity oracle for the transfer-minimal device-side wrap,
    bit-identical).

    ``epoch_boundary="overlap"`` (the default) makes the boundary itself
    asynchronous: the epoch runs as a SCAN-ONLY program (plan buffers
    donated), the Alg.2 shared-node memory sync is dispatched as a
    separate program the main thread never blocks on (its cross-host
    collectives drain behind epoch e+1's staging and scan), and the
    per-epoch loss read becomes an async device->host copy collected once
    after the loop.  ``"serial"`` is the fused-program oracle — scan+sync
    in one program, blocking ``fetch`` per epoch — and is bit-identical
    (the parity suite asserts exact equality of losses/params/memory/
    metrics).  Disable pipelining entirely with ``prefetch=False`` /
    ``depth=0`` + ``epoch_boundary="serial"`` when debugging.

    ``grid_layout`` picks the grid/T-CSR placement: ``"sharded"`` (the
    default whenever a ``mesh`` is given) row-range-shards the batch grid
    and per-device T-CSR over "part" so each device transfers only its
    own rows; ``"replicated"`` (the default for the vmap simulation, and
    the bit-parity oracle) ships every device the flat grid.  On a mesh
    spanning processes (``launch.mesh.make_tig_mesh``) each process
    additionally PLANS only its own devices' rows
    (``plan_epoch(local_ranks=...)``) and stages them with
    ``make_array_from_process_local_data`` — host grid bytes and H2D stay
    O(local devices) per host, and the Alg.2 shared-node memory sync
    genuinely crosses hosts.  Every process must call ``pac_train`` with
    identical arguments (standard SPMD contract).

    ``plan="device"`` (the default) ships each device only its raw-edge
    stream plus T-CSR and samples neighbor grids inside the scanned step
    (bit-identical to host planning); ``plan="host"`` keeps the
    pre-sampled grids.  ``host_replay=True`` implies host planning — it
    IS the legacy host-side oracle.

    ``eval_graph`` (the FULL chronological stream — ``TemporalGraph`` or
    ``ShardedStream`` — of which ``g_train`` is the train split) routes the
    trained parameters through the shared evaluation-protocol driver
    (``protocol.run_protocol``, the same code path as ``train_single`` /
    ``train_sharded(protocol=True)``), REUSING PAC's synchronized node
    memories: the per-device post-sync states are merged back to global
    rows (latest-timestamp rule, ``globalize_memory``) and val/test are
    scored from that warm state — the device replay of the train split is
    skipped, so ``metrics["train_ap"]`` is NaN.  Results attach to
    ``PACResult.metrics``.  ``eval_warm`` picks where that warm state
    comes from: ``"memory"`` (the default — PAC's synchronized memories,
    above), ``"replay"`` (the plain protocol oracle: replay the train
    split), or ``"restart"`` (TIGER-style: fit a restarter head on
    collected embeddings, rebuild memory in O(N) — the restarter is also
    saved next to the checkpoints when ``ckpt_dir`` is set, so an elastic
    relaunch can warm memory without any replay).

    Fault tolerance: ``ckpt_dir`` + ``ckpt_every=k`` atomically saves
    ``{params, opt_state, states}`` every k epochs (process 0 writes;
    every process joins the gather).  ``resume=True`` restores
    params/opt_state from the newest complete step and continues from the
    following epoch — bit-identical to an uninterrupted run, because each
    epoch's plan RNG and memory init depend only on ``(seed, ep)``.
    Resuming past the final epoch re-emits a fresh-memory result (saved
    states may be shaped for a different device count, so they are not
    reloaded).  ``faults`` (default: parsed from ``$REPRO_FAULTS``)
    deterministically injects failures at the named sites (``host_kill``,
    ``staging_oom``, ``prefetch_worker``, ``sync_fail``); in a multi-host
    run, any failure that classifies as a lost peer (``is_host_loss``)
    is re-raised as ``HostLossError`` so ``launch.pac_cluster`` can
    re-form the world over the survivors.
    """
    from repro.optim import adamw

    if plan not in ("host", "device"):
        raise ValueError(f"plan={plan!r}: expected 'host' or 'device'")
    if epoch_boundary not in ("overlap", "serial"):
        raise ValueError(f"epoch_boundary={epoch_boundary!r}: expected "
                         "'overlap' or 'serial'")
    overlap = epoch_boundary == "overlap"
    if host_replay:
        plan = "host"
    if grid_layout is None:
        grid_layout = "replicated" if (mesh is None or host_replay) \
            else "sharded"
    if grid_layout not in ("replicated", "sharded"):
        raise ValueError(f"grid_layout={grid_layout!r}")
    if host_replay and grid_layout == "sharded":
        raise ValueError("host_replay implies grid_layout='replicated'")
    if eval_warm not in ("memory", "replay", "restart"):
        raise ValueError(f"eval_warm={eval_warm!r}: expected 'memory', "
                         "'replay' or 'restart'")
    if resume and not ckpt_dir:
        raise ValueError("resume=True needs ckpt_dir")
    injector = faults if faults is not None else FaultInjector.from_env()

    # a mesh spanning >1 process: plan + stage only local devices' rows
    mesh_procs = sorted({d.process_index
                         for d in np.asarray(mesh.devices).flat}) \
        if mesh is not None else []
    multihost = len(mesh_procs) > 1
    if multihost:
        from repro.launch.mesh import local_part_ranks
        ranks_np = local_part_ranks(mesh)
    plan_ranks = ranks_np if (multihost and grid_layout == "sharded") \
        else None

    small_parts = partition.node_lists()
    if isinstance(g_train, ShardedStream):
        time_scale = time_scale_of(g_train.column("t"))
    else:
        time_scale = time_scale_of(g_train.t)

    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw(lr=lr, max_grad_norm=1.0)
    opt_state = opt.init(params)
    if multihost:
        # replicate once across the whole (cross-process) mesh; epoch
        # outputs keep the placement, so this happens only at init
        params = stage_replicated_tree(params, mesh)
        opt_state = stage_replicated_tree(opt_state, mesh)

    def build(ep: int) -> EpochPlan:
        injector.fire("prefetch_worker", epoch=ep)
        rng_ep = epoch_rng(seed, ep, 11)
        if shuffle_parts and len(small_parts) > num_devices:
            node_lists = shuffle_combine(small_parts, num_devices, rng_ep)
        elif len(small_parts) == num_devices:
            node_lists = small_parts
        else:
            node_lists = shuffle_combine(
                small_parts, num_devices, np.random.default_rng(seed))
        return plan_epoch(g_train, node_lists, partition.shared_nodes,
                          cfg, rng_ep, time_scale=time_scale,
                          host_replay=host_replay, plan=plan,
                          layout=grid_layout, local_ranks=plan_ranks)

    def to_device(ep_plan: EpochPlan):
        injector.fire("staging_oom")
        offsets = ep_plan.offsets if ep_plan.offsets is not None else \
            np.zeros(num_devices, np.int32)
        if not multihost:
            # single process: jnp.asarray suffices for every layout (jit
            # reshards at dispatch; all devices are addressable)
            dev = [
                {k: jnp.asarray(v) for k, v in ep_plan.batches.items()},
                jnp.asarray(offsets),
                jnp.asarray(ep_plan.n_batches),
                jnp.asarray(ep_plan.nfeat_local),
                jnp.asarray(ep_plan.efeat_local),
                jnp.asarray(ep_plan.shared_local),
            ]
            if ep_plan.tcsr is not None:
                dev.append(jnp.asarray(ep_plan.tcsr["indptr"]))
                dev.append({k: jnp.asarray(v)
                            for k, v in ep_plan.tcsr.items()
                            if k != "indptr"})
            return ep_plan, tuple(dev)

        # multi-process staging: mapped operands assemble the global
        # (N_dev, ...) array from THIS process's rows only (the olmax
        # per-process-slice idiom); plan-global scalars are sliced to the
        # local row range first.  Only a replicated grid layout ships
        # full flat arrays (the cross-host parity oracle).
        held_local = ep_plan.local_ranks is not None
        part = lambda a: stage_partitioned(  # noqa: E731
            np.asarray(a), mesh, num_devices)
        g2l = lambda a: np.asarray(a)[ranks_np]  # noqa: E731
        loc = (lambda a: np.asarray(a)) if held_local else g2l
        sharded_grid = ep_plan.layout == "sharded"
        # the replayed oracle grid is (N_dev, steps, ...) and mapped too
        grid_mapped = sharded_grid or ep_plan.host_replay
        grid_loc = loc if sharded_grid else g2l
        dev = [
            {k: (part(grid_loc(v)) if grid_mapped else
                 stage_replicated(v, mesh))
             for k, v in ep_plan.batches.items()},
            part(g2l(offsets)),
            part(g2l(ep_plan.n_batches)),
            part(loc(ep_plan.nfeat_local)),
            part(loc(ep_plan.efeat_local)),
            part(g2l(ep_plan.shared_local)),
        ]
        if ep_plan.tcsr is not None:
            dev.append(part(loc(ep_plan.tcsr["indptr"])))
            dev.append({k: (part(loc(v)) if sharded_grid else
                            stage_replicated(v, mesh))
                        for k, v in ep_plan.tcsr.items()
                        if k != "indptr"})
        return ep_plan, tuple(dev)

    # LRU of compiled epoch executors, mirroring make_eval_epoch's cache:
    # shuffle-combine draws alternate between a few (steps, capacity,
    # edge_capacity) shapes across epochs — keep each compiled program
    # live (move-to-end on hit) instead of rebuilding the jit wrapper
    # (and its compilation cache) every time the key changes.
    programs: dict = {}

    def epoch_program(ep_plan: EpochPlan):
        from repro.kernels import ops as _kops
        # cfg is fixed per pac_train call, but the executor's compiled
        # shapes also depend on n_layers (per-layer grids) and the
        # lane-padded dims the MXU tier launches — key them explicitly so
        # layer-count or padding-rule changes can't reuse a stale program.
        # The mesh and grid layout are part of the key too: a
        # process-spanning mesh and the vmap simulation (or two meshes /
        # layouts in one process) must never collide on the same program.
        key = (ep_plan.steps, ep_plan.capacity, ep_plan.edge_capacity,
               cfg.n_layers, _kops.lane_pad(cfg.dim),
               _kops.lane_pad(cfg.msg_dim), mesh, grid_layout,
               epoch_boundary)
        return lru_get(
            programs, key, _PAC_PROGRAMS_MAX,
            lambda: make_pac_epoch(
                cfg, opt, ep_plan.steps, ep_plan.capacity, mesh=mesh,
                sync_mode=sync_mode, host_replay=host_replay,
                device_plan=(plan == "device"), grid_layout=grid_layout,
                sync_epilogue=not overlap))

    def sync_program():
        # shape-polymorphic (jit retraces per state/shared shape inside
        # one wrapper), so a single cached program per mesh suffices
        return lru_get(
            programs, ("sync", mesh, sync_mode), _PAC_PROGRAMS_MAX,
            lambda: make_pac_sync(sync_mode=sync_mode, mesh=mesh))

    if multihost:
        # host values of cross-process arrays: reshard to fully
        # replicated (the all-gather over "part"), read the local shard
        gather = _replicating_gather(mesh)

        def fetch(tree):
            return jax.tree.map(
                lambda x: np.asarray(x.addressable_data(0)), gather(tree))

        def drain_local(tree):        # tree already gathered replicated
            return jax.tree.map(
                lambda x: np.asarray(x.addressable_data(0)), tree)
    else:
        def fetch(tree):
            return jax.tree.map(np.asarray, tree)

        drain_local = fetch

    def drain_async(tree):
        """Dispatch the device->host read WITHOUT blocking: reshard to
        replicated (multihost) and start the copy; ``drain_local``
        collects the host values once, after the loop."""
        tree = gather(tree) if multihost else tree
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        return tree

    start_epoch = 0
    if resume:
        step = latest_step(ckpt_dir)
        if step is not None:
            # restore on host (fetch is a collective in multihost: every
            # process joins), then re-stage exactly like the fresh init
            host = restore_checkpoint(ckpt_dir, step, {
                "params": fetch(params), "opt_state": fetch(opt_state)})
            if multihost:
                params = stage_replicated_tree(host["params"], mesh)
                opt_state = stage_replicated_tree(host["opt_state"], mesh)
            else:
                params = jax.tree.map(jnp.asarray, host["params"])
                opt_state = jax.tree.map(jnp.asarray, host["opt_state"])
            start_epoch = step + 1
            print(f"PAC_RESUME: step {step} restored from {ckpt_dir}, "
                  f"continuing at epoch {start_epoch}", flush=True)

    ckpt_writer = (not multihost) or jax.process_index() == 0

    all_losses = []
    last_plan = None
    states = None
    try:
        with EpochPrefetcher(build, epochs, to_device=to_device,
                             enabled=prefetch, depth=depth) as pf:
            for ep in range(start_epoch, epochs):
                injector.fire("host_kill", epoch=ep)
                ep_plan, dev = pf.get(ep)
                if overlap:
                    # scan-only program, then the sync epilogue as a
                    # separate dispatch the main thread never blocks on:
                    # its cross-host collectives drain while the worker
                    # stages epoch e+1 and the next scan is dispatched.
                    # dev[5] is shared_local — the one plan operand the
                    # scan program does not donate.
                    params, opt_state, raw_states, losses = epoch_program(
                        ep_plan)(params, opt_state, *dev)
                    injector.fire("sync_fail", epoch=ep)
                    states = sync_program()(raw_states, dev[5])
                    # deferred host read: async copy now, collect after
                    # the loop
                    all_losses.append(drain_async(losses))
                else:
                    injector.fire("sync_fail", epoch=ep)
                    params, opt_state, states, losses = epoch_program(
                        ep_plan)(params, opt_state, *dev)
                    all_losses.append(fetch(losses))
                last_plan = ep_plan
                if ckpt_dir and ckpt_every and (ep + 1) % ckpt_every == 0:
                    # fetch is collective — all processes call it; only
                    # process 0 touches the filesystem (atomic writes)
                    snap = {"params": fetch(params),
                            "opt_state": fetch(opt_state),
                            "states": fetch(states)}
                    if ckpt_writer:
                        save_checkpoint(ckpt_dir, ep, snap,
                                        metadata={"epoch": ep})
        if overlap:
            all_losses = [drain_local(l) for l in all_losses]

        if last_plan is None:
            # epochs=0 (or resume past the end): nothing trained — still
            # emit a consistent result (plan of the epoch that WOULD have
            # run, fresh stacked memories)
            last_plan = build(0)
            fresh = init_state(cfg, last_plan.capacity)
            states_host = jax.tree.map(
                lambda x: np.broadcast_to(
                    np.asarray(x), (num_devices,) + x.shape).copy(), fresh)
            params_host = fetch(params) if multihost else params
        else:
            # host copies once: globalize_memory / run_protocol / the
            # result run on host or the local default device, so
            # cross-process arrays must be gathered out of the mesh first
            states_host = fetch(states)
            params_host = fetch(params) if multihost else params
    except Exception as exc:
        if multihost and is_host_loss(exc):
            raise HostLossError(
                f"peer lost during PAC training: {exc}") from exc
        raise

    from repro.core.pac import derived_speedup as dsp

    metrics = None
    if eval_graph is not None:
        from repro.tig.batching import make_tables
        from repro.tig.protocol import run_protocol, split_views
        from repro.tig.stream import stage_device_tables

        splits = split_views(eval_graph)
        if isinstance(eval_graph, ShardedStream):
            tables_j = stage_device_tables(eval_graph)
        else:
            tables_j = {k: jnp.asarray(v) for k, v in make_tables(
                eval_graph.edge_feat, eval_graph.node_feat).items()}
        if eval_warm == "memory":
            warm = globalize_memory(
                states_host, last_plan, splits.num_nodes,
                cfg, time_rescale=time_scale / splits.time_scale)
            metrics = run_protocol(
                params_host, cfg, splits, tables_j, seed=seed,
                eval_node_class=eval_node_class, state=warm,
                replay_train=False)
        elif eval_warm == "replay":
            # plain protocol oracle: replay the train split for memory
            metrics = run_protocol(
                params_host, cfg, splits, tables_j, seed=seed,
                eval_node_class=eval_node_class, warm="replay")
        else:  # "restart": TIGER-style replayless memory reconstruction
            from repro.tig.restart import build_restarter, save_restarter

            rst, _ = build_restarter(
                params_host, cfg, splits, tables_j, seed=seed)
            if ckpt_dir and ckpt_writer:
                save_restarter(
                    os.path.join(ckpt_dir, "restarter.npz"), rst)
            metrics = run_protocol(
                params_host, cfg, splits, tables_j, seed=seed,
                eval_node_class=eval_node_class, warm="restart",
                restarter=rst)

    return PACResult(
        params=params_host,
        memory_states=states_host,
        losses=all_losses,
        derived_speedup=dsp(last_plan.edges_per_device),
        edges_per_device=last_plan.edges_per_device,
        plan=last_plan,
        metrics=metrics,
    )
