"""Chunked streaming data plane: out-of-core shards + epoch prefetch.

Million-node interaction streams must never be fully materialized in host
RAM (ROADMAP: "real-dataset ingestion at paper scale").  This module is the
disk <-> host <-> device plumbing between raw logs and the scanned epoch of
``repro.tig.engine``:

  * a memory-mapped **shard format** for edge streams (below),
  * a **pandas-free block reader** for JODIE/TGN CSVs that ingests
    arbitrarily large files one block at a time (``write_jodie_shards``),
  * **chunked device staging** of the per-edge feature table
    (``stage_device_tables``): the host only ever holds one shard's features;
    rows are written into a donated device buffer shard by shard,
  * an **EpochPrefetcher** that double-buffers host epoch planning: the plan
    for epoch e+1 is built on a worker thread (and optionally moved to
    device) while the ``lax.scan`` for epoch e runs.

Shard format (``tig-shards-v1``)
--------------------------------
A shard directory holds one chronological edge stream split into
fixed-size row ranges::

    <dir>/meta.json            format tag + sizes (see below)
    <dir>/shard_00000.src.npy  int64   (e_s,)   source node ids
    <dir>/shard_00000.dst.npy  int64   (e_s,)   destination node ids
    <dir>/shard_00000.t.npy    float64 (e_s,)   non-decreasing timestamps
    <dir>/shard_00000.label.npy int64  (e_s,)   dynamic labels (optional)
    <dir>/shard_00000.efeat.npy float32 (e_s, d_e) edge features
    <dir>/node_feat.npy        float32 (N, d_n) node features (optional;
                               absent means all-zeros, the paper's default)

``meta.json`` keys: ``format`` ("tig-shards-v1"), ``name``, ``num_nodes``,
``num_edges``, ``num_shards``, ``shard_edges`` (per-shard row counts),
``dim_edge``, ``dim_node``, ``has_labels``.  Every array is a plain ``.npy``
so readers use ``np.load(..., mmap_mode="r")`` — opening a stream touches
only ``meta.json``; array bytes are paged in on demand and never copied
unless a caller materializes them.  Shards are row ranges of ONE
chronological order: shard boundaries carry no semantic meaning and any
multiple-of-batch re-chunking is valid (``ChronoNeighborIndex.from_chunks``
relies on exactly this).
"""

from __future__ import annotations

import dataclasses
import io
import json
import os
import queue
import threading
from typing import Callable, Iterator, Optional, Sequence

import numpy as np

from repro.tig.graph import TemporalGraph

__all__ = [
    "SHARD_FORMAT",
    "ShardedStream",
    "write_graph_shards",
    "write_jodie_shards",
    "iter_jodie_blocks",
    "stage_device_tables",
    "stage_partitioned",
    "stage_replicated",
    "EpochPrefetcher",
]

SHARD_FORMAT = "tig-shards-v1"
DEFAULT_SHARD_EDGES = 262_144


# ======================================================================
# shard container
# ======================================================================

@dataclasses.dataclass
class ShardedStream:
    """A memory-mapped ``tig-shards-v1`` directory (see module docstring)."""

    path: str
    meta: dict

    @classmethod
    def open(cls, path: str) -> "ShardedStream":
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("format") != SHARD_FORMAT:
            raise ValueError(
                f"{path}: not a {SHARD_FORMAT} directory "
                f"(format={meta.get('format')!r})")
        return cls(path=path, meta=meta)

    # -- sizes ----------------------------------------------------------
    @property
    def num_edges(self) -> int:
        return int(self.meta["num_edges"])

    @property
    def num_nodes(self) -> int:
        return int(self.meta["num_nodes"])

    @property
    def num_shards(self) -> int:
        return int(self.meta["num_shards"])

    @property
    def shard_edges(self) -> list[int]:
        return list(self.meta["shard_edges"])

    @property
    def dim_edge(self) -> int:
        return int(self.meta["dim_edge"])

    @property
    def dim_node(self) -> int:
        return int(self.meta["dim_node"])

    @property
    def has_labels(self) -> bool:
        return bool(self.meta["has_labels"])

    @property
    def name(self) -> str:
        return str(self.meta.get("name", os.path.basename(self.path)))

    def _file(self, s: int, field: str) -> str:
        return os.path.join(self.path, f"shard_{s:05d}.{field}.npy")

    def shard_offsets(self) -> np.ndarray:
        """(S+1,) global edge offset of each shard boundary."""
        return np.concatenate(
            [[0], np.cumsum(self.shard_edges)]).astype(np.int64)

    def load(self, s: int, field: str, *, mmap: bool = True) -> np.ndarray:
        """One column of one shard; ``mmap=True`` returns a read-only map."""
        return np.load(self._file(s, field),
                       mmap_mode="r" if mmap else None)

    def edge_chunks(
        self, *, features: bool = False,
    ) -> Iterator[tuple]:
        """Yield (src, dst, t, eidx) per shard — id columns are materialized
        chunk-sized, ``eidx`` is the global edge index of each row.

        With ``features=True`` each tuple additionally carries the shard's
        (e_s, d_e) float32 edge-feature rows — materialized ONE shard at a
        time, so out-of-core consumers (e.g. PAC's per-device localization)
        never hold the full table."""
        offsets = self.shard_offsets()
        for s in range(self.num_shards):
            src = np.asarray(self.load(s, "src"))
            dst = np.asarray(self.load(s, "dst"))
            t = np.asarray(self.load(s, "t"))
            eidx = np.arange(offsets[s], offsets[s + 1], dtype=np.int64)
            if features:
                efeat = np.asarray(self.load(s, "efeat"), dtype=np.float32)
                yield src, dst, t, eidx, efeat
            else:
                yield src, dst, t, eidx

    def column(self, field: str) -> np.ndarray:
        """Materialize one id/label column across all shards (small: 8 bytes
        per edge — the feature table is what must stay on disk)."""
        return np.concatenate(
            [np.asarray(self.load(s, field))
             for s in range(self.num_shards)])

    def node_feat(self, *, mmap: bool = True) -> np.ndarray:
        f = os.path.join(self.path, "node_feat.npy")
        if os.path.exists(f):
            return np.load(f, mmap_mode="r" if mmap else None)
        return np.zeros((self.num_nodes, self.dim_node), dtype=np.float32)

    def as_graph(self) -> TemporalGraph:
        """Materialize the whole stream (tests / small datasets only)."""
        efeat = np.concatenate(
            [np.asarray(self.load(s, "efeat"))
             for s in range(self.num_shards)])
        return TemporalGraph(
            src=self.column("src"),
            dst=self.column("dst"),
            t=self.column("t"),
            edge_feat=efeat,
            node_feat=np.asarray(self.node_feat(mmap=False)),
            labels=self.column("label") if self.has_labels else None,
            name=self.name,
        )


def _write_meta(out_dir: str, *, name: str, num_nodes: int,
                shard_edges: list[int], dim_edge: int, dim_node: int,
                has_labels: bool) -> ShardedStream:
    meta = {
        "format": SHARD_FORMAT,
        "name": name,
        "num_nodes": int(num_nodes),
        "num_edges": int(sum(shard_edges)),
        "num_shards": len(shard_edges),
        "shard_edges": [int(e) for e in shard_edges],
        "dim_edge": int(dim_edge),
        "dim_node": int(dim_node),
        "has_labels": bool(has_labels),
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    return ShardedStream(path=out_dir, meta=meta)


def _save_shard(out_dir: str, s: int, src, dst, t, efeat, label) -> None:
    np.save(os.path.join(out_dir, f"shard_{s:05d}.src.npy"),
            np.asarray(src, np.int64))
    np.save(os.path.join(out_dir, f"shard_{s:05d}.dst.npy"),
            np.asarray(dst, np.int64))
    np.save(os.path.join(out_dir, f"shard_{s:05d}.t.npy"),
            np.asarray(t, np.float64))
    np.save(os.path.join(out_dir, f"shard_{s:05d}.efeat.npy"),
            np.asarray(efeat, np.float32))
    if label is not None:
        np.save(os.path.join(out_dir, f"shard_{s:05d}.label.npy"),
                np.asarray(label, np.int64))


def write_graph_shards(
    g: TemporalGraph,
    out_dir: str,
    *,
    shard_edges: int = DEFAULT_SHARD_EDGES,
) -> ShardedStream:
    """Shard an in-memory ``TemporalGraph`` (synthetic presets, tests)."""
    os.makedirs(out_dir, exist_ok=True)
    sizes = []
    for s, lo in enumerate(range(0, max(g.num_edges, 1), shard_edges)):
        hi = min(lo + shard_edges, g.num_edges)
        _save_shard(
            out_dir, s, g.src[lo:hi], g.dst[lo:hi], g.t[lo:hi],
            g.edge_feat[lo:hi],
            None if g.labels is None else g.labels[lo:hi])
        sizes.append(hi - lo)
    if not np.allclose(g.node_feat, 0.0):
        np.save(os.path.join(out_dir, "node_feat.npy"),
                g.node_feat.astype(np.float32))
    return _write_meta(
        out_dir, name=g.name, num_nodes=g.num_nodes, shard_edges=sizes,
        dim_edge=g.dim_edge, dim_node=g.dim_node,
        has_labels=g.labels is not None)


# ======================================================================
# JODIE CSV block reader (pandas-free, out-of-core)
# ======================================================================

def _sniff_columns(path: str, probe_rows: int = 1000) -> tuple[int, bool]:
    """(feature column count, whether a label column exists), decided from
    the widest of the first data rows — never the header, which in JODIE
    exports sometimes declares feature names the rows don't carry (and
    vice versa)."""
    cols = 0
    with open(path) as f:
        f.readline()  # header
        for _ in range(probe_rows):
            line = f.readline()
            if not line:
                break
            line = line.strip()
            if not line:
                continue
            cols = max(cols, len(line.split(",")))
    return max(cols - 4, 0), cols >= 4


def _sniff_feat_width(path: str, probe_rows: int = 1000) -> int:
    return _sniff_columns(path, probe_rows)[0]


def _parse_jodie_rows(lines: Sequence[str], n_feat: int):
    """Parse CSV data rows robustly: ragged feature columns are zero-padded
    or truncated to ``n_feat``, missing labels default to 0, integer and
    float timestamps both accepted.  Returns (users, items, t, labels,
    feats) numpy columns; blank lines are skipped."""
    users, items, ts, labels = [], [], [], []
    feats = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        parts = line.split(",")
        if len(parts) < 3:
            raise ValueError(f"unparseable JODIE row: {line!r}")
        users.append(int(float(parts[0])))
        items.append(int(float(parts[1])))
        ts.append(float(parts[2]))
        labels.append(int(float(parts[3]))
                      if len(parts) > 3 and parts[3].strip() else 0)
        row = [float(x) if x.strip() else 0.0 for x in parts[4:4 + n_feat]]
        if len(row) < n_feat:
            row.extend([0.0] * (n_feat - len(row)))
        feats.append(row)
    return (
        np.asarray(users, np.int64),
        np.asarray(items, np.int64),
        np.asarray(ts, np.float64),
        np.asarray(labels, np.int64),
        np.asarray(feats, np.float32).reshape(len(users), n_feat),
    )


def _parse_jodie_rows_fast(lines: Sequence[str], n_feat: int):
    """Vectorized parse of a WELL-FORMED block — every data row the same
    width, no empty fields — in one pass through numpy's C CSV tokenizer
    (``np.loadtxt``: the buffer is split/converted in C, no per-line Python
    loop).  Returns None when the block is ragged or irregular; the caller
    then falls back to ``_parse_jodie_rows``, whose per-line loop handles
    zero-padding, empty labels, and width mismatches row by row.  On the
    inputs the fast path accepts, both parsers produce identical columns.
    """
    try:
        a = np.loadtxt(io.StringIO("".join(lines)), delimiter=",",
                       comments=None, ndmin=2, dtype=np.float64)
    except ValueError:
        return None
    if a.size == 0 or a.shape[1] < 3:
        return None                       # <3 columns: let the fallback
    w = a.shape[1]                        # raise its diagnostic
    # nan/inf in the integer-bound columns (ids, label) would cast to
    # INT64_MIN silently; the fallback raises the proper diagnostic
    if not np.isfinite(a[:, :2]).all() or \
            (w > 3 and not np.isfinite(a[:, 3]).all()):
        return None
    n = len(a)
    feats = a[:, 4:4 + n_feat].astype(np.float32)
    if feats.shape[1] < n_feat:
        feats = np.concatenate(
            [feats, np.zeros((n, n_feat - feats.shape[1]), np.float32)],
            axis=1)
    return (
        a[:, 0].astype(np.int64),
        a[:, 1].astype(np.int64),
        a[:, 2],
        a[:, 3].astype(np.int64) if w > 3 else np.zeros(n, np.int64),
        feats.reshape(n, n_feat),
    )


def iter_jodie_blocks(
    path: str,
    *,
    block_rows: int = DEFAULT_SHARD_EDGES,
    n_feat: Optional[int] = None,
    fast: bool = True,
) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                    np.ndarray]]:
    """Stream a JODIE ``ml_<name>.csv`` as (users, items, t, labels, feats)
    blocks of ``block_rows`` rows — at no point is the whole file in RAM.

    With ``fast`` (the default) each well-formed block is parsed in one
    vectorized numpy pass; blocks with ragged/empty fields fall back to the
    robust per-line parser (results are identical either way —
    ``fast=False`` keeps the loop-only path for benchmarking/debugging).
    """
    if n_feat is None:
        n_feat = _sniff_feat_width(path)
    with open(path) as f:
        f.readline()  # header
        while True:
            lines = []
            for _ in range(block_rows):
                line = f.readline()
                if not line:
                    break
                lines.append(line)
            if not lines:
                return
            block = _parse_jodie_rows_fast(lines, n_feat) if fast else None
            if block is None:
                block = _parse_jodie_rows(lines, n_feat)
            if len(block[0]):
                yield block


def write_jodie_shards(
    csv_path: str,
    out_dir: str,
    *,
    shard_edges: int = DEFAULT_SHARD_EDGES,
    d_n: int = 172,
    name: Optional[str] = None,
) -> ShardedStream:
    """Chunked JODIE CSV -> ``tig-shards-v1`` ingestion.

    One pass over the file writing one shard at a time; item ids are stored
    raw during the pass and offset to live after user ids (the bipartite
    convention) by an in-place fix-up once the user count is known.  The
    stream must already be time-sorted (JODIE exports are); out-of-order
    rows raise rather than silently reordering a file that may not fit in
    memory.
    """
    os.makedirs(out_dir, exist_ok=True)
    n_feat, has_labels = _sniff_columns(csv_path)
    sizes: list[int] = []
    max_user = -1
    max_item = -1
    last_t = -np.inf
    s = 0
    for users, items, t, labels, feats in iter_jodie_blocks(
            csv_path, block_rows=shard_edges, n_feat=n_feat):
        if len(t) and (t[0] < last_t or np.any(np.diff(t) < 0)):
            raise ValueError(
                f"{csv_path}: timestamps are not non-decreasing; "
                "sort the export before sharding")
        last_t = float(t[-1])
        max_user = max(max_user, int(users.max()))
        max_item = max(max_item, int(items.max()))
        if feats.shape[1] == 0:
            feats = np.zeros((len(users), 1), dtype=np.float32)
        _save_shard(out_dir, s, users, items, t, feats,
                    labels if has_labels else None)
        sizes.append(len(users))
        s += 1
    if not sizes:
        raise ValueError(f"{csv_path}: no data rows")
    # fix-up pass: dst = num_users + item  (shard-sized memory at a time)
    nu = max_user + 1
    for k in range(s):
        f = os.path.join(out_dir, f"shard_{k:05d}.dst.npy")
        arr = np.load(f)
        np.save(f, arr + nu)
    return _write_meta(
        out_dir, name=name or os.path.basename(csv_path),
        num_nodes=nu + max_item + 1, shard_edges=sizes,
        dim_edge=max(n_feat, 1), dim_node=d_n,
        # a 3-column export (user,item,t) must not fabricate all-zero
        # labels for downstream node classification
        has_labels=has_labels)


# ======================================================================
# chunked device staging
# ======================================================================

def stage_device_tables(shards: ShardedStream) -> dict:
    """Device feature tables from shards WITHOUT a host-side full copy.

    The (E+1, d_e) edge-feature table (trailing zero dump row for -1
    neighbor remapping, as ``batching.make_tables``) is assembled on device:
    a donated buffer is updated shard by shard, so host memory peaks at one
    shard of rows instead of the full table.  Node features are all-zeros
    unless the stream carries a ``node_feat.npy`` (then staged the same
    way, row-chunked).
    """
    import jax
    import jax.numpy as jnp

    update = jax.jit(
        lambda buf, chunk, lo: jax.lax.dynamic_update_slice(
            buf, chunk, (lo, jnp.int32(0))),
        donate_argnums=(0,))

    efeat = jnp.zeros((shards.num_edges + 1, shards.dim_edge), jnp.float32)
    lo = 0
    for s in range(shards.num_shards):
        chunk = np.asarray(shards.load(s, "efeat"), dtype=np.float32)
        efeat = update(efeat, jnp.asarray(chunk),
                       jnp.asarray(lo, jnp.int32))
        lo += len(chunk)

    n = shards.num_nodes
    nf_path = os.path.join(shards.path, "node_feat.npy")
    nfeat = jnp.zeros((n + 1, shards.dim_node), jnp.float32)
    if os.path.exists(nf_path):
        nf = np.load(nf_path, mmap_mode="r")
        step = max(1, DEFAULT_SHARD_EDGES // max(shards.dim_node, 1))
        for lo_ in range(0, n, step):
            chunk = np.asarray(nf[lo_: lo_ + step], dtype=np.float32)
            nfeat = update(nfeat, jnp.asarray(chunk),
                           jnp.asarray(lo_, jnp.int32))
    return {"efeat": efeat, "nfeat": nfeat}


# ======================================================================
# multi-process (pod) staging
# ======================================================================

def stage_partitioned(local_rows: np.ndarray, mesh, n_global: int):
    """Assemble a "part"-sharded global array from THIS process's rows.

    ``local_rows`` holds only the rows of the caller's local devices
    (contiguous on the mesh's "part" axis — ``launch.mesh.make_tig_mesh``
    ordering); each process calls this with its own slice and jax stitches
    the global (n_global, ...) array without any process ever holding the
    full buffer — the olmax per-process-slice idiom, with the gather left
    implicit in the array's sharding instead of an eager ``all_gather``.
    Host bytes and H2D per process stay O(local devices).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    local_rows = np.ascontiguousarray(local_rows)
    spec = PartitionSpec("part", *([None] * (local_rows.ndim - 1)))
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), local_rows,
        (n_global,) + local_rows.shape[1:])


def stage_replicated(x, mesh):
    """Stage ``x`` fully replicated across every device of ``mesh``
    (including non-addressable ones in a multi-process run)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(np.asarray(x), NamedSharding(mesh,
                                                       PartitionSpec()))


# ======================================================================
# double-buffered epoch prefetch
# ======================================================================

_STOP = object()     # worker shutdown sentinel


class EpochPrefetcher:
    """Depth-configurable epoch pipeline: host planning and device staging
    run ahead of the consumer on ONE persistent worker thread.

    ``build_fn(epoch)`` calls happen in strict submission order on the
    single worker (stateful planning RNGs see the serial call sequence),
    so results are bit-identical to inline planning for ANY ``depth``.
    ``to_device`` (e.g. ``jax.device_put`` / ``jnp.asarray`` mapping) also
    runs on the worker, behind a SINGLE async staging slot: up to ``depth``
    host plans may be in flight, but at most one staged-but-unclaimed plan
    holds device buffers — the next ``to_device`` starts only once the
    consumer claims the previous one via ``get``.  Device memory stays
    bounded at one epoch's plan (the double-buffer invariant) while deeper
    pipelines absorb plan-time variance on the host side.  numpy and jax
    release the GIL for bulk work, so planning/staging genuinely overlap
    compute.

        with EpochPrefetcher(build, epochs, to_device=stage, depth=2) as pf:
            for ep in range(epochs):
                plan = pf.get(ep)   # plan e ready; e+1, e+2 in flight
                ... run device epoch ...

    ``get(e)`` retrieves plan e and refills the pipeline to ``depth``
    epochs in flight.  Exceptions in the worker surface at the
    corresponding ``get`` (and cancel the pipeline: no further epoch is
    submitted).  ``depth=0`` — or ``enabled=False`` — disables the worker
    entirely and builds inline.

    Also a context manager: ``with EpochPrefetcher(...) as pf:`` closes
    the pipeline on ANY exit — including an exception mid-epoch — so the
    worker thread is joined instead of leaking past the failure.
    """

    def __init__(
        self,
        build_fn: Callable[[int], object],
        num_epochs: int,
        *,
        to_device: Optional[Callable[[object], object]] = None,
        enabled: bool = True,
        depth: int = 1,
    ):
        if depth < 0:
            raise ValueError(f"depth={depth}: expected >= 0")
        self._build = build_fn
        self._to_device = to_device
        self._n = num_epochs
        self._depth = depth if enabled else 0
        self._enabled = self._depth > 0
        self._inbox: queue.Queue = queue.Queue()
        self._futures: dict[int, queue.Queue] = {}
        self._slot = threading.Semaphore(1)     # the device staging slot
        self._closing = threading.Event()
        self._worker: Optional[threading.Thread] = None

    def _worker_loop(self) -> None:
        import jax

        while True:
            job = self._inbox.get()
            if job is _STOP:
                return
            epoch, out = job
            try:
                with jax.profiler.TraceAnnotation("tig.plan"):
                    plan = self._build(epoch)
                if self._to_device is not None:
                    self._slot.acquire()
                    if self._closing.is_set():
                        # close() raced us awake: the result would be
                        # dropped anyway — skip staging, drain to the stop
                        # sentinel
                        self._slot.release()
                        continue
                    try:
                        with jax.profiler.TraceAnnotation("tig.stage"):
                            plan = self._to_device(plan)
                    except BaseException:
                        self._slot.release()
                        raise
                out.put((True, plan))
            except BaseException as exc:  # noqa: BLE001 — reraised at get()
                out.put((False, exc))

    def _submit(self, epoch: int) -> None:
        if epoch < 0 or epoch >= self._n or epoch in self._futures:
            return
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._worker_loop, daemon=True)
            self._worker.start()
        out: queue.Queue = queue.Queue(maxsize=1)
        self._futures[epoch] = out
        self._inbox.put((epoch, out))

    def _cancel(self) -> None:
        """Drop every not-yet-claimed submission: no further builds start
        (jobs the worker already began complete into orphaned queues)."""
        self._n = 0
        self._futures.clear()
        while True:
            try:
                self._inbox.get_nowait()
            except queue.Empty:
                return

    def close(self) -> None:
        """Stop the pipeline early: pending submissions are dropped, the
        persistent worker is unparked (the staging slot is released so a
        worker waiting to stage cannot deadlock the join) and JOINED in
        bounded time — it finishes at most the job it already started,
        then exits on the stop sentinel.  In-flight results are dropped
        for GC instead of staying pinned (a full epoch plan, possibly on
        device) while the caller moves on (e.g. patience-based early stop
        or an exception unwinding the training loop)."""
        self._closing.set()
        self._cancel()
        worker, self._worker = self._worker, None
        if worker is not None:
            self._slot.release()
            self._inbox.put(_STOP)
            worker.join()

    def __enter__(self) -> "EpochPrefetcher":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def get(self, epoch: int):
        """Block until the plan for ``epoch`` is ready (building it inline
        when the pipeline is disabled) and refill the pipeline to
        ``depth`` epochs in flight.  In a profiler trace the wait is the
        span ``tig.plan_wait``; the worker's builds and stagings are
        ``tig.plan`` and ``tig.stage``."""
        import jax

        if not self._enabled:
            with jax.profiler.TraceAnnotation("tig.plan"):
                plan = self._build(epoch)
            if self._to_device is not None:
                with jax.profiler.TraceAnnotation("tig.stage"):
                    plan = self._to_device(plan)
            return plan
        self._submit(epoch)
        out = self._futures.pop(epoch)
        with jax.profiler.TraceAnnotation("tig.plan_wait"):
            ok, plan = out.get()
        if not ok:
            self._cancel()      # the pipeline is poisoned past this epoch
            raise plan
        if self._to_device is not None:
            self._slot.release()    # claimed: free the staging slot
        for nxt in range(epoch + 1, epoch + 1 + self._depth):
            self._submit(nxt)
        return plan
