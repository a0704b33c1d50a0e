"""Device-side epoch planning tests (PR 6).

Three layers: (1) the T-CSR samplers — the pure-jnp oracle
(``kernels.ref.sample_ref``) and the Pallas kernel body on the interpret
backend — must match ``ChronoNeighborIndex.sample`` bit-for-bit on crafted
edge cases (degree-0 nodes, every-neighbor-newer-than-the-boundary,
K larger than any degree, out-of-core builds with empty chunks);
(2) the trainers — ``train_single`` / ``train_sharded`` / ``pac_train``
with ``plan="device"`` must be bit-identical to host planning (losses,
params, memory, metrics); (3) the supporting utilities — the shared LRU
(``tig.cache.lru_get``), the prefetcher context manager, and the roofline
H2D model's host-vs-device ordering.
"""

import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.neighbor_sample import (
    CHUNK,
    export_length,
    neighbor_sample_fwd,
)
from repro.kernels.ref import sample_ref
from repro.roofline.kernel_bytes import epoch_plan_bytes, sample_bytes
from repro.tig.cache import lru_get
from repro.tig.data import synthetic_tig
from repro.tig.models import TIGConfig
from repro.tig.sampler import ChronoNeighborIndex
from repro.tig.stream import EpochPrefetcher, write_graph_shards
from repro.tig.train import train_single, train_sharded

CFG = TIGConfig(dim=16, dim_time=8, dim_edge=16, dim_node=16,
                num_neighbors=4, batch_size=128)


def _device_sample(index, nodes, batch_of, *, backend):
    tcsr = {k: jnp.asarray(v) for k, v in index.device_export().items()}
    nodes = jnp.asarray(nodes, jnp.int32)
    batch_of = jnp.asarray(batch_of, jnp.int32)
    if backend == "interpret":
        out = neighbor_sample_fwd(
            tcsr["indptr"], tcsr["nbr"], tcsr["t"], tcsr["eidx"],
            tcsr["bat"], nodes, batch_of, k=index.k, interpret=True)
    else:
        out = ops.neighbor_sample(tcsr, nodes, batch_of, index.k,
                                  backend=backend)
    return tuple(np.asarray(x) for x in out)


def _assert_matches_host(index, nodes, batch_of):
    """Both device samplers == the host index, including the f64->f32 cast
    the export applies to times (the engine grids are f32 either way)."""
    hb, ht, he = index.sample(np.asarray(nodes, np.int64),
                              np.asarray(batch_of))
    for backend in ("xla", "interpret"):
        db, dt, de = _device_sample(index, nodes, batch_of, backend=backend)
        np.testing.assert_array_equal(db, hb, err_msg=backend)
        np.testing.assert_array_equal(de, he, err_msg=backend)
        np.testing.assert_array_equal(dt, ht.astype(np.float32),
                                      err_msg=backend)


# ------------------------------------------------------ T-CSR edge cases


def _crafted_index(k=4, batch_size=2):
    """8 nodes; node 7 has degree 0; node 0 appears only in the LAST batch
    (all neighbors newer than any earlier boundary); node 1 has degree 1
    (< K); node 2 is a hub with degree > K."""
    src = np.array([2, 2, 2, 2, 2, 1, 3, 0])
    dst = np.array([3, 4, 5, 6, 4, 2, 2, 2])
    t = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0])
    eidx = np.arange(len(src))
    return ChronoNeighborIndex(src, dst, t, eidx, 8, k, batch_size)


def test_sampler_edge_cases_match_host():
    index = _crafted_index()
    nodes = np.array([0, 1, 2, 3, 4, 5, 6, 7, 0, 2])
    for b in range(index.num_batches):
        _assert_matches_host(index, nodes, b)
    # per-row batch indices (the engine's fused 3B-row call shape)
    per_row = np.arange(len(nodes)) % index.num_batches
    _assert_matches_host(index, nodes, per_row)


@pytest.mark.parametrize("k,depth", [(10, 1), (16, 2)],
                         ids=["tig", "tig_mxu"])
def test_sampler_paper_widths_kernel_matches_oracle(k, depth):
    """The chunked kernel at the model's K and layer count (L * 3B query
    rows, per-row batch index and window) equals the oracle, on a stream
    whose event arrays span several 1024-event chunks and hub segments
    longer than one chunk."""
    from repro.tig.data import synthetic_tig

    g = synthetic_tig("small", seed=3)
    b = 200
    index = ChronoNeighborIndex(g.src, g.dst, g.t, np.arange(g.num_edges),
                                g.num_nodes, k, b)
    tcsr = {kk: jnp.asarray(v)
            for kk, v in index.device_export(depth=depth).items()}
    assert tcsr["nbr"].shape[0] % CHUNK == 0
    rng = np.random.default_rng(0)
    rows = 3 * b * depth
    nodes = jnp.asarray(rng.integers(0, g.num_nodes, rows), jnp.int32)
    batch_of = jnp.asarray(rng.integers(0, index.num_batches, rows),
                           jnp.int32)
    win = jnp.asarray(np.repeat(np.arange(depth - 1, -1, -1), 3 * b),
                      jnp.int32)
    args = (tcsr["indptr"], tcsr["nbr"], tcsr["t"], tcsr["eidx"],
            tcsr["bat"], nodes, batch_of)
    want = ref.sample_ref(*args, k, win)
    got = neighbor_sample_fwd(*args, k=k, interpret=True, window=win)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_sampler_degree_zero_and_all_newer_rows_are_fill():
    index = _crafted_index()
    for backend in ("xla", "interpret"):
        ids, tms, eix = _device_sample(index, [7, 0], 0, backend=backend)
        np.testing.assert_array_equal(ids, -1)      # degree 0 / all newer
        np.testing.assert_array_equal(eix, -1)
        np.testing.assert_array_equal(tms, -1.0)


def test_sampler_kernel_refuses_unaligned_events():
    """The kernel DMAs whole chunks: event arrays that are not
    ``export_length`` long are refused, not padded in the step."""
    tcsr = _crafted_index().device_export()
    assert len(tcsr["nbr"]) == export_length(16, 4)
    cut = {k: jnp.asarray(v[:-1] if k != "indptr" else v)
           for k, v in tcsr.items()}
    with pytest.raises(ValueError, match="export_length"):
        neighbor_sample_fwd(cut["indptr"], cut["nbr"], cut["t"], cut["eidx"],
                            cut["bat"], jnp.arange(8, dtype=jnp.int32),
                            jnp.int32(1), k=4, interpret=True)


def test_sampler_k_larger_than_any_degree():
    src = np.array([0, 1]); dst = np.array([1, 2])
    t = np.array([1.0, 2.0]); eidx = np.arange(2)
    index = ChronoNeighborIndex(src, dst, t, eidx, 3, 8, 1)
    nodes = np.array([0, 1, 2])
    for b in range(index.num_batches):
        _assert_matches_host(index, nodes, b)


def test_sampler_empty_stream():
    empty = np.array([], dtype=np.int64)
    index = ChronoNeighborIndex(empty, empty, empty.astype(float), empty,
                                5, 3, 4)
    _assert_matches_host(index, np.array([0, 2, 4]), 0)


def test_sampler_from_chunks_with_empty_shard():
    src = np.array([2, 2, 2, 2, 2, 1, 3, 0])
    dst = np.array([3, 4, 5, 6, 4, 2, 2, 2])
    t = np.arange(1.0, 9.0)
    eidx = np.arange(8)
    one_shot = ChronoNeighborIndex(src, dst, t, eidx, 8, 4, 2)
    empty = np.array([], dtype=np.int64)
    chunks = [
        (src[:3], dst[:3], t[:3], eidx[:3]),
        (empty, empty, empty.astype(float), empty),      # empty shard
        (src[3:], dst[3:], t[3:], eidx[3:]),
    ]
    chunked = ChronoNeighborIndex.from_chunks(chunks, 8, 4, 2)
    for key, a in one_shot.device_export().items():
        np.testing.assert_array_equal(chunked.device_export()[key], a,
                                      err_msg=key)
    nodes = np.arange(8)
    for b in range(chunked.num_batches):
        _assert_matches_host(chunked, nodes, b)


def test_device_export_composes_by_offset():
    """Two exports concatenated with offset indptr (the PAC flat layout)
    sample identically to each export alone."""
    ia, ib = _crafted_index(), _crafted_index(k=4, batch_size=2)
    ea, eb = ia.device_export(), ib.device_export()
    base = np.int32(len(ea["nbr"]))
    flat = {k: np.concatenate([ea[k], eb[k]])
            for k in ("nbr", "t", "eidx", "bat")}
    ref_ids, ref_t, ref_e = sample_ref(
        ea["indptr"], ea["nbr"], ea["t"], ea["eidx"], ea["bat"],
        jnp.arange(8, dtype=jnp.int32), jnp.int32(1), 4)
    ids, tms, eix = sample_ref(
        eb["indptr"] + base, flat["nbr"], flat["t"], flat["eidx"],
        flat["bat"], jnp.arange(8, dtype=jnp.int32), jnp.int32(1), 4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    np.testing.assert_array_equal(np.asarray(tms), np.asarray(ref_t))
    np.testing.assert_array_equal(np.asarray(eix), np.asarray(ref_e))


# --------------------------------------------- trainer host/device parity


def _tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def test_train_single_device_plan_bit_identical():
    g = synthetic_tig("tiny", seed=3)
    a = train_single(g, CFG, epochs=2, seed=0, plan="host")
    b = train_single(g, CFG, epochs=2, seed=0, plan="device")
    assert a.losses == b.losses
    assert a.val_ap == b.val_ap and a.test_ap == b.test_ap
    assert (a.test_ap_inductive == b.test_ap_inductive
            or (np.isnan(a.test_ap_inductive)
                and np.isnan(b.test_ap_inductive)))
    _tree_equal(a.params, b.params)
    _tree_equal(a.state, b.state)


def test_train_sharded_device_plan_bit_identical(tmp_path):
    g = synthetic_tig("tiny", seed=3)
    sh = write_graph_shards(g, str(tmp_path / "sh"), shard_edges=313)
    kw = dict(epochs=2, protocol=True, patience=2, seed=0)
    a = train_sharded(sh, CFG, plan="host", **kw)
    b = train_sharded(sh, CFG, plan="device", **kw)
    assert a.losses == b.losses and a.val_curve == b.val_curve
    assert a.best_epoch == b.best_epoch
    for key, v in a.metrics.items():
        w = b.metrics[key]
        assert (np.isnan(v) and np.isnan(w)) or v == w, key


def test_pac_train_device_plan_bit_identical():
    from repro.core import sep_partition
    from repro.tig.distributed import pac_train
    from repro.tig.graph import chronological_split

    cfg = TIGConfig(flavor="tgn", dim=16, dim_time=8, dim_edge=16,
                    dim_node=16, num_neighbors=4, batch_size=50)
    g = synthetic_tig("tiny", seed=0)
    train_g, _, _, _ = chronological_split(g)
    part = sep_partition(train_g.src, train_g.dst, train_g.t,
                         g.num_nodes, 4, k=0.05)
    kw = dict(num_devices=4, epochs=2, lr=2e-3, shuffle_parts=False)
    a = pac_train(train_g, part, cfg, plan="host", **kw)
    b = pac_train(train_g, part, cfg, plan="device", **kw)
    for la, lb in zip(a.losses, b.losses):
        np.testing.assert_array_equal(la, lb)
    _tree_equal(a.params, b.params)
    _tree_equal(a.memory_states, b.memory_states)


def test_pac_train_rejects_device_plan_with_host_replay():
    from repro.tig.distributed import plan_epoch

    g = synthetic_tig("tiny", seed=0)
    with pytest.raises(ValueError, match="host_replay"):
        plan_epoch(g, [np.arange(g.num_nodes)], np.zeros(0, np.int64),
                   CFG, np.random.default_rng(0), host_replay=True,
                   plan="device")


def test_build_batch_program_plan_validation():
    from repro.tig.batching import build_batch_program
    from repro.tig.train import graph_as_stream

    g = synthetic_tig("tiny", seed=0)
    stream, _ = graph_as_stream(g)
    with pytest.raises(ValueError, match="plan="):
        build_batch_program(stream, CFG, np.random.default_rng(0),
                            plan="gpu")
    batches, _ = build_batch_program(stream, CFG, np.random.default_rng(0),
                                     plan="device")
    assert not any(k.startswith("nbr") for k in batches)
    assert {"src", "dst", "neg", "t", "eidx", "valid"} <= set(batches)


# ----------------------------------------------------------- lru_get


def test_lru_get_builds_once_and_moves_hits_to_back():
    cache, built = {}, []

    def make(v):
        return lambda: built.append(v) or v

    for v in ("a", "b", "c"):
        assert lru_get(cache, v, 3, make(v)) == v
    assert lru_get(cache, "a", 3, make("a")) == "a"      # hit, no rebuild
    assert built == ["a", "b", "c"]
    # "b" is now least-recently-used; inserting "d" evicts it
    lru_get(cache, "d", 3, make("d"))
    assert list(cache) == ["c", "a", "d"]
    lru_get(cache, "b", 3, make("b"))
    assert built == ["a", "b", "c", "d", "b"]
    assert list(cache) == ["a", "d", "b"]


def test_lru_get_max_size_one():
    cache = {}
    assert lru_get(cache, 1, 1, lambda: "x") == "x"
    assert lru_get(cache, 2, 1, lambda: "y") == "y"
    assert list(cache) == [2]


# ------------------------------------------- prefetcher context manager


def test_prefetcher_context_manager_joins_on_exception():
    started = threading.Event()
    release = threading.Event()
    workers = []

    def build(i):
        if i == 1:                      # the in-flight prefetched epoch
            workers.append(threading.current_thread())
            started.set()
            release.wait(timeout=10)
        return i

    pf = EpochPrefetcher(build, 4, enabled=True)
    with pytest.raises(RuntimeError, match="boom"):
        with pf as entered:
            assert entered is pf
            assert pf.get(0) == 0       # kicks off epoch 1 on the worker
            assert started.wait(timeout=10)
            release.set()
            raise RuntimeError("boom")
    # __exit__ must have joined the worker and dropped pending epochs
    assert pf._worker is None and pf._futures == {}
    assert workers and not workers[0].is_alive()


def test_prefetcher_context_manager_plain_use():
    with EpochPrefetcher(lambda i: i * i, 3, enabled=True) as pf:
        assert [pf.get(i) for i in range(3)] == [0, 1, 4]
    assert pf._worker is None and pf._futures == {}


# ------------------------------------------------------- roofline model


def test_epoch_plan_bytes_device_strictly_below_host():
    for steps, batch, k, n, ev in ((118, 100, 5, 9227, 2 * 11_000),
                                   (1000, 200, 10, 100_000, 2_000_000)):
        m = epoch_plan_bytes(steps, batch, k, n, ev)
        assert m["device"] < m["host"]
        assert m["host"] == sum(m["host_detail"].values())
        assert m["device"] == sum(m["device_detail"].values())
        # records are shipped by BOTH plans; only the grids/T-CSR differ
        assert m["host_detail"]["records"] == m["device_detail"]["records"]


def test_sample_bytes_itemization():
    ob = sample_bytes(rows=300, k=5, total_events=22_000)
    assert ob.total == ob.read_bytes + ob.write_bytes > 0
    assert set(ob.writes) == {"ids", "times", "eidx"}
    # probe traffic grows with log2(events), window traffic with K
    assert sample_bytes(300, 5, 1 << 20).reads["bisect_probes"] > \
        ob.reads["bisect_probes"]
    assert sample_bytes(300, 10, 22_000).reads["nbr_window"] == \
        2 * ob.reads["nbr_window"]
