"""Row-local memory gradient.

The node-memory table is data with no gradient: ``flush_pending`` hands the
step its R = 2B updated rows, and every memory read inside the step is
served from them where the id is a live pending id.  These tests hold that
form to the dense-table formulation it replaced (a differentiable (N+1, d)
table written by the scatter mean, then plain gathers), check that the
step's backward pass builds no value with one row per node, and count the
reads the fresh rows serve.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.tig import models
from repro.tig.models import TIGConfig, init_params, step_loss
from repro.tig.modules import gru, mlp, rnn
from repro.tig.train import make_eval_step

TOL = 1e-5


# ----------------------------------------------------- dense-table oracle

def dense_flush(params, cfg, state):
    """The flush as a differentiable (N+1, d) table: scatter mean over
    (N+1)-row sums and counts (``ref.flush_ref`` through the fused kernel's
    oracle VJP on the GRU + Pallas path), then a scatter of the new rows."""
    n_dump = state["mem"].shape[0] - 1
    ids, raw, ts = state["pend_ids"], state["pend_raw"], state["pend_t"]
    live = ids < n_dump
    msg = mlp(params["msg"], raw) if cfg.message_fn == "mlp" else raw
    if cfg.updater == "gru" and cfg.use_pallas:
        p = params["upd"]
        mem, last, mbar = ops.fused_flush(
            ids, msg, ts, state["mem"], state["last"],
            p["xz"]["w"], p["hz"]["w"], p["xz"]["b"], p["hz"]["b"],
            backend=cfg.kernel_backend)
    else:
        sums = jnp.zeros((n_dump + 1, msg.shape[-1])).at[ids].add(
            jnp.where(live[:, None], msg, 0.0))
        cnt = jnp.zeros((n_dump + 1,)).at[ids].add(live.astype(jnp.float32))
        mbar = (sums / jnp.clip(cnt, 1.0)[:, None])[ids]
        upd_fn = gru if cfg.updater == "gru" else rnn
        s_new = upd_fn(params["upd"], mbar, state["mem"][ids])
        mem = state["mem"].at[ids].set(s_new).at[n_dump].set(0.0)
        last = state["last"].at[ids].max(jnp.where(live, ts, 0.0))
        last = last.at[n_dump].set(0.0)
    mem2 = state["mem2"]
    if cfg.flavor == "tige":
        s2_new = rnn(params["upd2"], mbar, state["mem2"][ids])
        mem2 = mem2.at[ids].set(s2_new).at[n_dump].set(0.0)
    return {"mem": mem, "mem2": mem2, "last": last,
            "pend_ids": jnp.full(ids.shape, n_dump, jnp.int32),
            "pend_raw": jnp.zeros_like(raw),
            "pend_t": jnp.zeros_like(ts)}, {"ids": ids}


def dense_read(cfg, state, fresh, q):
    if cfg.flavor == "tige":
        return 0.5 * (state["mem"][q] + state["mem2"][q])
    return state["mem"][q]


def dense_step_loss(monkeypatch, *args):
    with monkeypatch.context() as m:
        m.setattr(models, "flush_pending", dense_flush)
        m.setattr(models, "_read_memory", dense_read)
        return step_loss(*args)


# ------------------------------------------------------------ a hand case

def make_case(flavor, backend, n_layers, n=30, b=8, k=4, n_edges=50,
              seed=0):
    """A step whose pending ids repeat and include the dump row, whose
    queries and neighbors hit pending ids, and whose batch has padding
    ids and invalid rows."""
    cfg = TIGConfig(flavor=flavor, dim=16, dim_time=8, dim_edge=12,
                    dim_node=10, num_neighbors=k, batch_size=b,
                    message_fn="mlp", dim_msg=24, n_layers=n_layers,
                    use_pallas=backend != "xla",
                    kernel_backend="interpret" if backend != "xla"
                    else "auto")
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    params = init_params(ks[0], cfg)
    # pending ids: duplicates (3 and 7 twice, 11 three times) and two dead
    # rows on the dump row
    pend = np.array([3, 7, 3, 11, 5, 11, 9, 7, 11, 2, 14, 6, 13, 1, n, n],
                    np.int32)
    assert pend.shape == (2 * b,)
    state = {
        "mem": jax.random.normal(ks[1], (n + 1, cfg.dim)).at[n].set(0.0),
        "mem2": jax.random.normal(ks[2], (n + 1, cfg.dim)).at[n].set(0.0),
        "last": jax.random.uniform(ks[3], (n + 1,)).at[n].set(0.0),
        "pend_ids": jnp.asarray(pend),
        "pend_raw": jax.random.normal(ks[4], (2 * b, cfg.raw_msg_dim)),
        "pend_t": 1.0 + jax.random.uniform(ks[5], (2 * b,)),
    }
    valid = np.ones(b, bool)
    valid[[2, 6]] = False
    batch = {
        "src": np.array([3, 20, 11, 4, 7, -1, 22, 1], np.int64),
        "dst": np.array([16, 5, 17, 18, 11, 19, 9, 23], np.int64),
        "neg": np.array([24, 25, 3, 26, 27, 28, 2, 29], np.int64),
        "t": np.linspace(2.0, 3.0, b).astype(np.float32),
        "eidx": np.arange(b, dtype=np.int64),
        "valid": valid,
    }
    rng = np.random.default_rng(seed)
    shape = (b, k) if n_layers == 1 else (n_layers, b, k)
    for role in ("src", "dst", "neg"):
        # neighbors drawn so that about half hit pending ids, a third of
        # the slots empty
        nb = rng.choice(np.concatenate([pend[:14], np.arange(15, n)]),
                        size=shape)
        nb = np.where(rng.random(shape) < 0.3, -1, nb)
        batch[f"nbr_{role}"] = nb.astype(np.int64)
        batch[f"nbrt_{role}"] = rng.uniform(0.0, 2.0, shape).astype(
            np.float32)
        batch[f"nbre_{role}"] = np.where(
            nb >= 0, rng.integers(0, n_edges, shape), -1)
    batch = {key: jnp.asarray(v) for key, v in batch.items()}
    tables = {"efeat": jax.random.normal(ks[6], (n_edges + 1, cfg.dim_edge)),
              "nfeat": jax.random.normal(ks[7], (n + 1, cfg.dim_node))}
    return cfg, params, state, batch, tables


CASES = [(f, be, nl) for f in models.FLAVORS for be in ("xla", "interpret")
         for nl in ((1, 2) if f in ("tgn", "tige") else (1,))]


@pytest.mark.parametrize("flavor,backend,n_layers", CASES)
def test_step_grad_matches_dense_table(monkeypatch, flavor, backend,
                                       n_layers):
    cfg, params, state, batch, tables = make_case(flavor, backend, n_layers)

    def grads(step):
        def loss(p):
            l, (s, aux) = step(p, state, batch, tables, cfg)
            return l, (s, aux)
        return jax.grad(loss, has_aux=True)(params)

    got, (state_got, aux) = grads(step_loss)
    want, (state_want, _) = grads(
        lambda *a: dense_step_loss(monkeypatch, *a))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=TOL,
                                   rtol=TOL,
                                   err_msg=jax.tree_util.keystr(path))
    # the leaves that the flush feeds get a gradient
    for g in jax.tree.leaves((got["upd"], got["msg"]["l0"])):
        assert float(jnp.abs(g).sum()) > 0
    # the table write is the one the dense formulation makes
    for key in state_want:
        np.testing.assert_array_equal(
            np.asarray(state_got[key]), np.asarray(state_want[key]),
            err_msg=key)
    assert 0.0 < float(aux["fresh_read_share"]) < 1.0


# ------------------------------------------ no value with one row per node

def _avals(jaxpr):
    """Every abstract value of a jaxpr and of the jaxprs nested in it."""
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


def _node_rows_in_vjp(loss, params, n1):
    """Shapes with an axis of ``n1`` rows in the VJP of ``loss``."""
    _, vjp = jax.vjp(loss, params)
    closed = jax.make_jaxpr(vjp)(jnp.ones(()))
    shapes = [a.shape for a in _avals(closed.jaxpr) if hasattr(a, "shape")]
    shapes += [np.shape(c) for c in closed.consts]
    return [s for s in shapes if n1 in s]


@pytest.mark.parametrize("flavor,backend,n_layers", CASES)
def test_step_vjp_holds_no_node_table(monkeypatch, flavor, backend,
                                      n_layers):
    # N + 1 = 1009 is prime and shared by no other dimension
    cfg, params, state, batch, tables = make_case(flavor, backend, n_layers,
                                                  n=1008)
    n1 = state["mem"].shape[0]
    assert n1 == 1009

    def one_step(p):
        return step_loss(p, state, batch, tables, cfg)[0]

    def two_steps(p):
        # across steps the gradient reaches the first step's parameters
        # through the stashed messages, never through the written table
        l1, (s, _) = step_loss(p, state, batch, tables, cfg)
        return l1 + step_loss(p, s, batch, tables, cfg)[0]

    assert _node_rows_in_vjp(one_step, params, n1) == []
    assert _node_rows_in_vjp(two_steps, params, n1) == []
    # the guard sees the dense formulation's tables
    with monkeypatch.context() as m:
        m.setattr(models, "flush_pending", dense_flush)
        m.setattr(models, "_read_memory", dense_read)
        assert _node_rows_in_vjp(one_step, params, n1)


# ------------------------------------------------------ fresh_read_share

@pytest.mark.parametrize("flavor,want", [("tgn", 6 / 12), ("jodie", 4 / 9)])
def test_fresh_read_share_counts_hits(flavor, want):
    n, b, k = 40, 4, 2
    cfg = TIGConfig(flavor=flavor, dim=8, dim_time=4, dim_edge=4,
                    dim_node=4, num_neighbors=k, batch_size=b)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = models.init_state(cfg, n)
    tables = {"efeat": jnp.zeros((9, 4)), "nfeat": jnp.zeros((n + 1, 4))}

    def batch(src, dst, neg, valid, nbr_src):
        out = {"src": src, "dst": dst, "neg": neg, "valid": valid,
               "t": np.full(b, 1.0, np.float32), "eidx": np.arange(b)}
        for role in ("src", "dst", "neg"):
            nb = nbr_src if role == "src" else np.full((b, k), -1)
            out[f"nbr_{role}"] = nb
            out[f"nbrt_{role}"] = np.zeros((b, k), np.float32)
            out[f"nbre_{role}"] = np.where(nb >= 0, 0, -1)
        return {key: jnp.asarray(v) for key, v in out.items()}

    none = np.full((b, k), -1)
    # step 1 stashes src 1, 2, 3 and dst 10, 11, 12 (row 3 is invalid)
    b1 = batch(np.array([1, 2, 3, -1]), np.array([10, 11, 12, 13]),
               np.array([20, 21, 22, 23]),
               np.array([True, True, True, False]), none)
    # step 2 reads, over its valid rows 0-2: queries 1, 5, 2 / 10, 13, 20
    # / 3, 30, 31 (hits 1, 2, 10, 3: 13 came from an invalid row) and
    # neighbor slots 11, 12, 35 (hits 11, 12); row 3 is invalid
    b2 = batch(np.array([1, 5, 2, 7]), np.array([10, 13, 20, 21]),
               np.array([3, 30, 31, 32]),
               np.array([True, True, True, False]),
               np.array([[11, -1], [12, 35], [-1, -1], [-1, -1]]))
    step = make_eval_step(cfg)
    state, aux1 = step(params, state, b1, tables)
    assert float(aux1["fresh_read_share"]) == 0.0
    _, aux2 = step(params, state, b2, tables)
    assert float(aux2["fresh_read_share"]) == pytest.approx(want, abs=1e-7)
