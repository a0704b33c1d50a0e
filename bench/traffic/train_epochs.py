"""Traffic kind ``train_epochs``: whole training epochs through the
single-device epoch loop of ``repro.tig.train.train_single`` (without its
val/test scoring), in the same order of calls:

  * the train split's T-CSR is staged once (``_stage_tcsr``);
  * each epoch's raw-edge plan (``build_batch_program(plan="device")``) is
    built and staged by an ``EpochPrefetcher`` while the previous epoch runs;
  * node memory is reset (``init_state``) at each epoch start;
  * the scanned epoch program (``engine.make_train_epoch``) runs through
    ``train_epoch``, which ends in the blocking fetch of the mean loss.

Set-up ends with epoch 0, which compiles (or loads) the epoch program.
Its plan is the epoch's own with every step after the first
``check_steps`` marked invalid, so the program trains those steps and
then runs AdamW on zero gradients to the epoch's end; the plain reference
follows the same steps, and the check compares the losses, the gradients
as AdamW's second moment holds them, and the weights' change.  The window
then runs whole epochs until ``--seconds`` have passed.

The kind's check, besides ``run``: ``small`` cuts the cell to a CPU size,
``reading`` is ``run``'s check with no window (``bench/calibrate.py``
reads the limits' readings through it), and ``wrong_plan`` plants
negatives that are not the seed's draw.  It runs on one device, the
first of ``cell.devices``.

Traffic file keys: ``stream_edges`` (a prefix of the configuration's
stream, or null for all of it), ``train_frac``/``val_frac`` (the
chronological split), ``lr``, ``max_grad_norm``, ``check_steps`` (steps of
epoch 0 the reference follows) and ``trace_epochs`` (window epochs the
profiler records with ``--trace 1``).
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

import reference
import streamgen
import weights


def graph_params(conf: dict, traffic: dict) -> dict:
    keys = ("num_users", "num_items", "d_e", "d_n", "labeled", "classes",
            "zipf_users", "zipf_items", "repeat_prob")
    out = {k: conf[k] for k in keys}
    out["num_edges"] = traffic["stream_edges"] or conf["num_edges"]
    return out


def model_dict(conf: dict) -> dict:
    keys = ("flavor", "dim", "dim_time", "d_e", "d_n", "num_neighbors",
            "n_heads", "n_layers", "message_fn", "batch_size")
    return {k: conf[k] for k in keys}


def tig_config(conf: dict, kernel_backend: str):
    from repro.tig.models import TIGConfig

    return TIGConfig(
        flavor=conf["flavor"], dim=conf["dim"], dim_time=conf["dim_time"],
        dim_edge=conf["d_e"], dim_node=conf["d_n"],
        num_neighbors=conf["num_neighbors"], n_heads=conf["n_heads"],
        message_fn=conf["message_fn"], batch_size=conf["batch_size"],
        use_pallas=conf["use_pallas"], kernel_backend=kernel_backend,
        n_layers=conf["n_layers"])


class Setup:
    """Everything the program needs, made from the seed: the stream, the
    program's split and T-CSR, device tables, weights, optimizer, and the
    epoch program."""

    def __init__(self, conf, traffic, seed, kernel_backend="auto"):
        import jax
        from repro.optim import adamw
        from repro.tig.engine import make_train_epoch
        from repro.tig.graph import TemporalGraph
        from repro.tig.models import init_params
        from repro.tig.protocol import split_views
        from repro.tig.sampler import ChronoNeighborIndex
        from repro.tig.train import _stage_tcsr

        self.conf, self.traffic, self.seed = conf, traffic, seed
        # the configuration's precision, for every program of the process
        jax.config.update("jax_default_matmul_precision",
                          conf["matmul_precision"])
        self.stream = streamgen.generate(seed=seed,
                                         **graph_params(conf, traffic))
        s = self.stream
        n = s.num_nodes
        g = TemporalGraph(src=s.src, dst=s.dst, t=s.t, edge_feat=s.edge_feat,
                          node_feat=np.zeros((n, s.d_n), np.float32),
                          labels=s.labels, name=conf["name"])
        self.splits = split_views(g, traffic["train_frac"],
                                  traffic["val_frac"])
        self.train = self.splits.train
        self.cfg = tig_config(conf, kernel_backend)
        self.tables = device_tables(s)
        self.layout = jax.eval_shape(lambda k: init_params(k, self.cfg),
                                     jax.random.PRNGKey(0))
        self.params = weights.make_params(seed, self.layout)
        self.opt = adamw(lr=traffic["lr"],
                         max_grad_norm=traffic["max_grad_norm"])
        self.opt_state = self.opt.init(self.params)
        self.epoch_fn = make_train_epoch(self.cfg, self.opt)
        self.call_specs = None
        tr = self.train
        self.index = ChronoNeighborIndex(
            tr.src, tr.dst, tr.t, tr.eidx, n, self.cfg.num_neighbors,
            self.cfg.batch_size)
        self.tcsr = _stage_tcsr(self.index, self.cfg.n_layers)
        self.total_events = int(self.tcsr["nbr"].shape[0])
        self.steps = self.index.num_batches

    def plan(self, ep: int):
        from repro.tig.batching import build_batch_program
        from repro.tig.train import epoch_rng

        return build_batch_program(
            self.train, self.cfg, epoch_rng(self.seed, ep, 1),
            neg_pool=self.splits.neg_pool, index=self.index, plan="device")

    def prefetcher(self):
        from repro.tig.protocol import device_batches
        from repro.tig.stream import EpochPrefetcher

        return EpochPrefetcher(
            self.plan, 1 << 30,
            to_device=lambda pr: (device_batches(pr[0]), pr[1]), depth=1)

    def epoch(self, batches):
        """One epoch from a fresh memory; returns the per-step losses."""
        import jax
        from repro.tig.models import init_state
        from repro.tig.train import train_epoch

        out = {}

        def epoch_fn(*a, **kw):
            if self.call_specs is None:
                # the feed's arrays are uncommitted: so are these specs,
                # and lowering them finds the program the call compiled
                self.call_specs = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (a, kw))
            res = self.epoch_fn(*a, **kw)
            out["losses"] = res[3]
            return res

        state = init_state(self.cfg, self.stream.num_nodes)
        self.params, self.opt_state, _state, _mean = train_epoch(
            self.params, self.opt_state, state, batches, self.tables,
            epoch_fn, tcsr=self.tcsr)
        return out["losses"]

    def program_bytes(self) -> int:
        """Device bytes of the compiled epoch program the window drives:
        arguments + outputs - aliased + temporaries, by
        ``memory_analysis()`` (the runtime's peak leaves the program's
        temporaries out).  Lowered at the window's own shapes, so the
        compile is a cache hit."""
        a, kw = self.call_specs
        m = self.epoch_fn.lower(*a, **kw).compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)

    def free(self):
        for name in ("params", "opt_state", "tables", "tcsr", "epoch_fn"):
            setattr(self, name, None)
        gc.collect()


def device_tables(s) -> dict:
    """Edge-feature and node-feature tables with their trailing dump rows,
    made on the device (node features are all zero)."""
    import jax.numpy as jnp

    efeat = np.concatenate([s.edge_feat,
                            np.zeros((1, s.edge_feat.shape[1]), np.float32)])
    return {"efeat": jnp.asarray(efeat),
            "nfeat": jnp.zeros((s.num_nodes + 1, s.d_n), jnp.float32)}


def first_epoch(setup: Setup, pf) -> dict:
    """Epoch 0: the warm-up that compiles the epoch program, on the
    epoch's own plan with the steps after the first ``check_steps`` made
    invalid.  Keeps the plan rows the reference batches itself, the
    per-step losses, and per-leaf norms of the state it leaves."""
    import jax.numpy as jnp

    batches, _ = pf.get(0)
    k = check_steps(setup)
    rows = {key: np.asarray(batches[key][:k])
            for key in ("src", "dst", "neg", "t", "eidx", "valid")}
    valid = np.asarray(batches["valid"]).copy()
    valid[k:] = False
    # placed as the feed places every plan, so the window's epochs find
    # the very program this call compiles
    batches = dict(batches, valid=jnp.asarray(valid))
    p0 = leaves(setup.params)
    losses = np.asarray(setup.epoch(batches), np.float64)
    opt = setup.opt_state
    return {"rows": rows, "losses": losses,
            "norms": leaf_norms(p0, leaves(setup.params), leaves(opt["nu"]))}


def leaves(tree) -> dict:
    """Host copies of a tree's leaves by path, in float64."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            np.asarray(x, np.float64) for path, x in flat}


def leaf_norms(p0: dict, p1: dict, nu: dict) -> dict:
    """Per leaf: ``grad``, the norm of the square root of AdamW's second
    moment (the first steps' gradients as the optimizer got them, after
    clipping), and ``change``, the norm of the weights' change."""
    return {"grad": {k: float(np.sqrt(np.sum(nu[k]))) for k in nu},
            "change": {k: float(np.linalg.norm(p1[k] - p0[k])) for k in p0}}


def check_steps(setup) -> int:
    k = setup.traffic["check_steps"]
    return setup.steps if k is None else min(k, setup.steps)


def window(setup: Setup, pf, seconds: float, trace_dir=None,
           compiles=lambda: 0) -> dict:
    """Whole epochs until ``seconds`` have passed.  With ``trace_dir`` the
    profiler records the first ``trace_epochs`` of them."""
    import jax

    import trace_reduce

    ann = jax.profiler.TraceAnnotation
    n_trace = setup.traffic["trace_epochs"] if trace_dir else 0
    tracing = contextlib.ExitStack()
    paused = 0.0

    def stop():
        # writing the trace out is no part of the window
        nonlocal paused
        tracing.close()
        t = time.perf_counter()
        jax.profiler.stop_trace()
        paused += time.perf_counter() - t

    if n_trace:
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profile_options())
        tracing.enter_context(ann("bench.window"))
    c0 = compiles()
    t0 = time.perf_counter()
    ep, wait, bad = 1, 0.0, 0
    while True:
        tw = time.perf_counter()
        with ann("bench.plan_wait"):
            batches, _ = pf.get(ep)
        wait += time.perf_counter() - tw
        with ann("bench.epoch"):
            losses = setup.epoch(batches)
            bad += int((~np.isfinite(np.asarray(losses))).sum())
        del batches
        if ep == n_trace:
            stop()
        if time.perf_counter() - t0 - paused >= seconds:
            break
        ep += 1
    if ep < n_trace:
        stop()
    return {"epochs": ep, "window_s": time.perf_counter() - t0 - paused,
            "plan_wait_s": wait, "steps": ep * setup.steps,
            "edges": ep * setup.train.num_edges, "nonfinite_steps": bad,
            "compiles": compiles() - c0,
            "traced_epochs": min(ep, n_trace)}


def reference_run(conf, traffic, seed, layout, steps, *,
                  precision="highest"):
    """The plain reference over the first ``steps`` steps of epoch 0 and
    the zero-gradient AdamW steps to the epoch's end, from the
    configuration and the seed alone: it batches the stream and draws the
    epoch's negatives itself."""
    s = streamgen.generate(seed=seed, **graph_params(conf, traffic))
    n_train = int(len(s.src) * traffic["train_frac"])
    b = conf["batch_size"]
    epoch_steps = -(-n_train // b)
    t = s.t / reference.time_scale(s.t)
    stream = {"src": s.src[:n_train], "dst": s.dst[:n_train],
              "t": t[:n_train], "eidx": np.arange(n_train)}
    negatives = negatives_of(seed, s.dst, epoch_steps, b)
    params = weights.make_params(seed, layout)
    tables = device_tables(s)
    out = reference.follow(
        params, tables, s.num_nodes, stream, negatives, model_dict(conf),
        steps, lr=traffic["lr"], max_grad_norm=traffic["max_grad_norm"],
        precision=precision, epoch_steps=epoch_steps)
    return {"losses": out["losses"], "rows": out["rows"],
            "norms": leaf_norms(leaves(params), leaves(out["params"]),
                                leaves(out["nu"]))}


def negatives_of(seed: int, dst, steps: int, batch_size: int) -> np.ndarray:
    """Epoch 0's negative destinations: uniform over the stream's distinct
    destinations, from numpy's generator seeded with (seed, 1, 0), as
    ``train_single`` plans its epochs."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, 0]))
    return rng.choice(np.unique(dst), size=(steps, batch_size)).astype(
        np.int32)


def worst_leaf_gap(prog: dict, ref: dict, keys) -> float:
    """The largest gap between the program's and the reference's norm of
    a leaf, over the larger of the reference's norm of that leaf and of
    the median leaf."""
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    gaps = [abs(prog.get(k, np.inf) - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys]
    gap = max(gaps)
    return float(gap) if np.isfinite(gap) else float("inf")


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` is decided on: the entries of the plan's
    rows (negatives included) that differ from the reference's own
    batching and draw; the largest relative gap of the loss over the
    followed steps; and, by the worst leaf, the gap of the gradient norm
    and of the weights' change.  Leaves whose reference gradient is under
    a thousandth of the median leaf's (nought to rounding, as a key's bias
    under softmax) are left out of the change."""
    k = len(ref["losses"])
    mism = sum(int((np.asarray(prog["rows"][key]) != ref["rows"][key]).sum())
               for key in ("src", "dst", "neg", "t", "eidx", "valid"))
    rl = ref["losses"]
    gap = np.abs(np.asarray(prog["losses"][:k]) - rl) / np.maximum(
        np.abs(rl), 1e-12)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    g_ref = ref["norms"]["grad"]
    med = float(np.median(list(g_ref.values())))
    moved = [key for key, v in g_ref.items() if v >= 1e-3 * med]
    return {"plan_mismatch": float(mism),
            f"loss_gap.first{k}": float(gap.max()),
            f"grad_gap.first{k}": worst_leaf_gap(
                prog["norms"]["grad"], g_ref, g_ref),
            f"change_gap.first{k}": worst_leaf_gap(
                prog["norms"]["change"], ref["norms"]["change"], moved)}


def small(conf: dict, traffic: dict) -> tuple[dict, dict]:
    """The cell at its own widths, with 100 nodes, a 3,000-edge stream
    and one traced epoch."""
    return (dict(conf, num_users=60, num_items=40),
            dict(traffic, stream_edges=3000, trace_epochs=1))


def reading(cell, control=False) -> tuple[dict, dict, dict]:
    """Epoch 0 of the program on the cell's seed, compared with the plain
    reference as ``run`` compares them; returns (numbers, prog, ref).
    With ``control`` the reference at matmul precision "high" (three
    bfloat16 passes) takes the program's place, against the reference at
    "highest", on the plan the program would have been given."""
    setup = Setup(cell.conf, cell.traffic, cell.seed, cell.kernel_backend)
    if control:
        rows, _ = setup.plan(0)
    else:
        with setup.prefetcher() as pf:
            prog = first_epoch(setup, pf)
    k = check_steps(setup)
    layout = setup.layout
    setup.free()
    ref = reference_run(cell.conf, cell.traffic, cell.seed, layout, k)
    if control:
        low = reference_run(cell.conf, cell.traffic, cell.seed, layout, k,
                            precision="high")
        prog = {"rows": {key: rows[key][:k] for key in ref["rows"]},
                "losses": low["losses"], "norms": low["norms"]}
    return compare(prog, ref), prog, ref


@contextlib.contextmanager
def wrong_plan():
    """Plans whose negatives are not the seed's draw (each batch's rolled
    by one), which the plan comparison must refuse: the reference draws
    the epoch's negatives itself."""
    from repro.tig import batching

    orig = batching.build_batch_program

    def shifted(*a, **kw):
        batches, final = orig(*a, **kw)
        return dict(batches, neg=np.roll(batches["neg"], 1, axis=1)), final

    batching.build_batch_program = shifted
    try:
        yield
    finally:
        batching.build_batch_program = orig


def run(cell) -> dict:
    """One run of a cell: set-up with epoch 0, the window, then the check
    against the reference once the program's state is freed."""
    t0 = time.perf_counter()
    setup = Setup(cell.conf, cell.traffic, cell.seed, cell.kernel_backend)
    with setup.prefetcher() as pf:
        first = first_epoch(setup, pf)
        setup_s = time.perf_counter() - t0
        win = window(setup, pf, cell.seconds, cell.trace_dir, cell.compiles)
    peak = cell.memory_peak()
    program_bytes = setup.program_bytes()
    layout, steps, total_events = setup.layout, setup.steps, \
        setup.total_events
    k = check_steps(setup)
    setup.free()
    del setup
    ref = reference_run(cell.conf, cell.traffic, cell.seed, layout, k)
    numbers = compare(first, ref)
    return {"setup_s": setup_s, "memory_peak_bytes": peak,
            "program_bytes": program_bytes,
            "steps_per_epoch": steps, "total_events": total_events,
            "numbers": numbers, **win}
