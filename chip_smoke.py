"""Bring-up smoke of the SPEED main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # PAC across a four-chip host

One chip (the default): generate ``taobao-s`` from its seed (103k nodes,
2M edges), SEP-partition it, and train TGN at the paper's widths
(``configs.speed_tig.TIG``) with every TIG kernel on Pallas — neighbor
sampling, the fused message flush, and temporal attention forward and
backward — through ``train_single`` on a prefix of the stream, then score
val/test through ``run_protocol``.  The Pallas path is then checked
against the XLA path, both traced under ``default_matmul_precision
("highest")`` from the same initial params: a forward-only pass over the
whole training stream (memory keeps evolving, params fixed) must give the
same link logits within ``FWD_RTOL``, and one training epoch must give
the same per-step losses for the first ``TIGHT_STEPS`` steps within
``STEP_RTOL`` and the same epoch-mean loss within ``MEAN_RTOL``.  Later
steps are not compared one by one: f32 rounding differences compound
through Adam and the trajectories drift apart, as they do between two
XLA runs that differ only in matmul precision.

``--chips 4`` runs only the PAC phase on a 400k-edge prefix: SEP into 8
parts, ``pac_train`` for two epochs (so shuffle-combine re-plans once) on
the 4-chip mesh with the sharded grid layout, against the same call with
``mesh=None`` on one chip, all traced at "highest".  Two pairs of runs:
with ``lr=0`` (params fixed; memory evolves, is synced across devices and
re-planned) every step of both epochs must agree within ``STEP_RTOL``;
with the training rate the first ``TIGHT_STEPS`` steps must (this checks
the gradient all-reduce and update), and the later gap is reported, not
checked — over ~1275 lockstep steps two correct programs that round
differently part by a few percent in epoch-mean loss.  Params and node
memory must be spread over four distinct TPU devices.

Everything is printed as one JSON object per line; the last line of
standard output is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when JAX finds no TPU, when
``REPRO_KERNEL_BACKEND`` / ``REPRO_KERNEL_BWD`` would move a kernel off
Pallas, when a kernel is missing from the compiled epoch program, when a
loss is not finite or outside its tolerance, or when any phase raises.
All phases run in this one process: a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
PRESET = "taobao-s"
TRAIN_EDGES = 100_000     # stream prefix: 70k train edges = 350 steps
SEP_PARTS = 4             # one-chip phase: SEP stats only
PAC_EDGES = 400_000       # PAC prefix: ~1275 lockstep steps per epoch
PAC_PARTS = 8
PAC_DEVICES = 4
PAC_EPOCHS = 2
LR = 1e-3
# Two programs at f32 "highest" that sum in different orders (Pallas vs
# XLA, mesh vs one chip): forward passes and the first training steps agree
# to f32 rounding; later training steps drift apart (Adam compounds the
# rounding), so only the one-chip phase's 350-step epoch mean is compared
# (over PAC's ~1275 lockstep steps two such runs part by a few percent).
FWD_RTOL = 1e-5           # max |logit gap| / max |logit|, whole stream
TIGHT_STEPS = 10
STEP_RTOL = 1e-5          # per-step loss of a checked step
MEAN_RTOL = 1e-2          # epoch-mean loss
REQUIRED_KERNELS = ("neighbor_sample", "fused_flush", "temporal_attn",
                    "temporal_attn_bwd")
_KERNEL_RE = re.compile(
    r"%([A-Za-z_]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"")


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: {msg}")


def check_kernel_env() -> None:
    """Refuse an environment that would route a kernel off Pallas."""
    allowed = {"REPRO_KERNEL_BACKEND": ("pallas",),
               "REPRO_KERNEL_BWD": ("fused",)}
    for var, ok in allowed.items():
        val = os.environ.get(var)
        if val and val not in ok:
            fail(f"{var}={val} would move a kernel off Pallas")


def make_graph(preset: str, seed: int, n_edges: int):
    """The preset's full stream (all nodes) and its first ``n_edges``
    edges, which keep the full node-id space."""
    from repro.tig.data import synthetic_tig

    t0 = time.perf_counter()
    g = synthetic_tig(preset, seed=seed)
    prefix = g.slice_edges(np.arange(min(n_edges, g.num_edges)),
                           f"{g.name}[:{n_edges}]")
    log("data", preset=preset, seed=seed, nodes=g.num_nodes,
        edges=g.num_edges, prefix_edges=prefix.num_edges,
        seconds=time.perf_counter() - t0)
    return g, prefix


def tig_config(g, backend: str):
    from repro.configs.speed_tig import TIG

    return dataclasses.replace(TIG, dim_edge=g.dim_edge,
                               dim_node=g.dim_node, use_pallas=True,
                               kernel_backend=backend)


def partition_phase(g, parts: int):
    from repro.core import partition_stats, sep_partition

    part = sep_partition(g.src, g.dst, g.t, g.num_nodes, parts)
    st = partition_stats(part)
    log("sep", parts=parts, edges=g.num_edges, seconds=st.elapsed_s,
        edge_cut=st.edge_cut, replication_factor=st.replication_factor,
        shared_nodes=int(len(part.shared_nodes)))
    return part


def _finite(name: str, values) -> np.ndarray:
    arr = np.asarray(values, np.float64)
    if not np.all(np.isfinite(arr)):
        fail(f"{name}: non-finite loss")
    return arr


def train_phase(g, cfg, seed: int) -> dict:
    """``train_single`` for one epoch, then ``run_protocol`` scoring of the
    trained params."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.tig.batching import make_tables
    from repro.tig.protocol import run_protocol, split_views
    from repro.tig.train import train_single

    res = train_single(g, cfg, epochs=1, seed=seed, plan="device")
    _finite("train_single", res.losses)
    log("train_single", backend=cfg.backend,
        auto_backend=ops.default_backend(), epoch_seconds=res.epoch_seconds,
        mean_loss=res.losses, val_ap=res.val_ap, test_ap=res.test_ap)

    splits = split_views(g)
    tables = {k: jnp.asarray(v) for k, v in
              make_tables(g.edge_feat, g.node_feat).items()}
    t0 = time.perf_counter()
    m = run_protocol(res.params, cfg, splits, tables, seed=seed)
    out = {k: float(m[k]) for k in ("train_ap", "val_ap", "test_ap",
                                    "val_auc", "test_auc")}
    for k, v in out.items():
        if not 0.0 <= v <= 1.0:
            fail(f"run_protocol {k}={v}")
    log("run_protocol", seconds=time.perf_counter() - t0, **out)
    return out


def _rel(a, b) -> np.ndarray:
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-6)


def _trajectory_gap(name: str, got, want, tight_steps: int | None,
                    mean_rtol: float | None = None) -> dict:
    """Per-step loss agreement of two runs, (steps,) or (devices, steps):
    the first ``tight_steps`` steps (None: all) must agree within
    ``STEP_RTOL`` and, given ``mean_rtol``, the epoch means within it;
    the rest of the trajectory is reported only."""
    got, want = np.atleast_2d(got), np.atleast_2d(want)
    rel = _rel(got, want).max(axis=0)            # worst device per step
    over = np.nonzero(rel > STEP_RTOL)[0]
    checked = rel[:tight_steps]
    gap = {"steps": int(len(rel)),
           "checked_steps": int(len(checked)),
           "checked_max_rel": float(checked.max(initial=0.0)),
           "first_step_over_rtol": int(over[0]) if len(over) else -1,
           "max_rel": float(rel.max()),
           "mean_loss_rel": float(abs(got.mean() - want.mean())
                                  / abs(want.mean()))}
    log(name, **gap, step_rtol=STEP_RTOL, mean_rtol=mean_rtol)
    if gap["checked_max_rel"] > STEP_RTOL:
        fail(f"{name}: {gap['checked_steps']} checked steps part by "
             f"{gap['checked_max_rel']:.3g} (<= {STEP_RTOL})")
    if mean_rtol is not None and gap["mean_loss_rel"] > mean_rtol:
        fail(f"{name}: epoch-mean rel {gap['mean_loss_rel']:.3g} "
             f"(<= {mean_rtol})")
    return gap


def parity_phase(g, cfg, seed: int) -> dict:
    """Pallas vs XLA on ``g``'s train split from the same initial params:
    a forward-only pass and one training epoch per kernel path.  Returns
    per-path logits, losses, compile/run seconds and the kernel census of
    each compiled training program."""
    import jax
    import jax.numpy as jnp

    from repro.optim import adamw
    from repro.tig.batching import build_batch_program, make_tables
    from repro.tig.engine import make_eval_epoch, make_train_epoch
    from repro.tig.models import init_params, init_state
    from repro.tig.protocol import device_batches, split_views
    from repro.tig.sampler import ChronoNeighborIndex
    from repro.tig.train import epoch_rng

    splits = split_views(g)
    tr = splits.views[0]
    tables = {k: jnp.asarray(v) for k, v in
              make_tables(g.edge_feat, g.node_feat).items()}
    index = ChronoNeighborIndex(tr.src, tr.dst, tr.t, tr.eidx, g.num_nodes,
                                cfg.num_neighbors, cfg.batch_size)
    tcsr = {k: jnp.asarray(v)
            for k, v in index.device_export(depth=cfg.n_layers).items()}
    program, _ = build_batch_program(tr, cfg, epoch_rng(seed, 0, 1),
                                     neg_pool=splits.neg_pool, index=index,
                                     plan="device")
    batches = device_batches(program)

    out = {}
    for path, c in (("pallas", cfg),
                    ("xla", dataclasses.replace(cfg, use_pallas=False))):
        opt = adamw(lr=LR, max_grad_norm=1.0)
        fresh = lambda: init_params(jax.random.PRNGKey(seed), c)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            _, aux = make_eval_epoch(c)(fresh(), init_state(c, g.num_nodes),
                                        batches, tables, tcsr=tcsr)
            logits = np.concatenate([np.asarray(aux["pos_logit"]).ravel(),
                                     np.asarray(aux["neg_logit"]).ravel()])
            params = fresh()
            args = (params, opt.init(params), init_state(c, g.num_nodes),
                    batches, tables)
            t0 = time.perf_counter()
            compiled = make_train_epoch(c, opt).lower(
                *args, tcsr=tcsr).compile()
            compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        t0 = time.perf_counter()
        losses = _finite(path, compiled(*args, tcsr=tcsr)[3])
        out[path] = {
            "logits": _finite(f"{path} logits", logits),
            "losses": losses,
            "compile_seconds": compile_s,
            "run_seconds": time.perf_counter() - t0,
            "tpu_custom_calls": text.count(
                'custom_call_target="tpu_custom_call"'),
            "kernels": dict(Counter(_KERNEL_RE.findall(text))),
        }
        log("epoch_program", path=path, steps=len(losses),
            **{k: v for k, v in out[path].items()
               if k not in ("logits", "losses")},
            first_losses=losses[:3].tolist(), last_loss=losses[-1])

    lp, lx = out["pallas"]["logits"], out["xla"]["logits"]
    fwd = float(np.abs(lp - lx).max() / np.abs(lx).max())
    log("pallas_vs_xla_forward", logits=int(len(lp)), max_rel=fwd,
        rtol=FWD_RTOL)
    if fwd > FWD_RTOL:
        fail(f"Pallas and XLA forward logits disagree: {fwd:.3g} "
             f"(<= {FWD_RTOL})")
    _trajectory_gap("pallas_vs_xla_train", out["pallas"]["losses"],
                    out["xla"]["losses"], TIGHT_STEPS, MEAN_RTOL)
    return out


def pac_phase(g, cfg, seed: int, mesh) -> dict:
    """SEP into ``PAC_PARTS`` parts, then ``pac_train`` on ``mesh`` and on
    the ``mesh=None`` simulation, at ``lr=0`` and at ``LR``, all traced at
    "highest".  Returns the runs, keyed ``(lr, "mesh"|"reference")``, and
    the per-epoch loss gaps: every step checked at ``lr=0``, the first
    ``TIGHT_STEPS`` of epoch 0 when training."""
    import jax

    from repro.core import sep_partition
    from repro.tig.distributed import pac_train
    from repro.tig.graph import chronological_split

    train_g, _, _, _ = chronological_split(g)
    part = sep_partition(train_g.src, train_g.dst, train_g.t, g.num_nodes,
                         PAC_PARTS)
    runs, gaps = {}, {}
    for lr in (0.0, LR):
        for name, m in (("mesh", mesh), ("reference", None)):
            t0 = time.perf_counter()
            with jax.default_matmul_precision("highest"):
                res = pac_train(
                    train_g, part, cfg, num_devices=PAC_DEVICES,
                    epochs=PAC_EPOCHS, lr=lr, seed=seed, mesh=m,
                    grid_layout="sharded" if m is not None else None)
            runs[lr, name] = res
            log("pac_train", run=name, lr=lr,
                seconds=time.perf_counter() - t0,
                edges_per_device=res.edges_per_device.tolist(),
                steps=[int(l.shape[-1]) for l in res.losses],
                mean_loss=res.mean_loss_per_epoch().tolist())
        for e, (lm, lref) in enumerate(zip(runs[lr, "mesh"].losses,
                                           runs[lr, "reference"].losses)):
            name = f"pac_mesh_vs_reference_lr{lr:g}_epoch{e}"
            tight = None if lr == 0.0 else (TIGHT_STEPS if e == 0 else 0)
            gaps[name] = _trajectory_gap(
                name, _finite("pac mesh", lm), _finite("pac reference", lref),
                tight)
    return {"runs": runs, "gaps": gaps}


def check_pac_placement(res, cfg, devices) -> None:
    """Params live on every mesh device, and every device held at least
    one partition's node-memory table at its peak."""
    import jax

    want = {d.id for d in devices}
    for leaf in jax.tree_util.tree_leaves(res.params):
        got = {d.id for d in leaf.sharding.device_set}
        if got != want:
            fail(f"params on devices {sorted(got)}, want {sorted(want)}")
    table = (res.plan.capacity + 1) * cfg.dim * 4
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    log("pac_placement", devices=sorted(want), peak_bytes=peaks,
        memory_table_bytes=table)
    if min(peaks) < table:
        fail(f"a device peaked below one memory table: {peaks} < {table}")


def run_single() -> None:
    full, prefix = make_graph(PRESET, SEED, TRAIN_EDGES)
    partition_phase(full, SEP_PARTS)
    cfg = tig_config(full, "pallas")
    out = parity_phase(prefix, cfg, SEED)
    kernels = out["pallas"]["kernels"]
    missing = [k for k in REQUIRED_KERNELS if k not in kernels]
    if missing:
        fail(f"kernels missing from the compiled epoch program: {missing} "
             f"(found {kernels})")
    train_phase(prefix, cfg, SEED)


def run_pac() -> None:
    import jax

    from repro.launch.mesh import make_tig_mesh

    if len(jax.devices()) < PAC_DEVICES:
        fail(f"--chips {PAC_DEVICES} needs {PAC_DEVICES} devices, found "
             f"{len(jax.devices())}")
    _, prefix = make_graph(PRESET, SEED, PAC_EDGES)
    cfg = tig_config(prefix, "pallas")
    mesh = make_tig_mesh(PAC_DEVICES)
    out = pac_phase(prefix, cfg, SEED, mesh)
    check_pac_placement(out["runs"][LR, "mesh"], cfg,
                        list(mesh.devices.flat))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, PAC_DEVICES), default=1,
                    help=f"1: the one-chip main path; {PAC_DEVICES}: only "
                         "the PAC phase across chips")
    args = ap.parse_args(argv)
    check_kernel_env()

    from repro.launch.cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices")
    log("start", chips=args.chips, device_kind=dev.device_kind,
        devices=len(jax.devices()), jax=jax.__version__, cache=cache_dir)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_single()
    else:
        run_pac()
    log("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
