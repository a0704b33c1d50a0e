import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): prove the distribution config is
coherent without real hardware.

For every (architecture x input shape x mesh) combination this script
``.lower().compile()``s the real training / prefill / decode program against
ShapeDtypeStruct stand-ins (no allocation), prints memory_analysis() (fits
HBM?) and cost_analysis() (FLOPs/bytes for §Roofline), parses the collective
schedule from the optimized HLO, and writes one JSON per combination under
``experiments/dryrun/``.

Meshes: single-pod (16, 16) ("data", "model") = 256 chips, and multi-pod
(2, 16, 16) ("pod", "data", "model") = 512 chips (the "pod" axis shards the
batch — proving cross-pod data parallelism lowers).

The paper's own workload (speed-tig) is dry-run as the PAC shard_map program
on a 256-way "part" mesh (one sub-graph partition per chip).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|...]
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh, make_tig_mesh
from repro.models import model as M
from repro.optim import adamw
from repro.roofline.analysis import MODEL_FLOPS, analyze_compiled

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# long_500k runs only for sub-quadratic archs (DESIGN.md §4)
LONG_OK = {"rwkv6-1.6b", "hymba-1.5b", "starcoder2-3b"}

ENC_LEN_DECODE = 4096       # fixed encoder memory for seamless decode shapes


def microbatch_for(cfg, shape, n_batch_shards: int = 16) -> int:
    """Grad-accumulation splits: keep per-microbatch per-device ~1 sequence
    at 4k so remat-saved carries fit HBM.  Capped so each microbatch still
    divides the batch-sharding axes."""
    if shape.kind != "train":
        return 1
    cap = max(shape.global_batch // n_batch_shards, 1)
    per_dev = max(shape.global_batch // 16, 1)
    if cfg.d_model >= 3584 or cfg.is_moe:
        m = per_dev
    elif cfg.d_model >= 2048:
        m = max(per_dev // 2, 1)
    else:
        m = max(per_dev // 4, 1)
    return min(m, cap)


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (weak-type-correct,
    shardable, no device allocation)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        batch = {}
        if cfg.enc_dec:
            batch["frames"] = sds((b, s, cfg.d_model), bf16)
        if cfg.frontend == "vision":
            f = cfg.frontend_tokens
            batch["patches"] = sds((b, f, cfg.d_model), bf16)
            batch["positions3"] = sds((b, 3, s), i32)
            batch["tokens"] = sds((b, s - f), i32)
            batch["targets"] = sds((b, s - f), i32)
        else:
            batch["tokens"] = sds((b, s), i32)
            batch["targets"] = sds((b, s), i32)
        return batch
    # decode: cross-attn K/V live in the cache (filled at prefill)
    return {"token": sds((b,), i32), "pos": sds((b,), i32)}


def _shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_axis(global_batch: int, mesh, multi_pod: bool):
    """Batch sharding axes; B=1 (long_500k) cannot shard -> replicate."""
    data = mesh.shape["data"]
    pod = mesh.shape.get("pod", 1)
    if multi_pod and global_batch % (data * pod) == 0:
        return ("pod", "data")
    if global_batch % data == 0:
        return ("data",)
    return None


def _respec_batch(specs: dict, axes) -> dict:
    """Rewrite the leading batch axis of every batch spec to ``axes``."""
    def fix(p):
        rest = tuple(p)[1:]
        return P(axes, *rest)
    return {k: fix(v) for k, v in specs.items()}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               save: bool = True, verbose: bool = True) -> dict:
    if arch == "speed-tig":
        return dryrun_speed_tig(multi_pod=multi_pod, save=save,
                                verbose=verbose)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k" and arch not in LONG_OK:
        return {"arch": arch, "shape": shape_name,
                "status": "skipped (full attention; DESIGN.md §4)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = int(np.prod(list(mesh.shape.values())))
    tp = mesh.shape["model"]
    batch = input_specs(arch, shape_name)
    b_axes = _batch_axis(shape.global_batch, mesh, multi_pod)
    n_shards = 1
    if b_axes:
        n_shards = int(np.prod([mesh.shape[a] for a in
                                (b_axes if isinstance(b_axes, tuple)
                                 else (b_axes,))]))
    cfg = dataclasses.replace(
        cfg, microbatch=microbatch_for(cfg, shape, n_shards))
    bspecs = _respec_batch(
        M.batch_specs(cfg, shape.kind, multi_pod), b_axes)
    bspecs = {k: v for k, v in bspecs.items() if k in batch}
    # train: FSDP (params+opt state over data x model).  prefill: weights
    # also sharded over data (§Perf A2 — throughput path, per-layer weight
    # all-gathers overlap; required for 235B-class params to fit v5e).
    # decode: model-only (latency path; per-layer gathers would serialize —
    # the 235B config needs a larger serving mesh, noted in EXPERIMENTS.md).
    pspecs = M.param_specs(cfg, fsdp=(shape.kind in ("train", "prefill")))

    t0 = time.time()
    sharded_moe = cfg.is_moe and shape.kind in ("train", "prefill") \
        and not os.environ.get("REPRO_MOE_PJIT")
    with compat.set_mesh(mesh), \
            M.activation_batch_axes(b_axes, sharded_moe=sharded_moe):
        if shape.kind == "train":
            params_shape = jax.eval_shape(
                lambda k: M.init_params(k, cfg, tp),
                jax.random.PRNGKey(0))
            opt = adamw(lr=1e-4)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            ospecs = {
                "step": P(),
                "mu": pspecs,
                "nu": pspecs,
            }
            step = M.make_train_step(cfg, opt, tp, batch_axes=b_axes)
            jitted = jax.jit(
                step,
                in_shardings=(_shardings(mesh, pspecs),
                              _shardings(mesh, ospecs),
                              _shardings(mesh, bspecs)),
                out_shardings=(_shardings(mesh, pspecs),
                               _shardings(mesh, ospecs),
                               NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shape, opt_shape, batch)
            tokens = shape.global_batch * shape.seq_len
            mflops = MODEL_FLOPS(cfg.active_param_count(), tokens, "train")
        elif shape.kind == "prefill":
            params_shape = jax.eval_shape(
                lambda k: M.init_params(k, cfg, tp),
                jax.random.PRNGKey(0))
            params_shape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
                params_shape)
            fwd = lambda p, b: M.forward(p, b, cfg, tp)[0]
            logits_axes = P(b_axes, None, "model")
            jitted = jax.jit(
                fwd,
                in_shardings=(_shardings(mesh, pspecs),
                              _shardings(mesh, bspecs)),
                out_shardings=NamedSharding(mesh, logits_axes),
            )
            lowered = jitted.lower(params_shape, batch)
            tokens = shape.global_batch * shape.seq_len
            mflops = MODEL_FLOPS(cfg.active_param_count(), tokens, "infer")
        else:  # decode
            params_shape = jax.eval_shape(
                lambda k: M.init_params(k, cfg, tp),
                jax.random.PRNGKey(0))
            params_shape = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
                params_shape)
            cache_shape = jax.eval_shape(
                lambda: M.init_cache(cfg, tp, shape.global_batch,
                                     shape.seq_len, ENC_LEN_DECODE))
            cspecs = _respec_batch_cache(
                M.cache_specs(cfg, multi_pod), b_axes)
            sstep = lambda p, c, b: M.serve_step(p, c, b, cfg, tp)
            jitted = jax.jit(
                sstep,
                in_shardings=(_shardings(mesh, pspecs),
                              _shardings(mesh, cspecs),
                              _shardings(mesh, bspecs)),
                out_shardings=(NamedSharding(mesh, P(b_axes, "model")),
                               _shardings(mesh, cspecs)),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shape, cache_shape, batch)
            mflops = MODEL_FLOPS(cfg.active_param_count(),
                                 shape.global_batch, "infer")

        compiled = lowered.compile()

    elapsed = time.time() - t0
    report = analyze_compiled(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=chips, model_flops=mflops,
        note=f"tp={tp} microbatch={cfg.microbatch} "
             f"batch_axes={b_axes} kind={shape.kind}")
    out = report.to_json()
    out["status"] = "ok"
    out["compile_seconds"] = elapsed
    mem = compiled.memory_analysis()
    out["memory_analysis"] = str(mem)
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] compiled in "
              f"{elapsed:.1f}s")
        print("  memory:", mem)
        print(f"  flops(global)={report.hlo_flops:.3e} "
              f"bytes={report.hlo_bytes:.3e} "
              f"coll={report.collective_bytes:.3e}")
        print(f"  terms: compute={report.compute_s*1e3:.2f}ms "
              f"memory={report.memory_s*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"-> {report.dominant}-bound; useful={report.useful_ratio:.2f}")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fn = os.path.join(OUT_DIR, f"{arch}_{shape_name}_{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(out, f, indent=1)
    return out


def _respec_batch_cache(specs: dict, axes) -> dict:
    """Cache specs: batch is the SECOND axis (after layers)."""
    def fix(p):
        t = tuple(p)
        return P(t[0], axes, *t[2:])
    return {k: fix(v) for k, v in specs.items()}


def dryrun_speed_tig(*, multi_pod: bool, save: bool = True,
                     verbose: bool = True) -> dict:
    """Dry-run the PAC shard_map epoch program on a pod-scale 'part' mesh:
    256 (or 512) sub-graph partitions, one per chip — DGraphFin-scale node
    memory sharded per device (the paper's space-overhead story at pod
    scale).

    The lowered layout is the row-range-SHARDED data plane (PR 8): the
    (n_parts, steps, batch) raw-record grid AND the per-device T-CSR
    events are partitioned over "part" — after compilation the per-device
    input shards are asserted to be exactly ``1/n_parts`` of the global
    grid/event rows (each chip receives only its own rows; the replicated
    flat layout would ship every chip the full buffer)."""
    from repro.configs.speed_tig import TIG
    from repro.optim import adamw as _adamw
    from repro.tig.distributed import make_pac_epoch
    from repro.tig.models import init_params as tig_init
    from repro.kernels.neighbor_sample import export_length

    n_parts = 512 if multi_pod else 256
    mesh = make_tig_mesh(n_parts)
    mesh_name = f"part{n_parts}"
    cfg = TIG
    # DGraphFin-scale: 4.9M nodes / n_parts per device; a few batches/epoch
    capacity = 4_889_537 // n_parts + 1
    steps = 8
    b, k = cfg.batch_size, cfg.num_neighbors
    sds = jax.ShapeDtypeStruct
    i32, f32, b_ = jnp.int32, jnp.float32, jnp.bool_
    n_edges = 4_300_999
    e_cap = n_edges // n_parts + n_parts  # balanced partitions (SEP)
    # per-device T-CSR export: 2 endpoint events per edge + K*depth pad
    ev_cap = export_length(2 * e_cap, k, cfg.n_layers)

    def batch_tree():
        # device plan + sharded layout: per-chip (steps, ...) RAW edge
        # records, row-range sharded over "part" (each chip's rows live on
        # that chip only); neighbor grids are sampled on device from the
        # per-device T-CSR below.
        return {
            "src": sds((n_parts, steps, b), i32),
            "dst": sds((n_parts, steps, b), i32),
            "neg": sds((n_parts, steps, b), i32),
            "t": sds((n_parts, steps, b), f32),
            "eidx": sds((n_parts, steps, b), i32),
            "valid": sds((n_parts, steps, b), b_),
        }

    def tcsr_events():
        return {
            "nbr": sds((n_parts, ev_cap), i32),
            "t": sds((n_parts, ev_cap), f32),
            "eidx": sds((n_parts, ev_cap), i32),
            "bat": sds((n_parts, ev_cap), i32),
        }

    opt = _adamw(lr=1e-4, max_grad_norm=1.0)
    params_shape = jax.eval_shape(
        lambda key: tig_init(key, cfg), jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt.init, params_shape)
    n_shared = int(0.01 * 4_889_537)   # top_k=1% hubs shared

    epoch_fn = make_pac_epoch(cfg, opt, steps, capacity, mesh=mesh,
                              device_plan=True, grid_layout="sharded")
    t0 = time.time()
    lowered = epoch_fn.lower(
        params_shape, opt_shape, batch_tree(),
        sds((n_parts,), i32),            # per-device grid offsets (all 0)
        sds((n_parts,), i32),            # per-device real batch counts
        sds((n_parts, capacity + 1, cfg.dim_node), f32),
        sds((n_parts, e_cap + 1, cfg.dim_edge), f32),
        sds((n_parts, n_shared), i32),
        sds((n_parts, capacity + 1), i32),   # T-CSR indptr (unoffset)
        tcsr_events(),
    )
    compiled = lowered.compile()
    elapsed = time.time() - t0

    # the sharded-grid contract: each chip's input shard holds ONE row of
    # the grid and of the event buffer — 1/n_parts of the global rows
    args_sh = compiled.input_shardings[0]
    grid_shard = args_sh[2]["src"].shard_shape((n_parts, steps, b))
    ev_shard = args_sh[9]["nbr"].shard_shape((n_parts, ev_cap))
    assert grid_shard == (1, steps, b), grid_shard
    assert ev_shard == (1, ev_cap), ev_shard
    shrink = n_parts * steps * b // (grid_shard[0] * steps * b)
    assert shrink == n_parts, (shrink, n_parts)

    report = analyze_compiled(
        compiled, arch="speed-tig", shape="pac_epoch",
        mesh_name=mesh_name, chips=n_parts,
        model_flops=0.0,
        note=f"PAC epoch (sharded grid + T-CSR): {steps} lockstep steps, "
             f"batch {b}, capacity {capacity} nodes/device, "
             f"{n_shared} shared nodes")
    out = report.to_json()
    out["status"] = "ok"
    out["compile_seconds"] = elapsed
    out["memory_analysis"] = str(compiled.memory_analysis())
    out["grid_layout"] = "sharded"
    out["per_device_grid_rows"] = int(grid_shard[0] * steps)
    out["per_device_event_rows"] = int(ev_shard[0] * ev_cap)
    out["input_shrink_factor"] = int(shrink)
    if verbose:
        print(f"[speed-tig PAC x {mesh_name}] compiled in {elapsed:.1f}s")
        print("  memory:", compiled.memory_analysis())
        print(f"  sharded inputs: grid shard {grid_shard}, events "
              f"{ev_shard} -> {shrink}x smaller than replicated")
        print(f"  terms: compute={report.compute_s*1e3:.3f}ms "
              f"memory={report.memory_s*1e3:.3f}ms "
              f"collective={report.collective_s*1e3:.3f}ms")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fn = os.path.join(OUT_DIR, f"speed-tig_pac_{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(out, f, indent=1)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.cache import setup_compile_cache

    setup_compile_cache()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    combos = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    for a in archs:
        if a == "speed-tig":
            combos.append((a, "pac_epoch"))
            continue
        for s in shapes:
            combos.append((a, s))

    failures = []
    for a, s in combos:
        for mp in meshes:
            try:
                r = dryrun_one(a, s, multi_pod=mp, save=not args.no_save)
                if r.get("status", "").startswith("skip"):
                    print(f"[{a} x {s}] {r['status']}")
            except Exception as e:
                failures.append((a, s, mp, repr(e)))
                print(f"[{a} x {s} mp={mp}] FAILED: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures")
        sys.exit(1)
    print("\nall dry-runs OK")


if __name__ == "__main__":
    main()
