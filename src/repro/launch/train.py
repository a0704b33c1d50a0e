"""Unified training launcher.

Two pillars behind one CLI:
  * ``--arch speed-tig``  — the paper's pipeline: synthetic TIG -> SEP
    partitioning -> PAC multi-device training -> downstream eval.
  * ``--arch <llm-arch>`` — LM pretraining on the synthetic corpus with the
    pjit sharding rules (reduced configs on CPU; full configs are for the
    dry-run / real pods).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch speed-tig \
      --dataset small --devices 4 --parts 8 --topk 0.05 --epochs 3
  PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --reduced \
      --steps 200 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

__all__ = ["main"]


def train_tig(args) -> None:
    import jax

    from repro.core import partition_stats, sep_partition
    from repro.configs.speed_tig import TIG
    from repro.tig.data import synthetic_tig
    from repro.tig.distributed import pac_train
    from repro.tig.graph import chronological_split
    from repro.tig.models import TIGConfig
    from repro.tig.train import evaluate_params

    g = synthetic_tig(args.dataset, seed=args.seed)
    print(f"dataset: {g.stats()}")
    train_g, _, _, _ = chronological_split(g)

    t0 = time.perf_counter()
    part = sep_partition(train_g.src, train_g.dst, train_g.t,
                         g.num_nodes, args.parts, k=args.topk)
    print(f"SEP: {partition_stats(part)}")

    cfg = dataclasses.replace(
        TIG, dim=args.dim, dim_edge=g.dim_edge, dim_node=g.dim_node,
        dim_time=min(args.dim, 64), batch_size=args.batch,
        flavor=args.flavor)
    mesh = None
    if args.shard_map:
        from repro.launch.mesh import make_tig_mesh
        mesh = make_tig_mesh(args.devices)
    res = pac_train(train_g, part, cfg, num_devices=args.devices,
                    epochs=args.epochs, lr=args.lr, mesh=mesh,
                    grid_layout=args.grid_layout or None)
    print(f"PAC: derived speedup {res.derived_speedup:.2f}x, "
          f"edges/device {res.edges_per_device.tolist()}, "
          f"losses {res.mean_loss_per_epoch().round(4).tolist()}")
    ev = evaluate_params(g, cfg, res.params, eval_node_class=True)
    print(f"eval: {ev}")
    print(f"total {time.perf_counter() - t0:.1f}s")


def train_lm(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import LMDataConfig, packed_batches
    from repro.checkpoint import save_checkpoint
    from repro.models import init_params, make_train_step
    from repro.optim import adamw, linear_warmup_cosine

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.seq or args.batch:
        pass  # shapes live in the data config; model is shape-polymorphic
    dcfg = LMDataConfig(vocab=cfg.vocab, seq_len=args.seq or 128,
                        global_batch=args.batch or 8, seed=args.seed)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.arch}{' (reduced)' if args.reduced else ''}: "
          f"{n_params/1e6:.2f}M params, seq={dcfg.seq_len}, "
          f"batch={dcfg.global_batch}")

    opt = adamw(lr=linear_warmup_cosine(args.lr, 20, args.steps),
                weight_decay=0.1, max_grad_norm=1.0)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt))

    data = packed_batches(dcfg)
    t0 = time.perf_counter()
    tokens_seen = 0
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        tokens_seen += dcfg.global_batch * dcfg.seq_len
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"tok/s {tokens_seen/dt:,.0f}")
        if args.ckpt_dir and step and step % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step, params)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, params)
        print(f"saved final checkpoint to {args.ckpt_dir}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=None)
    # TIG options
    ap.add_argument("--dataset", default="small")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--topk", type=float, default=0.05)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--flavor", default="tgn",
                    choices=["jodie", "dyrep", "tgn", "tige"])
    ap.add_argument("--shard-map", action="store_true",
                    help="use real devices (set XLA_FLAGS for >1 on CPU)")
    ap.add_argument("--grid-layout", default="",
                    choices=["", "replicated", "sharded"],
                    help="PAC batch-grid layout; empty picks the default "
                         "(sharded on a mesh, replicated on vmap). Multi-"
                         "host pods should launch repro.launch.pac_cluster")
    # LM options
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args(argv)
    from repro.launch.cache import setup_compile_cache

    setup_compile_cache()
    if args.arch == "speed-tig":
        args.lr = args.lr or 1e-3
        args.batch = args.batch or 100
        train_tig(args)
    else:
        args.lr = args.lr or 3e-3
        train_lm(args)


if __name__ == "__main__":
    main()
