"""Size the node count of a configuration for one TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/size_nodes.py --config tgn-taobao \\
        --traffic train-epochs-100k --nodes 1000000 2000000 3000000

Compiles the one-chip epoch program (``engine.scan_train_epoch`` with the
carried params, optimizer state and node memory donated, as on the chip)
for a v5e that is described, not attached, at each node count, and prints
``memory_analysis()``: arguments + outputs - aliased + temporaries.  The
configuration's node count is the largest multiple of 250,000 whose total
stays within ``--budget`` bytes (12 GB of the chip's 16: the rest holds
the prefetched plan, the T-CSR, the edge table and the allocator's slack).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH / "traffic"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def analyse(conf: dict, traffic: dict, num_nodes: int, device) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.neighbor_sample import export_length
    from repro.optim import adamw
    from repro.tig.engine import scan_train_epoch
    from repro.tig.models import init_params, init_state

    import train_epochs

    conf = dict(conf, num_users=num_nodes // 2,
                num_items=num_nodes - num_nodes // 2)
    cfg = train_epochs.tig_config(conf, "pallas")
    sh = SingleDeviceSharding(device)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    on = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    edges = traffic["stream_edges"] or conf["num_edges"]
    n_train = int(edges * traffic["train_frac"])
    steps = -(-n_train // cfg.batch_size)
    b = cfg.batch_size
    opt = adamw(lr=traffic["lr"], max_grad_norm=traffic["max_grad_norm"])
    params = on(jax.eval_shape(lambda k: init_params(k, cfg),
                               jax.random.PRNGKey(0)))
    opt_state = on(jax.eval_shape(opt.init, params))
    state = on(jax.eval_shape(lambda: init_state(cfg, num_nodes)))
    batches = {k: sds((steps, b), dt) for k, dt in (
        ("src", jnp.int32), ("dst", jnp.int32), ("neg", jnp.int32),
        ("t", jnp.float32), ("eidx", jnp.int32), ("valid", jnp.bool_))}
    tables = {"efeat": sds((edges + 1, conf["d_e"]), jnp.float32),
              "nfeat": sds((num_nodes + 1, conf["d_n"]), jnp.float32)}
    ev = export_length(2 * n_train, cfg.num_neighbors, cfg.n_layers)
    tcsr = {"indptr": sds((num_nodes + 1,), jnp.int32),
            **{k: sds((ev,), dt) for k, dt in (
                ("nbr", jnp.int32), ("t", jnp.float32),
                ("eidx", jnp.int32), ("bat", jnp.int32))}}
    fn = jax.jit(functools.partial(scan_train_epoch, cfg=cfg, opt=opt),
                 donate_argnums=(0, 1, 2))
    compiled = fn.lower(params, opt_state, state, batches, tables,
                        tcsr=tcsr).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {"num_nodes": num_nodes,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes, "total_bytes": total}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--nodes", type=int, nargs="+", required=True)
    ap.add_argument("--budget", type=float, default=12e9)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    conf = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{args.traffic}.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    rows = [analyse(conf, traffic, n, topo.devices[0]) for n in args.nodes]
    for r in rows:
        print(json.dumps(r), flush=True)
    if len(rows) > 1:
        a, b = rows[0], rows[-1]
        per_node = (b["total_bytes"] - a["total_bytes"]) / (
            b["num_nodes"] - a["num_nodes"])
        fixed = a["total_bytes"] - per_node * a["num_nodes"]
        n_max = int((args.budget - fixed) / per_node) // 250_000 * 250_000
        print(json.dumps({"bytes_per_node": per_node, "fixed_bytes": fixed,
                          "budget_bytes": args.budget,
                          "num_nodes": n_max}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
