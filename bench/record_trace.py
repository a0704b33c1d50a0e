"""Record a traced window of a cell and reduce it by the program's stages.

    python3 bench/record_trace.py --workload tgn-taobao.train --seed N \\
        --seconds 50 --out build/trace [--nodes 1000 --edges 2000]

Runs the cell's set-up and window as ``bench/run.py --trace 1`` does (no
correctness check), keeps the trace as ``<out>/<name>.xplane.pb.gz`` and
the epoch program's ``{instruction: scope path}`` map (``scopes.scope_map``
of the compiled program, lowered at the window's shapes: a cache hit) as
``<out>/<name>.scopes.json``, and prints one JSON object: each stage's
device time per step (``scopes.STAGES``), the epoch program's busy time
per step, ``step_device_ms`` as ``bench/metrics`` reads it, device time
by (scope, forward | gradient), the longest labelled idle gaps, and the
host time per epoch of the traced and the untraced epochs of the window.
``--nodes`` and ``--edges`` shrink the cell's node count and stream, as
for the recorded fixture ``tests/bench/data/tgn_small_scoped``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH / "traffic", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import run  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402
import train_epochs  # noqa: E402


def cell_files(workload: str, nodes, edges) -> tuple[dict, dict]:
    _w, conf, traffic = run.cell_files(run.spec(), workload)
    if nodes:
        users = nodes * conf["num_users"] // (conf["num_users"]
                                              + conf["num_items"])
        conf = dict(conf, num_users=users, num_items=nodes - users)
    if edges:
        traffic = dict(traffic, stream_edges=edges)
    return conf, traffic


def record(conf, traffic, seed, seconds, out: Path, name: str) -> dict:
    setup = train_epochs.Setup(conf, traffic, seed)
    with tempfile.TemporaryDirectory() as tdir:
        with setup.prefetcher() as pf:
            train_epochs.first_epoch(setup, pf)
            win = train_epochs.window(setup, pf, seconds, tdir)
        out.mkdir(parents=True, exist_ok=True)
        xplane = out / f"{name}.xplane.pb.gz"
        with open(trace_reduce.find_xplane(tdir), "rb") as src, \
                gzip.open(xplane, "wb") as dst:
            shutil.copyfileobj(src, dst)
    a, kw = setup.call_specs
    smap = scopes.scope_map(
        setup.epoch_fn.lower(*a, **kw).compile().as_text())
    (out / f"{name}.scopes.json").write_text(
        json.dumps(smap, indent=0, sort_keys=True))
    return summary(str(xplane), smap, win, setup.steps,
                   setup.train.num_edges)


def summary(xplane: str, smap: dict, win: dict, steps_per_epoch: int,
            edges_per_epoch: int) -> dict:
    red = scopes.reduce(scopes.load(xplane), smap)
    steps = win["traced_epochs"] * steps_per_epoch
    devs = list(red["devices"].values())

    def per_step_ms(values):
        return sum(values) / len(values) / 1e6 / steps if values else None

    stage = scopes.stage_ms(red, steps)
    traced_s = red["window_ns"] / 1e9
    untraced = win["epochs"] - win["traced_epochs"]
    return {
        "stage_ms": stage,
        "stage_sum_ms": sum(stage.values()),
        "program_busy_ms": per_step_ms([d["program_busy_ns"] for d in devs]),
        "step_device_ms": per_step_ms([d["busy_ns"] for d in devs]),
        "breakdown": scopes.breakdown(red),
        "epochs": win["epochs"], "traced_epochs": win["traced_epochs"],
        "window_edges_per_s": win["edges"] / win["window_s"],
        "traced_epoch_s": traced_s / win["traced_epochs"],
        "untraced_epoch_s": (win["window_s"] - traced_s) / untraced
        if untraced else None,
        "edges_per_epoch": edges_per_epoch,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--name", default=None)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--edges", type=int, default=None)
    args = ap.parse_args(argv)
    w = {c["name"]: c for c in run.spec()["workloads"]}[args.workload]
    run.accelerators(w["chips"])
    run.compile_cache()
    conf, traffic = cell_files(args.workload, args.nodes, args.edges)
    out = record(conf, traffic, args.seed, args.seconds, args.out,
                 args.name or args.workload)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
