"""Readings, on a cell's own devices, that its check's limits are set from.

    python3 bench/calibrate.py --workload tgn-taobao.train \\
        --seeds 101 102 ... --control-seeds 201 202 203 \\
        --fault-seeds 301 302 303 --out readings

For each seed, in one process, at the cell's own size and on the cell's
own devices (as many chips as it asks for, found as ``bench/run.py``
finds them), the kind of the cell's traffic (``bench/traffic/<kind>.py``)
compares the program's first steps with the plain reference exactly as a
run does (its ``reading``), and one JSON line is written per reading:

* ``program``: the program as the configuration states it;
* ``control``: the reference at the precision below the configuration's
  (for float32 at "highest": matmul precision "high", three bfloat16
  passes) put in the program's place;
* ``fault.half_batch``: the program with half of every batch left out and
  the loss taken as the mean over the rest;
* ``fault.frozen``: the program with an optimizer step that returns the
  weights and its state unchanged.

The faults patch the step loss and the optimizer that every trainer
shares.  No window is measured: training's readings need none.
``limits/`` holds the limits set from these readings; ``PERF.md`` gives
both.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH / "traffic", BENCH.parent / "src"):
    sys.path.insert(0, str(p))

import run  # noqa: E402


@contextlib.contextmanager
def fault(name: str):
    """Plant a fault in the program for the duration of the block."""
    from repro.optim import Optimizer
    from repro.tig import engine

    if name == "half_batch":
        orig = engine.step_loss

        def half(params, state, batch, tables, cfg):
            b = batch["valid"].shape[0]
            keep = batch["valid"].at[b // 2:].set(False)
            return orig(params, state, dict(batch, valid=keep), tables, cfg)

        engine.step_loss = half
        try:
            yield
        finally:
            engine.step_loss = orig
    elif name == "frozen":
        orig = Optimizer.apply
        Optimizer.apply = lambda self, g, s, p: (p, s)
        try:
            yield
        finally:
            Optimizer.apply = orig
    else:
        yield


def worst_leaves(prog, ref, n=3):
    """The leaves with the largest gaps of gradient norm and of change,
    with the number of leaves the change leaves out."""
    import numpy as np

    out = {}
    g_ref = ref["norms"]["grad"]
    med_g = float(np.median(list(g_ref.values())))
    moved = {k for k, v in g_ref.items() if v >= 1e-3 * med_g}
    for what in ("grad", "change"):
        r, p = ref["norms"][what], prog["norms"][what]
        keys = list(r) if what == "grad" else sorted(moved)
        med = float(np.median([r[k] for k in keys]))
        gaps = sorted(((abs(p[k] - r[k]) / max(r[k], med, 1e-30), k)
                       for k in keys), reverse=True)[:n]
        out[what] = [[k, g] for g, k in gaps]
    out["change_left_out"] = sorted(set(g_ref) - moved)
    return out


def curve(prog_losses, ref_losses):
    import numpy as np

    k = len(ref_losses)
    gap = np.abs(prog_losses[:k] - ref_losses) / np.abs(ref_losses)
    at = [i for i in (0, 1, 2, 4, 9, 19, 49, 99, 199, k - 1) if i < k]
    return {str(i + 1): float(gap[i]) for i in at}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = run.spec()
    w, conf, traffic = run.cell_files(bench, args.workload)
    devices = run.accelerators(w["chips"])
    run.compile_cache()
    kind = run.kind_of(traffic)
    os.makedirs(args.out, exist_ok=True)
    path = Path(args.out) / f"{args.workload}.jsonl"
    jobs = [("program", s) for s in args.seeds]
    jobs += [("control", s) for s in args.control_seeds]
    jobs += [(f"fault.{f}", s) for f in ("half_batch", "frozen")
             for s in args.fault_seeds]
    with open(path, "a") as out:
        for what, seed in jobs:
            t0 = time.perf_counter()
            cell = run.Cell(conf=conf, traffic=traffic, seed=seed,
                            seconds=0.0, devices=devices)
            f = what.split(".", 1)[1] if what.startswith("fault.") else None
            with fault(f):
                nums, prog, ref = kind.reading(cell,
                                               control=what == "control")
            line = {"workload": args.workload, "reading": what,
                    "seed": seed, "numbers": nums,
                    "curve": curve(prog["losses"], ref["losses"]),
                    "leaves": worst_leaves(prog, ref),
                    "seconds": time.perf_counter() - t0}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
