"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix's ``kind`` names
the module under ``bench/traffic/`` that drives the program.  Every metric
is read by ``bench/metrics/<metric name>.py``; the limits of the
correctness check are ``bench/limits/<workload>.json``.  So a later change
adds a cell, a mix or a metric by adding files and entries, never by
editing one.

A kind module ``bench/traffic/<kind>.py`` owns everything that is
particular to its kind, its correctness check included, and provides:

* ``run(cell) -> dict``: one run of the cell (set-up, the window, then
  the check against the plain reference); the dict holds what the
  metric readers read, ``numbers`` (the check's numbers by name, as
  ``bench/limits/<workload>.json`` names them), ``steps``,
  ``nonfinite_steps`` and ``memory_peak_bytes``;
* ``small(conf, traffic) -> (conf, traffic)``: the cell at its own widths
  and a size a CPU test holds;
* ``reading(cell, control=False) -> (numbers, prog, ref)``: the check of
  ``run`` with no window, on the program's first steps; with ``control``
  the reference at the precision below the configuration's takes the
  program's place.  ``prog`` and ``ref`` are ``{"rows", "losses",
  "norms"}``: the plan rows the reference batches itself, the per-step
  losses, and per-leaf ``{"grad", "change"}`` norms;
* ``wrong_plan()``: a context manager that plants a plan the check must
  refuse: its number ``plan_mismatch`` counts the entries that differ.

The kind runs on ``cell.devices``: as many as the cell's ``chips``, in
JAX's order (a one-device kind runs on the first, JAX's default).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records part of the window and the result
carries the per-layer metrics.  The last line of standard output is one
JSON object; the numbers the check compared, each beside its limit, are
the last lines of standard error and the last key of that object.  A run
exits non-zero, with no result, when JAX finds no accelerator or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import re
import sys
import tempfile
from pathlib import Path
from typing import Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (BENCH, BENCH / "traffic", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """What a traffic kind gets: the files of the cell and the run's
    arguments, plus hooks into the harness."""

    conf: dict
    traffic: dict
    seed: int
    seconds: float
    trace_dir: Optional[str] = None
    kernel_backend: str = "auto"
    compiles: Callable[[], int] = lambda: 0
    devices: list = dataclasses.field(default_factory=list)

    def memory_peak(self) -> Optional[int]:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats()
            if stats and "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(ROOT / confs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return w, conf, traffic


def kind_of(traffic: dict):
    """The module of the traffic mix's kind, ``bench/traffic/<kind>.py``."""
    return load_module(BENCH / "traffic" / f"{traffic['kind']}.py")


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def accelerators(chips: int) -> list:
    """The devices the cell runs on; exits when JAX finds no accelerator
    or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench: JAX finds no accelerator")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def compile_cache() -> None:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout.  Every
    program is cached, however short its compile.  Source paths in the
    programs' locations are made relative to the checkout: a Pallas
    kernel's serialized body keeps them, and they are part of the cache
    key, so a checkout elsewhere would otherwise miss."""
    import jax

    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@contextlib.contextmanager
def compile_counter():
    """Yields a count of JAX backend compilations (cache loads included)
    made inside the block."""
    import jax

    n = [0]

    def on_event(event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield lambda: n[0]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            devices: list, kernel_backend: str = "auto",
            bench: Optional[dict] = None,
            files: Optional[tuple] = None) -> dict:
    """One run of a cell on ``devices`` (found by the caller); returns the
    result object.  ``files`` replaces the cell's (workload entry,
    configuration, traffic), as tests at a small size do."""
    import trace_reduce as tr
    from peaks import peaks_of

    bench = bench or spec()
    _w, conf, traffic = files or cell_files(bench, workload)
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    kind = kind_of(traffic)
    with tempfile.TemporaryDirectory() as tdir, \
            compile_counter() as compiles:
        cell = Cell(conf=conf, traffic=traffic, seed=seed,
                    seconds=seconds, trace_dir=tdir if trace else None,
                    kernel_backend=kernel_backend, compiles=compiles,
                    devices=devices)
        ctx = kind.run(cell)
        red = None
        if trace:
            red = tr.reduce(tr.load(tr.find_xplane(tdir)))
    kind_name = devices[0].device_kind
    ctx.update(conf=conf, traffic=traffic, trace=red,
               device_kind=kind_name, chips=len(devices))
    if devices[0].platform != "cpu":
        ctx["peaks"] = peaks_of(kind_name)

    metrics = {}
    for m in metrics_of(bench, workload, trace):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = {name: {"value": ctx["numbers"].get(name, math.inf),
                     "limit": lim} for name, lim in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and ctx["nonfinite_steps"] == 0
    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": len(devices),
              "memory_peak_bytes": ctx["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": ctx["steps"],
           "failed": ctx["nonfinite_steps"], "metrics": metrics,
           "device": device}
    if red is not None:
        busy = [d["busy_ns"] for d in red["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = red["window_ns"] / 1e9
        out["breakdown"] = tr.breakdown(red)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec()
    w, _conf, _traffic = cell_files(bench, args.workload)
    devices = accelerators(w["chips"])
    compile_cache()
    out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                  devices=devices, bench=bench)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
