"""The benchmark's correctness check on the CPU at a small size: a sound
run of each cell's path comes out correct; the control (the reference at
the precision below the configuration's in the program's place), each
fault a training cell can have (half of the batch left out; an optimizer
step that returns its state unchanged) and a plan the check must refuse
come out not correct under the cell's own limits.  The runs skip the
harness's look for a chip and drive the rest of a run, with the Pallas
kernels in interpret mode.

Each cell reaches its kind only through its traffic file, and is cut to
a CPU size by its kind's ``small``.  A cell on one chip runs its cases in
this process; a cell on more runs each in a child process with as many
CPU devices (``python tests/bench/test_bench_check.py <case> <workload>
<chips>``)."""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (BENCH, BENCH / "traffic"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import calibrate  # noqa: E402
import run  # noqa: E402
import train_epochs  # noqa: E402

# every cell: the benchmark's, and the JODIE cell whose files and limits
# are kept for a later entry (PERF.md)
CELLS = {w["name"]: w for w in run.spec()["workloads"]}
CELLS.setdefault("jodie-wikipedia.train", {
    "name": "jodie-wikipedia.train", "config": "jodie-wikipedia",
    "traffic": "train-epochs-full", "chips": 1})
WORKLOADS = sorted(CELLS)
SEED = 2**31 + 7


def small_files(workload):
    """The cell's (workload entry, configuration, traffic), at its own
    widths and the size its kind's ``small`` gives."""
    w = CELLS[workload]
    conf = run.load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = run.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return (w, *run.kind_of(traffic).small(conf, traffic))


def small_run(workload, chips, trace=False):
    # every metric is read in every cell; a reader with nothing to read
    # gives nothing
    spec = run.spec()
    spec["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in spec["per_layer"]]
    return run.execute(workload, SEED, 0.5, trace,
                       devices=jax.devices()[:chips],
                       kernel_backend="interpret", bench=spec,
                       files=small_files(workload))


def small_reading(workload, chips, control=False):
    _w, conf, traffic = small_files(workload)
    cell = run.Cell(conf=conf, traffic=traffic, seed=SEED, seconds=0.0,
                    kernel_backend="interpret",
                    devices=jax.devices()[:chips])
    return run.kind_of(traffic).reading(cell, control=control)[0]


def in_cell(case, workload, *args, chips=None):
    """Runs ``case(workload, chips, *args)`` at the cell's chip count: in
    this process where that is one, else in a child process with that many
    CPU devices.  Returns what the case returns."""
    chips = chips or CELLS[workload]["chips"]
    if chips == 1:
        return case(workload, 1, *args)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip())
    proc = subprocess.run(
        [sys.executable, __file__, case.__name__, workload, str(chips),
         *map(str, args)],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def sound_run_is_correct(workload, chips):
    out = small_run(workload, chips, trace=True)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["compiles_in_window"]["value"] == 0


def fault_is_not_correct(workload, chips, fault):
    with calibrate.fault(fault):
        out = small_run(workload, chips)
    assert not out["correct"], out["checks"]


def control_is_not_correct(workload, chips):
    numbers = small_reading(workload, chips, control=True)
    limits = run.load_json(BENCH / "limits" / f"{workload}.json")
    assert any(numbers[k] > lim for k, lim in limits.items()), numbers


def wrong_plan_is_not_correct(workload, chips):
    traffic = small_files(workload)[2]
    with run.kind_of(traffic).wrong_plan():
        out = small_run(workload, chips)
    assert out["checks"]["plan_mismatch"]["value"] > 0
    assert not out["correct"]


def reading_is_the_run_s_check(workload, chips):
    checks = small_run(workload, chips)["checks"]
    assert small_reading(workload, chips) == {
        k: c["value"] for k, c in checks.items()}


def checks_seen(workload, chips):
    """A sound run's checks, and how many devices the kind was given."""
    seen = []
    kind_of = run.kind_of

    def spied(traffic):
        kind = kind_of(traffic)
        kind_run = kind.run

        def spy(cell):
            seen.append(len(cell.devices))
            return kind_run(cell)

        kind.run = spy
        return kind

    run.kind_of = spied
    try:
        out = small_run(workload, chips)
    finally:
        run.kind_of = kind_of
    return {"seen": seen, "count": out["device"]["count"],
            "checks": out["checks"]}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    in_cell(sound_run_is_correct, workload)


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", ["half_batch", "frozen"])
def test_fault_is_not_correct(workload, fault):
    in_cell(fault_is_not_correct, workload, fault)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    in_cell(control_is_not_correct, workload)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_wrong_negatives_are_not_correct(workload):
    """The kind's ``wrong_plan`` (for ``train_epochs``, negatives that are
    not the seed's draw) fails the plan comparison."""
    in_cell(wrong_plan_is_not_correct, workload)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reading_gives_the_run_s_check_numbers(workload):
    """``bench/calibrate.py`` sets the limits from the kind's ``reading``:
    for the same seed it gives the numbers a run checks."""
    in_cell(reading_is_the_run_s_check, workload)


def test_a_cell_on_two_chips_gets_two_devices():
    """A cell that asks for two chips runs its case in a child process
    with two CPU devices; the kind is given both, and (a one-device kind)
    reads the checks it reads here on one."""
    child = in_cell(checks_seen, "tgn-taobao.train", chips=2)
    assert child["seen"] == [2] and child["count"] == 2
    here = checks_seen("tgn-taobao.train", 1)
    assert here["seen"] == [1]
    assert child["checks"] == here["checks"]


def test_each_kind_brings_its_check():
    """Every kind module provides the harness's contract, and the
    calibration tool names no kind: it reaches one through the cell's
    traffic file."""
    kinds = sorted(p.stem for p in (BENCH / "traffic").glob("*.py"))
    assert kinds
    for name in kinds:
        mod = run.load_module(BENCH / "traffic" / f"{name}.py")
        for attr in ("run", "small", "reading", "wrong_plan"):
            assert callable(getattr(mod, attr, None)), (name, attr)
    tree = ast.parse((BENCH / "calibrate.py").read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
        elif isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            names.add(node.value)
    assert not any(k in n for k in kinds for n in names), kinds


def _norms(grad, change):
    return {"grad": grad, "change": change}


def test_a_scaled_leaf_gradient_is_seen():
    """Adam's step hides a leaf's gradient scaled by a constant; the
    gradient norm by the worst leaf does not."""
    ref = {"losses": np.ones(3), "rows": {},
           "norms": _norms({"a": 1.0, "b": 2.0, "c": 3.0},
                           {"a": 0.1, "b": 0.1, "c": 0.1})}
    prog = {"losses": np.ones(3), "rows": {},
            "norms": _norms({"a": 1.0, "b": 4.0, "c": 3.0},
                            {"a": 0.1, "b": 0.1, "c": 0.1})}
    for key in ("src", "dst", "neg", "t", "eidx", "valid"):
        ref["rows"][key] = prog["rows"][key] = np.zeros((3, 2))
    nums = train_epochs.compare(prog, ref)
    assert nums["grad_gap.first3"] == 1.0
    assert nums["change_gap.first3"] == 0.0 and nums["loss_gap.first3"] == 0


def test_leaves_nought_to_rounding_stay_out_of_the_change():
    """A leaf whose reference gradient is under a thousandth of the median
    leaf's (a key's bias under softmax) moves by round-off alone: its
    change is not compared; the gap is taken against the median leaf."""
    ref = {"losses": np.ones(3), "rows": {},
           "norms": _norms({"w": 1.0, "v": 1.0, "k_bias": 1e-9},
                           {"w": 0.2, "v": 0.4, "k_bias": 1e-6})}
    prog = {"losses": np.ones(3), "rows": ref["rows"],
            "norms": _norms({"w": 1.0, "v": 1.0, "k_bias": 3e-9},
                            {"w": 0.2, "v": 0.4, "k_bias": 5e-3})}
    for key in ("src", "dst", "neg", "t", "eidx", "valid"):
        ref["rows"][key] = np.zeros((3, 2))
    nums = train_epochs.compare(prog, ref)
    assert nums["change_gap.first3"] == 0.0
    assert nums["grad_gap.first3"] == pytest.approx(2e-9)


if __name__ == "__main__":
    case, workload, chips, *rest = sys.argv[1:]
    if len(jax.devices()) < int(chips):
        raise SystemExit(f"{chips} devices asked for, "
                         f"{len(jax.devices())} found")
    print(json.dumps(globals()[case](workload, int(chips), *rest)))
