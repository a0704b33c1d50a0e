"""The benchmark's correctness check on the CPU at a small size: a sound
run of each cell's path comes out correct; the control (the reference at
three bfloat16 passes in the program's place) and each fault a training
cell can have (half of the batch left out; an optimizer step that returns
its state unchanged) come out not correct under the cell's own limits.
The runs skip the harness's look for a chip and drive the rest of a run,
with the Pallas kernels in interpret mode."""

from __future__ import annotations

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

# (configuration, traffic) of every cell: the benchmark's, and the JODIE
# cell whose files and limits are kept for a later entry (PERF.md)
CELLS = {w["name"]: (w["config"], w["traffic"])
         for w in run.spec()["workloads"]}
CELLS.setdefault("jodie-wikipedia.train",
                 ("jodie-wikipedia", "train-epochs-full"))
WORKLOADS = sorted(CELLS)
SEED = 2**31 + 7


def small_files(workload):
    """The cell at its own widths, with 100 nodes and a 3,000-edge
    stream."""
    config, mix = CELLS[workload]
    conf = run.load_json(BENCH / "configs" / f"{config}.json")
    traffic = run.load_json(BENCH / "traffic" / f"{mix}.json")
    return ({"name": workload}, dict(conf, num_users=60, num_items=40),
            dict(traffic, stream_edges=3000, trace_epochs=1))


def small_run(workload, trace=False):
    # every metric is read in every cell; a reader with nothing to read
    # gives nothing
    spec = run.spec()
    spec["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in spec["per_layer"]]
    return run.execute(workload, SEED, 0.5, trace, devices=jax.devices(),
                       kernel_backend="interpret", bench=spec,
                       files=small_files(workload))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = small_run(workload, trace=True)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", ["half_batch", "frozen"])
def test_fault_is_not_correct(workload, fault):
    with calibrate.fault(fault):
        out = small_run(workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    _w, conf, traffic = small_files(workload)
    numbers = calibrate.control_reading(conf, traffic, SEED, "interpret")[0]
    limits = run.load_json(BENCH / "limits" / f"{workload}.json")
    assert any(numbers[k] > lim for k, lim in limits.items()), numbers


@pytest.mark.parametrize("workload", WORKLOADS)
def test_wrong_negatives_are_not_correct(workload, monkeypatch):
    """The reference draws the epoch's negatives itself, so a plan whose
    negatives are not the seed's draw fails the plan comparison."""
    from repro.tig import batching

    orig = batching.build_batch_program

    def shifted(*a, **kw):
        batches, final = orig(*a, **kw)
        return dict(batches, neg=np.roll(batches["neg"], 1, axis=1)), final

    monkeypatch.setattr(batching, "build_batch_program", shifted)
    out = small_run(workload)
    assert out["checks"]["plan_mismatch"]["value"] > 0
    assert not out["correct"]


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
