"""Device time by the program's stage scopes and idle gaps labelled by its
host spans (``bench/scopes.py``): on synthetic events, and on a trace
recorded on a TPU v5e with its compiled program's scope map."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (BENCH, BENCH / "traffic"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import record_trace  # noqa: E402
import scopes  # noqa: E402

HLO = """\
HloModule jit_scan_train_epoch, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.3 = f32[8]{0} tanh(f32[8]{0} %p), metadata={op_type="tanh" \
op_name="jit(scan_train_epoch)/while/body/jvp(tig.memory.flush)/tanh"}
}

%body (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, \
calls=%fused_computation.1, metadata={op_type="tanh" \
op_name="jit(scan_train_epoch)/while/body/jvp(tig.memory.flush)/tanh"}
  %add_any.2 = f32[8]{0} add(f32[8]{0} %fusion.1, f32[8]{0} %c), \
metadata={op_type="add_any" op_name="jit(scan_train_epoch)/while/body/\
transpose(jvp(tig.embed))/add_any"}
  %neighbor_sample.9 = s32[8]{0} custom-call(f32[8]{0} %c), \
custom_call_target="tpu_custom_call", metadata={op_type="pallas_call" \
op_name="jit(scan_train_epoch)/while/body/tig.sample/neighbor_sample/\
pallas_call"}
  %select.4 = f32[8]{0} select(pred[8]{0} %p, f32[8]{0} %c, f32[8]{0} %c), \
metadata={op_type="select_n" op_name="jit(scan_train_epoch)/while/body/\
select_n"}
  ROOT %copy.5 = f32[8]{0} copy(f32[8]{0} %select.4)
}
"""


def test_scope_map_reads_op_names_of_ops_that_run():
    m = scopes.scope_map(HLO)
    assert m == {"fusion.1": "jvp(tig.memory.flush)",
                 "add_any.2": "transpose(jvp(tig.embed))",
                 "neighbor_sample.9": "tig.sample"}
    assert scopes.split_path(m["add_any.2"]) == ("tig.embed", "gradient")
    assert scopes.split_path(m["fusion.1"]) == ("tig.memory.flush",
                                                "forward")
    assert scopes.split_path("") == ("", "forward")


@pytest.mark.parametrize("path, stage", [
    ("tig.sample", "sample_device_ms"),
    ("jvp(tig.memory.flush)", "memory_device_ms"),
    ("transpose(jvp(tig.memory.flush))", "memory_device_ms"),
    ("jvp(tig.memory.stash)", "memory_device_ms"),
    ("transpose(jvp(tig.embed))", "embed_device_ms"),
    ("jvp(tig.decode)", "decode_device_ms"),
    ("tig.optimizer", "optimizer_device_ms"),
    ("", "unscoped_device_ms"),
    ("jvp(tig.embedding)", "unscoped_device_ms"),
])
def test_each_scope_path_has_one_stage(path, stage):
    assert scopes.stage_of(path) == stage


MAIN, WORKER = ("/host:CPU", 7), ("/host:CPU", 8)
SCOPES = {"fusion.1": "jvp(tig.memory.flush)",
          "fusion.2": "transpose(jvp(tig.memory.flush))",
          "add_any.3": "transpose(jvp(tig.embed))"}


def synthetic_events():
    """One epoch program run (1000-1600) beside a loss fetch (``jit__mean``,
    1700-1760) whose op has the same name as one of the epoch program's.
    Idle: 1000-1100 (plan wait), 1600-1700 (the loss fetch, inside
    ``bench.epoch``), 1760-2000 (the worker's plan alone, 1760-1900)."""
    prog, mean = "jit_scan_train_epoch", "jit__mean"
    return {
        "devices": {0: [
            ("fusion.1", 1100, 1300, prog), ("fusion.2", 1300, 1400, prog),
            ("add_any.3", 1400, 1450, prog), ("copy.4", 1450, 1600, prog),
            ("fusion.1", 1700, 1760, mean)]},
        "spans": [
            ("bench.window", 1000, 2000, MAIN),
            ("bench.plan_wait", 1000, 1100, MAIN),
            ("tig.plan_wait", 1010, 1100, MAIN),
            ("bench.epoch", 1100, 1760, MAIN),
            ("tig.dispatch", 1100, 1150, MAIN),
            ("tig.fetch", 1150, 1760, MAIN),
            ("tig.plan", 1500, 1900, WORKER),
        ]}


def test_reduce_keeps_one_program_and_partitions_it_by_stage():
    red = scopes.reduce(synthetic_events(), SCOPES)
    d = red["devices"][0]
    assert red["window_ns"] == 1000
    assert d["program_op_ns"] == {"fusion.1": 200, "fusion.2": 100,
                                  "add_any.3": 50, "copy.4": 150}
    assert d["program_busy_ns"] == 500
    assert d["stage_ns"] == {
        "sample_device_ms": 0, "memory_device_ms": 300,
        "embed_device_ms": 50, "decode_device_ms": 0,
        "optimizer_device_ms": 0, "unscoped_device_ms": 150}
    assert sum(d["stage_ns"].values()) == sum(d["program_op_ns"].values())
    assert d["scope_ns"] == {("tig.memory.flush", "forward"): 200,
                             ("tig.memory.flush", "gradient"): 100,
                             ("tig.embed", "gradient"): 50,
                             ("", "forward"): 150}
    ms = scopes.stage_ms(red, steps=2)
    assert ms["memory_device_ms"] == 300 / 1e6 / 2


def test_gap_labels_take_the_shortest_span_on_the_window_thread():
    d = scopes.reduce(synthetic_events(), SCOPES)["devices"][0]
    assert sorted(d["gaps"]) == sorted([
        ("tig.plan_wait", 100),   # 90 of 100 covered: shortest >= half
        ("tig.fetch", 100),       # nested in bench.epoch
        ("tig.plan", 240)])       # the worker's span covers most of it
    bd = scopes.breakdown(scopes.reduce(synthetic_events(), SCOPES))
    assert bd["device_scopes"][0] == ["tig.memory.flush", "forward", 200e-9]
    assert bd["idle_gaps"][0] == ["tig.plan", 240e-9]


def test_a_gap_half_covered_by_no_span_of_the_window_thread():
    spans = [("bench.epoch", 0, 30, MAIN), ("tig.fetch", 0, 40, MAIN),
             ("tig.stage", 0, 100, WORKER)]
    # bench.epoch and tig.fetch cover 30 and 40 of 100: under half
    assert scopes.label_gap(0, 100, spans, MAIN) == "tig.stage"
    assert scopes.label_gap(0, 70, spans, MAIN) == "tig.fetch"
    assert scopes.label_gap(200, 300, spans, MAIN) == "no span"


DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "tgn_small_scoped.xplane.pb.gz"
KERNEL_STAGES = {"neighbor_sample": ("tig.sample", "forward"),
                 "fused_flush": ("tig.memory.flush", "forward"),
                 "temporal_attn": ("tig.embed", "forward"),
                 "temporal_attn_bwd": ("tig.embed", "gradient")}


def test_stages_on_a_recorded_chip_trace():
    """A trace recorded on a TPU v5e (``bench/record_trace.py --nodes 1000
    --edges 2000``): one 7-step epoch of the TGN cell's path at its
    widths, with the program's scopes and spans."""
    smap = json.loads((DATA / "tgn_small_scoped.scopes.json").read_text())
    red = scopes.reduce(scopes.load(str(FIXTURE)), smap)
    assert list(red["devices"]) == [0]
    d = red["devices"][0]
    total = sum(d["program_op_ns"].values())
    assert total > 0
    # every op of the epoch program is in exactly one stage
    assert sum(d["stage_ns"].values()) == pytest.approx(total, rel=1e-6)
    ms = scopes.stage_ms(red, steps=7)
    assert sum(ms.values()) == pytest.approx(total / 1e6 / 7, rel=1e-6)
    assert all(v > 0 for k, v in ms.items() if k != "decode_device_ms")
    assert d["program_busy_ns"] <= total
    for kernel, where in KERNEL_STAGES.items():
        name = f"{kernel}.9"
        assert name in d["program_op_ns"]
        assert scopes.split_path(smap[name]) == where
    labels = [g[0] for g in d["gaps"]]
    assert any(lab.startswith("tig.") for lab in labels), d["gaps"]


def test_busy_time_reads_as_trace_reduce_reads_it():
    """One load of the recorded trace gives the device's busy time as the
    accepted ``step_device_ms`` reads it; without a scope map (a program
    that names no stage) every op of the epoch program is unscoped."""
    import trace_reduce

    red = scopes.reduce(scopes.load(str(FIXTURE)), {})
    old = trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))
    assert {n: d["busy_ns"] for n, d in red["devices"].items()} == {
        n: d["busy_ns"] for n, d in old["devices"].items()}
    d = red["devices"][0]
    assert d["stage_ns"]["unscoped_device_ms"] == sum(
        d["program_op_ns"].values())
    assert d["scope_ns"].keys() == {("", "forward")}


def test_record_trace_keeps_the_trace_and_the_scope_map(tmp_path):
    """The recorder at a small size on the CPU (XLA kernels): the trace
    holds the harness's and the program's host spans, the map every stage
    scope.  The CPU trace has no device plane, so no stage times."""
    import jax

    conf, traffic = record_trace.cell_files("tgn-taobao.train", 100, 3000)
    # the cell's set-up sets the process's matmul precision: restore it
    precision = jax.config.jax_default_matmul_precision
    try:
        out = record_trace.record(dict(conf, use_pallas=False), traffic,
                                  2**31 + 7, 0.0, tmp_path, "small")
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    assert out["epochs"] == out["traced_epochs"] == 1
    assert out["stage_ms"] == {} and out["program_busy_ms"] is None
    smap = json.loads((tmp_path / "small.scopes.json").read_text())
    assert {scopes.split_path(p)[0] for p in smap.values()} == {
        "tig.sample", "tig.memory.flush", "tig.memory.stash", "tig.embed",
        "tig.decode", "tig.optimizer"}
    events = scopes.load(str(tmp_path / "small.xplane.pb.gz"))
    assert {n for n, *_ in events["spans"]} >= {
        "bench.window", "bench.plan_wait", "bench.epoch", "tig.plan",
        "tig.stage", "tig.plan_wait", "tig.reset", "tig.dispatch",
        "tig.fetch"}
