"""The benchmark's yardstick on the CPU: its copy of the stream generator,
the peak table, the work models against hand-computed values, the trace
reduction, and the data-driven layout of ``BENCHMARK.json``."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (BENCH, BENCH / "traffic"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import peaks  # noqa: E402
import streamgen  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402


def test_stream_copy_reproduces_the_taobao_s_preset():
    from repro.tig.data import PRESETS, synthetic_tig

    p = PRESETS["taobao-s"]
    g = synthetic_tig("taobao-s", seed=0)
    s = streamgen.generate(
        seed=0, num_users=p["num_users"], num_items=p["num_items"],
        num_edges=p["num_edges"], d_e=p["d_e"], d_n=p["d_n"],
        labeled=p["labeled"], classes=p["classes"], zipf_users=1.6,
        zipf_items=1.4, repeat_prob=0.6)
    for name in ("src", "dst", "t", "edge_feat", "labels"):
        np.testing.assert_array_equal(getattr(s, name), getattr(g, name))
    assert s.num_nodes == g.num_nodes and s.d_n == g.dim_node


def test_peak_table_is_keyed_by_device_kind():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v9 imaginary")


CFG = {"flavor": "tgn", "dim": 172, "dim_time": 100, "d_e": 4, "d_n": 100,
       "num_neighbors": 10, "n_heads": 2, "batch_size": 200}


def test_kernel_work_by_hand():
    # neighbor sample: 600 rows, K=10, 140,010 events -> 18 bisection steps
    ns = work.neighbor_sample(600, 10, 140_010)
    assert ns.flops == 0
    assert ns.bytes == 600 * (12 + 18 * 4 + 10 * 12) + 600 * 10 * 12
    # fused flush: 400 rows, d_msg 448, d_mem 172
    ff = work.fused_flush(400, 448, 172)
    assert ff.flops == 2 * 400 * (448 + 172) * 516
    weights = (448 * 516 + 172 * 516 + 2 * 516) * 4
    assert ff.bytes == (400 * 448 * 4 + 3 * 400 * 4 + 400 * 4
                        + 400 * 172 * 4 + 400 * 4 + weights
                        + 400 * 172 * 4 + 400 * 4 + 400 * 448 * 4)
    # attention: 600 rows, K=10, 2 heads of 86
    q, kv = 600 * 2 * 86 * 4, 600 * 10 * 2 * 86 * 4
    fa = work.temporal_attn(600, 10, 2, 86)
    assert fa.flops == 4 * 600 * 2 * 10 * 86
    assert fa.bytes == q + 2 * kv + 6000 + q
    fb = work.temporal_attn_bwd(600, 10, 2, 86)
    assert fb.flops == 8 * 600 * 2 * 10 * 86
    assert fb.bytes == 2 * q + 2 * kv + 6000 + q + 2 * kv


def test_step_flops_by_hand():
    flush = 2 * 400 * (448 + 172) * 516
    embed = (2 * 600 * 372 * 172 + 2 * 2 * 600 * 10 * 276 * 172
             + 4 * 600 * 10 * 172 + 2 * 600 * (372 + 172) * 172)
    dec = 2 * 400 * (344 * 172 + 172)
    assert work.step_flops(CFG) == 3 * (flush + embed + dec)
    jodie = dict(CFG, flavor="jodie", d_e=172, d_n=172)
    assert work.step_flops(jodie) == 3 * (
        2 * 400 * (616 + 172) * 172 + 2 * 600 * 344 * 172 + dec)


def test_peak_hbm_reads_the_larger_of_runtime_and_program():
    import run

    reader = run.load_module(BENCH / "metrics" / "peak_hbm_mb.py")
    ctx = {"memory_peak_bytes": 2_334_000_000, "program_bytes": 10_100_000_000}
    assert reader.read(ctx) == 10_100.0
    assert reader.read(dict(ctx, program_bytes=None)) == 2_334.0
    assert reader.read({"memory_peak_bytes": None}) is None


def test_roofline_time_takes_the_larger_bound():
    p = peaks.peaks_of("TPU v5 lite")
    assert work.Work(flops=197e12, bytes=1.0).seconds(p) == 1.0
    assert work.Work(flops=1.0, bytes=819e9).seconds(p) == 1.0


def test_reduce_on_synthetic_events():
    ev = {"devices": {0: [("fusion", 100, 200), ("fused_flush", 150, 250),
                          ("all-reduce.1", 240, 300),
                          ("fusion", 400, 450)]},
          "spans": [("bench.window", 100, 500), ("bench.plan_wait", 300, 420),
                    ("bench.epoch", 420, 500)]}
    red = trace_reduce.reduce(ev)
    d = red["devices"][0]
    assert red["window_ns"] == 400
    assert d["busy_ns"] == 200 + 50
    assert d["op_ns"] == {"fusion": 150, "fused_flush": 100,
                          "all-reduce.1": 60}
    assert d["collective_ns"] == 60 and d["collective_exposed_ns"] == 50
    assert d["gaps"] == [("bench.plan_wait", 100), ("bench.epoch", 50)]
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["fusion", 150e-9]


FIXTURE = Path(__file__).parent / "data" / "tgn_small.xplane.pb.gz"


def test_reduce_on_a_recorded_chip_trace():
    """A trace recorded on a TPU v5e: one 7-step epoch of the TGN cell's
    path at its widths, with 1,000 nodes and a 2,000-edge stream."""
    ev = trace_reduce.load(str(FIXTURE))
    assert list(ev["devices"]) == [0]
    names = {n for n, _, _ in ev["devices"][0]}
    assert not any(n.startswith("while") for n in names)
    red = trace_reduce.reduce(ev)
    d = red["devices"][0]
    assert 0 < d["busy_ns"] < red["window_ns"]
    for kernel in ("neighbor_sample", "fused_flush", "temporal_attn",
                   "temporal_attn_bwd"):
        assert d["op_count"][f"{kernel}.9"] == 7
    # every operand of the attention kernels was staged on chip; the flush
    # reads part of its data (the memory table, aliased) from HBM
    assert trace_reduce.hbm_share(red["text"]["temporal_attn.9"]) == 0.0
    assert 0 < trace_reduce.hbm_share(red["text"]["fused_flush.9"]) < 1
    assert {g[0] for g in d["gaps"]} <= {"bench.epoch", "bench.plan_wait"}


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_finds_its_files():
    b = _bench()
    for c in b["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
    for w in b["workloads"]:
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "traffic" / f"{traffic['kind']}.py").is_file()
        limits = json.loads(
            (BENCH / "limits" / f"{w['name']}.json").read_text())
        assert limits and all(v >= 0 for v in limits.values())
    for m in b["end_to_end"] + b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    assert b["command"] == ["python3", "bench/run.py"]
    for p in b["paths"]:
        assert (ROOT / p).is_dir()


def test_names_units_and_bounds_keep_the_contract():
    import re

    b = _bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    metrics = b["end_to_end"] + b["per_layer"]
    for entry in b["configs"] + b["workloads"] + metrics:
        assert name.match(entry["name"]), entry["name"]
    for m in metrics:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in b["workloads"]}
    assert 1 <= b["run_seconds"] <= 51
