"""Shared arithmetic of the metric readers in ``bench/metrics/``.

A reader gets the run's context: the traffic kind's counts and host
timings, the cell's files, the device kind and its peaks, and (with
``--trace 1``) the reduced trace.  It returns a number, or None where the
run has nothing to read, and the harness then leaves the metric out."""

from __future__ import annotations

import re

import trace_reduce
import work


def share(part: float, whole: float):
    return 100.0 * part / whole if whole > 0 else None


def mean_device(ctx: dict, key: str):
    red = ctx.get("trace")
    if not red or not red["devices"]:
        return None
    vals = [d[key] for d in red["devices"].values()]
    return sum(vals) / len(vals)


def kernel_ops(ctx: dict, kernel: str) -> tuple[int, int, float]:
    """Total device time (ns), launch count and HBM share of the data
    (``trace_reduce.hbm_share``) of a kernel, over the traced devices.
    Ops are matched by the kernel's name, alone or with a numeric
    suffix."""
    red = ctx["trace"]
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)*$")
    ns = count = 0
    hbm = []
    for d in red["devices"].values():
        for name, t in d["op_ns"].items():
            if pat.match(name):
                ns += t
                count += d["op_count"][name]
                hbm.append(trace_reduce.hbm_share(red["text"].get(name, "")))
    return ns, count, (min(hbm) if hbm else 1.0)


def kernel_roofline(ctx: dict, kernel: str):
    """Share of the kernel's device time that its demanded work would take
    at the chip's peak: the larger of FLOPs over peak FLOP/s and demanded
    bytes over HBM bandwidth, where the bytes are scaled by the share of
    the kernel's data that lives in HBM (operands XLA has staged on chip
    are read outside the kernel's time)."""
    if not ctx.get("trace") or "peaks" not in ctx:
        return None
    ns, launches, hbm = kernel_ops(ctx, kernel)
    if not launches or ns <= 0:
        return None
    per = work.step_kernels(model(ctx), ctx["total_events"])[kernel]
    per = work.Work(flops=per.flops, bytes=per.bytes * hbm)
    return share(launches * per.seconds(ctx["peaks"]), ns / 1e9)


def model(ctx: dict) -> dict:
    c = ctx["conf"]
    return {k: c[k] for k in ("flavor", "dim", "dim_time", "d_e", "d_n",
                              "num_neighbors", "n_heads", "batch_size")}
