"""Profiler capture and the reduction from a device trace to numbers.

The JAX profiler writes an XSpace (``*.xplane.pb``).  Device planes are
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed operation, named by its HLO instruction text, for every
iteration of a scanned program, plus one event for the ``while`` op that
spans the loop.  The
benchmark's own host spans (``jax.profiler.TraceAnnotation`` with names
that start with ``bench.``) sit on the host plane on the same clock.

``reduce`` takes one window, ``[start, end)`` of the ``bench.window``
span, and returns per device: busy time (the union of op intervals),
per-op-name time and count, the idle gaps labelled by the host span that
covers most of each, and the part of collective time during which no
other op runs.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_TEXT = re.compile(r"^%?(\S+) = ")
SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|f(?:16|32|64)|bf16)"
                   r"\[([\d,]*)\]\{([^}]*)\}")
ATTRIBUTES = re.compile(r"\)\s*,\s*[a-z_]+=")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    return paths[0]


def profile_options():
    """Profiler settings: no Python function tracing and no HLO protos, so
    a trace of a few seconds stays small; host spans are kept."""
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's name (``fusion.12``,
    ``neighbor_sample.3``) out of the instruction text the trace holds."""
    m = OP_TEXT.match(text)
    return m.group(1) if m else text


def leaves(ops):
    """Drop container events (a ``while`` spanning its loop's ops): an
    event that wholly holds the event after it in start order."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or not (ops[i + 1][1] < o[2]
                                         and ops[i + 1][2] <= o[2])]


def load(path: str) -> dict:
    """Events of an XSpace file (gzipped where the name ends in ``.gz``):
    ``{"devices": {n: [(name, start, end)]}, "spans": [(name, start,
    end)], "text": {name: instruction text}}``, times in ns, device ops
    without their containers."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans, text = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = []
                for e in line.events:
                    name = op_name(e.name)
                    text.setdefault(name, e.name)
                    ops.append((name, e.start_ns, e.end_ns))
                devices[int(m.group(1))] = leaves(ops)
            elif not m:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans, "text": text}


def hbm_share(text: str) -> float:
    """Share of the bytes of an op's operands and results (by the shapes in
    its instruction text) that live in HBM, not in on-chip memory (a
    layout with ``S(n)``, n > 0): data XLA stages on chip ahead of an op is
    read outside the op's own time."""
    hbm = total = 0
    text = ATTRIBUTES.split(text, maxsplit=1)[0]
    for dtype, dims, layout in SHAPE.findall(text):
        n = DTYPE_BYTES.get(dtype, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
        if not re.search(r"S\([1-9]", layout):
            hbm += n
    return hbm / total if total else 1.0


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Parts of the (sorted, disjoint) intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(events: dict) -> tuple[int, int]:
    w = [(s, e) for n, s, e in events["spans"] if n == WINDOW_SPAN]
    if not w:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    return min(s for s, _ in w), max(e for _, e in w)


def reduce(events: dict, top: int = 10) -> dict:
    lo, hi = window_of(events)
    spans = [(n, s, e) for n, s, e in events["spans"] if n != WINDOW_SPAN]
    per_device = {}
    for dev, ops in sorted(events["devices"].items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if min(e, hi) > max(s, lo)]
        busy = _union((s, e) for _, s, e in ops)
        by_name, count = defaultdict(int), defaultdict(int)
        for n, s, e in ops:
            by_name[n] += e - s
            count[n] += 1
        coll = _union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        compute = _union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        gaps = _minus([(lo, hi)], busy)
        labelled = []
        for s, e in gaps:
            cover = defaultdict(int)
            for n, hs, he in spans:
                cover[n] += max(0, min(e, he) - max(s, hs))
            label = max(cover, key=cover.get) if cover and max(
                cover.values()) > 0 else "no bench span"
            labelled.append((label, e - s))
        per_device[dev] = {
            "busy_ns": _length(busy),
            "op_ns": dict(by_name),
            "op_count": dict(count),
            "collective_ns": _length(coll),
            "collective_exposed_ns": _length(_minus(coll, compute)),
            "gaps": sorted(labelled, key=lambda g: -g[1])[:top],
        }
    return {"window_ns": hi - lo, "devices": per_device,
            "text": events.get("text", {})}


def breakdown(red: dict, top: int = 10) -> dict:
    """Device ops by total time and the longest idle gaps, in seconds, over
    all devices of the window."""
    ops, gaps = defaultdict(int), []
    for d in red["devices"].values():
        for n, ns in d["op_ns"].items():
            ops[n] += ns
        gaps.extend(d["gaps"])
    top_ops = sorted(ops.items(), key=lambda x: -x[1])[:top]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in top_ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in top_gaps]}
