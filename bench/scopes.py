"""The program's own names in a device trace: time per stage of the step,
and idle gaps labelled by what the host code was doing.

The scanned step names its stages with ``jax.named_scope``
(``tig.sample``, ``tig.memory.flush``, ``tig.embed``, ``tig.decode``,
``tig.memory.stash``, ``tig.optimizer``).  The names reach the compiled
program's ``op_name`` metadata, and not the trace: ``scope_map`` reads
them out of ``compiled.as_text()``, as ``{instruction: scope path}``.  A
scope path keeps the transform around the scope: ``jvp(tig.embed)`` is
the forward pass, ``transpose(jvp(tig.embed))`` its gradient.  An
instruction's name holds only within its program, so ``load`` also keeps
the program (``XLA Modules`` event) each device op ran in.

The epoch loop's host code marks spans (``tig.plan``, ``tig.stage``,
``tig.plan_wait``, ``tig.reset``, ``tig.dispatch``, ``tig.fetch``) beside
the harness's own ``bench.`` spans; ``load`` keeps both, with their
thread.  ``reduce`` then gives per device, inside the ``bench.window``
span: the busy time of all programs, the epoch program's op times, their
sums by stage (``STAGES``, whose buckets partition the program's ops), by
(scope, forward | gradient), and the idle gaps, each labelled by the
shortest span on the window's thread that covers at least half of it,
else by the span that covers most of it.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict

import trace_reduce as tr

EPOCH_PROGRAM = "jit_scan_train_epoch"
SPAN_PREFIXES = ("bench.", "tig.")
MODULES_LINE = "XLA Modules"
TOP = 10              # idle gaps kept per device and in the breakdown
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name="([^"]*)"')
SCOPE = re.compile(r"^((?:[\w-]+\()*)(tig\.[\w.]+)\)*$")
PROGRAM = re.compile(r"^([^(]+)\(")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
FUSION_BODY = re.compile(r"\bfusion\(.*?\bcalls=%?([\w.\-]+)")

# per-step metric -> the scope it reads, with the scopes under it
# (``tig.memory`` holds ``tig.memory.flush`` and ``tig.memory.stash``);
# ``unscoped`` takes every other op
STAGES = {
    "sample_device_ms": "tig.sample",
    "memory_device_ms": "tig.memory",
    "embed_device_ms": "tig.embed",
    "decode_device_ms": "tig.decode",
    "optimizer_device_ms": "tig.optimizer",
}
UNSCOPED = "unscoped_device_ms"


def scope_path(op_name: str) -> str:
    """The innermost ``tig.`` scope of an ``op_name`` with its transforms
    (``transpose(jvp(tig.memory.flush))``), or "" outside every scope."""
    found = ""
    for part in op_name.split("/"):
        if SCOPE.match(part):
            found = part
    return found


def scope_map(hlo_text: str) -> dict:
    """``{instruction: scope path}`` of a compiled program's text, for the
    instructions under a ``tig.`` scope that run as ops of their own: the
    bodies of fusions are left out (a fusion runs, and is traced, as one
    op named by its own instruction)."""
    fused = set(FUSION_BODY.findall(hlo_text))
    out, skip = {}, False
    for line in hlo_text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            skip = head.group(1) in fused
            continue
        m = INSTRUCTION.match(line)
        if m and not skip and (path := scope_path(m.group(2))):
            out[m.group(1)] = path
    return out


def split_path(path: str) -> tuple[str, str]:
    """(scope, "forward" | "gradient") of a scope path; ("", "forward")
    for no scope."""
    m = SCOPE.match(path)
    if not m:
        return "", "forward"
    return m.group(2), "gradient" if "transpose(" in m.group(1) else "forward"


def stage_of(path: str) -> str:
    scope, _ = split_path(path)
    for metric, stage in STAGES.items():
        if scope == stage or scope.startswith(stage + "."):
            return metric
    return UNSCOPED


def program_name(module_event: str) -> str:
    """``jit_scan_train_epoch`` out of ``jit_scan_train_epoch(1234)``."""
    m = PROGRAM.match(module_event)
    return m.group(1) if m else module_event


def load(path: str) -> dict:
    """Events of an XSpace file (gzipped where the name ends in ``.gz``):
    ``{"devices": {n: [(name, start, end, program)]}, "spans": [(name,
    start, end, thread)]}``, times in ns, device ops without their
    containers, host spans named ``bench.*`` or ``tig.*``; a thread is
    (plane, line index)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        lines = {line.name: line for line in plane.lines}
        if m:
            ops = tr.leaves([(tr.op_name(e.name), e.start_ns, e.end_ns)
                             for e in lines[tr.OPS_LINE].events]) \
                if tr.OPS_LINE in lines else []
            mods = sorted((e.start_ns, e.end_ns, program_name(e.name))
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            devices[int(m.group(1))] = _with_programs(ops, mods)
            continue
        for i, line in enumerate(plane.lines):
            spans.extend((e.name, e.start_ns, e.end_ns, (plane.name, i))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIXES))
    return {"devices": devices, "spans": spans}


def _with_programs(ops, mods):
    """Each op with the program whose run holds its start ("" if none)."""
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        prog = mods[j][2] if j < len(mods) and mods[j][0] <= s else ""
        out.append((name, s, e, prog))
    return out


def label_gap(s: int, e: int, spans, thread) -> str:
    """The shortest span on ``thread`` that covers at least half of the gap
    ``[s, e)``; else the span that covers most of it; else "no span"."""
    cover = {}
    for n, hs, he, th in spans:
        c = max(0, min(e, he) - max(s, hs))
        if c > 0:
            cover[(n, hs, he, th)] = c
    half = [k for k, c in cover.items() if k[3] == thread and 2 * c >= e - s]
    if half:
        return min(half, key=lambda k: k[2] - k[1])[0]
    if cover:
        by_name = defaultdict(int)
        for k, c in cover.items():
            by_name[k[0]] += c
        return max(by_name, key=by_name.get)
    return "no span"


def _idle(intervals, lo: int, hi: int) -> list:
    """The parts of ``[lo, hi)`` that no interval (each inside it) covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def reduce(events: dict, smap: dict) -> dict:
    """Per device, inside the ``bench.window`` span, with ``smap`` the
    epoch program's ``scope_map``: ``busy_ns`` (every program's ops, as
    ``trace_reduce`` counts it), ``program_op_ns`` (the epoch program's op
    times by name), ``program_busy_ns``, ``stage_ns`` (by ``STAGES``
    metric and ``unscoped``), ``scope_ns`` (by (scope, forward |
    gradient)), and the ``TOP`` longest idle ``gaps`` (label, ns)."""
    lo, hi = tr.window_of({"spans": [sp[:3] for sp in events["spans"]]})
    thread = next(sp[3] for sp in events["spans"] if sp[0] == tr.WINDOW_SPAN)
    spans = [sp for sp in events["spans"] if sp[0] != tr.WINDOW_SPAN]
    per_device = {}
    for dev, ops in sorted(events["devices"].items()):
        ops = [(n, max(s, lo), min(e, hi), p) for n, s, e, p in ops
               if min(e, hi) > max(s, lo)]
        mine = [(n, s, e) for n, s, e, p in ops if p == EPOCH_PROGRAM]
        op_ns = defaultdict(int)
        for n, s, e in mine:
            op_ns[n] += e - s
        stage_ns = {m: 0 for m in (*STAGES, UNSCOPED)}
        scope_ns = defaultdict(int)
        for n, ns in op_ns.items():
            path = smap.get(n, "")
            stage_ns[stage_of(path)] += ns
            scope_ns[split_path(path)] += ns
        gaps = _idle([(s, e) for _, s, e, _ in ops], lo, hi)
        program_gaps = _idle([(s, e) for _, s, e in mine], lo, hi)
        per_device[dev] = {
            "busy_ns": hi - lo - sum(e - s for s, e in gaps),
            "program_op_ns": dict(op_ns),
            "program_busy_ns": hi - lo - sum(e - s for s, e in program_gaps),
            "stage_ns": stage_ns,
            "scope_ns": dict(scope_ns),
            "gaps": sorted(((label_gap(s, e, spans, thread), e - s)
                            for s, e in gaps), key=lambda g: -g[1])[:TOP],
        }
    return {"window_ns": hi - lo, "devices": per_device}


def stage_ms(red: dict, steps: int) -> dict:
    """Each stage's device time per step in ms, the mean over devices."""
    devs = list(red["devices"].values())
    if not devs or not steps:
        return {}
    return {m: sum(d["stage_ns"][m] for d in devs) / len(devs) / 1e6 / steps
            for m in (*STAGES, UNSCOPED)}


def breakdown(red: dict) -> dict:
    """``device_scopes``: seconds per (scope, forward | gradient), longest
    first, "" for ops under no scope; ``idle_gaps``: the ``TOP`` longest
    labelled gaps in seconds, over all devices."""
    by_scope, gaps = defaultdict(int), []
    for d in red["devices"].values():
        for key, ns in d["scope_ns"].items():
            by_scope[key] += ns
        gaps.extend(d["gaps"])
    return {"device_scopes": [[s, ph, ns / 1e9] for (s, ph), ns in sorted(
                by_scope.items(), key=lambda x: -x[1])],
            "idle_gaps": [[n, ns / 1e9] for n, ns in sorted(
                gaps, key=lambda g: -g[1])[:TOP]]}
