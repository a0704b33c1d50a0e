"""Demanded work of the TIG step: FLOPs and HBM bytes from shapes.

The byte models follow the program's own kernel byte models
(``repro/roofline/kernel_bytes.py``: one read per operand, one write per
result, raw widths, no lane padding), copied here so that a change to the
program cannot move the yardstick, with FLOP counts added.  They count the
work the algorithm demands, not what a kernel happens to move: the
sampler's search probes are 4 bytes each, not the chunk its DMA fetches.
A multiply-add is 2 FLOPs; elementwise work is not counted.
"""

from __future__ import annotations

import dataclasses

F32 = I32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def seconds(self, peaks: dict) -> float:
        """Least time on a chip with ``peaks``: the larger bound."""
        return max(self.flops / peaks["bf16_flops"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def dims(cfg: dict) -> dict:
    d, dt, de, dn = cfg["dim"], cfg["dim_time"], cfg["d_e"], cfg["d_n"]
    return {"d": d, "d_t": dt, "d_e": de, "d_n": dn,
            "d_msg": 2 * d + dt + de, "d_q": d + dn + dt, "d_kv": d + de + dt,
            "b": cfg["batch_size"], "k": cfg["num_neighbors"],
            "h": cfg["n_heads"]}


def neighbor_sample(rows: int, k: int, total_events: int) -> Work:
    """One sampler launch over ``rows`` queries: per row the start/stop/key
    scalars, one 4-byte probe per bisection step over the event keys, and
    three K-wide windows read; three (rows, K) grids written."""
    iters = max(1, int(total_events).bit_length())
    reads = rows * (3 * I32 + iters * I32 + k * (I32 + F32 + I32))
    writes = rows * k * (I32 + F32 + I32)
    return Work(flops=0.0, bytes=float(reads + writes))


def fused_flush(rows: int, d_msg: int, d_mem: int) -> Work:
    """Flush of ``rows`` pending messages: per-node mean, GRU update, write
    of the touched memory rows and timestamps.  Reads messages, ids, times,
    the touched memory rows and timestamps, the GRU weights; writes memory
    rows, timestamps and the mean messages."""
    msg, mem = rows * d_msg * F32, rows * d_mem * F32
    weights = (d_msg * 3 * d_mem + d_mem * 3 * d_mem + 2 * 3 * d_mem) * F32
    reads = msg + 3 * rows * I32 + rows * F32 + mem + rows * F32 + weights
    writes = mem + rows * F32 + msg
    flops = 2.0 * rows * (d_msg + d_mem) * 3 * d_mem
    return Work(flops=flops, bytes=float(reads + writes))


def temporal_attn(rows: int, k: int, h: int, d_head: int) -> Work:
    """Masked attention of ``rows`` queries over K neighbors: QK^T and AV."""
    q = rows * h * d_head * F32
    kv = rows * k * h * d_head * F32
    reads = q + 2 * kv + rows * k
    return Work(flops=4.0 * rows * h * k * d_head, bytes=float(reads + q))


def temporal_attn_bwd(rows: int, k: int, h: int, d_head: int) -> Work:
    """Its backward: dV, dP, dQ and dK (the score recomputation is not
    counted); reads q, k, v, mask and the output cotangent, writes dq, dk,
    dv."""
    q = rows * h * d_head * F32
    kv = rows * k * h * d_head * F32
    reads = 2 * q + 2 * kv + rows * k
    writes = q + 2 * kv
    return Work(flops=8.0 * rows * h * k * d_head, bytes=float(reads + writes))


def step_flops(cfg: dict) -> float:
    """FLOPs of one training step, forward and backward (3x the forward's
    matmuls: one for the forward, two for the gradients of inputs and
    weights), with no recomputation counted."""
    m = dims(cfg)
    b, k, d = m["b"], m["k"], m["d"]
    rows = 2 * b
    flush = 2.0 * rows * (m["d_msg"] + d) * (3 * d if cfg["flavor"] in (
        "tgn", "tige") else d)
    dec = 2.0 * 2 * b * (2 * d * d + d)
    if cfg["flavor"] == "jodie":
        embed = 2.0 * 3 * b * (d + m["d_n"]) * d
    else:
        q = 3 * b
        embed = (2.0 * q * m["d_q"] * d                 # query projection
                 + 2.0 * 2 * q * k * m["d_kv"] * d      # key and value
                 + 4.0 * q * k * d                      # scores and mix
                 + 2.0 * q * (m["d_q"] + d) * d)        # output projection
    return 3.0 * (flush + embed + dec)


def step_kernels(cfg: dict, total_events: int) -> dict:
    """Demanded work of each kernel launch of one step, by kernel name."""
    m = dims(cfg)
    b, k = m["b"], m["k"]
    d_head = m["d"] // m["h"]
    return {
        "neighbor_sample": neighbor_sample(3 * b, k, total_events),
        "fused_flush": fused_flush(2 * b, m["d_msg"], m["d"]),
        "temporal_attn": temporal_attn(3 * b, k, m["h"], d_head),
        "temporal_attn_bwd": temporal_attn_bwd(3 * b, k, m["h"], d_head),
    }
