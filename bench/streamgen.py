"""Seeded generator of bipartite temporal interaction streams.

A copy of the program's synthetic generator (``repro.tig.data``), kept with
the benchmark so that a change to the program cannot change the traffic it
is measured on.  Every parameter comes from a configuration file; with the
``taobao-s`` preset's parameters it reproduces that preset array for array
(``tests/bench/test_bench_yardstick.py``).

Behaviour: zipfian user activity and item popularity, a share of repeat
interactions rewired to the user's most recent non-repeat item, bursty
timestamps over 30 days, Gaussian edge features, all-zero node features,
and rare label flips of the source user.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Stream:
    src: np.ndarray            # (E,) int64, users in [0, num_users)
    dst: np.ndarray            # (E,) int64, items in [num_users, num_nodes)
    t: np.ndarray              # (E,) float64, non-decreasing
    edge_feat: np.ndarray      # (E, d_e) float32
    labels: Optional[np.ndarray]
    num_nodes: int
    d_n: int


def _rewire_repeats(users, items, repeat):
    """Each repeat edge takes the item of its user's most recent non-repeat
    edge (a user's first edge is always an anchor)."""
    ne = len(users)
    if ne == 0:
        return items.copy()
    order = np.argsort(users, kind="stable")
    u_s = users[order]
    first = np.empty(ne, dtype=bool)
    first[0] = True
    first[1:] = u_s[1:] != u_s[:-1]
    anchor = first | ~repeat[order]
    fill = np.maximum.accumulate(
        np.where(anchor, np.arange(ne, dtype=np.int64), 0))
    out = np.empty_like(items)
    out[order] = items[order][fill]
    return out


def generate(*, seed: int, num_users: int, num_items: int, num_edges: int,
             d_e: int, d_n: int, labeled: bool, classes: int,
             zipf_users: float, zipf_items: float,
             repeat_prob: float) -> Stream:
    rng = np.random.default_rng(seed)
    nu, ni, ne = num_users, num_items, num_edges
    users = rng.zipf(zipf_users, ne) % nu
    items = rng.zipf(zipf_items, ne) % ni
    repeat = rng.uniform(size=ne) < repeat_prob
    items = _rewire_repeats(users, items, repeat)
    src = users.astype(np.int64)
    dst = (nu + items).astype(np.int64)
    day = rng.integers(0, 30, ne)
    within = rng.exponential(1.0, ne)
    t = np.sort(day * 86_400.0 + within.cumsum() / within.sum() * 86_400.0)
    edge_feat = rng.normal(0, 1, (ne, d_e)).astype(np.float32)
    labels = None
    if labeled:
        labels = np.full(ne, 0, dtype=np.int64)
        flip = rng.uniform(size=ne) < 0.005 * classes
        labels[flip] = rng.integers(1, max(classes, 2), flip.sum())
    return Stream(src=src, dst=dst, t=t, edge_feat=edge_feat, labels=labels,
                  num_nodes=nu + ni, d_n=d_n)
