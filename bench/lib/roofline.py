"""The least work a neighbor search needs, and the chip's peaks.

The counts read nothing of how the program searches (its tiles, windows
or cell capacities), so the least time is a true lower bound for any
implementation and a share of it cannot pass 100%:

* least bytes: the scene's points read once (N x 12 B), the queries read
  once (Nq x 12 B), the results written once (Nq x (K x 8 + 4) B: an
  int32 id and a float32 squared distance per slot, and a count);
* least operations: 8 per returned neighbour (three differences, three
  products, two sums) over the run's own result counts.

The least time is the larger of bytes over the HBM bandwidth and
operations over the bf16 peak, the chip's highest rate, which keeps the
bound low.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")
FLOPS_PER_NEIGHBOUR = 8


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_bytes(n_points: int, n_queries: int, k: int) -> int:
    return 12 * n_points + 12 * n_queries + n_queries * (8 * k + 4)


def least_flops(neighbours: float) -> float:
    return FLOPS_PER_NEIGHBOUR * float(neighbours)


def least_seconds(n_points: int, n_queries: int, k: int,
                  neighbours: float, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(least_bytes(n_points, n_queries, k) / pk["hbm_bytes_per_s"],
               least_flops(neighbours) / pk["bf16_flops"])
