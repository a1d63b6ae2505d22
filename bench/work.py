"""The compulsory work of a clustering job, counted from its shapes.

The count is of the algorithm (paper Algorithm 1, two rounds), not of any
kernel, so it stays the same whatever implements the passes:

- round 1: GON on each machine's block makes ``k`` dependent passes (the
  first center's distances, then ``k - 1`` updates), each reading every
  row of the block once at f32 and computing ``(x - c)^2`` summed over
  ``d`` (3 flops an element);
- round 2: the same ``k`` passes over the union of the machines' centers;
- the radius fold reads the points once and computes every point's
  distance to the ``k`` centers (``2 k d`` flops a row by the matmul).

The bytes rest on one assumption: every block is larger than the chip's
on-chip memory, so each pass reads its block from HBM again (403 MB a
simulated machine's block and 6.44 GB a mesh shard at the cells' sizes,
far beyond the tens of MiB of on-chip memory). The union of round 2
(50 MB on one chip, 12.6 MB on four) could stay on the chip; it is under
1% of the bytes either way.

Counts are per chip: on a mesh each chip runs round 1 on its own rows and
the whole of round 2.
"""
from __future__ import annotations

import json
import os
from typing import NamedTuple

F32 = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class Work(NamedTuple):
    bytes: float
    flops: float


def mrg_job(rows: int, d: int, k: int, union: int) -> Work:
    """Work of one MRG job on one chip holding ``rows`` points of width
    ``d``; ``union`` is the number of candidate centers round 2 reduces
    (machines times ``k``, over all chips)."""
    passes = k * (rows + union)
    return Work(bytes=float(F32 * d * (passes + rows)),
                flops=float(3 * d * passes + 2 * k * d * rows))


def peaks(device_kind: str) -> dict:
    """The published peaks of a chip; an unknown chip is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def floor_s(work: Work, device_kind: str) -> tuple[float, str]:
    """-> (least seconds the chip could take, which peak bounds it)."""
    p = peaks(device_kind)
    t_mem = work.bytes / p["hbm_bytes_per_s"]
    t_fl = work.flops / p["flops_bf16"]
    return (t_mem, "hbm") if t_mem >= t_fl else (t_fl, "flops")
