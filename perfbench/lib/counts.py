"""What one propose call needs at the least, from its shapes alone.

The counts follow from the pool and forest shapes only, never from how the
program computes: ``N`` candidates of ``D`` knobs, ``S`` sources of ``T``
trees holding ``nodes`` nodes in all, trees at most ``depth`` deep, ``k``
picks.

Bytes: the host pool is read once (``N * D`` float64). Each node is read
once: split feature (int32), threshold (float64), two children (int32), leaf
mean and variance (float64), 36 bytes. The ``N`` aggregates (float64) are
written once, and the ``k`` picks (int32 index, float64 aggregate) once.

Compares: every candidate visits at most ``depth`` nodes of every tree,
``N * S * T * depth`` threshold compares. At the chip's int8 peak they take
far less time than the bytes take at its HBM bandwidth, so the bytes bound
the least time, and ``propose_roofline`` is that least time over the time the
device spent on one call.
"""

from __future__ import annotations

import json
import os
from typing import Dict

NODE_BYTES = 4 + 8 + 4 + 4 + 8 + 8


def propose_counts(N: int, D: int, S: int, T: int, nodes: int, depth: int,
                   k: int) -> Dict[str, float]:
    bytes_ = N * D * 8 + nodes * NODE_BYTES + N * 8 + k * (4 + 8)
    return {"bytes": float(bytes_), "compares": float(N * S * T * depth)}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device not in the table is
    an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device {device_kind!r} in {path}")
    return dict(table["devices"][device_kind], source=table["source"])


def least_seconds(counts: Dict[str, float], peak: Dict[str, float]) -> Dict[str, float]:
    """The least time by bytes and by compares, and which one bounds."""
    t_bytes = counts["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = counts["compares"] / peak["int8_op_per_s"]
    return {"seconds": max(t_bytes, t_ops), "bytes_s": t_bytes, "compares_s": t_ops,
            "bound": "bytes" if t_bytes >= t_ops else "compares"}
