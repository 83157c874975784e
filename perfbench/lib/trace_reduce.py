"""From a profiler trace of the window to the device's busy time, its
longest operations and its idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it as planes of lines of events (start and duration in ns, on one
clock for host and device). Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane. Busy time is the union
of their intervals inside the window, averaged over the chips; idle time is
the rest of the window. Each idle gap is attributed to the innermost host
span that covers its middle: the benchmark's own ``TraceAnnotation``s, and
the program's ``repro.obs`` spans put on the profiler's clock by a
clock-sync annotation (``CLOCK_MARK``).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_MARK = "perfbench/window"
CLOCK_MARK = "perfbench/clock"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted and
    disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between the disjoint ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_gaps(idle: Sequence[Interval], spans: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost span open at each gap's middle
    (``(start, end, name)`` spans, times in seconds)."""
    import numpy as np

    out: Dict[str, float] = defaultdict(float)
    starts = np.array([s for s, _, _ in spans], dtype=float)
    ends = np.array([e for _, e, _ in spans], dtype=float)
    length = ends - starts
    for a, b in idle:
        mid = (a + b) / 2
        open_ = np.flatnonzero((starts <= mid) & (ends >= mid))
        name = spans[open_[np.argmin(length[open_])]][2] if len(open_) else "no_span"
        out[name] += b - a
    return dict(out)


def op_name(hlo: str) -> str:
    """A device operation's name and result type from its HLO text
    (``%fusion.12 = f32[4096]{0} fusion(...)`` -> ``fusion.12 f32[4096]``)."""
    name, _, rest = hlo.partition(" = ")
    shape = "tuple" if rest.startswith("(") else rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {files}")
    return files[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def host_marks(pd, name: str) -> List[Interval]:
    """``(start, end)`` in seconds of every host event called ``name``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    out.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return sorted(out)


def device_ops(pd, plane_prefix: str = DEVICE_PLANE,
               line_name: Optional[str] = OPS_LINE
               ) -> Dict[str, List[Tuple[float, float, str]]]:
    """``(start, end, name)`` of every device operation, per device plane.
    With ``line_name`` None, the operations are the events that carry an
    ``hlo_op`` stat on any line (how XLA:CPU's host threads record them)."""
    out: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = []
        for line in plane.lines:
            if line_name is not None and line.name != line_name:
                continue
            for ev in line.events:
                if line_name is None and "hlo_op" not in {k for k, _ in ev.stats}:
                    continue
                s = ev.start_ns * 1e-9
                evs.append((s, s + ev.duration_ns * 1e-9, op_name(ev.name)))
        out[plane.name] = evs
    return out


def reduce(pd, spans: Sequence[Tuple[float, float, str]] = (),
           plane_prefix: str = DEVICE_PLANE, line_name: Optional[str] = OPS_LINE,
           window: Optional[Interval] = None) -> Dict:
    """Busy and window seconds, the ten device operations that took the most
    time and the idle seconds by host span, over the window annotation
    (or ``window``, in seconds on the trace's clock)."""
    if window is None:
        marks = host_marks(pd, WINDOW_MARK)
        if not marks:
            raise RuntimeError(f"no {WINDOW_MARK!r} annotation in the trace")
        window = marks[0]
    lo, hi = window
    per_plane = device_ops(pd, plane_prefix, line_name)
    if not per_plane:
        raise RuntimeError(f"no {plane_prefix}* plane with a {line_name!r} line")
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    n_ops = 0
    for evs in per_plane.values():
        busy = union([(s, e) for s, e, _ in evs], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] += d
                n_ops += 1
        for k, v in label_gaps(gaps(busy, lo, hi), spans).items():
            idle_by[k] += v
    n = len(per_plane)
    return {
        "busy_s": busy_total / n,
        "window_s": hi - lo,
        "chips": n,
        "n_ops": n_ops,
        "device_ops": top({k: v / n for k, v in op_time.items()}),
        "idle_gaps": top({k: v / n for k, v in idle_by.items()}),
    }
