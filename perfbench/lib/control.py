"""The check's control: the reference in float32, put in the program's place.

The configurations state float64. The control computes the same propose
answers in the next precision below, float32, from the same inputs as a run
of the cell with the same seed, and is read by the same numbers as the
program (``check.py``). Each cell's limits have to refuse it; the readings
it gives at the cells' own sizes set the upper end of each limit.

    python3 perfbench/lib/control.py <cell> <seed> [<seed> ...]

prints one JSON line of numbers per seed. The benchmark's own runs never
run it.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict

import numpy as np

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path[:0] = [os.path.join(_root, "src"), _root]

from perfbench.lib import check as C  # noqa: E402
from perfbench.lib import loads, spec  # noqa: E402
from perfbench.lib import setup_data as sd  # noqa: E402


def cell_inputs(name: str, rehearse: bool = False):
    bench = spec.benchmark()
    cell = spec.cell(bench, name)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if rehearse:
        from perfbench.run import rehearsal_sizes

        rehearsal_sizes(cfg, traffic)
    return cfg, traffic


def readings(name: str, seed: int, rehearse: bool = False,
             dtype=np.float32) -> Dict[str, float]:
    """The control's numbers in cell ``name`` on the inputs of ``seed``."""
    cfg, traffic = cell_inputs(name, rehearse)
    kind = traffic["mode"]
    out = []
    if kind == "score_topk":
        models, incs, ws = sd.fit_sources(cfg, seed)
        X = sd.host_pools(sd.space_of(cfg), 1, traffic["pool"], seed)[0]
        F = [sd.forest_data(m) for m in models]
        idx, agg = C.control_call(F, X, incs, ws, traffic["k"], dtype)
        out.append(C.check_call(F, X, incs, ws, idx, agg))
    else:
        d = loads.Tune(cfg, traffic, seed)
        _, calls = d.session(*d.seed_session())
        for models, X, incs, ws, got, _ in calls:
            F = [sd.forest_data(m) for m in models]
            idx, agg = C.control_call(F, X, incs, ws, len(got), dtype)
            out.append(C.check_call(F, X, incs, ws, idx, agg))
    return C.worst(out)


if __name__ == "__main__":
    for s in sys.argv[2:]:
        print(json.dumps({"cell": sys.argv[1], "seed": int(s),
                          "control": readings(sys.argv[1], int(s))}), flush=True)
