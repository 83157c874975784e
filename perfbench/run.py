"""MFTune's chip benchmark: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``perfbench/configs``) and a traffic mix (``perfbench/traffic``); the general
load in ``perfbench/lib/loads.py`` makes the inputs from ``--seed``,
compiles every shape the window uses, then calls the system for ``--seconds``
seconds in a closed loop with one caller. After the window the float64
reference (``perfbench/reference``) checks a sample of the answers, drawn
from the seed (``perfbench/lib/check.py``, limits in ``perfbench/limits``).

With ``--trace 0`` the last line of standard output is the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the JAX profiler and the
line holds the cell's per-layer metrics (``perfbench/metrics/<name>.py``) and
a breakdown of device time and idle gaps. The numbers compared, each beside
its limit, are the last lines of standard error and the ``checks`` of the
result line.

A cell's first run in a checkout compiles its programs into the checkout's
persistent cache in a child process (``--warm-only``), which ends before
this process touches the chip; the run then loads every program from the
cache, as every later run does. (A process that compiled the programs
itself ran them slower on a TPU v5e's host: 0.993 against 0.961 s a call
at 131072 candidates, with the same device time.) The child's time is the
first run's set-up.

Without a TPU, or with fewer chips than the cell asks for, the run exits 1
and prints no result. ``--rehearse`` runs the same path at the traffic
file's small ``rehearse`` sizes on any backend and prints no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.lib import spec  # noqa: E402

WARMED_DIR = os.path.join(ROOT, ".cache", "perfbench", "warmed")
CHECK_STREAM = 7
NO_CHIP_RC = 3


class NoChip(RuntimeError):
    pass


def _warmed_mark(name: str) -> str:
    return os.path.join(WARMED_DIR, f"{name}.json")


def warm_in_child(name: str, seed: int):
    """Compile cell ``name``'s programs into the persistent cache in a child
    process, unless a run in this checkout already has; returns the child's
    compile record, or None when nothing was to do."""
    mark = _warmed_mark(name)
    if not os.path.exists(mark):
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--workload", name, "--seed", str(seed),
                             "--seconds", "0", "--warm-only"]).returncode
        if rc == NO_CHIP_RC:
            raise NoChip("no TPU, or fewer chips than the cell asks for (warm-up process)")
        if rc != 0:
            raise RuntimeError(f"the warm-up process exited {rc}")
        with open(mark) as f:
            return json.load(f)
    return None


def _log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def rehearsal_sizes(cfg: dict, traffic: dict) -> None:
    """Apply the traffic file's ``rehearse`` sizes (to the traffic, and to the
    configuration's knowledge base)."""
    small = dict(traffic.pop("rehearse", {}))
    kb = small.pop("knowledge_base", {})
    traffic.update(small)
    cfg["knowledge_base"].update(kb)


def devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform}:{devs[0].device_kind}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _window(load, seconds: float, annotate):
    """Closed loop: steps until ``seconds`` have passed. Returns the window's
    length, the steps completed and each step's latency."""
    lat, steps = [], 0
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with annotate("perfbench/call"):
            steps += load.step()
        now = time.perf_counter()
        lat.append(now - t)
        if now - t0 >= seconds:
            return now - t0, steps, lat


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, warm_only: bool = False,
             child_compile: dict = None) -> dict:
    """One run of cell ``name``; returns the result object (the last line).
    ``warm_only`` stops after the warm-up and marks the cell as warmed;
    ``child_compile`` is the compile record of this run's warm-up process."""
    import contextlib

    import numpy as np

    from perfbench.lib import check as C
    from perfbench.lib import counts, loads
    from perfbench.lib.compile_log import CompileLog

    bench = spec.benchmark()
    cell = spec.cell(bench, name)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(name)
    if rehearse:
        rehearsal_sizes(cfg, traffic)
    devs = devices(cell["chips"], rehearse)
    dev = devs[0]
    import jax

    peak = None
    if not rehearse:
        # the checkout's own cache, at a fixed path, whatever the
        # environment says; every program is kept and none evicted, so that
        # every run after the first finds all of them
        from repro.compile_cache import CACHE_DIR

        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_compilation_cache_max_size", -1)
        peak = counts.peaks(dev.device_kind)
    log = CompileLog().install()
    t_init = time.perf_counter()

    load = loads.make(cfg, traffic, seed)
    t_data = time.perf_counter()
    load.warm()
    t_warm = time.perf_counter()
    setup_s = t_warm - T_START
    compiled = log.of("setup")
    if child_compile:
        compiled = {k: compiled[k] + child_compile[k] for k in compiled}
    _log(f"set-up on {dev.platform}:{dev.device_kind}: {setup_s!r} s = init "
         f"{t_init - T_START!r} s + inputs {t_data - t_init!r} s + warm-up "
         f"{t_warm - t_data!r} s; compile {compiled} (warm-up process: "
         f"{child_compile})")
    if warm_only:
        os.makedirs(WARMED_DIR, exist_ok=True)
        with open(_warmed_mark(name), "w") as f:
            json.dump(log.of("setup"), f)
        return {}

    log.phase = "window"
    tracer = None
    annotate = lambda _name: contextlib.nullcontext()  # noqa: E731
    if trace:
        from repro import obs

        # a directory of this run's own, so runs that trace at once in one
        # checkout keep their traces apart
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracer = obs.Tracer("perfbench")
        obs.set_tracer(tracer)
        annotate = jax.profiler.TraceAnnotation
        clock_pc = time.perf_counter()
        with annotate("perfbench/clock"):
            pass
    try:
        with annotate("perfbench/window"):
            window_s, steps, lat = _window(load, seconds, annotate)
    finally:
        if trace:
            obs.set_tracer(None)
            jax.profiler.stop_trace()
    in_window = log.of("window")
    q = statistics.quantiles(lat, n=4) if len(lat) > 1 else lat * 3
    _log(f"window: {window_s!r} s, {steps} steps, {len(lat)} calls; call "
         f"seconds min {min(lat)!r} quartiles {q!r} max {max(lat)!r}; "
         f"compiles inside the window: {in_window}")
    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    _log(f"peak_bytes_in_use per chip: {[s.get('peak_bytes_in_use') for s in stats]}")

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), CHECK_STREAM]))
    n_units = len(lat)
    picks = sorted(rng.choice(n_units, size=min(traffic["check_calls"], n_units),
                              replace=False).tolist())
    load.release()
    log.phase = "check"
    t_ref = time.perf_counter()
    readings = load.check(picks)
    ref_s = time.perf_counter() - t_ref
    numbers = C.worst(readings)
    failed = sum(any(r.get(k, 0.0) > v for k, v in limits.items()) for r in readings)
    correct = C.verdict(readings, limits)
    _log(f"check: {len(readings)} calls of units {picks} against the float64 "
         f"reference in {ref_s!r} s; compile {log.of('check')}")

    result = {"correct": correct, "attempted": steps, "failed": failed}
    metrics = {}
    if not trace:
        values = {"setup_s": setup_s}
        if load.kind == "propose":
            values["propose_s"] = window_s / steps
        else:
            values["iter_wall_s"] = window_s / steps
        for m in spec.metrics_of(bench, name, "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = {"steps": steps, "window_s": window_s, "compile": compiled}
        host_spans = [(tracer.epoch + e["ts"], tracer.epoch + e["ts"] + e["dur"], e["name"])
                      for e in tracer.events if e.get("type") == "span"]
        ctx["spans"] = [(n, e - s) for s, e, n in host_spans]
        if load.kind == "propose":
            ctx["counts"] = counts.propose_counts(**load.shapes())
            if peak is not None:
                ctx["least"] = counts.least_seconds(ctx["counts"], peak)
            _log(f"least time per call: {ctx['counts']} -> {ctx.get('least')}")
        try:
            ctx["trace"] = _reduce_trace(trace_dir, host_spans, clock_pc, rehearse)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec.metrics_of(bench, name, "per_layer"):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        result["device"]["busy_s"] = ctx["trace"]["busy_s"]
        result["device"]["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": numbers.get(k, float("inf")), "limit": v}
                        for k, v in limits.items()}
    return result


def _reduce_trace(trace_dir: str, host_spans, clock_pc: float,
                  rehearse: bool) -> dict:
    """Reduce the window's profiler trace in ``trace_dir``; the program's
    spans go onto the trace's clock through the clock-sync annotation."""
    from perfbench.lib import trace_reduce as T

    pd = T.load(T.xplane_file(trace_dir))
    clock = T.host_marks(pd, T.CLOCK_MARK)[0][0]
    shift = clock - clock_pc
    spans = [(s + shift, e + shift, n) for s, e, n in host_spans]
    spans += [(s, e, "perfbench/call") for s, e in T.host_marks(pd, "perfbench/call")]
    if rehearse and not any(p.name.startswith(T.DEVICE_PLANE) for p in pd.planes):
        out = T.reduce(pd, spans, plane_prefix="/host:CPU", line_name=None)
    else:
        out = T.reduce(pd, spans)
    _log(f"trace: {out['n_ops']} device operations, busy {out['busy_s']!r} s "
         f"of {out['window_s']!r} s on {out['chips']} chip(s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on any backend; prints no result line")
    ap.add_argument("--warm-only", action="store_true",
                    help="compile the cell's programs into the cache and stop")
    args = ap.parse_args(argv)
    try:
        child = None
        if not (args.rehearse or args.warm_only):
            child = warm_in_child(args.workload, args.seed)
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.rehearse, args.warm_only, child)
    except NoChip as e:
        _log(f"{e}; nothing was run")
        return NO_CHIP_RC if args.warm_only else 1
    if args.warm_only:
        return 0
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if args.rehearse:
        _log(f"rehearsal on {result['device']['platform']} passed through; "
             f"not a chip run, no result line")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
