"""The trace reducer: busy, idle and gap labels on hand-made intervals and on
a small trace recorded on the CPU."""

import pytest

from perfbench.lib import trace_reduce as T


def test_union_and_gaps_by_hand():
    busy = T.union([(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)], 0.0, 10.0)
    assert busy == [(1.0, 3.0), (5.0, 6.0), (9.0, 10.0)]
    assert T.gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0), (6.0, 9.0)]
    spans = [(0.0, 10.0, "window"), (2.5, 5.5, "inner")]
    assert T.label_gaps(T.gaps(busy, 0.0, 10.0), spans) == {
        "window": 4.0, "inner": 2.0}


def test_reduce_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(jnp.sin(x) * 3.0))
    x = jnp.linspace(0.0, 1.0, 1 << 16)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_MARK):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("perfbench/call"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pd = T.load(T.xplane_file(str(tmp_path)))
    window = T.host_marks(pd, T.WINDOW_MARK)[0]
    calls = T.host_marks(pd, "perfbench/call")
    assert len(calls) == 3
    spans = [(s, e, "perfbench/call") for s, e in calls]
    out = T.reduce(pd, spans, plane_prefix="/host:CPU", line_name=None)
    assert out["window_s"] == pytest.approx(window[1] - window[0])
    assert 0.0 < out["busy_s"] < out["window_s"]
    ops = dict(out["device_ops"])
    assert any(name.startswith("sort") for name in ops)
    idle = dict(out["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    # XLA:CPU runs an op on a host thread; its busy time lies inside the calls
    assert out["busy_s"] <= sum(e - s for s, e in calls) + 1e-9
