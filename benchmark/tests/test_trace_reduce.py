"""The trace reduction, on interval arithmetic and on a small recorded
CPU trace (the CPU backend runs its ops on host threads, which `load`
reads as device lines when told their name)."""

import time

import pytest

import trace_reduce as tr


def test_union_and_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert tr.union([(1, 1), (2, 1)]) == []
    got = tr.clip(tr.union([(0, 10), (12, 14)]), [(1, 2), (9, 13)])
    assert got == [(1, 2), (9, 10), (12, 13)]
    assert tr.total(got) == pytest.approx(3.0)


def test_short_names():
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(x)") == "fusion"
    assert tr.short_name(
        "%_window_hits_jit.1 = s32[8,128] custom-call(a)") == \
        "_window_hits_jit"
    assert tr.short_name("jit__stripe_stats(1542085)") == \
        "jit__stripe_stats"
    assert tr.short_name("copy-start") == "copy-start"


def _trace():
    # device 0 busy [1,3) and [4,5) (the second nested in a loop event),
    # device 1 busy [2,6); jobs [0,4) and [5,8)
    ops = {"/device:TPU:0": [("%while.1 = x", (1.0, 3.0)),
                             ("%fusion.2 = y", (1.5, 2.5)),
                             ("%_window_hits_jit.1 = z", (4.0, 5.0))],
           "/device:TPU:1": [("%fusion.3 = y", (2.0, 6.0))]}
    modules = {"/device:TPU:0": [("jit__window_hits_jit(7)", (4.0, 5.0)),
                                 ("jit_loop(9)", (1.0, 3.0))],
               "/device:TPU:1": [("jit_other(1)", (2.0, 6.0))]}
    return tr.Trace({1: (0.0, 4.0), 2: (5.0, 8.0)}, ops, modules)


def test_busy_window_and_modules():
    t = _trace()
    assert t.window_s() == pytest.approx(7.0)
    # device 0: [1,3) inside job 1; [4,5) outside both jobs -> 2 s;
    # device 1: [2,4) and [5,6) -> 3 s; averaged over the two chips
    assert t.busy_s() == pytest.approx(2.5)
    assert t.module_time("window_hits") is None
    assert t.module_time("loop", jobs=[1]) == pytest.approx(2.0)
    assert t.module_time("loop", jobs=[2]) is None
    names = dict(t.top_ops())
    assert names["while"] == pytest.approx(2.0)
    assert names["fusion"] == pytest.approx(1.0 + 3.0)


def test_idle_gaps_are_named_and_sum_to_idle():
    t = _trace()
    gaps = t.idle_gaps(lambda s, e: "host:a" if s < 4 else "host:b")
    idle = {k: v for k, v in gaps}
    # device 0 idles [0,1) and [3,4) in job 1, all of job 2
    assert idle == {"host:a": pytest.approx(2.0),
                    "host:b": pytest.approx(3.0)}


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for job in (1, 2):
        with jax.profiler.TraceAnnotation(f"{tr.JOB_PREFIX}{job}"):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    # no chip plane in a CPU profile
    assert tr.load(path).busy_s() is None
    t = tr.load(path, device_line_prefix="tf_XLAPjRtCpuClient")
    assert sorted(t.jobs) == [1, 2]
    assert t.window_s() >= 0.04
    busy = t.busy_s()
    assert busy is not None and 0 < busy < t.window_s()
    assert any(name.startswith("dot") for name, _ in t.top_ops())
