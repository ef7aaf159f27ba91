"""The trace reduction on small traces whose answers are known by hand."""
import pytest

from bench import trace as T


def small():
    """Two devices, 100 us window [0, 100000) ns.

    device 0: fusion 0-20, kernel 20-30 inside program decode_scan 0-40,
              all-reduce 50-70 with fusion 60-65 under it inside program
              train_step 50-70, fusion 90-110
    device 1: fusion 0-50 and 40-60 (overlapping), all-reduce 60-80
    host:     bench.engine_step 0-45, bench.wait_arrival 45-100
    """
    us = 1000.0
    d0 = T.Device(
        ops=[("fusion.1", 0, 20 * us), ("paged_attention_kernel", 20 * us,
                                        30 * us),
             ("all-reduce.3", 50 * us, 70 * us), ("fusion.2", 60 * us,
                                                  65 * us),
             ("fusion.9", 90 * us, 110 * us)],
        modules=[("jit__decode_scan_paged", 0, 40 * us),
                 ("jit_train_step", 50 * us, 70 * us)])
    d1 = T.Device(ops=[("fusion.1", 0, 50 * us), ("fusion.4", 40 * us,
                                                   60 * us),
                       ("all-reduce-start.1", 60 * us, 80 * us)])
    host = [("bench.trace_window", 0, 100 * us),
            ("bench.engine_step", 0, 45 * us),
            ("bench.wait_arrival", 45 * us, 100 * us)]
    return T.Trace({0: d0, 1: d1}, host, (0.0, 100 * us))


def test_busy_and_idle():
    tr = small()
    # device 0 busy 0-30, 50-70, 90-100 = 60 us; device 1 busy 0-80 = 80
    assert T.busy_s(tr) == pytest.approx(70e-6)
    assert T.window_s(tr) == pytest.approx(100e-6)
    assert T.idle_share(tr) == pytest.approx(30.0)


def test_time_of_kernel_and_programs():
    tr = small()
    assert T.time_of(tr, "paged_attention") == pytest.approx(10e-6)
    assert T.time_of(tr, "paged_attention", within="decode_scan") == \
        pytest.approx(10e-6)
    assert T.time_of(tr, "paged_attention", within="train_step") == 0.0
    assert T.time_of(tr, "decode_scan", "modules") == pytest.approx(40e-6)
    assert T.count_of(tr, "decode_scan") == 1


def test_top_programs():
    tr = small()
    # device 0 ran decode_scan 40 us and train_step 20; averaged over 2
    assert T.top_modules(tr) == [["jit__decode_scan_paged", 20e-6],
                                 ["jit_train_step", 10e-6]]


def test_idle_gaps_named_by_host_span():
    tr = small()
    gaps = T.idle_gaps(tr)
    # device 0 idles 30-50 (mostly under engine_step: 30-45) and 70-90
    assert [g[0] for g in gaps] == ["bench.engine_step",
                                   "bench.wait_arrival"] or \
        [g[0] for g in gaps] == ["bench.wait_arrival", "bench.engine_step"]
    assert sorted(g[1] for g in gaps) == pytest.approx([20e-6, 20e-6])


def test_breakdown_shape():
    b = T.breakdown(small())
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    name, secs = b["device_ops"][0]
    assert name == "fusion.1" and secs == pytest.approx(35e-6)


def test_interval_helpers():
    assert T.union([("a", 0, 2), ("b", 1, 3), ("c", 5, 6)]) == \
        [(0, 3), (5, 6)]
    assert T.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_read_recorded_profile(tmp_path):
    """A profile recorded here (CPU: host spans only) reads back with its
    window taken from the bench.trace_window span."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.read(str(tmp_path))
    names = [h[0] for h in tr.host]
    assert "bench.trace_window" in names and "bench.engine_step" in names
    win = [h for h in tr.host if h[0] == "bench.trace_window"][0]
    assert tr.window == (win[1], win[2]) and T.window_s(tr) > 0
    assert T.idle_share(tr) is None           # no TPU plane on the CPU
