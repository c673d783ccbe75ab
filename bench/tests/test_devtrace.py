"""The reduction from a profiler trace to busy time, idle share, kernel
time and named idle gaps."""
import devtrace
import pytest


def recorded():
    """Two chips over a 10 ms window: two sweeps' annotations, ops and
    one module event per chip (times in seconds on the trace clock)."""
    ops0 = [("fusion.1", 0.001, 0.002), ("copy.2", 0.0015, 0.003),
            ("fusion.1", 0.006, 0.007)]
    ops1 = [("fusion.1", 0.002, 0.004)]
    return {"devices": {
        "/device:TPU:0": {"ops": ops0,
                          "modules": [("jit__group_kernel(7)", 0.001, 0.003)]},
        "/device:TPU:1": {"ops": ops1,
                          "modules": [("jit__group_kernel(7)", 0.002, 0.004),
                                      ("jit_other(2)", 0.008, 0.009)]}},
        "marks": [(0.0, 0.005), (0.005, 0.010)]}


def test_union_merges_overlaps_and_clips():
    assert devtrace.union([(3, 5), (1, 2), (1.5, 2.5), (9, 12)], 0, 10) \
        == [(1, 2.5), (3, 5), (9, 10)]
    assert devtrace.gaps([(1, 2.5), (3, 5)], 0, 6) \
        == [(0, 1), (2.5, 3), (5, 6)]


def test_busy_idle_kernel_and_gap_names():
    # host spans on the host clock, 100 s behind the trace clock
    spans = [("device.grid", 100.0, 0.010, 0),
             ("device.acquire_traces", 100.0, 0.0009, 1),
             ("device.execute", 100.004, 0.002, 1)]
    red = devtrace.reduce(recorded(), spans, offset_s=-100.0,
                          kernel="_group_kernel")
    assert red["window_s"] == pytest.approx(0.010)
    # chip 0 busy 1-3 ms and 6-7 ms, chip 1 busy 2-4 ms: mean 2.5 ms
    assert red["busy_s"] == pytest.approx(0.0025)
    assert red["kernel_s"] == pytest.approx(0.002)
    assert red["devices"] == 2
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.002)]
    gaps = [(n, round(s, 9)) for n, s in red["idle_gaps"]]
    # chip 0's 3-6 ms gap has its midpoint inside device.execute, its
    # 7-10 ms gap only inside device.grid
    assert ("device.execute", 0.003) in gaps
    assert ("device.grid", 0.003) in gaps
    assert ("harness", 0.001) not in gaps
    longest = red["idle_gaps"][0]
    assert longest[1] == pytest.approx(0.006)      # chip 1, 4-10 ms
    assert longest[0] == "device.grid"


def test_no_device_op_gives_nothing():
    tr = recorded()
    for d in tr["devices"].values():
        d["ops"] = []
    assert devtrace.reduce(tr, [], 0.0, "_group_kernel") is None
    assert devtrace.reduce({"devices": {}, "marks": []}, [], 0.0, "k") is None


def test_load_reads_host_annotations(tmp_path):
    """A real trace written by jax.profiler (on the CPU: host planes
    only) yields the harness's annotations on the trace clock."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.sweep"):
        f(jnp.ones(8)).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.sweep"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    tr = devtrace.load(str(tmp_path), "bench.sweep")
    assert len(tr["marks"]) == 2
    assert all(e > s for s, e in tr["marks"])


def test_op_name_drops_the_hlo_text():
    assert devtrace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "%fusion.3"
    assert devtrace.op_name("jit__group_kernel(12)") == "jit__group_kernel(12)"
