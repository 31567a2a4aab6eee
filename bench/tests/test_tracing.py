"""The trace-to-metric reduction on synthesised traces: busy union, sort
share, idle gaps named by host spans, per-op totals."""
import os

import pytest

from bench import spec, tracing


def _trace():
    ops = [("fusion.1", 0, 100), ("sort.3", 50, 100),      # overlap: 0..150
           ("fusion.2", 300, 100),                         # 300..400
           ("sort.7", 900, 200)]                           # clipped at 1000
    spans = [("engine.step", 140, 200), ("bench.generate", 420, 300)]
    return tracing.Trace(window=(0, 1000), ops={"/device:TPU:0": ops},
                         spans=spans)


def test_union_and_busy():
    ev = _trace().ops["/device:TPU:0"]
    assert tracing.union(ev, 0, 1000) == [(0, 150), (300, 400), (900, 1000)]
    assert tracing.busy_ns(ev, 0, 1000) == 350
    assert tracing.busy_ns(ev, 100, 350) == 100


def test_reduce_shares_and_gaps():
    red = tracing.reduce(_trace(), [0])
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(350e-9)
    assert red.busy_s_per_device == [pytest.approx(350e-9)]
    # sort.3 covers 50..150, sort.7 covers 900..1000
    assert red.sort_s == pytest.approx(200e-9)
    assert dict(red.device_ops) == pytest.approx(
        {"fusion.1": 100e-9, "sort.3": 100e-9, "fusion.2": 100e-9,
         "sort.7": 100e-9})
    # gaps: 150..300 (engine.step overlaps 150 of it), 400..900
    assert red.idle_gaps[0] == ("bench.generate", pytest.approx(500e-9))
    assert red.idle_gaps[1] == ("engine.step", pytest.approx(150e-9))


def test_reduce_averages_over_devices():
    tr = _trace()
    tr.ops["/device:TPU:1"] = [("fusion.9", 0, 1000)]
    red = tracing.reduce(tr, [0, 1])
    assert red.busy_s == pytest.approx((350 + 1000) / 2 * 1e-9)
    assert red.busy_s_per_device == [pytest.approx(350e-9),
                                     pytest.approx(1000e-9)]
    assert red.op_s["fusion.9"] == pytest.approx(500e-9)


def test_reduce_reads_only_the_cells_devices():
    tr = _trace()
    alone = tracing.reduce(tr, [0])
    tr.ops["/device:TPU:1"] = []                         # idle, not the cell's
    tr.ops["/device:TPU:5"] = [("fusion.9", 0, 1000)]    # busy, not the cell's
    assert tracing.reduce(tr, [0]) == alone
    # a cell on the devices JAX numbers 5 and 0 reads those planes
    both = tracing.reduce(tr, [5, 0])
    assert both.busy_s_per_device == [pytest.approx(1000e-9),
                                      pytest.approx(350e-9)]
    with pytest.raises(RuntimeError):
        tracing.reduce(tr, [0, 2])                       # no plane of 2


def test_op_s_keeps_every_op_and_sums_to_their_time():
    ops = [(f"fusion.{i}", 100 * i, 50) for i in range(12)] + \
        [("fusion.0", 1150, 100)]                           # clipped to 50
    tr = tracing.Trace(window=(0, 1200), ops={"/device:TPU:0": ops})
    red = tracing.reduce(tr, [0])
    assert len(red.op_s) == 12 and len(red.device_ops) == 10
    assert sum(red.op_s.values()) == pytest.approx(12 * 50e-9 + 50e-9)
    assert red.op_s["fusion.0"] == pytest.approx(100e-9)
    assert red.device_ops[0] == ("fusion.0", red.op_s["fusion.0"])
    assert dict(red.device_ops).items() <= red.op_s.items()


def test_no_device_plane_raises():
    with pytest.raises(RuntimeError):
        tracing.reduce(tracing.Trace(window=(0, 1), ops={}, spans=[]), [0])


def _read(metric, m):
    return spec.reader(metric)(m)


def test_readers_from_reduction():
    red = tracing.reduce(_trace(), [0])
    m = {"trace": red, "batches_traced": 5, "least_s_traced": 35e-9}
    assert _read("device_idle_frac.sat", m) == pytest.approx(65.0)
    assert _read("step_device_ms.sat", m) == pytest.approx(350e-9 * 1e3 / 5)
    assert _read("fused_step_mfu.sat", m) == pytest.approx(10.0)
    assert _read("sort_frac.sat", m) == pytest.approx(100 * 200 / 350)


def test_readers_find_nothing_without_a_trace():
    m = {"trace": None, "batches_traced": 0}
    for name in ("device_idle_frac.sat", "step_device_ms.sat",
                 "fused_step_mfu.sat", "sort_frac.sat"):
        assert _read(name, m) is None


def test_load_reads_host_spans_from_a_profile(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1000.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(str(tmp_path))
    assert tr.window[1] > tr.window[0]
    assert [s[0] for s in tr.spans] == ["engine.step"]


def test_tpu_op_names_and_sorts():
    fused = ("%fusion.49 = f32[4194304,4]{0,1:T(4,128)} fusion(f32[4194304,4]"
             "{0,1} %copy.3, s32[131072]{0} %sort.5), kind=kLoop")
    sort = ("%sort.5 = (f32[65536]{0}, s32[65536]{0}) sort(f32[65536]{0} "
            "%p, s32[65536]{0} %iota), dimensions={0}")
    assert tracing.op_name(fused) == "fusion.49 f32[4194304,4]"
    assert tracing.op_name("sort.3") == "sort.3"
    assert not tracing.is_sort(fused)
    assert tracing.is_sort(sort) and tracing.is_sort("sort.3")
