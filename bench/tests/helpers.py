"""A small cell for CPU tests: the configuration and mix cut to sizes the
CPU runs in seconds, and stand-ins for the chip look and for the device
planes a CPU profile lacks."""
from __future__ import annotations

from bench import device, harness, spec, tracing

E2E = [("pps", "pkt/s"), ("setup_s", "s")]


def small_cell(name: str = "link-backbone-sat") -> spec.Cell:
    base = spec.cell(name)
    # long enough per flow that bfloat16 counts saturate: the control then
    # fails here as it does at the cell's size
    cfg = dict(base.config, n_slots=2048, queue_depth=4, epoch=256,
               chunk=2048, fit_records=64)
    mix = dict(base.mix, pool_packets=20000, flows=4096, src_hosts=2048,
               dst_hosts=512)
    return spec.Cell(name=name, chips=1, config=cfg, mix=mix,
                     end_to_end=[{"name": n, "unit": u} for n, u in E2E],
                     per_layer=[])


def on_cpu(monkeypatch) -> None:
    """Let a whole run go on with the CPU device: no chip look, the v5e
    peaks, no persistent compile cache."""
    import jax
    peaks = device.peaks
    monkeypatch.setattr(device, "chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(device, "peaks", lambda kind: peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def device_planes(monkeypatch, idle: int = 1) -> None:
    """Let a traced run read device planes on the CPU, whose profile has
    none: every JAX device is busy over each ``engine.step`` span of the
    traced slice, and ``idle`` planes of devices beyond them hold nothing."""
    import jax
    load = tracing.load

    def with_planes(logdir):
        tr = load(logdir)
        steps = [("fusion.0 f32[1]", s, d) for n, s, d in tr.spans
                 if n == "engine.step"]
        ids = [d.id for d in jax.devices()]
        for i in ids:
            tr.ops[tracing.plane(i)] = list(steps)
        for i in range(max(ids) + 1, max(ids) + 1 + idle):
            tr.ops[tracing.plane(i)] = []
        return tr

    monkeypatch.setattr(tracing, "load", with_planes)
