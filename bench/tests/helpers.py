"""A small cell for CPU tests: the configuration and mix cut to sizes the
CPU runs in seconds, and a stand-in for the chip look."""
from __future__ import annotations

from bench import device, harness, spec

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
