"""The saturating feed against a stand-in engine: whole chunks within the
ingress room, nothing shed, every packet of the window answered."""
import time

import numpy as np
import pytest

from bench import gen, harness
from bench.tests.helpers import small_cell


class FakeEngine:
    """The engine's public contract with a fixed cost per step: a step
    dispatches every ready tenant's chunks in batches of at most
    ``max_batch`` lanes, until no tenant has a chunk ready."""

    def __init__(self, tenants, chunk, depth, epoch, step_s=0.0,
                 max_batch=1):
        self.chunk, self.depth, self.epoch, self.step_s = (chunk, depth,
                                                           epoch, step_s)
        self.max_batch = max_batch
        self.buf = {t: 0 for t in range(tenants)}
        self.done = {t: 0 for t in range(tenants)}
        self.lanes = set()                    # lane counts dispatched

    def room(self, t):
        return self.depth * self.chunk - self.buf[t]

    def submit(self, t, pkts):
        take = min(len(pkts["ts"]), self.room(t))
        self.buf[t] += take
        return take

    def step(self):
        batches = 0
        while True:
            ready = [t for t in self.buf if self.buf[t] >= self.chunk]
            if not ready:
                break
            for i in range(0, len(ready), self.max_batch):
                lanes = ready[i:i + self.max_batch]
                self.lanes.add(len(lanes))
                for t in lanes:
                    self.buf[t] -= self.chunk
                    self.done[t] += self.chunk
                batches += 1
        if batches and self.step_s:
            time.sleep(self.step_s)
        return batches

    def results(self, t):
        n = self.done[t] // self.epoch
        gi = (np.arange(n) + 1) * self.epoch - 1
        return gi, np.ones(n, np.float32), np.zeros(n, bool)

    def stats(self):
        return {"aggregate": {"pkts_processed": sum(self.done.values())},
                "tenants": {t: {"records": self.done[t] // self.epoch}
                            for t in self.done}}


class NoMemory:
    def memory_stats(self):
        return None


def _run(step_s=0.0, seconds=1.0):
    cell = small_cell()
    r = harness.Run(cell, 11, False)
    cfg = cell.config
    pools = gen.pools(cell.mix, cfg["tenants"], 11)
    r.tenants = [harness.Tenant(t, p, None, None) for t, p in enumerate(pools)]
    r.engine = FakeEngine(cfg["tenants"], cfg["chunk"], cfg["queue_depth"],
                          cfg["epoch"], step_s)
    r.window(seconds)
    r.collect([NoMemory()])
    return r


def test_saturating_feed_never_sheds():
    r = _run(step_s=0.005, seconds=0.5)
    t = r.tenants[0]
    assert t.submitted % r.cfg["chunk"] == 0
    assert r.end_submitted == [t.submitted]
    assert r.m["drained_packets"] > 0
    assert r.unanswered == 0


def test_an_engine_that_drops_offered_packets_stops_the_run():
    r = harness.Run(small_cell(), 11, False)
    cfg = r.cfg
    r.tenants = [harness.Tenant(0, gen.pools(r.mix, 1, 11)[0], None, None)]
    r.engine = FakeEngine(1, cfg["chunk"], cfg["queue_depth"], cfg["epoch"])
    r.engine.submit = lambda t, pkts: len(pkts["ts"]) - 1
    with pytest.raises(RuntimeError):
        r.window(0.2)


@pytest.mark.parametrize("tenants,max_batch,lanes", [
    (1, 1, [1]), (4, 4, [4]), (16, 16, [16]), (6, 4, [4, 2]), (2, 4, [2])])
def test_warm_up_compiles_the_lane_counts_the_feed_dispatches(
        tenants, max_batch, lanes):
    assert harness.lane_counts(tenants, max_batch) == lanes
    cell = small_cell()
    cfg = cell.config
    r = harness.Run(cell, 11, False)
    pool = gen.pools(cell.mix, 1, 11)[0]
    r.tenants = [harness.Tenant(t, pool, None, None) for t in range(tenants)]
    r.engine = FakeEngine(tenants, cfg["chunk"], cfg["queue_depth"],
                          cfg["epoch"], max_batch=max_batch)
    r.window(0.2)
    assert sorted(r.engine.lanes) == sorted(lanes)
