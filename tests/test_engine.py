"""Multi-tenant detection engine: single-tenant bit-parity with
``DetectionService.process_stream``, N-tenant state isolation, bounded-queue
backpressure, the state pool lifecycle, and the ``repro.serving``
import-graph pin (serving/engine.py, core/state.py — DESIGN.md §10)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_state
from repro.core.state import StatePool
from repro.serving import DetectionEngine, DetectionService
from repro.traffic import synth_trace

N_SLOTS = 512
EPOCH = 32
CHUNK = 96


def _copy(state):
    return jax.tree_util.tree_map(jnp.copy, state)


def _eval_trace(attack: str, seed: int, n: int = 256):
    d = synth_trace(attack, n_train=64, n_benign_eval=n, n_attack=n,
                    seed=seed)
    return {k: v for k, v in d["eval"].items() if k != "label"}


def _states_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


@pytest.fixture(scope="module")
def svc():
    """One fitted scan-backend service shared by every engine test."""
    data = synth_trace("mirai", n_train=768, n_benign_eval=64,
                       n_attack=64, seed=0)
    s = DetectionService(epoch=EPOCH, n_slots=N_SLOTS, mode="exact",
                         backend="scan")
    s.observe_stream(data["train"], chunk=256)
    s.fit(fpr=0.05)
    return s


# ---------------------------------------------------------------------------
# single-tenant bit-parity with process_stream
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("attack", ["mirai", "syn_dos", "os_scan",
                                    "slowloris"])
def test_single_tenant_engine_matches_process_stream(svc, attack):
    """One tenant through the engine — tenant-batched fused step, pool
    gather/scatter, chunk cutting, partial-tail flush and all — must emit
    bit-identical (indices, scores, alarms) to the single-stream service
    on the same trace, and leave bit-identical flow tables."""
    ev = _eval_trace(attack, seed=11)
    st0, c0 = _copy(svc.state), svc.pkt_count
    want = svc.process_stream(ev, chunk=CHUNK)
    state_after = svc.state
    svc.state, svc.pkt_count = _copy(st0), c0

    eng = DetectionEngine.from_service(svc, n_tenants=2, chunk=CHUNK,
                                       queue_depth=4)
    tid = eng.add_tenant()
    eng.seed_tenant(tid, st0, c0)
    got = eng.run({tid: ev})[tid]
    assert len(want[0]) > 0
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert _states_equal(state_after, eng.pool.read(tid))
    # restore the module-scoped service for the next parametrization
    svc.state, svc.pkt_count = st0, c0


# ---------------------------------------------------------------------------
# N-tenant isolation
# ---------------------------------------------------------------------------
def test_tenant_isolation_results_and_states(svc):
    """Each tenant's engine output equals that tenant run ALONE (fresh
    tables both times): co-tenancy in the batched step must not leak
    state, records, or epoch accounting across lanes."""
    attacks = ["syn_dos", "ssdp_flood", "goldeneye", "fuzzing"]
    traces = {k: _eval_trace(a, seed=20 + k) for k, a in enumerate(attacks)}

    eng = DetectionEngine.from_service(svc, n_tenants=4, chunk=CHUNK,
                                       queue_depth=4)
    tids = [eng.add_tenant() for _ in range(4)]
    together = eng.run({tid: traces[k] for k, tid in enumerate(tids)})
    end_states = {k: eng.pool.read(tid) for k, tid in enumerate(tids)}

    for k, tid in enumerate(tids):
        solo = DetectionEngine.from_service(svc, n_tenants=1, chunk=CHUNK,
                                            queue_depth=4)
        t = solo.add_tenant()
        alone = solo.run({t: traces[k]})[t]
        for a, b in zip(together[tid], alone):
            np.testing.assert_array_equal(a, b)
        assert _states_equal(end_states[k], solo.pool.read(t))


def test_tenant_epoch_counters_never_mix(svc):
    """Tenants at different stream positions sample records at their OWN
    epoch boundaries: global indices stay per-tenant-continuous even when
    every chunk rides a shared batched call."""
    ev = _eval_trace("mirai", seed=31, n=160)
    eng = DetectionEngine.from_service(svc, n_tenants=2, chunk=64,
                                       queue_depth=8)
    a, b = eng.add_tenant(), eng.add_tenant()
    # tenant b starts mid-epoch (offset 7): boundaries shift accordingly
    eng.seed_tenant(b, init_state(N_SLOTS), pkt_count=7)
    out = eng.run({a: ev, b: ev})
    ia, ib = out[a][0], out[b][0]
    assert len(ia) and len(ib)
    assert all((i + 1) % EPOCH == 0 for i in ia)
    assert all((i + 1) % EPOCH == 0 for i in ib)
    # both streams hit the same ABSOLUTE boundaries, but tenant b's offset
    # means different packets feed each record — scores must diverge
    np.testing.assert_array_equal(ia, ib)
    assert not np.array_equal(out[a][1], out[b][1])


def _gather_scatter_step():
    """The tenant step with the pool moved by gather and scatter over the
    id vector, as wider batches move it: the reference for one lane."""
    from repro.serving.fused import _make_core
    core = jax.vmap(_make_core("scan", "exact", (), "einsum", (), EPOCH),
                    in_axes=(0, None, None, 0, 0))

    def step(pool, ids, net, threshold, base_mods, pkts):
        sub = jax.tree_util.tree_map(lambda x: x[ids], pool)
        sub, *out = core(sub, net, threshold, base_mods, pkts)
        return (jax.tree_util.tree_map(lambda p, s: p.at[ids].set(s),
                                       pool, sub), *out)

    return jax.jit(step)


@pytest.mark.parametrize("n_pool,slot", [(1, 0), (4, 0), (4, 3)])
def test_one_lane_writeback_matches_gather_scatter(svc, n_pool, slot):
    """A one-lane batch, gathered and written back by dynamic slice, gives
    what the gather/scatter write-back gives: equal records and alarms,
    scores and the lane's tables within 2 ulp, every other slot
    untouched bit for bit."""
    from repro.core.state import init_state_stacked
    from repro.serving.fused import make_tenant_step
    ref_step = _gather_scatter_step()
    thr = np.float32(svc.threshold)
    ev = _eval_trace("mirai", seed=71)
    lane = lambda k: {key: jnp.asarray(np.asarray(v)[None, k * CHUNK:
                                                     (k + 1) * CHUNK])
                      for key, v in ev.items()}
    # distinct, non-trivial tables in every slot: one chunk per slot
    pool = init_state_stacked(n_pool, N_SLOTS)
    for k in range(n_pool):
        pool = ref_step(pool, jnp.asarray([k], jnp.int32), svc.net, thr,
                        jnp.asarray([k], jnp.int32), lane(k))[0]
    args = (jnp.asarray([slot], jnp.int32), svc.net, thr,
            jnp.asarray([5], jnp.int32), lane(n_pool))
    got = make_tenant_step(epoch=EPOCH)(_copy(pool), *args)
    want = ref_step(_copy(pool), *args)
    idx, scores, alarms, counts = got[1:]
    assert int(counts[0]) > 0
    for g, w in ((idx, want[1]), (alarms, want[3]), (counts, want[4])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_max_ulp(np.asarray(scores), np.asarray(want[2]),
                                    maxulp=2)
    others = [k for k in range(n_pool) if k != slot]
    moved = False
    for p0, g, w in zip(jax.tree_util.tree_leaves(pool),
                        jax.tree_util.tree_leaves(got[0]),
                        jax.tree_util.tree_leaves(want[0])):
        p0, g, w = np.asarray(p0), np.asarray(g), np.asarray(w)
        if np.issubdtype(g.dtype, np.floating):
            np.testing.assert_array_max_ulp(g[slot], w[slot], maxulp=2)
        else:
            np.testing.assert_array_equal(g[slot], w[slot])
        np.testing.assert_array_equal(g[others], p0[others])
        moved |= not np.array_equal(g[slot], p0[slot])
    assert moved                      # the lane's new state was written


def test_stats_count_single_lane_dispatches(svc):
    """``stats()["aggregate"]`` counts fused calls and those that carried
    one tenant: every call of a one-tenant engine; fewer than all of a
    two-tenant engine fed both streams."""
    ev = _eval_trace("mirai", seed=73, n=160)
    n = len(ev["ts"])
    eng = DetectionEngine.from_service(svc, n_tenants=1, chunk=64,
                                       queue_depth=2)
    eng.run({eng.add_tenant(): ev})
    agg = eng.stats()["aggregate"]
    assert agg["dispatches"] == agg["single_lane_dispatches"] == -(-n // 64)

    short = {k: v[:n - 100] for k, v in ev.items()}
    eng = DetectionEngine.from_service(svc, n_tenants=2, chunk=64,
                                       queue_depth=2)
    a, b = eng.add_tenant(), eng.add_tenant()
    eng.run({a: ev, b: short})
    agg = eng.stats()["aggregate"]
    single, multi = (agg["single_lane_dispatches"],
                     agg["dispatches"] - agg["single_lane_dispatches"])
    assert 0 < single < agg["dispatches"] and multi > 0
    # every tenant chunk rode exactly one lane of one call
    assert single + 2 * multi == -(-n // 64) + -(-(n - 100) // 64)


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------
def test_bounded_queue_sheds_and_reports(svc):
    """A full ingress queue sheds (drop-tail) instead of blocking: the
    accepted prefix is processed normally, counters report the drops, and
    the engine drains without deadlock."""
    ev = _eval_trace("mirai", seed=41, n=300)
    n = len(ev["ts"])
    eng = DetectionEngine.from_service(svc, n_tenants=1, chunk=64,
                                       queue_depth=2)
    tid = eng.add_tenant()
    cap = 2 * 64
    accepted = eng.submit(tid, ev)          # one oversized burst, no ticks
    assert accepted == cap
    assert eng.room(tid) == 0
    assert eng.submit(tid, ev) == 0         # full: everything sheds
    eng.step()
    eng.flush()
    idx, scores, alarms = eng.results(tid)
    st = eng.stats()["tenants"][tid]
    assert st["pkts_dropped"] == (n - cap) + n
    assert st["pkts_processed"] == cap
    assert st["pkts_in"] == 2 * n
    # the accepted prefix is exactly the first `cap` packets of the trace
    svc_state, svc_count = _copy(svc.state), svc.pkt_count
    svc.state, svc.pkt_count = init_state(N_SLOTS), 0
    want = svc.process_stream({k: v[:cap] for k, v in ev.items()}, chunk=64)
    svc.state, svc.pkt_count = svc_state, svc_count
    for w, g in zip(want, (idx, scores, alarms)):
        np.testing.assert_array_equal(w, g)


def test_run_driver_respects_backpressure_without_drops(svc):
    """The offline ``run`` driver pauses feeding instead of shedding, so
    a tiny queue still processes the whole trace."""
    ev = _eval_trace("syn_dos", seed=43, n=200)
    eng = DetectionEngine.from_service(svc, n_tenants=1, chunk=64,
                                       queue_depth=1)
    tid = eng.add_tenant()
    eng.run({tid: ev})
    st = eng.stats()["tenants"][tid]
    assert st["pkts_dropped"] == 0
    assert st["pkts_processed"] == len(ev["ts"])


# ---------------------------------------------------------------------------
# state pool lifecycle
# ---------------------------------------------------------------------------
def test_state_pool_alloc_free_reset():
    pool = StatePool(3, 64)
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (0, 1) and pool.live == (0, 1) and pool.free_slots == 1
    # slots are independent: dirty one, the other stays fresh
    pool.stacked = jax.tree_util.tree_map(
        lambda x: x.at[a].set(jnp.ones_like(x[a])), pool.stacked)
    assert _states_equal(pool.read(b), init_state(64))
    assert not _states_equal(pool.read(a), init_state(64))
    pool.reset(a)
    assert _states_equal(pool.read(a), init_state(64))
    pool.free(a)
    assert pool.live == (b,)
    with pytest.raises(KeyError):
        pool.read(a)
    assert pool.alloc() == a            # lowest free slot, freshly reset
    c = pool.alloc()
    assert c == 2
    with pytest.raises(RuntimeError):
        pool.alloc()                    # exhausted: bounded pool rejects
    with pytest.raises(IndexError):
        pool.reset(99)


def test_state_pool_read_is_a_copy():
    pool = StatePool(2, 32)
    t = pool.alloc()
    snap = pool.read(t)
    pool.stacked = jax.tree_util.tree_map(
        lambda x: x.at[t].set(jnp.ones_like(x[t])), pool.stacked)
    assert _states_equal(snap, init_state(32))   # unaffected by the write


def test_engine_add_remove_tenants_reuses_slots(svc):
    eng = DetectionEngine.from_service(svc, n_tenants=2, chunk=64,
                                       queue_depth=2)
    a = eng.add_tenant()
    b = eng.add_tenant()
    with pytest.raises(RuntimeError):
        eng.add_tenant()
    eng.run({a: _eval_trace("mirai", seed=51, n=100)})
    eng.remove_tenant(a)
    c = eng.add_tenant()                 # reuses the freed slot, fresh state
    assert c == a
    assert _states_equal(eng.pool.read(c), init_state(N_SLOTS))
    assert eng.results(c)[0].shape == (0,)
    eng.remove_tenant(b)


# ---------------------------------------------------------------------------
# alarm delivery
# ---------------------------------------------------------------------------
def test_alarm_log_written_per_tenant(svc, tmp_path):
    ev = _eval_trace("syn_dos", seed=61)
    with DetectionEngine.from_service(svc, n_tenants=1, chunk=CHUNK,
                                      queue_depth=4,
                                      alarm_dir=str(tmp_path),
                                      alarm_format="csv") as eng:
        tid = eng.add_tenant()
        idx, scores, alarms = eng.run({tid: ev})[tid]
    n_alarms = int(np.asarray(alarms).sum())
    assert n_alarms > 0
    lines = (tmp_path / f"tenant{tid}.csv").read_text().strip().splitlines()
    assert lines[0] == "tenant,record_index,score"
    assert len(lines) == 1 + n_alarms
    got_idx = [int(l.split(",")[1]) for l in lines[1:]]
    np.testing.assert_array_equal(got_idx, idx[alarms])


# ---------------------------------------------------------------------------
# import-graph pin: repro.serving must not drag the LM stack in
# ---------------------------------------------------------------------------
def test_serving_import_graph_stays_detection_only():
    """Importing ``repro.serving`` must not import the LM model stack
    (``repro.models`` / ``repro.configs`` / ``repro.training``) — the
    seed's LM engine lives at ``repro.models.lm_engine`` now.  Runs in a
    fresh interpreter so this test is immune to import order."""
    allowed = ("repro.core", "repro.data", "repro.detection",
               "repro.distributed", "repro.kernels", "repro.serving",
               "repro.traffic")
    code = (
        "import sys, repro.serving\n"
        "mods = sorted(m for m in sys.modules\n"
        "              if m.startswith('repro.') and m.count('.') >= 1)\n"
        "print('\\n'.join(mods))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    bad = [m for m in out.stdout.split()
           if not m.startswith(allowed)]
    assert not bad, f"repro.serving pulled in disallowed modules: {bad}"


# ---------------------------------------------------------------------------
# profiler labels: device scopes in the fused step, host spans in the engine
# ---------------------------------------------------------------------------
STEP_SCOPES = ("pool.gather", "pool.scatter", "fc", "fc.sort", "fc.scan",
               "fc.store", "fc.record_gather", "md.kitnet")


def _path_scopes(op_name):
    """The path components of an HLO ``op_name``, transform wrappers such
    as ``vmap(...)`` / ``jit(...)`` removed."""
    import re
    return {re.sub(r"^\w+\((.*)\)$", r"\1", c) for c in op_name.split("/")}


def test_tenant_step_hlo_names_every_scope(svc):
    """The compiled tenant step's ``op_name`` metadata carries each of the
    step's named scopes, so a device trace can be split by layer; and
    every float scatter (a table store) under ``fc`` is an ``fc.store``,
    which the trace split relies on where the TPU compiler drops a
    scatter's metadata (bench/scopes.py)."""
    import re
    from repro.core.state import init_state_stacked
    from repro.serving.fused import make_tenant_step
    ev = _eval_trace("mirai", seed=3)
    pk = {k: jnp.asarray(np.asarray(v)[None, :CHUNK]) for k, v in ev.items()}
    step = make_tenant_step(epoch=EPOCH)
    hlo = step.lower(init_state_stacked(2, N_SLOTS),
                     jnp.asarray([1], jnp.int32), svc.net,
                     np.float32(svc.threshold), jnp.asarray([0], jnp.int32),
                     pk).compile().as_text()
    found = set().union(*(_path_scopes(n) for n in
                          re.findall(r'op_name="([^"]*)"', hlo)))
    assert set(STEP_SCOPES) <= found, set(STEP_SCOPES) - found
    stores = re.findall(r"= f\d+\[[^\n]* scatter\([^\n]*"
                        r'op_name="([^"]*)"', hlo)
    assert stores
    for op in stores:
        path = _path_scopes(op)
        assert "fc.store" in path or "fc" not in path, op


def test_engine_step_writes_nested_host_spans(svc, tmp_path):
    """Under ``jax.profiler`` one ``step()`` writes ``engine.dispatch`` per
    fused call with ``engine.slot_collisions`` inside it, and
    ``engine.drain`` around the drain of the older batch."""
    import glob
    import os
    ev = _eval_trace("mirai", seed=5)
    eng = DetectionEngine.from_service(svc, n_tenants=1, chunk=CHUNK,
                                       queue_depth=4)
    tid = eng.add_tenant()
    eng.submit(tid, {k: v[:CHUNK] for k, v in ev.items()})
    eng.step()                                   # compile outside the trace
    eng.submit(tid, {k: v[CHUNK:3 * CHUNK] for k, v in ev.items()})
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    assert eng.step() == 2
    jax.profiler.stop_trace()
    eng.flush()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    # two batches: two dispatches, the older batch drained in between
    assert len(spans["engine.dispatch"]) == 2
    assert len(spans["engine.slot_collisions"]) == 2
    assert len(spans["engine.drain"]) >= 1
    for (s, e), (ds, de) in zip(sorted(spans["engine.slot_collisions"]),
                                sorted(spans["engine.dispatch"])):
        assert ds <= s and e <= de
    for s, e in spans["engine.drain"]:
        assert not any(ds < e and s < de
                       for ds, de in spans["engine.dispatch"])
