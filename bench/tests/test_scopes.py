"""The split of the fused step by named scope and the naming of idle gaps
by the program's host spans (bench/scopes.py), on synthesised traces and
HLO text, and on a CPU profile of the engine."""
import pytest

from bench import scopes, spec, tracing

HLO = """HloModule jit_step, is_scheduled=true

%fused_store (param_0: f32[64,4], param_1: s32[8], param_2: f32[8,4]) -> f32[64,4] {
  %param_0 = f32[64,4]{1,0} parameter(0)
  %param_1 = s32[8]{0} parameter(1)
  %param_2 = f32[8,4]{1,0} parameter(2)
  %reshape.1 = f32[8,4]{1,0} reshape(%param_2), metadata={op_name="jit(step)/vmap(fc)/vmap(fc.scan)/squeeze"}
  ROOT %scatter.1 = f32[64,4]{1,0} scatter(%param_0, %param_1, %reshape.1), to_apply=%region_0
}

%fused_perm (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %iota.1 = s32[8]{0} iota(), metadata={op_name="jit(step)/vmap(fc)/vmap()/iota"}
  ROOT %scatter.2 = s32[8]{0} scatter(%param_0, %param_0, %iota.1), to_apply=%region_1
}

%fused_select (param_0: f32[1,64,4], param_1: f32[1,64,4], param_2: pred[]) -> f32[1,64,4] {
  %param_0 = f32[1,64,4]{2,1,0} parameter(0)
  %param_1 = f32[1,64,4]{2,1,0} parameter(1)
  %param_2 = pred[] parameter(2)
  %broadcast.1 = pred[1,64,4]{2,1,0} broadcast(%param_2), dimensions={}
  ROOT %select.1 = f32[1,64,4]{2,1,0} select(%broadcast.1, %param_0, %param_1), metadata={op_name="jit(step)/pool.scatter/scatter"}
}

ENTRY %main.1 (pool: f32[1,64,4], ids: s32[8]) -> f32[1,64,4] {
  %pool = f32[1,64,4]{2,1,0} parameter(0), metadata={op_name="pool['uni']['w']"}
  %ids = s32[8]{0} parameter(1), metadata={op_name="ids"}
  %copy.1 = f32[1,64,4]{1,2,0} copy(%pool)
  %bitcast.1 = f32[64,4]{1,0} bitcast(%copy.1)
  %sort.1 = s32[8]{0} sort(%ids), dimensions={0}, metadata={op_name="jit(step)/vmap(fc)/vmap(fc.sort)/jit(argsort)/sort"}
  %fusion.1 = s32[8]{0} fusion(%sort.1), kind=kCustom, calls=%fused_perm
  %add.1 = f32[8,4]{1,0} add(%bitcast.1, %bitcast.1), metadata={op_name="jit(step)/vmap(fc)/vmap(fc.scan)/add"}
  %fusion.2 = f32[64,4]{1,0} fusion(%bitcast.1, %fusion.1, %add.1), kind=kCustom, calls=%fused_store
  %copy.2 = f32[64,4]{0,1} copy(%fusion.2)
  %bitcast.2 = f32[1,64,4]{2,1,0} bitcast(%copy.2)
  %dot.1 = f32[8,4]{1,0} dot(%add.1, %add.1), metadata={op_name="jit(step)/vmap(md.kitnet)/jit(_score)/dot_general"}
  %custom-call.1 = f32[8]{0} custom-call(%dot.1), custom_call_target="x"
  ROOT %broadcast_select_fusion = f32[1,64,4]{2,1,0} fusion(%bitcast.2, %pool, %ids), kind=kLoop, calls=%fused_select, metadata={op_name="jit(step)/pool.scatter/scatter"}
}
"""


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/vmap(fc)/vmap(fc.store)/scatter", "fc.store"),
    ("jit(step)/pool.scatter/scatter", "pool.scatter"),
    ("jit(step)/pool.gather/gather", "pool.gather"),
    ("jit(step)/vmap(fc)/vmap(fc.sort)/jit(argsort)/sort", "fc.sort"),
    ("jit(step)/vmap(vmap(fc.scan))/mul", "fc.scan"),
    ("jit(step)/vmap(fc)/vmap()/reshape", "fc"),
    ("jit(step)/fc/fc.record_gather/concatenate", "fc.record_gather"),
    ("jit(step)/vmap(md.kitnet)/jit(_score)/dot_general", "md.kitnet"),
    ("jit(step)/vmap(fc)/vmap(fc.scan)/broadcast_in_dim;"
     "jit(step)/vmap(fc)/vmap()/reshape", "fc.scan"),
    ("jit(step)/epoch_gather/iota", scopes.UNSCOPED),
    ("pool['bi']['w']", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of_innermost_known_component(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_hlo_op_names_resolve_fusions_copies_and_bare_scatters():
    ops = scopes.hlo_op_names(HLO)
    # own metadata
    assert ops["sort.1"] == (
        "jit(step)/vmap(fc)/vmap(fc.sort)/jit(argsort)/sort", False)
    # a fusion with its own op_name (the root's)
    assert ops["broadcast_select_fusion"][0].endswith("pool.scatter/scatter")
    # a float scatter root without metadata: flagged, named by its inputs
    assert ops["fusion.2"] == (
        "jit(step)/vmap(fc)/vmap(fc.scan)/squeeze", True)
    # an integer scatter (a permutation, not a table store) is not flagged
    assert ops["fusion.1"] == ("jit(step)/vmap(fc)/vmap()/iota", False)
    # a copy moves what it copies; a bitcast of a copy likewise
    assert ops["copy.2"] == ops["bitcast.2"] == ops["fusion.2"]
    assert ops["copy.1"] == ("pool['uni']['w']", False)
    # nothing to go on
    assert ops["custom-call.1"] == ("", False)


def test_op_scopes_counts_bare_scatters_as_the_layer_store():
    sc = scopes.op_scopes(HLO)
    assert sc["fusion.2"] == sc["copy.2"] == "fc.store"
    assert sc["fusion.1"] == "fc"
    assert sc["add.1"] == "fc.scan"
    assert sc["dot.1"] == "md.kitnet"
    assert sc["broadcast_select_fusion"] == "pool.scatter"
    assert sc["copy.1"] == sc["custom-call.1"] == scopes.UNSCOPED


def test_hlo_without_entry_raises():
    with pytest.raises(ValueError):
        scopes.hlo_op_names("HloModule m\n")


def test_instruction_names():
    ev = ("%fusion.49 = f32[4194304,4]{0,1:T(4,128)} fusion(f32[4194304,4]"
          "{0,1} %copy.3), kind=kCustom, calls=%fused_computation.49")
    assert scopes.instruction(ev) == "fusion.49"
    assert scopes.instruction("sort.3") == "sort.3"


def test_self_time_gives_overlaps_to_the_later_op_and_adds_up():
    ev = [("outer", 0, 100), ("inner", 20, 30),           # nested
          ("a", 200, 100), ("b", 250, 100),               # partial overlap
          ("c", 900, 200)]                                # clipped at 1000
    got = scopes.self_ns(ev, 0, 1000)
    assert got == {"outer": 70, "inner": 30, "a": 50, "b": 100, "c": 100}
    assert sum(got.values()) == tracing.busy_ns(ev, 0, 1000)
    assert scopes.self_ns([], 0, 10) == {}


def test_gap_named_by_innermost_span():
    spans = [("engine.step", 0, 1000), ("engine.dispatch", 0, 300),
             ("engine.slot_collisions", 50, 200),
             ("engine.drain", 300, 600), ("bench.generate", 1200, 100)]
    # inside step and dispatch: the collision re-hash is innermost
    assert scopes.innermost_span((100, 150), spans) == \
        "engine.slot_collisions"
    # 20 in the dispatch, 80 in the drain
    assert scopes.innermost_span((280, 380), spans) == "engine.drain"
    # 40 in the re-hash, 50 in the dispatch around it, 60 in the drain
    assert scopes.innermost_span((210, 360), spans) == "engine.drain"
    assert scopes.innermost_span((240, 300), spans) == "engine.dispatch"
    # only the parent overlaps
    assert scopes.innermost_span((950, 1100), spans) == "engine.step"
    assert scopes.innermost_span((1150, 1250), spans) == "bench.generate"
    assert scopes.innermost_span((2000, 2100), spans) == "host.other"
    # the existing naming picks the parent for the same gap
    assert tracing.name_gap((100, 150), spans) == "engine.step"


def _trace():
    """Two fused steps on one device with scoped ops, program spans
    nested in the benchmark's."""
    ops = [("broadcast_select_fusion", 0, 40),            # pool.scatter
           ("add.1", 40, 100), ("fusion.2", 140, 60),     # fc.scan, fc.store
           ("sort.1", 200, 20), ("copy.1", 220, 30),      # fc.sort, unscoped
           ("dot.1", 250, 50),                            # md.kitnet
           ("fusion.1", 300, 50), ("copy.2", 350, 50),    # fc, fc.store
           ("broadcast_select_fusion", 600, 100),         # pool.scatter
           ("unknown.7", 700, 150)]                       # not in the HLO
    spans = [("engine.step", 0, 1000), ("engine.dispatch", 0, 50),
             ("engine.slot_collisions", 10, 30), ("engine.drain", 50, 500),
             ("engine.dispatch", 550, 70),
             ("engine.slot_collisions", 560, 40),
             ("bench.generate", 860, 100)]
    return tracing.Trace(window=(0, 1000), ops={"/device:TPU:0": ops},
                         spans=spans)


def test_split_layers_add_up_to_the_step():
    tr = _trace()
    sp = scopes.split(tr, scopes.op_scopes(HLO), steps=2)
    red = tracing.reduce(tr, [0])
    step_ms = spec.reader("step_device_ms.sat")(
        {"trace": red, "batches_traced": 2})
    layer = sp.layer_ms()
    assert layer["step"] == pytest.approx(step_ms)
    assert layer["pool"] + layer["fc"] + layer["md"] + \
        layer[scopes.UNSCOPED] == pytest.approx(step_ms)
    ms = 1e-6 / 2                                  # ns in the slice -> ms/step
    assert layer["pool"] == pytest.approx(140 * ms)
    assert layer["fc"] == pytest.approx((100 + 60 + 20 + 50 + 50) * ms)
    assert layer["fc_store"] == pytest.approx(110 * ms)
    assert layer["md"] == pytest.approx(50 * ms)
    assert layer[scopes.UNSCOPED] == pytest.approx((30 + 150) * ms)
    # the gap 400..600 lies in the drain and the second dispatch: the
    # drain overlaps it most; the one at 850..1000 is the generator's
    assert sp.idle_gaps[0] == ("engine.drain", pytest.approx(200e-9))
    assert sp.idle_gaps[1] == ("bench.generate", pytest.approx(150e-9))
    assert sp.span_ms("engine.dispatch") == pytest.approx(60e-6)
    assert sp.span_ms("engine.slot_collisions") == pytest.approx(35e-6)
    assert sp.span_ms("engine.drain") == pytest.approx(500e-6)


def test_split_without_steps_or_spans_reads_nothing():
    tr = _trace()
    tr.spans = []
    sp = scopes.split(tr, {}, steps=0)
    assert all(v is None for v in sp.layer_ms().values())
    assert all(sp.span_ms(n) is None for n in scopes.PROGRAM_SPANS)
    assert sp.idle_gaps[0][0] == "host.other"
    with pytest.raises(RuntimeError):
        scopes.split(tracing.Trace(window=(0, 1), ops={}, spans=[]), {}, 1)


def test_existing_readers_unchanged_by_program_spans():
    """The benchmark's four trace readers read the same values whether or
    not the program's spans are in the trace."""
    tr = _trace()
    bare = tracing.Trace(window=tr.window, ops=tr.ops,
                         spans=[s for s in tr.spans
                                if s[0] in tracing.HOST_SPANS])
    read = {}
    for t in (tr, bare):
        m = {"trace": tracing.reduce(t, [0]), "batches_traced": 2,
             "least_s_traced": 50e-9}
        read[id(t)] = {n: spec.reader(n)(m) for n in (
            "device_idle_frac.sat", "step_device_ms.sat",
            "fused_step_mfu.sat", "sort_frac.sat")}
    assert read[id(tr)] == read[id(bare)]
    assert read[id(tr)]["step_device_ms.sat"] == pytest.approx(
        (1000 - 200 - 150) * 1e-6 / 2)


@pytest.mark.parametrize("level", [0, 1])
def test_load_reads_program_spans_and_counts_python_events(tmp_path, level):
    """A CPU profile: the program's spans come back beside the
    benchmark's, and with the Python tracer off no ``$file:line`` event is
    written."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1000.0)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = level
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            with jax.profiler.TraceAnnotation("engine.slot_collisions"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("engine.drain"):
            pass
    jax.profiler.stop_trace()
    tr, runs, python_events = scopes.load(str(tmp_path))
    assert sorted(s[0] for s in tr.spans) == sorted(scopes.PROGRAM_SPANS)
    assert (python_events > 0) == bool(level)
    assert tr.ops == {} and runs == []           # no TPU plane on the CPU


def test_report_step_runs_and_shares():
    tr = _trace()
    sc = scopes.op_scopes(HLO)
    runs = [("jit_step(1)", -50, 450), ("jit_step(1)", 400, 400),
            ("jit_step(1)", 820, 300), ("jit_other(2)", 810, 5)]
    rep = scopes._report(scopes.split(tr, sc, steps=2), tr, runs, sc)
    # only the run wholly inside the slice counts; the other program's
    # run is shorter in total
    assert rep["step_runs"] == 1
    assert rep["step_runs_ms"] == pytest.approx(400e-6)
    assert sum(rep["scope_pct"].values()) == pytest.approx(100.0)
    assert rep["scoped_pct"] == pytest.approx(100 * (650 - 180) / 650)
    # no unscoped op reaches 1 ms a step at these sizes
    assert rep["unscoped_ops_over_1ms"] == []
    assert rep["ops_not_in_hlo"] == 1
