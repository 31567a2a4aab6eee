"""AOT compiles of the detection path for a described TPU v5e.

Nothing runs: each test lowers a jitted step or kernel at deployment size
(8,192 slots, 8,192-packet chunks, one record per 1,024 packets) for one
chip of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler installed on this host.  That catches what interpret mode cannot:
Mosaic refusing a kernel (unaligned slices, vector reads where SMEM
scalars are needed), and programs that do not fit the chip's 16 GiB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file, while only the worker that runs it may
load the library.  Where it cannot be described, every test here skips.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import init_state
from repro.core.state import init_state_stacked

N_SLOTS = 8192
CHUNK = 8192
EPOCH = 1024
HBM_BYTES = 16 * 2**30            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def net():
    from repro.detection.kitnet import train_kitnet
    rng = np.random.default_rng(0)
    return train_kitnet(rng.random((256, 80)).astype(np.float32), seed=0)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _pkts(lead=()):
    n = lead + (CHUNK,)
    f32 = lambda: jax.ShapeDtypeStruct(n, jnp.float32)
    u32 = lambda: jax.ShapeDtypeStruct(n, jnp.uint32)
    return {"ts": f32(), "src": u32(), "dst": u32(), "sport": u32(),
            "dport": u32(), "proto": u32(), "length": f32()}


def _compile(fn, args, sharding):
    compiled = jax.jit(fn).lower(*_shapes(args, sharding)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled.as_text()


@pytest.mark.parametrize("backend,md_backend,kernel", [
    ("scan", "einsum", False),       # the served default
    ("pallas", "pallas", True),      # interpret=None compiles for the chip
])
def test_fused_service_step_compiles(one_chip, net, backend, md_backend,
                                     kernel):
    from repro.serving.fused import make_fused_step
    step = make_fused_step(backend=backend, md_backend=md_backend,
                           epoch=EPOCH)
    args = (jax.eval_shape(lambda: init_state(N_SLOTS)), net,
            np.float32(0.5), np.int32(0), _pkts())
    hlo = _compile(step, args, one_chip)
    assert ("tpu_custom_call" in hlo) == kernel


def test_tenant_step_compiles(one_chip, net):
    from repro.serving.fused import make_tenant_step
    lanes = 4
    step = make_tenant_step(epoch=EPOCH)
    args = (jax.eval_shape(lambda: init_state_stacked(lanes, N_SLOTS)),
            np.arange(lanes, dtype=np.int32), net, np.float32(0.5),
            np.zeros(lanes, np.int32), _pkts((lanes,)))
    _compile(step, args, one_chip)


def _scope_ops(hlo, scope):
    """``(opcode, instruction name, element count)`` of every instruction
    in ``hlo`` whose ``op_name`` path holds ``scope``."""
    ops = []
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]*)\]"
                         r"\S* ([\w-]+)\(.*op_name=\"([^\"]*)\"",
                         hlo, re.M):
        name, dims, opcode, op_name = m.groups()
        if scope in (re.sub(r"^\w+\((.*)\)$", r"\1", c)
                     for c in op_name.split("/")):
            ops.append((opcode, name,
                        int(np.prod([int(d) for d in dims.split(",") if d]))))
    return ops


@pytest.mark.parametrize("pool_slots", [1, 4])
def test_one_lane_tenant_step_writes_back_in_place(one_chip, net,
                                                   pool_slots):
    """A one-lane batch writes its tables back by dynamic update: no
    table-sized select or scatter under ``pool.scatter`` (a one-index
    scatter lowers to a select over the lane's whole slot, which with one
    slot rewrites the whole pool every step); with more slots than lanes
    the write-back is a ``dynamic-update-slice``."""
    from repro.serving.fused import make_tenant_step
    step = make_tenant_step(epoch=EPOCH)
    args = (jax.eval_shape(lambda: init_state_stacked(pool_slots, N_SLOTS)),
            np.zeros(1, np.int32), net, np.float32(0.5),
            np.zeros(1, np.int32), _pkts((1,)))
    hlo = step.lower(*_shapes(args, one_chip)).compile().as_text()
    ops = _scope_ops(hlo, "pool.scatter")
    bad = [(op, name) for op, name, size in ops if size >= N_SLOTS
           and (op in ("select", "scatter") or "select" in name
                or "scatter" in name)]
    assert not bad, bad
    if pool_slots > 1:
        assert any(op == "dynamic-update-slice" and size >= N_SLOTS
                   for op, _, size in ops), ops


def test_feature_update_full_compiles(one_chip):
    from repro.kernels.feature_update import feature_update_full
    fn = functools.partial(feature_update_full, interpret=False)
    args = (jax.eval_shape(lambda: init_state(N_SLOTS)), _pkts())
    assert "tpu_custom_call" in _compile(fn, args, one_chip)


def test_sketch_update_full_compiles(one_chip):
    from repro.kernels.sketch_update import sketch_update_full
    fn = functools.partial(sketch_update_full, interpret=False)
    st = jax.eval_shape(lambda: init_state(N_SLOTS, state_backend="sketch",
                                           rows=4))
    assert "tpu_custom_call" in _compile(fn, (st, _pkts()), one_chip)


def test_kitnet_ensemble_compiles(one_chip, net):
    from repro.kernels.kitnet_ae import kitnet_ensemble
    k, m = net.idx.shape
    fn = functools.partial(kitnet_ensemble, interpret=False)
    p = net.params
    args = (jax.ShapeDtypeStruct((CHUNK // EPOCH, k, m), jnp.float32),
            p["W1"], p["b1"], p["W2"], p["b2"], net.mask)
    assert "tpu_custom_call" in _compile(fn, args, one_chip)
