"""Logical-axis sharding: models annotate activations/params with *logical*
axis names; launch code binds them to physical mesh axes.

No mesh bound (tests, single-device smoke) -> every annotation is a no-op,
so the exact same model code runs on 1 CPU device and on a 512-chip mesh.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# Default production rules: batch over (pod, data); model-parallel dims over
# model; experts over model (EP); sequence sharding (decode long-context KV)
# over data.
PRODUCTION_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "expert_cap": ("pod", "data"),
    "vocab": "model",
    "embed": None,
    "seq": None,
    "kv_seq": None,          # overridden to ("pod", "data") for long-context
    "ssm_inner": "model",
    "opt": ("pod", "data"),  # ZeRO-1 optimizer-state axis
    # Peregrine flow-table partitions (core/sharded.py): the shard axis of
    # the hash-partitioned flow state spreads over the DP axes
    "flow_shards": ("pod", "data"),
    # Peregrine multi-tenant engine (serving/engine.py): the tenant lanes of
    # the tenant-batched fused step spread over the DP axes
    "tenants": ("pod", "data"),
}


def ambient_mesh():
    """The (abstract) mesh bound by ``jax.set_mesh``, or ``None``.

    Abstract, so it resolves the same inside ``jax.jit`` (the fused step
    resolves placement at trace time) as outside; ``shard_map`` and
    ``NamedSharding`` constraints take it directly.  Callers use this
    instead of threading a mesh by hand (e.g. ``core/bucketed.py``).
    """
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _rule_binding(name: str):
    rules = current_rules()
    binding = rules.rules.get(name) if rules is not None else None
    if isinstance(binding, list):
        binding = tuple(binding)
    return binding


def flow_shards_binding():
    """The normalised ``flow_shards`` rule of the ambient axis rules, or
    ``None`` when unbound.  Shared by everything that keys compiled
    executables on the flow-table placement (``core/bucketed.py``'s
    trace-time resolution and ``serving/fused.py``'s step-cache key), so
    the two can never drift apart."""
    return _rule_binding("flow_shards")


def tenant_binding():
    """The normalised ``tenants`` rule — the mesh axis (or axes) the
    multi-tenant engine's lane dimension spreads over — or ``None`` when
    unbound.  Consumed by ``serving/fused.make_tenant_step`` both for the
    lane sharding constraint and for its step-cache key."""
    return _rule_binding("tenants")


class ShardContext:
    """Resolved mesh placement for the two-level bucketed scans.

    ``core/parallel.py``'s segmented-scan helpers take one of these (built
    by ``core/bucketed.py`` from the ambient mesh + ``flow_shards`` rule)
    and keep EVERY O(n) step of the chunked scan shard-local: the local
    per-chunk scans, the carry fix-up, and the where-selects all run inside
    one ``shard_map`` region whose only collective is ``gather_tails`` —
    an all-gather of the O(S) per-chunk tail summaries (a few KB), never a
    full-batch transfer.

    Instances are built once per (mesh, binding, device count) and cached
    (``core/bucketed._shard_ctx``) so they are stable jit-cache keys.
    """

    def __init__(self, mesh, binding):
        self.mesh = mesh
        self.binding = binding
        self.axes: Tuple[str, ...] = (binding if isinstance(binding, tuple)
                                      else (binding,))
        size = 1
        for a in self.axes:
            size *= mesh.shape[a]
        self.size = size

    def wrap(self, fn):
        """Run ``fn`` under ``shard_map`` with every input/output's leading
        (chunk) axis split over the bound mesh axes."""
        spec = P(self.binding)
        return jax.shard_map(fn, mesh=self.mesh, in_specs=spec,
                             out_specs=spec, check_vma=False)

    def gather_tails(self, t: jax.Array) -> jax.Array:
        """All-gather per-chunk tail summaries across shards: local
        ``(chunks/size, ...)`` -> global ``(chunks, ...)``.  The one
        collective the bucketed scans pay — O(S) elements, not O(n)."""
        return jax.lax.all_gather(t, self.axes, axis=0, tiled=True)

    def local_chunks(self, x: jax.Array, n_local: int) -> jax.Array:
        """Slice a combined ``(chunks, ...)`` array down to this shard's
        ``n_local`` chunks (the inverse of :meth:`gather_tails`)."""
        idx = 0
        for a in self.axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return jax.lax.dynamic_slice_in_dim(x, idx * n_local, n_local, 0)


def auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axes: the compiler propagates
    shardings and ``with_sharding_constraint`` is a placement hint.
    (``jax.make_mesh`` defaults to ``Explicit`` axes, under which every
    op on a sharded operand must resolve its output sharding and a
    constraint asserts instead of placing.)"""
    n = math.prod(shape)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


@contextlib.contextmanager
def flow_mesh(n_devices: Optional[int] = None, axis: str = "data",
              rules: Optional[Dict[str, Axis]] = None):
    """Bind an N-device mesh with the Peregrine placement rules in one shot.

    Builds a 1-D mesh of ``n_devices`` (default: every visible device) on
    logical axis ``axis``, sets it ambient, and binds
    ``{"flow_shards": axis, "tenants": axis}`` (override with ``rules``) —
    the two rules the bucketed FC engine and the multi-tenant engine place
    themselves by.  The forced-host-device harness
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``; DESIGN.md §12)
    plus this context manager is the whole multi-device story on CPU CI;
    on a real accelerator mesh the same call binds physical devices.
    """
    n = jax.device_count() if n_devices is None else int(n_devices)
    mesh = auto_mesh((n,), (axis,))
    with contextlib.ExitStack() as es:
        es.enter_context(jax.set_mesh(mesh))
        es.enter_context(use_rules(
            {"flow_shards": axis, "tenants": axis} if rules is None
            else rules))
        yield mesh


class AxisRules:
    def __init__(self, rules: Dict[str, Axis]):
        self.rules = dict(rules)

    def spec(self, names: Sequence[Optional[str]]) -> P:
        return P(*[self.rules.get(n) if n else None for n in names])


class _State(threading.local):
    def __init__(self):
        self.rules: Optional[AxisRules] = None


_STATE = _State()


@contextlib.contextmanager
def use_rules(rules: Optional[Dict[str, Axis]]):
    prev = _STATE.rules
    _STATE.rules = AxisRules(rules) if rules is not None else None
    try:
        yield _STATE.rules
    finally:
        _STATE.rules = prev


def current_rules() -> Optional[AxisRules]:
    return _STATE.rules


def logical_spec(names: Sequence[Optional[str]]) -> P:
    r = _STATE.rules
    if r is None:
        return P(*[None] * len(names))
    return r.spec(names)


def lshard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the sharding implied by logical axis ``names``.

    No-op when no rules are bound (single-device paths).
    """
    r = _STATE.rules
    if r is None:
        return x
    assert x.ndim == len(names), (x.shape, names)
    return jax.lax.with_sharding_constraint(x, r.spec(names))
