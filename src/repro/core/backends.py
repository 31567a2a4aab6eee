"""FC backend registry — one API, three interchangeable data planes.

Peregrine's architectural bet is that feature computation is the swappable,
throughput-critical stage (cf. Whisper's frequency-domain frontend and
flow-classification pipelines): the detector never cares *how* the 80
per-packet features were produced.  This module makes that explicit:

    new_state, feats = compute_features(state, pkts, backend="pallas")

Backends (all emit the identical (n, N_FEATURES) layout):

  * ``serial`` — the per-packet lax.scan oracle (core/pipeline.py).  The
    only backend that also supports ``mode="switch"`` (shift-approximated
    arithmetic + round-robin decay), which is inherently packet-serial.
  * ``scan``   — TPU-native segmented associative scans (core/parallel.py),
    O(log n) depth over a packet batch.  Exact mode only.
  * ``pallas`` — the full-feature Pallas kernel
    (kernels/feature_update.feature_update_full): the switch pipeline on a
    TPU core, flow tables resident in VMEM.  Exact mode only; interpreted
    when lowered for the CPU, compiled on TPU.
  * ``sharded`` — hash-partitioned flow tables (core/sharded.py): S shards
    executed in parallel (vmap / mesh placement via the ``flow_shards``
    logical axis), bit-identical to ``serial`` in both modes.  Select the
    partition count with ``shards=S``.  Its per-shard path is the packet-
    serial oracle, so it is the *switch-mode* partitioning story; for
    exact-mode throughput use ``bucketed``.
  * ``bucketed`` — bucketed data-parallel segmented scans
    (core/bucketed.py): the batch is flow-hash-compacted and cut into S
    balanced buckets scanned in parallel (``shard_map`` over the
    ``flow_shards`` mesh axis when bound).  Exact mode only; select the
    bucket count with ``buckets=S``.

``register_backend`` remains the extension point for further flow-table
backends (e.g. multi-host partitions).

State-backend dispatch: the five FC backends above all implement the
DENSE state contract (direct-indexed ``(n_slots, ...)`` tables).  A state
built with ``init_state(..., state_backend="sketch")`` carries its own
compute path (core/sketch.py); ``compute_features`` identifies it
structurally (``state_spec_of``) and routes there, with the ``backend=``
name demoted to an implementation hint (``pallas`` → the sketch Pallas
kernel, anything else → the pure-JAX reference).  ``"sketch"`` is also a
registered FC name so benchmark/CLI specs can spell it directly.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

from repro.core.state import state_spec_of

# name -> (fn(state, pkts, mode, **kw) -> (state, feats), supported modes)
_REGISTRY: Dict[str, Tuple[Callable, Tuple[str, ...]]] = {}

# name -> fn(state, pkts, sample_idx, **kw) -> (state, feats[sample_idx]):
# backends that can emit ONLY the sampled feature rows (state update still
# covers every packet) — the fused serving step's fast path
_SAMPLED: Dict[str, Callable] = {}

# legacy / convenience spellings
_ALIASES = {"parallel": "scan", "oracle": "serial", "kernel": "pallas"}


def register_backend(name: str, modes: Tuple[str, ...] = ("exact",)):
    """Register ``fn(state, pkts, mode=..., **kw)`` as FC backend ``name``."""
    def deco(fn):
        _REGISTRY[name] = (fn, modes)
        return fn
    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend(name: str) -> str:
    """Canonical backend name (alias-aware); raises on unknown names."""
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(f"unknown FC backend {name!r}; "
                         f"available: {available_backends()}")
    return name


@register_backend("serial", modes=("exact", "switch"))
def _serial(state, pkts, mode: str = "exact", **_kw):
    from repro.core.pipeline import process_serial
    return process_serial(state, pkts, mode=mode)


@register_backend("scan")
def _scan(state, pkts, mode: str = "exact", **_kw):
    from repro.core.parallel import process_parallel
    return process_parallel(state, pkts)


@register_backend("pallas")
def _pallas(state, pkts, mode: str = "exact", chunk=None, interpret=None,
            **_kw):
    from repro.kernels import ops
    return ops.feature_update_full(state, pkts, chunk=chunk or ops.BLOCK,
                                   interpret=interpret)


@register_backend("sharded", modes=("exact", "switch"))
def _sharded(state, pkts, mode: str = "exact", shards: int = 4, **_kw):
    from repro.core.sharded import process_sharded
    return process_sharded(state, pkts, shards=shards, mode=mode)


@register_backend("bucketed")
def _bucketed(state, pkts, mode: str = "exact", buckets: int = 4, **_kw):
    from repro.core.bucketed import process_bucketed
    return process_bucketed(state, pkts, buckets=buckets, mode=mode)


@register_backend("sketch")
def _sketch(state, pkts, mode: str = "exact", **kw):
    # only reachable with a non-sketch state (sketch states dispatch via
    # state_spec_of before the registry lookup)
    raise ValueError(
        "backend='sketch' needs sketch-backed state; build it with "
        "init_state(n_slots, state_backend='sketch', rows=R) — the state "
        f"passed here is {state_spec_of(state).name!r}")


def compute_features(state: Dict, pkts: Dict[str, jax.Array],
                     backend: str = "scan", mode: str = "exact",
                     **kw) -> Tuple[Dict, jax.Array]:
    """Run one packet batch through the selected FC backend.

    state: ``init_state`` dict; pkts: raw packet arrays.  Returns
    ``(new_state, feats (n, N_FEATURES))``.  Extra kwargs go to the backend
    (e.g. ``chunk=``/``interpret=`` for pallas).

    Donation contract: callers that wrap this in a donated jit (the fused
    serving step does, with ``state`` donated) must treat the passed-in
    state handle as consumed — continue from ``new_state`` only, and
    snapshot with ``tree_map(jnp.copy, state)`` beforehand if a restore
    point is needed (DESIGN.md §8).
    """
    name = resolve_backend(backend)
    spec = state_spec_of(state)
    if spec.compute is not None:
        # non-dense state carries its own compute path; the backend name
        # becomes an implementation hint (e.g. "pallas" -> sketch kernel)
        return spec.compute(state, pkts, mode=mode, fc_backend=name, **kw)
    fn, modes = _REGISTRY[name]
    if mode not in modes:
        raise ValueError(
            f"FC backend {name!r} does not support mode {mode!r} "
            f"(supports {modes}); use backend='serial' or 'sharded' "
            "for switch mode")
    return fn(state, pkts, mode=mode, **kw)


def register_sampled_backend(name: str, fn: Callable) -> None:
    """Register a record-sampled FC path for an existing backend:
    ``fn(state, pkts, sample_idx, **kw) -> (state, feats (m, F))``."""
    _SAMPLED[resolve_backend(name)] = fn


def _scan_sampled(state, pkts, sample_idx, **_kw):
    from repro.core.parallel import process_parallel_sampled
    return process_parallel_sampled(state, pkts, sample_idx)


def _bucketed_sampled(state, pkts, sample_idx, buckets: int = 4, **_kw):
    from repro.core.bucketed import process_bucketed_sampled
    return process_bucketed_sampled(state, pkts, sample_idx, buckets=buckets)


register_sampled_backend("scan", _scan_sampled)
register_sampled_backend("bucketed", _bucketed_sampled)


def compute_features_sampled(state: Dict, pkts: Dict[str, jax.Array],
                             sample_idx: jax.Array, backend: str = "scan",
                             mode: str = "exact", **kw
                             ) -> Tuple[Dict, jax.Array]:
    """One batch through the FC backend, emitting ONLY the sampled rows.

    Returns ``(new_state, feats (m, N_FEATURES))`` with ``new_state``
    identical to :func:`compute_features` and ``feats`` row-for-row equal
    to ``compute_features(...)[1][sample_idx]``.  Backends with a native
    record-sampled path (``scan``, ``bucketed``) skip materialising the
    unsampled rows;
    everything else computes the full matrix and gathers.  Traceable — the
    fused serving step (serving/fused.py) inlines it into one jit.
    """
    name = resolve_backend(backend)
    spec = state_spec_of(state)
    if spec.compute is not None:
        new_state, feats = spec.compute(state, pkts, mode=mode,
                                        fc_backend=name, **kw)
        return new_state, feats[sample_idx]
    fn = _SAMPLED.get(name)
    if fn is not None and mode == "exact":
        return fn(state, pkts, sample_idx, **kw)
    new_state, feats = compute_features(state, pkts, backend=name,
                                        mode=mode, **kw)
    return new_state, feats[sample_idx]


def default_backend(mode: str = "exact") -> str:
    """The sensible default for a given arithmetic mode."""
    return "scan" if mode == "exact" else "serial"
