"""Device-resident fused serving step: fc → epoch gather → MD in ONE jit.

The staged ``DetectionService.process`` path round-trips to the host twice
per chunk: the full (n, 80) feature matrix is pulled off device to run
numpy epoch sampling, then the sampled records are pushed back for KitNET
scoring.  On the measured host that throws away roughly two thirds of the
scan backend's FC throughput (benchmarks/results/throughput.json) — the
same CPU-cycle waste Peregrine's offloading exists to eliminate.

This module compiles the whole per-chunk pipeline as one donated jit:

    state, idx, scores, alarms, count = step(state, net, thr, base_mod, pkts)

* ``state`` is **donated** (``donate_argnums``) and carried on device — the
  flow tables never migrate, and the caller must treat the handle it passed
  in as consumed (DESIGN.md §8 records the contract).
* Epoch sampling runs as a jit-safe on-device gather
  (``repro.core.records.epoch_gather``): fixed-size index vector + valid
  count, so sampling stays inside the fused computation.
* FC runs through ``compute_features_sampled``: backends with a native
  record-sampled path (``scan``, ``bucketed``) update flow state for every
  packet but
  materialise feature statistics only at the sampled rows — sampling still
  happens *after* feature computation (the paper's architectural move),
  the unsampled rows just never leave the segmented scans.
* Only the sampled ``(idx, scores, alarms, count)`` ever cross to the host
  — never the (n, 80) feature matrix — and they cross *asynchronously*:
  the step returns device futures, so ``DetectionService.process_stream``
  can dispatch chunk k+1 before chunk k's results are drained.

Works with any registered FC backend (exact mode) × any MD backend; the
parity suite (tests/test_fused.py) holds serial-semantics FC backends to
bit-identical staged-vs-fused outputs.

The same per-chunk core serves two deployment shapes (DESIGN.md §10): the
single-stream ``DetectionService`` jits it directly (``make_fused_step``),
and the multi-tenant ``DetectionEngine`` vmaps it over a tenant axis
(``make_tenant_step``) — T tenants' chunks gathered from a stacked state
pool, advanced in ONE donated jit, and scattered back, tenant ids carried
with every lane so states and epoch counters never mix.

The step labels its device work with ``jax.named_scope`` (HLO ``op_name``
metadata only): ``pool.gather`` / ``pool.scatter`` around the tenant-pool
gather and scatter, ``fc`` around FC (with ``fc.*`` stages inside,
core/parallel.py) and ``md.kitnet`` around scoring and the threshold
compare, so a device trace splits the step by layer.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.backends import compute_features_sampled, resolve_backend
from repro.core.records import epoch_gather
from repro.detection.md_backends import md_score_fn
from repro.distributed.sharding import (ambient_mesh, flow_shards_binding,
                                        tenant_binding)


def _freeze(kw: Dict) -> Tuple:
    return tuple(sorted(kw.items()))


def _placement_token():
    """Ambient placement (mesh + ``flow_shards``/``tenants`` rules +
    device count).

    Part of the fused-step cache key: the partitioned FC backends
    (``bucketed``/``sharded``) resolve their mesh placement at trace time,
    so binding or unbinding a mesh must hand back a *different* step —
    otherwise the cached executable silently keeps the placement it was
    first traced under (the exact hazard ``core/bucketed.py`` resolves
    outside jit to avoid).  Shares the binding lookups with that resolver
    (``distributed/sharding``) so key and trace can never disagree.  The
    device count is in the token explicitly so a mesh re-bound under a
    different forced-device topology can never be served a stale step."""
    return (flow_shards_binding(), tenant_binding(), ambient_mesh(),
            jax.device_count())


def _tenant_sharding(placement: Tuple):
    """``NamedSharding`` spreading the tenant (leading) axis of the
    tenant-batched step over the ambient ``tenants`` rule, or ``None``
    when unplaced (no mesh, no rule, or the rule names axes the mesh
    lacks).  Resolved from the placement token at step-build time — the
    same values that key the cache — so the constraint and the cache can
    never disagree."""
    _, tenants, mesh, _ = placement
    if mesh is None or tenants is None:
        return None
    axes = tenants if isinstance(tenants, tuple) else (tenants,)
    if not all(a in mesh.axis_names for a in axes):
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(tenants))


def _make_core(backend: str, mode: str, backend_kw: Tuple,
               md_backend: str, md_kw: Tuple, epoch: int) -> Callable:
    """The SHARED per-chunk step: FC → on-device epoch gather → KitNET →
    threshold, state carried through.  Pure and traceable — the
    single-stream service jits it donated (``make_fused_step``) and the
    multi-tenant engine vmaps it over a tenant axis (``make_tenant_step``);
    both deployment shapes run the identical computation."""
    fc_kw = dict(backend_kw)
    score = md_score_fn(md_backend, **dict(md_kw))

    def step(state, net, threshold, base_mod, pkts):
        idx, count = epoch_gather(pkts["ts"].shape[0], epoch, base_mod)
        # record-sampled FC: the flow-table update covers every packet,
        # but feature rows are only materialised at the epoch boundaries —
        # sampling happens AFTER feature computation (the paper's move),
        # yet unsampled packets never pay the statistics-assembly cost
        with jax.named_scope("fc"):
            state, recs = compute_features_sampled(state, pkts, idx,
                                                   backend=backend, mode=mode,
                                                   **fc_kw)
        with jax.named_scope("md.kitnet"):
            scores = score(net, recs)
            alarms = scores > threshold
        return state, idx, scores, alarms, count

    return step


@functools.lru_cache(maxsize=None)
def _cached_step(backend: str, mode: str, backend_kw: Tuple,
                 md_backend: str, md_kw: Tuple, epoch: int,
                 placement: Tuple = (None, None, None, 1)) -> Callable:
    step = _make_core(backend, mode, backend_kw, md_backend, md_kw, epoch)
    return jax.jit(step, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _cached_tenant_step(backend: str, mode: str, backend_kw: Tuple,
                        md_backend: str, md_kw: Tuple, epoch: int,
                        placement: Tuple = (None, None, None, 1)) -> Callable:
    core = _make_core(backend, mode, backend_kw, md_backend, md_kw, epoch)
    # net and threshold are shared across tenants (one fitted detector,
    # many streams); state / epoch residue / packets carry the tenant axis
    vcore = jax.vmap(core, in_axes=(0, None, None, 0, 0))
    lane_sharding = _tenant_sharding(placement)

    def constrain(tree):
        # spread the tenant (leading) axis over the ``tenants`` mesh rule:
        # each device advances its lanes' FC scans + KitNET independently
        # (lanes share nothing but net/threshold, which XLA replicates).
        # A lane count that does not divide the axis still compiles — XLA
        # pads the partition — so ragged final batches stay placed.
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, lane_sharding),
            tree)

    def step(pool, tenant_ids, net, threshold, base_mods, pkts):
        # One unplaced lane moves by dynamic slice: a one-index gather /
        # scatter lowers to a select over the lane's whole slot (the whole
        # pool when it holds one tenant), where a dynamic update writes
        # the slot in place, or compiles away when it covers the pool.
        if lane_sharding is None and tenant_ids.shape[0] == 1:
            take = lambda x: jax.lax.dynamic_index_in_dim(x, tenant_ids[0])
            put = lambda p, s: jax.lax.dynamic_update_index_in_dim(
                p, s, tenant_ids[0], 0)
        else:
            take = lambda x: x[tenant_ids]
            put = lambda p, s: p.at[tenant_ids].set(s)
        with jax.named_scope("pool.gather"):
            sub = jax.tree_util.tree_map(take, pool)
        if lane_sharding is not None:
            sub, base_mods, pkts = (constrain(sub), constrain(base_mods),
                                    constrain(pkts))
        sub, idx, scores, alarms, counts = vcore(sub, net, threshold,
                                                 base_mods, pkts)
        with jax.named_scope("pool.scatter"):
            pool = jax.tree_util.tree_map(put, pool, sub)
        return pool, idx, scores, alarms, counts

    return jax.jit(step, donate_argnums=(0,))


def make_fused_step(backend: str = "scan", mode: str = "exact",
                    backend_kw: Dict = None, md_backend: str = "einsum",
                    md_kw: Dict = None, epoch: int = 1024) -> Callable:
    """Build (or fetch from cache) the fused per-chunk step.

    Returns ``step(state, net, threshold, base_mod, pkts)`` →
    ``(new_state, idx, scores, alarms, count)`` where every output is a
    device array: ``idx`` (ceil(n/epoch),) int32 within-chunk record
    positions zero-padded past ``count``; ``scores``/``alarms`` aligned
    with ``idx`` (rows past ``count`` are padding garbage — slice by the
    count before use).  ``base_mod`` is the running packet count modulo
    ``epoch`` (traced, so chunk position never forces a recompile).

    **Donation contract:** the ``state`` argument is donated — its buffers
    are invalidated by the call.  Never reuse the passed-in handle; always
    continue from the returned state, and snapshot with
    ``jax.tree_util.tree_map(jnp.copy, state)`` (an aliasing ``tree_map``
    of the identity keeps the doomed buffers).
    """
    return _cached_step(resolve_backend(backend), mode,
                        _freeze(backend_kw or {}), md_backend,
                        _freeze(md_kw or {}), epoch,
                        placement=_placement_token())


def make_tenant_step(backend: str = "scan", mode: str = "exact",
                     backend_kw: Dict = None, md_backend: str = "einsum",
                     md_kw: Dict = None, epoch: int = 1024) -> Callable:
    """Build (or fetch from cache) the TENANT-BATCHED fused step.

    Returns ``step(pool, tenant_ids, net, threshold, base_mods, pkts)`` →
    ``(new_pool, idx, scores, alarms, counts)``: the per-chunk core of
    :func:`make_fused_step` vmapped over a leading tenant axis.  ``pool``
    is a stacked state pytree (``core.state.init_state_stacked`` /
    ``StatePool.stacked``), ``tenant_ids`` a ``(T,)`` int32 vector of pool
    slots (traced — changing WHICH tenants ride a batch never recompiles;
    changing how MANY does), ``base_mods`` the ``(T,)`` per-tenant epoch
    residues, and ``pkts`` packet arrays stacked to ``(T, chunk)``.  Tenant
    states are gathered from the pool, advanced independently, and
    scattered back inside the same jit, so states and epoch counters
    cannot mix.  A one-lane batch (``T == 1``) with no mesh placement is
    gathered and written back by dynamic slice, in place; wider or placed
    batches by gather and scatter over ``tenant_ids``.  A one-lane batch
    is bitwise the single-stream step; in a wider batch the compiler may
    vectorise a lane's arithmetic differently, so its scores can differ
    from its solo run in the last ulp (tests/test_engine.py).
    ``net``/``threshold`` are shared: one fitted detector serving many
    streams.

    When a mesh is bound and the ``tenants`` logical axis has a rule
    (e.g. under ``distributed.sharding.flow_mesh``), the tenant axis of
    the gathered lanes is sharded over that rule — tenant lanes advance
    device-parallel, the engine's first mesh placement (DESIGN.md §12).
    The placement participates in the step cache key exactly like the
    flow-table placement.

    **Donation contract (DESIGN.md §8, unchanged):** ``pool`` is donated —
    continue from the returned pool only; ``tenant_ids`` must not repeat a
    tenant within one call (its state would be gathered once and scattered
    last-write-wins).
    """
    return _cached_tenant_step(resolve_backend(backend), mode,
                               _freeze(backend_kw or {}), md_backend,
                               _freeze(md_kw or {}), epoch,
                               placement=_placement_token())
