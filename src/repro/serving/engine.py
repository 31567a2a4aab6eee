"""Multi-tenant async detection engine: N tenant streams, one device.

``DetectionService`` is one synchronous loop over one stream; deployment
(ROADMAP "millions of users") is a switch feeding MANY concurrent tenant
streams into one control-plane detector.  ``DetectionEngine`` multiplexes
them (DESIGN.md §10):

* **Bounded state pool.**  Per-tenant flow-table state lives in a
  ``core.state.StatePool`` — one STACKED pytree with a leading tenant
  axis, so N tenants cost one device allocation per table; tenant slots
  are alloc'd/freed/reset as streams attach and detach.
* **Cross-tenant fused batching.**  Ready tenants' chunks are packed into
  ONE donated jit call (``serving/fused.make_tenant_step``): the service's
  per-chunk core — FC → on-device epoch gather → KitNET → threshold —
  vmapped over the tenant axis, tenant ids carried with every lane so
  states and per-tenant epoch counters never mix.  A one-lane batch is
  bitwise the single-tenant step (tests/test_engine.py), so one tenant
  through the engine reproduces ``DetectionService.process_stream``
  bit for bit; in a wider batch a lane's scores can differ from its solo
  run in the last ulp.
* **Backpressure.**  Each tenant has a bounded ingress buffer
  (``queue_depth`` chunks); ``submit`` sheds overflow (drop-tail), never
  blocks, and the shed count is reported per tenant — the engine cannot
  deadlock on a slow device.
* **Async dispatch-before-drain.**  As in ``process_stream``, batch k+1
  is dispatched to the device before batch k's O(records) results are
  drained, so steady-state throughput is bounded by the fused step.
* **Operational surface.**  Per-tenant p50/p99 chunk latency, aggregate
  pps, per-tenant drop/record/alarm counters (``stats()``), and
  daemon-style structured alarm delivery: a per-tenant CSV or JSONL alarm
  log (``alarm_dir=``) — the DPDK detector's ``run_background.sh`` +
  alarm-CSV operational shape.

One fitted detector (net + threshold) serves every tenant; isolation is
state isolation, not model isolation.  Donation contract (DESIGN.md §8)
applies to the pool exactly as to the single-stream state.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.core import resolve_backend
from repro.core.state import (StatePool, slot_collisions, state_backend_of,
                              state_config, state_slots)
from repro.detection.md_backends import (default_md_backend,
                                         validate_md_options)


class DetectionEngine:
    """Continuous-batching detection engine over a bounded tenant pool.

    Parameters
    ----------
    net, threshold:
        The fitted KitNET and alarm threshold shared by every tenant
        (train once via ``DetectionService``, then ``from_service``).
    epoch, n_slots, backend/backend_kw, md_backend/md_kw, mode:
        The per-chunk pipeline configuration, identical in meaning to
        ``DetectionService``; only exact mode is supported (the engine
        rides the fused device-resident path).
    n_tenants:
        State-pool capacity — the hard bound on concurrently attached
        tenant streams.
    chunk:
        Packets per fused-step lane.  Full chunks are batched across
        tenants; partial tails are flushed at ``flush()``.
    queue_depth:
        Ingress bound per tenant, in chunks: at most ``queue_depth *
        chunk`` packets may sit buffered; ``submit`` sheds the excess.
    max_batch:
        Most tenant lanes per fused call (default: ``n_tenants``).
    alarm_dir / alarm_format:
        When set, every drained alarm is appended to a per-tenant
        structured log ``<alarm_dir>/tenant<id>.{csv|jsonl}``.
    state_backend / state_kw:
        Flow-table layout of the tenant pool: ``"dense"`` (default) or
        ``"sketch"`` (``state_kw={"rows": R, "evict_age": ...}``);
        ``from_service`` inherits both from the service's state.  Dense
        pools additionally report per-tenant ``slot_collisions`` — the
        distinct flow keys that aliased an occupied slot per chunk.

    Profiler spans
    --------------
    While a ``jax.profiler`` trace is active the engine writes three
    host spans (``TraceAnnotation``; with no trace active each costs one
    disabled-annotation check):

    * ``engine.dispatch`` — one fused call's set-up: popping each lane's
      chunk, collision telemetry, stacking, the host-to-device copy and
      the enqueue of the step.
    * ``engine.slot_collisions`` — nested in ``engine.dispatch``: the
      host re-hash of each lane's chunk behind the dense-pool
      ``slot_collisions`` counter.
    * ``engine.drain`` — blocking on the oldest in-flight batch, then the
      result bookkeeping (records, counters, alarm logs).

    The fused step's device ops carry ``jax.named_scope`` labels
    (serving/fused.py), so a trace splits the device time by layer too.
    """

    def __init__(self, net, threshold: float, *, epoch: int = 1024,
                 n_slots: int = 8192, n_tenants: int = 4, chunk: int = 2048,
                 queue_depth: int = 8, max_batch: Optional[int] = None,
                 backend: Optional[str] = None, backend_kw: Optional[Dict] = None,
                 md_backend: Optional[str] = None, md_kw: Optional[Dict] = None,
                 mode: str = "exact", alarm_dir: Optional[str] = None,
                 alarm_format: str = "csv",
                 state_backend: str = "dense",
                 state_kw: Optional[Dict] = None):
        if mode != "exact":
            raise ValueError("DetectionEngine rides the fused exact-mode "
                             f"path; mode {mode!r} is not supported")
        if chunk < 1 or queue_depth < 1:
            raise ValueError("chunk and queue_depth must be positive")
        if alarm_format not in ("csv", "jsonl"):
            raise ValueError(f"alarm_format must be csv|jsonl, "
                             f"got {alarm_format!r}")
        self.net = net
        self.threshold = float(np.float32(threshold))
        self.epoch = int(epoch)
        self.mode = mode
        self.backend = resolve_backend(backend if backend is not None
                                       else "scan")
        self.backend_kw = dict(backend_kw or {})
        self.md_kw = dict(md_kw or {})
        self.md_backend = validate_md_options(
            md_backend if md_backend is not None else default_md_backend(),
            self.md_kw)
        self.chunk = int(chunk)
        self.queue_depth = int(queue_depth)
        self.max_batch = int(max_batch if max_batch is not None else n_tenants)
        self.state_backend = state_backend
        self.state_kw = dict(state_kw or {})
        self.n_slots = int(n_slots)
        self.pool = StatePool(n_tenants, n_slots, state_backend=state_backend,
                              **self.state_kw)
        self.alarm_dir = alarm_dir
        self.alarm_format = alarm_format
        # per-tenant host-side stream state (created by add_tenant)
        self._buf: Dict[int, collections.deque] = {}
        self._buffered: Dict[int, int] = {}
        self._pkt_count: Dict[int, int] = {}
        self._results: Dict[int, List] = {}
        self._lat: Dict[int, List[float]] = {}
        self._counters: Dict[int, Dict[str, int]] = {}
        self._alarm_files: Dict[int, object] = {}
        # in-flight fused batches, oldest first (dispatch-before-drain)
        self._inflight: collections.deque = collections.deque()
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._pkts_done = 0
        self._dispatches = 0
        self._single_lane_dispatches = 0

    # ------------------------------------------------------------------
    # construction from a trained service
    # ------------------------------------------------------------------
    @classmethod
    def from_service(cls, svc, **kw) -> "DetectionEngine":
        """Build an engine that runs the SAME per-chunk pipeline as a
        fitted ``DetectionService`` (net, threshold, epoch, slot budget,
        FC/MD backend selection all inherited; override via ``kw``)."""
        assert svc.net is not None, "fit the service first"
        cfg = dict(epoch=svc.epoch, n_slots=state_slots(svc.state),
                   backend=svc.backend, backend_kw=svc.backend_kw,
                   md_backend=svc.md_backend, md_kw=svc.md_kw,
                   mode=svc.mode,
                   state_backend=state_backend_of(svc.state),
                   state_kw=state_config(svc.state))
        cfg.update(kw)
        return cls(svc.net, svc.threshold, **cfg)

    # ------------------------------------------------------------------
    # tenant lifecycle
    # ------------------------------------------------------------------
    def add_tenant(self) -> int:
        """Attach a new tenant stream: claims a pool slot (fresh flow
        tables, epoch counter at zero) and an empty ingress queue."""
        tid = self.pool.alloc()
        self._buf[tid] = collections.deque()
        self._buffered[tid] = 0
        self._pkt_count[tid] = 0
        self._results[tid] = [[], [], []]
        self._lat[tid] = []
        self._counters[tid] = {"pkts_in": 0, "pkts_dropped": 0,
                               "pkts_processed": 0, "records": 0, "alarms": 0,
                               "slot_collisions": 0}
        return tid

    def remove_tenant(self, tid: int) -> None:
        """Detach a tenant and free its pool slot.  Buffered packets are
        discarded; drain in-flight work first (``flush``) if the tenant's
        remaining results matter."""
        if self._inflight:
            self._drain_all()
        self.pool.free(tid)
        for d in (self._buf, self._buffered, self._pkt_count, self._results,
                  self._lat, self._counters):
            d.pop(tid, None)
        f = self._alarm_files.pop(tid, None)
        if f is not None:
            f.close()

    def seed_tenant(self, tid: int, state: Dict, pkt_count: int = 0) -> None:
        """Start tenant ``tid`` from an existing flow-table state (a COPY
        is installed) and stream position — e.g. hand a
        ``DetectionService``'s post-training tables over so the tenant
        stream continues exactly where the training capture stopped."""
        if self._inflight:
            self._drain_all()
        self.pool.write(tid, state)
        self._pkt_count[tid] = int(pkt_count)

    def reset_tenant(self, tid: int) -> None:
        """Fresh capture on an attached tenant: zero its flow tables and
        epoch counter, drop its buffered packets (results are kept)."""
        if self._inflight:
            self._drain_all()
        self.pool._check(tid)
        self.pool.reset(tid)
        self._buf[tid].clear()
        self._buffered[tid] = 0
        self._pkt_count[tid] = 0

    # ------------------------------------------------------------------
    # ingress with backpressure
    # ------------------------------------------------------------------
    def room(self, tid: int) -> int:
        """Packets tenant ``tid``'s bounded ingress buffer still accepts."""
        return self.queue_depth * self.chunk - self._buffered[tid]

    def submit(self, tid: int, pkts: Dict[str, np.ndarray]) -> int:
        """Offer a packet batch to tenant ``tid``'s ingress queue.

        Never blocks: accepts up to ``room(tid)`` packets (FIFO order
        preserved), SHEDS the rest (drop-tail), and returns the accepted
        count; ``stats()[tid]["pkts_dropped"]`` accumulates the shed
        packets.  This is the backpressure contract — a slow device can
        cost coverage, never liveness."""
        n = len(pkts["ts"])
        self._counters[tid]["pkts_in"] += n
        take = max(0, min(n, self.room(tid)))
        if take:
            piece = {k: np.asarray(v[:take]) for k, v in pkts.items()
                     if k != "label"}
            self._buf[tid].append(piece)
            self._buffered[tid] += take
        dropped = n - take
        if dropped:
            self._counters[tid]["pkts_dropped"] += dropped
        return take

    def _pop(self, tid: int, size: int) -> Dict[str, np.ndarray]:
        """Pop exactly ``size`` packets from the front of the queue
        (splitting a buffered piece when the boundary lands inside it)."""
        buf = self._buf[tid]
        parts, got = [], 0
        while got < size:
            piece = buf.popleft()
            n = len(piece["ts"])
            if got + n > size:
                cut = size - got
                parts.append({k: v[:cut] for k, v in piece.items()})
                buf.appendleft({k: v[cut:] for k, v in piece.items()})
                got = size
            else:
                parts.append(piece)
                got += n
        self._buffered[tid] -= size
        if len(parts) == 1:
            return parts[0]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------
    def _tenant_step(self):
        from repro.serving.fused import make_tenant_step
        return make_tenant_step(backend=self.backend, mode=self.mode,
                                backend_kw=self.backend_kw,
                                md_backend=self.md_backend, md_kw=self.md_kw,
                                epoch=self.epoch)

    @functools.partial(annotate_function, name="engine.dispatch")
    def _dispatch(self, tids: List[int], size: int) -> None:
        """Pack one chunk from each tenant in ``tids`` into a single
        tenant-batched fused call.  Returns immediately with the batch in
        flight; ``self.pool.stacked`` is donated and replaced."""
        chunks = [self._pop(t, size) for t in tids]
        if self.state_backend == "dense":
            # dense-mode aliasing telemetry: distinct flow keys whose slots
            # collide inside this chunk (host-side numpy twin of the device
            # hash, so the fused call is untouched).  Sketch pools absorb
            # collisions by design and keep the counter at zero.
            with TraceAnnotation("engine.slot_collisions"):
                for t, c in zip(tids, chunks):
                    self._counters[t]["slot_collisions"] += \
                        slot_collisions(c, self.n_slots)["total"]
        pk = {k: jnp.asarray(np.stack([c[k] for c in chunks]))
              for k in chunks[0]}
        ids = jnp.asarray(np.asarray(tids, np.int32))
        base_mods = jnp.asarray(np.asarray(
            [self._pkt_count[t] % self.epoch for t in tids], np.int32))
        t0 = time.perf_counter()
        out = self._tenant_step()(self.pool.stacked, ids, self.net,
                                  np.float32(self.threshold), base_mods, pk)
        self.pool.stacked = out[0]
        self.pool.mark_dirty(tids)
        self._dispatches += 1
        self._single_lane_dispatches += len(tids) == 1
        bases = [self._pkt_count[t] for t in tids]
        for t in tids:
            self._pkt_count[t] += size
        if self._t_first is None:
            self._t_first = t0
        self._inflight.append((tids, bases, out[1:], t0, size))

    @functools.partial(annotate_function, name="engine.drain")
    def _drain_one(self) -> None:
        """Block on the OLDEST in-flight batch; only the O(records)
        sampled outputs cross to the host."""
        tids, bases, (idx, scores, alarms, counts), t0, size = \
            self._inflight.popleft()
        idx, scores = np.asarray(idx), np.asarray(scores)
        alarms, counts = np.asarray(alarms), np.asarray(counts)
        now = time.perf_counter()
        self._t_last = now
        for lane, tid in enumerate(tids):
            c = int(counts[lane])
            gi = idx[lane, :c].astype(np.int64) + bases[lane]
            sc, al = scores[lane, :c], alarms[lane, :c]
            acc = self._results[tid]
            acc[0].append(gi)
            acc[1].append(sc)
            acc[2].append(al)
            self._lat[tid].append(now - t0)
            cnt = self._counters[tid]
            cnt["pkts_processed"] += size
            cnt["records"] += c
            n_al = int(al.sum())
            cnt["alarms"] += n_al
            if n_al and self.alarm_dir is not None:
                self._log_alarms(tid, gi[al], sc[al])
        self._pkts_done += size * len(tids)

    def _drain_all(self) -> None:
        while self._inflight:
            self._drain_one()

    def step(self) -> int:
        """One engine tick: drain every READY tenant (a full chunk
        buffered) into tenant-batched fused calls, at most ``max_batch``
        lanes per call, dispatching each batch before the previous one is
        drained.  Returns the number of batches dispatched."""
        dispatched = 0
        while True:
            ready = [t for t in self.pool.live
                     if self._buffered.get(t, 0) >= self.chunk]
            if not ready:
                break
            for i in range(0, len(ready), self.max_batch):
                self._dispatch(ready[i:i + self.max_batch], self.chunk)
                dispatched += 1
                while len(self._inflight) > 1:   # keep ONE batch in flight
                    self._drain_one()
        return dispatched

    def flush(self) -> None:
        """Drain everything: remaining full chunks, then partial tails
        (tenants with equal tail length share a batch), then every
        in-flight batch.  After ``flush`` all submitted-and-accepted
        packets are reflected in ``results``."""
        self.step()
        tails: Dict[int, List[int]] = {}
        for t in self.pool.live:
            n = self._buffered.get(t, 0)
            if n:
                tails.setdefault(n, []).append(t)
        for size, tids in sorted(tails.items()):
            for i in range(0, len(tids), self.max_batch):
                self._dispatch(tids[i:i + self.max_batch], size)
                while len(self._inflight) > 1:   # keep ONE batch in flight
                    self._drain_one()
        self._drain_all()

    # ------------------------------------------------------------------
    # results / telemetry / alarm delivery
    # ------------------------------------------------------------------
    def results(self, tid: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Concatenated (global_record_indices, scores, alarms) drained so
        far for tenant ``tid`` — the same triple ``process_stream``
        returns."""
        gi, sc, al = self._results[tid]
        if not gi:
            return (np.zeros((0,), np.int64), np.zeros((0,), np.float32),
                    np.zeros((0,), bool))
        return np.concatenate(gi), np.concatenate(sc), np.concatenate(al)

    def stats(self) -> Dict:
        """Operational counters: per-tenant ingress/drop/record/alarm
        counts and p50/p99 per-chunk latency (ms), plus aggregate
        processed-packet count, pps over the dispatch→drain window, and
        the fused calls dispatched (``dispatches``), of which
        ``single_lane_dispatches`` carried one tenant.

        ``p50_ms``/``p99_ms`` run from a chunk's dispatch to its drain:
        the time a packet waited in the tenant's ingress queue before its
        chunk was dispatched is not in them."""
        per = {}
        for tid in self._counters:
            lat = np.asarray(self._lat[tid]) * 1e3
            per[tid] = dict(self._counters[tid])
            per[tid]["p50_ms"] = float(np.percentile(lat, 50)) if len(lat) else 0.0
            per[tid]["p99_ms"] = float(np.percentile(lat, 99)) if len(lat) else 0.0
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        return {"tenants": per,
                "aggregate": {"pkts_processed": self._pkts_done,
                              "wall_s": wall,
                              "pps": self._pkts_done / wall if wall else 0.0,
                              "dispatches": self._dispatches,
                              "single_lane_dispatches":
                                  self._single_lane_dispatches}}

    def _log_alarms(self, tid: int, gi: np.ndarray, sc: np.ndarray) -> None:
        f = self._alarm_files.get(tid)
        if f is None:
            os.makedirs(self.alarm_dir, exist_ok=True)
            path = os.path.join(self.alarm_dir,
                                f"tenant{tid}.{self.alarm_format}")
            f = open(path, "a")
            if self.alarm_format == "csv" and f.tell() == 0:
                f.write("tenant,record_index,score\n")
            self._alarm_files[tid] = f
        if self.alarm_format == "csv":
            f.writelines(f"{tid},{i},{s}\n" for i, s in zip(gi, sc))
        else:
            f.writelines(json.dumps({"tenant": tid, "record": int(i),
                                     "score": float(s)}) + "\n"
                         for i, s in zip(gi, sc))
        f.flush()

    def close(self) -> None:
        for f in self._alarm_files.values():
            f.close()
        self._alarm_files.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # convenience driver
    # ------------------------------------------------------------------
    def run(self, traces: Dict[int, Dict[str, np.ndarray]],
            feed: Optional[int] = None) -> Dict[int, Tuple]:
        """Feed whole traces through the engine, respecting backpressure
        (the driver pauses a tenant's feed instead of shedding), and run
        to completion: round-robin submit → tick → flush.  Returns
        ``{tid: (indices, scores, alarms)}``.  The deployment entry points
        remain ``submit``/``step``/``flush``; this is the offline/benchmark
        driver shape."""
        feed = self.chunk if feed is None else int(feed)
        cursors = {t: 0 for t in traces}
        total = {t: len(tr["ts"]) for t, tr in traces.items()}
        while True:
            moved = False
            for t, tr in traces.items():
                if cursors[t] >= total[t]:
                    continue
                take = min(feed, total[t] - cursors[t], self.room(t))
                if take:
                    piece = {k: v[cursors[t]:cursors[t] + take]
                             for k, v in tr.items()}
                    self.submit(t, piece)
                    cursors[t] += take
                    moved = True
            self.step()
            if not moved and all(cursors[t] >= total[t] for t in traces):
                break
        self.flush()
        return {t: self.results(t) for t in traces}
