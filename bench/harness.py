"""One run of one cell: set-up, the measured window, the check, the result.

Set-up makes every tenant's traffic pool from the seed, fits KitNET's
normalisers and threshold with the plain reference on the first records
(``weights.py``), builds ``DetectionEngine`` with the program's default FC
and MD backends, and warms every lane count the window batches
(``lane_counts``) through ``submit`` -> ``step``.  The window then drives the
same public entry with a saturating feed: a closed loop on ingress
``room``, whole chunks submitted whenever a tenant has room, so the engine
is never short of work and nothing is shed.  ``pps`` is packets drained in
the window over its seconds.

After the window the feed goes on (up to ``GRACE_S``) until every record
of the chunks submitted in the window has come back; then the peak device
memory is read, the engine is dropped, and every record returned is
compared with the plain reference (``reference.py``) over the same stream:
its indices, its score and its alarm, tenant by tenant.

A cell on more than one chip runs from the engine's build through the
collection of its results under its configuration's ``placement``, bound
by ``flow_mesh`` over exactly the cell's chips (``placed``).
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import device, flowhash, gen, reference, spec, tracing, weights, work

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GRACE_S = 60.0


class CompileCounter:
    """Backend compilations (persistent-cache loads included), counted from
    JAX's own monitoring event."""

    def __init__(self):
        import jax
        self.count = 0
        self.on = True

        def listen(event, secs, **_kw):
            if event == COMPILE_EVENT and self.on:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed place: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(spec.CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def lane_counts(tenants: int, max_batch: int) -> List[int]:
    """The lane counts of the batches a saturating feed makes: every tenant
    has a chunk ready at each step, so the engine dispatches full groups of
    ``max_batch`` and the remainder."""
    full, rest = divmod(tenants, max_batch)
    return ([max_batch] if full else []) + ([rest] if rest else [])


@contextlib.contextmanager
def placed(cell: spec.Cell, devs: Sequence):
    """The cell's placement bound over a mesh of exactly ``devs``; nothing
    is bound for one chip."""
    if cell.chips == 1:
        yield
        return
    from repro.distributed.sharding import flow_mesh
    with flow_mesh(cell.chips, axis=spec.MESH_AXIS,
                   rules=cell.config["placement"]) as mesh:
        if list(mesh.devices.flat) != list(devs):
            raise RuntimeError(f"the mesh holds {list(mesh.devices.flat)}, "
                               f"the cell runs on {list(devs)}")
        yield


@dataclass
class Tenant:
    tid: int
    pool: gen.Pool
    slots: np.ndarray                 # (4, P) pool slots
    dirb: np.ndarray                  # (P,) channel direction bit
    submitted: int = 0                # stream packets the engine has taken
    dispatched: int = 0               # packets the engine has dispatched
    warm_end: int = 0                 # packets submitted before the window


class Run:
    """Set-up and window state of one run."""

    def __init__(self, cell: spec.Cell, seed: int, tracing_on: bool):
        self.seed, self.cfg, self.mix = seed, cell.config, cell.mix
        self.tracing_on = tracing_on
        self.m: Dict = {}

    # -- spans: TraceAnnotations only in a traced run ----------------------
    def span(self, name: str):
        if not self.tracing_on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        cfg = self.cfg
        T, n_slots, epoch = int(cfg["tenants"]), int(cfg["n_slots"]), \
            int(cfg["epoch"])
        pools = gen.pools(self.mix, T, self.seed)
        self.tenants = []
        for t, p in enumerate(pools):
            sl, db = flowhash.slots(p.fields, n_slots)
            self.tenants.append(Tenant(t, p, sl, db))
        per = -(-int(cfg["fit_records"]) // T)
        prefix = np.concatenate([
            reference.fc_features(t.slots, t.dirb, t.pool.length,
                                  t.pool.ts_base, t.pool.span, per * epoch,
                                  n_slots, epoch, per)
            for t in self.tenants])
        self.net = weights.make(cfg, self.seed, prefix)
        from repro.serving import DetectionEngine
        self.engine = DetectionEngine(
            weights.program_net(self.net), self.net.threshold, epoch=epoch,
            n_slots=n_slots, n_tenants=T, chunk=int(cfg["chunk"]),
            queue_depth=int(cfg["queue_depth"]),
            max_batch=int(cfg["max_batch"]))
        for t in self.tenants:
            if self.engine.add_tenant() != t.tid:
                raise RuntimeError("tenant ids must be 0..T-1 in order")
        # warm each lane count the window batches: L tenants ready, one step
        chunk = int(cfg["chunk"])
        for lanes in lane_counts(T, int(cfg["max_batch"])):
            for t in self.tenants[:lanes]:
                self.offer(t, chunk)
            self.step()
        for t in self.tenants:
            t.warm_end = t.submitted

    # -- the engine's entry --------------------------------------------------
    def offer(self, t: Tenant, n: int) -> None:
        """Submit the next ``n`` stream packets of tenant ``t``; the feed
        offers only what fits, so the engine must take them all."""
        a = t.submitted
        with self.span("bench.generate"):
            piece = t.pool.slice(a, a + n)
        with self.span("engine.submit"):
            took = self.engine.submit(t.tid, piece)
        if took != n:
            raise RuntimeError(f"tenant {t.tid}: the engine took {took} of "
                               f"{n} packets offered within its room")
        t.submitted += n

    def step(self) -> int:
        rooms = [self.engine.room(t.tid) for t in self.tenants]
        with self.span("engine.step"):
            n = self.engine.step()
        for t, r in zip(self.tenants, rooms):
            t.dispatched += self.engine.room(t.tid) - r
        return n

    def feed(self) -> None:
        """Fill every tenant's ingress room with whole chunks."""
        chunk = int(self.cfg["chunk"])
        for t in self.tenants:
            room = self.engine.room(t.tid)
            if room >= chunk:
                self.offer(t, room // chunk * chunk)

    # -- the window ----------------------------------------------------------
    def window(self, seconds: float) -> None:
        import jax
        counter = CompileCounter()
        trace_at = (0.25 * seconds, 0.25 * seconds + min(4.0, 0.5 * seconds))
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if self.tracing_on else None
        self.traced = None
        trace_span = None
        disp0 = None
        processed0 = self.engine.stats()["aggregate"]["pkts_processed"]
        clock = time.perf_counter
        t0 = clock()
        now = 0.0
        batches = 0
        while now < seconds:
            if self.tracing_on:
                if trace_span is None and now >= trace_at[0]:
                    jax.profiler.start_trace(self.trace_dir)
                    trace_span = jax.profiler.TraceAnnotation(tracing.WINDOW)
                    trace_span.__enter__()
                    disp0 = [t.dispatched for t in self.tenants]
                    batches = 0
                elif trace_span is not None and self.traced is None \
                        and now >= trace_at[1]:
                    trace_span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    self.traced = (batches, disp0,
                                   [t.dispatched for t in self.tenants])
            self.feed()
            batches += self.step()
            now = clock() - t0
        if trace_span is not None and self.traced is None:
            trace_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.traced = (batches, disp0, [t.dispatched for t in self.tenants])
        counter.on = False
        m = self.m
        m["window_s"] = now
        m["compiles_in_window"] = counter.count
        m["drained_packets"] = \
            self.engine.stats()["aggregate"]["pkts_processed"] - processed0
        self.end_submitted = [t.submitted for t in self.tenants]
        self._grace(now, t0, clock)

    def _grace(self, now, t0, clock) -> None:
        """Keep feeding until the window's records are back."""
        epoch = int(self.cfg["epoch"])
        want = [a // epoch for a in self.end_submitted]
        while now < self.m["window_s"] + GRACE_S:
            stats = self.engine.stats()["tenants"]
            if all(stats[t.tid]["records"] >= w
                   for t, w in zip(self.tenants, want)):
                break
            self.feed()
            self.step()
            now = clock() - t0
        self.unanswered = sum(
            max(0, w - stats[t.tid]["records"])
            for t, w in zip(self.tenants, want))
        self.unanswered_pkts = self.unanswered * epoch

    # -- after the window ----------------------------------------------------
    def collect(self, devs) -> None:
        """Read what the engine returned, then drop the engine."""
        self.m["memory_peak_bytes"] = device.memory_peak_bytes(devs)
        self.results = [tuple(np.asarray(a) for a in
                              self.engine.results(t.tid))
                        for t in self.tenants]
        del self.engine
        gc.collect()

    def check(self, prec: str = "f64") -> Dict[str, float]:
        """The numbers compared with the reference's, over every returned
        record, and how many records and reference alarms were compared.
        ``prec="bf16"`` puts the bfloat16 reference in the program's place
        (the control).

        ``score_gap`` is the widest relative gap of a score from the
        reference's; ``alarm_margin`` the widest distance of a reference
        score from the threshold, as a share of it, among records whose
        alarm disagrees with the reference's (0 where none does)."""
        cfg = self.cfg
        n_slots, epoch = int(cfg["n_slots"]), int(cfg["epoch"])
        thr = self.net.threshold
        gap, margin, missing, compared, alarms = 0.0, 0.0, 0, 0, 0
        for t, (gi, sc, al) in zip(self.tenants, self.results):
            n_rec = int(gi.max()) // epoch + 1 if len(gi) else 0
            expect = (np.arange(n_rec, dtype=np.int64) + 1) * epoch - 1
            missing += int(np.setxor1d(expect, gi).size) + \
                int(len(gi) - np.unique(gi).size)
            if not n_rec:
                continue
            stream = (t.slots, t.dirb, t.pool.length, t.pool.ts_base,
                      t.pool.span, n_rec * epoch, n_slots, epoch, n_rec)
            ref = reference.kitnet_scores(self.net.host,
                                          reference.fc_features(*stream))
            if prec == "f64":
                keep = np.isin(gi, expect)
                rows = gi[keep] // epoch
                got_s, got_a = sc[keep].astype(np.float64), al[keep]
            else:
                rows = np.arange(n_rec)
                got_s = reference.kitnet_scores(
                    self.net.host,
                    reference.fc_features(*stream, prec="bf16"), prec="bf16")
                got_a = got_s > thr
            r = ref[rows]
            with np.errstate(divide="ignore", invalid="ignore"):
                rel = np.abs(got_s - r) / np.abs(r)
            rel = np.where(np.isfinite(rel), rel, np.inf)
            if len(rel):
                gap = max(gap, float(rel.max()))
            wrong = (r > thr) != got_a.astype(bool)
            if wrong.any():
                margin = max(margin, float(np.abs(r[wrong] / thr - 1.0).max()))
            compared += len(rows)
            alarms += int((r > thr).sum())
        return {"score_gap": gap, "alarm_margin": margin,
                "records_missing": missing, "unanswered": self.unanswered,
                "compared": compared, "reference_alarms": alarms}

    def reduce_trace(self, peak: Dict, devs: Sequence) -> None:
        """Per-layer inputs from the traced slice on the cell's chips."""
        red = tracing.reduce(tracing.load(self.trace_dir),
                             [d.id for d in devs])
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        batches, d0, d1 = self.traced
        chunk, epoch = int(self.cfg["chunk"]), int(self.cfg["epoch"])
        kn = work.kitnet_shapes(self.cfg["kitnet"]["ensemble_sizes"],
                                float(self.cfg["kitnet"]["hidden_ratio"]))
        tot = {"bytes": 0.0, "ops": 0.0}
        for t, a, b in zip(self.tenants, d0, d1):
            for c0 in range(a, b, chunk):
                g = np.arange(c0, c0 + chunk) % t.pool.size
                w = work.step_work(chunk, work.distinct_rows(t.slots[:, g]),
                                   chunk // epoch, 1, kn)
                tot["bytes"] += w["bytes"]
                tot["ops"] += w["ops"]
        self.m.update(trace=red, batches_traced=batches,
                      least_s_traced=work.least_seconds(tot, peak)
                      if batches else None)


def judge(checks: Dict[str, float], cfg: Dict) -> Tuple[bool, Dict]:
    """``correct`` and the limit of every compared number: the
    configuration's ``checks`` (set from sound runs, the control and the
    planted faults), and 0 for the exact counts."""
    limits = dict(cfg["checks"], records_missing=0, unanswered=0)
    ok = checks["compared"] > 0 and all(checks[k] <= limits[k]
                                        for k in limits)
    return ok, limits


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, cell: Optional[spec.Cell] = None) -> Dict:
    """One run; returns the result object printed as the last line."""
    import jax
    cell = cell or spec.cell(cell_name)
    devs = device.chips(cell.chips)
    kind = devs[0].device_kind
    peak = device.peaks(kind)
    enable_compile_cache()
    r = Run(cell, seed, trace)
    with placed(cell, devs):
        r.setup()
        r.m["setup_s"] = time.perf_counter() - t_start
        r.window(seconds)
        r.collect(devs)
    if trace:
        r.reduce_trace(peak, devs)
    checks = r.check()
    correct, limits = judge(checks, cell.config)
    metrics = {}
    for mdef in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(mdef["name"])(r.m)
        if v is not None:
            metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}
    dev = {"platform": devs[0].platform, "kind": kind,
           "count": jax.device_count(),
           "memory_peak_bytes": r.m["memory_peak_bytes"]}
    out = {"correct": bool(correct),
           "attempted": int(sum(r.end_submitted)
                            - sum(t.warm_end for t in r.tenants)),
           "failed": int(r.unanswered_pkts), "metrics": metrics,
           "device": dev}
    if trace:
        red = r.m["trace"]
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in red.device_ops],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    info = {"records_compared": checks["compared"],
            "reference_alarms": checks["reference_alarms"],
            "window_s": r.m["window_s"],
            "compiles_in_window": r.m["compiles_in_window"]}
    if trace:
        info["busy_s_per_device"] = red.busy_s_per_device
    print("info " + " ".join(f"{k}={v}" for k, v in info.items()),
          file=sys.stderr)
    out["info"] = info
    for k in limits:
        print(f"check {k} {checks[k]!r} limit {limits[k]!r}", file=sys.stderr)
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in limits}
    return out
