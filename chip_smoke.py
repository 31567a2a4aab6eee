#!/usr/bin/env python3
"""Smoke run of the served detection path on a TPU chip.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

One process drives the path through the entry points a user calls, at the
deployment widths the repo supports (8,192-slot flow tables, one record
per 1,024 packets, 8,192-packet chunks), on seeded synthetic traffic with
a seeded KitNET.  Phases (one chip):

  device   JAX must report a TPU; anything else exits 2 before any phase.
  train    ``DetectionService`` (scan FC + einsum KitNET) observes a
           98,304-packet benign prefix and fits.
  serve    ``process_stream`` over a 98,304-packet eval window with a
           Mirai attack mixed in: finite scores, attack AUC above the
           floor, and the first chunk's scan features inside the serial
           oracle's envelope.
  engine   ``DetectionEngine`` runs 4 tenant streams; each tenant's
           records match a solo service run of the same stream.
  pallas   the FC, sketch and KitNET Pallas kernels, compiled (a
           ``tpu_custom_call`` in the executable), against the serial FC
           oracle, the JAX sketch path and the einsum KitNET.

``--chips 4`` runs ``bucketed:4`` FC and the engine's tenant axis on a
4-device ``flow_mesh``, each against the same work on one device, and
prints the device set of the outputs' shardings.

Each phase prints one ``SMOKE {...}`` line with its checks, compile seconds
and wall seconds: a smoke run, not a benchmark.  Any failed check raises,
and the script exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
ATTACK = "mirai"
N_SLOTS = 8192
EPOCH = 1024
CHUNK = 8192
N_TRAIN = 12 * CHUNK
N_EVAL = 12 * CHUNK
N_TENANTS = 4
SKETCH_ROWS = 4
# attack-record AUC on this seed: 0.8202 in the CPU rehearsal (XLA:CPU,
# same code and seed); the floor leaves room for a few records whose
# rank moves under TPU rounding (96 eval records)
AUC_FLOOR = 0.78


def check(ok, what: str):
    if not ok:
        raise AssertionError(f"smoke check failed: {what}")


class _CompileClock:
    """Seconds of XLA backend compilation (persistent-cache reads included),
    from JAX's own monitoring event; tracing and lowering are not counted
    (they nest, and would count twice)."""

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


def phase(clock, name):
    """Decorator: run a phase, print its SMOKE line, return its result."""
    def run(fn):
        c0, t0 = clock.total, time.perf_counter()
        info = fn() or {}
        line = {"smoke": name, "ok": True,
                "compile_s": round(clock.total - c0, 3),
                "wall_s": round(time.perf_counter() - t0, 3), **info}
        print("SMOKE " + json.dumps(line), flush=True)
        return info
    return run


def _copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def _no_label(trace):
    return {k: v for k, v in trace.items() if k != "label"}


def _envelope(f, f_ref, what):
    """The serial-oracle envelope of the scan backend
    (tests/test_backends.py): every non-PCC value within 1 + 1e-3|ref|,
    and at least 99.5% of all values."""
    import numpy as np
    from repro.core import FEATURE_NAMES
    pcc = np.array([nm.endswith(":pcc") for nm in FEATURE_NAMES])
    ok = np.abs(f - f_ref) <= 1.0 + 1e-3 * np.abs(f_ref)
    check(ok[:, ~pcc].all(), f"{what}: non-pcc features outside envelope")
    check(ok.mean() >= 0.995, f"{what}: {ok.mean():.4f} inside envelope")
    return float(np.max(np.abs(f - f_ref) / (1.0 + np.abs(f_ref))))


def _rounding_close(f, f_ref, what):
    """Kernel-vs-reference agreement up to rounding
    (tests/test_state_backends.py): 1e-3 + 1e-4|ref| everywhere except the
    variance-cancellation columns (std/radius/cov/pcc), which get
    0.5 + 1e-3|ref| on their O(1e5) inputs."""
    import numpy as np
    from repro.core import FEATURE_NAMES
    loose = np.array([nm.endswith((":cov", ":pcc", ":radius", ":std"))
                      for nm in FEATURE_NAMES])
    d = np.abs(f - f_ref)
    check((d[:, ~loose] <= 1e-3 + 1e-4 * np.abs(f_ref[:, ~loose])).all(),
          f"{what}: tight columns")
    check((d[:, loose] <= 0.5 + 1e-3 * np.abs(f_ref[:, loose])).all(),
          f"{what}: cancellation columns")
    return float(np.max(d[:, ~loose] / (1.0 + np.abs(f_ref[:, ~loose]))))


def _kernel_compiled(fn, *args):
    """AOT-compile ``fn`` for the chip; the Mosaic kernel must be in it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call")
    return compiled


def _devices_of(x):
    return sorted(d.id for d in x.sharding.device_set)


def _train(clock, n_train=N_TRAIN):
    from repro.serving import DetectionService
    from repro.traffic import synth_trace

    data = synth_trace(ATTACK, n_train=n_train, n_benign_eval=N_EVAL // 2,
                       n_attack=N_EVAL // 2, seed=SEED)
    svc = DetectionService(epoch=EPOCH, n_slots=N_SLOTS)

    @phase(clock, "train")
    def _():
        check(svc.backend == "scan" and svc.md_backend == "einsum",
              "default backends")
        idx = svc.observe_stream(data["train"], chunk=CHUNK)
        svc.fit(fpr=0.01)
        check(len(idx) == n_train // EPOCH, "training records")
        return {"packets": n_train, "records": len(idx),
                "threshold": svc.threshold}

    return svc, data


def _solo_service(svc):
    from repro.serving import DetectionService
    solo = DetectionService(epoch=EPOCH, n_slots=N_SLOTS)
    solo.net, solo.threshold = svc.net, svc.threshold
    return solo


def one_chip(clock):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import compute_features, init_state
    from repro.detection.md_backends import md_score_fn, score_records
    from repro.detection.metrics import auc
    from repro.serving import DetectionEngine
    from repro.traffic import to_jnp

    svc, data = _train(clock)
    snap = _copy(svc.state)                     # trained tables
    ev = data["eval"]
    chunk0 = to_jnp({k: v[:CHUNK] for k, v in ev.items()})
    ref = {}

    @phase(clock, "serve")
    def _():
        start = svc.pkt_count
        idx, scores, alarms = svc.process_stream(ev, chunk=CHUNK)
        labels = ev["label"][idx - start]
        a = auc(scores, labels)
        check(len(scores) == N_EVAL // EPOCH, "eval records")
        check(np.isfinite(scores).all(), "finite scores")
        check(a >= AUC_FLOOR, f"attack AUC {a:.4f} < {AUC_FLOOR}")
        _, f_scan = compute_features(_copy(snap), chunk0, backend="scan")
        st_ser, f_ser = compute_features(_copy(snap), chunk0,
                                         backend="serial")
        ref["feats"], ref["state"] = np.asarray(f_ser), st_ser
        dev = _envelope(np.asarray(f_scan), ref["feats"], "scan vs serial")
        return {"packets": N_EVAL, "records": len(scores),
                "alarms": int(alarms.sum()), "auc": round(a, 4),
                "scan_vs_serial_max_rel": dev}

    @phase(clock, "engine")
    def _():
        per = N_EVAL // N_TENANTS
        streams = [_no_label({k: v[t * per:(t + 1) * per]
                              for k, v in ev.items()})
                   for t in range(N_TENANTS)]
        eng = DetectionEngine.from_service(svc, n_tenants=N_TENANTS,
                                           chunk=CHUNK)
        tids = [eng.add_tenant() for _ in range(N_TENANTS)]
        out = eng.run(dict(zip(tids, streams)))
        eng.close()
        worst = 0.0
        for t, s in zip(tids, streams):
            i_s, s_s, _ = _solo_service(svc).process_stream(s, chunk=CHUNK)
            i_e, s_e, _ = out[t]
            check(len(i_e) == per // EPOCH, f"tenant {t} records")
            check(np.array_equal(i_e, i_s), f"tenant {t} record indices")
            np.testing.assert_allclose(s_e, s_s, rtol=1e-4, atol=1e-6,
                                       err_msg=f"tenant {t} scores")
            worst = max(worst, float(np.max(np.abs(s_e - s_s))))
        return {"tenants": N_TENANTS, "packets_per_tenant": per,
                "max_abs_score_diff": worst}

    @phase(clock, "pallas")
    def _():
        fc = lambda st, pk: compute_features(st, pk, backend="pallas")
        fc.__name__ = "fc_pallas"
        st_p, f_p = _kernel_compiled(fc, snap, chunk0)(_copy(snap), chunk0)
        fc_dev = _rounding_close(np.asarray(f_p), ref["feats"],
                                 "pallas FC vs serial")
        for grp in ("uni", "bi"):
            for k in ("w", "ls", "last_t"):
                np.testing.assert_allclose(
                    np.asarray(st_p[grp][k]),
                    np.asarray(ref["state"][grp][k]), rtol=1e-4, atol=1e-3,
                    err_msg=f"pallas FC state {grp}/{k}")

        sk0 = init_state(N_SLOTS, state_backend="sketch", rows=SKETCH_ROWS)
        _, f_sk_ref = compute_features(_copy(sk0), chunk0)
        sk = lambda st, pk: compute_features(st, pk, backend="pallas")
        sk.__name__ = "sketch_pallas"
        _, f_sk = _kernel_compiled(sk, sk0, chunk0)(_copy(sk0), chunk0)
        sk_dev = _rounding_close(np.asarray(f_sk), np.asarray(f_sk_ref),
                                 "pallas sketch vs JAX sketch")

        recs = jnp.asarray(ref["feats"])
        s_e = score_records(svc.net, ref["feats"], backend="einsum")
        md = md_score_fn("pallas")
        md.__name__ = "kitnet_pallas"
        s_p = np.asarray(_kernel_compiled(md, svc.net, recs)(svc.net, recs))
        np.testing.assert_allclose(s_p, s_e, rtol=1e-5, atol=1e-5,
                                   err_msg="pallas MD vs einsum")
        return {"tpu_custom_call": ["fc_pallas", "sketch_pallas",
                                    "kitnet_pallas"],
                "fc_vs_serial_max_rel": fc_dev,
                "sketch_vs_jax_max_rel": sk_dev,
                "md_vs_einsum_max_abs": float(np.max(np.abs(s_p - s_e)))}


def four_chips(clock):
    import jax
    import numpy as np
    from repro.core import compute_features
    from repro.distributed.sharding import flow_mesh
    from repro.serving import DetectionEngine
    from repro.serving.fused import make_tenant_step
    from repro.traffic import to_jnp

    check(jax.device_count() == 4, f"{jax.device_count()} devices, want 4")
    svc, data = _train(clock, n_train=4 * CHUNK)
    snap = _copy(svc.state)
    ev = data["eval"]
    chunk0 = to_jnp({k: v[:CHUNK] for k, v in ev.items()})

    @phase(clock, "mesh4_bucketed")
    def _():
        st1, f1 = compute_features(_copy(snap), chunk0, backend="bucketed",
                                   buckets=4)
        with flow_mesh(4):
            st4, f4 = compute_features(_copy(snap), chunk0,
                                       backend="bucketed", buckets=4)
        dev = _envelope(np.asarray(f4), np.asarray(f1),
                        "bucketed:4 mesh vs one device")
        for grp in ("uni", "bi"):
            for k in ("w", "ls", "ss"):
                np.testing.assert_allclose(
                    np.asarray(st4[grp][k]), np.asarray(st1[grp][k]),
                    rtol=1e-3, atol=1.0, err_msg=f"bucketed state {grp}/{k}")
        return {"feats_devices": _devices_of(f4),
                "feats_sharding": str(f4.sharding),
                "state_devices": _devices_of(st4["bi"]["w"]),
                "max_rel_vs_one_device": dev}

    @phase(clock, "mesh4_engine")
    def _():
        per = 2 * CHUNK
        streams = [_no_label({k: v[t * per:(t + 1) * per]
                              for k, v in ev.items()})
                   for t in range(N_TENANTS)]

        def run():
            eng = DetectionEngine.from_service(svc, n_tenants=N_TENANTS,
                                               chunk=CHUNK)
            tids = [eng.add_tenant() for _ in range(N_TENANTS)]
            out = eng.run(dict(zip(tids, streams)))
            eng.close()
            return [out[t] for t in tids], eng

        one, _ = run()
        with flow_mesh(4):
            placed, eng = run()
            # the engine's tenant step on one 4-lane batch, to show where
            # the lanes land
            step = make_tenant_step(epoch=EPOCH)
            pk = {k: jax.numpy.stack([to_jnp(s)[k][:CHUNK] for s in streams])
                  for k in streams[0]}
            out = step(_copy(eng.pool.stacked),
                       jax.numpy.arange(N_TENANTS, dtype=jax.numpy.int32),
                       svc.net, np.float32(svc.threshold),
                       jax.numpy.zeros(N_TENANTS, jax.numpy.int32), pk)
        worst = 0.0
        for t, ((i1, s1, _), (i4, s4, _)) in enumerate(zip(one, placed)):
            check(len(i4) == per // EPOCH, f"tenant {t} records")
            check(np.array_equal(i1, i4), f"tenant {t} record indices")
            np.testing.assert_allclose(s4, s1, rtol=1e-4, atol=1e-6,
                                       err_msg=f"tenant {t} scores")
            worst = max(worst, float(np.max(np.abs(s4 - s1))))
        return {"tenants": N_TENANTS,
                "scores_devices": _devices_of(out[2]),
                "scores_sharding": str(out[2].sharding),
                "pool_devices": _devices_of(
                    jax.tree_util.tree_leaves(out[0])[0]),
                "max_abs_score_diff_vs_one_device": worst}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        sys.exit(2)
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = _CompileClock()
    print("SMOKE " + json.dumps({"smoke": "device", "ok": True,
                                 "kind": dev.device_kind,
                                 "count": jax.device_count(),
                                 "compile_cache": cache}), flush=True)
    (four_chips if args.chips == 4 else one_chip)(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
