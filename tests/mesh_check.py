"""Forced-multi-device worker for tests/test_mesh.py.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is read ONCE, when
jax initialises its backend, so a test session that already imported jax
cannot re-enter a different device topology in-process.  This script is
the escape hatch: ``test_mesh.py`` launches it as a subprocess per device
count —

    python tests/mesh_check.py <n_devices> [battery ...]

— it forces the topology BEFORE importing jax, runs the requested check
batteries (default: all), and prints one ``MESH-OK <battery>`` marker per
battery that passed.  Any assertion failure escapes as a traceback and a
nonzero exit, which the pytest side reports verbatim.

Batteries:

* ``ambient``  — distributed/sharding resolution at N>1: ``flow_mesh``
  binds an N-device mesh, ``ambient_mesh``/``flow_shards_binding``/
  ``tenant_binding`` see it, ``core/bucketed._resolve_placement`` accepts
  it (and falls back when the bucket count does not divide), and the
  placement cache keys (``_shard_ctx`` / fused ``_placement_token``)
  include the device count.
* ``parity``   — bucketed:S features AND final state on the N-device
  ``flow_shards`` mesh match the single-device flat-scan run across all
  attack generators, to the serial-oracle tolerance envelope of
  tests/test_bucketed.py.
* ``fused``    — fused-service stream continuity under the mesh: one-shot
  vs chunked ``process_stream`` under ``flow_mesh(N)``, and both against
  the unplaced single-device run (identical record indices, float-
  tolerance scores).
* ``sketch``   — sketch-backend state under a bound mesh: the Count-Min
  compute path runs unchanged with the mesh rules active (bit-identical
  state and features to the unplaced run).
* ``engine``   — the multi-tenant engine with its tenant axis spread over
  the mesh: per-tenant results match the unplaced engine, and the placed
  tenant step is a distinct compiled executable (cache keyed on
  placement).
"""
import os
import re
import sys

N_DEVICES = int(sys.argv[1]) if len(sys.argv) > 1 else 2
BATTERIES = sys.argv[2:] or ["ambient", "parity", "fused", "sketch",
                             "engine"]

# force the topology before jax initialises; strip any stale force flag
flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEVICES} " + flags)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (FEATURE_NAMES, N_FEATURES, compute_features,
                        init_state)
from repro.distributed.sharding import (ambient_mesh, flow_mesh,
                                        flow_shards_binding, tenant_binding)
from repro.traffic.generator import ATTACKS, benign_trace

assert jax.device_count() == N_DEVICES, (
    f"forced {N_DEVICES} devices, jax sees {jax.device_count()}")

N_PKTS = 256
N_SLOTS = 512
BUCKETS = 8

_PCC = [i for i, nm in enumerate(FEATURE_NAMES) if nm.endswith(":pcc")]
_NON_PCC = np.setdiff1d(np.arange(N_FEATURES), _PCC)


def _trace(attack, seed=0, n=N_PKTS):
    rng = np.random.default_rng(seed)
    ben = benign_trace(160, 6.0, rng)
    atk = ATTACKS[attack](120, 1.0, 5.0, rng)
    out = {k: np.concatenate([ben[k], atk[k]]) for k in ben}
    order = np.argsort(out["ts"], kind="stable")
    out = {k: v[order][:n] for k, v in out.items()}
    assert len(out["ts"]) == n, attack
    return {k: jnp.asarray(v) for k, v in out.items() if k != "label"}


def _assert_envelope(f, f_ref, tag):
    """The serial-oracle tolerance envelope of tests/test_bucketed.py."""
    ok = np.abs(f - f_ref) <= (1.0 + 1e-3 * np.abs(f_ref))
    assert ok[:, _NON_PCC].all(), (tag, "non-pcc envelope")
    assert ok.mean() >= 0.995, (tag, float(ok.mean()))


def _assert_state(st, st_ref, tag, exact=False):
    for grp in ("uni", "bi"):
        for k in st_ref[grp]:
            a, b = np.asarray(st[grp][k]), np.asarray(st_ref[grp][k])
            if exact or k == "rr":
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{tag}/{grp}/{k}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=1.0,
                                           err_msg=f"{tag}/{grp}/{k}")


def battery_ambient():
    from repro.core.bucketed import _resolve_placement, _shard_ctx
    from repro.serving.fused import _placement_token

    tok_out = _placement_token()
    assert _resolve_placement(BUCKETS) == (None, None)
    with flow_mesh(N_DEVICES) as mesh:
        m = ambient_mesh()
        assert m is not None and m.size == N_DEVICES, m
        assert flow_shards_binding() == "data"
        assert tenant_binding() == "data"
        rm, rb = _resolve_placement(BUCKETS)
        assert rm is not None and rb == "data", (rm, rb)
        # bucket counts that do not divide over the axis fall back
        assert _resolve_placement(N_DEVICES + 1) == (None, None)
        ctx = _shard_ctx(rm, rb, jax.device_count())
        assert ctx is not None and ctx.size == N_DEVICES
        # one cached context per (mesh, binding, device count)
        assert _shard_ctx(rm, rb, jax.device_count()) is ctx
        tok_in = _placement_token()
        assert tok_in != tok_out
        assert tok_in[-1] == N_DEVICES, tok_in  # device count is in the key
        assert tok_in[2] is not None and tok_in[2] == mesh.abstract_mesh
    assert _placement_token() == tok_out
    print("MESH-OK ambient")


def battery_parity():
    for attack in sorted(ATTACKS):
        pk = _trace(attack)
        st_ref, f_ref = compute_features(init_state(N_SLOTS), pk,
                                         backend="scan")
        with flow_mesh(N_DEVICES):
            st, f = compute_features(init_state(N_SLOTS), pk,
                                     backend="bucketed", buckets=BUCKETS)
        _assert_envelope(np.asarray(f), np.asarray(f_ref),
                         (attack, N_DEVICES))
        _assert_state(st, st_ref, f"{attack}/N={N_DEVICES}")
    print("MESH-OK parity")


def _fitted_bucketed_service():
    from repro.serving import DetectionService
    from repro.traffic import synth_trace

    data = synth_trace("mirai", n_train=1024, n_benign_eval=512,
                       n_attack=512, seed=0)
    svc = DetectionService(epoch=64, n_slots=N_SLOTS, mode="exact",
                           backend="bucketed", buckets=BUCKETS)
    svc.observe_stream(data["train"], chunk=512)
    svc.fit(fpr=0.05)
    ev = {k: v for k, v in data["eval"].items() if k != "label"}
    return svc, ev


def battery_fused():
    svc, ev = _fitted_bucketed_service()
    snap = jax.tree_util.tree_map(jnp.copy, svc.state)
    c0 = svc.pkt_count
    i_ref, s_ref, _ = svc.process(ev, fused=True)       # unplaced baseline
    svc.state = jax.tree_util.tree_map(jnp.copy, snap)
    svc.pkt_count = c0
    with flow_mesh(N_DEVICES):
        i1, s1, _ = svc.process(ev, fused=True)
    svc.state, svc.pkt_count = snap, c0
    with flow_mesh(N_DEVICES):
        i2, s2, _ = svc.process_stream(ev, chunk=256, fused=True)
    assert len(np.asarray(i_ref)) > 0
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-5)
    print("MESH-OK fused")


def battery_sketch():
    pk = _trace("mirai")
    st_ref, f_ref = compute_features(
        init_state(N_SLOTS, state_backend="sketch", rows=2), pk)
    with flow_mesh(N_DEVICES):
        st, f = compute_features(
            init_state(N_SLOTS, state_backend="sketch", rows=2), pk)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    for k, v in st_ref.items():
        if hasattr(v, "shape"):
            np.testing.assert_array_equal(np.asarray(st[k]), np.asarray(v),
                                          err_msg=k)
    print("MESH-OK sketch")


def battery_engine():
    from repro.serving import DetectionEngine
    from repro.serving.fused import make_tenant_step

    svc, ev = _fitted_bucketed_service()

    def run():
        eng = DetectionEngine.from_service(svc, n_tenants=2, chunk=256,
                                           queue_depth=4)
        tids = [eng.add_tenant() for _ in range(2)]
        out = eng.run({t: ev for t in tids})
        eng.close()
        return out

    kw = dict(backend="bucketed", backend_kw={"buckets": BUCKETS},
              epoch=64)
    o_ref = run()
    step_ref = make_tenant_step(**kw)
    with flow_mesh(N_DEVICES):
        o_mesh = run()
        assert make_tenant_step(**kw) is not step_ref
    assert make_tenant_step(**kw) is step_ref
    for t in o_ref:
        idx_r, sc_r, al_r = o_ref[t]
        idx_m, sc_m, al_m = o_mesh[t]
        assert len(idx_r) > 0
        np.testing.assert_array_equal(idx_r, idx_m, err_msg=str(t))
        np.testing.assert_allclose(sc_r, sc_m, rtol=1e-4, atol=1e-6,
                                   err_msg=str(t))
    print("MESH-OK engine")


if __name__ == "__main__":
    for b in BATTERIES:
        globals()[f"battery_{b}"]()
    print("MESH-DONE")
