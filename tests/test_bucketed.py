"""Bucketed mesh-parallel FC engine (core/bucketed.py): parity with the
flat scan backend across every attack generator and bucket count, ragged
batches, streaming continuity through DetectionService, the fused
record-sampled path, shard_map mesh placement, and the scan-fusion
primitive-count regressions (DESIGN.md §9).

Tolerance model: S=1 degenerates to the flat scan and must be
*bit-identical*.  S>1 reassociates the segmented combines at bucket cuts
(two-level scan), so raw atoms agree to a few ulp and cancellation-derived
columns (std/radius/cov) to the same envelope the scan backend itself is
held to against the serial oracle (tests/test_backends.py) — bucketed is
exactly as close to the serial oracle as scan is, which the oracle-parity
test pins directly.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FEATURE_NAMES, N_FEATURES, available_backends,
                        compute_features, init_state, process_bucketed,
                        resolve_backend)
from repro.core.backends import compute_features_sampled
from repro.traffic.generator import ATTACKS, benign_trace

N_PKTS = 256
N_SLOTS = 512

BUCKET_COUNTS = (1, 4, 16)

_PCC = [i for i, nm in enumerate(FEATURE_NAMES) if nm.endswith(":pcc")]
_NON_PCC = np.setdiff1d(np.arange(N_FEATURES), _PCC)


def _trace(attack: str, seed: int = 0, n: int = N_PKTS):
    """Benign background + one attack window, truncated to a fixed length
    so every parametrization shares one jit compilation per bucket count."""
    rng = np.random.default_rng(seed)
    ben = benign_trace(160, 6.0, rng)
    atk = ATTACKS[attack](120, 1.0, 5.0, rng)
    out = {k: np.concatenate([ben[k], atk[k]]) for k in ben}
    order = np.argsort(out["ts"], kind="stable")
    out = {k: v[order][:n] for k, v in out.items()}
    assert len(out["ts"]) == n, attack
    return {k: jnp.asarray(v) for k, v in out.items() if k != "label"}


@pytest.fixture(scope="module")
def scan_reference():
    cache = {}

    def get(attack):
        if attack not in cache:
            pk = _trace(attack)
            st, feats = compute_features(init_state(N_SLOTS), pk,
                                         backend="scan")
            cache[attack] = (pk, st, np.asarray(feats))
        return cache[attack]

    return get


@pytest.fixture(scope="module")
def serial_reference():
    cache = {}

    def get(attack):
        if attack not in cache:
            pk = _trace(attack)
            st, feats = compute_features(init_state(N_SLOTS), pk,
                                         backend="serial", mode="exact")
            cache[attack] = (pk, st, np.asarray(feats))
        return cache[attack]

    return get


# ---------------------------------------------------------------------------
# parity with the flat scan backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("buckets", BUCKET_COUNTS)
@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_bucketed_matches_scan(scan_reference, attack, buckets):
    """Features AND post-batch state track the flat scan: bit-identical at
    S=1 (the two-level path degenerates to one flat scan), a few-ulp
    reassociation envelope beyond (amplified only by the documented
    cancellation columns)."""
    pk, st_ref, f_ref = scan_reference(attack)
    st, f = compute_features(init_state(N_SLOTS), pk, backend="bucketed",
                             buckets=buckets)
    f = np.asarray(f)
    assert f.shape == (N_PKTS, N_FEATURES)
    assert np.isfinite(f).all()
    if buckets == 1:
        np.testing.assert_array_equal(f, f_ref, err_msg=attack)
    else:
        ok = np.abs(f - f_ref) <= (1.0 + 1e-3 * np.abs(f_ref))
        assert ok[:, _NON_PCC].all(), (attack, buckets)
        assert ok.mean() >= 0.995, (attack, buckets, ok.mean())
    for grp in ("uni", "bi"):
        for k in st_ref[grp]:
            a, b = np.asarray(st[grp][k]), np.asarray(st_ref[grp][k])
            if buckets == 1 or k == "rr":
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{attack}/S={buckets}/{grp}/{k}")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-3, atol=1.0,
                    err_msg=f"{attack}/S={buckets}/{grp}/{k}")


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_bucketed_matches_serial_oracle(serial_reference, attack):
    """Bucketed is held to the SAME serial-oracle envelope as the scan
    backend (test_backends.py): bucketing must not add error beyond the
    scan backend's own fp reassociation."""
    pk, st_ref, f_ref = serial_reference(attack)
    st, f = compute_features(init_state(N_SLOTS), pk, backend="bucketed",
                             buckets=4)
    f = np.asarray(f)
    ok = np.abs(f - f_ref) <= (1.0 + 1e-3 * np.abs(f_ref))
    assert ok[:, _NON_PCC].all(), attack
    assert ok.mean() >= 0.995, (attack, ok.mean())
    for grp in ("uni", "bi"):
        for k in st_ref[grp]:
            if k == "rr":
                continue
            np.testing.assert_allclose(
                np.asarray(st[grp][k]), np.asarray(st_ref[grp][k]),
                rtol=1e-3, atol=1.0, err_msg=f"{attack}/{grp}/{k}")


def test_bucketed_ragged_batch_padding():
    """n not divisible by S: sentinel-slot padding must neither leak into
    real flow state nor change the emitted row count."""
    pk = _trace("mirai", n=250)
    st_ref, f_ref = compute_features(init_state(N_SLOTS), pk,
                                     backend="scan")
    st, f = compute_features(init_state(N_SLOTS), pk, backend="bucketed",
                             buckets=16)                 # pad = 6
    f = np.asarray(f)
    assert f.shape == (250, N_FEATURES)
    ok = np.abs(f - np.asarray(f_ref)) <= (1.0 + 1e-3 * np.abs(f_ref))
    assert ok[:, _NON_PCC].all()
    for grp in ("uni", "bi"):
        for k in st_ref[grp]:
            np.testing.assert_allclose(
                np.asarray(st[grp][k]), np.asarray(st_ref[grp][k]),
                rtol=1e-3, atol=1.0, err_msg=f"{grp}/{k}")


# ---------------------------------------------------------------------------
# streaming + service integration
# ---------------------------------------------------------------------------
def test_bucketed_streaming_chunks_track_one_shot():
    """Chunked streaming with state carry tracks one-shot processing to
    the scan backend's cross-chunk tolerance (DESIGN.md §5: reduction
    order differs across chunk boundaries)."""
    pk = _trace("mirai")
    _, f_once = compute_features(init_state(N_SLOTS), pk,
                                 backend="bucketed", buckets=4)
    st = init_state(N_SLOTS)
    outs = []
    for i in range(0, N_PKTS, 64):
        chunk = {k: v[i:i + 64] for k, v in pk.items()}
        st, f = compute_features(st, chunk, backend="bucketed", buckets=4)
        outs.append(np.asarray(f))
    got, want = np.concatenate(outs), np.asarray(f_once)
    ok = np.abs(got - want) <= (1.0 + 1e-3 * np.abs(want))
    assert ok[:, _NON_PCC].all()
    assert ok.mean() >= 0.995


def test_detection_service_bucketed_stream_continuity():
    """DetectionService(backend='bucketed'): fused + staged paths agree,
    and chunked process_stream carries state/epoch accounting so record
    indices are identical to a one-batch run (scores to float tolerance —
    scan semantics, DESIGN.md §5)."""
    from repro.serving import DetectionService
    from repro.traffic import synth_trace

    data = synth_trace("mirai", n_train=768, n_benign_eval=256,
                       n_attack=256, seed=0)
    svc = DetectionService(epoch=32, n_slots=N_SLOTS, mode="exact",
                           backend="bucketed", buckets=4)
    svc.observe_stream(data["train"], chunk=256)
    svc.fit(fpr=0.05)
    assert svc.fused                     # exact mode defaults to fused
    ev = {k: v for k, v in data["eval"].items() if k != "label"}
    snap = jax.tree_util.tree_map(jnp.copy, svc.state)
    c0 = svc.pkt_count
    i1, s1, a1 = svc.process(ev, fused=True)
    assert len(i1) > 0
    svc.state, svc.pkt_count = jax.tree_util.tree_map(jnp.copy, snap), c0
    i2, s2, _ = svc.process(ev, fused=False)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)
    svc.state, svc.pkt_count = snap, c0
    i3, s3, _ = svc.process_stream(ev, chunk=96, fused=True)
    np.testing.assert_array_equal(i1, i3)
    np.testing.assert_allclose(s1, s3, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused record-sampled path
# ---------------------------------------------------------------------------
def test_bucketed_sampled_rows_match_full():
    """compute_features_sampled(backend='bucketed'): the same scans and
    store-backs run in both passes, so state matches to XLA-refusion ulp
    noise (the compiler fuses the scan combine differently depending on
    the emission subgraph — the scan backend has the identical envelope;
    the decayed residual-product sum ``sr`` reaches ~1e-5 relative) and
    emitted rows match full[idx] to the cancellation-column envelope."""
    pk = _trace("syn_dos")
    idx = jnp.asarray([5, 31, 63, 200, 255])
    st_f, full = compute_features(init_state(N_SLOTS), pk,
                                  backend="bucketed", buckets=4)
    st_s, rows = compute_features_sampled(init_state(N_SLOTS), pk, idx,
                                          backend="bucketed", buckets=4)
    for grp in ("uni", "bi"):
        for k in st_f[grp]:
            np.testing.assert_allclose(
                np.asarray(st_s[grp][k]), np.asarray(st_f[grp][k]),
                rtol=1e-4, atol=1e-3, err_msg=f"{grp}/{k}")
    want = np.asarray(full)[np.asarray(idx)]
    got = np.asarray(rows)
    ok = np.abs(got - want) <= (1.0 + 1e-3 * np.abs(want))
    assert ok[:, _NON_PCC].all()
    assert ok.mean() >= 0.995


def test_bucketed_sampled_is_registered():
    """The fused serving step must get the native record-sampled path —
    a bucketed service's fused jit never materialises unsampled rows."""
    from repro.core.backends import _SAMPLED
    assert "bucketed" in _SAMPLED


# ---------------------------------------------------------------------------
# mesh placement
# ---------------------------------------------------------------------------
def test_bucketed_under_mesh_rules_shard_map():
    """flow_shards binding + a bound mesh routes the local per-bucket
    scans through shard_map; with a 1-device mesh the computation is
    identical, so results must be bit-identical to the unplaced run."""
    from repro.core.bucketed import _resolve_placement
    from repro.distributed.sharding import auto_mesh, use_rules

    pk = _trace("os_scan")
    _, f_ref = compute_features(init_state(N_SLOTS), pk,
                                backend="bucketed", buckets=4)
    mesh = auto_mesh((jax.device_count(),), ("data",))
    with jax.set_mesh(mesh):
        with use_rules({"flow_shards": "data"}):
            m, binding = _resolve_placement(4)
            assert m is not None and binding == "data"
            _, f = compute_features(init_state(N_SLOTS), pk,
                                    backend="bucketed", buckets=4)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
    # unplaced fallbacks: no rules bound, and a rule naming a missing axis
    assert _resolve_placement(4) == (None, None)
    with jax.set_mesh(mesh):
        with use_rules({"flow_shards": "nope"}):
            assert _resolve_placement(4) == (None, None)


def test_fused_step_cache_keyed_on_placement():
    """Regression: binding a mesh + flow_shards rule mid-stream must hand
    back a DIFFERENT fused step (the partitioned backends resolve their
    placement at trace time, so a cached single-device executable would
    silently keep running unplaced)."""
    from repro.serving.fused import make_fused_step
    from repro.distributed.sharding import auto_mesh, use_rules

    unplaced = make_fused_step(backend="bucketed",
                               backend_kw={"buckets": 4}, epoch=32)
    mesh = auto_mesh((jax.device_count(),), ("data",))
    with jax.set_mesh(mesh):
        with use_rules({"flow_shards": "data"}):
            placed = make_fused_step(backend="bucketed",
                                     backend_kw={"buckets": 4}, epoch=32)
    assert placed is not unplaced
    # and re-resolving outside the context returns the unplaced step again
    assert make_fused_step(backend="bucketed", backend_kw={"buckets": 4},
                           epoch=32) is unplaced


# ---------------------------------------------------------------------------
# registry + error paths
# ---------------------------------------------------------------------------
def test_bucketed_registered_exact_only():
    assert "bucketed" in available_backends()
    assert resolve_backend("bucketed") == "bucketed"
    st = init_state(64)
    pk = _trace("syn_dos")
    with pytest.raises(ValueError, match="switch"):
        compute_features(st, pk, backend="bucketed", mode="switch")
    with pytest.raises(ValueError, match="buckets"):
        process_bucketed(st, pk, buckets=0)


# ---------------------------------------------------------------------------
# scan-fusion primitive counts (the perf contract of this engine)
# ---------------------------------------------------------------------------
def _count_sorts(jaxpr):
    c = 0
    for eq in jaxpr.eqns:
        if eq.primitive.name == "sort":
            c += 1
        for p in eq.params.values():
            for q in (p if isinstance(p, (list, tuple)) else (p,)):
                if hasattr(q, "jaxpr"):
                    c += _count_sorts(q.jaxpr)
    return c


def _dummy_batch(n=64):
    pk = {k: jnp.zeros((n,), jnp.int32)
          for k in ("src", "dst", "sport", "dport", "proto")}
    pk["ts"] = jnp.linspace(0.0, 1.0, n)
    pk["length"] = jnp.ones((n,))
    return pk


@pytest.mark.parametrize("chunks,max_scans", [(1, 4), (4, 8)])
def test_scan_fusion_primitive_counts(chunks, max_scans):
    """The fused pipeline pays ONE stacked associative scan per stream
    table (atoms w/ls/ss ride together), ONE latest-value scan per channel
    pass (both directions x atoms+residual lanes), and ONE SR scan — 4
    invocations per batch where the unfused code paid 11.  The bucketed
    two-level form doubles each (local scans + the O(S) tail-carry
    combine): ≤ 2 per stream table, as budgeted in DESIGN.md §9.  Sort
    primitives stay at ≤ 4 (one stable argsort per key type, vmapped) —
    bucket compaction derives from the existing sort, it never adds one."""
    from repro.core.parallel import _process_parallel_impl
    st = init_state(256)
    pk = _dummy_batch()
    with mock.patch.object(jax.lax, "associative_scan",
                           wraps=jax.lax.associative_scan) as m:
        jaxpr = jax.make_jaxpr(
            lambda s, p: _process_parallel_impl(s, p, chunks=chunks))(st, pk)
    assert m.call_count <= max_scans, m.call_count
    assert _count_sorts(jaxpr.jaxpr) <= 4
