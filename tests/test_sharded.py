"""Sharded flow-table backend: bit-exact equivalence with the serial oracle
across every attack generator and shard count, streaming chunk-carry, mesh
placement, and registry/service integration.

Slots never interact, so hash-partitioning the tables (shard = slot mod S)
and running the oracle's per-packet update inside each shard must reproduce
the serial backend *bit for bit* — these tests assert exact equality, far
inside the 1e-5 relative budget.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (N_FEATURES, available_backends, compute_features,
                        init_state, process_sharded, resolve_backend)
from repro.core.sharded import shard_tables, unshard_tables
from repro.traffic.generator import ATTACKS, benign_trace

N_PKTS = 256
N_SLOTS = 512

SHARD_COUNTS = (1, 4, 16)


def _trace(attack: str, seed: int = 0):
    """Benign background + one attack window, truncated to a fixed length
    so every parametrization shares one jit compilation per shard count."""
    rng = np.random.default_rng(seed)
    ben = benign_trace(160, 6.0, rng)
    atk = ATTACKS[attack](120, 1.0, 5.0, rng)
    out = {k: np.concatenate([ben[k], atk[k]]) for k in ben}
    order = np.argsort(out["ts"], kind="stable")
    out = {k: v[order][:N_PKTS] for k, v in out.items()}
    assert len(out["ts"]) == N_PKTS, attack
    return {k: jnp.asarray(v) for k, v in out.items() if k != "label"}


@pytest.fixture(scope="module")
def reference():
    cache = {}

    def get(attack):
        if attack not in cache:
            pk = _trace(attack)
            st, feats = compute_features(init_state(N_SLOTS), pk,
                                         backend="serial", mode="exact")
            cache[attack] = (pk, st, np.asarray(feats))
        return cache[attack]

    return get


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_sharded_matches_serial_bitexact(reference, attack, shards):
    pk, st_ref, f_ref = reference(attack)
    st, f = compute_features(init_state(N_SLOTS), pk, backend="sharded",
                             shards=shards)
    f = np.asarray(f)
    assert f.shape == (N_PKTS, N_FEATURES)
    np.testing.assert_array_equal(f, f_ref, err_msg=f"{attack}/S={shards}")
    for grp in ("uni", "bi"):
        for k in st_ref[grp]:
            np.testing.assert_array_equal(
                np.asarray(st[grp][k]), np.asarray(st_ref[grp][k]),
                err_msg=f"{attack}/S={shards}/{grp}/{k}")


def test_sharded_switch_mode_matches_serial():
    """Round-robin counters are per-slot state, so switch mode shards too."""
    pk = _trace("syn_dos")
    _, f_ref = compute_features(init_state(N_SLOTS), pk, backend="serial",
                                mode="switch")
    _, f = compute_features(init_state(N_SLOTS), pk, backend="sharded",
                            mode="switch", shards=4)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))


def test_sharded_streaming_chunks_bitexact():
    """Chunked streaming with state carry == one-shot, bit for bit."""
    pk = _trace("mirai")
    _, f_once = compute_features(init_state(N_SLOTS), pk, backend="sharded",
                                 shards=4)
    st = init_state(N_SLOTS)
    outs = []
    for i in range(0, N_PKTS, 64):
        chunk = {k: v[i:i + 64] for k, v in pk.items()}
        st, f = compute_features(st, chunk, backend="sharded", shards=4)
        outs.append(np.asarray(f))
    np.testing.assert_array_equal(np.concatenate(outs), np.asarray(f_once))


def test_shard_unshard_roundtrip():
    st = init_state(64)
    for shards in (1, 4, 16):
        back = unshard_tables(shard_tables(st, shards), shards)
        for grp in ("uni", "bi"):
            for k in st[grp]:
                np.testing.assert_array_equal(np.asarray(back[grp][k]),
                                              np.asarray(st[grp][k]),
                                              err_msg=f"S={shards}/{grp}/{k}")


def test_sharded_rejects_uneven_partition():
    st = init_state(100)           # 100 % 16 != 0
    pk = _trace("syn_dos")
    with pytest.raises(ValueError, match="not divisible"):
        process_sharded(st, pk, shards=16)


def test_sharded_registered_with_both_modes():
    assert "sharded" in available_backends()
    assert resolve_backend("sharded") == "sharded"
    st = init_state(64)
    pk = _trace("syn_dos")
    # scan/pallas still reject switch mode; the error names the alternatives
    with pytest.raises(ValueError, match="sharded"):
        compute_features(st, pk, backend="scan", mode="switch")


def test_detection_service_sharded_backend():
    from repro.serving import DetectionService
    svc = DetectionService(epoch=64, n_slots=N_SLOTS, backend="sharded",
                           shards=4)
    idx = svc.observe_benign(_trace("mirai"))
    assert svc.pkt_count == N_PKTS
    assert list(idx) == [63, 127, 191, 255]          # global record indices
    assert svc._train_feats[0].shape == (4, N_FEATURES)


def test_sharded_under_mesh_rules():
    """flow_shards logical-axis placement: bound rules + a 1-device mesh
    must leave results bit-identical (the constraint is layout, not math)."""
    import jax
    from repro.distributed.sharding import auto_mesh, use_rules

    pk = _trace("os_scan")
    _, f_ref = compute_features(init_state(N_SLOTS), pk, backend="serial",
                                mode="exact")
    mesh = auto_mesh((jax.device_count(),), ("data",))
    with jax.set_mesh(mesh):
        with use_rules({"flow_shards": "data"}):
            _, f = compute_features(init_state(N_SLOTS), pk,
                                    backend="sharded", shards=4)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
