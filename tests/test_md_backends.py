"""MD scoring backends: einsum vs fused-Pallas parity on every attack
generator, per-chunk streaming-score equality, and the train-time RMSE-pass
dispatch (repro/detection/md_backends.py, DESIGN.md §3)."""
import jax
import numpy as np
import pytest

from repro.core import compute_features, init_state
from repro.detection import (available_md_backends, resolve_md_backend,
                             score_kitnet, score_records, train_kitnet)
from repro.serving import DetectionService
from repro.traffic import ATTACKS, attack_trace, benign_trace, synth_trace, to_jnp

N_SLOTS = 2048


def _feats(trace):
    _, f = compute_features(init_state(N_SLOTS), to_jnp(trace),
                            backend="scan")
    return np.asarray(f)


@pytest.fixture(scope="module")
def net():
    """One KitNET fitted on benign features (the deployed object both
    backends must agree on)."""
    tr = benign_trace(1500, 8.0, np.random.default_rng(0))
    return train_kitnet(_feats(tr)[::4], seed=0)


def test_registry_and_aliases():
    assert available_md_backends() == ("einsum", "pallas")
    assert resolve_md_backend("kernel") == "pallas"
    assert resolve_md_backend("batched") == "einsum"
    with pytest.raises(ValueError, match="unknown MD backend"):
        resolve_md_backend("nope")


def test_unknown_md_options_rejected(net):
    """Misspelled/inapplicable md_kw options raise instead of silently
    measuring the defaults."""
    feats = np.zeros((4, 80), np.float32)
    with pytest.raises(TypeError, match="unexpected options"):
        score_records(net, feats, backend="pallas", block=256)  # typo of bb
    with pytest.raises(TypeError, match="unexpected options"):
        score_records(net, feats, backend="einsum", bb=256)
    with pytest.raises(TypeError, match="unexpected options"):
        DetectionService(n_slots=64, md_backend="pallas",
                         md_kw={"block": 256})


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_einsum_pallas_score_parity(net, attack):
    """score_records(backend="pallas") tracks the einsum reference to
    ≤1e-5 on the feature distribution of every attack generator."""
    feats = _feats(attack_trace(attack, 600, 0.0, 10.0, seed=1))
    s_e = score_records(net, feats, backend="einsum")
    s_p = score_records(net, feats, backend="pallas")
    assert np.isfinite(s_e).all() and np.isfinite(s_p).all()
    np.testing.assert_allclose(s_p, s_e, atol=1e-5, rtol=1e-5)
    # the einsum backend IS the historical score_kitnet path
    np.testing.assert_array_equal(s_e, score_kitnet(net, feats))


def test_pallas_scores_batch_independent(net):
    """Per-record scores must not depend on batch composition — the
    property that makes per-chunk streaming scoring exact."""
    feats = _feats(attack_trace("mirai", 400, 0.0, 10.0, seed=2))
    one = score_records(net, feats, backend="pallas")
    chunked = np.concatenate([
        score_records(net, feats[i:i + 37], backend="pallas")
        for i in range(0, len(feats), 37)])
    np.testing.assert_array_equal(one, chunked)


def test_train_kitnet_md_backend_dispatch():
    """train_kitnet's training-set RMSE pass runs through the selected
    backend; the resulting nets score equivalently (≤1e-5)."""
    rng = np.random.default_rng(3)
    feats = rng.random((600, 80)).astype(np.float32)
    n_e = train_kitnet(feats, seed=0)
    n_p = train_kitnet(feats, seed=0, md_backend="pallas",
                       md_kw={"bb": 64})
    np.testing.assert_allclose(np.asarray(n_p.out_min),
                               np.asarray(n_e.out_min), atol=1e-5)
    np.testing.assert_allclose(np.asarray(n_p.out_max),
                               np.asarray(n_e.out_max), atol=1e-5)
    batch = rng.random((100, 80)).astype(np.float32) * 2.0
    np.testing.assert_allclose(score_records(n_p, batch, backend="pallas"),
                               score_records(n_e, batch, backend="einsum"),
                               atol=1e-5, rtol=1e-5)


def test_process_stream_chunked_equals_one_batch_pallas_md():
    """Per-chunk MD scoring (pallas backend, serial-semantics FC): chunked
    global indices, scores, and alarms are bit-identical to one-batch."""
    data = synth_trace("mirai", n_train=1024, n_benign_eval=512,
                       n_attack=512, seed=4)
    svc = DetectionService(epoch=64, n_slots=1024, mode="exact",
                           backend="serial", md_backend="pallas",
                           md_kw={"bb": 32})   # MD flags route via md_kw
    assert svc.md_backend == "pallas"
    svc.observe_stream(data["train"], chunk=256)
    svc.fit(fpr=0.05)
    snap_state = jax.tree_util.tree_map(jax.numpy.copy, svc.state)  # fused steps donate
    snap_count = svc.pkt_count

    idx1, s1, a1 = svc.process(data["eval"])
    svc.state, svc.pkt_count = snap_state, snap_count
    # uneven chunking so epoch boundaries straddle chunk boundaries
    idx2, s2, a2 = svc.process_stream(data["eval"], chunk=200)

    np.testing.assert_array_equal(idx1, idx2)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(a1, a2)


@pytest.mark.parametrize("interpret", [None, True, False])
def test_kitnet_ensemble_interpret_rule(interpret):
    """kitnet_ensemble follows the kernels' interpret rule: ``None`` and
    ``True`` interpret on the CPU (bitwise the same kernel run), and
    ``False`` demands the Mosaic compile, which the CPU refuses."""
    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.uniform(ks[0], (13, 3, 6))
    w1 = jax.random.normal(ks[1], (3, 6, 4)) * 0.3
    b1 = jax.random.normal(ks[2], (3, 4)) * 0.1
    w2 = jax.random.normal(ks[3], (3, 4, 6)) * 0.3
    b2 = jax.random.normal(ks[4], (3, 6)) * 0.1
    mask = (jax.random.uniform(ks[0], (3, 6)) > 0.2).astype(np.float32)

    if interpret is False:
        with pytest.raises(ValueError, match="interpret mode"):
            ops.kitnet_ensemble(x, w1, b1, w2, b2, mask, bb=8,
                                interpret=False)
        return
    r = ops.kitnet_ensemble(x, w1, b1, w2, b2, mask, bb=8,
                            interpret=interpret)
    r_forced = ops.kitnet_ensemble(x, w1, b1, w2, b2, mask, bb=8,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_forced))
    want = ref.kitnet_ensemble_ref(x, w1, b1, w2, b2, mask)
    np.testing.assert_allclose(np.asarray(r), np.asarray(want), atol=1e-6)
