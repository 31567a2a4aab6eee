"""The plain reference: the serial FC in C against the program's serial
oracle, pool laps and shed ranges against an explicit stream, and the
KitNET reference against the program's scorer.  The program is imported
here only to check the reference; the reference itself imports nothing of
it."""
import numpy as np
import pytest

from bench import flowhash, gen, reference, spec

N_SLOTS, EPOCH = 256, 16
LOOSE = ("std", "radius", "cov", "pcc")   # variance-cancellation columns


def _pool(n=3000, seed=4):
    # few flows at a slow nominal rate: long flows, seconds between
    # packets, so every decay and both directions are exercised
    m = dict(spec.mix("backbone_zipf"), pool_packets=n, flows=64,
             src_hosts=16, dst_hosts=8, link_bps=1e4)
    return gen.pools(m, 1, seed)[0]


def _feats(p, n_gen, n_rec, prec="f64", ts=None, span=None):
    sl, db = flowhash.slots(p.fields, N_SLOTS)
    return reference.fc_features(sl, db, p.length,
                                 p.ts_base if ts is None else ts,
                                 p.span if span is None else span, n_gen,
                                 N_SLOTS, EPOCH, n_rec, prec=prec)


def test_slot_hash_matches_the_program():
    jnp = pytest.importorskip("jax.numpy")
    from repro.core.state import packet_slots
    p = _pool()
    sl, db = flowhash.slots(p.fields, N_SLOTS)
    ps = packet_slots({k: jnp.asarray(v) for k, v in p.fields.items()},
                      N_SLOTS)
    for i, k in enumerate(flowhash.KEYS):
        assert np.array_equal(np.asarray(ps[k]), sl[i])
    assert np.array_equal(np.asarray(ps["dir"]), db)


def test_fc_reference_matches_the_serial_oracle():
    """The oracle runs in float64 here, on the float32 timestamps the
    program is given.  In float32 the variance columns cancel
    (E[x^2] - mean^2) where a flow's packets are all one size, which is the
    program's rounding and not the reference's semantics."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import FEATURE_NAMES, compute_features, init_state
    p = _pool()
    n = p.size
    with jax.enable_x64():
        pk = {k: jnp.asarray(v) for k, v in p.slice(0, n).items()}
        pk["ts"] = pk["ts"].astype(jnp.float64)
        pk["length"] = pk["length"].astype(jnp.float64)
        st = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float64) if x.dtype == jnp.float32 else x,
            init_state(N_SLOTS))
        _, f = compute_features(st, pk, backend="serial")
        f = np.asarray(f)[EPOCH - 1::EPOCH]
    ref = _feats(p, n, n // EPOCH)
    loose = np.array([nm.endswith(LOOSE) for nm in FEATURE_NAMES])
    d = np.abs(f - ref)
    assert (d[:, ~loose] <= 1e-3 + 1e-4 * np.abs(ref[:, ~loose])).all()
    assert (d[:, loose] <= 0.5 + 1e-3 * np.abs(ref[:, loose])).all()


def test_laps_match_an_explicit_stream():
    p = _pool(n=1000)
    n_gen = 2600
    idx = np.arange(n_gen)
    lap, j = np.divmod(idx, p.size)
    flat = gen.Pool(fields={k: p.fields[k][j] for k in gen.FIELDS},
                    length=p.length[j], label=p.label[j],
                    ts_base=p.ts_base[j] + lap * p.span, span=1.0)
    n_rec = n_gen // EPOCH
    a = _feats(p, n_gen, n_rec)
    b = _feats(flat, n_gen, n_rec, ts=flat.ts_base, span=1.0)
    assert a.shape == (n_rec, 80)
    np.testing.assert_array_equal(a, b)


def test_bf16_control_is_far_from_float64():
    p = _pool()
    a, b = _feats(p, p.size, 50), _feats(p, p.size, 50, prec="bf16")
    rel = np.abs(a - b) / (1 + np.abs(a))
    assert rel.max() > 0.1


def _net(k=4, m=5, h=4, kh=3, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(20)[:m] for _ in range(k)]).astype(np.int32)
    f32 = lambda *s: rng.normal(size=s).astype(np.float32).astype(np.float64)
    return {"idx": idx, "mask": np.ones((k, m)), "W1": f32(k, m, h),
            "b1": f32(k, h), "W2": f32(k, h, m), "b2": f32(k, m),
            "V1": f32(k, kh), "c1": f32(kh), "V2": f32(kh, k), "c2": f32(k),
            "norm_min": np.zeros(20), "norm_max": np.full(20, 10.0),
            "out_min": np.zeros(k), "out_max": np.ones(k)}


def test_kitnet_reference_matches_the_program_scorer():
    jnp = pytest.importorskip("jax.numpy")
    from repro.detection.kitnet import KitNet, score_kitnet
    net = _net()
    x = np.random.default_rng(1).uniform(0, 12, (64, 20)).astype(np.float32)
    f = lambda v: jnp.asarray(np.asarray(v, np.float32))
    prog = KitNet(idx=jnp.asarray(net["idx"]), mask=f(net["mask"]),
                  params={n: f(net[n]) for n in ("W1", "b1", "W2", "b2",
                                                 "V1", "c1", "V2", "c2")},
                  norm_min=f(net["norm_min"]), norm_max=f(net["norm_max"]),
                  out_min=f(net["out_min"]), out_max=f(net["out_max"]))
    ref = reference.kitnet_scores(net, x.astype(np.float64))
    got = score_kitnet(prog, x)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    ctl = reference.kitnet_scores(net, x.astype(np.float64), prec="bf16")
    assert np.max(np.abs(ctl - ref) / ref) > 1e-3
