"""The traffic generator: deterministic per seed, Zipf and IMIX shares as
the mix states, pools replayed in laps."""
import hashlib

import numpy as np
import pytest

from bench import gen, spec


def _backbone(**over):
    return dict(spec.mix("backbone_zipf"), pool_packets=200_000,
                flows=8192, **over)


@pytest.mark.parametrize("seed", [2**31 + 12345, 0])
def test_same_seed_same_pool_other_seed_other_pool(seed):
    m = _backbone()
    a, b, c = gen.pools(m, 1, seed), gen.pools(m, 1, seed), gen.pools(m, 1, 7)
    for pa, pb, pc in zip(a, b, c):
        for f in gen.FIELDS:
            assert np.array_equal(pa.fields[f], pb.fields[f])
        assert np.array_equal(pa.ts_base, pb.ts_base)
        assert np.array_equal(pa.length, pb.length)
        assert not np.array_equal(pa.fields["src"], pc.fields["src"])


def test_backbone_zipf_and_imix_shares():
    m = _backbone(attack_share=0.0, reverse_share=0.0)
    p = gen.pools(m, 1, 3)[0]
    F = m["flows"]
    key = (p.fields["src"].astype(np.uint64) << np.uint64(32)) | \
        p.fields["sport"].astype(np.uint64)
    _, counts = np.unique(key, return_counts=True)
    top = np.sort(counts)[::-1]
    h = np.sum(1.0 / np.arange(1, F + 1))
    # the most popular flow carries 1/H(F) of the packets, the tenth 1/(10 H)
    assert top[0] / p.size == pytest.approx(1.0 / h, rel=0.05)
    assert top[9] / p.size == pytest.approx(0.1 / h, rel=0.15)
    sizes, n = np.unique(p.length, return_counts=True)
    assert list(sizes) == [64, 594, 1518]
    assert n / n.sum() == pytest.approx(np.array([7, 4, 1]) / 12, abs=0.01)
    assert p.length.mean() == pytest.approx(362.0, rel=0.02)
    # wire-time spacing at the nominal link rate
    assert p.span == pytest.approx(p.length.astype(float).sum() * 8
                                   / m["link_bps"], rel=1e-9)


def test_attack_share_and_families():
    m = _backbone()
    p = gen.pools(m, 1, 5)[0]
    assert p.label.mean() == pytest.approx(m["attack_share"], abs=1e-4)
    assert set(m["attacks"]) <= set(gen.ATTACKS)


def test_unknown_shape_and_tenant_count_raise():
    with pytest.raises(ValueError):
        gen.pools(dict(_backbone(), shape="enterprise"), 1, 1)
    with pytest.raises(ValueError):
        gen.pools(_backbone(), 2, 1)


def test_slices_replay_the_pool_in_laps():
    p = gen.pools(_backbone(), 1, 2)[0]
    P = p.size
    s = p.slice(P - 3, P + 3)
    assert np.array_equal(s["src"], np.r_[p.fields["src"][-3:],
                                          p.fields["src"][:3]])
    assert np.all(np.diff(s["ts"]) >= 0)
    due = np.r_[p.ts_base[-3:], p.ts_base[:3] + p.span]
    assert np.array_equal(s["ts"], due.astype(np.float32))
    assert p.ts_base[0] == 0.0 and p.span > p.ts_base[-1]


def _digest(p):
    h = hashlib.sha256()
    for f in gen.FIELDS:
        h.update(p.fields[f].tobytes())
    for a in (p.length, p.label, p.ts_base):
        h.update(a.tobytes())
    h.update(np.float64(p.span).tobytes())
    return h.hexdigest()


# digests of the pools the generator drew when the backbone shape was code
# of gen.py: the shape's file draws them bit for bit
@pytest.mark.parametrize("over,seed,digest", [
    (dict(pool_packets=200_000, flows=8192), 2**31 + 12345,
     "bbe889c3b58914ed31f4b70eb11ab19feabd309e413db193a2ed18226810792b"),
    (dict(pool_packets=200_000, flows=8192), 0,
     "99fcf83a1ed460de9a6a45cc1bf6a292bf5e8c36cf379b5ddcd7b86f064d2e04"),
    ({}, 2**31 + 777,
     "a4e98fa5bcec30bcd88241dceb88dff25839984f9ed241219704a692d3add305"),
])
def test_backbone_shape_draws_the_pools_it_always_drew(over, seed, digest):
    m = dict(spec.mix("backbone_zipf"), **over)
    assert _digest(gen.pools(m, 1, seed)[0]) == digest
