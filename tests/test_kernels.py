"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,K,Sq,Sk,D", [
    (1, 4, 4, 64, 64, 32),      # MHA square
    (2, 4, 2, 64, 64, 64),      # GQA
    (1, 8, 1, 96, 96, 32),      # MQA, non-multiple of block
    (2, 4, 4, 1, 128, 32),      # decode-like single query
    (1, 2, 2, 200, 72, 64),     # Sq > Sk ragged blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, H, K, Sq, Sk, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, K, Sk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, K, Sk, D)).astype(dtype)
    causal = Sq == Sk
    out = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap", [(16, 0.0), (0, 30.0), (24, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 80, 32))
    k = jax.random.normal(ks[1], (1, 2, 80, 32))
    v = jax.random.normal(ks[2], (1, 2, 80, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("n,n_slots,chunk", [(100, 64, 32), (257, 128, 64),
                                             (512, 32, 256)])
def test_feature_update_kernel(n, n_slots, chunk):
    rng = np.random.default_rng(n)
    table = {f: (jnp.zeros((n_slots, 4)) - (1.0 if f == "last_t" else 0.0))
             for f in ("last_t", "w", "ls", "ss")}
    slots = jnp.asarray(rng.integers(0, n_slots, n), jnp.int32)
    ts = jnp.asarray(np.sort(rng.uniform(0, 5, n)), jnp.float32)
    lens = jnp.asarray(rng.integers(60, 1500, n), jnp.float32)
    t1, s1 = ops.feature_update(table, slots, ts, lens, chunk=chunk)
    t2, s2 = ref.feature_update_ref(table, slots, ts, lens)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-3)
    for f in t1:
        np.testing.assert_allclose(np.asarray(t1[f]), np.asarray(t2[f]),
                                   rtol=1e-5, atol=1e-3)


def test_feature_update_warm_table():
    """Carry-in from a warm table must match the serial oracle."""
    rng = np.random.default_rng(0)
    n_slots = 64
    table = {f: (jnp.zeros((n_slots, 4)) - (1.0 if f == "last_t" else 0.0))
             for f in ("last_t", "w", "ls", "ss")}
    for r in range(3):
        n = 150
        slots = jnp.asarray(rng.integers(0, n_slots, n), jnp.int32)
        ts = jnp.asarray(np.sort(rng.uniform(r * 5, r * 5 + 5, n)), jnp.float32)
        lens = jnp.asarray(rng.integers(60, 1500, n), jnp.float32)
        t1, s1 = ops.feature_update(table, slots, ts, lens, chunk=64)
        t2, s2 = ref.feature_update_ref(table, slots, ts, lens)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5, atol=1e-3)
        table = t1


@pytest.mark.parametrize("B,k,m,h", [(10, 4, 8, 6), (77, 9, 10, 8),
                                     (256, 3, 5, 4)])
def test_kitnet_kernel(B, k, m, h):
    ks = jax.random.split(KEY, 5)
    x = jax.random.uniform(ks[0], (B, k, m))
    w1 = jax.random.normal(ks[1], (k, m, h)) * 0.3
    b1 = jax.random.normal(ks[2], (k, h)) * 0.1
    w2 = jax.random.normal(ks[3], (k, h, m)) * 0.3
    b2 = jax.random.normal(ks[4], (k, m)) * 0.1
    mask = (jax.random.uniform(KEY, (k, m)) > 0.2).astype(jnp.float32)
    r1 = ops.kitnet_ensemble(x, w1, b1, w2, b2, mask, bb=32)
    r2 = ref.kitnet_ensemble_ref(x, w1, b1, w2, b2, mask)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-6)


def test_flash_matches_model_attention_path():
    """The Pallas kernel and the model's jnp blockwise path agree."""
    from repro.models.attention import blockwise_attention, dense_attention
    from repro.configs import get_arch, reduced
    cfg = reduced(get_arch("deepseek-7b"))
    ks = jax.random.split(KEY, 3)
    B, S, H, D = 2, 64, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    d = dense_attention(q, k, v, cfg, pos, pos, causal=True, window=0)
    bw = blockwise_attention(q, k, v, cfg, pos, pos, causal=True, window=0,
                             kv_block=16)
    pl_out = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 causal=True, bq=32, bk=32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(d), np.asarray(bw), atol=2e-5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(pl_out), atol=2e-5)


@pytest.mark.parametrize("interpret", [None, True, False])
def test_interpret_rule(interpret):
    """``interpret=None`` interprets where the computation is lowered for
    the CPU, ``True`` always interprets, and ``False`` asks for the Mosaic
    compile, which the CPU refuses: no kernel silently falls back to the
    interpreter (kernels/__init__.run_pallas)."""
    rng = np.random.default_rng(7)
    n_slots, n = 64, 80
    table = {f: (jnp.zeros((n_slots, 4)) - (1.0 if f == "last_t" else 0.0))
             for f in ("last_t", "w", "ls", "ss")}
    slots = jnp.asarray(rng.integers(0, n_slots, n), jnp.int32)
    ts = jnp.asarray(np.sort(rng.uniform(0, 5, n)), jnp.float32)
    lens = jnp.asarray(rng.integers(60, 1500, n), jnp.float32)
    if interpret is False:
        with pytest.raises(ValueError, match="interpret mode"):
            ops.feature_update(table, slots, ts, lens, chunk=32,
                               interpret=False)
        return
    _, s1 = ops.feature_update(table, slots, ts, lens, chunk=32,
                               interpret=interpret)
    _, s2 = ref.feature_update_ref(table, slots, ts, lens)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-3)
