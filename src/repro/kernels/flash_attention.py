"""Flash attention (forward) as a Pallas TPU kernel.

Blockwise-softmax attention with causal masking, sliding window, logit
soft-capping and GQA — the compute hot-spot of every assigned LM arch.

Grid: (batch*q_heads, q_blocks, kv_blocks); the kv axis is innermost and
sequential, carrying the running max / denominator / accumulator in VMEM
scratch across kv steps (the standard TPU flash schedule).  Block shapes are
MXU-aligned (multiples of 128 on the matmul dims when head_dim allows).

Validated in interpret mode against ``ref.flash_attention_ref`` over shape /
dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import run_pallas

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 bq: int, bk: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < seq_kv                                 # kv padding
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bk",
                              "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bk: int = 128,
                    interpret=None):
    """q: (B, H, Sq, D); k/v: (B, K, Sk, D) with H a multiple of K.

    Returns (B, H, Sq, D) in q.dtype.
    """
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    bq = min(bq, max(Sq, 8))
    bk = min(bk, max(Sk, 8))
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    Sq_p, Sk_p = nq * bq, nk * bk
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    qf = q.reshape(B * H, Sq_p, D)
    kf = k.reshape(B * K, Sk_p, D)
    vf = v.reshape(B * K, Sk_p, D)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, seq_kv=Sk)
    make = lambda interp: pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interp,
    )
    out = run_pallas(make, qf, kf, vf, interpret=interpret)
    return out.reshape(B, H, Sq_p, D)[:, :, :Sq]
