"""KitNET autoencoder-ensemble forward (+RMSE) as a fused Pallas kernel.

The MD stage (§3.4): k small autoencoders reconstruct their feature subset;
their RMSEs feed the output AE.  This kernel fuses the whole ensemble layer:
grid (k, batch_blocks); each step runs one AE on one batch tile —
two MXU matmuls + sigmoids + masked RMSE reduction, never materialising the
reconstruction in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import run_pallas


def _ae_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, mask_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)                     # (bB, m)
    mask = mask_ref[0].astype(jnp.float32)               # (1, m)
    xm = x * mask
    h = jax.nn.sigmoid(
        jax.lax.dot_general(xm, w1_ref[0].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + b1_ref[0].astype(jnp.float32))
    y = jax.nn.sigmoid(
        jax.lax.dot_general(h, w2_ref[0].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + b2_ref[0].astype(jnp.float32))
    se = ((y - xm) ** 2) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    out_ref[0] = jnp.sqrt(se.sum(axis=-1, keepdims=True) / denom)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def kitnet_ensemble(x_sub, w1, b1, w2, b2, mask, *, bb: int = 128,
                    interpret=None):
    """x_sub: (B, k, m) gathered+normalised feature subsets.
    w1 (k,m,h), b1 (k,h), w2 (k,h,m), b2 (k,m), mask (k,m).
    Returns per-AE RMSE (B, k).
    """
    B, k, m = x_sub.shape
    h = w1.shape[-1]
    bb = min(bb, max(B, 8))
    nb = -(-B // bb)
    Bp = nb * bb
    if Bp != B:
        x_sub = jnp.pad(x_sub, ((0, Bp - B), (0, 0), (0, 0)))
    xk = x_sub.transpose(1, 0, 2)                        # (k, Bp, m)

    make = lambda interp: pl.pallas_call(
        _ae_kernel,
        grid=(k, nb),
        in_specs=[
            pl.BlockSpec((1, bb, m), lambda e, b: (e, b, 0)),
            pl.BlockSpec((1, m, h), lambda e, b: (e, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda e, b: (e, 0, 0)),
            pl.BlockSpec((1, h, m), lambda e, b: (e, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda e, b: (e, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda e, b: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, 1), lambda e, b: (e, b, 0)),
        out_shape=jax.ShapeDtypeStruct((k, Bp, 1), jnp.float32),
        interpret=interp,
        name="kitnet_ensemble",
    )
    out = run_pallas(make, xk, w1, b1[:, None, :], w2, b2[:, None, :],
                     mask[:, None, :], interpret=interpret)
    return out[:, :B, 0].T                               # (B, k)
