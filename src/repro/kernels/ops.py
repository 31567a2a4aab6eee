"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default everywhere) interprets a kernel when the
computation is lowered for the CPU and compiles it on any other platform;
``True``/``False`` force one or the other (``repro.kernels.run_pallas``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.feature_update import (
    BLOCK,
    feature_update as _feat,
    feature_update_full as _feat_full,
)
from repro.kernels.kitnet_ae import kitnet_ensemble as _kitnet
from repro.kernels.sketch_update import sketch_update_full as _sketch_full


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    bq=128, bk=128, interpret=None):
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  bq=bq, bk=bk, interpret=interpret)


def feature_update(table, slots, ts, lens, *, chunk=BLOCK, interpret=None):
    return _feat(table, slots.astype(jnp.int32), ts.astype(jnp.float32),
                 lens.astype(jnp.float32), chunk=chunk, interpret=interpret)


def feature_update_full(state, pkts, *, chunk=BLOCK, interpret=None):
    """Full 80-feature Peregrine FC (all key types + bi stats) in Pallas."""
    return _feat_full(state, pkts, chunk=chunk, interpret=interpret)


def sketch_update_full(state, pkts, *, chunk=BLOCK, interpret=None):
    """Count-Min sketch FC (all 80 features, CU + eviction) in Pallas."""
    return _sketch_full(state, pkts, chunk=chunk, interpret=interpret)


def kitnet_ensemble(x_sub, w1, b1, w2, b2, mask, *, bb=128, interpret=None):
    return _kitnet(x_sub, w1, b1, w2, b2, mask, bb=bb, interpret=interpret)
