"""Count-Min sketch flow state — bounded memory under unbounded cardinality.

The dense backend direct-indexes ``hash(key) % n_slots``: past the slot
budget, flows silently merge.  This backend stores every decay atom in R
independently-hashed rows of width W (Count-Min), reads the per-atom
minimum across rows, and writes with **conservative update** (only raise a
cell to the new estimate, never beyond — Estan & Varghese), so the
estimate stays a one-sided overestimate of the true decayed statistic and
collisions perturb a flow only while *all R* of its rows are contended.
This is the switch-register compromise the 100G software detectors make
(Whisper/OctoSketch lineage, PAPERS.md) translated to our decay atoms.

Layout (`init_sketch_state`): the dense tables with the slot axis replaced
by (rows, width) — uni atoms ``(N_UNI, R, W, N_DECAY)``, bi atoms
``(N_BI, R, W, 2, N_DECAY)``, channel SR state ``(N_BI, R, W, N_DECAY)``
plus a ``sw`` per-row channel packet count used to pick the least-collided
row for the *signed* SR statistic (min is a biased estimator for signed
values, so SR reads the row with the smallest conservative packet count —
at R=1 that is the only row and the choice is vacuous).  ``evict_age`` is
a traced f32 scalar leaf: cells idle longer than this many seconds are
treated as empty on access (aging/eviction — long-running streams stop
aliasing dead flows); 0 disables aging.

Row r of key type k hashes with salt ``KEY_SALTS[k] ^ (r * 0x85EBCA6B)``:
row 0 uses the dense salt, so a sketch with ``rows=1, n_slots=W`` maps
flows to exactly the dense slots and the STATE UPDATE degenerates to the
dense serial oracle bit-for-bit (the candidate formulation in
``_cu_update`` exists to preserve XLA's fma contraction of the oracle's
``v·δ + inc``).  The emitted sigma/mag/rad statistics — pure outputs
that never feed back into state — agree to float rounding only: XLA
contracts the variance expression differently in the two scan bodies,
and that choice is not controllable from the source.  Both halves are
pinned in tests/test_state_backends.py — the collision-free sizing of
the acceptance criteria.

Two implementations of the same update:

  * :func:`process_sketch` — pure-JAX reference, a per-packet ``lax.scan``
    mirroring ``core/pipeline._packet_step`` with R-row gathers/scatters.
    Conservative update is order-dependent THROUGH the cross-row min, so
    the sketch cannot ride the segmented-scan machinery (the associative
    reformulation dense ``scan``/``bucketed`` use does not exist here);
    like the serial oracle it is packet-serial.
  * ``kernels/sketch_update.sketch_update_full`` — the Pallas row-update
    kernel (hash rows precomputed host-side → in-kernel row gather →
    min/conservative-add combine), selected via ``fc_backend="pallas"``.

Dispatch: ``compute_features(state, pkts, backend=...)`` identifies a
sketch state structurally (``state_backend_of``) and routes here; the
``backend=`` name then only picks the implementation (``pallas`` → the
kernel, anything else → the reference scan).  Exact arithmetic only: the
switch round-robin mode is tied to the dense rr counters.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import arith
from repro.core.state import (
    KEY_SALTS, LAMBDAS, N_BI, N_DECAY, N_UNI, StateBackend, hash_fields,
    key_fields, register_state_backend,
)

_LAM = jnp.asarray(LAMBDAS, jnp.float32)

# row-salt derivation constant (murmur3 fmix): row 0 keeps the dense salt
_ROW_SALT_MIX = 0x85EBCA6B


def row_salt(base: int, r: int) -> int:
    """Salt of sketch row ``r`` for a key type with dense salt ``base``."""
    return (base ^ ((r * _ROW_SALT_MIX) & 0xFFFFFFFF)) & 0xFFFFFFFF


def init_sketch_state(n_slots: int, rows: int = 4,
                      evict_age: float = 0.0) -> Dict:
    """Fresh Count-Min flow tables: ``rows`` hashed rows of width
    ``n_slots`` per key type; ``evict_age`` seconds of idleness after
    which a cell reads as empty (0 = no aging)."""
    if rows < 1:
        raise ValueError(f"sketch needs at least one row, got {rows}")
    R, W = int(rows), int(n_slots)
    z = jnp.zeros
    return {
        "uni": {
            "last_t": z((N_UNI, R, W, N_DECAY)) - 1.0,
            "w": z((N_UNI, R, W, N_DECAY)),
            "ls": z((N_UNI, R, W, N_DECAY)),
            "ss": z((N_UNI, R, W, N_DECAY)),
        },
        "bi": {
            "last_t": z((N_BI, R, W, 2, N_DECAY)) - 1.0,
            "w": z((N_BI, R, W, 2, N_DECAY)),
            "ls": z((N_BI, R, W, 2, N_DECAY)),
            "ss": z((N_BI, R, W, 2, N_DECAY)),
            "res_last": z((N_BI, R, W, 2, N_DECAY)),
            "sr": z((N_BI, R, W, N_DECAY)),
            "sr_last_t": z((N_BI, R, W, N_DECAY)) - 1.0,
            "sw": z((N_BI, R, W, N_DECAY)),
        },
        "evict_age": jnp.float32(evict_age),
    }


def sketch_rows(state: Dict) -> int:
    return state["uni"]["w"].shape[1]


def sketch_width(state: Dict) -> int:
    return state["uni"]["w"].shape[2]


def sketch_packet_rows(pkts: Dict[str, jax.Array], rows: int,
                       width: int) -> Dict[str, jax.Array]:
    """Per-packet sketch column indices, (n, rows) per key type, plus the
    channel ``dir`` bit — the multi-row analogue of ``packet_slots``
    (identical canonicalisation via ``key_fields``; row 0 == the dense
    slot mapping of a width-``width`` dense table)."""
    fields, dirb = key_fields(pkts)
    w = jnp.uint32(width)
    out = {"dir": dirb}
    for k, f in fields.items():
        cols = [(hash_fields(f, row_salt(KEY_SALTS[k], r)) % w)
                .astype(jnp.int32) for r in range(rows)]
        out[k] = jnp.stack(cols, axis=-1)
    return out


# ---------------------------------------------------------------------------
# Pure-JAX reference update (per-packet lax.scan)
# ---------------------------------------------------------------------------
def _cu_update(lt, w, ls, ss, t, x, age):
    """Conservative-update decay + atom update across rows.

    lt/w/ls/ss: (K, R, N_DECAY) gathered cells; t/x scalars; age the
    eviction threshold (0 disables).  Returns the updated cells plus the
    per-atom Count-Min estimates (K, N_DECAY) — the post-update min across
    rows.  At R=1 the min is over one row, every max resolves to the
    candidate ``v·δ + inc``, and the stored state is bit-for-bit the
    oracle's ``_stream_update`` exact path.
    """
    dt = jnp.maximum(t - lt, 0.0)
    dead = (lt < 0.0) | ((age > 0.0) & (dt > age))
    delta = jnp.where(dead, 0.0, jnp.exp2(-_LAM * dt))
    # Per-row candidates v·δ + inc keep the oracle's mul+add expression
    # shape: XLA contracts it to an fma inside the scan, and a second use
    # of the raw product would block that contraction (verified on CPU),
    # so the conservative-update max compares ``cand - inc`` instead —
    # bitwise ``est`` whenever the estimate wins (always at R=1, where
    # min-of-candidates is the candidate and the whole update is
    # bit-for-bit the dense serial oracle), and within ~2 ulp of the
    # decayed value on collided rows where the row's own value wins.
    # the unit increment rides through an optimization barrier: as a
    # literal, XLA folds ``(w·δ + 1) - 1`` back to the raw product, whose
    # second use then blocks the fma (the traced x/x² increments of
    # ls/ss don't need the shield)
    one = jax.lax.optimization_barrier(jnp.float32(1.0))
    cw = w * delta + one
    cls = ls * delta + x
    css = ss * delta + x ** 2
    ew = jnp.min(cw, axis=1, keepdims=True)
    els = jnp.min(cls, axis=1, keepdims=True)
    ess = jnp.min(css, axis=1, keepdims=True)
    w2 = jnp.maximum(cw - one, ew)
    ls2 = jnp.maximum(cls - x, els)
    ss2 = jnp.maximum(css - x ** 2, ess)
    lt2 = jnp.broadcast_to(t, lt.shape)
    est = (ew[:, 0], els[:, 0], ess[:, 0])
    return lt2, w2, ls2, ss2, est


def _stats(w, ls, ss):
    mu = arith.div(ls, w, "exact")
    var = jnp.abs(arith.div(ss, w, "exact") - arith.square(mu, "exact"))
    return mu, var, arith.sqrt(var, "exact")


def _sketch_packet_step(tables: Dict, pkt: Dict, age) -> Tuple[Dict, jax.Array]:
    """One packet through the sketch — mirrors ``pipeline._packet_step``
    (exact mode) with R-row conservative-update access."""
    t, x = pkt["ts"], pkt["length"]
    R = tables["uni"]["w"].shape[1]
    ri = jnp.arange(R)[None, :]
    feats = []

    # ---- unidirectional key types ----
    uni = tables["uni"]
    ki = jnp.arange(N_UNI)[:, None]
    cols = jnp.stack([pkt["src_mac_ip"], pkt["src_ip"]])       # (2, R)
    g = lambda a: a[ki, ri, cols]                              # (2, R, ND)
    lt2, w2, ls2, ss2, (ew, els, ess) = _cu_update(
        g(uni["last_t"]), g(uni["w"]), g(uni["ls"]), g(uni["ss"]), t, x, age)
    mu, var, sigma = _stats(ew, els, ess)
    feats.append(jnp.stack([ew, mu, sigma], axis=-1).reshape(-1))
    s = lambda name, v: uni[name].at[ki, ri, cols].set(v)
    tables = {**tables, "uni": {"last_t": s("last_t", lt2), "w": s("w", w2),
                                "ls": s("ls", ls2), "ss": s("ss", ss2)}}

    # ---- bidirectional key types ----
    bi = tables["bi"]
    kb = jnp.arange(N_BI)[:, None]
    bcols = jnp.stack([pkt["channel"], pkt["socket"]])         # (2, R)
    d = pkt["dir"]
    o = 1 - d
    own = lambda a: a[kb, ri, bcols, d]                        # (2, R, ND)
    lt_o, w_o, ls_o, ss_o, (ew_o, els_o, ess_o) = _cu_update(
        own(bi["last_t"]), own(bi["w"]), own(bi["ls"]), own(bi["ss"]),
        t, x, age)
    mu_o, var_o, sig_o = _stats(ew_o, els_o, ess_o)

    # opposite-direction stats: stored values (stale, as on the switch),
    # aged-out cells read as empty, then the Count-Min min across rows
    opp = lambda a: a[kb, ri, bcols, o]
    lt_p = opp(bi["last_t"])
    zap = (age > 0.0) & ((t - lt_p) > age)
    rd = lambda a: jnp.min(jnp.where(zap, 0.0, opp(a)), axis=1)  # (2, ND)
    w_p, ls_p, ss_p = rd(bi["w"]), rd(bi["ls"]), rd(bi["ss"])
    mu_p, var_p, sig_p = _stats(w_p, ls_p, ss_p)

    # SR (decayed sum of cross-direction residual products): every row
    # keeps its own sr/res_last stream; the emitted value comes from the
    # row with the smallest conservative channel count sw (least collided)
    ch = lambda name: bi[name][kb, ri, bcols]                  # (2, R, ND)
    sr, sr_lt, sw = ch("sr"), ch("sr_last_t"), ch("sw")
    res_last_o = opp(bi["res_last"])                           # (2, R, ND)
    r_feat = x - mu_o                                          # (2, ND)
    dt_sr = jnp.maximum(t - sr_lt, 0.0)
    evict_sr = (age > 0.0) & (dt_sr > age)
    dsr = jnp.where((sr_lt < 0.0) | evict_sr, 0.0, jnp.exp2(-_LAM * dt_sr))
    r_opp = jnp.where(evict_sr, 0.0, res_last_o)
    sr2 = sr * dsr + r_feat[:, None, :] * r_opp                # (2, R, ND)
    sw_now = sw * dsr
    m_sw = jnp.min(sw_now, axis=1, keepdims=True)
    sw2 = jnp.maximum(sw_now, m_sw + 1.0)
    best = jnp.argmin(sw2, axis=1)                             # (2, ND)
    sr_est = jnp.take_along_axis(sr2, best[:, None, :], axis=1)[:, 0]

    mag = arith.sqrt(arith.square(mu_o, "exact")
                     + arith.square(mu_p, "exact"), "exact")
    rad = arith.sqrt(arith.square(var_o, "exact")
                     + arith.square(var_p, "exact"), "exact")
    cov = arith.div(sr_est, ew_o + w_p, "exact")
    pcc = arith.div(cov, sig_o * sig_p, "exact")
    feats.append(jnp.stack([ew_o, mu_o, sig_o, mag, rad, cov, pcc],
                           axis=-1).reshape(-1))

    sb = lambda name, v: bi[name].at[kb, ri, bcols, d].set(v)
    tables = {**tables, "bi": {
        "last_t": sb("last_t", lt_o), "w": sb("w", w_o),
        "ls": sb("ls", ls_o), "ss": sb("ss", ss_o),
        "res_last": sb("res_last",
                       jnp.broadcast_to(r_feat[:, None, :], sr2.shape)),
        "sr": bi["sr"].at[kb, ri, bcols].set(sr2),
        "sr_last_t": bi["sr_last_t"].at[kb, ri, bcols].set(
            jnp.broadcast_to(t, sr2.shape)),
        "sw": bi["sw"].at[kb, ri, bcols].set(sw2),
    }}
    return tables, jnp.concatenate(feats)


@jax.jit
def process_sketch(state: Dict, pkts: Dict[str, jax.Array]
                   ) -> Tuple[Dict, jax.Array]:
    """Pure-JAX reference sketch update: per-packet ``lax.scan`` (the
    conservative update's cross-row min breaks the associativity the
    segmented-scan backends exploit, so packet-serial is inherent).
    Returns ``(new_state, feats (n, N_FEATURES))``.
    """
    rows = sketch_packet_rows(pkts, sketch_rows(state), sketch_width(state))
    xs = {"ts": pkts["ts"].astype(jnp.float32),
          "length": pkts["length"].astype(jnp.float32), **rows}
    age = state["evict_age"]
    tables = {k: state[k] for k in ("uni", "bi")}

    def step(tb, x):
        return _sketch_packet_step(tb, x, age)

    tables, feats = jax.lax.scan(step, tables, xs)
    return {**tables, "evict_age": age}, feats


# ---------------------------------------------------------------------------
# compute dispatch + backend registration
# ---------------------------------------------------------------------------
def compute_features_sketch(state: Dict, pkts: Dict[str, jax.Array],
                            mode: str = "exact", fc_backend: str = "scan",
                            chunk=None, interpret=None,
                            **_kw) -> Tuple[Dict, jax.Array]:
    """Route a sketch-state batch to an implementation: ``pallas`` → the
    row-update kernel, anything else → the pure-JAX reference.  Partition
    kwargs of the dense backends (``buckets``/``shards``) are accepted and
    ignored — partitioning belongs to the dense slot layout."""
    if mode != "exact":
        raise ValueError("the sketch state backend supports exact "
                         f"arithmetic only, got mode={mode!r} (switch-mode "
                         "round-robin decay is tied to the dense rr "
                         "counters)")
    if fc_backend == "pallas":
        from repro.kernels.ops import BLOCK, sketch_update_full
        return sketch_update_full(state, pkts, chunk=chunk or BLOCK,
                                  interpret=interpret)
    return process_sketch(state, pkts)


register_state_backend(StateBackend(
    name="sketch",
    init=init_sketch_state,
    slots=sketch_width,
    matches=lambda s: isinstance(s, dict) and "evict_age" in s,
    config=lambda s: {"rows": sketch_rows(s),
                      "evict_age": float(jax.device_get(s["evict_age"]))},
    compute=compute_features_sketch,
))
