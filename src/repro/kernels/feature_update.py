"""Peregrine feature-atom update as a Pallas TPU kernel — the paper's switch
pipeline on a TPU core.

One grid step processes a block of packets with the flow tables resident in
VMEM; an in-kernel ``fori_loop`` applies, per packet:

    decay(dt) -> atom update (w, LS, SS across the 4 decay instances)
              -> statistics (mu, sigma)

exactly like the MAU pipeline (DESIGN.md §2).  The tables are copied into
their VMEM output blocks once, at grid step 0, and stay there across the
sequential grid (``input_output_aliases``), so the state never round-trips
to HBM between packet blocks.

Layout (DESIGN.md §2):

* **Tables are lane-packed.**  A ``(rows, N_DECAY)`` f32 table is viewed as
  ``(rows / 32, 128)``: 32 table rows share one 128-lane vector row.  A
  packet loads its vector row with a dynamic sublane index, rotates its 4
  lanes down to lanes 0..3 (``pltpu.roll``), updates them, and stores them
  back under a lane mask.  A ``(rows, 4)`` block would pad every row to 128
  lanes — 32x the bytes, more than a core's VMEM at 8,192 slots.
* **Per-packet scalars live in SMEM.**  Timestamps, lengths and the
  host-precomputed table rows arrive as 1-D SMEM blocks of ``chunk``
  packets (a multiple of 1,024 on TPU: XLA lays out a 1-D s32/f32 array
  in tiles of 1,024).  A dynamic read of a VMEM vector ref is not a
  scalar on TPU.
* **Statistics are emitted 128 lanes wide** (each 4-lane piece rotated to
  its offset) and sliced to the feature width outside the kernel.

Two kernels live here:

  * ``feature_update``       — the original single-key-type streaming update
    (kept as the minimal reference kernel and for the kernel unit tests);
  * ``feature_update_full``  — the complete Peregrine FC pipeline: all four
    key types, direction-paired bidirectional tables, and the
    SR/magnitude/radius/cov/PCC cross-direction statistics, emitting the
    same (n, N_FEATURES) layout as the serial oracle.  This is the
    ``backend="pallas"`` implementation behind
    ``repro.core.backends.compute_features``.

The lane-packing helpers are shared with ``kernels/sketch_update.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.state import (
    BI_STATS, LAMBDAS, N_BI, N_DECAY, N_FEATURES, N_UNI, UNI_STATS,
    packet_slots, state_slots,
)
from repro.kernels import run_pallas

LANES = 128
_G = LANES // N_DECAY            # table rows per lane-packed vector row
_ROW_ALIGN = 8 * _G              # pad rows to whole (8, 128) f32 tiles
BLOCK = 1024                     # default packets per grid step
_N_US, _N_BS = len(UNI_STATS), len(BI_STATS)


# ---------------------------------------------------------------------------
# lane-packed table helpers (shared with kernels/sketch_update.py)
# ---------------------------------------------------------------------------
def pack_rows(x):
    """``(rows, N_DECAY)`` table -> lane-packed ``(rows_p / 32, 128)``."""
    rows = x.shape[0]
    rp = -(-rows // _ROW_ALIGN) * _ROW_ALIGN
    return jnp.pad(x, ((0, rp - rows), (0, 0))).reshape(rp // _G, LANES)


def unpack_rows(y, rows: int):
    """Inverse of :func:`pack_rows`."""
    return y.reshape(-1, N_DECAY)[:rows]


def const_rows(evict_age=0.0):
    """The kernels' (8, 128) constant block: row 0 holds the decay rates
    tiled over the lanes (lane ``l`` -> LAMBDAS[l % 4]), row 1 the sketch
    eviction age broadcast over the lanes."""
    lam = jnp.tile(jnp.asarray(LAMBDAS, jnp.float32), _G)
    age = jnp.broadcast_to(jnp.asarray(evict_age, jnp.float32), (LANES,))
    return jnp.zeros((8, LANES), jnp.float32).at[0].set(lam).at[1].set(age)


def _lane():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def get_row(tab, r):
    """Row ``r`` of a packed table as ``(1, 128)``, its values in lanes 0..3
    (the other lanes hold neighbouring rows and are never stored)."""
    o = (r % _G) * N_DECAY
    return pltpu.roll(tab[pl.ds(r // _G, 1), :], (LANES - o) % LANES, 1)


def put_row(tab, r, v):
    """Store lanes 0..3 of ``v`` as row ``r`` of a packed table; the 31
    rows sharing its vector row keep their values."""
    q, o = r // _G, (r % _G) * N_DECAY
    lane = _lane()
    old = tab[pl.ds(q, 1), :]
    tab[pl.ds(q, 1), :] = jnp.where((lane >= o) & (lane < o + N_DECAY),
                                    pltpu.roll(v, o, 1), old)


def pack_lanes(pieces):
    """Lanes 0..3 of each piece, side by side: piece j -> lanes 4j..4j+3."""
    lane = _lane()
    out = pieces[0]
    for j, p in enumerate(pieces[1:], 1):
        lo = j * N_DECAY
        out = jnp.where((lane >= lo) & (lane < lo + N_DECAY),
                        pltpu.roll(p, lo, 1), out)
    return out


def decay_update(lam, lt, w, ls, ss, t, x):
    """One stream's decay + atom update (exact mode)."""
    fresh = lt < 0.0
    dt = jnp.maximum(t - lt, 0.0)
    delta = jnp.where(fresh, 0.0, jnp.exp2(-lam * dt))
    return w * delta + 1.0, ls * delta + x, ss * delta + x * x


def copy_tables_in(srcs, dsts):
    """Grid step 0: copy the HBM tables into their resident VMEM blocks."""
    @pl.when(pl.program_id(0) == 0)
    def _copy_in():
        for src, dst in zip(srcs, dsts):
            pltpu.sync_copy(src, dst)


def pad_packets(n: int, chunk: int) -> int:
    """Packets padded up to whole ``chunk`` blocks (at least one)."""
    return -(-max(n, 1) // chunk) * chunk


def table_call(kernel, packed, scalars, n_pad: int, chunk: int,
               interpret, consts=None):
    """Run ``kernel`` over ``n_pad / chunk`` sequential packet blocks.

    Operands: the :func:`const_rows` block (VMEM), the per-packet
    ``scalars`` (1-D, ``k * n_pad`` long each, as SMEM blocks of
    ``k * chunk``), then the lane-packed tables (left in HBM and copied
    in by the kernel).  Outputs: the updated tables (aliased onto their
    inputs, VMEM-resident for the whole grid) and the ``(n_pad, 128)``
    statistics.  ``vmem_limit_bytes`` covers the resident tables twice
    (the pipeline double-buffers each output block) plus the statistics
    blocks and 4 MiB of headroom; v5e compiles it at 8,192 slots.
    """
    smem = [pl.BlockSpec((a.shape[0] // n_pad * chunk,), lambda s: (s,),
                         memory_space=pltpu.SMEM) for a in scalars]
    tab_specs = [pl.BlockSpec(p.shape, lambda s: (0, 0)) for p in packed]
    tab_bytes = sum(p.size * 4 for p in packed)
    stats_bytes = chunk * LANES * 4
    vmem = 2 * tab_bytes + 2 * stats_bytes + (4 << 20)
    n_in = 1 + len(scalars)

    def make(interp):
        return pl.pallas_call(
            kernel,
            grid=(n_pad // chunk,),
            in_specs=[pl.BlockSpec((8, LANES), lambda s: (0, 0))] + smem
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(packed),
            out_specs=tab_specs + [
                pl.BlockSpec((chunk, LANES), lambda s: (s, 0))],
            out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)
                       for p in packed]
            + [jax.ShapeDtypeStruct((n_pad, LANES), jnp.float32)],
            input_output_aliases={n_in + k: k for k in range(len(packed))},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=int(vmem)),
            interpret=interp,
            name=kernel.func.__name__.lstrip("_"),
        )

    consts = const_rows() if consts is None else consts
    out = run_pallas(make, consts, *scalars, *packed,
                     interpret=interpret)
    return out[:-1], out[-1]


# ===========================================================================
# Single-key-type kernel
# ===========================================================================
def _fc_kernel(const_ref, slot_ref, ts_ref, len_ref, lt_i, w_i, ls_i, ss_i,
               lt, w, ls, ss, stats_ref, *, chunk: int, n_pkts: int):
    copy_tables_in((lt_i, w_i, ls_i, ss_i), (lt, w, ls, ss))
    step = pl.program_id(0)
    lam = const_ref[pl.ds(0, 1), :]                       # (1, 128)

    def body(i, _):
        valid = step * chunk + i < n_pkts
        r = slot_ref[i]
        t = ts_ref[i]
        x = len_ref[i]
        lt0 = get_row(lt, r)
        w2, ls2, ss2 = decay_update(lam, lt0, get_row(w, r), get_row(ls, r),
                                    get_row(ss, r), t, x)
        mu = ls2 / w2
        sig = jnp.sqrt(jnp.abs(ss2 / w2 - mu * mu))

        @pl.when(valid)
        def _store():
            put_row(lt, r, jnp.full_like(lt0, t))
            put_row(w, r, w2)
            put_row(ls, r, ls2)
            put_row(ss, r, ss2)
            stats_ref[pl.ds(i, 1), :] = pack_lanes([w2, mu, sig])

        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def feature_update(table, slots, ts, lens, *, chunk: int = BLOCK,
                   interpret=None):
    """Single-key-type streaming atom update.

    table: {"last_t","w","ls","ss"} each (n_slots, N_DECAY) f32.
    slots (n,) int32; ts/lens (n,) f32.
    Returns (new_table, stats (n, N_DECAY*3) = [w | mu | sigma] per decay).
    """
    n = slots.shape[0]
    n_slots = table["w"].shape[0]
    n_pad = pad_packets(n, chunk)
    pad = lambda a: jnp.pad(a, (0, n_pad - n))
    names = ("last_t", "w", "ls", "ss")
    kernel = functools.partial(_fc_kernel, chunk=chunk, n_pkts=n)
    tabs, stats = table_call(
        kernel, [pack_rows(table[k]) for k in names],
        [pad(slots), pad(ts), pad(lens)], n_pad, chunk, interpret)
    new_table = {k: unpack_rows(v, n_slots) for k, v in zip(names, tabs)}
    return new_table, stats[:n, :N_DECAY * 3]


# ===========================================================================
# Full-feature kernel: all four key types + bidirectional statistics
# ===========================================================================
#
# The table/row layout (uni keys stacked row-wise, bi keys interleaving
# direction as a reshape view, host-precomputed row indices, blocked stat
# emission + ``_BLOCKED_TO_ORACLE`` permutation, VMEM budget) is recorded in
# DESIGN.md §2.  Semantics are ``process_serial(..., mode="exact")``; the
# round-robin "switch" mode is inherently scalar-serial and stays on the
# oracle path.


def _blocked_to_oracle_perm():
    """Column permutation: kernel blocked layout -> oracle feature order."""
    perm = []
    for k in range(N_UNI):
        for d in range(N_DECAY):
            for s in range(_N_US):
                perm.append(k * N_DECAY * _N_US + s * N_DECAY + d)
    off = N_UNI * N_DECAY * _N_US
    for k in range(N_BI):
        for d in range(N_DECAY):
            for s in range(_N_BS):
                perm.append(off + k * N_DECAY * _N_BS + s * N_DECAY + d)
    return tuple(perm)


_BLOCKED_TO_ORACLE = _blocked_to_oracle_perm()

_FULL_TABLES = ("ult", "uw", "uls", "uss", "blt", "bw", "bls", "bss",
                "brl", "bsr", "bslt")
_N_IDX = N_UNI + N_BI            # SMEM row indices per packet


def _safe_div(a, b):
    """Exact-mode division (0 where the divisor is <= 0), delegated to the
    oracle's arithmetic so the two paths can never drift apart."""
    from repro.core import arith
    return arith.div(a, b, "exact")


def stats_of(w, ls, ss):
    mu = _safe_div(ls, w)
    var = jnp.abs(_safe_div(ss, w) - mu * mu)
    return mu, var, jnp.sqrt(var)


def _fc_full_kernel(const_ref, idx_ref, ts_ref, len_ref, *refs,
                    chunk: int, n_pkts: int):
    n_tab = len(_FULL_TABLES)
    copy_tables_in(refs[:n_tab], refs[n_tab:2 * n_tab])
    ult, uw, uls, uss, blt, bw, bls, bss, brl, bsr, bslt = \
        refs[n_tab:2 * n_tab]
    stats_ref = refs[2 * n_tab]
    step = pl.program_id(0)
    lam = const_ref[pl.ds(0, 1), :]                       # (1, 128)

    def body(i, _):
        valid = step * chunk + i < n_pkts
        t = ts_ref[i]
        x = len_ref[i]
        pieces = []

        # ---- unidirectional key types ----
        for ki in range(N_UNI):
            row = idx_ref[i * _N_IDX + ki]
            lt = get_row(ult, row)
            w2, ls2, ss2 = decay_update(lam, lt, get_row(uw, row),
                                        get_row(uls, row), get_row(uss, row),
                                        t, x)
            mu, var, sig = stats_of(w2, ls2, ss2)
            pieces += [w2, mu, sig]

            @pl.when(valid)
            def _store_uni():
                put_row(ult, row, jnp.full_like(lt, t))
                put_row(uw, row, w2)
                put_row(uls, row, ls2)
                put_row(uss, row, ss2)

        # ---- bidirectional key types ----
        for ki in range(N_BI):
            orow = idx_ref[i * _N_IDX + N_UNI + ki]     # own-direction row
            prow = orow ^ 1                             # opposite direction
            srow = orow >> 1                            # SR (channel) row

            lt_o = get_row(blt, orow)
            w_o, ls_o, ss_o = decay_update(lam, lt_o, get_row(bw, orow),
                                           get_row(bls, orow),
                                           get_row(bss, orow), t, x)
            mu_o, var_o, sig_o = stats_of(w_o, ls_o, ss_o)

            # stale opposite-direction stats (stored values, as on switch)
            w_p = get_row(bw, prow)
            mu_p, var_p, sig_p = stats_of(w_p, get_row(bls, prow),
                                          get_row(bss, prow))

            # SR: decayed sum of cross-direction residual products
            sr = get_row(bsr, srow)
            sr_lt = get_row(bslt, srow)
            dsr = jnp.where(sr_lt < 0.0, 0.0,
                            jnp.exp2(-lam * jnp.maximum(t - sr_lt, 0.0)))
            r = x - mu_o
            r_opp = get_row(brl, prow)
            sr2 = sr * dsr + r * r_opp

            mag = jnp.sqrt(mu_o * mu_o + mu_p * mu_p)
            rad = jnp.sqrt(var_o * var_o + var_p * var_p)
            cov = _safe_div(sr2, w_o + w_p)
            pcc = _safe_div(cov, sig_o * sig_p)
            pieces += [w_o, mu_o, sig_o, mag, rad, cov, pcc]

            @pl.when(valid)
            def _store_bi():
                put_row(blt, orow, jnp.full_like(lt_o, t))
                put_row(bw, orow, w_o)
                put_row(bls, orow, ls_o)
                put_row(bss, orow, ss_o)
                put_row(brl, orow, r)
                put_row(bsr, srow, sr2)
                put_row(bslt, srow, jnp.full_like(sr_lt, t))

        row_stats = pack_lanes(pieces)                  # (1, 128)

        @pl.when(valid)
        def _store_stats():
            stats_ref[pl.ds(i, 1), :] = row_stats

        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "n"))
def _fc_full_call(tables, idx, ts, lens, *, chunk: int, interpret, n: int):
    kernel = functools.partial(_fc_full_kernel, chunk=chunk, n_pkts=n)
    tabs, stats = table_call(
        kernel, [pack_rows(tables[k]) for k in _FULL_TABLES],
        [idx, ts, lens], ts.shape[0], chunk, interpret)
    new = {k: unpack_rows(v, tables[k].shape[0])
           for k, v in zip(_FULL_TABLES, tabs)}
    return new, stats[:n, :N_FEATURES]


def feature_update_full(state, pkts, *, chunk: int = BLOCK, interpret=None):
    """Full Peregrine FC (all 80 features) as one Pallas pipeline.

    state: the ``init_state`` dict (rr counters pass through untouched —
    round-robin decay belongs to switch mode, which stays on the serial
    oracle).  pkts: raw packet arrays ``{ts, src, dst, sport, dport, proto,
    length}``.  Returns ``(new_state, feats (n, N_FEATURES))`` matching
    ``process_serial(..., mode="exact")`` to float tolerance.
    """
    n_slots = state_slots(state)
    sl = packet_slots(pkts, n_slots)
    ts = pkts["ts"].astype(jnp.float32)
    lens = pkts["length"].astype(jnp.float32)
    n = ts.shape[0]

    # host-side row precomputation (see layout note above): per packet the
    # uni rows, then the own-direction bi rows; the kernel derives the
    # opposite-direction row (``^ 1``) and the channel row (``>> 1``)
    key_off = jnp.arange(N_UNI, dtype=jnp.int32) * n_slots
    urow = jnp.stack([sl["src_mac_ip"], sl["src_ip"]], -1) + key_off[None]
    bbase = jnp.stack([sl["channel"], sl["socket"]], -1) + key_off[None]
    brow_o = bbase * 2 + sl["dir"][:, None]
    n_pad = pad_packets(n, chunk)
    idx = jnp.pad(jnp.concatenate([urow, brow_o], -1).astype(jnp.int32),
                  ((0, n_pad - n), (0, 0))).reshape(-1)
    pad1 = lambda a: jnp.pad(a, (0, n_pad - n))
    uni, bi = state["uni"], state["bi"]
    tables = {
        "ult": uni["last_t"], "uw": uni["w"], "uls": uni["ls"],
        "uss": uni["ss"], "blt": bi["last_t"], "bw": bi["w"],
        "bls": bi["ls"], "bss": bi["ss"], "brl": bi["res_last"],
        "bsr": bi["sr"], "bslt": bi["sr_last_t"],
    }
    tables = {k: v.reshape(-1, N_DECAY) for k, v in tables.items()}
    new_tab, stats = _fc_full_call(tables, idx, pad1(ts), pad1(lens),
                                   chunk=chunk, interpret=interpret, n=n)

    feats = jnp.take(stats, jnp.asarray(_BLOCKED_TO_ORACLE), axis=1)
    sh_u = (N_UNI, n_slots, N_DECAY)
    sh_b = (N_BI, n_slots, 2, N_DECAY)
    new_state = {
        "uni": {"last_t": new_tab["ult"].reshape(sh_u),
                "w": new_tab["uw"].reshape(sh_u),
                "ls": new_tab["uls"].reshape(sh_u),
                "ss": new_tab["uss"].reshape(sh_u),
                "rr": uni["rr"]},
        "bi": {"last_t": new_tab["blt"].reshape(sh_b),
               "w": new_tab["bw"].reshape(sh_b),
               "ls": new_tab["bls"].reshape(sh_b),
               "ss": new_tab["bss"].reshape(sh_b),
               "res_last": new_tab["brl"].reshape(sh_b),
               "sr": new_tab["bsr"].reshape(N_BI, n_slots, N_DECAY),
               "sr_last_t": new_tab["bslt"].reshape(N_BI, n_slots, N_DECAY),
               "rr": bi["rr"]},
    }
    return new_state, feats
