"""Count-Min sketch flow-state update as a Pallas kernel.

The sketch analogue of ``feature_update._fc_full_kernel``: grid steps walk
blocks of packets with ALL sketch tables resident in VMEM; an in-kernel
``fori_loop`` applies, per packet and per key type:

    hash rows (host-precomputed indices) -> gather the R hashed cells
    -> decay to now -> per-atom min across rows (the Count-Min read)
    -> conservative update (raise each cell to min+increment, never past
       its own decayed value) -> statistics -> scatter the R cells back

Table layout mirrors the dense full kernel's flattening: the sketch's
(key, row, width[, dir]) axes collapse into one row axis — uni atoms
``(N_UNI·R·W, ND)``, direction-paired bi atoms ``(N_BI·R·W·2, ND)``,
channel SR state ``(N_BI·R·W, ND)`` — and each table is lane-packed to
``(rows / 32, 128)`` exactly like the dense kernel's
(``feature_update.get_row`` / ``put_row``).  Row indices are precomputed
host-side (vectorised hashing) and arrive in SMEM, so the kernel never
hashes; ``evict_age`` rides along as a lane-broadcast row of the
constant block (``feature_update.const_rows``).

The R-row loop is STATICALLY unrolled (R is a shape constant, typically
2-8), so on TPU each packet costs R dynamic-slice gathers + a vector
min/max chain per key type — no data-dependent control flow.

Semantics are ``core/sketch.process_sketch`` (the pure-JAX reference);
parity is pinned in tests/test_state_backends.py.  Exact arithmetic only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.state import N_BI, N_DECAY, N_FEATURES, N_UNI
from repro.kernels.feature_update import (
    BLOCK, _BLOCKED_TO_ORACLE, _safe_div, const_rows, copy_tables_in,
    decay_update, get_row, pack_lanes, pack_rows, pad_packets, put_row,
    stats_of, table_call, unpack_rows,
)

_TABLES = ("ult", "uw", "uls", "uss", "blt", "bw", "bls", "bss",
           "brl", "bsr", "bslt", "bsw")


def _sketch_kernel(const_ref, idx_ref, ts_ref, len_ref, *refs,
                   chunk: int, n_pkts: int, rows: int):
    n_tab = len(_TABLES)
    copy_tables_in(refs[:n_tab], refs[n_tab:2 * n_tab])
    ult, uw, uls, uss, blt, bw, bls, bss, brl, bsr, bslt, bsw = \
        refs[n_tab:2 * n_tab]
    stats_ref = refs[2 * n_tab]
    step = pl.program_id(0)
    lam = const_ref[pl.ds(0, 1), :]                     # (1, 128)
    age = const_ref[pl.ds(1, 1), :]                     # (1, 128)
    n_idx = (N_UNI + N_BI) * rows

    def _minimum(vals):
        m = vals[0]
        for v in vals[1:]:
            m = jnp.minimum(m, v)
        return m

    def _cu(lt_tab, w_tab, ls_tab, ss_tab, rws, t, x):
        """Gather R cells, decay, Count-Min estimate + conservative
        update.  Returns (per-row updated atoms, per-atom estimates)."""
        cand = {"w": [], "ls": [], "ss": []}
        for row in rws:
            lt = get_row(lt_tab, row)
            dt = jnp.maximum(t - lt, 0.0)
            dead = (lt < 0.0) | ((age > 0.0) & (dt > age))
            # decay_update with the dead mask standing in for "fresh";
            # candidate-only formulation (see core/sketch._cu_update): a
            # second use of the raw product v·δ would block the fma
            # contraction the serial oracle's expression gets
            w2, ls2, ss2 = decay_update(
                lam, jnp.where(dead, -1.0, lt), get_row(w_tab, row),
                get_row(ls_tab, row), get_row(ss_tab, row), t, x)
            cand["w"].append(w2)
            cand["ls"].append(ls2)
            cand["ss"].append(ss2)
        ew, els, ess = (_minimum(cand[k]) for k in ("w", "ls", "ss"))
        upd = [(jnp.maximum(cand["w"][r] - 1.0, ew),
                jnp.maximum(cand["ls"][r] - x, els),
                jnp.maximum(cand["ss"][r] - x * x, ess))
               for r in range(rows)]
        return upd, (ew, els, ess)

    def body(i, _):
        valid = step * chunk + i < n_pkts
        t = ts_ref[i]
        x = len_ref[i]
        base = i * n_idx
        pieces = []

        # ---- unidirectional key types ----
        for ki in range(N_UNI):
            rws = [idx_ref[base + ki * rows + r] for r in range(rows)]
            upd, (ew, els, ess) = _cu(ult, uw, uls, uss, rws, t, x)
            mu, var, sig = stats_of(ew, els, ess)
            pieces += [ew, mu, sig]

            @pl.when(valid)
            def _store_uni():
                for row, (w2, ls2, ss2) in zip(rws, upd):
                    put_row(ult, row, jnp.full_like(w2, t))
                    put_row(uw, row, w2)
                    put_row(uls, row, ls2)
                    put_row(uss, row, ss2)

        # ---- bidirectional key types ----
        for ki in range(N_BI):
            off = base + (N_UNI + ki) * rows
            orws = [idx_ref[off + r] for r in range(rows)]
            prws = [o ^ 1 for o in orws]                # opposite direction
            srws = [o >> 1 for o in orws]               # channel (SR) row

            upd, (ew_o, els_o, ess_o) = _cu(blt, bw, bls, bss, orws, t, x)
            mu_o, var_o, sig_o = stats_of(ew_o, els_o, ess_o)

            # stale opposite-direction stats: stored values, aged cells
            # read as empty, Count-Min min across rows
            wp, lsp, ssp = [], [], []
            for prow in prws:
                lt_p = get_row(blt, prow)
                zap = (age > 0.0) & ((t - lt_p) > age)
                z = lambda tab: jnp.where(zap, 0.0, get_row(tab, prow))
                wp.append(z(bw))
                lsp.append(z(bls))
                ssp.append(z(bss))
            w_p, ls_p, ss_p = _minimum(wp), _minimum(lsp), _minimum(ssp)
            mu_p, var_p, sig_p = stats_of(w_p, ls_p, ss_p)

            # SR per row; emit the row with the least conservative
            # channel count (running strict-< select == first argmin)
            r_res = x - mu_o
            sr2s, sw2s = [], []
            for prow, srow in zip(prws, srws):
                sr = get_row(bsr, srow)
                sr_lt = get_row(bslt, srow)
                dt_sr = jnp.maximum(t - sr_lt, 0.0)
                evict = (age > 0.0) & (dt_sr > age)
                dsr = jnp.where((sr_lt < 0.0) | evict, 0.0,
                                jnp.exp2(-lam * dt_sr))
                r_opp = jnp.where(evict, 0.0, get_row(brl, prow))
                sr2s.append(sr * dsr + r_res * r_opp)
                sw2s.append(get_row(bsw, srow) * dsr)
            m_sw = _minimum(sw2s)
            sw2s = [jnp.maximum(v, m_sw + 1.0) for v in sw2s]
            sr_sel, sw_min = sr2s[0], sw2s[0]
            for r in range(1, rows):
                take = sw2s[r] < sw_min
                sw_min = jnp.where(take, sw2s[r], sw_min)
                sr_sel = jnp.where(take, sr2s[r], sr_sel)

            mag = jnp.sqrt(mu_o * mu_o + mu_p * mu_p)
            rad = jnp.sqrt(var_o * var_o + var_p * var_p)
            cov = _safe_div(sr_sel, ew_o + w_p)
            pcc = _safe_div(cov, sig_o * sig_p)
            pieces += [ew_o, mu_o, sig_o, mag, rad, cov, pcc]

            @pl.when(valid)
            def _store_bi():
                for r in range(rows):
                    orow, srow = orws[r], srws[r]
                    w2, ls2, ss2 = upd[r]
                    put_row(blt, orow, jnp.full_like(w2, t))
                    put_row(bw, orow, w2)
                    put_row(bls, orow, ls2)
                    put_row(bss, orow, ss2)
                    put_row(brl, orow, r_res)
                    put_row(bsr, srow, sr2s[r])
                    put_row(bslt, srow, jnp.full_like(w2, t))
                    put_row(bsw, srow, sw2s[r])

        row_stats = pack_lanes(pieces)                  # (1, 128)

        @pl.when(valid)
        def _store_stats():
            stats_ref[pl.ds(i, 1), :] = row_stats

        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "n", "rows"))
def _sketch_call(tables, age, idx, ts, lens, *, chunk: int, interpret,
                 n: int, rows: int):
    kernel = functools.partial(_sketch_kernel, chunk=chunk, n_pkts=n,
                               rows=rows)
    tabs, stats = table_call(
        kernel, [pack_rows(tables[k]) for k in _TABLES], [idx, ts, lens],
        ts.shape[0], chunk, interpret, consts=const_rows(age))
    new = {k: unpack_rows(v, tables[k].shape[0])
           for k, v in zip(_TABLES, tabs)}
    return new, stats[:n, :N_FEATURES]


def sketch_update_full(state, pkts, *, chunk: int = BLOCK, interpret=None):
    """Full sketch-state FC (all 80 features) as one Pallas pipeline.

    state: an ``init_state(..., state_backend="sketch")`` dict.  Returns
    ``(new_state, feats (n, N_FEATURES))`` matching the pure-JAX
    reference ``core/sketch.process_sketch`` to float tolerance.
    """
    from repro.core.sketch import sketch_packet_rows, sketch_rows, \
        sketch_width

    R, W = sketch_rows(state), sketch_width(state)
    sl = sketch_packet_rows(pkts, R, W)
    ts = pkts["ts"].astype(jnp.float32)
    lens = pkts["length"].astype(jnp.float32)
    n = ts.shape[0]

    # host-side flattened row precomputation: uni row (k·R+r)·W + col,
    # own-direction bi row ((k·R+r)·W + col)·2 + d; the kernel derives the
    # opposite-direction row (``^ 1``) and the channel row (``>> 1``)
    key_off = (jnp.arange(N_UNI, dtype=jnp.int32) * R)[:, None] \
        + jnp.arange(R, dtype=jnp.int32)[None, :]               # (K, R)
    ucols = jnp.stack([sl["src_mac_ip"], sl["src_ip"]], 1)      # (n, K, R)
    urow = (key_off[None] * W + ucols).reshape(n, -1)
    bcols = jnp.stack([sl["channel"], sl["socket"]], 1)
    bbase = (key_off[None] * W + bcols).reshape(n, -1)          # (n, K·R)
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
        "bsr": bi["sr"], "bslt": bi["sr_last_t"], "bsw": bi["sw"],
    }
    tables = {k: v.reshape(-1, N_DECAY) for k, v in tables.items()}
    new_tab, stats = _sketch_call(
        tables, state["evict_age"], idx, pad1(ts), pad1(lens), chunk=chunk,
        interpret=interpret, n=n, rows=R)

    feats = jnp.take(stats, jnp.asarray(_BLOCKED_TO_ORACLE), axis=1)
    sh_u = (N_UNI, R, W, N_DECAY)
    sh_b = (N_BI, R, W, 2, N_DECAY)
    sh_s = (N_BI, R, W, N_DECAY)
    new_state = {
        "uni": {"last_t": new_tab["ult"].reshape(sh_u),
                "w": new_tab["uw"].reshape(sh_u),
                "ls": new_tab["uls"].reshape(sh_u),
                "ss": new_tab["uss"].reshape(sh_u)},
        "bi": {"last_t": new_tab["blt"].reshape(sh_b),
               "w": new_tab["bw"].reshape(sh_b),
               "ls": new_tab["bls"].reshape(sh_b),
               "ss": new_tab["bss"].reshape(sh_b),
               "res_last": new_tab["brl"].reshape(sh_b),
               "sr": new_tab["bsr"].reshape(sh_s),
               "sr_last_t": new_tab["bslt"].reshape(sh_s),
               "sw": new_tab["bsw"].reshape(sh_s)},
        "evict_age": state["evict_age"],
    }
    return new_state, feats
