"""TPU-native Peregrine feature computation: segmented associative scans.

The switch updates flow state one packet at a time.  On TPU we exploit that
the decayed-atom update  A_i = delta_i * A_{i-1} + x_i  is a *linear
first-order recurrence*, hence associative:

    (s2, a2) o (s1, a1) = (s1*s2, a1*s2 + a2)

so a whole packet batch is processed in O(log n) depth with
``jax.lax.associative_scan``, *segmented by flow* (sort by stream id, stable,
which preserves time order inside each stream).  Cross-direction state
(stale opposite-direction statistics, last-residual for SR) uses a segmented
"latest-value" scan, which is also associative.

Semantics are bit-for-bit the serial oracle's ``exact`` mode (tested to
float tolerance); the round-robin ``switch`` mode is inherently per-packet
serial and stays on the oracle path.

A batch pays ONE stable argsort per key type (vmapped over the stacked
tables: one sort primitive for the two uni keys, one for the two bi keys).
Everything else is derived: the bidirectional (slot, dir, time) stream
order comes from the (slot, time) channel sort via segmented cumsum ranks
(``_dir_interleave_perm``), and the ``res_last`` store-back reuses that
same permutation instead of re-sorting by the composite key —
``tests/test_fused.py`` pins the sort count at ≤ 4.

Scan primitives are *fused across atoms*: one stacked ``associative_scan``
over ``(n, N_DECAY, 3)`` carries the three decayed atoms (w, LS, SS) of a
stream table, and one stacked latest-value scan over ``(n, 2, N_DECAY, 4)``
carries both directions' stale atoms AND last-residuals of a channel pass —
4 ``associative_scan`` invocations per batch instead of the 11 the unfused
code paid (``tests/test_bucketed.py`` pins the counts).

Both segmented scans also run in *chunked two-level* form (``chunks=S``):
the flow-hash-sorted batch is cut into S equal slices, each slice scanned
independently (depth O(log n/S), mesh-placeable — ``core/bucketed.py``),
and an O(S) exclusive combine over per-chunk tails carries segments that
straddle a cut.  Chunked results equal the flat scan up to fp
reassociation (a few ulp; bit-identical at S=1).

``process_parallel_sampled`` is the record-sampled variant for the fused
serving step (DESIGN.md §8): flow-state updates cover every packet, but
feature statistics are only materialised at the sampled rows.

Requires ``pkts["ts"]`` sorted ascending (streams are time-ordered).

Device work carries ``jax.named_scope`` labels (HLO ``op_name`` metadata
only: no op, fusion or value changes), so a profiler trace splits FC by
stage: ``fc.sort`` the argsorts, ``fc.scan`` the table-carry reads, decay
preparation and segmented scans, ``fc.store`` the segment-end store-backs,
``fc.record_gather`` the sampled-row gathers, statistics and feature
assembly.  The serving step (serving/fused.py) wraps the pass in ``fc``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import arith
from repro.core.state import (
    LAMBDAS, N_BI, N_DECAY, N_UNI, packet_slots,
)

_LAM = jnp.asarray(LAMBDAS, jnp.float32)


# ---------------------------------------------------------------------------
# segmented-scan primitives
# ---------------------------------------------------------------------------
def _expand(a, ndim):
    """Append trailing singleton dims until ``a.ndim == ndim``."""
    while a.ndim < ndim:
        a = a[..., None]
    return a


def _linear_combine(l, r):
    fl, sl, al = l
    fr, sr, ar = r
    return (fl | fr,
            jnp.where(fr, sr, sl * sr),
            jnp.where(fr, ar, al * sr + ar))


def _last_combine(l, r):
    fl, vl, xl = l
    fr, vr, xr = r
    found = jnp.where(fr, vr, vl | vr)
    # a fresh segment with no valid element must contribute an explicit
    # zero: ``xr * 0`` would propagate NaN/inf from invalid rows
    val = jnp.where(fr, jnp.where(vr, xr, jnp.zeros_like(xr)),
                    jnp.where(vr, xr, xl))
    return (fl | fr, found, val)


def _chunk2(a, chunks):
    """(n, ...) -> (chunks, n//chunks, ...) — a free row-major reshape."""
    return a.reshape((chunks, a.shape[0] // chunks) + a.shape[1:])


def _excl_shift(t, identity):
    """Inclusive chunk-tail scan -> exclusive carry (identity at chunk 0)."""
    return jnp.concatenate([jnp.full_like(t[:1], identity), t[:-1]], axis=0)


def seg_linear_scan(seg_start, delta, x, chunks: int = 1, shard=None):
    """Segmented A_i = delta_i * A_{i-1} + x_i (A resets at segment starts).

    seg_start: (n,) bool; delta, x: (n, ...) broadcastable (``delta`` may be
    narrower than ``x`` in trailing dims — it broadcasts inside the
    combine).  Returns A with ``x``'s shape.

    ``chunks=S`` runs the two-level form: S independent local scans over
    equal slices of the array (each slice's flows are disjoint except for
    segments straddling a cut), then one exclusive combine over the S
    per-chunk tail summaries, then an O(n) elementwise fix-up — the same
    associative combine, reassociated.  ``shard`` (a
    ``distributed.sharding.ShardContext`` — core/bucketed.py builds one
    from the ambient mesh) places the whole two-level scan under
    ``shard_map`` over the chunk axis with every O(n) step shard-local:
    each device scans its own chunks, all-gathers the O(S) per-chunk tail
    summaries (the ONLY collective — a few KB), runs the tiny combine
    redundantly, and fixes up its own chunks.  No full-batch collectives.
    """
    f = _expand(seg_start, delta.ndim)
    if chunks <= 1:
        _, _, a = jax.lax.associative_scan(
            _linear_combine, (f, delta, x), axis=0)
        return a
    fc, dc, xc = (_chunk2(a, chunks) for a in (f, delta, x))

    if shard is None:
        lf, ls, la = jax.lax.associative_scan(_linear_combine, (fc, dc, xc),
                                              axis=1)
        # carry across cuts: segmented combine over per-chunk tails, excl.
        _, _, pa = jax.lax.associative_scan(
            _linear_combine, (lf[:, -1], ls[:, -1], la[:, -1]), axis=0)
        pa = _excl_shift(pa, 0)
        # combine(carry, local) per element; lf kills the carry as soon as
        # the chunk has seen a real segment start
        a = jnp.where(lf, la, pa[:, None] * ls + la)
        return a.reshape((x.shape[0],) + a.shape[2:])

    n_local = chunks // shard.size

    def local(fc, dc, xc):
        lf, ls, la = jax.lax.associative_scan(_linear_combine, (fc, dc, xc),
                                              axis=1)
        gf, gs, ga = (shard.gather_tails(t)
                      for t in (lf[:, -1], ls[:, -1], la[:, -1]))
        _, _, pa = jax.lax.associative_scan(
            _linear_combine, (gf, gs, ga), axis=0)
        pa = shard.local_chunks(_excl_shift(pa, 0), n_local)
        return jnp.where(lf, la, pa[:, None] * ls + la)

    a = shard.wrap(local)(fc, dc, xc)
    return a.reshape((x.shape[0],) + a.shape[2:])


def seg_last_scan(seg_start, valid, value, chunks: int = 1, shard=None):
    """Segmented latest-valid-value (inclusive). Returns (found, last_value).

    ``found[i]`` False means no valid element yet in i's segment.  ``valid``
    may carry extra trailing dims narrower than ``value`` (e.g. a per-
    direction mask ``(n, 2)`` against values ``(n, 2, ND, k)``) — it
    broadcasts inside the combine, and ``found`` is returned at the
    broadcast shape of ``valid``.  ``chunks``/``shard`` as in
    :func:`seg_linear_scan`.
    """
    f = _expand(seg_start, value.ndim)
    v = _expand(valid, value.ndim)
    if chunks <= 1:
        _, found, val = jax.lax.associative_scan(
            _last_combine, (f, v, value), axis=0)
        return found, val
    fc, vc, xc = (_chunk2(a, chunks) for a in (f, v, value))
    n = value.shape[0]

    if shard is None:
        lf, lv, lx = jax.lax.associative_scan(_last_combine, (fc, vc, xc),
                                              axis=1)
        _, pv, px = jax.lax.associative_scan(
            _last_combine, (lf[:, -1], lv[:, -1], lx[:, -1]), axis=0)
        pv = _excl_shift(pv, False)
        px = _excl_shift(px, 0)
        found = jnp.where(lf, lv, pv[:, None] | lv)
        val = jnp.where(lv, lx,
                        jnp.where(lf, jnp.zeros_like(lx), px[:, None]))
        return (found.reshape((n,) + found.shape[2:]),
                val.reshape((n,) + val.shape[2:]))

    n_local = chunks // shard.size

    def local(fc, vc, xc):
        lf, lv, lx = jax.lax.associative_scan(_last_combine, (fc, vc, xc),
                                              axis=1)
        gf, gv, gx = (shard.gather_tails(t)
                      for t in (lf[:, -1], lv[:, -1], lx[:, -1]))
        _, pv, px = jax.lax.associative_scan(_last_combine, (gf, gv, gx),
                                             axis=0)
        pv = shard.local_chunks(_excl_shift(pv, False), n_local)
        px = shard.local_chunks(_excl_shift(px, 0), n_local)
        found = jnp.where(lf, lv, pv[:, None] | lv)
        val = jnp.where(lv, lx,
                        jnp.where(lf, jnp.zeros_like(lx), px[:, None]))
        return found, val

    found, val = shard.wrap(local)(fc, vc, xc)
    return (found.reshape((n,) + found.shape[2:]),
            val.reshape((n,) + val.shape[2:]))


def _segments(sorted_ids):
    n = sorted_ids.shape[0]
    start = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_ids[1:] != sorted_ids[:-1]])
    end = jnp.concatenate([sorted_ids[1:] != sorted_ids[:-1],
                           jnp.ones((1,), bool)])
    return start, end


def _dir_interleave_perm(start, end, d):
    """Derive the (slot, dir, time) permutation from the (slot, time) sort.

    Given segment markers of the channel-sorted order and the per-element
    direction bits ``d``, returns ``gather`` such that ``X[gather]`` is the
    stable sort by the composite key ``slot*2 + dir`` — computed with
    segmented cumsum ranks in O(n), so the batch pays ONE argsort per key
    type instead of re-sorting for the directional view.
    """
    n = d.shape[0]
    ar = jnp.arange(n)
    seg_first = jax.lax.cummax(jnp.where(start, ar, -1))
    seg_last = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(end, ar, n))))
    d0 = (d == 0).astype(ar.dtype)
    pref0 = jnp.cumsum(d0)                  # inclusive dir-0 count
    excl0 = pref0 - d0
    base0 = excl0[seg_first]
    n0_seg = pref0[seg_last] - base0        # dir-0 population of the segment
    rank0 = excl0 - base0
    d1 = 1 - d0
    excl1 = jnp.cumsum(d1) - d1
    rank1 = excl1 - excl1[seg_first]
    pos = seg_first + jnp.where(d == 0, rank0, n0_seg + rank1)
    return arith.invert_perm(pos)


# ---------------------------------------------------------------------------
# one directional stream table pass
# ---------------------------------------------------------------------------
def stream_pass(tab, stream_ids, ts, lens, n_streams, order=None,
                sample=None, chunks: int = 1, shard=None):
    """Vectorised decayed-atom update for one table of streams.

    tab: {"last_t","w","ls","ss"} each (n_streams, N_DECAY).
    stream_ids/ts/lens: (n,). Returns (per-packet atoms dict in ORIGINAL
    order, updated table).  ``order`` is the stable sort by stream id; pass
    it when already available (derived or shared) to avoid a re-sort.
    ``sample`` restricts the returned atoms to those original-order rows
    (the table update always covers every packet) — the fused serving step
    only ever reads the sampled records, so the full-width gather back to
    packet order is skipped.  ``chunks``/``shard`` select the two-level
    bucketed scan (core/bucketed.py).

    The three decayed atoms ride ONE stacked scan over ``(n, N_DECAY, 3)``
    (lanes w/ls/ss) — identical per-lane math to three separate scans, a
    third of the scan dispatches.
    """
    n = stream_ids.shape[0]
    if order is None:
        with jax.named_scope("fc.sort"):
            order = jnp.argsort(stream_ids, stable=True)
    inv = arith.invert_perm(order)
    sid = stream_ids[order]
    t = ts[order]
    x = lens[order]
    start, end = _segments(sid)

    with jax.named_scope("fc.scan"):
        # per-packet decay: dt to previous packet in stream (table last_t
        # at start)
        t_prev_in = jnp.concatenate([t[:1], t[:-1]])
        last_t_tab = tab["last_t"][sid]                   # (n, N_DECAY)
        fresh = last_t_tab < 0.0
        dt = jnp.where(start[:, None],
                       jnp.where(fresh, 0.0, t[:, None] - last_t_tab),
                       (t - t_prev_in)[:, None])
        dt = jnp.maximum(dt, 0.0)
        delta = jnp.exp2(-_LAM[None, :] * dt)
        delta = jnp.where(start[:, None] & fresh, 0.0, delta)

        # stacked per-packet increments, table carry folded into first
        # elements: A_1 = delta_1*A_tab + x_1
        xs = jnp.stack([jnp.ones((n, N_DECAY)),
                        jnp.broadcast_to(x[:, None], (n, N_DECAY)),
                        jnp.broadcast_to((x ** 2)[:, None], (n, N_DECAY))],
                       axis=-1)                           # (n, ND, 3)
        tab_a = jnp.stack([tab["w"], tab["ls"], tab["ss"]], axis=-1)[sid]
        x0 = jnp.where(start[:, None, None], xs + delta[..., None] * tab_a,
                       xs)
        atoms3 = seg_linear_scan(start, delta[..., None], x0,
                                 chunks=chunks, shard=shard)  # (n, ND, 3)
        w, ls, ss = atoms3[..., 0], atoms3[..., 1], atoms3[..., 2]

    # store back last element of each segment (indices unique by construction)
    with jax.named_scope("fc.store"):
        sid_end = jnp.where(end, sid, n_streams)          # OOB drops
        new_tab = {
            "last_t": tab["last_t"].at[sid_end].set(
                jnp.broadcast_to(t[:, None], (n, N_DECAY)), mode="drop"),
            "w": tab["w"].at[sid_end].set(w, mode="drop"),
            "ls": tab["ls"].at[sid_end].set(ls, mode="drop"),
            "ss": tab["ss"].at[sid_end].set(ss, mode="drop"),
        }
    with jax.named_scope("fc.record_gather"):
        rows = inv if sample is None else inv[sample]
        atoms = {"w": w[rows], "ls": ls[rows], "ss": ss[rows]}
    return atoms, new_tab


def _stats(w, ls, ss):
    mu = jnp.where(w > 0, ls / jnp.maximum(w, 1e-12), 0.0)
    ex2 = jnp.where(w > 0, ss / jnp.maximum(w, 1e-12), 0.0)
    var = jnp.abs(ex2 - mu ** 2)
    return mu, var, jnp.sqrt(var)


# ---------------------------------------------------------------------------
# channel pass: stale opposite stats + SR recurrence
# ---------------------------------------------------------------------------
def channel_pass(bi_k, slots, dirs, ts, lens, own_atoms, n_slots,
                 order=None, dir_gather=None, sample=None, chunks: int = 1,
                 shard=None):
    """Cross-direction state for ONE bi key type.

    bi_k: the per-key-type slices of the bi table (each (n_slots, ...)).
    own_atoms: per-packet post-update atoms of the packet's own direction
    (original order, (n, N_DECAY) each).
    Returns (features pieces, updated bi_k).  ``order`` (stable sort by
    slot) and ``dir_gather`` (channel order -> (slot, dir, time) order,
    see ``_dir_interleave_perm``) are derived when not supplied.

    ``sample`` restricts the *emitted feature rows* to those
    original-order positions: the segmented scans and table store-backs
    always cover every packet (they carry the flow state), but the derived
    statistics (opposite-side stats, mag/radius/cov/pcc) and the feature
    stack are only materialised at the sampled rows — identical values to
    slicing the full output, row for row, since the per-row math is
    unchanged.

    The per-direction stale atoms AND last-residuals ride ONE stacked
    latest-value scan over ``(n, 2, N_DECAY, 4)`` (direction axis × lanes
    w/ls/ss/residual) — one scan dispatch where the unfused code paid four.
    """
    n = slots.shape[0]
    if order is None:
        with jax.named_scope("fc.sort"):
            order = jnp.argsort(slots, stable=True)
    inv = arith.invert_perm(order)
    sid = slots[order]
    d = dirs[order]
    t = ts[order]
    start, end = _segments(sid)
    if dir_gather is None:
        dir_gather = _dir_interleave_perm(start, end, d)

    own_w = own_atoms["w"][order]
    own_ls = own_atoms["ls"][order]
    own_ss = own_atoms["ss"][order]

    with jax.named_scope("fc.scan"):
        # --- residual vs own-direction mean (full width: SR consumes every
        # row)
        mu_own, _, _ = _stats(own_w, own_ls, own_ss)
        lens_s = lens[order]
        r = lens_s[:, None] - mu_own                              # (n, ND)

        # --- ONE latest-value scan: latest same-channel packet per direction,
        # lanes = (w, ls, ss, residual); the table fallback is applied at
        # emission (atoms) / consumption (residual) time ---
        lanes = jnp.stack([own_w, own_ls, own_ss, r], axis=-1)    # (n, ND, 4)
        latest = jnp.broadcast_to(lanes[:, None],
                                  (n, 2) + lanes.shape[1:])   # (n, 2, ND, 4)
        per_dir = jnp.stack([d == 0, d == 1], axis=1)             # (n, 2)
        found, val = seg_last_scan(start, per_dir, latest,
                                   chunks=chunks, shard=shard)
        found0, found1 = found[:, 0], found[:, 1]                 # (n, 1, 1)
        val0, val1 = val[:, 0, :, :3], val[:, 1, :, :3]           # (n, ND, 3)
        tabv = jnp.stack([bi_k["w"], bi_k["ls"], bi_k["ss"]], axis=-1)

        def latest_res(X):
            fnd = found[:, X, :, 0]                               # (n, 1)
            return jnp.where(fnd, val[:, X, :, 3],
                             bi_k["res_last"][:, X][sid])

        r0 = latest_res(0)
        r1 = latest_res(1)
        r_opp = jnp.where((d == 0)[:, None], r1, r0)

        # --- SR recurrence over the whole channel (both directions) ---
        t_prev = jnp.concatenate([t[:1], t[:-1]])
        sr_lt_tab = bi_k["sr_last_t"][sid]                        # (n, ND)
        fresh = sr_lt_tab < 0.0
        dt = jnp.where(start[:, None],
                       jnp.where(fresh, 0.0, t[:, None] - sr_lt_tab),
                       (t - t_prev)[:, None])
        dsr = jnp.exp2(-_LAM[None, :] * jnp.maximum(dt, 0.0))
        dsr = jnp.where(start[:, None] & fresh, 0.0, dsr)
        x_sr = r * r_opp
        x_sr = jnp.where(start[:, None], x_sr + dsr * bi_k["sr"][sid], x_sr)
        sr = seg_linear_scan(start, dsr, x_sr, chunks=chunks, shard=shard)

    # --- bidirectional stats, emitted at the requested rows only ---
    def emit(rows):
        sel = (lambda a: a) if rows is None else (lambda a: a[rows])
        dr = sel(d)
        ow, ols, oss = sel(own_w), sel(own_ls), sel(own_ss)
        v0 = jnp.where(sel(found0), sel(val0), tabv[:, 0][sel(sid)])
        v1 = jnp.where(sel(found1), sel(val1), tabv[:, 1][sel(sid)])
        opp = jnp.where((dr == 0)[:, None, None], v1, v0)     # (m,ND,3)
        opp_w, opp_ls, opp_ss = opp[..., 0], opp[..., 1], opp[..., 2]
        mu_o, var_o, sig_o = _stats(ow, ols, oss)
        mu_p, var_p, sig_p = _stats(opp_w, opp_ls, opp_ss)
        mag = jnp.sqrt(mu_o ** 2 + mu_p ** 2)
        rad = jnp.sqrt(var_o ** 2 + var_p ** 2)
        wsum = ow + opp_w
        cov = jnp.where(wsum > 0, sel(sr) / jnp.maximum(wsum, 1e-12), 0.0)
        sden = sig_o * sig_p
        pcc = jnp.where(sden > 0, cov / jnp.maximum(sden, 1e-12), 0.0)
        return jnp.stack([ow, mu_o, sig_o, mag, rad, cov, pcc],
                         axis=-1)                             # (m, ND, 7)

    with jax.named_scope("fc.record_gather"):
        feats = emit(None)[inv] if sample is None else emit(inv[sample])

    # --- store-back (segment ends; res_last per direction: last of each) ---
    with jax.named_scope("fc.store"):
        sid_end = jnp.where(end, sid, n_slots)
        new_bi = dict(bi_k)
        new_bi["sr"] = bi_k["sr"].at[sid_end].set(sr, mode="drop")
        new_bi["sr_last_t"] = bi_k["sr_last_t"].at[sid_end].set(
            jnp.broadcast_to(t[:, None], sr.shape), mode="drop")
        # last residual of each (channel, direction): last occurrence of the
        # composite key sid*2+d (unique per (segment, dir) since segments are
        # channel-contiguous) — the derived directional permutation IS the
        # stable sort by that key, so take its segment ends (no re-sort).
        k2s = (sid * 2 + d)[dir_gather]
        _, end2 = _segments(k2s)
        sid2_end = jnp.where(end2, k2s // 2, n_slots)
        d2 = k2s % 2
        new_bi["res_last"] = new_bi["res_last"].at[sid2_end, d2].set(
            r[dir_gather], mode="drop")
    return feats, new_bi


def _bi_key_pass(tabs, slots, dirs, ts, lens, n_slots, sample=None,
                 chunks: int = 1, shard=None):
    """Full bidirectional update for ONE bi key type with ONE argsort.

    tabs: the per-key slices of ``state["bi"]`` (last_t/w/ls/ss
    (n_slots, 2, ND); sr/sr_last_t (n_slots, ND); res_last (n_slots, 2, ND)).
    The channel sort (slot, time) is computed once; the directional stream
    order (slot, dir, time) the atom update needs is derived from it with
    segmented cumsum ranks, and the ``res_last`` store-back reuses the same
    derived permutation.  Returns (bi features (n|m, ND, 7), updated tabs);
    ``sample`` restricts the emitted feature rows (state is always full).
    """
    with jax.named_scope("fc.sort"):
        order = jnp.argsort(slots, stable=True)
    sid = slots[order]
    d_s = dirs[order]
    start, end = _segments(sid)
    dir_gather = _dir_interleave_perm(start, end, d_s)
    order_dir = order[dir_gather]

    # directional streams: stream id = slot*2 + dir; table layout
    # (n_slots, 2, ND) reshapes to that row id — a view, no data movement
    tab = {f: tabs[f].reshape(2 * n_slots, N_DECAY)
           for f in ("last_t", "w", "ls", "ss")}
    atoms, new_tab = stream_pass(tab, slots * 2 + dirs, ts, lens,
                                 2 * n_slots, order=order_dir,
                                 chunks=chunks, shard=shard)
    # stale-opposite fallback must be the PRE-batch table values
    bi_k_pre = {f: tabs[f] for f in
                ("sr", "sr_last_t", "res_last", "w", "ls", "ss")}
    fts, upd = channel_pass(bi_k_pre, slots, dirs, ts, lens, atoms, n_slots,
                            order=order, dir_gather=dir_gather,
                            sample=sample, chunks=chunks, shard=shard)
    new_tabs = {f: new_tab[f].reshape(n_slots, 2, N_DECAY)
                for f in ("last_t", "w", "ls", "ss")}
    new_tabs.update({f: upd[f] for f in ("sr", "sr_last_t", "res_last")})
    return fts, new_tabs


def _process_parallel_impl(state: Dict, pkts: Dict[str, jax.Array],
                           sample_idx=None, chunks: int = 1,
                           shard=None) -> Tuple[Dict, jax.Array]:
    from repro.core.state import state_slots
    n_slots = state_slots(state)
    sl = packet_slots(pkts, n_slots)
    ts = pkts["ts"].astype(jnp.float32)
    lens = pkts["length"].astype(jnp.float32)
    n_real = ts.shape[0]

    if chunks > 1:
        # equal-size chunks need n % chunks == 0: pad with sentinel-slot
        # packets that sort AFTER every real stream (their own segments at
        # the tail), never store back (OOB rows drop), and are never
        # emitted (feature rows are gathered for real packets only)
        pad = (-n_real) % chunks
        if pad:
            sl = {k: jnp.pad(v, (0, pad),
                             constant_values=0 if k == "dir" else n_slots)
                  for k, v in sl.items()}
            ts = jnp.pad(ts, (0, pad), mode="edge")   # keep ts monotone
            lens = jnp.pad(lens, (0, pad))
            if sample_idx is None:
                sample_idx = jnp.arange(n_real)
    n = n_real if sample_idx is None else sample_idx.shape[0]

    # ---- unidirectional: both key types vmapped over the stacked tables ----
    uni_ids = jnp.stack([sl[k] for k in ("src_mac_ip", "src_ip")])
    uni_tab = {f: state["uni"][f] for f in ("last_t", "w", "ls", "ss")}
    atoms, new_uni_tab = jax.vmap(
        lambda tab, ids: stream_pass(tab, ids, ts, lens, n_slots,
                                     sample=sample_idx, chunks=chunks,
                                     shard=shard)
    )(uni_tab, uni_ids)
    with jax.named_scope("fc.record_gather"):
        mu, _, sig = _stats(atoms["w"], atoms["ls"], atoms["ss"])
        uni_feats = jnp.stack([atoms["w"], mu, sig], axis=-1)  # (2,n|m,ND,3)

    # ---- bidirectional: both key types vmapped, one argsort each ----
    bi_slots = jnp.stack([sl[k] for k in ("channel", "socket")])
    bi_tabs = {f: state["bi"][f] for f in
               ("last_t", "w", "ls", "ss", "sr", "sr_last_t", "res_last")}
    bi_feats, new_bi_tabs = jax.vmap(
        lambda tabs, s: _bi_key_pass(tabs, s, sl["dir"], ts, lens, n_slots,
                                     sample=sample_idx, chunks=chunks,
                                     shard=shard)
    )(bi_tabs, bi_slots)                                     # (2, n|m, ND, 7)

    with jax.named_scope("fc.record_gather"):
        out = jnp.concatenate([
            jnp.moveaxis(uni_feats, 0, 1).reshape(n, -1),
            jnp.moveaxis(bi_feats, 0, 1).reshape(n, -1)], axis=-1)
    new_state = {"uni": {**new_uni_tab, "rr": state["uni"]["rr"]},
                 "bi": {**new_bi_tabs, "rr": state["bi"]["rr"]}}
    return new_state, out


def process_parallel_sampled(state: Dict, pkts: Dict[str, jax.Array],
                             sample_idx: jax.Array) -> Tuple[Dict, jax.Array]:
    """Exact-mode FC where only ``sample_idx``'s feature rows are emitted.

    The flow-table update still covers every packet (same new state as
    :func:`process_parallel`, to compiler-refusion ulp noise); the emitted
    rows equal ``process_parallel(...)[1][sample_idx]`` to the same noise
    — the per-row math is the same, it just never materialises the
    unsampled rows.  Built for the
    fused serving step (serving/fused.py), which samples records *after*
    feature computation exactly as the paper prescribes, so packets that
    close no epoch never pay the statistics-assembly cost.  Unjitted: the
    caller fuses it into its own jit.
    """
    return _process_parallel_impl(state, pkts, sample_idx)


process_parallel = jax.jit(_process_parallel_impl,
                           static_argnames=("chunks", "shard"))
process_parallel.__doc__ = (
    "Exact-mode Peregrine FC via segmented scans. Same I/O as "
    "``process_serial(..., mode='exact')``.")
