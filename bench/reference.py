"""Plain reference of the detection path: serial FC in C, KitNET in numpy.

Nothing here imports the program or takes anything it made.  The FC
reference (``ref/fcref.c``) walks the stream packet by packet in float64,
the KitNET reference runs the ensemble and output autoencoders on the
record features in float64.  ``prec="bf16"`` computes both in bfloat16
instead: the precision control, which a sound comparison must fail.

The C file is compiled once per checkout into ``bench/.build/`` (git
ignores it) and loaded with ctypes.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "ref", "fcref.c")
BUILD = os.path.join(HERE, ".build")
N_FEATURES = 80
_LIB = None


def _library():
    global _LIB
    if _LIB is not None:
        return _LIB
    with open(SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(BUILD, f"fcref-{tag}.so")
    if not os.path.exists(so):
        os.makedirs(BUILD, exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(["gcc", "-O2", "-ffp-contract=off", "-fPIC", "-shared",
                        "-pthread", SRC, "-o", tmp, "-lm"], check=True)
        os.replace(tmp, so)
    lib = ctypes.CDLL(so)
    i64, i32, dp, vp = (ctypes.c_int64, ctypes.c_int32, ctypes.c_double,
                        ctypes.c_void_p)
    lib.fc_reference.argtypes = [i64, vp, vp, vp, vp, dp, i64, i32, i32,
                                 ctypes.c_int, i64, vp, ctypes.c_int]
    lib.fc_reference.restype = i64
    _LIB = lib
    return lib


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def fc_features(pool_slots: np.ndarray, pool_dir: np.ndarray,
                pool_len: np.ndarray, pool_ts: np.ndarray, span: float,
                n_gen: int, n_slots: int, epoch: int, n_rec: int,
                prec: str = "f64", threads: Optional[int] = None
                ) -> np.ndarray:
    """Features (n_rec, 80) at the first ``n_rec`` record positions of the
    stream: the first ``n_gen`` packets of the pool replayed in laps."""
    P = int(pool_len.shape[0])
    slots = np.ascontiguousarray(pool_slots, np.int32)
    if slots.shape != (4, P) or slots.min(initial=0) < 0 or \
            slots.max(initial=0) >= n_slots:
        raise ValueError("pool slots must be (4, P) in [0, n_slots)")
    dirb = np.ascontiguousarray(pool_dir, np.uint8)
    length = np.ascontiguousarray(pool_len, np.float32)
    ts = np.ascontiguousarray(pool_ts, np.float64)
    if dirb.shape != (P,) or ts.shape != (P,):
        raise ValueError("pool arrays must all have P entries")
    out = np.zeros((max(n_rec, 1), N_FEATURES), np.float64)
    threads = threads or min(16, max(4, os.cpu_count() or 4))
    made = _library().fc_reference(
        P, _ptr(slots), _ptr(dirb), _ptr(length), _ptr(ts), float(span),
        int(n_gen), int(n_slots), int(epoch),
        1 if prec == "bf16" else 0, int(n_rec), _ptr(out), int(threads))
    if made < 0:
        raise MemoryError("fc reference could not allocate its tables")
    return out[:made]


# ---------------------------------------------------------------------------
# KitNET: 0-1 normalisation (clipped to [0, 4]), one autoencoder per feature
# group (d -> h -> d, sigmoid), RMSE per group, then the output autoencoder
# over the normalised group RMSEs; the score is its reconstruction RMSE.
# ---------------------------------------------------------------------------
def _rounder(prec: str):
    if prec == "bf16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    return lambda x: np.asarray(x, np.float64)


def _norm(x, lo, hi, r):
    return r(np.clip(r(r(x - lo) / np.maximum(r(hi - lo), 1e-9)), 0.0, 4.0))


def _group_rmse(p, idx, feats, r):
    sig = lambda z: r(1.0 / (1.0 + np.exp(-z)))
    x = _norm(r(feats), p["norm_min"], p["norm_max"], r)
    mask = p["mask"]
    sub = r(x[:, idx] * mask[None])                            # (n, k, m)
    h = sig(r(np.einsum("bkm,kmh->bkh", sub, p["W1"]) + p["b1"][None]))
    y = sig(r(np.einsum("bkh,khm->bkm", h, p["W2"]) + p["b2"][None]))
    se = r(r((y - sub) ** 2) * mask[None])
    return r(np.sqrt(r(se.sum(-1) / np.maximum(mask.sum(-1), 1.0)[None])))


def _params(net, prec):
    r = _rounder(prec)
    return r, {k: r(v) for k, v in net.items() if k != "idx"}, \
        np.asarray(net["idx"], np.int64)


def ensemble_rmse(net: Dict[str, np.ndarray], feats: np.ndarray) -> np.ndarray:
    """Group RMSEs (n, k) in float64: what the output normaliser is fit on."""
    r, p, idx = _params(net, "f64")
    return _group_rmse(p, idx, feats, r)


def kitnet_scores(net: Dict[str, np.ndarray], feats: np.ndarray,
                  prec: str = "f64") -> np.ndarray:
    """Anomaly score per record row of ``feats`` (n, 80)."""
    r, p, idx = _params(net, prec)
    sig = lambda z: r(1.0 / (1.0 + np.exp(-z)))
    rn = _norm(_group_rmse(p, idx, feats, r), p["out_min"], p["out_max"], r)
    h2 = sig(r(rn @ p["V1"] + p["c1"][None]))
    y2 = sig(r(h2 @ p["V2"] + p["c2"][None]))
    return r(np.sqrt(r(np.mean(r((y2 - rn) ** 2), axis=-1))))
