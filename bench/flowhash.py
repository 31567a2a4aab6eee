"""The dense flow-table slot hash, as the system under test defines it.

A copy kept with the benchmark: the plain reference needs it to put flows
into the same slots (colliding flows share a slot, which is part of the
semantics), and the counted-work function needs it to count the distinct
table rows a batch touches.  Per key type the canonicalised fields are
mixed with ``h = (h ^ v) * 0x9E3779B1; h ^= h >> 15`` from the seed
``salt ^ 0x811C9DC5``, and the slot is ``h % n_slots``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KEYS = ("src_mac_ip", "src_ip", "channel", "socket")
SALTS = {"src_mac_ip": 1, "src_ip": 2, "channel": 3, "socket": 4}


def _hash(fields, salt: int) -> np.ndarray:
    h = np.full(np.shape(fields[0]), np.uint32(salt ^ 0x811C9DC5), np.uint32)
    for f in fields:
        h = (h ^ np.asarray(f, np.uint32)) * np.uint32(0x9E3779B1)
        h = h ^ (h >> np.uint32(15))
    return h


def slots(pkts: Dict[str, np.ndarray], n_slots: int):
    """(4, n) int32 slots in ``KEYS`` order and the (n,) uint8 direction
    bit of the bidirectional keys (1 when src is the canonical high end)."""
    src, dst = np.asarray(pkts["src"]), np.asarray(pkts["dst"])
    sport, dport = np.asarray(pkts["sport"]), np.asarray(pkts["dport"])
    lo = (src < dst) | ((src == dst) & (sport <= dport))
    ip_lo, ip_hi = np.where(lo, src, dst), np.where(lo, dst, src)
    p_lo, p_hi = np.where(lo, sport, dport), np.where(lo, dport, sport)
    fields = {"src_mac_ip": (src,), "src_ip": (src,),
              "channel": (ip_lo, ip_hi),
              "socket": (ip_lo, ip_hi, p_lo, p_hi, pkts["proto"])}
    out = np.stack([(_hash(fields[k], SALTS[k]) % np.uint32(n_slots))
                    .astype(np.int32) for k in KEYS])
    return out, (~lo).astype(np.uint8)
