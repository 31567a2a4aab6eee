"""One general traffic generator, driven by a mix file (``traffic/<mix>.json``).

A mix gives the parameters; its ``shape`` names the file
``shapes/<shape>.py`` whose ``pools(mix, n_tenants, rng)`` turns them into
per-tenant packet pools with vectorised numpy, from the seed alone.  A pool
is replayed in laps: packet ``i`` of a tenant's stream is pool entry
``i % P`` with its timestamp advanced by ``(i // P) * span``.

This module keeps what shapes share: ``Pool``, and the attack families
(shapes copied from the published attack descriptions the repository's
synthetic traces follow) that ``mix_in_attacks`` writes over a share of a
pool's packets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

from bench import spec

TCP, UDP = 6, 17
FIELDS = ("src", "dst", "sport", "dport", "proto")


@dataclass
class Pool:
    """One tenant's packet pool."""
    fields: Dict[str, np.ndarray]      # src dst sport dport proto: uint32
    length: np.ndarray                 # float32 bytes
    label: np.ndarray                  # uint8, 1 = attack packet
    ts_base: np.ndarray                # float64 seconds, ascending, from 0
    span: float                        # one lap in seconds

    @property
    def size(self) -> int:
        return int(self.length.shape[0])

    def slice(self, a: int, b: int) -> Dict[str, np.ndarray]:
        """Stream packets [a, b) as the arrays handed to the engine
        (timestamps as float32, the type the engine computes in)."""
        P, out, i = self.size, [], a
        while i < b:
            lap, j = divmod(i, P)
            k = min(b - i, P - j)
            out.append((j, k, lap))
            i += k
        cat = lambda xs: xs[0] if len(xs) == 1 else np.concatenate(xs)
        pk = {f: cat([self.fields[f][j:j + k] for j, k, _ in out])
              for f in FIELDS}
        pk["length"] = cat([self.length[j:j + k] for j, k, _ in out])
        pk["ts"] = cat([(self.ts_base[j:j + k] + lap * self.span)
                        .astype(np.float32) for j, k, lap in out])
        return pk


# ---------------------------------------------------------------------------
# attack shapes: (n, rng, lan, wan) -> packet fields and sizes
# ---------------------------------------------------------------------------
def _const(n, v):
    return np.full(n, v, np.uint32)


def _a_syn_dos(n, rng, lan, wan):
    return dict(src=_const(n, wan + 0xBAD), dst=_const(n, wan + 1),
                sport=_const(n, rng.integers(1024, 65535)), dport=_const(n, 80),
                proto=_const(n, TCP), length=rng.normal(60, 4, n).clip(54, 80))


def _a_ssdp_flood(n, rng, lan, wan):
    return dict(src=(wan + 0x100 + rng.integers(0, 80, n)).astype(np.uint32),
                dst=_const(n, lan + 1), sport=_const(n, 1900),
                dport=_const(n, rng.integers(1024, 65535)), proto=_const(n, UDP),
                length=rng.normal(1300, 120, n).clip(300, 1514))


def _a_os_scan(n, rng, lan, wan):
    return dict(src=_const(n, wan + 0x5CA),
                dst=(lan + rng.integers(1, 60, n)).astype(np.uint32),
                sport=_const(n, 40000),
                dport=rng.integers(1, 1024, n).astype(np.uint32),
                proto=_const(n, TCP), length=rng.normal(60, 3, n).clip(54, 74))


def _a_mirai(n, rng, lan, wan):
    return dict(src=(lan + 0x200 + rng.integers(0, 25, n)).astype(np.uint32),
                dst=(lan + rng.integers(1, 200, n)).astype(np.uint32),
                sport=rng.integers(1024, 65535, n).astype(np.uint32),
                dport=np.where(rng.random(n) < 0.9, 23, 2323).astype(np.uint32),
                proto=_const(n, TCP), length=rng.normal(66, 8, n).clip(54, 120))


def _a_fuzzing(n, rng, lan, wan):
    return dict(src=_const(n, wan + 0xF22), dst=_const(n, wan + 2),
                sport=rng.integers(1024, 65535, n).astype(np.uint32),
                dport=rng.integers(1, 9000, n).astype(np.uint32),
                proto=_const(n, TCP), length=rng.uniform(60, 1514, n))


def _a_ssl_renegotiation(n, rng, lan, wan):
    return dict(src=_const(n, wan + 0x55D), dst=_const(n, wan + 1),
                sport=(40000 + np.arange(n) % 64).astype(np.uint32),
                dport=_const(n, 443), proto=_const(n, TCP),
                length=rng.normal(150, 60, n).clip(60, 600))


def _a_ddos_hulk(n, rng, lan, wan):
    return dict(src=(wan + 0x2000 + rng.integers(0, 300, n)).astype(np.uint32),
                dst=_const(n, wan + 1),
                sport=rng.integers(1024, 65535, n).astype(np.uint32),
                dport=_const(n, 80), proto=_const(n, TCP),
                length=rng.normal(350, 120, n).clip(60, 800))


def _a_ddos_loic(n, rng, lan, wan):
    return dict(src=(wan + 0x3000 + rng.integers(0, 150, n)).astype(np.uint32),
                dst=_const(n, wan + 1),
                sport=rng.integers(1024, 65535, n).astype(np.uint32),
                dport=_const(n, 80), proto=_const(n, UDP),
                length=rng.normal(500, 30, n).clip(200, 700))


def _a_goldeneye(n, rng, lan, wan):
    return dict(src=(wan + 0x4000 + rng.integers(0, 12, n)).astype(np.uint32),
                dst=_const(n, wan + 1),
                sport=(20000 + rng.integers(0, 40, n)).astype(np.uint32),
                dport=_const(n, 80), proto=_const(n, TCP),
                length=rng.normal(420, 90, n).clip(100, 900))


def _a_slowloris(n, rng, lan, wan):
    return dict(src=_const(n, wan + 0x510), dst=_const(n, wan + 1),
                sport=(25000 + rng.integers(0, 150, n)).astype(np.uint32),
                dport=_const(n, 80), proto=_const(n, TCP),
                length=rng.normal(70, 8, n).clip(54, 120))


ATTACKS: Dict[str, Callable] = {
    "mirai": _a_mirai, "syn_dos": _a_syn_dos, "ssdp_flood": _a_ssdp_flood,
    "os_scan": _a_os_scan, "fuzzing": _a_fuzzing,
    "ssl_renegotiation": _a_ssl_renegotiation,
    "ddos_hulk": _a_ddos_hulk, "ddos_loic": _a_ddos_loic,
    "goldeneye": _a_goldeneye, "slowloris": _a_slowloris,
}


def mix_in_attacks(pk: Dict[str, np.ndarray], share: float,
                   families: List[str], rng, lan: int, wan: int) -> np.ndarray:
    """Overwrite ``share`` of the positions with attack packets, split
    evenly over ``families``; returns the label vector."""
    n = pk["length"].shape[0]
    label = np.zeros(n, np.uint8)
    m = int(round(share * n))
    if not m or not families:
        return label
    pos = np.sort(rng.choice(n, size=m, replace=False))
    fam = np.arange(m) % len(families)
    for f, name in enumerate(families):
        at = pos[fam == f]
        a = ATTACKS[name](len(at), rng, lan, wan)
        for k in FIELDS:
            pk[k][at] = a[k]
        pk["length"][at] = a["length"]
    label[pos] = 1
    return label


def pools(mix: Dict, n_tenants: int, seed: int) -> List[Pool]:
    """Every tenant's pool for this mix and seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    return spec.shape(mix["shape"])(mix, n_tenants, rng)
