"""One general traffic generator, driven by a mix file (``traffic/<mix>.json``).

A mix gives the parameters; this module turns them into per-tenant packet
pools with vectorised numpy, from the seed alone.  A pool is replayed in
laps: packet ``i`` of a tenant's stream is pool entry ``i % P`` with its
timestamp advanced by ``(i // P) * span``.  One shape exists:

* ``backbone``: one stream of many concurrent 5-tuple flows whose packet
  shares follow Zipf(``zipf_s``), IMIX packet sizes, and timestamps spaced
  by each packet's wire time at the mix's nominal ``link_bps``.

It mixes in ``attack_share`` of packets from the attack families named in
``attacks`` (shapes copied from the published attack descriptions the
repository's synthetic traces follow).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

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


def _mix_in_attacks(pk: Dict[str, np.ndarray], share: float,
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


# ---------------------------------------------------------------------------
# backbone: Zipf flows, IMIX sizes, wire-time spacing at the nominal rate
# ---------------------------------------------------------------------------
def _backbone(mix: Dict, rng) -> Pool:
    P, F = int(mix["pool_packets"]), int(mix["flows"])
    w = 1.0 / np.arange(1, F + 1, dtype=np.float64) ** float(mix["zipf_s"])
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    rank = np.searchsorted(cdf, rng.random(P), side="right").clip(0, F - 1)
    flow = rng.permutation(F)[rank]          # popularity not tied to flow id
    lan, wan = int(mix["src_base"]), int(mix["dst_base"])
    f_src = (lan + rng.integers(0, int(mix["src_hosts"]), F)).astype(np.uint32)
    f_dst = (wan + rng.integers(0, int(mix["dst_hosts"]), F)).astype(np.uint32)
    f_sport = rng.integers(1024, 65536, F).astype(np.uint32)
    ports = np.asarray(mix["server_ports"], np.uint32)
    f_dport = ports[rng.integers(0, len(ports), F)]
    f_proto = np.where(rng.random(F) < float(mix["udp_share"]), UDP, TCP) \
        .astype(np.uint32)
    rev = rng.random(P) < float(mix["reverse_share"])
    s, d = f_src[flow], f_dst[flow]
    sp, dp = f_sport[flow], f_dport[flow]
    pk = {"src": np.where(rev, d, s), "dst": np.where(rev, s, d),
          "sport": np.where(rev, dp, sp), "dport": np.where(rev, sp, dp),
          "proto": f_proto[flow].copy()}
    sizes = np.asarray(mix["sizes"], np.float64)
    wts = np.asarray(mix["size_weights"], np.float64)
    pk["length"] = sizes[rng.choice(len(sizes), size=P, p=wts / wts.sum())]
    label = _mix_in_attacks(pk, float(mix["attack_share"]),
                            list(mix["attacks"]), rng, lan, wan)
    length = pk.pop("length").astype(np.float32)
    wire = length.astype(np.float64) * 8.0 / float(mix["link_bps"])
    ts = np.concatenate([[0.0], np.cumsum(wire[:-1])])
    return Pool(fields=pk, length=length, label=label, ts_base=ts,
                span=float(ts[-1] + wire[-1]))


def pools(mix: Dict, n_tenants: int, seed: int) -> List[Pool]:
    """Every tenant's pool for this mix and seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    shape = mix["shape"]
    if shape == "backbone":
        if n_tenants != 1:
            raise ValueError("the backbone shape is one stream")
        return [_backbone(mix, rng)]
    raise ValueError(f"unknown traffic shape {shape!r}")
