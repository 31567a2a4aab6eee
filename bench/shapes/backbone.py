"""backbone: one mirrored link as one stream of many concurrent 5-tuple
flows whose packet shares follow Zipf(``zipf_s``), IMIX packet sizes, and
timestamps spaced by each packet's wire time at the mix's nominal
``link_bps``, with ``attack_share`` of the packets from the attack
families named in ``attacks``."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.gen import TCP, UDP, Pool, mix_in_attacks


def pools(mix: Dict, n_tenants: int, rng) -> List[Pool]:
    if n_tenants != 1:
        raise ValueError("the backbone shape is one stream")
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
    label = mix_in_attacks(pk, float(mix["attack_share"]),
                           list(mix["attacks"]), rng, lan, wan)
    length = pk.pop("length").astype(np.float32)
    wire = length.astype(np.float64) * 8.0 / float(mix["link_bps"])
    ts = np.concatenate([[0.0], np.cumsum(wire[:-1])])
    return [Pool(fields=pk, length=length, label=label, ts_base=ts,
                 span=float(ts[-1] + wire[-1]))]
