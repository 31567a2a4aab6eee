"""The least work one fused step must do, counted from shapes and traffic.

The count does not depend on how the program implements the step:

* bytes: the packet fields in (7 x 4 B per packet); one read and one write
  of every distinct flow-table row the batch touches, per key type, at
  that key type's row size; the record outputs (index, score, alarm) and
  one count per lane; KitNET's weights read once;
* operations: KitNET's ensemble and output matmuls per record (2 per
  multiply-add, encoder and decoder) and a fixed FC count per packet, key
  type and decay.

The least time is the larger of bytes over the chip's memory bandwidth and
operations over its peak rate (``peaks.json``).
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

PACKET_FIELD_BYTES = 7 * 4          # ts src dst sport dport proto length
N_DECAY = 4
# one dense row per key type: (last_t, w, ls, ss) x 4 decays x 4 B + the
# 4 B round-robin counter; bidirectional rows hold both directions plus
# sr, sr_last_t (4 decays each), res_last (2 x 4) and the counter
ROW_BYTES = (68, 68, 196, 196)      # src_mac_ip, src_ip, channel, socket
# the exact-mode update of one (key type, decay) atom set per packet:
# dt, decay argument, exp2, three multiply-adds for w/ls/ss, the square,
# and for the bidirectional keys the residual and its decayed product sum
FC_OPS = 12
RECORD_OUT_BYTES = 4 + 4 + 1


def kitnet_shapes(sizes: Sequence[int], hidden_ratio: float) -> Dict[str, int]:
    k, m = len(sizes), max(sizes)
    return {"k": k, "m": m, "h": int(np.ceil(hidden_ratio * m)),
            "kh": int(np.ceil(hidden_ratio * k))}


def step_work(packets: int, distinct_rows: Sequence[int], records: int,
              lanes: int, kn: Dict[str, int]) -> Dict[str, float]:
    """Bytes and operations of one fused call.  ``distinct_rows`` is the
    number of distinct slots the call touches per key type, summed over
    its lanes."""
    k, m, h, kh = kn["k"], kn["m"], kn["h"], kn["kh"]
    weights = 4 * (2 * k * m * h + k * h + k * m + 2 * k * kh + kh + k
                   + 2 * 80 + 2 * k)
    table = sum(2 * r * b for r, b in zip(distinct_rows, ROW_BYTES))
    byts = (packets * PACKET_FIELD_BYTES + table
            + records * RECORD_OUT_BYTES + lanes * 4 + weights)
    ops = (records * (2 * 2 * k * m * h + 2 * 2 * k * kh)
           + packets * len(ROW_BYTES) * N_DECAY * FC_OPS)
    return {"bytes": float(byts), "ops": float(ops)}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["ops"] / peak["flops_bf16"])


def distinct_rows(slots: np.ndarray) -> list:
    """Distinct slots per key type of one lane's packets ((4, n) slots)."""
    return [int(np.unique(s).size) for s in slots]
