"""Table 3 analog: data-plane resource usage of the feature pipeline.

On Tofino the budget is stages/SRAM/TCAM/meter-ALUs; on TPU the analogous
budget is VMEM residency of the flow tables, the per-packet state touched,
and kernel grid occupancy.  Reported per slot-count so an operator can size
the tables exactly as §3.3's "Configuration" describes.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import save
from repro.core import init_state, N_FEATURES
from repro.core.state import LAMBDAS, N_BI, N_DECAY, N_UNI


def state_bytes(n_slots: int) -> dict:
    st = init_state(n_slots)
    total = sum(np.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(st))
    uni = sum(np.prod(l.shape) * l.dtype.itemsize
              for l in jax.tree_util.tree_leaves(st["uni"]))
    return {"n_slots": n_slots, "total_bytes": int(total),
            "uni_bytes": int(uni), "bi_bytes": int(total - uni)}


def main():
    rows = [state_bytes(n) for n in (4096, 8192, 65536, 1 << 20)]
    for r in rows:
        print(f"slots={r['n_slots']:8d}  state={r['total_bytes'] / 2**20:9.2f} MiB "
              f"(uni {r['uni_bytes'] / 2**20:7.2f} / bi {r['bi_bytes'] / 2**20:8.2f})")
    kernel = {
        "feature_update_vmem_per_keytype_bytes": int(8192 * N_DECAY * 4 * 4),
        "decay_instances": N_DECAY,
        "key_types": N_UNI + N_BI,
        "features_per_packet": N_FEATURES,
        "lambdas": list(LAMBDAS),
        "note": "the Pallas FC kernel holds its lane-packed tables "
                "resident, 512 B per slot (4 MiB at 8192 slots), and "
                "requests 2x that plus its statistics blocks plus 4 MiB "
                "of VMEM (13 MiB at 8192 slots; see DESIGN.md §2); "
                "Tofino comparison: the paper uses 100% of TNA pipe-0 "
                "stages and 37% SRAM (Table 3)",
    }
    print("feature_update VMEM @8192 slots/key:",
          kernel["feature_update_vmem_per_keytype_bytes"] / 2**20, "MiB")
    print("features/packet:", kernel["features_per_packet"])
    save("resource_usage", {"state": rows, "kernel": kernel})


if __name__ == "__main__":
    main()
