"""fused_step_mfu.sat: the whole fused step's share of the chip's peak:
the least time of the counted work of the traced batches (work.py: the
larger of bytes / HBM bandwidth and operations / peak rate) over the
device busy time they took, in %."""


def read(m):
    tr, least = m.get("trace"), m.get("least_s_traced")
    if tr is None or not least or tr.busy_s <= 0:
        return None
    return 100.0 * least / tr.busy_s
