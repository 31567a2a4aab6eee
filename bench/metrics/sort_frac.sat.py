"""sort_frac.sat: share of device busy time in HLO sort ops, in %."""


def read(m):
    tr = m.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * tr.sort_s / tr.busy_s
