"""device_idle_frac.sat: share of the traced slice in which no operation
ran on the device (1 - union of the XLA op intervals / slice), in %."""


def read(m):
    tr = m.get("trace")
    return None if tr is None else 100.0 * (1.0 - tr.busy_s / tr.window_s)
