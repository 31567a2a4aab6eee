"""step_device_ms.sat: device busy time in the traced slice over the fused
batches ``step()`` reported it dispatched there."""


def read(m):
    tr, n = m.get("trace"), m.get("batches_traced")
    return None if tr is None or not n else 1e3 * tr.busy_s / n
