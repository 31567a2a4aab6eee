"""Worker for ``test_cell_by_files.py``: one traced run of a cell on four
forced host devices.

    python <checkout>/bench/tests/cell_by_files.py <cell> <seed> <seconds>

The benchmark is imported from the checkout this file lies in, so a copy of
it that holds a new cell's files runs that cell as ``run.py`` would.  The
forced topology is fixed when JAX starts, hence a process of its own.  The
chip look and the device planes a CPU profile lacks are stood in for
(``helpers.on_cpu``, ``helpers.device_planes``); every fused step's scores
report the devices they lie on.  The last line of standard output is the
run's result object with ``score_devices`` (the device counts seen) added.
"""
import json
import os
import re
import sys
import time

N_DEVICES = 4
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEVICES} " + flags)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)


def main(cell: str, seed: int, seconds: float) -> int:
    import pytest

    from bench import harness
    from bench.tests.helpers import device_planes, on_cpu
    from repro.serving.engine import DetectionEngine

    mp = pytest.MonkeyPatch()
    on_cpu(mp)
    device_planes(mp)
    seen = set()
    tenant_step = DetectionEngine._tenant_step

    def reporting(self):
        step = tenant_step(self)

        def f(*args):
            out = step(*args)
            seen.add(len(out[2].sharding.device_set))
            return out
        return f

    mp.setattr(DetectionEngine, "_tenant_step", reporting)
    out = harness.run(cell, seed, seconds, True, time.perf_counter())
    out["score_devices"] = sorted(seen)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3])))
