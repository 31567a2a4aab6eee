"""A deployment enters the benchmark as new files and entries alone: in a
copy of the benchmark, a four-tenant shape, a configuration placed over
the mesh, a mix, a cell on four chips and a reader over every op's time,
run whole through ``harness.run`` on four forced host devices."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

SEED = 2**31 + 4242
CELL = "link-four-mesh4"

SHAPE = '''"""backbone4: four streams, each drawn as one backbone link."""
from bench import spec


def pools(mix, n_tenants, rng):
    one = spec.shape("backbone")
    return [one(mix, 1, rng)[0] for _ in range(n_tenants)]
'''

READER = '''"""top_op_share.mesh4: the costliest op's share of device busy time."""


def read(m):
    tr = m.get("trace")
    if tr is None or not tr.op_s or tr.busy_s <= 0:
        return None
    return 100.0 * max(tr.op_s.values()) / tr.busy_s
'''


def _checkout(root):
    """A copy of the benchmark with the new cell's files and entries."""
    b = root / "bench"
    shutil.copytree(spec.HERE, b, ignore=shutil.ignore_patterns(
        "__pycache__", ".build"))
    (b / "shapes" / "backbone4.py").write_text(SHAPE)
    (b / "metrics" / "top_op_share.mesh4.py").write_text(READER)
    cfg = dict(spec.config("link_backbone"), name="link_four", tenants=4,
               max_batch=4, placement={"tenants": spec.MESH_AXIS},
               n_slots=2048, queue_depth=4, epoch=256, chunk=2048,
               fit_records=64)
    (b / "configs" / "link_four.json").write_text(json.dumps(cfg))
    mix = dict(spec.mix("backbone_zipf"), shape="backbone4",
               pool_packets=20000, flows=4096, src_hosts=2048, dst_hosts=512)
    (b / "traffic" / "backbone4_small.json").write_text(json.dumps(mix))
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "link_four", "source": "test",
                             "file": "bench/configs/link_four.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "link_four",
                               "traffic": "backbone4_small", "chips": 4,
                               "why": "test"})
    bench["per_layer"].append({"name": "top_op_share.mesh4", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "pps",
                               "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return b / "tests" / "cell_by_files.py"


def test_a_four_chip_cell_added_by_files_runs(tmp_path):
    worker = _checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(spec.CHECKOUT, "src"))
    p = subprocess.run([sys.executable, str(worker), CELL, str(SEED), "2"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["info"]["compiles_in_window"] == 0
    assert out["info"]["records_compared"] > 0
    # every fused step's scores lie on the four devices of the mesh
    assert out["score_devices"] == [4]
    assert 0 < out["metrics"]["top_op_share.mesh4"]["value"] <= 100
    # the idle plane of a device the cell did not run on is left out
    per = out["info"]["busy_s_per_device"]
    assert len(per) == 4 and min(per) > 0
    assert out["device"]["busy_s"] == pytest.approx(sum(per) / 4)
