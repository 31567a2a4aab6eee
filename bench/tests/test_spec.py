"""Configurations, mixes and metric readers are found by name: a new one
is a new file and an entry, nothing else."""
import json
import shutil

import numpy as np
import pytest

from bench import gen, spec


@pytest.fixture
def tree(tmp_path, monkeypatch):
    for d in ("configs", "traffic", "metrics", "shapes"):
        shutil.copytree(f"{spec.HERE}/{d}", tmp_path / d)
    monkeypatch.setattr(spec, "HERE", str(tmp_path))
    return tmp_path


def test_committed_cells_resolve():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        c = spec.cell(w["name"], bench)
        assert c.config["name"] == w["config"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer
        for m in c.end_to_end + c.per_layer:
            assert callable(spec.reader(m["name"]))


def test_a_new_config_mix_metric_and_cell_are_found(tree):
    cfg = dict(spec.config("link_backbone"), name="link_tiny", n_slots=4096)
    (tree / "configs" / "link_tiny.json").write_text(json.dumps(cfg))
    mix = dict(spec.mix("backbone_zipf"), flows=1024)
    (tree / "traffic" / "backbone_small.json").write_text(json.dumps(mix))
    (tree / "metrics" / "echo.sat.py").write_text(
        "def read(m):\n    return m['x'] * 2\n")
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "link-tiny-sat",
                               "config": "link_tiny",
                               "traffic": "backbone_small", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "echo_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["link-backbone-sat"]})
    bench["per_layer"].append({"name": "echo.sat", "unit": "count",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "echo_rate"})
    c = spec.cell("link-tiny-sat", bench)
    assert c.config["n_slots"] == 4096 and c.mix["flows"] == 1024
    # an end-to-end metric without ``workloads`` is reported in every cell
    assert {m["name"] for m in c.end_to_end} == {"pps", "setup_s"}
    # a per-layer metric without ``workloads`` goes wherever its
    # end-to-end metric is reported
    names = [m["name"] for m in c.per_layer]
    assert "echo.sat" not in names       # the new cell reports no echo_rate
    c2 = spec.cell("link-backbone-sat", bench)
    assert "echo.sat" in [m["name"] for m in c2.per_layer]
    assert spec.reader("echo.sat")({"x": 21}) == 42


def test_a_new_shape_is_found_by_name(tree):
    (tree / "shapes" / "twice.py").write_text(
        "from bench import spec\n\n\n"
        "def pools(mix, n_tenants, rng):\n"
        "    one = spec.shape('backbone')\n"
        "    return [one(mix, 1, rng)[0] for _ in range(n_tenants)]\n")
    mix = dict(spec.mix("backbone_zipf"), shape="twice",
               pool_packets=5000, flows=512)
    a, b = gen.pools(mix, 2, 9)
    assert a.size == b.size == 5000
    assert not np.array_equal(a.fields["src"], b.fields["src"])
    with pytest.raises(ValueError):
        gen.pools(dict(mix, shape="thrice"), 2, 9)


def _cell_on(tree, chips, **over):
    cfg = dict(spec.config("link_backbone"), name="link_wide", **over)
    (tree / "configs" / "link_wide.json").write_text(json.dumps(cfg))
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "link-wide", "config": "link_wide",
                               "traffic": "backbone_zipf", "chips": chips,
                               "why": "test"})
    return spec.cell("link-wide", bench)


def test_a_cell_on_several_chips_needs_a_placement(tree):
    with pytest.raises(ValueError, match="placement"):
        _cell_on(tree, 4)
    with pytest.raises(ValueError, match="placement"):
        _cell_on(tree, 4, placement={})
    with pytest.raises(ValueError, match="mesh axis"):
        _cell_on(tree, 4, placement={"tenants": "model"})
    c = _cell_on(tree, 4, placement={"tenants": spec.MESH_AXIS})
    assert c.chips == 4 and c.config["placement"] == {"tenants": "data"}
    assert _cell_on(tree, 1).chips == 1       # one chip places nothing


def test_unknown_cell_raises():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")


def test_every_mix_key_has_a_source_or_is_a_cut():
    bench = spec.load_benchmark()
    for mix_name in {w["traffic"] for w in bench["workloads"]}:
        m = spec.mix(mix_name)
        params = set(m) - {"shape", "about", "source", "sources", "cuts"}
        assert params == set(m["sources"]) | set(m["cuts"]), mix_name
        assert not set(m["sources"]) & set(m["cuts"])


def test_reduced_keys_are_the_configs_own_and_explained():
    for c in spec.load_benchmark()["configs"]:
        cfg = spec.config(c["name"])
        assert c["reduced"] == cfg["reduced"]
        assert set(cfg["reduced"]) <= set(cfg) & set(cfg["assumed"])
