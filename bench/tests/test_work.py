"""The counted-work function against a hand count, and the peak table."""
import numpy as np
import pytest

from bench import device, work


def test_step_work_hand_count():
    kn = work.kitnet_shapes([10, 10, 10, 9, 9, 8, 8, 7, 5, 4], 0.75)
    assert kn == {"k": 10, "m": 10, "h": 8, "kh": 8}
    w = work.step_work(packets=1024, distinct_rows=[100, 100, 200, 300],
                       records=1, lanes=1, kn=kn)
    weights = 4 * (2 * 10 * 10 * 8 + 10 * 8 + 10 * 10 + 2 * 10 * 8 + 8 + 10
                   + 160 + 20)
    table = 2 * (100 * 68 + 100 * 68 + 200 * 196 + 300 * 196)
    assert w["bytes"] == 1024 * 28 + table + 9 + 4 + weights
    assert w["ops"] == (4 * 10 * 10 * 8 + 4 * 10 * 8) + 1024 * 4 * 4 * 12


def test_least_seconds_takes_the_binding_bound():
    peak = {"hbm_bytes_per_s": 1e9, "flops_bf16": 1e12}
    assert work.least_seconds({"bytes": 2e9, "ops": 1e12}, peak) == 2.0
    assert work.least_seconds({"bytes": 1e6, "ops": 3e12}, peak) == 3.0


def test_distinct_rows():
    s = np.array([[1, 1, 2], [0, 0, 0], [5, 6, 7], [9, 9, 8]])
    assert work.distinct_rows(s) == [2, 1, 3, 2]


def test_peaks_table_has_v5e_and_refuses_unknown_kinds():
    p = device.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["ops_int8"] == 393e12 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("source")


def test_no_tpu_is_refused():
    pytest.importorskip("jax")
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(device.NoChip):
        device.chips(1)
