"""``correct`` from a whole run at a small size on the CPU: sound runs pass,
and a run with the timed path broken underneath, with its alarm threshold
moved, or with the bfloat16 control in the program's place, fails.  The
chip look is stood in for (``helpers.on_cpu``); everything else is the run
``run.py`` makes."""
import os
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from bench import harness, spec  # noqa: E402
from bench.proof import threshold_scaled  # noqa: E402
from bench.tests.helpers import on_cpu, small_cell  # noqa: E402

engine_mod = pytest.importorskip("repro.serving.engine")
SEED = 2**31 + 101


def _run(monkeypatch, seconds=1.5):
    on_cpu(monkeypatch)
    return harness.run("link-backbone-sat", SEED, seconds, False,
                       time.perf_counter(), cell=small_cell())


def test_sound_run_is_correct(monkeypatch):
    out = _run(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["checks"]["records_missing"]["value"] == 0
    assert out["checks"]["alarm_margin"]["value"] == 0.0
    assert set(out["metrics"]) == {m["name"] for m in
                                   small_cell().end_to_end}
    assert list(out)[-1] == "checks"


def _state_unchanged(step):
    def f(pool, ids, net, thr, base, pk):
        out = step(jax.tree_util.tree_map(jnp.copy, pool), ids, net, thr,
                   base, pk)
        return (pool,) + tuple(out[1:])
    return f


def _half_batch(step):
    def f(pool, ids, net, thr, base, pk):
        n = pk["ts"].shape[1]
        return step(pool, ids, net, thr, base,
                    {k: v[:, : n // 2] for k, v in pk.items()})
    return f


def _answer_altered(step):
    def f(pool, ids, net, thr, base, pk):
        out = list(step(pool, ids, net, thr, base, pk))
        out[2] = out[2].at[:, 0].multiply(2.0)
        return tuple(out)
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    orig = engine_mod.DetectionEngine._tenant_step
    monkeypatch.setattr(engine_mod.DetectionEngine, "_tenant_step",
                        lambda self: fault(orig(self)))
    out = _run(monkeypatch)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("scale", [1.2, 0.8])
def test_moved_threshold_is_not_correct(monkeypatch, scale):
    with threshold_scaled(scale):
        out = _run(monkeypatch)
    assert not out["correct"], out["checks"]
    assert out["checks"]["alarm_margin"]["value"] > \
        out["checks"]["alarm_margin"]["limit"]
    # the scores are untouched: only the alarms catch it
    assert out["checks"]["score_gap"]["value"] <= \
        out["checks"]["score_gap"]["limit"]


def test_bf16_control_is_not_correct():
    cell = small_cell()
    r = harness.Run(cell, SEED, False)
    r.setup()
    r.window(3.0)
    r.collect(jax.devices()[:1])
    assert harness.judge(r.check(), cell.config)[0]
    assert not harness.judge(r.check(prec="bf16"), cell.config)[0]


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    root = os.path.dirname(spec.HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                        "--workload", "link-backbone-sat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
