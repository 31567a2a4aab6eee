"""Distribution tests on an 8-host-device mesh (subprocess: the main test
process must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """A (2,4) mesh train step produces the same loss as single-device."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs import TrainConfig, get_arch, reduced
        from repro.data import lm_batches
        from repro.models import build_model
        from repro.training import init_train_state, make_train_step
        from repro.distributed.mesh_rules import make_rules
        from repro.distributed.sharding import (use_rules, AxisRules,
                                                auto_mesh)
        from repro.distributed.params import param_specs, opt_specs, batch_specs
        from repro.configs.base import ShapeConfig

        cfg = reduced(get_arch("deepseek-7b"), n_kv_heads=4)
        m = build_model(cfg)
        tc = TrainConfig()
        b = next(iter(lm_batches(cfg.vocab, 8, 16, 1, seed=5)))
        batch = {k: jnp.asarray(v) for k, v in b.items()}

        # single device reference
        state = init_train_state(m, tc, jax.random.PRNGKey(0))
        _, met0 = jax.jit(make_train_step(m, tc))(state, batch)
        ref = float(met0["loss"])

        mesh = auto_mesh((2, 4), ("data", "model"))
        shp = ShapeConfig("t", 16, 8, "train")
        rules_d = make_rules(cfg, shp, multi_pod=False, model_size=4, dp_size=2)
        rules = AxisRules(rules_d)
        with use_rules(rules_d):
            state = init_train_state(m, tc, jax.random.PRNGKey(0))
            ps = param_specs(state["params"], cfg, rules, 4)
            os_ = opt_specs(state["opt"], ps, cfg, rules,
                            {"data": 2, "model": 4}, True)
            ss = {"params": ps, "opt": os_, "step": P()}
            bs = batch_specs(cfg, shp, rules)
            with jax.set_mesh(mesh):
                step = jax.jit(make_train_step(m, tc),
                               in_shardings=(ss, bs),
                               out_shardings=(ss, None))
                new_state, met = step(state, batch)
                loss = float(met["loss"])
        print(json.dumps({"ref": ref, "sharded": loss}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    # fp32 reduction order differs across the (2,4) partition; loss ~ O(7)
    assert abs(res["ref"] - res["sharded"]) < 1e-3 * max(1.0, res["ref"]), res


def test_seq_parallel_decode_matches_dense():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, json, math
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.distributed.seq_parallel import make_seq_parallel_decode
        from repro.models.attention import decode_attention
        from repro.configs import get_arch, reduced

        cfg = reduced(get_arch("deepseek-7b"))
        mesh = jax.make_mesh((8,), ("data",))
        B, H, K, S, D = 2, 4, 2, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kc = jax.random.normal(ks[1], (B, S, K, D))
        vc = jax.random.normal(ks[2], (B, S, K, D))
        cache_len = jnp.asarray([40, 64])

        want = decode_attention(q, kc, vc, cfg, cache_len, window=0)

        kv_spec = P(None, "data", None, None)
        q_spec = P(None, None, None, None)
        fn = make_seq_parallel_decode(mesh, ("data",), kv_spec, q_spec)
        with jax.set_mesh(mesh):
            kc_s = jax.device_put(kc, NamedSharding(mesh, kv_spec))
            vc_s = jax.device_put(vc, NamedSharding(mesh, kv_spec))
            got = fn(q, kc_s, vc_s, cache_len)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                    want.astype(jnp.float32))))
        print(json.dumps({"err": err}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["err"] < 1e-4, res


def test_elastic_checkpoint_remesh(tmp_path):
    """Save on a (4,2) mesh, restore on (2,2) with 4 devices — values equal."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.training.checkpoint import CheckpointManager

        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                 "b": jnp.ones((8,))}}
        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        sh8 = {{"w": NamedSharding(mesh8, P("data", "model")),
                "b": NamedSharding(mesh8, P("model"))}}
        tree8 = jax.tree_util.tree_map(jax.device_put, tree, sh8)
        mgr = CheckpointManager({str(tmp_path)!r})
        mgr.save(1, tree8)

        mesh4 = jax.make_mesh((2, 2), ("data", "model"))
        sh4 = {{"w": NamedSharding(mesh4, P("model", "data")),
                "b": NamedSharding(mesh4, P(None))}}
        restored, _ = mgr.restore(jax.eval_shape(lambda: tree), shardings=sh4)
        ok = bool(jnp.all(restored["w"] == tree["w"])) and \
             bool(jnp.all(restored["b"] == tree["b"]))
        print(json.dumps({{"ok": ok,
                           "shard": str(restored["w"].sharding.spec)}}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"], res


def test_dryrun_cell_compiles_on_small_mesh():
    """End-to-end lower+compile of a reduced arch on an 8-device mesh using
    the same machinery as the 512-device dry-run."""
    out = _run("""
        import jax, json
        from repro.launch.dryrun import collective_bytes
        hlo_sample = (
          "  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups={}\\n"
          "  %ag = (bf16[4,8], bf16[4,8]) all-gather(%y, %z), dim=0\\n"
          "  %d = f32[2] all-to-all-done(%s)\\n")
        print(json.dumps(collective_bytes(hlo_sample)))
    """, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["all-reduce"] == 16 * 128 * 4
    assert res["all-gather"] == 2 * 4 * 8 * 2
