"""§Perf cell-A optimization: shard_map local MoE dispatch must match the
dense global-view dispatch exactly (forward) and in gradients, on a real
(2,4) host-device mesh."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_local_dispatch_matches_dense():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_arch, reduced
        from repro.models import moe as moe_mod
        from repro.distributed import flags
        from repro.distributed.sharding import use_rules

        cfg = dataclasses.replace(
            reduced(get_arch("kimi-k2-1t-a32b")),
            n_experts=8, top_k=2, capacity_factor=8.0, n_shared_experts=1)
        key = jax.random.PRNGKey(0)
        p = moe_mod.moe_init(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.5
        y_ref, _ = moe_mod.moe_ffn(p, x, cfg)

        def loss(pp, xx):
            y, aux = moe_mod.moe_ffn(pp, xx, cfg)
            return jnp.sum(y ** 2) + 0.01 * aux
        g_ref = jax.grad(loss)(p, x)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rules = {"batch": ("data",), "experts": "model",
                 "expert_cap": ("data",), "ff": None, "fsdp": None}
        pspec = {"router": P(), "wi": P("model", None, None),
                 "wg": P("model", None, None), "wo": P("model", None, None),
                 "shared": {"wi": P(), "wg": P(), "wo": P()}}
        with use_rules(rules), \\
             flags.use_local_moe_dispatch(mesh, ("data",), "model"), \\
             jax.set_mesh(mesh):
            p_sh = jax.tree_util.tree_map(
                lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
                p, pspec)
            x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            y_loc, _ = jax.jit(lambda a, b: moe_mod.moe_ffn(a, b, cfg))(p_sh, x_sh)
            g_loc = jax.jit(jax.grad(loss))(p_sh, x_sh)
        ferr = float(jnp.max(jnp.abs(y_loc - y_ref)))
        gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g_loc), jax.tree_util.tree_leaves(g_ref)))
        print(json.dumps({"ferr": ferr, "gerr": gerr}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ferr"] < 1e-4, res
    assert res["gerr"] < 1e-3, res
