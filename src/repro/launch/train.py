"""Training launcher.

Single-host execution path of the same code the 512-chip dry-run lowers:
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --steps 50 \\
      --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

With --mesh data,model=AxB (and XLA_FLAGS host devices) it runs SPMD on a
host mesh; on real hardware the same flags drive the pod slice.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import TrainConfig, get_arch, reduced as reduce_cfg
from repro.configs.base import ShapeConfig
from repro.data import Prefetcher, lm_batches
from repro.distributed.mesh_rules import make_rules
from repro.distributed.params import batch_specs, opt_specs, param_specs
from repro.distributed.sharding import AxisRules, auto_mesh, use_rules
from repro.models import build_model
from repro.training import CheckpointManager, init_train_state, make_train_step
from repro.training.fault import StragglerMonitor, resilient_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    tc = TrainConfig(learning_rate=args.lr, remat=args.remat,
                     microbatches=args.microbatches,
                     warmup_steps=max(args.steps // 10, 1))

    mesh = None
    rules_d = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = auto_mesh((d, m), ("data", "model"))
        shp = ShapeConfig("cli", args.seq, args.batch, "train")
        rules_d = make_rules(cfg, shp, multi_pod=False, model_size=m,
                             dp_size=d)

    def run():
        state = init_train_state(model, tc, jax.random.PRNGKey(tc.seed))
        step_fn = make_train_step(model, tc)
        if mesh is not None:
            rules = AxisRules(rules_d)
            ps = param_specs(state["params"], cfg, rules,
                             mesh.devices.shape[1])
            os_ = opt_specs(state["opt"], ps, cfg, rules,
                            dict(zip(mesh.axis_names, mesh.devices.shape)),
                            tc.zero1)
            ss = {"params": ps, "opt": os_, "step": P()}
            bs = batch_specs(cfg, ShapeConfig("cli", args.seq, args.batch,
                                              "train"), rules)
            step_fn = jax.jit(
                step_fn,
                in_shardings=(ss, bs),
                out_shardings=(ss, None))
        else:
            step_fn = jax.jit(step_fn)

        batches = [
            {k: jnp.asarray(v) for k, v in b.items()}
            for b in Prefetcher(lm_batches(cfg.vocab, args.batch, args.seq,
                                           args.steps, seed=tc.seed))]
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        mon = StragglerMonitor()
        t0 = time.time()
        out = resilient_loop(step_fn, state, batches, ckpt,
                             ckpt_every=args.ckpt_every, monitor=mon)
        dt = time.time() - t0
        toks = args.steps * args.batch * args.seq
        print(f"steps={out['completed']} restarts={out['restarts']} "
              f"stragglers={len(mon.stragglers)} "
              f"loss={float(out['metrics']['loss']):.4f} "
              f"tokens/s={toks / dt:.0f}")

    if mesh is not None:
        with use_rules(rules_d), jax.set_mesh(mesh):
            run()
    else:
        run()


if __name__ == "__main__":
    main()
