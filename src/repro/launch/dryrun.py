import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count at backend
init, and the production meshes need 512 placeholder host devices.

Per cell this driver:
  1. builds the production mesh (16,16) or (2,16,16),
  2. binds arch/shape-conditional sharding rules (distributed/mesh_rules),
  3. lowers the cell's step function with explicit in/out shardings,
  4. compiles, prints memory_analysis() (proves the memory plan) and
     cost_analysis() (FLOPs/bytes for the roofline),
  5. parses the post-SPMD HLO for collective ops -> collective bytes,
  6. writes everything to benchmarks/results/dryrun/<cell>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # 2x16x16 only
"""
import argparse
import gc
import json
import re
import traceback
from typing import Dict

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, skip_reason, get_arch
from repro.distributed.flags import use_scan_unroll
from repro.distributed.mesh_rules import make_rules
from repro.distributed.params import (batch_specs, cache_specs, opt_specs,
                                      param_specs)
from repro.distributed.sharding import AxisRules, use_rules
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.launch.specs import arch_for_cell, input_specs, train_config_for, use_fsdp

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1,
          "f8e5m2": 1, "s16": 2, "u16": 2}


def _shape_bytes(text: str) -> int:
    """Sum bytes over every shape literal in ``text`` (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind from post-SPMD HLO.

    Result-shape bytes are the per-device payload: for all-reduce this equals
    the operand size; for all-gather it is the post-gather size (an upper
    bound ~n/(n-1) of the wire bytes); '-done' halves of async pairs are
    skipped to avoid double counting.
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, op = m.groups()
        b = _shape_bytes(shape_txt)
        out[op] = out.get(op, 0) + b
        out["total"] = out.get("total", 0) + b
    return out


def _spec_tree_for_cell(kind, cfg, shape, rules, mesh, tc):
    model_size = mesh_shape_dict(mesh).get("model", 1)
    fsdp = 1
    if use_fsdp(cfg):
        fs_axes = rules.rules.get("fsdp")
        if fs_axes:
            md = mesh_shape_dict(mesh)
            fs_axes = (fs_axes,) if isinstance(fs_axes, str) else fs_axes
            fsdp = int(np.prod([md[a] for a in fs_axes]))
    return model_size, fsdp


def _scan_period(cfg) -> int:
    """Layer-pattern period (layers are homogeneous modulo this)."""
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.alt_local_global:
        return 2
    return 1


def _has_layer_scan(cfg) -> bool:
    return cfg.family != "ssm"


def _lower_once(arch: str, shape_name: str, multi_pod: bool, cfg_in,
                unroll: bool, moe_local: bool = False,
                serve_opt: bool = False, fsdp_experts_only: bool = False):
    """Lower + compile one configuration. Returns raw metric dict."""
    import contextlib
    import dataclasses
    shape = SHAPES[shape_name]
    cfg = cfg_in
    mesh = make_production_mesh(multi_pod=multi_pod)
    md = mesh_shape_dict(mesh)
    long_ctx = shape.name == "long_500k"
    rules_dict = make_rules(cfg, shape, multi_pod=multi_pod,
                            model_size=md.get("model", 1),
                            dp_size=int(np.prod([v for k, v in md.items()
                                                 if k != "model"])))
    if fsdp_experts_only:
        rules_dict["fsdp2"] = None    # dense leaves stay TP-resident
    rules = AxisRules(rules_dict)
    model_size, fsdp_size = _spec_tree_for_cell(
        shape.kind, cfg, shape, rules, mesh, None)
    serve_ff_size = 0
    if serve_opt and shape.kind != "train":
        # serving posture: never FSDP-gather weights per step; 2D-shard the
        # expert ffn dim over the DP axes instead (hillclimb: kimi decode)
        fsdp_size = 0
        serve_ff_size = int(np.prod([v for k, v in md.items()
                                     if k != "model"]))

    with use_rules(rules_dict):
        step, args, cfg, tc = input_specs(arch, shape_name, cfg)

        if shape.kind == "train":
            state, batch = args
            pspecs = param_specs(state["params"], cfg, rules, model_size,
                                 fsdp_size)
            ospecs = opt_specs(state["opt"], pspecs, cfg, rules, md, tc.zero1)
            sspecs = {"params": pspecs, "opt": ospecs, "step": P()}
            if "ef_err" in state:
                sspecs["ef_err"] = pspecs
            bspecs = batch_specs(cfg, shape, rules)
            in_shardings = (sspecs, bspecs)
            out_shardings = (sspecs, None)
        elif shape.kind == "prefill":
            params, batch = args
            pspecs = param_specs(params, cfg, rules, model_size, fsdp_size,
                                 serve_ff_size)
            bspecs = batch_specs(cfg, shape, rules)
            in_shardings = (pspecs, bspecs)
            out_shardings = None
        else:  # decode
            params, tokens, cache = args
            pspecs = param_specs(params, cfg, rules, model_size, fsdp_size,
                                 serve_ff_size)
            cspecs = cache_specs(cache, cfg, rules, long_context=long_ctx)
            tspec = rules.spec(("batch", None))
            in_shardings = (pspecs, tspec, cspecs)
            out_shardings = (None, cspecs)

        from repro.distributed import flags as _flags
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        moe_ctx = (_flags.use_local_moe_dispatch(mesh, dp_axes, "model")
                   if moe_local else contextlib.nullcontext())
        with use_scan_unroll(unroll), moe_ctx, jax.set_mesh(mesh):
            jitted = jax.jit(
                step,
                in_shardings=in_shardings,
                out_shardings=out_shardings)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()

    coll = collective_bytes(hlo)
    n_devices = int(np.prod(list(md.values())))
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(f"{k}={v}" for k, v in md.items()),
        "multi_pod": multi_pod,
        "n_devices": n_devices,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "train_posture": {
            "optimizer": tc.optimizer, "param_dtype": tc.param_dtype,
            "remat": tc.remat, "zero1": tc.zero1,
            "fsdp": fsdp_size > 1,
        } if shape.kind == "train" else None,
        "memory_analysis": _mem_dict(mem),
        "arg_bytes_per_device": _arg_bytes(args, in_shardings, md),
        "cost_analysis": {k: float(v) for k, v in (cost or {}).items()
                          if np.isscalar(v) and "{" not in k},
        "collective_bytes": coll,
        "hlo_collective_ops": _coll_counts(hlo),
    }
    del compiled, lowered, jitted
    gc.collect()
    return record


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               moe_local: bool = False, serve_opt: bool = False,
               fsdp_experts_only: bool = False):
    """Lower + compile one cell.

    Primary compile uses the production scan form (memory plan + compile
    proof).  For scan-family archs the per-step cost (FLOPs / bytes /
    collective payloads) is derived from two truncated-depth UNROLLED
    lowerings extrapolated linearly in depth — exact because scan layers are
    homogeneous modulo the layer-pattern period (XLA's HloCostAnalysis counts
    while bodies once, so the scanned numbers under-report by ~n_layers).
    """
    import dataclasses
    shape = SHAPES[shape_name]
    cfg = arch_for_cell(arch, shape)
    record = _lower_once(arch, shape_name, multi_pod, cfg, unroll=False,
                         moe_local=moe_local, serve_opt=serve_opt,
                         fsdp_experts_only=fsdp_experts_only)
    record["cost_lowering"] = "scan_raw"

    if _has_layer_scan(cfg):
        p = _scan_period(cfg)
        L = cfg.n_layers
        if L > 2 * p:
            c1 = _lower_once(arch, shape_name, multi_pod,
                             dataclasses.replace(cfg, n_layers=p),
                             unroll=True, moe_local=moe_local,
                             serve_opt=serve_opt,
                             fsdp_experts_only=fsdp_experts_only)
            c2 = _lower_once(arch, shape_name, multi_pod,
                             dataclasses.replace(cfg, n_layers=2 * p),
                             unroll=True, moe_local=moe_local,
                             serve_opt=serve_opt,
                             fsdp_experts_only=fsdp_experts_only)

            def extrap(a: float, b: float) -> float:
                return max(a + (b - a) * (L - p) / p, b)

            cost = {}
            for k in set(c1["cost_analysis"]) & set(c2["cost_analysis"]):
                cost[k] = extrap(c1["cost_analysis"][k],
                                 c2["cost_analysis"][k])
            coll = {}
            for k in set(c1["collective_bytes"]) | set(c2["collective_bytes"]):
                coll[k] = int(extrap(c1["collective_bytes"].get(k, 0),
                                     c2["collective_bytes"].get(k, 0)))
            ops = {}
            for k in set(c1["hlo_collective_ops"]) | set(c2["hlo_collective_ops"]):
                ops[k] = int(round(extrap(c1["hlo_collective_ops"].get(k, 0),
                                          c2["hlo_collective_ops"].get(k, 0))))
            record["cost_analysis_scanned"] = record["cost_analysis"]
            record["collective_bytes_scanned"] = record["collective_bytes"]
            record["cost_analysis"] = cost
            record["collective_bytes"] = coll
            record["hlo_collective_ops"] = ops
            record["cost_lowering"] = f"unrolled_extrapolated(p={p},L={L})"
        else:
            rec_u = _lower_once(arch, shape_name, multi_pod, cfg, unroll=True,
                                moe_local=moe_local, serve_opt=serve_opt,
                         fsdp_experts_only=fsdp_experts_only)
            record["cost_analysis"] = rec_u["cost_analysis"]
            record["collective_bytes"] = rec_u["collective_bytes"]
            record["hlo_collective_ops"] = rec_u["hlo_collective_ops"]
            record["cost_lowering"] = "unrolled_full"
    else:
        record["cost_lowering"] = "python_unrolled"  # xLSTM: already exact
    return record


def _arg_bytes(args, in_shardings, mesh_dict) -> int:
    """Analytic per-device bytes of all inputs under their PartitionSpecs."""
    total = 0
    flat_a = jax.tree_util.tree_leaves(args)
    flat_s = jax.tree_util.tree_leaves(
        in_shardings, is_leaf=lambda x: isinstance(x, P) or x is None)
    for leaf, spec in zip(flat_a, flat_s):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        denom = 1
        if isinstance(spec, P):
            for d in spec:
                for a in (d if isinstance(d, tuple) else (d,)):
                    if a is not None:
                        denom *= mesh_dict.get(a, 1)
        total += n * leaf.dtype.itemsize // max(denom, 1)
    return total


def _mem_dict(mem) -> Dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(mem)[:2000]
    return out


def _coll_counts(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for op in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute"):
        out[op] = len(re.findall(rf"\b{op}\b", hlo_text))
    return out


def run_cells(archs, shapes, meshes, results_dir: str, force: bool = False):
    os.makedirs(results_dir, exist_ok=True)
    summary = []
    for arch in archs:
        for shape_name in shapes:
            reason = skip_reason(get_arch(arch), SHAPES[shape_name])
            if reason:
                fn = os.path.join(results_dir,
                                  f"{arch}__{shape_name}__skip.json")
                with open(fn, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name,
                               "skipped": reason}, f, indent=1)
                print(f"SKIP  {arch:24s} {shape_name:12s} {reason}")
                continue
            for multi_pod in meshes:
                tag = "multipod" if multi_pod else "singlepod"
                fn = os.path.join(results_dir,
                                  f"{arch}__{shape_name}__{tag}.json")
                if os.path.exists(fn) and not force:
                    print(f"CACHED {arch:24s} {shape_name:12s} {tag}")
                    continue
                try:
                    import time
                    t0 = time.time()
                    rec = lower_cell(arch, shape_name, multi_pod)
                    rec["compile_seconds"] = time.time() - t0
                    with open(fn, "w") as f:
                        json.dump(rec, f, indent=1)
                    mem = rec["memory_analysis"]
                    per_dev = (mem.get("argument_size_in_bytes", 0)
                               + mem.get("temp_size_in_bytes", 0)) / 2**30
                    flops = rec["cost_analysis"].get("flops", 0)
                    print(f"OK    {arch:24s} {shape_name:12s} {tag} "
                          f"mem/dev={per_dev:.2f}GiB flops={flops:.3g} "
                          f"coll={rec['collective_bytes'].get('total', 0)/2**30:.2f}GiB "
                          f"[{rec['compile_seconds']:.0f}s]")
                    summary.append(rec)
                except Exception as e:
                    with open(fn + ".err", "w") as f:
                        f.write(traceback.format_exc())
                    print(f"FAIL  {arch:24s} {shape_name:12s} {tag}: {e}")
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true",
                    help="run only the 2x16x16 mesh")
    ap.add_argument("--single-pod", action="store_true",
                    help="run only the 16x16 mesh")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--results", default=RESULTS_DIR)
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    if args.multi_pod:
        meshes = [True]
    elif args.single_pod:
        meshes = [False]
    else:
        meshes = [False, True]
    run_cells(archs, shapes, meshes, os.path.abspath(args.results),
              force=args.force)


if __name__ == "__main__":
    main()
