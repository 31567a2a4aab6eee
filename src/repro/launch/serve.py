"""Serving launcher: either the Peregrine detection service over a synthetic
packet stream, or LM serving with batched requests.

  PYTHONPATH=src python -m repro.launch.serve --mode detect --attack mirai
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch gemma2-2b
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced as reduce_cfg
from repro.launch.cache import enable_compile_cache
from repro.models import build_model


def _device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind}"


def serve_detect(args):
    from repro.detection.metrics import auc
    from repro.serving import DetectionService
    from repro.traffic import synth_trace

    data = synth_trace(args.attack, n_train=args.n_train,
                       n_benign_eval=args.n_eval // 2,
                       n_attack=args.n_eval // 2, seed=0)
    svc = DetectionService(epoch=args.epoch, mode=args.fc_mode)
    t0 = time.time()
    svc.observe_stream(data["train"], chunk=8192)
    svc.fit(fpr=0.01)
    print(f"trained on {svc.pkt_count} pkts in {time.time() - t0:.1f}s; "
          f"threshold={svc.threshold:.4f}")
    t0 = time.time()
    # record indices are global stream positions; the eval window starts at
    # the current packet count
    eval_start = svc.pkt_count
    idx, scores, alarms = svc.process_stream(data["eval"], chunk=8192)
    dt = time.time() - t0
    labels = data["eval"]["label"][idx - eval_start]
    n = len(data["eval"]["ts"])
    print(f"processed {n} pkts in {dt:.1f}s ({n / dt:.0f} pps on "
          f"{_device()}), "
          f"{len(scores)} records, {int(alarms.sum())} alarms, "
          f"AUC={auc(scores, labels):.3f}")


def serve_lm(args):
    from repro.models.lm_engine import Request, ServeEngine

    cfg = reduce_cfg(get_arch(args.arch)) if args.reduced else get_arch(args.arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_slots=args.slots, max_seq=256)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = jnp.asarray(rng.integers(1, cfg.vocab, size=16), jnp.int32)
        eng.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))
    t0 = time.time()
    outputs = eng.run()
    dt = time.time() - t0
    toks = sum(len(v) for v in outputs.values())
    print(f"served {len(outputs)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s on {_device()})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("detect", "lm"), default="detect")
    ap.add_argument("--attack", default="mirai")
    ap.add_argument("--epoch", type=int, default=1024)
    ap.add_argument("--fc-mode", default="exact", choices=("exact", "switch"))
    ap.add_argument("--n-train", type=int, default=20000)
    ap.add_argument("--n-eval", type=int, default=20000)
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "detect":
        serve_detect(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
