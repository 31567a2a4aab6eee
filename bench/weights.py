"""KitNET weights for a run, made by the benchmark from the seed.

The autoencoders' weights are drawn on the device in one jitted call, in
float32, the type they are served in.  The feature groups are a seeded
partition of the 80 features into groups of the configured sizes, so the
shapes are the same for every seed.  The input and output normalisers and
the alarm threshold are fit by the plain reference on the first records of
the stream, as Kitsune fits them on its benign prefix; the program never
trains.  Every number the program is given is float32, and the reference
uses the same numbers in float64.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

from bench import reference, work


@dataclass
class Net:
    host: Dict[str, np.ndarray]      # float64 copies of the served values
    threshold: float                 # float32 value


def _key_data(seed: int) -> np.ndarray:
    return np.random.SeedSequence([seed, 0xAE]).generate_state(2, np.uint32)


def make(cfg: Dict, seed: int, prefix_feats: np.ndarray) -> Net:
    import jax
    import jax.numpy as jnp

    kn_cfg = cfg["kitnet"]
    sizes = list(kn_cfg["ensemble_sizes"])
    if sum(sizes) != reference.N_FEATURES:
        raise ValueError("ensemble_sizes must partition the 80 features")
    kn = work.kitnet_shapes(sizes, float(kn_cfg["hidden_ratio"]))
    k, m, h, kh = kn["k"], kn["m"], kn["h"], kn["kh"]

    @jax.jit
    def draw(kd):
        ks = jax.random.split(jax.random.wrap_key_data(kd), 9)
        nrm = lambda i, shape, s: jax.random.normal(ks[i], shape, jnp.float32) * s
        return {
            "perm": jax.random.permutation(ks[0], reference.N_FEATURES),
            "W1": nrm(1, (k, m, h), 1.0 / np.sqrt(m)),
            "b1": nrm(2, (k, h), 0.1),
            "W2": nrm(3, (k, h, m), 1.0 / np.sqrt(h)),
            "b2": nrm(4, (k, m), 0.1),
            "V1": nrm(5, (k, kh), 1.0 / np.sqrt(k)),
            "c1": nrm(6, (kh,), 0.1),
            "V2": nrm(7, (kh, k), 1.0 / np.sqrt(kh)),
            "c2": nrm(8, (k,), 0.1),
        }

    drawn = {n: np.asarray(v) for n, v in
             draw(jnp.asarray(_key_data(seed))).items()}
    perm = drawn.pop("perm")
    idx = np.zeros((k, m), np.int32)
    mask = np.zeros((k, m), np.float32)
    at = 0
    for i, s in enumerate(sizes):
        idx[i, :s] = np.sort(perm[at:at + s])
        mask[i, :s] = 1.0
        at += s
    f32 = lambda x: np.asarray(x, np.float32).astype(np.float64)
    host = {n: v.astype(np.float64) for n, v in drawn.items()}
    host.update(idx=idx, mask=mask.astype(np.float64),
                norm_min=f32(prefix_feats.min(0)),
                norm_max=f32(prefix_feats.max(0)))
    r = reference.ensemble_rmse(host, prefix_feats)
    host.update(out_min=f32(r.min(0)), out_max=f32(r.max(0)))
    scores = reference.kitnet_scores(host, prefix_feats)
    thr = float(np.float32(np.quantile(scores, 1.0 - float(kn_cfg["fpr"]))))
    return Net(host=host, threshold=thr)


def program_net(net: Net):
    """The same weights as the program's KitNET pytree (float32 on device)."""
    import jax.numpy as jnp
    from repro.detection.kitnet import KitNet
    h = net.host
    f = lambda x: jnp.asarray(np.asarray(x, np.float32))
    params = {n: f(h[n]) for n in ("W1", "b1", "W2", "b2", "V1", "c1",
                                   "V2", "c2")}
    return KitNet(idx=jnp.asarray(h["idx"]), mask=f(h["mask"]),
                  params=params, norm_min=f(h["norm_min"]),
                  norm_max=f(h["norm_max"]), out_min=f(h["out_min"]),
                  out_max=f(h["out_max"]))
