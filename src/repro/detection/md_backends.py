"""MD (scoring) backend registry — the detection-side twin of the FC registry.

Peregrine's division of labour (Fig. 3) makes feature computation swappable
behind ``repro.core.backends.compute_features``; this module does the same
for the *MD stage* (§3.4 KitNET): the service never cares how the ensemble
reconstruction RMSEs were produced.

    scores = score_records(net, feats, backend="pallas")

Backends (all emit identical per-record anomaly scores, ≤1e-5 apart):

  * ``einsum`` — the batched-einsum path (detection/kitnet.py): every
    ensemble AE runs inside ONE padded einsum, whole scoring path under a
    single ``jax.jit``.  The default, and the training-time reference.
  * ``pallas`` — the fused ensemble kernel (kernels/kitnet_ae.py):
    gather + normalise on the host graph, then one ``pallas_call`` grid of
    (AE, batch-tile) steps — two MXU matmuls + sigmoids + masked RMSE per
    step, the reconstruction never materialised in HBM.  Interpreted when
    lowered for the CPU, compiled on TPU (``repro.kernels.run_pallas``;
    ``interpret=`` forces either — same plumbing as the FC kernels).

Each registered backend supplies the *ensemble* stage
``fn(params, idx, mask, xn) -> (B, k) RMSE`` plus a full scoring function;
``ensemble_rmse_records`` exposes the former so ``train_kitnet`` can run its
training-set RMSE pass (output-AE normalisation + training data) through the
same backend it will score with.  Design rationale: DESIGN.md §3.

``register_md_backend`` is the extension point (e.g. a quantised or
distilled scorer).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class _MDBackend(NamedTuple):
    score: Callable      # fn(net, X (B,F) jnp) -> (B,) scores
    ensemble: Callable   # fn(params, idx, mask, xn (B,F)) -> (B,k) RMSE
    options: frozenset   # kwarg names the backend accepts


_REGISTRY: Dict[str, _MDBackend] = {}

# legacy / convenience spellings
_ALIASES = {"batched": "einsum", "kernel": "pallas", "fused": "pallas"}


def register_md_backend(name: str, *, score: Callable, ensemble: Callable,
                        options: Tuple[str, ...] = ()):
    """Register an MD backend: a full scoring fn + its ensemble stage.

    ``options`` names the keyword options the backend accepts; anything
    else passed via ``md_kw``/``**kw`` raises instead of being silently
    swallowed (a misspelled tuning flag must not measure the default).
    """
    _REGISTRY[name] = _MDBackend(score=score, ensemble=ensemble,
                                 options=frozenset(options))


def validate_md_options(backend: str, kw: Dict) -> str:
    """Resolve ``backend`` and reject options it does not accept."""
    name = resolve_md_backend(backend)
    unknown = set(kw) - _REGISTRY[name].options
    if unknown:
        raise TypeError(
            f"MD backend {name!r} got unexpected options {sorted(unknown)}; "
            f"accepted: {sorted(_REGISTRY[name].options)}")
    return name


def available_md_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_md_backend(name: str) -> str:
    """Canonical MD backend name (alias-aware); raises on unknown names."""
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(f"unknown MD backend {name!r}; "
                         f"available: {available_md_backends()}")
    return name


def default_md_backend() -> str:
    return "einsum"


# ---------------------------------------------------------------------------
# einsum — the batched reference path (one jit over the whole score)
# ---------------------------------------------------------------------------
def _score_einsum(net, X, **_kw):
    from repro.detection.kitnet import _score
    return _score(net.params, net.idx, net.mask, net.norm_min, net.norm_max,
                  net.out_min, net.out_max, X)


def _ensemble_einsum(params, idx, mask, xn, **_kw):
    from repro.detection.kitnet import ensemble_rmse
    return ensemble_rmse(params, idx, mask, xn)


# ---------------------------------------------------------------------------
# pallas — fused ensemble kernel (kernels/kitnet_ae.kitnet_ensemble)
# ---------------------------------------------------------------------------
def _ensemble_pallas(params, idx, mask, xn, *, bb: int = 128, interpret=None,
                     **_kw):
    from repro.kernels import ops
    sub = xn[:, idx]                                   # (B, k, m) gather
    return ops.kitnet_ensemble(sub, params["W1"], params["b1"],
                               params["W2"], params["b2"], mask,
                               bb=bb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def _score_pallas_jit(params, idx, mask, lo, hi, r_lo, r_hi, X, *,
                      bb: int, interpret):
    from repro.detection.kitnet import _normalize, output_rmse
    from repro.kernels.kitnet_ae import kitnet_ensemble
    xn = _normalize(X, lo, hi)
    sub = xn[:, idx]                                   # (B, k, m) gather
    r = kitnet_ensemble(sub, params["W1"], params["b1"],
                        params["W2"], params["b2"], mask,
                        bb=bb, interpret=interpret)
    rn = _normalize(r, r_lo, r_hi)
    return output_rmse(params, rn)


def _score_pallas(net, X, *, bb: int = 128, interpret=None, **_kw):
    # one jit over the whole scoring path (like the einsum _score)
    return _score_pallas_jit(net.params, net.idx, net.mask, net.norm_min,
                             net.norm_max, net.out_min, net.out_max, X,
                             bb=bb, interpret=interpret)


register_md_backend("einsum", score=_score_einsum, ensemble=_ensemble_einsum)
register_md_backend("pallas", score=_score_pallas, ensemble=_ensemble_pallas,
                    options=("bb", "interpret"))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def score_records(net, feats: np.ndarray, backend: str = "einsum",
                  **kw) -> np.ndarray:
    """Anomaly RMSE per feature record through the selected MD backend.

    ``net`` is a fitted :class:`~repro.detection.kitnet.KitNet`; ``feats``
    is the (B, F) record matrix.  Extra kwargs go to the backend (e.g.
    ``bb=``/``interpret=`` for pallas).  Per-record scores are independent
    of the batch they arrive in, so chunked streaming scoring is
    bit-identical to one-batch scoring for every backend.
    """
    name = validate_md_options(backend, kw)
    X = jnp.asarray(feats, jnp.float32)
    return np.asarray(_REGISTRY[name].score(net, X, **kw))


def md_score_fn(backend: str = "einsum", **kw) -> Callable:
    """The selected backend's *traceable* scoring callable ``fn(net, X)``.

    ``score_records`` wraps the result in host arrays; this accessor hands
    out the raw jax-level function instead so a caller can inline the MD
    stage into a larger jit (the fused serving step) — ``net`` is a
    :class:`~repro.detection.kitnet.KitNet` pytree, ``X`` a (B, F) jnp
    array, and the return value stays on device.
    """
    name = validate_md_options(backend, kw)
    score = _REGISTRY[name].score
    return lambda net, X: score(net, X, **kw)


def ensemble_rmse_records(params, idx, mask, xn, backend: str = "einsum",
                          **kw) -> jnp.ndarray:
    """The ensemble stage alone: normalised records (B, F) -> (B, k) RMSE.

    Used by ``train_kitnet`` so its training-set RMSE pass (which fixes the
    output AE's normalisation and training inputs) runs through the same
    backend later used for scoring.
    """
    name = validate_md_options(backend, kw)
    return _REGISTRY[name].ensemble(params, idx, mask, xn, **kw)
