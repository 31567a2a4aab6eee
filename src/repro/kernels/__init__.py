"""Pallas kernels of the detection path (FC, sketch FC, KitNET MD).

Every kernel entry point takes ``interpret=None | True | False`` and runs
its ``pallas_call`` through :func:`run_pallas`:

* ``None`` (the default) interprets the kernel when the computation is
  lowered for the CPU and compiles it with Mosaic for any other platform.
  The choice is made at lowering time (``jax.lax.platform_dependent``), so
  it follows the platform the arrays are compiled for, not the host: an
  AOT compile for a described TPU gets the compiled kernel on a CPU host.
* ``True`` always interprets; ``False`` always compiles (and fails on the
  CPU). There is no silent fallback: on a TPU a kernel compiles or the
  run fails.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax


def run_pallas(make: Callable[[bool], Callable], *args,
               interpret: Optional[bool] = None):
    """Apply the kernel ``make(interpret)`` builds to ``args``."""
    if interpret is not None:
        return make(bool(interpret))(*args)
    return jax.lax.platform_dependent(*args, cpu=make(True),
                                      default=make(False))
