"""The one place that decides whether a pallas kernel runs.

Every kernel wrapper (ops/mono_gather, ops/fused_resolve, ops/tour_scan)
and the merge kernel's device-only trace choices ask :func:`select`.
``use_pallas``:

- ``None``: auto — the Mosaic kernel on a TPU backend, the lax twin
  elsewhere.  ``GRAFT_NO_PALLAS=1`` is the explicit operational
  kill-switch for the TPU case;
- ``True`` / ``False``: forced;
- ``"interpret"``: the Mosaic kernel through the pallas interpreter
  (tests on the CPU; the merge kernel's static ``use_pallas`` carries
  it down to every kernel of one trace).
"""
from __future__ import annotations

import os
from typing import Optional, Tuple, Union

import jax

UsePallas = Optional[Union[bool, str]]


def select(use_pallas: UsePallas,
           interpret: bool = False) -> Tuple[bool, bool]:
    """→ ``(run the kernel, interpret it)``."""
    if interpret or use_pallas == "interpret":
        return True, True
    if use_pallas is None:
        return (jax.default_backend() == "tpu" and
                os.environ.get("GRAFT_NO_PALLAS") != "1"), False
    return bool(use_pallas), False
