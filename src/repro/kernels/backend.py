"""Which implementation a kernel op runs.

Every ``ops.py`` in this package takes ``use_pallas`` and ``interpret``
switches; :func:`resolve` turns a caller's ``None`` into a choice.

* On a TPU a Pallas kernel is compiled.  Interpret mode there is refused:
  it would run the interpreter where the kernel belongs.
* Off a TPU a Pallas kernel can only run in interpret mode (the CPU
  validation path), so that is the default there.
* ``use_pallas=None`` reads ``REPRO_USE_PALLAS`` (default off), except
  where the op declares the kernel its TPU default (``tpu_default``, the
  serving hot path): on a TPU such an op runs the kernel whatever the
  environment says, and only an explicit ``use_pallas=False`` selects the
  jnp reference.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(use_pallas: Optional[bool], interpret: Optional[bool], *,
            tpu_default: bool = False) -> Tuple[bool, bool]:
    """Effective ``(use_pallas, interpret)`` for this process's backend."""
    tpu = on_tpu()
    if use_pallas is None:
        use_pallas = ((tpu and tpu_default)
                      or os.environ.get("REPRO_USE_PALLAS", "0") == "1")
    if interpret is None:
        interpret = not tpu
    if tpu and interpret:
        raise ValueError("interpret mode runs a Pallas kernel on the CPU "
                         "for validation; on a TPU the kernel is compiled")
    return use_pallas, interpret
