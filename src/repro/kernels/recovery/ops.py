"""Jit'd wrappers for the CATopt recovery/fitness kernel.

``basis_risk`` dispatches to the Pallas kernel when requested (TPU, or
interpret=True for CPU validation) and to the jnp oracle otherwise.  The
sqrt + budget penalty are cheap elementwise tails and always run in jnp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.recovery import ref as _ref
from repro.kernels.recovery.ref import PENALTY_WEIGHT, recovery  # noqa: F401



def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def basis_risk(il: jnp.ndarray, target: jnp.ndarray, w: jnp.ndarray,
               att, limit, budget, *,
               use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """RMSE basis risk + budget penalty.  w: (..., m) -> (...)."""
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return _ref.basis_risk(il, target, w, att, limit, budget)

    from repro.kernels.recovery.kernel import fitness_sq_pallas
    batch_shape = w.shape[:-1]
    m = w.shape[-1]
    wf = w.reshape(-1, m)
    P = wf.shape[0]
    il_p = _pad_to(il.astype(jnp.float32), 1, 128)
    wf_p = _pad_to(wf.astype(jnp.float32), 1, 128)
    # pad population/events to the block grid
    bp = min(128, max(8, P))
    wf_p = _pad_to(wf_p, 0, bp)
    be = min(256, il_p.shape[0])
    il_p = _pad_to(il_p, 0, be)
    tgt = _pad_to(target.astype(jnp.float32), 0, be)
    # padded events contribute clip(0-att,0,limit)-0 = 0 error when att>=0
    sq = fitness_sq_pallas(il_p, wf_p, tgt,
                           jnp.asarray(att, jnp.float32),
                           jnp.asarray(limit, jnp.float32),
                           block_p=bp, block_e=be, interpret=interpret)[:P]
    mse = sq / il.shape[0]
    over = jnp.maximum(jnp.sum(wf, axis=-1) - budget, 0.0)
    out = jnp.sqrt(mse) + PENALTY_WEIGHT * jnp.square(over)
    return out.reshape(batch_shape)
