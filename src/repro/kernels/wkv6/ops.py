"""Jit'd wrapper for the RWKV-6 recurrence kernel: (B, S, H, D) API."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.wkv6 import ref as _ref



def wkv(r, k, v, w, u, *, use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None, chunk: int = 128) -> jnp.ndarray:
    """r/k/v/w: (B, S, H, D); u: (H, D) -> (B, S, H, D)."""
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return _ref.wkv(r, k, v, w, u)
    from repro.kernels.wkv6.kernel import wkv_pallas
    B, S, H, D = r.shape
    to_flat = lambda x: x.transpose(0, 2, 1, 3).reshape(
        B * H, S, D).astype(jnp.float32)
    uf = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, D).astype(
        jnp.float32)
    out = wkv_pallas(to_flat(r), to_flat(k), to_flat(v), to_flat(w), uf,
                     chunk=chunk, interpret=interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3).astype(r.dtype)
