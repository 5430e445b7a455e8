"""Jit'd wrapper for flash attention: (B, S, H, D) API with GQA."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.flash_attention import ref as _ref



def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: int = 0, softcap: float = 0.0,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None,
              block_q: int = 256, block_k: int = 512) -> jnp.ndarray:
    """q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, H, D)."""
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return _ref.attention(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, k.shape[1], D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, v.shape[1], D)
    out = flash_attention_pallas(qf, kf, vf, n_q_heads=H, causal=causal,
                                 window=window, softcap=softcap,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Differentiable kernel path: Pallas forward (saving lse) + Pallas backward
# (dq / dk / dv kernels) wired through jax.custom_vjp.  O(S) memory in
# training — no (S, S) tensor and no full recompute.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def attention_vjp(q, k, v, n_q_heads: int, causal: bool, window: int,
                  softcap: float, block_q: int, block_k: int,
                  interpret: bool):
    """Flat layout: q (B*H, Sq, D); k/v (B*Hkv, Sk, D)."""
    from repro.kernels.flash_attention.kernel import flash_attention_fwd_lse
    out, _ = flash_attention_fwd_lse(
        q, k, v, n_q_heads=n_q_heads, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out


def _attention_vjp_fwd(q, k, v, n_q_heads, causal, window, softcap,
                       block_q, block_k, interpret):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd_lse
    out, lse = flash_attention_fwd_lse(
        q, k, v, n_q_heads=n_q_heads, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out, (q, k, v, out, lse)


def _attention_vjp_bwd(n_q_heads, causal, window, softcap, block_q, block_k,
                       interpret, res, do):
    from repro.kernels.flash_attention.kernel import flash_attention_bwd
    q, k, v, out, lse = res
    dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq, dk_h, dv_h = flash_attention_bwd(
        q, k, v, do, lse, dsum, n_q_heads=n_q_heads, causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    # GQA: group-sum per-query-head dk/dv onto the kv heads
    BH, Sk, D = dk_h.shape
    H = n_q_heads
    B = BH // H
    Hkv = k.shape[0] // B
    G = H // Hkv
    dk = dk_h.reshape(B, Hkv, G, Sk, D).sum(axis=2).reshape(B * Hkv, Sk, D)
    dv = dv_h.reshape(B, Hkv, G, Sk, D).sum(axis=2).reshape(B * Hkv, Sk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


attention_vjp.defvjp(_attention_vjp_fwd, _attention_vjp_bwd)


def attention_trainable(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, interpret: Optional[bool] = None,
                        block_q: int = 256, block_k: int = 512):
    """(B, S, H, D) API over the custom-vjp kernel pair."""
    interpret = resolve(True, interpret)[1]
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, k.shape[1], D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, v.shape[1], D)
    out = attention_vjp(qf, kf, vf, H, causal, window, softcap,
                        block_q, block_k, interpret)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
