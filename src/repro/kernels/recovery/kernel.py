"""Pallas TPU kernel: fused CATopt fitness.

Computes, for a population of weight vectors, the squared-error sum between
the clamped parametric recovery and the target recovery — in one pass over
the industry-loss matrix (IL never revisits HBM per individual):

    fitness_sq[p] = sum_e (clip(IL[e,:] @ w[p,:] - att, 0, limit) - target[e])^2

Tiling: grid (P/bp, E/be); each step loads an IL tile (be, m_pad) and a
population tile (bp, m_pad) into VMEM, runs the (be x m) @ (m x bp) matmul
on the MXU, applies the clamp + squared error on the VPU and accumulates
into the (bp,) output block.  The E axis is the innermost ("arbitrary")
grid dim so the output block is revisited and accumulated in place.

m is padded to a multiple of 128 lanes by ops.py; be/bp default to 256/128
=> VMEM footprint ~ (256 x m + 128 x m) * 4B  (~3 MiB at m=2048).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _fitness_kernel(il_ref, w_ref, target_ref, att_ref, limit_ref, out_ref):
    e_idx = pl.program_id(1)

    @pl.when(e_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    il = il_ref[...]            # (be, m)
    w = w_ref[...]              # (bp, m)
    att = att_ref[0, 0]
    limit = limit_ref[0, 0]
    target = target_ref[...]    # (1, be)
    # (be, bp) event-loss tile on the MXU
    loss = jax.lax.dot_general(il, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    rec = jnp.clip(loss - att, 0.0, limit)
    err = rec - target[0][:, None]          # (be, bp)
    out_ref[...] += jnp.sum(jnp.square(err), axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_p", "block_e", "interpret"))
def fitness_sq_pallas(il: jnp.ndarray, w: jnp.ndarray, target: jnp.ndarray,
                      att: jnp.ndarray, limit: jnp.ndarray, *,
                      block_p: int = 128, block_e: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    """il: (E, m) f32, m % 128 == 0; w: (P, m); target: (E,).

    Returns sum-of-squared-error fitness (P,) (no sqrt / penalty — those are
    cheap and stay in ops.py).
    """
    E, m = il.shape
    P, _ = w.shape
    bp = min(block_p, P)
    be = min(block_e, E)
    assert E % be == 0 and P % bp == 0, (E, P, be, bp)
    grid = (P // bp, E // be)

    out = pl.pallas_call(
        _fitness_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, m), lambda p, e: (e, 0)),       # IL tile
            pl.BlockSpec((bp, m), lambda p, e: (p, 0)),       # population tile
            pl.BlockSpec((1, be), lambda p, e: (0, e)),       # target tile
            pl.BlockSpec((1, 1), lambda p, e: (0, 0)),        # att
            pl.BlockSpec((1, 1), lambda p, e: (0, 0)),        # limit
        ],
        out_specs=pl.BlockSpec((1, bp), lambda p, e: (0, p)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(il, w, target[None], att.reshape(1, 1), limit.reshape(1, 1))
    return out[0]
