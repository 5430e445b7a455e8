"""Pallas TPU kernel: RWKV-6 recurrence with the (D x D) state in VMEM.

    out_t = r_t . S + (r_t . (u * k_t)) v_t
    S    <- diag(w_t) S + k_t v_t^T

Layout: heads flattened, (B*H, S, D) inputs.  Grid (B*H, S/chunk) with
dimension_semantics (parallel, arbitrary): the chunk axis is sequential and
the state scratch persists across chunks, so the recurrence never spills to
HBM.  Within a chunk a fori_loop steps one token at a time; each step is a
(D,) x (D, D) matvec + rank-1 update — VPU work with the (D, D) outer
product feeding the MXU at D=64..256.

VMEM: 4 x (chunk x D) inputs + (D x D) state  ~= 4*128*64*4 + 64*64*4
bytes at the defaults (chunk=128, D=64): ~0.15 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0]  # (D,)

    def step(t, _):
        r = r_ref[0, t]
        k = k_ref[0, t]
        v = v_ref[0, t]
        w = w_ref[0, t]
        S = state_ref[...]                                # (D, D)
        bonus = jnp.sum(r * u * k)                        # scalar
        out = r @ S + bonus * v                           # (D,)
        state_ref[...] = S * w[:, None] + k[:, None] * v[None, :]
        o_ref[0, t] = out.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, w, u, *, chunk: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """r/k/v/w: (BH, S, D) f32; u: (BH, D).  Returns (BH, S, D)."""
    BH, S, D = r.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    grid = (BH, S // c)
    spec = pl.BlockSpec((1, c, D), lambda i, ci: (i, ci, 0))
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=c),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, D), lambda i, ci: (i, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
