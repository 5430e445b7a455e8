"""Pallas TPU kernel: causal GQA flash attention (forward).

Layout: q is reshaped to (B*H, S, D), k/v to (B*Hkv, S, D); the BlockSpec
index map folds the GQA head-group mapping (kv row = b*Hkv + h//G) so no
materialised ``repeat`` ever hits HBM.

Grid: (B*H, Sq/bq, Sk/bk), dimension_semantics (parallel, parallel,
arbitrary): the kv axis is innermost/sequential, carrying the online-softmax
state (m, l, acc) in VMEM scratch.  Blocks fully outside the causal/window
band are skipped with ``pl.when`` (their DMA is still issued by the
prefetcher but no compute runs — the roofline counts it as free compute
skipping; the paged-attention kernel, DESIGN.md §4, shows the
data-dependent-extent alternative: its walk length is the live maximum).

VMEM per step: q(bq x D) + k,v(bk x D each) + scratch(bq x D + 2bq) f32.
Defaults bq=256, bk=512, D<=256  =>  ~1.2 MiB, well inside 16 MiB VMEM,
with MXU-aligned (multiple of 128) tile edges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e9


def _flash_fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_ref, l_ref, acc_ref, *,
                          causal: bool, window: int, softcap: float,
                          bq: int, bk: int, nk: int, scale: float):
    """Forward that additionally writes the per-row logsumexp L = m + log(l)
    (what the backward kernels need to recompute p without re-reducing)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1
    if window > 0:
        run &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32) * scale, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      causal: bool, window: int, softcap: float,
                      bq: int, bk: int, nk: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # skip blocks fully outside the causal/window band
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1
    if window > 0:
        run &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0]                       # (bq, D)
        k = k_ref[0]                       # (bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32) * scale, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Backward kernels (flash attention custom-vjp)
#
# Residuals: (q, k, v, o, lse) with lse = m + log(l) per row; the backward
# recomputes p = exp(s - lse) block-by-block (never materialising S x S),
# using D = rowsum(dO * O) for the softmax Jacobian:
#     dp = dO v^T;  ds = p * (dp - D);  dq += ds k;  dk += ds^T q;  dv += p^T dO
# Softcap: s_used = c*tanh(s_raw/c)  =>  ds_raw = ds_used * (1 - tanh^2).
# GQA: dk/dv are computed per *query* head and group-summed in ops.py.
# ---------------------------------------------------------------------------

def _bwd_block(q, k, v, do, lse, dsum, *, q_start, k_start, bq, bk,
               causal, window, softcap, scale):
    """Shared per-block math.  Returns (p, ds_raw) as f32 (bq, bk)."""
    s_raw = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if softcap > 0.0:
        t = jnp.tanh(s_raw / softcap)
        s_used = t * softcap
    else:
        s_used = s_raw
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    p = jnp.where(mask, jnp.exp(s_used - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(
        do.astype(jnp.float32), v.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - dsum[:, None])
    if softcap > 0.0:
        ds = ds * (1.0 - t * t)
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                         dq_ref, acc_ref, *, causal, window, softcap,
                         bq, bk, nk, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = jnp.bool_(True)
    if causal:
        run &= ki * bk <= qi * bq + bq - 1
    if window > 0:
        run &= ki * bk + bk - 1 >= qi * bq - window + 1

    @pl.when(run)
    def _body():
        _, ds = _bwd_block(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                           lse_ref[0], dsum_ref[0],
                           q_start=qi * bq, k_start=ki * bk, bq=bq, bk=bk,
                           causal=causal, window=window, softcap=softcap,
                           scale=scale)
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal, window,
                          softcap, bq, bk, nq, scale):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = jnp.bool_(True)
    if causal:
        run &= ki * bk <= qi * bq + bq - 1
    if window > 0:
        run &= ki * bk + bk - 1 >= qi * bq - window + 1

    @pl.when(run)
    def _body():
        p, ds = _bwd_block(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                           lse_ref[0], dsum_ref[0],
                           q_start=qi * bq, k_start=ki * bk, bq=bq, bk=bk,
                           causal=causal, window=window, softcap=softcap,
                           scale=scale)
        dv_acc[...] += jax.lax.dot_general(
            p, do_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _blocks(S, pref):
    b = min(pref, S)
    while S % b:
        b -= 1
    return b


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_k", "interpret", "n_q_heads"))
def flash_attention_fwd_lse(q, k, v, *, n_q_heads: int, causal=True,
                            window=0, softcap=0.0, block_q=256, block_k=512,
                            interpret=False):
    """Like flash_attention_pallas but also returns lse (B*H, Sq) f32."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    H = n_q_heads
    B = BH // H
    Hkv = BKV // B
    G = H // Hkv
    bq, bk = _blocks(Sq, block_q), _blocks(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    kernel = functools.partial(
        _flash_fwd_kernel_lse, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, scale=D ** -0.5)

    def kv_row(i, qi, ki):
        return ((i // H) * Hkv + (i % H) // G, ki, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_row),
            pl.BlockSpec((1, bk, D), kv_row),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bq), lambda i, qi, ki: (i, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out, lse


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_k", "interpret", "n_q_heads"))
def flash_attention_bwd(q, k, v, do, lse, dsum, *, n_q_heads: int,
                        causal=True, window=0, softcap=0.0,
                        block_q=256, block_k=512, interpret=False):
    """Returns (dq (BH,Sq,D), dk_per_qhead (BH,Sk,D), dv_per_qhead).

    dk/dv are per QUERY head; the ops wrapper group-sums them onto the
    kv heads (GQA)."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    H = n_q_heads
    B = BH // H
    Hkv = BKV // B
    G = H // Hkv
    bq, bk = _blocks(Sq, block_q), _blocks(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = D ** -0.5

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk, nk=nk, scale=scale),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda i, qi, ki: ((i // H) * Hkv + (i % H) // G,
                                            ki, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda i, qi, ki: ((i // H) * Hkv + (i % H) // G,
                                            ki, 0)),
            pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bq), lambda i, qi, ki: (i, qi)),
            pl.BlockSpec((1, bq), lambda i, qi, ki: (i, qi)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nq=nq, scale=scale),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, ki, qi: (i, qi, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda i, ki, qi: ((i // H) * Hkv + (i % H) // G,
                                            ki, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda i, ki, qi: ((i // H) * Hkv + (i % H) // G,
                                            ki, 0)),
            pl.BlockSpec((1, bq, D), lambda i, ki, qi: (i, qi, 0)),
            pl.BlockSpec((1, bq), lambda i, ki, qi: (i, qi)),
            pl.BlockSpec((1, bq), lambda i, ki, qi: (i, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda i, ki, qi: (i, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda i, ki, qi: (i, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    return dq, dk, dv


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_k", "interpret", "n_q_heads"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           n_q_heads: int,
                           causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           block_q: int = 256, block_k: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B*H, Sq, D); k/v: (B*Hkv, Sk, D).  Returns (B*H, Sq, D)."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    H = n_q_heads
    B = BH // H
    Hkv = BKV // B
    G = H // Hkv

    bq = min(block_q, Sq)
    while Sq % bq:
        bq -= 1
    bk = min(block_k, Sk)
    while Sk % bk:
        bk -= 1
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, scale=D ** -0.5)

    def kv_row(i, qi, ki):
        b = i // H
        h = i % H
        return (b * Hkv + h // G, ki, 0)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_row),
            pl.BlockSpec((1, bk, D), kv_row),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
