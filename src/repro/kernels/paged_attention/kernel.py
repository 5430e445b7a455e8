"""Pallas TPU kernel: paged-KV decode attention (block-table walk).

Layout: pages are kv-head-major, ``(num_blocks, Hkv, BS, D)`` per layer,
so one block is one whole ``(BS, D)`` page of one kv head.  Every block's
last two dims equal the array's, which is what the TPU lowering demands
of a block that is not a multiple of the (8, 128) tile.  int8 pools
carry fp32 per-row scale pools ``(num_blocks, Hkv, BS)``; the kernel
reads a page's scales for all heads as one ``(Hkv, BS)`` block and picks
its head's row (turned into a column to scale the page's rows).

Grid: (B, Hkv, max_live_blocks) with dimension_semantics (parallel,
parallel, arbitrary) — the innermost axis walks each request's *logical*
blocks in order, carrying the online-softmax state (m, l, acc) in VMEM
scratch exactly like the flash kernel.  Block tables and per-row position
bounds ride in as scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so
the K/V BlockSpec index map resolves logical block j to its physical page
``tables[b, j]`` before the DMA is issued: the gather is never
materialised in HBM.

Live-block early exit: the grid's third extent is the *tick's* live
maximum ``ceil((max position + 1) / BS)``, a static bound the engine
passes down, and each request clamps its own walk at
``ceil((pos + 1) / BS)`` — steps past a row's live length re-map their DMA
to the row's last live page (the pipeliner skips the refetch when the
index is unchanged) and skip compute via ``pl.when``.  Decode cost
therefore tracks actual sequence length, never pool capacity.

GQA: q is pre-folded to (B, Hkv, S*G, D) — the G query heads of a group
(plus the S chunk rows) become extra query rows against their single
shared kv head, so repeated K/V never exist anywhere.

Fused KV scatter: the fused variant takes this step's fresh K/V rows and
writes them into the visited page *in the kernel prologue* (the pools are
input/output aliased; every visited page is copied through and written
back).  Decode touches the cache once per layer — no separate
scatter-then-gather dispatch.  Padded rows (position -1) are simply not
written; the null block stays garbage by design.  With int8 pools the
fresh rows' scales are scattered into the (small) scale pools before the
launch: a scale block spans every kv head of its page, so the per-head
grid steps could not write it back without racing each other.

Windows: blocks wholly outside every live row's sliding window are
skipped by the same ``pl.when`` predicate (window arrives as a traced
scalar because the layer scan stacks per-layer windows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _column(row):
    """(1, n) -> (n, 1) through a masked lane reduction — exact, since
    each output sums one entry and zeros."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.where(eye, row, 0.0).sum(axis=1, keepdims=True)


def _paged_kernel(tab_ref, pos_ref, maxp_ref, minp_ref, win_ref,  # scalars
                  *refs, fuse: bool, quant: bool, S: int, BS: int, nb: int,
                  softcap: float, scale: float):
    refs = list(refs)
    qpos_ref, q_ref = refs.pop(0), refs.pop(0)
    kn_ref, vn_ref = (refs.pop(0), refs.pop(0)) if fuse else (None, None)
    kp_ref, vp_ref = refs.pop(0), refs.pop(0)
    ksp_ref, vsp_ref = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    o_ref = refs.pop(0)
    kpo_ref, vpo_ref = (refs.pop(0), refs.pop(0)) if fuse else (None, None)
    m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    maxp = maxp_ref[b]
    last = jnp.maximum(maxp, 0) // BS        # row's last live logical block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages are handled in 32-bit (f32 for fp pools, int32 for int8 ones):
    # the conversions are exact and keep every select and store on the
    # TPU's native 32-bit layout whatever the pool dtype
    wide = jnp.int32 if quant else jnp.float32
    k = kp_ref[0, 0].astype(wide)                            # (BS, D)
    v = vp_ref[0, 0].astype(wide)
    if fuse:
        # Copy the visited page through and scatter this step's fresh rows
        # into it.  Steps clamped past ``last`` alias the last live page and
        # their input block is NOT refetched (same index map output), so the
        # scatter must be re-applied there — hence the clamp on jl.
        jl = jnp.minimum(j, last)
        rows = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        for si in range(S):
            p = pos_ref[b, si]
            off = jnp.where((p >= 0) & (p // BS == jl), p % BS, -1)
            hit = rows == off
            k = jnp.where(hit, kn_ref[0, 0, si:si + 1, :].astype(wide), k)
            v = jnp.where(hit, vn_ref[0, 0, si:si + 1, :].astype(wide), v)
        kpo_ref[0, 0] = k.astype(kpo_ref.dtype)
        vpo_ref[0, 0] = v.astype(vpo_ref.dtype)

    win = win_ref[0]
    # run only live blocks that overlap some row's (causal, window) band
    run = (maxp >= 0) & (j <= last)
    run &= (j * BS + BS - 1) >= (minp_ref[b] - win + 1)

    @pl.when(run)
    def _body():
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        if quant:
            # fused dequant: int8 rows times their fp32 per-row scales
            kf = kf * _column(ksp_ref[0, pl.ds(h, 1), :])
            vf = vf * _column(vsp_ref[0, pl.ds(h, 1), :])
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (SG, D)
        s = jax.lax.dot_general(
            q, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (SG, BS)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        SG = q.shape[0]
        q_pos = qpos_ref[0]                                  # (SG, 1)
        k_pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (SG, BS), 1)
        valid = (k_pos <= q_pos) & ((q_pos - k_pos) < win) & (q_pos >= 0)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                                  # (SG, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _call(q, k_new, v_new, k_pool, v_pool, block_tables, positions, *,
          window, softcap: float, max_live_blocks: int, interpret: bool,
          fuse: bool, k_scale=None, v_scale=None):
    B, S, H, D = q.shape
    NB, Hkv, BS, _ = k_pool.shape
    G = H // Hkv
    SG = S * G
    MB = block_tables.shape[1]
    nb = max(1, min(int(max_live_blocks), MB))
    quant = k_scale is not None

    # fold GQA groups into query rows: row r = s*G + g <-> kv head h
    qf = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, SG, D)
    positions = positions.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    maxp = jnp.max(positions, axis=1)                               # (B,)
    minp = jnp.min(jnp.where(positions >= 0, positions, jnp.int32(2 ** 30)),
                   axis=1)                                          # (B,)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    qpos = jnp.repeat(positions, G, axis=1)[..., None]            # (B, SG, 1)

    def page_map(b, h, j, tab, pos, mx, mn, w):
        live_last = jnp.maximum(mx[b], 0) // BS
        return (tab[b, jnp.minimum(j, live_last)], h, 0, 0)

    def scale_page_map(b, h, j, tab, pos, mx, mn, w):
        live_last = jnp.maximum(mx[b], 0) // BS
        return (tab[b, jnp.minimum(j, live_last)], 0, 0)

    def head_map(b, h, j, *_):
        return (b, h, 0, 0)

    page_spec = pl.BlockSpec((1, 1, BS, D), page_map)
    in_specs = [pl.BlockSpec((1, SG, 1), lambda b, h, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, SG, D), head_map)]
    ins = [qpos, qf]
    if quant and fuse:
        # quantize once out here with the shared reference recipe, so the
        # int8 rows the prologue scatters — and the scales scattered here —
        # are bit-identical to ref.write_kv's
        from repro.kernels.paged_attention import ref as _ref
        k_new, ks = _ref.quantize_rows(k_new)
        v_new, vs = _ref.quantize_rows(v_new)
        k_scale = _ref.scatter_rows(k_scale, ks, positions, tables)
        v_scale = _ref.scatter_rows(v_scale, vs, positions, tables)
    if fuse:
        # fresh rows kv-head-major like the pages: one (S, D) block per head
        in_specs += [pl.BlockSpec((1, 1, S, D), head_map)] * 2
        ins += [k_new.astype(k_pool.dtype).transpose(0, 2, 1, 3),
                v_new.astype(v_pool.dtype).transpose(0, 2, 1, 3)]
    in_specs += [page_spec, page_spec]
    ins += [k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, Hkv, BS), scale_page_map)] * 2
        ins += [k_scale, v_scale]

    out_specs = [pl.BlockSpec((1, 1, SG, D), head_map)]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, SG, D), q.dtype)]
    aliases = {}
    if fuse:
        out_specs += [page_spec, page_spec]
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # pools are updated in place: unvisited pages must persist, so the
        # pool inputs MUST alias the pool outputs.  Operand indices count
        # the 5 scalar-prefetch operands: [qpos, q, k_new, v_new] puts the
        # pools at operands 9, 10 (outputs 1, 2).
        aliases = {9: 1, 10: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, Hkv, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((SG, 1), jnp.float32),
                        pltpu.VMEM((SG, 1), jnp.float32),
                        pltpu.VMEM((SG, D), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, fuse=fuse, quant=quant, S=S,
                               BS=BS, nb=nb, softcap=softcap,
                               scale=D ** -0.5)
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the custom call's HLO instruction, and so its event in the
        # profiler's device trace, is named after this, whatever the
        # functions around the call are called: tools that find the
        # kernel by name (its roofline in the benchmark) rely on it
        name=("paged_attention_update_pallas" if fuse
              else "paged_attention_pallas"),
    )(tables, positions, maxp, minp, win, *ins)

    out = res[0].reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4) \
                .reshape(B, S, H, D)
    if fuse and quant:
        return out, res[1], res[2], k_scale, v_scale
    if fuse:
        return out, res[1], res[2]
    return out


def _copy_page_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index maps (scalar prefetch)
    out_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def copy_page_pallas(pool, src, dst, *, interpret: bool = False):
    """Copy page ``src`` over page ``dst`` in every layer of a stacked
    (L, NB, ...) pool — the serving engine's copy-on-write primitive.

    One grid step per layer; the page ids ride in as scalar prefetch so
    the source/destination BlockSpec index maps resolve them before the
    DMAs are issued, exactly like the block-table walk above.  The pool
    is input/output aliased: only the visited destination page is
    written, everything else persists in place.
    """
    L = pool.shape[0]
    page = pool.shape[2:]
    zeros = (0,) * len(page)
    idx = jnp.stack([jnp.asarray(src, jnp.int32),
                     jnp.asarray(dst, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L,),
        in_specs=[pl.BlockSpec((1, 1, *page),
                               lambda l, idx: (l, idx[0], *zeros))],
        out_specs=[pl.BlockSpec((1, 1, *page),
                                lambda l, idx: (l, idx[1], *zeros))],
    )
    return pl.pallas_call(
        _copy_page_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 0 is the scalar prefetch, so the pool is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, pool)[0]


@functools.partial(jax.jit, static_argnames=("softcap", "max_live_blocks",
                                             "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, block_tables, positions, *,
                           window, softcap: float, max_live_blocks: int,
                           interpret: bool = False, k_scale=None,
                           v_scale=None):
    """Read-only block-table walk.  q: (B, S, H, D) -> (B, S, H, D).

    With ``k_scale``/``v_scale`` ((NB, Hkv, BS) fp32) the pools are int8
    and the walk dequantizes each visited page in the kernel body.
    """
    return _call(q, None, None, k_pool, v_pool, block_tables, positions,
                 window=window, softcap=softcap,
                 max_live_blocks=max_live_blocks, interpret=interpret,
                 fuse=False, k_scale=k_scale, v_scale=v_scale)


@functools.partial(jax.jit, static_argnames=("softcap", "max_live_blocks",
                                             "interpret"))
def paged_attention_update_pallas(q, k_new, v_new, k_pool, v_pool,
                                  block_tables, positions, *, window,
                                  softcap: float, max_live_blocks: int,
                                  interpret: bool = False, k_scale=None,
                                  v_scale=None):
    """Fused scatter + block-table walk.

    Writes this step's fresh K/V rows (B, S, Hkv, D) into their
    (NB, Hkv, BS, D) pages in the kernel prologue, then attends over the
    updated pages.  Returns
    (out (B, S, H, D), k_pool, v_pool).

    With ``k_scale``/``v_scale`` the pools are int8: the fresh rows are
    quantized with the shared reference recipe before the launch, their
    per-row scales scattered into the scale pools, the prologue scatters
    the int8 rows, the walk
    dequantizes in fp32, and the return grows to
    (out, k_pool, v_pool, k_scale, v_scale).
    """
    return _call(q, k_new, v_new, k_pool, v_pool, block_tables, positions,
                 window=window, softcap=softcap,
                 max_live_blocks=max_live_blocks, interpret=interpret,
                 fuse=True, k_scale=k_scale, v_scale=v_scale)
