"""Pallas TPU kernel: paged-KV attention (block-table walk).

Layout: pages are page-major, ``(num_blocks, Hkv, BS, D)`` per layer, so
one block is one whole page of every kv head, ``(Hkv, BS, D)``: its last
two dims equal the array's, which is what the TPU lowering demands of a
block that is not a multiple of the (8, 128) tile.  int8 pools carry fp32
per-row scale pools ``(num_blocks, Hkv, BS)``.

The walk: each row visits only its own live pages, from the page that
holds the first key of its window band (``minp - window + 1``) to its
last live page (``maxp // BS``).  The grid is ``(Hkv // HB, n)``: ``HB``
kv heads per step (all of them unless VMEM says otherwise), and ``n``
steps of a ragged schedule, one per (row, live page) pair, rows in order.
The schedule is computed before the launch from the rows' position
bounds, rides in as scalar prefetch with the block table, and ``n`` is a
traced grid bound.  So a shallow row stops at its own depth, not the
tick's, and a padded row (``maxp < 0``) takes one step, computes
nothing and writes zeros.  Each step's page of K and V, all ``HB``
heads at once, is fetched by the grid's own double-buffered pipeline
(``tables[row, page]`` resolves in the index map before the DMA), so the
next page — the next row's first page included — is in flight while this
one is used.  The pages are fetched by the pipeline and not by copies
issued in the body: Mosaic refuses to slice a memref whose minor dim is
under 128 lanes (64-wide heads), in HBM or VMEM, and a hand-made copy of
one page is such a slice.

Compute: pages are staged into a ``P``-page VMEM block, and the block is
computed once it is full or the row's walk ends: per kv head, one
``(P * BS, D)`` key matrix; q rows are walked in tiles of ``QB``, and
each tile's online softmax state (m, l, acc; f32 in VMEM scratch) is
updated once per block, not once per page; a tile none of whose queries
reaches the block (all-padding tiles too) is skipped.  q and K reach the
MXU in their own dtype, p and V in f32, with f32 accumulation.  int8
pages are dequantized in f32 as the reference does, by their per-row
scales: those are gathered before the launch into a lane-dense
``(Hkv, keys)`` strip per row, sliced per block.

Block size: chosen here from the call's shapes, never by an option.  A
block holds at most 1024 keys (a longer one only adds masked tail work),
the f32 score tile ``(QB, P * BS)`` at most 1 MiB, the staged K and V
pages at most 8 MiB, and never more pages than the tick's live bound;
query rows are tiled by at most 256.  So a decode row (``S * G`` a
handful of query rows) and a 128-token chunk at 16 query heads per kv
head (2048 query rows, in eight 256-row tiles) both take 1024-key
blocks of 16-token pages, and a wider page or head dim takes fewer
pages.  (On a v5e, 1024 keys beat 512, 256 and 2048 at both cells'
shapes.)  ``vmem_limit_bytes`` follows from the same arithmetic.  Nothing
is left to tune per model: the same shapes always give the same plan.

GQA: q is pre-folded to (B, Hkv, S*G, D) — the G query heads of a group
(plus the S chunk rows) become extra query rows against their single
shared kv head, so repeated K/V never exist anywhere.

Fused KV scatter: a row's fresh tokens sit at consecutive positions (one
segment: a decode token, a prefill chunk or a draft chain; -1 pads the
rest; the query positions the masks use follow from the same run), so
they land on at most ``NF = (S - 1) // BS + 2`` pages.  Before the launch
they are laid out as those pages' images, K and V stacked, ``(B, NF, 2,
Hkv, BS, D)`` (int8 rows quantized and their scales scattered into the
scale pools, exactly as the reference does).  The walk lays each fresh
page's rows over the fetched page before the page is staged — so
intra-chunk causality holds — and writes that page to the pool, which is
input/output aliased.  The pool output's index map follows the row's
fresh pages only (``clamp(page, first fresh, last)``), so the pipeline
writes back just those: 1 page for a decode row, up to ``NF`` for a
chunk, and no other.  A padded row's output maps to the null block,
which stays garbage by design.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

_MAX_KEYS = 1024                # keys per compute block
_SCORE_TILE_BYTES = 1 << 20     # one f32 (QB, keys) score tile
_STAGE_BYTES = 8 << 20          # the staged K and V pages of one block
_HEAD_STATE_BYTES = 24 << 20    # q, out and softmax state of the step's heads
_MAX_QUERY_TILE = 256


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM array: last dim padded to 128 lanes, second-last to
    the dtype's sublane tile."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = _round_up(rows, 8 * max(1, 4 // itemsize)) * _round_up(lanes, 128)
    for d in lead:
        n *= d
    return n * itemsize


def _plan(*, SG: int, Hkv: int, BS: int, D: int, nb: int, q_dtype,
          pool_dtype, NF: int, quant: bool):
    """(heads per step, pages per block, query tile, padded query rows,
    vmem limit) for one call, from its shapes alone."""
    QB = min(_round_up(SG, 8), _MAX_QUERY_TILE)
    SGp = _round_up(SG, QB)
    per_head = (4 * _vmem_bytes((SGp, D), q_dtype)       # q and out, x2
                + _vmem_bytes((SGp, D), jnp.float32)     # acc
                + 2 * _vmem_bytes((SGp, 1), jnp.float32))  # m, l
    HB = max(h for h in range(1, Hkv + 1)
             if Hkv % h == 0 and (h == 1 or h * per_head <= _HEAD_STATE_BYTES))
    page = _vmem_bytes((HB, BS, D), pool_dtype)
    P = max(1, min(_MAX_KEYS // BS, _SCORE_TILE_BYTES // (4 * QB * BS),
                   _STAGE_BYTES // (2 * page), nb))
    TK = P * BS
    nblocks = -(-nb // P)
    est = (HB * per_head
           + (2 * P + 8) * page                  # staged + pipelined pages
           + 4 * NF * page                       # fresh page images, x2
           + 8 * _vmem_bytes((QB, TK), jnp.float32)
           + 2 * HB * _vmem_bytes((TK, D), jnp.float32))
    if quant:
        est += 4 * HB * _vmem_bytes((1, nblocks * TK), jnp.float32)
    vmem = min(max(32 << 20, int(est * 1.5)), 100 << 20)
    return HB, P, QB, SGp, vmem


def _columns(row, n: int):
    """(1, TK) -> (TK, n), each row its entry repeated: a transpose of
    the row broadcast over 128-lane-aligned sublanes, exact."""
    wide = jax.lax.broadcast_in_dim(row, (_round_up(n, 128), row.shape[1]),
                                    (0, 1))
    return wide.T[:, :n]


def _paged_kernel(srow_ref, spage_ref, tab_ref, maxp_ref, minp_ref,
                  qoff_ref, win_ref, qlo_ref, qhi_ref,        # scalars
                  *refs, fuse: bool, quant: bool, BS: int, P: int, HB: int,
                  QB: int, G: int, nb: int, softcap: float, scale: float):
    refs = list(refs)
    q_ref = refs.pop(0)
    new_ref = refs.pop(0) if fuse else None
    kp_ref, vp_ref = refs.pop(0), refs.pop(0)
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    o_ref = refs.pop(0)
    kpo_ref, vpo_ref = (refs.pop(0), refs.pop(0)) if fuse else (None, None)
    kst, vst, m_ref, l_ref, acc_ref = refs
    s = pl.program_id(1)
    row, page = srow_ref[s], spage_ref[s]
    TK = P * BS
    SGp = q_ref.shape[2]
    # the MXU's operand dtype for q and K: neither is rounded
    cdt = jnp.float32 if quant else jnp.promote_types(q_ref.dtype,
                                                      kst.dtype)

    maxp, minp, win = maxp_ref[row], minp_ref[row], win_ref[0]
    live = maxp >= 0
    last = jnp.minimum(maxp // BS, nb - 1)        # last live logical page
    slot = page % P

    @pl.when((s == 0) & (pl.program_id(0) == 0))
    def _zero():
        # staged slots a block does not fill hold what an earlier block
        # left there: finite once zeroed, and the masks hide it
        kst[...] = jnp.zeros_like(kst)
        vst[...] = jnp.zeros_like(vst)

    @pl.when((s == 0) | (srow_ref[jnp.maximum(s - 1, 0)] != row))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def stage():
        kst[slot] = kp_ref[0]
        vst[slot] = vp_ref[0]

    if fuse:
        # pages from the first fresh one (minp's) on are written back; a
        # row whose fresh rows lie past the live bound writes its last
        # page back as it is, so no page is ever written from a stale
        # buffer
        fp0 = minp // BS
        written = live & (page >= jnp.minimum(fp0, last))
        pl.when(jnp.logical_not(written))(stage)

        @pl.when(written)
        def _merge():
            # lay this page's fresh rows over it, in 32-bit (exact, and
            # the TPU's native layout for selects whatever the dtype)
            wide = jnp.int32 if quant else jnp.float32
            t = page * BS + jax.lax.broadcasted_iota(jnp.int32,
                                                     (HB, BS, 1), 1)
            fresh = (t >= minp) & (t <= maxp)
            f = jnp.maximum(page - fp0, 0)
            for i, (src, stg, out) in enumerate(((kp_ref, kst, kpo_ref),
                                                 (vp_ref, vst, vpo_ref))):
                cur = jnp.where(fresh, new_ref[0, f, i].astype(wide),
                                src[0].astype(wide)).astype(stg.dtype)
                stg[slot] = cur
                out[0] = cur
    else:
        stage()

    @pl.when(live & ((slot == P - 1) | (page == last)))
    def _compute():
        k_pos0 = (page // P) * TK
        k_last = k_pos0 + TK - 1

        def head(h, carry):
            k = kst[:, h].astype(cdt).reshape(TK, -1)              # (TK, D)
            v = vst[:, h].astype(jnp.float32).reshape(TK, -1)
            if quant:
                # fused dequant: int8 rows times their fp32 per-row scales
                keys = pl.ds(0 if ks_ref.shape[-1] == TK
                             else pl.multiple_of(k_pos0, TK), TK)
                k = k * _columns(ks_ref[0, h, :, keys], k.shape[1])
                v = v * _columns(vs_ref[0, h, :, keys], v.shape[1])

            def update(r):
                q = q_ref[0, h, r, :].astype(cdt)
                if quant:
                    q = q * scale
                sc = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)            # (QB, TK)
                if not quant:
                    sc = sc * scale
                if softcap > 0.0:
                    sc = jnp.tanh(sc / softcap) * softcap
                # query row j*G + g is entry j of the row's run
                q_row = r.start + jax.lax.broadcasted_iota(
                    jnp.int32, (QB, 1), 0)
                q_pos = q_row // G + qoff_ref[row]                 # (QB, 1)
                q_pos = jnp.where((q_pos >= minp) & (q_pos <= maxp),
                                  q_pos, -1)
                k_pos = k_pos0 + jax.lax.broadcasted_iota(
                    jnp.int32, (QB, TK), 1)
                valid = (k_pos <= q_pos) & ((q_pos - k_pos) < win)
                sc = jnp.where(valid, sc, NEG_INF)
                m_prev = m_ref[h, r, :]                            # (QB, 1)
                m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
                p = jnp.exp(sc - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h, r, :] = l_ref[h, r, :] * corr + p.sum(
                    axis=1, keepdims=True)
                acc_ref[h, r, :] = acc_ref[h, r, :] * corr + \
                    jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                m_ref[h, r, :] = m_new

            def tile(t, carry):
                # the tile's valid query positions bound what it can see:
                # a block none of them reaches is skipped, and so is a
                # tile of padding alone
                r = pl.ds(pl.multiple_of(t * QB, QB), QB)
                qlo, qhi = qlo_ref[row, t], qhi_ref[row, t]
                seen = (qhi >= k_pos0) & (k_last > qlo - win)
                pl.when(seen)(functools.partial(update, r))
                return carry

            return jax.lax.fori_loop(0, SGp // QB, tile, carry)

        jax.lax.fori_loop(0, HB, head, 0)

    @pl.when(srow_ref[s + 1] != row)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _fresh_pages(new, first, minp, maxp, fp0, NF, BS, dtype):
    """(B, S, Hkv, D) fresh rows -> (B, NF, Hkv, BS, D) images of the
    pages they land on, page ``fp0`` first; slots no fresh row reaches
    hold zeros (the walk keeps the pool's bytes there).  A row's fresh
    rows are one run of consecutive positions from entry ``first``, so
    each slot's source is a gather from the run."""
    B, S, Hkv, D = new.shape
    t = fp0[:, None] * BS + jnp.arange(NF * BS, dtype=jnp.int32)[None]
    fresh = (t >= minp[:, None]) & (t <= maxp[:, None])       # (B, NF*BS)
    src = jnp.clip(first[:, None] + t - minp[:, None], 0, S - 1)
    rows = jnp.take_along_axis(new, src[:, :, None, None], axis=1)
    rows = jnp.where(fresh[:, :, None, None], rows.astype(dtype),
                     jnp.zeros((), dtype))
    return rows.reshape(B, NF, BS, Hkv, D).transpose(0, 1, 3, 2, 4)


def _schedule(maxp, minp, win, *, BS: int, nb: int):
    """The walk's ragged schedule: row and logical page of every step
    (one per live page of the window band, rows in order; one step for a
    padded row), a -1 row past the end, and the step count."""
    B = maxp.shape[0]
    live = maxp >= 0
    last = jnp.minimum(maxp // BS, nb - 1)
    lo = jnp.where(live, jnp.minimum(
        jnp.maximum(minp - win + 1, 0) // BS, last), 0)
    cnt = jnp.where(live, last - lo + 1, 1)
    ends = jnp.cumsum(cnt)
    n = ends[-1]
    steps = jnp.arange(B * nb + 1, dtype=jnp.int32)
    # a step's row: how many rows end at or before it (one vectorized
    # compare, not a search loop)
    row = jnp.minimum(jnp.sum(steps[:, None] >= ends[None, :], axis=1,
                              dtype=jnp.int32), B - 1)
    page = lo[row] + steps - (ends - cnt)[row]
    on = steps < n
    return (jnp.where(on, row, -1).astype(jnp.int32),
            jnp.where(on, page, 0).astype(jnp.int32), n)


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
    NF = min(S, (S - 1) // BS + 2)
    HB, P, QB, SGp, vmem = _plan(SG=SG, Hkv=Hkv, BS=BS, D=D, nb=nb,
                                 q_dtype=q.dtype, pool_dtype=k_pool.dtype,
                                 NF=NF if fuse else 0, quant=quant)
    TK = P * BS
    nblocks = -(-nb // P)

    # fold GQA groups into query rows: row r = s*G + g <-> kv head h
    qf = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, SG, D)
    positions = positions.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    maxp = jnp.max(positions, axis=1)                               # (B,)
    minp = jnp.min(jnp.where(positions >= 0, positions, jnp.int32(2 ** 30)),
                   axis=1)                                          # (B,)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    # a row's queries are one run of consecutive positions: entry j sits
    # at j + qoff (the first valid entry at minp)
    first = jnp.argmin(jnp.where(positions >= 0, positions, 2 ** 30),
                       axis=1).astype(jnp.int32)
    qoff = minp - first
    qpos = jnp.repeat(positions, G, axis=1)                         # (B, SG)
    if SGp != SG:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, SGp - SG), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, SGp - SG)), constant_values=-1)
    srow, spage, n = _schedule(maxp, minp, win[0], BS=BS, nb=nb)
    # each query tile's least and greatest valid position (-1 when the
    # whole tile is padding)
    tiles = qpos.reshape(B, SGp // QB, QB)
    qhi = jnp.max(tiles, axis=2)
    qlo = jnp.min(jnp.where(tiles >= 0, tiles, 2 ** 30), axis=2)

    def row_map(g, s, srow, *_):
        return (srow[s], g, 0, 0)

    def page_map(g, s, srow, spage, tab, *_):
        return (tab[srow[s], spage[s]], g, 0, 0)

    def fresh_map(g, s, srow, spage, tab, mx, mn, *_):
        # the pool output follows the row's fresh pages only, so the
        # pipeline writes back those and no other page; a padded row's
        # maps to its null entry (its layer's null block)
        r = srow[s]
        last = jnp.minimum(mx[r] // BS, nb - 1)
        page = jnp.maximum(spage[s], jnp.minimum(mn[r] // BS, last))
        return (tab[r, jnp.where(mx[r] >= 0, page, 0)], g, 0, 0)

    page_spec = pl.BlockSpec((1, HB, BS, D), page_map)
    in_specs = [pl.BlockSpec((1, HB, SGp, D), row_map)]
    ins = [qf]
    if quant and fuse:
        # quantize once out here with the shared reference recipe, so the
        # int8 rows merged in the walk — and the scales scattered here —
        # are bit-identical to ref.write_kv's
        from repro.kernels.paged_attention import ref as _ref
        k_new, ks = _ref.quantize_rows(k_new)
        v_new, vs = _ref.quantize_rows(v_new)
        k_scale = _ref.scatter_rows(k_scale, ks, positions, tables)
        v_scale = _ref.scatter_rows(v_scale, vs, positions, tables)
    if fuse:
        fp0 = jnp.where(maxp >= 0, minp // BS, 0)
        in_specs += [pl.BlockSpec((1, NF, 2, HB, BS, D),
                                  lambda g, s, sr, *_: (sr[s], 0, 0, g, 0, 0))]
        ins += [jnp.stack([
            _fresh_pages(x, first, minp, maxp, fp0, NF, BS, k_pool.dtype)
            for x in (k_new, v_new)], axis=2)]
    in_specs += [page_spec, page_spec]
    ins += [k_pool, v_pool]
    if quant:
        # each row's scales in logical key order, (B, Hkv, 1, keys): a
        # lane-dense strip the walk slices per block
        idx = jnp.pad(tables[:, :min(nblocks * P, MB)],
                      ((0, 0), (0, max(0, nblocks * P - MB))))

        def strip(sc):
            return sc[idx].transpose(0, 2, 1, 3).reshape(
                B, Hkv, 1, nblocks * TK)
        in_specs += [pl.BlockSpec((1, HB, 1, nblocks * TK), row_map)] * 2
        ins += [strip(k_scale), strip(v_scale)]

    out_specs = [pl.BlockSpec((1, HB, SGp, D), row_map)]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, SGp, D), q.dtype)]
    aliases = {}
    if fuse:
        out_specs += [pl.BlockSpec((1, HB, BS, D), fresh_map)] * 2
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # pools are updated in place: pages the walk does not write must
        # persist, so the pool inputs MUST alias the pool outputs.
        # Operand indices count the 9 scalar-prefetch operands:
        # [q, fresh pages] puts the pools at operands 11, 12 (outputs 1,
        # 2).
        aliases = {11: 1, 12: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(Hkv // HB, n),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((P, HB, BS, D), k_pool.dtype),
                        pltpu.VMEM((P, HB, BS, D), v_pool.dtype),
                        pltpu.VMEM((HB, SGp, 1), jnp.float32),
                        pltpu.VMEM((HB, SGp, 1), jnp.float32),
                        pltpu.VMEM((HB, SGp, D), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, fuse=fuse, quant=quant, BS=BS,
                               P=P, HB=HB, QB=QB, G=G, nb=nb,
                               softcap=softcap,
                               scale=D ** -0.5)
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # sequential: steps of one row share the softmax state, and
            # the staging buffer is zeroed by the first step only
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        # the custom call's HLO instruction, and so its event in the
        # profiler's device trace, is named after this, whatever the
        # functions around the call are called: tools that find the
        # kernel by name (its roofline in the benchmark) rely on it
        name=("paged_attention_update_pallas" if fuse
              else "paged_attention_pallas"),
    )(srow, spage, tables, maxp, minp, qoff, win, qlo, qhi, *ins)

    out = res[0][:, :, :SG].reshape(B, Hkv, S, G, D) \
        .transpose(0, 2, 1, 3, 4).reshape(B, S, H, D)
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
    (NB, Hkv, BS, D) pages as the walk reaches them, attending over the
    updated pages; only those pages are written.  A row's fresh rows are
    one run of consecutive positions (-1 pads the rest).  Returns
    (out (B, S, H, D), k_pool, v_pool).

    With ``k_scale``/``v_scale`` the pools are int8: the fresh rows are
    quantized with the shared reference recipe before the launch, their
    per-row scales scattered into the scale pools, the walk writes the
    int8 rows and dequantizes in fp32, and the return grows to
    (out, k_pool, v_pool, k_scale, v_scale).
    """
    return _call(q, k_new, v_new, k_pool, v_pool, block_tables, positions,
                 window=window, softcap=softcap,
                 max_live_blocks=max_live_blocks, interpret=interpret,
                 fuse=True, k_scale=k_scale, v_scale=v_scale)
