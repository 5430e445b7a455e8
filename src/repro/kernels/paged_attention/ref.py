"""Pure-jnp oracle for paged-KV decode attention (live-length contract).

Layout (vLLM-style): per layer the K/V cache is a pool of ``num_blocks``
pages of ``block_size`` tokens each —

    k_pool, v_pool : (num_blocks, n_kv_heads, block_size, head_dim)

kv-head-major, so one page of one kv head is a contiguous (block_size,
head_dim) tile — the block the Pallas kernel DMAs.  A request owns pages
through a block table (logical block -> physical page);
token position ``p`` lives at page ``table[p // bs]``, offset ``p % bs``.
Physical page 0 is the null block: padded rows write there and nothing
correct is ever read from it.

The oracle honours the same *live-length* contract as the Pallas kernel
(``kernel.py``): it gathers only the first ``max_live_blocks`` table entries
per row — the caller passes ``ceil((max_position + 1) / block_size)`` — so
its cost tracks actual sequence length, never pool capacity.  GQA is a
grouped reshape/einsum; repeated K/V are never materialised per query head.

Rows whose query position is -1 (padding) produce garbage-but-finite output
(a uniform average, exactly like a fully masked softmax); callers discard
those rows.

Unified ragged tick: ``unified_attention_update`` is the oracle for the
engine's single-dispatch flat token batch (every row one token, rows of a
request contiguous).  Scattering *all* fresh rows before the gather makes
intra-tick siblings visible through the ordinary causal mask, so the
oracle needs no segment bookkeeping — which is exactly what the Pallas
ragged kernel is validated against.  A speculative draft chain
(DESIGN.md §11) is just such a segment whose logits are read at every
position: scatter-before-gather plus causal masking is also what makes
the engine's rejected-tail rollback exact — stale rows a rejected draft
left in the pool sit strictly *after* every live fill mark, so no later
query can attend to them before they are overwritten.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def quantize_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization, one fp32 scale per (token row, kv head).

    x : (..., D) fresh K or V projections.
    Returns ``(q, scale)`` with ``q = clip(round(x / scale), -127, 127)``
    as int8 and ``scale = amax(|x|) / 127`` over the trailing head_dim.
    An all-zero row gets scale 0 and quantizes to zeros (guarded inverse).

    Scales are per *row*, not per page: a page fills incrementally across
    ticks, and row granularity lets each scatter quantize only its fresh
    tokens without revisiting (or re-scaling) rows already in the pool.
    Both backends share this exact fp32 recipe so int8 pools stay
    bit-identical between the reference scatter and the fused kernel.
    The scale is ``amax * const(1/127)`` rather than ``amax / 127``:
    XLA rewrites division by a constant into a reciprocal multiply in
    some fusion contexts but not others, and that 1-ulp wobble would
    break the cross-backend bit-identity of the scale pools.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows`: fp32 ``q * scale`` (broadcast over
    head_dim)."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def copy_page(pool: jnp.ndarray, src, dst) -> jnp.ndarray:
    """Copy one physical page across all layers (the COW primitive).

    pool : (L, NB, ...) stacked per-layer page pool (K or V)
    src/dst : scalar page ids (traced ints — one jit serves every copy)

    Returns the pool with page ``dst`` overwritten by page ``src`` in
    every layer.  The serving engine calls this before a request scatters
    into a page another table (or the prefix-cache hash index) still
    references, so shared pages are never mutated in place.
    """
    return pool.at[:, dst].set(pool[:, src])


def _slots(positions: jnp.ndarray, block_tables: jnp.ndarray, BS: int):
    """(page, offset) of every fresh row, flattened over (B, S).  Padded
    rows (position -1) are routed to the null block's first slot."""
    safe = jnp.maximum(positions, 0)
    phys = jnp.take_along_axis(block_tables, safe // BS, axis=1)  # (B, S)
    live = positions >= 0
    return (jnp.where(live, phys, 0).reshape(-1),
            jnp.where(live, safe % BS, 0).reshape(-1))


def scatter_rows(pool: jnp.ndarray, rows: jnp.ndarray,
                 positions: jnp.ndarray,
                 block_tables: jnp.ndarray) -> jnp.ndarray:
    """Write per-token rows into a kv-head-major pool (one layer).

    pool : (NB, Hkv, BS, ...) — a K/V pool (trailing D) or a scale pool
    rows : (B, S, Hkv, ...) matching trailing dims
    Real rows hit distinct slots because every position belongs to
    exactly one request; padded rows all land in the null block.
    """
    page, off = _slots(positions, block_tables, pool.shape[2])
    rows = rows.reshape(-1, *rows.shape[2:]).astype(pool.dtype)
    return pool.at[page, :, off].set(rows)


def write_kv(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
             k: jnp.ndarray, v: jnp.ndarray,
             positions: jnp.ndarray, block_tables: jnp.ndarray,
             k_scale: Optional[jnp.ndarray] = None,
             v_scale: Optional[jnp.ndarray] = None):
    """Scatter fresh K/V rows into their pages (one layer).

    k_pool/v_pool : (NB, Hkv, BS, D)
    k/v           : (B, S, Hkv, D) fresh projections
    positions     : (B, S) absolute token positions; -1 = padded row
    block_tables  : (B, MB) physical page ids
    k_scale/v_scale : (NB, Hkv, BS) fp32 per-row scale pools — present iff
                      the KV pools are int8-quantized (``kv_dtype="int8"``)

    With scale pools, the fresh rows are quantized *here* (fused into the
    scatter — the pool never holds fp rows) and the matching scales land
    in the same slots; returns a 4-tuple instead of 2.
    """
    if k_scale is not None:
        kq, ks = quantize_rows(k)
        vq, vs = quantize_rows(v)
        return (scatter_rows(k_pool, kq, positions, block_tables),
                scatter_rows(v_pool, vq, positions, block_tables),
                scatter_rows(k_scale, ks, positions, block_tables),
                scatter_rows(v_scale, vs, positions, block_tables))
    return (scatter_rows(k_pool, k, positions, block_tables),
            scatter_rows(v_pool, v, positions, block_tables))


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_tables: jnp.ndarray, positions: jnp.ndarray, *,
                    window: jnp.ndarray, softcap: float,
                    max_live_blocks: Optional[int] = None,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Attention over block-table-indexed pages (one layer).

    q : (B, S, H, D); positions (B, S) query positions (-1 = padded row).
    Returns (B, S, H, D).

    ``max_live_blocks`` bounds the gather: only the first that many table
    entries per row are read (the engine passes the tick's live maximum).
    ``None`` falls back to the full table width.  Entries past a row's own
    live length point at pages whose k_pos exceeds every valid query
    position, so the causal mask hides them either way.

    With ``k_scale``/``v_scale`` ((NB, Hkv, BS) fp32) the pools hold int8
    rows and the gather dequantizes in fp32 before the dot — fused into
    the page walk exactly like the Pallas kernel's page loop.
    """
    B, S, H, D = q.shape
    NB, Hkv, BS, _ = k_pool.shape
    G = H // Hkv
    MB = block_tables.shape[1]
    L = MB if max_live_blocks is None else max(1, min(int(max_live_blocks),
                                                      MB))
    tables = block_tables[:, :L]

    def pages(pool, scale):                 # (B, L*BS, Hkv, D) in q.dtype
        x = pool[tables] if scale is None else \
            dequantize(pool[tables], scale[tables])
        return x.transpose(0, 1, 3, 2, 4).reshape(
            B, L * BS, Hkv, D).astype(q.dtype)

    ck, cv = pages(k_pool, k_scale), pages(v_pool, v_scale)
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg * D ** -0.5, ck,
                   preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    k_pos = jnp.arange(L * BS)
    valid = k_pos[None, None, :] <= positions[:, :, None]        # (B, S, K)
    valid &= (positions[:, :, None] - k_pos[None, None, :]) < window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", prob.astype(cv.dtype), cv)
    return out.reshape(B, S, H, D)


def unified_attention_update(q: jnp.ndarray, k_new: jnp.ndarray,
                             v_new: jnp.ndarray, k_pool: jnp.ndarray,
                             v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                             positions: jnp.ndarray, *,
                             window: jnp.ndarray, softcap: float,
                             max_live_blocks: Optional[int] = None,
                             k_scale: Optional[jnp.ndarray] = None,
                             v_scale: Optional[jnp.ndarray] = None):
    """Oracle for the unified ragged tick: scatter everything, then gather.

    q/k_new/v_new carry one token per row ((T, 1, ...)); ``block_tables``
    is per row (the owning request's table) and ``positions`` (T, 1) with
    -1 marking padded rows.  Writing every fresh row into the pool *first*
    makes a prefilling token's intra-tick predecessors ordinary cache
    entries, and the causal mask does the rest — no segment bookkeeping.
    The per-token flat walk costs O(T · live) page gathers, so this is
    the validation oracle, never the serving path (the production op,
    ``ops.paged_attention_unified``, walks per request instead).

    With ``k_scale``/``v_scale`` (int8 pools) the return is a 5-tuple
    carrying the updated scale pools too.
    """
    if k_scale is not None:
        k_pool, v_pool, k_scale, v_scale = write_kv(
            k_pool, v_pool, k_new, v_new, positions, block_tables,
            k_scale, v_scale)
        out = paged_attention(q, k_pool, v_pool, block_tables, positions,
                              window=window, softcap=softcap,
                              max_live_blocks=max_live_blocks,
                              k_scale=k_scale, v_scale=v_scale)
        return out, k_pool, v_pool, k_scale, v_scale
    k_pool, v_pool = write_kv(k_pool, v_pool, k_new, v_new, positions,
                              block_tables)
    out = paged_attention(q, k_pool, v_pool, block_tables, positions,
                          window=window, softcap=softcap,
                          max_live_blocks=max_live_blocks)
    return out, k_pool, v_pool
