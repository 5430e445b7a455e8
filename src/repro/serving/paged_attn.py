"""Paged-KV attention ops: pooled cache, block-table scatter/gather, fused step.

Layout (vLLM-style): per layer the K/V cache is a pool of ``num_blocks``
pages of ``block_size`` tokens each —

    k, v : (L, num_blocks, n_kv_heads, block_size, head_dim)

(kv-head-major: one page of one kv head is the kernel's (BS, D) block).

A request owns pages through a block table (logical block -> physical page);
token position ``p`` of a request lives at page ``table[p // bs]``, offset
``p % bs``.  Physical page 0 is the null block (see ``blocks.NULL_BLOCK``):
padded rows write there and nothing correct is ever read from it.

The attention op boundary lives in ``repro.kernels.paged_attention`` and
has two backends behind one signature (the compiled kernel on a TPU, the
reference elsewhere unless ``REPRO_USE_PALLAS=1``; overridable per call):

  * reference — live-length gather: only the first ``max_live_blocks``
    table entries per row are materialised (the engine passes the tick's
    ``ceil((max position + 1) / block_size)``), GQA is a grouped einsum
    with no repeated K/V.  Cost tracks live sequence length, never pool
    capacity.
  * Pallas — a decode kernel that walks each request's block table
    in-place with online softmax, early-exits at the request's live block
    count, and fuses this step's K/V scatter into its prologue so decode
    touches the cache once per layer (no scatter-then-gather).

The boundary also carries ``ops.copy_page`` (reference ``.at[].set`` or a
small Pallas kernel), the engine's copy-on-write primitive: with the
prefix cache on (DESIGN.md §9) a shared page is copied to a private page
before any scatter would touch it, so the fused in-prologue scatter only
ever writes pages the request owns exclusively.

``paged_step`` runs the whole stacked layer scan for a batch of rows whose
positions differ per row — one fused dispatch per engine tick, regardless
of slot count.  It serves both roles:

    decode        : tokens (B, 1),  per-row positions
    chunked prefill: tokens (B, C), per-row position ranges, padded with -1

``unified_step`` is the engine's production tick (DESIGN.md §8): ONE
dispatch over a *flat ragged token batch* — every active request
contributes between 1 (decoding) and ``prefill_chunk`` (prefilling)
tokens, packed into per-token token/position vectors with a ``row_map``
naming the same pack request by request (block tables ride per request).  The trunk runs over
the flat batch (no padded request rows in the matmuls), attention walks
pages once per request through the row_map view, all fresh tokens scatter
into the paged KV in place, and the logits matmul runs only at each
request's *verify rows* (``verify_idx`` — the last packed token, plus
every draft-chain position when the engine speculates, DESIGN.md §11),
never over the whole batch.

Restricted to pure-attention decoder stacks (dense / moe families): paged
KV is meaningless for recurrent state (rwkv / ssm) and the engine excludes
encoder-decoder and image-prefix archs like the legacy engine does.

Cluster sharding (DESIGN.md §7): ``paged_step(..., tp=plan)`` runs the
same math as a shard_map body — weights/pools arrive as local slices per
``sharding.serving_param_specs``, the row-parallel ``wo`` products are
psum-reduced per sublayer, and the logits are computed as per-shard vocab
strips all-gathered once per step (``_sharded_logits``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import write_kv  # noqa: F401  (re-export)
from repro.models import moe as moe_lib
from repro.models.layers import (NEG_INF, apply_mlp, apply_norm, apply_rope,
                                 embed_tokens, logits_from_hidden,
                                 padded_vocab)
from repro.models.transformer import layer_windows
from repro.sharding import ServingTPPlan

Params = Dict[str, Any]


def supports(cfg) -> bool:
    """Paged KV applies to pure-attention decoder-only stacks."""
    return not (cfg.rwkv or cfg.parallel_ssm or cfg.n_encoder_layers
                or cfg.n_image_tokens)


def init_paged_cache(cfg, num_blocks: int, block_size: int,
                     dtype=None, kv_dtype: Optional[str] = None) -> Params:
    """Pooled paged KV cache for the full stack (block 0 = null block).

    ``kv_dtype="int8"`` stores pages quantized: the K/V pools become int8
    and the dict grows parallel fp32 per-row scale pools ``k_scale`` /
    ``v_scale`` of shape (L, NB, Hkv, BS) — one scale per (token row,
    kv head), written by the fused quantizing scatter and read by the
    fused-dequant page walk.  ``None`` (or ``"fp"``) keeps the model
    dtype — the original layout, byte-compatible with every existing
    caller.
    """
    assert supports(cfg), "paged cache needs a pure-attention decoder stack"
    if kv_dtype not in (None, "fp", "int8"):
        raise ValueError(f"kv_dtype must be None|'fp'|'int8', "
                         f"got {kv_dtype!r}")
    dt = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, num_blocks, cfg.n_kv_heads, block_size,
             cfg.head_dim)
    if kv_dtype == "int8":
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


# leaves the model uses in float32 (norms, the MoE router); every other
# weight is cast to ``cfg.dtype`` at each use
_F32_LEAVES = ("scale", "bias", "router")


def hold_in_compute_dtype(cfg, params: Params) -> Params:
    """Weights as a server holds them: every matrix in ``cfg.dtype``.

    Exact: each use in the paged step (and in ``generate``) already casts
    these weights to ``cfg.dtype``, so the token streams do not change,
    while a bf16 config holds half the bytes of float32 masters and the
    step no longer materialises a cast copy of them.  Leaves the model
    reads in float32 keep the param dtype.
    """
    dt = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x if getattr(path[-1], "key", None) in _F32_LEAVES
                         else x.astype(dt)),
        params)


def init_serving_params(cfg, key) -> Params:
    """Random weights from ``key``, held in ``cfg.dtype`` from the start
    (:func:`hold_in_compute_dtype`).  Init and cast are one jitted
    program, so the float32 masters never exist whole on the device."""
    from repro.models import model as M
    return jax.jit(lambda k: hold_in_compute_dtype(
        cfg, M.init_params(cfg, k)))(key)


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_tables: jnp.ndarray, positions: jnp.ndarray, *,
                    window: jnp.ndarray, softcap: float,
                    max_live_blocks: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attention over block-table-indexed pages (one layer).

    q : (B, S, H, D); positions (B, S) query positions (-1 = padded row).
    Returns (B, S, H, D).  Thin delegate to the kernel package's op
    boundary — see the module docstring for the two backends.
    """
    return paged_ops.paged_attention(q, k_pool, v_pool, block_tables,
                                     positions, window=window,
                                     softcap=softcap,
                                     max_live_blocks=max_live_blocks,
                                     use_pallas=use_pallas,
                                     interpret=interpret)


def _paged_layer(lp: Params, x: jnp.ndarray, cfg, *,
                 positions: jnp.ndarray, window: jnp.ndarray,
                 k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                 block_tables: jnp.ndarray,
                 max_live_blocks: Optional[int],
                 use_pallas: Optional[bool], interpret: Optional[bool],
                 tp: Optional[ServingTPPlan] = None,
                 row_map=None, max_seg_len: int = 1,
                 k_scale=None, v_scale=None):
    """One transformer layer over the paged cache (attn -> mlp/moe).

    Mirrors ``transformer.layer_body`` for the attention families, with the
    dense-cache insert/read swapped for the fused paged scatter+gather.

    Under a cluster plan (``tp``, inside shard_map) the head and hidden
    dims of the weights — and the pool's kv-head dim — are local slices;
    the row-parallel ``wo`` products are partial sums reduced by one psum
    per sublayer (Megatron-style, DESIGN.md §7).
    """
    B, S, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if tp is not None and tp.shard_attn:
        h, hkv = h // tp.size, hkv // tp.size
    xn = apply_norm(lp["ln1"], x)
    ap = lp["attn"]
    q = (xn @ ap["wq"].astype(xn.dtype)).reshape(B, S, h, hd)
    k = (xn @ ap["wk"].astype(xn.dtype)).reshape(B, S, hkv, hd)
    v = (xn @ ap["wv"].astype(xn.dtype)).reshape(B, S, hkv, hd)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    if row_map is None:
        res = paged_ops.paged_attention_update(
            q, k, v, k_pool, v_pool, block_tables, positions, window=window,
            softcap=cfg.attn_logit_softcap, max_live_blocks=max_live_blocks,
            use_pallas=use_pallas, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale)
    else:
        res = paged_ops.paged_attention_unified(
            q, k, v, k_pool, v_pool, block_tables, positions, row_map,
            window=window, softcap=cfg.attn_logit_softcap,
            max_live_blocks=max_live_blocks, max_seg_len=max_seg_len,
            use_pallas=use_pallas, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:
        out, k_pool, v_pool, k_scale, v_scale = res
    else:
        out, k_pool, v_pool = res
    attn_out = out.reshape(B, S, h * hd) @ ap["wo"].astype(x.dtype)
    if tp is not None and tp.shard_attn:
        attn_out = lax.psum(attn_out, tp.axis)
    x = x + attn_out

    xn = apply_norm(lp["ln2"], x)
    if cfg.moe is not None:
        ff, _ = moe_lib.apply_moe(lp["moe"], xn, cfg)
    else:
        ff = apply_mlp(lp["mlp"], xn, cfg.act)
        if tp is not None and tp.shard_mlp:
            ff = lax.psum(ff, tp.axis)
    return x + ff, k_pool, v_pool, k_scale, v_scale


def _sharded_logits(params: Params, x: jnp.ndarray, cfg,
                    tp: ServingTPPlan) -> jnp.ndarray:
    """Vocab-strip logits + the step's single all-gather (shard_map body).

    Each shard computes an (B, S, Vp/M) strip — against its local slice of
    an untied ``lm_head`` kernel, or a dynamic row slice of the (replicated)
    tied embedding table — then the full padded-vocab logits are gathered
    once.  Softcap and pad masking happen after the gather, in the exact
    order of ``logits_from_hidden`` (both are elementwise, so the result
    matches the single-device path).
    """
    Vp = padded_vocab(cfg.vocab)
    if cfg.tie_embeddings:
        shard = lax.dynamic_slice_in_dim(
            params["embed"]["table"], lax.axis_index(tp.axis) * (Vp // tp.size),
            Vp // tp.size, axis=0)             # (Vp/M, d)
        logits = x @ shard.astype(x.dtype).T
    else:
        logits = x @ params["lm_head"]["kernel"].astype(x.dtype)  # local strip
    logits = lax.all_gather(logits, tp.axis, axis=-1, tiled=True)
    if cfg.logit_softcap > 0.0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if Vp != cfg.vocab:
        logits = jnp.where(jnp.arange(Vp) >= cfg.vocab, NEG_INF, logits)
    return logits


def _stack(cfg, params: Params, cache: Params, tokens: jnp.ndarray,
           positions: jnp.ndarray, block_tables: jnp.ndarray, *,
           row_map, max_seg_len: int, max_live_blocks: Optional[int],
           use_pallas: Optional[bool], interpret: Optional[bool],
           tp: Optional[ServingTPPlan]
           ) -> Tuple[jnp.ndarray, Params]:
    """Embed + the stacked layer scan over the paged cache (shared trunk
    of ``paged_step`` and ``unified_step``).  Returns the final *un-normed*
    hidden states (B, S, d) and the new cache.

    The pools ride through the layer scan as a CARRY over one flat
    (L*NB, ...) page array, with each layer addressing its pages through
    offset block tables (table + i*NB).  Scanning them as per-layer xs
    instead would dynamic-slice and restack the whole pool every layer —
    an O(pool capacity) copy per tick that dwarfs the live-length
    attention.  As a carry, the scatter is an in-place loop-carry update
    and the gather touches only live pages.
    """
    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.rope_theta <= 0:  # learned absolute positions
        x = x + jnp.take(params["pos_embed"]["table"],
                         jnp.maximum(positions, 0), axis=0).astype(x.dtype)
    windows = layer_windows(cfg)
    L, NB = cache["k"].shape[:2]
    page_shape = cache["k"].shape[2:]
    kf = cache["k"].reshape(L * NB, *page_shape)
    vf = cache["v"].reshape(L * NB, *page_shape)
    quant = "k_scale" in cache
    # scale pools (int8 pages) ride the same flat-carry trick; None when
    # unquantized (an empty pytree — the scan carry structure still matches)
    ksf = cache["k_scale"].reshape(L * NB, *page_shape[:-1]) if quant \
        else None
    vsf = cache["v_scale"].reshape(L * NB, *page_shape[:-1]) if quant \
        else None

    def body(carry, scanned):
        h, kf, vf, ksf, vsf = carry
        lp, win, i = scanned
        h, kf, vf, ksf, vsf = _paged_layer(
            lp, h, cfg, positions=positions, window=win,
            k_pool=kf, v_pool=vf,
            block_tables=block_tables + i * NB,
            max_live_blocks=max_live_blocks,
            use_pallas=use_pallas, interpret=interpret,
            tp=tp, row_map=row_map, max_seg_len=max_seg_len,
            k_scale=ksf, v_scale=vsf)
        return (h, kf, vf, ksf, vsf), None

    (x, kf, vf, ksf, vsf), _ = lax.scan(
        body, (x, kf, vf, ksf, vsf),
        (params["layers"], jnp.asarray(windows), jnp.arange(L)))
    new = {"k": kf.reshape(cache["k"].shape),
           "v": vf.reshape(cache["v"].shape)}
    if quant:
        new["k_scale"] = ksf.reshape(cache["k_scale"].shape)
        new["v_scale"] = vsf.reshape(cache["v_scale"].shape)
    return x, new


def _logits(cfg, params: Params, x: jnp.ndarray,
            tp: Optional[ServingTPPlan]) -> jnp.ndarray:
    """Final norm + (possibly vocab-sharded) logits for (B, S, d) hidden."""
    x = apply_norm(params["final_ln"], x)
    if tp is not None and tp.shard_vocab:
        return _sharded_logits(params, x, cfg, tp)
    return logits_from_hidden(params, x, cfg)


def paged_step(cfg, params: Params, cache: Params, tokens: jnp.ndarray,
               positions: jnp.ndarray, block_tables: jnp.ndarray, *,
               max_live_blocks: Optional[int] = None,
               use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None,
               tp: Optional[ServingTPPlan] = None
               ) -> Tuple[jnp.ndarray, Params]:
    """Fused step over all rows: decode (S=1) or a prefill chunk (S=C).

    tokens          : (B, S) int32 (padded rows: anything)
    positions       : (B, S) int32 absolute positions, -1 for padded entries
    block_tables    : (B, MB) int32
    max_live_blocks : static bound on live logical blocks this tick —
                      ``ceil((max position + 1) / block_size)``; attention
                      cost scales with it, not with table width or pool size
    tp              : cluster tensor-parallel plan; when given the call must
                      run inside ``shard_map`` over ``tp.axis`` with params
                      and cache partitioned per ``sharding.serving_param_specs``
                      / ``serving_cache_spec`` (the engine does this) —
                      sublayer outputs are psummed and the logits are
                      all-gathered once per step

    Returns (logits (B, S, V_padded), new cache).  One dispatch advances
    every row by S tokens — per-token cost is flat in slot count, unlike
    the legacy engine's per-slot loop.
    """
    x, cache = _stack(cfg, params, cache, tokens, positions, block_tables,
                      row_map=None, max_seg_len=1,
                      max_live_blocks=max_live_blocks,
                      use_pallas=use_pallas, interpret=interpret, tp=tp)
    return _logits(cfg, params, x, tp), cache


def unified_step(cfg, params: Params, cache: Params, tokens: jnp.ndarray,
                 positions: jnp.ndarray, req_tables: jnp.ndarray,
                 row_map: jnp.ndarray, verify_idx: jnp.ndarray, *,
                 max_live_blocks: Optional[int] = None,
                 max_seg_len: int = 1,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 tp: Optional[ServingTPPlan] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    """ONE dispatch over the engine's flat ragged token batch (DESIGN.md §8).

    tokens      : (T,) int32 packed tokens — decoding requests contribute
                  one token plus up to ``draft_k`` speculative draft
                  tokens (DESIGN.md §11), prefilling requests a chunk;
                  padded tail: anything
    positions   : (T,) int32 absolute positions, -1 for padded entries
    req_tables  : (R, MB) int32 — each request row's block table (dead
                  rows: the null table); per request, never duplicated
                  per token
    row_map     : (R, max_seg_len) int32 — flat index of each request
                  row's s-th token, dead entries pointing at a padded flat
                  row (the per-request multi-query view the attention op
                  walks)
    verify_idx  : (R, W) int32 — per-request verify mask: the flat
                  indices at which this request needs next-token logits,
                  dead entries pointing at a padded flat row.  Prefill
                  rows use one live entry (their last packed token — the
                  historical ``last_idx``); a decode row carrying a
                  speculative draft chain lists EVERY chain position, so
                  one dispatch scores the whole chain for the engine's
                  accept/rollback.  The vocab matmul is O(R*W), never
                  O(T); ``W == 1`` reproduces the last-token-only tick
                  exactly.
    max_seg_len : static bound on segment length this tick (the largest
                  prefill chunk or draft chain packed); sizes the
                  per-request view
    tp          : as in :func:`paged_step` (runs inside the engine's
                  ``shard_map``; specs in ``sharding.unified_batch_specs``)

    Returns (logits (R, W, V_padded), new cache).  The trunk (embeddings,
    projections, MLP) runs over the FLAT batch — padded-to-chunk request
    rows never reach the matmuls — while the attention op walks pages per
    request; every new token's K/V is scattered in place and intra-chunk
    causality is handled by the attention op (see
    ``kernels.paged_attention.ops.paged_attention_unified``).  On a
    pure-decode tick (``max_seg_len == 1``) every flat row already is a
    whole request, so the tables are spread to per-token rows once and
    the per-layer gather machinery is skipped entirely.
    """
    if max_seg_len <= 1:
        tok_tables = jnp.zeros((tokens.shape[0], req_tables.shape[1]),
                               req_tables.dtype).at[row_map[:, 0]] \
                        .set(req_tables)
        x, cache = _stack(cfg, params, cache, tokens[:, None],
                          positions[:, None], tok_tables,
                          row_map=None, max_seg_len=1,
                          max_live_blocks=max_live_blocks,
                          use_pallas=use_pallas, interpret=interpret, tp=tp)
    else:
        x, cache = _stack(cfg, params, cache, tokens[:, None],
                          positions[:, None], req_tables,
                          row_map=row_map, max_seg_len=max_seg_len,
                          max_live_blocks=max_live_blocks,
                          use_pallas=use_pallas, interpret=interpret, tp=tp)
    # gather each request's verify rows BEFORE the vocab projection: the
    # logits matmul is the fat one, and only verify rows are consumed
    R, W = verify_idx.shape
    xv = jnp.take(x[:, 0], verify_idx.reshape(-1),
                  axis=0).reshape(R, W, -1)                # (R, W, d)
    return _logits(cfg, params, xv, tp), cache
