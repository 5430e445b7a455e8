"""Op boundary for paged-KV decode attention with a ``use_pallas`` switch.

The backend follows the platform (``repro.kernels.backend.resolve``): on
a TPU the compiled kernel runs, and only a caller's explicit
``use_pallas=False`` selects the jnp reference; off a TPU the reference
runs unless ``REPRO_USE_PALLAS=1`` (or ``use_pallas=True``) selects the
kernel, which then runs in interpret mode.  Callers
(``repro.serving.paged_attn``) only ever see the same signatures
regardless of backend:

    paged_attention(q, k_pool, v_pool, tables, positions, ...) -> out
    paged_attention_update(q, k_new, v_new, k_pool, v_pool, tables,
                           positions, ...) -> (out, k_pool, v_pool)
    paged_attention_unified(q, k_new, v_new, k_pool, v_pool, tables,
                            positions, row_map, ...)
                           -> (out, k_pool, v_pool)   # flat ragged tick
    copy_page(pool, src, dst) -> pool                 # COW primitive

With int8 pools (``kv_dtype="int8"``) every attention op also takes
``k_scale=``/``v_scale=`` ((NB, Hkv, BS) fp32 per-row scale pools) and
returns them updated: quantization is fused into the scatter, dequant
into the page walk, with a bit-identical recipe on both backends
(``ref.quantize_rows``).

The reference path is the live-length oracle in ``ref.py`` (update =
scatter via ``ref.write_kv`` then gather); the Pallas path walks block
tables in place with the scatter fused into the walk.

Both backends are shard-oblivious: on a cluster-sharded engine
(DESIGN.md §7) these ops run *inside* the step's ``shard_map``, so q and
the pools arrive already sliced to the shard's kv-head group —
``n_kv_heads`` here is the local head count and the kernel grid shrinks
with it.  Nothing in this module ever communicates across shards; the
psums/all-gather live in ``repro.serving.paged_attn``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.paged_attention import ref as _ref


def resolve(use_pallas: Optional[bool] = None,
            interpret: Optional[bool] = None) -> Tuple[bool, bool]:
    """Effective (use_pallas, interpret): the compiled kernel on a TPU,
    the environment's choice elsewhere (see the module docstring)."""
    return backend.resolve(use_pallas, interpret, tpu_default=True)


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_tables: jnp.ndarray, positions: jnp.ndarray, *,
                    window, softcap: float,
                    max_live_blocks: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Read-only paged attention.  q: (B, S, H, D) -> (B, S, H, D).

    ``k_scale``/``v_scale`` ((NB, Hkv, BS) fp32, present iff the pools are
    int8 — ``kv_dtype="int8"``) select the fused-dequant walk on either
    backend.
    """
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return _ref.paged_attention(q, k_pool, v_pool, block_tables,
                                    positions, window=window,
                                    softcap=softcap,
                                    max_live_blocks=max_live_blocks,
                                    k_scale=k_scale, v_scale=v_scale)
    from repro.kernels.paged_attention.kernel import paged_attention_pallas
    MB = block_tables.shape[1]
    live = MB if max_live_blocks is None else max_live_blocks
    return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                  positions, window=window, softcap=softcap,
                                  max_live_blocks=live, interpret=interpret,
                                  k_scale=k_scale, v_scale=v_scale)


def copy_page(pool: jnp.ndarray, src, dst, *,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Copy one physical page across all layers of a stacked (L, NB, ...)
    pool — the engine's copy-on-write primitive (a request about to
    scatter into a page the prefix cache still shares copies it first).

    ``src``/``dst`` are traced scalars, so one jit of the caller serves
    every copy.  Shard-oblivious like the attention ops: under a cluster
    plan the pool arrives kv-head sliced and each shard copies its own
    slice of the page.
    """
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return _ref.copy_page(pool, src, dst)
    from repro.kernels.paged_attention.kernel import copy_page_pallas
    return copy_page_pallas(pool, src, dst, interpret=interpret)


def paged_attention_update(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           positions: jnp.ndarray, *, window, softcap: float,
                           max_live_blocks: Optional[int] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None):
    """Scatter this step's fresh K/V, then attend.

    Returns (out (B, S, H, D), new k_pool, new v_pool).  On the Pallas path
    the scatter happens inside the kernel (one cache touch per layer); on
    the reference path it is ``ref.write_kv`` followed by the live-length
    gather.

    With ``k_scale``/``v_scale`` (int8 pools, ``kv_dtype="int8"``) the
    scatter quantizes the fresh rows, the walk dequantizes per page, and
    the return grows to (out, k_pool, v_pool, k_scale, v_scale) — both
    backends produce bit-identical quantized pools.
    """
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        if k_scale is not None:
            k_pool, v_pool, k_scale, v_scale = _ref.write_kv(
                k_pool, v_pool, k_new, v_new, positions, block_tables,
                k_scale, v_scale)
            out = _ref.paged_attention(q, k_pool, v_pool, block_tables,
                                       positions, window=window,
                                       softcap=softcap,
                                       max_live_blocks=max_live_blocks,
                                       k_scale=k_scale, v_scale=v_scale)
            return out, k_pool, v_pool, k_scale, v_scale
        k_pool, v_pool = _ref.write_kv(k_pool, v_pool, k_new, v_new,
                                       positions, block_tables)
        out = _ref.paged_attention(q, k_pool, v_pool, block_tables,
                                   positions, window=window, softcap=softcap,
                                   max_live_blocks=max_live_blocks)
        return out, k_pool, v_pool
    from repro.kernels.paged_attention.kernel import \
        paged_attention_update_pallas
    MB = block_tables.shape[1]
    live = MB if max_live_blocks is None else max_live_blocks
    return paged_attention_update_pallas(
        q, k_new, v_new, k_pool, v_pool, block_tables, positions,
        window=window, softcap=softcap, max_live_blocks=live,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)


def paged_attention_unified(q: jnp.ndarray, k_new: jnp.ndarray,
                            v_new: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, req_tables: jnp.ndarray,
                            positions: jnp.ndarray, row_map: jnp.ndarray, *,
                            window, softcap: float,
                            max_live_blocks: Optional[int] = None,
                            max_seg_len: int = 1,
                            use_pallas: Optional[bool] = None,
                            interpret: Optional[bool] = None,
                            k_scale: Optional[jnp.ndarray] = None,
                            v_scale: Optional[jnp.ndarray] = None):
    """Scatter + attend over a flat ragged token batch (the unified tick).

    Every flat row carries ONE token (q/k_new/v_new: (T, 1, ...),
    positions per row; rows of one request contiguous).  ``req_tables``
    (R, MB) int32 is each request's block-table row — per request, not
    per token, so a chunk never ships its table once per token — and
    ``row_map`` (R, max_seg_len) int32 is the flat index of each
    request's s-th token, dead entries pointing at a padded flat row
    (pos -1): the same ragged batch viewed per request.

    The walk is per REQUEST on both backends: q/k/v are gathered through
    ``row_map`` into a (R, max_seg_len) padded multi-query view and run
    through :func:`paged_attention_update` — one live-length page
    walk/gather per request with intra-chunk causal masking (for the
    Pallas backend that is the block-table-walk kernel over the R
    requests' own pages, fused scatter included).  Walking the flat
    rows directly would instead re-read every segment's pages once per
    token — chunk-width times the page traffic.

    Intra-chunk causality holds because a segment's fresh K/V rows are
    all scattered into their pages before (reference) or while (Pallas
    walk, before the page is attended) its queries attend, and the
    causal mask orders them.

    Segments are agnostic to what the tokens *are*: a prefill chunk and
    a speculative draft chain (DESIGN.md §11 — last accepted token +
    proposed continuation) pack identically.  The verifier just reads
    logits at every chain position instead of only the last one; the
    scatter-then-mask ordering above is exactly what lets the engine
    roll back a rejected tail by not advancing its fill mark — the
    stale K/V rows are overwritten by the next chain before any query
    can attend to them.

    Returns (out (T, 1, H, D), new k_pool, new v_pool) — plus the updated
    scale pools when ``k_scale``/``v_scale`` are given (int8 pools).
    """
    pos_req = jnp.take(positions.reshape(q.shape[0]), row_map, axis=0)
    gather = lambda a: jnp.take(a[:, 0], row_map, axis=0)  # noqa: E731
    res = paged_attention_update(
        gather(q), gather(k_new), gather(v_new), k_pool, v_pool,
        req_tables, pos_req, window=window, softcap=softcap,
        max_live_blocks=max_live_blocks, use_pallas=use_pallas,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    out_req = res[0]
    # route each padded-view output back to its flat row; dead map
    # entries all land on padded flat rows (garbage by design)
    out = jnp.zeros_like(q).at[row_map, 0].set(out_req)
    return (out,) + tuple(res[1:])
