"""PagedServingEngine: fused batched decode over a block-allocated KV cache.

Differences from the legacy ``repro.core.serving.ServingEngine``:

  * memory — KV lives in fixed-size pages owned per request through block
    tables; a finished request's pages recycle immediately instead of
    pinning a dense ``max_seq`` row.  Ownership is ref-counted: with
    ``prefix_cache=True`` full pages are content-hashed (token-chain
    digests), released pages park in a zero-ref LRU instead of the free
    list, and a new request whose prompt matches a cached chain attaches
    those pages by incref and prefills only the uncached tail — a shared
    system prompt is prefilled and stored ONCE no matter how many
    requests carry it.  Writes never mutate a shared page: the engine
    copies it on-device first (``ops.copy_page``, copy-on-write).
  * compute — every tick is ONE jitted ``unified_step`` dispatch over a
    flat ragged token batch (DESIGN.md §8): each active request
    contributes between 1 token (decoding) and ``prefill_chunk`` tokens
    (prefilling), packed with per-token slot/position vectors, so decodes
    and chunked prefills share a single launch and decode-bound steps
    never pay a separate prefill dispatch.  Logits are computed only at
    each request's last packed token.
  * admission — token-budget driven: the scheduler splits the tick's
    ``token_budget`` between phases (``FCFSScheduler.plan_tick``) —
    decoding requests always get their token, the remainder streams
    prompts in chunk-by-chunk in FCFS order, so long prompts never stall
    in-flight decodes.
  * scheduling — FCFS waiting queue with preemption when the page pool
    runs dry mid-decode: a victim (policy: evict-longest or evict-newest)
    releases its pages and is recomputed later; greedy decoding makes the
    recomputation token-exact.  Admission never preempts — a prefill that
    cannot get pages waits for in-flight requests to free them (preempting
    to admit livelocks a mutually-fitting pair of requests).
  * scale-out — pass ``mesh=`` (a platform Cluster or jax Mesh) to shard
    the weights, attention heads, and KV page pool tensor-parallel over
    the mesh's model axis: each tick becomes one ``shard_map`` dispatch,
    psum-reduced per sublayer with the logits all-gathered once per step
    (DESIGN.md §7).  Scheduling, allocation, and token streams are
    identical to the single-device engine.

Correctness contract (tested): a request served through this engine yields
exactly the tokens it would get from an isolated greedy ``generate``, under
ragged prompts, mid-flight admission, slot reuse, and preemption.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.obs import ServingTelemetry
from repro.serving import paged_attn
from repro.serving.blocks import (BlockAllocator, BlockTable, page_digest)
from repro.serving.scheduler import FCFSScheduler
from repro.serving.speculative import NGramDrafter

IDLE, PREFILL, DECODE = "idle", "prefill", "decode"


@dataclass
class PagedRequest:
    req_id: int
    prompt: np.ndarray                 # (S0,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    done: bool = False
    oom: bool = False                  # finished by pool/table exhaustion
    cancelled: bool = False            # aborted via cancel(), not completed

    def prefill_tokens(self) -> np.ndarray:
        """Tokens to (re)prefill.  Fresh: the prompt.  Preempted: prompt +
        all-but-last generated (the last generated token is fed by the
        next decode step, exactly as it would have been pre-preemption)."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated[:-1], np.int32)])


def _jit_entries(fn) -> int:
    """Programs in a jitted function's cache (a C++ count, no tracing);
    0 for a plain callable standing in for one."""
    size = getattr(fn, "_cache_size", None)
    return size() if size is not None else 0


class PagedServingEngine:
    """Continuous-batching serving engine over a paged KV cache.

    Construction compiles nothing; the first ``step()`` (or
    ``run_to_completion()``) triggers the jit.  Drive it either way:

        >>> eng = PagedServingEngine(cfg, params, max_slots=4)
        >>> rid = eng.submit(prompt_tokens, max_new_tokens=32)
        >>> results = eng.run_to_completion()      # {req_id: [token, ...]}

    or stream token-by-token via ``step()`` (returns ``{req_id: token}``
    per tick).  See ``docs/serving.md`` for the architecture walk-through.

    Args:
        cfg: a decoder-only attention ``ModelConfig`` (rwkv/ssm,
            encoder-decoder and image-prefix archs are rejected).
        params: the model's parameter pytree (``models.model.init_params``).
        max_slots: concurrent in-flight requests (batch rows per dispatch).
        block_size: tokens per KV page.
        max_blocks_per_seq: block-table width — the hard per-request cap is
            ``max_blocks_per_seq * block_size`` tokens (prompt + generated).
        num_blocks: page-pool size *including* the reserved null page; the
            default fits every slot's full table plus the null page.
        prefill_chunk: max prompt tokens prefetched per admitting slot per
            tick (long prompts stream in without stalling decodes).
        token_budget: cap on tokens packed into one unified dispatch.
            Decoding requests always fit (the effective floor is the
            decode count); the remainder is granted to prefilling
            requests in FCFS admission order (``FCFSScheduler.plan_tick``).
            ``None`` (default) packs every decode plus a full chunk per
            prefilling slot — the schedule the two-dispatch engine used.
        unified: ``True`` (default) runs the single-dispatch unified tick;
            ``False`` keeps the legacy two-dispatch tick (separate prefill
            and decode launches) — same token streams, kept for
            differential tests and benchmarking.
        prefix_cache: enable automatic prefix caching (DESIGN.md §9).
            Full pages are registered under token-chain content hashes as
            they fill; released pages park in a zero-ref LRU cache
            (evicted only under pool pressure), and admission matches
            each prompt against the hash chain — matched pages attach by
            incref, prefill starts after the cached prefix, and the
            scheduler's token budget is charged only for uncached
            tokens.  Shared pages are copy-on-write: before a request
            scatters into one, the engine copies it on-device
            (``ops.copy_page``).  Token streams are byte-identical with
            the cache on or off.  Default off.
        speculate: enable self-speculative decoding (DESIGN.md §11).
            Each decoding request keeps an n-gram index over its prompt +
            accepted tokens (:class:`~repro.serving.speculative
            .NGramDrafter`); per tick it proposes up to ``draft_k``
            continuation tokens which ride the unified dispatch as a
            multi-token chain, scored at every position
            (``verify_idx``).  The engine accepts the longest prefix the
            greedy argmax reproduces plus one bonus token — several
            tokens per request per dispatch on predictable text, and
            never a different stream: output is byte-identical to
            non-speculative greedy decoding.  Draft tokens are charged
            against ``token_budget`` after prefill chunks
            (``plan_tick``); rejected tails just rewind ``slot_filled``
            (their KV is overwritten before it is ever attendable).
            Default off.
        draft_k: max draft tokens proposed per request per tick (>= 1;
            only meaningful with ``speculate=True``).
        telemetry: ``True`` (default) attaches a
            :class:`repro.obs.ServingTelemetry` (DESIGN.md §10): one
            structured trace event per tick (dispatch kind, packed vs
            padded tokens, prefill/decode split, pool state, host vs
            device time), request lifecycle spans, and the latency
            histograms behind ``metrics()``'s p50/p99 fields — dump with
            :meth:`dump_trace`.  ``False`` disables all recording (the
            overhead-benchmark escape hatch; percentile fields become
            None).
        trace_capacity: tick-ring size — the trace keeps the newest
            ``trace_capacity`` ticks (spans: 8x that); older events are
            dropped and counted, never reallocated.
        preemption_policy: ``"longest"`` or ``"newest"`` — who gives pages
            back when the pool runs dry mid-decode (see ``FCFSScheduler``).
        kv_dtype: ``None``/``"fp"`` stores KV pages in the model dtype;
            ``"int8"`` quantizes pages symmetrically (DESIGN.md §13) with
            one fp32 scale per token row per kv head held in a parallel
            scale pool — quantization is fused into the scatter, dequant
            into the page walk on both backends, and a page costs
            ``head_dim + 4`` bytes per row per head instead of
            ``2 * head_dim`` (bf16), roughly doubling live requests at
            fixed pool bytes.  Token streams may differ from fp decoding
            (quantization error); kernel-vs-reference parity holds at the
            documented tolerance and pools are bit-identical across
            backends.
        preempt: what eviction does with a victim's pages (DESIGN.md
            §13).  ``"recompute"`` (default) frees them and re-prefills
            on re-admission; ``"swap"`` first snapshots the written pages
            to host RAM (``BlockAllocator.swap_out``) and re-admission
            streams the bytes back into freshly allocated pages
            (``swap_in``) instead of recomputing — byte-identical
            streams, no re-prefill compute.
        host_cache_pages: capacity (pages) of the digest-keyed host
            prefix cache: zero-ref cached pages evicted under pool
            pressure spill their bytes to host, and a later prefix match
            restores them into a fresh device page instead of
            re-prefilling.  0 (default) disables spilling.
        swap_pages_per_tick: soft cap on pages swapped in per tick
            (``preempt="swap"``): once a tick's restores reach the cap,
            further resumes wait for the next tick.  A single resume
            larger than the cap is still allowed (progress guarantee).
            ``None`` (default) = unbounded.
        live_block_quantum: floor for the static live-block bound before
            power-of-two bucketing (bounds jit retraces).
        use_pallas / interpret: attention backend override; ``None``
            follows the platform (``paged_attention.ops.resolve``): the
            compiled block-table-walk kernel on a TPU; off a TPU the jnp
            reference gather, or the kernel in interpret mode under
            ``REPRO_USE_PALLAS=1``.
        mesh: a ``jax.sharding.Mesh`` or a platform ``Cluster``
            (``Platform.create_cluster``) to shard the engine over.  With
            N > 1 devices on the mesh's model axis the weights, attention
            heads and KV page pool are partitioned tensor-parallel per
            ``sharding.serving_tp_plan`` and every step runs as one
            ``shard_map`` dispatch (the Pallas kernel executes per-shard;
            logits are all-gathered once per step).  Token streams are
            identical to the single-device engine.  ``None``: one device.

    The correctness contract (tested): every request yields exactly the
    tokens an isolated greedy ``generate`` would produce — under ragged
    prompts, mid-flight admission, slot/page reuse, preemption, and any
    cluster size.
    """

    def __init__(self, cfg, params, *, max_slots: int = 4,
                 block_size: int = 16,
                 max_blocks_per_seq: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: int = 16,
                 token_budget: Optional[int] = None,
                 unified: bool = True,
                 prefix_cache: bool = False,
                 speculate: bool = False,
                 draft_k: int = 4,
                 telemetry: bool = True,
                 trace_capacity: int = 4096,
                 preemption_policy: str = "longest",
                 kv_dtype: Optional[str] = None,
                 preempt: str = "recompute",
                 host_cache_pages: int = 0,
                 swap_pages_per_tick: Optional[int] = None,
                 live_block_quantum: int = 4,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 mesh=None,
                 clock=None):
        assert paged_attn.supports(cfg), \
            "paged engine needs a pure-attention decoder-only arch"
        # None follows the platform: the compiled kernel on a TPU
        from repro.kernels.paged_attention import ops as paged_ops
        self.use_pallas, self.interpret = paged_ops.resolve(use_pallas,
                                                            interpret)
        self.cfg = cfg
        self.max_slots = max_slots
        self.block_size = block_size
        # defaults sized like the legacy engine's (max_slots, 256) cache
        self.max_blocks = max_blocks_per_seq or -(-256 // block_size)
        self.num_blocks = num_blocks or max_slots * self.max_blocks + 1
        self.prefill_chunk = prefill_chunk
        if token_budget is not None and token_budget < 1:
            raise ValueError("token_budget must be >= 1 (or None for "
                             "unbounded packing)")
        self.token_budget = token_budget
        self.unified = unified
        self.prefix_cache = prefix_cache
        # KV capacity tiers (DESIGN.md §13): quantized pages + host swap
        if kv_dtype not in (None, "fp", "int8"):
            raise ValueError(f"kv_dtype must be None, 'fp' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = "int8" if kv_dtype == "int8" else "fp"
        if preempt not in ("recompute", "swap"):
            raise ValueError(f"preempt must be 'recompute' or 'swap', "
                             f"got {preempt!r}")
        self.preempt = preempt
        if swap_pages_per_tick is not None and swap_pages_per_tick < 1:
            raise ValueError("swap_pages_per_tick must be >= 1 or None")
        self.swap_pages_per_tick = swap_pages_per_tick
        # req_id -> (handle, phase, filled, chain) for swapped-out
        # requests waiting to stream their pages back in
        self._swap_handles: Dict[int, tuple] = {}
        self._tick_swap = [0, 0]       # [pages in, pages out] this tick
        # self-speculative decoding (DESIGN.md §11): n-gram drafts scored
        # in the same dispatch, accepted by exact greedy match
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        self.speculate = speculate
        self.draft_k = draft_k
        self.spec_drafted_total = 0    # draft tokens packed into dispatches
        self.spec_accepted_total = 0   # of those, accepted by the verify
        self.prefix_hit_tokens = 0     # prompt tokens served from the cache
        self.prefix_lookup_tokens = 0  # prompt tokens matched against it
        self.dispatches = 0            # trunk (step) launches issued so far
        # one clock drives telemetry AND scheduler stats (``clock=`` lets
        # the open-loop front end inject a virtual clock: arrivals, TTFT
        # and queue-wait then live on the same deterministic timeline)
        if clock is None:
            clock = time.perf_counter
        # observability spine (DESIGN.md §10): the scheduler feeds request
        # spans + latency histograms into it, step() one tick event
        self.telemetry = ServingTelemetry(enabled=telemetry,
                                          capacity=trace_capacity,
                                          clock=clock)
        # per-tick scratch, reset by step(): [packed, padded, prefill,
        # decode] token counts plus the fenced device-time window
        self._tick_pack = [0, 0, 0, 0]
        self._tick_spec = [0, 0]       # [drafted, accepted] this tick
        self._tick_device_s = 0.0
        self._tick_device_t0: Optional[float] = None
        # when this tick's first dispatch returned / its last sync
        # returned, and the programs its dispatches added to the jit cache
        self._tick_dispatch_t: Optional[float] = None
        self._tick_ready_t: Optional[float] = None
        self._tick_compiled = 0
        assert live_block_quantum >= 1
        self.live_block_quantum = live_block_quantum

        # cluster sharding: accept a platform Cluster or a raw Mesh; a
        # 1-device mesh collapses to the single-device path (same trace)
        self.mesh = getattr(mesh, "mesh", mesh)
        self.tp = None
        if self.mesh is not None:
            plan = sharding.serving_tp_plan(cfg, self.mesh)
            if plan.sharded:
                self.tp = plan

        # weights held in the compute dtype (exact; a no-op when the
        # caller loaded them so, as launch/serve.py does)
        params = paged_attn.hold_in_compute_dtype(cfg, params)
        self.params = params
        self.cache = paged_attn.init_paged_cache(cfg, self.num_blocks,
                                                 block_size,
                                                 kv_dtype=self.kv_dtype)
        kv_heads_per_shard = cfg.n_kv_heads
        if self.tp is not None:
            from jax.sharding import NamedSharding
            pspecs = sharding.serving_param_specs(params, self.tp)
            cspecs = sharding.serving_cache_specs(self.cache, self.tp)
            put = lambda tree, specs: jax.device_put(  # noqa: E731
                tree, jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), specs))
            self.params = put(params, pspecs)
            self.cache = put(self.cache, cspecs)
            self._shard_specs = (pspecs, cspecs)
            self._cache_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), cspecs)
            if self.tp.shard_attn:
                kv_heads_per_shard //= self.tp.size

        # per-shard pool accounting: each shard stores its kv-head slice of
        # every page, so N-way attention sharding divides per-device page
        # bytes by N (the headroom that lets a cluster raise num_blocks).
        # An int8 page costs 1 byte per element plus one fp32 scale per
        # token row per kv head; the fp baseline is kept beside it so
        # utilization() can report the capacity multiplier.
        fp_page_bytes = (2 * cfg.n_layers * block_size * kv_heads_per_shard
                         * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
        if self.kv_dtype == "int8":
            page_bytes = (2 * cfg.n_layers * block_size * kv_heads_per_shard
                          * (cfg.head_dim + 4))
        else:
            page_bytes = fp_page_bytes
        self.alloc = BlockAllocator(
            self.num_blocks, block_size,
            num_shards=self.tp.size if self.tp else 1,
            page_bytes_per_shard=page_bytes,
            kv_dtype=self.kv_dtype,
            fp_page_bytes_per_shard=fp_page_bytes,
            host_cache_pages=host_cache_pages)
        if host_cache_pages > 0:
            self.alloc.spill_hook = self._spill_page
        self.tables = [BlockTable(self.alloc, self.max_blocks)
                       for _ in range(max_slots)]
        self.scheduler = FCFSScheduler(preemption_policy=preemption_policy,
                                       clock=clock,
                                       telemetry=self.telemetry)
        # in-flight tick handle (step_begin/step_end split, DESIGN.md §12)
        self._pending = None
        self.slot_req: List[Optional[PagedRequest]] = [None] * max_slots
        self.slot_phase = [IDLE] * max_slots
        self.slot_seq: List[Optional[np.ndarray]] = [None] * max_slots
        self.slot_filled = np.zeros(max_slots, np.int64)  # tokens in cache
        # per-slot token-chain digests of the full pages written (or
        # attached) so far — the prefix cache's registration cursor
        self.slot_chain: List[List[bytes]] = [[] for _ in range(max_slots)]
        # per-slot n-gram drafters (speculate=True): built at the
        # prefill->decode transition, extended with accepted tokens only,
        # dropped on preempt/finish (rebuilt from scratch on re-admission)
        self.slot_drafter: List[Optional[NGramDrafter]] = [None] * max_slots
        self.finished: Dict[int, PagedRequest] = {}
        self._next_id = 0
        self._null_row = np.zeros((self.max_blocks,), np.int32)

        def greedy_local(p, c, t, pos, bt, live):
            # fuse the argmax so only (B, S) token ids cross the
            # device->host boundary per tick, not (B, S, vocab) logits
            logits, c = paged_attn.paged_step(
                cfg, p, c, t, pos, bt, max_live_blocks=live,
                use_pallas=self.use_pallas, interpret=self.interpret,
                tp=self.tp)
            return jnp.argmax(logits[..., :cfg.vocab],
                              axis=-1).astype(jnp.int32), c

        def greedy_unified_local(p, c, buf, live, chm, vw):
            # the whole ragged tick arrives as ONE packed int32 buffer
            # (one host->device transfer per tick — per-array device_puts
            # cost more than the dispatch itself on small ticks); the
            # slicing below is free under jit.  Fused argmax as above, but
            # logits exist only at each request's verify rows (last packed
            # token + any draft-chain positions), so (R, vw) ids cross the
            # host boundary — never (T, vocab) logits.
            t, pos, vidx, rmap, tabs = self._unpack(buf, chm, vw)
            logits, c = paged_attn.unified_step(
                cfg, p, c, t, pos, tabs, rmap, vidx,
                max_live_blocks=live, max_seg_len=chm,
                use_pallas=self.use_pallas, interpret=self.interpret,
                tp=self.tp)
            return jnp.argmax(logits[..., :cfg.vocab],
                              axis=-1).astype(jnp.int32), c

        def cow_local(c, src, dst):
            # copy-on-write: duplicate page `src` over fresh page `dst`
            # across all layers before a shared page would be scattered
            # into.  src/dst are traced, so ONE jit serves every copy.
            # Generic over the cache dict, so int8 scale pools ride along.
            from repro.kernels.paged_attention import ops as cow_ops
            copy = lambda pool: cow_ops.copy_page(  # noqa: E731
                pool, src, dst, use_pallas=self.use_pallas,
                interpret=self.interpret)
            return {name: copy(pool) for name, pool in c.items()}

        if self.tp is None:
            greedy_step = greedy_local
            greedy_unified = greedy_unified_local
            cow_step = cow_local
        else:
            from functools import partial

            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            pspecs, cspecs = self._shard_specs
            rep = P(None, None)

            def greedy_step(p, c, t, pos, bt, live):
                # one shard_map per tick: every shard advances its local
                # kv heads / hidden slice; psums + the logits all-gather
                # happen inside paged_step.  Built under jit, so `live`
                # stays a static closure, and check_vma is off because the
                # replicated outputs are only provably so to us, not to
                # the rewriter (pallas calls are opaque to it).
                fn = shard_map(partial(greedy_local, live=live),
                               mesh=self.mesh,
                               in_specs=(pspecs, cspecs, rep, rep, rep),
                               out_specs=(rep, cspecs), check_vma=False)
                return fn(p, c, t, pos, bt)

            def greedy_unified(p, c, buf, live, chm, vw):
                # the unified tick under the same one-shard_map-per-tick
                # scheme: the packed batch buffer is replicated
                # (host-built), weights/pools enter as local slices
                fn = shard_map(partial(greedy_unified_local, live=live,
                                       chm=chm, vw=vw),
                               mesh=self.mesh,
                               in_specs=(pspecs, cspecs,
                                         *sharding.unified_batch_specs()),
                               out_specs=(P(None, None), cspecs),
                               check_vma=False)
                return fn(p, c, buf)

            def cow_step(c, src, dst):
                # page ids are global, each shard copies its kv-head slice
                fn = shard_map(cow_local, mesh=self.mesh,
                               in_specs=(cspecs, P(), P()),
                               out_specs=cspecs, check_vma=False)
                return fn(c, src, dst)

        # `live` is static: attention gathers/walks only that many blocks
        # per row, so decode cost tracks the tick's live maximum, not the
        # pool.  The cache is donated so the per-layer K/V scatter updates
        # pages in place instead of copying the whole pool every tick.
        self._step_fn = jax.jit(greedy_step, static_argnums=(5,),
                                donate_argnums=(1,))
        # unified tick: `live`, plus the packed-batch bucket implied by the
        # array shapes, plus the static max-segment bound `chm` (the Pallas
        # sibling-scatter unroll) and the verify width `vw` (always 1 when
        # speculate=False) — all power-of-two bucketed by the caller so
        # retraces stay logarithmic
        self._unified_fn = jax.jit(greedy_unified, static_argnums=(3, 4, 5),
                                   donate_argnums=(1,))
        # COW copies mutate the pools in place (donated) between ticks
        self._cow_fn = jax.jit(cow_step, donate_argnums=(0,))

        # host swap tier (DESIGN.md §13): batched device<->host page
        # copies.  Gather reads pages out (device->host snapshot before a
        # swap preemption / prefix spill); scatter streams them back into
        # freshly allocated pages on resume.  Page-count buckets are
        # padded to powers of two with the null page (id 0, garbage by
        # design) so retraces stay logarithmic in swap size.
        def swap_gather(c, idx):
            return {name: pool[:, idx] for name, pool in c.items()}

        def swap_scatter(c, idx, payload):
            return {name: c[name].at[:, idx].set(payload[name])
                    for name in c}

        self._swap_gather_fn = jax.jit(swap_gather)
        if self.tp is None:
            self._swap_scatter_fn = jax.jit(swap_scatter,
                                            donate_argnums=(0,))
        else:
            # pin the restored pools to the cluster layout: the scatter
            # is elementwise over the sharded kv-head dim, so this is
            # layout-preserving, never a reshard
            self._swap_scatter_fn = jax.jit(
                swap_scatter, donate_argnums=(0,),
                out_shardings=self._cache_shardings)

    @property
    def capacity_tokens(self) -> int:
        """Hard per-request cap: block-table width in tokens."""
        return self.max_blocks * self.block_size

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int) -> int:
        """Queue a request; returns its ``req_id``.

        ``prompt`` is a 1-D int32 token array (non-empty);
        ``max_new_tokens >= 1`` tokens will be generated greedily.
        Requests that provably cannot fit the block table or the page
        pool raise ``ValueError`` up front instead of truncating later.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the first "
                             "token is emitted from the prefill logits)")
        # the last generated token is emitted without being written back,
        # so a request touches exactly prompt + max_new - 1 cache slots
        written = prompt.size + max_new_tokens - 1
        if written > self.capacity_tokens:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) needs {written} cache slots, over "
                f"the per-request capacity {self.capacity_tokens} "
                f"(= max_blocks_per_seq * block_size); raise "
                f"max_blocks_per_seq")
        if -(-written // self.block_size) > self.num_blocks - 1:
            raise ValueError(
                f"request needs {-(-written // self.block_size)} pages "
                f"but the pool only has {self.num_blocks - 1}; raise "
                f"num_blocks")
        req = PagedRequest(self._next_id, prompt, max_new_tokens)
        self._next_id += 1
        self.scheduler.submit(req, prompt.size)
        return req.req_id

    def cancel(self, req_id: int) -> bool:
        """Abort a request wherever it currently lives.

        Waiting requests are pulled out of the scheduler queue; slot-held
        requests (prefilling, decoding, or mid-speculation) release their
        pages back to the pool — shared pages decref into the prefix
        cache, private ones onto the free list — and free the slot for
        the next admission.  The request lands in ``finished`` with
        ``cancelled=True`` and whatever tokens it had produced.  Returns
        True if the request was cancelled, False if it was unknown or
        already finished.  A slot-held request cannot be cancelled while
        a tick is in flight (its tokens are packed into the running
        dispatch) — call :meth:`step_end` first; waiting requests can be
        cancelled at any point.
        """
        for req in self.scheduler.waiting:
            if req.req_id == req_id:
                self.scheduler.waiting.remove(req)
                ent = self._swap_handles.pop(req_id, None)
                if ent is not None:
                    self.alloc.swap_discard(ent[0])
                req.done = req.cancelled = True
                self.finished[req_id] = req
                self.scheduler.on_cancel(req_id)
                return True
        for slot, req in enumerate(self.slot_req):
            if req is None or req.req_id != req_id:
                continue
            if self._pending is not None:
                raise RuntimeError(
                    f"cancel({req_id}): request holds slot {slot} and a "
                    f"tick is in flight; call step_end() before "
                    f"cancelling slot-held requests")
            req.done = req.cancelled = True
            self.tables[slot].release()
            self.finished[req_id] = req
            self.scheduler.on_cancel(req_id)
            self.slot_req[slot] = None
            self.slot_phase[slot] = IDLE
            self.slot_seq[slot] = None
            self.slot_filled[slot] = 0
            self.slot_chain[slot] = []
            self.slot_drafter[slot] = None
            return True
        return False

    @property
    def active(self) -> int:
        """Requests currently holding a slot (prefilling or decoding)."""
        return sum(r is not None for r in self.slot_req)

    @property
    def queue(self) -> List[PagedRequest]:
        """Snapshot of the waiting (not yet admitted) requests, FCFS."""
        return list(self.scheduler.waiting)

    def metrics(self) -> Dict[str, object]:
        """Point-in-time engine report: scheduler summary (TTFT/latency/
        throughput), block-pool utilization (with per-shard byte
        accounting), prefix-cache hit/evict/COW counters, attention
        backend, cluster plan, and OOM count."""
        hit = self.prefix_hit_tokens
        seen = self.prefix_lookup_tokens
        return {"scheduler": self.scheduler.summary(),
                "blocks": self.alloc.utilization(),
                # router balancing signal (DESIGN.md §14): identical keys
                # and semantics on both engines — queued requests, and
                # the fraction of usable capacity still free
                "queue_depth": len(self.scheduler.waiting),
                "free_page_fraction":
                    self.alloc.num_free / max(1, self.num_blocks - 1),
                "tick": "unified" if self.unified else "legacy",
                "token_budget": self.token_budget,
                # KV capacity tiers (DESIGN.md §13): pool quantization +
                # preemption mode; the per-tier page/byte accounting and
                # swap counters live under "blocks" (utilization())
                "kv_dtype": self.kv_dtype,
                "preempt": self.preempt,
                "swapped_requests_waiting": len(self._swap_handles),
                # automatic prefix caching (DESIGN.md §9): token-level hit
                # rate over everything admitted, plus the allocator's
                # page-level hit/evict/COW counters
                "prefix_cache": {
                    "enabled": self.prefix_cache,
                    "hit_tokens": hit,
                    "lookup_tokens": seen,
                    "hit_rate": hit / seen if seen else 0.0,
                    "page_hits": self.alloc.cache_hits,
                    "evictions": self.alloc.cache_evictions,
                    "cow_copies": self.alloc.cow_copies,
                    "cached_pages": self.alloc.num_cached},
                # self-speculative decoding (DESIGN.md §11): draft tokens
                # packed into dispatches vs accepted by the greedy verify
                "speculative": {
                    "enabled": self.speculate,
                    "draft_k": self.draft_k,
                    "drafted_tokens": self.spec_drafted_total,
                    "accepted_tokens": self.spec_accepted_total,
                    "accept_rate": (self.spec_accepted_total
                                    / self.spec_drafted_total
                                    if self.spec_drafted_total else 0.0)},
                # trunk launches issued so far: the unified tick pays ONE
                # per step; the legacy tick up to two (prefill + decode).
                # Rare COW page copies launch separately (cow_copies).
                "dispatches": self.dispatches,
                "attention_backend":
                    "pallas-interpret" if self.use_pallas and self.interpret
                    else "pallas" if self.use_pallas else "reference",
                "cluster": None if self.tp is None else {
                    "axis": self.tp.axis, "shards": self.tp.size,
                    "shard_attn": self.tp.shard_attn,
                    "shard_mlp": self.tp.shard_mlp,
                    "shard_vocab": self.tp.shard_vocab},
                # requests truncated because the pool ran dry with no
                # preemption victims left (capacity misfits are rejected
                # at submit, so this is pure pool contention)
                "oom_finished": sum(r.oom for r in self.finished.values()),
                # observability spine (DESIGN.md §10): trace occupancy,
                # token/padding totals, host vs device split, tick-wall
                # percentiles — dump the full trace with dump_trace()
                "telemetry": self.telemetry.summary()}

    # ------------------------------------------------------------------
    # slot lifecycle
    # ------------------------------------------------------------------
    def _finish(self, slot: int, *, oom: bool = False) -> None:
        req = self.slot_req[slot]
        req.done = True
        req.oom = oom
        self.tables[slot].release()
        self.finished[req.req_id] = req
        self.scheduler.on_finish(req.req_id)
        self.slot_req[slot] = None
        self.slot_phase[slot] = IDLE
        self.slot_seq[slot] = None
        self.slot_filled[slot] = 0
        self.slot_chain[slot] = []
        self.slot_drafter[slot] = None

    def _vacate_dry(self, slot: int) -> None:
        """Admission-dry giveback: a prefilling slot could not get pages
        (admission never preempts), so it returns what it holds and waits.
        Recorded as a ``vacate`` span — not a preemption, nothing was
        evicted — so the trace's admit counts stay balanced."""
        if self.telemetry.enabled:
            self.telemetry.span(self.slot_req[slot].req_id, "vacate",
                                self.telemetry.clock())
        self._vacate(slot)

    def _vacate(self, slot: int) -> None:
        """Give the slot's pages back and requeue its request (front)."""
        req = self.slot_req[slot]
        self.tables[slot].release()
        self.scheduler.requeue_front(req)
        self.slot_req[slot] = None
        self.slot_phase[slot] = IDLE
        self.slot_seq[slot] = None
        self.slot_filled[slot] = 0
        self.slot_chain[slot] = []
        self.slot_drafter[slot] = None

    def _preempt(self, slot: int) -> None:
        self.scheduler.on_preempt(self.slot_req[slot].req_id)
        if self.preempt == "swap":
            self._swap_out_slot(slot)
        self._vacate(slot)

    # ------------------------------------------------------------------
    # host swap tier (DESIGN.md §13)
    # ------------------------------------------------------------------
    def _pages_to_host(self, blocks: List[int]) -> Dict[str, np.ndarray]:
        """Snapshot the given pages' bytes (every pool, every layer) to
        host arrays — one batched gather, padded to a pow2 bucket."""
        n = len(blocks)
        nb = 1 << (n - 1).bit_length()
        idx = np.zeros(nb, np.int32)
        idx[:n] = blocks
        got = self._swap_gather_fn(self.cache, jnp.asarray(idx))
        return {name: np.asarray(arr[:, :n]) for name, arr in got.items()}

    def _pages_from_host(self, blocks: List[int],
                         payload: Dict[str, np.ndarray]) -> None:
        """Stream a host payload back into freshly allocated pages — one
        batched scatter; padding rows land on the null page (garbage by
        design)."""
        n = len(blocks)
        nb = 1 << (n - 1).bit_length()
        idx = np.zeros(nb, np.int32)
        idx[:n] = blocks
        pay = {}
        for name, arr in payload.items():
            full = np.zeros(arr.shape[:1] + (nb,) + arr.shape[2:],
                            arr.dtype)
            full[:, :n] = arr
            pay[name] = jnp.asarray(full)
        self.cache = self._swap_scatter_fn(self.cache, jnp.asarray(idx),
                                           pay)

    def _written_pages(self, slot: int) -> int:
        """Pages of ``slot`` holding written KV rows (the partial tail
        page counts; allocated-but-unwritten pages past it do not)."""
        return -(-int(self.slot_filled[slot]) // self.block_size)

    def _swap_out_slot(self, slot: int) -> None:
        """Park the slot's written pages on the host before ``_vacate``
        decrefs them; re-admission restores the bytes instead of
        recomputing (``preempt="swap"``)."""
        n = self._written_pages(slot)
        if n == 0:
            return
        req = self.slot_req[slot]
        payload = self._pages_to_host(self.tables[slot].blocks[:n])
        handle = self.alloc.swap_out(n, payload)
        self._swap_handles[req.req_id] = (
            handle, self.slot_phase[slot], int(self.slot_filled[slot]),
            list(self.slot_chain[slot]))
        self._tick_swap[1] += n
        if self.telemetry.enabled:
            self.telemetry.span(req.req_id, "swap_out",
                                self.telemetry.clock(), pages=n)

    def _swap_resume(self, slot: int, req: PagedRequest) -> bool:
        """Try to restore a swapped-out request into ``slot``: allocate
        its pages (admission never preempts), stream the host payload
        back, and resume exactly where it was vacated.  Returns False —
        leaving the handle parked — when the pool cannot provide the
        pages yet or the tick's swap budget is spent."""
        handle, phase, filled, chain = self._swap_handles[req.req_id]
        n = self.alloc.swap_pages(handle)
        cap = self.swap_pages_per_tick
        if cap is not None and self._tick_swap[0] > 0 \
                and self._tick_swap[0] + n > cap:
            return False     # budget spent; next tick (progress: a tick's
            #                  first resume always proceeds, however big)
        blocks: List[int] = []
        for _ in range(n):
            blk = self.alloc.allocate()
            if blk is None:
                if blocks:
                    self.alloc.free(blocks)
                return False
            blocks.append(blk)
        n_pages, payload = self.alloc.swap_in(handle)
        del self._swap_handles[req.req_id]
        self._pages_from_host(blocks, payload)
        tab = self.tables[slot]
        tab.blocks = blocks
        tab.shared = 0           # restored pages are private copies
        self.slot_req[slot] = req
        self.slot_phase[slot] = phase
        self.slot_seq[slot] = req.prefill_tokens()
        self.slot_filled[slot] = filled
        self.slot_chain[slot] = chain if self.prefix_cache else []
        if phase == DECODE and self.speculate:
            self._make_drafter(slot)
        self._tick_swap[0] += n_pages
        if self.telemetry.enabled:
            self.telemetry.span(req.req_id, "swap_in",
                                self.telemetry.clock(), pages=n_pages)
        return True

    def _spill_page(self, blk: int, digest: bytes) -> None:
        """Allocator spill hook: a zero-ref cached page is about to be
        evicted for reuse — keep its bytes in the digest-keyed host cache
        so a later prefix match can restore instead of re-prefilling."""
        self.alloc.host_put(digest, self._pages_to_host([blk]))
        self._tick_swap[1] += 1

    def _choose_victim_for(self, slot: int) -> Optional[int]:
        """Pick a preemption victim to relieve pool pressure on ``slot``
        (zero-block slots free nothing — preempting them is pure churn)."""
        candidates = [(s, r.req_id, len(self.tables[s].blocks))
                      for s, r in enumerate(self.slot_req)
                      if r is not None and s != slot
                      and self.tables[s].blocks]
        return self.scheduler.choose_victim(candidates)

    def _ensure_blocks(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``n_tokens``, evicting victims
        per the scheduler's policy while the pool is dry.

        Decode-growth only: admission/prefill must NOT preempt (see
        ``_prefill_tick``) — two requests that each fit the pool alone
        but not together would otherwise evict each other's pages
        forever without either reaching a decode step (livelock)."""
        while not self.tables[slot].ensure(n_tokens):
            victim = self._choose_victim_for(slot)
            if victim is None:
                return False
            self._preempt(victim)
        return True

    def _admit(self) -> None:
        for slot in range(self.max_slots):
            if self.slot_req[slot] is not None:
                continue
            req = self.scheduler.next_request()
            if req is None:
                return
            if req.req_id in self._swap_handles:
                # swapped-out request: stream its pages back instead of
                # recomputing.  On failure (pool dry / tick swap budget
                # spent) it keeps its place at the head of the line and
                # admission stops — FCFS order is preserved either way.
                if self._swap_resume(slot, req):
                    self.scheduler.on_admit(req.req_id)
                    continue
                self.scheduler.requeue_front(req)
                return
            self.slot_req[slot] = req
            self.slot_phase[slot] = PREFILL
            seq = req.prefill_tokens()
            self.slot_seq[slot] = seq
            self.slot_filled[slot] = 0
            self.slot_chain[slot] = []
            if self.prefix_cache:
                matched, chain, blocks = self._match_prefix(seq)
                if blocks:
                    # attach the cached prefix by incref: prefill (and the
                    # scheduler's token budget) covers only the tail
                    self.tables[slot].fork_from_prefix(blocks)
                    self.slot_filled[slot] = matched
                    self.slot_chain[slot] = chain
                    self.prefix_hit_tokens += matched
                self.prefix_lookup_tokens += int(seq.size)
            self.scheduler.on_admit(req.req_id)

    # ------------------------------------------------------------------
    # prefix cache (DESIGN.md §9)
    # ------------------------------------------------------------------
    def _match_prefix(self, seq: np.ndarray):
        """Walk ``seq``'s token-chain digests through the allocator's hash
        index: the longest run of full pages already resident (in use by
        another request or parked in the zero-ref cache) is the request's
        cached prefix.

        Returns ``(matched_tokens, chain, blocks)``.  At least one token
        is always left to prefill — the first generated token comes from
        the prompt's last logits, so a fully-cached prompt re-computes its
        final token into a copy-on-write page (the sub-page attach is the
        one place a *partial* shared page gets written).
        """
        bs = self.block_size
        chain: List[bytes] = []
        blocks: List[int] = []
        parent = b""
        for k in range(int(seq.size) // bs):
            digest = page_digest(parent, seq[k * bs:(k + 1) * bs])
            blk = self.alloc.lookup(digest)
            if blk is None and self.alloc.host_cache_pages > 0:
                blk = self._restore_host_page(digest)
            if blk is None:
                break
            chain.append(digest)
            blocks.append(blk)
            parent = digest
        matched = len(blocks) * bs
        if matched >= seq.size:
            matched = int(seq.size) - 1
            if len(blocks) >= self.num_blocks - 1:
                # degenerate full match that alone fills the whole pool:
                # the last-token recompute's transient COW page could
                # never be allocated (nothing free, nothing evictable —
                # this request would hold every usable page), so fall
                # back to a page-aligned match and re-prefill the last
                # page into a normally-allocated private page instead
                chain.pop()
                blocks.pop()
                matched = len(blocks) * bs
        return matched, chain, blocks

    def _restore_host_page(self, digest: bytes) -> Optional[int]:
        """Second-chance prefix hit: the digest's page was evicted from
        the device pool but its bytes were spilled to the host cache —
        restore them into a fresh device page, re-register the digest,
        and park the page zero-ref in the device LRU so the caller's
        ``fork_from_prefix`` attaches it like any other cached page.
        Returns None when the host tier misses too or the pool is dry."""
        payload = self.alloc.host_lookup(digest)
        if payload is None:
            return None
        blk = self.alloc.allocate()
        if blk is None:
            self.alloc.host_put(digest, payload)     # keep the bytes
            return None
        self._pages_from_host([blk], payload)
        self.alloc.register(blk, digest)
        self.alloc.decref([blk])     # -> zero-ref cached, attachable
        self._tick_swap[0] += 1
        return blk

    def _tokens_range(self, slot: int, a: int, b: int) -> np.ndarray:
        """Tokens written at positions [a, b) of ``slot`` — prefill tokens
        from ``slot_seq``, decode-written tokens from ``generated``."""
        seq = self.slot_seq[slot]
        if b <= seq.size:
            return seq[a:b]
        req = self.slot_req[slot]
        gen = np.asarray(req.generated, np.int32)
        # position p >= seq.size holds generated[p - prompt_size]
        tail = gen[seq.size - req.prompt.size:]
        return np.concatenate([seq, tail])[a:b]

    def _register_pages(self, slot: int) -> None:
        """Extend the slot's digest chain over pages that just became full
        and index them in the allocator (content-addressed, dedup'd) so
        later prompts can attach them."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        chain = self.slot_chain[slot]
        for k in range(len(chain), int(self.slot_filled[slot]) // bs):
            parent = chain[-1] if chain else b""
            digest = page_digest(parent,
                                 self._tokens_range(slot, k * bs,
                                                    (k + 1) * bs))
            chain.append(digest)
            self.alloc.register(self.tables[slot].blocks[k], digest)

    def _cow_writable(self, slot: int, a: int, b: int, *,
                      may_preempt: bool) -> bool:
        """Make positions [a, b) of ``slot`` safe to scatter into: any
        shared page in that range is copied on-device to a private page
        first (``ops.copy_page``), so the fused in-prologue scatter never
        mutates a page another table or the hash index can still read.

        Allocation of the private copy follows the caller's pressure
        policy: prefill/admission never preempts (``may_preempt=False`` —
        the caller vacates instead), decode growth may evict victims
        exactly like ``_ensure_blocks``.  Returns False when no page can
        be found."""
        tab = self.tables[slot]
        bs = self.block_size
        shared = tab.shared                # cow() shrinks it as we go
        for idx in range(a // bs, (b - 1) // bs + 1):
            if idx >= shared:
                break                      # shared pages are a prefix
            if not self.alloc.page_shared(tab.blocks[idx]):
                continue                   # already exclusively ours
            while True:
                new = self.alloc.allocate()
                if new is not None:
                    break
                if not may_preempt:
                    return False
                victim = self._choose_victim_for(slot)
                if victim is None:
                    return False
                self._preempt(victim)
            self.cache = self._cow_fn(self.cache,
                                      jnp.asarray(tab.blocks[idx], jnp.int32),
                                      jnp.asarray(new, jnp.int32))
            tab.cow(idx, new)
        return True

    # ------------------------------------------------------------------
    # speculative decoding (DESIGN.md §11)
    # ------------------------------------------------------------------
    def _make_drafter(self, slot: int) -> None:
        """(Re)build the slot's n-gram index over everything known to be
        in the stream — prompt plus every accepted token.  Called at the
        prefill->decode transition, including re-admissions after
        preemption (the drafter is dropped with the slot's pages)."""
        req = self.slot_req[slot]
        dr = NGramDrafter()
        dr.reset(np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)]))
        self.slot_drafter[slot] = dr

    def _propose(self, slot: int) -> List[int]:
        """The slot's draft proposal for this tick: up to ``draft_k``
        continuation tokens from its n-gram index, capped so that the
        guaranteed bonus token always has output room
        (``max_new_tokens``) and the chain's KV fits the block table."""
        dr = self.slot_drafter[slot]
        if dr is None:
            return []
        req = self.slot_req[slot]
        k = min(self.draft_k,
                req.max_new_tokens - len(req.generated) - 1,
                self.capacity_tokens - int(self.slot_filled[slot]) - 1)
        if k <= 0:
            return []
        return dr.draft(k)

    def _accept(self, slot: int, draft: List[int], ids: np.ndarray,
                emitted: Dict[int, object]) -> None:
        """Exact accept/rollback for one decode row.

        ``ids[j]`` is the greedy argmax after consuming chain position
        ``j`` (chain = last generated token + the draft).  The longest
        draft prefix the model reproduces is accepted, plus the bonus
        token ``ids[m]`` — exactly what one-token-at-a-time greedy
        decoding would have produced, token for token.  Rollback is
        free: ``slot_filled`` advances only over accepted positions, and
        the rejected tail's KV is overwritten by real tokens before any
        later query could attend to it (scatter-first writes + causal
        masking), with block tables untouched.  With ``draft == []``
        this is precisely the historical single-token decode unpack."""
        req = self.slot_req[slot]
        m = 0
        while m < len(draft) and int(ids[m]) == draft[m]:
            m += 1
        toks = list(draft[:m]) + [int(ids[m])]
        self.slot_filled[slot] += m + 1
        for t in toks:
            req.generated.append(t)
            self.scheduler.on_token(req.req_id)
        if self.speculate:
            if self.slot_drafter[slot] is not None:
                self.slot_drafter[slot].extend(toks)
            self._tick_spec[1] += m
            self.spec_accepted_total += m
            if draft and self.telemetry.enabled:
                self.telemetry.spec_accept_len.record(m)
            emitted[req.req_id] = toks
        else:
            emitted[req.req_id] = toks[0]
        self._register_pages(slot)
        if len(req.generated) >= req.max_new_tokens:
            self._finish(slot)

    # ------------------------------------------------------------------
    # fused dispatches
    # ------------------------------------------------------------------
    def _unpack(self, buf: jnp.ndarray, chm: int, vw: int):
        """Split the packed unified-tick buffer (see ``_unified_tick``'s
        layout comment) back into its typed views — free under jit."""
        R, MB = self.max_slots, self.max_blocks
        Tb = (buf.shape[0] - R * vw - R * chm - R * MB) // 2
        tokens = buf[:Tb]
        positions = buf[Tb:2 * Tb]
        off = 2 * Tb
        verify_idx = buf[off:off + R * vw].reshape(R, vw)
        row_map = buf[off + R * vw:off + R * vw + R * chm].reshape(R, chm)
        req_tables = buf[off + R * vw + R * chm:].reshape(R, MB)
        return tokens, positions, verify_idx, row_map, req_tables

    def _live_bound(self, positions: np.ndarray) -> int:
        """Static live-block bound for one tick: the deepest position any
        row touches decides how many logical blocks attention must walk.
        ``live`` is a static jit arg, so round it up (quantum floor, then
        next power of two) to keep retraces logarithmic in sequence
        length instead of one per crossed block boundary."""
        live = int(positions.max()) // self.block_size + 1
        live = max(live, self.live_block_quantum)
        return min(1 << (live - 1).bit_length(), self.max_blocks)

    def _fence_start(self) -> float:
        """Open this tick's device window (first dispatch pins its start)."""
        t = self.telemetry.clock()
        if self._tick_device_t0 is None:
            self._tick_device_t0 = t
        return t

    def _dispatched(self, fn, entries: int) -> None:
        """Telemetry after a dispatch returned: the tick's first dispatch
        time, and the programs the call added to ``fn``'s jit cache."""
        if self._tick_dispatch_t is None:
            self._tick_dispatch_t = self.telemetry.clock()
        self._tick_compiled += _jit_entries(fn) - entries

    def _synced(self, f0: float) -> None:
        """Telemetry after a sync returned: the host's wait since the
        dispatch began, and the tick's last sync time."""
        self._tick_ready_t = self.telemetry.clock()
        self._tick_device_s += self._tick_ready_t - f0

    def _run(self, tokens: np.ndarray, positions: np.ndarray,
             tables: np.ndarray) -> np.ndarray:
        """Legacy-tick dispatch: returns the (B, S) greedy next-token ids."""
        tel = self.telemetry
        fence = tel.enabled
        with tel.phase("dispatch"):
            if fence:
                t0 = self._fence_start()
                entries = _jit_entries(self._step_fn)
            next_tokens, self.cache = self._step_fn(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(tables),
                self._live_bound(positions))
            if fence:
                self._dispatched(self._step_fn, entries)
        self.dispatches += 1
        with tel.phase("sync"):
            out = np.asarray(next_tokens)   # blocks until the step is done
        if fence:
            self._synced(t0)
        return out

    def _prefill_tick(self):
        """Legacy tick path (``unified=False``) only — the unified tick
        folds this dispatch into ``_unified_tick``.

        One chunk of prefill for every admitting slot, fused.

        Returns ({req_id: first_token} for prefills completed this tick —
        the first generated token comes from prefill logits — and the set
        of slots that just became decodable; those sit out this tick's
        decode so each step() emits at most one token per request)."""
        emitted: Dict[int, int] = {}
        ready: set = set()
        C = self.prefill_chunk
        plan = []  # (slot, start, end)
        for slot, req in enumerate(self.slot_req):
            if req is None or self.slot_phase[slot] != PREFILL:
                continue
            seq = self.slot_seq[slot]
            start = int(self.slot_filled[slot])
            end = min(start + C, seq.size)
            if not self.tables[slot].ensure(end) \
                    or not self._cow_writable(slot, start, end,
                                              may_preempt=False):
                # pool dry: admission never preempts (livelock with a
                # mutually-fitting pair otherwise) — give back whatever
                # was allocated and wait for in-flight requests to free
                # pages; submit() guarantees the request fits eventually
                self._vacate_dry(slot)
                continue
            plan.append((slot, start, end))
        if not plan:
            return emitted, ready
        n_pf = sum(end - start for _, start, end in plan)
        tp = self._tick_pack   # legacy prefill pads every slot to a chunk
        tp[0] += n_pf
        tp[1] += self.max_slots * C
        tp[2] += n_pf
        tokens = np.zeros((self.max_slots, C), np.int32)
        positions = np.full((self.max_slots, C), -1, np.int32)
        tables = np.tile(self._null_row, (self.max_slots, 1))
        for slot, start, end in plan:
            n = end - start
            tokens[slot, :n] = self.slot_seq[slot][start:end]
            positions[slot, :n] = np.arange(start, end, dtype=np.int32)
            tables[slot] = self.tables[slot].as_row()
        next_tokens = self._run(tokens, positions, tables)
        for slot, start, end in plan:
            req = self.slot_req[slot]
            self.slot_filled[slot] = end
            self._register_pages(slot)
            if end < self.slot_seq[slot].size:
                continue  # more chunks to go
            self.slot_phase[slot] = DECODE
            ready.add(slot)
            if not req.generated:
                # first generated token comes from the prompt's last logits
                nxt = int(next_tokens[slot, end - start - 1])
                req.generated.append(nxt)
                emitted[req.req_id] = [nxt] if self.speculate else nxt
                self.scheduler.on_token(req.req_id)
            if len(req.generated) >= req.max_new_tokens:
                self._finish(slot)
            elif self.speculate:
                self._make_drafter(slot)
        return emitted, ready

    def _decode_tick(self, skip=frozenset()) -> Dict[int, object]:
        """Legacy tick path (``unified=False``) only — one fused decode
        dispatch: one token for every decoding slot (``skip``: slots whose
        prefill completed this very tick).  With ``speculate=True`` every
        decoding slot additionally packs its n-gram draft chain (no
        token budget on the legacy tick, so drafts are never throttled)
        and the accept runs over the per-position argmax ids."""
        emitted: Dict[int, object] = {}
        drafts: Dict[int, List[int]] = {}
        for slot, req in enumerate(self.slot_req):
            if req is None or self.slot_phase[slot] != DECODE \
                    or slot in skip:
                continue
            filled = int(self.slot_filled[slot])
            if self.speculate:
                prop = self._propose(slot)
                if prop and self._ensure_blocks(slot, filled + 1 + len(prop)) \
                        and self._cow_writable(slot, filled,
                                               filled + 1 + len(prop),
                                               may_preempt=True):
                    drafts[slot] = prop
                    continue
            if self.slot_filled[slot] >= self.capacity_tokens:
                self._finish(slot, oom=True)     # out of table bounds
            elif not self._ensure_blocks(slot, filled + 1) \
                    or not self._cow_writable(slot, filled, filled + 1,
                                              may_preempt=True):
                self._finish(slot, oom=True)     # pool dry, no victims
        decoding = [s for s, r in enumerate(self.slot_req)
                    if r is not None and self.slot_phase[s] == DECODE
                    and s not in skip]
        if not decoding:
            return emitted
        drafts = {s: d for s, d in drafts.items() if s in set(decoding)}
        drafted = sum(len(d) for d in drafts.values())
        W = 1
        if self.speculate:
            wmax = max(1 + len(drafts.get(s, ())) for s in decoding)
            W = min(1 << (wmax - 1).bit_length(), self.draft_k + 1)
        tp = self._tick_pack   # legacy decode pads every slot to the
        tp[0] += len(decoding) + drafted    # tick's chain width
        tp[1] += self.max_slots * W
        tp[3] += len(decoding) + drafted
        self._tick_spec[0] += drafted
        self.spec_drafted_total += drafted
        tokens = np.zeros((self.max_slots, W), np.int32)
        positions = np.full((self.max_slots, W), -1, np.int32)
        tables = np.tile(self._null_row, (self.max_slots, 1))
        for slot in decoding:
            chain = ([self.slot_req[slot].generated[-1]]
                     + drafts.get(slot, []))
            n = len(chain)
            tokens[slot, :n] = chain
            positions[slot, :n] = np.arange(
                int(self.slot_filled[slot]),
                int(self.slot_filled[slot]) + n, dtype=np.int32)
            tables[slot] = self.tables[slot].as_row()
        next_tokens = self._run(tokens, positions, tables)
        for slot in decoding:
            self._accept(slot, drafts.get(slot, []), next_tokens[slot],
                         emitted)
        return emitted

    def _unified_launch(self) -> Optional[Dict[str, object]]:
        """Plan + pack + LAUNCH the unified tick without blocking on its
        result (the dispatch/collect split behind ``step_begin``/
        ``step_end``).  Returns the in-flight context for
        :meth:`_unified_collect`, or None when there was nothing to pack.
        """
        tel = self.telemetry
        with tel.phase("pack"):
            packed = self._unified_pack()
        if packed is None:
            return None
        buf, live, chm, vw, ctx = packed
        fence = ctx["fence"] = tel.enabled
        with tel.phase("dispatch"):
            if fence:
                ctx["f0"] = self._fence_start()
                entries = _jit_entries(self._unified_fn)
            ctx["next_tokens"], self.cache = self._unified_fn(
                self.params, self.cache, jnp.asarray(buf), live, chm, vw)
            if fence:
                self._dispatched(self._unified_fn, entries)
        self.dispatches += 1
        # next_tokens is still a device future here: the host is free
        # until _unified_collect's np.asarray sync — the open-loop front
        # end admits newly arrived requests in that window
        return ctx

    def _unified_pack(self):
        """Plan + pack the unified tick: returns (packed int32 buffer,
        live-block bound, segment bound, verify width, context for
        :meth:`_unified_collect`), or None when there is nothing to pack.

        ONE dispatch for the whole tick: decodes + prefill chunks packed
        into a flat ragged token batch under the scheduler's token split.

        Planning mirrors the two-dispatch tick exactly (prefill page
        growth first — vacate, never preempt, when the pool is dry; then
        decode growth, which may preempt per policy), so with
        ``token_budget=None`` the token streams are identical to the
        legacy tick's; the only difference is the launch count.

        With ``speculate=True`` (DESIGN.md §11) each decoding slot may
        additionally pack its n-gram draft chain: the scheduler grants
        draft budgets the way it grants prefill chunks (charged against
        ``token_budget`` after prompts; the one-token decode floor is
        untouched), the chain rides as a multi-token segment scored at
        every position via ``verify_idx``, and the unpack accepts the
        longest greedy-matching prefix plus one bonus token.
        """
        # -- prefill planning: scheduler splits the budget ---------------
        prefill_req = []
        for slot, req in enumerate(self.slot_req):
            if req is None or self.slot_phase[slot] != PREFILL:
                continue
            need = self.slot_seq[slot].size - int(self.slot_filled[slot])
            prefill_req.append((slot, req.req_id, need))
        decode_slots = [s for s, r in enumerate(self.slot_req)
                        if r is not None and self.slot_phase[s] == DECODE]
        # -- draft proposals: granted from the budget's leftovers --------
        drafts: Dict[int, List[int]] = {}
        if self.speculate and decode_slots:
            want = []
            for slot in decode_slots:
                prop = self._propose(slot)
                if prop:
                    drafts[slot] = prop
                    want.append((slot, self.slot_req[slot].req_id,
                                 len(prop)))
            grants, draft_grants = self.scheduler.plan_tick(
                self.token_budget, decode_slots, prefill_req,
                self.prefill_chunk, draft=want)
            drafts = {s: d[:draft_grants.get(s, 0)]
                      for s, d in drafts.items() if draft_grants.get(s, 0)}
        else:
            grants = self.scheduler.plan_tick(
                self.token_budget, decode_slots, prefill_req,
                self.prefill_chunk)
        plan = []  # (slot, start, end)
        for slot, _rid, _need in prefill_req:
            n = grants.get(slot, 0)
            if n <= 0:
                continue
            start = int(self.slot_filled[slot])
            if not self.tables[slot].ensure(start + n) \
                    or not self._cow_writable(slot, start, start + n,
                                              may_preempt=False):
                # pool dry: admission never preempts (livelock with a
                # mutually-fitting pair otherwise) — give back whatever
                # was allocated and wait for in-flight requests to free
                # pages; submit() guarantees the request fits eventually
                self._vacate_dry(slot)
                continue
            plan.append((slot, start, start + n))
        # -- decode planning: growth may preempt (incl. planned prefills) -
        for slot in decode_slots:
            if self.slot_req[slot] is None:
                continue                         # preempted by an earlier slot
            filled = int(self.slot_filled[slot])
            d = len(drafts.get(slot, ()))
            if d and not (self._ensure_blocks(slot, filled + 1 + d)
                          and self._cow_writable(slot, filled,
                                                 filled + 1 + d,
                                                 may_preempt=True)):
                # the chain doesn't fit: shrink the draft away before
                # giving up — a plain decode needs only one more slot
                drafts.pop(slot)
                d = 0
            if self.slot_filled[slot] >= self.capacity_tokens:
                self._finish(slot, oom=True)     # out of table bounds
            elif d == 0 and (
                    not self._ensure_blocks(slot, filled + 1)
                    or not self._cow_writable(slot, filled, filled + 1,
                                              may_preempt=True)):
                self._finish(slot, oom=True)     # pool dry, no victims
        plan = [(s, a, b) for s, a, b in plan
                if self.slot_req[s] is not None
                and self.slot_phase[s] == PREFILL]
        decoding = [s for s in decode_slots
                    if self.slot_req[s] is not None
                    and self.slot_phase[s] == DECODE]
        drafts = {s: d for s, d in drafts.items() if s in set(decoding)}
        if not plan and not decoding:
            return None
        # -- pack the flat ragged batch ----------------------------------
        # Tb always leaves at least one padded tail row: the per-request
        # view's dead row_map entries need a flat row whose output is
        # garbage by design (position -1, null table).  Buckets are
        # multiples of 4 capped at the pack's true maximum — pow2 buckets
        # would double the trunk exactly at the common saturated sizes
        # (every slot decoding, or every slot streaming a full chunk)
        drafted = sum(len(d) for d in drafts.values())
        seg = [1 + len(drafts.get(s, ())) for s in decoding]
        T = sum(seg) + sum(end - start for _, start, end in plan)
        row_cap = max(self.prefill_chunk,
                      1 + self.draft_k if self.speculate else 1)
        Tb = min(-(-(T + 1) // 4) * 4, self.max_slots * row_cap + 1)
        R, MB = self.max_slots, self.max_blocks
        chunk_max = max([end - start for _, start, end in plan] + seg or [1])
        chm = min(1 << (chunk_max - 1).bit_length(), Tb)
        # verify width: how many per-request positions need logits — 1
        # (the last packed token) without speculation, the longest draft
        # chain with it; pow2-bucketed like chm so retraces stay bounded
        vw = 1
        if self.speculate and drafts:
            vw = min(1 << (max(seg) - 1).bit_length(), self.draft_k + 1)
        # ONE packed int32 buffer carries the whole tick —
        #   [tokens | positions | verify_idx | row_map | req_tables]
        # — so each tick pays a single host->device transfer (per-array
        # device_puts dominate small ticks) and a single dispatch.  Block
        # tables ride per REQUEST row, never once per packed token.
        buf = np.zeros(2 * Tb + R * vw + R * chm + R * MB, np.int32)
        tokens = buf[:Tb]
        positions = buf[Tb:2 * Tb]
        positions[:] = -1
        verify_idx = buf[2 * Tb:2 * Tb + R * vw].reshape(R, vw)
        verify_idx[:] = T      # dead entries hit the padded tail row
        # per-request view of the same pack (attention walks pages once
        # per request); dead entries hit the padded tail row
        row_map = buf[2 * Tb + R * vw:2 * Tb + R * vw + R * chm] \
            .reshape(R, chm)
        row_map[:] = T
        req_tables = buf[2 * Tb + R * vw + R * chm:].reshape(R, MB)
        r = 0
        for slot in decoding:
            # the decode segment is the draft chain: last generated token
            # followed by the drafted continuation, at consecutive
            # positions — packed exactly like a prefill chunk
            chain = ([self.slot_req[slot].generated[-1]]
                     + drafts.get(slot, []))
            n = len(chain)
            tokens[r:r + n] = chain
            positions[r:r + n] = np.arange(
                int(self.slot_filled[slot]),
                int(self.slot_filled[slot]) + n, dtype=np.int32)
            req_tables[slot] = self.tables[slot].as_row()
            verify_idx[slot, :n] = np.arange(r, r + n, dtype=np.int32)
            row_map[slot, :n] = np.arange(r, r + n, dtype=np.int32)
            r += n
        for slot, start, end in plan:
            n = end - start
            tokens[r:r + n] = self.slot_seq[slot][start:end]
            positions[r:r + n] = np.arange(start, end, dtype=np.int32)
            req_tables[slot] = self.tables[slot].as_row()
            verify_idx[slot, 0] = r + n - 1
            row_map[slot, :n] = np.arange(r, r + n, dtype=np.int32)
            r += n
        self._tick_pack = [T, Tb, T - sum(seg), sum(seg)]
        self._tick_spec[0] += drafted
        self.spec_drafted_total += drafted
        return (buf, self._live_bound(positions), chm, vw,
                {"decoding": decoding, "drafts": drafts, "plan": plan})

    def _unified_collect(self, ctx: Optional[Dict[str, object]]
                         ) -> Dict[int, object]:
        """Sync the in-flight unified dispatch and unpack its results:
        accept decode/draft chains, advance prefill cursors, emit first
        tokens, finish/retire slots.  The blocking ``np.asarray`` here is
        the tick's only device sync."""
        emitted: Dict[int, object] = {}
        if ctx is None:
            return emitted
        decoding, drafts, plan = ctx["decoding"], ctx["drafts"], ctx["plan"]
        tel = self.telemetry
        with tel.phase("sync"):
            # (max_slots, vw) ids; blocks until the tick is done
            next_tokens = np.asarray(ctx["next_tokens"])
        if ctx["fence"]:
            self._synced(ctx["f0"])
        with tel.phase("unpack"):
            for slot in decoding:
                self._accept(slot, drafts.get(slot, []), next_tokens[slot],
                             emitted)
            for slot, start, end in plan:
                req = self.slot_req[slot]
                self.slot_filled[slot] = end
                self._register_pages(slot)
                if end < self.slot_seq[slot].size:
                    continue  # more chunks to go
                self.slot_phase[slot] = DECODE
                if not req.generated:
                    # first generated token comes from the prompt's last
                    # logits
                    nxt = int(next_tokens[slot, 0])
                    req.generated.append(nxt)
                    emitted[req.req_id] = [nxt] if self.speculate else nxt
                    self.scheduler.on_token(req.req_id)
                if len(req.generated) >= req.max_new_tokens:
                    self._finish(slot)
                elif self.speculate:
                    self._make_drafter(slot)
        return emitted

    # ------------------------------------------------------------------
    def step_begin(self) -> Dict[str, object]:
        """Admit + plan + pack + LAUNCH one tick without blocking on its
        result.  Pair with :meth:`step_end`, which syncs and unpacks.

        The window between the two calls is the open-loop front end's
        overlap slot (DESIGN.md §12): while the device executes the tick,
        the host is free to do admission-side work for tick N+1 —
        ``submit()`` (scheduler queue + telemetry spans only) is legal in
        the window; ``cancel()`` of a slot-held request is not, because
        its tokens are packed into the running dispatch (waiting-queue
        cancels are fine).  Only the unified tick overlaps: the legacy
        two-dispatch tick has an internal host sync between its prefill
        and decode launches, so ``unified=False`` runs the whole tick
        eagerly here and ``step_end`` just returns the stored result.

        Returns an opaque pending handle (also tracked on the engine, so
        ``step_end()`` can be called with no argument).  Calling
        ``step_begin`` again before ``step_end`` raises."""
        if self._pending is not None:
            raise RuntimeError("step_begin() called with a tick already "
                               "in flight; call step_end() first")
        tel = self.telemetry
        self._tick_spec = [0, 0]
        self._tick_swap = [0, 0]
        pend: Dict[str, object] = {"kind": "unified" if self.unified
                                   else "legacy"}
        if tel.enabled:
            self._tick_pack = [0, 0, 0, 0]
            self._tick_device_s = 0.0
            self._tick_device_t0 = None
            self._tick_dispatch_t = self._tick_ready_t = None
            self._tick_compiled = 0
            # pre-tick counter snapshot: the tick event carries this
            # tick's deltas, not running totals (totals live in the meta
            # record)
            pend["pre"] = (self.scheduler.preemptions_total,
                           self.alloc.cow_copies, self.prefix_hit_tokens,
                           self.dispatches, len(self.finished))
            pend["t0"] = tel.clock()
        with tel.phase("admit"):
            self._admit()
        if self.unified:
            pend["ctx"] = self._unified_launch()
        else:
            emitted, fresh = self._prefill_tick()
            emitted.update(self._decode_tick(skip=fresh))
            pend["emitted"] = emitted
        self._pending = pend
        return pend

    def step_end(self, pending: Optional[Dict[str, object]] = None
                 ) -> Dict[int, object]:
        """Sync + unpack the tick launched by :meth:`step_begin` and
        record its telemetry event.  Returns the tick's emitted tokens
        ({req_id: token} — see :meth:`step`)."""
        if pending is None:
            pending = self._pending
        if pending is None or pending is not self._pending:
            raise RuntimeError("step_end() without a matching "
                               "step_begin()")
        self._pending = None
        if "emitted" in pending:
            emitted = pending["emitted"]
        else:
            emitted = self._unified_collect(pending["ctx"])
        tel = self.telemetry
        if "t0" in pending:
            wall = tel.clock() - pending["t0"]
            with tel.phase("record"):
                pre = pending["pre"]
                in_use, cached, free = self.alloc.snapshot()
                pk = self._tick_pack
                n_emitted = (sum(len(v) for v in emitted.values())
                             if self.speculate else len(emitted))
                tel.record_tick(
                    t=pending["t0"], kind=pending["kind"], wall_s=wall,
                    device_s=self._tick_device_s,
                    device_t=self._tick_device_t0,
                    packed_tokens=pk[0], padded_tokens=pk[1],
                    prefill_tokens=pk[2], decode_tokens=pk[3],
                    drafted=self._tick_spec[0],
                    accepted=self._tick_spec[1],
                    emitted=n_emitted, live_slots=self.active,
                    waiting=len(self.scheduler.waiting),
                    pool_free=free, pool_cached=cached, pool_in_use=in_use,
                    prefix_hit_tokens=self.prefix_hit_tokens - pre[2],
                    preemptions=self.scheduler.preemptions_total - pre[0],
                    cow_copies=self.alloc.cow_copies - pre[1],
                    dispatches=self.dispatches - pre[3],
                    finished=len(self.finished) - pre[4],
                    swap_in=self._tick_swap[0], swap_out=self._tick_swap[1],
                    quant=self.kv_dtype == "int8",
                    dispatch_t=self._tick_dispatch_t,
                    ready_t=self._tick_ready_t,
                    compiled=self._tick_compiled)
        return emitted

    def step(self) -> Dict[int, object]:
        """Admit, then advance every in-flight request by up to one tick:
        one decode token per decoding slot and one prefill chunk per
        prefilling slot — fused into ONE dispatch on the default unified
        path (two on the legacy ``unified=False`` path).  Returns
        {req_id: new_token}, including first tokens emitted from completed
        prefills (unlike the legacy core engine, whose step() excludes
        them).  With ``speculate=True`` a decoding request can advance by
        several tokens per tick (accepted draft + bonus), so the values
        become token *lists*: {req_id: [token, ...]}.  With telemetry on,
        every step also records one structured tick event (DESIGN.md §10)
        — dump with :meth:`dump_trace`.  ``step()`` is exactly
        ``step_end(step_begin())``; callers that want to overlap host
        work with the device tick use the two halves directly."""
        return self.step_end(self.step_begin())

    def dump_trace(self, path, fmt: Optional[str] = None) -> str:
        """Write the telemetry trace to ``path`` with the current
        ``metrics()`` embedded as the meta record.  ``fmt``: ``"jsonl"``
        or ``"chrome"``; None picks by suffix (``.json`` -> Chrome
        trace_event for chrome://tracing / Perfetto, anything else ->
        JSONL).  Returns the format written.  Raises RuntimeError when
        the engine was built with ``telemetry=False`` (an empty dump
        would read as "nothing happened")."""
        if not self.telemetry.enabled:
            raise RuntimeError("engine was built with telemetry=False; "
                               "nothing was recorded to dump")
        return self.telemetry.dump(path, fmt=fmt, meta=self.metrics())

    def clear_finished(self) -> Dict[int, List[int]]:
        """Drop retained finished requests and their accounting; returns
        what was dropped.  Long-lived engines call this between waves —
        ``finished`` otherwise grows without bound."""
        out = {rid: r.generated for rid, r in self.finished.items()}
        for rid in self.finished:
            self.scheduler.forget(rid)
        self.finished.clear()
        return out

    def _state_fingerprint(self):
        """Hashable snapshot of every input the next tick's decisions
        read: waiting order, slot occupancy/phase/fill, generated
        lengths, finish count, pool counts.  The engine is deterministic
        given this state, so an emit-less step that leaves it unchanged
        can never make progress later — a loop that keeps stepping would
        spin forever (see :meth:`run_to_completion`)."""
        return (tuple(r.req_id for r in self.scheduler.waiting),
                tuple((r.req_id, self.slot_phase[s],
                       int(self.slot_filled[s]), len(r.generated))
                      for s, r in enumerate(self.slot_req)
                      if r is not None),
                len(self.finished), self.alloc.snapshot())

    def _raise_stuck(self, reason: str) -> None:
        stuck = sorted([r.req_id for r in self.slot_req if r is not None]
                       + [r.req_id for r in self.scheduler.waiting])
        raise RuntimeError(
            f"run_to_completion: {reason} with {self.active} active and "
            f"{len(self.scheduler.waiting)} waiting requests "
            f"(req ids {stuck}); a silent partial result is "
            f"indistinguishable from a complete one")

    def run_to_completion(self, max_steps: int = 10_000
                          ) -> Dict[int, List[int]]:
        """Drain queue + slots; returns every request finished so far —
        including ones submitted after the call starts.  Finished
        requests are retained until ``clear_finished()``.  Raises
        RuntimeError if work remains after ``max_steps`` (a silent
        partial result is indistinguishable from a complete one), or
        immediately when two consecutive emit-less steps leave the
        engine state fingerprint unchanged — zero admissible work (e.g.
        the pool externally exhausted) used to busy-spin the full step
        budget; determinism makes one repeated state a proof of
        livelock."""
        last_fp = None
        for _ in range(max_steps):
            if not self.scheduler.has_waiting and self.active == 0:
                break
            if self.step():
                last_fp = None
                continue
            fp = self._state_fingerprint()
            if fp == last_fp:
                self._raise_stuck("no step can make progress (every "
                                  "admissible slot is blocked)")
            last_fp = fp
        if self.scheduler.has_waiting or self.active:
            self._raise_stuck(f"step budget exhausted after {max_steps} "
                              f"steps; raise max_steps")
        return {rid: req.generated for rid, req in self.finished.items()}
