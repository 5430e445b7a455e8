"""Batched serving driver: prefill + decode loop with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 4 --prompt-len 32 --gen 32

``--engine`` selects the serving path: ``batch`` (static batched generate),
``legacy`` (per-slot continuous batching, ``repro.core.serving``), or
``paged`` (paged-KV fused continuous batching, ``repro.serving``).  The
paged engine runs the unified ragged tick by default — ONE dispatch per
tick over decodes + prefill chunks, capped by ``--token-budget`` (0 =
unbounded); ``--tick legacy`` restores the two-dispatch tick for
comparison (DESIGN.md §8).  ``--prefix-cache`` turns on automatic prefix
caching (DESIGN.md §9): ref-counted KV pages, content-hash prompt
matching, copy-on-write — identical token streams, shared prefixes
prefilled once.  ``--speculate`` (with ``--draft-k K``) turns on
self-speculative decoding (DESIGN.md §11): n-gram drafting + batched
verify in the same tick, byte-identical greedy streams, fewer ticks per
token on repetitive output.  ``--kv-dtype int8`` stores KV pages
quantized (per-row fp32 scales, dequant fused into the attention walk)
for ~2x page capacity at fixed pool bytes; ``--preempt swap`` parks a
preempted request's pages in host RAM and streams them back on resume
instead of recomputing, with ``--host-cache-pages N`` adding a host-RAM
spill tier for evicted prefix-cache pages (DESIGN.md §13).  ``--trace
PATH`` dumps the paged engine's telemetry
trace after the run (DESIGN.md §10): JSONL, or a Chrome trace_event
timeline when PATH ends in ``.json`` — summarize or validate it with
``tools/tracestats.py``.  The attention backend follows the platform: the
compiled Pallas block-table-walk kernel on a TPU; off a TPU the reference
gather, or the kernel in interpret mode under ``REPRO_USE_PALLAS=1`` — no
flags needed; the report's ``attention_backend`` field shows which one
served.

``--cluster NAME`` scales the paged engine out (DESIGN.md §7): the driver
creates a named cluster through the platform verbs (``create_cluster`` over
all visible devices, or ``--cluster-size N``) and serves the same trace
through ``Platform.serve_on_cluster`` — weights, attention heads, and the
KV page pool sharded tensor-parallel over the cluster mesh.  On a CPU host,
force a multi-device "cluster" with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--replicas N`` scales *out* instead (DESIGN.md §14): N identical paged
engines behind a ``ReplicaRouter`` — ``--routing affinity`` (default)
places each request on the replica whose page-digest caches already hold
its prompt prefix, with pool-pressure balancing as the fallback;
``--routing rr`` is the round-robin baseline.  Token streams stay
byte-identical to a single engine; the report carries the fleet rollup
plus per-replica engine reports, and ``--trace`` writes one merged JSONL
stream (``tools/tracestats.py`` splits and checks it per replica).
Composes with ``--cluster``: each replica is itself TP-sharded.

``--open-loop`` switches from pre-staged prompts to *open-loop* serving
(DESIGN.md §12): a seeded ``repro.serving.loadgen`` workload —
``--mix`` x ``--arrivals`` (``poisson``/``bursty``/``trace``, paced by
``--rate`` req/s or replayed from ``--trace-file``) — is served through
``ServingFrontend`` on the wall clock, arrivals admitted on the
generator's schedule (not the engine's), host admission overlapped with
the in-flight tick.  The report carries the SLO scorecard: p50/p99
TTFT, per-token latency, throughput vs goodput under ``--slo-ttft`` /
``--slo-tpot``.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --engine paged --open-loop --mix chat --arrivals poisson \
        --rate 20 --requests 32 --slo-ttft 0.5
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config, reduced
from repro.models import model as M


def generate(cfg, params, prompts: jnp.ndarray, gen: int, *,
             temperature: float = 0.0, key=None):
    """prompts: (B, S0) -> (B, S0+gen) greedy/temperature sampling."""
    B, S0 = prompts.shape
    cache = M.init_cache(cfg, B, S0 + gen)
    batch = {"tokens": prompts}
    if cfg.n_image_tokens:
        batch["image_embeds"] = jnp.zeros((B, cfg.n_image_tokens,
                                           cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.n_encoder_layers:
        batch["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                    jnp.dtype(cfg.dtype))
    hidden, cache = M.prefill_cached(cfg, params, batch, cache)
    from repro.models.layers import logits_from_hidden
    logits = logits_from_hidden(params, hidden[:, -1:], cfg)

    decode = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    toks = prompts
    n_prefix = cfg.n_image_tokens or 0
    key = key if key is not None else jax.random.PRNGKey(0)
    for i in range(gen):
        if temperature > 0:
            key, k = jax.random.split(key)
            nxt = jax.random.categorical(k, logits[:, -1] / temperature)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        nxt = nxt[:, None].astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt], axis=1)
        pos = jnp.asarray(n_prefix + toks.shape[1] - 1, jnp.int32)
        logits, cache = decode(params, cache, nxt, pos)
    return toks


def _run_engine(cfg, params, prompts, gen: int, engine: str,
                block_size: int, token_budget=None, unified: bool = True,
                prefix_cache: bool = False, trace=None,
                speculate: bool = False, draft_k: int = 4,
                kv_dtype: str = "fp", preempt: str = "recompute",
                host_cache_pages: int = 0, replicas: int = 1,
                routing: str = "affinity"):
    """Serve ``prompts`` through a continuous-batching engine."""
    max_slots = prompts.shape[0]
    max_seq = prompts.shape[1] + gen + 1
    if engine == "paged":
        from repro.serving import PagedServingEngine, ReplicaRouter

        def build(i):
            return PagedServingEngine(
                cfg, params, max_slots=max_slots, block_size=block_size,
                max_blocks_per_seq=-(-max_seq // block_size),
                token_budget=token_budget, unified=unified,
                prefix_cache=prefix_cache, speculate=speculate,
                draft_k=draft_k, kv_dtype=kv_dtype, preempt=preempt,
                host_cache_pages=host_cache_pages)

        eng = (ReplicaRouter(build, replicas, routing=routing)
               if replicas > 1 else build(0))
    else:
        from repro.core.serving import ServingEngine
        eng = ServingEngine(cfg, params, max_slots=max_slots,
                            max_seq=max_seq)
    for row in np.asarray(prompts):
        eng.submit(row, gen)
    results = eng.run_to_completion()
    # both engines expose the same metrics() schema (the legacy engine
    # pins paged-only sections to their "not applicable" shape), so the
    # report stays diffable field by field across --engine
    extra = eng.metrics()
    if trace is not None:
        extra["trace"] = {"path": str(trace),
                          "format": eng.dump_trace(trace)}
    return results, extra


def _run_openloop(cfg, params, args, token_budget, unified):
    """Serve a seeded open-loop workload through ``ServingFrontend`` on
    the wall clock; returns ``(results, extra)`` like the other paths,
    with the SLO scorecard under ``extra["open_loop"]``."""
    from repro.serving import (PagedServingEngine, ReplicaRouter,
                               ServingFrontend)
    from repro.serving.loadgen import build_workload
    wl = build_workload(mix=args.mix, arrivals=args.arrivals,
                        n=args.requests, seed=args.seed, vocab=cfg.vocab,
                        rate=args.rate, trace=args.trace_file)
    cap = max(r.prompt.size + r.max_new_tokens for r in wl) + 1

    def build(i):
        return PagedServingEngine(
            cfg, params, max_slots=args.batch,
            block_size=args.block_size,
            max_blocks_per_seq=-(-cap // args.block_size),
            token_budget=token_budget, unified=unified,
            prefix_cache=args.prefix_cache, speculate=args.speculate,
            draft_k=args.draft_k, kv_dtype=args.kv_dtype,
            preempt=args.preempt,
            host_cache_pages=args.host_cache_pages)

    eng = (ReplicaRouter(build, args.replicas, routing=args.routing)
           if args.replicas > 1 else build(0))
    fe = ServingFrontend(eng)
    fids = fe.submit_workload(wl)
    fe.drain()
    results = {fid: fe.result(fid).tokens for fid in fids}
    extra = eng.metrics()
    extra["open_loop"] = fe.report(slo_ttft_s=args.slo_ttft,
                                   slo_tpot_s=args.slo_tpot)
    extra["workload"] = {"mix": args.mix, "arrivals": args.arrivals,
                         "requests": len(wl), "seed": args.seed,
                         "rate_req_s": args.rate}
    if args.trace is not None:
        extra["trace"] = {"path": str(args.trace),
                          "format": eng.dump_trace(args.trace)}
    return results, extra


def _run_cluster(cfg, params, prompts, gen: int, cluster: str,
                 cluster_size: int, block_size: int, token_budget=None,
                 unified: bool = True, prefix_cache: bool = False,
                 trace=None, speculate: bool = False, draft_k: int = 4,
                 open_loop=None, kv_dtype: str = "fp",
                 preempt: str = "recompute", host_cache_pages: int = 0,
                 replicas: int = 1, routing: str = "affinity"):
    """Serve ``prompts`` through the paged engine sharded over a named
    cluster: ``create_cluster`` -> ``serve_on_cluster`` -> ``terminate``.
    With ``open_loop`` (a dict of loadgen/SLO kwargs) the cluster job
    serves a seeded open-loop workload through the front end instead of
    the pre-staged prompts."""
    import pathlib
    import shutil
    import tempfile

    from repro.core.platform import Platform
    ws = pathlib.Path(tempfile.mkdtemp(prefix="serve-ws-"))
    plat = Platform(ws)
    max_seq = prompts.shape[1] + gen + 1
    if open_loop is not None:
        from repro.serving.loadgen import MIXES
        m = MIXES[open_loop["mix"]]
        max_seq = m.shared_prefix + m.prompt[1] + m.gen[1] + 1
    try:
        n = cluster_size or plat.pool.total
        plat.create_cluster(cluster, n, model_axis=n,
                            description="serving cluster")
        handle = plat.serve_on_cluster(
            cluster, cfg, params,
            None if open_loop is not None else
            [(row, gen) for row in np.asarray(prompts)],
            open_loop=open_loop,
            max_slots=prompts.shape[0], block_size=block_size,
            max_blocks_per_seq=-(-max_seq // block_size),
            token_budget=token_budget, unified=unified,
            prefix_cache=prefix_cache, trace=trace,
            speculate=speculate, draft_k=draft_k, kv_dtype=kv_dtype,
            preempt=preempt, host_cache_pages=host_cache_pages,
            replicas=replicas, routing=routing)
        out = handle.result
        extra = dict(out["metrics"], devices=n, run=handle.runname)
        return out["results"], extra
    finally:
        if cluster in plat.clusters:
            plat.terminate_cluster(cluster)
        shutil.rmtree(ws, ignore_errors=True)  # throwaway CLI workspace


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine", choices=("batch", "legacy", "paged"),
                    default="batch")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size (paged engine)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-tick token cap for the unified ragged "
                         "dispatch (paged engine; 0 = unbounded packing)")
    ap.add_argument("--tick", choices=("unified", "legacy"),
                    default="unified",
                    help="paged engine tick: 'unified' fuses prefill + "
                         "decode into one dispatch (DESIGN.md §8); "
                         "'legacy' keeps the two-dispatch tick")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable automatic prefix caching (paged engine): "
                         "ref-counted pages, content-hash prompt matching, "
                         "copy-on-write (DESIGN.md \u00a79)")
    ap.add_argument("--speculate", action="store_true",
                    help="enable self-speculative decoding (paged engine): "
                         "n-gram drafting + batched verify, byte-identical "
                         "greedy streams (DESIGN.md \u00a711)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max draft tokens proposed per request per tick "
                         "(with --speculate)")
    ap.add_argument("--kv-dtype", choices=("fp", "int8"), default="fp",
                    help="KV page storage (paged engine): 'int8' stores "
                         "pages quantized with per-row fp32 scales — "
                         "~2x page capacity at fixed pool bytes "
                         "(DESIGN.md §13)")
    ap.add_argument("--preempt", choices=("recompute", "swap"),
                    default="recompute",
                    help="preemption policy (paged engine): 'swap' parks "
                         "the victim's KV pages in host RAM and streams "
                         "them back on resume instead of recomputing "
                         "(byte-identical streams; DESIGN.md §13)")
    ap.add_argument("--host-cache-pages", type=int, default=0,
                    help="host-RAM spill tier capacity, in pages, for "
                         "evicted prefix-cache pages (paged engine, with "
                         "--prefix-cache; 0 disables)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind a "
                         "ReplicaRouter (paged engine; DESIGN.md §14). "
                         "1 drives the engine directly")
    ap.add_argument("--routing", choices=("affinity", "rr"),
                    default="affinity",
                    help="replica placement (with --replicas > 1): "
                         "'affinity' probes each replica's page-digest "
                         "caches and falls back to pool-pressure "
                         "balancing under an anti-herd cap; 'rr' is the "
                         "round-robin baseline")
    ap.add_argument("--cluster", default=None, metavar="NAME",
                    help="serve sharded over a named cluster created via "
                         "the platform verbs (paged engine only)")
    ap.add_argument("--cluster-size", type=int, default=0,
                    help="devices in the cluster (default: all visible)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump the serving telemetry trace here after the "
                         "run (paged engine; DESIGN.md §10) — JSONL, "
                         "or Chrome trace_event when PATH ends in .json "
                         "(open in chrome://tracing or Perfetto)")
    ap.add_argument("--open-loop", action="store_true",
                    help="serve a seeded open-loop workload through "
                         "ServingFrontend instead of pre-staged prompts "
                         "(paged engine; DESIGN.md §12)")
    ap.add_argument("--mix", default="chat",
                    help="open-loop request-shape mix: chat, longdoc, "
                         "agents, or classify (repro.serving.loadgen)")
    ap.add_argument("--arrivals", choices=("poisson", "bursty", "trace"),
                    default="poisson",
                    help="open-loop arrival process (trace replays "
                         "--trace-file)")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrival rate, req/s (--arrivals poisson)")
    ap.add_argument("--requests", type=int, default=32,
                    help="open-loop workload size")
    ap.add_argument("--seed", type=int, default=0,
                    help="pins the random weights and prompts, and the "
                         "open-loop workload (arrivals AND request "
                         "content)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="arrival trace to replay (--arrivals trace): "
                         "one float per line, or JSONL with t/"
                         "prompt_len/max_new_tokens")
    ap.add_argument("--slo-ttft", type=float, default=None, metavar="S",
                    help="TTFT SLO in seconds for the goodput scorecard")
    ap.add_argument("--slo-tpot", type=float, default=None, metavar="S",
                    help="per-output-token SLO in seconds for the "
                         "goodput scorecard")
    args = ap.parse_args(argv)
    from repro.launch.bootstrap import enable_compile_cache
    enable_compile_cache()

    if args.engine != "batch" and args.temperature > 0:
        ap.error("--temperature is only supported with --engine batch "
                 "(the serving engines decode greedily)")
    if args.cluster is not None and args.engine != "paged":
        ap.error("--cluster requires --engine paged (the sharded path "
                 "is the paged engine)")
    if args.engine != "paged" and (args.token_budget or
                                   args.tick != "unified" or
                                   args.prefix_cache or args.speculate or
                                   args.kv_dtype != "fp" or
                                   args.preempt != "recompute" or
                                   args.host_cache_pages):
        ap.error("--token-budget/--tick/--prefix-cache/--speculate/"
                 "--kv-dtype/--preempt/--host-cache-pages are "
                 "paged-engine knobs")
    if args.trace is not None and args.engine != "paged":
        ap.error("--trace requires --engine paged (the telemetry spine "
                 "lives in the paged engine; DESIGN.md §10)")
    if args.open_loop and args.engine != "paged":
        ap.error("--open-loop requires --engine paged (the front end "
                 "serves over the paged engine; DESIGN.md §12)")
    if args.open_loop and args.arrivals == "trace" \
            and args.trace_file is None:
        ap.error("--arrivals trace needs --trace-file")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.engine != "paged":
        ap.error("--replicas/--routing require --engine paged (the "
                 "router fans out over paged engines; DESIGN.md §14)")
    if args.replicas > 1 and args.trace is not None \
            and args.trace.endswith(".json"):
        ap.error("merged multi-replica traces are JSONL-only; use a "
                 ".jsonl --trace path with --replicas > 1")
    token_budget = args.token_budget or None
    unified = args.tick == "unified"
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    key = jax.random.PRNGKey(args.seed)
    if args.engine == "paged":
        # a server holds its weights in the compute dtype from load
        from repro.serving.paged_attn import init_serving_params
        params = init_serving_params(cfg, key)
    else:
        params = M.init_params(cfg, key)
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    if args.engine == "batch":
        out = generate(cfg, params, prompts, args.gen,
                       temperature=args.temperature)
        n_tokens = args.batch * args.gen
        shape = list(out.shape)
        extra = {}
    elif args.cluster is not None:
        open_loop = None
        if args.open_loop:
            open_loop = dict(mix=args.mix, arrivals=args.arrivals,
                             n=args.requests, seed=args.seed,
                             rate=args.rate, trace=args.trace_file,
                             slo_ttft_s=args.slo_ttft,
                             slo_tpot_s=args.slo_tpot)
        results, extra = _run_cluster(cfg, params, prompts, args.gen,
                                      args.cluster, args.cluster_size,
                                      args.block_size, token_budget,
                                      unified, args.prefix_cache,
                                      args.trace, args.speculate,
                                      args.draft_k, open_loop=open_loop,
                                      kv_dtype=args.kv_dtype,
                                      preempt=args.preempt,
                                      host_cache_pages=args.host_cache_pages,
                                      replicas=args.replicas,
                                      routing=args.routing)
        n_tokens = sum(len(v) for v in results.values())
        shape = [len(results)]
    elif args.open_loop:
        results, extra = _run_openloop(cfg, params, args, token_budget,
                                       unified)
        n_tokens = sum(len(v) for v in results.values())
        shape = [len(results)]
    else:
        results, extra = _run_engine(cfg, params, prompts, args.gen,
                                     args.engine, args.block_size,
                                     token_budget, unified,
                                     args.prefix_cache, args.trace,
                                     args.speculate, args.draft_k,
                                     args.kv_dtype, args.preempt,
                                     args.host_cache_pages,
                                     args.replicas, args.routing)
        n_tokens = sum(len(v) for v in results.values())
        shape = [len(results)]
    wall = time.time() - t0
    report = {
        "arch": cfg.name, "engine": args.engine, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": args.gen,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(n_tokens / wall, 1),
        "output_shape": shape,
        **extra,
    }
    print(json.dumps(report, indent=1))
    if args.engine != "batch":
        # callers that check the streams get them; the print stays short
        report["results"] = results
    return report


if __name__ == "__main__":
    main()
