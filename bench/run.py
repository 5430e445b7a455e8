"""Run one benchmark cell once and print one JSON line.

    python bench/run.py --workload granite-3-2b.decode --seed 7 \
        --seconds 30 --trace 0

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/mixes/<traffic>.json``).  One process, on the chip it starts on:

1. set-up: the weights are made on the device from ``--seed`` by the
   configuration's architecture module (``bench/archs/<module>.py``, named
   by the file's ``bench_arch``, ``dense_gqa`` where it names none), the
   serving engine is built, every program shape the cell's traffic can
   reach is compiled or loaded from the persistent cache, and the
   clients are started (closed loop: each client's first request with a
   staggered output length, so completions are spread from the start;
   set-up ends once every first prompt is prefilled);
2. the window: ``--seconds`` of traffic through the program's
   ``ServingFrontend`` -> ``PagedServingEngine`` (unified tick) ->
   ``paged_attn.unified_step`` -> the Pallas paged kernel, every token
   timed when the front end makes it visible;
3. the check: a seeded sample of the requests the window finished, the
   longest among them, is run through the module's float32 reference;
   the widest gap by which a served token's reference logit lies below
   the reference's best must stay under the cell's limit
   (``bench/limits/<workload>.json``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and prints the per-layer metrics, each
read by its own reader in ``bench/metrics/<name>.py``.  The last line on
standard output is the result; the numbers compared, each beside its
limit, end it under ``checks`` and are repeated as the last lines on
standard error.

It exits non-zero and prints no result where JAX finds no TPU, fewer
chips than the cell asks for, or no program beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up counts from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402
from bench import window as win  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"       # fixed: the path is part of the key
BLOCK = 16                            # tokens per KV page
RAMP_TICKS = 10_000                   # set-up prefill ticks, at most
CHECK_TOKENS = 600                    # served tokens the check compares
CHECK_REQUESTS = 6                    # ... over at most this many requests
DEFAULT_ARCH = "dense_gqa"            # a configuration's module, unnamed


class BenchError(RuntimeError):
    """A run that cannot be measured: exit non-zero, print no result."""


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------
def load_manifest() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return json.loads(path.read_text())


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, bench: Path = BENCH) -> dict:
    path = bench / "configs" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no configuration file {path}")
    return json.loads(path.read_text())


def load_limits(workload: str, bench: Path = BENCH) -> dict:
    path = bench / "limits" / f"{workload}.json"
    if not path.is_file():
        raise BenchError(f"no limits file {path}")
    return json.loads(path.read_text())


def _load_module(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench: Path = BENCH):
    """The per-layer metric reader ``bench/metrics/<name>.py``: a module
    with ``read(run) -> float | None``."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    return _load_module(path, f"bench_metric_{name}")


def load_arch(name: str, bench: Path = BENCH):
    """The architecture module ``bench/archs/<name>.py``: weights,
    reference, program config, work counts and kernel names of one kind
    of model (``archs/dense_gqa.py`` lists what it gives)."""
    path = bench / "archs" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no architecture module {name!r} at {path}")
    return _load_module(path, f"bench_arch_{name}")


def arch_for(spec: dict, bench: Path = BENCH):
    """The module the configuration's file names (``bench_arch``)."""
    return load_arch(spec.get("bench_arch", DEFAULT_ARCH), bench)


def cell_metrics(manifest: dict, cell: dict, key: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest[key]
            if "workloads" not in m or cell["name"] in m["workloads"]]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def configure_cache() -> str:
    """JAX's persistent compilation cache: where the environment says, or
    a fixed directory in the checkout.  Every program is kept, however
    fast it compiled, so a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs compiled or loaded from the persistent cache (JAX's
    backend-compile event covers both)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def engine_settings(spec: dict, mix: dict) -> dict:
    """The configuration's engine settings, the mix's additions, and what
    follows from the mix's lengths."""
    es = dict(spec["engine"])
    es.update(mix.get("engine", {}))
    mb = -(-traffic.max_request_tokens(mix) // BLOCK)
    es["max_blocks_per_seq"] = mb
    es["live_block_quantum"] = mb
    return es


def _chunk_sizes(es: dict, mix: dict) -> List[int]:
    """Every prefill grant one request can get in a tick.  With no token
    budget a prompt streams whole chunks and then its remainder; a budget
    can cut a grant anywhere below the chunk."""
    C, B = es["prefill_chunk"], es.get("token_budget")
    if B is not None:
        return list(range(1, min(C, B) + 1))
    sizes = set()
    for p in traffic.length_grid(mix["prompt"]).tolist():
        sizes.add(p % C or C)
        if p > C:
            sizes.add(C)
    return sorted(sizes)


def reachable_shapes(es: dict, mix: dict) -> List[tuple]:
    """Every (flat bucket, segment bound) a tick of this cell can have.

    A tick packs ``n`` decode rows and ``k`` prefill grants (sizes from
    ``_chunk_sizes``) with ``k + n`` at most the slot count; a token
    budget leaves the grants ``budget - n`` tokens at most.  The engine
    rounds the flat batch up to a multiple of 4 (one padded row at least,
    capped at ``slots x chunk + 1``) and the longest segment up to a power
    of two (``engine._unified_launch``).  Ragged prompts or a budget reach
    many more shapes than whole chunks do; the set is exact for the mix,
    so set-up warms what the traffic can reach and nothing else."""
    R, C, B = es["max_slots"], es["prefill_chunk"], es.get("token_budget")
    cap = R * C + 1
    sizes = _chunk_sizes(es, mix)
    bits = lambda c: (c - 1).bit_length()          # noqa: E731
    top = max(bits(c) for c in sizes) + 1
    # reach[k][b]: sums of k grants whose longest is 2**b at most (b = 0:
    # no grant longer than one token), as a boolean vector over sums
    reach = [np.zeros((top, R * C + 1), bool) for _ in range(R + 1)]
    reach[0][0, 0] = True
    for k in range(R):
        for b in range(top):
            row = reach[k][b]
            if not row.any():
                continue
            for c in sizes:
                nb = max(b, bits(c))
                reach[k + 1][nb, c:] |= row[:R * C + 1 - c]
    shapes = set()
    for k in range(R + 1):
        for b in range(top):
            sums = np.flatnonzero(reach[k][b])
            for n in range(R + 1 - k):
                if k + n == 0:
                    continue
                room = None if B is None else max(0, B - n)
                for s in sums.tolist():
                    if room is not None and s > room:
                        break
                    T = n + s
                    tb = min(-(-(T + 1) // 4) * 4, cap)
                    shapes.add((tb, min(1 << b, tb)))
    return sorted(shapes)


def warm(engine, shapes) -> None:
    """Compile (or load) the unified tick for every reachable shape by
    driving the engine's own jitted tick with all rows padded, so no
    page is written."""
    import jax
    import jax.numpy as jnp
    R, MB = engine.max_slots, engine.max_blocks
    live = engine._live_bound(np.zeros(1, np.int64))
    for tb, chm in shapes:
        buf = np.zeros(2 * tb + R + R * chm + R * MB, np.int32)
        buf[tb:2 * tb] = -1                      # every position padded
        buf[2 * tb:2 * tb + R + R * chm] = tb - 1
        out, engine.cache = engine._unified_fn(
            engine.params, engine.cache, jnp.asarray(buf), live, chm, 1)
        jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
class Harness:
    """Drives the program's front end and times what it makes visible.

    Each token is stamped when the front end routes it (the time a
    streaming client would see it); each tick's segments are recorded at
    launch for the FLOP and byte counts.  Host spans around the calls
    into the program go into the profiler's trace when it runs."""

    def __init__(self, engine, mix: dict, seed: int, vocab: int,
                 annotate: bool):
        from repro.serving.frontend import ServingFrontend
        import jax
        harness = self
        self.mix = mix
        self.clock = time.perf_counter
        self.annotate = annotate
        self._ann = jax.profiler.TraceAnnotation
        self.tok_t: Dict[int, List[float]] = {}
        self.ticks: List[dict] = []
        self.owner: Dict[int, int] = {}          # front-end id -> client
        self.submit_t: Dict[int, float] = {}
        self.reached_t: Dict[int, float] = {}    # arrival reached engine

        class Frontend(ServingFrontend):
            def _route(self, emitted):
                now = harness.clock()
                for eid, v in emitted.items():
                    fr = self._by_engine.get(eid)
                    if fr is not None:
                        n = len(v) if isinstance(v, list) else 1
                        harness.tok_t.setdefault(fr.req_id, []).extend(
                            [now] * n)
                super()._route(emitted)

            def _pump_arrivals(self):
                before = set(self._by_engine)
                with harness.span("bench.pump"):
                    n = super()._pump_arrivals()
                now = harness.clock()
                for eid in set(self._by_engine) - before:
                    harness.reached_t[self._by_engine[eid].req_id] = now
                return n

        self.fe = Frontend(engine, clock=self.clock)
        self.seed, self.vocab = seed, vocab
        self.chunk = engine.prefill_chunk
        begin, end = engine.step_begin, engine.step_end

        def step_begin():
            t0 = harness.clock()
            with harness.span("bench.step_begin"):
                pend = begin()
            ctx = pend.get("ctx") or {}
            segs = [(int(engine.slot_filled[s]), int(engine.slot_filled[s])
                     + 1) for s in ctx.get("decoding", [])]
            segs += [(a, b) for _, a, b in ctx.get("plan", [])]
            emits = len(ctx.get("decoding", [])) + sum(
                1 for s, a, b in ctx.get("plan", [])
                if b >= engine.slot_seq[s].size)
            harness.ticks.append({"t0": t0, "segments": segs,
                                 "logits_rows": emits})
            return pend

        def step_end(pend=None):
            with harness.span("bench.step_end"):
                out = end(pend)
            if harness.ticks:
                harness.ticks[-1]["t1"] = harness.clock()
            return out

        engine.step_begin, engine.step_end = step_begin, step_end
        self.stream = traffic.requests(mix, seed, vocab)

    def span(self, name: str):
        if self.annotate:
            return self._ann(name)
        return _Null()

    def submit(self, req: traffic.Request, client: int, *,
               at: Optional[float] = None, max_new: Optional[int] = None):
        with self.span("bench.submit"):
            fid = self.fe.submit(req.prompt, max_new or req.max_new_tokens,
                                 at=at)
        self.owner[fid] = client
        self.submit_t[fid] = self.fe._reqs[fid].arrival_t
        return fid

    def take(self) -> traffic.Request:
        return next(self.stream)

    # -- closed loop --------------------------------------------------------
    def start_closed(self) -> None:
        """Every client sends its first request, which stands for a
        request ``(i + 0.5) / N`` of the way through its answer when the
        window opens: the whole prefill chunks of that progress ride in
        its prompt as the answer so far, and the progress is taken off its
        answer.  So the window opens on caches spread over the mix's
        depths, and completions spread out from the start.  Returns once
        every first prompt is prefilled (set-up)."""
        n = int(self.mix["clients"])
        history = traffic.rng_for(self.seed, "history")
        first = []
        for i in range(n):
            req = self.take()
            done = int(req.max_new_tokens * (i + 0.5) / n)
            h = done // self.chunk * self.chunk
            prompt = np.concatenate([req.prompt, history.integers(
                0, self.vocab, h, dtype=np.int32)])
            first.append(self.submit(
                traffic.Request(prompt, req.max_new_tokens - done), i))
        for _ in range(RAMP_TICKS):
            if all(self.tok_t.get(f) for f in first):
                return
            self.fe._round()
            self._refill_closed()
        raise BenchError("the first prompts did not prefill in set-up")

    def _refill_closed(self) -> None:
        while self.fe._fresh:
            fid, _ = self.fe._fresh.popitem()
            self.submit(self.take(), self.owner[fid])

    # -- open loop ----------------------------------------------------------
    def start_open(self, t_open: float, seconds: float) -> None:
        """Queue every arrival due in the window, from ``t_open``."""
        for req in self.stream:
            if req.t > seconds:
                return
            self.submit(req, -1, at=t_open + req.t)

    def run_window(self, seconds: float) -> tuple:
        """Serve for ``seconds``; returns the window's (open, close), the
        close being the end of the first round past the deadline."""
        closed = self.mix["loop"] == "closed"
        with self.span("bench.window"):
            t_open = self.clock()
            if not closed:
                self.start_open(t_open, seconds)
            while True:
                self.fe._round()
                if closed:
                    self._refill_closed()
                else:
                    self.fe._fresh.clear()
                now = self.clock()
                if now - t_open >= seconds:
                    return t_open, now


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def check_sample(harness: Harness, t_close: float, seed: int) -> List[int]:
    """Front-end ids of requests to compare, up to ``CHECK_TOKENS`` served
    tokens over at most ``CHECK_REQUESTS`` requests: of those the window
    finished, the deepest, then others in a seeded order; where they hold
    fewer tokens than that, requests still being served at the close, in
    the same way (every token they hold came from the timed path, and an
    answer of thousands of tokens outlasts a window)."""
    fe = harness.fe
    done, live = [], []
    for fid, fr in fe._reqs.items():
        if fr.cancelled or not fr.tokens:
            continue
        if fr.done and fr.finished_t is not None and fr.finished_t <= t_close:
            done.append(fid)
        elif not fr.done:
            live.append(fid)
    size = lambda f: len(fe._reqs[f].tokens)      # noqa: E731
    depth = lambda f: len(fe._reqs[f].prompt) + size(f)  # noqa: E731
    rng = traffic.rng_for(seed, "check")
    done.sort(key=lambda f: (-depth(f), f))
    live.sort(key=lambda f: (-depth(f), f))
    order = done[:1] + rng.permutation(done[1:]).astype(int).tolist()
    order += live[:1] + rng.permutation(live[1:]).astype(int).tolist()
    pick, served = [], 0
    for fid in order:
        if served >= CHECK_TOKENS or len(pick) >= CHECK_REQUESTS:
            break
        pick.append(int(fid))
        served += size(fid)
    return pick


def run_check(arch, model: dict, params, harness: Harness, pick: List[int],
              pad_to: int, low: Optional[str] = None) -> Dict[str, float]:
    """The widest gap over the sampled requests, by the architecture
    module's reference; with ``low`` also the control's (``control_gap``):
    the gap of the tokens a forward in that lower precision puts first,
    on the same prompts and served tokens."""
    fe = harness.fe
    out = {"widest_gap": 0.0, "served_tokens": 0, "short_requests": 0,
           "requests": len(pick)}
    if low is not None:
        out["control_gap"] = 0.0
    for fid in pick:
        fr = fe._reqs[fid]
        if fr.done and len(fr.tokens) != fr.max_new_tokens:
            out["short_requests"] += 1
        g = arch.served_gaps(model, params, fr.prompt, fr.tokens,
                             low=low, pad_to=pad_to)
        if low is not None:
            g, c = g
            out["control_gap"] = max(out["control_gap"], float(c.max()))
        out["widest_gap"] = max(out["widest_gap"], float(g.max()))
        out["served_tokens"] += len(fr.tokens)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(manifest: dict, cell: dict, seed: int, seconds: float,
             trace: bool, *, spec: Optional[dict] = None,
             mix: Optional[dict] = None, limits: Optional[dict] = None,
             t_process: float = T_PROCESS, fault=None,
             control: Optional[str] = None,
             peak: Optional[dict] = None, bench: Path = BENCH) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``spec``/``mix``/``limits`` default to the files the manifest names,
    found under ``bench`` with the architecture module, the metric
    readers and the peaks.
    ``fault`` (tests only) is called with the engine before the window to
    break the timed path; ``control`` (a float8 type)
    replaces the served tokens by those a reference forward in that
    precision puts first (the reference-side control); ``peak`` stands
    in for the chip's row of ``peaks.json``."""
    import jax
    from repro.serving.engine import PagedServingEngine

    spec = spec or load_config(cell["config"], bench)
    mix = mix or traffic.load_mix(cell["traffic"], bench / "mixes")
    limits = limits or load_limits(cell["name"], bench)
    model = spec["model"]
    arch = arch_for(spec, bench)
    dev = jax.devices()[0]
    counter = CompileCounter()

    phases = {}
    mark = [t_process]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - mark[0]
        mark[0] = now

    cfg = arch.program_config(spec)
    params = arch.make_params(model, seed)
    jax.block_until_ready(params)
    phase("start_and_weights_s")
    es = engine_settings(spec, mix)
    engine = PagedServingEngine(
        cfg, params, max_slots=es["max_slots"], block_size=BLOCK,
        max_blocks_per_seq=es["max_blocks_per_seq"],
        num_blocks=es["num_blocks"], prefill_chunk=es["prefill_chunk"],
        token_budget=es.get("token_budget"), kv_dtype=es["kv_dtype"],
        prefix_cache=es["prefix_cache"], speculate=es["speculate"],
        live_block_quantum=es["live_block_quantum"],
        trace_capacity=1 << 16)
    phase("engine_s")
    shapes = reachable_shapes(es, mix)
    warm(engine, shapes)
    phase(f"warm_{len(shapes)}_programs_s")
    if fault is not None:
        fault(engine)
    harness = Harness(engine, mix, seed, model["vocab"], annotate=trace)
    if mix["loop"] == "closed":
        harness.start_closed()
    phase("first_prompts_s")
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    compiles0 = counter.n
    t_setup = time.perf_counter() - t_process
    t_open, t_close = harness.run_window(seconds)
    compiles = counter.n - compiles0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    fe = harness.fe
    w = win.window_stats(harness.tok_t, harness.submit_t, t_open, t_close)
    attempted = sum(1 for fid, fr in fe._reqs.items()
                    if fr.engine_id is not None and
                    harness.submit_t[fid] <= t_close)
    failed = sum(1 for fr in fe._reqs.values() if fr.oom)
    tel_ticks = list(engine.telemetry.ticks.items())
    tel_spans = list(engine.telemetry.spans.items())
    tel_epoch = engine.telemetry.epoch
    es_used = dict(es)

    # the check, once the program's state is freed
    pick = check_sample(harness, t_close, seed)
    engine.cache = None
    del engine
    gc.collect()
    mark[0] = time.perf_counter()
    chk = run_check(arch, model, params, harness, pick,
                    es_used["max_blocks_per_seq"] * BLOCK, low=control)
    del params
    gc.collect()
    phase("check_s")

    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, arch.KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)

    lim = limits["widest_gap"]
    checks = {
        "widest_gap": {"value": chk["widest_gap"], "limit": lim},
        "requests_compared": {"value": chk["requests"], "limit": 1},
        "tokens_compared": {"value": chk["served_tokens"], "limit": 1},
        "short_requests": {"value": chk["short_requests"], "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    if control is not None:
        # the control in the program's place: judged by its own gap
        chk["served_gap"], chk["widest_gap"] = (chk["widest_gap"],
                                                chk["control_gap"])
        checks["widest_gap"]["value"] = chk["widest_gap"]
        checks["served_gap"] = {"value": chk["served_gap"], "limit": lim}
    correct = (chk["requests"] >= 1 and chk["served_tokens"] >= 1
               and chk["widest_gap"] <= lim
               and chk["short_requests"] == 0 and failed == 0)

    if peak is None and trace:
        peak = win.peak_for(dev.device_kind, bench)
    run = win.RunData(
        model=model, arch=arch, mix=mix, engine=es_used, t_open=t_open,
        t_close=t_close, ticks=harness.ticks, telemetry_ticks=tel_ticks,
        telemetry_spans=tel_spans, telemetry_epoch=tel_epoch,
        window=w, reached_t=harness.reached_t, submit_t=harness.submit_t,
        compiles_in_window=compiles, trace=reduced, peak=peak)
    metrics: Dict[str, dict] = {}
    if not trace:
        values = {"tokens_per_s": w["tokens_per_s"],
                  "ttft_p95_ms": w["ttft_p95_ms"],
                  "itl_p95_ms": w["itl_p95_ms"], "setup_s": t_setup}
        for m in cell_metrics(manifest, cell, "end_to_end"):
            v = values.get(m["name"])
            if v is None:
                raise BenchError(f"metric {m['name']} has no reading")
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell_metrics(manifest, cell, "per_layer"):
            v = load_reader(m["name"], bench).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    out["phases"] = phases
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("float8_e4m3fn",), default=None,
                    help="compare the tokens a reference forward in this "
                         "precision puts first (a control)")
    args = ap.parse_args(argv)
    try:
        manifest = load_manifest()
        cell = find_cell(manifest, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("the program (src/repro) is not beside the "
                             "benchmark")
        sys.path.insert(0, str(ROOT / "src"))
        configure_cache()
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"needs a TPU; JAX found {devs[0].platform}")
        if len(devs) < int(cell["chips"]):
            raise BenchError(f"the cell needs {cell['chips']} chips, JAX "
                             f"found {len(devs)}")
        res = run_cell(manifest, cell, args.seed, args.seconds,
                       bool(args.trace), control=args.control)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("phases: " + json.dumps(res.pop("phases")), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
