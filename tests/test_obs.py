"""Observability spine (DESIGN.md §10): metrics math, trace rings,
exporter schemas, and the scheduler/engine integration.

What must hold:

  * fixed-bucket histogram percentiles agree with numpy's exact order
    statistics to within the geometric bucket ratio (the estimator's
    documented error bound);
  * the tick/span rings are bounded (oldest dropped, drops counted);
  * a dumped trace is structurally valid in BOTH formats — the JSONL
    invariants (``tools/tracestats.py --check``: schema-complete ticks,
    packed sums == running counters, span pairing) and Chrome
    trace_event JSON with non-empty ``traceEvents``;
  * ``metrics()`` keeps its top-level schema, identical across the paged
    and legacy engines;
  * ``FCFSScheduler.summary()`` keeps its historical ``mean_*`` keys and
    running-total semantics across ``forget()``, with the new ``p*_*``
    fields riding along (None when telemetry is disabled).
"""
import itertools
import json

import numpy as np
import pytest

from repro.obs import (PHASES, Counter, Histogram, MetricsRegistry, Ring,
                       SPAN_KINDS, TICK_FIELDS, ServingTelemetry,
                       log_bucket_edges)
from repro.serving.scheduler import FCFSScheduler

# one geometric bucket is a 10^(1/12) ~ 1.21x span; interpolation inside
# the winning bucket keeps the estimate within that ratio of the exact
# order statistic (plus edge effects), so 1.3x is the acceptance band
BUCKET_RTOL = 0.30


# ---------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------
def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    for scale in (1e-4, 1e-2, 1.0):
        samples = rng.lognormal(mean=np.log(scale), sigma=1.0, size=5000)
        h = Histogram("t")
        for s in samples:
            h.record(s)
        for q in (50, 90, 99):
            exact = float(np.percentile(samples, q))
            est = h.percentile(q)
            assert exact / (1 + BUCKET_RTOL) <= est \
                <= exact * (1 + BUCKET_RTOL), \
                f"q={q} scale={scale}: est {est} vs exact {exact}"
        assert h.count == len(samples)
        assert np.isclose(h.mean, samples.mean())


def test_histogram_single_sample_and_clamping():
    h = Histogram("t")
    assert h.percentile(50) is None and h.mean is None
    h.record(0.0421)
    # one sample: every quantile IS that sample (min/max clamping)
    for q in (1, 50, 99):
        assert h.percentile(q) == pytest.approx(0.0421)
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["min"] == snap["max"] == 0.0421


def test_histogram_overflow_and_zero():
    h = Histogram("t", edges=[1.0, 2.0, 4.0])
    for v in (0.0, 8.0, 9.0, 10.0):     # below-range and overflow bucket
        h.record(v)
    assert h.count == 4
    assert h.percentile(99) <= 10.0     # clamped to observed max
    assert 0.0 <= h.percentile(1) <= 1.0   # within the winning bucket


def test_log_bucket_edges_cover_range():
    edges = log_bucket_edges(1e-6, 1e3, 12)
    assert edges[0] == pytest.approx(1e-6)
    assert edges[-1] >= 1e3
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    assert all(np.isclose(r, 10 ** (1 / 12)) for r in ratios)


def test_registry_get_or_create_and_type_guard():
    r = MetricsRegistry()
    c = r.counter("a")
    c.inc(3)
    assert r.counter("a") is c and r.counter("a").value == 3
    h = r.histogram("c")
    h.record(0.5)
    with pytest.raises(TypeError):
        r.histogram("a")                # 'a' is already a Counter
    with pytest.raises(TypeError):
        r.counter("c")                  # 'c' is already a Histogram
    snap = r.snapshot()
    assert snap["a"] == 3 and snap["c"]["count"] == 1
    assert isinstance(Counter("x").value, int)


def test_ring_wraparound():
    r = Ring(4)
    for i in range(10):
        r.append(i)
    assert len(r) == 4 and r.total == 10 and r.dropped == 6
    assert r.items() == [6, 7, 8, 9]    # newest kept, oldest dropped


# ---------------------------------------------------------------------
# telemetry: spans, ticks, exporters (fake clock — no engine needed)
# ---------------------------------------------------------------------
def _fake_clock():
    counter = itertools.count()
    return lambda: float(next(counter))


def _drive_telemetry(capacity=64):
    """A synthetic serving run: 2 requests, one preempted + resumed."""
    tel = ServingTelemetry(capacity=capacity, clock=_fake_clock())
    for rid in (0, 1):
        tel.span(rid, "submit", prompt_tokens=8)
        tel.span(rid, "admit", resume=False)
    tel.span(0, "first_token")
    tel.span(1, "preempt")
    tel.span(1, "admit", resume=True)
    tel.span(1, "first_token")
    for i, rid in enumerate((0, 1)):
        tel.span(rid, "finish", generated_tokens=4)
        tel.record_tick(t=float(20 + i), kind="unified", wall_s=0.5,
                        device_s=0.3, device_t=float(20 + i) + 0.1,
                        packed_tokens=5, padded_tokens=8,
                        prefill_tokens=3, decode_tokens=2, emitted=2,
                        live_slots=2, waiting=0, pool_free=10,
                        pool_cached=0, pool_in_use=5,
                        prefix_hit_tokens=0, preemptions=0, cow_copies=0,
                        dispatches=1, finished=1)
    return tel


def test_trace_jsonl_schema_and_pairing(tmp_path):
    tel = _drive_telemetry()
    path = tmp_path / "trace.jsonl"
    assert tel.dump(path, meta={"extra": 1}) == "jsonl"
    from tools import tracestats
    meta, ticks, spans, fmt = tracestats.load(str(path))
    assert fmt == "jsonl"
    assert meta["schema"] == 5 and meta["engine"] == {"extra": 1}
    assert len(ticks) == 2 and len(spans) == 10
    for t in ticks:
        for f in TICK_FIELDS:
            assert f in t, f
    assert all(s["kind"] in SPAN_KINDS for s in spans)
    summary = tracestats.summarize(meta, ticks, spans)
    assert summary["packed_tokens"] == 10
    assert summary["budget_utilization"] == pytest.approx(10 / 16)
    # every admit balances a preempt or the terminal finish
    assert tracestats.check(meta, ticks, spans, summary) == []


def test_trace_swap_schema_and_vacate_pairing(tmp_path):
    """v4 schema: swap tick fields ride along, swap spans count toward
    summary page totals, and an admission-dry ``vacate`` closes an admit
    exactly like a policy preempt does (DESIGN.md §13)."""
    tel = ServingTelemetry(capacity=64, clock=_fake_clock())
    tel.span(0, "submit", prompt_tokens=8)
    tel.span(0, "admit", resume=False)
    tel.span(0, "vacate")               # admission-dry giveback
    tel.span(0, "admit", resume=True)
    tel.span(0, "preempt")
    tel.span(0, "swap_out", pages=3)
    tel.span(0, "admit", resume=True)
    tel.span(0, "swap_in", pages=3)
    tel.span(0, "first_token")
    tel.span(0, "finish", generated_tokens=4)
    tel.record_tick(t=20.0, kind="unified", wall_s=0.5, device_s=0.3,
                    device_t=20.1, packed_tokens=5, padded_tokens=8,
                    prefill_tokens=3, decode_tokens=2, emitted=2,
                    live_slots=1, waiting=0, pool_free=10, pool_cached=0,
                    pool_in_use=5, prefix_hit_tokens=0, preemptions=1,
                    cow_copies=0, dispatches=1, finished=1,
                    swap_in=3, swap_out=3, quant=True)
    path = tmp_path / "swap.jsonl"
    tel.dump(path)
    from tools import tracestats
    meta, ticks, spans, _ = tracestats.load(str(path))
    assert ticks[0]["swap_in"] == 3 and ticks[0]["quant"] is True
    summary = tracestats.summarize(meta, ticks, spans)
    assert summary["swap_in_pages"] == 3
    assert summary["swap_out_pages"] == 3
    assert summary["quant"] is True
    assert tracestats.check(meta, ticks, spans, summary) == []
    # dropping the vacate breaks the admit balance: 3 admits vs 1 preempt
    spans2 = [s for s in spans if s["kind"] != "vacate"]
    errs = tracestats.check(meta, ticks, spans2,
                            tracestats.summarize(meta, ticks, spans2))
    assert any("admits" in e for e in errs)
    # swap_in without a prior swap_out is a corrupt trace
    spans3 = [s for s in spans if s["kind"] != "swap_out"]
    errs3 = tracestats.check(meta, ticks, spans3,
                             tracestats.summarize(meta, ticks, spans3))
    assert any("swap_in" in e for e in errs3)


def test_tracestats_check_catches_violations(tmp_path):
    tel = ServingTelemetry(clock=_fake_clock())
    tel.span(0, "admit")                # admit with no submit first
    tel.span(0, "finish")
    tel.record_tick(t=5.0, kind="unified", wall_s=0.1, device_s=0.0,
                    device_t=None, packed_tokens=1, padded_tokens=1,
                    prefill_tokens=1, decode_tokens=0, emitted=0,
                    live_slots=0, waiting=0, pool_free=0, pool_cached=0,
                    pool_in_use=0, prefix_hit_tokens=0, preemptions=0,
                    cow_copies=0, dispatches=1, finished=0)
    path = tmp_path / "bad.jsonl"
    tel.dump(path)
    from tools import tracestats
    meta, ticks, spans, _ = tracestats.load(str(path))
    errs = tracestats.check(meta, ticks, spans,
                            tracestats.summarize(meta, ticks, spans))
    assert any("not 'submit'" in e for e in errs)
    assert tracestats.check({}, [], None, {}) == ["trace has no tick events"]


def test_trace_chrome_export(tmp_path):
    tel = _drive_telemetry()
    path = tmp_path / "trace.json"
    assert tel.dump(path) == "chrome"
    doc = json.loads(path.read_text())  # must be valid JSON
    evs = doc["traceEvents"]
    assert evs, "empty traceEvents"
    assert doc["metadata"]["schema"] == 5
    phases = {e["ph"] for e in evs}
    assert phases >= {"M", "X", "i"}    # metadata, complete, instant
    tick_evs = [e for e in evs if e.get("cat") == "tick"]
    assert len(tick_evs) == 2
    assert all(e["dur"] == pytest.approx(0.5e6) for e in tick_evs)
    # request 1 was preempted: its row holds two running phases
    req1 = [e for e in evs if e.get("tid") == 101 and e["ph"] == "X"]
    assert sum(e["name"] == "running" for e in req1) == 2
    # the preempt reopened a queued phase between them
    assert sum(e["name"] == "queued" for e in req1) == 2
    # Chrome round-trip through tracestats: ticks reconstruct
    from tools import tracestats
    meta, ticks, spans, fmt = tracestats.load(str(path))
    assert fmt == "chrome" and spans is None and len(ticks) == 2
    assert tracestats.check(meta, ticks, spans,
                            tracestats.summarize(meta, ticks, spans)) == []


def test_disabled_telemetry_records_nothing():
    tel = ServingTelemetry(enabled=False, capacity=1, clock=_fake_clock())
    tel.span(0, "submit")
    tel.record_tick(t=0.0, kind="unified", wall_s=0.1, device_s=0.0,
                    device_t=None, packed_tokens=1, padded_tokens=1,
                    prefill_tokens=1, decode_tokens=0, emitted=0,
                    live_slots=0, waiting=0, pool_free=0, pool_cached=0,
                    pool_in_use=0, prefix_hit_tokens=0, preemptions=0,
                    cow_copies=0, dispatches=1, finished=0)
    assert len(tel.ticks) == 0 and len(tel.spans) == 0
    assert tel.epoch is None            # no clock reads either
    s = tel.summary()
    assert s["enabled"] is False and s["ticks"] == 0


# ---------------------------------------------------------------------
# scheduler integration: percentiles + byte-compatible summary keys
# ---------------------------------------------------------------------
class _Req:
    def __init__(self, rid):
        self.req_id = rid


def _run_fake_requests(sched, n=20, gen=4):
    """Drive n requests through the scheduler lifecycle on a fake clock
    (one unit per event); returns nothing — summary() is the output."""
    for rid in range(n):
        sched.submit(_Req(rid), 8)
        sched.on_admit(rid)
        for _ in range(gen):
            sched.on_token(rid)
        sched.on_finish(rid)


def test_summary_percentiles_with_telemetry():
    clock = _fake_clock()
    tel = ServingTelemetry(clock=clock)
    sched = FCFSScheduler(clock=clock, telemetry=tel)
    _run_fake_requests(sched)
    s = sched.summary()
    # historical keys intact, new percentile keys populated
    for key in ("requests", "finished", "waiting", "preemptions",
                "mean_ttft_s", "mean_latency_s", "generated_tokens",
                "tokens_per_s"):
        assert key in s, key
    for key in ("p50_ttft_s", "p90_ttft_s", "p99_ttft_s",
                "p50_latency_s", "p99_latency_s", "p50_inter_token_s",
                "p99_inter_token_s", "p50_queue_wait_s",
                "p99_queue_wait_s"):
        assert s[key] is not None and s[key] > 0, key
    # fake clock: every request's TTFT is exactly 2 ticks (submit ->
    # admit -> first token), so the estimate must land within a bucket
    assert s["p50_ttft_s"] == pytest.approx(2.0, rel=BUCKET_RTOL)
    assert s["mean_ttft_s"] == pytest.approx(2.0)


def test_summary_without_telemetry_keeps_schema():
    """A standalone scheduler (no telemetry attached) keeps the exact
    historical mean_* values and reports percentile keys as None."""
    sched = FCFSScheduler(clock=_fake_clock())
    _run_fake_requests(sched, n=3)
    s = sched.summary()
    assert s["mean_ttft_s"] == pytest.approx(2.0)
    assert s["p99_ttft_s"] is None and s["p50_latency_s"] is None


def test_summary_percentiles_survive_forget():
    """Percentiles, like the mean_* running totals, must not deflate
    when finished requests are forgotten (clear_finished())."""
    clock = _fake_clock()
    tel = ServingTelemetry(clock=clock)
    sched = FCFSScheduler(clock=clock, telemetry=tel)
    _run_fake_requests(sched, n=10)
    before = sched.summary()
    for rid in range(10):
        sched.forget(rid)
    after = sched.summary()
    assert after == before              # running aggregates: no deflation
    assert sched.stats == {}
    assert after["p99_ttft_s"] is not None


def test_preemption_span_and_counter():
    clock = _fake_clock()
    tel = ServingTelemetry(clock=clock)
    sched = FCFSScheduler(clock=clock, telemetry=tel)
    sched.submit(_Req(0), 4)
    sched.on_admit(0)
    sched.on_preempt(0)
    sched.on_admit(0)                   # resume
    sched.on_token(0)
    sched.on_finish(0)
    assert sched.preemptions_total == 1
    kinds = [s["kind"] for s in tel.spans.items()]
    assert kinds == ["submit", "admit", "preempt", "admit",
                     "first_token", "finish"]
    resumes = [s.get("resume") for s in tel.spans.items()
               if s["kind"] == "admit"]
    assert resumes == [False, True]


# ---------------------------------------------------------------------
# engine-level schema (slow path: builds real engines on the tiny config)
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    import jax

    from repro.config import get_config, reduced
    from repro.models import model as M
    cfg = reduced(get_config("granite-3-2b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


# the metrics() contract: these exact top-level keys, on BOTH engines
METRICS_KEYS = {"scheduler", "blocks", "tick", "token_budget",
                "kv_dtype", "preempt", "swapped_requests_waiting",
                "prefix_cache", "speculative", "dispatches",
                "attention_backend", "cluster", "oom_finished",
                "telemetry", "queue_depth", "free_page_fraction"}


def test_engine_metrics_schema_and_trace(setup, tmp_path):
    from repro.serving import PagedServingEngine
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4)
    rng = np.random.default_rng(0)
    n_reqs, gen = 3, 5
    prompts = [rng.integers(0, cfg.vocab, 7).astype(np.int32)
               for _ in range(n_reqs)]
    for p in prompts:
        eng.submit(p, gen)
    eng.run_to_completion()
    m = eng.metrics()
    assert set(m) == METRICS_KEYS
    tel = m["telemetry"]
    assert tel["enabled"] and tel["ticks"] > 0 and tel["dropped_ticks"] == 0
    assert 0 < tel["budget_utilization"] <= 1.0
    assert m["scheduler"]["p99_ttft_s"] is not None

    # dump + full validation through the CLI-level checker
    path = tmp_path / "trace.jsonl"
    eng.dump_trace(path)
    from tools import tracestats
    meta, ticks, spans, _ = tracestats.load(str(path))
    summary = tracestats.summarize(meta, ticks, spans)
    assert tracestats.check(meta, ticks, spans, summary) == []
    # acceptance invariant: packed tokens == served tokens exactly
    # (each request packs prompt + gen - 1: first token rides on prefill)
    assert summary["packed_tokens"] == n_reqs * (7 + gen - 1)
    # offline exact p99 TTFT vs the histogram estimate: within a bucket
    exact = summary["ttft_s"]["p99"]
    est = meta["metrics"]["ttft_s"]["p99"]
    assert est == pytest.approx(exact, rel=0.35)
    # Chrome flavor of the same run
    cpath = tmp_path / "trace.json"
    assert eng.dump_trace(cpath) == "chrome"
    assert json.loads(cpath.read_text())["traceEvents"]


def test_engine_telemetry_off(setup, tmp_path):
    from repro.serving import PagedServingEngine
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4,
                             telemetry=False)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32), 4)
    results = eng.run_to_completion()
    assert len(results) == 1            # token path unaffected
    m = eng.metrics()
    assert set(m) == METRICS_KEYS
    assert m["telemetry"]["enabled"] is False
    assert m["telemetry"]["ticks"] == 0
    assert m["scheduler"]["p99_ttft_s"] is None
    with pytest.raises(RuntimeError):
        eng.dump_trace(tmp_path / "no.jsonl")


def test_legacy_engine_metrics_schema(setup):
    """The legacy engine's minimal metrics() pins the same top-level
    schema, so serve.py reports stay diffable across --engine."""
    from repro.core.serving import ServingEngine
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=32)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32), 4)
    eng.run_to_completion()
    m = eng.metrics()
    assert set(m) == METRICS_KEYS
    assert m["tick"] == "slot" and m["dispatches"] > 0
    assert m["telemetry"]["enabled"] is False
    assert m["scheduler"]["num_finished"] == 1


def test_trace_ring_bounded_on_engine(setup):
    """A tiny trace_capacity drops old ticks but never grows, and the
    meta record owns the running totals the ring no longer covers."""
    from repro.serving import PagedServingEngine
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4,
                             trace_capacity=4)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 4).astype(np.int32), 12)
    eng.run_to_completion()
    tel = eng.telemetry
    assert len(tel.ticks) == 4
    assert tel.ticks.dropped > 0
    # running counters keep the full history the ring dropped
    assert tel.registry.counter("ticks").value == tel.ticks.total


# ---------------------------------------------------------------------
# host phases (schema 5): ``serve.*`` profiler spans + per-tick phase_s
# ---------------------------------------------------------------------
def _record(tel, t=0.0):
    """Close the open tick; returns its ``phase_s``."""
    tel.record_tick(t=t, kind="unified", wall_s=0.0, device_s=0.0,
                    device_t=None, packed_tokens=0, padded_tokens=0,
                    prefill_tokens=0, decode_tokens=0, emitted=0,
                    live_slots=0, waiting=0, pool_free=0, pool_cached=0,
                    pool_in_use=0, prefix_hit_tokens=0, preemptions=0,
                    cow_copies=0, dispatches=0, finished=0)
    return tel.ticks.items()[-1]["phase_s"]


def test_phase_self_times_do_not_overlap():
    # the fake clock moves one second per read
    tel = ServingTelemetry(clock=_fake_clock())
    with tel.phase("pack"):                 # 0
        with tel.phase("dispatch"):         # 1 .. 2
            pass
        with tel.phase("sync"):             # 3 .. 4
            pass
    # pack ran 0 .. 5 with 2 s of phases inside it
    assert _record(tel) == {"dispatch": 1.0, "sync": 1.0, "pack": 3.0}
    assert _record(tel) == {}               # the next tick starts empty


def test_gc_time_is_taken_from_the_phase_it_interrupts():
    import gc

    from repro.obs import trace
    tel = ServingTelemetry(clock=_fake_clock())
    trace._on_gc("start", {})               # outside every phase: ignored
    trace._on_gc("stop", {})
    with tel.phase("unpack"):               # 0
        trace._on_gc("start", {})           # 1
        trace._on_gc("stop", {})            # 2
    assert _record(tel) == {"gc": 1.0, "unpack": 2.0}
    # a real collection inside a phase, on the real clock
    real = ServingTelemetry()
    with real.phase("route"):
        gc.collect()
    phases = _record(real)
    assert phases["gc"] > 0 and phases["route"] >= 0


def test_unknown_phase_is_refused():
    with pytest.raises(KeyError):
        ServingTelemetry().phase("bogus")
    assert set(PHASES) >= {"pump", "route", "harvest", "sync", "gc"}


def _serve(eng, n=3, gen=5, seed=0):
    from repro.serving.frontend import ServingFrontend
    fe = ServingFrontend(eng)
    rng = np.random.default_rng(seed)
    for _ in range(n):
        fe.submit(rng.integers(0, eng.cfg.vocab, 7).astype(np.int32), gen)
    return fe.drain()


def test_disabled_telemetry_makes_no_span_reads_no_clock(setup,
                                                        monkeypatch):
    """``telemetry=False``: the serving path builds no profiler
    annotation, reads no clock for telemetry and registers no garbage
    collection hook."""
    import gc

    import jax.profiler

    from repro.obs import trace
    from repro.serving import PagedServingEngine
    cfg, params = setup
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: made.append(name))
    hooked = trace._on_gc in gc.callbacks
    while trace._on_gc in gc.callbacks:
        gc.callbacks.remove(trace._on_gc)
    try:
        eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                                 max_blocks_per_seq=12, prefill_chunk=4,
                                 telemetry=False)

        def no_clock():
            raise AssertionError("disabled telemetry read the clock")
        eng.telemetry.clock = no_clock
        assert len(_serve(eng)) == 3
        assert made == [] and trace._on_gc not in gc.callbacks
        assert eng.telemetry.phase("pack") is eng.telemetry.phase("sync")
        # an enabled instance does build them
        ServingTelemetry().phase("pack")
        assert made == ["serve.pack"]
    finally:
        if hooked and trace._on_gc not in gc.callbacks:
            gc.callbacks.append(trace._on_gc)


def test_one_gc_hook_for_many_engines(setup):
    import gc
    import weakref

    from repro.obs import trace
    from repro.serving import PagedServingEngine
    cfg, params = setup
    engines = [PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                                  max_blocks_per_seq=4)
               for _ in range(4)]
    assert gc.callbacks.count(trace._on_gc) == 1
    ref = weakref.ref(engines[0])
    del engines
    gc.collect()
    assert ref() is None                    # the hook holds no engine


def test_engine_trace_phases_pass_tracestats(setup, tmp_path):
    """A schema-5 dump of a served run passes ``tracestats --check``;
    copies broken in each of the phase checks fail it."""
    from repro.serving import PagedServingEngine
    from tools import tracestats
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4)
    _serve(eng)
    path = tmp_path / "trace.jsonl"
    eng.dump_trace(path)
    assert tracestats.main([str(path), "--check"]) == 0
    meta, ticks, spans, _ = tracestats.load(str(path))
    seen = set().union(*(t["phase_s"] for t in ticks))
    assert seen <= set(PHASES)
    assert {"pump", "route", "harvest", "admit", "pack", "dispatch",
            "sync", "unpack", "record"} <= seen
    assert all(t["ready_t"] >= t["dispatch_t"] for t in ticks)

    def negative(tk):
        tk[1]["phase_s"] = dict(tk[1]["phase_s"], pack=-1e-3)

    def too_long(tk):
        tk[2]["phase_s"] = dict(tk[2]["phase_s"], pump=10.0)

    def early_ready(tk):
        tk[1]["ready_t"] = tk[1]["dispatch_t"] - 1e-3

    def missing(tk):
        del tk[0]["phase_s"]

    for breaks, says in ((negative, "negative phase"),
                         (too_long, "since the previous tick"),
                         (early_ready, "before dispatch_t"),
                         (missing, "missing fields")):
        bad = [dict(t) for t in ticks]
        breaks(bad)
        errs = tracestats.check(meta, bad, spans,
                                tracestats.summarize(meta, bad, spans))
        assert any(says in e for e in errs), (says, errs)
    # the Chrome export draws the phases as a counter track
    cpath = tmp_path / "trace.json"
    eng.dump_trace(cpath)
    evs = json.loads(cpath.read_text())["traceEvents"]
    assert any(e.get("cat") == "phase" and e["ph"] == "C" for e in evs)


def test_compiled_names_the_tick_that_compiled(setup):
    from repro.serving import PagedServingEngine
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4)
    eng.submit(np.arange(4, dtype=np.int32), 6)
    eng.run_to_completion()
    compiled = [t["compiled"] for t in eng.telemetry.ticks.items()]
    # a 4-token prefill chunk, then the first decode: each a new shape;
    # every later decode reuses the decode program
    assert compiled == [1, 1, 0, 0, 0, 0]
    assert sum(compiled) == eng._unified_fn._cache_size()


def test_legacy_tick_records_dispatch_and_sync(setup):
    from repro.serving import PagedServingEngine
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=4,
                             unified=False)
    _serve(eng, n=2)
    ticks = eng.telemetry.ticks.items()
    assert all({"dispatch", "sync"} <= set(t["phase_s"]) for t in ticks)
    assert all(t["ready_t"] >= t["dispatch_t"] for t in ticks)
    assert sum(t["compiled"] for t in ticks) == eng._step_fn._cache_size()
