"""Scenario catalogue for the open-loop serving front end (DESIGN.md §12).

What is pinned here:

  * open-loop serving is a *scheduling* change, never a *token* change:
    streams served through ``ServingFrontend`` on a virtual clock are
    byte-identical to closed-loop ``run_to_completion`` on the same
    workload, with dispatch double-buffering on or off;
  * burst overload soaks (hundreds of requests, preemption + prefix
    cache + speculation all enabled) finish exactly and leave the
    allocator/scheduler empty;
  * cancel releases pages back to the pool from every lifecycle stage —
    before arrival, waiting, mid-prefill, mid-decode — and is refused
    only while the victim's tokens are packed into an in-flight tick;
  * ``run_to_completion`` raises the stuck-request error immediately
    when no step can make progress (it used to busy-spin the entire
    step budget — the regression test here hung before the fix);
  * the ``step_begin``/``step_end`` split enforces its pairing contract
    and admits submissions inside the overlap window;
  * (hypothesis, import-gated) arbitrary submit/stream/cancel/drain
    interleavings never double-free pages, never drop a finish event,
    and streamed tokens always equal the engine's emitted tokens.
"""
import jax
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.models import model as M
from repro.serving import PagedServingEngine, ServingFrontend, VirtualClock
from repro.serving import loadgen

try:
    from hypothesis import settings
    from hypothesis import strategies as st
    from hypothesis.stateful import (RuleBasedStateMachine, invariant,
                                     precondition, rule)
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _open_engine(cfg, params, vc, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_blocks_per_seq", 16)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("prefill_chunk", 8)
    return PagedServingEngine(cfg, params, clock=vc, **kw)


# ---------------------------------------------------------------------------
# open-loop == closed-loop
# ---------------------------------------------------------------------------
def test_open_vs_closed_byte_identical(setup):
    """The same workload served open-loop (arrivals spread over a fake
    clock, both double-buffer modes) and closed-loop (pre-staged,
    run_to_completion) yields byte-identical per-request streams."""
    cfg, params = setup
    wl = loadgen.build_workload(mix="chat", arrivals="poisson", n=10,
                                seed=11, vocab=cfg.vocab, rate=200.0)
    eng = PagedServingEngine(cfg, params, max_slots=4, block_size=4,
                             max_blocks_per_seq=16, num_blocks=64,
                             prefill_chunk=8)
    ids = [eng.submit(r.prompt, r.max_new_tokens) for r in wl]
    closed = eng.run_to_completion()
    closed_streams = [closed[i] for i in ids]
    for double_buffer in (True, False):
        vc = VirtualClock()
        fe = ServingFrontend(_open_engine(cfg, params, vc),
                             double_buffer=double_buffer,
                             virtual_tick_s=0.002)
        fids = fe.submit_workload(wl)
        out = fe.drain()
        assert [out[f] for f in fids] == closed_streams, double_buffer
        rep = fe.report()
        assert rep["finished"] == len(wl)
        assert rep["p99_ttft_s"] is not None
        assert rep["p50_tpot_s"] is not None


def test_openloop_trace_arrivals(setup):
    """A trace-file workload (shape overrides included) serves to the
    same streams as the equivalent closed-loop run."""
    cfg, params = setup
    wl = loadgen.build_workload(mix="classify", arrivals="trace", seed=0,
                                vocab=cfg.vocab,
                                trace=[0.0, 0.0, 0.05, 0.2, 0.21])
    vc = VirtualClock()
    fe = ServingFrontend(_open_engine(cfg, params, vc))
    fe.submit_workload(wl)
    out = fe.drain()
    eng = PagedServingEngine(cfg, params, max_slots=4, block_size=4,
                             max_blocks_per_seq=16, num_blocks=64,
                             prefill_chunk=8)
    ids = [eng.submit(r.prompt, r.max_new_tokens) for r in wl]
    closed = eng.run_to_completion()
    assert [out[f] for f in range(len(wl))] == [closed[i] for i in ids]


# ---------------------------------------------------------------------------
# burst overload soak: preemption + prefix cache + speculation together
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tier", [
    dict(),
    dict(kv_dtype="int8", preempt="swap"),
], ids=["fp-recompute", "int8-swap"])
def test_burst_overload_soak(setup, tier):
    """~500 bursty requests through a deliberately tight pool with every
    engine feature on at once: preemption fires, the prefix cache serves
    the agents' shared system prompt, speculation accepts drafts — and
    every stream still finishes exactly, leaving the engine empty.  The
    int8-swap flavor re-runs the soak on the capacity tiers (DESIGN.md
    §13): quantized pages, preempted pages parked in host RAM — same
    exactness, plus the swap store must drain."""
    cfg, params = setup
    burst = dict(rate_lo=20.0, rate_hi=400.0, dwell_lo_s=0.25,
                 dwell_hi_s=0.15)
    agents = loadgen.build_workload(mix="agents", arrivals="bursty",
                                    n=250, seed=21, vocab=cfg.vocab,
                                    burst=burst)
    chat = loadgen.build_workload(mix="chat", arrivals="bursty", n=250,
                                  seed=22, vocab=cfg.vocab, burst=burst)
    wl = sorted(agents + chat, key=lambda r: r.t)
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, num_blocks=21, token_budget=32,
                       prefix_cache=True, speculate=True, draft_k=4,
                       trace_capacity=8192, **tier)
    fe = ServingFrontend(eng, virtual_tick_s=0.004)
    fids = fe.submit_workload(wl)
    out = fe.drain()
    assert len(out) == len(wl) == 500
    for fid, r in zip(fids, wl):
        fr = fe.result(fid)
        assert not fr.oom and len(fr.tokens) == r.max_new_tokens
    # all three contention paths actually exercised
    assert eng.scheduler.preemptions_total > 0
    assert eng.prefix_hit_tokens > 0
    assert eng.spec_accepted_total > 0
    # ...and the engine is empty afterwards
    assert eng.active == 0 and not eng.scheduler.has_waiting
    assert eng.alloc.snapshot()[0] == 0          # nothing in use
    assert not fe._arrivals and not fe._cancel_q
    if tier:
        # 500 requests of swap traffic leaked nothing: every parked
        # payload was streamed back (or discarded), no request is still
        # waiting on swapped pages
        u = eng.alloc.utilization()
        assert u["swapped_out_pages"] > 0
        assert u["swapped_in_pages"] == u["swapped_out_pages"]
        assert u["host_pages"] == 0
        assert eng.metrics()["swapped_requests_waiting"] == 0


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------
def test_cancel_mid_prefill_releases_pages(setup):
    cfg, params = setup
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, max_slots=2, prefill_chunk=4)
    fe = ServingFrontend(eng)
    fid = fe.submit(np.arange(40, dtype=np.int32), 4)
    fe._round()                      # admit + first prefill chunk only
    slot = next(s for s, r in enumerate(eng.slot_req) if r is not None)
    assert eng.slot_phase[slot] == "prefill"
    assert eng.alloc.snapshot()[0] > 0
    assert fe.cancel(fid) is True
    in_use, _cached, free = eng.alloc.snapshot()
    assert in_use == 0 and free == eng.num_blocks - 1
    assert eng.active == 0
    assert fe.drain() == {} and fe.result(fid).cancelled
    # the trace carries the terminal cancel span
    spans = [s for s in eng.telemetry.spans.items()
             if s["kind"] == "cancel"]
    assert len(spans) == 1
    assert eng.metrics()["scheduler"]["cancelled"] == 1


def test_cancel_mid_decode_releases_pages(setup):
    cfg, params = setup
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, max_slots=2)
    fe = ServingFrontend(eng)
    keep = fe.submit(np.arange(6, dtype=np.int32), 12)
    kill = fe.submit(np.arange(8, dtype=np.int32), 12)
    stream = fe.stream(kill)
    got = [next(stream) for _ in range(3)]       # decode well under way
    slot = next(s for s, r in enumerate(eng.slot_req)
                if r is not None
                and r.req_id == fe.result(kill).engine_id)
    assert eng.slot_phase[slot] == "decode"
    assert fe.cancel(kill) is True
    assert eng.slot_req[slot] is None
    assert fe.result(kill).done and fe.result(kill).cancelled
    assert list(stream) == []                    # generator terminates
    assert fe.result(kill).tokens[:3] == got
    # the survivor is unaffected and the pool fully drains
    out = fe.drain()
    assert len(out[keep]) == 12
    assert eng.alloc.snapshot()[0] == 0
    # double-cancel / cancel-after-finish are no-ops
    assert fe.cancel(kill) is False and fe.cancel(keep) is False


def test_cancel_waiting_and_before_arrival(setup):
    cfg, params = setup
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, max_slots=1)
    fe = ServingFrontend(eng)
    a = fe.submit(np.arange(6, dtype=np.int32), 4)
    b = fe.submit(np.arange(6, dtype=np.int32) + 1, 4)   # queued behind a
    c = fe.submit(np.arange(6, dtype=np.int32) + 2, 4,
                  at=vc() + 99.0)                        # far-future arrival
    fe._round()
    assert fe.result(b).engine_id is not None            # waiting in engine
    assert fe.cancel(b) is True and fe.cancel(c) is True
    out = fe.drain()
    assert set(out) == {a} and len(out[a]) == 4
    assert fe.result(c).engine_id is None                # never submitted
    # only b reached the engine, so exactly one cancel span
    assert eng.metrics()["scheduler"]["cancelled"] == 1


def test_drain_pops_cancelled_future_arrivals(setup):
    """Two cancelled arrivals still in the future with the engine idle:
    each round of ``drain`` only pops one, and that is progress, not a
    livelock."""
    cfg, params = setup
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, max_slots=2)
    fe = ServingFrontend(eng, virtual_tick_s=0.001)
    prompt = np.arange(1, dtype=np.int32)
    assert fe.cancel(fe.submit(prompt, 1, at=vc() + 0.002))
    kept = fe.submit(prompt, 1)
    fe._round()
    assert fe.cancel(fe.submit(prompt, 1, at=vc() + 0.002))
    assert fe.drain() == {kept: fe.result(kept).tokens}
    assert not fe._arrivals and eng.active == 0


def test_cancel_refused_while_tick_in_flight(setup):
    """A slot-held request cannot be cancelled mid-dispatch (its tokens
    are packed into the running tick); the front end defers instead."""
    cfg, params = setup
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, max_slots=2)
    fe = ServingFrontend(eng)
    fid = fe.submit(np.arange(8, dtype=np.int32), 6)
    fe._pump_arrivals()
    pend = eng.step_begin()
    with pytest.raises(RuntimeError, match="in flight"):
        eng.cancel(fe.result(fid).engine_id)
    assert fe.cancel(fid) is True                # defers, no raise
    assert not fe.result(fid).done               # not applied yet
    fe._route(eng.step_end(pend))
    fe._apply_cancels()
    assert fe.result(fid).done and fe.result(fid).cancelled
    assert eng.alloc.snapshot()[0] == 0


# ---------------------------------------------------------------------------
# drain-after-burst leaves everything empty; trace validates end to end
# ---------------------------------------------------------------------------
def test_drain_after_burst_clean(setup, tmp_path):
    cfg, params = setup
    wl = loadgen.build_workload(mix="agents", arrivals="bursty", n=40,
                                seed=5, vocab=cfg.vocab)
    vc = VirtualClock()
    eng = _open_engine(cfg, params, vc, num_blocks=40, prefix_cache=True)
    fe = ServingFrontend(eng, virtual_tick_s=0.003)
    fids = fe.submit_workload(wl)
    fe.cancel(fids[7])               # pre-arrival: never reaches the engine
    for _ in range(3):               # let the burst start flowing...
        fe._round()
    live = next(f for f in fids if fe.result(f).engine_id is not None
                and not fe.result(f).done)
    fe.cancel(live)                  # ...then cancel one engine-side
    out = fe.drain()
    assert set(out) == set(fids) - {fids[7], live}
    assert eng.active == 0 and not eng.scheduler.has_waiting
    in_use, _cached, _free = eng.alloc.snapshot()
    assert in_use == 0
    assert not fe._arrivals and not fe._by_engine and not eng.finished
    # a second drain is a no-op
    assert fe.drain() == {}
    # the full trace (with its cancel span) passes tracestats --check
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__)
                           .resolve().parents[1]))
    from tools import tracestats
    path = tmp_path / "openloop.jsonl"
    eng.dump_trace(path)
    meta, ticks, spans, _fmt = tracestats.load(path)
    summary = tracestats.summarize(meta, ticks, spans)
    assert tracestats.check(meta, ticks, spans, summary) == []
    assert any(s["kind"] == "cancel" for s in spans)


# ---------------------------------------------------------------------------
# run_to_completion must raise, not spin, on zero admissible work
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("unified", [True, False])
def test_stuck_engine_raises_instead_of_spinning(setup, unified):
    """Regression: with the pool externally exhausted, admission vacates
    the slot every tick and re-queues the request — zero progress.
    run_to_completion used to busy-spin all max_steps ticks (this test
    hung for ~forever with max_steps=10**9); now one repeated state
    fingerprint raises the stuck-request error immediately."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=4, num_blocks=4,
                             prefill_chunk=4, unified=unified)
    held = [eng.alloc.allocate() for _ in range(eng.num_blocks - 1)]
    assert all(b is not None for b in held)      # pool is now empty
    rid = eng.submit(np.arange(8, dtype=np.int32), 2)
    with pytest.raises(RuntimeError, match="no step can make progress"):
        eng.run_to_completion(max_steps=10**9)
    # releasing the pool unblocks the same request, token-exact
    eng.alloc.decref(held)
    results = eng.run_to_completion()
    assert len(results[rid]) == 2


def test_stuck_guard_legacy_core_engine(setup):
    """The dense-cache engine carries the same no-progress guard (its
    normal dynamics can't livelock, so the guard is exercised by
    stubbing step out)."""
    from repro.core.serving import ServingEngine
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=32)
    eng.submit(np.arange(4, dtype=np.int32), 2)
    eng.step = lambda: {}
    with pytest.raises(RuntimeError, match="no step can make progress"):
        eng.run_to_completion(max_steps=10**9)


# ---------------------------------------------------------------------------
# step_begin / step_end pairing contract
# ---------------------------------------------------------------------------
def test_step_begin_end_contract(setup):
    cfg, params = setup
    eng = _open_engine(cfg, params, None)
    rid = eng.submit(np.arange(6, dtype=np.int32), 4)
    pend = eng.step_begin()
    with pytest.raises(RuntimeError, match="already in flight"):
        eng.step_begin()
    # submissions are legal inside the overlap window
    rid2 = eng.submit(np.arange(5, dtype=np.int32), 3)
    emitted = eng.step_end(pend)
    with pytest.raises(RuntimeError, match="without a matching"):
        eng.step_end(pend)
    results = eng.run_to_completion()
    assert len(results[rid]) == 4 and len(results[rid2]) == 3
    # a stale handle from a previous tick is rejected
    with pytest.raises(RuntimeError, match="without a matching"):
        eng.step_end({"kind": "unified"})
    assert isinstance(emitted, dict)


# ---------------------------------------------------------------------------
# hypothesis state-machine fuzz (import-gated like tests/test_properties)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    _FUZZ: dict = {}

    def _fuzz_env():
        """One shared engine across examples: jit buckets compile once,
        and every example must leave the engine spotless for the next —
        which is itself the invariant under test."""
        if not _FUZZ:
            cfg = reduced(get_config("granite-3-2b"))
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            vc = VirtualClock()
            eng = PagedServingEngine(cfg, params, max_slots=2,
                                     block_size=4, max_blocks_per_seq=8,
                                     num_blocks=12, prefill_chunk=4,
                                     trace_capacity=256, clock=vc)
            _FUZZ.update(cfg=cfg, eng=eng, vc=vc)
        return _FUZZ

    class FrontendMachine(RuleBasedStateMachine):
        """Arbitrary submit/stream/cancel/drain interleavings.

        Checked continuously: page conservation (in_use + cached + free
        == usable pool, no double-free can ever overshoot) and the
        tick-pairing state.  Checked at teardown: every request reached
        exactly one terminal state (finish or cancel, never dropped) and
        every non-cancelled stream carries exactly its requested tokens
        (``_harvest_finished`` asserts streamed == emitted on the way).
        """

        def __init__(self):
            super().__init__()
            env = _fuzz_env()
            self.eng, self.vc = env["eng"], env["vc"]
            assert self.eng.active == 0 and not self.eng.scheduler.waiting
            self.fe = ServingFrontend(self.eng, virtual_tick_s=0.001)
            self.expect: dict = {}       # fid -> requested max_new_tokens

        @rule(plen=st.integers(1, 6), gen=st.integers(1, 3),
              delay=st.sampled_from([0.0, 0.002, 0.05]))
        def submit(self, plen, gen, delay):
            prompt = np.arange(plen, dtype=np.int32) % 17
            fid = self.fe.submit(prompt, gen, at=self.vc() + delay)
            self.expect[fid] = gen

        @precondition(lambda self: self.fe._has_work())
        @rule()
        def tick(self):
            self.fe._round()

        @precondition(lambda self: any(
            not fr.done and not fr.cancelled
            for fr in self.fe._reqs.values()))
        @rule(pick=st.integers(0, 10**6))
        def cancel(self, pick):
            live = [fid for fid, fr in self.fe._reqs.items()
                    if not fr.done and not fr.cancelled]
            assert self.fe.cancel(live[pick % len(live)])

        @rule(n=st.integers(1, 4))
        def stream_some(self, n):
            """Consume a few tokens of the oldest live stream."""
            live = [fid for fid, fr in self.fe._reqs.items()
                    if not fr.done and not fr.cancelled]
            if not live:
                return
            it = self.fe.stream(live[0])
            for _ in range(n):
                if next(it, None) is None:
                    break

        @rule()
        def drain(self):
            self.fe.drain()

        @invariant()
        def pages_conserved(self):
            in_use, cached, free = self.eng.alloc.snapshot()
            assert in_use + cached + free == self.eng.num_blocks - 1
            assert self.eng._pending is None

        def teardown(self):
            self.fe.drain()
            for fid, gen in self.expect.items():
                fr = self.fe.result(fid)
                assert fr.done, f"req {fid} lost its finish event"
                if not fr.cancelled:
                    assert len(fr.tokens) == gen, fid
            assert self.eng.active == 0
            assert not self.eng.scheduler.waiting
            assert self.eng.alloc.snapshot()[0] == 0
            self.eng.clear_finished()

    FrontendMachine.TestCase.settings = settings(
        max_examples=12, stateful_step_count=20, deadline=None)
    TestFrontendFuzz = FrontendMachine.TestCase

    _FUZZ_SWAP: dict = {}

    def _fuzz_swap_env():
        """Capacity-tier flavor of the shared fuzz engine: int8 pages,
        swap preemption, host prefix spill, and a pool tight enough
        that the swap paths actually fire under the interleavings."""
        if not _FUZZ_SWAP:
            cfg = reduced(get_config("granite-3-2b"))
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            vc = VirtualClock()
            eng = PagedServingEngine(cfg, params, max_slots=2,
                                     block_size=4, max_blocks_per_seq=8,
                                     num_blocks=9, prefill_chunk=4,
                                     trace_capacity=256, clock=vc,
                                     kv_dtype="int8", preempt="swap",
                                     prefix_cache=True,
                                     host_cache_pages=4)
            _FUZZ_SWAP.update(cfg=cfg, eng=eng, vc=vc)
        return _FUZZ_SWAP

    class SwapFrontendMachine(FrontendMachine):
        """The same submit/stream/cancel/drain interleavings over the
        KV capacity tiers (DESIGN.md §13).  Page conservation must hold
        while pages commute between the device pool and host RAM, the
        swap store must never hold a payload without a waiting owner,
        and teardown additionally requires the store drained (the host
        *prefix* cache may legitimately retain spilled pages)."""

        def __init__(self):
            RuleBasedStateMachine.__init__(self)
            env = _fuzz_swap_env()
            self.eng, self.vc = env["eng"], env["vc"]
            assert self.eng.active == 0 and not self.eng.scheduler.waiting
            self.fe = ServingFrontend(self.eng, virtual_tick_s=0.001)
            self.expect = {}

        @invariant()
        def swap_store_owned(self):
            assert (len(self.eng._swap_handles)
                    == len(self.eng.alloc._swap_store))

        def teardown(self):
            super().teardown()
            assert not self.eng.alloc._swap_store
            assert self.eng.metrics()["swapped_requests_waiting"] == 0

    SwapFrontendMachine.TestCase.settings = settings(
        max_examples=8, stateful_step_count=20, deadline=None)
    TestSwapFrontendFuzz = SwapFrontendMachine.TestCase
