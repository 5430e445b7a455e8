"""Paged-KV serving subsystem: exactness, scheduling, and allocator tests.

The contract under test: every request served through PagedServingEngine
yields exactly the tokens an isolated greedy ``generate`` would produce —
under ragged prompt lengths, mid-flight admission, slot reuse, sliding
windows, and preemption-driven recomputation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.launch.serve import generate
from repro.models import model as M
from repro.serving import BlockAllocator, PagedServingEngine
from repro.serving.blocks import NULL_BLOCK, BlockTable
from repro.serving.scheduler import FCFSScheduler


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _ref(cfg, params, prompt, gen):
    out = generate(cfg, params, jnp.asarray(prompt)[None], gen)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_matches_isolated_generation_ragged(setup):
    """Ragged prompts through 2 slots, chunked prefill crossing page
    boundaries, tokens identical to isolated greedy decoding."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=12, prefill_chunk=3)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 8, 6, 1)]
    gens = [5, 3, 6, 4]
    ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    results = eng.run_to_completion()
    for rid, p, g in zip(ids, prompts, gens):
        assert results[rid] == _ref(cfg, params, p, g)


def test_mid_flight_admission(setup):
    """Requests submitted while others are decoding stay token-exact and
    are returned by a run_to_completion that started before them."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=10, prefill_chunk=4)
    rng = np.random.default_rng(1)
    first = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
             for n in (6, 9)]
    ids = [eng.submit(p, 8) for p in first]
    for _ in range(4):                      # get the first wave in flight
        eng.step()
    late = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for n in (7, 5)]
    ids += [eng.submit(p, 6) for p in late]
    results = eng.run_to_completion()
    assert set(results) == set(ids)
    for rid, p, g in zip(ids, first + late, [8, 8, 6, 6]):
        assert results[rid] == _ref(cfg, params, p, g)


def test_slot_and_block_reuse(setup):
    """More requests than slots: every page returns to the free list and
    recycled pages don't leak stale K/V into later requests."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=6, num_blocks=7,
                             prefill_chunk=4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=4 + i).astype(np.int32)
               for i in range(3)]
    ids = [eng.submit(p, 4) for p in prompts]
    results = eng.run_to_completion()
    for rid, p in zip(ids, prompts):
        assert results[rid] == _ref(cfg, params, p, 4)
    util = eng.alloc.utilization()
    assert util["in_use"] == 0 and util["free"] == eng.num_blocks - 1
    assert util["total_freed"] == util["total_allocated"] > 0
    assert eng.active == 0 and not eng.scheduler.has_waiting
    # retained results can be dropped to bound long-lived memory
    dropped = eng.clear_finished()
    assert set(dropped) == set(ids)
    assert not eng.finished and not eng.scheduler.stats
    assert eng.run_to_completion() == {}


def test_preemption_recompute_exact(setup):
    """A pool too small for both requests forces preemption; recomputation
    under greedy decoding reproduces the exact token stream."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 7)]
    gens = [9, 8]
    refs = [_ref(cfg, params, p, g) for p, g in zip(prompts, gens)]
    for policy in ("longest", "newest"):
        eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                                 max_blocks_per_seq=6, num_blocks=8,
                                 prefill_chunk=4, preemption_policy=policy)
        ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        results = eng.run_to_completion()
        m = eng.metrics()["scheduler"]
        assert m["preemptions"] >= 1, policy
        # accounting survives preemption: counted tokens == actual tokens
        assert m["generated_tokens"] == sum(len(v) for v in results.values())
        for rid, ref in zip(ids, refs):
            assert results[rid] == ref, policy


def test_mutually_fitting_pair_serializes(setup):
    """Two requests that each fit the pool alone but not together must
    serialize through admission-waits, not livelock by evicting each
    other's pages (admission never preempts)."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=8,
                             max_blocks_per_seq=1, num_blocks=2,
                             prefill_chunk=4)   # one usable page total
    prompts = [np.arange(4, dtype=np.int32), np.arange(4, dtype=np.int32) + 9]
    ids = [eng.submit(p, 3) for p in prompts]
    results = eng.run_to_completion(max_steps=200)
    for rid, p in zip(ids, prompts):
        assert results[rid] == _ref(cfg, params, p, 3)


def test_run_to_completion_raises_on_step_exhaustion(setup):
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=4)
    eng.submit(np.arange(3, dtype=np.int32), 8)
    with pytest.raises(RuntimeError):
        eng.run_to_completion(max_steps=2)   # cannot finish in 2 ticks
    assert eng.run_to_completion() is not None   # drains fine afterwards


def test_sliding_window_exact(setup):
    """Per-layer windows (local + global) bind through the paged path."""
    cfg, _ = setup
    cfg = reduced(get_config("granite-3-2b"), sliding_window=6,
                  global_every=2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=8, prefill_chunk=5)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (9, 5)]
    ids = [eng.submit(p, 8) for p in prompts]
    results = eng.run_to_completion()
    for rid, p in zip(ids, prompts):
        assert results[rid] == _ref(cfg, params, p, 8)


def test_step_emits_every_token_once(setup):
    """Streaming contract: driving the engine via step() yields each
    generated token exactly once, including the prefill-produced first
    token and max_new_tokens=1 requests."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=8, prefill_chunk=4)
    rng = np.random.default_rng(7)
    streams: dict = {}
    ids = [eng.submit(rng.integers(0, cfg.vocab, n), g)
           for n, g in ((5, 4), (7, 1), (3, 6))]
    for _ in range(200):
        for rid, tok in eng.step().items():
            streams.setdefault(rid, []).append(tok)
        if not eng.scheduler.has_waiting and eng.active == 0:
            break
    results = eng.run_to_completion()
    assert set(streams) == set(ids)
    for rid in ids:
        assert streams[rid] == results[rid]


def test_moe_arch_exact():
    """The paged layer's MoE branch (dropless reduced config) is exact."""
    cfg = reduced(get_config("olmoe-1b-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=8, prefill_chunk=4)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 7)]
    ids = [eng.submit(p, 5) for p in prompts]
    results = eng.run_to_completion()
    for rid, p in zip(ids, prompts):
        assert results[rid] == _ref(cfg, params, p, 5)


def test_block_allocator_exhaustion_recycling():
    alloc = BlockAllocator(num_blocks=5, block_size=4)
    got = [alloc.allocate() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4]       # null block never handed out
    assert alloc.allocate() is None          # exhausted
    alloc.free(got[:2])
    assert alloc.num_free == 2
    again = [alloc.allocate(), alloc.allocate()]
    assert None not in again and NULL_BLOCK not in again
    assert alloc.allocate() is None          # exhausted again
    util = alloc.utilization()
    assert util["peak_in_use"] == 4 and util["in_use"] == 4


def test_block_table_growth_and_release():
    alloc = BlockAllocator(num_blocks=6, block_size=4)
    t = BlockTable(alloc, max_blocks=4)
    assert t.ensure(1) and len(t.blocks) == 1
    assert t.ensure(4) and len(t.blocks) == 1     # same page
    assert t.ensure(5) and len(t.blocks) == 2     # crosses a boundary
    row = t.as_row()
    assert row.shape == (4,) and (row[2:] == NULL_BLOCK).all()
    t.release()
    assert alloc.num_in_use == 0 and t.blocks == []


def test_scheduler_fcfs_accounting():
    clock = iter(float(i) for i in range(100))
    sched = FCFSScheduler(preemption_policy="longest",
                          clock=lambda: next(clock))

    class R:
        def __init__(self, rid):
            self.req_id = rid

    a, b = R(0), R(1)
    sched.submit(a, prompt_tokens=4)   # t=0
    sched.submit(b, prompt_tokens=8)   # t=1
    assert sched.next_request() is a   # FCFS order
    sched.on_admit(0)                  # t=2
    sched.on_token(0)                  # t=3 (first token reads the clock)
    sched.on_token(0)                  # no clock read after the first
    sched.on_finish(0)                 # t=4
    st = sched.stats[0]
    assert st.ttft == 3.0 and st.latency == 4.0 and st.generated_tokens == 2
    # victim selection: longest = most blocks held
    assert sched.choose_victim([(0, 0, 2), (1, 1, 5)]) == 1
    assert sched.choose_victim([]) is None
    summary = sched.summary()
    assert summary["finished"] == 1 and summary["requests"] == 2


def test_unified_one_dispatch_per_tick_matches_legacy_streams(setup):
    """The unified tick issues exactly ONE jitted dispatch per working
    step() and emits, tick for tick, the same {req_id: token} dicts as
    the legacy two-dispatch tick on a trace where prefill and decode
    overlap throughout (more requests than slots, long prompts)."""
    cfg, params = setup
    kw = dict(max_slots=2, block_size=4, max_blocks_per_seq=12,
              prefill_chunk=3)
    eng_u = PagedServingEngine(cfg, params, **kw)
    eng_l = PagedServingEngine(cfg, params, unified=False, **kw)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (9, 5, 11, 2)]
    gens = [6, 8, 3, 5]
    for eng in (eng_u, eng_l):
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
    ticks = working = 0
    while eng_u.scheduler.has_waiting or eng_u.active:
        before = eng_u.dispatches
        out_u = eng_u.step()
        out_l = eng_l.step()
        assert out_u == out_l                    # same emissions, same tick
        assert eng_u.dispatches - before <= 1    # ONE dispatch per tick
        working += eng_u.dispatches - before
        ticks += 1
    assert eng_u.metrics()["tick"] == "unified"
    assert eng_u.dispatches == working <= ticks
    # the legacy tick paid a separate prefill launch whenever admission
    # overlapped decoding; the unified tick never does
    assert eng_l.dispatches > eng_u.dispatches
    res_u, res_l = eng_u.run_to_completion(), eng_l.run_to_completion()
    assert res_u == res_l


def test_token_budget_exact_and_throttles(setup):
    """A token_budget caps each tick's pack: streams stay exact at any
    budget (decodes always fit — the budget floors at the decode count),
    while small budgets stretch the same trace over more ticks."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (10, 7)]
    gens = [4, 6]
    refs = [_ref(cfg, params, p, g) for p, g in zip(prompts, gens)]
    ticks_by_budget = {}
    for budget in (None, 6, 1):
        eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                                 max_blocks_per_seq=8, prefill_chunk=4,
                                 token_budget=budget)
        ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        ticks = 0
        while eng.scheduler.has_waiting or eng.active:
            eng.step()
            ticks += 1
        results = eng.run_to_completion()
        for rid, ref in zip(ids, refs):
            assert results[rid] == ref, budget
        ticks_by_budget[budget] = ticks
        assert eng.metrics()["token_budget"] == budget
    # budget=1 cannot stream a 4-token chunk per tick: more ticks, same
    # tokens; budget=None reproduces the unthrottled schedule
    assert ticks_by_budget[1] > ticks_by_budget[6] >= ticks_by_budget[None]
    with pytest.raises(ValueError):
        PagedServingEngine(cfg, params, token_budget=0)


@pytest.mark.parametrize("policy", ["longest", "newest"])
def test_unified_preemption_mid_chunk_exact(setup, policy):
    """Decode growth running the pool dry evicts a *mid-prefill* victim
    (its chunk is dropped from the very tick's pack); recomputation on
    re-admission keeps every stream token-exact under both policies."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=2, block_size=4,
                             max_blocks_per_seq=6, num_blocks=7,
                             prefill_chunk=2, preemption_policy=policy)
    preempted_phases = []
    orig = eng.scheduler.on_preempt

    def spy(req_id):
        slot = next(s for s, r in enumerate(eng.slot_req)
                    if r is not None and r.req_id == req_id)
        preempted_phases.append((req_id, eng.slot_phase[slot],
                                 int(eng.slot_filled[slot])))
        orig(req_id)

    eng.scheduler.on_preempt = spy
    rng = np.random.default_rng(10)
    a = rng.integers(0, cfg.vocab, 4).astype(np.int32)    # decodes long
    b = rng.integers(0, cfg.vocab, 14).astype(np.int32)   # streams in slowly
    ida, idb = eng.submit(a, 8), eng.submit(b, 3)
    results = eng.run_to_completion()
    assert preempted_phases, "pool was never contended"
    rid, phase, filled = preempted_phases[0]
    assert rid == idb and phase == "prefill" and 0 < filled < b.size
    assert results[ida] == _ref(cfg, params, a, 8)
    assert results[idb] == _ref(cfg, params, b, 3)


def test_plan_tick_budget_split():
    """plan_tick: decodes are always granted; leftover budget streams
    prefills in first-admission order, chunk-capped; None = unbounded;
    a preempted request keeps its seniority on re-admission."""
    sched = FCFSScheduler()

    class R:
        def __init__(self, rid):
            self.req_id = rid

    for rid in (0, 1, 2):
        sched.submit(R(rid), prompt_tokens=4)
        sched.next_request()
        sched.on_admit(rid)
    prefill = [(5, 2, 10), (3, 1, 3)]           # slot 3 admitted earlier
    # unbounded: full chunk each regardless of decode load
    assert sched.plan_tick(None, [0, 1], prefill, chunk=4) == {5: 4, 3: 3}
    # budget 6, 2 decodes -> 4 prefill tokens, oldest admission first
    assert sched.plan_tick(6, [0, 1], prefill, chunk=4) == {3: 3, 5: 1}
    # decode floor: budget below the decode count still decodes everyone
    assert sched.plan_tick(1, [0, 1], prefill, chunk=4) == {}
    # no decodes: budget goes entirely to the queue head's chunk
    assert sched.plan_tick(2, [], prefill, chunk=4) == {3: 2}
    # preempt + re-admit request 1: its latest admission order moves (the
    # "newest" eviction policy must see it), but NOT its budget seniority
    sched.on_preempt(1)
    sched.on_admit(1)
    assert sched._admitted_order[1] > sched._admitted_order[2]
    assert sched.plan_tick(6, [0, 1], prefill, chunk=4) == {3: 3, 5: 1}


def test_summary_survives_forget():
    """Satellite regression: forget() of finished requests must not
    deflate the running aggregates (tokens_per_s, latency, counts)."""
    clock = iter(float(i) for i in range(100))
    sched = FCFSScheduler(clock=lambda: next(clock))

    class R:
        def __init__(self, rid):
            self.req_id = rid

    for rid in (0, 1):
        sched.submit(R(rid), prompt_tokens=4)   # t=0, t=1
        sched.next_request()
        sched.on_admit(rid)                     # t=2, t=3
    for _ in range(3):
        sched.on_token(0)                       # first token: t=4
    sched.on_preempt(0)
    sched.on_finish(0)                          # t=5
    before = sched.summary()
    assert before["finished"] == 1 and before["generated_tokens"] == 3
    assert before["preemptions"] == 1
    sched.forget(0)                             # pre-fix: stats dropped
    after = sched.summary()
    for key in ("finished", "generated_tokens", "preemptions",
                "mean_ttft_s", "mean_latency_s", "tokens_per_s"):
        assert after[key] == before[key], key
    assert after["requests"] == 2               # total ever submitted
    sched.on_token(1)
    sched.on_finish(1)
    assert sched.summary()["generated_tokens"] == 4


def test_legacy_run_to_completion_returns_late_submissions(setup):
    """Satellite regression: requests submitted after run_to_completion
    starts (here: after a manual step) are still returned."""
    cfg, params = setup
    from repro.core.serving import ServingEngine
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=32)
    rng = np.random.default_rng(5)
    r0 = eng.submit(rng.integers(0, cfg.vocab, 4), 3)
    eng.run_to_completion()
    r1 = eng.submit(rng.integers(0, cfg.vocab, 5), 2)
    while eng.queue or eng.active:       # r1 finishes outside the call
        eng.step()
    results = eng.run_to_completion()    # pre-fix: snapshot -> {}
    assert set(results) >= {r0, r1}
    assert len(results[r1]) == 2


def test_submit_validates_capacity(setup):
    """Requests that provably cannot fit are rejected up front instead of
    silently truncating (paged); legacy rejects prompts >= max_seq."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=2)      # capacity 8
    with pytest.raises(ValueError):
        eng.submit(np.arange(6, dtype=np.int32), 10)    # 6 + 10 - 1 > 8
    # exact fit: 5 + 4 - 1 == 8 slots (last token is never written back)
    rid = eng.submit(np.arange(5, dtype=np.int32), 4)
    assert len(eng.run_to_completion()[rid]) == 4
    assert eng.metrics()["oom_finished"] == 0
    with pytest.raises(ValueError):
        eng.submit(np.arange(2, dtype=np.int32), 0)     # prefill-only
    # fits the table but can never fit the pool -> rejected up front
    small = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                               max_blocks_per_seq=4, num_blocks=3)
    with pytest.raises(ValueError):
        small.submit(np.arange(10, dtype=np.int32), 4)
    from repro.core.serving import ServingEngine
    leg = ServingEngine(cfg, params, max_slots=1, max_seq=8)
    with pytest.raises(ValueError):
        leg.submit(np.arange(8, dtype=np.int32), 1)
    with pytest.raises(ValueError):
        leg.submit(np.arange(2, dtype=np.int32), 0)


def test_paged_rejects_unsupported_archs(setup):
    cfg, params = setup
    rw = reduced(get_config("rwkv6-1.6b"))
    with pytest.raises(AssertionError):
        PagedServingEngine(rw, M.init_params(rw, jax.random.PRNGKey(0)))


def test_serving_weights_held_in_compute_dtype(setup):
    """Serving holds every matrix in ``cfg.dtype`` and keeps the leaves
    the model reads in float32; the cast is exact, so a bf16 config
    serves the same tokens from cast weights as ``generate`` does from
    float32 masters."""
    import dataclasses

    from repro.serving.paged_attn import (hold_in_compute_dtype,
                                          init_serving_params)
    cfg, params = setup
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    held = hold_in_compute_dtype(cfg, params)
    assert held["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    assert held["embed"]["table"].dtype == jnp.bfloat16
    assert held["layers"]["ln1"]["scale"].dtype == jnp.float32
    assert held["final_ln"]["scale"].dtype == jnp.float32
    init = init_serving_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.array_equal(a, b)),
        init, held))
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab
    eng = PagedServingEngine(cfg, params, max_slots=1, block_size=4,
                             max_blocks_per_seq=4)
    rid = eng.submit(prompt, 4)
    assert eng.run_to_completion()[rid] == _ref(cfg, params, prompt, 4)


def test_compile_cache_placement(monkeypatch):
    """The compile cache honours JAX_COMPILATION_CACHE_DIR untouched and
    otherwise sets a fixed directory inside the checkout — on a TPU only."""
    from repro.launch import bootstrap
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert bootstrap.enable_compile_cache() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert bootstrap.enable_compile_cache() is None     # CPU: no cache
    assert calls == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = bootstrap.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == str(bootstrap.CACHE_DIR)
    assert bootstrap.CACHE_DIR.parent == \
        __import__("pathlib").Path(__file__).resolve().parents[1]
