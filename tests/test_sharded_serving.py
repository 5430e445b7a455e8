"""Cluster-sharded paged serving: token exactness and pool accounting.

The tentpole contract (DESIGN.md §7): the PagedServingEngine sharded over a
named cluster mesh emits *exactly* the token streams of the single-device
engine on the same request trace — under full tensor-parallel sharding,
partial (fallback) sharding, preemption, and the per-shard Pallas kernel.

The main test process must keep exactly 1 device (dry-run/bench contract),
so every mesh case runs in a child interpreter with forced host devices
(``conftest.run_child``), exactly like ``test_multidevice.py``.
"""
import pytest
from conftest import run_child

from repro.serving.blocks import BlockAllocator
from repro.sharding import ServingTPPlan, serving_cache_spec, \
    serving_param_spec


# shared child preamble: a ragged trace served twice — single-device vs
# sharded over a platform cluster — and compared token-for-token
_TRACE = """
    import jax, numpy as np, pathlib, tempfile
    from repro.config import get_config, reduced
    from repro.core.platform import Platform
    from repro.models import model as M
    from repro.serving import PagedServingEngine

    def serve(cfg, params, mesh, lens=(5, 8, 3, 6), gens=(5, 3, 6, 4), **kw):
        kw.setdefault("max_slots", 2)
        kw.setdefault("block_size", 4)
        kw.setdefault("max_blocks_per_seq", 12)
        kw.setdefault("prefill_chunk", 3)
        eng = PagedServingEngine(cfg, params, mesh=mesh, **kw)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
                   for n in lens]
        ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        res = eng.run_to_completion()
        return [res[i] for i in ids], eng

    plat = Platform(pathlib.Path(tempfile.mkdtemp()))
"""


def test_sharded_token_exact_tp2():
    """2-way cluster: full TP (attn+mlp+vocab sharded), preemption forced
    by a tight pool, per-shard pool accounting halves page bytes."""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("c2", 2, model_axis=2)
        single, ref_eng = serve(cfg, params, None)
        shard, eng = serve(cfg, params, cluster)
        assert eng.tp.size == 2 and eng.tp.shard_attn and eng.tp.shard_mlp \\
            and eng.tp.shard_vocab, eng.tp
        assert shard == single, (shard, single)
        u1, u2 = ref_eng.alloc.utilization(), eng.alloc.utilization()
        assert u2["num_shards"] == 2 and u1["num_shards"] == 1
        assert u2["page_bytes_per_shard"] * 2 == u1["page_bytes_per_shard"]
        assert u2["pool_bytes_per_shard"] * 2 == u1["pool_bytes_per_shard"]

        # tight pool: preemption-driven recompute stays exact when sharded
        # (same trace as test_preemption_recompute_exact: two requests
        # whose tables cannot both fit the 7 usable pages)
        small = dict(lens=(6, 7), gens=(9, 8), max_blocks_per_seq=6,
                     num_blocks=8, prefill_chunk=4)
        single, _ = serve(cfg, params, None, **small)
        shard, eng = serve(cfg, params, cluster, **small)
        assert eng.metrics()["scheduler"]["preemptions"] >= 1
        assert shard == single, (shard, single)
        print("ok")
    """, devices=2, preamble=_TRACE)
    assert "ok" in out


def test_sharded_token_exact_tp4_and_fallback():
    """4-way cluster: fully divisible heads shard the KV pool 4 ways; the
    default config (kv=2) degrades attention to replicated but still
    shards MLP + vocab — both remain token-exact."""
    out = run_child("""
        cluster = plat.create_cluster("c4", 4, model_axis=4)

        cfg = reduced(get_config("granite-3-2b"), n_heads=4, n_kv_heads=4)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        single, _ = serve(cfg, params, None)
        shard, eng = serve(cfg, params, cluster)
        assert eng.tp.size == 4 and eng.tp.shard_attn
        assert shard == single, (shard, single)

        cfg = reduced(get_config("granite-3-2b"))    # kv=2: 4 doesn't divide
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        single, _ = serve(cfg, params, None)
        shard, eng = serve(cfg, params, cluster)
        assert not eng.tp.shard_attn and eng.tp.shard_mlp \\
            and eng.tp.shard_vocab, eng.tp
        assert shard == single, (shard, single)
        print("ok")
    """, devices=4, preamble=_TRACE)
    assert "ok" in out


def test_mesh_of_one_collapses_to_single_device():
    """A 1-device cluster is the single-device engine (no shard_map), and
    ``serve --cluster`` semantics hold at N=1."""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("c1", 1, model_axis=1)
        single, _ = serve(cfg, params, None)
        shard, eng = serve(cfg, params, cluster)
        assert eng.tp is None and eng.metrics()["cluster"] is None
        assert eng.alloc.utilization()["num_shards"] == 1
        assert shard == single
        print("ok")
    """, devices=1, preamble=_TRACE)
    assert "ok" in out


def test_sharded_unified_budget_and_legacy_tick_exact():
    """Unified-tick invariants survive sharding: on a 2-way cluster the
    default engine (a) emits the same streams as the legacy two-dispatch
    tick, (b) stays exact under a tick token_budget, and (c) reports one
    dispatch per working step.  (TP1/TP4 coverage: the other child tests
    run the same default unified engine on 1- and 4-device meshes.)"""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("cu", 2, model_axis=2)
        single, _ = serve(cfg, params, None)
        unified, eng_u = serve(cfg, params, cluster)
        legacy, eng_l = serve(cfg, params, cluster, unified=False)
        assert eng_u.metrics()["tick"] == "unified"
        assert unified == single == legacy, (unified, single, legacy)
        assert eng_u.dispatches < eng_l.dispatches, \\
            (eng_u.dispatches, eng_l.dispatches)

        budget, eng_b = serve(cfg, params, cluster, token_budget=4)
        assert budget == single, (budget, single)
        assert eng_b.metrics()["token_budget"] == 4
        print("ok")
    """, devices=2, preamble=_TRACE)
    assert "ok" in out


def test_sharded_speculative_token_exact_tp2():
    """Speculative decoding survives tensor-parallel sharding (DESIGN.md
    §11): on a 2-way cluster the verify logits are reduced across shards
    before the argmax, so drafted/accepted counts AND token streams must
    match the single-device speculative engine — and both must match the
    non-speculative streams byte for byte."""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("cs", 2, model_axis=2)

        def serve_rep(mesh, **kw):
            eng = PagedServingEngine(cfg, params, mesh=mesh, max_slots=2,
                                     block_size=4, max_blocks_per_seq=12,
                                     prefill_chunk=3, **kw)
            rng = np.random.default_rng(5)
            pat = rng.integers(0, cfg.vocab, size=3).astype(np.int32)
            prompts = [np.tile(pat, 4).astype(np.int32),
                       rng.integers(0, cfg.vocab, size=7).astype(np.int32),
                       np.tile(pat, 2).astype(np.int32)]
            ids = [eng.submit(p, g) for p, g in zip(prompts, (12, 6, 10))]
            res = eng.run_to_completion()
            return [res[i] for i in ids], eng

        plain, _ = serve_rep(None)
        spec1, e1 = serve_rep(None, speculate=True, draft_k=4)
        spec2, e2 = serve_rep(cluster, speculate=True, draft_k=4)
        assert spec1 == plain, (spec1, plain)
        assert spec2 == plain, (spec2, plain)
        m1 = e1.metrics()["speculative"]
        m2 = e2.metrics()["speculative"]
        assert m2["drafted_tokens"] > 0, m2
        assert (m1["drafted_tokens"], m1["accepted_tokens"]) == \\
            (m2["drafted_tokens"], m2["accepted_tokens"]), (m1, m2)
        print("ok")
    """, devices=2, preamble=_TRACE)
    assert "ok" in out


def test_sharded_pallas_interpret_exact():
    """The Pallas block-table-walk kernel runs *per shard* inside the
    step's shard_map (interpret mode on CPU) and stays token-exact."""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("ck", 2, model_axis=2)
        kw = dict(use_pallas=True, interpret=True)
        single, _ = serve(cfg, params, None, **kw)
        shard, eng = serve(cfg, params, cluster, **kw)
        assert eng.metrics()["attention_backend"] == "pallas-interpret"
        assert eng.tp.shard_attn
        assert shard == single, (shard, single)
        print("ok")
    """, devices=2, preamble=_TRACE)
    assert "ok" in out


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_sharded_prefix_cache_exact(devices):
    """Prefix caching is shard-oblivious (DESIGN.md §9): on a 1/2/4-way
    cluster a shared-system-prompt trace served twice with
    prefix_cache=True — warm wave riding cached pages, incl. a
    fully-cached aligned prompt that forces a per-shard copy_page COW —
    emits byte-identical streams to the same engine with the cache off,
    with hits and COW copies actually recorded."""
    out = run_child("""
        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cluster = plat.create_cluster("pc", %d, model_axis=%d)
        rng = np.random.default_rng(1)
        sysp = rng.integers(0, cfg.vocab, 8).astype(np.int32)
        prompts = [np.concatenate(
            [sysp, rng.integers(0, cfg.vocab, n).astype(np.int32)])
            for n in (4, 0, 3)]      # the 0-suffix prompt is page-aligned
        gens = (5, 4, 6)

        def waves(pc):
            eng = PagedServingEngine(cfg, params, mesh=cluster, max_slots=2,
                                     block_size=4, max_blocks_per_seq=8,
                                     prefill_chunk=3, prefix_cache=pc)
            out = []
            for _ in range(2):
                ids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
                res = eng.run_to_completion()
                out.append([res[i] for i in ids])
                eng.clear_finished()
            return out, eng.metrics()["prefix_cache"]

        plain, m_off = waves(False)
        cached, m = waves(True)
        assert cached == plain, (cached, plain)
        assert m_off["hit_tokens"] == 0
        assert m["hit_tokens"] > 0 and m["cow_copies"] >= 1, m
        print("ok")
    """ % (devices, devices), devices=devices, preamble=_TRACE)
    assert "ok" in out


def test_serve_on_cluster_verb():
    """`create_cluster` + `serve_on_cluster` + `get_results` round-trip:
    the platform verb serves the trace under the cluster lock, persists
    tokens to the run store, and unlocks on completion."""
    out = run_child("""
        import jax, numpy as np, pathlib, tempfile
        from repro.config import get_config, reduced
        from repro.core.platform import Platform
        from repro.models import model as M

        cfg = reduced(get_config("granite-3-2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        plat = Platform(pathlib.Path(tempfile.mkdtemp()))
        plat.create_cluster("srv", 2, model_axis=2)
        rng = np.random.default_rng(0)
        reqs = [(rng.integers(0, cfg.vocab, n).astype(np.int32), g)
                for n, g in ((5, 4), (7, 3))]
        h = plat.serve_on_cluster("srv", cfg, params, reqs,
                                  runname="serve-run", max_slots=2,
                                  block_size=4, max_blocks_per_seq=8)
        assert h.status == "done", h.error
        res = h.result
        assert sorted(len(t) for t in res["results"].values()) == [3, 4]
        assert res["metrics"]["cluster"]["shards"] == 2
        outdir = plat.get_results("serve-run")
        assert (outdir / "tokens.npz").exists()
        assert not plat.clusters["srv"].in_use
        plat.terminate_cluster("srv")

        # a data-parallel cluster would leave devices silently idle for
        # serving -> rejected with guidance instead
        from repro.core.resources import ResourceError
        plat.create_cluster("dp", 2, model_axis=1)
        try:
            plat.serve_on_cluster("dp", cfg, params, reqs)
        except ResourceError as e:
            assert "model_axis=2" in str(e)
        else:
            raise AssertionError("model_axis=1 cluster was not rejected")
        print("ok")
    """, devices=2, preamble=_TRACE)
    assert "ok" in out


# ---------------------------------------------------------------------------
# host-side (no mesh needed): plan rules + allocator accounting
# ---------------------------------------------------------------------------

def test_serving_param_spec_rules():
    plan = ServingTPPlan(axis="model", size=2, shard_attn=True,
                         shard_mlp=True, shard_vocab=True)
    P = serving_param_spec
    # embeddings always replicated (shard_map lookup must be local)
    assert P("embed/table", (512, 64), plan) == (None, None)
    # stacked layer weights keep the lead dim whole
    assert P("layers/attn/wq", (2, 64, 64), plan) == (None, None, "model")
    assert P("layers/attn/wo", (2, 64, 64), plan) == (None, "model", None)
    assert P("layers/mlp/wg", (2, 64, 128), plan) == (None, None, "model")
    assert P("layers/mlp/wo", (2, 128, 64), plan) == (None, "model", None)
    assert P("layers/ln1/scale", (2, 64), plan) == (None, None)
    assert P("lm_head/kernel", (64, 512), plan) == (None, "model")
    assert P("layers/moe/wg", (2, 4, 64, 64), plan) == (None,) * 4
    # pools are (L, NB, Hkv, BS, D): kv heads over the model axis
    assert serving_cache_spec(plan) == (None, None, "model", None, None)

    off = ServingTPPlan(axis="model", size=4, shard_attn=False,
                        shard_mlp=False, shard_vocab=False)
    for path, shape in (("layers/attn/wq", (2, 64, 64)),
                        ("layers/mlp/wo", (2, 128, 64)),
                        ("lm_head/kernel", (64, 512))):
        assert P(path, shape, off) == (None,) * len(shape)
    assert serving_cache_spec(off) == (None,) * 5


def test_allocator_per_shard_accounting():
    """N-way sharding divides per-shard page bytes by N; byte accounting
    tracks in-use pages (the field an operator sizes device memory with)."""
    a = BlockAllocator(9, 4, num_shards=4, page_bytes_per_shard=256)
    u = a.utilization()
    assert u["num_shards"] == 4
    assert u["pool_bytes_per_shard"] == 9 * 256
    assert u["in_use_bytes_per_shard"] == 0
    got = [a.allocate() for _ in range(3)]
    assert a.utilization()["in_use_bytes_per_shard"] == 3 * 256
    a.free(got)
    assert a.utilization()["in_use_bytes_per_shard"] == 0
    # default: single shard, no byte fields without a page size
    u = BlockAllocator(5, 4).utilization()
    assert u["num_shards"] == 1 and "page_bytes_per_shard" not in u
