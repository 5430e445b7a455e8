"""The harness end to end on the CPU at a tiny size: a sound run is
correct, every planted fault and the lower-precision control are not,
and the command refuses to run without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import run, traffic

DATA = run.BENCH / "tests" / "data"
CELL = {"name": "tiny.tiny", "config": "tiny", "traffic": "tiny",
        "chips": 1}
MANIFEST = {
    "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                   {"name": "itl_p95_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": n, "unit": "%"} for n in
                  ("batch_occupancy", "kv_pool_used_share",
                   "compiles_in_window", "model_step_mfu",
                   "paged_attn_roofline", "device_idle_share")]}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(seed=3, trace=False, mix="tiny", **kw):
    return run.run_cell(
        MANIFEST, CELL, seed, 1.0, trace,
        spec=run.load_config("tiny", DATA),
        mix=traffic.load_mix(mix, DATA / "mixes"),
        limits=run.load_limits("tiny", DATA), t_process=time.perf_counter(),
        peak=PEAK, **kw)


def test_sound_run_is_correct():
    res = _run(seed=2 ** 32 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["requests_compared"]["value"] >= 1


def test_traced_run_reports_per_layer_metrics():
    res = _run(trace=False)
    assert res["metrics"]
    # prompts at their drawn lengths: set-up warms every shape they reach
    traced = _run(seed=5, trace=True, mix="tiny_ragged")
    m = traced["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert 0 < m["batch_occupancy"]["value"] <= 100
    assert 0 < m["kv_pool_used_share"]["value"] <= 100
    assert traced["correct"]


def test_open_loop_times_from_the_due_time():
    man = dict(MANIFEST, end_to_end=MANIFEST["end_to_end"]
               + [{"name": "ttft_p95_ms", "unit": "ms"}])
    res = run.run_cell(
        man, CELL, 4, 1.0, False, spec=run.load_config("tiny", DATA),
        mix=traffic.load_mix("tiny_open", DATA / "mixes"),
        limits=run.load_limits("tiny", DATA), t_process=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0
    assert 20 <= res["attempted"] <= 60          # 40/s over one second


def _state_unchanged(engine):
    import jax
    import jax.numpy as jnp
    fn = engine._unified_fn

    def step(p, c, *a):
        keep = jax.tree.map(jnp.copy, c)
        toks, _ = fn(p, c, *a)
        return toks, keep
    engine._unified_fn = step


def _token_altered(engine):
    fn = engine._unified_fn
    vocab = engine.cfg.vocab

    def step(p, c, *a):
        toks, c = fn(p, c, *a)
        return (toks + 1) % vocab, c
    engine._unified_fn = step


def _half_batch_left_out(engine):
    fn = engine._unified_fn
    half = engine.max_slots // 2

    def step(p, c, *a):
        toks, c = fn(p, c, *a)
        return toks.at[half:].set(0), c
    engine._unified_fn = step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch_left_out],
                         ids=["state_unchanged", "token_altered",
                              "half_batch_left_out"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    # compare many requests, so that which ones the window finished (which
    # depends on the host's speed) cannot leave the faulty rows out
    monkeypatch.setattr(run, "CHECK_REQUESTS", 24)
    res = _run(seed=7, fault=fault)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_is_not_correct():
    res = _run(seed=9, control="float8_e4m3fn")
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] <= \
        res["checks"]["served_gap"]["limit"]


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-2b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_exits_nonzero_without_a_chip():
    out = _bench_cmd(run.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
