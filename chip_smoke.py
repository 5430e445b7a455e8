"""Bring-up check: serve granite-3-2b at full width on one TPU chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # TP=4 cluster vs one device, 4 chips

One process, so one holder of the chip.  Weights and data are random from
fixed seeds.  Phases (one chip):

  kernel  the compiled paged-attention kernels against the jnp reference
          (``ref.py``) at granite-3-2b's widths — fused update over bf16
          and int8 pools, the unified ragged view, and ``copy_page``
          (which must match exactly);
  serve   ``repro.launch.serve.main`` with ``--engine paged``, no
          ``--reduced``: every request gets its full token count and the
          report says the compiled kernel served (``attention_backend``);
          prints the share of tokens that agree with isolated
          ``generate``;
  logits  one served step (``paged_attn.unified_step`` through the kernel,
          prefilling every prompt) against a float32 forward of the model
          at ``precision=HIGHEST``.

``--four-chip`` runs only the cluster path: the same requests served
tensor-parallel over four chips (``--cluster``), then one device; streams
are compared token by token and the TP=4 step's logits against the
one-device step's.

It exits non-zero, and prints no result, unless JAX's first device is a
TPU.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ARCH = "granite-3-2b"
SEED = 0
BATCH, PROMPT, GEN = 8, 128, 32
# bf16 kernel vs reference (both read the same bf16/int8 pages): the
# tolerance of the bf16 parity test in tests/test_paged_kernel.py
KERNEL_TOL = 2e-2
# served bf16 logits vs the float32 forward, and TP=4 vs one device:
# ||difference|| / ||reference|| over all rows and the vocabulary.  bf16
# keeps 8 mantissa bits (0.4% per rounding); a 40-layer granite stack at
# reduced width lands near 2% in the max norm on the CPU
LOGITS_TOL = 5e-2


NOTES: list = []


def note(msg: str) -> None:
    """Print a result line; all of them are repeated at the end, after the
    serving reports, so the tail of the output carries every number."""
    NOTES.append(msg)
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache (count,
    seconds — JAX's backend-compile event covers both) and cache hits."""

    def __init__(self):
        from jax import monitoring
        self.n, self.secs, self.hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.secs, self.hits


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def _case(rng, cfg, *, B, S, filled, n_fresh, MB, int8):
    """Random pools/tables at the config's widths; row b holds
    ``filled[b]`` cached tokens and adds ``n_fresh[b]`` this step."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import ref
    H, HKV, D, BS = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16
    NB = 1 + B * MB
    pages = rng.permutation(np.arange(1, NB))      # scattered page ids
    pos = np.full((B, S), -1, np.int32)
    tables = np.zeros((B, MB), np.int32)
    for b, (f, n) in enumerate(zip(filled, n_fresh)):
        pos[b, :n] = f + np.arange(n)
        tables[b] = pages[b * MB:(b + 1) * MB]
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    c = dict(q=normal(B, S, H, D), k_new=normal(B, S, HKV, D),
             v_new=normal(B, S, HKV, D), block_tables=jnp.asarray(tables),
             positions=jnp.asarray(pos))
    for name in ("k", "v"):
        pool = normal(NB, HKV, BS, D)
        if int8:
            pool, scale = ref.quantize_rows(pool)
            c[f"{name}_scale"] = scale
        c[f"{name}_pool"] = pool
    live = int(pos.max()) // BS + 1
    return c, live, pos >= 0


def _compare(name, got, want, valid, pools_got, pools_want):
    import numpy as np
    g = np.asarray(got, np.float32)[valid]
    w = np.asarray(want, np.float32)[valid]
    err = float(np.max(np.abs(g - w)))
    ok = bool(np.all(np.abs(g - w) <= KERNEL_TOL + KERNEL_TOL * np.abs(w)))
    # fused scatter: every page but the null block bit-identical
    same = all(np.array_equal(np.asarray(a)[1:], np.asarray(b)[1:])
               for a, b in zip(pools_got, pools_want))
    note(f"kernel {name}: max|kernel-ref| {err:.6g} "
         f"(tol {KERNEL_TOL} + {KERNEL_TOL}*|ref|), pools identical {same}")
    if not (ok and same):
        fail(f"kernel {name} disagrees with the reference")


def kernel_phase(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import ops, ref
    rng = np.random.default_rng(SEED)
    win = jnp.int32(1 << 30)
    filled = [0, 5, 16, 100, 255, 511, 777, 1000]
    for int8 in (False, True):
        c, live, valid = _case(rng, cfg, B=8, S=1, filled=filled,
                               n_fresh=[1] * 8, MB=64, int8=int8)
        run = lambda use: ops.paged_attention_update(  # noqa: E731
            **c, window=win, softcap=0.0, max_live_blocks=live,
            use_pallas=use)
        got, want = run(True), run(False)
        _compare("decode " + ("int8" if int8 else "bf16"), got[0], want[0],
                 valid, got[1:], want[1:])

    # unified ragged view: decodes and prefill chunks in one flat batch
    segs = [(0, 16), (40, 16), (100, 1), (333, 1), (17, 8), (900, 1),
            (64, 16), (250, 3)]
    R, C, MB = len(segs), 16, 64
    c, live, _ = _case(rng, cfg, B=R, S=C, filled=[f for f, _ in segs],
                       n_fresh=[n for _, n in segs], MB=MB, int8=False)
    pos = np.asarray(c["positions"])
    real = int((pos >= 0).sum())
    T = real + 1                                    # one dead flat row
    row_map = np.full((R, C), real, np.int32)
    flat_pos = np.full((T, 1), -1, np.int32)
    pick = np.zeros(T, np.int64)                    # flat row -> (r, s)
    r0 = 0
    for r, (_, n) in enumerate(segs):
        row_map[r, :n] = np.arange(r0, r0 + n)
        flat_pos[r0:r0 + n, 0] = pos[r, :n]
        pick[r0:r0 + n] = r * C + np.arange(n)
        r0 += n
    flat = lambda a: jnp.take(a.reshape(R * C, 1, *a.shape[2:]),  # noqa: E731
                              jnp.asarray(pick), axis=0)
    args = dict(q=flat(c["q"]), k_new=flat(c["k_new"]),
                v_new=flat(c["v_new"]), k_pool=c["k_pool"],
                v_pool=c["v_pool"], req_tables=c["block_tables"],
                positions=jnp.asarray(flat_pos),
                row_map=jnp.asarray(row_map))
    run = lambda use: ops.paged_attention_unified(  # noqa: E731
        **args, window=win, softcap=0.0, max_live_blocks=live,
        max_seg_len=C, use_pallas=use)
    got, want = run(True), run(False)
    _compare("unified", got[0], want[0], flat_pos[:, 0] >= 0, got[1:],
             want[1:])

    pool = jnp.asarray(rng.standard_normal((cfg.n_layers, 65, cfg.n_kv_heads,
                                            16, cfg.head_dim)), jnp.bfloat16)
    got = ops.copy_page(pool, jnp.int32(3), jnp.int32(60), use_pallas=True)
    exact = bool(jnp.array_equal(got, ref.copy_page(pool, 3, 60)))
    note(f"kernel copy_page: exact {exact}")
    if not exact:
        fail("copy_page differs from the reference")


# ---------------------------------------------------------------------------
# serve + logits phases
# ---------------------------------------------------------------------------

def serve_args(extra=()):
    return ["--arch", ARCH, "--engine", "paged", "--batch", str(BATCH),
            "--prompt-len", str(PROMPT), "--gen", str(GEN),
            "--seed", str(SEED), *extra]


def serve(argv, counter, label):
    """One served run through the CLI entry point; returns its report,
    with the streams under ``results``."""
    from repro.launch import serve as serve_cli
    n0, s0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    report = serve_cli.main(argv)
    wall = time.perf_counter() - t0
    n1, s1, h1 = counter.snapshot()
    note(f"{label}: wall {wall:.3f} s, programs compiled or loaded in "
         f"the served window {n1 - n0} ({s1 - s0:.3f} s, {h1 - h0} from "
         f"the persistent cache), backend {report['attention_backend']}")
    if report["attention_backend"] != "pallas":
        fail(f"{label} served through {report['attention_backend']}, "
             "not the compiled kernel")
    results = report["results"]
    short = {rid: len(t) for rid, t in results.items() if len(t) != GEN}
    if len(results) != BATCH or short:
        fail(f"{label}: {len(results)} requests finished, short: {short}")
    return report


def inputs(cfg):
    """The served run's weights and prompts (``serve.main``'s seeds)."""
    import jax

    from repro.serving.paged_attn import init_serving_params
    key = jax.random.PRNGKey(SEED)
    prompts = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                 (BATCH, PROMPT), 0, cfg.vocab)
    return init_serving_params(cfg, key), prompts


def agreement(a, b) -> float:
    pairs = [(x, y) for rid in a for x, y in zip(a[rid], b[rid])]
    return sum(x == y for x, y in pairs) / max(1, len(pairs))


def prefill_step(cfg, params, prompts, tp=None, mesh=None):
    """Logits (R, V) of ONE unified tick that prefills every prompt whole,
    through the compiled kernel — on one device, or TP-sharded over
    ``mesh`` the way the engine shards a tick."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import sharding
    from repro.serving import paged_attn
    R, P = prompts.shape
    BS = 16
    MB = -(-P // BS)
    cache = paged_attn.init_paged_cache(cfg, 1 + R * MB, BS)
    tables = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB), jnp.int32)
    flat = np.arange(R * P).reshape(R, P)
    batch = (prompts.reshape(-1).astype(jnp.int32),
             jnp.tile(jnp.arange(P, dtype=jnp.int32), R), tables,
             jnp.asarray(flat, jnp.int32),
             jnp.asarray(flat[:, -1:], jnp.int32))

    def step(p, c, *b):
        return paged_attn.unified_step(cfg, p, c, *b, max_live_blocks=MB,
                                       max_seg_len=P, use_pallas=True,
                                       tp=tp)[0]

    if tp is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P_
        pspecs = sharding.serving_param_specs(params, tp)
        cspecs = sharding.serving_cache_specs(cache, tp)
        put = lambda t, s: jax.device_put(t, jax.tree.map(  # noqa: E731
            lambda x: NamedSharding(mesh, x), s))
        params, cache = put(params, pspecs), put(cache, cspecs)
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(pspecs, cspecs) + (P_(),) * 5,
                             out_specs=P_(), check_vma=False)
    logits = jax.jit(step)(params, cache, *batch)
    return np.asarray(logits[:, 0, :cfg.vocab], np.float32)


def reference_logits(cfg, params, prompts):
    """Last-position logits of a float32 forward at precision=HIGHEST, one
    layer at a time so only one layer's weights exist in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.layers import apply_norm, embed_tokens, \
        logits_from_hidden
    from repro.models.transformer import layer_body, layer_windows
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    pos = jnp.arange(prompts.shape[1])
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: embed_tokens(e, t, cfg32))(
            f32(params["embed"]), prompts)
        layer = jax.jit(lambda lp, x, w: layer_body(
            lp, x, cfg32, positions=pos, window=w)[0])
        for i, w in enumerate(layer_windows(cfg)):
            lp = f32(jax.tree.map(lambda a: a[i], params["layers"]))
            x = layer(lp, x, jnp.int32(w))
        head = jax.jit(lambda p, x: logits_from_hidden(
            p, apply_norm(p["final_ln"], x), cfg32))
        logits = head({"embed": f32(params["embed"]),
                       "final_ln": f32(params["final_ln"])}, x[:, -1:])
    return np.asarray(logits[:, 0, :cfg.vocab], np.float32)


def compare_logits(label, got, want):
    import numpy as np
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    worst = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    argmax = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    note(f"{label}: ||diff||/||ref|| {err:.6g} (bound {LOGITS_TOL}), "
         f"max|diff|/max|ref| {worst:.6g}, argmax agreement {argmax:.3f}")
    if not err <= LOGITS_TOL:
        fail(f"{label} outside the bound")


def one_chip(cfg, counter):
    import numpy as np

    from repro.launch.serve import generate
    t0 = time.perf_counter()
    kernel_phase(cfg)
    note(f"kernel phase: {time.perf_counter() - t0:.3f} s")

    report = serve(serve_args(), counter, "serve")
    gc.collect()
    params, prompts = inputs(cfg)
    ref = np.asarray(generate(cfg, params, prompts, GEN))[:, PROMPT:]
    ids = sorted(report["results"])
    share = agreement({i: report["results"][i] for i in ids},
                      {i: ref[k].tolist() for k, i in enumerate(ids)})
    note(f"serve: token agreement with isolated generate {share:.4f}")

    compare_logits("logits", prefill_step(cfg, params, prompts),
                   reference_logits(cfg, params, prompts))
    del params
    gc.collect()


def four_chip(cfg, counter):
    from repro.core.resources import build_cluster_mesh
    from repro.sharding import serving_tp_plan
    import jax
    if len(jax.devices()) < 4:
        fail(f"--four-chip needs 4 devices, found {len(jax.devices())}")
    tp = serve(serve_args(["--cluster", "smoke", "--cluster-size", "4"]),
               counter, "serve tp4")
    if (tp.get("cluster") or {}).get("shards") != 4:
        fail(f"cluster report: {tp.get('cluster')}")
    gc.collect()
    one = serve(serve_args(), counter, "serve 1 device")
    gc.collect()
    note(f"tp4 vs 1 device: token agreement "
         f"{agreement(tp['results'], one['results']):.4f}")
    params, prompts = inputs(cfg)
    want = prefill_step(cfg, params, prompts)
    mesh = build_cluster_mesh(jax.devices()[:4], model_axis=4)
    got = prefill_step(cfg, params, prompts, tp=serving_tp_plan(cfg, mesh),
                       mesh=mesh)
    compare_logits("logits tp4 vs 1 device", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="only the TP=4 cluster path and its one-device "
                         "comparison (needs 4 chips)")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    try:
        from repro.config import get_config
        from repro.launch.bootstrap import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")
    note(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    note(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    (four_chip if args.four_chip else one_chip)(cfg, counter)
    n, secs, hits = counter.snapshot()
    stats = dev.memory_stats() or {}
    note(f"total: {time.perf_counter() - t0:.3f} s, programs compiled or "
         f"loaded {n} ({secs:.3f} s, {hits} from the persistent cache), "
         f"peak HBM bytes "
         f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print("--- summary ---\n" + "\n".join(NOTES))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
