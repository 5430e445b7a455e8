"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.recovery import ops as rec_ops, ref as rec_ref
from repro.kernels.wkv6 import ops as wkv_ops, ref as wkv_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 128, 4, 2, 64), (1, 256, 8, 1, 128), (2, 64, 4, 4, 64),
    (1, 128, 6, 3, 128), (1, 512, 2, 2, 64),
])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 32, 0.0), (False, 0, 0.0), (True, 0, 30.0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(B, S, H, Hkv, D, causal, window, softcap,
                                dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    r = fa_ref.attention(q, k, v, causal=causal, window=window,
                         softcap=softcap)
    p = fa_ops.attention(q, k, v, causal=causal, window=window,
                         softcap=softcap, use_pallas=True, interpret=True,
                         block_q=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(r, np.float32),
                               np.asarray(p, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("E,m,P", [(256, 128, 16), (500, 300, 37),
                                   (128, 512, 64), (1024, 256, 8)])
def test_recovery_vs_ref(E, m, P):
    ks = jax.random.split(KEY, 3)
    il = jnp.abs(jax.random.normal(ks[0], (E, m)))
    w = jax.random.uniform(ks[1], (P, m))
    target = jnp.abs(jax.random.normal(ks[2], (E,)))
    r = rec_ref.basis_risk(il, target, w, 5.0, 20.0, 30.0)
    p = rec_ops.basis_risk(il, target, w, 5.0, 20.0, 30.0,
                           use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,S,H,D,chunk", [
    (2, 64, 4, 16, 16), (1, 96, 2, 32, 32), (2, 128, 3, 64, 64),
    (1, 64, 1, 128, 16),
])
def test_wkv6_vs_ref(B, S, H, D, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D)))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    a = wkv_ref.wkv(r, k, v, w, u)
    b = wkv_ops.wkv(r, k, v, w, u, use_pallas=True, interpret=True,
                    chunk=chunk)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-4, rtol=2e-4)


def test_flash_attention_used_by_model_matches_chunked():
    """The model's in-graph chunked attention equals the kernel oracle."""
    from repro.models.layers import _chunked_attention
    ks = jax.random.split(KEY, 3)
    B, S, H, Hkv, D = 2, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    ref = fa_ref.attention(q, k, v, causal=True, window=64)
    got = _chunked_attention(q, k, v, causal=True,
                             window=jnp.asarray(64), q_offset=0,
                             softcap=0.0, q_chunk=32, k_chunk=32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 32, 0.0), (True, 0, 30.0), (False, 0, 0.0),
])
def test_flash_attention_backward_kernels_vs_ref_grads(causal, window,
                                                       softcap):
    """Pallas dq/dk/dv kernels (custom_vjp) == autodiff through the oracle."""
    B, S, H, Hkv, D = 2, 128, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))

    def loss_ref(q, k, v):
        o = fa_ref.attention(q, k, v, causal=causal, window=window,
                             softcap=softcap)
        return jnp.sum(jnp.sin(o))

    def loss_ker(q, k, v):
        o = fa_ops.attention_trainable(q, k, v, causal=causal,
                                       window=window, softcap=softcap,
                                       interpret=True, block_q=32,
                                       block_k=64)
        return jnp.sum(jnp.sin(o))

    g1 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ker, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_pallas_attention_trainable_through_model():
    """A full train-style grad through the model with kernels enabled."""
    import dataclasses
    from repro.config import get_config, reduced
    from repro.models import model as M
    from tests.conftest import small_batch
    cfg = reduced(get_config("granite-3-2b"))
    cfg_k = dataclasses.replace(cfg, scan_layers=False,
                                use_pallas_attention=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = small_batch(cfg, S=64)
    g1 = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params)
    g2 = jax.grad(lambda p: M.loss_fn(cfg_k, p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# backend selection (repro.kernels.backend): the platform chooses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("env", [None, "0", "1"])
def test_backend_resolve_follows_platform(monkeypatch, tpu, env):
    """The paged ops run the compiled kernel on a TPU whatever
    REPRO_USE_PALLAS says; elsewhere the env var picks and the kernel runs
    in interpret mode.  Other kernels stay opt-in on every platform."""
    from repro.kernels import backend
    from repro.kernels.paged_attention import ops as paged_ops
    monkeypatch.setattr(backend, "on_tpu", lambda: tpu)
    if env is None:
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("REPRO_USE_PALLAS", env)
    opted_in = env == "1"
    assert paged_ops.resolve() == (tpu or opted_in, not tpu)
    assert backend.resolve(None, None) == (opted_in, not tpu)
    # an explicit argument always wins: the reference on any platform
    assert paged_ops.resolve(use_pallas=False) == (False, not tpu)


def test_backend_refuses_interpret_on_tpu(monkeypatch):
    from repro.kernels import backend
    from repro.kernels.paged_attention import ops as paged_ops
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="interpret"):
        paged_ops.resolve(interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        backend.resolve(True, True)
    monkeypatch.setattr(backend, "on_tpu", lambda: False)
    assert backend.resolve(True, True) == (True, True)
