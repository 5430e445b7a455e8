"""Mixture-of-Experts block (grok-1: 8e top-2; olmoe: 64e top-8).

Dispatch is sort-based (dropless up to a capacity factor): assignments are
ranked within their expert via a stable argsort, scattered into a dense
(E, C, d) buffer, processed with batched per-expert matmuls (MXU friendly),
and combined back with router weights.  This avoids the GShard one-hot
dispatch tensor (T x E x C) which is quadratically oversized at 64 experts.

Under pjit, the (E, C, d) buffer is sharding-constrained so the batched
matmuls run expert-parallel over the "model" axis (EP) when E divides the
axis, or hidden-sharded (TP-in-expert) otherwise (grok-1: E=8 < 16).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import sharding
from repro.models.layers import _normal

Params = Dict[str, Any]


def init_moe(cfg, key, n_layers: int) -> Params:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    L = (n_layers,) if n_layers else ()
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: Params = {
        "router": _normal(ks[0], L + (d, E), d ** -0.5, jnp.float32),
        "wo": _normal(ks[3], L + (E, f, d), f ** -0.5, dt),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["wg"] = _normal(ks[1], L + (E, d, f), d ** -0.5, dt)
        p["wu"] = _normal(ks[2], L + (E, d, f), d ** -0.5, dt)
    else:
        p["wi"] = _normal(ks[1], L + (E, d, f), d ** -0.5, dt)
    return p


def capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def apply_moe(p: Params, x: jnp.ndarray, cfg,
              cap: Optional[int] = None):
    """x: (B, S, d) -> ((B, S, d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.num_experts, m.top_k
    C = cap if cap is not None else capacity(T, cfg)

    xf = x.reshape(T, d)
    router_logits = (xf.astype(jnp.float32) @ p["router"])  # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)              # renormalise

    flat_ids = expert_ids.reshape(-1)                        # (T*k,)
    order = jnp.argsort(flat_ids, stable=True)
    inv = jnp.argsort(order)                                 # rank in sorted order
    counts = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts                     # (E,)
    slot = inv - starts[flat_ids]                            # pos within expert
    keep = slot < C
    dest = jnp.where(keep, flat_ids * C + slot, E * C)       # drop index

    token_of = jnp.arange(T * k) // k
    buf = jnp.zeros((E * C, d), x.dtype).at[dest].set(
        xf[token_of], mode="drop").reshape(E, C, d)
    buf = sharding.constrain(buf, "tp" if E % _tp() == 0 else None, None, None)

    # batched per-expert FFN
    if cfg.act in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(x.dtype))
        act = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = act * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(x.dtype)),
                        approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))
    out_buf = sharding.constrain(out_buf,
                                 "tp" if E % _tp() == 0 else None, None, None)

    gathered = out_buf.reshape(E * C, d).at[jnp.minimum(dest, E * C - 1)].get()
    gathered = jnp.where((keep & (dest < E * C))[:, None], gathered, 0.0)
    weighted = gathered * gate_vals.reshape(-1, 1).astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[token_of].add(weighted)
    return out.reshape(B, S, d), aux_loss(router_logits, expert_ids, E)


def _tp() -> int:
    info = sharding.active_info()
    return info.tp_size if info is not None else 1


# ---------------------------------------------------------------------------
# Explicit expert-parallel dispatch via shard_map (§Perf iteration).
#
# The GSPMD path above leaves the (T*k, d) gather/scatter tensors sharded at
# the partitioner's discretion, which at 64 experts materialises global
# dispatch buffers (olmoe train_4k baseline: 179 GiB/device peak).  Here the
# dispatch is written per-device: tokens stay in their data shard, each
# model-rank dispatches ONLY to its local experts (EP) or computes all
# experts with the hidden dim sharded (TP fallback, grok's E=8 < 16), and
# the combine is one psum over the model axis.
# ---------------------------------------------------------------------------

def _local_dispatch_compute(xf, router_w, wg, wu, wi, wo, cfg, e0, E_loc,
                            C: int):
    """Per-device MoE over local experts [e0, e0+E_loc).  xf: (T, d)."""
    m = cfg.moe
    T, d = xf.shape
    E, k = m.num_experts, m.top_k
    router_logits = xf.astype(jnp.float32) @ router_w          # (T, E) full
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_ids = expert_ids.reshape(-1)
    local = (flat_ids >= e0) & (flat_ids < e0 + E_loc)
    lids = jnp.where(local, flat_ids - e0, E_loc)               # E_loc = drop
    order = jnp.argsort(lids, stable=True)
    inv = jnp.argsort(order)
    counts = jnp.zeros((E_loc + 1,), jnp.int32).at[lids].add(1)
    starts = jnp.cumsum(counts) - counts
    slot = inv - starts[lids]
    keep = local & (slot < C)
    dest = jnp.where(keep, lids * C + slot, E_loc * C)

    token_of = jnp.arange(T * k) // k
    buf = jnp.zeros((E_loc * C, d), xf.dtype).at[dest].set(
        xf[token_of], mode="drop").reshape(E_loc, C, d)

    if cfg.act in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xf.dtype))
        uu = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xf.dtype))
        act = jax.nn.silu(g) if cfg.act == "swiglu" else \
            jax.nn.gelu(g, approximate=True)
        h = act * uu
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wi.astype(xf.dtype)),
                        approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(xf.dtype))

    gathered = out_buf.reshape(E_loc * C, d).at[
        jnp.minimum(dest, E_loc * C - 1)].get()
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered * gate_vals.reshape(-1, 1).astype(xf.dtype)
    out = jnp.zeros((T, d), xf.dtype).at[token_of].add(weighted)
    return out, aux_loss(router_logits, expert_ids, E)


def apply_moe_shard_map(p: Params, x: jnp.ndarray, cfg,
                        info: "sharding.MeshInfo"):
    """Expert-parallel MoE with explicit per-device dispatch + one psum."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    E = m.num_experts
    M = info.tp_size
    tp = info.tp_axis
    dp = info.dp_axes
    ep = E % M == 0                 # expert-parallel vs TP-in-expert
    B = x.shape[0]
    dp_ok = B % max(1, info.dp_size) == 0
    x_batch_axes = (dp if len(dp) > 1 else dp[0]) if dp_ok else None

    has_glu = cfg.act in ("swiglu", "geglu")
    wg = p.get("wg")
    wu = p.get("wu")
    wi = p.get("wi")
    wo = p["wo"]
    if ep:
        w_spec = P(tp, None, None)
        wo_spec = P(tp, None, None)
    else:
        w_spec = P(None, None, tp)
        wo_spec = P(None, tp, None)

    def device_fn(x_loc, router_w, *ws):
        Bl, S, d = x_loc.shape
        xf = x_loc.reshape(Bl * S, d)
        T_loc = xf.shape[0]
        if ep:
            E_loc = E // M
            e0 = lax.axis_index(tp) * E_loc
            # local capacity: expected local share + slack
            C = max(8, -(-int(T_loc * m.top_k * m.capacity_factor / E) // 8) * 8)
        else:
            E_loc, e0 = E, 0
            C = max(8, -(-int(T_loc * m.top_k * m.capacity_factor / E) // 8) * 8)
        g_, u_, i_ = None, None, None
        if has_glu:
            g_, u_ = ws[0], ws[1]
        else:
            i_ = ws[0]
        o_ = ws[-1]
        out, aux = _local_dispatch_compute(xf, router_w, g_, u_, i_, o_,
                                           cfg, e0, E_loc, C)
        # EP: ranks hold disjoint experts -> psum combines their outputs.
        # TP: outputs are partial sums over the sharded hidden dim -> the
        # same psum is the correct reduction.
        out = lax.psum(out, tp)
        aux = lax.pmean(aux, dp) if dp_ok and dp else aux
        aux = lax.pmean(aux, tp)
        return out.reshape(Bl, S, d), aux[None]

    in_specs = [P(x_batch_axes, None, None), P(None, None)]
    ws = []
    if has_glu:
        ws += [wg, wu]
        in_specs += [w_spec, w_spec]
    else:
        ws += [wi]
        in_specs += [w_spec]
    ws += [wo]
    in_specs += [wo_spec]

    fn = shard_map(device_fn, mesh=info.mesh,
                   in_specs=tuple(in_specs),
                   out_specs=(P(x_batch_axes, None, None), P(None)),
                   check_vma=False)
    out, aux = fn(x, p["router"], *ws)
    return out, aux[0]


def aux_loss(router_logits: jnp.ndarray, expert_ids: jnp.ndarray, E: int):
    """Standard load-balancing auxiliary loss (Switch-style)."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    me = probs.mean(0)
    one_hot = jax.nn.one_hot(expert_ids, E).sum(1)
    ce = one_hot.mean(0)
    return E * jnp.sum(me * ce)
