"""Sharding rules: path-rule PartitionSpecs for params + activation constraints.

Training strategy (GSPMD; serving uses the shard_map plan further down,
DESIGN.md §7):
  - batch over data axes ("pod", "data")
  - tensor parallel over "model": attention heads (when divisible), MLP
    hidden, MoE experts (or per-expert hidden when expert count is not
    divisible), vocab/embedding
  - optional FSDP: remaining large axis of every weight over "data"
  - KV caches: kv-heads over "model" when divisible, else sequence over
    "model"

Activation constraints go through a small context (``activation_sharding``)
so model code stays mesh-agnostic and runs unsharded in CPU smoke tests.
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh
    dp_axes: Tuple[str, ...]   # ("pod", "data") or ("data",)
    tp_axis: str               # "model"

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= int(self.mesh.shape[a])
        return n

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]


def mesh_info(mesh: Mesh) -> MeshInfo:
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = "model" if "model" in names else names[-1]
    return MeshInfo(mesh=mesh, dp_axes=dp, tp_axis=tp)


# ---------------------------------------------------------------------------
# Activation-sharding context
# ---------------------------------------------------------------------------

_ACTIVE: Optional[MeshInfo] = None


@contextmanager
def activation_sharding(info: Optional[MeshInfo]):
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = info
    try:
        yield
    finally:
        _ACTIVE = prev


def active_info() -> Optional[MeshInfo]:
    return _ACTIVE


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Apply with_sharding_constraint if a mesh context is active.

    spec entries: "dp" expands to the data axes tuple, "tp" to the model
    axis, None stays None.
    """
    info = _ACTIVE
    if info is None:
        return x
    parts = []
    for s in spec:
        if s == "dp":
            parts.append(info.dp_axes if len(info.dp_axes) != 1 else info.dp_axes[0])
        elif s == "tp":
            parts.append(info.tp_axis)
        else:
            parts.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(info.mesh, P(*parts)))


# ---------------------------------------------------------------------------
# Param partition specs (path rules)
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def param_spec(path: str, shape: Tuple[int, ...], cfg, info: MeshInfo) -> P:
    """Partition spec for one parameter, by path rules.

    Weights under ``layers/`` (or encoder/decoder stacks) carry a leading
    stacked-layer dim which is never sharded.
    """
    tp = info.tp_axis
    M = info.tp_size
    fsdp_axis = "data" if (cfg.fsdp and "data" in info.mesh.axis_names) else None
    stacked = bool(re.search(r"(^|/)layers/", path))
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape

    def maybe_fsdp(spec_body, prefer_axis_idx, dims=None):
        """If FSDP, shard over data on the preferred axis when free, else
        on any other free divisible axis (expert tensors: E may be blocked
        but d is shardable).  ``dims`` are the tensor dims spec_body refers
        to (defaults to the trailing dims of the body)."""
        if fsdp_axis is None:
            return spec_body
        sb = list(spec_body)
        dims = dims if dims is not None else body[-len(sb):]
        dsize = info.mesh.shape["data"]
        candidates = [prefer_axis_idx] + [i for i in range(len(sb))
                                          if i != prefer_axis_idx]
        for i in candidates:
            if sb[i] is None and dims[i] % dsize == 0 and dims[i] >= dsize:
                sb[i] = fsdp_axis
                break
        return tuple(sb)

    leaf = path.rsplit("/", 1)[-1]
    parent = path.rsplit("/", 2)[-2] if path.count("/") >= 1 else ""

    # --- embeddings / heads -------------------------------------------------
    if path.endswith("embed/table") or path.endswith("pos_embed/table"):
        body_spec = (tp, None) if body[0] % M == 0 else (None, None)
        if path.endswith("pos_embed/table"):
            body_spec = (None, None)
        return P(*lead, *maybe_fsdp(body_spec, 1))
    if path.endswith("lm_head/kernel"):
        return P(*lead, *maybe_fsdp((None, tp), 0))

    # --- norms / scalars ----------------------------------------------------
    if leaf in ("scale", "bias") or len(body) <= 1:
        return P(*lead, *([None] * len(body)))

    # --- MoE ----------------------------------------------------------------
    if parent == "moe" or "/moe/" in path:
        if leaf == "router":           # (d, E)
            return P(*lead, None, None)
        E = body[0]
        if E % M == 0:                 # expert parallel
            return P(*lead, tp, *maybe_fsdp((None,) * (len(body) - 1), 0))
        # TP within experts: shard the f dim
        if leaf in ("wg", "wu", "wi"):  # (E, d, f)
            return P(*lead, None, *maybe_fsdp((None, tp), 0))
        if leaf == "wo":               # (E, f, d)
            return P(*lead, None, *maybe_fsdp((tp, None), 1))

    # --- attention ----------------------------------------------------------
    if parent in ("attn", "cross") or "/attn/" in path or "/cross/" in path:
        h, hkv = cfg.n_heads, cfg.n_kv_heads
        if leaf == "wq":               # (d, h*hd)
            sb = (None, tp) if (h * cfg.head_dim) % M == 0 and h % M == 0 else (None, None)
            return P(*lead, *maybe_fsdp(sb, 0))
        if leaf in ("wk", "wv"):       # (d, hkv*hd)
            sb = (None, tp) if hkv % M == 0 else (None, None)
            return P(*lead, *maybe_fsdp(sb, 0))
        if leaf == "wo":               # (h*hd, d)
            sb = (tp, None) if h % M == 0 else (None, None)
            return P(*lead, *maybe_fsdp(sb, 1))

    # --- MLP ----------------------------------------------------------------
    if parent == "mlp" or "/mlp/" in path:
        if leaf in ("wg", "wu", "wi"):  # (d, f)
            sb = (None, tp) if body[-1] % M == 0 else (None, None)
            return P(*lead, *maybe_fsdp(sb, 0))
        if leaf == "wo":                # (f, d)
            sb = (tp, None) if body[0] % M == 0 else (None, None)
            return P(*lead, *maybe_fsdp(sb, 1))

    # --- RWKV / SSM ---------------------------------------------------------
    if "/rwkv/" in path or "/ssm/" in path or parent in ("rwkv", "ssm"):
        # projections (d, X): shard X over model when divisible
        sb = list((None,) * len(body))
        if body[-1] % M == 0 and len(body) >= 2:
            sb[-1] = tp
        return P(*lead, *maybe_fsdp(tuple(sb), 0))

    # default: replicate (with FSDP on the first big axis)
    sb = (None,) * len(body)
    return P(*lead, *maybe_fsdp(sb, 0))


def param_specs(params_shape: Any, cfg, info: MeshInfo):
    """Pytree of PartitionSpecs matching a params(-shaped) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(_path_str(path), leaf.shape, cfg, info),
        params_shape)


def named_shardings(params_shape: Any, cfg, info: MeshInfo):
    specs = param_specs(params_shape, cfg, info)
    return jax.tree.map(lambda s: NamedSharding(info.mesh, s), specs)


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_spec(cfg, info: MeshInfo, batch: int) -> P:
    """Spec for a KV cache entry (B, S, Hkv, D) (stacked layers -> lead None)."""
    dp = info.dp_axes if len(info.dp_axes) != 1 else info.dp_axes[0]
    M = info.tp_size
    b_axis = dp if batch % max(1, info.dp_size) == 0 and batch >= info.dp_size else None
    if cfg.n_kv_heads and cfg.n_kv_heads % M == 0:
        return P(None, b_axis, None, info.tp_axis, None)
    return P(None, b_axis, info.tp_axis, None, None)


def batch_spec(info: MeshInfo, batch: int) -> P:
    dp = info.dp_axes if len(info.dp_axes) != 1 else info.dp_axes[0]
    if batch % max(1, info.dp_size) == 0 and batch >= info.dp_size:
        return P(dp, None)
    return P(None, None)


# ---------------------------------------------------------------------------
# Serving tensor-parallel plan (cluster-sharded paged engine, DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# The paged serving engine runs its whole decode step under ``shard_map``
# (not GSPMD), so every leaf a shard sees is a *local* slice and the plan
# below must only shard tensors whose local math stays closed:
#
#   * attention  — wq/wk/wv column-sharded over heads, wo row-sharded:
#     each shard holds Hkv/M kv heads of the KV page pool and attends its
#     own head group end to end; the wo product is a partial sum -> psum.
#   * mlp        — wg/wu column-sharded over d_ff, wo row-sharded -> psum.
#   * vocab      — the lm_head (or tied embedding read-out) is sharded over
#     padded-vocab columns; each shard computes a V/M logits strip and the
#     full logits are all-gathered ONCE per decode step.
#   * embeddings — always replicated: a shard_map body cannot look up a
#     token row it does not hold (unlike GSPMD, there is no resharding).
#
# Each component degrades to replicated (still token-exact, no speedup)
# when its axis is not divisible by the mesh's model-parallel size, so any
# config runs on any cluster size.

@dataclass(frozen=True)
class ServingTPPlan:
    """How one model is tensor-parallelised over a serving cluster mesh.

    Attributes:
        axis: mesh axis name the shards live on (normally ``"model"``).
        size: number of shards (the axis extent).
        shard_attn: attention heads AND the paged KV pool are partitioned
            (requires ``n_heads % size == 0 and n_kv_heads % size == 0``).
        shard_mlp: MLP hidden dim is partitioned (``d_ff % size == 0``;
            MoE archs replicate their expert stack instead).
        shard_vocab: logits are computed as per-shard vocab strips and
            all-gathered (``padded_vocab % size == 0``).
    """
    axis: str
    size: int
    shard_attn: bool
    shard_mlp: bool
    shard_vocab: bool

    @property
    def sharded(self) -> bool:
        return self.size > 1


def serving_tp_plan(cfg, mesh: Mesh, axis: Optional[str] = None
                    ) -> ServingTPPlan:
    """Derive the tensor-parallel plan for serving ``cfg`` on ``mesh``.

    Follows the same divisibility rules as ``param_spec`` (shard when the
    axis divides, replicate otherwise) restricted to what is shard_map-local
    (see the block comment above).
    """
    from repro.models.layers import padded_vocab
    axis = axis or mesh_info(mesh).tp_axis
    M = int(mesh.shape[axis])
    multi = M > 1
    return ServingTPPlan(
        axis=axis, size=M,
        shard_attn=multi and cfg.n_heads % M == 0
        and cfg.n_kv_heads % M == 0,
        shard_mlp=multi and cfg.moe is None and cfg.d_ff % M == 0,
        shard_vocab=multi and padded_vocab(cfg.vocab) % M == 0)


def serving_param_spec(path: str, shape: Tuple[int, ...],
                       plan: ServingTPPlan) -> P:
    """shard_map in-spec for one serving parameter, by path rules.

    Unlike :func:`param_spec` (GSPMD training specs) this never shards
    embeddings or anything whose local math would be open (see the plan
    block comment); stacked ``layers/`` leaves keep their lead dim whole.
    """
    tp = plan.axis
    stacked = bool(re.search(r"(^|/)layers/", path))
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    leaf = path.rsplit("/", 1)[-1]
    replicated = P(*lead, *([None] * len(body)))

    if path.endswith("embed/table") or path.endswith("pos_embed/table"):
        return replicated                      # local token lookup
    if path.endswith("lm_head/kernel"):        # (d, Vp)
        return P(*lead, None, tp) if plan.shard_vocab else replicated
    if leaf in ("scale", "bias") or len(body) <= 1:
        return replicated
    if "/moe/" in path:
        return replicated        # experts replicate: routing is not local
    if "/attn/" in path and plan.shard_attn:
        if leaf in ("wq", "wk", "wv"):         # (d, heads*hd) col-parallel
            return P(*lead, None, tp)
        if leaf == "wo":                       # (h*hd, d) row-parallel
            return P(*lead, tp, None)
    if "/mlp/" in path and plan.shard_mlp:
        if leaf in ("wg", "wu", "wi"):         # (d, f) col-parallel
            return P(*lead, None, tp)
        if leaf == "wo":                       # (f, d) row-parallel
            return P(*lead, tp, None)
    return replicated


def serving_param_specs(params: Any, plan: ServingTPPlan):
    """Pytree of shard_map PartitionSpecs for the serving step's params."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: serving_param_spec(_path_str(path), leaf.shape,
                                              plan),
        params)


def unified_batch_specs() -> Tuple[P, ...]:
    """shard_map in-specs for the unified tick's flat ragged token batch
    (DESIGN.md §8): the single packed int32 buffer (tokens, positions,
    segment vectors, row map, and block tables in one host-built array) is
    replicated — every shard advances the *same* token set over its local
    weight/pool slices; only the weights, pools, and logits strips shard
    (``serving_param_specs`` / ``serving_cache_spec``)."""
    return (P(None),)


def serving_cache_spec(plan: ServingTPPlan) -> P:
    """Spec for one paged KV pool (L, num_blocks, Hkv, block_size, D):
    kv heads over the model axis when attention is sharded, else
    replicated.  Every shard sees the full pool in *pages* either way —
    the block allocator's page ids are global."""
    if plan.shard_attn:
        return P(None, None, plan.axis, None, None)
    return P(None, None, None, None, None)


def serving_scale_spec(plan: ServingTPPlan) -> P:
    """Spec for one quantization scale pool (L, num_blocks, Hkv,
    block_size) — the per-row fp32 scales of an int8 KV pool
    (``kv_dtype="int8"``).  Shards exactly like the pool it scales: kv
    heads over the model axis when attention shards, else replicated."""
    if plan.shard_attn:
        return P(None, None, plan.axis, None)
    return P(None, None, None, None)


def serving_cache_specs(cache: Any, plan: ServingTPPlan):
    """Per-pool specs for a whole paged cache dict: K/V pools via
    :func:`serving_cache_spec`, scale pools (``k_scale``/``v_scale``,
    one dim shorter) via :func:`serving_scale_spec`."""
    cspec, sspec = serving_cache_spec(plan), serving_scale_spec(plan)
    return {name: sspec if name.endswith("_scale") else cspec
            for name in cache}
