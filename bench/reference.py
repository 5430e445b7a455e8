"""The plain reference: a float32 forward of a dense GQA decoder.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, written from the
architecture's equations and importing nothing of the program: token
embedding, then per layer RMSNorm -> attention (rotary position on the
first ``rope_fraction`` of each head's dims, adjacent pairs rotated
together; grouped-query heads, head ``h`` reading kv head ``h // G``;
causal softmax) -> residual -> RMSNorm -> SwiGLU MLP -> residual, then a
final RMSNorm and the output head (the embedding table when tied).

It runs one layer at a time, with only that layer's weights in float32,
and blocked over query rows, so a 16k-token sequence fits beside nothing
else on one chip.  Logits are formed only at the positions asked for.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

HIGHEST = None        # set on first use (keeps jax out of import time)
Q_BLOCK = 512         # query rows per attention/MLP block
LEN_QUANTUM = 512     # sequences are padded to a multiple of this
V_BLOCK = 16384       # output-head columns cast to float32 at once


def _jax():
    global HIGHEST
    import jax
    HIGHEST = jax.lax.Precision.HIGHEST
    return jax


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * jax_rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def jax_rsqrt(x):
    import jax
    return jax.lax.rsqrt(x)


def _rope(x, pos, theta, fraction):
    """x: (S, n, D); rotate adjacent pairs of the first ``rot`` dims."""
    import jax.numpy as jnp
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, rot/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :rot:2], x[..., 1:rot:2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([rotated, x[..., rot:]], axis=-1)


def _fake_quant(x, low: Optional[str]):
    """``x`` rounded to ``low`` (a float8 type) with one scale for the
    tensor, as a lower-precision matmul would see it; identity for None."""
    if low is None:
        return x
    import jax.numpy as jnp
    fmax = float(jnp.finfo(low).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmax
    return (x / s).astype(low).astype(jnp.float32) * s


@lru_cache(maxsize=None)
def _layer_fn(L_key: tuple, low: Optional[str] = None):
    """Jitted float32 layer for one model shape; with ``low`` every
    matmul operand is rounded to that type first (the control)."""
    jax = _jax()
    import jax.numpy as jnp
    h, hkv, hd, theta, frac, eps = L_key
    G = h // hkv
    fq = lambda x: _fake_quant(x, low)  # noqa: E731

    def mm(a, b):
        return jnp.matmul(fq(a), fq(b), precision=HIGHEST)

    def layer(lp, x):
        S = x.shape[0]
        pos = jnp.arange(S)
        xn = _rms(x, lp["ln1"], eps)
        k = _rope(mm(xn, lp["wk"]).reshape(S, hkv, hd), pos, theta, frac)
        v = mm(xn, lp["wv"]).reshape(S, hkv, hd)
        nq = S // Q_BLOCK

        def attend(i):
            rows = jax.lax.dynamic_slice_in_dim(xn, i * Q_BLOCK, Q_BLOCK)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            q = _rope(mm(rows, lp["wq"]).reshape(Q_BLOCK, h, hd), qpos,
                      theta, frac).reshape(Q_BLOCK, hkv, G, hd)
            s = jnp.einsum("qkgd,skd->kgqs", fq(q), fq(k),
                           precision=HIGHEST) * hd ** -0.5
            s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :,
                                                           None],
                          s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgqs,skd->qkgd", fq(p), fq(v),
                           precision=HIGHEST)
            return mm(o.reshape(Q_BLOCK, h * hd), lp["wo"])

        x = x + jax.lax.map(attend, jnp.arange(nq)).reshape(S, -1)

        def mlp(rows):
            rn = _rms(rows, lp["ln2"], eps)
            return rows + mm(jax.nn.silu(mm(rn, lp["wg"])) * mm(rn, lp["wu"]),
                             lp["wo_mlp"])

        return jax.lax.map(mlp, x.reshape(nq, Q_BLOCK, -1)).reshape(S, -1)

    return jax.jit(layer)


def _layer_weights(params, i: int):
    import jax.numpy as jnp
    f32 = lambda a: jnp.asarray(a[i], jnp.float32)  # noqa: E731
    lay = params["layers"]
    return {"ln1": f32(lay["ln1"]["scale"]), "ln2": f32(lay["ln2"]["scale"]),
            "wq": f32(lay["attn"]["wq"]), "wk": f32(lay["attn"]["wk"]),
            "wv": f32(lay["attn"]["wv"]), "wo": f32(lay["attn"]["wo"]),
            "wg": f32(lay["mlp"]["wg"]), "wu": f32(lay["mlp"]["wu"]),
            "wo_mlp": f32(lay["mlp"]["wo"])}


def logits(model: dict, params, tokens: np.ndarray,
           at: Sequence[int], low: Optional[str] = None,
           pad_to: int = 0) -> np.ndarray:
    """Float32 logits (len(at), vocab) of ``tokens`` at positions ``at``.
    ``low`` (a float8 type name) computes every matmul from operands
    rounded to it: the lower-precision control.

    ``model`` is the configuration's ``model`` block; ``params`` the
    weights in the engine's layout.  The sequence is padded at its end to
    a multiple of ``LEN_QUANTUM`` (causal, so the padding changes no
    earlier position) so that sequences of similar length share one
    compiled program; ``pad_to`` pads it further, to one length for every
    sequence of a cell (one program, compiled once)."""
    jax = _jax()
    import jax.numpy as jnp
    S = len(tokens)
    Sp = -(-max(S, pad_to) // LEN_QUANTUM) * LEN_QUANTUM
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    eps = float(model.get("norm_eps", 1e-6))
    key = (model["n_heads"], model["n_kv_heads"], model["head_dim"],
           float(model["rope_theta"]), float(model.get("rope_fraction", 1.0)),
           eps)
    layer = _layer_fn(key, low)
    x = jnp.take(jnp.asarray(params["embed"]["table"]), jnp.asarray(toks),
                 axis=0).astype(jnp.float32)
    for i in range(model["n_layers"]):
        x = layer(_layer_weights(params, i), x)
    rows = x[jnp.asarray(np.asarray(at, np.int32))]
    rows = _rms(rows, jnp.asarray(params["final_ln"]["scale"], jnp.float32),
                eps)
    if model["tie_embeddings"]:
        head = jnp.asarray(params["embed"]["table"]).T
    else:
        head = jnp.asarray(params["lm_head"]["kernel"])
    V = model["vocab"]
    if low is not None:      # one scale for the whole head, as a matmul
        hmax = float(jnp.max(jnp.abs(head[:, :V].astype(jnp.float32))))
    out = []
    for a in range(0, V, V_BLOCK):     # the head in float32, a block at once
        w = head[:, a:min(a + V_BLOCK, V)].astype(jnp.float32)
        if low is not None:
            fmax = float(jnp.finfo(low).max)
            w = (w * (fmax / hmax)).astype(low).astype(jnp.float32) \
                * (hmax / fmax)
        out.append(np.asarray(jnp.matmul(_fake_quant(rows, low), w,
                                         precision=HIGHEST), np.float32))
    return np.concatenate(out, axis=1)


def served_gaps(model: dict, params, prompt: np.ndarray,
                served: Sequence[int], low: Optional[str] = None,
                pad_to: int = 0):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the argmax).

    With ``low`` also the control's: at the same positions of the same
    prompt and served tokens, the gap of the token a forward in that
    lower precision puts first; returns (served gaps, control gaps)."""
    served = np.asarray(served, np.int64)
    seq = np.concatenate([np.asarray(prompt, np.int64), served[:-1]])
    at = len(prompt) - 1 + np.arange(len(served))
    lg = logits(model, params, seq, at, pad_to=pad_to)
    rows = np.arange(len(served))
    gaps = lg.max(axis=1) - lg[rows, served]
    if low is None:
        return gaps
    pick = logits(model, params, seq, at, low=low,
                  pad_to=pad_to).argmax(axis=1)
    return gaps, lg.max(axis=1) - lg[rows, pick]
