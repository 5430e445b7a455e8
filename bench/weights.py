"""Random weights for a configuration, made by the benchmark from the seed.

One jitted call builds every leaf on the device, in the type it is served
in (matrices in the configuration's dtype, norm scales in float32), in the
parameter layout the serving engine takes.  The plain reference reads the
same arrays, so neither side uses anything the program made.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def padded_vocab(vocab: int, multiple: int = 256) -> int:
    return -(-vocab // multiple) * multiple


def leaf_specs(model: dict) -> Dict[Tuple[str, ...], Tuple[tuple, str, float]]:
    """{path: (shape, dtype, std)}; a norm scale's std is its spread
    around 1."""
    L, d, f = model["n_layers"], model["d_model"], model["d_ff"]
    h, hkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    dt = model["dtype"]
    vp = padded_vocab(model["vocab"])
    specs = {
        ("embed", "table"): ((vp, d), dt, 0.02),
        ("layers", "ln1", "scale"): ((L, d), "float32", 0.1),
        ("layers", "ln2", "scale"): ((L, d), "float32", 0.1),
        ("layers", "attn", "wq"): ((L, d, h * hd), dt, d ** -0.5),
        ("layers", "attn", "wk"): ((L, d, hkv * hd), dt, d ** -0.5),
        ("layers", "attn", "wv"): ((L, d, hkv * hd), dt, d ** -0.5),
        ("layers", "attn", "wo"): ((L, h * hd, d), dt, (h * hd) ** -0.5),
        ("layers", "mlp", "wg"): ((L, d, f), dt, d ** -0.5),
        ("layers", "mlp", "wu"): ((L, d, f), dt, d ** -0.5),
        ("layers", "mlp", "wo"): ((L, f, d), dt, f ** -0.5),
        ("final_ln", "scale"): ((d,), "float32", 0.1),
    }
    if not model["tie_embeddings"]:
        specs[("lm_head", "kernel")] = ((d, vp), dt, d ** -0.5)
    return specs


def weight_bytes(model: dict) -> int:
    return sum(int(np.prod(s)) * np.dtype(_np_dtype(dt)).itemsize
               for s, dt, _ in leaf_specs(model).values())


def _np_dtype(name: str):
    import ml_dtypes
    return ml_dtypes.bfloat16 if name == "bfloat16" else np.dtype(name)


def key_from_seed(seed: int):
    """A JAX key from a seed of any width (JAX's own seeds hold 32 bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def make_params(model: dict, seed: int):
    """The parameter tree, built on the default device in one program."""
    import jax
    import jax.numpy as jnp
    specs = leaf_specs(model)

    def build(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for k, (path, (shape, dt, std)) in zip(keys, specs.items()):
            if path[-1] == "scale":   # a norm scale: 1 + noise
                leaf = 1.0 + std * jax.random.normal(k, shape, jnp.float32)
            else:
                leaf = jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf
        return tree

    return jax.jit(build)(key_from_seed(seed))
