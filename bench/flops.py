"""Operations and bytes the algorithm needs: the paged-attention kernel and
the whole model step, computed from shapes.

Counts are logical: a head of 64 dims moves 64 dims, whatever lane padding
the device stores it with, so padding shows as a lower roofline share and
never as more work.  The counts take the configuration's ``model`` block
(``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``d_ff``, ``vocab``, ``dtype``).  ``segments_work``, ``itemsize`` and
``roofline_seconds`` serve every architecture module; the rest are the
dense module's (``archs/dense_gqa.py``).
"""
from __future__ import annotations

from typing import Iterable, Tuple

LANES = 128           # the TPU's lane width: the minor dim as stored

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def itemsize(dtype: str) -> int:
    return _ITEM[dtype]


def kv_page_bytes(model: dict, block_size: int = 16, *,
                  stored: bool = False) -> int:
    """Bytes of one KV page over all layers (K and V).  ``stored`` rounds
    the head dim up to the lane width, as the device holds it."""
    d = model["head_dim"]
    if stored:
        d = -(-d // LANES) * LANES
    return (2 * model["n_layers"] * model["n_kv_heads"] * block_size * d
            * itemsize(model["dtype"]))


def layer_matmul_params(model: dict) -> int:
    d, f = model["d_model"], model["d_ff"]
    q = model["n_heads"] * model["head_dim"]
    kv = model["n_kv_heads"] * model["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def attention_flops(model: dict, positions_plus_one: int) -> int:
    """QK^T and PV over causal contexts, all layers: ``positions_plus_one``
    is the sum over the tokens of (position + 1), the keys each sees."""
    return (4 * model["n_layers"] * model["n_heads"] * model["head_dim"]
            * positions_plus_one)


def segments_work(segments: Iterable[Tuple[int, int]]):
    """(tokens, sum of position + 1, sum of context walked) over request
    segments ``(start, end)``: tokens at positions start .. end - 1, a
    request walking ``end`` cached keys."""
    n = keys = walked = 0
    for a, b in segments:
        n += b - a
        keys += (b * (b + 1) - a * (a + 1)) // 2
        walked += b
    return n, keys, walked


def paged_kernel_cost(model: dict, segments) -> Tuple[int, int]:
    """(FLOPs, bytes) one tick's paged attention needs over all layers.

    Bytes: each request reads its whole context of K and V once per kv
    head, its queries once, writes its outputs and its fresh K/V rows."""
    n, keys, walked = segments_work(segments)
    L, hd = model["n_layers"], model["head_dim"]
    h, hkv = model["n_heads"], model["n_kv_heads"]
    it = itemsize(model["dtype"])
    kv_read = 2 * hkv * walked * hd * it
    qo = 2 * h * n * hd * it
    kv_write = 2 * hkv * n * hd * it
    return attention_flops(model, keys), L * (kv_read + qo + kv_write)


def step_flops(model: dict, segments, logits_rows: int) -> int:
    """Model FLOPs of one tick: 2 x matmul params per token, attention
    over each token's context, and the output head at ``logits_rows``."""
    n, keys, _ = segments_work(segments)
    return (2 * model["n_layers"] * layer_matmul_params(model) * n
            + attention_flops(model, keys)
            + 2 * model["d_model"] * model["vocab"] * logits_rows)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

