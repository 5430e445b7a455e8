"""Seeded request streams for the benchmark, driven by a mix file.

A mix (``bench/mixes/<name>.json``) is data only: the loop type (closed,
with a client count, or open, with a Poisson rate) and the prompt and
output length distributions.  This module turns a mix and a seed into the
requests one run offers.

Every seed offers the same set of sizes and gaps in another order: the
lengths are the distribution's quantiles on a fixed grid and the
inter-arrival gaps (open loop) the exponential's, each permuted by the
seed, so two seeds differ in which request comes when and in token
content, never in how much work the window holds.  (The program's load
generator draws Poisson gaps afresh per seed, which changes the work
with the seed; it is not used.)
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "mixes"
GRID = 64            # quantile points per length distribution


@dataclass
class Request:
    prompt: np.ndarray          # (P,) int32 token ids
    max_new_tokens: int
    t: float = 0.0              # open loop: offset of its due time


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    path = Path(mix_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use of the seed (any non-negative
    integer, however wide)."""
    return np.random.default_rng([int(seed), *stream.encode()])


# ---------------------------------------------------------------------------
# sizes: quantiles on a fixed grid, permuted by the seed
# ---------------------------------------------------------------------------
def _ndtri(p: np.ndarray) -> np.ndarray:
    """Standard normal quantile (Acklam's rational approximation,
    relative error under 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, float)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    return out


def length_grid(spec: dict, n: int = GRID) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 0.5) / n.

    spec: ``{"median", "sigma", "min", "max"}`` of a lognormal, or
    ``{"min", "max"}`` alone for a uniform one (a fixed length where the
    two are equal)."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if "median" in spec:
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * _ndtri(p))
    else:
        x = lo + p * (hi - lo)
    return np.ceil(np.clip(x, lo, hi)).astype(np.int64)


def exp_gaps(rate: float, n: int = GRID) -> np.ndarray:
    """``n`` inter-arrival gaps at the exponential's quantiles."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / float(rate)


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The requests the mix offers under ``seed``, without end.

    Sizes (and, open loop, gaps) come in blocks of ``GRID``: each block is
    one seeded permutation of the fixed quantile grid, so any ``GRID``
    consecutive requests hold exactly the grid's sizes.  Open loop, ``t``
    is each request's due time from the stream's start."""
    prompts = length_grid(mix["prompt"])
    outputs = length_grid(mix["output"])
    order = rng_for(seed, "order")
    tokens = rng_for(seed, "tokens")
    gaps = exp_gaps(mix["rate_per_s"]) if mix["loop"] == "open" else None
    t = 0.0
    while True:
        ip = order.permutation(GRID)
        io = order.permutation(GRID)
        ig = order.permutation(GRID)
        for k in range(GRID):
            if gaps is not None:
                t += float(gaps[ig[k]])
            prompt = tokens.integers(0, vocab, int(prompts[ip[k]]),
                                     dtype=np.int32)
            yield Request(prompt, int(outputs[io[k]]), t)


def max_request_tokens(mix: dict) -> int:
    """The most cache slots one request of the mix can need."""
    return int(length_grid(mix["prompt"]).max()
               + length_grid(mix["output"]).max())
