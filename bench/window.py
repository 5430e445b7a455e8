"""Window arithmetic: the end-to-end numbers of one run, from timestamps.

A rate is all the work of the window over all its time.  A tail is taken
over every sample in the window: every request whose first token fell in
it (TTFT, from the time the request was due), every gap between two
consecutive visible tokens of a request that ended in it.  Percentiles
are exact (linear interpolation between order statistics), the arithmetic
of the program's ``loadgen.percentile``, copied here.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from bench.flops import roofline_seconds


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Exact percentile by linear interpolation (None on empty input)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), p))


def window_stats(tok_t: Dict[int, List[float]], due_t: Dict[int, float],
                 t_open: float, t_close: float) -> dict:
    """tok_t: request -> visible time of each of its tokens, in order;
    due_t: request -> the time it was due (sent)."""
    span = t_close - t_open
    tokens = 0
    gaps: List[float] = []
    ttft: List[float] = []
    for rid, ts in tok_t.items():
        ts = np.asarray(ts, float)
        inside = (ts > t_open) & (ts <= t_close)
        tokens += int(inside.sum())
        if len(ts) and inside[0]:
            ttft.append(ts[0] - due_t[rid])
        if len(ts) > 1:
            g = np.diff(ts)
            gaps.extend(g[inside[1:]].tolist())
    p = lambda v, q: None if not v else 1e3 * percentile(v, q)  # noqa: E731
    return {"window_s": span, "tokens": tokens,
            "tokens_per_s": tokens / span if span > 0 else None,
            "ttft_n": len(ttft), "ttft_p50_ms": p(ttft, 50),
            "ttft_p95_ms": p(ttft, 95), "itl_n": len(gaps),
            "itl_p50_ms": p(gaps, 50), "itl_p95_ms": p(gaps, 95)}


def peak_for(kind: str, bench: Path) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown chip is an error."""
    table = json.loads((Path(bench) / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclass
class RunData:
    """What a per-layer metric reader can read about one run."""
    model: dict
    arch: Any                         # the configuration's module
    mix: dict
    engine: dict
    t_open: float
    t_close: float
    ticks: List[dict]                 # harness: t0, t1, segments, rows
    telemetry_ticks: List[dict]       # the program's tick events
    telemetry_spans: List[dict]       # the program's request spans
    telemetry_epoch: Optional[float]  # their clock's zero
    window: dict
    reached_t: Dict[int, float]       # arrival handed to the scheduler
    submit_t: Dict[int, float]        # arrival due
    compiles_in_window: int
    trace: Optional[dict]             # trace_reduce.reduce_dir output
    peak: Optional[dict]

    def window_ticks(self) -> List[dict]:
        """Harness ticks launched and finished inside the window."""
        return [t for t in self.ticks if "t1" in t
                and t["t0"] >= self.t_open and t["t1"] <= self.t_close]

    def window_telemetry(self) -> List[dict]:
        if self.telemetry_epoch is None:
            return []
        a = self.t_open - self.telemetry_epoch
        b = self.t_close - self.telemetry_epoch
        return [e for e in self.telemetry_ticks if a <= e["t"] <= b]

    def roofline_share(self, kernel: str) -> Optional[float]:
        """Percent of its roofline ``kernel`` reaches in a traced run: the
        least time of the (FLOPs, bytes) the architecture module's
        ``kernel_cost`` gives for each of the window's ticks, over the
        summed duration of the kernel's own events; None where the trace
        has none."""
        tr = self.trace
        if not tr or not tr["kernel_s"].get(kernel):
            return None
        least = 0.0
        for t in self.window_ticks():
            fl, nb = self.arch.kernel_cost(self.model, t["segments"])[kernel]
            least += roofline_seconds(fl, nb, self.peak)
        return 100.0 * least / tr["kernel_s"][kernel]
