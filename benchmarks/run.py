"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (harness contract) and writes
the same rows as JSON to ``experiments/bench/rows.json`` — kernel rows
(including the paged-attention dense-vs-live pair) land there for the
acceptance gates.

  fig4   speedup.py            — paper Fig. 4 (speed-up vs cluster size)
  fig5   best_timing.py        — paper Fig. 5 (best-case timings)
  fig6/7 platform_overhead.py  — paper Figs. 6/7 (platform phase costs)
  kernels kernels_bench.py     — kernel-layer microbenches
  serving serving.py           — decode tokens/s vs batch + pool sweep
  roofline roofline_table.py   — per (arch x shape) roofline terms
"""
from __future__ import annotations

import importlib
import json

# speedup and best_timing only spawn children; they run before any module
# that imports jax is loaded, so this process holds no device meanwhile
MODULES = ("speedup", "best_timing", "platform_overhead", "kernels_bench",
           "serving", "catopt_scale", "roofline_table")


def main() -> None:
    from benchmarks.common import ALL_ROWS as rows
    from benchmarks.common import RESULTS
    print("name,us_per_call,derived")
    for name in MODULES:
        importlib.import_module(f"benchmarks.{name}").main()
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "rows.json"
    out.write_text(json.dumps(
        [{"name": n, "us_per_call": us, "derived": d} for n, us, d in rows],
        indent=1))
    print(f"# wrote {len(rows)} rows to {out}")


if __name__ == "__main__":
    main()
