"""Benchmark helpers: timing + subprocess-with-N-devices runner."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time
from typing import Callable

REPO = pathlib.Path(__file__).resolve().parents[1]
RESULTS = REPO / "experiments" / "bench"


def time_call(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (block_until_ready'd by caller fn)."""
    for _ in range(warmup):
        fn(*args)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run_with_devices(code: str, devices: int, timeout: int = 900) -> dict:
    """Run a snippet in a child with N host (CPU) devices; it must print one
    JSON line starting with RESULT.  The child is pinned to the CPU: forced
    host devices exist only there, and a chip belongs to one process —
    which may be the parent."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{out.stdout}\n{out.stderr}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return json.loads(line[len("RESULT"):])
    raise RuntimeError(f"no RESULT line:\n{out.stdout}")


# every emit() call records here too, so harness runners (benchmarks/run.py)
# can dump one JSON with exactly the rows that went to CSV
ALL_ROWS: list = []


def emit(rows):
    """Print the contract CSV: name,us_per_call,derived."""
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
        ALL_ROWS.append((name, us, derived))
