"""Multi-host bootstrap: turn scheduler environment into a jax.distributed
initialization (the "create cluster" verb at real-pod scale).

Supported launchers (auto-detected from env):
  * TPU pods (GKE/QR): JAX autodetects — plain ``jax.distributed.initialize()``
  * SLURM:     SLURM_PROCID / SLURM_NTASKS / SLURM_STEP_NODELIST
  * manual:    REPRO_COORDINATOR / REPRO_NUM_PROCESSES / REPRO_PROCESS_ID

On a 1000-node deployment this is the only file that touches launcher
specifics; everything above it (Platform, meshes, steps) is host-count
agnostic because shardings are expressed in global shapes.
"""
from __future__ import annotations

import os
import pathlib
from dataclasses import dataclass
from typing import Optional

# fixed, inside the checkout: the cache directory is part of every entry's
# key, so a path that moved between runs would never hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


@dataclass(frozen=True)
class BootstrapInfo:
    launcher: str
    process_id: int
    num_processes: int
    coordinator: Optional[str]


def detect() -> BootstrapInfo:
    if "REPRO_NUM_PROCESSES" in os.environ:
        return BootstrapInfo(
            launcher="manual",
            process_id=int(os.environ.get("REPRO_PROCESS_ID", "0")),
            num_processes=int(os.environ["REPRO_NUM_PROCESSES"]),
            coordinator=os.environ.get("REPRO_COORDINATOR",
                                       "localhost:12345"))
    if "SLURM_NTASKS" in os.environ and int(os.environ["SLURM_NTASKS"]) > 1:
        nodelist = os.environ.get("SLURM_STEP_NODELIST", "localhost")
        head = nodelist.split(",")[0].replace("[", "").split("-")[0]
        return BootstrapInfo(
            launcher="slurm",
            process_id=int(os.environ["SLURM_PROCID"]),
            num_processes=int(os.environ["SLURM_NTASKS"]),
            coordinator=f"{head}:12345")
    if os.environ.get("TPU_WORKER_HOSTNAMES") or \
            os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return BootstrapInfo(launcher="tpu", process_id=-1,
                             num_processes=-1, coordinator=None)
    return BootstrapInfo(launcher="single", process_id=0, num_processes=1,
                         coordinator=None)


def enable_compile_cache() -> Optional[str]:
    """Keep compiled programs across runs; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set in code.  Otherwise, on a TPU, the
    cache goes to :data:`CACHE_DIR`; off a TPU compiles are cheap and
    nothing is cached (``None``).  Call it from an entry point before the
    first compile, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def initialize(info: Optional[BootstrapInfo] = None) -> BootstrapInfo:
    """Idempotent jax.distributed bring-up.  Single-process: no-op."""
    import jax
    info = info or detect()
    if info.launcher == "single":
        return info
    if info.launcher == "tpu":
        jax.distributed.initialize()
        return info
    jax.distributed.initialize(coordinator_address=info.coordinator,
                               num_processes=info.num_processes,
                               process_id=info.process_id)
    return info
