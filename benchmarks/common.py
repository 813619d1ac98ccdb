"""Shared benchmark utilities."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cpu_workers(code: str, n_workers: int = 8,
                    timeout: int = 560) -> str:
    """Run ``code`` in a child python on ``n_workers`` emulated CPU
    devices and return its stdout.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): it measures
    the host emulation of ``mpirun -np N``, and it must never try to
    open an accelerator that the parent process may already hold.  A
    child that exits non-zero raises, so the benchmark run fails.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_workers}",
               PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"emulated-worker child exited "
                           f"{res.returncode}:\n{res.stderr[-4000:]}")
    return res.stdout


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-time per call in microseconds (blocking on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
