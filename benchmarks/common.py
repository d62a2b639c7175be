"""Benchmark helpers: timing + subprocess runner for multi-device benches.

``run_with_devices`` is a CPU simulation of a multi-device host: the child
runs on ``n_devices`` forced host devices with ``JAX_PLATFORMS=cpu`` (the
parent may already hold an accelerator, which a child could not open).
Rows computed in it say ``platform=cpu``."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import jax

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def time_us(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 1200
                     ) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{res.stderr[-2000:]}")
    return res.stdout


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
