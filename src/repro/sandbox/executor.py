"""Prime Sandboxes simulation (paper §2.3) — code execution for RL training.

The real system is a Kubernetes/gVisor stack whose *point* is to make
sandboxed execution look, to the training loop, like a local process spawn:
warm pools make acquisition effectively instantaneous, readiness is
push-based (the sidecar webhooks the trainer the moment it boots), and
failures surface as explicit statuses that the environment turns into
completion-masking. None of the k8s machinery transfers to a JAX runtime —
what we reproduce is that *interface and failure semantics*, so the RL loop
exercises exactly the code paths the paper's loop does:

  * ``SandboxPool.acquire(image)``   — warm-pool hit = instant; cold boot =
    simulated provisioning latency, readiness signalled by completing an
    asyncio future (the push webhook analogue, §2.3.3).
  * ``sandbox.execute(code, timeout)`` — runs untrusted Python in a separate
    OS process (our isolation boundary) with a hard timeout.
  * any failure (timeout / crash / pool exhaustion) returns a non-ok status;
    the CodeEnv masks the rollout's completion, as §3.1.2 prescribes.

Density accounting mirrors §2.3.4: the pool tracks a packing factor and
oversubscription so the benchmark can reproduce the utilization argument.
"""
from __future__ import annotations

import asyncio
import dataclasses
import multiprocessing as mp
import queue
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

_EXEC_POOL: Optional[mp.pool.Pool] = None


def _get_pool() -> mp.pool.Pool:
    global _EXEC_POOL
    if _EXEC_POOL is None:
        # workers come from a fork server started clean, not from a fork of
        # this process: by the time the first sandbox runs, JAX may hold
        # the accelerator and its runtime threads, which a fork would copy
        ctx = mp.get_context("forkserver")
        _EXEC_POOL = ctx.Pool(processes=4)
    return _EXEC_POOL


def _run_user_code(code: str) -> dict:
    """Executed in the worker process: run `code`, capture stdout/err."""
    import contextlib
    import io
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            exec(code, {"__name__": "__main__"})
        return {"status": "ok", "stdout": out.getvalue(), "error": ""}
    except BaseException:
        return {"status": "error", "stdout": out.getvalue(),
                "error": traceback.format_exc(limit=3)}


@dataclass
class ExecResult:
    status: str                  # ok | error | timeout | sandbox_failure
    stdout: str = ""
    error: str = ""
    latency_s: float = 0.0

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclass
class Sandbox:
    sandbox_id: int
    image: str
    warm: bool
    created_at: float = field(default_factory=time.monotonic)
    executions: int = 0
    released: bool = False

    async def execute(self, code: str, timeout: float = 5.0) -> ExecResult:
        """Run untrusted code in a worker process with a hard timeout."""
        if self.released:
            return ExecResult("sandbox_failure", error="sandbox released")
        t0 = time.monotonic()
        loop = asyncio.get_running_loop()
        pool = _get_pool()
        async_res = pool.apply_async(_run_user_code, (code,))

        def wait():
            return async_res.get(timeout=timeout)

        try:
            res = await loop.run_in_executor(None, wait)
        except mp.TimeoutError:
            return ExecResult("timeout", latency_s=time.monotonic() - t0)
        except Exception as e:  # worker crash etc.
            return ExecResult("sandbox_failure", error=str(e),
                              latency_s=time.monotonic() - t0)
        self.executions += 1
        return ExecResult(res["status"], stdout=res["stdout"],
                          error=res["error"], latency_s=time.monotonic() - t0)


class SandboxPool:
    """Warm-pool sandbox provisioner with push-based readiness.

    ``packing_factor`` bounds concurrently-live sandboxes (the §2.3.4
    bin-packing density limit); acquisitions beyond it queue until a release,
    mirroring Burstable-QoS oversubscription rather than failing.
    """

    def __init__(self, *, warm_images: tuple = ("python:default",),
                 warm_size: int = 8, cold_boot_s: float = 0.0,
                 packing_factor: int = 256, failure_rate: float = 0.0,
                 seed: int = 0):
        self.warm_images = set(warm_images)
        self.warm_size = warm_size
        self.cold_boot_s = cold_boot_s
        self.packing_factor = packing_factor
        self.failure_rate = failure_rate
        self._next_id = 0
        self._live = 0
        self._waiters: Deque[asyncio.Future] = deque()
        self._warm: Dict[str, List[Sandbox]] = {
            img: [self._make(img, warm=True) for _ in range(warm_size)]
            for img in self.warm_images}
        import random
        self._rng = random.Random(seed)
        # metrics
        self.acquisitions = 0
        self.cold_boots = 0
        self.peak_live = 0

    def _make(self, image: str, warm: bool) -> Sandbox:
        sb = Sandbox(self._next_id, image, warm)
        self._next_id += 1
        return sb

    async def acquire(self, image: str = "python:default") -> Sandbox:
        """Warm hit: instantaneous. Cold: simulated boot, readiness pushed
        via future completion (§2.3.3's webhook, not polling)."""
        while self._live >= self.packing_factor:
            fut = asyncio.get_running_loop().create_future()
            self._waiters.append(fut)
            await fut
        self._live += 1
        self.peak_live = max(self.peak_live, self._live)
        self.acquisitions += 1
        if self._rng.random() < self.failure_rate:
            self._live -= 1
            self._wake()
            raise SandboxProvisionError(f"provisioning failed for {image}")
        pool = self._warm.get(image)
        if pool:
            return pool.pop()
        self.cold_boots += 1
        if self.cold_boot_s:
            await asyncio.sleep(self.cold_boot_s)  # image-streaming boot
        return self._make(image, warm=False)

    def release(self, sb: Sandbox) -> None:
        sb.released = True
        self._live -= 1
        if sb.warm and len(self._warm.get(sb.image, ())) < self.warm_size:
            # replenish the warm pool with a fresh instance
            self._warm.setdefault(sb.image, []).append(
                self._make(sb.image, warm=True))
        self._wake()

    def _wake(self) -> None:
        while self._waiters and self._live < self.packing_factor:
            fut = self._waiters.popleft()
            if not fut.done():
                fut.set_result(None)

    def stats(self) -> dict:
        return {"acquisitions": self.acquisitions, "cold_boots": self.cold_boots,
                "warm_hits": self.acquisitions - self.cold_boots,
                "peak_live": self.peak_live}


class SandboxProvisionError(RuntimeError):
    pass


def shutdown_executor() -> None:
    global _EXEC_POOL
    if _EXEC_POOL is not None:
        _EXEC_POOL.terminate()
        _EXEC_POOL = None
