"""Background parallel kernel compilation.

On the TPU, compiling one solve program takes seconds to tens of seconds
(XLA's TPU sort network alone compiles in 12-40 s at 65536+ elements —
tests/test_tpu_compile.py compiles the forward ahead of time for v5e), and a
solve compiles ~30 shapes. The persistent compilation cache
(utils/platform.use_compile_cache) makes a repeat run warm; a cold run
still compiles every shape, so compiling them serially would dominate it.

This module turns compilation into background work: kernels are lowered
eagerly (cheap, host-side) and compiled on DAEMON worker threads, so the
solver overlaps compilation of upcoming capacities with execution of current
ones, the precise backward shapes (known the moment forward discovery ends)
compile while the deepest levels resolve — and speculative compiles still in
flight can never block interpreter exit (a stock ThreadPoolExecutor's
non-daemon workers would).

There is no reference counterpart (SURVEY.md §2.2 — the reference is pure
interpreted Python); this is infrastructure the XLA execution model makes
necessary, the moral analog of the reference relying on mpi4py being
imported once, not per message.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, Hashable

import jax

from gamesmanmpi_tpu.utils.env import env_float, env_int


def _workers() -> int:
    return max(1, env_int("GAMESMAN_COMPILE_WORKERS", 8))


def _heavy_slots() -> int:
    """Concurrent-compile limit for HEAVY programs (big-capacity kernels).

    Big-capacity compiles each take a ~GB host working set, so eight at
    once can exhaust host memory where the same programs compile fine
    serially. Heavy jobs therefore share a small semaphore; light jobs keep
    the full pool.
    """
    return max(1, env_int("GAMESMAN_HEAVY_COMPILES", 2))


class Precompiler:
    """Schedules jit-function compilations on daemon worker threads.

    Keys match the engine's kernel-cache keys, so a kernel is compiled at
    most once per process whether it was scheduled ahead of time or demanded
    synchronously. `get` returns the AOT-compiled executable when the
    schedule won the race, else None (caller falls back to calling the jit
    function, which compiles inline). Successfully consumed futures are
    evicted so executables are owned by the caller's kernel cache, not
    pinned here.
    """

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._futures: Dict[Hashable, Future] = {}
        self._lock = threading.Lock()
        self._threads: list = []
        self._closed = False
        self._heavy_sem = threading.Semaphore(_heavy_slots())

    def _ensure_threads(self) -> None:
        # Caller holds self._lock (schedule does).
        if self._threads or self._closed:
            return
        for i in range(_workers()):
            t = threading.Thread(
                target=self._worker, name=f"gm-compile-{i}", daemon=True
            )
            t.start()
            self._threads.append(t)

    @staticmethod
    def _transient(e: Exception) -> bool:
        """Errors worth one retry: a compile failing under load (HTTP 5xx
        / INTERNAL / UNAVAILABLE), not deterministic
        failures like an OOM-sized speculative shape (whose messages can
        embed arbitrary numbers — match structured markers only)."""
        msg = str(e)
        return any(
            t in msg for t in ("HTTP 5", "INTERNAL", "UNAVAILABLE")
        )

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:  # close() sentinel
                return
            fut, fn, avals, heavy = item
            if self._closed:
                # Cancelled by close(); resolve the future so a blocking
                # get() can never hang on a dead pool (this also covers a
                # heavy job requeued behind the close sentinels).
                fut.set_exception(RuntimeError("precompiler closed"))
                continue
            if heavy and not self._heavy_sem.acquire(blocking=False):
                if self._closed:
                    # Never requeue after close: the item could land behind
                    # the close sentinels with every worker already gone,
                    # leaving its future unresolved forever.
                    fut.set_exception(RuntimeError("precompiler closed"))
                    continue
                # No heavy slot free: requeue and stay available for light
                # jobs — heavy work must never park the whole pool.
                self._q.put(item)
                time.sleep(0.25)
                continue
            try:
                if not fut.set_running_or_notify_cancel():
                    continue
                try:
                    fut.set_result(fn.lower(*avals).compile())
                except Exception as e:  # noqa: BLE001 - maybe retry once
                    if not self._transient(e):
                        fut.set_exception(e)
                        continue
                    # Back off and retry once before giving up
                    # (the caller then falls back to an inline compile).
                    try:
                        time.sleep(8.0)
                        fut.set_result(fn.lower(*avals).compile())
                    except Exception as e2:  # noqa: BLE001
                        fut.set_exception(e2)
                except BaseException as e:  # noqa: BLE001 - report via future
                    # Never let the worker die with the future unresolved —
                    # a blocked get() would hang a solve forever.
                    fut.set_exception(e)
            finally:
                if heavy:
                    self._heavy_sem.release()

    def schedule(self, key: Hashable, fn, avals: tuple,
                 heavy: bool = False) -> None:
        """Schedule `fn.lower(*avals).compile()` in the background (idempotent).

        fn must be a jax.jit-wrapped callable; avals are
        jax.ShapeDtypeStruct leaves matching the call signature. heavy=True
        routes the job through the small heavy-compile semaphore (see
        _heavy_slots).
        """
        with self._lock:
            if key in self._futures or self._closed:
                return
            self._ensure_threads()
            fut = Future()
            self._futures[key] = fut
            self._q.put((fut, fn, avals, heavy))

    def get(self, key: Hashable, block: bool = True):
        """The compiled executable for `key`, or None if never scheduled.

        block=True waits for an in-flight compile (still a win: the wait is
        the residual, not the full compile, and other compiles progress
        meanwhile). A successful result is evicted — the caller caches it.
        """
        with self._lock:
            fut = self._futures.get(key)
        if fut is None:
            return None
        if not block and not fut.done():
            return None
        try:
            result = fut.result()
        except Exception:
            # A failed background compile (OOM-sized speculative cap,
            # transient compiler error) must not kill the solve — the caller's inline jit path
            # remains correct; drop the future so a retry is possible.
            result = None
        with self._lock:
            self._futures.pop(key, None)
        return result

    def scheduled(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._futures

    def purge(self, predicate) -> None:
        """Drop scheduled futures whose key matches `predicate`.

        Used by the engine's stale-epoch sweep: after a genuine backend
        clear no old-epoch key can ever be fetched again, so keeping the
        futures would pin executables and their closed-over Mesh/device
        objects forever. Not-yet-running jobs are cancelled (the worker's
        set_running_or_notify_cancel skips them — no wasted ~15 s remote
        compile); in-flight ones finish and are garbage-collected with
        their future."""
        with self._lock:
            stale = [k for k in self._futures if predicate(k)]
            for k in stale:
                self._futures.pop(k).cancel()

    def close(self) -> None:
        """Stop the worker threads; jobs not yet running are cancelled
        (their futures resolve with an exception, so blocking get()s
        return None instead of hanging). The instance stays closed:
        schedule() becomes a no-op and get() reports the cancellations.

        The process-wide singleton never needs this (daemon threads die
        with the process); standalone instances — tests construct several —
        must close, or each leaks its worker pool for the process
        lifetime (a full-suite run accumulated 30+ idle compile threads
        this way).
        """
        with self._lock:
            if self._closed:
                return
            self._closed = True
            n = len(self._threads)
        # One sentinel per STARTED thread (the env-derived _workers() can
        # have changed since the pool started).
        for _ in range(n):
            self._q.put(None)
        # Drain jobs that were already queued BEHIND the sentinels: every
        # worker may exit on a sentinel before reaching them, which would
        # leave their futures unresolved and a blocking get() hung. The
        # sentinels consumed here are re-put for the workers.
        sentinels = 0
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                sentinels += 1
                continue
            fut = item[0]
            if not fut.done():
                try:
                    fut.set_exception(RuntimeError("precompiler closed"))
                except Exception:  # pragma: no cover - raced with a worker
                    pass
        for _ in range(sentinels):
            self._q.put(None)


_GLOBAL: Precompiler | None = None


def _atexit_drain() -> None:
    """Let in-flight compiles retire before interpreter teardown.

    Daemon threads die with the process — but one killed INSIDE an XLA
    compile aborts teardown (C++ "terminate called ... FATAL: exception
    not rethrown", observed when a solve scheduled kernels moments
    before process exit). Closing cancels everything still queued; the
    bounded join then waits out only compiles already on a worker. A
    stuck compile must not hang exit forever — hence the cap
    (GAMESMAN_COMPILE_EXIT_GRACE seconds, default 120).
    """
    pre = _GLOBAL
    if pre is None:
        return
    pre.close()
    grace = env_float("GAMESMAN_COMPILE_EXIT_GRACE", 120.0)
    deadline = time.time() + grace
    for t in pre._threads:
        t.join(timeout=max(0.0, deadline - time.time()))


def global_precompiler() -> Precompiler:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = Precompiler()
        import atexit

        atexit.register(_atexit_drain)
    return _GLOBAL


def sds(shape, dtype, sharding=None) -> jax.ShapeDtypeStruct:
    """Shorthand ShapeDtypeStruct for schedule() avals.

    sharding: pass the NamedSharding the kernel will actually be called
    with for mesh-partitioned (shard_map) kernels — an AOT executable is
    strict about input shardings, so scheduling one with unsharded avals
    would compile a program the call site then rejects. The sharded
    engine's edge-backward prescheduling is the first user.
    """
    if sharding is not None:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.ShapeDtypeStruct(shape, dtype)
