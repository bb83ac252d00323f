#!/usr/bin/env python
"""Focused chip check for ops/pallas_gather.monotone_window_gather.

Answers, in under ~2 minutes of chip time, the CHIP_PLAN §1 question the
full microbench2 run spends 15 minutes around: does Mosaic accept the
kernel, is it CORRECT on silicon (vs the XLA gather), and does it beat
XLA's ~9-11 ns/element random-access gather on the dense engine's actual
access pattern (globally non-decreasing indices)?

Prints one human line per case plus a final JSON line
{"kernel_ok": bool, "best": {...}} for artifacts.

Run it alone: a chip belongs to one process at a time.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from gamesmanmpi_tpu.utils.platform import apply_platform_env

# --smoke is by definition an off-chip run: force CPU ourselves rather
# than requiring the operator to remember GAMESMAN_PLATFORM=cpu.
if "--smoke" in sys.argv:
    os.environ.setdefault("GAMESMAN_PLATFORM", "cpu")
# Honor GAMESMAN_PLATFORM before the first backend touch.
apply_platform_env()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from gamesmanmpi_tpu.ops.pallas_gather import monotone_window_gather  # noqa: E402


def timeit(fn, *args, n=3, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def main() -> int:
    # --smoke: the r4 session lost its only window slot to an untested
    # launcher (ModuleNotFoundError) — this flag runs the EXACT same
    # entrypoints off-chip (CPU interpret, tiny sizes) so the tool is
    # provably runnable before it ever costs chip time.
    smoke = "--smoke" in sys.argv
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev})"
          + (" [SMOKE: interpret, tiny]" if smoke else ""), flush=True)
    rng = np.random.default_rng(0)
    N = 64 * 1024 if smoke else 32 * 1024 * 1024
    M = 16 * 1024 if smoke else 8 * 1024 * 1024
    idx_np = np.sort(rng.integers(0, M, size=N)).astype(np.int32)
    idx = jnp.asarray(idx_np)

    results = []
    kernel_ok = True
    for dtype, hi in ((np.uint32, 1 << 30), (np.uint8, 256)):
        tb_np = rng.integers(0, hi, size=M, dtype=dtype)
        tb = jnp.asarray(tb_np)
        name = np.dtype(dtype).name

        secs_x, ref = timeit(lambda t, i: t[i], tb, idx)
        print(f"xla gather {name} [{N//1024}K from {M//1024}K]"
              f"      {secs_x*1e3:9.2f} ms", flush=True)
        ref_np = np.asarray(ref)

        for block, window in ((2048, 8192), (4096, 16384), (8192, 32768)):
            label = f"pallas monotone {name} b={block} w={window}"
            try:
                fn = jax.jit(lambda t, i: monotone_window_gather(
                    t, i, block=block, window=window, interpret=smoke))
                secs, (out, nmiss) = timeit(fn, tb, idx)
            except Exception as e:  # Mosaic rejection or runtime failure
                kernel_ok = False
                print(f"{label}  FAILED: {type(e).__name__}: {e}"[:220],
                      flush=True)
                continue
            nmiss = int(nmiss)
            good = bool((np.asarray(out) == ref_np).all()) and nmiss == 0
            print(f"{label}  {secs*1e3:9.2f} ms   miss={nmiss} "
                  f"correct={good}  speedup={secs_x/secs:5.2f}x", flush=True)
            if not good:
                kernel_ok = False
            results.append({"dtype": name, "block": block, "window": window,
                            "secs": round(secs, 4), "nmiss": nmiss,
                            "correct": good,
                            "xla_secs": round(secs_x, 4),
                            "speedup": round(secs_x / secs, 2)})

    # int64-idx leg (6x6+ flat spaces): same data, idx widened — must be
    # bit-identical and Mosaic-accepted (the kernel sees only block-local
    # int32 offsets; this proves the wrapper's claim on silicon).
    tb = jnp.asarray(rng.integers(0, 1 << 30, size=M, dtype=np.uint32))
    ref64 = np.asarray(tb[idx])
    try:
        fn64 = jax.jit(lambda t, i: monotone_window_gather(
            t, i, block=2048, window=8192, interpret=smoke))
        secs64, (out64, nm64) = timeit(fn64, tb, idx.astype(jnp.int64))
        good64 = (bool((np.asarray(out64) == ref64).all())
                  and int(nm64) == 0)
        print(f"pallas monotone uint32 i64-idx b=2048 w=8192  "
              f"{secs64*1e3:9.2f} ms   miss={int(nm64)} correct={good64}",
              flush=True)
        if not good64:
            kernel_ok = False
        results.append({"dtype": "uint32_i64idx", "block": 2048,
                        "window": 8192, "secs": round(secs64, 4),
                        "nmiss": int(nm64), "correct": good64})
    except Exception as e:
        kernel_ok = False
        print(f"pallas i64-idx leg FAILED: {type(e).__name__}: {e}"[:220],
              flush=True)

    best = max((r for r in results if r["correct"]),
               key=lambda r: r.get("speedup", 0.0), default=None)
    print(json.dumps({"kernel_ok": kernel_ok, "device": dev.platform,
                      "best": best}), flush=True)
    return 0 if kernel_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
