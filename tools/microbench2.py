#!/usr/bin/env python
"""Round-3b microbenchmarks: find the next lever past 4.3M pos/s.

BENCH_r03's warm profile is sort-bound forward (XLA TPU sort ~0.85 GB/s)
and gather-bound backward. This harness measures the candidate
replacements on the real chip before any is built:

- elementwise bandwidth (the achievable roofline);
- XLA sort cost vs size (does a VMEM-resident row sort beat one big sort?);
- batched row sorts [R, C] (the "partition into buckets, sort buckets"
  plan needs per-row sorts to be much faster per element);
- u8-key pair sort (cost of a partition pass done via lax.sort);
- gather bandwidth vs table size (does a VMEM-sized table gather fast?);
- permutation-inversion: scatter vs pair-sort (dedup_provenance's sort #2);
- pure-JAX bitonic merge of two sorted halves (sorted-merge lever);
- a trivial Pallas kernel (does Pallas/Mosaic work on the chip?).

Usage: python tools/microbench2.py [--quick]
"""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import gamesmanmpi_tpu  # noqa: F401  (x64 on)
from gamesmanmpi_tpu.utils.platform import apply_platform_env, use_compile_cache

apply_platform_env()  # GAMESMAN_PLATFORM=cpu for off-chip dry runs
use_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np


def _scalarize(r):
    leaves = jax.tree_util.tree_leaves(r)
    acc = jnp.uint32(0)
    for leaf in leaves:
        acc = acc + jnp.max(leaf).astype(jnp.uint32)
    return acc


def timeit(label, fn, *args, n=3, warmup=2, bytes_moved=None):
    f = jax.jit(lambda *a: _scalarize(fn(*a)))
    try:
        for _ in range(warmup):
            np.asarray(f(*args))
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            np.asarray(f(*args))
            ts.append(time.perf_counter() - t0)
    except Exception as e:  # pragma: no cover - chip-side diagnostics
        print(f"{label:52s} FAILED: {type(e).__name__}: {e}"[:200], flush=True)
        return None
    best = min(ts)
    bw = f"  {bytes_moved/best/1e9:8.2f} GB/s" if bytes_moved else ""
    print(f"{label:52s} best {best*1e3:9.2f} ms{bw}", flush=True)
    return best


def bitonic_merge(a, b):
    """Merge two sorted [N] arrays into one sorted [2N] array.

    concat(a, reverse(b)) is bitonic; log2(2N) compare-exchange stages
    sort a bitonic sequence. Each stage is a reshape + min/max — pure
    elementwise traffic, no sort network.
    """
    x = jnp.concatenate([a, b[::-1]])
    n = x.shape[0]
    s = n // 2
    while s >= 1:
        y = x.reshape(-1, 2, s)
        lo = jnp.minimum(y[:, 0, :], y[:, 1, :])
        hi = jnp.maximum(y[:, 0, :], y[:, 1, :])
        x = jnp.stack([lo, hi], axis=1).reshape(n)
        s //= 2
    return x


def main():
    quick = "--quick" in sys.argv
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev})", file=sys.stderr)

    from gamesmanmpi_tpu.utils.env import env_int

    N = env_int("GAMESMAN_MB_N", 32 * 1024 * 1024)
    rng = np.random.default_rng(0)
    keys_np = rng.integers(0, 1 << 30, size=N, dtype=np.uint32)
    keys = jnp.asarray(keys_np)

    # 0. sync floor + elementwise bandwidth (the achievable roofline)
    tiny = jnp.arange(256, dtype=jnp.uint32)
    timeit("sync floor", lambda x: x + 1, tiny, n=10)
    timeit(f"elementwise x+1 u32 [{N>>20}M]", lambda x: x + 1, keys,
           bytes_moved=2 * 4 * N)
    timeit(f"elementwise 5-op u32 [{N>>20}M]",
           lambda x: ((x * 3) ^ (x >> 7)) + (x << 2), keys,
           bytes_moved=2 * 4 * N)

    # 1. XLA sort scaling with size (is small-sort per-element cheaper?)
    for m in (1, 4, 32):
        sz = min(m * 1024 * 1024, N)
        timeit(f"sort u32 [{sz>>20}M]", jnp.sort, keys[:sz],
               bytes_moved=2 * 4 * sz)

    # 2. batched row sorts, constant total 32M
    for rows, cols in ((32, N // 32), (256, N // 256), (2048, N // 2048)):
        x = keys.reshape(rows, cols)
        timeit(f"row sort [{rows} x {cols>>10}K]",
               lambda v: jnp.sort(v, axis=-1), x, bytes_moved=2 * 4 * N)

    # 3. partition pass cost: u8-key pair sort (bucket id = top 8 bits)
    def bucket_sort(k):
        bid = (k >> jnp.uint32(22)).astype(jnp.uint8)
        return jax.lax.sort((bid, k), num_keys=1, is_stable=False)[1]

    timeit(f"u8-key pair sort (partition) [{N>>20}M]", bucket_sort, keys,
           bytes_moved=2 * 5 * N)

    # 4. gather bandwidth vs table size
    for m, label in ((64 * 1024, "64K"), (1024 * 1024, "1M"),
                     (8 * 1024 * 1024, "8M")):
        table = jnp.asarray(
            rng.integers(0, 1 << 30, size=m, dtype=np.uint32))
        idx = jnp.asarray(rng.integers(0, m, size=N, dtype=np.int32))
        timeit(f"gather u32 [{N>>20}M from {label}]",
               lambda t, i: t[i], table, idx, bytes_moved=4 * N)
    # sorted (monotone) indices: does locality help XLA's gather?
    table8 = jnp.asarray(rng.integers(0, 1 << 30, size=8 * 1024 * 1024,
                                      dtype=np.uint32))
    sidx = jnp.asarray(np.sort(
        rng.integers(0, 8 * 1024 * 1024, size=N, dtype=np.int32)))
    timeit(f"gather u32 sorted idx [{N>>20}M from 8M]",
           lambda t, i: t[i], table8, sidx, bytes_moved=4 * N)

    # 5. permutation inversion: scatter vs pair sort
    perm_np = rng.permutation(N).astype(np.int32)
    perm = jnp.asarray(perm_np)
    vals = jnp.asarray(rng.integers(0, 1 << 30, size=N, dtype=np.int32))

    def inv_scatter(p, v):
        return jnp.zeros_like(v).at[p].set(v, unique_indices=True)

    def inv_sort(p, v):
        return jax.lax.sort((p, v), num_keys=1, is_stable=False)[1]

    timeit(f"perm inversion scatter [{N>>20}M]", inv_scatter, perm, vals,
           bytes_moved=3 * 4 * N)
    timeit(f"perm inversion pair sort [{N>>20}M]", inv_sort, perm, vals,
           bytes_moved=3 * 4 * N)

    # 6. bitonic merge of two sorted 16M halves vs sorting 32M
    h = N // 2
    a = jnp.asarray(np.sort(keys_np[:h]))
    b = jnp.asarray(np.sort(keys_np[h:]))
    timeit(f"bitonic merge [{h>>20}M + {h>>20}M]", bitonic_merge, a, b,
           bytes_moved=2 * 4 * N * int(np.log2(N)))
    timeit(f"jnp.sort same total [{N>>20}M]", jnp.sort, keys,
           bytes_moved=2 * 4 * N)

    # 6b. searchsorted on u64 (the hybrid boundary join's primitive: child
    # packed states searched in the sorted sparse level-B table — the
    # GAMESMAN_SEARCH decision at the join's scale, and the per-element
    # cost the CHIP_PLAN §2b cutover arithmetic needs). Queries half-hit.
    M8 = 8 * 1024 * 1024
    tbl64 = jnp.asarray(np.sort(
        rng.integers(0, 1 << 60, size=M8, dtype=np.uint64)))
    q64 = jnp.asarray(np.where(
        rng.integers(0, 2, size=N).astype(bool),
        np.asarray(tbl64)[rng.integers(0, M8, size=N)],
        rng.integers(0, 1 << 60, size=N, dtype=np.uint64),
    ))
    for method in ("scan", "sort"):
        timeit(
            f"searchsorted u64 {method} [{N>>20}M in 8M]",
            lambda t, q, m=method: jnp.searchsorted(t, q, method=m),
            tbl64, q64, bytes_moved=8 * N,
        )

    # 7. does Pallas compile/run over this backend at all?
    if not quick:
        try:
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu  # noqa: F401 - availability probe

            def k_copy(x_ref, o_ref):
                o_ref[:] = x_ref[:] * jnp.uint32(2)

            def pallas_double(x):
                return pl.pallas_call(
                    k_copy,
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                    grid=(x.shape[0] // (8 * 1024 * 128),),
                    in_specs=[pl.BlockSpec((8 * 1024 * 128,),
                                           lambda i: (i,))],
                    out_specs=pl.BlockSpec((8 * 1024 * 128,),
                                           lambda i: (i,)),
                )(x)

            timeit(f"pallas elementwise 2x [{N>>20}M]", pallas_double, keys,
                   bytes_moved=2 * 4 * N)
        except Exception as e:  # pragma: no cover
            print(f"pallas unavailable: {type(e).__name__}: {e}"[:200],
                  flush=True)

    # 8. u64 sort (the 6x5+ board dtype)
    keys64 = keys.astype(jnp.uint64)
    timeit(f"sort u64 [{N>>20}M]", jnp.sort, keys64, bytes_moved=2 * 8 * N)


if __name__ == "__main__":
    main()
