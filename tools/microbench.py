#!/usr/bin/env python
"""Microbenchmarks attributing TPU solve time to primitive ops.

Run on the real chip: `python tools/microbench.py`. Times the building blocks
of the solver hot path (sort, dedup-compaction variants, lookup variants,
gathers, host transfers, dispatch latency) so regressions like BENCH_r02's
TPU-slower-than-CPU result can be attributed instead of guessed at
(VERDICT.md round 2, "Next round" item 1).

Every timed function reduces its outputs to one scalar on device and the
harness fetches that scalar (a 4-byte download) to synchronize.
"""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import gamesmanmpi_tpu  # noqa: F401  (x64 on)
from gamesmanmpi_tpu.utils.platform import use_compile_cache

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


def timeit(label, fn, *args, n=5, warmup=2):
    """fn must end in a scalar (use scalar=True wrappers below)."""
    f = jax.jit(lambda *a: _scalarize(fn(*a)))
    for _ in range(warmup):
        np.asarray(f(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(*args))
        ts.append(time.perf_counter() - t0)
    best = min(ts)
    med = sorted(ts)[len(ts) // 2]
    print(f"{label:48s} best {best*1e3:9.2f} ms  med {med*1e3:9.2f} ms",
          flush=True)
    return best


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev})", file=sys.stderr)

    N = 32 * 1024 * 1024  # ~ a big 5x5 level's children (cap*M)
    M = 8 * 1024 * 1024   # ~ a big solved-level table

    rng = np.random.default_rng(0)
    keys_np = rng.integers(0, 1 << 30, size=N, dtype=np.uint32)
    table_np = np.sort(rng.integers(0, 1 << 30, size=M, dtype=np.uint32))
    keys = jnp.asarray(keys_np)
    table = jnp.asarray(table_np)
    tvals = jnp.asarray(rng.integers(0, 4, size=M, dtype=np.uint8))
    trem = jnp.asarray(rng.integers(0, 40, size=M, dtype=np.int32))

    # 0. dispatch+sync latency: the floor for any timed op here
    tiny = jnp.arange(256, dtype=jnp.uint32)
    timeit("sync floor: tiny kernel + 4B fetch", lambda x: x + 1, tiny, n=20)

    # 1. sort
    timeit(f"sort u32 [{N>>20}M]", jnp.sort, keys)
    keys64 = keys.astype(jnp.uint64)
    timeit(f"sort u64 [{N>>20}M]", jnp.sort, keys64)

    # 2. dedup variants
    from gamesmanmpi_tpu.ops.dedup import sort_unique
    timeit(f"sort_unique (current impl)   [{N>>20}M]", sort_unique, keys)

    def sort_unique_scatter(states):
        """The rejected O(N) compaction: cumsum + scatter (r2's impl)."""
        sentinel = jnp.uint32(0xFFFFFFFF)
        s = jnp.sort(states)
        first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
        keep = first & (s != sentinel)
        idx = (jnp.cumsum(keep.astype(jnp.int32)) - 1)
        out = jnp.full(s.shape, sentinel, dtype=s.dtype)
        out = out.at[jnp.where(keep, idx, s.shape[0])].set(s, mode="drop")
        count = jnp.sum(keep).astype(jnp.int32)
        return out, count

    timeit(f"sort_unique (scatter compact)[{N>>20}M]", sort_unique_scatter,
           keys)

    def sort_unique_resort(states):
        sentinel = jnp.uint32(0xFFFFFFFF)
        s = jnp.sort(states)
        first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
        keep = first & (s != sentinel)
        marked = jnp.where(keep, s, sentinel)
        out = jnp.sort(marked)
        count = jnp.sum(keep).astype(jnp.int32)
        return out, count

    timeit(f"sort_unique (mark+resort)    [{N>>20}M]", sort_unique_resort, keys)

    def scatter_only(s):
        keep = (s & 1) == 0
        idx = (jnp.cumsum(keep.astype(jnp.int32)) - 1)
        out = jnp.full(s.shape, jnp.uint32(0xFFFFFFFF), dtype=s.dtype)
        return out.at[jnp.where(keep, idx, s.shape[0])].set(s, mode="drop")

    timeit(f"scatter compaction alone     [{N>>20}M]", scatter_only, keys)

    timeit(f"cumsum int32 [{N>>20}M]",
           lambda s: jnp.cumsum((s & 1).astype(jnp.int32)), keys)
    timeit(f"cumsum int64 [{N>>20}M]", lambda s: jnp.cumsum(s & 1), keys)

    # 2b. pair sort (provenance forward) vs packed-u64 single sort — the
    # open question for the next forward optimization: lax.sort with a
    # carried operand vs packing (key<<32 | origin) into one u64.
    origin = jnp.arange(N, dtype=jnp.int32)

    def pair_sort(k, o):
        return jax.lax.sort((k, o), num_keys=1, is_stable=False)

    def packed_sort(k, o):
        packed = (k.astype(jnp.uint64) << jnp.uint64(32)) | (
            o.astype(jnp.uint64)
        )
        s = jnp.sort(packed)
        return (s >> jnp.uint64(32)).astype(jnp.uint32), (
            s & jnp.uint64(0xFFFF_FFFF)
        ).astype(jnp.int32)

    timeit(f"pair sort (u32,i32) [{N>>20}M]", pair_sort, keys, origin, n=3)
    timeit(f"packed u64 sort     [{N>>20}M]", packed_sort, keys, origin, n=3)

    # 3. lookup variants
    timeit(f"searchsorted scan  [{N>>20}M in {M>>20}M]",
           lambda k, t: jnp.searchsorted(t, k).astype(jnp.uint32), keys, table,
           n=3)
    timeit(f"searchsorted sort  [{N>>20}M in {M>>20}M]",
           lambda k, t: jnp.searchsorted(t, k, method="sort").astype(jnp.uint32),
           keys, table, n=3)

    from gamesmanmpi_tpu.ops.lookup import lookup_sorted
    timeit(f"lookup_sorted (current) [{N>>20}M in {M>>20}M]", lookup_sorted,
           keys, table, tvals, trem, n=3)

    # 4. gather
    idx = jnp.asarray(rng.integers(0, M, size=N, dtype=np.int32))
    timeit(f"gather u32 [{N>>20}M from {M>>20}M]", lambda t, i: t[i], table,
           idx, n=3)

    # 5. transfers (latency + bandwidth)
    for mb in (1, 16, 256):
        big = jnp.zeros(mb * 256 * 1024, dtype=jnp.uint32)
        np.asarray(jnp.max(big))  # ensure materialized
        t0 = time.perf_counter()
        _ = np.asarray(big)
        dt = time.perf_counter() - t0
        print(f"{f'download {mb}MB device->host':48s} {dt*1e3:12.2f} ms "
              f"({mb/dt:.1f} MB/s)", flush=True)
    for mb in (1, 16, 256):
        host = np.zeros(mb * 256 * 1024, dtype=np.uint32)
        t0 = time.perf_counter()
        x = jnp.asarray(host)
        np.asarray(jnp.max(x))
        dt = time.perf_counter() - t0
        print(f"{f'upload {mb}MB host->device':48s} {dt*1e3:12.2f} ms "
              f"({mb/dt:.1f} MB/s)", flush=True)

    # 6. solver kernels (connect4 5x5)
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.solve.engine import expand_core, resolve_level

    g = get_game("connect4:w=5,h=5")
    B = 4 * 1024 * 1024
    states = jnp.asarray(rng.integers(0, 1 << 30, size=B, dtype=np.uint32))
    timeit(f"expand_core c4 5x5 [{B>>20}M]", lambda s: expand_core(g, s),
           states, n=3)

    wstates = jnp.asarray(np.sort(
        rng.integers(0, 1 << 30, size=B, dtype=np.uint32)))
    timeit(f"resolve_level c4 5x5 [{B>>20}M vs {B>>20}M]",
           lambda s, a, b, c: resolve_level(g, s, ((a, b, c),)), states,
           wstates, tvals[:B], trem[:B], n=3)

    # primitive/decompose alone
    timeit(f"primitive c4 5x5 [{B>>20}M]", lambda s: g.primitive(s), states,
           n=3)
    timeit(f"expand (no dedup) c4 5x5 [{B>>20}M]",
           lambda s: g.expand(s), states, n=3)


if __name__ == "__main__":
    main()
