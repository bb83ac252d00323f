"""Memo-table lookup: child-value queries against solved levels.

Reference counterpart: `pos in resolved` dict probes plus the SEND_BACK
round-trip to the owner rank (src/process.py LOOK_UP path, SURVEY.md §3.2-3.3).
Here solved levels are sorted uint32/uint64 arrays with SENTINEL tails, and a
whole frontier's child queries become one vectorized search per level of the
lookup window — no messages, no dict.

TPU notes (tools/microbench.py, v5e): `jnp.searchsorted`'s default
binary-search method ('scan') costs log2(N) dependent gathers per key —
7.0 s for 32M keys in an 8M table — while method='sort' (sort-merge join)
does the same in 1.0 s; and three separate value gathers cost ~0.35 s each,
so for uint32 games the (state, value, remoteness) record is fused into ONE
uint64 payload gather (state in the high 32 bits doubles as the hit check).
This kernel is the backward pass's dominant cost; these two choices are what
took the r02 solve off the 8x-slower-than-CPU floor.
"""

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.utils.platform import platform_auto_flag
from gamesmanmpi_tpu.core.codec import pack_cells, unpack_cells
from gamesmanmpi_tpu.core.values import UNDECIDED


def search_method() -> str:
    """searchsorted lowering, resolved at trace time for the platform that
    will execute: 'sort' (sort-merge join) on accelerators — binary search
    costs log2(N) DEPENDENT gathers/key, 7x slower at 32M keys on the v5e
    (module docstring) — but on CPU the dependent gathers are cheap and the
    merge's full re-sort is what dominates (the r03 backward ran 20 s vs
    r01's ~2 s on the same 5x4 board because of it). GAMESMAN_SEARCH=
    sort|scan overrides for A/B."""
    return platform_auto_flag(
        "GAMESMAN_SEARCH", accel="sort", cpu="scan",
        choices=("sort", "scan"),
    )


@jax.named_scope("lookup")
def lookup_sorted(keys, table_states, table_values, table_remoteness,
                  method: str | None = None):
    """Look keys up in one sorted solved level.

    keys: [K] unsigned (SENTINEL entries allowed; they miss).
    table_states: [N] sorted, same dtype as keys, SENTINEL tail.
    method: searchsorted lowering; kernel BUILDERS resolve it via
    search_method() when the builder runs (the moment the cache key is
    computed) and pass it down, so a flag flip between scheduling a
    background compile and its tracing cannot produce a program that
    disagrees with its key. None = resolve at trace time (non-cached uses).
    Returns (values [K] uint8 — UNDECIDED on miss, remoteness [K] int32,
    hit [K] bool).
    """
    if method is None:
        method = search_method()
    sentinel = sentinel_for(keys.dtype)
    n = table_states.shape[0]
    idx = jnp.searchsorted(table_states, keys, method=method)
    idx = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    cells = pack_cells(table_values, table_remoteness)
    if keys.dtype == jnp.uint32:
        # Fused record: one u64 gather instead of three (state high, cell low).
        payload = (table_states.astype(jnp.uint64) << jnp.uint64(32)) | (
            cells.astype(jnp.uint64)
        )
        p = payload[idx]
        hit = ((p >> jnp.uint64(32)).astype(keys.dtype) == keys) & (
            keys != sentinel
        )
        values, remoteness = unpack_cells(
            (p & jnp.uint64(0xFFFF_FFFF)).astype(jnp.uint32)
        )
    else:
        hit = (table_states[idx] == keys) & (keys != sentinel)
        values, remoteness = unpack_cells(cells[idx])
    values = jnp.where(hit, values, jnp.uint8(UNDECIDED))
    remoteness = jnp.where(hit, remoteness, 0)
    return values, remoteness, hit


@jax.named_scope("lookup")
def lookup_window(keys, window, method: str | None = None):
    """Look keys up across a window of solved levels.

    window: sequence of (states, values, remoteness) triples (each as in
    lookup_sorted). Each key hits at most one level (a state's level is a
    function of the state). method: see lookup_sorted. Returns
    (values, remoteness, hit) like lookup_sorted.
    """
    shape = keys.shape
    values = jnp.full(shape, UNDECIDED, dtype=jnp.uint8)
    remoteness = jnp.zeros(shape, dtype=jnp.int32)
    hit = jnp.zeros(shape, dtype=bool)
    for ts, tv, tr in window:
        v, r, h = lookup_sorted(keys, ts, tv, tr, method)
        values = jnp.where(h, v, values)
        remoteness = jnp.where(h, r, remoteness)
        hit = hit | h
    return values, remoteness, hit
