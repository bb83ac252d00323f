"""Frontier deduplication: sort-unique over packed states.

The reference dedups implicitly through its per-rank memo dict — a position
seen twice hits `resolved`/`pending` and is not re-expanded (src/process.py,
SURVEY.md §3.2). A dict is hostile to TPUs; the level-synchronous rebuild
dedups each frontier wholesale with sort + neighbor-compare + resort, a
static-shape O(n log n) pattern XLA maps well (SURVEY.md §7 "Dedup at scale").
"""

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.utils.platform import platform_auto_flag


def compact_method() -> str:
    """Compaction lowering for the dedup's keep-mask, resolved at
    builder/cache-key time for the executing platform. 'resort' (re-sort
    with sentinels sinking to the tail) on accelerators: cumsum+scatter is
    1.7x SLOWER on the v5e (tools/microbench.py: 393 ms vs 231 ms at 32M
    uint32) because XLA serializes arbitrary-index scatters while its TPU
    sort is a fast vectorized network. On CPU the scatter is O(N) and
    beats the re-sort (~1.4x at 4M uint64). GAMESMAN_COMPACT=
    resort|scatter overrides (unknown values raise)."""
    return platform_auto_flag(
        "GAMESMAN_COMPACT", accel="resort", cpu="scatter",
        choices=("resort", "scatter"),
    )


def compaction_sort_bytes(itemsize: int) -> int:
    """Sort-operand bytes per element the compaction adds — the one place
    the traffic model knows 'resort' is a sort and 'scatter' is not
    (callers sum this into bytes_sorted roofline denominators)."""
    return itemsize if compact_method() == "resort" else 0


@jax.named_scope("dedup")
def compact_sorted(s, keep, compact: str | None = None):
    """Compact kept entries of a SORTED array to the front (sorted order
    preserved), sentinel tail. keep must be False on sentinel entries.
    compact: lowering; kernel builders resolve via compact_method() at
    builder time and pass it down (see lookup_sorted's method param for
    why). None = resolve at trace time."""
    sentinel = sentinel_for(s.dtype)
    if compact is None:
        compact = compact_method()
    if compact == "scatter":
        n = s.shape[0]
        idx = jnp.cumsum(keep.astype(jnp.int32)) - 1
        # Dropped (out-of-bounds) writes for non-kept entries; kept ones
        # land at their run index. No slot is written twice.
        return jnp.full_like(s, sentinel).at[
            jnp.where(keep, idx, n)
        ].set(s, mode="drop")
    return jnp.sort(jnp.where(keep, s, sentinel))


@jax.named_scope("dedup")
def sort_unique(states, compact: str | None = None):
    """Sort states, drop duplicates/sentinels, compact to the front.

    Input: [N] uint32/uint64 (may contain SENTINEL padding of the same dtype).
    Returns (sorted_unique [N] with all uniques first then SENTINEL tail,
             count of unique non-sentinel entries, int32).

    Sort, mark duplicate-run followers as SENTINEL, then compact (re-sort
    on accelerators, cumsum+scatter on CPU — see compact_method).
    """
    sentinel = sentinel_for(states.dtype)
    s = jnp.sort(states)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    keep = first & (s != sentinel)
    out = compact_sorted(s, keep, compact)
    count = jnp.sum(keep).astype(jnp.int32)
    return out, count
