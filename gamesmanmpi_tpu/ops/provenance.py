"""Dedup provenance: carry origin slots through the frontier sort.

The forward pass's dedup sort already determines where every child lands
in the next level's sorted table. Keeping that knowledge costs one extra
pair sort in forward (the "pair-sort trick": sort (child, origin-slot)
pairs, number the unique runs, route the run index back through a second
pair sort on the origin) and turns the backward pass into pure index
arithmetic — gathers + combine, no search and no re-expansion. This is
the shape both Pentago's parallel in-core retrograde analysis
(arXiv:1404.0743) and the consumer-grade 7x6 Connect-Four solve
(arXiv:2507.05267) use to keep retrograde passes bandwidth-bound.

Two consumers share these kernels (the reason they live in ops/, not in
an engine):

* the single-device engine (solve/engine.py expand_provenance /
  resolve_provenance): uidx indexes the next level's sorted prefix
  directly, the backward resolve is one gather per child;
* the sharded engine (parallel/sharded.py, GAMESMAN_BACKWARD=edges):
  the dedup runs on the OWNER shard after the all_to_all, so the
  unique-index is within the owner's level slice and travels back to the
  parent shard as a routed "edge" — the backward step all_to_alls the
  stored edge indices instead of re-expanded child states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.core.codec import pack_cells, unpack_cells
from gamesmanmpi_tpu.core.values import UNDECIDED
from gamesmanmpi_tpu.ops.dedup import compact_sorted


@jax.named_scope("dedup")
def dedup_provenance(flat, compact: str | None = None):
    """Sort-unique [N] states AND report where each input slot landed.

    Returns (uniq [N] sorted uniques first + sentinel tail, count int32,
    uidx [N] int32): uidx[j] is the index of flat[j] within the `uniq`
    prefix, -1 for sentinel slots. Every slot in a duplicate run shares
    the survivor's unique-index (cumsum over run-first markers is
    constant within the run).

    compact: compaction lowering, resolved at BUILD time by kernel
    builders (None = read the platform at trace time; see
    ops.dedup.compact_method).
    """
    sentinel = sentinel_for(flat.dtype)
    origin = jax.lax.iota(jnp.int32, flat.shape[0])
    s, o = jax.lax.sort((flat, origin), num_keys=1, is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    keep = first & (s != sentinel)
    uid = jnp.cumsum(keep.astype(jnp.int32)) - 1
    uid = jnp.where(s != sentinel, uid, -1)
    _, uidx = jax.lax.sort((o, uid), num_keys=1, is_stable=False)
    uniq = compact_sorted(s, keep, compact)
    count = jnp.sum(keep).astype(jnp.int32)
    return uniq, count, uidx


@jax.named_scope("gather")
def gather_cells(uidx, wvals, wrem):
    """Packed (value, remoteness) cells for stored unique-indices.

    uidx: [...] int32 indices into the deeper level's prefix (-1 = no
    child — yields the UNDECIDED cell 0). wvals/wrem: the deeper level's
    solved values [W] uint8 / remoteness [W] int32. Returns uint32 cells,
    same shape as uidx.
    """
    cells = pack_cells(wvals, wrem)
    got = cells[jnp.clip(uidx, 0, cells.shape[0] - 1)]
    return jnp.where(uidx >= 0, got, jnp.uint32(0))


def provenance_sort_bytes(itemsize: int, compaction: int) -> int:
    """Sort-operand bytes per child slot of dedup_provenance: the
    (state, i32) pair sort + the (i32, i32) inversion pair sort + the
    compaction (callers sum this into bytes_sorted roofline
    denominators; see docs/ARCHITECTURE.md "Efficiency accounting")."""
    return itemsize + 12 + compaction


def combine_edge_cells(cells_flat, max_moves: int):
    """Unpack per-edge reply cells into ([B, M] values, remoteness, mask).

    cells_flat: [B*M] uint32 packed cells in parent child-slot order,
    cell 0 (UNDECIDED) marking no-edge slots — a real edge always carries
    a decided value, so the UNDECIDED cell doubles as the invalid-slot
    flag exactly like the lookup path's miss flag.
    """
    cv, cr = unpack_cells(cells_flat.reshape(-1, max_moves))
    mask = cv != UNDECIDED
    return cv, cr, mask
