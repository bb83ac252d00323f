"""Dedup provenance: carry origin slots through the frontier sort.

The forward pass's dedup sort already determines where every child lands
in the next level's sorted table. Keeping that knowledge (the "pair-sort
trick": sort (child, origin-slot) pairs and number the unique runs) turns
the backward pass into pure index arithmetic: a gather, a permutation and
a combine, no search and no re-expansion. This is the shape both Pentago's
parallel in-core retrograde analysis (arXiv:1404.0743) and the
consumer-grade 7x6 Connect-Four solve (arXiv:2507.05267) use to keep
retrograde passes bandwidth-bound.

Two consumers keep the provenance in different orders:

* the single-device engine (solve/engine.py expand_provenance /
  resolve_provenance) keeps it in the dedup sort's own order
  (dedup_provenance_sorted): per sorted slot, the origin slot it came from
  and whether it opens a run of equal children. The run index is then
  non-decreasing in that order and steps by 0 or 1, so the backward
  fetches the deeper level's cells with the monotone window gather
  (ops/pallas_gather.py) and returns them to parent slot order with one
  (origin, cell) pair sort (sorted_child_cells): the very sort the forward
  would otherwise run to route the run index back, moved to the backward;
* the sharded engine (parallel/sharded.py, GAMESMAN_BACKWARD=edges) needs
  the run index in routed order (dedup_provenance): the dedup runs on the
  OWNER shard after the all_to_all, so the unique-index is within the
  owner's level slice and travels back to the parent shard as a routed
  "edge" through a second all_to_all, and the backward step all_to_alls
  the stored edge indices instead of re-expanded child states.

The two share the first pair sort (_sort_runs) and nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.core.codec import pack_cells, unpack_cells
from gamesmanmpi_tpu.core.values import UNDECIDED
from gamesmanmpi_tpu.ops.dedup import compact_sorted
from gamesmanmpi_tpu.ops.pallas_gather import monotone_window_gather

#: View size of the sorted-order cell fetch: the run index steps by at most
#: 1 per slot, so a block of 1024 lanes spans at most 1024 table entries,
#: and two 8x128 int32 tiles from the tile of its first index hold them all.
SORTED_FETCH_WINDOW = 2048

_FIRST_BIT = -(1 << 31)  # int32 bit 31: the slot opens a run of equal children

#: What a level's perm is padded with to a larger common capacity: each
#: such slot stands for its own position's origin (sorted_child_cells).
PERM_PAD = (1 << 31) - 1


def _sort_runs(flat):
    """The (state, origin) pair sort both provenance dedups start from.

    -> (s sorted states, o their origin slots int32, keep: True on the
    first slot of each run of equal non-sentinel states)."""
    sentinel = sentinel_for(flat.dtype)
    origin = jax.lax.iota(jnp.int32, flat.shape[0])
    s, o = jax.lax.sort((flat, origin), num_keys=1, is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    keep = first & (s != sentinel)
    return s, o, keep


@jax.named_scope("dedup")
def dedup_provenance(flat, compact: str | None = None):
    """Sort-unique [N] states AND report where each input slot landed.

    Returns (uniq [N] sorted uniques first + sentinel tail, count int32,
    uidx [N] int32): uidx[j] is the index of flat[j] within the `uniq`
    prefix, -1 for sentinel slots. Every slot in a duplicate run shares
    the survivor's unique-index (cumsum over run-first markers is
    constant within the run). The sharded engine's owner dedup.

    compact: compaction lowering, resolved at BUILD time by kernel
    builders (None = read the platform at trace time; see
    ops.dedup.compact_method).
    """
    sentinel = sentinel_for(flat.dtype)
    s, o, keep = _sort_runs(flat)
    uid = jnp.cumsum(keep.astype(jnp.int32)) - 1
    uid = jnp.where(s != sentinel, uid, -1)
    _, uidx = jax.lax.sort((o, uid), num_keys=1, is_stable=False)
    uniq = compact_sorted(s, keep, compact)
    count = jnp.sum(keep).astype(jnp.int32)
    return uniq, count, uidx


@jax.named_scope("dedup")
def dedup_provenance_sorted(flat, compact: str | None = None):
    """Sort-unique [N] states, keeping the provenance in SORTED order.

    Returns (uniq [N] sorted uniques first + sentinel tail, count int32,
    perm [N] int32, nreal int32): perm[i] is the origin slot of the i-th
    smallest input, with bit 31 set where it opens a run of equal
    non-sentinel states; nreal is the number of non-sentinel inputs, which
    fill perm's first nreal slots. The classic engine's forward
    (sorted_child_cells is its backward half); N < 2**31.

    compact: as dedup_provenance's.
    """
    sentinel = sentinel_for(flat.dtype)
    s, o, keep = _sort_runs(flat)
    perm = o | jnp.where(keep, jnp.int32(_FIRST_BIT), jnp.int32(0))
    uniq = compact_sorted(s, keep, compact)
    count = jnp.sum(keep).astype(jnp.int32)
    nreal = jnp.sum(s != sentinel, dtype=jnp.int32)
    return uniq, count, perm, nreal


def fetch_method() -> str:
    """Lowering of sorted_child_cells' fetch, resolved at builder time for
    the executing platform, as solve/dense.py picks its gather: the Pallas
    window kernel ("pallas") on accelerators, XLA's gather of the same
    monotone indices ("plain") on the CPU."""
    return "pallas" if jax.default_backend() != "cpu" else "plain"


@jax.named_scope("gather")
def sorted_child_cells(perm, nreal, wvals, wrem, max_moves: int,
                       method: str = "plain"):
    """Packed (value, remoteness) child cells, moves major, from
    dedup_provenance_sorted's perm/nreal over C*M child slots and the
    deeper level's solved values [W] uint8 / remoteness [W] int32.

    The run index uid = cumsum(run-first) - 1 is non-decreasing over perm
    and steps by 0 or 1, so the fetch table[uid] streams (method "pallas":
    ops/pallas_gather's window kernel, every lane served from its views;
    "plain": XLA's gather); slots at and past nreal (sentinel children
    and PERM_PAD padding) get cell 0 (UNDECIDED). One (slot, cell) pair
    sort then returns the cells to parent order, a sort rather than a
    scatter, which the TPU serialises (ops/dedup.py). It sorts on the
    move-major slot m*C + b of origin b*M + m, so the cells come out
    [M, C]: a combine over [C, M] rows would pad each row of M to a
    128-lane tile on the TPU.
    Returns (cells [M, C] uint32, the fetch's missed lanes int32 scalar: 0
    unless a block's lanes outran its views, each still fetched exactly).
    """
    pos = jax.lax.iota(jnp.int32, perm.shape[0])
    first = perm < 0
    # A padding slot follows every slot of the level's own capacity, so
    # its position is the origin it stands for.
    origin = jnp.where(perm == PERM_PAD, pos, perm & jnp.int32(~_FIRST_BIT))
    # Clamped at 0 for a level with no real child at all.
    uid = jnp.maximum(
        jnp.cumsum(first.astype(jnp.int32), dtype=jnp.int32) - 1, 0)
    table = pack_cells(wvals, wrem)
    if method == "pallas":
        got, missed = monotone_window_gather(
            table, uid, window=SORTED_FETCH_WINDOW,
            interpret=jax.default_backend() == "cpu")
    else:
        got = table[uid]
        missed = jnp.int32(0)
    cells = jnp.where(pos < nreal, got, jnp.uint32(0))
    M = max_moves
    C = perm.shape[0] // M
    slot = (origin % M) * C + origin // M
    _, cells = jax.lax.sort((slot, cells), num_keys=1, is_stable=False)
    return cells.reshape(M, C), missed


def provenance_sort_bytes(itemsize: int, compaction: int) -> int:
    """Sort-operand bytes per child slot of dedup_provenance (the sharded
    engine's): the (state, i32) pair sort + the (i32, i32) inversion pair
    sort + the compaction. The classic engine's dedup_provenance_sorted
    runs only the first pair sort and the compaction (itemsize + 4 +
    compaction); its backward's (i32, u32) un-permute sort is 8 more
    (callers sum these into bytes_sorted roofline denominators; see
    docs/ARCHITECTURE.md "Efficiency accounting")."""
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
