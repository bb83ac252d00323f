"""Hybrid dense/BFS solver for large Connect-4 boards.

The dense engine (solve/dense.py) pays for ENCODABLE positions — a
closed-form superset of the reachable set whose blowup concentrates in the
near-full levels (2.5x at 5x5 but 10-16x at 6x6/7x6, docs/ARCHITECTURE.md
"Hybrid candidate"). The BFS engine (solve/engine.py) pays for REACHABLE
positions but buys them with sort-heavy discovery and lookup joins. This
module composes them at a cutover level K:

* levels 0..K   — dense: no discovery, no sorts, 1 byte/position over the
  encodable set (its blowup is small at low levels);
* levels K+1..N — classic level-BFS over reachable positions only, exactly
  where the encodable superset explodes.

The seam needs only existing machinery plus two small kernels:

1. the dense reachability sweep (build_reach_step) runs UP to B = K+1 and
   keeps level B's reach mask;
2. `build_extract_step` turns level B's reachable (row, rank) cells into
   the game's packed guard-encoded states (packed = current | guards) —
   one sorted frontier, handed to the BFS forward;
3. the BFS engine solves levels B..N from that frontier (its forward
   starts at an arbitrary frontier since engine._forward_fast accepts
   one) and materializes level B's sparse (states, values, remoteness);
4. `build_boundary_step` resolves dense level K: children are constructed
   as packed states (child = opponent | (guards + newbit), the same
   branch-free drop as games/connect4.expand) and looked up in level B's
   sorted table by binary search / sort-join (ops.lookup lowering rules);
5. levels K-1..0 are standard dense steps chaining dense cell arrays.

Correctness across the seam: children of reachable positions are
reachable, so a reachable level-K parent can never miss the level-B
table; unreachable (garbage) parents may miss and absorb UNDECIDED
cells, but garbage is read only by garbage ancestors — the same
quarantine argument the pure dense engine makes for its encodable
superset (dense.py module docstring).

The cutover decision is a measured quantity (chip-rate dense vs BFS —
docs/CHIP_PLAN.md); the default is the 2/3 point recorded in the
ARCHITECTURE table, override with GAMESMAN_HYBRID_CUTOVER or the
`cutover=` argument.

Reference parity: this solves the same contract as the reference's
solver (value + remoteness of the root and, as a by-product, of every
reachable position — SURVEY.md §1); the engine split is pure
implementation strategy, pinned bit-identical to both component engines
in tests/test_hybrid.py.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from gamesmanmpi_tpu.core.bitops import sentinel_for
from gamesmanmpi_tpu.core.values import LOSE, UNDECIDED
from gamesmanmpi_tpu.games.connect4 import Connect4
from gamesmanmpi_tpu.ops.combine import combine_children
from gamesmanmpi_tpu.ops.dedup import sort_unique
from gamesmanmpi_tpu.ops.lookup import search_method
from gamesmanmpi_tpu.solve.dense import (
    DenseSolver,
    _connected_fold,
    _unrank_bits,
    n1_of_level,
)
from gamesmanmpi_tpu.solve.engine import Solver, get_kernel
from gamesmanmpi_tpu.utils.env import env_int_strict as _env_int_strict
from gamesmanmpi_tpu.utils.env import env_opt
from gamesmanmpi_tpu.utils.platform import platform_auto_bool


def default_cutover(ncells: int) -> int:
    """The 2/3 point: at 6x6 this is K=24, where encodable(<=K) = 3.1e10
    of the 6.0e11 total (ARCHITECTURE "Hybrid candidate" table) — the
    dense region keeps ~95% of the blowup out while still covering the
    bulk of the backward work. A measured chip ratio refines this."""
    return (2 * ncells) // 3


def build_extract_step(tables, level: int, cblock: int, rank_dtype,
                       canon_fn=None):
    """Level-B frontier extraction: (row, rank) reach cells -> packed states.

    Returned fn:
      (rank0 rank_dtype scalar, reach [P, cblock] u8 block,
       binom, cellidx [ncells, P], filled [P], guards [P])
      -> packed [P, cblock] state_dtype, SENTINEL where not reachable
         (or rank past the class size).

    packed = current-player stones | guards (games/connect4.py encoding);
    at level B the player to move is p1 iff B is even.

    canon_fn (sym=1 only): the game's canonicalize, applied to the kept
    packed states so the handed-off frontier is mirror representatives —
    the BFS engines' tables are canonical, and a non-canonical frontier
    would seed them with both class members. Applied BEFORE sentinel
    fill: canonicalizing the sentinel would corrupt the padding.
    """
    ncells = tables.ncells
    dt = jnp.uint64 if tables.bits_dtype == np.uint64 else jnp.uint32
    n1 = n1_of_level(level)
    C = tables.class_size[level]
    current_is_p1 = level % 2 == 0
    bitpos = [int(b) for b in tables.bitpos]
    sentinel = sentinel_for(np.dtype(np.uint64 if dt == jnp.uint64
                                     else np.uint32))

    def step(rank0, reach, binom, cellidx, filled, guards):
        ranks = (rank0.astype(rank_dtype)
                 + jax.lax.iota(rank_dtype, cblock)[None, :])
        in_range = ranks < rank_dtype(C)
        p1 = _unrank_bits(ranks, n1, binom, cellidx, bitpos, dt, rank_dtype)
        current = p1 if current_is_p1 else filled[:, None] ^ p1
        packed = current | guards[:, None]
        if canon_fn is not None:
            packed = canon_fn(packed)
        keep = (reach != 0) & in_range
        return jnp.where(keep, packed, dt(sentinel))

    return step


def build_boundary_step(tables, level: int, cblock: int, wcap: int,
                        rank_dtype, method: str, canon_fn=None):
    """Dense resolve of cutover level K against the sparse level-B table.

    Identical to build_dense_step except the child value source: instead
    of gathering cells from the dense level-(K+1) array, each child is
    CONSTRUCTED as a packed state (child = opponent | (guards + newbit_c),
    the branch-free drop of games/connect4.expand) and searched in the
    BFS level-B table (kstates [wcap] sorted + SENTINEL tail, kcells
    [wcap] dense-format u8 cells). Misses yield UNDECIDED — impossible
    for reachable parents (their children are reachable by construction),
    garbage-quarantined otherwise (module docstring).

    canon_fn (sym=1 only): children are canonicalized before the search —
    the level-B table holds mirror representatives, and the mirror
    preserves value and remoteness, so the representative's cell IS the
    child's (the same rule canonical_children applies inside the BFS
    backward).

    Returned fn:
      (rank0, kstates [wcap], kcells [wcap] u8,
       binom, cellidx, filled, guards, newbit [P, w], valid [P, w])
      -> cells [P, cblock] u8 (value | remoteness << 2)
    """
    w, h, connect = tables.width, tables.height, tables.connect
    dt = jnp.uint64 if tables.bits_dtype == np.uint64 else jnp.uint32
    n1 = n1_of_level(level)
    p1_moves = level % 2 == 0     # player moving OUT of level K
    mover_is_p1 = level % 2 == 1  # player who made the ply INTO it
    bitpos = [int(b) for b in tables.bitpos]

    def step(rank0, kstates, kcells, binom, cellidx, filled, guards,
             newbit, valid):
        P = filled.shape[0]
        p1 = _unrank_bits(
            (rank0.astype(rank_dtype)
             + jax.lax.iota(rank_dtype, cblock)[None, :]),
            n1, binom, cellidx, bitpos, dt, rank_dtype,
        )
        p2 = filled[:, None] ^ p1
        mover = p1 if mover_is_p1 else p2
        current = p2 if mover_is_p1 else p1
        mover_line = _connected_fold(mover, h, connect, dt)
        current_line = _connected_fold(current, h, connect, dt)
        prim_mask = mover_line | current_line

        opponent = p2 if p1_moves else p1  # not moving out of K
        child_vals, child_rems, masks = [], [], []
        for c in range(w):
            child = opponent | (guards[:, None] + newbit[:, c : c + 1])
            if canon_fn is not None:
                child = canon_fn(child)
            idx = jnp.searchsorted(
                kstates, child.reshape(-1), method=method
            )
            idx = jnp.clip(idx, 0, kstates.shape[0] - 1).astype(jnp.int32)
            hit = kstates[idx] == child.reshape(-1)
            cell = jnp.where(
                hit, kcells[idx], jnp.uint8(UNDECIDED)
            ).reshape(child.shape)
            child_vals.append(cell & jnp.uint8(3))
            child_rems.append((cell >> jnp.uint8(2)).astype(jnp.int32))
            masks.append(valid[:, c : c + 1] & jnp.ones((1, cblock), bool))

        cv = jnp.stack(child_vals, axis=-1).reshape(P * cblock, w)
        cr = jnp.stack(child_rems, axis=-1).reshape(P * cblock, w)
        mk = (jnp.stack(masks, axis=-1)
              & ~prim_mask[..., None]).reshape(P * cblock, w)
        values, rem_out = combine_children(cv, cr, mk)
        values = values.reshape(P, cblock)
        rem_out = rem_out.reshape(P, cblock)
        values = jnp.where(prim_mask, jnp.uint8(LOSE), values)
        rem_out = jnp.where(prim_mask, 0, rem_out)
        return values | (jnp.clip(rem_out, 0, 63).astype(jnp.uint8)
                         << jnp.uint8(2))

    return step


def build_boundary_children_step(tables, level: int, cblock: int,
                                 rank_dtype, canon_fn=None):
    """Streamed boundary, phase 1: one rank block's packed children.

    Returned fn:
      (rank0, binom, cellidx, filled, guards, newbit)
      -> (children [P, cblock, w] state_dtype, prim_mask [P, cblock] bool)

    Same unrank/line/drop algebra as build_boundary_step, but the children
    are EMITTED so the per-window-block lookups (phase 2) never repeat the
    unrank walks — the dense engine's whole economy is amortizing them.
    canon_fn (sym=1): children are emitted as mirror representatives, so
    phase 2's searches hit the canonical level-B blocks.
    """
    w, h, connect = tables.width, tables.height, tables.connect
    dt = jnp.uint64 if tables.bits_dtype == np.uint64 else jnp.uint32
    n1 = n1_of_level(level)
    p1_moves = level % 2 == 0
    mover_is_p1 = level % 2 == 1
    bitpos = [int(b) for b in tables.bitpos]

    def step(rank0, binom, cellidx, filled, guards, newbit):
        p1 = _unrank_bits(
            (rank0.astype(rank_dtype)
             + jax.lax.iota(rank_dtype, cblock)[None, :]),
            n1, binom, cellidx, bitpos, dt, rank_dtype,
        )
        p2 = filled[:, None] ^ p1
        mover = p1 if mover_is_p1 else p2
        current = p2 if mover_is_p1 else p1
        prim_mask = (_connected_fold(mover, h, connect, dt)
                     | _connected_fold(current, h, connect, dt))
        opponent = p2 if p1_moves else p1
        children = jnp.stack(
            [opponent | (guards[:, None] + newbit[:, c : c + 1])
             for c in range(w)],
            axis=-1,
        )
        if canon_fn is not None:
            children = canon_fn(children)
        return children, prim_mask

    return step


def build_boundary_lookup_acc_step(method: str):
    """Streamed boundary, phase 2 (once per window block): search one
    SORTED block of the level-B table and accumulate hit cells.

    Blocks partition a sorted table, so each child hits in at most one
    block; a hit cell is nonzero (decided value), so accumulate is a
    select — the same invariant as the sharded streamed window
    (parallel/sharded._sharded_lookup_acc_step).

    Returned fn: (children_flat [N], acc [N] u8, kstates [wb],
    kcells [wb] u8) -> acc' [N] u8.

    Deliberately NOT ops.lookup.lookup_sorted: its fused one-gather
    payload applies only to uint32 states, and every board big enough to
    need streaming (6x5+) packs in uint64 — where lookup_sorted's
    separate (u8 value, i32 remoteness) arrays would also 5x the
    per-block host->device upload this path exists to minimize. The
    1-byte dense cell keeps the stream at (state + 1 B) per entry.
    """

    def step(children_flat, acc, kstates, kcells):
        idx = jnp.searchsorted(kstates, children_flat, method=method)
        idx = jnp.clip(idx, 0, kstates.shape[0] - 1).astype(jnp.int32)
        hit = kstates[idx] == children_flat
        return jnp.where(hit, kcells[idx], acc)

    return step


def build_boundary_combine_step(cblock: int, w: int):
    """Streamed boundary, phase 3: accumulated child cells -> level-K cells.

    Returned fn: (acc [P, cblock, w] u8, prim_mask [P, cblock] bool,
    valid [P, w] bool) -> cells [P, cblock] u8 — the exact combine tail of
    build_boundary_step.
    """

    def step(acc, prim_mask, valid):
        P = valid.shape[0]
        cv = (acc & jnp.uint8(3)).reshape(P * cblock, w)
        cr = (acc >> jnp.uint8(2)).astype(jnp.int32).reshape(P * cblock, w)
        mk = (valid[:, None, :] & ~prim_mask[..., None]).reshape(
            P * cblock, w
        )
        values, rem_out = combine_children(cv, cr, mk)
        values = values.reshape(P, cblock)
        rem_out = rem_out.reshape(P, cblock)
        values = jnp.where(prim_mask, jnp.uint8(LOSE), values)
        rem_out = jnp.where(prim_mask, 0, rem_out)
        return values | (jnp.clip(rem_out, 0, 63).astype(jnp.uint8)
                         << jnp.uint8(2))

    return step


def _concat_trim(blocks, nblk: int, cblock: int, C: int):
    """Join per-rank-block [P, cblock] results and trim the pad lanes of
    the ragged last block — the one tail both boundary lowerings share."""
    cells = blocks[0] if nblk == 1 else jnp.concatenate(blocks, axis=1)
    if nblk * cblock != C:
        cells = cells[:, :C]
    return cells


class HybridSolveResult:
    """Duck-typed SolveResult: dense cells below the cutover, sparse BFS
    tables above it."""

    def __init__(self, game, tables, cutover: int, value: int,
                 remoteness: int, cells, bfs_levels, stats: dict):
        self.game = game
        self._tables = tables
        self.cutover = cutover
        self.value = int(value)
        self.remoteness = int(remoteness)
        self.cells = cells            # {level<=K: [P, C] u8} or None
        self.levels = bfs_levels      # {level>K: LevelTable} or None
        self.stats = stats

    @property
    def num_positions(self) -> int:
        return self.stats["positions"]

    def lookup(self, state) -> tuple[int, int]:
        """(value, remoteness) of a packed position from whichever side of
        the cutover owns its level. Dense-side semantics match
        DenseSolveResult.lookup (answers for the encodable superset,
        refuses the fabricated mover-already-won class); BFS-side matches
        SolveResult.lookup (reachable positions only)."""
        state = int(state)
        level, row, rank = self._tables.locate(state)
        if level <= self.cutover:
            if self.cells is None:
                raise KeyError("solved in no-tables mode")
            if self._tables.current_player_has_line(level, row, rank):
                raise KeyError(
                    f"state {state:#x} is not a position (the player to "
                    "move already has a line); its cell is a placeholder"
                )
            cell = int(self.cells[level][row, rank])
            return cell & 3, cell >> 2
        if self.levels is None:
            raise KeyError("solved in no-tables mode")
        if self.game.sym:
            # BFS-side tables hold mirror representatives; canonicalize
            # the query so either class member answers (the dense side
            # above needs no such step — it indexes the full space).
            from gamesmanmpi_tpu.solve.engine import canonical_scalar

            state, level = canonical_scalar(self.game, state)
        table = self.levels.get(level)
        if table is not None:
            i = int(np.searchsorted(table.states, state))
            if i < table.states.shape[0] and int(table.states[i]) == state:
                return int(table.values[i]), int(table.remoteness[i])
        raise KeyError(f"state {state:#x} not reachable/solved")


class HybridSolver:
    """Compose the dense engine (levels <= cutover) with level-BFS
    (levels > cutover) — see the module docstring.

    cutover: last dense level K (0 <= K < ncells). None reads
    GAMESMAN_HYBRID_CUTOVER, else default_cutover(ncells).

    devices: 1 = fully single-device; >1 = BOTH regions use the mesh —
    the BFS region (where the reachable set and the sort work live) runs
    the owner-routed ShardedSolver, and the dense region's sweep and
    backward rank-partition their level kernels over the same mesh
    (DenseSolver devices=N; docs/ARCHITECTURE.md "Mesh-partitioned
    dense"). Only the boundary join and frontier extraction stay
    single-device: they are one level's worth of work at the cutover,
    which the HBM-pair bound already forces to be small (a cutover whose
    boundary does not fit one chip is the wrong cutover — see the
    ARCHITECTURE capacity table).
    """

    def __init__(self, game: Connect4, cutover: Optional[int] = None,
                 store_tables: bool = True, logger=None,
                 devices: int = 1):
        if not isinstance(game, Connect4):
            raise TypeError("HybridSolver requires a Connect4-family game")
        self.game = game
        # sym=1: the BFS region keeps the mirror reduction (it is where
        # the reachable-set cost lives — the v4-16 6x6 plan budgets its
        # per-chip peak level WITH sym), while the dense region indexes
        # the FULL space through a sym-free twin: dense perfect indexing
        # enumerates (row, rank) classes and cannot skip mirror
        # duplicates, and its low levels are the cheap side of the
        # cutover. The seam canonicalizes in both directions (extracted
        # frontier -> representatives; boundary-join children ->
        # representatives before the level-B search), mirroring what
        # canonical_children does inside both BFS engines.
        self.dense_game = (
            Connect4(game.width, game.height, game.connect, sym=False)
            if game.sym else game
        )
        self.store_tables = store_tables
        self.logger = logger
        self.devices = int(devices)
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        #: window blocks streamed through the boundary join (observable
        #: for the streamed-path tests; 0 = the table stayed resident).
        self.boundary_stream_blocks = 0
        # Boundary-join capacity knobs, parsed HERE so a typo fails fast
        # with a clear message instead of a raw traceback after the sweep
        # and the whole BFS phase have already run (the join reads them
        # last).
        self.resident_mb = _env_int_strict("GAMESMAN_HYBRID_RESIDENT_MB",
                                           2048)
        self.wblock = _env_int_strict("GAMESMAN_HYBRID_WBLOCK", 1 << 22)
        # The dense half (kernels, consts, tables); its reach sweep is run
        # partially by this class, so disable its own full sweep. devices
        # passes through: the dense region's level kernels rank-partition
        # over the same mesh the BFS region shards over (the capacity-plan
        # composition for 6x6 — docs/ARCHITECTURE.md "Mesh-partitioned
        # dense"); the boundary join stays single-device.
        self.dense = DenseSolver(self.dense_game,
                                 store_tables=store_tables,
                                 logger=logger, count_positions=False,
                                 devices=self.devices)
        self.tables = self.dense.tables
        nc = self.tables.ncells
        if cutover is None:
            env = env_opt("GAMESMAN_HYBRID_CUTOVER")
            if env:
                try:
                    cutover = int(env)
                except ValueError:
                    raise ValueError(
                        f"GAMESMAN_HYBRID_CUTOVER={env!r} is not an integer"
                    ) from None
            else:
                cutover = default_cutover(nc)
        if not 0 <= cutover < nc:
            raise ValueError(
                f"cutover must be in [0, {nc}) for a {nc}-cell board, "
                f"got {cutover}"
            )
        self.cutover = int(cutover)

    # ------------------------------------------------------------- phases

    def _log(self, **rec) -> None:
        if self.logger is not None:
            self.logger.log(rec)

    def _sweep_to_boundary(self):
        """Dense reachability sweep 0..B; returns (per-level counts 0..B,
        level-B reach array [P*C] on device). The loop itself — including
        the run-ahead drain that keeps big boards from enqueueing every
        level before a kernel retires — is DenseSolver._sweep_levels."""
        return self.dense._sweep_levels(self.cutover + 1)

    def _extract_frontier(self, reach_flat) -> np.ndarray:
        """Level-B reachable (row, rank) cells -> sorted packed states."""
        d, t, g = self.dense, self.tables, self.game
        B = self.cutover + 1
        P = len(t.profiles[B])
        C = t.class_size[B]
        cblock, nblk = d._cblock(B)
        consts = d._upload_consts(B, for_reach=True)
        guards = jnp.asarray(t.level_consts(B)["guards"])
        reach = reach_flat.reshape(P, C)

        def key(kind):
            return (kind, self.tables.width, self.tables.height,
                    self.tables.connect, B, cblock)

        # Keyed on the SYM game (g.cache_key embeds the _sym name): the
        # canonicalizing and plain extract programs must never share a
        # cache entry.
        canon = g.canonicalize if g.sym else None
        step = get_kernel(
            g, "hyx", key("hyx"),
            lambda _g: build_extract_step(
                t, B, cblock, d._rank_dtype, canon_fn=canon
            ),
        )
        pieces = []
        for b in range(nblk):
            lo = b * cblock
            blk = jax.lax.slice(
                reach, (0, lo), (P, min(lo + cblock, C))
            )
            if blk.shape[1] != cblock:  # ragged last block: pad with 0s
                blk = jnp.concatenate(
                    [blk, jnp.zeros((P, cblock - blk.shape[1]), jnp.uint8)],
                    axis=1,
                )
            packed = step(
                d._rank0(b, cblock), blk,
                consts["binom"], consts["cellidx"], consts["filled"],
                guards,
            )
            # Distinct (row, rank) are distinct positions, so without sym
            # this is pure compaction; with sym two cells can share a
            # representative, making the per-block unique a real dedup.
            uniq, count = sort_unique(packed.reshape(-1))
            n = int(count)
            if n:
                pieces.append(np.asarray(uniq[:n]))
        if not pieces:
            return np.empty(0, dtype=g.state_dtype)
        frontier = np.concatenate(pieces)
        if g.sym:
            # Mirror pairs can fall in different rank blocks; the host
            # merge must dedup ACROSS blocks too, not just sort.
            return np.unique(frontier)
        frontier.sort()
        return frontier

    def _dense_cell_table(self, bfs_table) -> tuple:
        """BFS LevelTable -> (sorted padded states, dense u8 cells) HOST
        arrays for the boundary join (uploaded whole in resident mode,
        block-sliced in streamed mode)."""
        from gamesmanmpi_tpu.ops.padding import pad_to_bucket

        states = pad_to_bucket(bfs_table.states)
        cells = np.zeros(states.shape[0], np.uint8)
        n = bfs_table.states.shape[0]
        cells[:n] = (
            bfs_table.values.astype(np.uint8)
            | (np.clip(bfs_table.remoteness, 0, 63).astype(np.uint8) << 2)
        )
        return states, cells

    def _resolve_boundary(self, kstates, kcells):
        """Dense level-K cells resolved against the sparse level-B table.

        Two lowerings, chosen by the table's size against
        GAMESMAN_HYBRID_RESIDENT_MB (default 2 GiB):

        * resident — the whole (states, cells) table lives in HBM and one
          fused kernel per rank block searches it (build_boundary_step);
        * streamed — the table stays on HOST and is streamed through HBM
          in GAMESMAN_HYBRID_WBLOCK-position blocks: children materialize
          once per rank block (phase 1), each sorted block is searched
          with hits accumulated by select (phase 2, at most one hit per
          child across the stream), one combine per rank block (phase 3).
          HBM then holds O(rank block + window block), decoupling the
          join from reachable(B) — the same mechanism as the sharded
          solver's streamed window. Known cost: the table re-uploads once
          per rank block.
        """
        d, t, g = self.dense, self.tables, self.game
        K = self.cutover
        P = len(t.profiles[K])
        C = t.class_size[K]
        cblock, nblk = d._cblock(K)
        consts = d._upload_consts(K, for_reach=False)
        guards = jnp.asarray(t.level_consts(K)["guards"])
        wcap = int(kstates.shape[0])
        sm = search_method()
        w = t.width

        def kkey(kind, *extra):
            return (kind, t.width, t.height, t.connect, K, cblock) + extra

        canon = g.canonicalize if g.sym else None
        table_bytes = wcap * (kstates.dtype.itemsize + 1)
        if table_bytes <= self.resident_mb << 20:
            step = get_kernel(
                g, "hyb", kkey("hyb", wcap, sm),
                lambda _g: build_boundary_step(
                    t, K, cblock, wcap, d._rank_dtype, sm, canon_fn=canon,
                ),
            )
            ks_dev, kc_dev = jnp.asarray(kstates), jnp.asarray(kcells)
            blocks = []
            for b in range(nblk):
                blocks.append(step(
                    d._rank0(b, cblock), ks_dev, kc_dev,
                    consts["binom"], consts["cellidx"], consts["filled"],
                    guards, consts["newbit"], consts["valid"],
                ))
            return _concat_trim(blocks, nblk, cblock, C)

        # Streamed path.
        wb = min(max(256, 1 << (self.wblock - 1).bit_length()), wcap)
        children_step = get_kernel(
            g, "hybc", kkey("hybc"),
            lambda _g: build_boundary_children_step(
                t, K, cblock, d._rank_dtype, canon_fn=canon
            ),
        )
        acc_step = get_kernel(
            g, "hyba", kkey("hyba", wb, sm),
            lambda _g: build_boundary_lookup_acc_step(sm),
        )
        combine_step = get_kernel(
            g, "hybk", kkey("hybk"),
            lambda _g: build_boundary_combine_step(cblock, w),
        )
        blocks = []
        for b in range(nblk):
            children, prim = children_step(
                d._rank0(b, cblock),
                consts["binom"], consts["cellidx"], consts["filled"],
                guards, consts["newbit"],
            )
            flat = children.reshape(-1)
            acc = jnp.zeros(flat.shape, jnp.uint8)
            for off in range(0, wcap, wb):
                acc = acc_step(
                    flat, acc,
                    jnp.asarray(kstates[off : off + wb]),
                    jnp.asarray(kcells[off : off + wb]),
                )
                self.boundary_stream_blocks += 1
            blocks.append(combine_step(
                acc.reshape(P, cblock, w), prim, consts["valid"]
            ))
        return _concat_trim(blocks, nblk, cblock, C)

    # -------------------------------------------------------------- solve

    def solve(self) -> HybridSolveResult:
        g, t, d = self.game, self.tables, self.dense
        K = self.cutover
        B = K + 1
        t0 = time.perf_counter()
        # Background-compile the dense region's kernels (bounded at B —
        # levels past the cutover belong to the BFS engine).
        d.schedule_compiles(reach_first=True, last_level=B)

        # Phase 1-2: dense sweep to the boundary, extract the BFS frontier.
        counts, reach_flat = self._sweep_to_boundary()
        frontier = self._extract_frontier(reach_flat)
        if g.sym:
            # The sweep counts the FULL reachable set at B; extraction
            # canonicalizes, so representatives number between half and
            # all of it (self-mirror positions keep the count above N/2).
            ok = (counts[B] == 0 and frontier.shape[0] == 0) or (
                counts[B] // 2 <= frontier.shape[0] <= counts[B]
            )
        else:
            ok = frontier.shape[0] == counts[B]
        if not ok:
            raise RuntimeError(
                f"hybrid seam: extracted {frontier.shape[0]} level-{B} "
                f"states but the sweep counted {counts[B]} "
                f"(sym={int(g.sym)}) — extraction/sweep disagree"
            )
        t_sweep = time.perf_counter() - t0
        # frontier = what is HANDED to the BFS region (representatives
        # under sym=1); reachable = the sweep's full-space count. Equal
        # without sym; both logged so the ~2x sym gap is auditable.
        self._log(phase="hybrid_sweep", boundary=B,
                  frontier=int(frontier.shape[0]), reachable=counts[B],
                  secs=round(t_sweep, 3))

        # Phase 3: BFS over levels B..N from the extracted frontier —
        # single-device or owner-routed sharded, per `devices`. The
        # engines' internals are driven directly (no root lookup), so the
        # solve()-time knob resolution happens here for the single-device
        # path; the sharded path resolves its own.
        if self.devices > 1:
            from gamesmanmpi_tpu.parallel import ShardedSolver

            bfs = ShardedSolver(g, num_shards=self.devices,
                                store_tables=self.store_tables)
            bfs.materialize_root_table = True  # the boundary join reads B
            levels = bfs._forward_fast(frontier, B)
            bfs_counts = {L: int(rec.counts.sum())
                          for L, rec in levels.items()}
            resolved = bfs._backward(
                levels, B, int(frontier[0]) if frontier.size else 0
            )
        else:
            bfs = Solver(g, store_tables=self.store_tables)
            bfs.use_provenance = platform_auto_bool(
                "GAMESMAN_PROVENANCE", accel=True, cpu=False
            )
            levels = bfs._forward_fast(frontier, B)
            bfs_counts = {L: rec.n for L, rec in levels.items()}
            resolved = bfs._backward_fast(levels, root_level=B)
        k1_table = resolved[B]
        t_bfs = time.perf_counter() - t0 - t_sweep
        self._log(phase="hybrid_bfs", levels=len(bfs_counts),
                  positions=sum(bfs_counts.values()), secs=round(t_bfs, 3))

        # Phase 4: the boundary join at K.
        kstates, kcells = self._dense_cell_table(k1_table)
        boundary_cells = self._resolve_boundary(kstates, kcells)

        # Phase 5: standard dense backward K-1..0 chained from the boundary
        # (DenseSolver._backward_level, with its run-ahead drain).
        saved = {} if self.store_tables else None
        if saved is not None:
            saved[K] = np.asarray(boundary_cells)
        # _replicate: the boundary kernel's output (and each chained
        # level's sharded cells) must be mesh-replicated before feeding
        # the next rank-partitioned level kernel (same chaining rule as
        # DenseSolver.solve; no-op at devices=1).
        child_flat = d._replicate(boundary_cells.reshape(-1))
        d._undrained = 0
        d._gather_miss = []
        gather_lanes = 0
        for L in range(K - 1, -1, -1):
            P = len(t.profiles[L])
            C = t.class_size[L]
            gather_lanes += P * C * t.width
            cells = d._backward_level(L, child_flat)
            child_flat = d._replicate(cells.reshape(-1))
            d._maybe_drain(P * C, child_flat, L)
            if saved is not None:
                saved[L] = np.asarray(cells).reshape(P, C)

        # The dense levels' missed gather lanes come with the root: no
        # per-level sync.
        root_cell, *missed = jax.device_get(
            [jnp.reshape(child_flat, (-1,))[0], *d._gather_miss])
        root_cell, gather_miss = int(root_cell), sum(int(m) for m in missed)
        value, remoteness = root_cell & 3, root_cell >> 2
        t_total = time.perf_counter() - t0

        positions = (sum(v for L, v in counts.items() if L <= K)
                     + sum(bfs_counts.values()))
        stats = {
            "game": g.name,
            "engine": "hybrid",
            "cutover": K,
            # With sym=1 the two regions count DIFFERENT things: the
            # dense region the full reachable set (it indexes the full
            # space), the BFS region mirror representatives — the
            # breakdown keys make the mixed total auditable.
            "positions": positions,
            "positions_dense_region": sum(
                v for L, v in counts.items() if L <= K),
            "positions_bfs_region": sum(bfs_counts.values()),
            "positions_per_sec": positions / max(t_total, 1e-9),
            # Discovery = sweep + extraction; everything after is resolve.
            "secs_forward": t_sweep,
            "secs_backward": t_total - t_sweep,
            "secs_total": t_total,
            "secs_bfs": t_bfs,
            "bytes_sorted": bfs.bytes_sorted,
            "bytes_gathered": bfs.bytes_gathered,
            # Canonical size actually seeded into the BFS region; the
            # full-space sweep count sits alongside (equal when sym=0).
            "frontier_at_boundary": int(frontier.shape[0]),
            "reachable_at_boundary": counts[B],
            # The dense levels below the cutover: child-cell gather lanes
            # and those the pallas gather's views missed (DenseSolver's).
            "gather_lanes": gather_lanes,
            "gather_miss_lanes": gather_miss,
        }
        self._log(phase="done", **{k: v for k, v in stats.items()
                                   if k != "game"})
        return HybridSolveResult(
            g, t, K, value, remoteness, saved,
            dict(resolved) if self.store_tables else None, stats,
        )
