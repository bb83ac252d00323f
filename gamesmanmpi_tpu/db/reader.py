"""DbReader: mmap-backed vectorized lookup into a solved-position DB.

The read side of db/format.py: open the manifest, reconstruct the game
from its registry spec, memory-map each level's (keys, cells) pair
lazily, and answer batches of raw positions with (value, remoteness).
Queries are canonicalized through the game's symmetry before probing —
exactly the contract of SolveResult.lookup — so a sym=1 database answers
for every member of a stored class. The per-level search is the same
searchsorted-confirm shape as ops/lookup.py, in its NumPy form
(db/format.probe_sorted_np): on host, against a memory-mapped level, a
binary search touches O(log n) pages, which is what makes a multi-GB
level servable from disk without loading it.

Canonicalize + level_of run as one batched kernel on the host CPU
backend (same policy as solve/engine.canonical_scalar: a query batch
gains nothing from the accelerator, and serving processes are pinned to
the host CPU — utils/platform.pin_host_cpu), padded to power-of-two
buckets so the serving
process compiles O(log max-batch) programs, not one per batch size.
"""

from __future__ import annotations

import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.compress import BlockCorruptError
from gamesmanmpi_tpu.core.codec import unpack_cells_np
from gamesmanmpi_tpu.core.values import LOSE, TIE, UNDECIDED, WIN
from gamesmanmpi_tpu.db.format import (
    MANIFEST_NAME,
    DbFormatError,
    file_sha256,
    level_is_blocked,
    probe_sorted_np,
    read_manifest,
)
from gamesmanmpi_tpu.obs import default_registry
from gamesmanmpi_tpu.obs.qtrace import qspan
from gamesmanmpi_tpu.ops.padding import bucket_size, pad_to
from gamesmanmpi_tpu.resilience import faults
from gamesmanmpi_tpu.solve.engine import get_kernel, undecided_mask
from gamesmanmpi_tpu.store import (
    BlockStore,
    SealedBlockStream,
    TieredCache,
    default_store,
    open_npy_mmap,
)
from gamesmanmpi_tpu.utils.env import env_bool, env_int, env_opt

# Smallest query-kernel capacity: batches are tiny next to frontiers, and
# every distinct capacity is a compiled program.
_MIN_QUERY_BUCKET = 256


def _canon_builder(game):
    def f(states):
        c = game.canonicalize(states)
        return c, game.level_of(c)

    return f


def _expand_builder(game):
    # Expands the RAW queried positions and returns both the raw children
    # (the legal moves of the position the client actually holds — a
    # sym=1 best-move answer must be playable from it, not from its class
    # representative) and their canonical twins for probing; value and
    # remoteness are sym-invariant, so the canonical probe scores the raw
    # move exactly. Children of padding/terminal lanes become sentinel, so
    # a junk lane can never accidentally probe a real state. Child levels
    # come out of the same program — no second canonicalize/level pass
    # over the k*max_moves expansion set (the biggest serving kernel).
    def f(states):
        children, mask = game.expand(states)
        mask = mask & undecided_mask(game, states)[:, None]
        raw = jnp.where(mask, children, game.sentinel)
        canon = jnp.where(mask, game.canonicalize(children), game.sentinel)
        return raw, canon, mask, game.level_of(canon.reshape(-1))

    return f


class DbReader:
    """Read-only handle on a finalized solved-position database."""

    def __init__(self, directory, game=None, *, verify: bool = False,
                 registry=None, shm=None):
        self.dir = pathlib.Path(directory)
        self.manifest = read_manifest(self.dir)
        #: DB epoch — the manifest sha. THE invalidation token of every
        #: fast path layered over this reader (ISSUE 18): shared-memory
        #: block slots are stamped with it, the server's ETag embeds
        #: it, and the opening book implicitly carries it (building a
        #: book rewrites the manifest). A reload that changes the DB
        #: changes the epoch, and everything stale becomes a miss.
        self.epoch = file_sha256(self.dir / MANIFEST_NAME)
        reg = registry or default_registry()
        self._m_probe_secs = reg.histogram(
            "gamesman_db_probe_seconds",
            "wall seconds per batched level probe (searchsorted + "
            "cell gather)",
        )
        self._m_probe_queries = reg.counter(
            "gamesman_db_probe_queries_total", "positions probed"
        )
        self._m_page_touches = reg.counter(
            "gamesman_db_mmap_page_touches_total",
            "estimated mmap pages touched: ceil(log2(level keys)) per "
            "binary-search query plus one cells page per hit — the "
            "working-set denominator that says whether a level is being "
            "served from page cache or disk",
        )
        if game is None and self.manifest.get("game_spec") is not None:
            # gamedsl DB: the manifest embeds the canonical spec document,
            # so the game reconstructs even when the original .json file
            # moved or changed — the DB answers for the rules it was
            # solved under, never for whatever the path now holds.
            from gamesmanmpi_tpu.gamedsl import GameSpec, SpecError
            from gamesmanmpi_tpu.gamedsl.compiler import compile_spec

            try:
                game = compile_spec(
                    GameSpec.from_dict(self.manifest["game_spec"])
                )
            except SpecError as e:
                raise DbFormatError(
                    f"{self.dir}: embedded game_spec is not "
                    f"compilable: {e}"
                ) from e
        if game is None:
            from gamesmanmpi_tpu.games import get_game

            try:
                game = get_game(self.manifest["spec"])
            except (KeyError, ValueError) as e:
                raise DbFormatError(
                    f"{self.dir}: manifest spec "
                    f"{self.manifest['spec']!r} is not constructible: {e}"
                ) from e
        if game.name != self.manifest["game"]:
            raise DbFormatError(
                f"{self.dir} belongs to game {self.manifest['game']!r}, "
                f"not {game.name!r}"
            )
        if np.dtype(game.state_dtype).name != self.manifest["state_dtype"]:
            raise DbFormatError(
                f"{self.dir}: state dtype {self.manifest['state_dtype']} "
                f"!= game's {np.dtype(game.state_dtype).name}"
            )
        self.game = game
        self._levels = {
            int(k): rec for k, rec in self.manifest["levels"].items()
        }
        self._arrays: dict = {}
        self._blocked: dict = {}
        self._shm = shm  # cross-worker decoded-block tier (store/shm.py)
        self._store = None
        self._private_store = False
        self._m_decode_secs = None
        self._m_cache_hits = self._m_cache_misses = None
        self._hits = 0  # guarded-by: _stats_lock
        self._misses = 0  # guarded-by: _stats_lock
        self._stats_lock = None
        if any(level_is_blocked(rec) for rec in self._levels.values()):
            import threading

            # Decompress-on-probe state (format v2), ISSUE 11: decoded
            # blocks live in the SHARED block-store cache (one byte
            # budget across every reader/route in the process — the
            # private per-reader LRUs this replaces each held their own
            # copy of the hot head). GAMESMAN_DB_CACHE_MB, when set
            # explicitly, still carves a private store for this reader
            # (legacy per-reader budget; tests use it to force
            # eviction), labeled so two private caches on one registry
            # keep separable series.
            if env_opt("GAMESMAN_DB_CACHE_MB"):
                self._store = BlockStore(
                    cache=TieredCache(
                        max(1, env_int("GAMESMAN_DB_CACHE_MB", 64)) << 20,
                        registry=reg, labels={"db": self.dir.name},
                    ),
                    prefetch_threads=0, writebehind=False, registry=reg,
                    labels={"db": self.dir.name},
                )
                self._private_store = True
            else:
                self._store = default_store()
            self._stats_lock = threading.Lock()
            # Per-reader hit/miss series survive the unification: the
            # db label separates routes within one worker, the worker
            # label separates workers (docs/OBSERVABILITY.md).
            self._m_cache_hits = reg.counter(
                "gamesman_db_cache_hits_total",
                "probes answered from an already-decoded hot block",
                db=self.dir.name,
            )
            self._m_cache_misses = reg.counter(
                "gamesman_db_cache_misses_total",
                "probes that had to decode a cold block",
                db=self.dir.name,
            )
            self._m_decode_secs = reg.histogram(
                "gamesman_db_block_decode_seconds",
                "wall seconds decoding one cold (keys, cells) block pair "
                "on the probe path (cache misses only)",
                db=self.dir.name,
            )
        # The resident opening book (db/book.py) rides the reader so
        # every consumer — fleet worker, single-process server, CLI —
        # gets the short path for free when the manifest seals one.
        # Loading re-hashes the seal; a corrupt book refuses the reader
        # (never a wrong fast answer). GAMESMAN_SERVE_BOOK=0 opts out.
        self.book = None
        if self.manifest.get("book") and env_bool("GAMESMAN_SERVE_BOOK",
                                                  True):
            from gamesmanmpi_tpu.db.book import OpeningBook

            self.book = OpeningBook.load(self.dir, self.manifest)
        if verify:
            from gamesmanmpi_tpu.db.check import check_db

            problems = check_db(self.dir)
            if problems:
                raise DbFormatError(
                    f"{self.dir}: integrity check failed: {problems[0]}"
                    + (f" (+{len(problems) - 1} more)"
                       if len(problems) > 1 else "")
                )

    # ------------------------------------------------------------- plumbing

    @property
    def num_positions(self) -> int:
        return int(self.manifest.get(
            "num_positions",
            sum(rec["count"] for rec in self._levels.values()),
        ))

    @property
    def levels(self) -> list[int]:
        return sorted(self._levels)

    def _level_arrays(self, level: int):
        """(keys, cells) of one level, memory-mapped on first touch
        (store/sealed.open_npy_mmap — the v1 door)."""
        pair = self._arrays.get(level)
        if pair is None:
            rec = self._levels[level]
            keys = open_npy_mmap(self.dir / rec["keys"])
            cells = open_npy_mmap(self.dir / rec["cells"])
            pair = self._arrays[level] = (keys, cells)
        return pair

    def _blocked_level(self, level: int) -> SealedBlockStream:
        """The v2 probe handle of one level, opened on first touch.
        Lock-free under concurrent probes: a race opens two handles and
        the setdefault loser closes its fds — strictly cheaper than
        serializing every first touch behind a lock."""
        bl = self._blocked.get(level)
        if bl is None:
            try:
                fresh = SealedBlockStream(
                    self.dir, level, self._levels[level]
                )
            except (BlockCorruptError, OSError) as e:
                raise DbFormatError(
                    f"{self.dir}: level {level} block stream unreadable: "
                    f"{e}"
                ) from e
            bl = self._blocked.setdefault(level, fresh)
            if bl is not fresh:
                fresh.close()
        return bl

    def cache_stats(self):
        """Hot-block cache counters (dict), or None for a v1 DB — the
        serving batcher rides these on its serve_batch records so
        per-worker cache behavior lands in the JSONL stream. hits and
        misses are THIS reader's probes; bytes/blocks/evictions are the
        backing store cache's (shared across readers unless
        GAMESMAN_DB_CACHE_MB carved a private one)."""
        if self._store is None:
            return None
        backing = self._store.cache.stats()
        with self._stats_lock:
            hits, misses = self._hits, self._misses
        return {
            "hits": hits,
            "misses": misses,
            "evictions": backing["evictions"],
            "bytes": backing["bytes"],
            "blocks": backing["blocks"],
        }

    def close(self) -> None:
        """Drop the mmaps, close block-stream fds (everything also dies
        with the reader). Decoded blocks: a PRIVATE store's cache is
        cleared; the shared store keeps its entries — they are keyed by
        stream inode, so they can never leak into a different DB, and
        another reader of the same DB may still be serving them."""
        self._arrays.clear()
        for bl in self._blocked.values():
            bl.close()
        self._blocked.clear()
        if self._private_store and self._store is not None:
            self._store.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def _cpu_kernel(self, kind: str, cap: int, builder, arg):
        """Run a cached batched kernel on the host CPU backend."""
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            cpu = None
        if cpu is not None:
            with jax.default_device(cpu):
                fn = get_kernel(self.game, f"{kind}_cpu", cap, builder)
                return fn(jnp.asarray(arg))
        fn = get_kernel(self.game, kind, cap, builder)
        return fn(jnp.asarray(arg))

    def _canon_levels(self, q: np.ndarray):
        """Batched canonicalize + level_of: [K] -> (canon [K], levels [K])."""
        cap = bucket_size(q.shape[0], _MIN_QUERY_BUCKET)
        with qspan("canonicalize", queries=int(q.shape[0])):
            c, lv = self._cpu_kernel(
                "dbcanon", cap, _canon_builder, pad_to(q, cap)
            )
        n = q.shape[0]
        return (
            np.asarray(c)[:n].astype(self.game.state_dtype),
            np.asarray(lv)[:n],
        )

    # -------------------------------------------------------------- queries

    def lookup(self, queries) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Batched probe: raw positions -> (values, remoteness, found).

        queries: array-like of packed positions (any symmetry-class
        member). Returns (values [K] uint8 — UNDECIDED on miss,
        remoteness [K] int32 — 0 on miss, found [K] bool). One
        searchsorted per distinct level present in the batch.
        """
        q = np.ascontiguousarray(
            np.asarray(queries, dtype=self.game.state_dtype)
        )
        if q.shape[0] == 0:
            return (
                np.zeros(0, dtype=np.uint8),
                np.zeros(0, dtype=np.int32),
                np.zeros(0, dtype=bool),
            )
        return self._probe(*self._canon_levels(q))

    def _probe(self, canon: np.ndarray, levels: np.ndarray):
        """Probe ALREADY-CANONICAL states with known levels (the second
        half of lookup; split out so lookup_best canonicalizes a batch
        once and reuses it for both the probe and the expansion)."""
        k = canon.shape[0]
        faults.fire("db.probe", queries=k)
        t0 = time.perf_counter()
        values = np.full(k, UNDECIDED, dtype=np.uint8)
        remoteness = np.zeros(k, dtype=np.int32)
        found = np.zeros(k, dtype=bool)
        real = canon != self.game.sentinel
        pages = 0
        for lv in np.unique(levels[real]):
            rec = self._levels.get(int(lv))
            if rec is None:
                continue
            sel = np.nonzero(real & (levels == lv))[0]
            if level_is_blocked(rec):
                self._probe_blocked_level(
                    int(lv), canon, sel, values, remoteness, found
                )
                continue
            keys, cells = self._level_arrays(int(lv))
            with qspan("searchsorted", level=int(lv),
                       queries=int(sel.size)):
                idx, hit = probe_sorted_np(keys, canon[sel])
            hsel = sel[hit]
            if hsel.size:
                v, r = unpack_cells_np(np.asarray(cells[idx[hit]]))
                values[hsel] = v
                remoteness[hsel] = r
                found[hsel] = True
            # Page-touch model, not a kernel counter: each binary search
            # descends ~log2(n) key pages (upper levels share pages and
            # stay cached, so this is a ceiling), each hit reads one
            # cells page.
            pages += sel.size * max(
                1, math.ceil(math.log2(max(int(keys.shape[0]), 2)))
            ) + int(hsel.size)
        self._m_probe_queries.inc(k)
        self._m_page_touches.inc(pages)
        self._m_probe_secs.observe(time.perf_counter() - t0)
        return values, remoteness, found

    def _probe_blocked_level(self, lv: int, canon, sel, values,
                             remoteness, found) -> None:
        """Decompress-on-probe for one v2 level: route each query to its
        block by first_keys, decode only the touched blocks (hot-block
        LRU first), then the same searchsorted-confirm as v1 inside the
        decoded block. Corruption discovered mid-probe (torn block, crc
        mismatch) raises DbFormatError so the serving breaker counts a
        reader fault instead of a wrong answer going out."""
        bl = self._blocked_level(lv)
        if bl.num_blocks == 0 or sel.size == 0:
            return
        q = canon[sel]
        # side="right" - 1: the block whose first key is <= q. Queries
        # below the level's first key clip to block 0, where the
        # equality confirm rejects them (same sentinel-free argument as
        # probe_sorted_np).
        with qspan("searchsorted", level=int(lv), queries=int(sel.size)):
            bids = np.searchsorted(
                bl.first_keys, q.astype(np.uint64, copy=False),
                side="right",
            ) - 1
            np.clip(bids, 0, bl.num_blocks - 1, out=bids)
        for b in np.unique(bids):
            # Shared-store read: keyed by the stream's inode identity
            # (see SealedBlockStream.ident), so every reader/route of
            # one DB shares one decoded copy, and an overwrite-swapped
            # DB can never serve the old directory's blocks.
            def _decode(bl=bl, b=int(b), lv=lv):
                t0 = time.perf_counter()
                try:
                    with qspan("block_decode", level=int(lv),
                               block=int(b)):
                        # The fault fires INSIDE the span: an injected
                        # delay here is the slow-decode shape, and the
                        # resulting trace must attribute it to decode.
                        faults.fire("serve.block_decode",
                                    level=int(lv), block=int(b))
                        pair = bl.read_block(b)
                except (BlockCorruptError, OSError) as e:
                    raise DbFormatError(
                        f"{self.dir}: level {lv} block {b} "
                        f"unreadable: {e}"
                    ) from e
                self._m_decode_secs.observe(time.perf_counter() - t0)
                return pair

            loader = _decode
            if self._shm is not None:
                # The cross-worker tier sits UNDER the private store:
                # private miss -> shm probe (a sibling worker's decode,
                # one memcpy) -> real pread+decode, which is then
                # published for the rest of the fleet. Epoch-stamped:
                # a reloaded DB's slots read as misses, never as wrong
                # blocks (store/shm.py).
                def loader(bl=bl, b=int(b), decode=_decode):
                    key = (bl.ident[0], bl.ident[1], int(b))
                    pair = self._shm.get(key, self.epoch)
                    if pair is None:
                        pair = decode()
                        self._shm.put(key, self.epoch, pair[0], pair[1])
                    return pair

            pair, hit = self._store.read_ex((bl.ident, int(b)), loader)
            with self._stats_lock:
                if hit:
                    self._hits += 1
                else:
                    self._misses += 1
            if hit and self._m_cache_hits is not None:
                self._m_cache_hits.inc()
            elif not hit and self._m_cache_misses is not None:
                self._m_cache_misses.inc()
            bkeys, bcells = pair
            bsel = sel[bids == b]
            idx, hit = probe_sorted_np(
                bkeys, canon[bsel].astype(bkeys.dtype, copy=False)
            )
            hsel = bsel[hit]
            if hsel.size:
                v, r = unpack_cells_np(bcells[idx[hit]])
                values[hsel] = v
                remoteness[hsel] = r
                found[hsel] = True

    def lookup_best(self, queries):
        """lookup + the optimal child of each decided, non-terminal query.

        Returns (values, remoteness, found, best [K] state_dtype) where
        best is a packed child of the QUERIED position — a legal move the
        client can actually play, even against a sym=1 database —
        realizing the parent's value (WIN -> a LOSE child of minimum
        remoteness; LOSE -> a WIN child of maximum remoteness, delaying;
        TIE -> a TIE child of maximum remoteness), or the sentinel when
        there is no move (terminal positions, misses). Children are scored
        through their canonical twins in the same probe path.
        """
        q = np.ascontiguousarray(
            np.asarray(queries, dtype=self.game.state_dtype)
        )
        k = q.shape[0]
        sentinel = self.game.sentinel
        best = np.full(k, sentinel, dtype=self.game.state_dtype)
        if k == 0:
            return (
                np.zeros(0, dtype=np.uint8),
                np.zeros(0, dtype=np.int32),
                np.zeros(0, dtype=bool),
                best,
            )
        values, remoteness, found = self._probe(*self._canon_levels(q))
        if not found.any():
            return values, remoteness, found, best
        cap = bucket_size(k, _MIN_QUERY_BUCKET)
        # Expand the RAW queries (see _expand_builder: best must be a legal
        # move of the queried position, while the probe goes through the
        # canonical twins — value/remoteness are sym-invariant).
        raw_children, canon_children, mask, clevels = self._cpu_kernel(
            "dbexpand", cap, _expand_builder, pad_to(q, cap)
        )
        m = raw_children.shape[1]
        children = np.asarray(raw_children).reshape(-1)[: k * m].reshape(k, m)
        mask = np.asarray(mask)[:k]
        cv, cr, cfound = self._probe(
            np.asarray(canon_children)
            .reshape(-1)[: k * m]
            .astype(self.game.state_dtype),
            np.asarray(clevels)[: k * m],
        )
        cv = cv.reshape(k, m)
        cr = cr.reshape(k, m)
        cand_ok = mask & cfound.reshape(k, m)
        big = np.int64(1) << 40  # past any packable remoteness
        for want, prefer_min in ((WIN, True), (LOSE, False), (TIE, False)):
            rows = found & (values == want) & cand_ok.any(axis=1)
            if not rows.any():
                continue
            # WIN wants a LOSE child; LOSE has only WIN children; TIE wants
            # a TIE child (combine_host, solve/oracle.py).
            child_want = {WIN: LOSE, LOSE: WIN, TIE: TIE}[want]
            cand = cand_ok & (cv == child_want)
            rows &= cand.any(axis=1)
            if not rows.any():
                continue
            score = np.where(
                cand, cr.astype(np.int64), big if prefer_min else -big
            )
            pick = (
                score.argmin(axis=1) if prefer_min else score.argmax(axis=1)
            )
            best[rows] = children[np.arange(k), pick][rows]
        return values, remoteness, found, best
