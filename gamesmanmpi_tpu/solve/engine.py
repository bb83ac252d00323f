"""The level-synchronous retrograde solver (single device).

This replaces the reference's entire L1 distributed runtime — the Process
event loop, priority work queue and Job dispatch table (src/process.py,
src/job.py; SURVEY.md §2.2, §3.2-3.4) — with two bulk phases per level.
The Job types map as follows:

  reference Job (SURVEY.md §2.2)   here
  -------------------------------  -------------------------------------------
  LOOK_UP / DISTRIBUTE             forward pass: expand a whole level's
                                   frontier in one vmapped kernel; children are
                                   dedup'd (sort-unique) and become the next
                                   level's frontier instead of being mailed to
                                   owner ranks one Job at a time.
  CHECK_FOR_UPDATES                gone — no polling; the level barrier is the
                                   only synchronization.
  SEND_BACK / RESOLVE              backward pass: for each level (deepest
                                   first) regenerate children, look their
                                   values up in already-solved deeper levels
                                   (ops.lookup), and combine (ops.combine).
  FINISHED                         the backward loop reaching the root level.

Scheduling differs from the reference by design (SURVEY.md §2.4: asynchronous
small-message actors are anti-idiomatic on TPU); observable behavior — the
(value, remoteness) of every reachable position — is preserved and tested
against a pure-Python oracle.

Two execution paths share the kernels:

* **Fast path** (games with `uniform_level_jump`, i.e. every move advances the
  level by exactly 1 — tic-tac-toe, connect4): fully device-resident. The
  frontier chains on-device level to level (the next frontier is a static
  slice of the dedup output), and the backward window is exactly the
  previously-resolved level, which is already on-device. Host work per level
  is one scalar sync (the unique-count) plus the result-table download.
* **Generic path** (multi-jump games — subtraction games, Nim): children span
  multiple levels, so per-level pools are merged on host and the lookup
  window covers `max_level_jump` deeper levels.

Compiled-program economy: XLA compiles one program per shape, and on the TPU
a sort-bearing program takes seconds to tens of seconds to compile
(tests/test_tpu_compile.py), while dispatch is cheap. The persistent compile
cache (utils/platform.use_compile_cache) serves a repeat run; for a cold one
there are three defenses, in order of importance:

* kernels are compiled in PARALLEL in the background (solve/precompile.py):
  a capacity ladder is scheduled at solve start, doubled ahead of frontier
  growth during forward, and the exact backward shapes — known the moment
  forward ends — are scheduled deepest-first so compilation overlaps
  execution;
* the backward kernel is keyed on ONE common capacity (states and window
  both padded to max of the two buckets), not on (cap, window-cap) pairs —
  halving backward shape count;
* all kernels are cached at module level keyed on (game.cache_key, kind,
  shapes), so re-instantiated Solvers (benchmark repeats, CLI reruns) reuse
  executables, and frontier capacities are power-of-two buckets so the shape
  count is O(log max-frontier), not O(levels).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gamesmanmpi_tpu.core.codec import unpack_cells
from gamesmanmpi_tpu.core.values import UNDECIDED
from gamesmanmpi_tpu.games.base import TensorGame
from gamesmanmpi_tpu.ops.combine import combine_children
from gamesmanmpi_tpu.ops.dedup import (
    compact_method,
    compaction_sort_bytes,
    sort_unique,
)
from gamesmanmpi_tpu.ops.lookup import lookup_window, search_method
from gamesmanmpi_tpu.ops.provenance import (
    PERM_PAD,
    dedup_provenance_sorted,
    fetch_method,
    sorted_child_cells,
)
from gamesmanmpi_tpu.ops.padding import MIN_BUCKET, bucket_size, pad_to, pad_to_bucket
from gamesmanmpi_tpu.obs import (
    Heartbeat,
    SolveStatusTracker,
    Span,
    default_registry,
    maybe_status_server,
    trace_span,
)
from gamesmanmpi_tpu.obs import flightrec
from gamesmanmpi_tpu.resilience import faults
from gamesmanmpi_tpu.resilience import memguard, preempt
from gamesmanmpi_tpu.resilience.retry import retry_call
from gamesmanmpi_tpu.resilience.supervisor import maybe_watchdog
from gamesmanmpi_tpu.solve.precompile import global_precompiler, sds
from gamesmanmpi_tpu.utils.env import env_float, env_int, env_str
from gamesmanmpi_tpu.utils.platform import backend_epoch, platform_auto_bool


class LevelTable(NamedTuple):
    """Solved records for one level: parallel arrays sorted by state."""

    states: np.ndarray  # game.state_dtype, sorted ascending
    values: np.ndarray  # uint8
    remoteness: np.ndarray  # int32


class SolveResult:
    """Full solve output: root answer + per-level tables + stats."""

    def __init__(self, game: TensorGame, value: int, remoteness: int,
                 levels: Dict[int, LevelTable], stats: dict):
        self.game = game
        self.value = int(value)
        self.remoteness = int(remoteness)
        self.levels = levels
        self.stats = stats

    @property
    def num_positions(self) -> int:
        # stats carries the authoritative count (valid in store_tables=False
        # mode, where `levels` holds only the root level).
        if "positions" in self.stats:
            return self.stats["positions"]
        return sum(t.states.shape[0] for t in self.levels.values())

    def lookup(self, state) -> tuple[int, int]:
        """(value, remoteness) of any reachable packed state.

        Queries are canonicalized, so symmetry-reduced tables answer for
        every member of a stored class. The probe itself is the shared
        canonicalize→probe search (core/probe.py) — one code path with
        the solved-position DB and checkpoint point queries.
        """
        from gamesmanmpi_tpu.core.probe import probe_sorted_np

        state, level = canonical_scalar(self.game, state)
        table = self.levels.get(level)
        if table is not None:
            idx, hit = probe_sorted_np(
                table.states,
                np.asarray([state], dtype=table.states.dtype),
            )
            if hit[0]:
                i = idx[0]
                return int(table.values[i]), int(table.remoteness[i])
        raise KeyError(f"state {int(state):#x} not reachable/solved")


class SolverError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Module-level kernel cache: (game.cache_key, kind, *shape info) -> jitted fn.
# Lives for the process so repeated Solver instances (bench repeats, parity
# tests, CLI reruns) never recompile. Bounded in practice: a handful of kinds
# x O(log max-frontier) capacities per game. Builders receive the game and
# must close over nothing else (a cached kernel outlives the Solver that
# first built it).
_KERNELS: dict = {}


# ---------------------------------------------------------------------------
# Dispatch accounting: how many device computations a solve issues, per
# level, as a bench record can cite it. Every device computation the
# engines issue — cached-kernel calls (counted in get_kernel's wrapper),
# plus the eager slice/pad/upload/download ops the hot loops perform
# between kernels — calls note_dispatch. The active solver registers a
# sink (set_dispatch_sink) that tallies a per-(phase, level) breakdown and
# the gamesman_dispatches_total{phase} registry counter; with no solver
# active the note is a no-op (canonical_scalar point queries etc.).
_DISPATCH_SINK = None


def set_dispatch_sink(sink):
    """Install a dispatch sink; returns the previous one (nest-safe — the
    hybrid engine runs a Solver inside its own solve)."""
    global _DISPATCH_SINK
    prev = _DISPATCH_SINK
    _DISPATCH_SINK = sink
    return prev


def note_dispatch(kind: str) -> None:
    sink = _DISPATCH_SINK
    if sink is not None:
        sink(kind)


def _counted(kind: str, fn):
    """Wrap a cached kernel so every invocation is tallied by the active
    solver's sink. Host-side bookkeeping at kernel-call rate (a few per
    level), never per-position."""

    def call(*args, **kwargs):
        note_dispatch(kind)
        return fn(*args, **kwargs)

    return call


def roofline_stats(hbm_bytes: int, positions: int, wall_secs: float,
                   chips: int = 1) -> dict:
    """The ISSUE 15 roofline rollup both engines put in their stats and
    bench.py folds into the record: analytic HBM operand throughput and
    per-chip solve rate."""
    wall = max(float(wall_secs), 1e-9)
    return {
        "operand_gbps": round(hbm_bytes / wall / 1e9, 3),
        "pps_per_chip": round(positions / wall / max(int(chips), 1), 1),
    }


def tally_dispatch(solver, kind: str) -> None:
    """The one dispatch-sink body both engines share (their _on_dispatch
    methods delegate here, so the gamesman_dispatches_total series and the
    per-(phase, level) keying can never fork between them). `solver` needs
    progress / game / dispatch_total / level_dispatches / dispatch_by_kind
    — the attributes Solver and ShardedSolver both carry."""
    solver.dispatch_total += 1
    ph = solver.progress.get("phase", "init")
    lvl = solver.progress.get("level", -1)
    key = (ph, lvl)
    solver.level_dispatches[key] = solver.level_dispatches.get(key, 0) + 1
    solver.dispatch_by_kind[kind] = \
        solver.dispatch_by_kind.get(kind, 0) + 1
    default_registry().counter(
        "gamesman_dispatches_total",
        "device computations/transfers dispatched by the engines",
        phase=ph, game=solver.game.name,
    ).inc()


def _cache_key(game: TensorGame, kind: str, shape_key, lowering):
    """Cache key for a kernel. Builders whose programs embed a
    flag/platform-resolved lowering — the searchsorted method
    (GAMESMAN_SEARCH), the dedup compaction (GAMESMAN_COMPACT) — pass the
    RESOLVED choices as the `lowering` tuple at their
    get_kernel/schedule_kernel call site. The
    builder itself captures the same values when it runs (immediately, at
    key time — schedule_kernel calls builder(game) before handing the
    traceable to the pool), so a mid-process flag flip can neither reuse a
    kernel traced under the other lowering nor produce a program that
    disagrees with its key. Each kind carries only the knobs its program
    actually contains — keying every kind on every knob would recompile
    byte-identical kernels on a flag flip (the doubled compile load
    stress-crashed XLA's CPU compiler once in a full-suite run).

    Every key also carries the backend EPOCH (utils/platform.py): when
    force_platform genuinely clears backends, executables closed over the
    old device objects (sharded kernels close over a Mesh) must not be
    reused — they fail with "incompatible devices for jitted computation"."""
    if lowering:
        return (game.cache_key, kind, shape_key, tuple(lowering),
                backend_epoch())
    return (game.cache_key, kind, shape_key, backend_epoch())


# Epoch whose kernels _KERNELS currently holds. Keys carry the epoch, so
# stale entries are unreachable after a genuine backend clear — but without
# a sweep they would leak (executables + closed-over Mesh/device objects)
# once per clear in long-lived processes. Per-game private caches are not
# swept: they die with their game instance.
_KERNELS_EPOCH = 0


def _sweep_stale_kernels() -> None:
    global _KERNELS_EPOCH
    epoch = backend_epoch()
    if epoch != _KERNELS_EPOCH:
        _KERNELS.clear()
        # Scheduled background compiles under old-epoch keys can never be
        # fetched either (every _cache_key ends with the epoch) — purge
        # them too, or their futures pin executables/Mesh objects and
        # queued ones burn ~15 s worker compiles for unreachable results.
        global_precompiler().purge(
            lambda k: isinstance(k, tuple) and bool(k) and k[-1] != epoch
        )
        _KERNELS_EPOCH = epoch


def _named(fn, kind: str):
    """A kernel's traced function, named after its kind: JAX names the
    program ``jit_<kind>`` (``jit_dense_step``, ``jit_fwdp``), which is
    what a profiler trace's reduction keys on."""

    @functools.wraps(fn)
    def kernel(*args, **kwargs):
        return fn(*args, **kwargs)

    kernel.__name__ = kernel.__qualname__ = kind
    return kernel


def get_kernel(game: TensorGame, kind: str, shape_key, builder,
               lowering=(), jit_kwargs=None):
    # Games whose identity is per-instance (TensorizedModule: host callbacks
    # can't be compared) carry their own cache dict, so their kernels are
    # garbage-collected with the game instead of pinning it process-wide.
    # jit_kwargs (in_shardings/out_shardings for mesh-partitioned kernels)
    # must be reflected in the caller's shape_key — the cache can't see
    # inside them.
    _sweep_stale_kernels()
    cache = getattr(game, "_private_kernel_cache", _KERNELS)
    key = _cache_key(game, kind, shape_key, lowering)
    fn = cache.get(key)
    if fn is None:
        # A background compile scheduled for this key wins over inline jit:
        # waiting out its residual beats restarting a 15 s remote compile.
        pre = global_precompiler()
        if pre.scheduled(key):
            compiled = pre.get(key, block=False)
            if compiled is None and pre.scheduled(key):
                # Still compiling: the solve waits, and says so.
                with trace_span("kernel_wait", kind=kind):
                    compiled = pre.get(key, block=True)
            if compiled is not None:
                cache[key] = compiled
                return _counted(kind, compiled)
        fn = cache[key] = jax.jit(_named(builder(game), kind),
                                  **(jit_kwargs or {}))
    return _counted(kind, fn)


def schedule_kernel(game: TensorGame, kind: str, shape_key, builder, avals,
                    heavy: bool = False, lowering=(), jit_kwargs=None):
    """Queue a background compile of a kernel (idempotent, never blocks).

    avals must match the call signature get_kernel's users will invoke the
    kernel with — the compiled executable is shared through the same cache
    key. heavy marks big-working-set programs that must not compile at
    full pool concurrency (see precompile._heavy_slots). builder(game) runs
    HERE (only tracing is deferred to the pool), so builder-captured
    lowering knobs are resolved at the same moment as the key.
    """
    if getattr(game, "_private_kernel_cache", None) is not None:
        # Per-instance-cached games (compat host-callback modules): their
        # kernels must die with the instance; routing them through the
        # process-wide precompiler would pin the instance via its future.
        return
    _sweep_stale_kernels()
    cache = _KERNELS
    key = _cache_key(game, kind, shape_key, lowering)
    if key in cache:
        return
    pre = global_precompiler()
    if pre.scheduled(key):
        return
    pre.schedule(key, jax.jit(_named(builder(game), kind),
                              **(jit_kwargs or {})),
                 tuple(avals), heavy=heavy)


def canonical_scalar(game: TensorGame, state):
    """(canonical state, topological level) of one packed state.

    The shared scalar entry for roots and point queries; runs through the
    process-wide kernel cache so per-query dispatch is O(1) even for games
    with expensive canonicalize (dihedral tic-tac-toe).

    Compiled for the HOST CPU backend when one is available: a one-element
    kernel gains nothing from the accelerator, and every accelerator
    compile costs seconds of solve startup.
    """

    def build(g):
        def f(s):
            c = g.canonicalize(s)
            return c, g.level_of(c)

        return f

    try:
        # local_devices, not devices: under multi-process execution
        # devices("cpu")[0] is process 0's device — any other process
        # would compute onto a non-addressable buffer and die fetching it.
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    arg = np.array([state], dtype=game.state_dtype)
    if cpu is not None:
        with jax.default_device(cpu):
            fn = get_kernel(game, "canon1cpu", 1, build)
            c, lvl = fn(arg)
    else:
        fn = get_kernel(game, "canon1", 1, build)
        c, lvl = fn(arg)
    return game.state_dtype(np.asarray(c)[0]), int(np.asarray(lvl)[0])


def _table_bytes(table: LevelTable) -> int:
    """Host bytes of one level's table (what a ``download`` span moved)."""
    return int(table.states.nbytes + table.values.nbytes
               + table.remoteness.nbytes)


def undecided_mask(game: TensorGame, states):
    """Which lanes hold real, non-terminal positions: [B] bool."""
    return (states != game.sentinel) & (game.primitive(states) == UNDECIDED)


@jax.named_scope("expand")
def canonical_children(game: TensorGame, states, active):
    """expand + canonicalize + deactivate parents + sentinel-fill.

    The one implementation of the per-level child generation all four solver
    kernels (single/sharded x forward/backward) share: children of inactive
    parents (padding lanes, primitives) are sentinel; survivors are
    symmetry-class representatives (identity for games without sym).
    Returns (children [B, M], mask [B, M]).
    """
    children, mask = game.expand(states)
    children = game.canonicalize(children)
    mask = mask & active[:, None]
    children = jnp.where(mask, children, game.sentinel)
    return children, mask


@jax.named_scope("expand")
def expand_core(game: TensorGame, states, compact: str | None = None):
    """Shared expand+mask+dedup: [B] -> (uniq [B*M] sorted, count).

    compact: compaction lowering, resolved at BUILD time by kernel
    builders (None = read the platform at trace time; see
    ops.dedup.compact_method)."""
    children, _ = canonical_children(game, states, undecided_mask(game, states))
    return sort_unique(children.reshape(-1), compact)


@jax.named_scope("expand")
def expand_provenance(game: TensorGame, states, compact: str | None = None):
    """Forward expand that also keeps the dedup sort's provenance.

    Returns (uniq [B*M], count, perm [B*M] int32, nreal int32, prim [B]
    uint8), perm and nreal as ops/provenance.dedup_provenance_sorted gives
    them: in the dedup sort's order, each slot's origin child slot b*M + m,
    with bit 31 set where it opens a run of equal children, the nreal real
    (non-sentinel) children first. prim is primitive(states).

    Rationale: the forward dedup sort already determines where every child
    lands in the next level's sorted table. Carrying the origin slot through
    the sort (one extra operand) preserves that knowledge, so the backward
    pass needs NO search and NO re-expansion: a monotone fetch of the deeper
    level's cells in sorted order, one pair sort back to parent order and
    the combine (see resolve_provenance). It saves the sort-merge join (the
    backward pass's dominant cost) per level, for one operand more in the
    forward sort. The first pair sort is shared with the sharded engine's
    edge-cached forward, which keeps its provenance in routed order instead
    (ops/provenance.dedup_provenance).
    """
    prim = game.primitive(states)
    active = (states != game.sentinel) & (prim == UNDECIDED)
    children, _ = canonical_children(game, states, active)
    uniq, count, perm, nreal = dedup_provenance_sorted(children.reshape(-1),
                                                       compact)
    return uniq, count, perm, nreal, prim


@jax.named_scope("combine")
def resolve_cells(n, prim, cells):
    """Combine child cells, moves major, into a level's results.

    n: scalar int32 — number of real rows (real states are a prefix of the
    capacity, a dedup-compaction invariant). prim: [C] uint8 (from forward).
    cells: [M, C] uint32 packed child cells, cells[m, b] for parent b's
    move m, cell 0 where there is no child (a real child's value is always
    decided). -> (values, rem, misses).

    Lookup misses are structurally impossible here (the cells were fetched
    through the very sort that built the deeper level), so the consistency
    counter only tracks non-primitive zero-move rows.
    """
    C = prim.shape[0]
    valid = jax.lax.iota(jnp.int32, C) < n
    undecided = valid & (prim == UNDECIDED)
    cv, cr = unpack_cells(cells)
    mask = (cv != UNDECIDED) & undecided[None, :]
    values, remoteness = combine_children(cv.T, cr.T, mask.T)
    values = jnp.where(
        undecided, values,
        jnp.where(valid, prim, jnp.uint8(UNDECIDED)),
    )
    remoteness = jnp.where(undecided, remoteness, 0)
    misses = jnp.sum(undecided & ~jnp.any(mask, axis=0))
    return values, remoteness, misses


def resolve_provenance(n, prim, perm, nreal, wvals, wrem, max_moves: int,
                       method: str = "plain"):
    """Backward resolve from stored provenance: fetch, un-permute, combine.

    perm/nreal: the level's expand_provenance outputs, [C*M] and scalar.
    wvals/wrem: the deeper level's solved values/remoteness [W]. method:
    the fetch's lowering (ops/provenance.fetch_method). -> (values, rem,
    misses, the fetch's missed lanes); see sorted_child_cells and
    resolve_cells.
    """
    cells, missed = sorted_child_cells(perm, nreal, wvals, wrem, max_moves,
                                       method)
    values, remoteness, misses = resolve_cells(n, prim, cells)
    return values, remoteness, misses, missed


@jax.named_scope("expand")
def expand_with_levels(game: TensorGame, states, compact: str | None = None):
    """Generic-path forward: expand_core + each child's topological level."""
    uniq, count = expand_core(game, states, compact)
    levels = jnp.where(uniq != game.sentinel, game.level_of(uniq), -1)
    return uniq, levels, count


@jax.named_scope("lookup")
def resolve_level(game: TensorGame, states, window,
                  method: str | None = None):
    """[B] states + solved deeper levels -> (values, remoteness, misses).

    Children are canonicalized to match the canonical solved tables.
    method: searchsorted lowering (see ops.lookup.lookup_sorted).
    """
    valid = states != game.sentinel
    prim = game.primitive(states)
    undecided = valid & (prim == UNDECIDED)
    children, mask = canonical_children(game, states, undecided)
    child_vals, child_rem, hit = lookup_window(children, window, method)
    values, remoteness = combine_children(child_vals, child_rem, mask)
    values = jnp.where(undecided, values, jnp.where(valid, prim, UNDECIDED))
    remoteness = jnp.where(undecided, remoteness, 0)
    # Consistency counters (SURVEY.md §5.2): child lookups that missed the
    # solved window, and non-primitive positions with zero legal moves
    # (a game-definition error — they would silently score LOSE/0).
    misses = jnp.sum(mask & ~hit) + jnp.sum(undecided & ~jnp.any(mask, axis=-1))
    return values, remoteness, misses


def _env_int(name: str, default: int) -> int:
    """Read an integer env knob lazily; malformed values degrade to the
    default with a warning instead of breaking package import. (Public
    re-export of utils.env.env_int — the sharded engine imports these
    names from here; the body lives in the one module GM301 audits.)"""
    return env_int(name, default)


def _env_float(name: str, default: float) -> float:
    """Float twin of _env_int (same degradation contract)."""
    return env_float(name, default)


def _backward_block() -> int:
    """Max positions resolved per backward kernel call (per shard).

    The backward step's temporaries scale with cap*max_moves (child blocks,
    lookup gathers, routing buffers in the sharded solver); levels wider
    than this are processed in column blocks against the same window, so
    peak memory is bounded by the block, not the level. Power-of-two,
    lazily read from GAMESMAN_BACKWARD_BLOCK (positions; 0 = unbounded,
    never block). Default 4M rows: the provenance resolve blocks for free
    (no per-block window re-join — just gathers against the shared table),
    and a 4M-row block's ~0.6 GB of temporaries leaves the v5e's ~15 GB
    usable HBM to the stored levels + provenance (a 16M-row block OOMed the
    5x5 solve with provenance resident).
    """
    n = _env_int("GAMESMAN_BACKWARD_BLOCK", 1 << 22)
    if n <= 0:
        return 1 << 62  # unbounded
    return max(256, 1 << (n - 1).bit_length())


def _device_store_bytes() -> int:
    """Device-resident level-store budget for the fast path (bytes of packed
    states, plus forward provenance, kept on device between the forward and
    backward phases; levels past the budget are spilled to host and
    re-uploaded during backward, and their provenance is dropped). Default
    sized for the 16 GB v5e: ~8 GB stored leaves ~2x headroom for the
    biggest level's kernel temporaries."""
    return _env_int("GAMESMAN_DEVICE_STORE_MB", 8192) << 20


class _Level:
    """One discovered level: host states + optionally the device copy.

    prim/perm/nreal are the forward pass's provenance (expand_provenance):
    this level's primitive values, and its out-edges in the order of the
    dedup sort that built the NEXT level. Device-only, kept while the store
    budget allows; when absent the backward pass falls back to the
    sort-merge join.
    """

    __slots__ = ("n", "host", "dev", "prim", "perm", "nreal")

    def __init__(self, n: int, host: Optional[np.ndarray], dev):
        self.n = n  # real (non-sentinel) count
        self.host = host  # np [n] sorted, or None if only on device
        self.dev = dev  # jnp [cap] sorted + sentinel tail, or None
        self.prim = None  # jnp [cap] uint8, or None
        self.perm = None  # jnp [cap*M] int32, or None
        self.nreal = None  # jnp int32 scalar, or None

    def host_states(self) -> np.ndarray:
        if self.host is None:
            note_dispatch("download")
            self.host = np.asarray(self.dev[: self.n])
        return self.host


class Solver:
    """Single-device level-synchronous solver for a TensorGame."""

    def __init__(
        self,
        game: TensorGame,
        *,
        min_bucket: Optional[int] = None,
        paranoid: bool = False,
        logger=None,
        checkpointer=None,
        force_generic: bool = False,
        store_tables: bool = True,
        level_sink=None,
        heartbeat_secs: Optional[float] = None,
    ):
        self.game = game
        if min_bucket is None:
            # On accelerators every distinct capacity is a ~15 s remote
            # compile, and a 64k-row kernel still runs in ~a millisecond —
            # so fold all small levels into one capacity there. On CPU
            # (tests, fake meshes) compiles are cheap; keep kernels tiny.
            default = MIN_BUCKET if jax.default_backend() == "cpu" else 65536
            min_bucket = _env_int("GAMESMAN_MIN_BUCKET", default)
        self.min_bucket = min_bucket
        self.paranoid = paranoid
        self.logger = logger
        self.checkpointer = checkpointer
        #: False = big-run mode: only the root level's table is materialized
        #: on host (plus checkpoints); see the sharded solver's docstring.
        self.store_tables = store_tables
        #: Export hook (db/writer.DbWriter.add_level_table): called with
        #: (level, LevelTable) for every level as the backward pass
        #: resolves it, deepest first — so a DB export streams level by
        #: level and never holds the full table in host memory
        #: (combine with store_tables=False).
        self.level_sink = level_sink
        #: Heartbeat period in seconds (0 = off); None reads
        #: GAMESMAN_HEARTBEAT_SECS. The heartbeat thread reads `progress`
        #: (replaced atomically per level, never mutated in place) so a
        #: wedged multi-hour solve still reports where it stopped.
        if heartbeat_secs is None:
            heartbeat_secs = _env_float("GAMESMAN_HEARTBEAT_SECS", 0.0)
        self.heartbeat_secs = float(heartbeat_secs)
        self.progress: dict = {"phase": "init"}
        self.fast = bool(game.uniform_level_jump) and not force_generic
        self.device_store_bytes = _device_store_bytes()
        self.backward_block = _backward_block()
        # Analytic traffic counters (SURVEY.md §5.5): operand bytes of the
        # sort/gather kernels, the denominators that turn positions/sec
        # into a roofline fraction for this memory-bound workload. Computed
        # from static shapes (no device counters); XLA's TPU sort makes
        # ~log2(n) passes, so HBM traffic is ~log2(n) x these bytes — the
        # convention docs/ARCHITECTURE.md states.
        self.bytes_sorted = 0
        self.bytes_gathered = 0
        #: Lanes of the provenance backward's sorted-order fetch, and its
        #: per-level missed lanes (device scalars, summed once at the end).
        self.gather_lanes = 0
        self._gather_miss: list = []
        # Background compiles only pay off where compiles are expensive
        # (remote accelerator); on CPU they would just slow the test suite.
        flag = env_str("GAMESMAN_PRECOMPILE", "auto")
        if flag == "auto":
            self.precompile = jax.default_backend() != "cpu"
        else:
            self.precompile = flag not in ("0", "off", "false")
        self._cap_ceiling = self._cap_limit() if self.precompile else 0
        # Provenance forward (expand_provenance: a pair sort + a re-sort)
        # trades forward sort work for a search-free backward — a clear win
        # on the TPU, where the backward's sort-merge join was the dominant
        # cost. On CPU the same trade REGRESSED the solve ~5x (BENCH_r01
        # 813k vs BENCH_r03 150k pos/s on 5x4, with the forward's sort work
        # then tripled) while the backward it saves was already cheap.
        # Keyed on the platform that will execute, not on an env var
        # benches could forget
        # (GAMESMAN_PROVENANCE=0/1 remains as a test/experiment override).
        # RESOLVED AT SOLVE TIME, like every other platform-auto knob: a
        # force_platform between construction and solve() must re-resolve
        # (speculate/search/compact all would; this must not lag behind on
        # the stale platform).
        self.use_provenance: bool | None = None
        #: transient level-step failures absorbed by retry (stats field;
        #: the registry carries the per-point gamesman_retries_total).
        self.retries = 0
        #: dispatch accounting (see note_dispatch): total device
        #: computations/transfers this solve issued, and the per-(phase,
        #: level) breakdown the zero-extra-dispatch tests assert on.
        self.dispatch_total = 0
        self.level_dispatches: Dict[tuple, int] = {}
        self.dispatch_by_kind: Dict[str, int] = {}
        #: analytic host-transfer bytes (frontier/table uploads+downloads
        #: and checkpoint materializations) — the host-side roofline
        #: denominator next to bytes_sorted/bytes_gathered's HBM side.
        self.bytes_host = 0
        #: live-status progress model (obs/status.py): per-level schedule
        #: + ETA behind the GAMESMAN_STATUS_PORT /status endpoint.
        self.status_tracker = SolveStatusTracker()

    def _on_dispatch(self, kind: str) -> None:
        """Dispatch sink (set_dispatch_sink) — see tally_dispatch."""
        tally_dispatch(self, kind)

    def _retry(self, point: str, fn, reset=None, level=None):
        """Level-step retry wrapper: bounded exponential backoff on
        transient runtime errors, re-entering from the step's held
        inputs via ``reset`` (see resilience.retry)."""

        def on_retry(attempt, exc):
            self.retries += 1

        return retry_call(fn, point=point, reset=reset, level=level,
                          logger=self.logger, on_retry=on_retry)

    # ---------------------------------------------------------------- kernels

    def _expand_impl(self, states):
        """[B] states -> (unique children, their levels, count).

        Traceable generic-path forward (also the driver compile-check entry).
        """
        return expand_with_levels(self.game, states)

    # Cached kernel getters. Builders close over the game only — a cached
    # kernel outlives this Solver (see _KERNELS).

    @staticmethod
    def _fwdp_builder(game):
        # Builders run at cache-key time (inside get_kernel/
        # schedule_kernel), so resolving the lowering knobs HERE keeps the
        # traced program consistent with the key even when a background
        # worker traces it later.
        cm = compact_method()
        return lambda states: expand_provenance(game, states, cm)

    @staticmethod
    def _bwd_builder(game):
        sm = search_method()  # resolved at cache-key time

        def f(states, *window_flat):
            window = tuple(
                (window_flat[i], window_flat[i + 1], window_flat[i + 2])
                for i in range(0, len(window_flat), 3)
            )
            return resolve_level(game, states, window, sm)

        return f

    @staticmethod
    def _bwdp_builder(game):
        M = game.max_moves
        fm = fetch_method()  # resolved at cache-key time
        return lambda n, prim, perm, nreal, wvals, wrem: resolve_provenance(
            n, prim, perm, nreal, wvals, wrem, M, fm
        )

    @staticmethod
    def _bwdg_builder(game):
        M = game.max_moves
        fm = fetch_method()
        return lambda perm, nreal, wvals, wrem: sorted_child_cells(
            perm, nreal, wvals, wrem, M, fm
        )

    @staticmethod
    def _bwdc_builder(game):
        return resolve_cells

    @staticmethod
    def _fwdf_builder(game):
        cm = compact_method()
        return lambda states: expand_core(game, states, cm)

    @staticmethod
    def _fwd_lowering():
        """Knob the forward kernels embed: the dedup compaction."""
        return (compact_method(),)

    def _fwdp(self, cap: int):
        """Provenance forward: states[cap] -> (uniq, count, perm, nreal,
        prim)."""
        return get_kernel(self.game, "fwdp", cap, self._fwdp_builder,
                          lowering=self._fwd_lowering())

    def _fwdf(self, cap: int):
        """Plain fast forward (one dedup sort, no provenance): states[cap]
        -> (uniq, count). The CPU default — see use_provenance."""
        return get_kernel(self.game, "fwdf", cap, self._fwdf_builder,
                          lowering=self._fwd_lowering())

    def _bwdp(self, cap: int):
        """Provenance backward of a whole level: (n, prim[cap],
        perm[cap*M], nreal, wvals[cap], wrem[cap]) -> (values, rem, misses,
        missed fetch lanes)."""
        return get_kernel(self.game, "bwdp", cap, self._bwdp_builder,
                          lowering=(fetch_method(),))

    def _bwdg(self, cap: int):
        """A wide level's child-cell fetch: (perm[cap*M], nreal,
        wvals[cap], wrem[cap]) -> (cells[M, cap], missed fetch lanes)."""
        return get_kernel(self.game, "bwdg", cap, self._bwdg_builder,
                          lowering=(fetch_method(),))

    def _bwdc(self, block: int):
        """A block of a wide level's combine: (n, prim[block],
        cells[M, block]) -> (values, rem, misses)."""
        return get_kernel(self.game, "bwdc", block, self._bwdc_builder)

    def _fwd_generic(self, cap: int):
        def build(game):
            cm = compact_method()  # resolved at cache-key time
            return lambda states: expand_with_levels(game, states, cm)

        return get_kernel(self.game, "fwdg", cap, build,
                          lowering=self._fwd_lowering())

    def _bwd(self, cap: int, wcaps: tuple):
        """Backward: states[cap] + window levels -> (values, rem, misses).

        wcaps: tuple of window-level capacities (possibly empty — deepest
        level, everything primitive; the fast path always passes a single
        window level padded to the common capacity, see _backward_fast).
        """
        return get_kernel(
            self.game, "bwd", (cap, tuple(wcaps)), self._bwd_builder,
            lowering=(search_method(),),  # lookup_window's search lowering
        )

    # ---------------------------------------------- background compile plan

    def _cap_limit(self) -> int:
        """Largest capacity worth speculatively compiling for.

        Bounded by the state space (2^state_bits can't be exceeded by a
        frontier) and by device memory for the kernel's temporaries
        (children block + sort buffers ~ 4x children bytes).
        """
        g = self.game
        item = np.dtype(g.state_dtype).itemsize
        mem = _env_int("GAMESMAN_PRECOMPILE_MEM_MB", 4096) << 20
        by_mem = mem // max(g.max_moves * item * 4, 1)
        by_space = 1 << min(g.state_bits, 34)
        return bucket_size(max(min(by_mem, by_space), 1), self.min_bucket)

    def _sched_bwd(self, cap: int, wcaps: tuple) -> None:
        if cap > self._cap_ceiling:
            return
        dt = self.game.state_dtype
        avals = [sds((cap,), dt)]
        for w in wcaps:
            avals += [sds((w,), dt), sds((w,), np.uint8), sds((w,), np.int32)]
        schedule_kernel(
            self.game, "bwd", (cap, tuple(wcaps)), self._bwd_builder, avals,
            heavy=self._heavy(max((cap,) + tuple(wcaps))),
            lowering=(search_method(),),
        )

    def _heavy(self, cap: int) -> bool:
        """Programs whose children block exceeds ~256 MB compile under the
        heavy semaphore — concurrent big compiles can exhaust host memory
        (see precompile._heavy_slots)."""
        item = np.dtype(self.game.state_dtype).itemsize
        return cap * self.game.max_moves * item > (256 << 20)

    def _sched_fwdp(self, cap: int) -> None:
        if cap > self._cap_ceiling:
            return
        schedule_kernel(
            self.game, "fwdp", cap, self._fwdp_builder,
            (sds((cap,), self.game.state_dtype),),
            heavy=self._heavy(cap), lowering=self._fwd_lowering(),
        )

    def _sched_fwdf(self, cap: int) -> None:
        if cap > self._cap_ceiling:
            return
        schedule_kernel(
            self.game, "fwdf", cap, self._fwdf_builder,
            (sds((cap,), self.game.state_dtype),),
            heavy=self._heavy(cap), lowering=self._fwd_lowering(),
        )

    def _sched_fwd_step(self, cap: int) -> None:
        """Schedule whichever forward kernel this solver will request."""
        if self.use_provenance:
            self._sched_fwdp(cap)
        else:
            self._sched_fwdf(cap)

    def _sched_bwd_step(self, cap: int) -> None:
        """Schedule whichever backward kernels this solver will request
        for a level of common capacity `cap` (see _backward_plan)."""
        if self.use_provenance:
            self._sched_bwdp(cap)
        else:
            self._sched_bwd(min(cap, self._block_size()), (cap,))

    def _sched_bwdp(self, cap: int) -> None:
        """The provenance backward's kernels at common capacity `cap`: one
        bwdp, or for a level wider than the block the bwdg fetch and the
        bwdc combine (see _resolve_blocked_prov)."""
        if cap > self._cap_ceiling:
            return
        g = self.game
        M = g.max_moves
        lowering = (fetch_method(),)
        fetch_avals = (
            sds((cap * M,), np.int32),
            sds((), np.int32),
            sds((cap,), np.uint8),
            sds((cap,), np.int32),
        )
        block = self._block_size()
        if cap <= block:
            schedule_kernel(
                g, "bwdp", cap, self._bwdp_builder,
                (sds((), np.int32), sds((cap,), np.uint8)) + fetch_avals,
                heavy=self._heavy(cap), lowering=lowering,
            )
            return
        schedule_kernel(g, "bwdg", cap, self._bwdg_builder, fetch_avals,
                        heavy=self._heavy(cap), lowering=lowering)
        schedule_kernel(
            g, "bwdc", block, self._bwdc_builder,
            (sds((), np.int32), sds((block,), np.uint8),
             sds((M, block), np.uint32)),
            heavy=self._heavy(block),
        )

    def _schedule_initial_ladder(self) -> None:
        """Queue background compiles for the first few capacity doublings.

        Forward growth will outrun a ~15 s compile long before the ladder
        top is reached; scheduling the whole plausible ladder up front lets
        the pool compile ~8 shapes concurrently while small levels execute.
        """
        cap = self.min_bucket
        for _ in range(7):
            if cap > self._cap_ceiling:
                break
            self._sched_fwd_step(cap)
            self._sched_bwd_step(cap)
            cap *= 2

    def _block_size(self) -> int:
        """Largest power of two <= backward_block: caps are powers of two,
        so this always divides cap exactly (no ragged final block), even
        when the attribute was set directly to an odd value. Shared by
        _resolve_blocked and the backward compile scheduler — their kernel
        keys must agree."""
        return 1 << max(self.backward_block, 1).bit_length() - 1

    def _resolve_blocked_prov(self, n: int, prim, perm, nreal, wvals,
                              wrem):
        """Provenance resolve, its combine in column blocks when the level
        is wide. -> (values, rem, misses, missed fetch lanes).

        The fetch and its un-permute sort run once over the whole level
        (bwdg): the sorted order spans every parent. The combine keeps
        _resolve_blocked's contract: per-block temporaries bounded by the
        block; results concatenate on device; misses accumulate on device.
        """
        C = prim.shape[0]
        M = self.game.max_moves
        block = self._block_size()
        if C <= block:
            return self._bwdp(C)(np.int32(n), prim, perm, nreal, wvals, wrem)
        cells, missed = self._bwdg(C)(perm, nreal, wvals, wrem)
        values, rems = [], []
        misses = None
        for off in range(0, C, block):
            nb = np.int32(min(max(n - off, 0), block))
            v, r, m = self._bwdc(block)(
                nb,
                jax.lax.slice(prim, (off,), (off + block,)),
                jax.lax.slice(cells, (0, off), (M, off + block)),
            )
            values.append(v)
            rems.append(r)
            misses = m if misses is None else misses + m
        return jnp.concatenate(values), jnp.concatenate(rems), misses, missed

    def _resolve_blocked(self, states_dev, wcaps: tuple, window_args: tuple):
        """Backward-resolve a level, in column blocks when it is wide.

        Levels wider than `backward_block` run the same kernel per block
        against the same window — peak temporaries are bounded by the block
        (SURVEY.md §7 "Memory budget"); results concatenate on device.
        """
        cap = states_dev.shape[0]
        block = self._block_size()
        if cap <= block:
            return self._bwd(cap, wcaps)(states_dev, *window_args)
        values, rems = [], []
        misses = None
        for off in range(0, cap, block):
            v, r, m = self._bwd(block, wcaps)(
                jax.lax.slice(states_dev, (off,), (off + block,)),
                *window_args,
            )
            values.append(v)
            rems.append(r)
            # Accumulate on device — callers sync the total only under
            # --paranoid, so block dispatch never serializes on the host.
            misses = m if misses is None else misses + m
        return jnp.concatenate(values), jnp.concatenate(rems), misses

    # ------------------------------------------------------------- fast phase

    def _forward_fast(self, init, start_level: int,
                      resume: Optional[Dict[int, np.ndarray]] = None,
                      ) -> Dict[int, _Level]:
        """Device-resident forward sweep for uniform_level_jump games.

        Two latency hiders on top of the level loop:

        * the expand kernel is expand_provenance — its perm/nreal/prim
          outputs are stored (budget permitting) so the backward pass needs
          no search (see resolve_provenance);
        * the next level's expand is dispatched SPECULATIVELY at the current
          capacity before the unique-count host sync;
          most levels keep their bucket, so the device computes through the
          sync instead of idling. A mispredicted bucket just re-dispatches
          at the right capacity — the speculative result is dropped.

        With a checkpointer, each level's frontier is saved the moment its
        count is known (same total bytes as the old end-of-forward snapshot
        — host_states() caches the download — but a mid-forward death keeps
        the prefix). `resume` is that prefix from a previous interrupted
        run: expansion continues from its deepest level; earlier levels
        carry no provenance, so the backward pass uses the lookup join for
        them, exactly as for budget-evicted levels.
        """
        g = self.game
        levels: Dict[int, _Level] = {}
        if resume:
            ks = sorted(resume)
            if ks != list(range(ks[0], ks[-1] + 1)) or ks[0] != start_level:
                raise SolverError(
                    f"forward checkpoint levels {ks} are not contiguous from "
                    f"the root level {start_level} — stale checkpoint "
                    "directory?"
                )
            for kk in ks:
                arr = np.asarray(resume[kk], dtype=g.state_dtype)
                levels[kk] = _Level(arr.shape[0], arr, None)
            k = ks[-1]
            host0 = levels[k].host
        else:
            # init: one root state, or a whole sorted frontier (the hybrid
            # engine starts BFS at its cutover level's reachable set).
            host0 = np.atleast_1d(np.asarray(init, dtype=g.state_dtype))
            k = start_level
        cap0 = bucket_size(host0.shape[0], self.min_bucket)
        note_dispatch("upload")
        frontier = jnp.asarray(pad_to(host0, cap0))
        if resume:
            levels[k].dev = frontier
        else:
            levels[k] = _Level(host0.shape[0], host0, frontier)
            if self.checkpointer is not None:
                with trace_span("checkpoint", level=k, kind="frontier"):
                    self.checkpointer.save_frontier_level(k, host0)
        stored_bytes = frontier.nbytes
        # Speculation keeps the accelerator busy through the host sync; on
        # CPU the sync is microseconds and a dropped speculative expand is
        # real wasted work.
        speculate = platform_auto_bool(
            "GAMESMAN_SPECULATE", accel=True, cpu=False
        )

        def fwd_step(arr):
            """Dispatch the platform-selected forward kernel; normalize to
            (uniq, count, perm|None, nreal|None, prim|None)."""
            if self.use_provenance:
                return self._fwdp(arr.shape[0])(arr)
            u, c = self._fwdf(arr.shape[0])(arr)
            return u, c, None, None, None

        pending = fwd_step(frontier)
        while True:
            sp = Span("forward", logger=self.logger, level=k)
            self.progress = {
                "phase": "forward", "level": k, "frontier": levels[k].n,
            }
            # Level boundary: everything before this level is saved
            # (save_frontier_level is eager), so a grace signal stops
            # HERE and the next run resumes expansion from level k.
            preempt.check("forward", level=k, logger=self.logger)
            memguard.check("forward", level=k, logger=self.logger)
            cap = frontier.shape[0]
            d0 = self.dispatch_total
            spec = spec_input = None
            if speculate:
                note_dispatch("eager")
                spec_input = jax.lax.slice(pending[0], (0,), (cap,))
                spec = fwd_step(spec_input)
            # The expand+dedup kernel retires AT this host sync (dispatch
            # is async), so the dedup/sort wait is what this span times.
            # The sync is the level's transient-failure surface: a transient
            # runtime error raises here, and the retry re-dispatches from the
            # frontier (still in hand) — checkpoint-consistent re-entry.
            holder = [pending]

            def _sync(holder=holder, k=k):
                faults.fire("engine.forward", level=k)
                faults.fire("engine.dedup", level=k)
                return int(holder[0][1])  # the one host sync per level

            def _redispatch(holder=holder, frontier=frontier):
                holder[0] = fwd_step(frontier)

            with trace_span("dedup", level=k):
                n = self._retry("engine.forward", _sync, reset=_redispatch,
                                level=k)
            if holder[0] is not pending:
                pending = holder[0]
                spec = spec_input = None  # speculation predates the retry
            uniq, count, perm, nreal, prim = pending
            rec = levels[k]
            if perm is not None:
                extra = prim.nbytes + perm.nbytes
                if n > 0 and stored_bytes + extra <= self.device_store_bytes:
                    # Keep this level's provenance for the search-free
                    # backward.
                    rec.prim, rec.perm, rec.nreal = prim, perm, nreal
                    stored_bytes += extra
            if n == 0:
                # Terminal probe: the span's trace event is kept (its
                # wait time is real) but no JSONL record — the per-level
                # stream is unchanged from the hand-rolled log calls.
                sp.end(log=False)
                self.status_tracker.forward_level(k, levels[k].n, sp.secs)
                flightrec.boundary("forward", k)
                break
            if k + 1 >= g.num_levels:
                # num_levels is the declared exclusive bound on level_of over
                # reachable states; children past it mean the game's
                # level_of/num_levels contract is broken (and, unchecked,
                # a buggy level function could loop forever here).
                raise SolverError(
                    f"game {g.name}: children found at level {k + 1} but "
                    f"num_levels={g.num_levels} — level_of/num_levels "
                    "inconsistent"
                )
            next_cap = bucket_size(n, self.min_bucket)
            if next_cap > cap:
                # Frontier grew into a new bucket: queue compiles two and
                # four doublings ahead so growth never outruns the pool.
                # Backward kernels block at _block_size() — schedule the key
                # the backward pass will actually request.
                for ahead in (next_cap * 2, next_cap * 4):
                    self._sched_fwd_step(ahead)
                    self._sched_bwd_step(ahead)
            if next_cap == cap and spec is not None:
                nxt = spec_input
                pending = spec
            else:
                note_dispatch("eager")
                if next_cap <= uniq.shape[0]:
                    nxt = jax.lax.slice(uniq, (0,), (next_cap,))
                else:
                    # bucket(n) can exceed cap*M for non-power-of-two
                    # branching factors (e.g. M=7: n in (1024, 1792] at
                    # cap=256); extend with sentinel padding on device — no
                    # host round-trip.
                    nxt = jnp.concatenate(
                        [
                            uniq,
                            jnp.full(
                                next_cap - uniq.shape[0], g.sentinel,
                                dtype=uniq.dtype,
                            ),
                        ]
                    )
                pending = fwd_step(nxt)
            rec = _Level(n, None, nxt)
            if stored_bytes + nxt.nbytes > self.device_store_bytes:
                # Device-store budget exhausted: keep this level on host only
                # (backward re-uploads it); the live frontier still chains on
                # device.
                rec.host_states()
                rec.dev = None
            else:
                stored_bytes += nxt.nbytes
            levels[k + 1] = rec
            frontier = nxt
            if self.checkpointer is not None:
                with trace_span("checkpoint", level=k + 1, kind="frontier"):
                    self.checkpointer.save_frontier_level(k + 1,
                                                          rec.host_states())
            item = np.dtype(g.state_dtype).itemsize
            # Only operands of actual sorts count (the traffic denominator
            # must match the kernel the platform lowered).
            compaction = compaction_sort_bytes(item)
            if self.use_provenance:
                # expand_provenance: (child, origin i32) pair sort + the
                # compaction (the un-permute sort is the backward's).
                level_sort_bytes = cap * g.max_moves * (item + 4 + compaction)
            else:
                # expand_core: one dedup sort + the compaction.
                level_sort_bytes = cap * g.max_moves * (item + compaction)
            self.bytes_sorted += level_sort_bytes
            # Host-transfer bytes this level caused: the frontier download
            # for the checkpoint write (host_states caches it) — the
            # per-level roofline denominator on the host side.
            fwd_host_bytes = (
                n * item if self.checkpointer is not None else 0
            )
            self.bytes_host += fwd_host_bytes
            sp.end(
                frontier=levels[k].n,
                children=n,
                bytes_sorted=level_sort_bytes,
                bytes_hbm=level_sort_bytes,
                bytes_host=fwd_host_bytes,
                dispatches=self.dispatch_total - d0,
            )
            self.status_tracker.forward_level(k, levels[k].n, sp.secs)
            flightrec.boundary("forward", k)
            k += 1
        return levels

    @staticmethod
    def _pad_dev(arr, cap: int, fill):
        """Pad a 1-D device array to `cap` with `fill` (no-op when already)."""
        if arr.shape[0] >= cap:
            return arr
        note_dispatch("eager")
        return jnp.concatenate(
            [arr, jnp.full(cap - arr.shape[0], fill, dtype=arr.dtype)]
        )

    def _level_host_bytes(self, k: int, root_level: int, cap: int,
                          n: int, item: int, uploaded: bool,
                          from_checkpoint: bool) -> int:
        """Analytic host-transfer bytes of one resolved fast-path level
        (the roofline span field): the state re-upload when the level
        was host-spilled, plus the table materialization download
        (states + packed values/remoteness) when one will happen."""
        will_tbl = (
            self.store_tables or k == root_level
            or self.checkpointer is not None
            or self.level_sink is not None
        )
        return (
            (cap * item if uploaded else 0)
            + (n * (item + 5)
               if will_tbl and not from_checkpoint else 0)
        )

    def _backward_plan(self, levels: Dict[int, _Level]):
        """Per-level common capacity: max of own and window (deeper) bucket.

        Padding states and window to ONE capacity keys the backward kernel
        on a single integer, collapsing the (cap, window-cap) shape
        cross-product — at ~15 s per remote compile this halves backward
        compile count; the padding itself is a device-side concat.
        """
        ks = sorted(levels, reverse=True)
        caps = {k: bucket_size(levels[k].n, self.min_bucket) for k in ks}
        common = {}
        # Common-capacity padding halves backward COMPILE count — the right
        # trade at ~15 s per remote compile, the wrong one on CPU where
        # compiles are cheap and the padding is real lookup/combine work on
        # alternating levels. The provenance resolve requires it regardless
        # (its blocked kernel assumes states and window share one shape).
        pad = self.use_provenance or jax.default_backend() != "cpu"
        for k in ks:
            if k + 1 in caps and pad:
                common[k] = max(caps[k], caps[k + 1])
            else:
                common[k] = caps[k]
        return ks, caps, common

    def _backward_fast(self, levels: Dict[int, _Level],
                       root_level: int) -> Dict[int, LevelTable]:
        """Deepest-first resolve; the window is the previous (deeper) level."""
        g = self.game
        resolved: Dict[int, LevelTable] = {}
        completed = (
            set(self.checkpointer.completed_levels())
            if self.checkpointer is not None
            else set()
        )
        ks, caps, common = self._backward_plan(levels)
        # All backward shapes are now known exactly; queue them deepest-first
        # so compilation overlaps the deep levels' execution. Checkpointed
        # levels load instead of resolving — no kernel needed.
        block = self._block_size()
        for k in ks:
            if k in completed:
                continue
            C = common[k]
            rec = levels[k]
            if k + 1 in levels and rec.perm is not None:
                self._sched_bwdp(C)
            else:
                # Window shape = its own bucket padded to C (no-op pad when
                # the plan uses exact buckets) — must match the key the
                # resolve below will request.
                wcaps = (max(C, caps[k + 1]),) if k + 1 in levels else ()
                self._sched_bwd(min(C, block), wcaps)
        prev = None  # (states_dev, values_dev, rem_dev) of level k+1, at its C
        for k in ks:
            sp = Span("backward", logger=self.logger, level=k)
            d0 = self.dispatch_total
            rec = levels[k]
            n = rec.n
            self.progress = {"phase": "backward", "level": k, "n": n}
            preempt.check("backward", level=k, logger=self.logger)
            memguard.check("backward", level=k, logger=self.logger)
            C = common[k]
            uploaded = rec.dev is None
            if rec.dev is not None:
                states_dev = rec.dev
            else:
                note_dispatch("upload")
                states_dev = jnp.asarray(
                    pad_to(rec.host_states(),
                           bucket_size(n, self.min_bucket))
                )
            states_dev = self._pad_dev(states_dev, C, g.sentinel)
            cap = states_dev.shape[0]
            from_checkpoint = k in completed
            item = np.dtype(g.state_dtype).itemsize
            lvl_sort_bytes = lvl_gather_bytes = lvl_lanes = 0
            lvl_missed = None
            table = None
            if from_checkpoint:
                from gamesmanmpi_tpu.utils.checkpoint import TORN_NPZ_ERRORS

                try:
                    table = self.checkpointer.load_level(k)
                except TORN_NPZ_ERRORS as e:
                    # Torn or crc-mismatching sealed level (the loader
                    # already quarantined a crc failure): degrade to the
                    # intact prefix — the frontier is still known, so the
                    # level recomputes and re-seals over the quarantine.
                    self.checkpointer.quarantine_and_log(k, e, self.logger)
                    from_checkpoint = False
            if from_checkpoint:
                states_host = rec.host_states()
                if table.states.shape[0] != n or not (
                    np.asarray(table.states, dtype=g.state_dtype) == states_host
                ).all():
                    raise SolverError(
                        f"checkpointed level {k} does not match the discovered "
                        "frontier — stale checkpoint directory?"
                    )
                values_dev = jnp.asarray(pad_to_cap_u8(table.values, cap))
                rem_dev = jnp.asarray(pad_to_cap_i32(table.remoteness, cap))
            else:
                def _resolve():
                    # The level's inputs (states_dev, prev window triple,
                    # stored provenance) are all still referenced, so a
                    # transient failure re-dispatches idempotently.
                    nonlocal lvl_sort_bytes, lvl_gather_bytes, lvl_lanes, \
                        lvl_missed
                    faults.fire("engine.backward", level=k)
                    if prev is not None and rec.perm is not None:
                        slots = C * g.max_moves
                        # The monotone fetch: perm read (4 B) + packed
                        # cell (4 B) per child slot; the un-permute sort:
                        # (i32 origin, u32 cell) pairs.
                        lvl_gather_bytes = slots * 8
                        lvl_sort_bytes = slots * 8
                        lvl_lanes = slots
                        # Resolve from forward provenance: no search, no
                        # re-expansion (see resolve_provenance).
                        wcap = caps[k + 1]
                        note_dispatch("eager")
                        note_dispatch("eager")
                        wv = jax.lax.slice(prev[1], (0,), (wcap,))
                        wr = jax.lax.slice(prev[2], (0,), (wcap,))
                        *out, lvl_missed = self._resolve_blocked_prov(
                            n,
                            self._pad_dev(rec.prim, C, np.uint8(UNDECIDED)),
                            self._pad_dev(rec.perm, slots,
                                          np.int32(PERM_PAD)),
                            rec.nreal,
                            self._pad_dev(wv, C, np.uint8(UNDECIDED)),
                            self._pad_dev(wr, C, np.int32(0)),
                        )
                        return out
                    if prev is not None:
                        if search_method() == "sort":
                            # Sort-merge join operands + fused u64 payload
                            # gather with its i32 indices.
                            lvl_sort_bytes = (C * g.max_moves + C) * (item + 4)
                            lvl_gather_bytes = C * g.max_moves * 12
                        else:
                            # Binary search: no join sort; one fused payload
                            # gather per child (the log2(W) traversal reads
                            # are not modeled).
                            lvl_gather_bytes = C * g.max_moves * 8
                    if prev is None:
                        args, wcaps = (), ()
                    else:
                        # Slice the deeper level down to its own bucket, then
                        # pad to this level's common capacity when the plan
                        # uses one (see _backward_plan; exact buckets on
                        # CPU, so _pad_dev may no-op and the window keeps
                        # its own shape).
                        wcap = caps[k + 1]
                        for _ in range(3):
                            note_dispatch("eager")
                        ws = jax.lax.slice(prev[0], (0,), (wcap,))
                        wv = jax.lax.slice(prev[1], (0,), (wcap,))
                        wr = jax.lax.slice(prev[2], (0,), (wcap,))
                        args = (
                            self._pad_dev(ws, C, g.sentinel),
                            self._pad_dev(wv, C, np.uint8(UNDECIDED)),
                            self._pad_dev(wr, C, np.int32(0)),
                        )
                        wcaps = (args[0].shape[0],)
                    return self._resolve_blocked(states_dev, wcaps, args)

                values_dev, rem_dev, misses = self._retry(
                    "engine.backward", _resolve, level=k
                )
                if lvl_missed is not None:
                    self.gather_lanes += lvl_lanes
                    self._gather_miss.append(lvl_missed)
                if self.paranoid and int(misses) > 0:
                    raise SolverError(
                        f"level {k}: {int(misses)} consistency failures (child "
                        "lookups outside the solved window — level_of/"
                        "max_level_jump inconsistent — or non-primitive "
                        "positions with zero legal moves)"
                    )
            prev = (states_dev, values_dev, rem_dev)
            # The level's host-side tail: table materialization (the
            # downloads), export, checkpoint seal, buffer release.
            tbl = table
            if tbl is None and (
                self.store_tables
                or k == root_level
                or self.checkpointer is not None
                or self.level_sink is not None
            ):
                note_dispatch("download")
                with trace_span("download", level=k) as dl:
                    tbl = LevelTable(
                        states=rec.host_states(),
                        values=np.asarray(values_dev[:n]),
                        remoteness=np.asarray(rem_dev[:n]),
                    )
                    dl.set(bytes=_table_bytes(tbl))
            if tbl is not None and (self.store_tables or k == root_level):
                resolved[k] = tbl
            if self.level_sink is not None and tbl is not None:
                with trace_span("db_export", level=k, n=n):
                    self.level_sink(k, tbl)
            if self.checkpointer is not None and not from_checkpoint:
                with trace_span("checkpoint", level=k, kind="level"):
                    self.checkpointer.save_level(k, tbl)
            rec.dev = None  # release the forward copy
            rec.prim = rec.perm = rec.nreal = None  # release provenance
            if not self.store_tables:
                rec.host = None
            if not from_checkpoint and C >= (1 << 21):
                # Bound enqueue run-ahead: with no per-level downloads the
                # host races through the whole backward, allocating every
                # level's padded inputs before any kernel retires — enough
                # to OOM HBM at 5x5 scale. An 8-byte fetch (~65 ms) per BIG
                # level caps liveness at ~one level's working set; small
                # levels stay fully async.
                with trace_span("drain", level=k):
                    np.asarray(misses)
            self.bytes_sorted += lvl_sort_bytes
            self.bytes_gathered += lvl_gather_bytes
            lvl_host_bytes = self._level_host_bytes(
                k, root_level, cap, n, item, uploaded, from_checkpoint
            )
            self.bytes_host += lvl_host_bytes
            sp.end(
                n=n,
                resumed=from_checkpoint,
                bytes_sorted=lvl_sort_bytes,
                bytes_gathered=lvl_gather_bytes,
                bytes_hbm=lvl_sort_bytes + lvl_gather_bytes,
                bytes_host=lvl_host_bytes,
                dispatches=self.dispatch_total - d0,
            )
            self.status_tracker.backward_level(k, n, sp.secs,
                                               resumed=from_checkpoint)
            flightrec.boundary("backward", k)
        return resolved

    # ---------------------------------------------------------- generic phase

    def _forward_generic(self, pools: Dict[int, np.ndarray], start_level: int):
        """Host-pooled forward for multi-jump games (children span levels)."""
        g = self.game
        k = start_level
        while pools and k <= max(pools):
            if k not in pools:
                k += 1
                continue
            sp = Span("forward", logger=self.logger, level=k)
            frontier = pools[k]
            self.progress = {
                "phase": "forward", "level": k,
                "frontier": int(frontier.shape[0]),
            }
            preempt.check("forward", level=k, logger=self.logger)
            memguard.check("forward", level=k, logger=self.logger)
            padded = pad_to_bucket(frontier, self.min_bucket)
            note_dispatch("upload")
            states_dev = jnp.asarray(padded)
            uniq, levels, count = self._fwd_generic(padded.shape[0])(
                states_dev
            )
            # expand_core's dedup sort (+ compaction re-sort when the
            # platform lowers compaction as a sort).
            item = np.dtype(g.state_dtype).itemsize
            lvl_sort_bytes = (
                padded.shape[0] * g.max_moves
                * (item + compaction_sort_bytes(item))
            )
            self.bytes_sorted += lvl_sort_bytes
            # Generic-path dedup is two-stage: the kernel's sort-unique
            # (whose wait is the int(count) sync) plus the host-side
            # merge of multi-jump children into per-level pools.
            with trace_span("dedup", level=k):
                holder = [(uniq, levels, count)]

                def _sync(holder=holder, k=k):
                    faults.fire("engine.forward", level=k)
                    faults.fire("engine.dedup", level=k)
                    u, lv, c = holder[0]
                    nn = int(c)
                    return nn, np.asarray(u[:nn]), np.asarray(lv[:nn])

                def _redispatch(holder=holder, states_dev=states_dev,
                                padded=padded):
                    holder[0] = self._fwd_generic(padded.shape[0])(
                        states_dev
                    )

                n, kids, kid_levels = self._retry(
                    "engine.forward", _sync, reset=_redispatch, level=k
                )
                for lv in np.unique(kid_levels):
                    lv = int(lv)
                    if lv >= g.num_levels:
                        raise SolverError(
                            f"game {g.name}: children found at level {lv} "
                            f"but num_levels={g.num_levels} — level_of/"
                            "num_levels inconsistent"
                        )
                    batch = kids[kid_levels == lv]
                    if lv in pools:
                        pools[lv] = np.union1d(pools[lv], batch)
                    else:
                        pools[lv] = batch
            sp.end(
                frontier=int(frontier.shape[0]),
                children=n,
                bytes_sorted=lvl_sort_bytes,
                bytes_hbm=lvl_sort_bytes,
            )
            self.status_tracker.forward_level(
                k, int(frontier.shape[0]), sp.secs
            )
            flightrec.boundary("forward", k)
            k += 1

    def _backward_generic(self, pools: Dict[int, np.ndarray],
                          root_level: int) -> Dict[int, LevelTable]:
        """Resolve all levels deepest-first against a multi-level window.

        Levels already present in the checkpoint (a previous, preempted run)
        are loaded instead of recomputed — restart-from-level recovery.
        store_tables=False only bounds result-RAM here (tables are still
        materialized transiently for the host window cache; the multi-jump
        games in the catalog are small — the big-run mode that avoids
        downloads entirely is the fast path and the sharded solver).
        """
        g = self.game
        resolved: Dict[int, LevelTable] = {}
        padded_cache: Dict[int, tuple] = {}
        completed = (
            set(self.checkpointer.completed_levels())
            if self.checkpointer is not None
            else set()
        )
        for k in sorted(pools, reverse=True):
            sp = Span("backward", logger=self.logger, level=k)
            states = pools[k]
            padded = pad_to_bucket(states, self.min_bucket)
            n = states.shape[0]
            self.progress = {"phase": "backward", "level": k, "n": int(n)}
            preempt.check("backward", level=k, logger=self.logger)
            memguard.check("backward", level=k, logger=self.logger)
            from_checkpoint = k in completed
            lvl_sort_bytes = lvl_gather_bytes = 0
            table = None
            if from_checkpoint:
                from gamesmanmpi_tpu.utils.checkpoint import TORN_NPZ_ERRORS

                try:
                    table = self.checkpointer.load_level(k)
                except TORN_NPZ_ERRORS as e:
                    # Same degrade contract as the fast path: quarantine
                    # and recompute from the still-known frontier.
                    self.checkpointer.quarantine_and_log(k, e, self.logger)
                    from_checkpoint = False
            if from_checkpoint:
                if table.states.shape[0] != n or not (
                    np.asarray(table.states, dtype=g.state_dtype) == states
                ).all():
                    raise SolverError(
                        f"checkpointed level {k} does not match the discovered "
                        "frontier — stale checkpoint directory?"
                    )
            else:
                window_levels = [
                    k + j
                    for j in range(1, g.max_level_jump + 1)
                    if (k + j) in padded_cache
                ]
                window_flat = []
                for L in window_levels:
                    window_flat.extend(padded_cache[L])
                wcaps = tuple(padded_cache[L][0].shape[0] for L in window_levels)
                item = np.dtype(g.state_dtype).itemsize
                cm = padded.shape[0] * g.max_moves
                if search_method() == "sort":
                    # Per-window-level sort-merge joins + payload gathers.
                    lvl_sort_bytes = sum(
                        (cm + w) * (item + 4) for w in wcaps
                    )
                    lvl_gather_bytes = cm * 12 * len(wcaps)
                else:
                    # Binary search: payload gathers only.
                    lvl_gather_bytes = cm * 8 * len(wcaps)
                self.bytes_sorted += lvl_sort_bytes
                self.bytes_gathered += lvl_gather_bytes

                def _resolve():
                    faults.fire("engine.backward", level=k)
                    return self._resolve_blocked(
                        jnp.asarray(padded), wcaps,
                        tuple(jnp.asarray(a) for a in window_flat),
                    )

                values_dev, rem_dev, misses = self._retry(
                    "engine.backward", _resolve, level=k
                )
                if self.paranoid and int(misses) > 0:
                    raise SolverError(
                        f"level {k}: {int(misses)} consistency failures (child "
                        "lookups outside the solved window — level_of/"
                        "max_level_jump inconsistent — or non-primitive "
                        "positions with zero legal moves)"
                    )
                with trace_span("download", level=k) as dl:
                    table = LevelTable(states=states,
                                       values=np.asarray(values_dev[:n]),
                                       remoteness=np.asarray(rem_dev[:n]))
                    dl.set(bytes=_table_bytes(table))
            if self.store_tables or k == root_level:
                resolved[k] = table
            if self.level_sink is not None:
                with trace_span("db_export", level=k, n=int(n)):
                    self.level_sink(k, table)
            cap = padded.shape[0]
            pv = np.full(cap, UNDECIDED, dtype=np.uint8)
            pr = np.zeros(cap, dtype=np.int32)
            pv[:n] = table.values
            pr[:n] = table.remoteness
            padded_cache[k] = (padded, pv, pr)
            # Levels deeper than the lookback window can never be read again.
            for done in [d for d in padded_cache if d > k + g.max_level_jump]:
                del padded_cache[done]
            item = np.dtype(g.state_dtype).itemsize
            # Deliberately NOT _level_host_bytes: generic-path pools are
            # host-resident (the padded frontier uploads every level)
            # and states never re-download — only the packed
            # values/remoteness (5 B/row) come back.
            lvl_host_bytes = (
                padded.shape[0] * item
                + (0 if from_checkpoint else int(n) * 5)
            )
            self.bytes_host += lvl_host_bytes
            sp.end(
                n=n,
                resumed=from_checkpoint,
                bytes_sorted=lvl_sort_bytes,
                bytes_gathered=lvl_gather_bytes,
                bytes_hbm=lvl_sort_bytes + lvl_gather_bytes,
                bytes_host=lvl_host_bytes,
            )
            self.status_tracker.backward_level(k, int(n), sp.secs,
                                               resumed=from_checkpoint)
            flightrec.boundary("backward", k)
            if self.checkpointer is not None and not from_checkpoint:
                with trace_span("checkpoint", level=k, kind="level"):
                    self.checkpointer.save_level(k, table)
        return resolved

    # ------------------------------------------------------------------ solve

    def solve(self) -> SolveResult:
        """Public entry: the solve body under an optional heartbeat.

        The heartbeat thread (obs/heartbeat.py) reads `self.progress` —
        replaced atomically at each phase/level boundary — and emits
        periodic JSONL records + registry gauges, so a wedged multi-hour
        solve reports its last known level, RSS, and device memory. The
        watchdog (resilience/supervisor.py, GAMESMAN_WATCHDOG_SECS)
        reads the same progress and turns a stall past its adaptive
        deadline into a diagnosed abort with the checkpoint prefix
        intact."""
        hb = None
        if self.heartbeat_secs > 0:
            hb = Heartbeat(
                self.heartbeat_secs,
                progress=lambda: self.progress,
                logger=self.logger,
            ).start()
        wd = maybe_watchdog(lambda: self.progress, logger=self.logger)
        # Live status endpoint (GAMESMAN_STATUS_PORT / --status-port):
        # read-only /status + /metrics served for the solve's lifetime.
        self.status_tracker.begin(
            game=self.game.name, engine="classic", world=1, rank=0,
        )
        status_srv = maybe_status_server(self._status_payload)
        prev_sink = set_dispatch_sink(self._on_dispatch)
        try:
            return self._solve_impl()
        finally:
            set_dispatch_sink(prev_sink)
            if hb is not None:
                hb.stop()
            if wd is not None:
                wd.stop()
            if status_srv is not None:
                status_srv.stop()

    def _status_payload(self) -> dict:
        """The /status body (runs on HTTP handler threads: reads only
        atomically-replaced state — the `progress` contract)."""
        snap = self.status_tracker.snapshot(progress=self.progress)
        snap["retries"] = self.retries
        snap["dispatches_total"] = self.dispatch_total
        return snap

    def _solve_impl(self) -> SolveResult:
        g = self.game
        t0 = time.perf_counter()
        # Platform-auto knob, resolved here (not in __init__) so a
        # force_platform between construction and solve() is honored.
        self.use_provenance = platform_auto_bool(
            "GAMESMAN_PROVENANCE", accel=True, cpu=False
        )
        if self.checkpointer is not None:
            self.checkpointer.bind_game(g.name)
        saved = (
            self.checkpointer.load_frontiers()
            if self.checkpointer is not None
            else None
        )
        # A previous run's interrupted forward left per-level frontier
        # files: continue expansion from its deepest level.
        partial = (
            self.checkpointer.load_forward_levels()
            if self.fast and saved is None and self.checkpointer is not None
            else {}
        )
        if self.fast and saved is None:
            if not partial:
                # Fully-resumed runs skip forward discovery entirely — the
                # ladder's speculative forward compiles would be dead
                # weight; mid-forward resumes (below) seed the plan at the
                # resumed capacity instead of the root's min_bucket.
                self._schedule_initial_ladder()
            else:
                cap = bucket_size(partial[max(partial)].shape[0],
                                  self.min_bucket)
                for c in (cap, cap * 2, cap * 4):
                    self._sched_fwd_step(c)
                    self._sched_bwd_step(c)
        init, start_level = canonical_scalar(g, g.initial_state())
        if self.fast:
            if saved is not None:
                levels = {
                    k: _Level(v.shape[0], np.asarray(v, dtype=g.state_dtype),
                              None)
                    for k, v in saved.items()
                }
            else:
                levels = self._forward_fast(init, start_level,
                                            resume=partial or None)
                if self.checkpointer is not None:
                    self.checkpointer.mark_frontiers_complete()
            t_forward = time.perf_counter() - t0
            num_positions = sum(rec.n for rec in levels.values())
            # Forward fixed the per-level position counts: publish the
            # level schedule so /status's ETA model knows the remaining
            # backward work exactly (obs/status.py).
            self.status_tracker.set_schedule(
                {k: rec.n for k, rec in levels.items()}
            )
            resolved = self._backward_fast(levels, start_level)
        else:
            if saved is not None:
                pools = {
                    k: np.asarray(v, dtype=g.state_dtype)
                    for k, v in saved.items()
                }
            else:
                pools = {start_level: np.array([init], g.state_dtype)}
                self._forward_generic(pools, start_level)
                if self.checkpointer is not None:
                    self.checkpointer.save_frontiers(pools)
            t_forward = time.perf_counter() - t0
            num_positions = sum(int(a.shape[0]) for a in pools.values())
            self.status_tracker.set_schedule(
                {k: int(a.shape[0]) for k, a in pools.items()}
            )
            resolved = self._backward_generic(pools, start_level)

        t_total = time.perf_counter() - t0
        root = resolved[start_level]
        i = int(np.searchsorted(root.states, init))
        if i >= root.states.shape[0] or root.states[i] != init:
            # A canonicalization/level_of bug would otherwise silently read
            # a neighboring entry (VERDICT.md r2 weak #6: make it loud).
            raise SolverError(
                f"root state {int(init):#x} missing from its solved level "
                f"{start_level} — canonicalize/level_of inconsistent"
            )
        value = int(root.values[i])
        remoteness = int(root.remoteness[i])
        gather_miss = sum(int(m) for m in jax.device_get(self._gather_miss))
        stats = {
            "game": g.name,
            "engine": "classic",
            "positions": num_positions,
            "levels": len(resolved),
            "secs_forward": t_forward,
            "secs_backward": t_total - t_forward,
            "secs_total": t_total,
            "positions_per_sec": num_positions / max(t_total, 1e-9),
            # Transient level-step failures absorbed by retry (0 on a
            # clean run; the per-point breakdown is in the registry's
            # gamesman_retries_total).
            "retries": self.retries,
            # Roofline denominators (SURVEY.md §5.5): analytic operand
            # bytes of the sort/gather kernels; see docs/ARCHITECTURE.md
            # "Efficiency accounting" for how to read them.
            "bytes_sorted": self.bytes_sorted,
            "bytes_gathered": self.bytes_gathered,
            # The provenance backward's sorted-order fetch: its lanes, and
            # those its window kernel's views missed (re-fetched exactly).
            "gather_lanes": self.gather_lanes,
            "gather_miss_lanes": gather_miss,
            # Dispatch economy: total device computations/transfers this
            # solve issued, and per discovered level.
            "dispatches_total": self.dispatch_total,
            "dispatches_per_level": round(
                self.dispatch_total
                / max(len(levels) if self.fast else len(pools), 1), 2),
            # ISSUE 15 roofline accounting: the per-solve rollup of the
            # per-level bytes_hbm/bytes_host/dispatches/wall span fields.
            "bytes_host": self.bytes_host,
            "roofline": roofline_stats(
                self.bytes_sorted + self.bytes_gathered,
                num_positions, t_total, chips=1,
            ),
        }
        self.progress = {"phase": "done"}
        if self.logger is not None:
            self.logger.log({"phase": "done", **stats})
        # Solve-level registry rollups: the counters a /metrics scrape (or
        # --metrics-out dump) aggregates across every solve this process
        # ran — the per-level breakdown lives in gamesman_span_seconds.
        reg = default_registry()
        reg.counter(
            "gamesman_solves_total", "completed solves", game=g.name
        ).inc()
        reg.counter(
            "gamesman_solve_positions_total",
            "reachable positions solved", game=g.name,
        ).inc(num_positions)
        reg.histogram(
            "gamesman_solve_seconds", "wall seconds per full solve",
            game=g.name,
        ).observe(t_total)
        return SolveResult(g, value, remoteness, resolved, stats)


def pad_to_cap_u8(a, cap: int) -> np.ndarray:
    out = np.full(cap, UNDECIDED, dtype=np.uint8)
    out[: len(a)] = a
    return out


def pad_to_cap_i32(a, cap: int) -> np.ndarray:
    out = np.zeros(cap, dtype=np.int32)
    out[: len(a)] = a
    return out


def solve(game: TensorGame, **kwargs) -> SolveResult:
    """Convenience: Solver(game, **kwargs).solve()."""
    return Solver(game, **kwargs).solve()
