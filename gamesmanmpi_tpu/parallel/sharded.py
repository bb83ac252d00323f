"""The sharded level-synchronous solver (multi-device).

This is the TPU rebuild of the reference's distributed runtime proper
(src/process.py's cross-rank behavior, SURVEY.md §3.2-3.3 and §5.8):

  reference (per message/position)      here (per level, per shard)
  ------------------------------------  --------------------------------------
  comm.send(Job(LOOK_UP, child),        forward: expand locally, bucket all
     dest=hash(child) % world_size)     children by owner_shard(child), one
                                        lax.all_to_all over the ICI mesh,
                                        then sort-unique locally (dedup is
                                        local after owner routing)
  per-rank memo dict {pos: value}       per-shard sorted (states, cells)
                                        arrays — the hash-partitioned
                                        position table in sharded HBM
  SEND_BACK child result to parent      backward: owner-routed result
                                        reduction. Default (GAMESMAN_
                                        BACKWARD=edges, uniform-level-jump
                                        games): forward stored each child's
                                        unique-index within its owner's
                                        level slice, so the backward step is
                                        all_to_all the stored edge indices,
                                        gather packed cells on the owner,
                                        all_to_all the reply — no search, no
                                        re-expansion. Fallback (=lookup, or
                                        any level without stored edges):
                                        child-state queries all_to_all to
                                        owner shards, local sort-merge-join/
                                        binary-search lookup, packed
                                        (value,remoteness) cells back
  FINISHED broadcast                    the backward loop reaching the root

Memory scaling: every per-shard buffer — level slice, window slice, routing
buffers — is O(level/S), never O(level). The round-1 design all_gathered the
whole solved window onto every shard (O(level) per shard), which could not
reach the 6x6/6x7 targets; this owner-routed backward is the scalable shape
SURVEY.md §5.8 prescribes (VERDICT.md round 1, item 2).

Device residency: for uniform_level_jump games the frontier chains on device
shard-to-shard across levels (the next frontier IS the routed dedup output,
resized to the next capacity bucket on device); multi-jump games (children
span levels) keep per-level POOLS on device, merged by a per-target-level
sort-unique kernel (_merge_fn) as each level's routed children arrive. The
backward window is the previously-resolved level's device triples (or a
host-spilled stream, see _run_backward_step_streamed). Host work per level
is counts syncs only — no np.union1d, no per-level downloads (VERDICT r1
item 3, r2 item 5).

Capacity planning: all_to_all buffers are [num_shards, capacity] with
SENTINEL padding. Overflow (a shard sending more than capacity to one peer)
is detected via per-destination counts returned from the kernel and retried
with a doubled capacity — the "capacity counters + host-side spill loop
(rare path)" design of SURVEY.md §5.8. `spill_retries` counts the retries
(observable; tests force the path deterministically by shrinking
`_initial_route_cap`).

Like the single-device engine, compiled steps are cached process-wide
(solve/engine._KERNELS via get_kernel) keyed on game identity, mesh devices
and shapes, and capacities are power-of-two buckets — re-instantiated solvers
reuse XLA executables, and the shape count stays O(log max-frontier).

Shard-count invariance (same tables for 1 and N shards) is the test contract
replacing the reference's `mpirun -np 1` vs `-np N` (SURVEY.md §4.2).
"""

from __future__ import annotations

import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from gamesmanmpi_tpu.core.codec import (
    pack_cells,
    pack_cells_np,
    unpack_cells,
    unpack_cells_np,
)
from gamesmanmpi_tpu.core.hashing import owner_shard, owner_shard_np
from gamesmanmpi_tpu.core.values import UNDECIDED
from gamesmanmpi_tpu.games.base import TensorGame
from gamesmanmpi_tpu.ops.combine import combine_children
from gamesmanmpi_tpu.ops.dedup import (
    compact_method,
    compaction_sort_bytes,
    sort_unique,
)
from gamesmanmpi_tpu.ops.lookup import (
    lookup_sorted,
    lookup_window,
    search_method,
)
from gamesmanmpi_tpu.ops.padding import bucket_size
from gamesmanmpi_tpu.ops.provenance import (
    combine_edge_cells,
    dedup_provenance,
    provenance_sort_bytes,
)
from gamesmanmpi_tpu.obs import (
    SolveStatusTracker,
    Span,
    default_registry,
    maybe_status_server,
    trace_span,
)
from gamesmanmpi_tpu.obs import flightrec
from gamesmanmpi_tpu.obs import status as obs_status
from gamesmanmpi_tpu.parallel.mesh import AXIS, make_mesh
from gamesmanmpi_tpu.resilience import faults
from gamesmanmpi_tpu.resilience import preempt
from gamesmanmpi_tpu.resilience.coordination import (
    ABORT,
    OK,
    RETRY,
    CoordinatedAbort,
    CoordinationError,
    coordination_from_env,
)
from gamesmanmpi_tpu.resilience import memguard
from gamesmanmpi_tpu.resilience.retry import is_transient, retry_call
from gamesmanmpi_tpu.resilience.supervisor import maybe_watchdog
from gamesmanmpi_tpu.store import WriteTicket, default_store
from gamesmanmpi_tpu.utils.checkpoint import (
    TORN_NPZ_ERRORS,
    CheckpointGeometryError,
    reshard_enabled,
    reshard_shard_stream,
)
from gamesmanmpi_tpu.utils.env import (
    env_float as _env_float,
    env_opt,
    env_str,
)
from gamesmanmpi_tpu.solve.engine import (
    LevelTable,
    SolveResult,
    SolverError,
    _backward_block,
    _device_store_bytes,
    _env_int,
    canonical_children,
    canonical_scalar,
    get_kernel,
    roofline_stats,
    set_dispatch_sink,
    tally_dispatch,
)


def _window_block() -> int:
    """Max per-shard window-level capacity kept resident in HBM.

    Window levels wider than this are spilled to host after resolving and
    STREAMED back through HBM in blocks during lookup (see
    _run_backward_step_streamed) — per-shard peak window memory becomes
    O(block), not O(level/S). This is the capacity mechanism the 7x6 row of
    docs/ARCHITECTURE.md's plan needs: at that scale one window level is
    ~244 GB/chip on a v4-32, far beyond HBM. Power-of-two positions per
    shard, env GAMESMAN_WINDOW_BLOCK.
    """
    n = _env_int("GAMESMAN_WINDOW_BLOCK", 1 << 22)
    if n <= 0:
        return 1 << 62  # 0 = never spill (mirrors GAMESMAN_BACKWARD_BLOCK)
    return max(256, 1 << (n - 1).bit_length())


def _pad_shards(shard_arrays: List[np.ndarray], cap: int) -> np.ndarray:
    """Stack per-shard 1-D state arrays into [S, cap] with SENTINEL pad.

    The dtype (and sentinel) follows the input arrays' dtype.
    """
    from gamesmanmpi_tpu.core.bitops import sentinel_for

    S = len(shard_arrays)
    dtype = shard_arrays[0].dtype
    out = np.full((S, cap), sentinel_for(dtype), dtype=dtype)
    for s, arr in enumerate(shard_arrays):
        out[s, : arr.shape[0]] = arr
    return out


def _fetch_global(arr) -> np.ndarray:
    """np.asarray that works across processes.

    A P(AXIS)-sharded array under multi-process execution spans
    non-addressable devices, which plain np.asarray refuses; the gather
    collective (multihost_utils.process_allgather) fetches the
    fully-replicated value instead — every rank ends up holding the
    global copy, which is exactly what the callers (level
    materialization, whole-level host spill) need to stay byte-identical
    with the single-process engine.
    """
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr))
    return np.asarray(arr)


def _host_addressable(*arrays) -> bool:
    """Whether this process alone can copy every array to the host: each
    is on this process's devices, so no copy is a gather collective that
    must keep its place in the collective order on the solve thread."""
    return all(a.is_fully_addressable for a in arrays
               if isinstance(a, jax.Array))


def _merge_level(shards: List[np.ndarray], values: np.ndarray,
                 remoteness: np.ndarray, counts: np.ndarray) -> LevelTable:
    """One level's global table from its per-shard sorted slices and the
    ``[S, cap]`` value/remoteness rows: the real rows concatenated and put
    in state order. States are unique within a level, so the stable sort
    (a merge of the sorted runs) gives the one order there is."""
    gs, gv, gr = [], [], []
    for s, n in enumerate(counts):
        n = int(n)
        gs.append(shards[s])
        gv.append(values[s, :n])
        gr.append(remoteness[s, :n])
    states = np.concatenate(gs)
    order = np.argsort(states, kind="stable")
    return LevelTable(
        states=states[order],
        values=np.concatenate(gv)[order],
        remoteness=np.concatenate(gr)[order],
    )


class _TableAssembler:
    """The sharded backward's level tables, assembled on one background
    thread so the solve thread goes straight on to the next level's
    dispatch instead of idling the mesh through each copy and merge.

    A job holds its own references to the level's arrays (the solve thread
    drops ``rec.dev``/``rec.host`` right after submitting) and never reads
    the level record. Jobs run in submission order; a job's error is
    re-raised on the solve thread by ``poll``/``join``; ``close`` cancels
    what has not started and waits out the one running, on every path.
    """

    def __init__(self, logger=None):
        self.logger = logger
        self._pool = None
        self._pending: Dict[int, Future] = {}
        self._tables: Dict[int, LevelTable] = {}
        self._ahead = None  # the last submitted level's values on device
        #: levels handed to the worker
        self.levels = 0
        #: seconds the solve thread spent blocked in join
        self.wait_s = 0.0

    def submit(self, k: int, counts: np.ndarray, host, dev, values_dev,
               rem_dev, nbytes: int) -> None:
        """Queue level k: states from ``host`` (per-shard slices) or else
        from ``dev`` (``[S, cap]``); the device-to-host copies start now.
        Returns once the previously submitted level's step has run."""
        for a in (dev, values_dev, rem_dev):
            if a is not None:
                a.copy_to_host_async()
        # The synchronous copy this replaces also held the solve thread
        # within a level of the device. The edge-cached steps have no other
        # sync, so without this wait the loop would queue every remaining
        # level, each holding its buffers and re-uploaded edges on the
        # device at once. Level k is queued behind the one awaited here,
        # so the mesh stays busy while the solve thread waits.
        if self._ahead is not None:
            jax.block_until_ready(self._ahead)
        self._ahead = values_dev
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="gamesman-assemble"
            )
        self._pending[k] = self._pool.submit(
            self._assemble, k, counts, host, dev, values_dev, rem_dev,
            nbytes,
        )
        self.levels += 1

    def _assemble(self, k, counts, host, dev, values_dev, rem_dev, nbytes):
        with trace_span("assemble", logger=self.logger, level=int(k),
                        kind="table", bytes=int(nbytes),
                        n=int(counts.sum())):
            if host is None:
                stacked = np.asarray(dev)
                host = [stacked[s, :int(n)] for s, n in enumerate(counts)]
            return _merge_level(host, np.asarray(values_dev),
                                np.asarray(rem_dev), counts)

    def poll(self) -> None:
        """Collect the finished jobs; a failed one raises here."""
        for k, fut in list(self._pending.items()):
            if fut.done():
                del self._pending[k]
                self._tables[k] = fut.result()

    def join(self, on_wait=None) -> Dict[int, LevelTable]:
        """Every submitted level's table, waiting for the worker;
        ``on_wait(k)`` runs before each wait (watchdog progress)."""
        if not self._pending:
            return self._tables
        t0 = time.perf_counter()
        try:
            for k in list(self._pending):
                if on_wait is not None:
                    on_wait(k)
                self._tables[k] = self._pending.pop(k).result()
        finally:
            self.wait_s += time.perf_counter() - t0
        return self._tables

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None


def _all_to_all(x):
    """The owner-routed exchange: a tiled all_to_all over the mesh axis,
    under the ``route`` scope (docs/OBSERVABILITY.md "Device scopes")."""
    with jax.named_scope("route"):
        return jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0,
                                  tiled=True)


@jax.named_scope("route")
def _gather_counts(count, counts):
    """The forward step's control plane, replicated on every shard: [S]
    unique counts and [S, S] per-(source, destination) send counts."""
    return jax.lax.all_gather(count, AXIS), jax.lax.all_gather(counts, AXIS)


@jax.named_scope("route")
def _route_by_owner(flat, S: int, cap_out: int, sentinel):
    """Bucket a flat state array by owner shard for all_to_all routing.

    The device half of the reference's `dest=hash(pos) % world_size` send
    (SURVEY.md §3.2): stable-sort by destination, position each element in
    its destination bucket, scatter into a [S, cap_out] send buffer.

    Returns (send [S, cap_out] sentinel-padded, counts [S] int32 true
    per-destination sizes for overflow detection, s_owner, pos, order) —
    the last three let the caller route replies back to the original layout.
    """
    owner = jnp.where(flat == sentinel, S, owner_shard(flat, S)).astype(
        jnp.int32
    )
    order = jnp.argsort(owner, stable=True)
    s_owner = owner[order]
    s_elems = flat[order]
    first = jnp.searchsorted(s_owner, jnp.arange(S + 1))
    pos = jnp.arange(s_owner.shape[0]) - first[jnp.clip(s_owner, 0, S)]
    counts = (first[1:] - first[:-1]).astype(jnp.int32)
    send = jnp.full((S, cap_out), sentinel, dtype=flat.dtype)
    # Out-of-range rows (owner==S) and overflow (pos>=cap_out) drop.
    send = send.at[s_owner, pos].set(s_elems, mode="drop")
    return send, counts, s_owner, pos, order


def _sharded_forward_step(game: TensorGame, S: int, route_cap: int, local,
                          compact: str | None = None,
                          provenance: bool = False):
    """Per-shard forward body: expand -> owner-bucket -> all_to_all -> dedup.

    local: [1, cap] this shard's frontier slice (shard_map gives the leading
    mesh axis). Returns ([1, S*route_cap] unique children, then REPLICATED
    control-plane outputs: [S] per-shard unique counts and [S, S] per-
    (src,dst) send counts for overflow detection). Control outputs are
    all_gathered on device so the host can read them under multi-host
    execution too, where a P(AXIS)-sharded array is not fully addressable.

    provenance=True additionally threads the owner's dedup-sort provenance
    back to the parent shard (the sharded half of the edge-cached backward,
    ops/provenance): the dedup runs as dedup_provenance, each routed slot's
    unique-index-within-owner travels back through a second all_to_all, and
    the routing bookkeeping is folded into one [cap*M] `slot` map — slot[j]
    is the linear index into the [S, route_cap] reply buffer where child
    slot j's answer will sit during backward (-1 = no child). Extra outputs
    (before the control plane): eidx [1, S*route_cap] int32, slot
    [1, cap*M] int32.
    """
    sentinel = game.sentinel
    local = local[0]
    with jax.named_scope("expand"):
        valid = local != sentinel
        prim = game.primitive(local)
        active = valid & (prim == UNDECIDED)
        children, _ = canonical_children(game, local, active)
        flat = children.reshape(-1)
    send, counts, s_owner, pos, order = _route_by_owner(
        flat, S, route_cap, sentinel
    )
    routed = _all_to_all(send)
    if not provenance:
        uniq, count = sort_unique(routed.reshape(-1), compact)
        all_counts, all_sends = _gather_counts(count, counts)
        return uniq[None], all_counts, all_sends
    uniq, count, uidx = dedup_provenance(routed.reshape(-1), compact)
    # Route each child's unique-index-within-owner back to its parent:
    # uidx is in routed layout (row i = slots received from source i), so
    # the return all_to_all lands row o of the parent's eidx with the uids
    # of the children it sent to owner o, in routing order.
    eidx = _all_to_all(uidx.reshape(S, route_cap))
    # slot[j]: where child slot j's reply lives in eidx.reshape(-1). Out-of-
    # range rows (sentinel children, owner==S) and overflow (pos >=
    # route_cap — the host retries it at a larger capacity) map to -1.
    with jax.named_scope("route"):
        in_range = (s_owner < S) & (pos < route_cap)
        lin = jnp.where(in_range, s_owner * route_cap + pos,
                        -1).astype(jnp.int32)
        slot = jnp.full((flat.shape[0],), -1, jnp.int32).at[order].set(lin)
    all_counts, all_sends = _gather_counts(count, counts)
    return uniq[None], eidx.reshape(-1)[None], slot[None], all_counts, \
        all_sends


def _route_core(game: TensorGame, S: int, qcap: int, local):
    """Shared backward prologue: expand + owner-route the child queries.

    local: [cap] (already unwrapped). Returns (queries [S, qcap], qcounts
    [S], s_owner, pos, order) — the bookkeeping un-permutes replies back to
    the [B, M] child layout in _reply_core. Used by both the fused backward
    step and the streamed route phase so the two can never drift.
    """
    sentinel = game.sentinel
    with jax.named_scope("expand"):
        prim = game.primitive(local)
        undecided = (local != sentinel) & (prim == UNDECIDED)
        children, _ = canonical_children(game, local, undecided)
        flat = children.reshape(-1)
    send, qcounts, s_owner, pos, order = _route_by_owner(
        flat, S, qcap, sentinel
    )
    queries = _all_to_all(send)
    return queries, qcounts, s_owner, pos, order


def _reply_core(game: TensorGame, S: int, qcap: int, local, reply, s_owner,
                pos, order):
    """Shared backward epilogue: un-permute reply cells + negamax combine.

    reply: [S, qcap] packed cells AFTER the return all_to_all (a hit always
    carries a decided value — WIN/LOSE/TIE != UNDECIDED=0 — so the
    UNDECIDED cell doubles as the miss flag). Children are re-expanded for
    the mask (cheap elementwise). Returns (values [cap], remoteness [cap],
    misses scalar, NOT yet psum'd).
    """
    sentinel = game.sentinel
    with jax.named_scope("expand"):
        valid = local != sentinel
        prim = game.primitive(local)
        undecided = valid & (prim == UNDECIDED)
        children, mask = canonical_children(game, local, undecided)
    B, M = children.shape
    with jax.named_scope("gather"):
        if qcap == 0:
            child_vals = jnp.full((B, M), UNDECIDED, dtype=jnp.uint8)
            child_rem = jnp.zeros((B, M), dtype=jnp.int32)
            hit = jnp.zeros((B, M), dtype=bool)
        else:
            in_range = (s_owner < S) & (pos < qcap)
            got = reply[jnp.clip(s_owner, 0, S - 1),
                        jnp.clip(pos, 0, qcap - 1)]
            got = jnp.where(in_range, got, 0)
            flat_reply = (
                jnp.zeros((B * M,), dtype=reply.dtype).at[order].set(got)
            )
            child_vals, child_rem = unpack_cells(flat_reply.reshape(B, M))
            hit = child_vals != UNDECIDED
    with jax.named_scope("combine"):
        values, remoteness = combine_children(child_vals, child_rem, mask)
        values = jnp.where(undecided, values,
                           jnp.where(valid, prim, UNDECIDED))
        remoteness = jnp.where(undecided, remoteness, 0)
        # Consistency counters (SURVEY.md §5.2): missed child lookups
        # (including routing overflow, which the host retries) + zero-move
        # UNDECIDED positions (see engine.resolve_level).
        misses = jnp.sum(mask & ~hit) + jnp.sum(
            undecided & ~jnp.any(mask, axis=-1)
        )
    return values, remoteness, misses


def _sharded_backward_step(game: TensorGame, S: int, qcap: int, local,
                           window_flat, method: str | None = None):
    """Per-shard backward body: owner-routed child-value reduction.

    The SEND_BACK/RESOLVE analog (SURVEY.md §3.3, §5.8): child queries are
    all_to_all'd to their owner shards, answered by local binary search in
    the owner's sorted window slices, and the (value, remoteness) replies —
    packed into one uint32 cell each (core/codec) — are all_to_all'd back
    and un-permuted to the [B, M] child layout for the negamax combine.

    local: [1, cap] this shard's level slice. window_flat: flat sequence of
    (states, values, remoteness) triples, one per window level, each the
    LOCAL [1, capL] shard slice (NOT gathered — per-shard memory is
    O(level/S)). qcap == 0 means no window (deepest level; no queries).

    Returns ([1, cap] values, [1, cap] remoteness, [1] misses,
    [1, S] per-destination query counts for overflow detection).
    """
    local = local[0]
    if qcap == 0:
        reply = s_owner = pos = order = None
        qcounts = jnp.zeros((S,), dtype=jnp.int32)
    else:
        window = tuple(
            (window_flat[i][0], window_flat[i + 1][0], window_flat[i + 2][0])
            for i in range(0, len(window_flat), 3)
        )
        queries, qcounts, s_owner, pos, order = _route_core(
            game, S, qcap, local
        )
        vals, rems, _ = lookup_window(queries.reshape(-1), window, method)
        with jax.named_scope("lookup"):
            reply = pack_cells(vals, rems).reshape(S, qcap)
        reply = _all_to_all(reply)
    values, remoteness, misses = _reply_core(
        game, S, qcap, local, reply, s_owner, pos, order
    )
    # Control plane replicated for multi-host readability (see forward step).
    with jax.named_scope("route"):
        total_misses = jax.lax.psum(misses, AXIS)
        all_qcounts = jax.lax.all_gather(qcounts, AXIS)  # [S, S] replicated
    return values[None], remoteness[None], total_misses, all_qcounts


def _sharded_route_step(game: TensorGame, S: int, qcap: int, local):
    """Streamed backward, phase 1: expand + owner-route the child queries.

    Splits _sharded_backward_step at the window boundary (same _route_core)
    so the window can be streamed through HBM between phases instead of
    being resident. The routing bookkeeping (s_owner, pos, order — how to
    un-permute replies back to the [B, M] child layout) leaves the kernel
    as P(AXIS) outputs and is fed back to _sharded_reply_step unchanged.
    """
    local = local[0]
    queries, qcounts, s_owner, pos, order = _route_core(game, S, qcap, local)
    with jax.named_scope("route"):
        all_qcounts = jax.lax.all_gather(qcounts, AXIS)  # [S, S] replicated
        # The accumulator is born on device here (one extra output) —
        # creating it outside would cost a dedicated zeros kernel compile
        # per shape.
        acc = jnp.zeros(queries.shape, dtype=jnp.uint32)
    return (
        queries[None],
        acc[None],
        s_owner.astype(jnp.int32)[None],
        pos.astype(jnp.int32)[None],
        order.astype(jnp.int32)[None],
        all_qcounts,
    )


def _sharded_lookup_acc_step(queries, acc, wstates, wvals, wrem,
                             method: str | None = None):
    """Streamed backward, phase 2 (once per window block): local lookup.

    Looks this shard's routed queries up in ONE block of its window slice
    and accumulates hits into the packed-cell buffer. Blocks partition a
    sorted level slice, so each query hits in at most one block across the
    whole stream; a hit cell is nonzero (decided value), so accumulate is a
    select. No collectives — pure local compute.
    """
    q = queries[0].reshape(-1)
    v, r, h = lookup_sorted(q, wstates[0], wvals[0], wrem[0], method)
    with jax.named_scope("lookup"):
        cell = pack_cells(v, r)
        out = jnp.where(h, cell, acc[0].reshape(-1))
    return out.reshape(acc[0].shape)[None]


def _sharded_reply_step(game: TensorGame, S: int, qcap: int, local, acc,
                        s_owner, pos, order):
    """Streamed backward, phase 3: reply all_to_all + negamax combine.

    The tail of _sharded_backward_step (same _reply_core): accumulated
    cells travel back to the querying shards, are un-permuted into the
    [B, M] child layout, and combined.
    """
    local = local[0]
    reply = _all_to_all(acc[0])
    values, remoteness, misses = _reply_core(
        game, S, qcap, local, reply, s_owner[0], pos[0], order[0]
    )
    with jax.named_scope("route"):
        total_misses = jax.lax.psum(misses, AXIS)
    return values[None], remoteness[None], total_misses


def _sharded_edges_route_step(S: int, ecap: int, eidx):
    """Edge-cached backward, phase 1: all_to_all the stored edge indices.

    eidx: [1, S*ecap] this shard's stored edge map (row o = the unique-
    indices, within owner o's deeper-level slice, of the children this
    shard routed to o during forward). After the collective each OWNER
    holds the index requests addressed to it. Also births the packed-cell
    accumulator (one extra output — same rationale as _sharded_route_step).
    """
    e = eidx[0].reshape(S, ecap)
    q = _all_to_all(e)
    with jax.named_scope("route"):
        acc = jnp.zeros((S * ecap,), dtype=jnp.uint32)
    return q.reshape(-1)[None], acc[None]


@jax.named_scope("gather")
def _sharded_edges_gather_step(q, acc, wvals, wrem, off):
    """Edge-cached backward, phase 2 (once per window block): owner gather.

    Accumulates packed (value, remoteness) cells for the edge requests
    whose index lands in this block [off, off+W) of the owner's deeper-
    level slice. Indices were derived from the very dedup sort that built
    that slice, so every real edge hits in exactly one block; a real cell
    is nonzero (decided value), so accumulation is a select. Pure local
    compute — no collectives, no search.
    """
    qq = q[0]
    W = wvals[0].shape[0]
    rel = qq - off[0]
    hit = (qq >= 0) & (rel >= 0) & (rel < W)
    cells = pack_cells(wvals[0], wrem[0])
    got = cells[jnp.clip(rel, 0, W - 1)]
    return jnp.where(hit, got, acc[0])[None]


def _sharded_edges_reply_step(game: TensorGame, S: int, ecap: int, local,
                              acc, slot):
    """Edge-cached backward, phase 3: reply all_to_all + negamax combine.

    The accumulated cells travel back to the querying shards; the stored
    `slot` map places each child's cell directly into the [B, M] child
    layout — no un-permute sort, no re-expansion (primitive() is the only
    per-state work). Misses are structurally impossible for real edges;
    the consistency counter tracks only zero-move non-primitive rows.
    """
    local = local[0]
    reply = _all_to_all(acc[0].reshape(S, ecap)).reshape(-1)
    sl = slot[0]
    with jax.named_scope("gather"):
        got = jnp.where(
            sl >= 0, reply[jnp.clip(sl, 0, reply.shape[0] - 1)],
            jnp.uint32(0)
        )
    with jax.named_scope("combine"):
        cv, cr, mask = combine_edge_cells(got, game.max_moves)
        valid = local != game.sentinel
        prim = game.primitive(local)
        undecided = valid & (prim == UNDECIDED)
        mask = mask & undecided[:, None]
        values, remoteness = combine_children(cv, cr, mask)
        values = jnp.where(undecided, values,
                           jnp.where(valid, prim, UNDECIDED))
        remoteness = jnp.where(undecided, remoteness, 0)
        misses = jnp.sum(undecided & ~jnp.any(mask, axis=-1))
    with jax.named_scope("route"):
        total_misses = jax.lax.psum(misses, AXIS)
    return values[None], remoteness[None], total_misses


class _HostSpill:
    """A resolved level spilled to host, multi-host safe.

    Holds each ADDRESSABLE shard's rows as numpy (downloaded via
    `addressable_shards`, so each process touches only its own devices —
    a plain np.asarray on a P(AXIS)-sharded array raises under multi-host
    execution) and re-uploads column blocks as global arrays via
    jax.make_array_from_single_device_arrays.
    """

    def __init__(self, global_shape, sharding, shards):
        self.global_shape = global_shape  # (S, cap)
        self.sharding = sharding
        #: list of (device, index-tuple, np rows [1, cap]) per local shard
        self.shards = shards

    @classmethod
    def download(cls, arr) -> "_HostSpill":
        shards = [
            (s.device, s.index, np.asarray(s.data))
            for s in arr.addressable_shards
        ]
        return cls(arr.shape, arr.sharding, shards)

    @property
    def cap(self) -> int:
        return self.global_shape[1]

    def block(self, off: int, width: int):
        """Upload rows [:, off:off+width] as a global [S, width] array."""
        parts = [
            jax.device_put(rows[:, off:off + width], device)
            for device, _, rows in self.shards
        ]
        return jax.make_array_from_single_device_arrays(
            (self.global_shape[0], width), self.sharding, parts
        )


class _SLevel:
    """One discovered level, sharded: per-shard counts + device/host states.

    eidx/slot/ecap are the forward pass's edge provenance (see
    _sharded_forward_step provenance=True): this level's out-edge indices
    into the NEXT level's per-owner prefixes, plus the slot map that places
    reply cells back into the [B, M] child layout. Each is a jax
    P(AXIS)-sharded array, a _HostSpill (budget-evicted), or None (no edges
    — lookup backward for this level).
    """

    __slots__ = ("counts", "dev", "host", "eidx", "slot", "ecap", "sent")

    def __init__(self, counts: np.ndarray, dev, host):
        self.counts = counts  # np [S] real (non-sentinel) per-shard counts
        self.dev = dev  # jax [S, cap] P(AXIS)-sharded, sorted slices, or None
        self.host = host  # list of per-shard sorted np arrays, or None
        self.eidx = None  # [S, S*ecap] int32 edge indices (see class doc)
        self.slot = None  # [S, cap*M] int32 reply-slot map
        self.ecap = 0  # per-(src,dst) routing capacity the edges used
        #: children this level routed in forward, one per move edge (the
        #: edge-cached backward routes the same edges back); None unknown
        self.sent = None

    def host_shards(self) -> List[np.ndarray]:
        if self.host is None:
            stacked = _fetch_global(self.dev)
            self.host = [
                stacked[s, : int(self.counts[s])]
                for s in range(stacked.shape[0])
            ]
        return self.host


class ShardedSolver:
    """Hash-partitioned solver over a 1-D device mesh."""

    def __init__(
        self,
        game: TensorGame,
        *,
        num_shards: int | None = None,
        mesh=None,
        min_bucket: int = 256,
        paranoid: bool = False,
        logger=None,
        checkpointer=None,
        force_generic: bool = False,
        store_tables: bool = True,
    ):
        self.game = game
        self.store_tables = store_tables
        self.mesh = mesh if mesh is not None else make_mesh(num_shards)
        self.S = self.mesh.devices.shape[0]
        self.min_bucket = min_bucket
        self.paranoid = paranoid
        self.logger = logger
        self.checkpointer = checkpointer
        self.fast = bool(game.uniform_level_jump) and not force_generic
        self.device_store_bytes = _device_store_bytes()
        self.backward_block = _backward_block()
        # The async block store (ISSUE 11): shared with the checkpointer
        # (one byte budget, one write-behind queue, one prefetch pool per
        # process). Wrapped/stubbed checkpointers in tests may not expose
        # a store — fall back to the process default.
        self.store = (
            getattr(checkpointer, "store", None) if checkpointer is not None
            else None
        ) or default_store()
        #: store counters at solve start — stats() reports this solve's
        #: deltas (the store is process-wide and outlives solves).
        self._store_t0 = self.store.stats()
        #: pipelined checkpoint seals (single-process write-behind): each
        #: entry is (tickets, seal_fn); the oldest flushes when the queue
        #: exceeds one level's worth, and everything flushes at phase
        #: boundaries — so level k's DEFLATE+fsync overlaps level k-1's
        #: compute while the payload-before-seal order stays absolute.
        self._pending_seals: List = []
        #: edge arrays dropped to the disk tier (sealed edge-shard files)
        #: because the host-RAM spill budget was exhausted — reloaded via
        #: the store (prefetch makes them cache hits) during backward.
        self.edges_bytes_disk = 0
        #: host-RAM bytes currently held by budget-evicted edge spills,
        #: capped by the store cache budget (the host tier).
        self._host_spill_bytes = 0
        # Route-capacity headroom (strict parse, fail-fast like the other
        # capacity knobs): see _initial_route_cap.
        raw = env_opt("GAMESMAN_ROUTE_HEADROOM")
        try:
            self.route_headroom = float(raw) if raw else 2.0
        except ValueError:
            raise SolverError(
                f"GAMESMAN_ROUTE_HEADROOM={raw!r} is not a number"
            ) from None
        import math

        if not math.isfinite(self.route_headroom) or self.route_headroom <= 0:
            # nan/inf parse as floats but would crash mid-solve inside
            # _initial_route_cap's int() — fail here, at construction.
            raise SolverError(
                f"GAMESMAN_ROUTE_HEADROOM must be a finite number > 0, "
                f"got {self.route_headroom}"
            )
        # Backward strategy (ISSUE 3): 'edges' = edge-cached provenance
        # backward (gathers + collectives, no search, no re-expansion) for
        # every level whose edges exist, falling back to the lookup join
        # per level where they don't (pre-edge checkpoints, generic-path
        # games, budget-evicted big runs resumed without edge files);
        # 'lookup' = always the owner-routed sort-merge/binary-search join.
        # Strict parse, fail-fast at construction like the other knobs.
        raw = env_str("GAMESMAN_BACKWARD", "edges")
        if raw not in ("edges", "lookup"):
            raise SolverError(
                f"GAMESMAN_BACKWARD={raw!r}: expected 'edges' or 'lookup'"
            )
        self.backward_mode = raw
        # Edge provenance rides the uniform-level-jump fast path only:
        # the generic path's per-target-level pool merges re-sort each
        # pool as later contributions arrive, which would invalidate any
        # index issued before the merge.
        self.use_edges = self.backward_mode == "edges" and self.fast
        #: levels resolved via the edge-cached backward (the observable
        #: for the A/B and fallback tests).
        self.backward_edges_levels = 0
        # Background compiles of the edge-backward shapes (same policy as
        # the single-device engine: only worth it where compiles are
        # remote ~15 s RPCs; on CPU they would just slow the suite).
        flag = env_str("GAMESMAN_PRECOMPILE", "auto")
        if flag == "auto":
            self.precompile = jax.default_backend() != "cpu"
        else:
            self.precompile = flag not in ("0", "off", "false")
        #: bytes of edge arrays evicted from device to host (big-run mode).
        self.edges_bytes_spilled = 0
        #: checkpoint/spill tier I/O accounting: raw array bytes handed to
        #: the checkpointer vs bytes that actually landed on disk (the
        #: delta is what GAMESMAN_CKPT_COMPRESS — incl. the block-framed
        #: ``blocks`` mode — saved this run; see stats()["ckpt_bytes_*"]).
        self.ckpt_bytes_raw = 0
        self.ckpt_bytes_stored = 0
        #: number of capacity-overflow retries taken (forward + backward);
        #: the observable for the spill-path tests.
        self.spill_retries = 0
        #: per-shard window capacity above which resolved levels spill to
        #: host and stream back through HBM in blocks during lookup.
        self.window_block = _window_block()
        #: number of window blocks streamed through HBM (observable for the
        #: window-streaming tests; 0 when every window stayed resident).
        self.window_stream_blocks = 0
        #: hybrid seam: materialize the backward root level's global table
        #: even in big-run mode (the boundary join reads it); plain solves
        #: leave it False and take the device-replicated root answer only.
        self.materialize_root_table = False
        #: backward level tables assembled off the solve thread, and the
        #: seconds the solve thread waited for them (done record fields).
        self.tables_async_levels = 0
        self.table_wait_s = 0.0
        # Analytic traffic counters (SURVEY.md §5.5): payload bytes of the
        # all_to_all collectives and operand bytes of the sort/gather
        # kernels — the denominators that make positions/sec readable
        # against ICI/HBM rooflines (docs/ARCHITECTURE.md "Efficiency
        # accounting").
        self.bytes_routed = 0
        #: the all_to_all buffers' bytes, capacity padding included —
        #: what the collectives moved (bytes_routed counts only the real
        #: entries in them), every overflow retry's attempt counted.
        self.bytes_routed_padded = 0
        self.bytes_sorted = 0
        self.bytes_gathered = 0
        #: transient level-step failures absorbed by retry (stats field).
        self.retries = 0
        #: dispatch accounting (see engine.note_dispatch): device
        #: computations/transfers this solve issued, with the per-(phase,
        #: level) breakdown.
        self.dispatch_total = 0
        self.level_dispatches: Dict[tuple, int] = {}
        self.dispatch_by_kind: Dict[str, int] = {}
        #: elastic resume (ISSUE 13): shard count the adopted checkpoint
        #: tree was sealed at when it differs from this run's (None = no
        #: reshard happened), and how many levels fell back from the
        #: edge-cached backward because their sealed edge shards carry a
        #: foreign geometry (edge slot maps cannot re-map — the per-level
        #: lookup join is the structural fallback).
        self.resharded_from = None
        self.edges_geometry_fallback_levels = 0
        #: this process's rank in the multi-process run (0 single-process).
        self.rank = jax.process_index()
        self.num_processes = jax.process_count()
        # Cross-rank retry/abort consensus (resilience/coordination.py):
        # built from GAMESMAN_COORD_ADDR under multi-process execution so
        # transient faults at collective fault points are retried by ALL
        # ranks together or aborted by all ranks together — a lone rank
        # re-entering a step that contains an all_to_all while its peers
        # proceed would wedge the job forever. None = rank-local retry
        # (single process, or coordination unconfigured).
        self.coord = coordination_from_env(self.rank, self.num_processes)
        #: per-collective deadline (GAMESMAN_COLLECTIVE_TIMEOUT, seconds):
        #: under multi-process execution a peer's death leaves this rank
        #: BLOCKED inside the collective — uninterruptible from Python —
        #: so the only honest recovery is the watchdog contract: dump
        #: per-rank progress and exit 124 with the checkpoint prefix
        #: intact. 0 = off.
        self.collective_timeout = _env_float(
            "GAMESMAN_COLLECTIVE_TIMEOUT", 0.0
        )
        #: phase/level progress for the watchdog (replaced atomically,
        #: never mutated — same contract as the single-device engine's).
        self.progress: dict = {"phase": "init", "rank": self.rank}
        #: live-status progress model + endpoint (obs/status.py,
        #: GAMESMAN_STATUS_PORT): rank 0 additionally serves the
        #: fleet-merged view scraped via the coordinator address book.
        self.status_tracker = SolveStatusTracker()
        self._status_server = None
        # Mesh identity participates in the process-wide kernel cache key
        # (same shard count over different device sets must not share).
        self._mesh_key = tuple(d.id for d in self.mesh.devices.flat)
        self._sharding = NamedSharding(self.mesh, P(AXIS))

    def _download(self, level: int, kind: str, nbytes: int):
        """The ``download`` span (docs/OBSERVABILITY.md) around one copy
        of device arrays to the host: a level's ``table``, a spilled
        ``window`` or ``edges``, or a ``level`` kept on the host."""
        return trace_span("download", logger=self.logger, level=int(level),
                          kind=kind, bytes=int(nbytes))

    def _check_preempt(self, phase: str, level) -> None:
        """Rank-coordinated level-boundary preemption point (ISSUE 12).

        Single-process: one flag check. Multi-process: every rank folds
        its local grace flag into an epoch round at this boundary — the
        signal lands asynchronously, so without consensus rank A could
        unwind at level k while rank B enters level k's first collective
        and wedges until the collective deadline. The round (ABORT from
        any preempted rank beats OK) makes every rank raise
        :class:`PreemptionRequested` at the SAME program point, so the
        whole world drains to exit 75 together with the deepest mutually
        sealed prefix on disk. A CoordinationError here converts to
        CoordinatedAbort via _propose_step — exit 124, still resumable.
        """
        # Host-memory guard first (ISSUE 13): past the limit this rank
        # raises HostMemoryExceeded — a clean, classifiable, resumable
        # death at the boundary instead of a kernel OOM-kill mid-level
        # (rank-local by design: peers unwind via the collective
        # deadline, and the campaign's oom policy escalates geometry).
        memguard.check(phase, level=level, logger=self.logger)
        flagged = preempt.requested()
        if self.coord is not None:
            decision = self._propose_step(
                "preempt", level, 0, phase, ABORT if flagged else OK, None
            )
            flagged = flagged or decision != OK
        if flagged:
            preempt.check(phase, level=level, logger=self.logger)
            # A peer was preempted but this rank's own flag is unset
            # (its signal is still in flight): same unwind, attributed.
            raise preempt.PreemptionRequested(
                f"peer rank preempted at {phase} boundary (level {level})"
            )

    def _on_dispatch(self, kind: str) -> None:
        """Dispatch sink (engine.set_dispatch_sink): one shared tally body
        with the single-device engine (engine.tally_dispatch) so the
        gamesman_dispatches_total series can never fork between them."""
        tally_dispatch(self, kind)

    def _retry(self, point: str, fn, reset=None, level=None, entry=None):
        """Level-step retry wrapper (see resilience.retry): the sharded
        steps' inputs — frontier, window triples, edge arrays — stay
        referenced across the step, so re-dispatch is idempotent.

        ``entry`` is the step's host-side prelude — the call site's
        literal ``faults.fire`` — evaluated BEFORE any collective
        dispatches: under multi-process execution that is the one
        program point where a rank-local failure is still safely
        retryable, because no rank has entered the collective yet. With
        a coordination handle the whole retry decision is a cross-rank
        consensus round (_retry_collective); without one (single
        process) the behavior is exactly PR 4's rank-local retry_call.
        """
        if self.coord is None:

            def unit():
                if entry is not None:
                    entry()
                return fn()

            def on_retry(attempt, exc):
                self.retries += 1

            return retry_call(unit, point=point, reset=reset, level=level,
                              logger=self.logger, on_retry=on_retry)
        return self._retry_collective(point, fn, reset, level, entry)

    def _retry_collective(self, point: str, fn, reset, level, entry=None):
        """Collective-safe retry: all ranks enter, retry, or abort a
        level step TOGETHER.

        Protocol per attempt: every rank evaluates the step's entry
        (fault points fire here, before any collective dispatches),
        proposes ok/retry/abort for the shared epoch
        ``<seq>:<point>:L<level>:a<attempt>:pre``, and acts on the
        fleet's decision — so a transient injected on ONE rank turns
        into a retry on EVERY rank (each counts it: the
        ``gamesman_retries_total`` criterion), and a fatal anywhere
        aborts everywhere. A failure DURING the dispatched step (a
        collective transport error) goes through a ``post`` round
        instead: peers that already completed the step will never join
        it, so the round resolves by deadline into a coordinated abort
        — the one correct answer once ranks have diverged — while a
        symmetric failure (all ranks raised) agrees to retry.
        Consensus-service failures (coordinator death) convert to
        CoordinatedAbort, never a hang.
        """
        attempts = max(1, _env_int("GAMESMAN_RETRY_ATTEMPTS", 3))
        base = _env_float("GAMESMAN_RETRY_BASE_SECS", 0.25)
        for attempt in range(1, attempts + 1):
            err = None
            try:
                faults.fire("sharded.collective", step=point, level=level)
                if entry is not None:
                    entry()
            except Exception as e:  # noqa: BLE001 - classified below
                err = e
            verdict = self._verdict_for(err, attempt, attempts)
            decision = self._propose_step(point, level, attempt, "pre",
                                          verdict, err)
            if decision == RETRY:
                self._note_coordinated_retry(point, level, attempt, err)
                if base > 0:
                    time.sleep(base * (2 ** (attempt - 1)))
                if reset is not None:
                    reset()
                continue
            if decision != OK:
                self._coordinated_abort(point, level, err, verdict)
            try:
                with self._collective_deadline(point, level):
                    return fn()
            except Exception as e:  # noqa: BLE001 - classified below
                if not is_transient(e) or attempt >= attempts:
                    raise
                decision = self._propose_step(point, level, attempt,
                                              "post", RETRY, e)
                if decision == RETRY:
                    self._note_coordinated_retry(point, level, attempt, e)
                    if base > 0:
                        time.sleep(base * (2 ** (attempt - 1)))
                    if reset is not None:
                        reset()
                    continue
                self._coordinated_abort(point, level, e, RETRY)
        raise SolverError(
            f"retry loop for {point} level {level} exhausted without a "
            "decision"
        )  # pragma: no cover - every branch returns, continues, or raises

    @staticmethod
    def _verdict_for(err, attempt: int, attempts: int) -> str:
        if err is None:
            return OK
        if is_transient(err) and attempt < attempts:
            return RETRY
        return ABORT

    def _propose_step(self, point: str, level, attempt: int, phase: str,
                      verdict: str, err) -> str:
        tag = f"{point}:L{level}:a{attempt}:{phase}"
        try:
            return self.coord.propose(tag, verdict)
        except CoordinationError as e:
            # The consensus service itself failed (coordinator death,
            # wire junk): abort — a guess here could strand a peer
            # inside a collective.
            raise CoordinatedAbort(
                f"coordination failed at {tag} (rank {self.rank}): {e}"
            ) from (err or e)

    def _note_coordinated_retry(self, point: str, level, attempt: int,
                                err) -> None:
        """Every rank records the fleet-wide retry decision — the
        counters must AGREE across ranks, whichever rank hosted the
        fault (rank-labelled via the registry's constant labels)."""
        self.retries += 1
        default_registry().counter(
            "gamesman_retries_total",
            "transient step failures absorbed by retry",
            point=point,
        ).inc()
        flightrec.record(
            "retry", point=point, attempt=attempt, level=level,
            coordinated=True,
            error=str(err)[:120] if err is not None else "peer",
        )
        if self.logger is not None:
            rec = {
                "phase": "retry",
                "point": point,
                "attempt": attempt,
                "rank": self.rank,
                "coordinated": True,
                "error": str(err)[:200] if err is not None else "peer",
            }
            if level is not None:
                rec["level"] = int(level)
            self.logger.log(rec)

    def _coordinated_abort(self, point: str, level, err, verdict):
        """ABORT decision: raise this rank's own error only when IT was
        the abort cause (verdict ABORT — fail fast with the real fatal).
        A rank whose local failure was retryable (or absent) aborts
        because of a PEER: that must surface as CoordinatedAbort — the
        exception the CLI maps to the exit-124 resumable-abort contract
        — not as a transient traceback that misattributes the abort to
        a fault the fleet would have retried."""
        if err is not None and verdict == ABORT:
            raise err
        detail = (f"rank {self.rank} was healthy" if err is None
                  else f"rank {self.rank} proposed retry for: "
                  f"{str(err)[:200]}")
        raise CoordinatedAbort(
            f"fleet aborted at {point} level {level} ({detail})"
        ) from err

    def _collective_deadline(self, point: str, level):
        """Deadline guard around one dispatched collective step: when a
        peer dies mid-collective this rank blocks forever inside the
        runtime, so a daemon timer dumps this rank's progress and exits
        124 — the watchdog's abort contract, checkpoint prefix intact,
        and every surviving rank does the same within the deadline
        (the 'coordinated resumable abort'). Off unless
        GAMESMAN_COLLECTIVE_TIMEOUT > 0 and the run is multi-process.
        """
        import contextlib

        secs = self.collective_timeout
        if secs <= 0 or self.num_processes <= 1:
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def guard():
            import threading

            def expire():
                from gamesmanmpi_tpu.resilience.supervisor import (
                    WATCHDOG_EXIT_CODE,
                )
                import os
                import sys

                rec = {
                    "phase": "collective_abort",
                    "point": point,
                    "level": level,
                    "rank": self.rank,
                    "deadline_secs": secs,
                    "progress": dict(self.progress),
                }
                sys.stderr.write(
                    f"[coordination] collective deadline expired: {rec}\n"
                )
                sys.stderr.flush()
                default_registry().counter(
                    "gamesman_collective_deadline_expired_total",
                    "collectives aborted by the per-collective deadline",
                    point=point,
                ).inc()
                if self.logger is not None:
                    try:
                        self.logger.log(rec)
                    except Exception:  # noqa: BLE001 - exiting anyway
                        pass
                # Post-mortem before the hard exit: this rank's ring
                # names the collective it died inside (timer thread,
                # never a signal handler — flightrec's locking is fine).
                flightrec.record("collective_deadline", point=point,
                                 level=level)
                flightrec.dump("collective_deadline")
                os._exit(WATCHDOG_EXIT_CODE)

            timer = threading.Timer(secs, expire)
            timer.daemon = True
            timer.start()
            try:
                yield
            finally:
                timer.cancel()

        return guard()

    # ------------------------------------------------------------- jit builds

    def _forward_fn(self, cap: int, route_cap: int,
                    provenance: bool = False):
        """Compiled forward step: [S, cap] states -> routed unique children.

        provenance=True is the edge-cached variant (two extra P(AXIS)
        outputs: eidx + slot, see _sharded_forward_step) — a separate
        program and cache kind, so GAMESMAN_BACKWARD=lookup never pays the
        provenance pair sorts.
        """
        mesh, S = self.mesh, self.S

        def build(game):
            cm = compact_method()  # resolved at cache-key time

            def per_shard(local):
                return _sharded_forward_step(game, S, route_cap, local, cm,
                                             provenance)

            data_specs = (P(AXIS), P(AXIS), P(AXIS)) if provenance \
                else (P(AXIS),)
            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=P(AXIS),
                out_specs=data_specs + (P(), P()),
                check_vma=False,  # all_gathered control outputs ARE replicated
            )

        return get_kernel(
            self.game, "sfwdp" if provenance else "sfwd",
            (self._mesh_key, cap, route_cap), build,
            lowering=(compact_method(),),
        )

    # Edge-backward kernel builders are factored out of their get_kernel
    # call sites so _schedule_backward_edges can queue background compiles
    # under the SAME cache keys the resolve will fetch (see get_kernel /
    # schedule_kernel in solve/engine.py).

    def _eroute_build(self, ecap: int):
        mesh, S = self.mesh, self.S

        def build(game):
            def per_shard(eidx):
                return _sharded_edges_route_step(S, ecap, eidx)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=P(AXIS),
                out_specs=(P(AXIS), P(AXIS)),
            )

        return build

    def _eroute_fn(self, ecap: int):
        """Compiled edge-backward phase 1 (see _sharded_edges_route_step)."""
        return get_kernel(
            self.game, "sert", (self._mesh_key, ecap),
            self._eroute_build(ecap),
        )

    def _egather_build(self, ecap: int, wcap: int):
        mesh = self.mesh

        def build(game):
            return shard_map(
                _sharded_edges_gather_step,
                mesh=mesh,
                in_specs=(P(AXIS),) * 4 + (P(),),
                out_specs=P(AXIS),
            )

        return build

    def _egather_fn(self, ecap: int, wcap: int):
        """Compiled edge-backward phase 2 (one window block's gather)."""
        return get_kernel(
            self.game, "serg", (self._mesh_key, ecap, wcap),
            self._egather_build(ecap, wcap),
        )

    def _ereply_build(self, cap: int, ecap: int):
        mesh, S = self.mesh, self.S

        def build(game):
            def per_shard(local, acc, slot):
                return _sharded_edges_reply_step(game, S, ecap, local, acc,
                                                 slot)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS),) * 3,
                out_specs=(P(AXIS), P(AXIS), P()),
                check_vma=False,  # psum misses ARE replicated
            )

        return build

    def _ereply_fn(self, cap: int, ecap: int):
        """Compiled edge-backward phase 3 (see _sharded_edges_reply_step)."""
        return get_kernel(
            self.game, "serp", (self._mesh_key, cap, ecap),
            self._ereply_build(cap, ecap),
        )

    def _schedule_backward_edges(self, levels, completed) -> None:
        """Queue background compiles for the edge-backward kernels.

        Every shape is known exactly the moment forward ends — (cap, ecap)
        per level plus the window capacity of the level below — and a cold
        TPU compile takes seconds per program, so deepest-first
        scheduling overlaps shallow levels' compilation with deep levels'
        execution: the same plan the single-device engine runs for its
        backward shapes (solve/precompile.py). The avals carry the mesh
        shardings the resolve will call with — AOT executables are strict
        about them (see precompile.sds).
        """
        from gamesmanmpi_tpu.solve.engine import schedule_kernel
        from gamesmanmpi_tpu.solve.precompile import sds

        S = self.S
        shard = self._sharding
        repl = NamedSharding(self.mesh, P())
        dt = self.game.state_dtype
        M = self.game.max_moves
        caps = {
            k: (rec.dev.shape[1] if rec.dev is not None
                else bucket_size(
                    int(rec.counts.max()) if rec.counts.size else 0,
                    self.min_bucket))
            for k, rec in levels.items()
        }
        for k in sorted(levels, reverse=True):
            rec = levels[k]
            if k in completed or (k + 1) not in levels:
                continue
            cap = caps[k]
            ecap = rec.ecap
            if rec.eidx is None:
                # Resume path: edges live only in the checkpoint's sealed
                # npz files (_load_edges reads them level by level during
                # the resolve) — the very scenario where overlapping the
                # ~15 s-per-program compiles matters most. The manifest
                # carries the geometry; schedule only what _load_edges
                # will actually accept (same shards/slot_len validation).
                info = (self.checkpointer.edge_level_info(k)
                        if self.checkpointer is not None else None)
                if (not info or info.get("shards") != S
                        or info.get("slot_len") != cap * M):
                    continue
                ecap = int(info["ecap"])
            # The gather runs against the resident window (cap of k+1 when
            # it fits window_block) or window_block-wide streamed slices —
            # min() covers both, matching _resolve_edges_level's shapes.
            wcap = min(caps[k + 1], self.window_block)
            schedule_kernel(
                self.game, "sert", (self._mesh_key, ecap),
                self._eroute_build(ecap),
                (sds((S, S * ecap), np.int32, shard),),
            )
            schedule_kernel(
                self.game, "serg", (self._mesh_key, ecap, wcap),
                self._egather_build(ecap, wcap),
                (
                    sds((S, S * ecap), np.int32, shard),
                    sds((S, S * ecap), np.uint32, shard),
                    sds((S, wcap), np.uint8, shard),
                    sds((S, wcap), np.int32, shard),
                    sds((1,), np.int32, repl),
                ),
            )
            schedule_kernel(
                self.game, "serp", (self._mesh_key, cap, ecap),
                self._ereply_build(cap, ecap),
                (
                    sds((S, cap), dt, shard),
                    sds((S, S * ecap), np.uint32, shard),
                    sds((S, cap * M), np.int32, shard),
                ),
            )

    def _resize_fn(self, in_cap: int, out_cap: int):
        """Per-shard slice/pad [S, in_cap] -> [S, out_cap], on device.

        Sorted-unique slices keep their real entries first, so slicing to
        the next capacity bucket (>= max per-shard count) is exact.
        """
        mesh = self.mesh

        def build(game):
            # The dedup's output trimmed (or padded) to the next level's
            # capacity bucket: the tail of the dedup.
            @jax.named_scope("dedup")
            def per_shard(local):
                x = local[0]
                if out_cap <= in_cap:
                    y = jax.lax.slice(x, (0,), (out_cap,))
                else:
                    y = jnp.concatenate(
                        [
                            x,
                            jnp.full(out_cap - in_cap, game.sentinel,
                                     dtype=x.dtype),
                        ]
                    )
                return y[None]

            return shard_map(
                per_shard, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
            )

        return get_kernel(
            self.game, "srsz", (self._mesh_key, in_cap, out_cap), build
        )

    def _backward_fn(self, cap: int, window_caps: tuple, qcap: int):
        """Compiled backward step for one level against local window slices."""
        mesh, S = self.mesh, self.S
        n_windows = len(window_caps)

        def build(game):
            sm = search_method()  # resolved at cache-key time

            def per_shard(local, *window_flat):
                return _sharded_backward_step(game, S, qcap, local,
                                              window_flat, sm)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS),) + (P(AXIS),) * (3 * n_windows),
                out_specs=(P(AXIS), P(AXIS), P(), P()),
                check_vma=False,  # psum/all_gather outputs ARE replicated
            )

        return get_kernel(
            self.game,
            "sbwd",
            (self._mesh_key, cap, tuple(window_caps), qcap),
            build,
            lowering=(search_method(),),  # lookup_window's search lowering
        )

    def _route_fn(self, cap: int, qcap: int):
        """Compiled streamed-backward phase 1 (see _sharded_route_step)."""
        mesh, S = self.mesh, self.S

        def build(game):
            def per_shard(local):
                return _sharded_route_step(game, S, qcap, local)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=P(AXIS),
                out_specs=(P(AXIS),) * 5 + (P(),),
                check_vma=False,  # all_gathered qcounts ARE replicated
            )

        return get_kernel(
            self.game, "srt", (self._mesh_key, cap, qcap), build
        )

    def _lookup_acc_fn(self, qcap: int, wcap: int):
        """Compiled streamed-backward phase 2 (one window block)."""
        mesh = self.mesh

        def build(game):
            sm = search_method()  # resolved at cache-key time

            def step(queries, acc, wstates, wvals, wrem):
                return _sharded_lookup_acc_step(queries, acc, wstates,
                                                wvals, wrem, sm)

            return shard_map(
                step,
                mesh=mesh,
                in_specs=(P(AXIS),) * 5,
                out_specs=P(AXIS),
            )

        return get_kernel(
            self.game, "sla", (self._mesh_key, qcap, wcap), build,
            lowering=(search_method(),),
        )

    def _reply_fn(self, cap: int, qcap: int):
        """Compiled streamed-backward phase 3 (see _sharded_reply_step)."""
        mesh, S = self.mesh, self.S

        def build(game):
            def per_shard(local, acc, s_owner, pos, order):
                return _sharded_reply_step(game, S, qcap, local, acc,
                                           s_owner, pos, order)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS),) * 5,
                out_specs=(P(AXIS), P(AXIS), P()),
                check_vma=False,  # psum misses ARE replicated
            )

        return get_kernel(
            self.game, "srp", (self._mesh_key, cap, qcap), build
        )

    def _root_fn(self, cap: int):
        """Replicated (value, remoteness) of one state from a device triple.

        The FINISHED analog for multi-host runs: the root's answer leaves
        the device as a psum-replicated scalar pair, never as a host
        download of a cross-process sharded array.
        """
        mesh = self.mesh

        def build(game):
            def per_shard(states, values, rem, query):
                ts, tv, tr = states[0], values[0], rem[0]
                with jax.named_scope("lookup"):
                    idx = jnp.clip(
                        jnp.searchsorted(ts, query[0]), 0, ts.shape[0] - 1
                    )
                    hit = ts[idx] == query[0]
                    v = jnp.where(hit, tv[idx].astype(jnp.int32), 0)
                    r = jnp.where(hit, tr[idx], 0)
                with jax.named_scope("route"):
                    return (
                        jax.lax.psum(v, AXIS),
                        jax.lax.psum(r, AXIS),
                    )

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
                out_specs=(P(), P()),
                check_vma=False,  # psum outputs ARE replicated
            )

        return get_kernel(
            self.game, "sroot", (self._mesh_key, cap), build
        )

    def _merge_fn(self, pool_cap: int, child_cap: int):
        """Merge routed children of one target level into its pool, on device.

        Per shard: select children whose level_of == target (a replicated
        scalar arg, so one kernel serves every level), concat with the
        existing pool slice, sort-unique. Both inputs are per-shard sorted
        owner-consistent sets, so the output is too. Replaces the old
        host-side np.union1d pool merging (VERDICT r2 item 5).
        """
        mesh = self.mesh

        def build(game):
            cm = compact_method()  # resolved at cache-key time

            @jax.named_scope("dedup")
            def per_shard(pool, kids, target):
                p, c = pool[0], kids[0]
                lv = jnp.where(
                    c != game.sentinel, game.level_of(c), -1
                )
                sel = jnp.where(lv == target[0], c, game.sentinel)
                uniq, count = sort_unique(
                    jnp.concatenate([p, sel]), cm
                )
                with jax.named_scope("route"):
                    return uniq[None], jax.lax.all_gather(count, AXIS)

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS), P(AXIS), P()),
                out_specs=(P(AXIS), P()),
                check_vma=False,  # all_gathered counts ARE replicated
            )

        return get_kernel(
            self.game, "smrg", (self._mesh_key, pool_cap, child_cap), build,
            lowering=(compact_method(),),
        )

    def _level_check_fn(self, cap: int):
        """Children-per-target-level histogram + contract check.

        Returns (bad, per_target[J]) replicated: `bad` counts children whose
        level violates (kmin, kmax] — a broken level_of/max_level_jump/
        num_levels contract, surfaced instead of silently dropping
        positions — and per_target[j] counts children at level kmin+1+j, so
        the merge loop skips target levels that received nothing.
        """
        mesh = self.mesh

        def build(game):
            J = game.max_level_jump

            def per_shard(kids, kmin, kmax):
                c = kids[0]
                with jax.named_scope("expand"):
                    valid = c != game.sentinel
                    lv = jnp.where(valid, game.level_of(c), -1)
                    bad = jnp.sum(
                        valid & ((lv <= kmin[0]) | (lv > kmax[0]))
                    )
                    per = jnp.stack(
                        [jnp.sum(lv == kmin[0] + 1 + j) for j in range(J)]
                    )
                with jax.named_scope("route"):
                    return (jax.lax.psum(bad, AXIS),
                            jax.lax.psum(per, AXIS))

            return shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(AXIS), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,  # psum outputs ARE replicated
            )

        return get_kernel(
            self.game, "schk", (self._mesh_key, cap), build
        )

    # ------------------------------------------------------ capacity planning

    def _initial_route_cap(self, cap: int) -> int:
        """First-try per-(src,dst) all_to_all capacity for a level of `cap`.

        Expected bucket load is cap*max_moves/S; the headroom factor
        (GAMESMAN_ROUTE_HEADROOM, default 2.0) absorbs owner skew.
        Overflow is detected exactly (per-destination counts) and retried
        at the exact size — tests shrink this estimate to force the spill
        path deterministically. At 1e8+ frontiers the route/sort buffers
        scale with S*S*route_cap, so on a fake mesh (all shards in ONE
        host's RAM) headroom 1.0 halves peak memory for the price of an
        occasional one-step retry: the r5 8-shard 5x6 witness was
        OOM-killed at its peak level with the 2x default (130 GB RSS on
        a 125 GB box) and fits with 1.0.
        """
        return bucket_size(
            max(64, int(self.route_headroom * cap * self.game.max_moves)
                // self.S),
            self.min_bucket,
        )

    # ----------------------------------------------------------------- phases

    def _seed(self, init) -> tuple[List[np.ndarray], np.ndarray]:
        """Owner-partition the starting state(s): one root, or a whole
        sorted frontier (the hybrid engine starts sharded BFS at its
        cutover level's reachable set)."""
        g = self.game
        arr = np.atleast_1d(np.asarray(init, dtype=g.state_dtype))
        shards = self._repartition(np.sort(arr) if arr.shape[0] > 1
                                   else arr)
        counts = np.array([a.shape[0] for a in shards], dtype=np.int64)
        return shards, counts

    def _forward_fast(self, init, start_level: int,
                      resume: Dict[int, list] | None = None,
                      ) -> Dict[int, _SLevel]:
        """Device-resident forward sweep for uniform_level_jump games.

        The frontier chains on device: each level's routed+dedup'd children
        (already per-shard sorted) are resized to the next capacity bucket
        without leaving HBM. Host work per level: one counts sync.

        With a checkpointer, every discovered level's shard rows are saved
        immediately (save_forward_level_shard; sealed per level by process
        0 post-barrier) so a death mid-discovery keeps the prefix; `resume`
        is that prefix ({level: per-shard arrays} at THIS shard count) and
        expansion continues from its deepest level. The consolidated
        end-of-forward snapshot still supersedes these files on completion
        — it alone supports shard-count-changing resumes.
        """
        g = self.game
        S = self.S
        if resume:
            ks = sorted(resume)
            if ks != list(range(ks[0], ks[-1] + 1)) or ks[0] != start_level:
                raise SolverError(
                    f"forward checkpoint levels {ks} are not contiguous "
                    f"from the root level {start_level} — stale checkpoint "
                    "directory?"
                )
            levels = {}
            for kk in ks:
                shards = [np.asarray(a, dtype=g.state_dtype)
                          for a in resume[kk]]
                levels[kk] = _SLevel(
                    np.array([a.shape[0] for a in shards], dtype=np.int64),
                    None, shards,
                )
            k = ks[-1]
            deep = levels[k]
            counts = deep.counts
            cap = bucket_size(int(counts.max()), self.min_bucket)
            frontier = jax.device_put(
                _pad_shards(deep.host, cap), self._sharding
            )
            deep.dev = frontier
        else:
            shards, counts = self._seed(init)
            cap = bucket_size(int(counts.max()), self.min_bucket)
            frontier = jax.device_put(_pad_shards(shards, cap),
                                      self._sharding)
            levels = {start_level: _SLevel(counts, frontier, shards)}
            k = start_level
            self._ckpt_forward_level(k, levels[k])
        stored_bytes = frontier.nbytes
        item = np.dtype(g.state_dtype).itemsize
        # Routed bytes per child: its state out, and with edges the uid
        # reply riding back.
        per_child = item + 4 if self.use_edges else item
        while True:
            n = int(levels[k].counts.sum())
            sp = Span("forward", logger=self.logger, level=k)
            self.progress = {
                "phase": "forward", "level": k, "rank": self.rank,
                "frontier": n,
            }
            # Level boundary: level k's incremental frontier (and edge)
            # files are already enqueued/sealed — a grace signal stops
            # HERE and resume re-expands from the deepest sealed level.
            self._check_preempt("forward", k)
            b0 = (self.bytes_routed, self.bytes_sorted,
                  self.bytes_routed_padded)
            disp0 = self.dispatch_total
            route_cap = self._initial_route_cap(cap)
            eidx = slot = None
            while True:
                # The whole dispatch+counts-sync is the retried unit: a
                # transient collective failure re-dispatches from the
                # frontier, which stays referenced across the step.
                def _step(cap=cap, route_cap=route_cap, frontier=frontier):
                    if self.use_edges:
                        u, e, sl, c, sc = self._forward_fn(
                            cap, route_cap, provenance=True
                        )(frontier)
                    else:
                        u, c, sc = self._forward_fn(cap, route_cap)(frontier)
                        e = sl = None
                    return u, e, sl, c, np.asarray(sc)

                uniq, eidx, slot, count, sends = self._retry(
                    "sharded.forward", _step, level=k,
                    entry=lambda k=k: faults.fire("sharded.forward",
                                                  level=k),
                )
                self.bytes_routed_padded += S * S * route_cap * per_child
                max_sent = int(sends.max())
                if max_sent <= route_cap:
                    break
                self.spill_retries += 1
                route_cap = bucket_size(max_sent)
            sent = int(sends.sum())
            levels[k].sent = sent
            self.bytes_routed += sent * per_child
            compaction = compaction_sort_bytes(item)
            if self.use_edges:
                prov_bytes = provenance_sort_bytes(item, compaction)
                self.bytes_sorted += S * S * route_cap * prov_bytes
            else:
                self.bytes_sorted += S * S * route_cap * (item + compaction)
            counts = np.asarray(count).reshape(-1).astype(np.int64)
            total = int(counts.sum())
            if total == 0:
                # Terminal probe: the span is traced, but writes no JSONL
                # record (no level was discovered).
                self.status_tracker.forward_level(k, n, sp.end(log=False))
                flightrec.boundary("forward", k)
                break
            if self.use_edges:
                # Edges belong to the level just EXPANDED (they index into
                # level k+1's per-owner prefixes). Device-resident while
                # the store budget allows, host-spilled past it — the
                # backward step re-uploads spilled edges exactly like
                # spilled level states.
                cur = levels[k]
                cur.ecap = route_cap
                extra = eidx.nbytes + slot.nbytes
                to_disk = False
                if stored_bytes + extra <= self.device_store_bytes:
                    cur.eidx, cur.slot = eidx, slot
                    stored_bytes += extra
                else:
                    with self._download(k, "edges", extra):
                        cur.eidx = _HostSpill.download(eidx)
                        cur.slot = _HostSpill.download(slot)
                    self.edges_bytes_spilled += extra
                    # Disk tier (ISSUE 11): when the host-RAM tier (the
                    # store cache budget) is exhausted too AND the edge
                    # files are being sealed anyway, keep NO resident
                    # copy — backward reloads them through the store,
                    # where the level schedule's readahead hints turn
                    # the loads into cache hits.
                    to_disk = (
                        self.checkpointer is not None
                        and self._host_spill_bytes + extra
                        > self.store.cache.budget_bytes
                    )
                    if not to_disk:
                        self._host_spill_bytes += extra
                self._ckpt_edges_level(k, cur)
                if to_disk:
                    # The save path extracted + enqueued its own host
                    # copies above; the sealed files are authoritative.
                    cur.eidx = cur.slot = None
                    self.edges_bytes_disk += extra
            if k + 1 >= g.num_levels:
                raise SolverError(
                    f"game {g.name}: children found at level {k + 1} but "
                    f"num_levels={g.num_levels} — level_of/num_levels "
                    "inconsistent"
                )
            next_cap = bucket_size(int(counts.max()), self.min_bucket)
            nxt = self._resize_fn(uniq.shape[-1], next_cap)(uniq)
            rec = _SLevel(counts, nxt, None)
            if stored_bytes + nxt.nbytes > self.device_store_bytes:
                # Device-store budget exhausted: keep this level on host only
                # (backward re-uploads it); the live frontier still chains on
                # device.
                with self._download(k + 1, "level", nxt.nbytes):
                    rec.host_shards()
                rec.dev = None
            else:
                stored_bytes += nxt.nbytes
            levels[k + 1] = rec
            frontier = nxt
            cap = next_cap
            self._ckpt_forward_level(k + 1, rec)
            lvl_secs = sp.end(
                n=n,
                frontier=n,
                children=total,
                shards=S,
                route_cap=route_cap,
                bytes_routed=self.bytes_routed - b0[0],
                bytes_routed_padded=self.bytes_routed_padded - b0[2],
                bytes_sorted=self.bytes_sorted - b0[1],
                bytes_hbm=self.bytes_sorted - b0[1],
                dispatches=self.dispatch_total - disp0,
            )
            self.status_tracker.forward_level(k, n, lvl_secs)
            flightrec.boundary("forward", k)
            k += 1
        return levels

    def _forward_generic(self, init, start_level: int) -> Dict[int, _SLevel]:
        """Device-resident forward for multi-jump games (children span
        levels).

        Each expanded level's routed children are grouped by topological
        level and merged into per-level device pools ON DEVICE (one
        sort-unique merge per reachable target level — see _merge_fn); the
        old path downloaded every level's children and merged host pools
        with np.union1d. Host work per level is counts syncs only
        (VERDICT r2 item 5). Levels pop in ascending order, so every
        contribution to level L lands before L is expanded.
        """
        g = self.game
        S = self.S
        J = g.max_level_jump
        shards, counts = self._seed(init)
        cap0 = bucket_size(int(counts.max()), self.min_bucket)
        frontier0 = jax.device_put(_pad_shards(shards, cap0), self._sharding)
        levels: Dict[int, _SLevel] = {}
        #: level -> (dev [S, cap] per-shard sorted pool, np [S] counts)
        pools: Dict[int, tuple] = {start_level: (frontier0, counts)}
        stored_bytes = 0
        item = np.dtype(g.state_dtype).itemsize
        while pools:
            k = min(pools)
            sp = Span("forward", logger=self.logger, level=k)
            self.progress = {"phase": "forward", "level": k,
                             "rank": self.rank}
            self._check_preempt("forward", k)
            b0 = (self.bytes_routed, self.bytes_sorted,
                  self.bytes_routed_padded)
            disp0 = self.dispatch_total
            frontier, counts = pools.pop(k)
            rec = _SLevel(counts, frontier, None)
            levels[k] = rec
            # Pending (not yet popped) pools are live device state too —
            # count them against the budget when deciding whether this
            # retained level may stay resident.
            pending_bytes = sum(p.nbytes for p, _ in pools.values())
            if (stored_bytes + pending_bytes + frontier.nbytes
                    > self.device_store_bytes):
                with self._download(k, "level", frontier.nbytes):
                    rec.host_shards()
                rec.dev = None
            else:
                stored_bytes += frontier.nbytes
            cap = frontier.shape[1]
            route_cap = self._initial_route_cap(cap)
            while True:
                def _step(cap=cap, route_cap=route_cap, frontier=frontier):
                    u, c, sc = self._forward_fn(cap, route_cap)(frontier)
                    return u, c, np.asarray(sc)

                uniq, count, sends = self._retry(
                    "sharded.forward", _step, level=k,
                    entry=lambda k=k: faults.fire("sharded.forward",
                                                  level=k),
                )
                self.bytes_routed_padded += S * S * route_cap * item
                max_sent = int(sends.max())
                if max_sent <= route_cap:
                    break
                self.spill_retries += 1
                route_cap = bucket_size(max_sent)
            self.bytes_routed += int(sends.sum()) * item
            compaction = compaction_sort_bytes(item)
            self.bytes_sorted += S * S * route_cap * (item + compaction)
            ccounts = np.asarray(count).reshape(-1)
            total = int(ccounts.sum())
            if total > 0:
                ccap = bucket_size(int(ccounts.max()), self.min_bucket)
                kmax = min(k + J, g.num_levels - 1)

                # Collective-safe retried unit (GM603): the resize +
                # level-check kernels route children through an
                # all_to_all/psum — `uniq` stays referenced across the
                # step, so re-dispatch is idempotent.
                def _check_step(ccap=ccap, uniq=uniq, k=k, kmax=kmax):
                    children = self._resize_fn(uniq.shape[-1], ccap)(uniq)
                    bad, per_target = self._level_check_fn(ccap)(
                        children,
                        np.full(1, k, np.int32),
                        np.full(1, kmax, np.int32),
                    )
                    return children, int(bad), np.asarray(per_target)

                children, bad, per_target = self._retry(
                    "sharded.forward", _check_step, level=k,
                    entry=lambda k=k: faults.fire("sharded.forward",
                                                  level=k),
                )
                if bad > 0:
                    raise SolverError(
                        f"game {g.name}: {bad} children outside levels "
                        f"({k}, {kmax}] — level_of/max_level_jump/"
                        "num_levels inconsistent"
                    )
                empty_pool = None
                for j in range(1, J + 1):
                    L = k + j
                    if L >= g.num_levels:
                        break
                    if int(per_target[j - 1]) == 0:
                        continue  # no child landed here; skip the merge
                    pool, _ = pools.get(L, (None, None))
                    if pool is None:
                        if empty_pool is None:
                            empty_pool = jax.device_put(
                                _pad_shards(
                                    [np.empty(0, g.state_dtype)] * S,
                                    bucket_size(1, self.min_bucket),
                                ),
                                self._sharding,
                            )
                        pool = empty_pool

                    # Same discipline for the merge dispatch: inputs
                    # (pool, children) are held across the step, the
                    # pools[L] assignment lands only on success.
                    def _merge_step(pool=pool, children=children, L=L,
                                    ccap=ccap):
                        merged, mcount = self._merge_fn(
                            pool.shape[1], ccap
                        )(pool, children, np.full(1, L, np.int32))
                        mcounts = np.asarray(mcount).reshape(-1) \
                            .astype(np.int64)
                        mcap = bucket_size(int(mcounts.max()),
                                           self.min_bucket)
                        return (
                            self._resize_fn(merged.shape[-1], mcap)(merged),
                            mcounts,
                        )

                    pools[L] = self._retry(
                        "sharded.forward", _merge_step, level=k,
                        entry=lambda k=k: faults.fire("sharded.forward",
                                                      level=k),
                    )
                    self.bytes_sorted += (
                        S * (pool.shape[1] + ccap) * (item + compaction)
                    )
            n = int(counts.sum())
            lvl_secs = sp.end(
                n=n,
                frontier=n,
                children=total,
                shards=S,
                route_cap=route_cap,
                bytes_routed=self.bytes_routed - b0[0],
                bytes_routed_padded=self.bytes_routed_padded - b0[2],
                bytes_sorted=self.bytes_sorted - b0[1],
                bytes_hbm=self.bytes_sorted - b0[1],
                dispatches=self.dispatch_total - disp0,
            )
            self.status_tracker.forward_level(k, n, lvl_secs)
            flightrec.boundary("forward", k)
        return levels

    def _run_backward_step(self, stacked, cap: int, window_caps: tuple,
                           window_flat) -> tuple:
        """One backward kernel call with the qcap overflow-retry loop."""
        S = self.S
        # Queries out (state bytes) + packed cells back.
        item = np.dtype(self.game.state_dtype).itemsize
        qcap = self._initial_route_cap(cap) if window_caps else 0
        while True:
            values, rem, misses, qcounts = self._backward_fn(
                cap, window_caps, qcap
            )(stacked, *window_flat)
            if qcap == 0:
                break
            qcounts = np.asarray(qcounts)
            self.bytes_routed_padded += S * S * qcap * (item + 4)
            max_sent = int(qcounts.max())
            if max_sent <= qcap:
                break
            self.spill_retries += 1
            qcap = bucket_size(max_sent)
        if qcap:
            self.bytes_routed += int(qcounts.sum()) * (item + 4)
            if search_method() == "sort":
                # Sort-merge join operands + fused payload gather w/ idx.
                self.bytes_sorted += (
                    S * (S * qcap + sum(window_caps)) * (item + 4)
                )
                self.bytes_gathered += S * S * qcap * 12
            else:
                # Binary search: no join sort, one payload gather per query
                # (log2 traversal reads not modeled).
                self.bytes_gathered += S * S * qcap * 8
        return values, rem, misses

    def _run_backward_step_streamed(self, stacked, cap: int, windows):
        """One backward step with the window STREAMED through HBM in blocks.

        windows: list of (states, values, remoteness) _HostSpill triples,
        each [S, wcapL] (padded, per-shard-sorted slices). Route once, then
        per window block: upload [S, wblock] slices, look up, accumulate
        packed cells; reply once. Per-shard window memory is O(wblock);
        queries/bookkeeping are O(cap·M) — both independent of level size.

        Known cost at extreme scale: when the RESOLVING side also blocks
        (_resolve_blocked_streamed), the window is re-uploaded once per
        resolve block — host->device traffic x (level/backward_block). The
        fix direction is a rotating HBM pool of window blocks shared across
        resolve blocks; not needed below 7x6 scale.
        """
        S = self.S
        item = np.dtype(self.game.state_dtype).itemsize
        qcap = self._initial_route_cap(cap)
        while True:
            queries, acc, s_owner, pos, order, qcounts = self._route_fn(
                cap, qcap
            )(stacked)
            qcounts = np.asarray(qcounts)
            self.bytes_routed_padded += S * S * qcap * (item + 4)
            max_sent = int(qcounts.max())
            if max_sent <= qcap:
                break
            self.spill_retries += 1
            qcap = bucket_size(max_sent)
        self.bytes_routed += int(qcounts.sum()) * (item + 4)
        for ws, wv, wr in windows:
            wb = min(self.window_block, ws.cap)
            for off in range(0, ws.cap, wb):
                blk = (ws.block(off, wb), wv.block(off, wb),
                       wr.block(off, wb))
                acc = self._lookup_acc_fn(qcap, wb)(queries, acc, *blk)
                self.window_stream_blocks += 1
                if search_method() == "sort":
                    self.bytes_sorted += S * (S * qcap + wb) * (item + 4)
                    self.bytes_gathered += S * S * qcap * 12
                else:
                    self.bytes_gathered += S * S * qcap * 8
        return self._reply_fn(cap, qcap)(stacked, acc, s_owner, pos, order)

    def _blocked_loop(self, stacked, step):
        """Column-block the resolving side: run `step(block_slice, block)`
        per block and concatenate. Shared by the resident and streamed
        resolvers — the block arithmetic must stay identical for their
        kernel keys to match the pre-scheduled shapes."""
        cap = stacked.shape[1]
        # Power-of-two floor: divides the (power-of-two) cap exactly.
        block = 1 << max(self.backward_block, 1).bit_length() - 1
        if cap <= block:
            return step(stacked, cap)
        values, rems = [], []
        misses = None
        for off in range(0, cap, block):
            v, r, m = step(stacked[:, off : off + block], block)
            values.append(v)
            rems.append(r)
            # Device-side accumulation; synced only under --paranoid.
            misses = m if misses is None else misses + m
        return (
            jnp.concatenate(values, axis=1),
            jnp.concatenate(rems, axis=1),
            misses,
        )

    def _resolve_blocked(self, stacked, window_caps: tuple, window_flat):
        """Backward-resolve a level, in column blocks when it is wide.

        Per-shard temporaries (child blocks, routing buffers) scale with
        the block, not the level — the HBM bound the 6x6/6x7 capacity plan
        relies on (docs/ARCHITECTURE.md). The window stays resident here;
        levels wider than window_block take _resolve_blocked_streamed
        instead, which streams the window through HBM too.
        """
        return self._blocked_loop(
            stacked,
            lambda blk, c: self._run_backward_step(
                blk, c, window_caps, window_flat
            ),
        )

    def _resolve_blocked_streamed(self, stacked, windows):
        """Streamed-window resolve, also blocking the resolving side.

        Composes both blockings: per-shard peak is O(resolve block) for
        children/routing and O(window block) for the window — the full 7x6
        memory shape (docs/ARCHITECTURE.md capacity plan).
        """
        return self._blocked_loop(
            stacked,
            lambda blk, c: self._run_backward_step_streamed(blk, c, windows),
        )

    def _repartition(self, states: np.ndarray) -> List[np.ndarray]:
        """Split a sorted global state array into per-shard sorted arrays."""
        owners = owner_shard_np(states, self.S)
        return [states[owners == s] for s in range(self.S)]

    def _backward(self, levels: Dict[int, _SLevel], root_level: int,
                  init) -> Dict[int, LevelTable]:
        """Deepest-first owner-routed resolve; unified fast/generic path.

        The window cache holds the device triples (states, values,
        remoteness) of the last `max_level_jump` resolved levels — each
        P(AXIS)-sharded, so per-shard window memory stays O(level/S).

        With store_tables=False only the root level's table is materialized
        on host (plus whatever the checkpointer persists) — the big-run mode
        where accumulating every level's table in host RAM is the remaining
        O(total-positions) cost (docs/ARCHITECTURE.md capacity plan).

        Level tables are assembled on a background worker where this
        process can copy the level by itself (_materialize_level); every
        one is joined before the tables are returned.
        """
        assembler = _TableAssembler(self.logger)
        try:
            resolved = self._backward_levels(levels, root_level, init,
                                             assembler)

            def _waiting(k):
                self.progress = {"phase": "assemble", "level": k,
                                 "rank": self.rank}

            resolved.update(assembler.join(on_wait=_waiting))
        finally:
            assembler.close()
            self.tables_async_levels = assembler.levels
            self.table_wait_s = assembler.wait_s
        return resolved

    def _backward_levels(self, levels: Dict[int, _SLevel], root_level: int,
                         init, assembler: _TableAssembler
                         ) -> Dict[int, LevelTable]:
        """The deepest-first resolve loop of _backward: the tables of the
        levels assembled on the solve thread; ``assembler`` holds the
        others."""
        g = self.game
        S = self.S
        # Forward's seals (edges + frontier levels) must all be visible
        # before the backward reads edge_level_info/completed_levels.
        self._flush_seals()
        resolved: Dict[int, LevelTable] = {}
        dev_cache: Dict[int, tuple] = {}
        # Window levels wider than window_block per shard live here as host
        # numpy triples and are streamed back through HBM in blocks during
        # lookup (per-shard window memory O(block), not O(level/S)).
        host_cache: Dict[int, tuple] = {}
        completed = (
            set(self.checkpointer.completed_levels())
            if self.checkpointer is not None
            else set()
        )
        if self.precompile and self.use_edges:
            # All edge-backward shapes are known now; compile them in the
            # background, deepest-first, while the deep levels execute.
            self._schedule_backward_edges(levels, completed)
        order = sorted(levels, reverse=True)
        for i, k in enumerate(order):
            b0 = (self.bytes_routed, self.bytes_sorted, self.bytes_gathered,
                  self.bytes_routed_padded)
            io0 = self.store.stats()["io_wait_secs"]
            rec = levels[k]
            self.progress = {
                "phase": "backward", "level": k, "rank": self.rank,
                "n": int(rec.counts.sum()),
            }
            self._check_preempt("backward", k)
            assembler.poll()  # a failed table assembly fails the solve now
            # Batched readahead from the level schedule: while THIS
            # level resolves, the store's pool decodes the NEXT level's
            # sealed checkpoint/edge shards — the solve thread's loads
            # one iteration from now become cache hits (today's
            # synchronous spill loads, overlapped away).
            if i + 1 < len(order):
                self._hint_backward_level(
                    order[i + 1], levels[order[i + 1]], completed
                )
            from_checkpoint = k in completed
            # Edge-cached resolve when this level's forward edges exist
            # (in memory, spilled, or sealed in the checkpoint dir) AND the
            # deeper level they index is in the window cache; every other
            # level takes the lookup join — the structural fallback that
            # keeps pre-edge checkpoints and generic-path games solving.
            want_edges = (
                self.use_edges and not from_checkpoint
                and ((k + 1) in dev_cache or (k + 1) in host_cache)
                and self._edges_available(k, rec)
            )
            mode = "edges" if want_edges else "lookup"
            # Distinct span names so a mixed solve's JSONL/registry shows
            # exactly which levels ran which backward (docs/OBSERVABILITY);
            # the span starts BEFORE the budget-evicted level's re-upload
            # and the edge load, like the t0 it replaced, so per-level
            # secs reconcile with the solve-level secs_backward.
            sp = Span("backward_edges" if want_edges else "backward",
                      logger=self.logger, level=k)
            n_max = int(rec.counts.max()) if rec.counts.size else 0
            if rec.dev is None:
                cap = bucket_size(n_max, self.min_bucket)
                rec.dev = jax.device_put(
                    _pad_shards(rec.host_shards(), cap), self._sharding
                )
            cap = rec.dev.shape[1]
            edges = self._load_edges(k, rec, cap) if want_edges else None
            if edges is None:
                mode = "lookup"  # rare torn/mismatched edge files degrade
            loaded = None
            if from_checkpoint:
                try:
                    loaded = self._load_checkpointed_level(
                        k, rec, cap, root_level
                    )
                except TORN_NPZ_ERRORS as e:
                    # Torn or crc-mismatching sealed level: quarantine and
                    # degrade to a recompute — the frontier is still known
                    # and the deeper window is already resolved. (The
                    # lookup join, not edges: the edge decision was taken
                    # before the load and this path is rare.)
                    self.checkpointer.quarantine_and_log(k, e, self.logger)
                    from_checkpoint = False
                    mode = "lookup"
            if loaded is not None:
                pv, pr, table = loaded
                values_dev = jax.device_put(pv, self._sharding)
                rem_dev = jax.device_put(pr, self._sharding)
            elif edges is not None:
                # Edge-cached resolve: collectives + gathers on stored
                # indices — no search, no re-expansion, no join sort
                # (bytes_sorted contribution: zero).
                eidx, slot, ecap = edges

                def _resolve_e(eidx=eidx, slot=slot, ecap=ecap, rec=rec,
                               k=k):
                    return self._resolve_edges_level(
                        rec, eidx, slot, ecap,
                        dev_cache.get(k + 1), host_cache.get(k + 1),
                    )

                values_dev, rem_dev, misses = self._retry(
                    "sharded.backward", _resolve_e, level=k,
                    entry=lambda k=k: faults.fire("sharded.backward",
                                                  level=k),
                )
                self.backward_edges_levels += 1
                del eidx, slot
                rec.eidx = rec.slot = None  # release the edge arrays
                if self.paranoid and int(_fetch_global(misses).sum()) > 0:
                    raise SolverError(
                        f"level {k}: consistency failures (zero-move "
                        "non-primitive positions)"
                    )
                table = self._materialize_level(
                    k, rec, values_dev, rem_dev, root_level, assembler
                )
            else:
                window_levels = [
                    k + j
                    for j in range(1, g.max_level_jump + 1)
                    if (k + j) in dev_cache or (k + j) in host_cache
                ]
                if all(L in dev_cache for L in window_levels):
                    window_caps = tuple(
                        dev_cache[L][0].shape[1] for L in window_levels
                    )
                    window_flat = []
                    for L in window_levels:
                        window_flat.extend(dev_cache[L])

                    def _resolve_l(rec=rec, window_caps=window_caps,
                                   window_flat=window_flat):
                        return self._resolve_blocked(
                            rec.dev, window_caps, window_flat
                        )

                    values_dev, rem_dev, misses = self._retry(
                        "sharded.backward", _resolve_l, level=k,
                        entry=lambda k=k: faults.fire("sharded.backward",
                                                      level=k),
                    )
                else:
                    # At least one window level was spilled: stream ALL of
                    # them (a resident one is downloaded once — mixing
                    # resident and streamed lookups would double the kernel
                    # shapes for a rare multi-jump corner).
                    windows = []
                    for L in window_levels:
                        if L not in host_cache:
                            # Move (not copy) the resident level to the host
                            # cache: one download, no double memory, and
                            # shallower levels that window on L reuse it.
                            with self._download(L, "window", sum(
                                    a.nbytes for a in dev_cache[L])):
                                host_cache[L] = tuple(
                                    _HostSpill.download(a)
                                    for a in dev_cache[L]
                                )
                            del dev_cache[L]
                        windows.append(host_cache[L])

                    def _resolve_s(rec=rec, windows=windows):
                        return self._resolve_blocked_streamed(
                            rec.dev, windows
                        )

                    values_dev, rem_dev, misses = self._retry(
                        "sharded.backward", _resolve_s, level=k,
                        entry=lambda k=k: faults.fire("sharded.backward",
                                                      level=k),
                    )
                if self.paranoid and int(_fetch_global(misses).sum()) > 0:
                    raise SolverError(
                        f"level {k}: consistency failures (missed child "
                        "lookups or zero-move non-primitive positions)"
                    )
                table = self._materialize_level(
                    k, rec, values_dev, rem_dev, root_level, assembler
                )
            if table is not None and (self.store_tables or k == root_level):
                resolved[k] = table
            if k == root_level:
                # The root answer leaves the device replicated (multi-host
                # safe) — the only result a big-run solve must produce.
                # The kernel psums across shards, so the dispatch is
                # collective-safe-retried like every other step (GM603):
                # its inputs stay referenced, re-dispatch is idempotent.
                def _root_step(cap=cap, rec=rec, values_dev=values_dev,
                               rem_dev=rem_dev):
                    v, r = self._root_fn(cap)(
                        rec.dev, values_dev, rem_dev,
                        jnp.full((1,), init, dtype=g.state_dtype),
                    )
                    return int(v), int(r)

                self._root_answer = self._retry(
                    "sharded.backward", _root_step, level=k,
                    entry=lambda k=k: faults.fire("sharded.backward",
                                                  level=k),
                )
            if self.checkpointer is not None and not from_checkpoint:
                # One npz per addressable shard — each multi-host process
                # writes only the shards it owns, nothing global assembles.
                self._checkpoint_level_shards(k, rec, values_dev, rem_dev)
            if cap <= self.window_block:
                dev_cache[k] = (rec.dev, values_dev, rem_dev)
            else:
                # Too wide to keep resident as a window: spill to host (via
                # addressable shards — multi-host safe), to be streamed back
                # in blocks by shallower levels' lookups.
                triple = (rec.dev, values_dev, rem_dev)
                with self._download(k, "window",
                                    sum(a.nbytes for a in triple)):
                    host_cache[k] = tuple(
                        _HostSpill.download(a) for a in triple
                    )
            rec.dev = None  # the cache owns the device copy now
            rec.eidx = rec.slot = None  # edges can never be read again
            if not self.store_tables:
                rec.host = None  # bound host RAM in big-run mode
            for done in [d for d in dev_cache if d > k + g.max_level_jump]:
                del dev_cache[done]
            for done in [d for d in host_cache if d > k + g.max_level_jump]:
                del host_cache[done]
            sp.end(
                n=int(rec.counts.sum()),
                shards=S,
                mode=mode,
                resumed=from_checkpoint,
                bytes_routed=self.bytes_routed - b0[0],
                bytes_routed_padded=self.bytes_routed_padded - b0[3],
                bytes_sorted=self.bytes_sorted - b0[1],
                bytes_gathered=self.bytes_gathered - b0[2],
                bytes_hbm=(self.bytes_sorted - b0[1])
                + (self.bytes_gathered - b0[2]),
                io_wait_secs=round(
                    self.store.stats()["io_wait_secs"] - io0, 6
                ),
            )
            self.status_tracker.backward_level(
                k, int(rec.counts.sum()), sp.secs,
                resumed=from_checkpoint,
            )
            flightrec.boundary("backward", k)
        return resolved

    def _hint_backward_level(self, k: int, rec, completed) -> None:
        """Readahead hints for one upcoming backward level: its sealed
        checkpoint shards (resume) and/or its disk-tiered edge shards.
        Hinting is advisory — an evicted or rejected hint degrades to
        the synchronous sealed read, never a wrong answer."""
        ck = self.checkpointer
        if ck is None or not hasattr(ck, "prefetch_level_shards"):
            return  # stubbed checkpointers in tests: skip readahead
        manifest = ck.load_manifest()
        if k in completed:
            if manifest.get("sharded_levels", {}).get(str(k)) == self.S:
                ck.prefetch_level_shards(k, self.S, manifest)
            else:
                ck.prefetch_level(k)
        elif self.use_edges and rec.eidx is None:
            info = manifest.get("edge_levels", {}).get(str(k))
            if info and info.get("shards") == self.S:
                ck.prefetch_edges_level(k, self.S, manifest)

    def _load_checkpointed_level(self, k: int, rec, cap: int,
                                 root_level: int):
        """Restart-from-level: (values [S, cap], remoteness [S, cap],
        table|None) of a sealed level, validated against the discovered
        frontier. Per-shard files at a matching shard count load
        shard-to-shard with no global assembly; a global file (or a
        different shard count) goes through assemble + repartition.
        Raises a TORN_NPZ_ERRORS member on unreadable/corrupt files
        (caller quarantines + recomputes) and SolverError on a genuine
        frontier mismatch (stale directory — still fatal)."""
        g = self.game
        S = self.S
        pv = np.full((S, cap), UNDECIDED, dtype=np.uint8)
        pr = np.zeros((S, cap), dtype=np.int32)
        table = None
        manifest = self.checkpointer.load_manifest()
        sealed_count = manifest.get("sharded_levels", {}).get(str(k))
        if sealed_count == S or (
            sealed_count is not None and reshard_enabled()
        ):
            shards = rec.host_shards()
            if sealed_count == S:
                per_shard = [
                    self.checkpointer.load_level_shard(k, s, manifest)
                    for s in range(S)
                ]
            else:
                # Reshard-on-resume (ISSUE 13): stream the level sealed
                # at S_old shards into THIS run's S shards — one sealed
                # file decoded at a time through the block store, rows
                # re-partitioned by the owner hash, packed cells riding
                # along row-aligned. No global table ever assembles
                # (the pre-elastic path paid load_level's full sort).
                if hasattr(self.checkpointer, "prefetch_level_shards"):
                    # Stubbed checkpointers in tests may not expose
                    # readahead; hints are advisory anyway.
                    self.checkpointer.prefetch_level_shards(
                        k, sealed_count, manifest
                    )

                def _one(s):
                    st, cells = self.checkpointer.load_level_shard(
                        k, s, manifest
                    )
                    return st.astype(g.state_dtype), cells

                per_shard = reshard_shard_stream(_one, sealed_count, S)
            loaded = []
            for s in range(S):
                st, cells = per_shard[s]
                if st.shape[0] != shards[s].shape[0] or not (
                    st.astype(g.state_dtype) == shards[s]
                ).all():
                    raise SolverError(
                        f"checkpointed level {k} (shard {s}) does "
                        "not match the discovered frontier — stale "
                        "checkpoint directory?"
                    )
                v, r = unpack_cells_np(cells)
                pv[s, : v.shape[0]] = v
                pr[s, : r.shape[0]] = r
                loaded.append((st, v, r))
            if self.store_tables or (
                k == root_level and self.materialize_root_table
            ):
                # Assemble from the shards already in hand (a
                # load_level call would re-read every file).
                states = np.concatenate([t[0] for t in loaded])
                order = np.argsort(states)
                table = LevelTable(
                    states=states[order].astype(g.state_dtype),
                    values=np.concatenate([t[1] for t in loaded])[order],
                    remoteness=np.concatenate(
                        [t[2] for t in loaded]
                    )[order],
                )
        else:
            table = self.checkpointer.load_level(k)
            table = LevelTable(
                states=np.asarray(table.states, dtype=g.state_dtype),
                values=table.values,
                remoteness=table.remoteness,
            )
            shards = rec.host_shards()
            expected = np.sort(np.concatenate(shards)) if shards \
                else np.empty(0, g.state_dtype)
            if table.states.shape[0] != expected.shape[0] or not (
                table.states == expected
            ).all():
                raise SolverError(
                    f"checkpointed level {k} does not match the "
                    "discovered frontier — stale checkpoint "
                    "directory?"
                )
            owners = owner_shard_np(table.states, S)
            for s in range(S):
                sel = owners == s
                pv[s, : sel.sum()] = table.values[sel]
                pr[s, : sel.sum()] = table.remoteness[sel]
        return pv, pr, table

    def _materialize_level(self, k: int, rec, values_dev, rem_dev,
                           root_level: int, assembler: _TableAssembler):
        """Global LevelTable of one resolved level, or None in big-run mode
        or where ``assembler`` builds it.

        Checkpointing no longer forces a global table: levels are
        checkpointed per shard (VERDICT r2 item 4), so big-run + checkpoint
        does zero global materialization. The hybrid engine's boundary join
        needs ITS root level (= the cutover boundary) as a table even in
        big-run mode — in plain solves the root answer instead leaves the
        device via _root_fn and no table materializes.

        Where this process can copy the level by itself the copy and merge
        go to the assembler's worker, off the solve thread. Under
        multi-process execution each copy is a gather collective, so it
        stays here, in its place in the collective order.
        """
        if not (self.store_tables or (
                k == root_level and self.materialize_root_table)):
            return None  # big-run mode: nothing leaves the device
        # Global table for this level (kept sharded on device during the
        # solve; materialized for the result).
        dev = rec.dev if rec.host is None else None
        size = values_dev.nbytes + rem_dev.nbytes + (
            dev.nbytes if dev is not None else 0)
        if _host_addressable(values_dev, rem_dev, dev):
            assembler.submit(k, rec.counts.copy(), rec.host, dev,
                             values_dev, rem_dev, size)
            return None
        with self._download(k, "table", size):
            shards = rec.host_shards()
            values = _fetch_global(values_dev)
            remoteness = _fetch_global(rem_dev)
        return _merge_level(shards, values, remoteness, rec.counts)

    def _edges_available(self, k: int, rec) -> bool:
        """Cheap pre-Span predicate: will _load_edges plausibly succeed?

        In-memory edges (device or spilled), or sealed checkpoint files at
        this shard count. The full geometry validation and the actual
        reads happen in _load_edges; a rare torn/mismatched file degrades
        the level to the lookup join mid-span, recorded in its `mode`
        field.
        """
        if rec.eidx is not None:
            return True
        if self.checkpointer is None:
            return False
        info = self.checkpointer.edge_level_info(k)
        if info and info.get("shards") != self.S:
            # Sealed at a foreign shard count: the eidx/slot maps index
            # into per-owner prefixes that no longer exist at this
            # geometry and CANNOT re-map — this level degrades to the
            # lookup backward (the per-level structural fallback), and
            # the count is the elastic-resume observable.
            self.edges_geometry_fallback_levels += 1
            return False
        return bool(info)

    def _load_edges(self, k: int, rec, cap: int):
        """Device-resident (eidx, slot, ecap) of level k's edges, or None.

        In-memory edges win (device arrays as-is; host-spilled ones
        re-upload whole, exactly like a spilled level's states). Otherwise
        sealed per-(level, shard) edge files from the checkpoint directory
        — an interrupted run resumed from its frontier snapshot — load when
        their shard count and slot geometry match this run. Anything
        missing, torn, or mismatched degrades to None and the caller falls
        back to the lookup backward: a pre-edge checkpoint keeps resuming.
        """
        if rec.eidx is not None:
            if isinstance(rec.eidx, _HostSpill):
                return (rec.eidx.block(0, rec.eidx.cap),
                        rec.slot.block(0, rec.slot.cap), rec.ecap)
            return rec.eidx, rec.slot, rec.ecap
        if self.checkpointer is None:
            return None
        manifest = self.checkpointer.load_manifest()
        info = manifest.get("edge_levels", {}).get(str(k))
        if (not info or info.get("shards") != self.S
                or info.get("slot_len") != cap * self.game.max_moves):
            return None
        ecap = int(info["ecap"])
        from gamesmanmpi_tpu.utils.checkpoint import TORN_NPZ_ERRORS

        try:
            es, ss = [], []
            for s in range(self.S):
                e, sl = self.checkpointer.load_edges_shard(k, s, manifest)
                es.append(np.asarray(e, dtype=np.int32))
                ss.append(np.asarray(sl, dtype=np.int32))
        except TORN_NPZ_ERRORS:
            return None  # torn edge files: degrade to the lookup join
        if any(e.shape[0] != self.S * ecap for e in es) or any(
                sl.shape[0] != cap * self.game.max_moves for sl in ss):
            return None
        rec.sent = int(sum(np.count_nonzero(e >= 0) for e in es))
        return (jax.device_put(np.stack(es), self._sharding),
                jax.device_put(np.stack(ss), self._sharding), ecap)

    def _resolve_edges_level(self, rec, eidx, slot, ecap: int, wdev,
                             wspill):
        """Resolve one level from stored edges (the SEND_BACK analog with
        the search deleted): all_to_all the stored indices, gather packed
        cells on the owners, all_to_all the reply, combine via the stored
        slot map. No re-expansion, no join — bytes_sorted contribution is
        zero by construction.

        wdev: the deeper level's resident (states, values, remoteness)
        device triple, or None when it was host-spilled — then wspill is
        its _HostSpill triple and the gather streams value/remoteness
        blocks through HBM (the same window_block mechanism as the lookup
        path, but only the 5-byte cells stream — never the states).
        """
        S = self.S
        # The off operand must carry the replicated sharding the scheduled
        # AOT executables were compiled for (plain np arrays would not).
        repl = NamedSharding(self.mesh, P())
        q, acc = self._eroute_fn(ecap)(eidx)
        # i32 index queries out, packed u32 cells back: the buffers whole,
        # and the level's real edges (every slot of the buffers when the
        # count is unknown).
        self.bytes_routed_padded += S * S * ecap * 8
        self.bytes_routed += (S * S * ecap if rec.sent is None
                              else rec.sent) * 8
        if wdev is not None:
            _, wv, wr = wdev
            acc = self._egather_fn(ecap, wv.shape[1])(
                q, acc, wv, wr,
                jax.device_put(np.zeros(1, np.int32), repl),
            )
            self.bytes_gathered += S * S * ecap * 8  # idx read + cell
        else:
            _, wv, wr = wspill
            wb = min(self.window_block, wv.cap)
            for off in range(0, wv.cap, wb):
                acc = self._egather_fn(ecap, wb)(
                    q, acc, wv.block(off, wb), wr.block(off, wb),
                    jax.device_put(np.full(1, off, np.int32), repl),
                )
                self.window_stream_blocks += 1
                self.bytes_gathered += S * S * ecap * 8
        return self._ereply_fn(rec.dev.shape[1], ecap)(rec.dev, acc, slot)

    def _shard_ranks(self) -> List[int]:
        """shard index -> owning process rank (all zeros single-host):
        the rank-set stamp each seal records so resume can tell WHICH
        process was responsible for a torn or missing shard file."""
        return [int(d.process_index) for d in self.mesh.devices.flat]

    @staticmethod
    def _shard_id(shard) -> int:
        """Global shard index of an addressable shard.

        A 1-device sharding reports index (slice(None), ...) — start is
        None, meaning offset 0 (this crashed num_shards=1 checkpointing
        when formatted into a filename).
        """
        return shard.index[0].start or 0

    def _shard_rows(self, rec, s: int):
        """One shard's real rows of a level, downloading only that shard.

        Uses addressable shards when the level is device-resident (multi-
        host: a process can only ever reach its own shards), else the host
        copy. Returns None when shard s is not addressable here.
        """
        if rec.dev is not None:
            for sh in rec.dev.addressable_shards:
                if self._shard_id(sh) == s:
                    return np.asarray(sh.data)[0][: int(rec.counts[s])]
            return None
        if self.num_processes > 1:
            # Host-resident level under multi-process execution (a
            # resumed checkpoint prefix, or a budget spill fetched via
            # the gather collective): every rank holds the full copy, so
            # write-ownership follows the mesh — the rank owning the
            # shard's device writes its file, everyone else defers. One
            # writer per shard, no racy duplicate snapshot files.
            if self._shard_ranks()[s] != self.rank:
                return None
        return rec.host_shards()[s]

    @staticmethod
    def _sync_processes(tag: str) -> None:
        """Barrier across processes before sealing a checkpoint manifest —
        process 0 must not mark shard sets complete while peers still
        write (torn checkpoints on preemption otherwise)."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(tag)

    def _ckpt_forward_level(self, k: int, rec) -> None:
        """Incrementally checkpoint one just-discovered level's shards.

        Forward alone outlasts the preemption/MTBF horizon at big-board
        scale; per-level saves keep the discovered prefix on a death
        mid-sweep (the single-device engine does the same). Each process
        writes only its addressable shards; process 0 seals the level after
        the barrier, so a torn level is never listed in the manifest.
        """
        if self.checkpointer is None:
            return
        tickets: List = []
        for s in range(self.S):
            rows = self._shard_rows(rec, s)
            if rows is not None:
                self._count_ckpt_bytes(
                    self.checkpointer.save_forward_level_shard(k, s, rows),
                    tickets,
                )

        def _seal(k=k):
            self._sync_processes(f"forward_level_{k}_shards_written")
            if jax.process_index() == 0:
                self.checkpointer.finish_forward_level(
                    k, self.S, ranks=self._shard_ranks(), drain=False
                )

        self._seal_after_writes(tickets, _seal)

    def _checkpoint_frontier_shards(self, levels) -> None:
        """Per-shard frontier snapshot files, one shard at a time.

        No global frontier array assembles anywhere (VERDICT r2 item 4):
        each (level, shard) row set downloads individually, peak host
        memory is one shard's frontiers, and under multi-host each process
        writes only the shards its devices own (process 0 seals the
        manifest).
        """
        self._flush_seals()  # the consolidated snapshot supersedes them
        tickets: List = []
        for s in range(self.S):
            pools = {}
            for k, rec in levels.items():
                rows = self._shard_rows(rec, s)
                if rows is not None:
                    pools[k] = rows
            if pools or jax.process_count() == 1:
                self._count_ckpt_bytes(
                    self.checkpointer.save_frontier_shard(s, pools),
                    tickets,
                )
        # Once-per-solve seal: run it eagerly (no pipelining partner).
        self._run_seal(tickets, lambda: None)
        self._sync_processes("frontier_shards_written")
        if jax.process_index() == 0:
            self.checkpointer.finish_frontier_shards(self.S, drain=False)

    def _checkpoint_level_shards(self, k: int, rec, values_dev,
                                 rem_dev) -> None:
        """Checkpoint one resolved level as per-shard npz files.

        Downloads via addressable shards (multi-host: each process sees and
        writes only its own devices' rows); the shard count is recorded in
        the manifest by process 0 so resume can validate/repartition.
        """

        def rows(arr):
            return {
                self._shard_id(s): np.asarray(s.data)[0]
                for s in arr.addressable_shards
            }

        sv, sr, ss = rows(values_dev), rows(rem_dev), rows(rec.dev)
        tickets: List = []
        for s, states in ss.items():
            n = int(rec.counts[s])
            cells = pack_cells_np(sv[s][:n], sr[s][:n])
            self._count_ckpt_bytes(
                self.checkpointer.save_level_shard(k, s, states[:n], cells),
                tickets,
            )

        def _seal(k=k):
            self._sync_processes(f"level_{k}_shards_written")
            if jax.process_index() == 0:
                self.checkpointer.finish_level_shards(
                    k, self.S, ranks=self._shard_ranks(), drain=False
                )

        self._seal_after_writes(tickets, _seal)

    def _count_ckpt_bytes(self, sizes, tickets=None) -> None:
        """Fold one checkpoint write's result into the run totals (stats
        ckpt_bytes_raw/ckpt_bytes_stored). ``sizes`` is a WriteTicket
        (write-behind — resolved later, when its seal waits on it; the
        ``tickets`` list collects it), a (raw, stored) pair (inline
        write), or None — wrapped/stubbed checkpointers (the resume
        tests' recording shims) may return None — skip, don't crash a
        solve over bookkeeping."""
        if not sizes:
            return
        if isinstance(sizes, WriteTicket):
            if tickets is not None:
                tickets.append(sizes)
            return
        raw, stored = sizes
        self.ckpt_bytes_raw += int(raw)
        self.ckpt_bytes_stored += int(stored)

    # ------------------------------------------------ seal pipelining
    # Payload writes ride the store's write-behind queue; seals (manifest
    # RMW) stay on the SOLVE thread, deferred one level: the seal for
    # level k's files runs when level k-1's checkpoint call arrives (or
    # at the next phase boundary), after waiting on exactly level k's
    # write tickets. Manifest mutation therefore never leaves this
    # thread, payload-before-seal stays absolute, and a death mid-queue
    # leaves unsealed strays resume already ignores (chaos: the
    # store.writebehind fault point). Multi-process runs seal eagerly —
    # the post-write barrier is a collective and cannot be deferred.

    def _run_seal(self, tickets, seal_fn) -> None:
        t0 = time.perf_counter()
        for t in tickets:
            self._count_ckpt_bytes(t.result())
        waited = time.perf_counter() - t0
        if waited > 1e-6:
            self.store._note_wait(waited)
        seal_fn()

    def _seal_after_writes(self, tickets, seal_fn) -> None:
        """Schedule one artifact-set seal after its payload writes."""
        if self.num_processes > 1 or not self.store.writebehind:
            self._run_seal(tickets, seal_fn)
            return
        self._pending_seals.append((tickets, seal_fn))
        # Depth 2 = one level's artifacts (edges + frontier, or one
        # level seal) in flight: flushing the OLDER level here is what
        # buys a full level of compute to overlap its writes.
        while len(self._pending_seals) > 2:
            self._run_seal(*self._pending_seals.pop(0))

    def _flush_seals(self) -> None:
        """Run every deferred seal (phase boundaries, solve end, and
        before any manifest read that must see them)."""
        while self._pending_seals:
            self._run_seal(*self._pending_seals.pop(0))

    def store_stats(self) -> dict:
        """This solve's block-store I/O deltas (the store is process-
        wide): io_wait_secs is every second the solve thread spent
        blocked on store I/O — the sync-vs-prefetch A/B observable —
        prefetch_hit_rate is reads served by cache/in-flight prefetch,
        and writebehind_queue_depth is the peak since process start."""
        now = self.store.stats()
        t0 = self._store_t0
        hits = now["prefetch_hits"] - t0["prefetch_hits"]
        misses = now["prefetch_misses"] - t0["prefetch_misses"]
        return {
            "io_wait_secs": now["io_wait_secs"] - t0["io_wait_secs"],
            "prefetch_hits": hits,
            "prefetch_misses": misses,
            "prefetch_hit_rate": (
                hits / (hits + misses) if hits + misses else 0.0
            ),
            "writebehind_writes": (
                now["writebehind_writes"] - t0["writebehind_writes"]
            ),
            "writebehind_queue_depth": now["writebehind_queue_depth_peak"],
        }

    @staticmethod
    def _rows_of(arr, s: int):
        """One shard's row of a [S, W] device array or _HostSpill (None
        when shard s is not addressable in this process)."""
        if isinstance(arr, _HostSpill):
            for _, index, rows in arr.shards:
                if (index[0].start or 0) == s:
                    return rows[0]
            return None
        for sh in arr.addressable_shards:
            if ShardedSolver._shard_id(sh) == s:
                return np.asarray(sh.data)[0]
        return None

    def _ckpt_edges_level(self, k: int, rec) -> None:
        """Persist one level's edge arrays as per-(level, shard) npz files.

        Saved the moment forward computes them — so a death between
        forward and backward resumes straight into the edge-cached
        backward instead of paying the lookup join for every level (the
        "host-spilled alongside the per-(level, shard) checkpoint npz
        files" leg of the edge design). Same multi-host write discipline
        as every other sharded artifact: each process writes only its
        addressable shards, process 0 seals post-barrier, and the seal
        records the geometry (shards, ecap, slot_len) resume validates.
        """
        if self.checkpointer is None:
            return
        tickets: List = []
        for s in range(self.S):
            e = self._rows_of(rec.eidx, s)
            sl = self._rows_of(rec.slot, s)
            if e is not None and sl is not None:
                self._count_ckpt_bytes(
                    self.checkpointer.save_edges_shard(k, s, e, sl),
                    tickets,
                )
        slot_len = (rec.slot.cap if isinstance(rec.slot, _HostSpill)
                    else rec.slot.shape[1])
        ecap = rec.ecap

        def _seal(k=k, slot_len=int(slot_len), ecap=ecap):
            self._sync_processes(f"edges_level_{k}_shards_written")
            if jax.process_index() == 0:
                self.checkpointer.finish_edges_level(
                    k, self.S, ecap, slot_len,
                    ranks=self._shard_ranks(), drain=False,
                )

        self._seal_after_writes(tickets, _seal)

    # ------------------------------------------------------------------ solve

    def solve(self) -> SolveResult:
        """Public entry: the solve body under the env-gated watchdog
        (GAMESMAN_WATCHDOG_SECS — same stall-abort contract as the
        single-device engine; `progress` is replaced atomically at each
        phase/level boundary)."""
        wd = maybe_watchdog(lambda: self.progress, logger=self.logger)
        self.status_tracker.begin(
            game=self.game.name, engine="sharded", shards=self.S,
            world=self.num_processes, rank=self.rank,
        )
        self._status_server = maybe_status_server(
            self._status_payload, rank=self.rank,
            world=self.num_processes,
        )
        if self._status_server is not None and self.coord is not None:
            # Publish this rank's /status address into the coordinator's
            # address book so rank 0's fleet view can scrape it.
            try:
                self.coord.announce(self._status_server.address)
            except CoordinationError:
                pass  # status stays rank-local; the solve is unaffected
        prev_sink = set_dispatch_sink(self._on_dispatch)
        try:
            return self._solve_impl()
        finally:
            set_dispatch_sink(prev_sink)
            if self._status_server is not None:
                self._status_server.stop()
                self._status_server = None
            # Pending pipelined seals are safe to run even on the error
            # path — their payload writes are already queued and waited
            # on — and losing them would unseal levels whose files are
            # intact. Never mask the primary failure with a seal error.
            try:
                self._flush_seals()
            except Exception:  # noqa: BLE001 - secondary failure only
                pass
            if wd is not None:
                wd.stop()
            if self.coord is not None:
                self.coord.close()

    def _status_payload(self) -> dict:
        """The /status body (HTTP handler threads; reads only
        atomically-replaced state). Rank 0 of a multi-process run folds
        in the fleet-merged view: every announced peer's /status is
        scraped (short deadline, dead peers degrade to absent) and
        per-level walls merge as max-across-ranks with stragglers
        flagged past GAMESMAN_STATUS_STRAGGLER_FACTOR x the median."""
        snap = self.status_tracker.snapshot(progress=self.progress)
        snap["retries"] = self.retries
        snap["dispatches_total"] = self.dispatch_total
        try:
            snap["io"] = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in self.store_stats().items()
            }
        except Exception:  # noqa: BLE001 - stubbed stores in tests
            pass
        if self.rank == 0 and self.num_processes > 1:
            peer_snaps = {0: snap}
            if self.coord is not None:
                try:
                    book = self.coord.peers()
                except CoordinationError:
                    book = {}
                for r, addr in book.items():
                    if r == 0:
                        continue
                    got = obs_status.fetch_status(addr)
                    if got is not None:
                        peer_snaps[r] = got
            snap["fleet"] = obs_status.merge_fleet(
                peer_snaps, world=self.num_processes
            )
        return snap

    def _solve_impl(self) -> SolveResult:
        g = self.game
        t0 = time.perf_counter()
        init, start_level = canonical_scalar(g, g.initial_state())
        if self.checkpointer is not None:
            self.checkpointer.bind_game(g.name)
            # Elastic-resume gate (ISSUE 13): compare the sealed
            # geometry against this run's ONCE, up front — a mismatch
            # either becomes an explicit reshard adoption (logged, the
            # loaders re-partition on read) or, with GAMESMAN_RESHARD=0,
            # a loud error naming both geometries (never an opaque
            # abort, never a silent forward re-run). Stubbed
            # checkpointers in tests may not expose the check.
            check_geom = getattr(
                self.checkpointer, "check_resume_geometry", None
            )
            if check_geom is not None:
                try:
                    geom = check_geom(self.S, self.num_processes)
                except CheckpointGeometryError as e:
                    raise SolverError(str(e)) from e
                if geom["status"] == "reshard":
                    sealed = geom["sealed"]
                    self.resharded_from = (
                        sealed.get("num_shards")
                        or (sealed["shard_counts"] or [None])[-1]
                    )
                    if self.logger is not None:
                        self.logger.log({
                            "phase": "reshard",
                            "from_shards": sealed["shard_counts"],
                            "from_world": sealed.get("num_processes"),
                            "to_shards": self.S,
                            "to_world": self.num_processes,
                            "epoch": sealed.get("epoch"),
                        })
            if self.coord is not None:
                # Rank-consistent resume: every rank independently reads
                # the manifest and digests its resume state (deepest
                # mutually-sealed level + the sealed sets). Identical
                # digests meet at one epoch and pass; ANY divergence —
                # a rank seeing a different checkpoint directory or a
                # half-synced filesystem — lands on different epochs,
                # which the barrier deadline turns into a coordinated
                # abort instead of a silently-forking solve.
                digest = self.checkpointer.resume_digest(self.S)
                self.coord.barrier(f"resume:{digest}")
            if self.rank == 0:
                # Stamp the run AFTER the agreement (the stamp mutates
                # the manifest the digest reads): seals taken this run
                # carry this epoch + the rank that owns each shard.
                self.checkpointer.stamp_run(
                    self.num_processes, self._shard_ranks()
                )
            if self.coord is not None:
                self.coord.barrier("run_stamped")
        saved_shards = (
            self.checkpointer.load_frontier_shards(self.S)
            if self.checkpointer is not None
            else None
        )
        saved = None
        if saved_shards is None and self.checkpointer is not None:
            saved = self.checkpointer.load_frontiers()
        if saved_shards is not None:
            # Per-shard snapshot at a matching shard count: shard-to-shard
            # resume, no global assembly or repartition.
            levels = {}
            for k, arrs in saved_shards.items():
                shards = [np.asarray(a, dtype=g.state_dtype) for a in arrs]
                levels[k] = _SLevel(
                    np.array([a.shape[0] for a in shards], dtype=np.int64),
                    None,
                    shards,
                )
        elif saved is not None:
            levels = {}
            for k, v in saved.items():
                shards = self._repartition(np.asarray(v, dtype=g.state_dtype))
                levels[k] = _SLevel(
                    np.array([a.shape[0] for a in shards], dtype=np.int64),
                    None,
                    shards,
                )
        elif self.fast:
            # A previous run's interrupted forward left sealed per-level
            # shard files at this shard count: continue from its deepest.
            partial = (
                self.checkpointer.load_forward_level_shards(self.S)
                if self.checkpointer is not None
                else {}
            )
            levels = self._forward_fast(init, start_level,
                                        resume=partial or None)
        else:
            levels = self._forward_generic(init, start_level)
        if (saved is None and saved_shards is None
                and self.checkpointer is not None):
            self._checkpoint_frontier_shards(levels)
            self._sync_processes("forward_level_files_superseded")
            if jax.process_index() == 0:
                # The consolidated snapshot is sealed; the incremental
                # per-level files are now a redundant second copy of the
                # biggest artifact on disk.
                self.checkpointer.drop_forward_level_shards()
        t_forward = time.perf_counter() - t0
        # Positions counted from the per-shard counters, not the tables —
        # valid in store_tables=False mode too.
        num_positions = sum(int(rec.counts.sum()) for rec in levels.values())
        # The level schedule is fixed: /status's ETA model now knows the
        # remaining backward work exactly (obs/status.py).
        self.status_tracker.set_schedule(
            {k: int(rec.counts.sum()) for k, rec in levels.items()}
        )
        resolved = self._backward(levels, start_level, init)
        # Settle the tail of the pipeline before accounting: deferred
        # seals run, their tickets resolve into ckpt_bytes_*, and the
        # store deltas below include every write this solve issued.
        self._flush_seals()
        if self.checkpointer is not None:
            try:
                # Refresh the gamesman_ckpt_bytes{kind} disk gauges with
                # everything this solve left on disk (the campaign's
                # disk monitor reads the same accounting between
                # attempts).
                self.checkpointer.disk_usage()
            except (OSError, AttributeError):
                pass  # stubbed checkpointers / racing cleanup
        t_total = time.perf_counter() - t0
        root_value, root_rem = self._root_answer
        stats = {
            "game": g.name,
            "engine": "sharded",
            "shards": self.S,
            "positions": num_positions,
            "levels": len(levels),
            "retries": self.retries,
            "spill_retries": self.spill_retries,
            "backward": self.backward_mode,
            "backward_edges_levels": self.backward_edges_levels,
            "resharded_from": self.resharded_from,
            "edges_geometry_fallback_levels":
                self.edges_geometry_fallback_levels,
            "edges_bytes_spilled": self.edges_bytes_spilled,
            "edges_bytes_disk": self.edges_bytes_disk,
            "ckpt_bytes_raw": self.ckpt_bytes_raw,
            "ckpt_bytes_stored": self.ckpt_bytes_stored,
            "tables_async_levels": self.tables_async_levels,
            "table_wait_s": self.table_wait_s,
            "secs_forward": t_forward,
            "secs_backward": t_total - t_forward,
            "secs_total": t_total,
            "positions_per_sec": num_positions / max(t_total, 1e-9),
            "bytes_routed": self.bytes_routed,
            "bytes_routed_padded": self.bytes_routed_padded,
            "bytes_sorted": self.bytes_sorted,
            "bytes_gathered": self.bytes_gathered,
            # Dispatch economy (see engine stats of the same names).
            "dispatches_total": self.dispatch_total,
            "dispatches_per_level": round(
                self.dispatch_total / max(len(levels), 1), 2),
            # ISSUE 15 roofline rollup (engine.roofline_stats): HBM
            # operand bytes are the sort+gather sides (routed bytes are
            # ICI traffic, accounted separately); bytes_host approximates
            # the host side from the spill + checkpoint payloads.
            # chips = shards only on REAL accelerator meshes: a faked
            # CPU mesh (tests, CPU benches) is one physical chip, and
            # dividing by S there would make this field disagree 8x
            # with bench.py's identically-named record field.
            "bytes_host": self.edges_bytes_spilled + self.ckpt_bytes_raw,
            "roofline": roofline_stats(
                self.bytes_sorted + self.bytes_gathered,
                num_positions, t_total,
                chips=(self.S if jax.devices()[0].platform != "cpu"
                       else 1),
            ),
            **self.store_stats(),
            # Reachable positions per level, from the per-shard counts:
            # the same record field as the dense engine's, whichever
            # backward each level ran.
            "reachable_per_level": {
                int(k): int(rec.counts.sum())
                for k, rec in sorted(levels.items())
            },
        }
        self.progress = {"phase": "done", "rank": self.rank}
        if self.logger is not None:
            self.logger.log({"phase": "done", **stats})
        return SolveResult(g, root_value, root_rem, resolved, stats)
