"""The sharded backward's level tables, assembled off the solve thread.

Where this process can copy a level by itself the copy and the merge of
its shard slices run on a background worker; under multi-process
execution they stay on the solve thread. Both paths must give the same
tables byte for byte. The tests drive the synchronous one through the
same addressability predicate the engine uses.
"""

import threading

import numpy as np
import pytest

import jax

from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.parallel import ShardedSolver
from gamesmanmpi_tpu.parallel import sharded as sh
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.solve.hybrid import HybridSolver

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (fake) devices"
)


class _Records:
    """A logger that keeps the records, from any thread."""

    def __init__(self):
        self.records = []
        self._lock = threading.Lock()

    def log(self, record):
        with self._lock:
            self.records.append(dict(record))

    def phase(self, name):
        return [r for r in self.records if r.get("phase") == name]


def _solve(spec, **kw):
    logger = _Records()
    result = ShardedSolver(get_game(spec), num_shards=4, logger=logger,
                           **kw).solve()
    return result, logger


def _assemble_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("gamesman-assemble")]


@pytest.mark.parametrize("spec", ["connect4:w=4,h=4", "nim:heaps=3-4-5"])
def test_async_tables_equal_sync_tables(spec, monkeypatch):
    """Connect-4 rides the fast path (edge-cached backward), nim the
    generic one (lookup backward): every level's table from the worker
    equals the solve thread's, array for array and dtype for dtype."""
    fast, fast_log = _solve(spec)
    monkeypatch.setattr(sh, "_host_addressable", lambda *a: False)
    slow, slow_log = _solve(spec)

    assert sorted(fast.levels) == sorted(slow.levels)
    for k in slow.levels:
        a, b = fast.levels[k], slow.levels[k]
        for name in ("states", "values", "remoteness"):
            x, y = getattr(a, name), getattr(b, name)
            assert x.dtype == y.dtype, (k, name)
            np.testing.assert_array_equal(x, y, err_msg=f"{k} {name}")
    assert (fast.value, fast.remoteness) == (slow.value, slow.remoteness)

    done = fast_log.phase("done")[-1]
    assert done["tables_async_levels"] == done["levels"] == len(fast.levels)
    assert done["table_wait_s"] >= 0
    # The worker's copies are `assemble` spans, never `download`.
    assembled = fast_log.phase("assemble")
    assert sorted(r["level"] for r in assembled) == sorted(fast.levels)
    assert {r["kind"] for r in assembled} == {"table"}
    assert {r["n"] for r in assembled if r["level"] == min(fast.levels)} \
        == {1}
    assert not [r for r in fast_log.phase("download")
                if r.get("kind") == "table"]

    done = slow_log.phase("done")[-1]
    assert done["tables_async_levels"] == 0
    assert done["table_wait_s"] == 0
    assert not slow_log.phase("assemble")
    assert len([r for r in slow_log.phase("download")
                if r.get("kind") == "table"]) == len(slow.levels)
    assert not _assemble_threads()


def test_assembly_error_fails_the_solve(monkeypatch):
    """A merge that raises on the worker surfaces as the solve's own
    exception, promptly, and leaves no worker thread behind."""
    merge = sh._merge_level

    def failing(shards, values, remoteness, counts):
        if int(np.sum(counts)) == 1:  # the root level
            raise RuntimeError("injected assembly fault")
        return merge(shards, values, remoteness, counts)

    monkeypatch.setattr(sh, "_merge_level", failing)
    caught = []

    def run():
        try:
            ShardedSolver(get_game("tictactoe"), num_shards=4).solve()
        except BaseException as e:  # noqa: BLE001 - inspected below
            caught.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=300)
    assert not t.is_alive(), "solve hung on a failed table assembly"
    assert len(caught) == 1
    assert "injected assembly fault" in str(caught[0])
    assert not _assemble_threads()


def test_assembly_error_raised_at_the_next_level(monkeypatch):
    """A failure deep in the backward stops the solve at the next level
    boundary, before the shallower levels resolve."""
    merge = sh._merge_level
    solver = ShardedSolver(get_game("tictactoe"), num_shards=4)
    deepest = []

    def failing(shards, values, remoteness, counts):
        if not deepest:
            deepest.append(1)
            raise RuntimeError("injected at the deepest level")
        return merge(shards, values, remoteness, counts)

    monkeypatch.setattr(sh, "_merge_level", failing)
    polled = []
    poll = sh._TableAssembler.poll

    def poll_after_job(self):
        # Let the worker finish what it has, so the next poll sees it.
        for fut in list(self._pending.values()):
            fut.exception()
        polled.append(1)
        return poll(self)

    monkeypatch.setattr(sh._TableAssembler, "poll", poll_after_job)
    with pytest.raises(RuntimeError, match="deepest level"):
        solver.solve()
    # The deepest level's poll found nothing; the next one raised.
    assert len(polled) == 2
    assert solver.tables_async_levels == 1
    assert not _assemble_threads()


def test_solve_thread_stays_within_a_level_of_the_device(monkeypatch):
    """The edge-cached steps have no host sync of their own: each submit
    returns only once the level before it has run, so the loop never
    queues more than one level behind the running one."""
    submit = sh._TableAssembler.submit
    waited, seen = [], []
    block = jax.block_until_ready

    def recording_block(x):
        waited.append(x)
        return block(x)

    def checking(self, k, counts, host, dev, values_dev, rem_dev, nbytes):
        prev = self._ahead
        del waited[:]
        submit(self, k, counts, host, dev, values_dev, rem_dev, nbytes)
        seen.append(prev is None
                    or (any(w is prev for w in waited) and prev.is_ready()))

    monkeypatch.setattr(sh.jax, "block_until_ready", recording_block)
    monkeypatch.setattr(sh._TableAssembler, "submit", checking)
    result, _ = _solve("tictactoe")
    assert len(seen) == len(result.levels) and all(seen)


def test_store_tables_false_submits_nothing():
    result, log = _solve("tictactoe", store_tables=False)
    assert result.levels == {}
    done = log.phase("done")[-1]
    assert done["tables_async_levels"] == 0
    assert not log.phase("assemble")


@pytest.mark.parametrize("addressable", [True, False])
def test_hybrid_boundary_table_either_path(addressable, monkeypatch):
    """The hybrid's sharded region hands back its boundary table (its
    root level, one past the cutover) from the worker or from the solve
    thread; the boundary join then gives the classic solver's answers
    either way."""
    submitted = []
    submit = sh._TableAssembler.submit

    def counting(self, k, *a, **kw):
        submitted.append(k)
        return submit(self, k, *a, **kw)

    monkeypatch.setattr(sh._TableAssembler, "submit", counting)
    if not addressable:
        monkeypatch.setattr(sh, "_host_addressable", lambda *a: False)
    g = get_game("connect4:w=3,h=3,connect=3")
    ref = Solver(g).solve()
    hy = HybridSolver(g, cutover=4, devices=4, store_tables=False).solve()
    assert (hy.value, hy.remoteness, hy.num_positions) == (
        ref.value, ref.remoteness, ref.num_positions)
    assert submitted == ([5] if addressable else [])
    table = ref.levels[5]
    bfs = HybridSolver(g, cutover=4, devices=4).solve()
    for s, v, r in zip(table.states, table.values, table.remoteness):
        assert bfs.lookup(int(s)) == (int(v), int(r))
