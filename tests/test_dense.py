"""Dense class-partitioned engine (solve/dense.py): parity + machinery.

The dense engine solves a superset of the reachable space through perfect
combinadic indexing; these tests pin (a) the rank/unrank machinery, (b)
full-value parity against the BFS engine on boards small enough to solve
both ways in CI, and (c) the exact reachable counts — 4x4's 161,029 is
Tromp's published count, so the reachability sweep is externally anchored
the same way the BFS engine's 5x5 count is.
"""

import numpy as np
import pytest

from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.solve.dense import (
    DenseSolver,
    DenseTables,
    n1_of_level,
)


def test_rank_unrank_roundtrip():
    rng = np.random.default_rng(0)
    for w, h in ((4, 3), (3, 4), (4, 4)):
        t = DenseTables(w, h)
        for L in (0, 1, 2, w * h // 2, w * h - 1, w * h):
            P = len(t.profiles[L])
            C = t.class_size[L]
            for _ in range(10):
                row = int(rng.integers(P))
                rank = int(rng.integers(C))
                bits = t.unrank_np(L, row, rank)
                assert bin(bits).count("1") == n1_of_level(L)
                assert t.rank_np(L, row, bits) == rank


def test_locate_roundtrips_reachable_states():
    g = get_game("connect4:w=3,h=3,connect=3")
    t = DenseTables(3, 3, 3)
    r = Solver(g).solve()
    for L, tab in r.levels.items():
        for s in tab.states[:50]:
            level, row, rank = t.locate(int(s))
            assert level == L
            # unrank must reproduce the player-1 stones of the state
            bits = t.unrank_np(level, row, rank)
            assert t.rank_np(level, row, bits) == rank


def test_dense_full_parity_3x3c3():
    g = get_game("connect4:w=3,h=3,connect=3")
    rc = Solver(g).solve()
    rd = DenseSolver(g).solve()
    assert (rd.value, rd.remoteness) == (rc.value, rc.remoteness)
    # Exact reachable count: the sweep must agree with BFS discovery.
    assert rd.num_positions == rc.num_positions
    checked = 0
    for _, tab in rc.levels.items():
        for s, v, rem in zip(tab.states, tab.values, tab.remoteness):
            assert rd.lookup(int(s)) == (int(v), int(rem))
            checked += 1
    assert checked == rc.num_positions


def test_dense_checkpoint_resume(tmp_path):
    """Restart-from-level for the backward sweep: a run that died after
    saving levels K..nc must rechain from K's cells without recomputing
    them, and a fully-checkpointed rerun must compute nothing."""
    from gamesmanmpi_tpu.utils import LevelCheckpointer

    g = get_game("connect4:w=3,h=3,connect=3")
    full = DenseSolver(g).solve()
    nc = full._tables.ncells

    # Simulate an interrupted run: persist only the top 4 levels.
    d = str(tmp_path / "dense_ck")
    ck = LevelCheckpointer(d)
    ck.bind_game(g.name + ":dense")
    for L in range(nc - 3, nc + 1):
        ck.save_dense_level(L, full.cells[L])

    resumed_solver = DenseSolver(g, checkpointer=LevelCheckpointer(d))
    orig = resumed_solver._backward_level

    def guarded(L, child_flat):
        assert L <= nc - 4, f"resume recomputed checkpointed level {L}"
        return orig(L, child_flat)

    resumed_solver._backward_level = guarded
    resumed = resumed_solver.solve()
    assert (resumed.value, resumed.remoteness, resumed.num_positions) == (
        full.value, full.remoteness, full.num_positions
    )
    for L in full.cells:
        assert np.array_equal(
            np.asarray(full.cells[L]), np.asarray(resumed.cells[L])
        ), L

    # Everything is now on disk: a second resume computes NOTHING.
    final_solver = DenseSolver(g, checkpointer=LevelCheckpointer(d))

    def poisoned(L, child_flat):
        raise AssertionError(f"fully-resumed solve recomputed level {L}")

    final_solver._backward_level = poisoned
    final = final_solver.solve()
    assert (final.value, final.remoteness) == (full.value, full.remoteness)

    # A different game must be refused loudly.
    with pytest.raises(ValueError, match="belongs to game"):
        DenseSolver(get_game("connect4:w=4,h=4"),
                    checkpointer=LevelCheckpointer(d)).solve()


def test_pallas_mesh_falls_back_until_chip_proven(monkeypatch):
    """devices>1 + gather_mode=pallas is exercised only in CPU interpret
    mode; on a real accelerator the Mosaic custom call's behaviour under
    auto-SPMD is chip-unproven (ADVICE r4), so the constructor must fall
    back to the plain XLA gather — with an env escape hatch for the
    chip-session step that will prove it."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 fake devices")
    import gamesmanmpi_tpu.solve.dense as dense_mod

    g = get_game("connect4:w=3,h=3,connect=3")
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    monkeypatch.setattr(dense_mod.jax, "default_backend", lambda: "tpu")
    with pytest.warns(UserWarning, match="not yet chip-proven"):
        s = DenseSolver(g, devices=2)
    assert s.gather_mode == "plain"
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER_PALLAS_MESH", "1")
    s2 = DenseSolver(g, devices=2)
    assert s2.gather_mode == "pallas"
    # Single-device pallas is chip-provable independently; no fallback.
    monkeypatch.delenv("GAMESMAN_DENSE_GATHER_PALLAS_MESH")
    assert DenseSolver(g).gather_mode == "pallas"


def test_dense_sharded_parity_3x3c3():
    """devices=4 partitions every level kernel's rank axis over the mesh;
    cells must be BIT-identical to the single-device engine (the same
    programs, just partitioned — any drift means the sharding changed
    semantics, not layout)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    g = get_game("connect4:w=3,h=3,connect=3")
    r1 = DenseSolver(g).solve()
    r4 = DenseSolver(g, devices=4).solve()
    assert (r4.value, r4.remoteness, r4.num_positions) == (
        r1.value, r1.remoteness, r1.num_positions
    )
    for L in r1.cells:
        assert np.array_equal(
            np.asarray(r1.cells[L]), np.asarray(r4.cells[L])
        ), L
    # Uneven split: a mesh width that does NOT divide the class sizes
    # exercises the rank-axis round-up padding.
    r3 = DenseSolver(g, devices=3).solve()
    assert (r3.value, r3.remoteness, r3.num_positions) == (
        r1.value, r1.remoteness, r1.num_positions
    )
    for L in r1.cells:
        assert np.array_equal(
            np.asarray(r1.cells[L]), np.asarray(r3.cells[L])
        ), L


@pytest.mark.slow
def test_dense_parity_4x4():
    g = get_game("connect4:w=4,h=4")
    rc = Solver(g).solve()
    rd = DenseSolver(g).solve()
    assert (rd.value, rd.remoteness) == (rc.value, rc.remoteness)
    # 161,029 is Tromp's published 4x4 legal-position count.
    assert rd.num_positions == rc.num_positions == 161029
    # Per-LEVEL reachable counts must match BFS discovery exactly, not
    # just the total (a compensating over/undercount pair would pass the
    # sum).
    for L, n in rd.stats["reachable_per_level"].items():
        assert n == rc.levels[L].states.shape[0], (L, n)
    rng = np.random.default_rng(7)
    for _, tab in rc.levels.items():
        n = tab.states.shape[0]
        for i in rng.choice(n, size=min(200, n), replace=False):
            assert rd.lookup(int(tab.states[i])) == (
                int(tab.values[i]), int(tab.remoteness[i])
            )


def test_device_rank_unrank_match_host_u64_board():
    # 4x7 needs (7+1)*4 = 32 > 31 state bits -> the uint64 kernel path the
    # 6x5 board ladder uses. A full u64 solve is too big for CI, but the
    # rank/unrank kernels themselves are one call.
    import jax
    import jax.numpy as jnp

    from gamesmanmpi_tpu.solve.dense import _rank_bits, _unrank_bits

    t = DenseTables(4, 7)
    assert t.bits_dtype == np.uint64
    L = 9
    P = len(t.profiles[L])
    C = t.class_size[L]
    rng = np.random.default_rng(3)
    cb = 32
    ranks = rng.integers(0, C, size=(P, cb), dtype=np.uint32)
    cellidx = np.ascontiguousarray(
        t.cellidx_rows(L).astype(np.int32).T
    )  # [ncells, P]
    binom = t.binom.astype(np.uint32)

    bits = jax.jit(lambda r: _unrank_bits(
        r, n1_of_level(L), jnp.asarray(binom), jnp.asarray(cellidx),
        [int(b) for b in t.bitpos], jnp.uint64, jnp.uint32,
    ))(jnp.asarray(ranks))
    back = jax.jit(lambda b: _rank_bits(
        b, jnp.asarray(binom), jnp.asarray(cellidx),
        [int(b2) for b2 in t.bitpos], jnp.uint64, jnp.uint32,
    ))(bits)
    bits_np = np.asarray(bits)
    back_np = np.asarray(back)
    for p in range(P):
        for i in range(cb):
            assert int(bits_np[p, i]) == t.unrank_np(L, p, int(ranks[p, i]))
            assert int(back_np[p, i]) == int(ranks[p, i])


def test_dense_full_parity_tall_board():
    # Taller-than-wide: every solve-parity board so far had w >= h; this
    # pins the (column, row) indexing asymmetry end to end.
    g = get_game("connect4:w=3,h=4,connect=3")
    rc = Solver(g).solve()
    rd = DenseSolver(g).solve()
    assert (rd.value, rd.remoteness) == (rc.value, rc.remoteness)
    assert rd.num_positions == rc.num_positions
    checked = 0
    for _, tab in rc.levels.items():
        for s, v, rem in zip(tab.states, tab.values, tab.remoteness):
            assert rd.lookup(int(s)) == (int(v), int(rem))
            checked += 1
    assert checked == rc.num_positions


def test_dense_rejects_sym_and_non_connect4():
    with pytest.raises(ValueError):
        DenseSolver(get_game("connect4:w=4,h=4,sym=1"))
    with pytest.raises(TypeError):
        DenseSolver(get_game("tictactoe"))


def test_dense_no_tables_mode():
    g = get_game("connect4:w=3,h=3,connect=3")
    rd = DenseSolver(g, store_tables=False).solve()
    assert rd.cells is None
    assert (rd.value, rd.remoteness) == (3, 9)  # TIE, remoteness 9
    with pytest.raises(KeyError):
        rd.lookup(int(g.initial_state()))


def test_dense_sorted_gather_matches_plain(monkeypatch):
    """The monotone (sorted) fill feeding the Pallas window gather
    (interpret mode here) on 5x2: at default blocking and with tiny blocks
    (block_elems=64: kernel blocks span several profile rows and the tail
    block's pad lanes). Every level table matches the plain gather; the
    default blocking misses no lane."""
    g = get_game("connect4:w=5,h=2")
    plain = DenseSolver(g).solve()
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    pal = DenseSolver(g).solve()
    blocked = DenseSolver(g, block_elems=64).solve()
    assert pal.stats["gather_miss_lanes"] == 0
    for run in (pal, blocked):
        assert run.stats["gather_mode"] == "pallas"
        assert run.stats["gather_lanes"] == plain.stats["gather_lanes"]
        for L, cells in plain.cells.items():
            np.testing.assert_array_equal(run.cells[L], cells)


def test_dense_solve_schedules_reach_kernels_only_for_a_sweep(monkeypatch):
    """A solve schedules the count sweep's kernels only when it will run
    the sweep: once the board's counts are known, a solve queues its
    backward steps alone."""
    from gamesmanmpi_tpu.solve import dense as dmod

    kinds = []
    real = dmod.schedule_kernel

    def spy(game, kind, *args, **kwargs):
        kinds.append(kind)
        return real(game, kind, *args, **kwargs)

    monkeypatch.setattr(dmod, "schedule_kernel", spy)
    monkeypatch.setattr(dmod, "_REACH_COUNTS", {})
    g = get_game("connect4:w=3,h=3,connect=3")
    first = DenseSolver(g).solve()
    assert "dense_reach" in kinds and "dense_step" in kinds
    kinds.clear()
    second = DenseSolver(g).solve()
    assert "dense_step" in kinds and "dense_reach" not in kinds
    assert second.stats["positions"] == first.stats["positions"] > 0


def test_dense_pallas_gather_parity_4x3(monkeypatch):
    """The Pallas window gather (interpret mode here) against the plain
    XLA gather on the smallest board whose gathers span several kernel
    blocks (up to 10 per step): every level table hashes the same, and no
    lane missed both views."""
    import hashlib

    def digests(result):
        return {L: hashlib.sha256(np.asarray(c).tobytes()).hexdigest()
                for L, c in result.cells.items()}

    g = get_game("connect4:w=4,h=3")
    plain = DenseSolver(g).solve()
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    pal = DenseSolver(g).solve()
    assert pal.stats["gather_mode"] == "pallas"
    assert pal.stats["gather_lanes"] == plain.stats["gather_lanes"] > 0
    assert pal.stats["gather_miss_lanes"] == 0
    assert (pal.value, pal.remoteness) == (plain.value, plain.remoteness)
    assert digests(pal) == digests(plain)


@pytest.mark.parametrize("backend,want", [("tpu", "pallas"),
                                          ("cpu", "plain")])
def test_dense_gather_default_by_platform(backend, want, monkeypatch):
    """GAMESMAN_DENSE_GATHER unset: the window gather on an accelerator,
    the plain XLA gather on the CPU."""
    import jax

    monkeypatch.delenv("GAMESMAN_DENSE_GATHER", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    g = get_game("connect4:w=3,h=3,connect=3")
    assert DenseSolver(g).gather_mode == want


@pytest.mark.slow  # pallas kernel emulated on CPU, full-solve A/B
def test_dense_pallas_gather_matches_plain(monkeypatch):
    # GAMESMAN_DENSE_GATHER=pallas routes the monotone fill through the
    # Mosaic window gather (interpret mode on CPU) with its miss fix-up;
    # every cell of every level table must match the plain-gather solve.
    # block_elems sized so the big 4x4 levels split into several blocks
    # of rounded-up cblock >= PALLAS_BLOCK (row-aligned kernel blocks)
    # while small levels keep cblock = C.
    g = get_game("connect4:w=4,h=4")
    plain = DenseSolver(g, block_elems=150_000).solve()
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    pal = DenseSolver(g, block_elems=150_000).solve()
    assert (pal.value, pal.remoteness, pal.num_positions) == (
        plain.value, plain.remoteness, plain.num_positions
    )
    for L, cells in plain.cells.items():
        np.testing.assert_array_equal(pal.cells[L], cells)


@pytest.mark.slow  # pallas int64 path emulated on CPU, full-solve A/B
def test_dense_pallas_gather_int64_flat_matches_plain(monkeypatch):
    # int64 flat index spaces (6x6+, where the gather win matters most)
    # are pallas-eligible since r5: the kernel wrapper derives
    # block-local int32 offsets outside Mosaic. A real int64 board does
    # not fit CI, so force the 6x6+ flat dtype on a 4x4 — the kernels are
    # keyed and built from _flat_dtype, so every index computation runs
    # the int64 program end to end.
    import jax.numpy as jnp

    g = get_game("connect4:w=4,h=4")
    plain = DenseSolver(g, block_elems=150_000).solve()
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    pal64 = DenseSolver(g, block_elems=150_000)
    assert pal64.gather_mode == "pallas"
    assert pal64._flat_dtype == jnp.int32  # 4x4 is natively int32
    pal64._flat_dtype = jnp.int64
    r = pal64.solve()
    assert (r.value, r.remoteness, r.num_positions) == (
        plain.value, plain.remoteness, plain.num_positions
    )
    for L, cells in plain.cells.items():
        np.testing.assert_array_equal(r.cells[L], cells)


def test_dense_blocked_levels_match_unblocked():
    # Tiny block_elems forces nblk > 1 on every non-trivial level,
    # exercising the block concat + tail-slice path end to end.
    g = get_game("connect4:w=3,h=3,connect=3")
    whole = DenseSolver(g).solve()
    blocked = DenseSolver(g, block_elems=64).solve()
    assert (blocked.value, blocked.remoteness) == (whole.value,
                                                  whole.remoteness)
    for L, cells in whole.cells.items():
        np.testing.assert_array_equal(blocked.cells[L], cells)


def test_dense_lookup_refuses_garbage_positions():
    # 3x3 connect-3, level 6 (player 1 to move): player 1 already owns all
    # of column 0 (a vertical line) — not a position; the table cell there
    # is a placeholder and lookup must refuse it rather than serve it.
    g = get_game("connect4:w=3,h=3,connect=3")
    rd = DenseSolver(g).solve()
    garbage = 0b1111 | (1 << 6) | (1 << 9)  # heights (3,2,1), p1 = col 0
    with pytest.raises(KeyError):
        rd.lookup(garbage)
    # ...while a real position at the same level still answers.
    assert rd.lookup(int(g.initial_state())) == (rd.value, rd.remoteness)


def test_dense_counts_file_roundtrip(tmp_path, monkeypatch):
    from gamesmanmpi_tpu.solve import dense as dmod

    path = tmp_path / "counts.json"
    monkeypatch.setenv("GAMESMAN_DENSE_COUNTS_FILE", str(path))
    key = (3, 3, 3)
    counts = {0: 1, 1: 3, 2: 12}
    dmod._store_cached_counts(key, counts)
    assert dmod._load_cached_counts(key) == counts
    assert dmod._load_cached_counts((9, 9, 4)) is None

    # The sidecar feeds the benchmark numerator, so records are stamped:
    # an unstamped/foreign record (old engine, hand edit) must be refused
    # and re-swept, not trusted.
    import json

    data = json.loads(path.read_text())
    tag = dmod._counts_tag(key)
    assert data[tag]["version"] == dmod._COUNTS_SCHEMA_VERSION
    assert data[tag]["board"] == tag

    for tamper in (
        {tag: {"0": 1, "1": 3}},  # pre-stamp format
        {tag: {**data[tag], "version": -1}},  # wrong engine version
        {tag: {**data[tag], "board": "9x9x9"}},  # copied entry
        {tag: {**data[tag], "counts": {"0": 2, "1": 3}}},  # bad invariant
        {tag: {**data[tag], "counts": {"99": 5, "0": 1}}},  # level > cells
    ):
        path.write_text(json.dumps(tamper))
        assert dmod._load_cached_counts(key) is None

    # Disabled cache reads/writes nothing.
    monkeypatch.setenv("GAMESMAN_DENSE_COUNTS_FILE", "0")
    assert dmod._load_cached_counts(key) is None


def test_dense_count_cached_across_instances():
    g = get_game("connect4:w=3,h=3,connect=3")
    a = DenseSolver(g).solve()
    b = DenseSolver(g).solve()
    assert b.stats["secs_count_reachable"] == 0.0  # second solve reuses it
    assert a.num_positions == b.num_positions
