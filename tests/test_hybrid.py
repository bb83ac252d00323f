"""Hybrid dense/BFS engine: bit-identical to both component engines.

The hybrid is pure implementation strategy (SURVEY.md §1's contract is
value+remoteness of every reachable position); these tests pin it to the
classic solver's full tables across cutover placements, including the
degenerate ends where one engine does almost all the work.
"""

import numpy as np
import pytest

from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.solve.hybrid import HybridSolver, default_cutover


def _full_parity(spec: str, cutovers):
    g = get_game(spec)
    ref = Solver(g).solve()
    for K in cutovers:
        hy = HybridSolver(get_game(spec), cutover=K).solve()
        assert (hy.value, hy.remoteness) == (ref.value, ref.remoteness), K
        # Reachable count must match the BFS discovery exactly (the dense
        # sweep and the BFS frontier are cross-checked inside solve too).
        assert hy.num_positions == ref.num_positions, K
        for level, table in ref.levels.items():
            for i in range(table.states.shape[0]):
                s = int(table.states[i])
                assert hy.lookup(s) == (
                    int(table.values[i]), int(table.remoteness[i])
                ), (K, level, hex(s))


def test_hybrid_full_parity_3x3c3():
    # Cutovers spanning the whole range: K=0 (dense solves only the empty
    # board), the default, and K=ncells-1 (BFS solves only the full level).
    _full_parity("connect4:w=3,h=3,connect=3", (0, 3, default_cutover(9), 8))


@pytest.mark.slow  # ~64 s CPU full-board parity; 3x3c3 covers the seam fast
def test_hybrid_full_parity_4x3():
    _full_parity("connect4:w=4,h=3", (5, 8))


def test_hybrid_reports_pallas_gather_lanes(monkeypatch):
    """The dense levels below the cutover run the Pallas window gather
    (interpret mode here), exact against the plain gather, and the done
    record counts their lanes; a second solve by the same solver reports
    the same counts, not a running total."""
    spec = "connect4:w=4,h=3"
    plain = HybridSolver(get_game(spec), cutover=6).solve()
    monkeypatch.setenv("GAMESMAN_DENSE_GATHER", "pallas")
    hs = HybridSolver(get_game(spec), cutover=6)
    first = hs.solve()
    second = hs.solve()
    assert plain.stats["gather_miss_lanes"] == 0
    for run in (first, second):
        assert (run.value, run.remoteness) == (plain.value, plain.remoteness)
        assert run.stats["gather_lanes"] == plain.stats["gather_lanes"] > 0
        assert (run.stats["gather_miss_lanes"]
                == first.stats["gather_miss_lanes"])
        for L, cells in plain.cells.items():
            np.testing.assert_array_equal(run.cells[L], cells)


def test_hybrid_validates_args():
    g4 = get_game("connect4:w=3,h=3,connect=3")
    with pytest.raises(ValueError, match="cutover"):
        HybridSolver(g4, cutover=9)  # == ncells: no BFS region
    with pytest.raises(ValueError, match="cutover"):
        HybridSolver(g4, cutover=-1)
    with pytest.raises(TypeError):
        HybridSolver(get_game("tictactoe"))


def test_hybrid_sym_parity_3x3c3():
    """sym=1 (VERDICT r4 #4): the BFS region keeps the mirror reduction,
    the dense region indexes the full space through a sym-free twin, and
    the seam canonicalizes both directions. Root must match the classic
    sym solve; every reachable position — BOTH members of each mirror
    class, ground truth from the full non-sym solve — must answer."""
    spec = "connect4:w=3,h=3,connect=3"
    ref = Solver(get_game(spec + ",sym=1")).solve()
    plain = Solver(get_game(spec)).solve()
    for K in (0, 3, default_cutover(9), 8):
        hy = HybridSolver(get_game(spec + ",sym=1"), cutover=K).solve()
        assert (hy.value, hy.remoteness) == (ref.value, ref.remoteness), K
        # Region accounting: dense counts the FULL reachable set (its
        # indexing cannot skip mirror duplicates), BFS representatives.
        assert hy.stats["positions_dense_region"] == sum(
            plain.levels[L].states.shape[0]
            for L in plain.levels if L <= K
        ), K
        assert hy.stats["positions_bfs_region"] == sum(
            ref.levels[L].states.shape[0] for L in ref.levels if L > K
        ), K
        for level, table in plain.levels.items():
            for i in range(table.states.shape[0]):
                s = int(table.states[i])
                assert hy.lookup(s) == (
                    int(table.values[i]), int(table.remoteness[i])
                ), (K, level, hex(s))


def test_hybrid_sym_sharded_bfs():
    """sym=1 with devices>1: the mirror-reduced BFS region rides the
    owner-routed ShardedSolver — the exact composition the v4-16 6x6
    plan costs out (sym on the sharded BFS side)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    ref = Solver(get_game("connect4:w=3,h=3,connect=3,sym=1")).solve()
    hy = HybridSolver(get_game("connect4:w=3,h=3,connect=3,sym=1"),
                      cutover=4, devices=4).solve()
    assert (hy.value, hy.remoteness) == (ref.value, ref.remoteness)
    for level, table in ref.levels.items():
        for i in range(table.states.shape[0]):
            s = int(table.states[i])
            assert hy.lookup(s) == (
                int(table.values[i]), int(table.remoteness[i])
            ), (level, hex(s))


def test_hybrid_env_cutover(monkeypatch):
    monkeypatch.setenv("GAMESMAN_HYBRID_CUTOVER", "4")
    hy = HybridSolver(get_game("connect4:w=3,h=3,connect=3"))
    assert hy.cutover == 4


def test_hybrid_no_tables_root_only():
    g = get_game("connect4:w=3,h=3,connect=3")
    ref = Solver(g).solve()
    hy = HybridSolver(g, store_tables=False, cutover=5).solve()
    assert (hy.value, hy.remoteness, hy.num_positions) == (
        ref.value, ref.remoteness, ref.num_positions
    )
    with pytest.raises(KeyError):
        hy.lookup(int(g.initial_state()))


def test_hybrid_garbage_lookup_refused():
    """Dense-side lookup refuses the fabricated mover-already-won class,
    exactly like DenseSolveResult.lookup."""
    g = get_game("connect4:w=3,h=3,connect=3")
    hy = HybridSolver(g, cutover=6).solve()
    # Level 6, heights (3,2,1): the player to move (p1, 3 stones) owns all
    # of column 0 — a completed vertical line of their own, so this cell
    # is a fabricated terminal, never a position.
    h1 = 4
    guards = (1 << 3) | (1 << (h1 + 2)) | (1 << (2 * h1 + 1))
    current = 0b111  # the mover's own completed line in column 0
    with pytest.raises(KeyError, match="line"):
        hy.lookup(guards | current)


def test_cli_engine_hybrid(capsys):
    from gamesmanmpi_tpu.cli import main as cli_main

    rc = cli_main(["connect4:w=3,h=3,connect=3", "--engine", "hybrid",
                   "--hybrid-cutover", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "positions: 694" in out
    assert "value: TIE" in out

    # Eligibility errors mirror the dense engine's.
    rc = cli_main(["tictactoe", "--engine", "hybrid"])
    assert rc == 2


def test_cli_engine_hybrid_sym(capsys):
    """sym=1 is hybrid-eligible at the CLI (r5): the mirror-reduced BFS
    region rides behind the same flag surface."""
    from gamesmanmpi_tpu.cli import main as cli_main

    rc = cli_main(["connect4:w=3,h=3,connect=3,sym=1", "--engine",
                   "hybrid", "--hybrid-cutover", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "value: TIE" in out
    assert "remoteness: 9" in out
    # 453 = full-space dense levels 0..5 + mirror representatives 6..9:
    # the mixed count unique to THIS composition (non-sym hybrid/classic
    # print 694), so a CLI regression silently dropping sym, or an
    # engine fallback that still exits 0, cannot pass on TIE/r9 alone.
    assert "positions: 453" in out


def test_cli_hybrid_bad_cutover_exits_cleanly(capsys, monkeypatch):
    from gamesmanmpi_tpu.cli import main as cli_main

    rc = cli_main(["connect4:w=3,h=3,connect=3", "--engine", "hybrid",
                   "--hybrid-cutover", "99"])
    assert rc == 2
    assert "cutover" in capsys.readouterr().err

    monkeypatch.setenv("GAMESMAN_HYBRID_CUTOVER", "24k")
    rc = cli_main(["connect4:w=3,h=3,connect=3", "--engine", "hybrid"])
    assert rc == 2
    assert "not an integer" in capsys.readouterr().err


def test_hybrid_sharded_bfs_parity():
    """devices>1 routes the BFS region through the owner-routed
    ShardedSolver on the fake mesh; the result must be bit-identical to
    the single-device hybrid and the classic solver."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    g = get_game("connect4:w=3,h=3,connect=3")
    ref = Solver(g).solve()
    hy = HybridSolver(g, cutover=4, devices=4).solve()
    assert (hy.value, hy.remoteness) == (ref.value, ref.remoteness)
    assert hy.num_positions == ref.num_positions
    for level, table in ref.levels.items():
        for i in range(table.states.shape[0]):
            s = int(table.states[i])
            assert hy.lookup(s) == (
                int(table.values[i]), int(table.remoteness[i])
            ), (level, hex(s))


def test_hybrid_sharded_no_tables():
    """Big-run sharded hybrid: only the boundary table materializes (the
    seam needs it); the result still answers root + counts exactly."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    g = get_game("connect4:w=3,h=3,connect=3")
    hy = HybridSolver(g, cutover=4, devices=4, store_tables=False).solve()
    assert (hy.value, hy.remoteness, hy.num_positions) == (3, 9, 694)
    with pytest.raises(KeyError):
        hy.lookup(int(g.initial_state()))


def test_hybrid_streamed_boundary_parity(monkeypatch):
    """Forcing the boundary table out of residency must stream it through
    the join in blocks with bit-identical results (the mechanism that
    decouples the seam's HBM need from reachable(B) on giant boards)."""
    g = get_game("connect4:w=4,h=3")
    ref = Solver(g).solve()
    monkeypatch.setenv("GAMESMAN_HYBRID_RESIDENT_MB", "0")
    monkeypatch.setenv("GAMESMAN_HYBRID_WBLOCK", "256")
    hy_solver = HybridSolver(get_game("connect4:w=4,h=3"), cutover=6)
    hy = hy_solver.solve()
    assert hy_solver.boundary_stream_blocks > 1  # streaming really engaged
    assert (hy.value, hy.remoteness) == (ref.value, ref.remoteness)
    assert hy.num_positions == ref.num_positions
    for level, table in ref.levels.items():
        for i in range(table.states.shape[0]):
            s = int(table.states[i])
            assert hy.lookup(s) == (
                int(table.values[i]), int(table.remoteness[i])
            ), (level, hex(s))


def test_hybrid_bad_capacity_knobs_fail_fast(monkeypatch):
    """Boundary-join capacity typos must fail at construction with a
    clear message, not hours later when the join finally reads them."""
    monkeypatch.setenv("GAMESMAN_HYBRID_RESIDENT_MB", "2g")
    with pytest.raises(ValueError, match="not an integer"):
        HybridSolver(get_game("connect4:w=3,h=3,connect=3"), cutover=4)
    monkeypatch.delenv("GAMESMAN_HYBRID_RESIDENT_MB")
    monkeypatch.setenv("GAMESMAN_HYBRID_WBLOCK", "4M")
    with pytest.raises(ValueError, match="not an integer"):
        HybridSolver(get_game("connect4:w=3,h=3,connect=3"), cutover=4)
