"""solve/: end-to-end solves with oracle parity (SURVEY.md §4.2 axis 1).

Golden values (SURVEY.md §4.2 table): 3x3 tic-tac-toe is a TIE with
remoteness 9; normal-play Nim is a first-player WIN iff XOR of heaps != 0;
1-2-10 subtraction follows mod-3 arithmetic.
"""

import numpy as np
import pytest

from gamesmanmpi_tpu.core.values import WIN, LOSE, TIE
from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.solve import Solver, oracle_solve

from helpers import REF_GAMES, load_module, assert_table_parity, full_table


def _solve_both(spec, ref_file, **solver_kw):
    result = Solver(get_game(spec), paranoid=True, **solver_kw).solve()
    _, _, oracle_table = oracle_solve(load_module(REF_GAMES / ref_file))
    return result, oracle_table


def test_tictactoe_3x3_full_parity():
    result, oracle_table = _solve_both("tictactoe", "tictactoe.py")
    assert result.value == TIE
    assert result.remoteness == 9
    assert result.num_positions == 5478  # classic reachable count
    assert_table_parity(result, oracle_table)


def test_subtract_1210_parity_and_closed_form():
    result, oracle_table = _solve_both(
        "subtract:total=10,moves=1-2", "ten_to_zero.py"
    )
    # 10 % 3 == 1 -> first player WIN (takes 1, leaves a multiple of 3).
    assert result.value == WIN
    assert_table_parity(result, oracle_table)
    # Closed form for every position: LOSE iff pos % 3 == 0.
    for pos, (value, _) in full_table(result).items():
        assert value == (LOSE if pos % 3 == 0 else WIN)


def test_subtract_misere():
    game = get_game("subtract:total=10,moves=1-2,misere=1")
    result = Solver(game, paranoid=True).solve()
    # Misère: LOSE iff pos % 3 == 1; 10 % 3 == 1 -> first player LOSE.
    assert result.value == LOSE


def test_nim_345_parity_and_xor_rule():
    result, oracle_table = _solve_both("nim:heaps=3-4-5", "nim_345.py")
    assert result.value == WIN  # 3 ^ 4 ^ 5 == 2 != 0
    assert_table_parity(result, oracle_table)
    # XOR rule across the whole table (normal play).
    game = get_game("nim:heaps=3-4-5")
    for pos, (value, _) in full_table(result).items():
        heaps = [(pos >> (i * game.bits)) & ((1 << game.bits) - 1) for i in range(3)]
        x = heaps[0] ^ heaps[1] ^ heaps[2]
        assert value == (LOSE if x == 0 else WIN), f"XOR rule broken at {heaps}"


def test_connect4_4x4_full_parity():
    result, oracle_table = _solve_both("connect4:w=4,h=4", "connect4_4x4.py")
    assert_table_parity(result, oracle_table)


def test_result_lookup():
    result = Solver(get_game("tictactoe"), paranoid=True).solve()
    value, rem = result.lookup(result.game.initial_state())
    assert (value, rem) == (TIE, 9)
    with pytest.raises(KeyError):
        # An unreachable "position": both players on the same cell.
        result.lookup(np.uint64(1 | (1 << 9)))


def test_blocked_backward_parity():
    """Wide levels resolved in column blocks (GAMESMAN_BACKWARD_BLOCK bound)
    must produce the identical table."""
    from helpers import full_table

    base = Solver(get_game("tictactoe")).solve()
    blocked = Solver(get_game("tictactoe"), paranoid=True)
    blocked.backward_block = 256  # well below the widest level's capacity
    result = blocked.solve()
    assert full_table(result) == full_table(base)


_C4_4X4_ORACLE = []


def _c4_4x4_oracle():
    if not _C4_4X4_ORACLE:
        _, _, table = oracle_solve(load_module(REF_GAMES / "connect4_4x4.py"))
        _C4_4X4_ORACLE.append(table)
    return _C4_4X4_ORACLE[0]


@pytest.mark.parametrize("source,block,fetch", [
    ("builtin", "whole", "plain"),
    ("builtin", "blocked", "plain"),
    ("rule_file", "whole", "plain"),
    ("rule_file", "blocked", "plain"),
    ("builtin", "blocked", "pallas"),   # the window kernel, interpreted
    ("rule_file", "whole", "pallas"),
])
def test_provenance_backward_oracle_parity(monkeypatch, tmp_path, source,
                                           block, fetch):
    """The classic backward from forward provenance (the child cells
    fetched in the dedup sort's order, un-permuted by one pair sort, then
    combined) against the oracle: Connect-4 4x4 built in, and as the
    benchmark's rule file cut to 4x4; each level's combine whole, and in
    several blocks; the fetch through XLA's gather and through the Pallas
    window kernel, which must serve every lane from its views."""
    import json

    import gamesmanmpi_tpu.solve.engine as engine
    from helpers import REPO

    monkeypatch.setenv("GAMESMAN_PROVENANCE", "1")
    monkeypatch.setenv("GAMESMAN_BACKWARD_BLOCK",
                       "256" if block == "blocked" else "0")
    monkeypatch.setattr(engine, "fetch_method", lambda: fetch)
    if source == "builtin":
        game = get_game("connect4:w=4,h=4")
    else:
        import pathlib

        doc = json.loads((pathlib.Path(REPO) / "benchmarks" / "specs"
                          / "connect4_6x4.json").read_text())
        doc["name"] = "connect4_4x4_rules"
        doc["board"] = {"width": 4, "height": 4}
        path = tmp_path / "connect4_4x4_rules.json"
        path.write_text(json.dumps(doc))
        game = get_game(str(path))
    solver = Solver(game, paranoid=True)
    result = solver.solve()
    assert_table_parity(result, _c4_4x4_oracle())
    kinds = solver.dispatch_by_kind
    assert kinds.get("bwdp", 0) > 0
    assert ("bwdc" in kinds) == (block == "blocked")
    assert result.stats["gather_lanes"] > 0
    assert result.stats["gather_miss_lanes"] == 0


def test_chomp_parity_and_strategy_stealing():
    """Chomp 3x3: full-table oracle parity; every board >1x1 is a
    first-player WIN (strategy stealing), the closed-form anchor."""
    result, oracle_table = _solve_both("chomp:w=3,h=3", "chomp_33.py")
    assert result.value == WIN
    assert_table_parity(result, oracle_table)


def test_chomp_boards_win_and_1x1_loses():
    assert Solver(get_game("chomp:w=4,h=3")).solve().value == WIN
    assert Solver(get_game("chomp:w=2,h=2")).solve().value == WIN
    # 1x1 is the poison-only position itself: primitive LOSE, remoteness 0.
    r = Solver(get_game("chomp:w=1,h=1")).solve()
    assert r.value == LOSE and r.remoteness == 0


def test_store_tables_false_root_only():
    """Big-run mode: same root answer and position count, only the root
    level materialized (fast and generic paths)."""
    for spec in ("tictactoe", "subtract:total=10,moves=1-2"):
        full = Solver(get_game(spec)).solve()
        lean = Solver(get_game(spec), store_tables=False).solve()
        assert (lean.value, lean.remoteness) == (full.value, full.remoteness)
        assert lean.num_positions == full.num_positions
        assert len(lean.levels) == 1  # root only


def test_platform_conditional_paths_parity(monkeypatch):
    """The platform-auto lowerings (provenance forward + speculation,
    searchsorted method, dedup compaction) resolve differently on CPU vs
    accelerator; on the CPU suite the accelerator-default side would
    otherwise go untested end to end. Force each non-default side and
    assert full-table parity with the default solve."""
    from helpers import full_table

    g = "connect4:w=4,h=3"
    base = Solver(get_game(g), paranoid=True).solve()
    base_tab = full_table(base)

    forced = {
        "GAMESMAN_PROVENANCE": "1",   # TPU default: provenance forward
        "GAMESMAN_SPECULATE": "1",    # TPU default: speculative dispatch
        "GAMESMAN_SEARCH": "sort",    # TPU default: sort-merge join lookup
        "GAMESMAN_COMPACT": "resort", # TPU default: re-sort compaction
    }
    for var, val in forced.items():
        monkeypatch.setenv(var, val)
        r = Solver(get_game(g), paranoid=True).solve()
        assert (r.value, r.remoteness) == (base.value, base.remoteness), var
        assert full_table(r) == base_tab, var
        monkeypatch.delenv(var)
    # All four at once (the exact accelerator configuration).
    for var, val in forced.items():
        monkeypatch.setenv(var, val)
    r = Solver(get_game(g), paranoid=True).solve()
    assert (r.value, r.remoteness) == (base.value, base.remoteness)
    assert full_table(r) == base_tab


def test_dispatch_counter_registry_series():
    """gamesman_dispatches_total{phase} grows with a solve."""
    from gamesmanmpi_tpu.obs import default_registry

    reg = default_registry()
    game = get_game("tictactoe")
    before = reg.counter(
        "gamesman_dispatches_total",
        phase="forward", game=game.name,
    ).value
    Solver(game).solve()
    after = reg.counter(
        "gamesman_dispatches_total",
        phase="forward", game=game.name,
    ).value
    assert after > before


# The exact lowerings an accelerator resolves (platform_auto_flag/_bool):
# provenance forward + gather-only backward, speculative dispatch, the
# sort-merge join and the re-sort compaction.
ACCEL_CONFIG = {
    "GAMESMAN_PROVENANCE": "1",
    "GAMESMAN_SPECULATE": "1",
    "GAMESMAN_SEARCH": "sort",
    "GAMESMAN_COMPACT": "resort",
}

needs_mesh = pytest.mark.skipif(
    len(__import__("jax").devices()) < 8, reason="needs 8 (fake) devices"
)


def _accel_solver(kind, spec, tmp_path):
    """-> (spec, thunk running the accelerator-configured solve)."""
    import pathlib

    from helpers import REPO

    if kind == "gamedsl":
        spec = str(pathlib.Path(REPO) / spec)
    if kind == "sharded":
        from gamesmanmpi_tpu.parallel import ShardedSolver

        return spec, lambda: ShardedSolver(get_game(spec), num_shards=4,
                                           paranoid=True).solve()
    if kind == "resume":
        from gamesmanmpi_tpu.utils.checkpoint import LevelCheckpointer

        def run():
            ck = str(tmp_path / "ck")
            Solver(get_game(spec), paranoid=True,
                   checkpointer=LevelCheckpointer(ck)).solve()
            # Every level sealed: the second run must load, not resolve.
            resumed = Solver(get_game(spec), paranoid=True,
                             checkpointer=LevelCheckpointer(ck))
            result = resumed.solve()
            assert not {"fwdp", "bwdp", "bwd"} & set(resumed.dispatch_by_kind)
            return result

        return spec, run
    return spec, lambda: Solver(get_game(spec), paranoid=True).solve()


@pytest.mark.parametrize("kind,spec,backward", [
    ("classic", "tictactoe", None),               # dihedral symmetry
    ("classic", "connect4:w=4,h=4", None),
    ("classic", "connect4:w=4,h=3,sym=1", None),  # mirror canonicalize
    ("classic", "nim:heaps=3-4-5", None),         # generic path
    ("classic", "chomp:w=3,h=3", None),           # generic, widest moves
    ("gamedsl", "examples/specs/gomoku_4x3x3.json", None),
    ("gamedsl", "examples/specs/mnk_3x3x3_misere.json", None),
    ("gamedsl", "examples/specs/connect4_4x4.json", None),
    pytest.param("sharded", "connect4:w=4,h=3", "lookup", marks=needs_mesh),
    pytest.param("sharded", "connect4:w=4,h=3", "edges", marks=needs_mesh),
    pytest.param("sharded", "nim:heaps=3-4-5", "lookup", marks=needs_mesh),
    pytest.param("sharded", "nim:heaps=3-4-5", "edges", marks=needs_mesh),
    ("resume", "connect4:w=4,h=3", None),         # checkpoint, then resume
])
def test_accelerator_configuration_parity(monkeypatch, tmp_path, kind,
                                          spec, backward):
    """Every engine path under the accelerator's lowerings, all at once,
    against the CPU default solve's full table: the path the chip runs,
    on every game family, the sharded engine in both backward modes and a
    checkpoint resume."""
    spec, run = _accel_solver(kind, spec, tmp_path)
    base = Solver(get_game(spec), paranoid=True).solve()
    for var, val in ACCEL_CONFIG.items():
        monkeypatch.setenv(var, val)
    if backward is not None:
        monkeypatch.setenv("GAMESMAN_BACKWARD", backward)
    r = run()
    assert (r.value, r.remoteness) == (base.value, base.remoteness)
    assert r.num_positions == base.num_positions
    assert full_table(r) == full_table(base)


def test_connect4_bitboard_solve_parity(monkeypatch):
    """End-to-end: bitboard on/off produce identical full tables (the
    flag is part of cache_key, so kernels cannot cross-contaminate)."""
    base = Solver(get_game("connect4:w=4,h=3"), paranoid=True).solve()
    monkeypatch.setenv("GAMESMAN_C4_BITBOARD", "0")
    loop = Solver(get_game("connect4:w=4,h=3"), paranoid=True).solve()
    assert full_table(loop) == full_table(base)
