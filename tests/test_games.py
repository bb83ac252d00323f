"""games/: tensorized kernels vs scalar reference-style modules.

Per-move parity (SURVEY.md §4.2): for random reachable positions, the batched
expand/primitive must agree exactly with the scalar module of identical
packing in examples/ref_games/.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.solve.oracle import normalize_value

from helpers import REF_GAMES, load_module

# Smoke tier: fast, compile-light, single-process-safe (see pyproject).
pytestmark = pytest.mark.smoke


def _random_walk_positions(module, rng, n_walks=60):
    """Sample reachable positions by random playouts of the scalar module."""
    seen = {module.initial_position}
    for _ in range(n_walks):
        pos = module.initial_position
        while True:
            if normalize_value(module.primitive(pos)) != 0:
                break
            moves = list(
                getattr(module, "gen_moves", getattr(module, "generate_moves", None))(
                    pos
                )
            )
            if not moves:
                break
            pos = module.do_move(pos, moves[rng.integers(len(moves))])
            seen.add(pos)
    return sorted(seen)


CASES = [
    ("tictactoe", "tictactoe.py"),
    ("subtract:total=10,moves=1-2", "ten_to_zero.py"),
    ("nim:heaps=3-4-5", "nim_345.py"),
    ("connect4:w=4,h=4", "connect4_4x4.py"),
    ("chomp:w=3,h=3", "chomp_33.py"),
]


@pytest.mark.parametrize("spec,ref_file", CASES)
def test_expand_primitive_parity(spec, ref_file):
    game = get_game(spec)
    module = load_module(REF_GAMES / ref_file)
    rng = np.random.default_rng(42)
    positions = _random_walk_positions(module, rng)
    states = jnp.asarray(np.array(positions, dtype=np.uint64))

    children, mask = game.expand(states)
    prim = game.primitive(states)
    children = np.asarray(children)
    mask = np.asarray(mask)
    prim = np.asarray(prim)

    gen = getattr(module, "gen_moves", None) or module.generate_moves
    for i, pos in enumerate(positions):
        expected_prim = normalize_value(module.primitive(pos))
        assert prim[i] == expected_prim, f"primitive mismatch at {pos:#x}"
        expected_children = sorted(module.do_move(pos, m) for m in gen(pos))
        got = sorted(int(c) for c, ok in zip(children[i], mask[i]) if ok)
        assert got == expected_children, f"expand mismatch at {pos:#x}"


@pytest.mark.parametrize("spec,ref_file", CASES)
def test_initial_state_matches(spec, ref_file):
    game = get_game(spec)
    module = load_module(REF_GAMES / ref_file)
    assert int(game.initial_state()) == int(module.initial_position)


@pytest.mark.parametrize("spec,ref_file", CASES)
def test_level_function_is_topological(spec, ref_file):
    """Every move strictly raises level_of by at most max_level_jump."""
    game = get_game(spec)
    module = load_module(REF_GAMES / ref_file)
    rng = np.random.default_rng(7)
    positions = _random_walk_positions(module, rng, n_walks=30)
    states = jnp.asarray(np.array(positions, dtype=np.uint64))
    levels = np.asarray(game.level_of(states))
    children, mask = game.expand(states)
    child_levels = np.asarray(game.level_of(children.reshape(-1))).reshape(mask.shape)
    mask = np.asarray(mask)
    prim = np.asarray(game.primitive(states))
    for i in range(len(positions)):
        if prim[i] != 0:
            continue
        for j in range(mask.shape[1]):
            if mask[i, j]:
                jump = child_levels[i, j] - levels[i]
                assert 1 <= jump <= game.max_level_jump


def test_connect4_describe_and_moves():
    game = get_game("connect4:w=4,h=4")
    s = game.initial_state()
    states = jnp.asarray(np.array([s], dtype=np.uint64))
    children, mask = game.expand(states)
    assert np.asarray(mask).all()  # all 4 columns open
    # One move fills one cell.
    levels = np.asarray(game.level_of(children[0]))
    assert (levels == 1).all()


def test_mnk_444_forward_smoke():
    """BASELINE config #2 (4x4 tictactoe / mnk(4,4,4)): the vmapped move-gen
    kernel compiles and expands correctly on the bigger board (full solve is
    exercised on TPU via the bench ladder, not in CI)."""
    game = get_game("tictactoe:m=4,n=4,k=4")
    s = game.initial_state()
    states = jnp.asarray(np.array([s], dtype=np.uint64))
    children, mask = game.expand(states)
    assert int(np.asarray(mask).sum()) == 16  # 16 opening moves
    levels = np.asarray(game.level_of(children[0]))
    assert (levels == 1).all()
    prim = np.asarray(game.primitive(children[0]))
    assert (prim == 0).all()  # no opening move ends the game


@pytest.mark.parametrize("wh", [(4, 4), (5, 4), (7, 6)])
def test_connect4_bitboard_decompose_parity(wh):
    """The whole-word masked-smear decompose must be bit-identical to the
    per-column msb loop on every REACHABLE state shape (random playouts;
    garbage lanes are out of contract — the engines mask them)."""
    w, h = wh
    game = get_game(f"connect4:w={w},h={h}")
    rng = np.random.default_rng(3)
    states = [int(game.initial_state())]
    frontier = [int(game.initial_state())]
    for _ in range(min(w * h, 12)):
        batch = np.asarray(frontier, dtype=game.state_dtype)
        kids, mask = jax.jit(game.expand)(jnp.asarray(batch))
        kids, mask = np.asarray(kids), np.asarray(mask)
        nxt = list(np.unique(kids[mask]))
        if not nxt:
            break
        rng.shuffle(nxt)
        frontier = nxt[:256]
        states.extend(frontier)
    batch = jnp.asarray(np.asarray(states, dtype=game.state_dtype))
    fast = jax.jit(game._decompose)(batch)
    ref = jax.jit(game._decompose_loop)(batch)
    for a, b, name in zip(fast, ref,
                          ("guards", "filled", "current", "opponent")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
