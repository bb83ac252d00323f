"""Compile-only tests against the TPU v5e compiler, with no chip attached.

The TPU compiler is installed here and compiles for a described v5e:2x2
topology: what it refuses here would be refused on the chip, at no chip
time. Nothing runs, so these say nothing about results or speed.

The accelerator-only lowerings are chosen explicitly (monkeypatched env
knobs): code that asks ``jax.default_backend()`` still sees the CPU here.
The topology is described inside a fixture, never at import: only one
process may load the TPU runtime, and every xdist worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from gamesmanmpi_tpu.games import get_game

# The lowerings platform_auto_flag/_bool pick on an accelerator.
_ACCEL_KNOBS = {
    "GAMESMAN_COMPACT": "resort",
    "GAMESMAN_SEARCH": "sort",
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from gamesmanmpi_tpu.parallel import make_mesh

    return make_mesh(4, devices=list(topo.devices))


@pytest.fixture
def accel_knobs(monkeypatch):
    for k, v in _ACCEL_KNOBS.items():
        monkeypatch.setenv(k, v)


@pytest.mark.parametrize("spec,dtype", [
    ("connect4:w=6,h=4", "uint32"),
    ("connect4:w=7,h=6", "uint64"),
])
def test_classic_expand_compiles(spec, dtype, one_chip, accel_knobs):
    """The classic engine's accelerator forward (provenance expand +
    dedup) at the 65536 minimum bucket it uses on the chip."""
    from gamesmanmpi_tpu.solve import Solver

    game = get_game(spec)
    assert jnp.dtype(game.state_dtype) == jnp.dtype(dtype)
    fn = Solver._fwdp_builder(game)
    states = jax.ShapeDtypeStruct((65536,), game.state_dtype,
                                  sharding=one_chip)
    compiled = jax.jit(fn).lower(states).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.parametrize("kind", ["bwdp", "bwdg"])
def test_classic_provenance_backward_compiles(kind, one_chip, monkeypatch):
    """The classic engine's accelerator backward from provenance for 6x4
    at the 65536 minimum bucket: the sorted-order fetch through the Pallas
    window kernel (a Mosaic custom call), then the un-permute sort, whole
    (bwdp) or as a wide level's fetch alone (bwdg)."""
    import numpy as np

    from gamesmanmpi_tpu.solve import Solver

    game = get_game("connect4:w=6,h=4")
    cap, M = 65536, game.max_moves
    # fetch_method reads the default backend when the builder runs.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    builder = {"bwdp": Solver._bwdp_builder,
               "bwdg": Solver._bwdg_builder}[kind]
    fn = builder(game)
    fetch = [((cap * M,), np.int32), ((), np.int32), ((cap,), np.uint8),
             ((cap,), np.int32)]
    shapes = ([((), np.int32), ((cap,), np.uint8)] if kind == "bwdp"
              else []) + fetch
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_dense_step(level, gather_mode, one_chip, monkeypatch):
    from gamesmanmpi_tpu.solve.dense import DenseSolver, build_dense_step

    ds = DenseSolver(get_game("connect4:w=6,h=4"), store_tables=False)
    ds.gather_mode = gather_mode
    cblock, _ = ds._cblock(level)
    # build_dense_step interprets a Pallas kernel when the default
    # backend is the CPU, as it is here: build the step as the TPU would.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = build_dense_step(ds.tables, level, cblock, ds._rank_dtype,
                          ds._flat_dtype, gather_mode=gather_mode)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
             for a in ds._avals(level, cblock, for_reach=False)]
    return jax.jit(fn).lower(*avals).compile()


def test_dense_mid_level_step_compiles(one_chip, monkeypatch):
    """One mid-level backward kernel of the dense engine for 6x4 with the
    plain XLA gather."""
    _compile_dense_step(12, "plain", one_chip, monkeypatch)


@pytest.mark.parametrize("level", [12, 23])
def test_dense_pallas_step_compiles(level, one_chip, monkeypatch):
    """The dense backward step --engine auto runs on the TPU: 6x4's middle
    level and level 23 (the widest colex jumps) with the Pallas window
    gather, which must be in the program as a Mosaic custom call."""
    compiled = _compile_dense_step(level, "pallas", one_chip, monkeypatch)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_forward_step_compiles_with_all_to_all(mesh4, accel_knobs):
    """The owner-routed forward step on a 4-chip mesh: the compiler keeps
    the all_to_all that routes each child to its owner shard."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    game = get_game("connect4:w=5,h=5")
    solver = ShardedSolver(game, mesh=mesh4, min_bucket=64)
    cap = 1024
    states = jax.ShapeDtypeStruct((4, cap), game.state_dtype,
                                  sharding=NamedSharding(mesh4, P("shards")))
    compiled = jax.jit(solver._forward_fn(cap, cap)).lower(states).compile()
    assert "all-to-all" in compiled.as_text()


@pytest.mark.parametrize("idx_dtype", ["int32", "int64"])
def test_pallas_monotone_gather_compiles(idx_dtype, one_chip):
    from gamesmanmpi_tpu.ops.pallas_gather import monotone_window_gather

    table = jax.ShapeDtypeStruct((1 << 22,), jnp.uint8, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((1 << 20,), jnp.dtype(idx_dtype),
                               sharding=one_chip)
    compiled = jax.jit(monotone_window_gather).lower(table, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
