"""utils/: metrics JSONL, checkpoint/resume, profiler hook, CLI."""

import json

import numpy as np
import pytest

from gamesmanmpi_tpu.cli import main as cli_main
from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.utils import JsonlLogger, LevelCheckpointer, maybe_profile
from gamesmanmpi_tpu.utils.checkpoint import save_result_npz

from helpers import REF_GAMES


def test_jsonl_logger_and_solver_records(tmp_path):
    path = tmp_path / "metrics.jsonl"
    logger = JsonlLogger(str(path))
    result = Solver(get_game("subtract:total=10,moves=1-2"), logger=logger).solve()
    logger.close()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    phases = {r["phase"] for r in records}
    assert {"forward", "backward", "done"} <= phases
    done = [r for r in records if r["phase"] == "done"][0]
    assert done["positions"] == result.num_positions
    assert done["positions_per_sec"] > 0


def test_checkpoint_roundtrip(tmp_path):
    ckpt = LevelCheckpointer(str(tmp_path / "ckpt"))
    result = Solver(get_game("tictactoe"), checkpointer=ckpt).solve()
    assert sorted(ckpt.completed_levels()) == sorted(result.levels.keys())
    for level, table in result.levels.items():
        loaded = ckpt.load_level(level)
        assert (loaded.states == table.states).all()
        assert (loaded.values == table.values).all()
        assert (loaded.remoteness == table.remoteness).all()


def test_save_result_npz(tmp_path):
    result = Solver(get_game("subtract:total=10,moves=1-2")).solve()
    out = tmp_path / "table.npz"
    save_result_npz(str(out), result)
    with np.load(out) as z:
        names = set(z.files)
    assert any(n.startswith("states_") for n in names)
    assert any(n.startswith("cells_") for n in names)


def test_maybe_profile_noop_and_trace(tmp_path):
    with maybe_profile(None):
        pass
    with maybe_profile(str(tmp_path / "trace")):
        Solver(get_game("subtract:total=5,moves=1-2")).solve()
    assert any((tmp_path / "trace").iterdir())


def test_cli_builtin_game(capsys):
    rc = cli_main(["tictactoe"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "value: TIE" in out
    assert "remoteness: 9" in out
    assert "positions: 5478" in out


def test_cli_sharded(capsys):
    rc = cli_main(["subtract:total=10,moves=1-2", "--devices", "4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "value: WIN" in out


def test_cli_compat_module(capsys):
    rc = cli_main([str(REF_GAMES / "tictactoe.py")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "value: TIE" in out
    assert "remoteness: 9" in out


def test_checkpoint_resume_skips_recompute(tmp_path):
    """Restart-from-level: a resumed solve must not re-expand or re-resolve."""
    d = str(tmp_path / "resume")
    first = Solver(get_game("tictactoe"), checkpointer=LevelCheckpointer(d)).solve()
    resumed_solver = Solver(
        get_game("tictactoe"), checkpointer=LevelCheckpointer(d)
    )
    # Poison the compute paths: resume must never touch them.
    def _poisoned(*a, **k):
        raise AssertionError("resume recomputed a level")

    resumed_solver._fwdp = _poisoned
    resumed_solver._fwd_generic = _poisoned
    resumed_solver._bwd = _poisoned
    resumed_solver._bwdp = _poisoned
    resumed = resumed_solver.solve()
    assert resumed.value == first.value
    assert resumed.remoteness == first.remoteness
    assert resumed.num_positions == first.num_positions


def test_manifest_writes_are_atomic(tmp_path):
    """A peer process may read the manifest while process 0 seals levels;
    every read must parse (old or new content, never torn). The
    truncate-in-place write this replaces crashed a two-process run with
    JSONDecodeError mid-seal (round 4). Threads stand in for processes —
    same file, same syscalls."""
    import threading

    ckpt = LevelCheckpointer(str(tmp_path / "atomic"))
    errors = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                ckpt.load_manifest()
            except Exception as e:  # torn read
                errors.append(e)
                return

    t = threading.Thread(target=reader)
    t.start()
    try:
        for i in range(300):
            ckpt.finish_forward_level(i, 4)
    finally:
        stop.set()
        t.join()
    assert not errors
    assert len(ckpt.load_manifest()["forward_level_shards"]) == 300
    # No temp files left behind.
    assert not list((tmp_path / "atomic").glob("*.tmp"))


def test_forward_checkpoint_resume_mid_forward(tmp_path):
    """A run killed mid-DISCOVERY resumes from the deepest saved frontier.

    Forward alone is a multi-hour phase at big-board scale — it can
    outlast the host it runs on — so frontiers are checkpointed per level
    as discovered, not only after the sweep completes (the r04 gap the 6x6
    feasibility analysis named). The kill lands after level 3's save; the
    resumed run must re-expand only from level 3 down and still match the
    uncheckpointed solve exactly.
    """
    d = str(tmp_path / "fwd_resume")
    full = Solver(get_game("tictactoe")).solve()

    class _Die(Exception):
        pass

    ckpt = LevelCheckpointer(d)
    orig = LevelCheckpointer.save_frontier_level

    def dying(level, states):
        orig(ckpt, level, states)
        if level >= 3:
            raise _Die()

    ckpt.save_frontier_level = dying
    with pytest.raises(_Die):
        Solver(get_game("tictactoe"), checkpointer=ckpt).solve()
    assert LevelCheckpointer(d).load_manifest()["forward_levels"] == [0, 1, 2, 3]

    resumed_ckpt = LevelCheckpointer(d)
    saved_during_resume = []

    def recording(level, states):
        saved_during_resume.append(level)
        orig(resumed_ckpt, level, states)

    resumed_ckpt.save_frontier_level = recording
    resumed = Solver(get_game("tictactoe"), checkpointer=resumed_ckpt).solve()
    # Levels 0-3 came from disk: only 4+ are newly discovered and saved.
    assert saved_during_resume and min(saved_during_resume) == 4
    assert (resumed.value, resumed.remoteness) == (full.value, full.remoteness)
    assert resumed.num_positions == full.num_positions
    for level, table in full.levels.items():
        rt = resumed.levels[level]
        assert (rt.states == table.states).all()
        assert (rt.values == table.values).all()
        assert (rt.remoteness == table.remoteness).all()
    # A third run resumes the COMPLETED forward without any discovery.
    assert LevelCheckpointer(d).load_frontiers() is not None


def test_sharded_forward_checkpoint_resume_mid_forward(tmp_path):
    """Sharded analog of the mid-discovery resume: per-(level, shard)
    frontier files keep the prefix; completion consolidates into the
    per-shard snapshot and drops the now-redundant incremental files."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    from gamesmanmpi_tpu.parallel import ShardedSolver

    d = str(tmp_path / "fwd_resume_sharded")
    full = ShardedSolver(get_game("tictactoe"), num_shards=4).solve()

    class _Die(Exception):
        pass

    ckpt = LevelCheckpointer(d)
    orig = LevelCheckpointer.save_forward_level_shard

    def dying(level, shard, states):
        orig(ckpt, level, shard, states)
        # Level 3's files all land but the level is never SEALED — the
        # resume must treat it as absent and re-expand from level 2.
        if level >= 3 and shard == 3:
            raise _Die()

    ckpt.save_forward_level_shard = dying
    with pytest.raises(_Die):
        ShardedSolver(get_game("tictactoe"), num_shards=4,
                      checkpointer=ckpt).solve()
    sealed = LevelCheckpointer(d).load_manifest()["forward_level_shards"]
    assert sorted(int(k) for k in sealed) == [0, 1, 2]

    resumed_ckpt = LevelCheckpointer(d)
    saved_during_resume = []

    def recording(level, shard, states):
        saved_during_resume.append(level)
        orig(resumed_ckpt, level, shard, states)

    resumed_ckpt.save_forward_level_shard = recording
    resumed = ShardedSolver(get_game("tictactoe"), num_shards=4,
                            checkpointer=resumed_ckpt).solve()
    assert saved_during_resume and min(saved_during_resume) == 3
    assert (resumed.value, resumed.remoteness) == (full.value, full.remoteness)
    assert resumed.num_positions == full.num_positions
    # Completion consolidated the snapshot and dropped the incrementals.
    manifest = LevelCheckpointer(d).load_manifest()
    assert manifest.get("frontier_shards") == 4
    assert "forward_level_shards" not in manifest
    import os as _os

    assert not [f for f in _os.listdir(d) if f.startswith("frontier_")]


def test_checkpoint_resume_sharded(tmp_path):
    import jax

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs 4 fake devices")
    from gamesmanmpi_tpu.parallel import ShardedSolver

    d = str(tmp_path / "resume_sharded")
    first = ShardedSolver(
        get_game("nim:heaps=3-4-5"), num_shards=4,
        checkpointer=LevelCheckpointer(d),
    ).solve()
    resumed = ShardedSolver(
        get_game("nim:heaps=3-4-5"), num_shards=4,
        checkpointer=LevelCheckpointer(d),
    )
    # Poison the step builders: resume must not recompile/run any level.
    def _poisoned(*a, **k):
        raise AssertionError("sharded resume recomputed a level")

    resumed._forward_fn = _poisoned
    resumed._backward_fn = _poisoned
    result = resumed.solve()
    assert (result.value, result.remoteness) == (first.value, first.remoteness)


def test_forward_level_shards_torn_dir_keeps_intact_prefix(tmp_path):
    """A manifest that seals a level whose shard files are gone (torn
    directory) must not abort the resumed solve with FileNotFoundError
    (ADVICE r4); it degrades to the intact contiguous-from-root prefix —
    at big-run scale that prefix is hours of re-discovery."""
    ckpt = LevelCheckpointer(str(tmp_path / "torn"))
    for level in range(3):
        for s in range(2):
            ckpt.save_forward_level_shard(
                level, s, np.arange(4, dtype=np.uint32))
        ckpt.finish_forward_level(level, 2)
    assert set(ckpt.load_forward_level_shards(2)) == {0, 1, 2}
    (ckpt.dir / "frontier_0001.shard_0000.npz").unlink()
    # Torn at level 1: level 0 survives, 1+ (and anything above the tear)
    # re-run. The result stays contiguous-from-root — _forward_fast's
    # resume contract.
    assert set(ckpt.load_forward_level_shards(2)) == {0}
    (ckpt.dir / "frontier_0000.shard_0001.npz").unlink()
    assert ckpt.load_forward_level_shards(2) == {}


def test_drop_forward_level_shards_manifest_before_unlink(tmp_path):
    """drop must pop the manifest entries and persist BEFORE unlinking:
    a death in between leaves orphan files (harmless), never sealed
    entries pointing at deleted files (ADVICE r4). Simulated by making
    the first unlink die."""
    from pathlib import Path

    ckpt = LevelCheckpointer(str(tmp_path / "drop_order"))
    for s in range(2):
        ckpt.save_forward_level_shard(0, s, np.arange(4, dtype=np.uint32))
    ckpt.finish_forward_level(0, 2)

    class _Die(Exception):
        pass

    orig_unlink = Path.unlink

    def dying_unlink(self, *a, **k):
        if self.name.startswith("frontier_"):
            raise _Die()
        return orig_unlink(self, *a, **k)

    Path.unlink = dying_unlink
    try:
        with pytest.raises(_Die):
            ckpt.drop_forward_level_shards()
    finally:
        Path.unlink = orig_unlink
    fresh = LevelCheckpointer(str(tmp_path / "drop_order"))
    # Manifest entries are gone even though the files survive; a resumed
    # run re-runs forward instead of crashing on the sealed entries.
    assert "forward_level_shards" not in fresh.load_manifest()
    assert fresh.load_forward_level_shards(2) == {}


def test_paranoid_catches_zero_move_undecided():
    """A non-primitive position with no legal moves must trip --paranoid."""
    import pytest

    from gamesmanmpi_tpu.games.subtract import Subtract
    from gamesmanmpi_tpu.core.values import UNDECIDED
    import jax.numpy as jnp

    class BrokenGame(Subtract):
        # primitive() never fires, so position 0 is UNDECIDED with no moves.
        def primitive(self, states):
            return jnp.full(states.shape, UNDECIDED, dtype=jnp.uint8)

    from gamesmanmpi_tpu.solve.engine import SolverError

    with pytest.raises(SolverError, match="consistency"):
        Solver(BrokenGame(total=4, moves=(1, 2)), paranoid=True).solve()


def test_tensorized_module_requires_level_fn():
    """level_of cannot be auto-derived (a global invariant, see
    compat.solve_module_jitted); max_moves CAN (probe + grow-and-retry)."""
    import pytest

    from gamesmanmpi_tpu.compat import TensorizedModule, load_game_module

    module = load_game_module(REF_GAMES / "ten_to_zero.py")
    with pytest.raises(ValueError, match="level"):
        TensorizedModule(module)


def test_cli_compat_warns_on_unsupported_flags(tmp_path, capsys):
    rc = cli_main(
        [
            str(REF_GAMES / "ten_to_zero.py"),
            "--devices",
            "4",
            "--table-out",
            str(tmp_path / "t.npz"),
            "--jsonl",
            str(tmp_path / "m.jsonl"),
        ]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "needs the tensorized compat path" in captured.err
    assert (tmp_path / "t.npz").exists()
    assert "done" in (tmp_path / "m.jsonl").read_text()


def test_cli_tensorized_compat_module(tmp_path, capsys):
    """A module declaring level_of + max_moves drives the real engine
    (solver flags work; no host-solve warning)."""
    mod = tmp_path / "ttz_t.py"
    mod.write_text(
        "initial_position = 10\n"
        "max_moves = 2\n"
        "max_level_jump = 2\n"
        "def level_of(pos):\n    return 10 - pos\n"
        "def gen_moves(pos):\n    return [m for m in (1, 2) if pos >= m]\n"
        "def do_move(pos, move):\n    return pos - move\n"
        "def primitive(pos):\n    return 'LOSE' if pos == 0 else None\n"
    )
    rc = cli_main([str(mod), "--paranoid"])
    captured = capsys.readouterr()
    assert rc == 0
    assert "value: WIN" in captured.out
    assert "remoteness: 7" in captured.out
    assert "warning" not in captured.err


def test_cli_coordinator_flag_plumbing(monkeypatch, capsys):
    """--coordinator must drive jax.distributed.initialize (mocked) before
    the solve, with the CLI's process-group arguments passed through."""
    import gamesmanmpi_tpu.parallel.mesh as mesh_mod

    calls = {}

    def fake_init(**kwargs):
        calls.update(kwargs)

    monkeypatch.setattr(mesh_mod.jax.distributed, "initialize", fake_init)
    rc = cli_main(
        [
            "subtract:total=6,moves=1-2",
            "--coordinator", "10.0.0.1:8476",
            "--num-processes", "1",
            "--process-id", "0",
        ]
    )
    assert rc == 0
    assert calls == {
        "coordinator_address": "10.0.0.1:8476",
        "num_processes": 1,
        "process_id": 0,
    }


def test_cli_coordinator_requires_process_args(capsys):
    rc = cli_main(["tictactoe", "--coordinator", "10.0.0.1:8476"])
    captured = capsys.readouterr()
    assert rc == 2
    assert "--num-processes" in captured.err


def test_cli_query_flag(capsys):
    rc = cli_main(
        ["subtract:total=10,moves=1-2", "--query", "9", "--query", "0x3",
         "--query", "99"]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "query 9: value=LOSE remoteness=" in captured.out
    assert "query 0x3: value=LOSE" in captured.out  # 3 % 3 == 0 -> LOSE
    assert "query 99: not reachable" in captured.out


def test_cli_query_flag_compat_host(capsys):
    rc = cli_main(
        [str(REF_GAMES / "ten_to_zero.py"), "--query", "3", "--query", "77"]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "query 3: value=LOSE" in captured.out
    assert "query 77: not reachable" in captured.out


def test_sharded_checkpoint_per_shard_files(tmp_path):
    """Sharded checkpoints are per-shard npz files — no global level or
    frontier arrays are assembled to write them (VERDICT r2 item 4) — and
    resume works shard-to-shard at the same shard count AND via
    repartition at a different one."""
    import jax

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs 4 fake devices")
    import pathlib

    from gamesmanmpi_tpu.parallel import ShardedSolver

    d = str(tmp_path / "shard_ckpt")
    first = ShardedSolver(
        get_game("tictactoe"), num_shards=4, store_tables=False,
        checkpointer=LevelCheckpointer(d),
    ).solve()
    files = {p.name for p in pathlib.Path(d).iterdir()}
    assert any(".shard_" in f and f.startswith("level_") for f in files)
    assert any(f.startswith("frontiers.shard_") for f in files)
    # Big-run mode + checkpoint must not write any GLOBAL level/frontier
    # file (the single-host bottleneck the per-shard format removes).
    assert not any(
        f.startswith("level_") and ".shard_" not in f for f in files
    )
    assert "frontiers.npz" not in files

    # Same-shard-count resume: shard-to-shard, and no recompute.
    same = ShardedSolver(
        get_game("tictactoe"), num_shards=4, store_tables=False,
        checkpointer=LevelCheckpointer(d),
    )

    def _poisoned(*a, **k):
        raise AssertionError("resume recomputed a level")

    same._forward_fn = _poisoned
    same._backward_fn = _poisoned
    r_same = same.solve()
    assert (r_same.value, r_same.remoteness) == (first.value, first.remoteness)

    # Different shard count: assemble + repartition fallback.
    r_other = ShardedSolver(
        get_game("tictactoe"), num_shards=2,
        checkpointer=LevelCheckpointer(d),
    ).solve()
    assert (r_other.value, r_other.remoteness) == (
        first.value, first.remoteness,
    )


def test_sharded_checkpoint_single_shard(tmp_path):
    """num_shards=1 checkpoints and resumes (a 1-device sharding reports
    shard index slice(None) — start None — which must map to shard 0)."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    d = str(tmp_path / "one_shard")
    first = ShardedSolver(
        get_game("subtract:total=10,moves=1-2"), num_shards=1,
        checkpointer=LevelCheckpointer(d),
    ).solve()
    resumed = ShardedSolver(
        get_game("subtract:total=10,moves=1-2"), num_shards=1,
        checkpointer=LevelCheckpointer(d),
    ).solve()
    assert (resumed.value, resumed.remoteness) == (first.value, first.remoteness)


def test_force_platform_noop_and_epoch_keying(monkeypatch):
    """Chip-session discipline regression (VERDICT r3 weak #1): every
    in-process CLI run calls apply_platform_env; with GAMESMAN_PLATFORM=cpu
    set (the documented rule while a chip session runs elsewhere) that used
    to clear_backends even though CPU was already active, poisoning sharded
    kernels cached on the old device objects. force_platform must (a)
    no-op when the requested platform is already the default backend, and
    (b) when a clear IS genuine, bump the backend epoch so kernel caches
    and dense device-const caches rebuild instead of reusing stale
    executables."""
    import jax

    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.utils import platform as plat

    game = get_game("subtract:total=10,moves=1-2")
    first = ShardedSolver(game, num_shards=2).solve()

    # (a) Re-forcing the active platform must not clear backends: the same
    # device objects remain valid and a cached sharded kernel still runs.
    devices_before = jax.devices()
    epoch_before = plat.backend_epoch()
    plat.force_platform("cpu", fake_devices=len(devices_before))
    assert plat.backend_epoch() == epoch_before
    assert jax.devices() == devices_before
    again = ShardedSolver(game, num_shards=2).solve()
    assert (again.value, again.remoteness) == (first.value, first.remoteness)

    # (b) A genuine clear bumps the epoch; epoch-keyed caches rotate.
    from gamesmanmpi_tpu.solve.engine import _cache_key
    from gamesmanmpi_tpu.solve.dense import DenseTables

    key_old = _cache_key(game, "k", (1,), lowering=())
    tables = DenseTables(3, 3)
    tables._dev_binom = object()
    tables._dev_consts[(0, False)] = object()
    monkeypatch.setattr(plat, "_BACKEND_EPOCH", plat.backend_epoch() + 1)
    assert _cache_key(game, "k", (1,), lowering=()) != key_old
    tables.drop_stale_device_caches()
    assert tables._dev_binom is None and not tables._dev_consts


def test_use_compile_cache_placement(monkeypatch):
    """One policy for every entry point: a cache directory given from
    outside wins and nothing is set; otherwise the fixed repo path."""
    import jax

    from gamesmanmpi_tpu.utils import platform as plat

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        plat.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            plat.REPO_CACHE_DIR)
        assert plat.REPO_CACHE_DIR.name == ".jax_compile_cache"

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        plat.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cli_compile_cache_goes_where_env_says(tmp_path):
    """A CLI solve with JAX_COMPILATION_CACHE_DIR set fills that directory
    and writes none of its entries into the repo's default cache."""
    import os
    import subprocess
    import sys

    from gamesmanmpi_tpu.utils.platform import REPO_CACHE_DIR

    def entries(d):
        return set(os.listdir(d)) if os.path.isdir(d) else set()

    cache = tmp_path / "cache"
    before = entries(REPO_CACHE_DIR)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = subprocess.run(
        [sys.executable, "-m", "gamesmanmpi_tpu.cli", "tictactoe"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "remoteness: 9" in proc.stdout
    ours = entries(cache)
    assert ours, "no compile-cache entries in JAX_COMPILATION_CACHE_DIR"
    # Other test workers may write the repo cache meanwhile; none of them
    # writes THIS run's entries.
    assert not (entries(REPO_CACHE_DIR) - before) & ours


def test_cli_query_from_dense_checkpoints_no_tables(tmp_path, capsys):
    """The dense analog of the big-run query contract: --engine dense
    --no-tables --checkpoint-dir holds every solved cell as per-level
    dense_NNNN.npz; --query must locate the cell by perfect index in one
    level file, not report 'not reachable'."""
    from gamesmanmpi_tpu.core.values import value_name

    full = Solver(get_game("connect4:w=3,h=3,k=3")).solve()
    picks = []
    for level in sorted(full.levels):
        states = full.levels[level].states
        if states.shape[0] and level > 0:
            picks.append(int(states[states.shape[0] // 2]))
        if len(picks) == 5:
            break
    assert len(picks) == 5

    d = str(tmp_path / "densebig")
    argv = ["connect4:w=3,h=3,k=3", "--engine", "dense", "--no-tables",
            "--checkpoint-dir", d]
    for s in picks:
        argv += ["--query", hex(s)]
    rc = cli_main(argv)
    out = capsys.readouterr().out
    assert rc == 0
    for s in picks:
        v, r = full.lookup(s)
        assert (
            f"query {hex(s)}: value={value_name(v)} remoteness={r}" in out
        )
    assert "not reachable" not in out


def test_cli_query_from_shard_checkpoints_no_tables(tmp_path, capsys):
    """SURVEY §1's by-product contract at big-run scale (VERDICT r3
    missing #4): with --no-tables nothing is materialized in host memory,
    but --checkpoint-dir holds every solved cell as per-(level, shard)
    npz — --query must answer from those files (one shard read, chosen by
    the owner hash), not report 'not reachable'."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 fake devices")
    from gamesmanmpi_tpu.core.values import value_name

    # Reference answers from a full in-memory solve.
    full = Solver(get_game("tictactoe")).solve()
    picks = []
    for level in sorted(full.levels):
        states = full.levels[level].states
        if states.shape[0] and level > 0:
            picks.append(int(states[states.shape[0] // 2]))
        if len(picks) == 5:
            break
    assert len(picks) == 5

    d = str(tmp_path / "bigrun")
    argv = ["tictactoe", "--devices", "4", "--no-tables",
            "--checkpoint-dir", d]
    for s in picks:
        argv += ["--query", hex(s)]
    rc = cli_main(argv)
    out = capsys.readouterr().out
    assert rc == 0
    for s in picks:
        v, r = full.lookup(s)
        assert (
            f"query {hex(s)}: value={value_name(v)} remoteness={r}" in out
        )
    assert "not reachable" not in out
