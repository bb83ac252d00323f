"""CLI: the rebuild of the reference's solver_launcher.py (SURVEY.md §2.2, §3.1).

The reference is launched as
    mpirun -np N python solver_launcher.py games/tictactoe.py
and prints the solved value + remoteness of the initial position (plus elapsed
time) from rank 0. Here there is no mpirun: device parallelism comes from the
JAX mesh, so the same solve is
    python solve_launcher.py tictactoe
    python solve_launcher.py connect4:w=5,h=4 --devices 4
    python solve_launcher.py path/to/ref_style_game.py      (compat path)

A file path argument is the reference's dynamic game-module import: the module
is loaded, validated for the 4-function API, and solved unmodified via the
compat layer. Built-in tensorized games are selected by spec string
(gamesmanmpi_tpu.games.get_game).

Three serving subcommands ride in front of the flat solve CLI (which is
unchanged — any first argument that is not a subcommand name parses exactly
as before):

    python -m gamesmanmpi_tpu.cli export-db GAME --out DB [--from-checkpoint D]
    python -m gamesmanmpi_tpu.cli serve DB [--port N] [--batch-window-ms MS]
    python -m gamesmanmpi_tpu.cli query DB POS [POS ...]

export-db builds the immutable solved-position database (db/) from a fresh
solve (streamed level-by-level through the engine's level_sink hook) or from
an existing --checkpoint-dir; serve answers batched POST /query over it
(serve/); query probes it offline. docs/SERVING.md is the full spec.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from gamesmanmpi_tpu.utils.env import env_opt


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="solve_launcher",
        description="Strongly solve a two-player abstract game (value + remoteness).",
    )
    p.add_argument(
        "game",
        nargs="?",
        default=None,
        help="built-in game spec (e.g. tictactoe, connect4:w=5,h=4, nim:heaps=3-4-5), "
        "a path to a declarative GameSpec .json file (docs/GAMEDSL.md), "
        "or a path to a reference-style game module file; omit when "
        "--spec is given",
    )
    p.add_argument(
        "--spec",
        default=None,
        metavar="SPEC.json",
        help="declarative GameSpec file compiled by gamedsl "
        "(docs/GAMEDSL.md) — equivalent to passing the path as GAME",
    )
    p.add_argument(
        "--devices",
        type=int,
        default=1,
        help="number of devices to shard the solve over (1 = single device)",
    )
    p.add_argument(
        "--paranoid",
        action="store_true",
        help="enable internal consistency re-verification (SURVEY.md §5.2)",
    )
    p.add_argument(
        "--jsonl",
        default=None,
        help="write per-level structured metrics to this JSONL file (§5.5)",
    )
    p.add_argument(
        "-v",
        "--verbose",
        action="store_true",
        help="print per-level progress lines to stderr (the reference's "
        "debug-print flag analog, SURVEY.md §5.5)",
    )
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        help="save per-level solved tables for restart-from-level (§5.4)",
    )
    p.add_argument(
        "--profile-dir",
        default=None,
        help="capture a jax.profiler trace of the solve into this dir (§5.1)",
    )
    # Observability layer (docs/OBSERVABILITY.md): phase spans + metrics
    # registry, alongside the low-level JAX trace above.
    p.add_argument(
        "--trace-events",
        default=None,
        metavar="OUT.json",
        help="dump Chrome trace-event JSON of the solver's phase spans "
        "(forward/dedup/backward/checkpoint/db_export) — loads in "
        "chrome://tracing / Perfetto",
    )
    p.add_argument(
        "--metrics-out",
        default=None,
        metavar="OUT.json",
        help="dump the process metrics-registry snapshot (span histograms, "
        "solve counters) as JSON when the solve finishes",
    )
    p.add_argument(
        "--heartbeat-secs",
        type=float,
        default=None,
        metavar="S",
        help="emit a heartbeat record (phase/level progress, RSS, device "
        "memory) every S seconds so long solves are diagnosable "
        "mid-flight (env GAMESMAN_HEARTBEAT_SECS; 0 = off)",
    )
    p.add_argument(
        "--status-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve read-only live solve status on this port: GET "
        "/status (phase/level, positions solved, per-level progress "
        "model with ETA, fleet-merged per-rank view on rank 0) and GET "
        "/metrics (Prometheus text). 0 = ephemeral port (published via "
        "GAMESMAN_STATUS_ADDR_FILE); env GAMESMAN_STATUS_PORT; unset = "
        "off (docs/OBSERVABILITY.md \"Live status\")",
    )
    p.add_argument(
        "--watchdog-secs",
        type=float,
        default=None,
        metavar="S",
        help="abort (exit 124, diagnostics dumped, checkpoint prefix "
        "intact) when a level stalls longer than "
        "max(S, GAMESMAN_WATCHDOG_FACTOR x slowest recent level) — "
        "turns a wedged backend into a resumable death (env "
        "GAMESMAN_WATCHDOG_SECS; 0 = off)",
    )
    p.add_argument(
        "--table-out",
        default=None,
        help="dump the full solved table as .npz (packed cells per level)",
    )
    p.add_argument(
        "--no-tables",
        action="store_true",
        help="big-run mode: materialize only the root level's table on host "
        "(positions are still counted; combine with --checkpoint-dir to "
        "persist full tables level-by-level instead)",
    )
    p.add_argument(
        "--engine",
        choices=("auto", "classic", "dense", "hybrid"),
        default="auto",
        help="solver engine: 'classic' = level-BFS discovery (all games); "
        "'dense' = class-partitioned perfect-indexing engine (Connect-4 "
        "family, single device, sym=0 — no sorts, 1 byte/position); "
        "'hybrid' = dense below --hybrid-cutover, BFS above (giant "
        "boards, same eligibility as dense); 'auto' picks dense when "
        "eligible",
    )
    p.add_argument(
        "--hybrid-cutover",
        type=int,
        default=None,
        metavar="K",
        help="last dense level of --engine hybrid (default: 2/3 of the "
        "board's cells; see solve/hybrid.py)",
    )
    p.add_argument(
        "--query",
        action="append",
        default=None,
        metavar="POS",
        help="after solving, also print the value/remoteness of this packed "
        "position (decimal or 0x-hex; repeatable). Queries are "
        "canonicalized, so symmetry-reduced solves answer for any class "
        "member",
    )
    # Capacity knobs (CLI spellings of the GAMESMAN_* env vars; the flag
    # wins when both are set). docs/ARCHITECTURE.md capacity plan.
    p.add_argument(
        "--backward-block",
        type=int,
        default=None,
        metavar="POSITIONS",
        help="resolve levels in column blocks of this many positions "
        "(bounds backward temporaries; 0 = never block; env "
        "GAMESMAN_BACKWARD_BLOCK)",
    )
    p.add_argument(
        "--window-block",
        type=int,
        default=None,
        metavar="POSITIONS",
        help="sharded: spill window levels wider than this (per shard) to "
        "host and stream them back through HBM in blocks (0 = never "
        "spill; env GAMESMAN_WINDOW_BLOCK)",
    )
    p.add_argument(
        "--device-store-mb",
        type=int,
        default=None,
        metavar="MB",
        help="device-resident budget for discovered levels + provenance "
        "between forward and backward; excess spills to host (env "
        "GAMESMAN_DEVICE_STORE_MB)",
    )
    p.add_argument(
        "--backward",
        choices=("edges", "lookup"),
        default=None,
        help="sharded backward strategy: 'edges' (default) resolves each "
        "level from the forward pass's stored edge indices — gathers + "
        "collectives, no search, no re-expansion — falling back to "
        "'lookup' per level where edges are missing (pre-edge "
        "checkpoints, multi-jump games); 'lookup' forces the owner-"
        "routed search join everywhere (env GAMESMAN_BACKWARD)",
    )
    # Multi-host bring-up (SURVEY.md §5.8 control plane): one process per
    # host, jax.distributed over DCN, mesh over all addressable devices.
    # docs/ARCHITECTURE.md "Multi-host launch" shows a v4-32 example.
    p.add_argument(
        "--coordinator",
        default=None,
        help="coordinator address host:port for multi-host runs "
        "(jax.distributed.initialize over DCN)",
    )
    p.add_argument(
        "--num-processes",
        type=int,
        default=None,
        help="total number of processes in the multi-host run",
    )
    p.add_argument(
        "--process-id",
        type=int,
        default=None,
        help="this process's index in [0, num-processes)",
    )
    return p


def _lookup_checkpoint(game, checkpointer, state):
    """(value, remoteness) of one position from a checkpoint directory, or
    None. Dense directories (manifest "dense_levels") locate the cell by
    perfect index in one dense_NNNN.npz; classic directories canonicalize
    and level the query exactly like the engine, then read one
    (level, shard) npz (LevelCheckpointer.lookup_level_state).

    Never raises: the solve already succeeded, so a missing shard file (a
    multi-host run's remote shard, a torn write) degrades this one query
    to unanswerable — it must not abort the report or the remaining
    queries."""
    try:
        dense_levels = checkpointer.load_manifest().get("dense_levels")
        if dense_levels:
            from gamesmanmpi_tpu.solve.dense import tables_for

            t = tables_for(game.width, game.height, game.connect)
            level, row, rank = t.locate(int(state))
            if (level not in dense_levels
                    or t.current_player_has_line(level, row, rank)):
                # Never solved (interrupted run) / fabricated class (the
                # player to move already has a line: its cell is a
                # placeholder, same refusal as DenseSolveResult.lookup).
                return None
            cache = getattr(checkpointer, "_dense_query_cache", None)
            if cache is not None and cache[0] == level:
                cells = cache[1]
            else:
                # Memoize the last-loaded level: batched queries cluster,
                # and at big-run scale one level file is a large read.
                cells = checkpointer.load_dense_level(level)
                checkpointer._dense_query_cache = (level, cells)
            cell = int(cells[row * t.class_size[level] + rank])
            return cell & 3, cell >> 2
        from gamesmanmpi_tpu.solve.engine import canonical_scalar

        canon, level = canonical_scalar(game, state)
        return checkpointer.lookup_level_state(level, int(canon))
    except Exception as e:  # noqa: BLE001 - per-query degradation
        print(f"warning: checkpoint query failed ({e!r})", file=sys.stderr)
        return None


def _report(result, devices: int, elapsed: float, args) -> None:
    """The rank-0 output block (SURVEY.md §2.1.4), shared by every engine
    path: value + remoteness + elapsed, optional table dump."""
    from gamesmanmpi_tpu.core.values import value_name

    print(f"game: {result.game.name}")
    print(f"devices: {devices}")
    print(f"positions: {result.num_positions}")
    print(f"value: {value_name(result.value)}")
    print(f"remoteness: {result.remoteness}")
    print(f"elapsed: {elapsed:.3f}s")
    print(
        f"throughput: {result.stats['positions_per_sec']:.0f} positions/sec"
    )
    if args.table_out:
        from gamesmanmpi_tpu.utils.checkpoint import save_result_npz

        save_result_npz(args.table_out, result)
        print(f"table written: {args.table_out}")
    if args.query:
        from gamesmanmpi_tpu.obs import trace_span

        with trace_span("query", queries=len(args.query)):
            _answer_queries(result, args)


def _answer_queries(result, args) -> None:
    """Print one line per ``--query`` position, from the solved table."""
    from gamesmanmpi_tpu.core.values import value_name

    ckpt = None
    if getattr(args, "checkpoint_dir", None):
        from gamesmanmpi_tpu.utils import LevelCheckpointer

        ckpt = LevelCheckpointer(args.checkpoint_dir)
    for q in args.query:
        # The reference prints only the root; point queries answer for any
        # reachable position from the solved table (SolveResult.lookup
        # canonicalizes, so sym=1 tables answer for all class members). In
        # big-run mode (--no-tables) the in-memory result holds only the
        # root level, but a --checkpoint-dir run has every solved cell on
        # disk — serve the query from the per-(level, shard) npz instead
        # of declaring it unreachable (SURVEY.md §1's by-product contract).
        try:
            state = int(q, 0)
            try:
                value, rem = result.lookup(state)
            except KeyError:
                hit = (
                    _lookup_checkpoint(result.game, ckpt, state)
                    if ckpt is not None else None
                )
                if hit is None:
                    print(f"query {q}: not reachable")
                    continue
                value, rem = hit
            print(f"query {q}: value={value_name(value)} remoteness={rem}")
        except (ValueError, OverflowError) as e:
            # Bad literal / doesn't fit the game's state dtype — report per
            # query; the solve itself already succeeded.
            print(f"query {q}: invalid position ({e})")


def _dump_flightrec(reason: str) -> None:
    """Leave the flight recorder's post-mortem (recent spans/levels/
    retries/faults + in-flight spans) on every abnormal solve exit —
    the file lands in GAMESMAN_FLIGHTREC_DIR, which main() defaults to
    the checkpoint directory. Never raises: the post-mortem writer must
    not add its own failure to the one it records."""
    from gamesmanmpi_tpu.obs import flightrec

    flightrec.dump(reason)


#: Serving subcommands dispatched ahead of the flat solve parser. A game
#: spec can never collide: specs are lowercase single tokens already taken
#: by the registry, and module paths contain a '.' or '/'.
_DB_COMMANDS = ("export-db", "serve", "query", "registry")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # `gamesman solve ...` reads symmetrically with export-db/serve/query;
    # the flat grammar (game spec first) stays the default. No game is
    # named "solve", so the token is unambiguous.
    if argv and argv[0] == "solve":
        argv = argv[1:]
    if argv and argv[0] in _DB_COMMANDS:
        return _db_main(argv)
    args = build_parser().parse_args(argv)
    if args.spec is not None:
        if args.game is not None:
            print(
                "error: pass either GAME or --spec, not both",
                file=sys.stderr,
            )
            return 2
        args.game = args.spec
    elif args.game is None:
        print(
            "error: a game is required: GAME or --spec SPEC.json",
            file=sys.stderr,
        )
        return 2
    # Capacity flags are CLI spellings of the env knobs the engines read at
    # construction; set them before any solver is built, and restore on
    # exit so programmatic main() calls don't leak config to the next one.
    saved_env = {}
    # Flight-recorder dumps land next to the checkpoints by default: a
    # checkpointed solve's post-mortems (crash, watchdog, preemption
    # deadline, level-boundary snapshots) belong with the tree they
    # describe. An explicit GAMESMAN_FLIGHTREC_DIR wins.
    flightrec_dir = (
        args.checkpoint_dir
        if args.checkpoint_dir and not env_opt("GAMESMAN_FLIGHTREC_DIR")
        else None
    )
    for flag, env in (
        (args.backward_block, "GAMESMAN_BACKWARD_BLOCK"),
        (args.window_block, "GAMESMAN_WINDOW_BLOCK"),
        (args.device_store_mb, "GAMESMAN_DEVICE_STORE_MB"),
        (args.heartbeat_secs, "GAMESMAN_HEARTBEAT_SECS"),
        (args.watchdog_secs, "GAMESMAN_WATCHDOG_SECS"),
        (args.backward, "GAMESMAN_BACKWARD"),
        (args.status_port, "GAMESMAN_STATUS_PORT"),
        (flightrec_dir, "GAMESMAN_FLIGHTREC_DIR"),
    ):
        if flag is not None:
            saved_env[env] = env_opt(env)
            os.environ[env] = str(flag)
    try:
        return _main(args)
    finally:
        for env, old in saved_env.items():
            if old is None:
                os.environ.pop(env, None)
            else:
                os.environ[env] = old


def _main(args) -> int:
    from gamesmanmpi_tpu.utils.platform import (
        apply_platform_env,
        use_compile_cache,
    )

    # Honor GAMESMAN_PLATFORM=cpu|tpu (and GAMESMAN_FAKE_DEVICES) before
    # any backend init; --devices N on a faked-CPU run needs >= N devices.
    apply_platform_env(default_fake_devices=max(args.devices, 1))
    use_compile_cache()
    # Multi-host identity: the flags win, the env (GAMESMAN_COORDINATOR /
    # GAMESMAN_NUM_PROCESSES / GAMESMAN_PROCESS_ID — how tools/
    # launch_multihost.py configures its children) fills the gaps.
    coordinator = args.coordinator or env_opt("GAMESMAN_COORDINATOR")
    if coordinator:
        # Must run before the first backend touch so every process joins the
        # same PJRT world; the mesh then spans all addressable devices.
        # Either spelling needs the full identity triple: without it,
        # init_distributed's env_int defaults (1 process, rank 0) would
        # quietly give every host its own one-process world all claiming
        # rank 0 — an obscure bind/handshake failure instead of this.
        if (args.num_processes is None
                and env_opt("GAMESMAN_NUM_PROCESSES") is None) or (
                args.process_id is None
                and env_opt("GAMESMAN_PROCESS_ID") is None):
            print(
                "error: a coordinator (--coordinator / "
                "GAMESMAN_COORDINATOR) requires --num-processes and "
                "--process-id (or their GAMESMAN_* env twins)",
                file=sys.stderr,
            )
            return 2
        from gamesmanmpi_tpu.parallel.mesh import init_distributed

        init_distributed(
            coordinator_address=coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        _configure_rank_env(coordinator, args)
    t0 = time.perf_counter()

    logger = _build_logger(args)
    if logger is not None and coordinator:
        import jax

        if jax.process_count() > 1:
            from gamesmanmpi_tpu.utils.metrics import RankLogger

            logger = RankLogger(logger, jax.process_index())
    # Loggers are context managers: the JSONL handle closes even when a
    # solve aborts mid-level (partial metrics beat a lost buffered tail).
    # The obs scope nests inside so both artifacts (--trace-events,
    # --metrics-out) are written even when the solve itself raises.
    with _logger_scope(logger):
        with _obs_scope(args):
            return _solve_main(args, t0, logger)


def _rank_path(path: str, rank: int) -> str:
    """``out.jsonl`` -> ``out.rank0.jsonl``: per-rank artifact names.

    N processes handed one ``--jsonl``/``--metrics-out`` path must not
    race each other onto the same file; rank-qualified siblings keep
    every rank's stream intact and tools/obs_report.py merges them.
    """
    root, ext = os.path.splitext(path)
    return f"{root}.rank{rank}{ext}"


def _configure_rank_env(coordinator: str, args) -> None:
    """Post-initialize rank plumbing for a multi-process run.

    * ``GAMESMAN_COORD_ADDR`` (the retry-consensus coordinator,
      resilience/coordination.py) defaults to the jax coordinator's host
      at port+1 so a bare two-flag launch gets coordinated retry for
      free; an explicit env value wins.
    * Every ``gamesman_*`` series and JSONL record this process emits
      gains a ``rank`` label (docs/OBSERVABILITY.md) — without it the
      per-rank metrics of an N-process run are indistinguishable.
    * File artifacts (--jsonl/--metrics-out/--trace-events/--table-out)
      become rank-qualified siblings so ranks never race onto one path.
    """
    import jax

    if jax.process_count() <= 1:
        return
    rank = jax.process_index()
    for field in ("jsonl", "metrics_out", "trace_events", "table_out"):
        val = getattr(args, field, None)
        if val:
            setattr(args, field, _rank_path(val, rank))
    if not env_opt("GAMESMAN_COORD_ADDR"):
        host, _, port = coordinator.rpartition(":")
        try:
            os.environ["GAMESMAN_COORD_ADDR"] = (
                f"{host or '127.0.0.1'}:{int(port) + 1}"
            )
        except ValueError:
            pass  # unparsable port: coordination stays unconfigured
    from gamesmanmpi_tpu.obs import default_registry

    default_registry().set_constant_labels(rank=str(jax.process_index()))


def _solve_main(args, t0: float, logger) -> int:
    import pathlib

    from gamesmanmpi_tpu.core.values import value_name
    from gamesmanmpi_tpu.obs import trace_span
    from gamesmanmpi_tpu.utils.profiling import maybe_profile

    checkpointer = None
    if args.checkpoint_dir:
        from gamesmanmpi_tpu.utils.checkpoint import LevelCheckpointer

        checkpointer = LevelCheckpointer(args.checkpoint_dir)

    # A .json file is a declarative GameSpec, not a compat module: it
    # compiles through get_game below and drives the real engine.
    if (pathlib.Path(args.game).is_file()
            and not args.game.lower().endswith(".json")):
        if args.engine in ("dense", "hybrid"):
            # The validation below never runs on the compat path; without
            # this, --engine dense/hybrid would be silently ignored here.
            print(
                f"error: --engine {args.engine} applies to the built-in "
                "Connect-4 family, not compat game modules",
                file=sys.stderr,
            )
            return 2
        # Reference-style plugin module: runs unmodified (compat path).
        from gamesmanmpi_tpu.compat import load_game_module, solve_module

        try:
            module = load_game_module(args.game)
        except (AttributeError, ImportError) as e:
            # Module validation, solver_launcher.py-style (SURVEY.md §3.1).
            print(f"error: invalid game module {args.game!r}: {e}", file=sys.stderr)
            return 2
        engine_capable = hasattr(module, "level_of")
        for flag, name in (
            (args.devices > 1, "--devices"),
            (args.paranoid, "--paranoid"),
            (args.checkpoint_dir, "--checkpoint-dir"),
            (args.backward_block is not None, "--backward-block"),
            (args.window_block is not None, "--window-block"),
            (args.device_store_mb is not None, "--device-store-mb"),
        ):
            if flag and not engine_capable:
                print(
                    f"warning: {name} needs the tensorized compat path and "
                    "is ignored on the host solve; define level_of(pos) in "
                    "the module (max_moves is auto-derived) to drive the "
                    "TPU engine",
                    file=sys.stderr,
                )
        if engine_capable:
            # Modules that declare a topological level function are lifted
            # onto the batched protocol and driven by the real engine —
            # all solver flags work, including --devices (host callbacks
            # run per shard-batch). max_moves is taken from the module or
            # auto-derived with grow-and-retry (compat.solve_module_jitted).
            from gamesmanmpi_tpu.compat import solve_module_jitted

            with maybe_profile(args.profile_dir), \
                    trace_span("solve", engine="compat"):
                result = solve_module_jitted(
                    module,
                    devices=args.devices,
                    paranoid=args.paranoid,
                    logger=logger,
                    checkpointer=checkpointer,
                    store_tables=not args.no_tables,
                )
            _report(result, args.devices, time.perf_counter() - t0, args)
            return 0
        else:
            with maybe_profile(args.profile_dir), \
                    trace_span("solve", engine="host"):
                value, remoteness, table = solve_module(module)
            elapsed = time.perf_counter() - t0
            print(f"game: {pathlib.Path(args.game).stem} (compat module)")
            print(f"positions: {len(table)}")
            print(f"value: {value_name(value)}")
            print(f"remoteness: {remoteness}")
            print(f"elapsed: {elapsed:.3f}s")
            if args.table_out:
                from gamesmanmpi_tpu.utils.checkpoint import save_table_npz

                save_table_npz(args.table_out, table)
                print(f"table written: {args.table_out}")
            for q in args.query or ():
                try:
                    hit = table.get(int(q, 0))
                except ValueError as e:
                    print(f"query {q}: invalid position ({e})")
                    continue
                if hit is None:
                    print(f"query {q}: not reachable")
                else:
                    print(
                        f"query {q}: value={value_name(hit[0])} "
                        f"remoteness={hit[1]}"
                    )
            if logger is not None:
                logger.log(
                    {
                        "phase": "done",
                        "game": pathlib.Path(args.game).stem,
                        "compat": True,
                        "positions": len(table),
                        "secs_total": elapsed,
                    }
                )
            return 0
    else:
        from gamesmanmpi_tpu.games import get_game

        try:
            game = get_game(args.game)
        except (KeyError, ValueError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            print(
                "known games: tictactoe[:m=,n=,k=,sym=], "
                "connect4[:w=,h=,k=,sym=], subtract[:total=,moves=,misere=], "
                "nim[:heaps=,misere=], chomp[:w=,h=] — or a path to a "
                "reference-style game module file (sym=1 enables "
                "board-symmetry reduction)",
                file=sys.stderr,
            )
            return 2
    from gamesmanmpi_tpu.games.connect4 import Connect4

    family_base = (
        isinstance(game, Connect4) and not game.sym
        and not args.paranoid and not args.table_out
    )
    family_ok = family_base and not args.checkpoint_dir
    # devices > 1 partitions the dense level kernels over the mesh by rank
    # (DenseSolver devices=N); the hybrid's dense region stays
    # single-device while its BFS region shards. An EXPLICIT --engine
    # dense also accepts --checkpoint-dir (per-level cell restart); auto
    # keeps routing checkpointed runs to the classic engine, whose
    # checkpoints don't pay a per-level device download.
    dense_eligible = family_base if args.engine == "dense" else family_ok
    if args.engine == "dense" and not dense_eligible:
        print(
            "error: --engine dense needs a Connect-4-family game "
            "with sym=0 and no --paranoid/--table-out "
            "(those live in the classic engine)",
            file=sys.stderr,
        )
        return 2
    # The hybrid accepts sym=1 (r5): its BFS region keeps the mirror
    # reduction and the dense region runs a sym-free twin — see
    # solve/hybrid.py.
    hybrid_ok = (
        isinstance(game, Connect4)
        and not args.paranoid and not args.table_out
        and not args.checkpoint_dir
    )
    if args.engine == "hybrid" and not hybrid_ok:
        print(
            "error: --engine hybrid needs a Connect-4-family game "
            "and no --checkpoint-dir/--paranoid/--table-out "
            "(those live in the classic engine)",
            file=sys.stderr,
        )
        return 2
    if args.engine == "auto" and dense_eligible:
        # Same platform policy as bench.py: the dense engine's rank loops
        # are shaped for the TPU's vector units; on CPU the classic engine
        # measured faster. An explicit --engine dense still forces it.
        import jax

        if jax.devices()[0].platform == "cpu":
            dense_eligible = False
        if args.devices > 1:
            # auto + a mesh keeps the OLD routing (owner-sharded BFS,
            # which shards MEMORY): the mesh dense engine re-replicates
            # each level, so it only fits boards whose peak level fits one
            # device — a policy the user opts into with --engine dense.
            dense_eligible = False
    if args.engine == "hybrid":
        from gamesmanmpi_tpu.solve.hybrid import HybridSolver

        engine = "hybrid"

        try:
            solver = HybridSolver(
                game,
                cutover=args.hybrid_cutover,
                store_tables=not args.no_tables,
                logger=logger,
                devices=args.devices,
            )
        except ValueError as e:
            # Bad --hybrid-cutover / GAMESMAN_HYBRID_CUTOVER: CLI misuse
            # exits 2 with a message, like every other argument error.
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.engine != "classic" and dense_eligible:
        from gamesmanmpi_tpu.solve.dense import DenseSolver

        engine = "dense"

        try:
            solver = DenseSolver(
                game,
                store_tables=not args.no_tables,
                logger=logger,
                devices=args.devices,
                checkpointer=checkpointer,
            )
        except ValueError as e:  # bad --devices: CLI misuse exits 2
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.devices > 1:
        from gamesmanmpi_tpu.parallel import ShardedSolver

        engine = "sharded"

        solver = ShardedSolver(
            game,
            num_shards=args.devices,
            paranoid=args.paranoid,
            logger=logger,
            checkpointer=checkpointer,
            store_tables=not args.no_tables,
        )
    else:
        from gamesmanmpi_tpu.solve import Solver

        engine = "classic"
        solver = Solver(
            game,
            paranoid=args.paranoid,
            logger=logger,
            checkpointer=checkpointer,
            store_tables=not args.no_tables,
        )
    from gamesmanmpi_tpu.resilience.coordination import CoordinatedAbort
    from gamesmanmpi_tpu.resilience.preempt import (
        GRACE_EXIT_CODE,
        PreemptionRequested,
        install_grace_handler,
    )

    # Preemption grace (docs/DISTRIBUTED.md "Campaigns"): SIGTERM/SIGUSR1
    # drain the solve to the next level boundary — everything complete is
    # sealed by the solve's own teardown — and exit 75 (resumable). Only
    # a CHECKPOINTED solve gets the handlers: exit 75 promises "restart
    # me against the same checkpoint directory", and a solve with
    # nothing to seal should keep dying promptly on SIGTERM (systemd /
    # k8s stop) instead of computing to the next boundary for a lie.
    restore_grace = (
        install_grace_handler() if checkpointer is not None
        else (lambda: None)
    )
    try:
        with maybe_profile(args.profile_dir), \
                trace_span("solve", engine=engine):
            result = solver.solve()
    except PreemptionRequested as e:
        _dump_flightrec("preempted")
        progress = getattr(solver, "progress", {})
        print(f"preempted: {e}\nprogress: {progress}", file=sys.stderr)
        sys.stderr.flush()
        if logger is not None:
            logger.log({"phase": "preempted", "detail": str(e)[:200],
                        **{("in_phase" if k == "phase" else k): v
                           for k, v in progress.items()
                           if isinstance(v, (int, str, float))}})
            logger.close()
        import jax

        if jax.process_count() > 1:
            # Same contract as the coordinated abort below: a clean exit
            # would block in jax's distributed-shutdown barrier when a
            # peer is already gone.
            os._exit(GRACE_EXIT_CODE)
        return GRACE_EXIT_CODE
    except MemoryError as e:
        _dump_flightrec("oom")
        # Host allocator exhaustion — the guard's HostMemoryExceeded at
        # a level boundary, or a real MemoryError mid-level. Either way
        # the sealed prefix is intact (atomic payload writes, atomic
        # seals) and the death must CLASSIFY: the "out of memory" /
        # RESOURCE_EXHAUSTED diagnostics below are what the campaign's
        # log-tail classifier reads as `oom` before answering with
        # geometry escalation (docs/DISTRIBUTED.md "Elastic resume").
        progress = getattr(solver, "progress", {})
        print(f"out of memory: {e}\nprogress: {progress}",
              file=sys.stderr)
        sys.stderr.flush()
        if logger is not None:
            logger.log({"phase": "oom", "error": str(e)[:200],
                        **{("in_phase" if k == "phase" else k): v
                           for k, v in progress.items()
                           if isinstance(v, (int, str, float))}})
            logger.close()
        import jax

        if jax.process_count() > 1:
            # Clean exit would block in jax's shutdown barrier while
            # peers are unwinding through the collective deadline.
            os._exit(1)
        return 1
    except CoordinatedAbort as e:
        import jax

        if jax.process_count() <= 1:
            # Multi-process ranks must NOT pay the dump's file I/O here:
            # jax's coordination service is already racing to SIGABRT
            # this process over the dead peer, and losing that race
            # turns the contractual exit 124 into -6 (observed in the
            # 2-process kill-resume chaos test). Their post-mortems come
            # from the level-boundary ring checkpoints and the
            # collective-deadline dump, which runs before the race
            # starts.
            _dump_flightrec("coordinated_abort")
        # The fleet agreed to stop (a peer died, diverged, or timed out):
        # same resumable-abort contract as the watchdog — diagnostics to
        # stderr, exit 124, checkpoint prefix intact, restart resumes.
        from gamesmanmpi_tpu.resilience.supervisor import WATCHDOG_EXIT_CODE

        progress = getattr(solver, "progress", {})
        print(f"coordinated abort: {e}\nprogress: {progress}",
              file=sys.stderr)
        sys.stderr.flush()
        if logger is not None:
            # progress carries its own "phase" (forward/backward) — keep
            # it under a different key or this record masquerades as a
            # normal level row in obs_report's table.
            logger.log({"phase": "coordinated_abort", "error": str(e)[:200],
                        **{("in_phase" if k == "phase" else k): v
                           for k, v in progress.items()
                           if isinstance(v, (int, str, float))}})
            logger.close()
        # os._exit, not return: a clean interpreter exit would run jax's
        # distributed-shutdown barrier, which blocks on the dead peer
        # until the coordination service SIGABRTs this process ~100 s
        # later — the watchdog contract is "gone within the deadline".
        os._exit(WATCHDOG_EXIT_CODE)
    except Exception:
        # The crash handler: any other death leaves the flight
        # recorder's post-mortem (last completed level, in-flight
        # spans) before the traceback propagates — exactly the cases
        # that used to need a rerun under instrumentation.
        _dump_flightrec("crash")
        raise
    finally:
        restore_grace()
    _report(result, args.devices, time.perf_counter() - t0, args)
    return 0


# --------------------------------------------------------------- serving CLI


def _db_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gamesman-db",
        description="Solved-position database: export, serve, query "
        "(docs/SERVING.md).",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser(
        "export-db",
        help="build an immutable DB from a fresh solve or a checkpoint dir",
    )
    pe.add_argument("game", nargs="?", default=None,
                    help="built-in game spec, or a GameSpec .json file "
                    "(the manifest embeds the canonical spec document, so "
                    "the DB stays self-describing); omit when --spec is "
                    "given")
    pe.add_argument("--spec", default=None, metavar="SPEC.json",
                    help="declarative GameSpec file (docs/GAMEDSL.md) — "
                    "equivalent to passing the path as GAME")
    pe.add_argument("--out", required=True, help="DB output directory")
    pe.add_argument(
        "--from-checkpoint",
        default=None,
        metavar="DIR",
        help="convert an existing --checkpoint-dir instead of re-solving "
        "(classic-engine checkpoints, global or sharded)",
    )
    pe.add_argument("--overwrite", action="store_true",
                    help="replace an existing DB in --out")
    pe.add_argument(
        "--compress",
        action="store_true",
        default=None,
        help="write format v2: block-compressed levels (compress/ — "
        "entropy-coded keys/cells in independently-decodable blocks, "
        "per-block index in the manifest; the reader decodes only "
        "probed blocks through a hot-block cache). Default from "
        "GAMESMAN_DB_COMPRESS; v1 DBs stay readable forever",
    )
    pe.add_argument(
        "--book-plies", type=int, default=None, metavar="N",
        help="also build the resident opening book: every position "
        "within N plies of the initial position, scored through the "
        "finished DB and sealed as book.gmb in the manifest — the "
        "serving hot path answers book hits from RAM (docs/SERVING.md "
        "\"Hot path\"). Default from GAMESMAN_BOOK_PLIES; 0 = no book",
    )
    pe.add_argument("--jsonl", default=None,
                    help="write per-level export metrics to this JSONL file")
    pe.add_argument("-v", "--verbose", action="store_true",
                    help="print per-level progress to stderr")

    ps = sub.add_parser(
        "serve", help="serve POST /query, GET /healthz, GET /metrics"
    )
    ps.add_argument("db", nargs="?", default=None,
                    help="DB directory (from export-db); omit when "
                    "--fleet-manifest names the DBs")
    ps.add_argument("--host", default="127.0.0.1")
    ps.add_argument("--port", type=int, default=8947,
                    help="0 = ephemeral (the bound port is printed)")
    ps.add_argument(
        "--workers",
        type=int,
        default=None,
        metavar="N",
        help="fleet mode: supervise N worker processes sharing this "
        "port's accept queue (forked after DbReader open — mmap pages "
        "shared; heartbeat liveness, backoff restart, rolling reload; "
        "docs/SERVING.md). 0/unset = single in-process server (env "
        "GAMESMAN_SERVE_WORKERS)",
    )
    ps.add_argument(
        "--fleet-manifest",
        default=None,
        metavar="FILE",
        help="route multiple game DBs from one fleet manifest JSON "
        '({"version": 1, "games": [{"name": ..., "db": ...}]}); '
        "POST /query/<name> selects the game. Implies fleet mode; "
        "SIGHUP or POST /reload on the control port rolls the fleet "
        "onto a re-read manifest",
    )
    ps.add_argument(
        "--control-port",
        type=int,
        default=0,
        metavar="P",
        help="fleet mode: supervisor control endpoint port (fleet-level "
        "GET /healthz aggregating per-worker state, GET /metrics, "
        "POST /reload); 0 = ephemeral (printed in the banner)",
    )
    ps.add_argument(
        "--batch-window-ms",
        type=float,
        default=2.0,
        help="micro-batch coalescing window: concurrent requests arriving "
        "within it flush as ONE vectorized DB probe",
    )
    ps.add_argument("--cache-size", type=int, default=65536,
                    help="LRU hot-position cache entries (0 disables)")
    ps.add_argument(
        "--request-timeout-ms",
        type=float,
        default=None,
        help="per-request deadline on the batcher: a request not "
        "answered within it gets 503 + Retry-After instead of hanging "
        "(env GAMESMAN_REQUEST_TIMEOUT in seconds; 0 = no deadline)",
    )
    ps.add_argument(
        "--max-queue",
        type=int,
        default=1024,
        help="load shedding: refuse (503) new queries when this many "
        "requests are already parked in the coalescing queue",
    )
    ps.add_argument("--jsonl", default=None,
                    help="write per-batch serving metrics to this JSONL file")
    ps.add_argument(
        "--no-trace",
        action="store_true",
        help="disable query-path tracing + tail sampling (sets "
        "GAMESMAN_TRACE=0 for this process and, in fleet mode, every "
        "worker; GET /traces then serves an empty ring). Tracing is on "
        "by default — its off-path cost is one attribute fetch per span "
        "site (docs/OBSERVABILITY.md \"Query tracing & SLOs\")",
    )
    ps.add_argument("-v", "--verbose", action="store_true")

    pq = sub.add_parser("query", help="probe a DB offline (no server)")
    pq.add_argument("db", help="DB directory (from export-db)")
    pq.add_argument("positions", nargs="+",
                    help="packed positions, decimal or 0x-hex")

    pr = sub.add_parser(
        "registry",
        help="DB registry: publish epochs, serve the catalog, run "
        "solve-on-demand jobs (docs/SERVING.md)",
    )
    rsub = pr.add_subparsers(dest="registry_cmd", required=True)

    rserve = rsub.add_parser(
        "serve",
        help="serve the sha256-sealed catalog + blob streams over HTTP",
    )
    rserve.add_argument("--root", required=True,
                        help="registry root directory (catalog.json + dbs/)")
    rserve.add_argument("--host", default="127.0.0.1")
    rserve.add_argument("--port", type=int, default=0,
                        help="0 = ephemeral (the bound port is printed)")
    rserve.add_argument(
        "--jobs", action="store_true",
        help="accept POST /solve: unregistered-game queries become "
        "durable solve-on-demand jobs in <root>/jobs.jsonl",
    )

    rpub = rsub.add_parser(
        "publish",
        help="copy a DB into the registry and seal a new catalog epoch",
    )
    rpub.add_argument("db", help="DB directory (from export-db)")
    rpub.add_argument("--root", required=True, help="registry root directory")
    rpub.add_argument("--name", default=None,
                      help="catalog name (default: the DB's game name)")

    rrun = rsub.add_parser(
        "run-jobs",
        help="claim queued solve-on-demand jobs and drive each through "
        "campaign solve -> export-db -> publish",
    )
    rrun.add_argument("--root", required=True, help="registry root directory")
    rrun.add_argument("--work-dir", default=None,
                      help="checkpoint/export scratch (default <root>/work)")
    rrun.add_argument("--book-plies", type=int, default=0, metavar="N",
                      help="also build an N-ply opening book before publish")
    rrun.add_argument("--once", action="store_true",
                      help="run at most one job, then exit")
    return p


def _build_logger(args):
    """The --jsonl/--verbose TeeLogger every command shares (solve path
    and serving subcommands build it identically; one place to wire a
    new sink). None when neither flag is set."""
    from gamesmanmpi_tpu.utils.metrics import JsonlLogger, StdoutLogger, TeeLogger

    if not (args.jsonl or args.verbose):
        return None
    return TeeLogger(
        JsonlLogger(args.jsonl) if args.jsonl else None,
        StdoutLogger() if args.verbose else None,
    )


def _logger_scope(logger):
    """Context that closes `logger` on exit (loggers are context
    managers), or a no-op when logging is off."""
    import contextlib

    return logger if logger is not None else contextlib.nullcontext()


def _obs_scope(args):
    """--trace-events / --metrics-out lifetime: install a trace sink for
    the solve and write both artifacts on exit, aborts included (a
    partial trace of a dead solve is exactly when it is most wanted)."""
    import contextlib

    @contextlib.contextmanager
    def scope():
        from gamesmanmpi_tpu.obs import default_registry
        from gamesmanmpi_tpu.obs.tracing import trace_events_scope

        with trace_events_scope(getattr(args, "trace_events", None)):
            try:
                yield
            finally:
                out = getattr(args, "metrics_out", None)
                if out:
                    with open(out, "w") as fh:
                        json.dump(
                            default_registry().snapshot(), fh, indent=1
                        )

    return scope()


def _cmd_export_db(args) -> int:
    from gamesmanmpi_tpu.db import DbFormatError, DbWriter, export_checkpoint
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.utils.env import env_bool, env_int

    if args.spec is not None:
        if args.game is not None:
            print("error: pass either GAME or --spec, not both",
                  file=sys.stderr)
            return 2
        args.game = args.spec
    elif args.game is None:
        print("error: a game is required: GAME or --spec SPEC.json",
              file=sys.stderr)
        return 2
    try:
        game = get_game(args.game)
    except (KeyError, ValueError) as e:
        print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
        return 2
    compress = (
        env_bool("GAMESMAN_DB_COMPRESS", False)
        if args.compress is None
        else bool(args.compress)
    )
    t0 = time.time()
    logger = _build_logger(args)
    with _logger_scope(logger):
        try:
            if args.from_checkpoint:
                import pathlib

                from gamesmanmpi_tpu.utils.checkpoint import LevelCheckpointer

                if not pathlib.Path(args.from_checkpoint).is_dir():
                    # Check BEFORE LevelCheckpointer: its constructor
                    # mkdirs, so a typo'd path would be created on disk
                    # and misreported as "no completed levels".
                    print(
                        f"error: no such checkpoint directory: "
                        f"{args.from_checkpoint}",
                        file=sys.stderr,
                    )
                    return 2
                manifest = export_checkpoint(
                    LevelCheckpointer(args.from_checkpoint),
                    game,
                    args.game,
                    args.out,
                    overwrite=args.overwrite,
                    logger=logger,
                    compress=compress,
                )
            else:
                # Fresh solve, streamed: each level flows into the writer as
                # the backward pass resolves it (level_sink), so the export
                # never holds the full table in host memory.
                from gamesmanmpi_tpu.solve import Solver

                writer = DbWriter(
                    args.out, game, args.game, overwrite=args.overwrite,
                    compress=compress,
                )
                try:
                    Solver(
                        game,
                        logger=logger,
                        store_tables=False,
                        level_sink=writer.add_level_table,
                    ).solve()
                    manifest = writer.finalize()
                except BaseException:  # incl. Ctrl-C mid-solve: the old
                    writer.abort()     # DB keeps serving, staging is gone
                    raise
        except (DbFormatError, FileNotFoundError) as e:
            # FileNotFoundError: a torn checkpoint (manifest-listed shard
            # file deleted) — a usage-visible input problem, not a crash.
            print(f"error: {e}", file=sys.stderr)
            return 2
        book_plies = (
            env_int("GAMESMAN_BOOK_PLIES", 0)
            if args.book_plies is None else int(args.book_plies)
        )
        if book_plies > 0:
            # After finalize on purpose: the book is scored through a
            # real reader over the sealed DB, and sealing it rewrites
            # the manifest (new DB epoch) exactly once more.
            from gamesmanmpi_tpu.db.book import build_book

            manifest["book"] = build_book(args.out, book_plies, game=game)
    print(f"database written: {args.out}")
    print(f"game: {manifest['game']}")
    print(f"levels: {len(manifest['levels'])}")
    print(f"positions: {manifest['num_positions']}")
    book_rec = manifest.get("book")
    if book_rec:
        print(
            f"opening book: {book_rec['count']} entries to "
            f"{book_rec['plies']} plies"
        )
    comp = manifest.get("compression")
    if comp:
        ratio = comp["raw_bytes"] / max(comp["stored_bytes"], 1)
        print(
            f"compressed: {comp['stored_bytes']} bytes "
            f"({ratio:.2f}x vs raw cells)"
        )
    print(f"elapsed: {time.time() - t0:.3f}s")
    return 0


def _cmd_serve(args) -> int:
    import signal
    import threading

    from gamesmanmpi_tpu.db import DbFormatError, DbReader
    from gamesmanmpi_tpu.serve import QueryServer
    from gamesmanmpi_tpu.utils.env import env_int

    workers = (
        env_int("GAMESMAN_SERVE_WORKERS", 0)
        if args.workers is None else args.workers
    )
    if args.no_trace:
        # Env, not a constructor knob: workers (fork AND exec spawn
        # modes) inherit the environment, so one setting covers the
        # whole fleet and every TraceRing/SloEngine built under it.
        os.environ["GAMESMAN_TRACE"] = "0"
    if args.db is None and not args.fleet_manifest:
        print("error: serve needs a DB directory (or --fleet-manifest)",
              file=sys.stderr)
        return 2
    if workers > 0 or args.fleet_manifest:
        return _cmd_serve_fleet(args, max(1, workers))
    try:
        reader = DbReader(args.db)
    except DbFormatError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    logger = _build_logger(args)
    with _logger_scope(logger):
        try:
            server = QueryServer(
                reader,
                host=args.host,
                port=args.port,
                window=args.batch_window_ms / 1e3,
                cache_size=args.cache_size,
                max_queue=args.max_queue,
                request_timeout=(
                    args.request_timeout_ms / 1e3
                    if args.request_timeout_ms is not None else None
                ),
                logger=logger,
            )
        except OSError as e:  # port in use / unbindable host
            print(
                f"error: cannot bind {args.host}:{args.port} ({e})",
                file=sys.stderr,
            )
            return 2
        print(
            f"serving {reader.game.name} ({reader.num_positions} positions) "
            f"on http://{args.host}:{server.port} "
            f"(POST /query, GET /healthz, GET /metrics, GET /traces)",
            flush=True,  # a supervisor tailing the pipe needs the banner NOW
        )
        # Graceful shutdown: SIGINT/SIGTERM flip /healthz to "draining"
        # (new queries 503 so a load balancer fails over), let in-flight
        # requests and the coalescing batch finish, then tear down — the
        # JSONL logger closes via the surrounding scope either way. The
        # old path was a bare serve_forever(): SIGTERM tore down nothing.
        stop = threading.Event()

        def _on_signal(signum, frame):
            stop.set()

        previous = {}
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                previous[sig] = signal.signal(sig, _on_signal)
            except ValueError:  # not the main thread (programmatic use)
                pass
        server.start()
        try:
            stop.wait()
            print("draining: refusing new queries, flushing in-flight "
                  "batches", file=sys.stderr)
            server.begin_drain()
        finally:
            server.stop()
            for sig, handler in previous.items():
                signal.signal(sig, handler)
    return 0


def _cmd_serve_fleet(args, workers: int) -> int:
    """`serve --workers N [--fleet-manifest F]`: the supervised
    multi-worker fleet (docs/SERVING.md "Fleet serving").

    The supervisor binds the socket, opens every DB's reader, then
    forks the workers — this parent deliberately never touches a jax
    backend, which is what keeps the fork spawn path legal (see
    serve/supervisor.ServeSupervisor._use_fork). SIGTERM/SIGINT drain
    the whole fleet; SIGHUP rolls it onto a re-read manifest.
    """
    import signal

    from gamesmanmpi_tpu.db import DbFormatError
    from gamesmanmpi_tpu.serve import (
        ServeSupervisor,
        load_fleet_manifest,
        single_db_entries,
    )

    if args.fleet_manifest and args.db:
        print("error: pass a DB directory or --fleet-manifest, not both",
              file=sys.stderr)
        return 2
    logger = _build_logger(args)
    with _logger_scope(logger):
        try:
            entries = (
                load_fleet_manifest(args.fleet_manifest)
                if args.fleet_manifest else single_db_entries(args.db)
            )
            supervisor = ServeSupervisor(
                entries,
                workers=workers,
                host=args.host,
                port=args.port,
                control_port=args.control_port,
                manifest_path=args.fleet_manifest,
                server_config={
                    "window": args.batch_window_ms / 1e3,
                    "cache_size": args.cache_size,
                    "max_queue": args.max_queue,
                    "request_timeout": (
                        args.request_timeout_ms / 1e3
                        if args.request_timeout_ms is not None else None
                    ),
                },
                jsonl=args.jsonl,
                logger=logger,
            )
        except (ValueError, DbFormatError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        except OSError as e:  # port in use / unbindable host
            print(
                f"error: cannot bind {args.host}:{args.port} ({e})",
                file=sys.stderr,
            )
            return 2
        games = ", ".join(e.name or "default" for e in entries)
        print(
            f"serving fleet [{games}] on "
            f"http://{args.host}:{supervisor.port} with {workers} "
            f"worker(s) "
            f"(control http://{args.host}:{supervisor.control_port} — "
            "GET /healthz, GET /metrics, GET /traces, POST /reload)",
            flush=True,  # a harness tailing the pipe needs the banner NOW
        )
        previous = {}

        def _on_stop(signum, frame):
            supervisor.request_stop()

        def _on_hup(signum, frame):
            supervisor.request_reload()

        for sig, handler in ((signal.SIGINT, _on_stop),
                             (signal.SIGTERM, _on_stop),
                             (signal.SIGHUP, _on_hup)):
            try:
                previous[sig] = signal.signal(sig, handler)
            except ValueError:  # not the main thread (programmatic use)
                pass
        try:
            return supervisor.run()
        finally:
            for sig, handler in previous.items():
                signal.signal(sig, handler)


def _cmd_query(args) -> int:
    from gamesmanmpi_tpu.core.values import value_name
    from gamesmanmpi_tpu.db import DbFormatError, DbReader

    try:
        reader = DbReader(args.db)
    except DbFormatError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from gamesmanmpi_tpu.db.format import parse_position

    states = []
    order = []  # (query string, packed state or None)
    for q in args.positions:
        try:
            state = parse_position(reader.game, q)
            order.append((q, len(states)))
            states.append(state)
        except ValueError as e:
            order.append((q, None))
            print(f"query {q}: invalid position ({e})")
    if states:
        values, rem, found, best = reader.lookup_best(states)
        sentinel = int(reader.game.sentinel)
        for q, i in order:
            if i is None:
                continue
            if not found[i]:
                print(f"query {q}: not in database")
                continue
            line = (
                f"query {q}: value={value_name(values[i])} "
                f"remoteness={int(rem[i])}"
            )
            if int(best[i]) != sentinel:
                line += f" best={hex(int(best[i]))}"
            print(line)
    return 0


def _cmd_registry(args) -> int:
    import pathlib
    import signal
    import threading

    from gamesmanmpi_tpu.db.format import DbFormatError, read_manifest
    from gamesmanmpi_tpu.registry.jobs import JobQueue, run_pending
    from gamesmanmpi_tpu.registry.server import RegistryServer, publish_db

    root = pathlib.Path(args.root)
    if args.registry_cmd == "publish":
        try:
            name = args.name or str(read_manifest(args.db)["game"])
            record = publish_db(root, name, args.db)
        except (DbFormatError, ValueError, OSError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"name": name, "epoch": record["epoch"],
                          "files": len(record["files"])}))
        return 0

    if args.registry_cmd == "run-jobs":
        queue = JobQueue(root / "jobs.jsonl")
        work = pathlib.Path(args.work_dir) if args.work_dir else root / "work"
        results = run_pending(queue, root, work,
                              book_plies=args.book_plies, once=args.once,
                              log=_jsonl_stderr)
        print(json.dumps({"ran": len(results), "results": results},
                         default=str))
        return 0 if all(r["ok"] for r in results) else 1

    # registry serve
    queue = JobQueue(root / "jobs.jsonl") if args.jobs else None
    srv = RegistryServer(root, host=args.host, port=args.port, queue=queue)
    print(
        f"registry [{root}] on {srv.url} "
        f"({'with' if queue else 'no'} solve-on-demand queue)",
        flush=True,
    )
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    srv.start()
    try:
        stop.wait()
    finally:
        srv.stop()
    return 0


def _jsonl_stderr(record):
    sys.stderr.write(json.dumps(record, default=str) + "\n")
    sys.stderr.flush()


def _db_main(argv) -> int:
    from gamesmanmpi_tpu.utils.platform import (
        apply_platform_env,
        pin_host_cpu,
        use_compile_cache,
    )

    args = _db_parser().parse_args(argv)
    if args.cmd in ("serve", "query"):
        # The reader's kernels are host-side by design: serving and
        # querying never load the accelerator, so N fleet workers (forked
        # from this process) do not race for the chip.
        pin_host_cpu()
    else:
        # export-db / registry solve: same platform policy as the solve
        # path, before the first backend touch.
        apply_platform_env(default_fake_devices=1)
        use_compile_cache()
    if args.cmd == "export-db":
        return _cmd_export_db(args)
    if args.cmd == "serve":
        return _cmd_serve(args)
    if args.cmd == "registry":
        return _cmd_registry(args)
    return _cmd_query(args)


if __name__ == "__main__":
    sys.exit(main())
