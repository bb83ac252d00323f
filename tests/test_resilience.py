"""Resilience subsystem: fault injection, retry, integrity, degradation.

Acceptance axes (ISSUE 4):

* chaos parity — a solve KILLED at any registered fault point and then
  resumed produces a byte-identical table to an uninterrupted solve
  (subprocess tests, marked slow; ttt single-device + sharded connect4);
* transient recovery — an injected transient runtime error at each
  engine fault point is absorbed by retry (retry counter >= 1) with
  oracle-exact results, while an injected fatal error still fails fast
  with the checkpoint prefix intact (fast in-process tests, tier-1);
* checkpoint integrity — a sealed level whose bytes rot fails its
  manifest crc32, is quarantined (.corrupt) and recomputed from the
  intact prefix;
* serving degradation — reader faults trip the circuit breaker (503 +
  /healthz "degraded", never a hang past the request deadline) and the
  background half-open re-probe recovers to "ok" without a restart;
* fleet chaos (ISSUE 7) — the serve.worker_spawn / serve.heartbeat /
  serve.reload fault points: a crash-looping worker opens the
  restart-storm breaker, a heartbeat stall is killed and restarted,
  and a faulted rolling reload fails closed with the fleet untouched
  (lifecycle + load chaos live in tests/test_serve_fleet.py).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.resilience import faults
from gamesmanmpi_tpu.resilience.faults import FatalFault, TransientFault
from gamesmanmpi_tpu.resilience.retry import is_transient, retry_call
from gamesmanmpi_tpu.resilience.supervisor import Watchdog
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.utils.checkpoint import (
    LevelCheckpointer,
    _loadz,
    file_crc32,
    save_result_npz,
)

from helpers import REPO, full_table

_CLI = [sys.executable, "-m", "gamesmanmpi_tpu.cli"]


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """Every test starts and ends disarmed, with fast retry backoff."""
    monkeypatch.setenv("GAMESMAN_RETRY_BASE_SECS", "0.01")
    faults.clear()
    yield
    faults.clear()


#: The in-process tests' game: the 3x3 connect-3 board (694 positions,
#: uniform level jump -> fast path) — every engine/checkpoint code path
#: the resilience layer touches, at a fraction of tictactoe's cost. The
#: chaos subprocess tests below keep full tictactoe (the acceptance
#: game).
_C3 = "connect4:w=3,h=3,connect=3"


@pytest.fixture(scope="module")
def c3_clean():
    """Uninterrupted connect-3 solve: the in-process parity baseline."""
    return Solver(get_game(_C3)).solve()


@pytest.fixture(scope="module")
def ttt_clean():
    """Uninterrupted tictactoe solve: the chaos parity baseline."""
    return Solver(get_game("tictactoe")).solve()


# ----------------------------------------------------------- faults (unit)


def test_fault_spec_parsing_and_schedule():
    faults.configure("engine.forward:transient:2")
    faults.fire("engine.forward")  # visit 1: nothing
    with pytest.raises(TransientFault):
        faults.fire("engine.forward")  # visit 2: fires
    faults.fire("engine.forward")  # visit 3: nothing (one-shot schedule)

    faults.configure("db.probe:fatal:always")
    for _ in range(3):
        with pytest.raises(FatalFault):
            faults.fire("db.probe")

    # Seeded Bernoulli schedules replay identically.
    def sequence():
        faults.configure("serve.flush:transient:p0.5@7")
        fired = []
        for i in range(20):
            try:
                faults.fire("serve.flush")
                fired.append(False)
            except TransientFault:
                fired.append(True)
        return fired

    a, b = sequence(), sequence()
    assert a == b and any(a) and not all(a)

    with pytest.raises(ValueError):
        faults.configure("no.such.point:kill")
    with pytest.raises(ValueError):
        faults.configure("db.probe:frobnicate")
    faults.clear()
    faults.fire("db.probe")  # disarmed: free and silent


def test_transient_classification():
    assert is_transient(TransientFault("x"))
    assert not is_transient(FatalFault("x"))
    assert is_transient(RuntimeError("UNAVAILABLE: socket closed"))
    assert is_transient(RuntimeError("DEADLINE_EXCEEDED: transport stall"))
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED: OOM"))
    assert not is_transient(ValueError("UNAVAILABLE"))  # not a runtime error
    assert not is_transient(KeyboardInterrupt())


def test_retry_call_reset_and_exhaustion():
    calls = []

    def flaky():
        calls.append("call")
        if len([c for c in calls if c == "call"]) < 3:
            raise TransientFault("injected transient")
        return "done"

    assert retry_call(
        flaky, point="t", reset=lambda: calls.append("reset"),
        attempts=3, base_secs=0,
    ) == "done"
    assert calls == ["call", "reset", "call", "reset", "call"]

    with pytest.raises(TransientFault):
        retry_call(lambda: (_ for _ in ()).throw(TransientFault("x")),
                   point="t", attempts=2, base_secs=0)
    with pytest.raises(FatalFault):  # fatal: no second call
        n = []
        retry_call(lambda: n.append(1) or (_ for _ in ()).throw(
            FatalFault("x")), point="t", attempts=3, base_secs=0)


# ------------------------------------------- transient recovery (engines)


@pytest.mark.parametrize(
    "point", ["engine.forward", "engine.dedup", "engine.backward"]
)
def test_transient_absorbed_at_engine_points(point, c3_clean):
    """An injected transient at each engine fault point is absorbed by
    retry (counter >= 1) with results identical to a clean solve."""
    faults.configure(f"{point}:transient:2")
    result = Solver(get_game(_C3)).solve()
    assert result.stats["retries"] >= 1
    assert (result.value, result.remoteness) == (
        c3_clean.value, c3_clean.remoteness
    )
    assert full_table(result) == full_table(c3_clean)


@pytest.mark.parametrize("point", ["sharded.forward", "sharded.backward"])
def test_transient_absorbed_at_sharded_points(point, c3_clean):
    from gamesmanmpi_tpu.parallel import ShardedSolver

    faults.configure(f"{point}:transient:2")
    result = ShardedSolver(get_game(_C3), num_shards=2).solve()
    assert result.stats["retries"] >= 1
    assert full_table(result) == full_table(c3_clean)


def test_transient_absorbed_generic_path():
    """Multi-jump (generic-path) forward/backward retry too."""
    from gamesmanmpi_tpu.solve.oracle import oracle_solve
    from helpers import REF_GAMES, load_module

    faults.configure("engine.forward:transient:1,engine.backward:transient:1")
    result = Solver(get_game("nim:heaps=3-4-5")).solve()
    assert result.stats["retries"] >= 2
    _, _, oracle = oracle_solve(load_module(REF_GAMES / "nim_345.py"))
    assert full_table(result) == oracle


def _fire_counts(game_spec, num_shards=2):
    """Per-point fault fire sequence of one clean sharded solve —
    locates specific retried units by visit index."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    seq = []
    real_fire = faults.fire

    def recording_fire(point, **kw):
        seq.append(point)
        return real_fire(point, **kw)

    faults.fire = recording_fire
    try:
        result = ShardedSolver(get_game(game_spec), num_shards=num_shards
                               ).solve()
    finally:
        faults.fire = real_fire
    return result, seq


def test_transient_absorbed_generic_sharded_check_merge():
    """GM603 regression (lint round 10): the generic forward path's
    level-check and merge dispatches are collective-safe-retried. Visit
    2 of sharded.forward on a multi-jump game is the first level's
    check step (visit 1 is its frontier expansion) — a transient there
    must be absorbed oracle-exact, not crash the solve."""
    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.solve.oracle import oracle_solve
    from helpers import REF_GAMES, load_module

    clean, seq = _fire_counts("nim:heaps=3-4-5")
    forward_fires = seq.count("sharded.forward")
    # the generic path must fire MORE than once per level now that the
    # check/merge units are routed through _retry
    assert forward_fires > clean.stats["levels"], (forward_fires, seq)
    faults.configure("sharded.forward:transient:2")
    result = ShardedSolver(get_game("nim:heaps=3-4-5"), num_shards=2
                           ).solve()
    assert result.stats["retries"] >= 1
    _, _, oracle = oracle_solve(load_module(REF_GAMES / "nim_345.py"))
    assert full_table(result) == oracle


def test_transient_absorbed_at_sharded_root_step(c3_clean):
    """GM603 regression (lint round 10): the backward root-answer
    dispatch (a psum across shards) is retried too. The LAST
    sharded.backward fire of a solve is the root step — inject a
    transient exactly there and require absorption with the exact
    root answer."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    _, seq = _fire_counts(_C3)
    last_backward_visit = seq.count("sharded.backward")
    assert last_backward_visit > 0
    faults.configure(
        f"sharded.backward:transient:{last_backward_visit}"
    )
    result = ShardedSolver(get_game(_C3), num_shards=2).solve()
    assert result.stats["retries"] >= 1
    assert (result.value, result.remoteness) == (
        c3_clean.value, c3_clean.remoteness
    )
    assert full_table(result) == full_table(c3_clean)


def test_fatal_fails_fast_with_checkpoint_prefix_intact(tmp_path, c3_clean):
    """A fatal error mid-backward aborts immediately; the levels sealed
    before it remain loadable and the next run resumes to parity."""
    ck = LevelCheckpointer(tmp_path / "ck")
    faults.configure("engine.backward:fatal:3")
    with pytest.raises(FatalFault):
        Solver(get_game(_C3), checkpointer=ck).solve()
    # Prefix intact: forward discovery complete, >= 2 levels sealed
    # (visits 1-2 resolved + saved before visit 3 died).
    assert ck.load_manifest().get("frontiers_complete")
    sealed = ck.completed_levels()
    assert len(sealed) >= 2
    for k in sealed:
        ck.load_level(k)  # loads clean (atomic saves, valid crc)
    faults.clear()
    resumed = Solver(get_game(_C3), checkpointer=ck).solve()
    assert full_table(resumed) == full_table(c3_clean)


# -------------------------------------------------- checkpoint integrity


def _flip_byte(path, offset_frac=0.5):
    size = os.path.getsize(path)
    off = max(0, int(size * offset_frac))
    with open(path, "r+b") as fh:
        fh.seek(off)
        b = fh.read(1)
        fh.seek(off)
        fh.write(bytes([b[0] ^ 0xFF]))


@pytest.mark.parametrize("ckpt_mode", ["auto", "blocks"])
def test_crc_quarantines_corrupt_level_and_recomputes(tmp_path, c3_clean,
                                                      monkeypatch,
                                                      ckpt_mode):
    """Silent bit-rot in a sealed level: crc mismatch on resume ->
    quarantine (.corrupt) -> the level recomputes from the intact
    prefix -> parity. Parametrized over the block-compressed checkpoint
    format (ISSUE 9): torn compressed blocks must quarantine-and-degrade
    exactly like v1 files."""
    monkeypatch.setenv("GAMESMAN_CKPT_COMPRESS", ckpt_mode)
    ck = LevelCheckpointer(tmp_path / "ck")
    Solver(get_game(_C3), checkpointer=ck).solve()
    sealed = ck.completed_levels()
    victim = sealed[len(sealed) // 2]
    victim_file = tmp_path / "ck" / f"level_{victim:04d}.npz"
    recorded = ck.load_manifest()["crc"][victim_file.name]
    _flip_byte(victim_file)
    assert file_crc32(victim_file) != recorded  # the rot is real
    resumed = Solver(get_game(_C3),
                     checkpointer=LevelCheckpointer(tmp_path / "ck")).solve()
    assert full_table(resumed) == full_table(c3_clean)
    corrupt = list((tmp_path / "ck").glob("*.corrupt"))
    assert any(victim_file.name in p.name for p in corrupt)
    # The recompute re-sealed the level with a fresh crc.
    ck2 = LevelCheckpointer(tmp_path / "ck")
    assert victim in ck2.completed_levels()
    assert ck2.load_manifest()["crc"][victim_file.name] == \
        file_crc32(victim_file)


def test_crc_quarantines_corrupt_frontier_and_reexpands(tmp_path, c3_clean):
    """Bit-rot in a frontier file degrades the forward snapshot to the
    intact prefix (re-expansion resumes from its deepest level)."""
    ck = LevelCheckpointer(tmp_path / "ck")
    Solver(get_game(_C3), checkpointer=ck).solve()
    frontier = tmp_path / "ck" / "frontier_0004.npz"
    _flip_byte(frontier)
    resumed = Solver(get_game(_C3),
                     checkpointer=LevelCheckpointer(tmp_path / "ck")).solve()
    assert full_table(resumed) == full_table(c3_clean)
    assert (tmp_path / "ck" / "frontier_0004.npz.corrupt").exists()


def test_crc_verify_can_be_disabled(tmp_path, monkeypatch):
    ck = LevelCheckpointer(tmp_path / "ck")
    Solver(get_game(_C3), checkpointer=ck).solve()
    monkeypatch.setenv("GAMESMAN_CKPT_VERIFY", "0")
    # With verification off a rotted file is only caught if the zip
    # itself breaks — the knob exists for read-heavy resumes on trusted
    # storage. Just assert the clean path still loads.
    for k in ck.completed_levels():
        ck.load_level(k)


# -------------------------------------------------------------- watchdog


def test_watchdog_expires_on_stall_and_dumps_diagnostics(capfd):
    fired = threading.Event()
    records = []

    class Log:
        def log(self, rec):
            records.append(rec)

    prog = {"phase": "backward", "level": 3}
    wd = Watchdog(lambda: prog, min_secs=0.1, factor=2.0, poll=0.02,
                  action=fired.set, logger=Log()).start()
    try:
        assert fired.wait(5.0)
    finally:
        wd.stop()
    assert wd.expired
    assert records and records[0]["phase"] == "watchdog_abort"
    assert records[0]["progress"] == prog
    err = capfd.readouterr().err
    assert "stall detected" in err
    # Thread stacks were dumped (faulthandler output).
    assert "Current thread" in err or "Thread" in err


def test_watchdog_tracks_progress_and_adapts_deadline():
    fired = threading.Event()
    prog = {"phase": "forward", "level": 0}
    wd = Watchdog(lambda: prog, min_secs=0.2, factor=3.0, poll=0.02,
                  action=fired.set).start()
    try:
        for lvl in range(1, 4):  # steady progress: no expiry
            time.sleep(0.05)
            prog = {"phase": "forward", "level": lvl}
        assert not fired.is_set()
        assert wd.deadline() >= 0.2
    finally:
        wd.stop()


# ------------------------------------------------- serving degradation


@pytest.fixture(scope="module")
def nim_reader(tmp_path_factory):
    # Any registry game works for the degradation tests; the subtraction
    # game is the cheapest DB in the catalog.
    from gamesmanmpi_tpu.db import DbReader, export_result

    spec = "subtract:total=21,moves=1-2-3"
    d = tmp_path_factory.mktemp("resdb")
    export_result(Solver(get_game(spec)).solve(), d, spec)
    with DbReader(d) as reader:
        yield reader


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def test_breaker_opens_on_reader_faults_and_self_heals(nim_reader):
    """Batcher-level: consecutive reader faults trip the breaker; misses
    fail fast; the background half-open re-probe closes it once the
    reader heals — no restart, no client request spent probing."""
    from gamesmanmpi_tpu.obs import MetricsRegistry
    from gamesmanmpi_tpu.serve import Batcher, BatcherTripped

    pos = int(nim_reader.game.initial_state())
    batcher = Batcher(
        nim_reader, window=0.002, cache_size=0, breaker_threshold=2,
        breaker_cooldown=0.1, request_timeout=5.0,
        registry=MetricsRegistry(),
    )
    try:
        faults.configure("db.probe:fatal:always")
        for _ in range(2):  # two faulted flushes open the circuit
            with pytest.raises(FatalFault):
                batcher.submit([pos])
        deadline = time.monotonic() + 5
        while batcher.state == "ok" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert batcher.state != "ok"
        with pytest.raises(BatcherTripped) as e:
            batcher.submit([pos])
        assert e.value.retry_after >= 1
        assert batcher.metrics()["breaker_opens"] >= 1
        # Reader heals: the worker's half-open probe closes the circuit.
        faults.clear()
        deadline = time.monotonic() + 10
        while batcher.state != "ok" and time.monotonic() < deadline:
            time.sleep(0.02)
        assert batcher.state == "ok"
        out = batcher.submit([pos])
        assert out[0][2] is True  # found again
    finally:
        batcher.close()


def test_server_degrades_and_recovers_over_http(nim_reader):
    """HTTP-level acceptance: injected reader faults -> 503 (never a
    hang past the deadline), /healthz 'degraded', breaker recovery to
    'ok' without a restart."""
    from gamesmanmpi_tpu.serve import QueryServer

    pos = int(nim_reader.game.initial_state())
    with QueryServer(
        nim_reader, window=0.002, cache_size=0,
        breaker_threshold=2, breaker_cooldown=0.1, request_timeout=2.0,
    ) as server:
        base = f"http://127.0.0.1:{server.port}"
        assert _get(base + "/healthz")[1]["status"] == "ok"
        faults.configure("db.probe:fatal:always")
        codes = []
        for _ in range(3):
            try:
                t0 = time.monotonic()
                _post(base + "/query", {"positions": [pos]})
                codes.append(200)
            except urllib.error.HTTPError as e:
                codes.append(e.code)
                if e.code == 503:
                    assert e.headers["Retry-After"] is not None
            assert time.monotonic() - t0 < 5  # never hangs
        assert 500 in codes  # the raw reader faults
        deadline = time.monotonic() + 5
        while (_get(base + "/healthz")[1]["status"] != "degraded"
               and time.monotonic() < deadline):
            time.sleep(0.02)
        health = _get(base + "/healthz")[1]
        assert health["status"] == "degraded"
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base + "/query", {"positions": [pos]})
        assert e.value.code == 503
        # Heal the reader; the breaker closes in the background.
        faults.clear()
        deadline = time.monotonic() + 10
        while (_get(base + "/healthz")[1]["status"] != "ok"
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert _get(base + "/healthz")[1]["status"] == "ok"
        status, body = _post(base + "/query", {"positions": [pos]})
        assert status == 200 and body["results"][0]["found"]
        metrics = _get(base + "/metrics.json")[1]
        assert metrics["reader_faults"] >= 2
        assert metrics["breaker_opens"] >= 1


def test_request_deadline_times_out_as_503(nim_reader):
    """A wedged flush (injected delay) must answer 503 + Retry-After
    within the request deadline, not hang the client."""
    from gamesmanmpi_tpu.serve import QueryServer

    pos = int(nim_reader.game.initial_state())
    faults.configure("serve.flush:delay=0.5:always")
    with QueryServer(
        nim_reader, window=0.001, cache_size=0, request_timeout=0.05,
    ) as server:
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"http://127.0.0.1:{server.port}/query",
                  {"positions": [pos]})
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] is not None
        assert time.monotonic() - t0 < 2
        assert server.metrics()["timeouts"] >= 1


def test_chaos_slow_block_decode_dominates_trace_and_burns_slo(
        tmp_path_factory, monkeypatch):
    """ISSUE 17 chaos acceptance, single-server sized: arm a
    ``serve.block_decode`` delay fault on a compressed (v2) DB and (1)
    the sampled trace — joined by the CLIENT's minted trace id — must
    attribute the latency to the decode span, and (2) the latency SLO's
    fast-window burn rate must cross fast-burn (healthz 'degraded')
    during the fault and recover after it without a restart."""
    from gamesmanmpi_tpu.db import DbReader, export_result
    from gamesmanmpi_tpu.obs.qtrace import (
        format_traceparent,
        mint_trace_ids,
    )
    from gamesmanmpi_tpu.serve import QueryServer

    spec = "subtract:total=21,moves=1-2-3"
    d = tmp_path_factory.mktemp("chaosv2")
    export_result(Solver(get_game(spec)).solve(), d, spec, compress=True)
    # Keep-everything head sampling is NOT needed: the delayed queries
    # are kept as "slow". Shrink the SLO windows/volume gate so a
    # handful of slow requests trips fast-burn and a few seconds of
    # health recovers it (BUCKET_SECS=1 makes that honest).
    monkeypatch.setenv("GAMESMAN_TRACE_SLOW_MS", "60")
    monkeypatch.setenv("GAMESMAN_SLO_P99_MS", "60")
    monkeypatch.setenv("GAMESMAN_SLO_MIN_REQUESTS", "4")
    monkeypatch.setenv("GAMESMAN_SLO_FAST_WINDOW_SECS", "4")
    # One slow request among seven is a ~14x burn on the 1% latency
    # budget — just under the 14.4 default, so declare the paging
    # threshold this test means to cross.
    monkeypatch.setenv("GAMESMAN_SLO_FAST_BURN", "5")
    delay_ms = 150.0
    # Positions in DISTINCT solve levels: each level is its own v2
    # block stream, so every query forces a fresh (delayed) decode —
    # same-level repeats would hit the decoded-block cache and be fast.
    positions = [20, 17, 14, 11, 8, 5, 2]
    with DbReader(d) as reader, QueryServer(
        reader, window=0.001, cache_size=0, request_timeout=10.0,
    ) as server:
        base = f"http://127.0.0.1:{server.port}"
        faults.configure(f"serve.block_decode:delay={delay_ms / 1e3}"
                         ":always")
        tid, sid = mint_trace_ids()
        req = urllib.request.Request(
            base + "/query",
            data=json.dumps({"positions": [positions[0]]}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": format_traceparent(tid, sid)},
            method="POST",
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
        client_ms = (time.perf_counter() - t0) * 1e3
        assert body["results"][0]["found"]
        assert client_ms >= delay_ms  # the fault really ran

        # Join the server-side sampled trace by the client's trace id.
        rec = next(t for t in _get(base + "/traces")[1]["traces"]
                   if t["trace_id"] == tid)
        assert rec["keep"] == "slow" and rec["parent_id"] == sid
        decode_ms = sum(s["dur_ms"] for s in rec["spans"]
                        if s["name"] == "block_decode")
        assert decode_ms >= delay_ms * 0.9
        # The decode span dominates the trace, and the traced duration
        # accounts for the client-observed latency (within the HTTP +
        # loopback overhead).
        assert decode_ms >= 0.5 * rec["dur_ms"]
        assert rec["dur_ms"] <= client_ms

        # Burn the latency budget: every remaining cold-level query
        # eats the decode delay, all inside the 2s fast window.
        for pos in positions[1:]:
            status, body = _post(base + "/query", {"positions": [pos]})
            assert status == 200 and body["results"][0]["found"]
        health = _get(base + "/healthz")[1]
        lat = health["slo"]["routes"]["default"]["latency"]
        assert lat["fast_burn"] and lat["burn_fast"] > 5.0
        assert health["status"] == "degraded"  # pre-emptive amber

        # The fault ends; the decoded blocks are cached, traffic is
        # fast again, and the fast window forgets the bad second.
        faults.clear()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            for pos in positions:
                _post(base + "/query", {"positions": [pos]})
            health = _get(base + "/healthz")[1]
            if health["status"] == "ok":
                break
            time.sleep(0.25)
        assert health["status"] == "ok"
        assert not health["slo"]["routes"]["default"]["latency"][
            "fast_burn"]


def test_drain_flips_healthz_and_refuses_new_queries(nim_reader):
    from gamesmanmpi_tpu.serve import QueryServer

    pos = int(nim_reader.game.initial_state())
    with QueryServer(nim_reader) as server:
        base = f"http://127.0.0.1:{server.port}"
        assert _post(base + "/query", {"positions": [pos]})[0] == 200
        server.begin_drain()
        assert _get(base + "/healthz")[1]["status"] == "draining"
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base + "/query", {"positions": [pos]})
        assert e.value.code == 503


# -------------------------------------------------- chaos (subprocess)


def _run_cli(args, extra_env=None, timeout=600):
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env.pop("GAMESMAN_FAULTS", None)
    env.update(extra_env or {})
    return subprocess.run(
        _CLI + list(args), capture_output=True, text=True,
        timeout=timeout, env=env, cwd=str(REPO),
    )


def _assert_tables_equal(a, b):
    # _loadz, not np.load: byte-parity means LOGICAL table equality, and
    # a blocks-mode run's --table-out is block-framed on disk (the
    # ckpt_mode chaos parametrization compares against a plain golden).
    with _loadz(a) as za, _loadz(b) as zb:
        assert sorted(za.files) == sorted(zb.files)
        for f in za.files:
            assert np.array_equal(za[f], zb[f]), f


@pytest.fixture(scope="module")
def ttt_clean_table(tmp_path_factory, ttt_clean):
    path = tmp_path_factory.mktemp("golden") / "ttt.npz"
    save_result_npz(path, ttt_clean)
    return path


#: Every solve-path fault point a single-device run visits. This is the
#: systematized chaos surface: killing at each, resuming, and asserting
#: byte parity is the whole-failure-surface generalization of PR 3's
#: one-off edge-spill-resume test.
_SINGLE_POINTS = [
    "engine.forward", "engine.dedup", "engine.backward",
    "ckpt.save_frontier", "ckpt.save_level",
]


@pytest.mark.slow
@pytest.mark.parametrize("point", _SINGLE_POINTS)
def test_chaos_kill_and_resume_parity_ttt(point, tmp_path, ttt_clean_table):
    ck = tmp_path / "ck"
    killed = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": f"{point}:kill:2"},
    )
    assert killed.returncode == faults.KILL_EXIT_CODE, (
        f"{point}: expected injected death, got rc={killed.returncode}\n"
        + killed.stderr[-2000:]
    )
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck), "--table-out", str(out)]
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "positions: 5478" in resumed.stdout
    _assert_tables_equal(out, ttt_clean_table)


@pytest.mark.slow
@pytest.mark.parametrize("ckpt_mode", ["auto", "blocks"])
def test_chaos_torn_seal_and_resume_parity(tmp_path, ttt_clean_table,
                                           ckpt_mode):
    """The torn-write kind: a sealed level file is truncated and the
    process dies. Resume must quarantine (crc/zip failure) and
    recompute to parity — identically when the checkpoint is
    block-compressed (GAMESMAN_CKPT_COMPRESS=blocks, ISSUE 9): a torn
    compressed file is just one more TORN_NPZ_ERRORS shape."""
    ck = tmp_path / "ck"
    killed = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": "ckpt.save_level:torn:2",
         "GAMESMAN_CKPT_COMPRESS": ckpt_mode},
    )
    assert killed.returncode == faults.TORN_EXIT_CODE, killed.stderr[-2000:]
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck), "--table-out", str(out)],
        {"GAMESMAN_CKPT_COMPRESS": ckpt_mode},
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_tables_equal(out, ttt_clean_table)
    assert list(ck.glob("*.corrupt")), "torn file was not quarantined"


@pytest.mark.slow
def test_chaos_double_death_resume(tmp_path, ttt_clean_table):
    """Die mid-backward, then die again during the resume's level load,
    then finish: two deaths, one checkpoint directory, exact parity."""
    ck = tmp_path / "ck"
    first = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": "engine.backward:kill:3"},
    )
    assert first.returncode == faults.KILL_EXIT_CODE, first.stderr[-2000:]
    second = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": "ckpt.load_level:kill:1"},
    )
    assert second.returncode == faults.KILL_EXIT_CODE, second.stderr[-2000:]
    out = tmp_path / "resumed.npz"
    final = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck), "--table-out", str(out)]
    )
    assert final.returncode == 0, final.stderr[-2000:]
    _assert_tables_equal(out, ttt_clean_table)


_C4 = "connect4:w=4,h=4"


@pytest.fixture(scope="module")
def c4_clean_table(tmp_path_factory):
    from gamesmanmpi_tpu.parallel import ShardedSolver

    path = tmp_path_factory.mktemp("golden") / "c4.npz"
    save_result_npz(
        path, ShardedSolver(get_game(_C4), num_shards=2).solve()
    )
    return path


@pytest.mark.slow
@pytest.mark.parametrize(
    "point",
    [
        "sharded.forward", "sharded.backward", "ckpt.save_level",
        # ISSUE 11: death on the write-behind worker right after a
        # queued payload write lands, BEFORE its seal can run — the
        # unsealed stray must be invisible to resume (the solve thread
        # may already be a level ahead when the kill fires).
        "store.writebehind",
    ],
)
def test_chaos_kill_and_resume_parity_sharded_c4(point, tmp_path,
                                                 c4_clean_table):
    ck = tmp_path / "ck"
    killed = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": f"{point}:kill:3"},
    )
    assert killed.returncode == faults.KILL_EXIT_CODE, (
        f"{point}: expected injected death, got rc={killed.returncode}\n"
        + killed.stderr[-2000:]
    )
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck),
         "--table-out", str(out)]
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_tables_equal(out, c4_clean_table)


@pytest.mark.slow
def test_chaos_watchdog_aborts_wedged_solve(tmp_path):
    """A wedged level (injected long delay) under the watchdog exits 124
    with diagnostics; the checkpoint prefix resumes to completion."""
    import signal as _signal  # noqa: F401 - documents the non-signal abort

    ck = tmp_path / "ck"
    wedged = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck), "--watchdog-secs", "1"],
        {"GAMESMAN_FAULTS": "engine.backward:delay=120:2",
         "GAMESMAN_WATCHDOG_FACTOR": "1"},
        timeout=300,
    )
    assert wedged.returncode == 124, (
        f"rc={wedged.returncode}\n" + wedged.stderr[-2000:]
    )
    assert "stall detected" in wedged.stderr
    resumed = _run_cli(["tictactoe", "--checkpoint-dir", str(ck)])
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "positions: 5478" in resumed.stdout


# ------------------------------------ distributed chaos (ISSUE 6)
#
# The rank-death scenarios: under REAL 2-process execution (tools/
# launch_multihost.py) a transient at a collective fault point must be
# retried by ALL ranks together, a dead rank must turn into a
# coordinated abort (every survivor exits 124 within the barrier
# deadline, checkpoint prefix intact), and a full restart must resume
# to byte-parity — never a hang. Fault points covered here:
# sharded.collective (collective entry), coord.barrier (epoch-barrier
# proposal), coord.handshake (coordinator dial).


def _coordinated_world1_solver(game_spec, num_shards=2):
    """A sharded solver driven through the collective-safe retry
    protocol with a real (world-1) consensus service: every retry
    decision is a genuine epoch round over a loopback socket, in one
    process — the tier-1 way to exercise _retry_collective."""
    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.resilience.coordination import (
        Coordination,
        CoordinatorServer,
        EpochBarrier,
    )

    solver = ShardedSolver(get_game(game_spec), num_shards=num_shards)
    srv = CoordinatorServer(1, deadline=10.0)
    solver.coord = Coordination(
        EpochBarrier(srv.address, 0, deadline=10.0), srv
    )
    return solver


def test_coordinated_retry_at_collective_point(c3_clean):
    """A transient at sharded.collective (the collective-entry fault
    point) resolves through a consensus round into a coordinated retry:
    counter bumped, results oracle-exact."""
    faults.configure("sharded.collective:transient:2")
    result = _coordinated_world1_solver(_C3).solve()
    assert result.stats["retries"] >= 1
    assert full_table(result) == full_table(c3_clean)


def test_coordinated_abort_on_fatal_at_collective_point():
    """A fatal at the collective entry aborts through the same round —
    fail fast, no retry loop, coordination torn down cleanly."""
    faults.configure("sharded.collective:fatal:2")
    solver = _coordinated_world1_solver(_C3)
    with pytest.raises(FatalFault):
        solver.solve()
    assert solver.retries == 0


def test_coordinated_abort_attribution():
    """ABORT decisions must attribute correctly: a rank whose own
    verdict was ABORT fails fast with ITS error; a rank that proposed
    retry (or was healthy) aborts because of a PEER and must raise
    CoordinatedAbort — the exception the CLI maps to exit 124 — never
    its own retryable error as if the fleet had refused a retry."""
    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.resilience.coordination import (
        ABORT,
        RETRY,
        CoordinatedAbort,
    )

    solver = ShardedSolver(get_game(_C3), num_shards=2)
    fatal = FatalFault("own fatal")
    with pytest.raises(FatalFault):
        solver._coordinated_abort("sharded.forward", 3, fatal, ABORT)
    flaky = TransientFault("flaky link")
    with pytest.raises(CoordinatedAbort) as ei:
        solver._coordinated_abort("sharded.forward", 3, flaky, RETRY)
    assert ei.value.__cause__ is flaky
    assert "proposed retry" in str(ei.value)
    with pytest.raises(CoordinatedAbort, match="healthy"):
        solver._coordinated_abort("sharded.forward", 3, None, ABORT)


def _launch_world(args, tmp, per_rank_env=None, env=None, timeout=240):
    from tools import launch_multihost

    return launch_multihost.launch(
        list(args), processes=2, timeout=timeout, log_dir=str(tmp),
        per_rank_env=per_rank_env, env=env,
    )


_NO_BACKEND = "Multiprocess computations aren't implemented"


def _skip_unless_world_spawned(ranks):
    if any(r.returncode != 0 and _NO_BACKEND in r.stderr for r in ranks):
        pytest.skip("backend cannot run multiprocess collectives "
                    "(no CPU Gloo) — the harness cannot spawn a world")


@pytest.mark.slow
def test_chaos_rank_death_coordinated_abort_and_resume(tmp_path):
    """THE rank-death acceptance scenario: SIGKILL one rank mid-level on
    a 2-process sharded connect4 solve. The survivor must abort within
    the barrier deadline (exit 124, not a harness kill), the checkpoint
    prefix must stay intact, and a full restart must resume to
    byte-parity with an uninterrupted solve."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    ck = tmp_path / "ck"
    t0 = time.monotonic()
    ranks = _launch_world(
        [_C4, "--devices", "4", "--checkpoint-dir", str(ck)],
        tmp_path,
        env={"GAMESMAN_BARRIER_SECS": "10",
             "GAMESMAN_COLLECTIVE_TIMEOUT": "60"},
        per_rank_env={1: {"GAMESMAN_FAULTS": "sharded.forward:kill:3"}},
        timeout=150,
    )
    elapsed = time.monotonic() - t0
    _skip_unless_world_spawned(ranks)
    by = {r.rank: r for r in ranks}
    assert by[1].returncode == faults.KILL_EXIT_CODE, (
        by[1].returncode, by[1].stderr[-2000:]
    )
    # The survivor exited THROUGH the coordinated-abort contract — 124
    # within the deadline — not None (a straggler the harness killed).
    assert by[0].returncode == 124, (
        by[0].returncode, by[0].stderr[-2000:]
    )
    assert elapsed < 150, "survivor did not abort within the deadline"
    assert "coordinated abort" in by[0].stderr.lower()
    # Prefix intact: whatever sealed before the death loads clean.
    ck_obj = LevelCheckpointer(ck)
    for k in ck_obj.completed_levels():
        ck_obj.load_level(k)
    # Full restart reaches byte-parity with an uninterrupted 4-shard run.
    ranks2 = _launch_world(
        [_C4, "--devices", "4", "--checkpoint-dir", str(ck),
         "--table-out", str(tmp_path / "resumed.npz")],
        tmp_path,
    )
    for r in ranks2:
        assert r.returncode == 0, (r.rank, r.stderr[-2000:])
    golden = tmp_path / "golden.npz"
    save_result_npz(
        golden, ShardedSolver(get_game(_C4), num_shards=4).solve()
    )
    _assert_tables_equal(tmp_path / "resumed.rank0.npz", golden)


@pytest.mark.slow
def test_chaos_transient_on_one_rank_retries_on_all_ranks(tmp_path):
    """Acceptance: a transient injected at the collective fault point on
    ONE rank is retried consistently on all ranks — the solve completes
    and gamesman_retries_total agrees across ranks."""
    ranks = _launch_world(
        [_C3, "--devices", "4",
         "--metrics-out", str(tmp_path / "metrics.json")],
        tmp_path,
        env={"GAMESMAN_BARRIER_SECS": "20"},
        per_rank_env={1: {"GAMESMAN_FAULTS": "sharded.collective:transient:2"}},
    )
    _skip_unless_world_spawned(ranks)
    for r in ranks:
        assert r.returncode == 0, (r.rank, r.stderr[-2000:])
        assert "value: TIE" in r.stdout and "remoteness: 9" in r.stdout
    retries = []
    for rank in range(2):
        snap = json.loads(
            (tmp_path / f"metrics.rank{rank}.json").read_text()
        )
        rows = snap["gamesman_retries_total"]["values"]
        assert all(row["labels"]["rank"] == str(rank) for row in rows)
        retries.append(sum(int(row["value"]) for row in rows))
    # The faulted rank AND the healthy rank absorbed the same retry —
    # the whole point of the consensus round.
    assert retries[0] == retries[1] >= 1, retries


@pytest.mark.slow
@pytest.mark.parametrize(
    "spec", ["coord.handshake:kill:1", "coord.barrier:kill:2"]
)
def test_chaos_rank_death_at_coordination_points(spec, tmp_path):
    """A rank dying INSIDE the coordination layer itself (dialing the
    coordinator; at an epoch-barrier proposal) still resolves into a
    coordinated abort of the survivors within the deadline, and a clean
    restart completes."""
    ck = tmp_path / "ck"
    ranks = _launch_world(
        [_C3, "--devices", "4", "--checkpoint-dir", str(ck)],
        tmp_path,
        env={"GAMESMAN_BARRIER_SECS": "8"},
        per_rank_env={1: {"GAMESMAN_FAULTS": spec}},
        timeout=150,
    )
    _skip_unless_world_spawned(ranks)
    by = {r.rank: r for r in ranks}
    assert by[1].returncode == faults.KILL_EXIT_CODE, (
        by[1].returncode, by[1].stderr[-2000:]
    )
    assert by[0].returncode == 124, (
        by[0].returncode, by[0].stderr[-2000:]
    )
    ranks2 = _launch_world(
        [_C3, "--devices", "4", "--checkpoint-dir", str(ck)], tmp_path
    )
    for r in ranks2:
        assert r.returncode == 0, (r.rank, r.stderr[-2000:])
        assert "value: TIE" in r.stdout


@pytest.mark.slow
def test_serve_sigterm_drains_gracefully(tmp_path):
    """`cli serve` under SIGTERM: drains (stderr says so) and exits 0
    instead of dying mid-request with no teardown."""
    from gamesmanmpi_tpu.db import export_result

    spec = "subtract:total=10,moves=1-2"
    db = tmp_path / "db"
    export_result(Solver(get_game(spec)).solve(), db, spec)
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env.pop("GAMESMAN_FAULTS", None)
    proc = subprocess.Popen(
        _CLI + ["serve", str(db), "--port", "0",
                "--jsonl", str(tmp_path / "serve.jsonl")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=str(REPO),
    )
    try:
        line = proc.stdout.readline()
        assert "serving" in line, line
        port = int(line.split("http://127.0.0.1:")[1].split(" ")[0].strip())
        status, health = _get(f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert health["status"] == "ok"
        proc.send_signal(subprocess.signal.SIGTERM)
        rc = proc.wait(timeout=60)
        assert rc == 0
        assert "draining" in proc.stderr.read()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# ------------------------------------ preemption grace + enospc (ISSUE 12)
#
# Two new failure classes above the kill/torn matrix: SIGTERM/SIGUSR1
# must drain the solve to a level boundary and exit 75 with everything
# complete sealed (grace), and an injected OSError(ENOSPC) —
# GAMESMAN_FAULTS kind `enospc`, incl. at store.writebehind — must fail
# fast exactly like a torn write: prefix intact, resume to byte-parity,
# never a wrong answer. The campaign layer above both lives in
# tests/test_campaign.py.


def _arm_preempt_on_fire(point, visit):
    """Deliver SIGUSR1 to ourselves at the `visit`th fire of `point`:
    a deterministic mid-solve preemption (the handler runs on the main
    thread before the next bytecode, so the flag is set before the next
    level boundary)."""
    import signal as _signal

    state = {"n": 0}
    real_fire = faults.fire

    def firing(p, **kw):
        if p == point:
            state["n"] += 1
            if state["n"] == visit:
                _signal.raise_signal(_signal.SIGUSR1)
        return real_fire(p, **kw)

    faults.fire = firing
    return lambda: setattr(faults, "fire", real_fire)


def test_preempt_drains_at_boundary_and_resumes_parity(tmp_path, c3_clean):
    """In-process grace: SIGUSR1 mid-backward raises
    PreemptionRequested at the next level boundary; sealed levels load
    clean and the resumed solve reaches parity."""
    from gamesmanmpi_tpu.resilience import preempt

    ck = LevelCheckpointer(tmp_path / "ck")
    restore = preempt.install_grace_handler()
    unfire = _arm_preempt_on_fire("engine.backward", 2)
    try:
        with pytest.raises(preempt.PreemptionRequested):
            Solver(get_game(_C3), checkpointer=ck).solve()
    finally:
        unfire()
        restore()  # also resets the flag + disarms the deadline
    assert not preempt.requested()
    sealed = ck.completed_levels()
    assert sealed  # backward visit 2 resolved+sealed at least one level
    for k in sealed:
        ck.load_level(k)
    resumed = Solver(get_game(_C3),
                     checkpointer=LevelCheckpointer(tmp_path / "ck")).solve()
    assert full_table(resumed) == full_table(c3_clean)


def test_preempt_sharded_coordinated_round(tmp_path, c3_clean):
    """The sharded boundary check is a consensus round (world-1 here):
    a preempted solve unwinds through PreemptionRequested with pending
    seals flushed, and resumes to parity."""
    from gamesmanmpi_tpu.resilience import preempt

    ck = LevelCheckpointer(tmp_path / "ck")
    solver = _coordinated_world1_solver(_C3)
    solver.checkpointer = ck
    restore = preempt.install_grace_handler()
    unfire = _arm_preempt_on_fire("sharded.backward", 2)
    try:
        with pytest.raises(preempt.PreemptionRequested):
            solver.solve()
    finally:
        unfire()
        restore()
    for k in ck.completed_levels():
        ck.load_level(k)
    from gamesmanmpi_tpu.parallel import ShardedSolver

    resumed = ShardedSolver(
        get_game(_C3), num_shards=2,
        checkpointer=LevelCheckpointer(tmp_path / "ck"),
    ).solve()
    assert full_table(resumed) == full_table(c3_clean)


def test_preempt_not_transient_and_resets():
    from gamesmanmpi_tpu.resilience import preempt

    assert not is_transient(preempt.PreemptionRequested("x"))
    preempt.reset()
    assert not preempt.requested()
    preempt.check("forward", level=0)  # disarmed: no raise


def test_enospc_fault_kind_fails_fast_prefix_intact(tmp_path, c3_clean):
    """`enospc` at a sealed-level write point: OSError(ENOSPC), never
    retried (a full disk refills), prefix intact, resume to parity —
    the torn-write degrade contract."""
    import errno

    ck = LevelCheckpointer(tmp_path / "ck")
    faults.configure("ckpt.save_level:enospc:2")
    with pytest.raises(OSError) as ei:
        Solver(get_game(_C3), checkpointer=ck).solve()
    assert ei.value.errno == errno.ENOSPC
    assert not is_transient(ei.value)  # retrying ENOSPC is wrong
    sealed = ck.completed_levels()
    for k in sealed:
        ck.load_level(k)  # whatever sealed before the death loads clean
    faults.clear()
    resumed = Solver(get_game(_C3),
                     checkpointer=LevelCheckpointer(tmp_path / "ck")).solve()
    assert full_table(resumed) == full_table(c3_clean)


def test_oom_fault_kind_fails_fast_prefix_intact(tmp_path, c3_clean):
    """`oom` at a backward point: MemoryError carrying the
    RESOURCE_EXHAUSTED marker (the campaign classifier's food), never
    retried (an OOM at a fixed shape OOMs again), prefix intact,
    resume to parity — the enospc contract for memory."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    ck = LevelCheckpointer(tmp_path / "ck")
    faults.configure("sharded.backward:oom:2")
    with pytest.raises(MemoryError) as ei:
        ShardedSolver(get_game(_C3), num_shards=2,
                      checkpointer=ck).solve()
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert not is_transient(ei.value)  # retrying an OOM is wrong
    sealed = ck.completed_levels()
    for k in sealed:
        ck.load_level(k)  # whatever sealed before the death loads clean
    faults.clear()
    resumed = ShardedSolver(
        get_game(_C3), num_shards=2,
        checkpointer=LevelCheckpointer(tmp_path / "ck"),
    ).solve()
    assert full_table(resumed) == full_table(c3_clean)


def test_memguard_trips_at_boundary_and_resume_parity(tmp_path, c3_clean,
                                                      monkeypatch):
    """The host-memory guard: past GAMESMAN_HOST_MEM_LIMIT_MB the solve
    raises HostMemoryExceeded at the NEXT level boundary — prefix
    sealed, resume (limit lifted) to parity; off by default."""
    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.resilience import memguard

    memguard.check("forward", level=0)  # disarmed: no raise
    ck = LevelCheckpointer(tmp_path / "ck")
    monkeypatch.setenv("GAMESMAN_HOST_MEM_LIMIT_MB", "1")  # any RSS trips
    with pytest.raises(memguard.HostMemoryExceeded) as ei:
        ShardedSolver(get_game(_C3), num_shards=2,
                      checkpointer=ck).solve()
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert not is_transient(ei.value)
    monkeypatch.delenv("GAMESMAN_HOST_MEM_LIMIT_MB")
    resumed = ShardedSolver(
        get_game(_C3), num_shards=2,
        checkpointer=LevelCheckpointer(tmp_path / "ck"),
    ).solve()
    assert full_table(resumed) == full_table(c3_clean)


@pytest.mark.slow
@pytest.mark.parametrize("point", ["sharded.forward:oom:3",
                                   "sharded.backward:oom:2"])
def test_chaos_oom_resumes_parity(tmp_path, c4_clean_table, point):
    """oom injected in a whole process at a forward and a backward
    point (the chaos-matrix entries for the `oom` kind): the process
    dies with classifiable RESOURCE_EXHAUSTED/out-of-memory
    diagnostics on stderr — what the campaign's death classifier reads
    as `oom` — and resume reaches byte-parity."""
    ck = tmp_path / "ck"
    died = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": point},
    )
    assert died.returncode != 0
    assert "out of memory" in died.stderr
    assert "RESOURCE_EXHAUSTED" in died.stderr
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck),
         "--table-out", str(out)]
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_tables_equal(out, c4_clean_table)


@pytest.mark.slow
def test_chaos_enospc_mid_writebehind_resumes_parity(tmp_path,
                                                     c4_clean_table):
    """enospc injected on the write-behind worker (store.writebehind):
    the ticket failure surfaces at the seal on the solve thread, the
    process dies with the prefix intact — an unsealed stray at worst —
    and resume reaches byte-parity. The enospc chaos-matrix entry for
    the sharded engine."""
    ck = tmp_path / "ck"
    died = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck)],
        {"GAMESMAN_FAULTS": "store.writebehind:enospc:3"},
    )
    assert died.returncode != 0
    assert "No space left on device" in died.stderr
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        [_C4, "--devices", "2", "--checkpoint-dir", str(ck),
         "--table-out", str(out)]
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_tables_equal(out, c4_clean_table)


@pytest.mark.slow
def test_chaos_sigterm_preempts_single_process(tmp_path, ttt_clean_table):
    """Whole-process grace: SIGTERM mid-backward -> exit 75 within the
    grace deadline, 'preempted' diagnostics on stderr, resume to
    byte-parity."""
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env["GAMESMAN_FAULTS"] = "engine.backward:delay=0.7:always"
    env["GAMESMAN_PREEMPT_GRACE_SECS"] = "60"
    ck = tmp_path / "ck"
    proc = subprocess.Popen(
        _CLI + ["tictactoe", "--checkpoint-dir", str(ck)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=str(REPO),
    )
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if list(ck.glob("level_*.npz")):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("solve never sealed a level")
        t0 = time.monotonic()
        proc.send_signal(subprocess.signal.SIGTERM)
        rc = proc.wait(timeout=120)
        graced = time.monotonic() - t0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    _, err = proc.communicate()
    from gamesmanmpi_tpu.resilience.preempt import GRACE_EXIT_CODE

    assert rc == GRACE_EXIT_CODE, err[-2000:]
    assert graced < 60, "drain blew the grace deadline"
    assert "preempted" in err
    out = tmp_path / "resumed.npz"
    resumed = _run_cli(
        ["tictactoe", "--checkpoint-dir", str(ck),
         "--table-out", str(out)]
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    _assert_tables_equal(out, ttt_clean_table)


@pytest.mark.slow
def test_chaos_sigterm_multiprocess_grace_both_ranks(tmp_path):
    """SIGTERM to BOTH ranks mid-level: the rank-coordinated boundary
    round makes every rank drain at the same program point — each exits
    75 (or 124 if wedged past the deadline), never a hang, never a torn
    tree — and a restart resumes to parity."""
    from tools.launch_multihost import start_world

    from gamesmanmpi_tpu.resilience.preempt import GRACE_EXIT_CODE

    ck = tmp_path / "ck"
    delay = "sharded.backward:delay=0.7:always"
    env = dict(os.environ)
    env.update({
        "GAMESMAN_PLATFORM": "cpu",
        "GAMESMAN_BARRIER_SECS": "20",
        "GAMESMAN_PREEMPT_GRACE_SECS": "90",
        "GAMESMAN_FAULTS_RANK_0": delay,
        "GAMESMAN_FAULTS_RANK_1": delay,
    })
    env.pop("GAMESMAN_FAULTS", None)
    world = start_world(
        [_C3, "--devices", "4", "--checkpoint-dir", str(ck)],
        processes=2, log_dir=str(tmp_path), env=env,
    )
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        if list(ck.glob("level_*.shard_*.npz")):
            break
        time.sleep(0.1)
    world.send_signal(subprocess.signal.SIGTERM)
    ranks = world.wait(120)
    _skip_unless_world_spawned(ranks)
    for r in ranks:
        assert r.returncode in (GRACE_EXIT_CODE, 124), (
            r.rank, r.returncode, r.stderr[-2000:]
        )
    # At least one rank drained through the grace path proper.
    assert any(r.returncode == GRACE_EXIT_CODE for r in ranks), [
        r.returncode for r in ranks
    ]
    ck_obj = LevelCheckpointer(ck)
    for k in ck_obj.completed_levels():
        ck_obj.load_level(k)
    ranks2 = _launch_world(
        [_C3, "--devices", "4", "--checkpoint-dir", str(ck)], tmp_path
    )
    for r in ranks2:
        assert r.returncode == 0, (r.rank, r.stderr[-2000:])
        assert "value: TIE" in r.stdout


@pytest.mark.slow
def test_chaos_kill_sweep_every_level_boundary(tmp_path):
    """ISSUE 12 satellite: resume-under-kill at EVERY level boundary of
    a small sharded solve — not sampled points. A clean checkpointed
    run counts the level-seal visits; then each visit index in turn is
    a kill schedule, and every resume depth must reach byte-parity."""
    from gamesmanmpi_tpu.parallel import ShardedSolver

    ck0 = tmp_path / "count_ck"
    seq = []
    real_fire = faults.fire

    def recording_fire(point, **kw):
        seq.append(point)
        return real_fire(point, **kw)

    faults.fire = recording_fire
    try:
        clean = ShardedSolver(
            get_game(_C3), num_shards=2,
            checkpointer=LevelCheckpointer(ck0),
        ).solve()
    finally:
        faults.fire = real_fire
    golden = tmp_path / "golden.npz"
    save_result_npz(golden, clean)
    boundaries = seq.count("ckpt.save_level")
    assert boundaries >= 5, seq  # every solved level seals once
    for visit in range(1, boundaries + 1):
        ck = tmp_path / f"ck_{visit:02d}"
        killed = _run_cli(
            [_C3, "--devices", "2", "--checkpoint-dir", str(ck)],
            {"GAMESMAN_FAULTS": f"ckpt.save_level:kill:{visit}"},
        )
        assert killed.returncode == faults.KILL_EXIT_CODE, (
            f"visit {visit}: rc={killed.returncode}\n"
            + killed.stderr[-2000:]
        )
        out = tmp_path / f"resumed_{visit:02d}.npz"
        resumed = _run_cli(
            [_C3, "--devices", "2", "--checkpoint-dir", str(ck),
             "--table-out", str(out)]
        )
        assert resumed.returncode == 0, (
            f"visit {visit}:\n" + resumed.stderr[-2000:]
        )
        _assert_tables_equal(out, golden)


# ------------------------------------------------- serving fleet chaos


def _fleet_db(tmp_path):
    from gamesmanmpi_tpu.db import export_result

    spec = "subtract:total=10,moves=1-2"
    db = tmp_path / "db"
    export_result(Solver(get_game(spec)).solve(), db, spec)
    return db


def _fleet_proc(db, tmp_path, extra_env):
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env["GAMESMAN_SERVE_RESTART_BASE_SECS"] = "0.05"
    env.pop("GAMESMAN_FAULTS", None)
    env.update(extra_env)
    return subprocess.Popen(
        _CLI + ["serve", str(db), "--port", "0", "--workers", "2",
                "--control-port", "0"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )


def _fleet_ports(proc):
    banner = proc.stdout.readline()
    assert "serving fleet" in banner, banner
    return (int(banner.split("http://127.0.0.1:")[1].split(" ")[0]),
            int(banner.split("http://127.0.0.1:")[2].split(" ")[0]))


def test_chaos_worker_spawn_crashloop_opens_storm_breaker(tmp_path):
    """serve.worker_spawn chaos: a worker whose every spawn dies at the
    fault point (a rotted replica shape — the same failure recurs on
    each restart) trips the slot's restart-storm breaker; the healthy
    worker keeps the fleet answering, degraded."""
    db = _fleet_db(tmp_path)
    proc = _fleet_proc(db, tmp_path, {
        "GAMESMAN_FAULTS_WORKER_0": "serve.worker_spawn:fatal:always",
        "GAMESMAN_SERVE_STORM_RESTARTS": "2",
        "GAMESMAN_SERVE_STORM_SECS": "600",
    })
    try:
        port, cport = _fleet_ports(proc)
        control = f"http://127.0.0.1:{cport}"
        deadline = time.monotonic() + 120
        st = {}
        while time.monotonic() < deadline:
            st = _get(control + "/healthz")[1]
            if st["workers"]["0"]["breaker"] == "open" \
                    and st["workers"]["1"]["state"] == "ready":
                break
            time.sleep(0.2)
        assert st["workers"]["0"]["breaker"] == "open", st
        assert st["workers"]["0"]["state"] == "broken"
        assert st["workers"]["0"]["restarts"] >= 2
        # The injected warm-start refusal is attributed on the slot.
        assert "rc=3" in st["workers"]["0"]["last_error"]
        assert st["status"] == "degraded"
        # The surviving worker still answers through the shared socket.
        status, body = _post(f"http://127.0.0.1:{port}/query",
                             {"positions": [10]})
        assert status == 200
        assert body["results"][0]["found"]
        proc.send_signal(subprocess.signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_chaos_heartbeat_stall_is_killed_and_restarted(tmp_path):
    """serve.heartbeat chaos: a delay injected on the beat path stalls
    the worker's liveness signal; the supervisor's beat deadline turns
    the silent hang into SIGKILL + backoff restart while the sibling
    keeps serving."""
    db = _fleet_db(tmp_path)
    proc = _fleet_proc(db, tmp_path, {
        # The 2nd beat of worker 0 sleeps far past the beat deadline.
        "GAMESMAN_FAULTS_WORKER_0": "serve.heartbeat:delay=60:2",
        "GAMESMAN_SERVE_HEARTBEAT_SECS": "0.1",
        "GAMESMAN_SERVE_HEARTBEAT_TIMEOUT": "1.0",
    })
    try:
        port, cport = _fleet_ports(proc)
        control = f"http://127.0.0.1:{cport}"
        deadline = time.monotonic() + 120
        st = {}
        while time.monotonic() < deadline:
            st = _get(control + "/healthz")[1]
            if st["workers"]["0"]["restarts"] >= 1 \
                    and st["workers"]["1"]["state"] == "ready":
                break
            time.sleep(0.2)
        assert st["workers"]["0"]["restarts"] >= 1, st
        status, body = _post(f"http://127.0.0.1:{port}/query",
                             {"positions": [10]})
        assert status == 200
        assert body["results"][0]["found"]
    finally:
        proc.kill()
        proc.wait()


def test_chaos_reload_fault_fails_closed_fleet_untouched(tmp_path):
    """serve.reload chaos: a fault at the top of a rolling reload fails
    the RELOAD, not the fleet — no worker is drained, the error is
    reported on /healthz state, and the next (clean) reload rolls."""
    from gamesmanmpi_tpu.serve import ServeSupervisor, single_db_entries

    from helpers import fake_fleet_spawn

    db = _fleet_db(tmp_path)
    faults.configure("serve.reload:fatal:1")
    sup = ServeSupervisor(
        single_db_entries(db), workers=2, control_port=None,
        heartbeat_secs=0.05, heartbeat_timeout=5.0, restart_base=0.01,
        spawn=fake_fleet_spawn(lambda i: "ok"),
    ).start()
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if sup.status()["status"] == "ok":
                break
            time.sleep(0.05)
        gen0_pids = {w["pid"] for w in sup.status()["workers"].values()}
        sup.request_reload()
        st = {}
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = sup.status()
            if st["last_reload_error"]:
                break
            time.sleep(0.05)
        assert "FatalFault" in (st["last_reload_error"] or ""), st
        assert st["gen"] == 0
        assert st["reloads_done"] == 0
        assert st["status"] == "ok"
        # No worker was drained by the failed reload.
        assert {w["pid"] for w in st["workers"].values()} == gen0_pids
        # The fault was one-shot (visit 1): the next reload rolls clean.
        sup.request_reload()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = sup.status()
            if st["reloads_done"] == 1 and st["status"] == "ok":
                break
            time.sleep(0.05)
        assert st["reloads_done"] == 1, st
        assert st["gen"] == 1
        assert st["last_reload_error"] is None
    finally:
        sup.stop()


# --------------------------------------------------- registry chaos matrix
#
# DB distribution (ISSUE 19) fault points, every shape the pull/publish/
# solve-on-demand paths claim to survive, run through real subprocesses:
#
#   registry.fetch:torn      torn download mid-range -> resumed pull
#   registry.install:kill    SIGKILL before rename-install -> no install
#   registry.publish:kill    death after payload, before catalog seal
#   jobs.claim:kill          runner SIGKILL after the fsync'd claim
#
# The invariant in all four: the fleet-visible state (catalog, installed
# epoch, job ledger) either did not change or converges on retry —
# never a half-landed epoch a worker could serve from.


@pytest.fixture(scope="module")
def registry_db(tmp_path_factory):
    """Tiny subtract DB: the registry chaos tests' published artifact."""
    from gamesmanmpi_tpu.db import export_result

    spec = "subtract:total=10,moves=1-2"
    d = tmp_path_factory.mktemp("regdb") / "sub"
    export_result(Solver(get_game(spec)).solve(), d, spec)
    return d


def _registry_env(**extra):
    env = dict(os.environ, GAMESMAN_PLATFORM="cpu")
    env.pop("GAMESMAN_FAULTS", None)
    env.update(extra)
    return env


def test_chaos_torn_download_resumes_to_verified_install(
        registry_db, tmp_path):
    """registry.fetch chaos: a download torn mid-range (file truncated,
    process killed) leaves only quarantined staging bytes; re-running
    the pull resumes from the verified prefix and installs a DB that
    passes the full integrity gate."""
    from gamesmanmpi_tpu.db.check import check_db
    from gamesmanmpi_tpu.registry.server import RegistryServer, publish_db

    root = tmp_path / "registry"
    publish_db(root, "sub", registry_db)
    srv = RegistryServer(root)
    srv.start()
    try:
        dest = tmp_path / "replica"
        cmd = [sys.executable, str(REPO / "tools" / "pull_db.py"),
               srv.url, "sub", "--dest", str(dest), "--json"]
        torn = subprocess.run(
            cmd, env=_registry_env(GAMESMAN_FAULTS="registry.fetch:torn:2"),
            capture_output=True, text=True, cwd=str(REPO), timeout=120,
        )
        assert torn.returncode == faults.TORN_EXIT_CODE, torn.stderr[-2000:]
        # Nothing installed; the partial bytes live only in staging.
        assert not [d for d in dest.iterdir()
                    if not d.name.startswith(".")]
        assert list((dest / ".registry_tmp").rglob("*"))
        clean = subprocess.run(
            cmd, env=_registry_env(), capture_output=True, text=True,
            cwd=str(REPO), timeout=120,
        )
        assert clean.returncode == 0, clean.stderr[-2000:]
        rec = json.loads(clean.stdout)["pulled"][0]
        assert rec["installed"]
        # The fully-verified file from before the tear was NOT refetched.
        assert rec["resumed_files"] >= 1
        assert check_db(rec["db"]) == []
    finally:
        srv.stop()


def test_chaos_kill_before_install_keeps_replica_clean(
        registry_db, tmp_path):
    """registry.install chaos: SIGKILL after every staged file verified
    but before the atomic rename leaves NO installed epoch (a fleet
    manifest can never name it); the re-run reuses every verified
    staged file and installs."""
    from gamesmanmpi_tpu.registry.server import RegistryServer, publish_db

    root = tmp_path / "registry"
    rec = publish_db(root, "sub", registry_db)
    srv = RegistryServer(root)
    srv.start()
    try:
        dest = tmp_path / "replica"
        cmd = [sys.executable, str(REPO / "tools" / "pull_db.py"),
               srv.url, "sub", "--dest", str(dest), "--json"]
        killed = subprocess.run(
            cmd,
            env=_registry_env(GAMESMAN_FAULTS="registry.install:kill:1"),
            capture_output=True, text=True, cwd=str(REPO), timeout=120,
        )
        assert killed.returncode == faults.KILL_EXIT_CODE, \
            killed.stderr[-2000:]
        assert not [d for d in dest.iterdir()
                    if not d.name.startswith(".")]
        rerun = subprocess.run(
            cmd, env=_registry_env(), capture_output=True, text=True,
            cwd=str(REPO), timeout=120,
        )
        assert rerun.returncode == 0, rerun.stderr[-2000:]
        out = json.loads(rerun.stdout)["pulled"][0]
        assert out["installed"]
        # Every file was already staged + verified: zero refetches.
        assert out["resumed_files"] == len(rec["files"])
        assert out["refetched_files"] == 0
    finally:
        srv.stop()


def test_chaos_publish_kill_keeps_old_catalog_authoritative(
        registry_db, tmp_path):
    """registry.publish chaos: the publisher dying AFTER the payload
    directory lands but BEFORE the catalog seal must leave the old
    catalog authoritative (replicas keep pulling the old epoch); a
    re-publish of the same DB converges to a sealed catalog."""
    from gamesmanmpi_tpu.registry.server import catalog_seal, load_catalog

    root = tmp_path / "registry"
    cmd = _CLI + ["registry", "publish", str(registry_db),
                  "--root", str(root), "--name", "sub"]
    killed = subprocess.run(
        cmd, env=_registry_env(GAMESMAN_FAULTS="registry.publish:kill:1"),
        capture_output=True, text=True, cwd=str(REPO), timeout=120,
    )
    assert killed.returncode == faults.KILL_EXIT_CODE, killed.stderr[-2000:]
    # Payload landed, but the catalog never sealed the new epoch.
    assert list((root / "dbs" / "sub").iterdir())
    cat = load_catalog(root)
    assert cat["dbs"] == {}
    assert cat["seal"] == catalog_seal({})
    republish = subprocess.run(
        cmd, env=_registry_env(), capture_output=True, text=True,
        cwd=str(REPO), timeout=120,
    )
    assert republish.returncode == 0, republish.stderr[-2000:]
    cat = load_catalog(root)
    assert set(cat["dbs"]) == {"sub"}
    assert cat["seal"] == catalog_seal(cat["dbs"])


def test_chaos_runner_sigkill_at_claim_resumes_to_published_db(tmp_path):
    """jobs.claim chaos: the solve-on-demand runner SIGKILLed right
    after its claim record is fsync'd leaves a running job with a dead
    pid; the next runner's classify-and-resume reclaims it and drives
    the job all the way to a published catalog epoch."""
    from gamesmanmpi_tpu.registry.jobs import JobQueue
    from gamesmanmpi_tpu.registry.server import load_catalog

    root = tmp_path / "registry"
    queue = JobQueue(root / "jobs.jsonl")
    job = queue.enqueue("subtract:total=5,moves=1-2", name="sub5")
    cmd = _CLI + ["registry", "run-jobs", "--root", str(root), "--once"]
    killed = subprocess.run(
        cmd, env=_registry_env(GAMESMAN_FAULTS="jobs.claim:kill:1"),
        capture_output=True, text=True, cwd=str(REPO), timeout=180,
    )
    assert killed.returncode == faults.KILL_EXIT_CODE, killed.stderr[-2000:]
    # The claim is durable: the ledger shows a running job whose pid is
    # dead — exactly what the reclaim classifier looks for.
    state = queue.jobs()[job["id"]]
    assert state["state"] == "running"
    resumed = subprocess.run(
        cmd, env=_registry_env(), capture_output=True, text=True,
        cwd=str(REPO), timeout=600,
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    out = json.loads(resumed.stdout)
    assert out["results"][0]["ok"], out
    assert queue.jobs()[job["id"]]["state"] == "done"
    assert "sub5" in load_catalog(root)["dbs"]
