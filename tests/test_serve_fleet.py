"""Fleet serving: supervisor/worker lifecycle over shared-socket DBs.

Acceptance axes (ISSUE 7):

* fork-after-open sharing — a CLI fleet whose supervisor opened every
  DbReader BEFORE forking answers oracle-exact from every worker
  (the mmap pages are the parent's, shared through the page cache);
* supervised lifecycle — a SIGKILLed worker is detected (pipe EOF),
  restarted with backoff, and re-verifies (check_db gate + self-probe)
  before rejoining the ready set; a crash-looping worker opens the
  restart-storm breaker instead of burning CPU; a mute worker is
  treated as hung and killed;
* rolling reload — POST /reload drains ONE worker at a time onto a
  re-read fleet manifest with zero failed requests; a junk manifest
  fails the reload and leaves the fleet serving untouched;
* drain correctness — QueryServer.stop() wakes handler threads parked
  in recv on idle keep-alive connections instead of waiting out their
  socket timeout (the server.py:414 accounting fix).

State-machine tests run against scripted fake workers
(helpers.FAKE_FLEET_WORKER — no jax, milliseconds); the end-to-end
tests run real workers through the CLI (fork mode) and in-process
supervisor (exec mode).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from gamesmanmpi_tpu.core.values import value_name
from gamesmanmpi_tpu.db import DbReader, export_result
from gamesmanmpi_tpu.db.check import DbFormatError, verify_for_serving
from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.serve import (
    FleetEntry,
    QueryServer,
    ServeSupervisor,
    load_fleet_manifest,
    single_db_entries,
)
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.solve.oracle import oracle_solve
from gamesmanmpi_tpu.utils.env import env_bool

from helpers import REF_GAMES, REPO, fake_fleet_spawn, load_module

_CLI = [sys.executable, "-m", "gamesmanmpi_tpu.cli"]


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


def _wait_for(pred, timeout=30.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture(scope="module")
def sub_db(tmp_path_factory):
    """Tiny subtract-game DB: the fleet tests' cheap routed artifact."""
    spec = "subtract:total=10,moves=1-2"
    d = tmp_path_factory.mktemp("fleetdb") / "sub"
    export_result(Solver(get_game(spec)).solve(), d, spec)
    return d


@pytest.fixture(scope="module")
def nim_db(tmp_path_factory):
    """nim_345 DB + oracle: the fork-mode oracle-exactness pair."""
    spec = "nim:heaps=3-4-5"
    d = tmp_path_factory.mktemp("fleetnim") / "nim"
    export_result(Solver(get_game(spec)).solve(), d, spec)
    _, _, oracle = oracle_solve(load_module(REF_GAMES / "nim_345.py"))
    return d, oracle


# ------------------------------------------------------- manifest / gates


def test_fleet_manifest_parses_and_resolves_relative(tmp_path, sub_db):
    mdir = tmp_path / "fleet"
    mdir.mkdir()
    (mdir / "dbs").mkdir()
    (mdir / "dbs" / "sub").symlink_to(sub_db)
    manifest = mdir / "fleet.json"
    manifest.write_text(json.dumps({
        "version": 1,
        "games": [{"name": "sub", "db": "dbs/sub"},
                  {"name": "abs", "db": str(sub_db)}],
    }))
    entries = load_fleet_manifest(manifest)
    assert [e.name for e in entries] == ["sub", "abs"]
    # Relative paths resolve against the manifest's own directory.
    assert entries[0].db == str(mdir / "dbs" / "sub")
    assert entries[1].db == str(sub_db)


@pytest.mark.parametrize("doc, why", [
    ("not json {", "junk"),
    ({"version": 2, "games": [{"name": "a", "db": "."}]}, "version"),
    ({"version": 1, "games": []}, "empty"),
    ({"version": 1, "games": [{"name": "a"}]}, "missing db"),
    ({"version": 1, "games": [{"name": "a/b", "db": "."}]}, "bad token"),
    ({"version": 1, "games": [{"name": "a", "db": "."},
                              {"name": "a", "db": "."}]}, "duplicate"),
    ({"version": 1, "games": [{"name": "a", "db": "nope"}]}, "no dir"),
])
def test_fleet_manifest_rejects_junk(tmp_path, doc, why):
    path = tmp_path / "fleet.json"
    path.write_text(doc if isinstance(doc, str) else json.dumps(doc))
    with pytest.raises(ValueError):
        load_fleet_manifest(path)


def test_verify_for_serving_gate(tmp_path, sub_db, monkeypatch):
    """The warm-start gate: clean DB verifies True, rot raises, and
    GAMESMAN_SERVE_VERIFY=0 skips (returning False, not True)."""
    assert verify_for_serving(sub_db) is True
    monkeypatch.setenv("GAMESMAN_SERVE_VERIFY", "0")
    assert verify_for_serving(sub_db) is False
    monkeypatch.setenv("GAMESMAN_SERVE_VERIFY", "junk")
    with pytest.warns(UserWarning):
        assert verify_for_serving(sub_db) is True  # warn-and-default
    monkeypatch.delenv("GAMESMAN_SERVE_VERIFY")
    import shutil

    rotted = tmp_path / "rot"
    shutil.copytree(sub_db, rotted)
    victim = next(rotted.glob("level_*.cells.npy"))
    data = bytearray(victim.read_bytes())
    data[-1] ^= 0xFF
    victim.write_bytes(bytes(data))
    with pytest.raises(DbFormatError):
        verify_for_serving(rotted)


def test_env_bool_contract(monkeypatch):
    for raw, want in [("0", False), ("off", False), ("FALSE", False),
                      ("no", False), ("1", True), ("on", True),
                      ("True", True), ("yes", True)]:
        monkeypatch.setenv("X_FLEET_FLAG", raw)
        assert env_bool("X_FLEET_FLAG", not want) is want, raw
    monkeypatch.delenv("X_FLEET_FLAG")
    assert env_bool("X_FLEET_FLAG", True) is True
    assert env_bool("X_FLEET_FLAG", False) is False


# ------------------------------------------------------ multi-DB routing


def test_query_server_routes_fleet(sub_db, nim_db):
    """One QueryServer, two DBs: /query/<name> routes per game, each
    route has its own batcher/breaker, /healthz carries the fleet map,
    and the bare /query 404s (two games -> no default route)."""
    nim_dir, oracle = nim_db
    with DbReader(sub_db) as sub_reader, DbReader(nim_dir) as nim_reader:
        with QueryServer(
            readers={"sub": sub_reader, "nim": nim_reader}
        ) as server:
            base = f"http://127.0.0.1:{server.port}"
            pos = sorted(oracle)[0]
            status, body = _post(base + "/query/nim",
                                 {"positions": [hex(pos)]})
            assert status == 200
            v, r = oracle[pos]
            rec = body["results"][0]
            assert (rec["value"], rec["remoteness"]) == (value_name(v), r)
            status, body = _post(base + "/query/sub", {"positions": [10]})
            assert status == 200
            assert body["results"][0]["found"]
            # Unknown names and the bare route list what IS routable.
            try:
                _post(base + "/query/nope", {"positions": [1]})
                raise AssertionError("unknown game did not 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert json.loads(e.read())["games"] == ["nim", "sub"]
            try:
                _post(base + "/query", {"positions": [1]})
                raise AssertionError("bare /query did not 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
            _, health = _get(base + "/healthz")
            assert health["status"] == "ok"
            assert set(health["games"]) == {"nim", "sub"}
            assert health["games"]["nim"]["breaker"] == "ok"
            _, metrics = _get(base + "/metrics.json")
            assert set(metrics["games"]) == {"nim", "sub"}
            assert metrics["games"]["nim"]["batches"] >= 1
            # One-game fleets keep the bare /query default route.
            server.self_probe()  # also the worker warm-start path


def test_single_game_fleet_keeps_default_route(sub_db):
    with DbReader(sub_db) as reader:
        with QueryServer(readers={"sub": reader}) as server:
            base = f"http://127.0.0.1:{server.port}"
            status, body = _post(base + "/query", {"positions": [10]})
            assert status == 200
            status, body = _post(base + "/query/sub", {"positions": [10]})
            assert status == 200
            # Legacy flat identity fields survive for one-game servers.
            _, health = _get(base + "/healthz")
            assert health["game"] == reader.game.name
            assert health["positions"] == reader.num_positions


def test_stop_wakes_idle_keepalive_connections(sub_db):
    """The server.py:414 fix: an idle keep-alive connection parked in
    recv must not pin stop() until its 30 s socket timeout — the drain
    shuts idle connections down and returns promptly."""
    with DbReader(sub_db) as reader:
        server = QueryServer(reader)
        server.start()
        port = server.port
        conn = socket.create_connection(("127.0.0.1", port), timeout=10)
        body = json.dumps({"positions": [10]}).encode()
        conn.sendall(
            b"POST /query HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body
        )
        first = conn.recv(65536)
        assert first.startswith(b"HTTP/1.1 200")
        # The connection is now IDLE keep-alive: its handler thread sits
        # in a blocking read for a next request that never comes.
        t0 = time.monotonic()
        server.stop()
        stop_secs = time.monotonic() - t0
        assert stop_secs < 4.0, (
            f"stop() took {stop_secs:.1f}s — idle keep-alive connections "
            "were not woken"
        )
        # The client sees a clean close (EOF), not a mid-response cut.
        conn.settimeout(5)
        rest = b"x"
        while rest:
            rest = conn.recv(65536)
        conn.close()


# ---------------------------------------- supervisor state machine (fakes)


def _fake_supervisor(sub_db, modes, **kw):
    kw.setdefault("workers", len(modes))
    kw.setdefault("control_port", None)
    kw.setdefault("heartbeat_secs", 0.05)
    kw.setdefault("heartbeat_timeout", 0.6)
    kw.setdefault("restart_base", 0.01)
    kw.setdefault("restart_max", 0.05)
    kw.setdefault("drain_grace", 5.0)
    return ServeSupervisor(
        single_db_entries(sub_db),
        spawn=fake_fleet_spawn(lambda i: modes[i]),
        **kw,
    )


def test_supervisor_restarts_killed_worker(sub_db):
    sup = _fake_supervisor(sub_db, ["ok", "ok"]).start()
    try:
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok" and s,
            what="fleet ready",
        )
        victim = st["workers"]["0"]["pid"]
        os.kill(victim, signal.SIGKILL)
        _wait_for(
            lambda: (s := sup.status())["workers"]["0"]["restarts"] >= 1
            and s["workers"]["0"]["state"] == "ready"
            and s["workers"]["0"]["pid"] != victim,
            what="worker restarted after SIGKILL",
        )
        # The replacement re-reported its warm-start verification: a
        # restarted worker rejoins only through the verify gate.
        assert sup.status()["workers"]["0"]["verified"] == {"default": True}
    finally:
        sup.stop()
    assert all(w["state"] == "stopped"
               for w in sup.status()["workers"].values())


def test_supervisor_storm_breaker_opens_on_crash_loop(sub_db):
    """A slot that dies at every spawn trips the restart-storm breaker
    ('broken', breaker 'open') instead of restarting forever; the
    healthy worker keeps the fleet degraded-but-up."""
    sup = _fake_supervisor(
        sub_db, ["crash", "ok"], storm_restarts=3, storm_secs=60.0,
    ).start()
    try:
        st = _wait_for(
            lambda: (s := sup.status())["workers"]["0"]["breaker"] == "open"
            and s,
            what="storm breaker open",
        )
        assert st["workers"]["0"]["state"] == "broken"
        assert st["workers"]["0"]["restarts"] >= 3
        _wait_for(
            lambda: sup.status()["workers"]["1"]["state"] == "ready",
            what="healthy worker ready",
        )
        assert sup.status()["status"] == "degraded"
    finally:
        sup.stop()


def test_supervisor_kills_mute_worker_as_hung(sub_db):
    """A worker whose beats stop (but whose process lives) is hung: the
    liveness deadline SIGKILLs it into an ordinary restart."""
    sup = _fake_supervisor(sub_db, ["mute"]).start()
    try:
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok" and s,
            what="fleet ready",
        )
        _wait_for(
            lambda: sup.status()["workers"]["0"]["restarts"] >= 1,
            what="hung worker restarted",
        )
    finally:
        sup.stop()


def test_supervisor_rolling_reload_and_failed_reload(tmp_path, sub_db):
    """A manifest reload rolls one worker at a time onto the new
    generation; a junk manifest fails the reload and leaves the running
    fleet untouched."""
    manifest = tmp_path / "fleet.json"
    manifest.write_text(json.dumps({
        "version": 1, "games": [{"name": "sub", "db": str(sub_db)}],
    }))
    sup = _fake_supervisor(
        sub_db, ["ok", "ok"], manifest_path=manifest,
    ).start()
    try:
        _wait_for(lambda: sup.status()["status"] == "ok",
                  what="fleet ready")
        pids = {w["pid"] for w in sup.status()["workers"].values()}
        sup.request_reload()
        st = _wait_for(
            lambda: (s := sup.status())["reloads_done"] == 1
            and s["status"] == "ok" and s,
            what="rolling reload done",
        )
        assert st["gen"] == 1
        assert all(w["gen"] == 1 for w in st["workers"].values())
        # Every worker was replaced (drained + respawned), none dropped:
        # a rolled worker exits 0, so restarts (death counter) stays 0.
        new_pids = {w["pid"] for w in st["workers"].values()}
        assert not (pids & new_pids)
        assert all(w["restarts"] == 0 for w in st["workers"].values())
        # Now rot the manifest: the reload must fail CLOSED.
        manifest.write_text("{ not json")
        sup.request_reload()
        st = _wait_for(
            lambda: (s := sup.status())["last_reload_error"] and s,
            what="failed reload reported",
        )
        assert "fleet manifest" in st["last_reload_error"]
        assert st["gen"] == 1  # nothing rolled
        assert st["status"] == "ok"
        assert st["reloads_done"] == 1
    finally:
        sup.stop()


# -------------------------------------------------- end-to-end (real CLI)


def test_cli_fleet_forks_after_open_and_survives_worker_kill(
        nim_db, tmp_path):
    """The ISSUE 7 chaos gate, tier-1 sized: a 2-worker CLI fleet (fork
    mode — the supervisor opened the DbReader BEFORE forking, so the
    workers share its mmap pages) answers the whole nim_345 oracle
    exactly; under load-gen traffic a SIGKILLed worker drops at most
    its in-flight requests while the fleet keeps answering; the
    replacement re-verifies before rejoining; a rolling reload then
    completes with zero failed requests."""
    nim_dir, oracle = nim_db
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env["GAMESMAN_SERVE_RESTART_BASE_SECS"] = "0.1"
    env.pop("GAMESMAN_FAULTS", None)
    proc = subprocess.Popen(
        _CLI + ["serve", str(nim_dir), "--port", "0", "--workers", "2",
                "--control-port", "0",
                "--jsonl", str(tmp_path / "serve.jsonl")],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )
    try:
        banner = proc.stdout.readline()
        assert "serving fleet" in banner, banner
        port = int(banner.split("http://127.0.0.1:")[1].split(" ")[0])
        cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
        base, control = (f"http://127.0.0.1:{port}",
                         f"http://127.0.0.1:{cport}")
        st = _wait_for(
            lambda: (s := _get(control + "/healthz")[1])["status"] == "ok"
            and s,
            timeout=120, what="fleet ready",
        )
        # Fork mode: the whole point of opening readers in the parent.
        assert st["spawn_mode"] == "fork"
        assert all(w["verified"] == {"default": True}
                   for w in st["workers"].values())

        # Oracle-exactness through the shared socket (both workers
        # accept from one queue; every answer must agree with the
        # oracle no matter which worker served it).
        positions = sorted(oracle)
        for i in range(0, len(positions), 64):
            chunk = [hex(p) for p in positions[i:i + 64]]
            status, body = _post(base + "/query", {"positions": chunk})
            assert status == 200
            for q, rec in zip(chunk, body["results"]):
                v, r = oracle[int(q, 0)]
                assert (rec["found"], rec["value"], rec["remoteness"]) \
                    == (True, value_name(v), r), q

        # Chaos mid-load: drive the load harness and SIGKILL one ready
        # worker halfway through.
        pos_file = tmp_path / "positions.txt"
        pos_file.write_text("\n".join(hex(p) for p in positions))
        out_json = tmp_path / "load.json"
        conc = 4
        load = subprocess.Popen(
            [sys.executable, str(REPO / "tools" / "load_gen.py"), base,
             "--positions-file", str(pos_file), "--duration", "6",
             "--concurrency", str(conc), "--slo-p99-ms", "5000",
             "--max-dropped", str(conc), "--json", str(out_json)],
            stdout=subprocess.PIPE, text=True, cwd=str(REPO),
        )
        time.sleep(2.0)
        st = _get(control + "/healthz")[1]
        victim = next(w for w in st["workers"].values()
                      if w["state"] == "ready")
        os.kill(victim["pid"], signal.SIGKILL)
        assert load.wait(timeout=120) == 0, load.stdout.read()
        record = json.loads(out_json.read_text())
        assert record["ok"] > 0
        assert record["errors"] == 0
        assert record["mismatches"] == 0
        assert record["dropped"] <= conc

        # The killed slot restarted AND re-verified before rejoining.
        st = _wait_for(
            lambda: (s := _get(control + "/healthz")[1])["status"] == "ok"
            and all(w["state"] == "ready"
                    for w in s["workers"].values()) and s,
            timeout=60, what="killed worker restarted",
        )
        assert sum(w["restarts"] for w in st["workers"].values()) == 1
        assert all(w["verified"] == {"default": True}
                   for w in st["workers"].values())

        # Rolling reload with zero request failures: queries in one
        # thread, POST /reload in another, every query must answer.
        failures = []
        done = threading.Event()

        def _hammer():
            while not done.is_set():
                try:
                    status, body = _post(
                        base + "/query", {"positions": [hex(positions[0])]},
                        timeout=10,
                    )
                    if status != 200 or not body["results"][0]["found"]:
                        failures.append(body)
                except Exception as e:  # noqa: BLE001 - collected
                    failures.append(e)

        t = threading.Thread(target=_hammer)
        t.start()
        try:
            urllib.request.urlopen(urllib.request.Request(
                control + "/reload", method="POST", data=b""), timeout=10)
            _wait_for(
                lambda: (s := _get(control + "/healthz")[1])
                ["reloads_done"] >= 1 and s["status"] == "ok",
                timeout=120, what="rolling reload done",
            )
        finally:
            done.set()
            t.join(timeout=30)
        assert not failures, failures[:3]
        st = _get(control + "/healthz")[1]
        assert st["gen"] == 1

        # Supervisor /metrics speaks Prometheus and carries the fleet
        # series.
        with urllib.request.urlopen(control + "/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
        assert "gamesman_serve_worker_restarts_total" in text
        assert "gamesman_serve_reloads_total" in text

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


_WORKER_PLATFORM_PROBE = """
import json, socket, sys
import jax
from gamesmanmpi_tpu.serve import worker
worker.run_worker = lambda cfg, sock, fd: print(
    "PLATFORMS", jax.config.jax_platforms) or 0
lsock = socket.socket()
sys.exit(worker.main([json.dumps({"listen_fd": lsock.fileno(),
                                  "pipe_fd": -1})]))
"""


def test_reexec_worker_pins_host_cpu():
    """An exec-spawned serve worker pins jax to the host CPU before any
    backend init even with no platform named in its environment: N fleet
    workers on a TPU host must never load the TPU runtime."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("GAMESMAN_PLATFORM", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER_PLATFORM_PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PLATFORMS cpu" in proc.stdout.splitlines()


def test_supervisor_exec_mode_serves_and_recovers(sub_db, monkeypatch):
    """In-process supervisor in a jax-initialized parent: the fork path
    is forbidden (XLA runtime does not survive fork), so workers
    re-exec — and the lifecycle contract (ready via verify+self-probe,
    SIGKILL -> restart) holds identically."""
    # The re-exec'd worker pins itself to the host CPU (serve/worker.py
    # main); the env knob keeps the parent's explicit platform choice.
    monkeypatch.setenv("GAMESMAN_PLATFORM", "cpu")
    sup = ServeSupervisor(
        single_db_entries(sub_db), workers=1, control_port=None,
        restart_base=0.1, heartbeat_secs=0.2, heartbeat_timeout=30.0,
    ).start()
    try:
        assert sup.status()["spawn_mode"] == "exec"
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok" and s,
            timeout=180, what="exec worker ready",
        )
        assert st["workers"]["0"]["verified"] == {"default": True}
        base = f"http://127.0.0.1:{sup.port}"
        status, body = _post(base + "/query", {"positions": [10]})
        assert status == 200
        assert body["results"][0]["found"]
        os.kill(st["workers"]["0"]["pid"], signal.SIGKILL)
        _wait_for(
            lambda: (s := sup.status())["workers"]["0"]["restarts"] >= 1
            and s["status"] == "ok",
            timeout=180, what="exec worker restarted",
        )
        status, body = _post(base + "/query", {"positions": [10]})
        assert status == 200
    finally:
        sup.stop()


def _post_with_headers(url, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def test_cli_fleet_fork_mode_propagates_traceparent(sub_db, tmp_path):
    """ISSUE 17 e2e, fork mode: a client traceparent survives the
    shared-socket fleet — the answering worker echoes the trace id on
    the response header, keeps the trace in its ring (head sampling
    pinned to keep-everything via env, which fork workers inherit), and
    ships it on a heartbeat beat to the supervisor, where the control
    port serves it fleet-wide (GET /traces) stamped with the worker
    index."""
    from gamesmanmpi_tpu.obs.qtrace import (
        format_traceparent,
        mint_trace_ids,
        parse_traceparent,
    )

    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env["GAMESMAN_SERVE_RESTART_BASE_SECS"] = "0.1"
    env["GAMESMAN_SERVE_HEARTBEAT_SECS"] = "0.2"
    env["GAMESMAN_TRACE_HEAD_N"] = "1"
    env.pop("GAMESMAN_FAULTS", None)
    proc = subprocess.Popen(
        _CLI + ["serve", str(sub_db), "--port", "0", "--workers", "2",
                "--control-port", "0"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )
    try:
        banner = proc.stdout.readline()
        assert "serving fleet" in banner, banner
        port = int(banner.split("http://127.0.0.1:")[1].split(" ")[0])
        cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
        base, control = (f"http://127.0.0.1:{port}",
                         f"http://127.0.0.1:{cport}")
        st = _wait_for(
            lambda: (s := _get(control + "/healthz")[1])["status"] == "ok"
            and s,
            timeout=120, what="fleet ready",
        )
        assert st["spawn_mode"] == "fork"

        tids = []
        # Distinct NON-initial positions: the worker's startup
        # self-probe warmed the answer cache for the initial position,
        # and a pure cache hit records no batcher/reader spans.
        for pos in (9, 8, 7, 6, 5, 4):
            tid, sid = mint_trace_ids()
            status, headers, body = _post_with_headers(
                base + "/query", {"positions": [pos]},
                headers={"traceparent": format_traceparent(tid, sid)},
            )
            assert status == 200 and body["results"][0]["found"]
            echoed = parse_traceparent(headers.get("traceparent"))
            assert echoed is not None and echoed[0] == tid
            assert echoed[1] != sid  # the server's own span id
            tids.append(tid)

        # Kept traces ride heartbeat beats into the supervisor's
        # fleet-wide ring; the control port serves the aggregate.
        def _ours():
            snap = _get(control + "/traces")[1]
            assert snap["kind"] == "qtrace_fleet"
            got = [t for t in snap["traces"]
                   if t.get("trace_id") in tids]
            return got or None

        got = _wait_for(_ours, timeout=60,
                        what="client traces on the control port")
        for rec in got:
            assert rec["status"] == "ok" and rec["code"] == 200
            assert rec["worker"] in (0, 1)  # supervisor-stamped slot
            assert rec["keep"] in ("head", "slow")
            names = {s["name"] for s in rec["spans"]}
            assert "queue_wait" in names

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_supervisor_exec_mode_propagates_traceparent(sub_db,
                                                     monkeypatch):
    """ISSUE 17 e2e, exec mode: the same traceparent contract when the
    worker was re-exec'd (env-inherited trace knobs, beat-shipped
    traces) — asserted through ServeSupervisor.traces(), the object
    backing control GET /traces."""
    from gamesmanmpi_tpu.obs.qtrace import (
        format_traceparent,
        mint_trace_ids,
        parse_traceparent,
    )

    monkeypatch.setenv("GAMESMAN_PLATFORM", "cpu")
    # Exec workers inherit os.environ (subprocess.Popen without env=):
    # this knob must reach the child or nothing below samples.
    monkeypatch.setenv("GAMESMAN_TRACE_HEAD_N", "1")
    sup = ServeSupervisor(
        single_db_entries(sub_db), workers=1, control_port=None,
        restart_base=0.1, heartbeat_secs=0.2, heartbeat_timeout=30.0,
    ).start()
    try:
        assert sup.status()["spawn_mode"] == "exec"
        _wait_for(
            lambda: sup.status()["status"] == "ok",
            timeout=180, what="exec worker ready",
        )
        base = f"http://127.0.0.1:{sup.port}"
        tid, sid = mint_trace_ids()
        # Non-initial position: the self-probe warmed the answer cache
        # for the initial one, and a cache hit records no spans.
        status, headers, body = _post_with_headers(
            base + "/query", {"positions": [7]},
            headers={"traceparent": format_traceparent(tid, sid)},
        )
        assert status == 200 and body["results"][0]["found"]
        echoed = parse_traceparent(headers.get("traceparent"))
        assert echoed is not None and echoed[0] == tid

        def _ours():
            snap = sup.traces()
            got = [t for t in snap["traces"]
                   if t.get("trace_id") == tid]
            return got or None

        (rec,) = _wait_for(_ours, timeout=60,
                           what="trace shipped over the exec beat")
        assert rec["parent_id"] == sid
        assert rec["worker"] == 0
        assert {s["name"] for s in rec["spans"]} >= {"queue_wait"}

        # The burn-rate snapshot rides the same beat: control /status
        # (sup.status()) shows the per-worker SLO view, not just the
        # degraded/ok flip it induces.
        def _slo_on_status():
            st = sup.status()
            slo = st["workers"]["0"].get("slo")
            return st if isinstance(slo, dict) and "routes" in slo else None

        st = _wait_for(_slo_on_status, timeout=30,
                       what="slo snapshot on the beat")
        assert st["slo_fast_burn"] is False
        assert "p99_ms" in st["workers"]["0"]["slo"]
    finally:
        sup.stop()


def test_workers_never_outlive_a_sigkilled_supervisor(sub_db, tmp_path):
    """No orphans: a worker wedged in WARM START (nothing written on
    the heartbeat pipe yet, so EPIPE can never tell it the supervisor
    died) must still notice the SIGKILLed supervisor — the reparent
    watchdog — and a ready worker notices via its next beat. Both gone
    within seconds, nobody left accept()ing on an unowned socket."""
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    # Stall worker 0's warm start at the spawn fault point.
    env["GAMESMAN_FAULTS_WORKER_0"] = "serve.worker_spawn:delay=60"
    env["GAMESMAN_SERVE_HEARTBEAT_SECS"] = "0.2"
    env.pop("GAMESMAN_FAULTS", None)
    proc = subprocess.Popen(
        _CLI + ["serve", str(sub_db), "--port", "0", "--workers", "2",
                "--control-port", "0"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )
    try:
        banner = proc.stdout.readline()
        cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
        control = f"http://127.0.0.1:{cport}"
        st = _wait_for(
            lambda: (s := _get(control + "/healthz")[1])
            ["workers"]["0"]["state"] == "starting"
            and s["workers"]["0"]["pid"]
            and s["workers"]["1"]["state"] == "ready" and s,
            timeout=60, what="worker 0 wedged in warm start",
        )
        pids = [st["workers"]["0"]["pid"], st["workers"]["1"]["pid"]]
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)

        def _all_gone():
            for pid in pids:
                try:
                    os.kill(pid, 0)
                    return False
                except ProcessLookupError:
                    pass
            return True

        _wait_for(_all_gone, timeout=10, interval=0.25,
                  what="workers exiting after supervisor SIGKILL")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# ------------------------------------------------ review-round regressions


def test_reload_requested_mid_roll_is_queued_not_dropped(tmp_path, sub_db):
    """A reload asked for while a roll is in progress must run after
    that roll finishes — the 202 is a promise, not a maybe."""
    sup = _fake_supervisor(
        sub_db, ["slowdrain", "slowdrain"], drain_grace=10.0,
    ).start()
    try:
        _wait_for(lambda: sup.status()["status"] == "ok",
                  what="fleet ready")
        sup.request_reload()
        _wait_for(lambda: sup.status()["reload_in_progress"],
                  what="first roll started")
        sup.request_reload()  # mid-roll: must queue, not vanish
        st = _wait_for(
            lambda: (s := sup.status())["reloads_done"] == 2
            and s["status"] == "ok" and s,
            timeout=60, what="second (queued) reload completed",
        )
        assert st["gen"] == 2
    finally:
        sup.stop()


def test_half_open_probe_death_reopens_breaker(sub_db):
    """The storm breaker's cool-off buys ONE probe spawn: a dead probe
    re-opens the breaker immediately instead of granting a fresh
    storm budget of crash-loops per window."""
    sup = _fake_supervisor(sub_db, ["ok"], storm_restarts=3)
    slot = sup._slots[0]
    now = time.monotonic()
    # An ordinary first death backs off without breaking.
    sup._schedule_restart(slot, now, "exit rc=3")
    assert slot.state == "restarting"
    # The spawn after a broken hold-down is marked as the probe; its
    # death must go straight back to broken, window contents be damned.
    slot.half_open = True
    slot.recent = []
    sup._schedule_restart(slot, now + 1, "exit rc=3")
    assert slot.state == "broken"
    assert sup.status()["workers"]["0"]["breaker"] == "open"
    sup._shutdown()


def test_prehello_silence_gets_spawn_grace_not_beat_deadline(sub_db):
    """A freshly spawned worker that has not written its first byte yet
    (cold exec spawn: interpreter + jax import) is judged against the
    spawn grace, not the beat deadline; after its first byte the tight
    deadline applies."""

    class _Recorder:
        def __init__(self):
            self.signals = []

        def kill(self, sig):
            self.signals.append(sig)

        def poll(self):
            return None

    sup = _fake_supervisor(sub_db, ["ok"], heartbeat_timeout=0.5)
    assert sup.spawn_grace >= 60.0
    slot = sup._slots[0]
    slot.state = "starting"
    slot.proc = _Recorder()
    slot.last_msg = time.monotonic() - 5.0  # silent for 5 s
    slot.heard = False
    sup._check_liveness(time.monotonic())
    assert slot.proc.signals == []  # within spawn grace: left alone
    slot.heard = True  # first byte arrived; beat deadline now applies
    sup._check_liveness(time.monotonic())
    assert signal.SIGKILL in slot.proc.signals
    slot.proc = None
    sup._shutdown()


def test_cli_fleet_fork_shares_compressed_db_readers(nim_db, tmp_path):
    """ISSUE 9's fleet axis: a 2-worker fork-mode fleet over a
    block-compressed (format v2) DB — the supervisor opened the
    decompressing DbReader (and its block-stream fds) BEFORE forking —
    answers the whole nim_345 oracle exactly while each worker runs its
    own hot-block cache (copy-on-write after fork: no cross-worker
    corruption is possible, and the test proves the answers), with
    per-worker cache metrics observable on /metrics and db_cache_*
    figures riding the worker-stamped serve JSONL streams."""
    _, oracle = nim_db
    spec = "nim:heaps=3-4-5"
    v2 = tmp_path / "nimv2"
    export_result(Solver(get_game(spec)).solve(), v2, spec, compress=True)
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    # A 1 MB budget forces real decode + eviction traffic under load.
    env["GAMESMAN_DB_CACHE_MB"] = "1"
    env.pop("GAMESMAN_FAULTS", None)
    jsonl = tmp_path / "serve.jsonl"
    proc = subprocess.Popen(
        _CLI + ["serve", str(v2), "--port", "0", "--workers", "2",
                "--control-port", "0", "--jsonl", str(jsonl)],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )
    try:
        banner = proc.stdout.readline()
        assert "serving fleet" in banner, banner
        port = int(banner.split("http://127.0.0.1:")[1].split(" ")[0])
        cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
        base = f"http://127.0.0.1:{port}"
        st = _wait_for(
            lambda: (s := _get(f"http://127.0.0.1:{cport}/healthz")[1])
            ["status"] == "ok" and s,
            timeout=120, what="fleet ready",
        )
        assert st["spawn_mode"] == "fork"
        # Both workers verified the COMPRESSED DB through the same
        # check_db gate (full block decode) before joining.
        assert all(w["verified"] == {"default": True}
                   for w in st["workers"].values())
        positions = sorted(oracle)
        for i in range(0, len(positions), 64):
            chunk = [hex(p) for p in positions[i:i + 64]]
            status, body = _post(base + "/query", {"positions": chunk})
            assert status == 200
            for q, rec in zip(chunk, body["results"]):
                v, r = oracle[int(q, 0)]
                assert (rec["found"], rec["value"], rec["remoteness"]) \
                    == (True, value_name(v), r), q
        # Worker-side cache series, worker-labeled (the serve port is
        # answered by whichever worker accepts; sample until one shows
        # its registry).
        def _cache_metrics():
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            return ("gamesman_db_cache_hits_total" in text
                    and 'worker="' in text) and text
        text = _wait_for(_cache_metrics, timeout=30,
                         what="worker cache metrics")
        assert "gamesman_db_block_decode_seconds" in text
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # The worker-stamped JSONL streams carry the cache trajectory, and
    # obs_report folds them into per-worker hit-rate columns.
    records = []
    for path in tmp_path.glob("serve*.jsonl"):
        for line in path.read_text().splitlines():
            if line.strip():
                records.append(json.loads(line))
    batches = [r for r in records if r.get("phase") == "serve_batch"]
    assert batches
    assert any("db_cache_hits" in r for r in batches)
    assert {r.get("worker") for r in batches} - {None}
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    lines = obs_report.summarize_serving(records)
    assert any("db_cache_hit_rate=" in line for line in lines), lines


def test_cli_fleet_without_db_is_a_usage_error(tmp_path):
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    proc = subprocess.run(
        _CLI + ["serve", "--workers", "2"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=120,
    )
    assert proc.returncode == 2
    assert "needs a DB directory" in proc.stderr


def test_external_sigterm_respawns_instead_of_parking(sub_db):
    """A worker SIGTERM'd by an operator (not the supervisor) drains
    and exits 0 — the slot must be replaced, not parked 'stopped':
    the supervisor owns the fleet size."""
    sup = _fake_supervisor(sub_db, ["ok", "ok"]).start()
    try:
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok" and s,
            what="fleet ready",
        )
        victim = st["workers"]["0"]["pid"]
        os.kill(victim, signal.SIGTERM)  # external: no roll in progress
        st = _wait_for(
            lambda: (s := sup.status())["workers"]["0"]["state"] == "ready"
            and s["workers"]["0"]["pid"] != victim and s,
            what="externally drained worker respawned",
        )
        # A clean drain is not a death: no backoff restart was charged.
        assert st["workers"]["0"]["restarts"] == 0
        assert st["status"] == "ok"
    finally:
        sup.stop()


def test_wedged_teardown_does_not_cascade_to_siblings(sub_db):
    """A worker that closes its pipe but lingers (wedged teardown,
    SIGTERM-immune) is SIGKILLed promptly — and the reap must not
    starve the sibling's heartbeat reads into a phantom 'stall' that
    SIGKILLs the healthy half of the fleet."""
    sup = _fake_supervisor(sub_db, ["wedge", "ok"]).start()
    try:
        _wait_for(lambda: sup.status()["status"] == "ok",
                  what="fleet ready")
        # The wedge fires ~80 ms after ready; wait for its restart.
        _wait_for(
            lambda: sup.status()["workers"]["0"]["restarts"] >= 1,
            what="wedged worker reaped and restarted",
        )
        time.sleep(1.0)  # a cascade would kill worker 1 within this
        assert sup.status()["workers"]["1"]["restarts"] == 0, \
            "healthy sibling was killed during the wedge reap"
    finally:
        sup.stop()


def test_roll_aborts_and_rolls_back_when_replacement_cannot_warm_start(
        tmp_path, sub_db):
    """A structurally-valid manifest whose DB fails the worker verify
    gate passes the parent's reload checks — the roll must then ABORT
    and roll BACK to the pre-reload config instead of wedging forever
    at N-1 capacity with all future reloads blocked."""
    import subprocess as sp

    from gamesmanmpi_tpu.serve.supervisor import _ExecProc

    from helpers import FAKE_FLEET_WORKER

    manifest = tmp_path / "fleet.json"

    def write_manifest(name):
        manifest.write_text(json.dumps({
            "version": 1, "games": [{"name": name, "db": str(sub_db)}],
        }))

    write_manifest("good")

    def spawn(idx, cfg):
        # The fake analog of the verify gate: any worker built for the
        # "bad" game refuses to come up.
        mode = ("crash" if any(n == "bad" for n, _ in cfg["entries"])
                else "ok")
        r, w = os.pipe()
        proc = sp.Popen(
            [sys.executable, "-c", FAKE_FLEET_WORKER, str(w), mode],
            pass_fds=(w,),
        )
        os.close(w)
        return _ExecProc(proc), r

    sup = ServeSupervisor(
        load_fleet_manifest(manifest), workers=2, control_port=None,
        manifest_path=manifest, heartbeat_secs=0.05,
        heartbeat_timeout=0.6, restart_base=0.01, restart_max=0.05,
        storm_restarts=2, storm_secs=60.0, drain_grace=5.0, spawn=spawn,
    ).start()
    try:
        _wait_for(lambda: sup.status()["status"] == "ok",
                  what="fleet ready")
        write_manifest("bad")  # structurally valid; workers will refuse
        sup.request_reload()
        st = _wait_for(
            lambda: (s := sup.status())["last_reload_error"]
            and "aborted" in s["last_reload_error"] and s,
            timeout=60, what="roll aborted",
        )
        # ...and the rollback restores full capacity on the OLD config.
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok"
            and not s["reload_in_progress"] and s,
            timeout=60, what="rollback roll completed",
        )
        assert "aborted" in st["last_reload_error"]
        assert sorted(e.name for e in sup.entries) == ["good"]
        # A corrective reload is NOT blocked by the aborted one.
        write_manifest("good2")
        sup.request_reload()
        st = _wait_for(
            lambda: (s := sup.status())["games"] == ["good2"]
            and s["status"] == "ok" and s,
            timeout=60, what="corrective reload",
        )
        assert st["last_reload_error"] is None
    finally:
        sup.stop()


def test_externally_drained_worker_that_wedges_is_killed(sub_db):
    """An operator SIGTERM whose teardown wedges after announcing
    'draining' still gets a drain deadline from the supervisor — the
    slot is SIGKILLed and replaced, never left lingering at N-1."""
    sup = _fake_supervisor(sub_db, ["stuckdrain"], drain_grace=1.0).start()
    try:
        st = _wait_for(
            lambda: (s := sup.status())["status"] == "ok" and s,
            what="fleet ready",
        )
        victim = st["workers"]["0"]["pid"]
        os.kill(victim, signal.SIGTERM)  # external; teardown will wedge
        st = _wait_for(
            lambda: (s := sup.status())["workers"]["0"]["state"] == "ready"
            and s["workers"]["0"]["pid"] != victim and s,
            timeout=30, what="wedged drain killed and replaced",
        )
        assert st["workers"]["0"]["restarts"] >= 1
    finally:
        sup.stop()
