"""ISSUE 15 mission control: live /status, flight recorder, roofline gate.

Four surfaces under test:

* the live status endpoint (obs/status.py) — tracker/ETA model units,
  the HTTP server, env gating, fleet merge with straggler flagging, and
  a REAL in-process sharded solve polled live (monotone positions
  solved, phase transitions, a finite converging ETA);
* the flight recorder (obs/flightrec.py) — ring bounds, in-flight span
  tracking, atomic dumps, and the abnormal exit paths: injected fatal
  fault (the CLI crash handler), watchdog abort, SIGTERM preemption;
* the coordinator address book (announce/peers) the fleet scraper uses;
* tools/bench_compare.py's regression gate exit codes.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from gamesmanmpi_tpu.obs import Span, flightrec
from gamesmanmpi_tpu.obs import status as obs_status
from gamesmanmpi_tpu.obs.registry import (
    MetricsRegistry,
    estimate_quantiles,
)
from helpers import REPO, load_module

_CLI = [sys.executable, "-m", "gamesmanmpi_tpu.cli"]


def _get_json(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


# ------------------------------------------------------ registry quantiles


def test_estimate_quantiles_interpolates_within_buckets():
    bounds = (1.0, 2.0, 4.0, float("inf"))
    counts = [1, 2, 3, 1]  # 7 samples
    q = estimate_quantiles(bounds, counts, (0.5, 0.95, 0.99))
    # p50: target 3.5 lands in the (2, 4] bucket (cum 3 before it):
    # 2 + 2 * (3.5 - 3) / 3.
    assert abs(q[0.5] - (2 + 2 * 0.5 / 3)) < 1e-9
    # p99: target 6.93 lands in the +Inf bucket -> saturates at the
    # last finite bound, never an invented value.
    assert q[0.99] == 4.0


def test_estimate_quantiles_empty_histogram_is_none():
    q = estimate_quantiles((1.0, float("inf")), [0, 0])
    assert q[0.5] is None and q[0.99] is None


def test_histogram_snapshot_carries_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("gamesman_q_seconds", "x", buckets=[1, 2, 4])
    for v in (0.5, 1.5, 3.0, 3.0):
        h.observe(v)
    row = reg.snapshot()["gamesman_q_seconds"]["values"][0]
    assert set(row["quantiles"]) == {"p50", "p95", "p99"}
    assert 1.0 < row["quantiles"]["p50"] <= 4.0
    # Unobserved histograms snapshot with null quantiles, not a crash.
    reg.histogram("gamesman_q2_seconds", "x", buckets=[1])
    row2 = reg.snapshot()["gamesman_q2_seconds"]["values"][0]
    assert row2["quantiles"]["p99"] is None


# ------------------------------------------------------------ ETA tracker


def test_tracker_eta_converges_with_level_schedule():
    t = obs_status.SolveStatusTracker()
    assert t.eta_secs() is None  # no schedule yet
    t.set_schedule({0: 100, 1: 100, 2: 100})
    assert t.eta_secs() is None  # nothing resolved yet
    t.backward_level(2, 100, 1.0)
    assert t.eta_secs() == pytest.approx(2.0)  # 200 left at 100 pps
    t.backward_level(1, 100, 1.0)
    assert t.eta_secs() == pytest.approx(1.0)
    t.backward_level(0, 100, 1.0)
    assert t.eta_secs() == 0.0
    snap = t.snapshot({"phase": "backward", "level": 0})
    assert snap["positions_solved"] == 300
    assert snap["levels_solved"] == 3 and snap["levels_total"] == 3


def test_tracker_resumed_levels_do_not_poison_eta():
    """A checkpoint-resumed level replays millions of positions in
    milliseconds; the ETA's throughput EWMA must skip it or a restarted
    run claims hours of work finish in seconds."""
    t = obs_status.SolveStatusTracker()
    t.set_schedule({0: 1000, 1: 1000, 2: 1000})
    t.backward_level(2, 1000, 0.001, resumed=True)  # replayed from disk
    assert t.eta_secs() is None  # no real throughput observed yet
    t.backward_level(1, 1000, 10.0)  # real compute: 100 pps
    assert t.eta_secs() == pytest.approx(10.0)
    # A later resumed level still shrinks the remaining work but not
    # the rate model.
    t.backward_level(0, 1000, 0.001, resumed=True)
    assert t.eta_secs() == 0.0
    assert t.snapshot()["positions_solved"] == 3000
    assert t.snapshot()["throughput_pps"] == pytest.approx(100.0)


def test_status_request_counter_label_is_bounded():
    """Probed junk paths must not mint unbounded registry series."""
    reg = MetricsRegistry()
    srv = obs_status.StatusServer(
        lambda: {}, port=0, registry=reg
    ).start()
    try:
        for path in ("/admin", "/etc/passwd", "/x" * 3):
            with pytest.raises(urllib.error.HTTPError):
                _get_json(f"http://{srv.address}{path}")
        _get_json(f"http://{srv.address}/status")
    finally:
        srv.stop()
    rows = reg.snapshot()["gamesman_status_requests_total"]["values"]
    paths = {r["labels"]["path"] for r in rows}
    assert paths <= {"/status", "/metrics", "other"}
    other = next(r for r in rows if r["labels"]["path"] == "other")
    assert other["value"] == 3


def test_tracker_positions_solved_is_monotone_under_updates():
    t = obs_status.SolveStatusTracker()
    seen = []
    for lvl in (5, 4, 3):
        t.backward_level(lvl, 10, 0.1)
        seen.append(t.snapshot()["positions_solved"])
    assert seen == sorted(seen)


# ----------------------------------------------------------- HTTP server


def test_status_server_serves_status_metrics_and_404(tmp_path):
    reg = MetricsRegistry()
    reg.counter("gamesman_fixture_total", "x").inc(3)
    addr_file = tmp_path / "addr"
    srv = obs_status.StatusServer(
        lambda: {"phase": "forward", "level": 7},
        port=0, registry=reg, addr_file=str(addr_file),
    ).start()
    try:
        assert addr_file.read_text() == srv.address
        got = _get_json(f"http://{srv.address}/status")
        assert got == {"phase": "forward", "level": 7}
        with urllib.request.urlopen(
            f"http://{srv.address}/metrics", timeout=5
        ) as resp:
            text = resp.read().decode()
        assert "gamesman_fixture_total 3" in text
        assert "gamesman_status_requests_total" in text
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(f"http://{srv.address}/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_status_server_provider_error_is_500_not_death():
    srv = obs_status.StatusServer(
        lambda: 1 / 0, port=0, registry=MetricsRegistry()
    ).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(f"http://{srv.address}/status")
        assert ei.value.code == 500
    finally:
        srv.stop()


def test_maybe_status_server_env_gating(monkeypatch):
    monkeypatch.delenv("GAMESMAN_STATUS_PORT", raising=False)
    assert obs_status.maybe_status_server(lambda: {}) is None
    monkeypatch.setenv("GAMESMAN_STATUS_PORT", "junk")
    assert obs_status.maybe_status_server(lambda: {}) is None
    monkeypatch.setenv("GAMESMAN_STATUS_PORT", "0")
    srv = obs_status.maybe_status_server(lambda: {"ok": True})
    assert srv is not None
    try:
        assert _get_json(f"http://{srv.address}/status") == {"ok": True}
    finally:
        srv.stop()


# ------------------------------------------------------------ fleet merge


def _snap(levels, eta=None, phase="backward", solved=0):
    return {
        "phase": phase, "level": min(levels) if levels else None,
        "positions_solved": solved, "eta_secs": eta,
        "levels": {
            str(k): {"n": 10, "fwd_secs": f, "bwd_secs": b}
            for k, (f, b) in levels.items()
        },
    }


def test_merge_fleet_max_walls_and_straggler_flagging():
    snaps = {
        0: _snap({3: (1.0, 1.0), 4: (1.0, 0.0)}, eta=5.0, solved=100),
        1: _snap({3: (1.0, 1.1), 4: (1.0, 0.0)}, eta=6.0, solved=100),
        2: _snap({3: (1.0, 9.0), 4: (1.0, 0.0)}, eta=30.0, solved=60),
    }
    fleet = obs_status.merge_fleet(snaps, world=3, factor=1.5)
    assert fleet["world"] == 3
    assert fleet["ranks_reporting"] == [0, 1, 2]
    # Per-level wall is max across ranks (the level ran once,
    # collectively), not a sum.
    assert fleet["levels"]["3"]["wall_secs"] == pytest.approx(10.0)
    # Rank 2's level-3 wall (10.0) is far past 1.5x the median (2.1):
    # flagged, with the evidence attached.
    assert [s["rank"] for s in fleet["stragglers"]] == [2]
    assert fleet["stragglers"][0]["level"] == 3
    assert fleet["stragglers"][0]["lag"] > 1.5
    # Fleet ETA is the slowest rank's (the fleet finishes when the
    # last rank does).
    assert fleet["eta_secs"] == pytest.approx(30.0)


def test_merge_fleet_without_divergence_flags_nobody():
    snaps = {
        0: _snap({3: (1.0, 1.0)}),
        1: _snap({3: (1.0, 1.05)}),
    }
    fleet = obs_status.merge_fleet(snaps, world=2, factor=1.5)
    assert fleet["stragglers"] == []


def test_fetch_status_dead_peer_degrades_to_none():
    assert obs_status.fetch_status("127.0.0.1:1", timeout=0.2) is None


# ------------------------------------------------- coordinator address book


def test_coordinator_announce_and_peers():
    from gamesmanmpi_tpu.resilience.coordination import (
        CoordinatorServer,
        EpochBarrier,
    )

    server = CoordinatorServer(world=2, deadline=5.0)
    try:
        c0 = EpochBarrier(server.address, 0, deadline=5.0)
        c1 = EpochBarrier(server.address, 1, deadline=5.0)
        c0.announce("127.0.0.1:1111")
        c1.announce("127.0.0.1:2222")
        assert c0.peers() == {0: "127.0.0.1:1111", 1: "127.0.0.1:2222"}
        # Re-announce overwrites (a restarted rank rebinds a new port).
        c1.announce("127.0.0.1:3333")
        assert c0.peers()[1] == "127.0.0.1:3333"
    finally:
        server.close()


# -------------------------------------------------- live solve end-to-end


def test_live_status_during_real_sharded_solve(monkeypatch, tmp_path):
    """The acceptance shape, in-process: a real 2-shard solve serves
    /status while running; polls observe monotone positions_solved,
    the forward->backward phase transition, and a finite ETA."""
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.parallel import ShardedSolver
    from gamesmanmpi_tpu.resilience import faults

    addr_file = tmp_path / "addr"
    monkeypatch.setenv("GAMESMAN_STATUS_PORT", "0")
    monkeypatch.setenv("GAMESMAN_STATUS_ADDR_FILE", str(addr_file))
    # Stretch each forward level so the poller observes mid-flight
    # state deterministically (delays are absorbed, never fatal).
    faults.configure("sharded.forward:delay=0.04:always")
    solver = ShardedSolver(get_game("tictactoe"), num_shards=2)
    done = {}

    def run():
        done["result"] = solver.solve()

    t = threading.Thread(target=run)
    t.start()
    samples = []
    addr = None
    try:
        while t.is_alive():
            if addr is None:
                try:
                    addr = addr_file.read_text().strip()
                except OSError:
                    time.sleep(0.01)
                    continue
            try:
                samples.append(
                    _get_json(f"http://{addr}/status", timeout=2)
                )
            except Exception:
                pass
            time.sleep(0.01)
        t.join()
    finally:
        faults.clear()
    assert done["result"].value is not None
    assert len(samples) >= 3, "poller never observed the live solve"
    solved = [s["positions_solved"] for s in samples]
    assert solved == sorted(solved), "positions_solved regressed"
    phases = {s.get("phase") for s in samples}
    assert "forward" in phases and "backward" in phases
    etas = [s["eta_secs"] for s in samples
            if s.get("eta_secs") is not None]
    assert etas, "no finite ETA observed during backward"
    assert all(e < 3600 for e in etas)
    # The identity + io fields ride every snapshot.
    assert samples[-1]["engine"] == "sharded"
    assert samples[-1]["shards"] == 2
    assert "io" in samples[-1]


# --------------------------------------------------------- flight recorder


def test_flightrec_ring_bound_and_dropped_accounting():
    rec = flightrec.FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("x", i=i)
    snap = rec.snapshot()
    assert len(snap["events"]) == 16
    assert snap["dropped"] == 24
    assert snap["events"][-1]["i"] == 39  # newest survive


def test_flightrec_tracks_inflight_spans():
    base = flightrec.default_recorder().snapshot()
    n0 = len(base["inflight_spans"])
    sp = Span("forward", level=9)
    mid = flightrec.default_recorder().snapshot()
    names = [s["span"] for s in mid["inflight_spans"]]
    assert names.count("forward") == n0_forward(base) + 1
    sp.end()
    after = flightrec.default_recorder().snapshot()
    assert len(after["inflight_spans"]) == n0
    assert any(
        e["kind"] == "span" and e.get("span") == "forward"
        and e.get("level") == 9
        for e in after["events"]
    )


def n0_forward(snap):
    return sum(
        1 for s in snap["inflight_spans"] if s["span"] == "forward"
    )


def test_flightrec_dump_is_atomic_and_named(tmp_path):
    rec = flightrec.FlightRecorder(capacity=32)
    rec.level_complete("forward", 5)
    rec.record("retry", point="engine.forward")
    path = rec.dump("unit_test", directory=str(tmp_path), rank="7")
    assert path == str(tmp_path / "flightrec_7.json")
    body = json.loads((tmp_path / "flightrec_7.json").read_text())
    assert body["reason"] == "unit_test"
    assert body["last_completed"] == {"forward": 5}
    assert any(e["kind"] == "retry" for e in body["events"])
    assert not list(tmp_path.glob("*.tmp*"))  # tmp+replace left no turd


def test_flightrec_boundary_dump_gated_on_env(tmp_path, monkeypatch):
    monkeypatch.delenv("GAMESMAN_FLIGHTREC_DIR", raising=False)
    flightrec.boundary("forward", 1)  # env unset: notes, never writes
    assert not list(tmp_path.glob("flightrec_*.json"))
    monkeypatch.setenv("GAMESMAN_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.delenv("GAMESMAN_PROCESS_ID", raising=False)
    flightrec.boundary("forward", 2)
    body = json.loads((tmp_path / "flightrec_0.json").read_text())
    assert body["reason"] == "boundary"
    assert body["last_completed"]["forward"] == 2


def test_watchdog_abort_dumps_flightrec(tmp_path, monkeypatch):
    from gamesmanmpi_tpu.resilience.supervisor import Watchdog

    monkeypatch.setenv("GAMESMAN_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.delenv("GAMESMAN_PROCESS_ID", raising=False)
    fired = threading.Event()
    wd = Watchdog(
        lambda: {"phase": "backward", "level": 3},
        min_secs=0.05, poll=0.01, action=fired.set,
        registry=MetricsRegistry(),
    ).start()
    try:
        assert fired.wait(timeout=10)
    finally:
        wd.stop()
    body = json.loads((tmp_path / "flightrec_0.json").read_text())
    assert body["reason"] == "watchdog_abort"
    assert any(e["kind"] == "watchdog_abort" for e in body["events"])


def test_cli_fatal_fault_leaves_crash_flightrec(tmp_path):
    """Injected fatal fault mid-backward: the CLI's crash handler dumps
    flightrec_0.json (into the checkpoint dir by default) naming the
    last completed forward level and the events leading to the death."""
    ck = tmp_path / "ck"
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    env["GAMESMAN_FAULTS"] = "engine.backward:fatal"
    env.pop("GAMESMAN_FLIGHTREC_DIR", None)
    proc = subprocess.run(
        _CLI + ["tictactoe", "--checkpoint-dir", str(ck)],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=300,
    )
    assert proc.returncode != 0
    body = json.loads((ck / "flightrec_0.json").read_text())
    assert body["reason"] == "crash"
    assert body["last_completed"]["forward"] >= 0
    assert any(e["kind"] == "fault" for e in body["events"])
    assert any(
        e.get("span") == "forward" for e in body["events"]
        if e["kind"] == "span"
    )


def test_cli_sigterm_preemption_leaves_flightrec(tmp_path):
    """SIGTERM grace drain (exit 75) also leaves the post-mortem."""
    ck = tmp_path / "ck"
    env = dict(os.environ)
    env["GAMESMAN_PLATFORM"] = "cpu"
    # Stretch forward levels so the signal lands mid-solve.
    env["GAMESMAN_FAULTS"] = "engine.forward:delay=0.2:always"
    env.pop("GAMESMAN_FLIGHTREC_DIR", None)
    proc = subprocess.Popen(
        _CLI + ["connect4:w=4,h=4", "--checkpoint-dir", str(ck)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=str(REPO),
    )
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if (ck / "manifest.json").exists():
                break
            if proc.poll() is not None:
                pytest.fail(f"solve died early: {proc.stderr.read()}")
            time.sleep(0.1)
        time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert rc == 75, proc.stderr.read()
    body = json.loads((ck / "flightrec_0.json").read_text())
    assert body["reason"] == "preempted"
    assert "forward" in body["last_completed"]


# ---------------------------------------------------------- campaign proxy


def test_campaign_status_payload_proxies_child(tmp_path):
    from gamesmanmpi_tpu.resilience.campaign import (
        Campaign,
        CampaignConfig,
    )

    cfg = CampaignConfig(
        solver_args=["tictactoe"],
        checkpoint_dir=str(tmp_path / "ck"),
        max_attempts=2, no_progress_limit=2,
    )
    camp = Campaign(cfg)
    camp._attempt = 3
    camp._last_cause = "killed"
    camp._no_progress = 1
    child = obs_status.StatusServer(
        lambda: {"phase": "backward", "level": 4,
                 "positions_solved": 123},
        port=0, registry=MetricsRegistry(),
        addr_file=str(camp._solve_addr_file),
    ).start()
    try:
        payload = camp._status_payload()
    finally:
        child.stop()
    assert payload["kind"] == "campaign"
    assert payload["attempt"] == 3
    assert payload["last_cause"] == "killed"
    assert payload["breaker"] == "closed"
    assert payload["solve"]["positions_solved"] == 123
    assert "progress" in payload  # jax-free checkpoint progress


def test_campaign_death_classifier_dumps_flightrec(tmp_path):
    """One fatally-wounded attempt: the campaign's classifier leaves
    flightrec_campaign.json next to the attempt logs, and the attempt
    itself (boundary dumps armed by the campaign env) leaves
    flightrec_0.json."""
    from gamesmanmpi_tpu.resilience.campaign import (
        Campaign,
        CampaignConfig,
    )

    ck = tmp_path / "ck"
    cfg = CampaignConfig(
        solver_args=["tictactoe"],
        checkpoint_dir=str(ck),
        max_attempts=2, no_progress_limit=2,
        backoff_base_secs=0.01, backoff_max_secs=0.01,
        chaos=["engine.backward:fatal"],
    )
    rc = Campaign(cfg).run()
    # Attempt 1 crashes (injected fatal), attempt 2 resumes clean.
    assert rc == 0
    log_dir = tmp_path / "ck" / "logs"
    camp_body = json.loads(
        (log_dir / "flightrec_campaign.json").read_text()
    )
    assert camp_body["rank"] == "campaign"
    assert any(
        e["kind"] == "campaign_attempt" and e.get("cause") == "crash"
        for e in camp_body["events"]
    )
    # The attempt's own dumps (GAMESMAN_FLIGHTREC_DIR armed by the
    # campaign env) name its last completed level. (Attempt 2 resumed
    # from complete frontiers, so its final boundary is a backward one.)
    child_body = json.loads((log_dir / "flightrec_0.json").read_text())
    assert child_body["last_completed"], "no level boundary recorded"


def test_campaign_sigkilled_attempt_leaves_flightrec(tmp_path):
    """The acceptance shape: an attempt SIGKILLed mid-solve (kill fault
    — no in-process exit path at all) still leaves flightrec_0.json,
    because the campaign arms GAMESMAN_FLIGHTREC_DIR and the engines
    checkpoint the ring at every level boundary."""
    from gamesmanmpi_tpu.resilience.campaign import (
        Campaign,
        CampaignConfig,
    )

    ck = tmp_path / "ck"
    cfg = CampaignConfig(
        solver_args=["tictactoe"],
        checkpoint_dir=str(ck),
        max_attempts=3, no_progress_limit=3,
        backoff_base_secs=0.01, backoff_max_secs=0.01,
        chaos=["ckpt.save_level:kill:2"],
    )
    rc = Campaign(cfg).run()
    assert rc == 0
    log_dir = ck / "logs"
    camp_body = json.loads(
        (log_dir / "flightrec_campaign.json").read_text()
    )
    assert any(
        e["kind"] == "campaign_attempt" and e.get("cause") == "killed"
        for e in camp_body["events"]
    )
    # The SIGKILLed attempt's boundary dump (or the clean retry's final
    # one — latest writer wins) names a completed level and carries the
    # in-flight span table.
    child_body = json.loads((log_dir / "flightrec_0.json").read_text())
    assert child_body["last_completed"]
    assert "inflight_spans" in child_body
    assert any(e["kind"] == "level" for e in child_body["events"])


# ---------------------------------------------------------- bench_compare


def _bench_record(value, metric="fixture_pps", device="cpu", **extra):
    return {
        "metric": metric, "value": value, "device": device,
        "roofline": {"operand_gbps": 0.1, "pps_per_chip": value},
        "dispatches": {"total": 10, "per_level": 2.0},
        **extra,
    }


def test_bench_compare_gates_regression(tmp_path):
    bench_compare = load_module(REPO / "tools" / "bench_compare.py")
    ref = tmp_path / "BENCH_ref.json"
    ref.write_text(json.dumps(_bench_record(1000.0)))
    traj = str(tmp_path / "BENCH_*.json")
    ok = tmp_path / "new_ok.json"
    ok.write_text(json.dumps(_bench_record(950.0)))
    assert bench_compare.main(
        [str(ok), "--trajectory", traj]
    ) == 0
    # A synthetic 2x slowdown gates non-zero at the default threshold.
    slow = tmp_path / "new_slow.json"
    slow.write_text(json.dumps(_bench_record(500.0)))
    assert bench_compare.main(
        [str(slow), "--trajectory", traj]
    ) == 1
    # --min-ratio overrides the default.
    assert bench_compare.main(
        [str(slow), "--trajectory", traj, "--min-ratio", "0.4"]
    ) == 0


def test_bench_compare_no_reference_passes_with_note(tmp_path, capsys):
    bench_compare = load_module(REPO / "tools" / "bench_compare.py")
    new = tmp_path / "new.json"
    new.write_text(json.dumps(_bench_record(1.0, metric="novel_pps")))
    assert bench_compare.main(
        [str(new), "--trajectory", str(tmp_path / "BENCH_*.json")]
    ) == 0
    assert "no comparable reference" in capsys.readouterr().out


def test_bench_compare_usage_errors_exit_2(tmp_path):
    bench_compare = load_module(REPO / "tools" / "bench_compare.py")
    assert bench_compare.main([str(tmp_path / "missing.json")]) == 2
    junk = tmp_path / "junk.json"
    junk.write_text("not a record")
    assert bench_compare.main([str(junk)]) == 2


def test_bench_compare_passes_committed_trajectory():
    """The acceptance gate: the newest committed record passes the
    committed trajectory with the default threshold."""
    bench_compare = load_module(REPO / "tools" / "bench_compare.py")
    assert bench_compare.main([str(REPO / "BENCH_fused_r14.json")]) == 0


# ------------------------------------------------------------ solve stats


def test_solver_stats_carry_roofline_rollup():
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.solve import Solver

    stats = Solver(get_game("subtract:total=12,moves=1-2")).solve().stats
    rf = stats["roofline"]
    assert set(rf) == {"operand_gbps", "pps_per_chip"}
    assert rf["pps_per_chip"] > 0
    assert rf["operand_gbps"] >= 0
    assert stats["bytes_host"] >= 0
