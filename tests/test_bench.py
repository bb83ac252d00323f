"""bench.py contract tests (subprocess): the driver consumes its stdout.

On success the benchmark prints exactly one JSON line on stdout with the
fields a record needs (metric/value/device/engine); a failing engine makes
the whole bench fail, with no record and no demotion. These run the real
script on the tiny 3x3 connect-3 board, CPU-pinned.
"""

import json
import os
import subprocess
import sys

import pytest

# Smoke tier: fast, compile-light, single-process-safe (see pyproject).
pytestmark = pytest.mark.smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def _bench_proc(extra_env, timeout=600):
    env = dict(os.environ)
    # Isolate from the suite's 8-device faking: conftest put the device-
    # count flag into XLA_FLAGS, which the child would inherit; a real
    # bench invocation runs single-device.
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env.update(
        GAMESMAN_PLATFORM="cpu",
        BENCH_GAME="connect4:w=3,h=3,connect=3",
        BENCH_SYM="0",
        BENCH_LADDER="0",
        BENCH_REPEATS="1",
    )
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, _BENCH], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=_REPO,
    )


def _run_bench(extra_env, timeout=600):
    proc = _bench_proc(extra_env, timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    return json.loads(lines[0]), proc.stderr


def test_bench_smoke_contract():
    """BENCH_SMOKE=1: the fast contract check that pins the record
    fields so they can't rot — warmup excluded from the variance block
    but published raw, and dispatch economy present."""
    record, stderr = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_SMOKE": "1",
    })
    # Warmup contract: excluded from n/median/all_pps, raw rate kept.
    assert record["runs"]["n"] == 1
    assert len(record["runs"]["all_pps"]) == 1
    assert len(record["runs"]["warmup_pps"]) == 1
    # Dispatch economy fields.
    assert record["dispatches"]["total"] > 0
    assert record["dispatches"]["per_level"] > 0
    # Roofline fields: operand throughput and per-chip rate.
    rf = record["roofline"]
    assert set(rf) == {"operand_gbps", "pps_per_chip"}
    assert rf["pps_per_chip"] == record["value"]
    # The XLA host-feature-mismatch spam is filtered from the forwarded
    # stderr (it dwarfed the run lines in BENCH_r05.json's tail).
    assert "host machine features" not in stderr
    assert "could lead to execution errors" not in stderr


def test_bench_hybrid_sym_subrun_keeps_engine():
    """ADVICE r5 leftover (pinned by ISSUE 10): BENCH_ENGINE=hybrid must
    NOT gate on game.sym — the secondary sym sub-run benches the SAME
    hybrid engine as the primary, and the sym sub-record says which
    engine actually ran so a silent demotion can never masquerade as a
    hybrid measurement."""
    record, stderr = _run_bench({
        "BENCH_ENGINE": "hybrid",
        "BENCH_SYM": "1",
    })
    assert record["engine"] == "hybrid", stderr[-1000:]
    assert "demoting to the classic engine" not in stderr
    sym = record.get("sym")
    assert sym is not None, "sym sub-run missing from the record"
    assert sym["engine"] == "hybrid", sym
    assert sym["positions"] > 0


@pytest.mark.slow
def test_bench_dense_happy_path():
    record, _ = _run_bench({"BENCH_ENGINE": "dense"})
    assert record["engine"] == "dense"
    assert record["positions"] == 694  # exact reachable count
    assert record["device"] == "cpu"  # the explicit GAMESMAN_PLATFORM pin
    assert "fallback_cpu" not in record  # there is no fallback any more
    assert record["value"] > 0
    # Variance block (VERDICT r4 weak #1): best is the headline, the
    # per-run spread is published alongside it.
    assert record["runs"]["n"] == 1
    assert record["runs"]["median_pps"] <= record["value"]
    assert len(record["runs"]["all_pps"]) == 1


@pytest.mark.slow
def test_bench_multiprocess_smoke_artifact(tmp_path):
    """BENCH_PROCESSES=2 (ISSUE 6): the bench additionally drives a REAL
    2-process sharded solve through tools/launch_multihost.py and emits
    a MULTICHIP-style artifact with per-rank level times, while stdout
    stays exactly one JSON line with a multiprocess summary."""
    out = tmp_path / "MULTICHIP_mp.json"
    record, stderr = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_PROCESSES": "2",
        "BENCH_MP_GAME": "connect4:w=3,h=3,connect=3",
        "BENCH_PROCESSES_OUT": str(out),
    })
    assert record["positions"] == 694  # the primary metric still ran
    mp = record["multiprocess"]
    artifact = json.loads(out.read_text())
    if not mp["ok"] and "Multiprocess computations" in artifact.get(
            "error", ""):
        pytest.skip("backend cannot run multiprocess collectives")
    assert mp["ok"] is True, artifact.get("error")
    assert mp["processes"] == 2 and mp["shards"] == 4
    assert mp["positions"] == 694
    assert artifact["rc_by_rank"] == [0, 0]
    # Per-rank level times: every level row carries BOTH ranks' forward
    # seconds (the point of the artifact — a perf trajectory per rank).
    assert artifact["levels"], artifact
    for row in artifact["levels"]:
        assert set(row["fwd_secs"]) == {"0", "1"} or \
            set(row["bwd_secs"]) == {"0", "1"}, row
    assert set(artifact["done_by_rank"]) == {"0", "1"}


def test_bench_fails_without_accelerator():
    # No chip and no explicit CPU request: the bench fails instead of
    # publishing a CPU number (the suite's env pins JAX to the CPU).
    env = {"GAMESMAN_PLATFORM": ""}
    proc = _bench_proc(env, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_bench_fails_when_dense_breaks():
    # An unknown dense-only knob value breaks DenseSolver's constructor; the
    # bench must fail, not demote to the classic engine and publish a
    # record that names an engine nobody asked for.
    proc = _bench_proc(
        {"BENCH_ENGINE": "dense", "GAMESMAN_DENSE_GATHER": "bogus"}
    )
    assert proc.returncode != 0
    assert "GAMESMAN_DENSE_GATHER" in proc.stderr
    assert "demoting" not in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


@pytest.mark.slow
def test_bench_serve_slo_artifact(tmp_path):
    """BENCH_SERVE=1 (ISSUE 7): the bench additionally exports a DB,
    launches the supervised fleet, drives load-gen traffic through a
    mid-load worker SIGKILL, and gates on the latency SLO — stdout
    stays exactly one JSON line with a serve summary, the full record
    lands in BENCH_SERVE_OUT."""
    out = tmp_path / "BENCH_serve.json"
    record, _ = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_SERVE": "1",
        "BENCH_SERVE_GAME": "subtract:total=21,moves=1-2-3",
        "BENCH_SERVE_SECS": "4",
        "BENCH_SERVE_CONC": "4",
        "BENCH_SERVE_SLO_P99_MS": "2000",
        "BENCH_SERVE_OUT": str(out),
    })
    sv = record["serve"]
    artifact = json.loads(out.read_text())
    assert sv["ok"] is True, artifact.get("error")
    assert sv["workers"] == 2
    assert sv["slo_ok"] is True
    assert sv["mismatches"] == 0
    assert sv["dropped"] <= 4  # the in-flight budget of the kill
    assert sv["worker_restarts"] == 1
    assert sv["recovered_secs"] is not None
    assert artifact["spawn_mode"] == "fork"
    assert artifact["requests"] > 0
    assert artifact["p99_ms"] > 0
    # The chaos really happened and really healed inside the run.
    assert artifact["killed_worker"] in ("0", "1")


@pytest.mark.slow
def test_bench_campaign_artifact(tmp_path):
    """BENCH_CAMPAIGN=1 (ISSUE 12): the bench additionally drives a
    chaos campaign — injected kills at forward/backward/write-behind,
    auto-resumed to completion by tools/run_campaign.py — and gates on
    byte-parity vs an uninterrupted solve. Single-process tiny config
    here; the committed artifacts/CAMPAIGN_r12.json is the 2-process
    5x4 acceptance run of the same code path."""
    out = tmp_path / "BENCH_campaign.json"
    record, _ = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_CAMPAIGN": "1",
        "BENCH_CAMPAIGN_GAME": "connect4:w=3,h=3,connect=3",
        "BENCH_CAMPAIGN_PROCESSES": "1",
        "BENCH_CAMPAIGN_SHARDS": "2",
        "BENCH_CAMPAIGN_OUT": str(out),
    }, timeout=900)
    cb = record["campaign"]
    artifact = json.loads(out.read_text())
    assert cb["ok"] is True, artifact.get("error")
    assert cb["chaos_ok"] is True
    assert cb["parity_ok"] is True
    assert cb["attempts"] == 4
    assert cb["causes"] == ["killed"] * 3 + ["complete"]
    # The artifact carries the whole ledger: every attempt auditable.
    phases = [r.get("phase") for r in artifact["ledger"]]
    assert phases.count("campaign_attempt") == 4
    assert phases[-1] == "campaign_done"


@pytest.mark.slow
def test_bench_campaign_elastic_artifact(tmp_path):
    """BENCH_CAMPAIGN_ELASTIC=1 (ISSUE 13): a solve SIGKILLed at 4
    shards is resumed by a campaign at 2 shards (reshard adoption on
    the ledger), and an injected-oom campaign auto-escalates 2->4
    shards to completion — both byte-parity vs an uninterrupted solve.
    Tiny config here; the committed artifacts/CAMPAIGN_r13.json is the
    5x4 acceptance run of the same code path."""
    out = tmp_path / "BENCH_campaign_elastic.json"
    record, _ = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_CAMPAIGN_ELASTIC": "1",
        "BENCH_CAMPAIGN_ELASTIC_GAME": "connect4:w=3,h=3,connect=3",
        "BENCH_CAMPAIGN_ELASTIC_SHARDS": "2",
        "BENCH_CAMPAIGN_ELASTIC_SEAL_SHARDS": "4",
        "BENCH_CAMPAIGN_ELASTIC_OOM_SHARDS": "2",
        "BENCH_CAMPAIGN_ELASTIC_OUT": str(out),
    }, timeout=900)
    eb = record["campaign_elastic"]
    artifact = json.loads(out.read_text())
    assert eb["ok"] is True, json.dumps(artifact)[:2000]
    assert eb["reshard"]["sealed_shards"] == 4
    assert eb["reshard"]["attempt_shards"] == 2
    assert eb["reshard"]["parity_ok"] is True
    assert eb["oom"]["causes"][0] == "oom"
    assert eb["oom"]["causes"][-1] == "complete"
    assert eb["oom"]["escalations"][0]["from_shards"] == 2
    assert eb["oom"]["escalations"][0]["to_shards"] == 4
    assert eb["oom"]["parity_ok"] is True
    # Both scenario ledgers ride the artifact, auditable end to end.
    assert any(r.get("phase") == "campaign_reshard"
               for r in artifact["oom"]["ledger"])


@pytest.mark.slow
def test_bench_db_compress_artifact(tmp_path):
    """BENCH_DB_COMPRESS=1 (ISSUE 9): the bench additionally solves a
    board once, exports it v1 AND block-compressed v2, proves the two
    logically identical (full content, not a sample), gates the
    whole-DB ratio, and serves BOTH through real fleets under load-gen
    traffic gating the v2 p99 — stdout stays exactly one JSON line
    with a db_compress summary, the full A/B lands in
    BENCH_DB_COMPRESS_OUT."""
    out = tmp_path / "BENCH_db_compress.json"
    record, _ = _run_bench({
        "BENCH_ENGINE": "classic",
        "BENCH_DB_COMPRESS": "1",
        # ttt compresses well even at tiny scale; 1.5x keeps the gate
        # honest without demanding the 5x4 board's 15x in a smoke test.
        "BENCH_DB_GAME": "tictactoe",
        "BENCH_DB_MIN_RATIO": "1.5",
        "BENCH_DB_SECS": "3",
        "BENCH_DB_CONC": "4",
        "BENCH_DB_SLO_P99_MS": "2000",
        "BENCH_DB_COMPRESS_OUT": str(out),
    })
    dbc = record["db_compress"]
    artifact = json.loads(out.read_text())
    assert dbc["ok"] is True, artifact.get("error")
    assert dbc["full_equal"] is True
    assert dbc["ratio"] >= 1.5
    assert dbc["ratio_ok"] is True and dbc["slo_ok"] is True
    assert artifact["positions"] == 5478
    for arm in ("v1", "v2"):
        assert artifact[arm]["errors"] == 0
        assert artifact[arm]["mismatches"] == 0
        assert artifact[arm]["p99_ms"] > 0
    assert artifact["v2_bytes"] < artifact["v1_bytes"]
