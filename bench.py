#!/usr/bin/env python
"""Benchmark: positions-solved/sec/chip (BASELINE.json tracked metric).

Runs a full strong solve of a Connect-4 board on the available accelerator
and reports throughput over the complete solve (forward discovery + backward
value/remoteness propagation, all reachable positions).

Board selection: BASELINE.json's primary-metric config is Connect-4 6x6 on a
v4-16; on a single chip we default to the largest board that solves in a
benchmark-friendly time and raise it as kernels speed up (override with
BENCH_GAME). The metric (positions/sec/chip) is comparable across boards.

`vs_baseline`: the reference publishes no numbers (BASELINE.md), so the ratio
is computed against the north-star-implied per-chip rate: 4.5e12 states in
1 hour on 32 chips = 39.06M positions/sec/chip. vs_baseline = value / 39.06e6.

The measurement runs in ONE child process, the one that holds the chip,
under a wall-clock deadline; this parent never imports jax. There is no
fallback: a child that finds no accelerator fails, and so does the bench.
An explicit GAMESMAN_PLATFORM=cpu run (the tests' tiny boards) stays
allowed and records `device: "cpu"`.

Prints exactly ONE JSON line on stdout; everything else goes to stderr.
"""

import json
import os
import statistics
import subprocess
import sys
import threading
import time

# BASELINE.md's north star: 4.5e12 positions in 1h on 32 chips.
NORTH_STAR_PPS = 4.5e12 / 3600.0 / 32.0  # 39.06M pos/s/chip

#: Published HBM bandwidth by jax device_kind, GB/s (Google Cloud
#: documentation, "TPU v5e"). A kind that is missing is an error.
HBM_GBPS = {"TPU v5 lite": 819.0}


def _is_feature_spam(line: str) -> bool:
    """XLA's host-feature-mismatch warning: a single multi-hundred-char
    line enumerating every CPU feature flag, emitted at backend init. It
    dwarfed the actual run lines in BENCH_r05.json's driver-captured
    stderr tail (ISSUE 14), carrying zero signal for this workload —
    filter it out of everything this script forwards."""
    return (
        "host machine features" in line
        or "could lead to execution errors" in line
        or ("+sse" in line and "-amx" in line)
    )


def _filter_spam(text: str) -> str:
    """Drop feature-mismatch spam lines from a captured stderr blob."""
    return "".join(
        line for line in text.splitlines(keepends=True)
        if not _is_feature_spam(line)
    )


def _pump_filtered(src, dst) -> None:
    """Forward a child's stderr line by line, minus the feature spam —
    live progress for the operator, a readable tail for the driver."""
    try:
        for line in src:
            if not _is_feature_spam(line):
                dst.write(line)
                dst.flush()
    except ValueError:  # dst closed during interpreter teardown
        pass

def _last_json(text: str | bytes | None) -> dict | None:
    """Parse the LAST JSON object line out of a child's stdout."""
    if not text:
        return None
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _run_inner(deadline: float) -> dict | None:
    """Run the measurement child; return its parsed JSON record or None.

    The child's stderr is forwarded live; stdout is captured and the last
    JSON object line wins. The child prints its PRIMARY record as soon as
    the primary solves finish and an enriched record at the end, so a child
    killed at the deadline during the optional sym/ladder extras (the
    longest solves) costs the extras, not the primary measurement: both
    the nonzero-exit and the timeout path salvage the last JSON line.
    """
    # stderr is PIPED through a filter thread (live forwarding minus the
    # XLA host-feature spam — see _is_feature_spam) instead of inherited;
    # stdout is collected on a second thread so the deadline kill can
    # still salvage everything written before it.
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--inner"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    collected: list = []
    t_err = threading.Thread(
        target=_pump_filtered, args=(proc.stderr, sys.stderr), daemon=True
    )
    t_out = threading.Thread(
        target=lambda: collected.append(proc.stdout.read()), daemon=True
    )
    t_err.start()
    t_out.start()
    try:
        rc = proc.wait(timeout=deadline)
    except subprocess.TimeoutExpired:
        print(f"bench child: exceeded {deadline:.0f}s deadline, killed",
              file=sys.stderr)
        proc.kill()
        proc.wait()
        rc = -1
    t_out.join(timeout=30.0)
    t_err.join(timeout=30.0)
    out = collected[0] if collected else ""
    record = _last_json(out)
    if rc != 0:
        print(f"bench child: exited rc={rc}"
              + (" (salvaged partial record)" if record else ""),
              file=sys.stderr)
    if record is None and rc == 0:
        print("bench child: produced no JSON record", file=sys.stderr)
    return record


def _env_float(name: str, default: float) -> float:
    """Parse a float env knob; a malformed value must not kill the parent
    (the whole point of the parent is that a JSON line always appears)."""
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        print(f"{name} is not a number; using {default}", file=sys.stderr)
        return default


def _multiprocess_smoke() -> dict | None:
    """BENCH_PROCESSES=2: a real N-process sharded solve through
    tools/launch_multihost.py, folded into a MULTICHIP_r06.json-style
    artifact (BENCH_PROCESSES_OUT) with per-rank level times — the
    distributed path's perf trajectory before the big multi-host runs.

    Runs in the PARENT (the harness is subprocess-only, so this side
    never touches jax) and must never kill the bench: any failure is
    recorded in the artifact and the summary, not raised.
    """
    try:
        procs = int(os.environ.get("BENCH_PROCESSES", "0"))
    except ValueError:
        print("BENCH_PROCESSES is not a number; skipping", file=sys.stderr)
        return None
    if procs <= 1:
        return None
    import tempfile

    from tools.launch_multihost import DEFAULT_LOCAL_DEVICES, launch

    spec = os.environ.get("BENCH_MP_GAME", "connect4:w=4,h=4")
    out_path = os.environ.get("BENCH_PROCESSES_OUT", "MULTICHIP_mp.json")
    shards = procs * DEFAULT_LOCAL_DEVICES
    artifact = {
        "processes": procs, "shards": shards, "game": spec, "ok": False,
    }
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="bench_mp_") as td:
            jsonl = os.path.join(td, "m.jsonl")
            ranks = launch(
                [spec, "--devices", str(shards), "--no-tables",
                 "--jsonl", jsonl],
                processes=procs, timeout=_env_float(
                    "GAMESMAN_BENCH_DEADLINE", 3000.0),
                log_dir=td,
            )
            artifact["rc_by_rank"] = [r.returncode for r in ranks]
            artifact["secs_wall"] = round(time.perf_counter() - t0, 3)
            for r in ranks:
                if r.returncode != 0:
                    artifact["error"] = (
                        f"rank {r.rank} rc={r.returncode}: "
                        + r.stderr[-1500:]
                    )
                    return artifact
            # Per-rank level times from the rank-stamped JSONL streams
            # (the rank label is why they merge unambiguously).
            levels: dict = {}
            done: dict = {}
            for rank in range(procs):
                path = os.path.join(td, f"m.rank{rank}.jsonl")
                with open(path) as fh:
                    for line in fh:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        phase = rec.get("phase")
                        if phase in ("forward", "backward",
                                     "backward_edges") and "level" in rec:
                            row = levels.setdefault(
                                int(rec["level"]),
                                {"fwd_secs": {}, "bwd_secs": {}},
                            )
                            col = ("fwd_secs" if phase == "forward"
                                   else "bwd_secs")
                            row[col][str(rank)] = round(
                                row[col].get(str(rank), 0.0)
                                + float(rec.get("secs", 0.0)), 4)
                        elif phase == "done":
                            done[str(rank)] = {
                                "positions": rec.get("positions"),
                                "secs_total": round(
                                    rec.get("secs_total", 0.0), 3),
                            }
            artifact["levels"] = [
                {"level": k, **levels[k]} for k in sorted(levels)
            ]
            artifact["done_by_rank"] = done
            positions = max(
                (d.get("positions") or 0 for d in done.values()),
                default=0,
            )
            artifact["positions"] = positions
            artifact["positions_per_sec"] = round(
                positions / max(artifact["secs_wall"], 1e-9), 1)
            artifact["ok"] = True
    except Exception as e:  # noqa: BLE001 - the bench must survive this
        artifact["error"] = f"{type(e).__name__}: {e}"
    finally:
        artifact.setdefault("secs_wall",
                            round(time.perf_counter() - t0, 3))
        try:
            with open(out_path, "w") as fh:
                json.dump(artifact, fh, indent=1)
            print(f"multiprocess smoke: wrote {out_path} "
                  f"(ok={artifact['ok']})", file=sys.stderr)
        except OSError as e:
            print(f"multiprocess smoke: cannot write {out_path}: {e}",
                  file=sys.stderr)
    return artifact


def _launch_fleet(db: str, workers: int, env: dict | None = None,
                  extra_args: list | None = None):
    """Launch `cli serve --workers N` on ephemeral ports and wait until
    the fleet reports ready — the subprocess choreography _serve_bench
    and _db_compress_bench share (bounded banner read: a supervisor that
    wedges before its banner must fail the bench into the artifact, not
    hang it; every other wait is deadline-bounded too).

    -> {"proc", "port", "cport", "status"} on success (caller owns
    SIGTERM/kill teardown of proc), or {"error": ..., "proc": ...} —
    proc may be live on the error path and must still be torn down.
    """
    import json as _json
    import threading
    import urllib.request

    proc = subprocess.Popen(
        [sys.executable, "-m", "gamesmanmpi_tpu.cli", "serve", db,
         "--port", "0", "--workers", str(workers),
         "--control-port", "0", *(extra_args or [])],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, **env) if env else None,
    )
    try:
        got: list = []
        t = threading.Thread(
            target=lambda: got.append(proc.stdout.readline()), daemon=True
        )
        t.start()
        t.join(120.0)
        if not got or not got[0]:
            return {"error": "fleet supervisor printed no banner",
                    "proc": proc}
        banner = got[0]
        port = int(banner.split("http://127.0.0.1:")[1].split(" ")[0])
        cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
        ready_deadline = time.monotonic() + 180.0
        status = {}
        while time.monotonic() < ready_deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{cport}/healthz", timeout=10
                ) as resp:
                    status = _json.loads(resp.read())
            except OSError:
                status = {}  # control port not accepting yet — keep polling
            if status.get("status") == "ok":
                break
            time.sleep(0.25)
        if status.get("status") != "ok":
            return {"error": f"fleet never became ready: {status}",
                    "proc": proc}
        return {"proc": proc, "port": port, "cport": cport,
                "status": status}
    except BaseException:
        # Unanticipated failure (malformed banner, poll crash): the
        # caller never sees `proc`, so nothing downstream could tear the
        # fleet down — kill it HERE or it outlives the bench.
        proc.kill()
        proc.wait()
        raise


def _serve_bench() -> dict | None:
    """BENCH_SERVE=1: the serving-fleet SLO benchmark (ROADMAP item 3).

    Exports a DB (child process), launches the supervised fleet
    (`cli serve --workers N`), drives concurrent query load through
    tools/load_gen for BENCH_SERVE_SECS, SIGKILLs one worker mid-load
    (BENCH_SERVE_CHAOS=0 disables), and gates on the latency SLO:
    p99-under-load <= BENCH_SERVE_SLO_P99_MS with zero dropped requests
    beyond the killed worker's in-flight budget and zero answer
    mismatches. The full record lands in BENCH_SERVE_OUT
    (BENCH_serve.json) — the p99-under-load trajectory next to the
    solve-throughput BENCH_*.json one.

    Runs in the PARENT (jax-free: load_gen is stdlib-only and the DB
    positions are read with plain numpy) and must never kill the bench:
    failures are recorded in the artifact, not raised.
    """
    if os.environ.get("BENCH_SERVE", "0") in ("0", "", "off"):
        return None
    import signal
    import tempfile
    import threading
    import urllib.request

    from tools.load_gen import run_load

    spec = os.environ.get("BENCH_SERVE_GAME", "connect4:w=4,h=4")
    workers = int(_env_float("BENCH_SERVE_WORKERS", 2))
    duration = _env_float("BENCH_SERVE_SECS", 10.0)
    conc = int(_env_float("BENCH_SERVE_CONC", 8))
    slo_ms = _env_float("BENCH_SERVE_SLO_P99_MS", 250.0)
    chaos = os.environ.get("BENCH_SERVE_CHAOS", "1") not in ("0", "off")
    out_path = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    artifact = {
        "game": spec, "workers": workers, "concurrency": conc,
        "slo_p99_ms": slo_ms, "chaos": chaos, "ok": False,
    }

    def _get_json(url):
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    proc = None
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="bench_serve_") as td:
            db = os.path.join(td, "db")
            export = subprocess.run(
                [sys.executable, "-m", "gamesmanmpi_tpu.cli", "export-db",
                 spec, "--out", db],
                timeout=deadline, capture_output=True, text=True,
            )
            if export.returncode != 0:
                artifact["error"] = "export-db failed: " \
                    + export.stderr[-1000:]
                return artifact
            fleet = _launch_fleet(db, workers)
            proc = fleet.get("proc")
            if "error" in fleet:
                artifact["error"] = fleet["error"]
                return artifact
            port, cport = fleet["port"], fleet["cport"]
            control = f"http://127.0.0.1:{cport}"
            artifact["spawn_mode"] = fleet["status"].get("spawn_mode")
            positions = _db_sample_positions(db)
            killed = {}

            def _chaos():
                try:
                    time.sleep(max(0.5, min(duration / 2, duration - 1)))
                    st = _get_json(control + "/healthz")
                    for idx, w in st.get("workers", {}).items():
                        if w.get("state") == "ready" and w.get("pid"):
                            killed["worker"] = idx
                            killed["pid"] = w["pid"]
                            killed["at"] = time.monotonic()
                            os.kill(w["pid"], signal.SIGKILL)
                            return
                    killed["error"] = "no ready worker to kill"
                except Exception as e:  # noqa: BLE001 - recorded below
                    killed["error"] = f"{type(e).__name__}: {e}"

            if chaos:
                threading.Thread(target=_chaos, daemon=True).start()
            load = run_load(
                f"http://127.0.0.1:{port}", positions,
                duration=duration, concurrency=conc,
            )
            load.pop("answers", None)
            artifact.update(load)
            if chaos and "pid" not in killed:
                # The kill never fired: say WHY the chaos gate fails
                # instead of an unexplained ok=False.
                artifact["error"] = "chaos kill did not fire: " + \
                    killed.get("error", "kill thread never ran")
            if chaos and killed.get("pid"):
                recover_deadline = time.monotonic() + 60.0
                recovered = None
                while time.monotonic() < recover_deadline:
                    st = _get_json(control + "/healthz")
                    w = st["workers"].get(killed["worker"], {})
                    if w.get("state") == "ready" \
                            and w.get("pid") != killed["pid"]:
                        recovered = time.monotonic() - killed["at"]
                        break
                    time.sleep(0.2)
                st = _get_json(control + "/healthz")
                artifact["worker_restarts"] = sum(
                    w.get("restarts", 0)
                    for w in st.get("workers", {}).values()
                )
                artifact["killed_worker"] = killed["worker"]
                artifact["recovered_secs"] = (
                    None if recovered is None else round(recovered, 2)
                )
            artifact["slo_ok"] = artifact.get("p99_ms", 1e9) <= slo_ms
            # The shed budget: a SIGKILLed worker may drop its in-flight
            # requests (at most the client concurrency) — more dropped
            # than that means requests failed that chaos cannot excuse.
            artifact["drop_budget"] = conc if chaos else 0
            artifact["ok"] = bool(
                artifact["slo_ok"]
                and artifact.get("mismatches", 1) == 0
                and artifact.get("errors", 1) == 0
                and artifact.get("dropped", 0) <= artifact["drop_budget"]
                and (not chaos or artifact.get("recovered_secs") is not None)
            )
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
            proc = None
            ab = _serve_trace_ab(db, workers, conc, positions)
            if ab is not None:
                artifact["trace_ab"] = ab
    except Exception as e:  # noqa: BLE001 - the bench must survive this
        artifact["error"] = f"{type(e).__name__}: {e}"
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        artifact.setdefault("secs_wall", round(time.perf_counter() - t0, 3))
        try:
            with open(out_path, "w") as fh:
                json.dump(artifact, fh, indent=1)
            print(f"serve bench: wrote {out_path} (ok={artifact['ok']})",
                  file=sys.stderr)
        except OSError as e:
            print(f"serve bench: cannot write {out_path}: {e}",
                  file=sys.stderr)
    return artifact


def _serve_trace_ab(db: str, workers: int, conc: int,
                    positions) -> dict | None:
    """BENCH_SERVE_TRACE_AB=1 (default on under BENCH_SERVE): the
    tracing-overhead A/B arm (ISSUE 17).

    Two fresh chaos-free fleets over the already-exported DB — one with
    query tracing on (the default), one with GAMESMAN_TRACE=0 in the
    fleet's environment — each driven by the same load shape. The gate
    (checked by tools/bench_compare.py): tracing-on p99 must stay within
    BENCH_SERVE_TRACE_MAX_PCT (5%) of tracing-off, with
    BENCH_SERVE_TRACE_SLACK_MS (2 ms) of absolute slack so a
    sub-millisecond p99 doesn't fail the ratio on scheduler noise.
    Sampling is tail-based, so the on-arm cost is span bookkeeping on
    every request — exactly what this arm bounds.
    """
    if os.environ.get("BENCH_SERVE_TRACE_AB", "1") in ("0", "", "off"):
        return None
    import signal

    from tools.load_gen import run_load

    secs = _env_float("BENCH_SERVE_AB_SECS", 5.0)
    max_pct = _env_float("BENCH_SERVE_TRACE_MAX_PCT", 5.0)
    slack_ms = _env_float("BENCH_SERVE_TRACE_SLACK_MS", 2.0)
    ab: dict = {"max_delta_pct": max_pct, "slack_ms": slack_ms,
                "secs": secs, "ok": False}
    arms: dict = {}
    for arm, env in (("on", {"GAMESMAN_TRACE": "1"}),
                     ("off", {"GAMESMAN_TRACE": "0"})):
        fleet = _launch_fleet(db, workers, env=env)
        proc = fleet.get("proc")
        try:
            if "error" in fleet:
                ab["error"] = f"{arm} arm: {fleet['error']}"
                return ab
            load = run_load(
                f"http://127.0.0.1:{fleet['port']}", positions,
                duration=secs, concurrency=conc,
            )
            arms[arm] = {
                "p50_ms": load["p50_ms"], "p99_ms": load["p99_ms"],
                "qps": load["qps"], "requests": load["requests"],
                "errors": load["errors"], "dropped": load["dropped"],
            }
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
            proc = None
        finally:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
    ab.update(arms)
    on, off = arms["on"]["p99_ms"], arms["off"]["p99_ms"]
    ab["delta_pct"] = round((on - off) / max(off, 1e-9) * 100.0, 2)
    ab["ok"] = bool(on <= off * (1.0 + max_pct / 100.0) + slack_ms)
    return ab


def _serve_hot_bench() -> dict | None:
    """BENCH_SERVE_HOT=1: the serving hot-path A/B (ISSUE 18).

    One compressed DB with a sealed opening book, two fresh fork-mode
    fleets over it, the same deterministic zipf request stream on each:

    * **baseline** — ``GAMESMAN_SHM_CACHE_MB=0`` +
      ``GAMESMAN_SERVE_BOOK=0``: every query runs the full path
      (canonicalize, private-cache block decode);
    * **hot** — the defaults: book hits answered from resident arrays,
      block decodes published to the cross-worker shared-memory cache,
      batcher dedup collapsing the zipf head.

    Both arms squeeze the PRIVATE decoded-block cache
    (``GAMESMAN_DB_CACHE_MB``) so the DB does not fit one worker's
    RAM — the deployment the shared tier exists for. Gates: zero
    errors/dropped/mismatches on both arms, hot qps >= baseline AND
    hot p99 <= baseline, book hits > 0, shm hits > 0, and a
    conditional-GET pass on the hot arm revalidating (304) with zero
    errors. Artifact -> BENCH_SERVE_HOT_OUT (BENCH_serve_hot.json),
    gated by tools/bench_compare.py's check_serve_hot. Runs in the
    PARENT (subprocess + stdlib load_gen only; never touches jax).
    """
    if os.environ.get("BENCH_SERVE_HOT", "0") in ("0", "", "off"):
        return None
    import signal
    import tempfile
    import urllib.request

    from tools.load_gen import run_load

    # The board must produce a DB that does NOT fit the squeezed
    # private cache — a toy DB is resident everywhere, probes cost
    # nothing, and the hot tiers read as pure overhead.
    spec = os.environ.get("BENCH_SERVE_HOT_GAME", "connect4:w=5,h=4")
    workers = int(_env_float("BENCH_SERVE_HOT_WORKERS", 2))
    secs = _env_float("BENCH_SERVE_HOT_SECS", 8.0)
    conc = int(_env_float("BENCH_SERVE_HOT_CONC", 8))
    zipf_s = _env_float("BENCH_SERVE_HOT_ZIPF_S", 1.1)
    plies = int(_env_float("BENCH_SERVE_HOT_BOOK_PLIES", 4))
    cache_mb = os.environ.get("BENCH_SERVE_HOT_DB_CACHE_MB", "1")
    # Both arms get a bounded answer LRU: on a toy bench DB the default
    # 65536-entry cache would swallow the whole sampled position set,
    # hiding the probe path the hot tiers exist to accelerate (a real
    # DB's query space dwarfs any per-worker answer cache).
    lru = os.environ.get("BENCH_SERVE_HOT_CACHE_SIZE", "256")
    # Single-position requests: the interactive regime the hot path
    # targets. A request is only exempt from the batcher window when
    # EVERY position in it is book-answered, so multi-position chunks
    # would re-impose the window wait on the whole zipf head.
    chunk = int(_env_float("BENCH_SERVE_HOT_CHUNK", 1))
    # Tight coalescing window, both arms: at interactive chunk=1 depth a
    # wide window makes every request's latency mostly *waiting for
    # strangers*, drowning the probe costs the A/B exists to compare.
    window_ms = _env_float("BENCH_SERVE_HOT_WINDOW_MS", 0.5)
    out_path = os.environ.get("BENCH_SERVE_HOT_OUT", "BENCH_serve_hot.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    dist = f"zipf:{zipf_s:g}"
    hot: dict = {
        "bench": "serve_hot_ab", "game": spec, "workers": workers,
        "concurrency": conc, "dist": dist, "book_plies": plies,
        "db_cache_mb": cache_mb, "cache_size": lru, "chunk": chunk,
        "window_ms": window_ms, "secs": secs, "ok": False,
    }
    artifact = {
        "metric": "serve_hot_qps", "value": 0.0,
        "device": os.environ.get("GAMESMAN_PLATFORM", "cpu"),
        "serve_hot": hot,
    }
    counters_wanted = (
        "gamesman_book_hits_total", "gamesman_shm_hits_total",
        "gamesman_shm_misses_total", "gamesman_shm_stores_total",
        "gamesman_shm_evictions_total", "gamesman_batch_dup_hits_total",
    )

    def _scrape_counters(url: str) -> dict:
        """Max-over-scrapes of the hot-path counters: each /metrics GET
        lands on whichever worker accepts it (registries are
        per-process), so repeated one-shot connections sample the fleet
        and the max proves at least one worker crossed zero."""
        best = {n: 0.0 for n in counters_wanted}
        for _ in range(max(4, workers * 4)):
            req = urllib.request.Request(
                url + "/metrics", headers={"Connection": "close"}
            )
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    text = resp.read().decode()
            except OSError:
                continue
            cur = {n: 0.0 for n in counters_wanted}
            for line in text.splitlines():
                if line.startswith("#"):
                    continue
                for n in counters_wanted:
                    if line.startswith(n + "{") or line.startswith(n + " "):
                        try:
                            cur[n] += float(line.rsplit(" ", 1)[1])
                        except ValueError:
                            pass
            for n in counters_wanted:
                best[n] = max(best[n], cur[n])
        return best

    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="bench_serve_hot_") as td:
            db = os.path.join(td, "db")
            export = subprocess.run(
                [sys.executable, "-m", "gamesmanmpi_tpu.cli", "export-db",
                 spec, "--out", db, "--compress",
                 "--book-plies", str(plies)],
                timeout=deadline, capture_output=True, text=True,
            )
            if export.returncode != 0:
                hot["error"] = "export-db failed: " + export.stderr[-1000:]
                return artifact
            # A wide sample (all blocks, thousands of positions): the
            # zipf head must overflow the bounded answer LRU and the
            # tail must overflow the squeezed private block cache, or
            # neither arm ever probes after warmup and the A/B measures
            # nothing but fixed HTTP overhead.
            positions = _db_sample_positions(db, per_level=256, cap=4096)
            if not positions:
                hot["error"] = "no positions sampled from the DB"
                return artifact
            shared_env = {"GAMESMAN_DB_CACHE_MB": cache_mb}
            arm_envs = {
                "baseline": dict(shared_env, GAMESMAN_SHM_CACHE_MB="0",
                                 GAMESMAN_SERVE_BOOK="0"),
                "hot": shared_env,
            }
            for arm, env in arm_envs.items():
                fleet = _launch_fleet(
                    db, workers, env=env,
                    extra_args=["--cache-size", lru,
                                "--batch-window-ms", f"{window_ms:g}"],
                )
                proc = fleet.get("proc")
                try:
                    if "error" in fleet:
                        hot["error"] = f"{arm} arm: {fleet['error']}"
                        return artifact
                    hot.setdefault(
                        "spawn_mode", fleet["status"].get("spawn_mode")
                    )
                    url = f"http://127.0.0.1:{fleet['port']}"
                    load = run_load(
                        url, positions, duration=secs, concurrency=conc,
                        chunk_size=chunk, dist=dist, seed=18,
                    )
                    hot[arm] = {
                        k: load[k] for k in
                        ("qps", "p50_ms", "p95_ms", "p99_ms", "requests",
                         "ok", "shed", "errors", "dropped", "mismatches")
                    }
                    if arm == "hot":
                        # Same fleet, same zipf stream, conditional GETs:
                        # the edge-cacheable form must revalidate (304)
                        # without a single wrong or failed answer.
                        get = run_load(
                            url, positions, concurrency=conc,
                            duration=max(2.0, secs / 2), dist=dist,
                            mode="get", seed=18,
                        )
                        hot["get"] = {
                            k: get[k] for k in
                            ("qps", "p99_ms", "requests", "ok",
                             "not_modified", "errors", "dropped",
                             "mismatches")
                        }
                        hot["counters"] = _scrape_counters(url)
                    proc.send_signal(signal.SIGTERM)
                    proc.wait(timeout=60)
                    proc = None
                finally:
                    if proc is not None and proc.poll() is None:
                        proc.kill()
                        proc.wait()
            base, hotarm = hot["baseline"], hot["hot"]
            ctr, get = hot["counters"], hot["get"]
            hot["clean"] = all(
                a["errors"] == 0 and a["dropped"] == 0
                and a["mismatches"] == 0 for a in (base, hotarm)
            )
            hot["perf_ok"] = bool(
                hotarm["qps"] >= base["qps"]
                and hotarm["p99_ms"] <= base["p99_ms"]
            )
            hot["book_hits"] = ctr["gamesman_book_hits_total"]
            hot["shm_hits"] = ctr["gamesman_shm_hits_total"]
            hot["hits_ok"] = bool(
                hot["book_hits"] > 0 and hot["shm_hits"] > 0
            )
            hot["get_ok"] = bool(
                get["errors"] == 0 and get["dropped"] == 0
                and get["mismatches"] == 0 and get["not_modified"] > 0
            )
            hot["ok"] = bool(
                hot["clean"] and hot["perf_ok"] and hot["hits_ok"]
                and hot["get_ok"]
            )
            artifact["value"] = hotarm["qps"]
    except Exception as e:  # noqa: BLE001 - the bench must survive this
        hot["error"] = f"{type(e).__name__}: {e}"
    finally:
        hot.setdefault("secs_wall", round(time.perf_counter() - t0, 3))
        try:
            with open(out_path, "w") as fh:
                json.dump(artifact, fh, indent=1)
            print(f"serve hot bench: wrote {out_path} (ok={hot['ok']})",
                  file=sys.stderr)
        except OSError as e:
            print(f"serve hot bench: cannot write {out_path}: {e}",
                  file=sys.stderr)
    return artifact


def _registry_bench() -> dict | None:
    """BENCH_REGISTRY=1: the DB-distribution robustness proof (ISSUE 19).

    Solve-on-demand end to end, through a crash: a registry with an
    empty catalog takes a POST /solve for a missing DB, the job runner
    is SIGKILLed right after its claim record is durable
    (`jobs.claim:kill:1` -> exit 77), a second runner reclaims the dead
    claim and drives campaign -> export-db -> publish; a replica then
    pulls the published epoch (checksums verified before the atomic
    install), a fork-mode fleet serves it, a re-exported epoch B is
    published and synced in under the fleet's rolling reload, and the
    SAME query must answer identically from both epochs with the ETag
    flipping exactly once. Gates: runner kill rc 77, job state
    `running` after the kill and `done` after the resume, the epoch in
    the sealed catalog, a verified install, `reloads_done == 1`, and
    matching answers across the flip. Record lands in
    BENCH_REGISTRY_OUT (BENCH_registry.json).

    Runs in the PARENT (registry/pull/jobs are stdlib+numpy; the
    solves happen in child processes) and must never kill the bench:
    failures are recorded in the artifact, not raised.
    """
    if os.environ.get("BENCH_REGISTRY", "0") in ("0", "", "off"):
        return None
    import signal
    import tempfile
    import threading
    import urllib.request

    spec = os.environ.get("BENCH_REGISTRY_GAME", "subtract:total=10")
    name = os.environ.get("BENCH_REGISTRY_NAME", "sub")
    out_path = os.environ.get("BENCH_REGISTRY_OUT", "BENCH_registry.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    artifact = {"game": spec, "name": name, "ok": False}
    cli = [sys.executable, "-m", "gamesmanmpi_tpu.cli"]
    env = dict(os.environ, GAMESMAN_PLATFORM="cpu")
    env.pop("GAMESMAN_FAULTS", None)

    def _get_json(url):
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    proc = None
    srv = None
    t0 = time.perf_counter()
    try:
        from gamesmanmpi_tpu.registry import (
            JobQueue,
            RegistryServer,
            catalog_seal,
            load_catalog,
            publish_db,
            pull_db,
            sync_fleet,
        )
        from gamesmanmpi_tpu.registry.pull import ensure_db
        from gamesmanmpi_tpu.resilience.faults import KILL_EXIT_CODE

        with tempfile.TemporaryDirectory(prefix="bench_registry_") as td:
            root = os.path.join(td, "registry")
            queue = JobQueue(os.path.join(root, "jobs.jsonl"))
            srv = RegistryServer(root, queue=queue)
            srv.start()

            # 1. Solve-on-demand: the DB does not exist -> queued job.
            job = ensure_db(srv.url, name, spec)
            artifact["enqueued"] = {
                "status": job.get("status"), "job": job.get("id"),
            }

            # 2. Runner SIGKILLed right after its claim is durable.
            kill = subprocess.run(
                cli + ["registry", "run-jobs", "--root", root, "--once"],
                env=dict(env, GAMESMAN_FAULTS="jobs.claim:kill:1"),
                timeout=deadline, capture_output=True, text=True,
            )
            after_kill = list(
                JobQueue(os.path.join(root, "jobs.jsonl")).jobs().values()
            )
            artifact["runner_kill"] = {
                "rc": kill.returncode,
                "job_state": after_kill[0]["state"] if after_kill else None,
            }

            # 3. The next runner reclaims the dead claim and finishes.
            resume = subprocess.run(
                cli + ["registry", "run-jobs", "--root", root, "--once"],
                env=env, timeout=deadline, capture_output=True, text=True,
            )
            after = list(
                JobQueue(os.path.join(root, "jobs.jsonl")).jobs().values()
            )
            cat = load_catalog(root)
            artifact["runner_resume"] = {
                "rc": resume.returncode,
                "job_state": after[0]["state"] if after else None,
                "published": name in cat["dbs"],
                "catalog_sealed": cat["seal"] == catalog_seal(cat["dbs"]),
            }
            if resume.returncode != 0:
                artifact["error"] = "resume runner failed: " \
                    + resume.stderr[-1000:]
                return artifact

            # 4. Replica pull + fleet serve on the pulled epoch.
            dest = os.path.join(td, "dbs")
            pulled = pull_db(srv.url, name, dest)
            artifact["pull"] = {
                k: pulled[k] for k in
                ("epoch", "installed", "resumed_files", "refetched_files")
            }
            manifest = os.path.join(td, "fleet.json")
            with open(manifest, "w") as fh:
                json.dump({"version": 1, "games": [
                    {"name": name, "db": pulled["db"]}]}, fh)
            proc = subprocess.Popen(
                cli + ["serve", "--fleet-manifest", manifest, "--port",
                       "0", "--workers", "2", "--control-port", "0"],
                stdout=subprocess.PIPE, text=True, env=env,
            )
            got: list = []
            t = threading.Thread(
                target=lambda: got.append(proc.stdout.readline()),
                daemon=True,
            )
            t.start()
            t.join(120.0)
            if not got or not got[0]:
                artifact["error"] = "fleet printed no banner"
                return artifact
            banner = got[0]
            port = int(banner.split("http://127.0.0.1:")[1].split(" ")[0])
            cport = int(banner.split("http://127.0.0.1:")[2].split(" ")[0])
            base = f"http://127.0.0.1:{port}"
            control = f"http://127.0.0.1:{cport}"
            ready = time.monotonic() + 180.0
            while time.monotonic() < ready:
                try:
                    if _get_json(control + "/healthz")["status"] == "ok":
                        break
                except (OSError, ValueError, KeyError):
                    pass
                time.sleep(0.25)

            def _query():
                with urllib.request.urlopen(
                        f"{base}/query?p=0x2", timeout=10) as resp:
                    return resp.headers.get("ETag"), json.loads(resp.read())

            etag_a, answer_a = _query()
            artifact["serve"] = {"etag_a": etag_a, "answer_a": answer_a}

            # 5. Epoch B (same content, compressed) rolls in under sync.
            db_b = os.path.join(td, "db_b")
            export = subprocess.run(
                cli + ["export-db", spec, "--out", db_b, "--compress"],
                env=env, timeout=deadline, capture_output=True, text=True,
            )
            if export.returncode != 0:
                artifact["error"] = "epoch B export failed: " \
                    + export.stderr[-1000:]
                return artifact
            publish_db(root, name, db_b)
            sync = sync_fleet(srv.url, [name], manifest, dest,
                              control_url=control)
            artifact["sync"] = {
                "status": sync["status"], "rolled": sync["rolled"],
                "failed": sync["failed"],
            }
            flip = time.monotonic() + 60.0
            status = {}
            while time.monotonic() < flip:
                status = _get_json(control + "/healthz")
                if status.get("reloads_done") == 1 \
                        and status.get("status") == "ok":
                    break
                time.sleep(0.25)
            etag_b, answer_b = _query()
            artifact["serve"].update(etag_b=etag_b, answer_b=answer_b)
            artifact["reloads_done"] = status.get("reloads_done")
            artifact["registry_sync"] = status.get("registry_sync")
            artifact["ok"] = bool(
                artifact["runner_kill"]["rc"] == KILL_EXIT_CODE
                and artifact["runner_kill"]["job_state"] == "running"
                and artifact["runner_resume"]["job_state"] == "done"
                and artifact["runner_resume"]["published"]
                and artifact["runner_resume"]["catalog_sealed"]
                and pulled["installed"]
                and sync["status"] == "rolled"
                and status.get("reloads_done") == 1
                and etag_a and etag_b and etag_a != etag_b
                and answer_a == answer_b
            )
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
            proc = None
    except Exception as e:  # noqa: BLE001 - the bench must survive this
        artifact["error"] = f"{type(e).__name__}: {e}"
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        if srv is not None:
            srv.stop()
        artifact["secs_wall"] = round(time.perf_counter() - t0, 3)
        try:
            with open(out_path, "w") as fh:
                json.dump(artifact, fh, indent=1)
            print(f"registry bench: wrote {out_path} "
                  f"(ok={artifact['ok']})", file=sys.stderr)
        except OSError as e:
            print(f"registry bench: cannot write {out_path}: {e}",
                  file=sys.stderr)
    return artifact


def _store_bench() -> dict | None:
    """BENCH_STORE=1: the block-store I/O-overlap A/B (ISSUE 11).

    One spill-forcing sharded solve — device-store budget 0 so every
    discovered level and edge array leaves HBM, host tier squeezed to a
    few MB so edge arrays drop to the DISK tier (their sealed
    per-(level, shard) files become the only copy) — run twice from a
    cold checkpoint directory:

    * **sync** — `GAMESMAN_STORE_PREFETCH_THREADS=0`,
      `GAMESMAN_STORE_WRITEBEHIND=0`: every sealed read and every
      DEFLATE+fsync blocks the solve thread, exactly the pre-store
      code's behavior; `io_wait_secs` is the full I/O bill.
    * **prefetch** — the store's defaults: the backward schedule's
      readahead hints decode the next level's edge/checkpoint shards
      while the current level computes, and payload writes ride the
      write-behind worker.

    Gates: the prefetch arm's `io_wait_secs` strictly below the sync
    arm's, and the two `--table-out` tables byte-identical (the overlap
    must change WHEN bytes move, never WHICH bytes). Runs in the PARENT
    (subprocess-only, never touches jax); any failure is recorded, not
    raised. Full record → BENCH_STORE_OUT; summary joins the bench
    record under `store`.
    """
    if os.environ.get("BENCH_STORE", "0") in ("0", "", "off"):
        return None
    import tempfile

    import numpy as np

    spec = os.environ.get("BENCH_STORE_GAME", "connect4:w=4,h=4")
    shards = int(_env_float("BENCH_STORE_SHARDS", 2))
    out_path = os.environ.get("BENCH_STORE_OUT", "BENCH_store.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    record: dict = {
        "bench": "store_prefetch_ab",
        "spec": spec,
        "shards": shards,
        "config": {
            "GAMESMAN_DEVICE_STORE_MB": "0",
            "GAMESMAN_STORE_CACHE_MB": "4",
        },
    }

    def _arm(name: str, workdir: str, env: dict) -> dict:
        table = os.path.join(workdir, f"{name}.npz")
        metrics = os.path.join(workdir, f"{name}.jsonl")
        base = {
            "GAMESMAN_PLATFORM": "cpu",
            "GAMESMAN_FAKE_DEVICES": str(shards),
            # Spill-forcing: nothing stays in HBM between phases, and
            # the host tier is too small for the edge arrays — the
            # backward's edge loads come from sealed files on disk.
            "GAMESMAN_DEVICE_STORE_MB": "0",
            "GAMESMAN_STORE_CACHE_MB": "4",
        }
        base.update(env)
        child_env = dict(os.environ)
        child_env.pop("GAMESMAN_FAULTS", None)
        child_env.update(base)
        proc = subprocess.run(
            [sys.executable, "-m", "gamesmanmpi_tpu.cli", spec,
             "--devices", str(shards),
             "--checkpoint-dir", os.path.join(workdir, f"{name}_ck"),
             "--table-out", table, "--jsonl", metrics],
            capture_output=True, text=True, timeout=deadline,
            env=child_env, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        arm: dict = {"rc": proc.returncode, "table": table}
        if proc.returncode != 0:
            arm["error"] = proc.stderr[-1000:]
            return arm
        done = None
        try:
            with open(metrics) as fh:
                for line in fh:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if rec.get("phase") == "done":
                        done = rec
        except OSError as e:
            arm["error"] = f"metrics unreadable: {e}"
            return arm
        if done is None:
            arm["error"] = "no done record in metrics stream"
            return arm
        for key in ("io_wait_secs", "prefetch_hits", "prefetch_misses",
                    "prefetch_hit_rate", "writebehind_writes",
                    "writebehind_queue_depth", "edges_bytes_disk",
                    "edges_bytes_spilled", "positions", "secs_total",
                    "positions_per_sec"):
            if key in done:
                arm[key] = done[key]
        return arm

    try:
        with tempfile.TemporaryDirectory(prefix="bench_store_") as wd:
            record["sync"] = _arm("sync", wd, {
                "GAMESMAN_STORE_PREFETCH_THREADS": "0",
                "GAMESMAN_STORE_WRITEBEHIND": "0",
            })
            record["prefetch"] = _arm("prefetch", wd, {
                "GAMESMAN_STORE_PREFETCH_THREADS": "2",
                "GAMESMAN_STORE_WRITEBEHIND": "1",
            })
            sync, pref = record["sync"], record["prefetch"]
            if "error" not in sync and "error" not in pref:
                record["io_wait_ok"] = bool(
                    pref["io_wait_secs"] < sync["io_wait_secs"]
                )
                # Byte parity: --table-out is always PLAIN npz (the
                # user-facing format), so member-wise equality IS the
                # solved-table equality proof.
                parity = True
                with np.load(sync["table"]) as za, \
                        np.load(pref["table"]) as zb:
                    parity = sorted(za.files) == sorted(zb.files) and all(
                        np.array_equal(za[f], zb[f]) for f in za.files
                    )
                record["parity_ok"] = bool(parity)
                record["io_wait_ratio"] = round(
                    pref["io_wait_secs"]
                    / max(sync["io_wait_secs"], 1e-9), 4
                )
                record["ok"] = bool(
                    record["io_wait_ok"] and record["parity_ok"]
                )
            else:
                record["ok"] = False
                record["error"] = (
                    sync.get("error") or pref.get("error") or "arm failed"
                )
            # The table paths die with the tempdir — drop them from the
            # committed artifact.
            sync.pop("table", None)
            pref.pop("table", None)
    except Exception as e:  # noqa: BLE001 - must never kill the bench
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {e}"
    try:
        with open(out_path, "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
        print(f"store bench: wrote {out_path} "
              f"(ok={record.get('ok')})", file=sys.stderr)
    except OSError as e:
        print(f"store bench: cannot write {out_path}: {e}",
              file=sys.stderr)
    return record


def _gamedsl_bench() -> dict | None:
    """BENCH_GAMEDSL=1: hand-written vs compiled-spec connect4 A/B.

    The game compiler's performance contract (ISSUE 16) is that a
    compiled GameSpec solves within 10% of the hand-written module it
    replicates — the lowering emits the same masks and smear shifts, so
    any gap is compiler overhead. Two CLI children on the same config
    (CPU-pinned for comparability): the registry spec
    ``connect4:w=W,h=H`` and a generated GameSpec .json for the same
    board. Best positions/sec of BENCH_GAMEDSL_RUNS (default 2) per arm;
    gates: compiled/hand >= BENCH_GAMEDSL_MIN_RATIO (default 0.9) and
    byte-identical --table-out tables. Runs in the PARENT
    (subprocess-only, never touches jax); any failure is recorded, not
    raised. Full record → BENCH_GAMEDSL_OUT; summary joins the bench
    record under `gamedsl`. The artifact doubles as a
    tools/bench_compare.py record (metric
    ``gamedsl_compiled_connect4_pps_ratio``).
    """
    if os.environ.get("BENCH_GAMEDSL", "0") in ("0", "", "off"):
        return None
    import tempfile

    import numpy as np

    width = int(_env_float("BENCH_GAMEDSL_W", 5))
    height = int(_env_float("BENCH_GAMEDSL_H", 4))
    runs = max(1, int(_env_float("BENCH_GAMEDSL_RUNS", 2)))
    min_ratio = _env_float("BENCH_GAMEDSL_MIN_RATIO", 0.9)
    out_path = os.environ.get("BENCH_GAMEDSL_OUT", "BENCH_gamedsl.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    hand_spec = f"connect4:w={width},h={height}"
    spec_doc = {
        "gamedsl": 1,
        "name": f"connect4_{width}x{height}",
        "board": {"width": width, "height": height},
        "moves": {"family": "drop"},
        "win": {"kind": "k_in_line", "k": 4},
    }
    record: dict = {
        "bench": "gamedsl_compiled_ab",
        "metric": "gamedsl_compiled_connect4_pps_ratio",
        "unit": "ratio",
        "device": "cpu",
        "game": hand_spec,
        "spec_doc": spec_doc,
        "runs": runs,
        "min_ratio": min_ratio,
    }

    def _arm(name: str, game_arg: str, workdir: str) -> dict:
        table = os.path.join(workdir, f"{name}.npz")
        child_env = dict(os.environ)
        child_env.pop("GAMESMAN_FAULTS", None)
        child_env["GAMESMAN_PLATFORM"] = "cpu"
        arm: dict = {"game": game_arg}
        best = 0.0
        for i in range(runs):
            cmd = [sys.executable, "-m", "gamesmanmpi_tpu.cli", game_arg]
            if i == 0:
                cmd += ["--table-out", table]
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=deadline,
                env=child_env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            if proc.returncode != 0:
                arm["error"] = proc.stderr[-1000:]
                return arm
            pps = None
            for line in proc.stdout.splitlines():
                if line.startswith("throughput:"):
                    try:
                        pps = float(line.split()[1])
                    except (IndexError, ValueError):
                        pass
            if pps is None:
                arm["error"] = "no throughput line in solve output"
                return arm
            best = max(best, pps)
        arm["positions_per_sec"] = best
        arm["table"] = table
        return arm

    try:
        with tempfile.TemporaryDirectory(prefix="bench_gamedsl_") as wd:
            spec_path = os.path.join(wd, "spec.json")
            with open(spec_path, "w") as fh:
                json.dump(spec_doc, fh)
            hand = _arm("hand", hand_spec, wd)
            compiled = _arm("compiled", spec_path, wd)
            record["hand"] = hand
            record["compiled"] = compiled
            if "error" not in hand and "error" not in compiled:
                ratio = (compiled["positions_per_sec"]
                         / max(hand["positions_per_sec"], 1e-9))
                record["value"] = round(ratio, 4)
                record["hand_pps"] = hand["positions_per_sec"]
                record["compiled_pps"] = compiled["positions_per_sec"]
                # --table-out is plain npz: member-wise equality IS the
                # solved-table equality proof (same convention as the
                # store bench).
                with np.load(hand["table"]) as za, \
                        np.load(compiled["table"]) as zb:
                    parity = sorted(za.files) == sorted(zb.files) and all(
                        np.array_equal(za[f], zb[f]) for f in za.files
                    )
                record["parity_ok"] = bool(parity)
                record["ratio_ok"] = bool(ratio >= min_ratio)
                record["ok"] = bool(parity and record["ratio_ok"])
            else:
                record["ok"] = False
                record["error"] = (
                    hand.get("error") or compiled.get("error")
                    or "arm failed"
                )
            # The table paths die with the tempdir — drop them from the
            # committed artifact.
            hand.pop("table", None)
            compiled.pop("table", None)
    except Exception as e:  # noqa: BLE001 - must never kill the bench
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {e}"
    record.setdefault("value", 0.0)
    try:
        with open(out_path, "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
        print(f"gamedsl bench: wrote {out_path} "
              f"(ok={record.get('ok')})", file=sys.stderr)
    except OSError as e:
        print(f"gamedsl bench: cannot write {out_path}: {e}",
              file=sys.stderr)
    return record


def _campaign_bench() -> dict | None:
    """BENCH_CAMPAIGN=1: the self-healing campaign proof (ISSUE 12).

    One `tools/run_campaign.py` invocation drives a sharded solve —
    BENCH_CAMPAIGN_PROCESSES > 1 makes each attempt a real
    launch_multihost world — through THREE injected SIGKILLs at
    distinct points (forward, backward, mid-write-behind; rank 0 in a
    world, its peers exiting through the coordinated abort) to
    completion with zero operator input. Gates: campaign rc 0, the
    ledger records every attempt with the injected causes, and the
    final `--table-out` table is byte-identical to an uninterrupted
    solve of the same config. Full record (ledger included) →
    BENCH_CAMPAIGN_OUT; summary joins the bench record under
    `campaign`. Runs in the PARENT (subprocess-only); failures are
    recorded, never raised.
    """
    if os.environ.get("BENCH_CAMPAIGN", "0") in ("0", "", "off"):
        return None
    import tempfile

    import numpy as np

    spec = os.environ.get("BENCH_CAMPAIGN_GAME", "connect4:w=5,h=4")
    processes = int(_env_float("BENCH_CAMPAIGN_PROCESSES", 2))
    shards = int(_env_float("BENCH_CAMPAIGN_SHARDS", 4))
    out_path = os.environ.get("BENCH_CAMPAIGN_OUT", "BENCH_campaign.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    chaos = [
        "sharded.forward:kill:3",       # mid-forward
        "sharded.backward:kill:2",      # mid-backward
        "store.writebehind:kill:1",     # mid-write-behind payload
    ]
    record: dict = {
        "bench": "self_healing_campaign",
        "spec": spec,
        "processes": processes,
        "shards": shards,
        "chaos": chaos,
    }
    repo = os.path.dirname(os.path.abspath(__file__))

    def _resumed_table(workdir: str) -> str:
        # An N-process attempt rank-qualifies --table-out; the table is
        # the GLOBAL solved table either way, so rank0's file is
        # canonical. The golden solve is always single-process.
        if processes > 1:
            return os.path.join(workdir, "resumed.rank0.npz")
        return os.path.join(workdir, "resumed.npz")

    try:
        with tempfile.TemporaryDirectory(prefix="bench_campaign_") as wd:
            child_env = dict(os.environ)
            child_env.pop("GAMESMAN_FAULTS", None)
            child_env.update({
                "GAMESMAN_PLATFORM": "cpu",
                "GAMESMAN_CAMPAIGN_BACKOFF_BASE_SECS": "0.2",
                # A dead rank must resolve into a coordinated abort,
                # not a wedged world the attempt timeout reaps.
                "GAMESMAN_BARRIER_SECS": "30",
                "GAMESMAN_COLLECTIVE_TIMEOUT": "120",
            })
            t0 = time.time()
            golden_cmd = [
                sys.executable, "-m", "gamesmanmpi_tpu.cli", spec,
                "--devices", str(shards),
                "--table-out", os.path.join(wd, "golden.npz"),
            ]
            golden_env = dict(child_env)
            golden_env["GAMESMAN_FAKE_DEVICES"] = str(shards)
            golden = subprocess.run(
                golden_cmd, capture_output=True, text=True,
                timeout=deadline, env=golden_env, cwd=repo,
            )
            record["golden_secs"] = round(time.time() - t0, 3)
            if golden.returncode != 0:
                record["ok"] = False
                record["error"] = "golden: " + golden.stderr[-1000:]
                raise StopIteration
            ck = os.path.join(wd, "ck")
            cmd = [
                sys.executable, os.path.join(repo, "tools",
                                             "run_campaign.py"),
                spec, "--checkpoint-dir", ck,
                "--processes", str(processes),
            ]
            for c in chaos:
                cmd += ["--chaos", c]
            cmd += ["--", "--devices", str(shards),
                    "--table-out", os.path.join(wd, "resumed.npz")]
            t0 = time.time()
            camp = subprocess.run(
                cmd, capture_output=True, text=True, timeout=deadline,
                env=child_env, cwd=repo,
            )
            record["campaign_rc"] = camp.returncode
            record["campaign_secs"] = round(time.time() - t0, 3)
            ledger = []
            try:
                with open(os.path.join(ck, "campaign.jsonl")) as fh:
                    for line in fh:
                        try:
                            ledger.append(json.loads(line))
                        except json.JSONDecodeError:
                            continue
            except OSError:
                pass
            record["ledger"] = ledger
            attempts = [r for r in ledger
                        if r.get("phase") == "campaign_attempt"]
            record["attempts"] = len(attempts)
            record["causes"] = [a.get("cause") for a in attempts]
            record["resume_levels"] = [a.get("resume_level")
                                       for a in attempts]
            if camp.returncode != 0:
                record["ok"] = False
                record["error"] = camp.stderr[-2000:]
                raise StopIteration
            # The three injected deaths really happened, then it healed.
            record["chaos_ok"] = bool(
                len(attempts) == len(chaos) + 1
                and all(a.get("cause") == "killed"
                        for a in attempts[:len(chaos)])
                and attempts[-1].get("cause") == "complete"
            )
            parity = True
            with np.load(os.path.join(wd, "golden.npz")) as za, \
                    np.load(_resumed_table(wd)) as zb:
                parity = sorted(za.files) == sorted(zb.files) and all(
                    np.array_equal(za[f], zb[f]) for f in za.files
                )
            record["parity_ok"] = bool(parity)
            record["ok"] = bool(record["chaos_ok"] and parity)
    except StopIteration:
        pass
    except Exception as e:  # noqa: BLE001 - must never kill the bench
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {e}"
    try:
        with open(out_path, "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
        print(f"campaign bench: wrote {out_path} "
              f"(ok={record.get('ok')})", file=sys.stderr)
    except OSError as e:
        print(f"campaign bench: cannot write {out_path}: {e}",
              file=sys.stderr)
    return record


def _campaign_elastic_bench() -> dict | None:
    """BENCH_CAMPAIGN_ELASTIC=1: the elastic-resume proof (ISSUE 13).

    Two scenarios against one golden uninterrupted solve:

    * **reshard** — a sharded solve at BENCH_CAMPAIGN_ELASTIC_SEAL_SHARDS
      is SIGKILLed mid-backward, then a campaign resumes it at
      BENCH_CAMPAIGN_ELASTIC_SHARDS (a different shard count): the tree
      is adopted by reshard-on-resume (the ledger's first attempt shows
      sealed_shards != shards) and driven to completion;
    * **oom** — a campaign started at BENCH_CAMPAIGN_ELASTIC_OOM_SHARDS
      takes an injected `oom` death, auto-escalates geometry (shards
      doubled, store cache halved — the campaign_reshard ledger record)
      and completes at the escalated count.

    Gates: both campaigns rc 0 with zero operator input, every
    geometry change on the ledger, and BOTH `--table-out` tables
    byte-identical to the golden solve (shard-count invariance across
    resume). Runs in the PARENT (subprocess-only); failures land in
    the artifact, never raise. Full record → BENCH_CAMPAIGN_ELASTIC_OUT.
    """
    if os.environ.get("BENCH_CAMPAIGN_ELASTIC", "0") in ("0", "", "off"):
        return None
    import tempfile

    import numpy as np

    spec = os.environ.get("BENCH_CAMPAIGN_ELASTIC_GAME",
                          "connect4:w=5,h=4")
    shards = int(_env_float("BENCH_CAMPAIGN_ELASTIC_SHARDS", 4))
    seal_shards = int(_env_float("BENCH_CAMPAIGN_ELASTIC_SEAL_SHARDS", 8))
    oom_shards = int(_env_float("BENCH_CAMPAIGN_ELASTIC_OOM_SHARDS", 2))
    out_path = os.environ.get("BENCH_CAMPAIGN_ELASTIC_OUT",
                              "BENCH_campaign_elastic.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    record: dict = {
        "bench": "elastic_campaign",
        "spec": spec,
        "shards": shards,
        "seal_shards": seal_shards,
        "oom_shards": oom_shards,
    }
    repo = os.path.dirname(os.path.abspath(__file__))

    def _ledger_of(ck: str) -> list:
        out = []
        try:
            with open(os.path.join(ck, "campaign.jsonl")) as fh:
                for line in fh:
                    try:
                        out.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue
        except OSError:
            pass
        return out

    def _parity(a: str, b: str) -> bool:
        with np.load(a) as za, np.load(b) as zb:
            return sorted(za.files) == sorted(zb.files) and all(
                np.array_equal(za[f], zb[f]) for f in za.files
            )

    try:
        with tempfile.TemporaryDirectory(prefix="bench_elastic_") as wd:
            base_env = dict(os.environ)
            base_env.pop("GAMESMAN_FAULTS", None)
            base_env.pop("XLA_FLAGS", None)
            base_env.update({
                "GAMESMAN_PLATFORM": "cpu",
                "GAMESMAN_CAMPAIGN_BACKOFF_BASE_SECS": "0.2",
            })
            t0 = time.time()
            golden = os.path.join(wd, "golden.npz")
            golden_env = dict(base_env)
            golden_env["GAMESMAN_FAKE_DEVICES"] = str(shards)
            g = subprocess.run(
                [sys.executable, "-m", "gamesmanmpi_tpu.cli", spec,
                 "--devices", str(shards), "--table-out", golden],
                capture_output=True, text=True, timeout=deadline,
                env=golden_env, cwd=repo,
            )
            record["golden_secs"] = round(time.time() - t0, 3)
            if g.returncode != 0:
                record["ok"] = False
                record["error"] = "golden: " + g.stderr[-1000:]
                raise StopIteration

            # --- scenario 1: SIGKILL at S=seal, campaign resumes at S
            ck = os.path.join(wd, "ck_reshard")
            kill_env = dict(base_env)
            kill_env["GAMESMAN_FAKE_DEVICES"] = str(seal_shards)
            kill_env["GAMESMAN_FAULTS"] = "sharded.backward:kill:2"
            t0 = time.time()
            killed = subprocess.run(
                [sys.executable, "-m", "gamesmanmpi_tpu.cli", spec,
                 "--devices", str(seal_shards),
                 "--checkpoint-dir", ck],
                capture_output=True, text=True, timeout=deadline,
                env=kill_env, cwd=repo,
            )
            resumed = os.path.join(wd, "resumed.npz")
            camp_env = dict(base_env)
            camp_env["GAMESMAN_FAKE_DEVICES"] = str(shards)
            camp = subprocess.run(
                [sys.executable,
                 os.path.join(repo, "tools", "run_campaign.py"), spec,
                 "--checkpoint-dir", ck, "--",
                 "--devices", str(shards), "--table-out", resumed],
                capture_output=True, text=True, timeout=deadline,
                env=camp_env, cwd=repo,
            )
            ledger = _ledger_of(ck)
            attempts = [r for r in ledger
                        if r.get("phase") == "campaign_attempt"]
            record["reshard"] = {
                "kill_rc": killed.returncode,
                "campaign_rc": camp.returncode,
                "secs": round(time.time() - t0, 3),
                "attempts": len(attempts),
                "causes": [a.get("cause") for a in attempts],
                "sealed_shards": (attempts[0].get("sealed_shards")
                                  if attempts else None),
                "attempt_shards": (attempts[0].get("shards")
                                   if attempts else None),
                "parity_ok": (camp.returncode == 0
                              and _parity(golden, resumed)),
                "ledger": ledger,
            }
            record["reshard"]["ok"] = bool(
                killed.returncode != 0
                and camp.returncode == 0
                and record["reshard"]["sealed_shards"] == seal_shards
                and record["reshard"]["attempt_shards"] == shards
                and record["reshard"]["parity_ok"]
            )
            if camp.returncode != 0:
                record["reshard"]["error"] = camp.stderr[-2000:]

            # --- scenario 2: injected oom, campaign auto-escalates
            ck2 = os.path.join(wd, "ck_oom")
            resumed2 = os.path.join(wd, "resumed_oom.npz")
            oom_env = dict(base_env)
            oom_env["GAMESMAN_FAKE_DEVICES"] = str(oom_shards)
            t0 = time.time()
            camp2 = subprocess.run(
                [sys.executable,
                 os.path.join(repo, "tools", "run_campaign.py"), spec,
                 "--checkpoint-dir", ck2,
                 "--chaos", "sharded.backward:oom:2", "--",
                 "--devices", str(oom_shards),
                 "--table-out", resumed2],
                capture_output=True, text=True, timeout=deadline,
                env=oom_env, cwd=repo,
            )
            ledger2 = _ledger_of(ck2)
            attempts2 = [r for r in ledger2
                         if r.get("phase") == "campaign_attempt"]
            reshards2 = [r for r in ledger2
                         if r.get("phase") == "campaign_reshard"]
            record["oom"] = {
                "campaign_rc": camp2.returncode,
                "secs": round(time.time() - t0, 3),
                "attempts": len(attempts2),
                "causes": [a.get("cause") for a in attempts2],
                "escalations": [
                    {k: r.get(k) for k in
                     ("from_shards", "to_shards", "from_cache_mb",
                      "to_cache_mb")}
                    for r in reshards2
                ],
                "parity_ok": (camp2.returncode == 0
                              and _parity(golden, resumed2)),
                "ledger": ledger2,
            }
            record["oom"]["ok"] = bool(
                camp2.returncode == 0
                and record["oom"]["causes"][:1] == ["oom"]
                and record["oom"]["causes"][-1:] == ["complete"]
                and reshards2
                and reshards2[0].get("from_shards") == oom_shards
                and reshards2[0].get("to_shards") == oom_shards * 2
                and record["oom"]["parity_ok"]
            )
            if camp2.returncode != 0:
                record["oom"]["error"] = camp2.stderr[-2000:]
            record["ok"] = bool(
                record["reshard"]["ok"] and record["oom"]["ok"]
            )
    except StopIteration:
        pass
    except Exception as e:  # noqa: BLE001 - must never kill the bench
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {e}"
    try:
        with open(out_path, "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
        print(f"elastic campaign bench: wrote {out_path} "
              f"(ok={record.get('ok')})", file=sys.stderr)
    except OSError as e:
        print(f"elastic campaign bench: cannot write {out_path}: {e}",
              file=sys.stderr)
    return record


def _db_compress_bench() -> dict | None:
    """BENCH_DB_COMPRESS=1: the compressed-DB ratio + latency benchmark
    (ROADMAP item 2 / ISSUE 9).

    One solve (child process, checkpointed), exported twice — format v1
    and block-compressed v2 — then:

    * integrity + **full logical equality**: tools/check_db.py checks
      the v2 directory and proves it answers every position identically
      to the v1 export (--same-as: levels, keys, cells — not a sample);
    * **ratio gate**: whole-DB stored bytes v1/v2 from the real files,
      gated on BENCH_DB_MIN_RATIO (default 3x, the ROADMAP claim);
    * **probe latency under load**: each directory serves through a
      real `serve --workers N` fleet driven by tools/load_gen; the v2
      p99 is gated on BENCH_DB_SLO_P99_MS (default 250 ms — the
      BENCH_serve_r07.json SLO must survive decompress-on-probe).

    Runs in the PARENT (jax-free: exports/serving are subprocesses,
    sampling reads the v1 .npy keys with plain numpy) and must never
    kill the bench: failures land in the artifact, not as exceptions.
    The full record writes to BENCH_DB_COMPRESS_OUT
    (BENCH_db_compress.json); a summary joins the bench record.
    """
    if os.environ.get("BENCH_DB_COMPRESS", "0") in ("0", "", "off"):
        return None
    import signal
    import tempfile

    from tools.load_gen import run_load

    spec = os.environ.get("BENCH_DB_GAME", "connect4:w=5,h=4")
    workers = int(_env_float("BENCH_DB_WORKERS", 2))
    duration = _env_float("BENCH_DB_SECS", 8.0)
    conc = int(_env_float("BENCH_DB_CONC", 8))
    slo_ms = _env_float("BENCH_DB_SLO_P99_MS", 250.0)
    min_ratio = _env_float("BENCH_DB_MIN_RATIO", 3.0)
    out_path = os.environ.get("BENCH_DB_COMPRESS_OUT",
                              "BENCH_db_compress.json")
    deadline = _env_float("GAMESMAN_BENCH_DEADLINE", 3000.0)
    artifact = {
        "game": spec, "workers": workers, "concurrency": conc,
        "slo_p99_ms": slo_ms, "min_ratio": min_ratio, "ok": False,
    }

    def _serve_and_load(db: str, positions) -> dict:
        """Launch a fleet over one DB dir (_launch_fleet), drive
        load_gen, tear down. -> load record (qps/p50/p99/errors/
        mismatches/answers)."""
        fleet = _launch_fleet(db, workers)
        proc = fleet.get("proc")
        try:
            if "error" in fleet:
                return {"error": fleet["error"]}
            load = run_load(
                f"http://127.0.0.1:{fleet['port']}", positions,
                duration=duration, concurrency=conc,
            )
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
            return load
        finally:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()

    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="bench_dbc_") as td:
            ckpt = os.path.join(td, "ckpt")
            v1, v2 = os.path.join(td, "v1"), os.path.join(td, "v2")
            solve = subprocess.run(
                [sys.executable, "-m", "gamesmanmpi_tpu.cli", spec,
                 "--checkpoint-dir", ckpt],
                timeout=deadline, capture_output=True, text=True,
            )
            if solve.returncode != 0:
                artifact["error"] = "solve failed: " + solve.stderr[-1000:]
                return artifact
            # Scrub GAMESMAN_DB_COMPRESS for the exports: the A/B is
            # meaningless unless the v1 arm REALLY writes v1 (the env
            # knob would silently flip it; v2's explicit --compress
            # wins either way).
            export_env = dict(os.environ)
            export_env.pop("GAMESMAN_DB_COMPRESS", None)
            for out_dir, extra in ((v1, []), (v2, ["--compress"])):
                export = subprocess.run(
                    [sys.executable, "-m", "gamesmanmpi_tpu.cli",
                     "export-db", spec, "--out", out_dir,
                     "--from-checkpoint", ckpt, *extra],
                    timeout=deadline, capture_output=True, text=True,
                    env=export_env,
                )
                if export.returncode != 0:
                    artifact["error"] = (
                        f"export-db {extra} failed: " + export.stderr[-1000:]
                    )
                    return artifact
            # Integrity + full v1-equality + per-level stats, in the
            # jax-capable child (the checker itself is numpy-only but
            # lives inside the package).
            stats_json = os.path.join(td, "stats.json")
            chk = subprocess.run(
                [sys.executable, os.path.join("tools", "check_db.py"),
                 v2, "--quiet", "--same-as", v1,
                 "--stats-json", stats_json],
                timeout=deadline, capture_output=True, text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            # Distinct gates from one run: --same-as differences print
            # as "differs from" problem lines, integrity problems as
            # anything else — an operator triaging the artifact can see
            # WHICH gate failed without re-running. A checker that died
            # WITHOUT reporting (import error, usage error, traceback)
            # proves neither gate: both stay false, never vacuously
            # true.
            found = [l for l in chk.stderr.splitlines()
                     if l.startswith("PROBLEM: ")]
            reported = chk.returncode == 0 or bool(found)
            artifact["check_ok"] = reported and not any(
                "differs from" not in l for l in found
            )
            artifact["full_equal"] = reported and not any(
                "differs from" in l for l in found
            )
            if chk.returncode != 0:
                artifact["error"] = "check_db: " + chk.stderr[-1000:]
                return artifact
            with open(stats_json) as fh:
                stats = json.load(fh)
            v1_bytes = _dir_bytes(v1)
            v2_bytes = _dir_bytes(v2)
            artifact.update({
                "positions": stats["num_positions"],
                "levels": len(stats["levels"]),
                "v1_bytes": v1_bytes,
                "v2_bytes": v2_bytes,
                "ratio": v1_bytes / max(v2_bytes, 1),
                "manifest_ratio": stats["ratio"],
            })
            positions = _db_sample_positions(v1)
            for arm, db in (("v1", v1), ("v2", v2)):
                load = _serve_and_load(db, positions)
                load.pop("answers", None)
                artifact[arm] = {
                    k: load.get(k)
                    for k in ("qps", "ok", "p50_ms", "p95_ms", "p99_ms",
                              "errors", "mismatches", "shed", "dropped",
                              "error")
                    if k in load
                }
            artifact["ratio_ok"] = artifact["ratio"] >= min_ratio
            artifact["slo_ok"] = (
                artifact.get("v2", {}).get("p99_ms", 1e9) <= slo_ms
            )
            artifact["ok"] = bool(
                artifact["ratio_ok"] and artifact["slo_ok"]
                and artifact["full_equal"]
                and artifact.get("v1", {}).get("errors", 1) == 0
                and artifact.get("v2", {}).get("errors", 1) == 0
                and artifact.get("v1", {}).get("mismatches", 1) == 0
                and artifact.get("v2", {}).get("mismatches", 1) == 0
            )
    except Exception as e:  # noqa: BLE001 - the bench must survive this
        artifact["error"] = f"{type(e).__name__}: {e}"
    finally:
        artifact.setdefault("secs_wall", round(time.perf_counter() - t0, 3))
        try:
            with open(out_path, "w") as fh:
                json.dump(artifact, fh, indent=1)
            print(
                f"db-compress bench: wrote {out_path} "
                f"(ok={artifact['ok']}, "
                f"ratio={artifact.get('ratio', 0):.2f}x)",
                file=sys.stderr,
            )
        except OSError as e:
            print(f"db-compress bench: cannot write {out_path}: {e}",
                  file=sys.stderr)
    return artifact


def _dir_bytes(directory: str) -> int:
    """Total file bytes under one directory (non-recursive: DB dirs are
    flat)."""
    return sum(
        e.stat().st_size for e in os.scandir(directory) if e.is_file()
    )


def _db_sample_positions(db: str, per_level: int = 64,
                         cap: int = 512) -> list:
    """Sample query positions straight off the DB's key files (plain
    numpy mmap reads — no DbReader, no jax: this runs in the parent)."""
    import glob

    import numpy as np

    positions: list = []
    for path in sorted(glob.glob(os.path.join(db, "level_*.keys.npy"))):
        keys = np.load(path, mmap_mode="r")
        n = int(keys.shape[0])
        step = max(1, n // per_level)
        positions.extend(int(k) for k in keys[::step][:per_level])
    if not positions:
        # Format v2 (block-compressed) directory: no .npy key files to
        # mmap. Decode the key frames in a child (the real codec path
        # lives behind the package __init__, which imports jax — and
        # this parent never touches jax).
        positions = _db_sample_positions_v2(db, per_level)
    if not positions:
        # Last resort: the manifest's per-block first_keys are real
        # positions and already resident. A coarse sample (one position
        # per block) — fine for smoke, too small for cache-pressure
        # benches.
        try:
            with open(os.path.join(db, "manifest.json")) as fh:
                manifest = json.load(fh)
            for key in sorted(manifest.get("levels", {}), key=int):
                positions.extend(
                    int(k) for k in
                    manifest["levels"][key].get("first_keys", [])
                    [:per_level]
                )
        except (OSError, ValueError):
            pass  # caller's load run will surface the empty sample
    if len(positions) > cap:
        step = len(positions) // cap
        positions = positions[::step][:cap]
    return positions


#: Runs in a short-lived child: decode every level's key frames with the
#: real block codec and print a level-ordered stride sample. argv:
#: db_dir per_level.
_SAMPLE_V2_CHILD = """
import os, sys
import numpy as np
from gamesmanmpi_tpu.db.format import read_manifest
from gamesmanmpi_tpu.compress.blocks import decode_block
db, per_level = sys.argv[1], int(sys.argv[2])
m = read_manifest(db)
out = []
for lvl in sorted(m.get("levels", {}), key=int):
    rec = m["levels"][lvl]
    idx = rec.get("keys_blocks")
    if not idx:
        continue
    with open(os.path.join(db, rec["keys"]), "rb") as fh:
        stream = fh.read()
    offs = np.concatenate(([0], np.cumsum(idx["lengths"], dtype=np.int64)))
    nblocks = len(idx["lengths"])
    per_block = max(1, per_level // nblocks)
    for b in range(nblocks):
        arr = decode_block(idx, b, stream[offs[b]:offs[b + 1]])
        step = max(1, arr.shape[0] // per_block)
        out.extend(int(k) for k in arr[::step][:per_block])
print(" ".join(map(str, out)))
"""


def _db_sample_positions_v2(db: str, per_level: int) -> list:
    """Sample real keys from a v2 (block-compressed) DB, spread across
    every block of every level — so a zipf stream over the sample
    actually exercises block residency, not just each block's first key.
    Returns [] on any failure (caller falls back to first_keys)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _SAMPLE_V2_CHILD, db, str(per_level)],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        if proc.returncode != 0:
            return []
        return [int(tok) for tok in proc.stdout.split()]
    except (OSError, subprocess.SubprocessError, ValueError):
        return []


def main() -> int:
    # The parent never touches jax: the one child that does holds the chip
    # (or the CPU, under an explicit GAMESMAN_PLATFORM=cpu).
    record = _run_inner(_env_float("GAMESMAN_BENCH_DEADLINE", 3000.0))
    if record is None:
        print("bench failed: the measurement child produced no record",
              file=sys.stderr)
        return 1
    mp = _multiprocess_smoke()
    if mp is not None:
        # Summary only — the per-rank level times live in the artifact
        # file (BENCH_PROCESSES_OUT); the one-line record stays one line.
        record["multiprocess"] = {
            k: mp.get(k) for k in
            ("processes", "shards", "ok", "positions",
             "positions_per_sec", "secs_wall", "error")
            if k in mp
        }
    dbc = _db_compress_bench()
    if dbc is not None:
        # Summary only — per-level ratios and both load arms live in the
        # artifact file (BENCH_DB_COMPRESS_OUT).
        record["db_compress"] = {
            k: dbc.get(k) for k in
            ("ratio", "ok", "ratio_ok", "slo_ok", "full_equal",
             "positions", "v1_bytes", "v2_bytes", "error")
            if k in dbc
        }
        for arm in ("v1", "v2"):
            if arm in dbc:
                record["db_compress"][f"{arm}_p99_ms"] = \
                    dbc[arm].get("p99_ms")
    sb = _store_bench()
    if sb is not None:
        # Summary only — the per-arm stats live in the artifact file
        # (BENCH_STORE_OUT); the one-line record stays one line.
        record["store"] = {
            k: sb.get(k) for k in
            ("ok", "io_wait_ok", "parity_ok", "io_wait_ratio", "error")
            if k in sb
        }
        for arm in ("sync", "prefetch"):
            if arm in sb and "io_wait_secs" in sb[arm]:
                record["store"][f"{arm}_io_wait_secs"] = \
                    sb[arm]["io_wait_secs"]
    gd = _gamedsl_bench()
    if gd is not None:
        # Summary only — per-arm run details live in the artifact file
        # (BENCH_GAMEDSL_OUT); the one-line record stays one line.
        record["gamedsl"] = {
            k: gd.get(k) for k in
            ("ok", "ratio_ok", "parity_ok", "value", "hand_pps",
             "compiled_pps", "error")
            if k in gd
        }
    cb = _campaign_bench()
    if cb is not None:
        # Summary only — the full ledger lives in the artifact file
        # (BENCH_CAMPAIGN_OUT); the one-line record stays one line.
        record["campaign"] = {
            k: cb.get(k) for k in
            ("ok", "chaos_ok", "parity_ok", "attempts", "causes",
             "campaign_rc", "campaign_secs", "error")
            if k in cb
        }
    eb = _campaign_elastic_bench()
    if eb is not None:
        # Summary only — the ledgers live in the artifact file
        # (BENCH_CAMPAIGN_ELASTIC_OUT); the one-line record stays one
        # line.
        record["campaign_elastic"] = {"ok": eb.get("ok")}
        for scenario in ("reshard", "oom"):
            if scenario in eb:
                record["campaign_elastic"][scenario] = {
                    k: v for k, v in eb[scenario].items()
                    if k != "ledger"
                }
        if "error" in eb:
            record["campaign_elastic"]["error"] = eb["error"]
    sv = _serve_bench()
    if sv is not None:
        # Summary only — the full load/chaos record lives in the
        # artifact file (BENCH_SERVE_OUT); the one-line record stays
        # one line.
        record["serve"] = {
            k: sv.get(k) for k in
            ("workers", "concurrency", "ok", "slo_ok", "qps",
             "p50_ms", "p99_ms", "shed", "dropped", "mismatches",
             "worker_restarts", "recovered_secs", "error")
            if k in sv
        }
        if "trace_ab" in sv:
            record["serve"]["trace_ab"] = {
                k: sv["trace_ab"].get(k)
                for k in ("ok", "delta_pct", "max_delta_pct", "error")
                if k in sv["trace_ab"]
            }
    sh = _serve_hot_bench()
    if sh is not None:
        # Summary only — arm details live in the artifact file
        # (BENCH_SERVE_HOT_OUT); the one-line record stays one line.
        shs = sh.get("serve_hot") or {}
        record["serve_hot"] = {
            k: shs.get(k) for k in
            ("ok", "clean", "perf_ok", "hits_ok", "get_ok",
             "book_hits", "shm_hits", "error")
            if k in shs
        }
        for arm in ("baseline", "hot"):
            if arm in shs:
                record["serve_hot"][arm] = {
                    k: shs[arm].get(k) for k in ("qps", "p99_ms")
                }
    rb = _registry_bench()
    if rb is not None:
        # Summary only — the full crash/resume/flip record lives in the
        # artifact file (BENCH_REGISTRY_OUT).
        record["registry"] = {
            "ok": rb.get("ok"),
            "runner_kill_rc": (rb.get("runner_kill") or {}).get("rc"),
            "resume_state": (rb.get("runner_resume") or {})
            .get("job_state"),
            "reloads_done": rb.get("reloads_done"),
        }
        if "error" in rb:
            record["registry"]["error"] = rb["error"]
    print(json.dumps(record))
    return 0


def inner() -> int:
    """The actual measurement: runs entirely in one child process."""
    from gamesmanmpi_tpu.utils.platform import apply_platform_env

    if (os.environ.get("BENCH_ENGINE") == "sharded"
            and not os.environ.get("GAMESMAN_FAKE_DEVICES")):
        # The sharded config needs a mesh; a CPU-pinned run fakes
        # BENCH_SHARDS host devices (a real accelerator mesh is used
        # as-is — make_solver clamps to the devices present). Parse and
        # clamp HERE too: exporting a malformed BENCH_SHARDS raw would
        # crash apply_platform_env's int() before any record prints,
        # while make_solver deliberately tolerates it with the same
        # try/except -> 8.
        try:
            shards = int(os.environ.get("BENCH_SHARDS", "8"))
        except ValueError:
            shards = 8
        os.environ["GAMESMAN_FAKE_DEVICES"] = str(max(1, shards))
    apply_platform_env()

    import gamesmanmpi_tpu  # noqa: F401  (enables x64 before first trace)
    import jax

    from gamesmanmpi_tpu.utils.platform import use_compile_cache

    use_compile_cache()

    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.games.connect4 import Connect4
    from gamesmanmpi_tpu.solve import Solver

    dev = jax.devices()[0]
    print(f"bench device: {dev.platform} ({dev})", file=sys.stderr)
    if dev.platform == "cpu" and os.environ.get("GAMESMAN_PLATFORM") != "cpu":
        # No silent CPU numbers: a CPU run is asked for by name.
        raise SystemExit("bench: no accelerator found (set "
                         "GAMESMAN_PLATFORM=cpu for a CPU run)")

    # Engine selection: the dense class-partitioned engine (solve/dense.py)
    # is the fast path for non-symmetric Connect-4 boards on the
    # accelerator; on a CPU run its VPU-shaped rank loops lose to
    # the classic engine, so auto resolves by platform. BENCH_ENGINE=
    # classic|dense pins one for comparison runs.
    bench_engine = os.environ.get("BENCH_ENGINE", "auto")
    if bench_engine == "auto":
        bench_engine = "classic" if dev.platform == "cpu" else "dense"

    def make_solver(game):
        if bench_engine == "sharded":
            # The owner-routed sharded engine over BENCH_SHARDS devices
            # (fake host devices on CPU — see the GAMESMAN_FAKE_DEVICES
            # defaulting at the top of inner()). This is the config the
            # edge-cached backward A/B runs against: GAMESMAN_BACKWARD=
            # edges|lookup selects the backward, and the record's
            # secs_backward + efficiency.bytes_sorted carry the delta.
            from gamesmanmpi_tpu.parallel import ShardedSolver

            try:
                shards = int(os.environ.get("BENCH_SHARDS", "8"))
            except ValueError:
                shards = 8
            have = len(jax.devices())
            if have < max(1, shards):
                # Unpinned CPU boxes land here (GAMESMAN_FAKE_DEVICES is
                # honored only under a GAMESMAN_PLATFORM pin): the solve
                # still runs, but an "8-shard" A/B on 1 shard would be a
                # silent lie — say so, and the record's `shards` field
                # (from the solver's stats) carries the truth.
                print(
                    f"sharded bench: only {have} device(s) available, "
                    f"requested {shards} shards — running {have}-shard "
                    "(pin GAMESMAN_PLATFORM=cpu to fake a mesh)",
                    file=sys.stderr,
                )
            shards = max(1, min(shards, have))
            return ShardedSolver(game, num_shards=shards,
                                 store_tables=False)
        # HybridSolver accepts sym=1 since r5 (its BFS region keeps the
        # mirror reduction; the dense region runs a sym-free twin), so the
        # secondary sym run benches the SAME engine as the primary. An
        # engine that fails to build raises: no silent demotion.
        if bench_engine == "hybrid" and isinstance(game, Connect4):
            from gamesmanmpi_tpu.solve.hybrid import HybridSolver

            return HybridSolver(game, store_tables=False)
        if bench_engine == "dense" and isinstance(game, Connect4) \
                and not game.sym:
            # The reachable count is a per-board constant, not part of the
            # solve; sweep it NOW (make_solver runs before the timer) so
            # run 0's measurement isn't deflated by it.
            from gamesmanmpi_tpu.solve.dense import DenseSolver

            solver = DenseSolver(game, store_tables=False)
            solver.reachable_counts()
            return solver
        # store_tables=False: the metric measures SOLVING, not the
        # ~600 MB result download to the host; the root's (value,
        # remoteness) is still checked every run.
        return Solver(game, store_tables=False)

    # Default board: the largest that solves in benchmark-friendly time on
    # the platform that actually runs (BASELINE.md configs #3-#4 ladder).
    default_spec = (
        "connect4:w=5,h=4" if dev.platform == "cpu" else "connect4:w=5,h=5"
    )
    spec = os.environ.get("BENCH_GAME", default_spec)
    # >=3 on-chip: r04's 6x4 record was best-of-2 with an unexplained 5x
    # spread between its two runs (VERDICT r4 weak #1) — three repeats
    # plus a published median makes a one-off outlier visible in the
    # record itself. CPU keeps 2 (each run is minutes, and a CPU number
    # is a diagnostic, not the tracked metric).
    smoke = os.environ.get("BENCH_SMOKE", "0") not in ("0", "", "off")
    repeats = int(os.environ.get(
        "BENCH_REPEATS",
        "1" if smoke else ("2" if dev.platform == "cpu" else "3")))
    # ISSUE 14: first-run compile time polluted r05's variance block
    # (all_pps [296k, 792k] — the median halved by a compile artifact).
    # An explicit warmup solve runs BEFORE the timed repeats and is
    # excluded from value/median; its raw rate stays in the artifact
    # (runs.warmup_pps) so nothing is hidden.
    warmup = int(os.environ.get("BENCH_WARMUP", "1"))

    def _core_record(name: str, best_pps: float, stats: dict,
                     pps_list: list, warmup_list: list = None) -> dict:
        """The FULL driver-format record, shared by the provisional
        records (printed after every primary run) and the final enriched
        one — one construction site so they can never silently diverge,
        and so a salvaged provisional carries every key a consumer may
        index unconditionally (the same schema invariant the zeroed
        bench-failed record upholds). The final path overwrites
        `efficiency` with the roofline-aware version."""
        traffic = (stats.get("bytes_sorted", 0)
                   + stats.get("bytes_gathered", 0))
        rec = {
            "metric": f"{name}_positions_solved_per_sec_per_chip",
            "value": round(best_pps, 1),
            "unit": "positions/sec/chip",
            "vs_baseline": round(best_pps / NORTH_STAR_PPS, 6),
            "device": dev.platform,
            "engine": stats.get("engine", "classic"),
            "secs_forward": round(stats.get("secs_forward", 0.0), 3),
            "secs_backward": round(stats.get("secs_backward", 0.0), 3),
            "positions": stats["positions"],
            # value is best-of-N (the warm rate); runs makes the spread
            # auditable — a median far below best flags a 6x4-style
            # outlier (VERDICT r4 weak #1) instead of hiding it. Warmup
            # runs are excluded from n/median/all_pps (compile time is
            # not throughput) but their raw rates are preserved.
            "runs": {
                "n": len(pps_list),
                "median_pps": round(statistics.median(pps_list), 1)
                if pps_list else 0.0,
                # First 16 only: repeats is normally 2-3; a stress run
                # with hundreds must not balloon the driver's one-line
                # record (n and median_pps stay exact over every run).
                "all_pps": [round(p, 1) for p in pps_list[:16]],
                "warmup_pps": [
                    round(p, 1) for p in (warmup_list or [])[:16]
                ],
            },
            "efficiency": {
                "bytes_sorted": stats.get("bytes_sorted", 0),
                "bytes_gathered": stats.get("bytes_gathered", 0),
                "operand_gbps": round(
                    traffic / max(stats.get("secs_total", 0.0), 1e-9)
                    / 1e9, 3),
            },
            # ISSUE 11: seconds the solve thread spent blocked on block-
            # store I/O (spill/checkpoint/edge loads + seal drains) —
            # 0.0 for in-memory solves; future BENCH_*.json track I/O
            # overlap alongside throughput.
            "io_wait_secs": round(stats.get("io_wait_secs", 0.0), 3),
            # Dispatch economy: total/per-level device dispatches the
            # engine issued.
            "dispatches": {
                "total": stats.get("dispatches_total", 0),
                "per_level": stats.get("dispatches_per_level", 0.0),
            },
            # Roofline fields: analytic HBM operand throughput
            # and the headline per-chip rate — what bench_compare diffs
            # across the committed BENCH_* trajectory.
            "roofline": {
                "operand_gbps": round(
                    traffic / max(stats.get("secs_total", 0.0), 1e-9)
                    / 1e9, 3),
                # Per CHIP, same rule as the engines' roofline_stats:
                # shards count as chips only on a real accelerator mesh
                # (a faked CPU mesh is one physical chip) — the record
                # and the solve stats must agree on this field's
                # denominator or an 8-shard TPU record inflates 8x.
                "pps_per_chip": round(
                    best_pps / (stats.get("shards", 1)
                                if dev.platform != "cpu" else 1), 1),
            },
        }
        if "shards" in stats:
            # Sharded engine only: the shard count that ACTUALLY ran (a
            # device-starved box clamps below BENCH_SHARDS — see
            # make_solver's warning; the record must not imply otherwise).
            rec["shards"] = stats["shards"]
        if "backward" in stats:
            rec["backward"] = stats["backward"]
        return rec

    def run_solves(game_spec: str, nruns: int, provisional: bool = False,
                   nwarmup: int = 0):
        """Best-of-N solve of one board; returns (best pps, best stats,
        [per-run pps], [warmup pps]) — best is the headline (warm-rate),
        the per-run list feeds the published median so variance is
        auditable, and nwarmup compile-eating runs are excluded from both
        but reported raw (runs.warmup_pps).

        provisional=True (the PRIMARY spec only) prints a driver-format
        record line after EVERY completed run: the parent keeps the last
        JSON line it sees, so a deadline kill between repeats
        salvages best-of-the-completed-runs instead of discarding them.

        A failing engine raises: the record names the engine that ran.
        """
        game = get_game(game_spec)
        best_pps, best_stats = 0.0, None
        all_pps = []
        warm_pps = []
        nwarmup = max(nwarmup, 0)
        for i in range(-nwarmup, max(nruns, 1)):
            is_warm = i < 0
            solver = make_solver(game)
            t0 = time.perf_counter()
            result = solver.solve()
            dt = time.perf_counter() - t0
            pps = result.num_positions / dt
            print(
                f"run {'w' if is_warm else ''}{i} [{game.name}]: "
                f"{result.num_positions} positions "
                f"in {dt:.3f}s = {pps:,.0f} pos/s "
                f"(fwd {result.stats['secs_forward']:.1f}s / "
                f"bwd {result.stats['secs_backward']:.1f}s, "
                f"value={result.value}, remoteness={result.remoteness})",
                file=sys.stderr,
            )
            if is_warm:
                warm_pps.append(pps)
                continue
            all_pps.append(pps)
            if pps > best_pps:
                best_pps, best_stats = pps, dict(result.stats)
            if provisional:
                prov = _core_record(game.name, best_pps, best_stats,
                                    all_pps, warm_pps)
                prov["provisional"] = True
                print(json.dumps(prov), flush=True)
        return best_pps, best_stats, all_pps, warm_pps

    best, stats, runs_pps, warm_pps = run_solves(
        spec, repeats, provisional=True, nwarmup=warmup
    )

    # Roofline framing (SURVEY.md §5.5): analytic operand bytes of the
    # sort/gather kernels vs the chip's memory bandwidth. v5e HBM is
    # 819 GB/s; XLA's sort makes ~log2(n) passes, so true traffic is a
    # multiple of operand bytes — this fraction is a LOWER bound on
    # utilization (docs/ARCHITECTURE.md "Efficiency accounting"). The
    # denominator must describe the platform that actually RAN: a CPU
    # record against a TPU roofline is a misleading artifact (VERDICT r3
    # weak #4), so CPU runs omit the roofline fields entirely unless
    # GAMESMAN_HBM_GBPS supplies a measured host figure.
    traffic = stats.get("bytes_sorted", 0) + stats.get("bytes_gathered", 0)
    operand_gbps = traffic / max(stats["secs_total"], 1e-9) / 1e9
    efficiency = {
        "bytes_sorted": stats.get("bytes_sorted", 0),
        "bytes_gathered": stats.get("bytes_gathered", 0),
        "operand_gbps": round(operand_gbps, 3),
    }
    roofline = None
    roofline_env = os.environ.get("GAMESMAN_HBM_GBPS")
    if roofline_env is not None:
        try:
            roofline = float(roofline_env)
        except ValueError:
            # A malformed override must not resurrect the TPU default on a
            # CPU record — warn and fall through to the platform rule.
            print(f"GAMESMAN_HBM_GBPS={roofline_env!r} is not a number; "
                  "ignoring", file=sys.stderr)
    if roofline is None and dev.platform != "cpu":
        if dev.device_kind not in HBM_GBPS:
            raise SystemExit(f"bench: no HBM peak known for device kind "
                             f"{dev.device_kind!r} (add it to HBM_GBPS)")
        roofline = HBM_GBPS[dev.device_kind]
    if roofline is not None:
        roofline = max(roofline, 1e-9)
        efficiency["hbm_roofline_gbps"] = roofline
        efficiency["roofline_frac"] = round(operand_gbps / roofline, 6)

    record = _core_record(get_game(spec).name, best, stats, runs_pps,
                          warm_pps)
    record["efficiency"] = efficiency  # roofline-aware upgrade
    # Publish the primary measurement NOW: if the deadline kills the child
    # during the optional sym/ladder solves below, the parent salvages this
    # line instead of discarding a completed accelerator run (the enriched
    # record printed at the end wins when everything succeeds).
    print(json.dumps(record), flush=True)

    # Secondary: the mirror-symmetry variant (halves the 6x6+ table; the
    # capacity plan depends on its throughput cost — VERDICT.md r2 item 7).
    sym = None
    if (os.environ.get("BENCH_SYM", "0" if smoke else "1")
            not in ("0", "off") and "sym" not in spec):
        try:
            sep = "," if ":" in spec else ":"
            # 2 runs: the sym kernels are a separate compile family, so the
            # first run is compile-dominated; best-of reports the warm rate.
            sym_pps, sym_stats, sym_runs, _ = run_solves(
                spec + sep + "sym=1", 2)
            sym = {
                "positions_per_sec": round(sym_pps, 1),
                "median_pps": round(statistics.median(sym_runs), 1),
                "positions": sym_stats["positions"],
                # The engine that ACTUALLY ran the sym solve (ADVICE r5):
                # engine-eligibility differs by sym (dense needs sym=0).
                "engine": sym_stats.get("engine", "classic"),
            }
        except Exception as e:  # pragma: no cover - diagnostic only
            print(f"sym bench failed: {e!r}", file=sys.stderr)

    # Board ladder (BASELINE.md configs #3-#4): one solve of the next
    # board up, recorded alongside the primary metric. Default 6x4 (~95M
    # positions, the widest uint32 board); BENCH_LADDER=0 disables,
    # BENCH_LADDER=<spec> overrides.
    ladder = None
    ladder_spec = os.environ.get(
        "BENCH_LADDER", "0" if smoke else "connect4:w=6,h=4")
    if (ladder_spec not in ("0", "off", "") and ladder_spec != spec
            and dev.platform != "cpu"):
        try:
            # Same repeat count as the primary: the on-chip default is 3
            # (median lands in the record), and an explicit BENCH_REPEATS
            # is respected rather than silently overridden.
            lad_pps, lad_stats, lad_runs, _ = run_solves(
                ladder_spec, repeats)
            ladder = {
                "game": lad_stats["game"],
                "positions": lad_stats["positions"],
                "positions_per_sec": round(lad_pps, 1),
                "median_pps": round(statistics.median(lad_runs), 1),
                "secs_forward": round(lad_stats["secs_forward"], 3),
                "secs_backward": round(lad_stats["secs_backward"], 3),
            }
        except Exception as e:  # pragma: no cover - diagnostic only
            print(f"ladder bench failed: {e!r}", file=sys.stderr)

    if sym is not None:
        record["sym"] = sym
    if ladder is not None:
        record["ladder"] = ladder
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(inner() if "--inner" in sys.argv else main())
