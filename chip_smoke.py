#!/usr/bin/env python
"""Prove that the solve path runs on the TPU, through the user's entry point.

    python chip_smoke.py               # one chip: connect4 6x4 dense + classic,
                                       # and tictactoe
    python chip_smoke.py --four-chips  # four chips: connect4 5x5 sharded over
                                       # 4 chips vs dense on 1 chip

Every solve goes through ``gamesmanmpi_tpu.cli.main``, the function behind
``solve_launcher.py``, in this one process (a chip belongs to one process).
The answers are checked against published counts and against each other.
Any failed check raises, so the exit code is non-zero and the last line is
not the ``"ok": true`` record. With no TPU it fails before solving anything.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

# Tromp's published position counts (John's Connect Four Playground).
C4_6X4_POSITIONS = 94_910_577
C4_5X5_POSITIONS = 69_763_700


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def solve(argv: list[str], env: dict[str, str] | None = None) -> dict:
    """Run one CLI solve; return its reported answer and timings."""
    from gamesmanmpi_tpu import cli

    saved = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    try:
        with tempfile.TemporaryDirectory() as tmp:
            jsonl = os.path.join(tmp, "solve.jsonl")
            out = io.StringIO()
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(out):
                rc = cli.main([*argv, "--jsonl", jsonl])
            wall = time.perf_counter() - t0
            with open(jsonl) as f:
                done = [r for r in map(json.loads, f)
                        if r.get("phase") == "done"]
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    check(rc == 0, f"{argv}: cli exit code {rc}\n{out.getvalue()}")
    check(len(done) == 1, f"{argv}: expected one 'done' record, got {done}")
    report = dict(
        line.split(": ", 1) for line in out.getvalue().splitlines()
        if ": " in line
    )
    solve_secs = float(done[0]["secs_total"])
    res = {
        "argv": argv,
        "engine": done[0]["engine"],
        "positions": int(report["positions"]),
        "value": report["value"],
        "remoteness": int(report["remoteness"]),
        "setup_secs": wall - solve_secs,
        "solve_secs": solve_secs,
    }
    print(json.dumps({**res, "peak_bytes_in_use": peak_bytes()}), flush=True)
    return res


def peak_bytes() -> list[int | None]:
    """Each device's peak bytes in use so far in this process (None where
    the backend keeps no memory statistics)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def agree(a: dict, b: dict, positions: int) -> None:
    for r in (a, b):
        check(r["positions"] == positions,
              f"{r['argv']}: {r['positions']} positions, want {positions}")
    for key in ("value", "remoteness"):
        check(a[key] == b[key],
              f"{key} differs: {a['argv']} {a[key]!r} vs {b['argv']} {b[key]!r}")


def one_chip() -> None:
    dense = solve(["connect4:w=6,h=4"],
                  env={"GAMESMAN_DENSE_COUNTS_FILE": "0"})
    check(dense["engine"] == "dense",
          f"--engine auto ran {dense['engine']}, want dense on the TPU")
    classic = solve(["connect4:w=6,h=4", "--engine", "classic"])
    agree(dense, classic, C4_6X4_POSITIONS)
    ttt = solve(["tictactoe"])
    check((ttt["value"], ttt["remoteness"], ttt["positions"])
          == ("TIE", 9, 5478), f"tictactoe: {ttt}")


def four_chips() -> None:
    sharded = solve(["connect4:w=5,h=5", "--engine", "classic",
                     "--devices", "4"])
    dense = solve(["connect4:w=5,h=5", "--engine", "dense"],
                  env={"GAMESMAN_DENSE_COUNTS_FILE": "0"})
    check(dense["engine"] == "dense", f"dense run ran {dense['engine']}")
    agree(sharded, dense, C4_5X5_POSITIONS)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip sharded solve and its "
                   "1-chip dense reference")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices() = {devices})",
              file=sys.stderr)
        return 1
    if len(devices) < want:
        print(f"chip_smoke: need {want} chips, have {len(devices)}",
              file=sys.stderr)
        return 1

    from gamesmanmpi_tpu.utils.platform import use_compile_cache

    use_compile_cache()
    (four_chips if args.four_chips else one_chip)()
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
