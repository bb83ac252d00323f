#!/usr/bin/env python
"""Gate a bench record against the committed BENCH_* trajectory.

"As fast as the hardware allows" (ROADMAP item 3) is only a measured
claim while someone diffs every new bench record against the history —
this tool makes that diff a one-command CI gate::

    python bench.py > BENCH_new.json
    python tools/bench_compare.py BENCH_new.json

It loads every committed ``BENCH_*.json`` at the repo root (the
trajectory; older rounds wrapped their record under a ``parsed`` key —
both shapes load), picks the comparable references — same ``metric``
and same ``device`` as the new record — and compares the new record's
headline value against the trajectory's best. The command exits
nonzero (status 1) when ``new / best < --min-ratio`` (default 0.6, env
``BENCH_COMPARE_MIN_RATIO``): a regression past the threshold fails
CI; a pass prints the ratio plus the roofline-field deltas
(operand_gbps, dispatches/level) so a
borderline run is explainable from the output alone. Usage errors
(unreadable/record-less input) exit with status 2; no comparable
reference (first record on a new metric or device) passes with a note
— there is nothing honest to gate against.

Stdlib-only and jax-free, like tools/obs_report.py (whose ``--json``
output covers per-level reports; this tool covers the one-line bench
records).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # tools/ scripts get sys.path[0]=tools/
    sys.path.insert(0, REPO)

from gamesmanmpi_tpu.utils.env import env_float  # noqa: E402


def load_record(path: str):
    """One bench record from a file: a plain record dict, a
    ``{"parsed": {...}}`` wrapper (the r01-r05 artifact shape), or the
    last record-bearing line of a JSONL stream (bench.py prints
    provisional records line by line). None when no record is found."""
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError:
        return None
    candidates = []
    try:
        candidates.append(json.loads(text))
    except ValueError:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                candidates.append(json.loads(line))
            except ValueError:
                continue
    for obj in reversed(candidates):
        if not isinstance(obj, dict):
            continue
        if isinstance(obj.get("parsed"), dict):
            obj = obj["parsed"]
        if "metric" in obj and "value" in obj:
            return obj
    return None


def load_trajectory(pattern: str):
    """Every record the glob yields, newest-name-last: [(path, rec)]."""
    out = []
    for path in sorted(glob.glob(pattern)):
        rec = load_record(path)
        if rec is not None:
            out.append((path, rec))
    return out


def _fmt_delta(label: str, new, ref) -> str:
    if new is None or ref is None:
        return f"  {label}: n/a"
    return f"  {label}: {new} (trajectory best run: {ref})"


def compare(new: dict, trajectory, min_ratio: float) -> tuple:
    """-> (ok, lines). Reference = best comparable trajectory value."""
    refs = [
        (path, rec) for path, rec in trajectory
        if rec.get("metric") == new.get("metric")
        and rec.get("device") == new.get("device")
        and float(rec.get("value") or 0.0) > 0
        and not rec.get("provisional")
    ]
    lines = []
    if not refs:
        lines.append(
            f"no comparable reference for metric={new.get('metric')!r} "
            f"device={new.get('device')!r} in the trajectory — "
            "nothing to gate against (pass)"
        )
        return True, lines
    best_path, best = max(refs, key=lambda pr: float(pr[1]["value"]))
    ratio = float(new.get("value") or 0.0) / float(best["value"])
    lines.append(
        f"{new['metric']}: new={float(new['value']):.1f} vs best "
        f"{float(best['value']):.1f} ({os.path.basename(best_path)}) "
        f"-> ratio {ratio:.3f} (min {min_ratio:.3f})"
    )
    nrf, brf = new.get("roofline") or {}, best.get("roofline") or {}
    neff = new.get("efficiency") or {}
    beff = best.get("efficiency") or {}
    lines.append(_fmt_delta(
        "operand_gbps",
        nrf.get("operand_gbps", neff.get("operand_gbps")),
        brf.get("operand_gbps", beff.get("operand_gbps")),
    ))
    lines.append(_fmt_delta(
        "dispatches_per_level",
        (new.get("dispatches") or {}).get("per_level"),
        (best.get("dispatches") or {}).get("per_level"),
    ))
    ab_ok, ab_lines = check_trace_ab(new)
    lines.extend(ab_lines)
    hot_ok, hot_lines = check_serve_hot(new)
    lines.extend(hot_lines)
    if ratio < min_ratio:
        lines.append(
            f"REGRESSION: new value is {ratio:.2f}x the trajectory best "
            f"(threshold {min_ratio:.2f}x) — investigate before "
            "committing this record"
        )
        return False, lines
    if not ab_ok or not hot_ok:
        return False, lines
    lines.append("ok")
    return True, lines


def check_trace_ab(new: dict) -> tuple:
    """-> (ok, lines): the query-tracing overhead gate (ISSUE 17).

    A record carrying a serve trace A/B arm (bench.py's
    ``serve.trace_ab`` summary, or a BENCH_serve artifact's top-level
    ``trace_ab``) must show tracing-on p99 within the arm's recorded
    threshold of tracing-off — ``ok`` is computed by bench.py at
    measurement time; this gate makes CI refuse a record where sampling
    overhead crossed it. Records without the arm pass untouched.
    """
    ab = (new.get("serve") or {}).get("trace_ab") or new.get("trace_ab")
    if not isinstance(ab, dict):
        return True, []
    if "error" in ab:
        return False, [
            f"TRACE A/B BROKEN: {ab['error']} — the overhead arm never "
            "measured; rerun before committing this record"
        ]
    delta = ab.get("delta_pct")
    limit = ab.get("max_delta_pct")
    line = (
        f"trace_ab: tracing-on p99 delta {delta}% "
        f"(limit {limit}% + slack) -> "
        + ("ok" if ab.get("ok") else "OVER BUDGET")
    )
    if not ab.get("ok"):
        return False, [
            line,
            "TRACING OVERHEAD REGRESSION: tail-sampled query tracing "
            "costs more than the recorded p99 budget — investigate "
            "before committing this record",
        ]
    return True, [line]


def check_serve_hot(new: dict) -> tuple:
    """-> (ok, lines): the serving hot-path A/B gate (ISSUE 18).

    A record carrying a serve hot-path arm (bench.py's ``serve_hot``
    summary, or a BENCH_serve_hot artifact's top-level ``serve_hot``)
    must show the hot arm (opening book + cross-worker shared block
    cache + batcher dedup) beating the cold baseline on BOTH qps and
    p99 under the same zipf stream, with zero errors/mismatches on
    either arm, book AND shm hit counters above zero, and the
    conditional-GET pass revalidating clean — ``ok`` is computed by
    bench.py at measurement time; this gate makes CI refuse a record
    where the hot path stopped paying for itself (or stopped being
    exercised at all). Records without the arm pass untouched.
    """
    hot = new.get("serve_hot")
    if not isinstance(hot, dict):
        return True, []
    if "error" in hot:
        return False, [
            f"SERVE HOT A/B BROKEN: {hot['error']} — the hot-path arm "
            "never measured; rerun before committing this record"
        ]
    base_arm = hot.get("baseline") or {}
    hot_arm = hot.get("hot") or {}
    line = (
        f"serve_hot: hot {hot_arm.get('qps')} qps / "
        f"{hot_arm.get('p99_ms')} ms p99 vs baseline "
        f"{base_arm.get('qps')} qps / {base_arm.get('p99_ms')} ms p99, "
        f"book_hits={hot.get('book_hits')} shm_hits={hot.get('shm_hits')}"
        " -> " + ("ok" if hot.get("ok") else "FAILED")
    )
    if not hot.get("ok"):
        detail = ", ".join(
            g for g in ("clean", "perf_ok", "hits_ok", "get_ok")
            if not hot.get(g, True)
        ) or "gate flags missing"
        return False, [
            line,
            f"SERVE HOT PATH REGRESSION ({detail}): the book/shm/dedup "
            "stack no longer beats the cold baseline (or went "
            "unexercised) — investigate before committing this record",
        ]
    return True, [line]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_compare",
        description="Diff a new bench record against the committed "
        "BENCH_* trajectory; nonzero status on regression past the "
        "threshold (docs/OBSERVABILITY.md \"Roofline fields\").",
    )
    p.add_argument("record", help="new bench record (bench.py stdout, "
                   "a BENCH_*.json artifact, or a JSONL stream)")
    p.add_argument("--trajectory", default=None, metavar="GLOB",
                   help="reference records (default: BENCH_*.json at "
                   "the repo root)")
    p.add_argument("--min-ratio", type=float, default=None,
                   help="fail when new/best falls below this (env "
                   "BENCH_COMPARE_MIN_RATIO, default 0.6)")
    args = p.parse_args(argv)
    min_ratio = (
        env_float("BENCH_COMPARE_MIN_RATIO", 0.6)
        if args.min_ratio is None else float(args.min_ratio)
    )
    new = load_record(args.record)
    if new is None:
        print(f"error: no bench record found in {args.record!r}",
              file=sys.stderr)
        return 2
    pattern = args.trajectory or os.path.join(REPO, "BENCH_*.json")
    trajectory = [
        (path, rec) for path, rec in load_trajectory(pattern)
        if os.path.abspath(path) != os.path.abspath(args.record)
    ]
    ok, lines = compare(new, trajectory, min_ratio)
    print("\n".join(lines))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
