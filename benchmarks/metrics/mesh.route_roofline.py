"""The routing's share of its interconnect roofline, in percent.

Least work, fixed by the board whatever implements it
(``least_bytes_per_chip``): each move edge whose child another chip owns
crosses the interconnect twice, the child's state out and its 1-byte
packed answer back. Under a uniform owner hash over S chips that is
(S - 1) / S of the configuration's edges, shared by the S chips. It is
bound by one chip's ICI bandwidth, from ``benchmarks/ici_peaks.json`` by
``device_kind``; an unknown kind is an error.

Time: device seconds per chip of the exchanges, the "XLA Ops" events
inside the ``benchmark.window`` span whose name is an all-to-all and whose
name path's innermost scope is ``route`` (``scopes.py``). An exchange that
XLA splits into a ``-start`` and a ``-done`` op counts from the start of
the one to the end of the other; overlapping exchanges count once."""

import json
import re
from pathlib import Path

import scopes
import trace_reduce

EXCHANGE = re.compile(r"^(.*?all[-_]to[-_]all)(?:[-_](start|done))?(.*)$")


def least_bytes_per_chip(config: dict, chips: int) -> float:
    """Bytes each chip must move over ICI in one solve of the board."""
    board = config["board"]
    state_bytes = 4 if (board["height"] + 1) * board["width"] <= 32 else 8
    crossing = config["edges"] * (chips - 1) / chips
    return crossing * (state_bytes + 1) / chips


def exchange_intervals(ctx) -> dict[str, list[tuple[int, int]]] | None:
    """{device plane: [(start, end)] of its route exchanges in the
    window}; None untraced."""
    win = scopes._window(ctx)
    if win is None or not ctx.get("profile_data"):
        return None
    lo, hi = win
    out = {}
    for plane, ops in scopes._devices(ctx).items():
        starts, spans = {}, []
        for s, e, ev in sorted(ops, key=lambda x: x[0]):
            m = EXCHANGE.match(ev.name)
            if not m:
                continue
            key = m.group(1) + m.group(3)
            if m.group(2) == "done":
                if key in starts:
                    spans.append((starts.pop(key), e))
                continue
            _, path = scopes._op_path(ctx, plane, ev, dict(ev.stats))
            if scopes.scope_of(path) != "route":
                continue
            if m.group(2) == "start":
                starts[key] = s
            else:
                spans.append((s, e))
        out[plane] = trace_reduce.union(trace_reduce.clip(spans, lo, hi))
    return out


def read(ctx):
    found = exchange_intervals(ctx)
    if not found or not any(found.values()):
        return None
    busy = [sum(b - a for a, b in spans) for spans in found.values()]
    route_s = sum(busy) / len([b for b in busy if b]) / 1e9
    peaks = json.loads(
        (Path(__file__).parents[1] / "ici_peaks.json").read_text())
    kind = ctx["device"]["kind"]
    if kind not in peaks["devices"]:
        raise KeyError(f"no ICI peak for device kind {kind!r} in "
                       "ici_peaks.json")
    least_s = least_bytes_per_chip(ctx["config"], ctx["chips"]) / peaks[
        "devices"][kind]["ici_bytes_per_s"]
    return 100.0 * least_s / route_s
