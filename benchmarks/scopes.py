"""Device time by kernel scope, and device idle time by host span, from
the loaded profiler trace of a traced run (``ctx["profile_data"]``).

The program names its kernels' ops with ``jax.named_scope`` from one
vocabulary (docs/OBSERVABILITY.md "Device scopes") and opens a profiler
annotation for each of its spans. Both land in the one trace the harness
records, on one clock:

- ``scope_seconds``: device seconds of the "XLA Ops" events inside the
  ``benchmark.window`` span, by the innermost vocabulary scope on each
  op's name path, per chip. The path is the ``tf_op`` stat of the op's
  event metadata (where the TPU keeps it), else its program's HLO in the
  trace, by module and instruction (the CPU's op events name only those);
  ``xplane_meta`` reads both. An op's own time leaves out the ops nested
  in it on its line, so a loop is not counted twice. Ops with no scope
  fall under None.
- ``idle_under``: device-idle seconds (the gaps ``trace_reduce.gaps``
  finds in the window) that fall under host spans of a name, per chip.

A program that has no scopes, or no ``solve`` span, gives nothing (None)
and no error: the readers run on programs that predate them.
"""

from __future__ import annotations

import re
from collections import Counter

import trace_reduce as tr
import xplane_meta

SCOPES = ("expand", "dedup", "lookup", "gather", "combine", "route")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def scope_of(path: str | None) -> str | None:
    """The innermost vocabulary scope on a name path."""
    if not path:
        return None
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def self_times(events: list[tuple[int, int, object]]):
    """(start, end, item) events of one line -> [(item, own ns)], where an
    event's own time leaves out the events nested inside it."""
    out, stack = [], []  # stack: [start, end, item, nested ns]

    def pop():
        start, end, item, nested = stack.pop()
        out.append((item, max(0, end - start - nested)))
        if stack:
            stack[-1][3] += end - start

    for s, e, item in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            pop()
        stack.append([s, e, item, 0])
    while stack:
        pop()
    return out


def _window(ctx):
    t = ctx.get("trace")
    return tuple(t["span_ns"]) if t else None


def _devices(ctx):
    """{device plane: [(start, end, event)] of its "XLA Ops" events}."""
    if "_scope_devices" not in ctx:
        classify = ctx.get("classify", tr.tpu_lines)
        devices: dict[str, list] = {}
        for plane in ctx["profile_data"].planes:
            for line in plane.lines:
                if classify(plane, line) != "ops":
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((s, s + int(ev.duration_ns), ev))
        ctx["_scope_devices"] = devices
    return ctx["_scope_devices"]


def _metadata(ctx) -> dict:
    """The name paths the trace's metadata keeps (``xplane_meta``), read
    once: ``events`` by device plane and op name, ``programs`` by program
    and instruction. Programs are keyed both as named in the trace
    (``jit_fwdp(12)``) and by module name alone (``jit_fwdp``), where an
    instruction takes the path most of that module's programs give it:
    an op event's program id need not be the metadata's."""
    if "_scope_metadata" not in ctx:
        path = ctx.get("xplane")
        meta = xplane_meta.read(open(path, "rb").read()) if path \
            else {"events": {}, "programs": {}}
        programs = meta["programs"]
        votes: dict[str, dict[str, Counter]] = {}
        for program, insts in list(programs.items()):
            base = votes.setdefault(_PROGRAM_ID.sub("", program), {})
            for inst, p in insts.items():
                base.setdefault(inst, Counter())[p] += 1
        for base, insts in votes.items():
            programs.setdefault(base, {i: c.most_common(1)[0][0]
                                       for i, c in insts.items()})
        ctx["_scope_metadata"] = meta
    return ctx["_scope_metadata"]


def _op_path(ctx, plane: str, ev, stats: dict) -> tuple[str, str | None]:
    """(where the name path was found, path) of one op event."""
    meta = _metadata(ctx)
    path = meta["events"].get(plane, {}).get(ev.name)
    if path is not None:
        return "metadata", path
    module = stats.get("hlo_module")
    for name in (f"{module}({stats.get('program_id')})", module):
        path = meta["programs"].get(name, {}).get(stats.get("hlo_op"))
        if path is not None:
            return "hlo", path
    return "none", None


def scope_seconds(ctx) -> dict | None:
    """{scope or None: device seconds per chip}, with the unscoped ops'
    seconds by name path under ``"unscoped_ops"`` and the count of ops
    whose path came from each source under ``"found_by"``; None
    untraced."""
    win = _window(ctx)
    if win is None or not ctx.get("profile_data"):
        return None
    if "_scope_seconds" in ctx:
        return ctx["_scope_seconds"]
    lo, hi = win
    totals: dict = {}
    unscoped: dict[str, float] = {}
    found_by = {"metadata": 0, "hlo": 0, "none": 0}
    used = 0
    for plane, ops in _devices(ctx).items():
        inside = [(max(s, lo), min(e, hi), ev) for s, e, ev in ops
                  if e > lo and s < hi]
        if not inside:
            continue
        used += 1
        for ev, ns in self_times(inside):
            stats = dict(ev.stats)
            how, path = _op_path(ctx, plane, ev, stats)
            found_by[how] += 1
            scope = scope_of(path)
            totals[scope] = totals.get(scope, 0.0) + ns / 1e9
            if scope is None:
                name = path or f"{stats.get('hlo_module', '?')}:{ev.name}"
                unscoped[name] = unscoped.get(name, 0.0) + ns / 1e9
    if not used:
        return None
    out = {k: v / used for k, v in totals.items()}
    out["unscoped_ops"] = {k: v / used for k, v in unscoped.items()}
    out["found_by"] = found_by
    ctx["_scope_seconds"] = out
    return out


def kernel_seconds(ctx, scope: str) -> float | None:
    """Device seconds per chip of one scope: 0 where no op has it, None
    where no op has any scope (a program without them)."""
    got = scope_seconds(ctx)
    if not got or not any(k in SCOPES for k in got):
        return None
    return got.get(scope, 0.0)


def host_spans(ctx, name: str) -> list[tuple[int, int]]:
    """The union of the host events of one name (every thread)."""
    if "_scope_host" not in ctx:
        host: dict[str, list] = {}
        for plane in ctx["profile_data"].planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
        ctx["_scope_host"] = host
    return tr.union(ctx["_scope_host"].get(name, []))


def idle_gaps(ctx) -> list[list[tuple[int, int]]] | None:
    """Each device's idle gaps in the window, sorted; None untraced."""
    win = _window(ctx)
    if win is None or not ctx.get("profile_data"):
        return None
    lo, hi = win
    out = []
    for ops in _devices(ctx).values():
        busy = tr.clip([(s, e) for s, e, _ in ops], lo, hi)
        if busy:
            out.append(tr.gaps(tr.union(busy), lo, hi))
    return out or None


def overlap_ns(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(ctx, spans: list[tuple[int, int]]) -> float | None:
    """Device-idle seconds per chip that fall under sorted, disjoint
    spans."""
    per_device = idle_gaps(ctx)
    if per_device is None:
        return None
    total = sum(overlap_ns(gaps, spans) for gaps in per_device)
    return total / 1e9 / len(per_device)


def idle_under_span(ctx, name: str) -> float | None:
    """Device-idle seconds per chip under host spans of one name: 0 where
    there is none, None where the program opens no ``solve`` span (a
    program without these spans)."""
    if _window(ctx) is None or not ctx.get("profile_data"):
        return None
    if not host_spans(ctx, "solve"):
        return None
    return idle_under(ctx, host_spans(ctx, name))
