"""Device-idle seconds per chip of the traced window that fall outside
every ``solve`` span of the program: the entry point's own work before
and after the engine runs (parsing, set-up, ``--query`` answering). Gaps
under a ``download`` or ``kernel_wait`` span there are left to those
metrics, so the three never count a gap twice (``scopes.py``)."""

import scopes
import trace_reduce


def read(ctx):
    if not ctx.get("trace") or not ctx.get("profile_data"):
        return None
    solves = scopes.host_spans(ctx, "solve")
    if not solves:
        return None
    lo, hi = ctx["trace"]["span_ns"]
    claimed = trace_reduce.union(
        solves + scopes.host_spans(ctx, "download")
        + scopes.host_spans(ctx, "kernel_wait"))
    return scopes.idle_under(ctx, trace_reduce.gaps(claimed, lo, hi))
