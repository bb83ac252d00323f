"""Spans and kernel names on the profiler's own trace (CPU).

A ``Span`` is a profiler annotation while it is open, so a running
profiler records it as a host event carrying its fields; with no profiler
it leaves nothing and never raises. The dense engine's per-level record
is now a span and keeps its JSONL keys. Every engine kernel's program is
named after its kind, never ``jit__lambda``.
"""

import json

import pytest

from gamesmanmpi_tpu.obs import MetricsRegistry, Span


def _profiled(tmp_path, body):
    """Run body() under the JAX profiler; -> {host event name: [events]}
    and the set of XLA module names, from the written trace."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host, modules = {}, set()
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
                elif plane.name.startswith("/host:"):
                    host.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, stats))
    return host, modules


def test_span_is_a_host_event_with_its_fields(tmp_path):
    reg = MetricsRegistry()

    def body():
        sp = Span("forward", registry=reg, level=3, kind="x", ratio=0.5,
                  resumed=False)
        sp.set(frontier=7)
        sp.end(children=9)

    host, _ = _profiled(tmp_path, body)
    [(start, end, stats)] = host["forward"]
    # Integers and strings ride along; floats and flags do not.
    assert stats == {"level": 3, "kind": "x", "frontier": 7, "children": 9}
    assert end > start


def test_spans_may_end_out_of_order(tmp_path):
    reg = MetricsRegistry()

    def body():
        outer = Span("forward", registry=reg, level=1)
        inner = Span("dedup", registry=reg, level=1)
        outer.end()  # the parent ends first: non-nested order
        inner.end()

    host, _ = _profiled(tmp_path, body)
    [(o0, o1, _)] = host["forward"]
    [(i0, i1, _)] = host["dedup"]
    assert o0 <= i0 < o1 <= i1


@pytest.mark.parametrize("fail", ["open", "metadata", "close"])
def test_span_without_profiler_leaves_nothing_and_never_raises(
        tmp_path, monkeypatch, fail):
    import jax.profiler

    reg = MetricsRegistry()
    before = Span("orphan", registry=reg, level=1)
    before.end()
    host, _ = _profiled(tmp_path, lambda: Span("later", registry=reg).end())
    assert "orphan" not in host and "later" in host

    real = jax.profiler.TraceAnnotation

    class Broken(real):
        def __init__(self, *a, **k):
            if fail == "open":
                raise RuntimeError("no annotation")
            super().__init__(*a, **k)

        def set_metadata(self, **k):
            if fail == "metadata":
                raise RuntimeError("no metadata")
            super().set_metadata(**k)

        def __exit__(self, *exc):
            if fail == "close":
                raise RuntimeError("no close")
            return super().__exit__(*exc)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Broken)
    sp = Span("solve", registry=reg, engine="dense")
    sp.set(level=2)
    assert sp.end(n=5) >= 0.0
    assert sp.fields == {"engine": "dense", "level": 2, "n": 5}


def test_dense_backward_records_keep_their_keys(tmp_path):
    from gamesmanmpi_tpu.core.values import value_name
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.solve.dense import DenseSolver
    from gamesmanmpi_tpu.utils.metrics import JsonlLogger

    path = tmp_path / "m.jsonl"
    logger = JsonlLogger(str(path))
    res = DenseSolver(get_game("connect4:w=4,h=4"), logger=logger).solve()
    logger.close()
    assert (value_name(res.value), res.remoteness) == ("TIE", 16)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    levels = [r for r in recs if r["phase"] == "dense_backward"]
    assert [r["level"] for r in levels] == list(range(16, -1, -1))
    for r in levels:
        keys = set(r) - {"secs_since_last_drain"}
        assert keys == {"t", "phase", "level", "classes", "class_size",
                        "secs"}
        assert r["secs"] >= 0 and r["classes"] >= 1


def test_classic_programs_are_named_by_kernel_kind(tmp_path):
    from gamesmanmpi_tpu.games import get_game
    from gamesmanmpi_tpu.solve import Solver

    solver = Solver(get_game("tictactoe"))
    holder = []
    host, modules = _profiled(
        tmp_path, lambda: holder.append(solver.solve()))
    assert holder[0].num_positions == 5478
    assert "jit__lambda" not in modules
    kernels = {k for k in solver.dispatch_by_kind
               if f"jit_{k}" in modules}
    assert {"fwdf", "bwd"} <= kernels, (modules, solver.dispatch_by_kind)
