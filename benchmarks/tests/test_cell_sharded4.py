"""The four-chip cell ``c4_5x5.sharded4`` rehearsed on the host CPU at 4x4
over four virtual devices: it agrees with the reference, and ``correct``
comes out false for the control, for each fault of ``fault_run.py`` and for
the mesh's own fault (``mesh_fault_run.py``). Then the cell's new readers
on a synthetic trace, where each number is known exactly, and on a traced
rehearsal. No number here is a device number."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]

import trace_reduce as tr  # noqa: E402
from test_readers import MS, context, read, xspace  # noqa: E402

CELL = "c4_5x5.sharded4"
SMALL = HERE / "data" / "c4-4x4-builtin.json"
# run.py starts JAX before the CLI could fake devices: the CPU backend has
# to offer four from the start.
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def rehearse(*extra, script="fault_run.py", fault="none", seed=2**31 + 25):
    cmd = [sys.executable, str(HERE / script), fault, "--workload", CELL,
           "--seed", str(seed), "--seconds", "1", "--trace", "0",
           "--cpu-rehearsal", str(SMALL), *extra]
    proc = subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {}  # a rehearsal prints no device metric
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    return line, proc.stderr


def test_rehearsal_is_correct():
    line, err = rehearse()
    assert line["correct"], err[-2000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_control_is_not_correct():
    line, _ = rehearse("--control")
    assert not line["correct"]
    assert line["checks"]["answers_wrong"]["value"] > 0


@pytest.mark.parametrize("script,fault", [
    ("fault_run.py", "unchanged"), ("fault_run.py", "half"),
    ("fault_run.py", "altered"), ("mesh_fault_run.py", "lost_peer"),
])
def test_fault_is_not_correct(script, fault):
    line, _ = rehearse(script=script, fault=fault)
    assert not line["correct"]


# Device ops (ms) of one chip: the owner bucketing sort and a synchronous
# exchange under route; an exchange split into a start and a done (the
# done carries no path), with a gather running between them; a combine;
# an all-to-all outside the route scope; and an exchange after the window.
OPS = [
    (0, 10, "sort.7", "jit(sfwdp)/shard_map/route/sort"),
    (10, 30, "all_to_all.13", "jit(sfwdp)/shard_map/route/all_to_all"),
    (30, 32, "all-to-all-start.2", "jit(serp)/shard_map/route/all_to_all"),
    (32, 48, "fusion.9", "jit(serp)/shard_map/gather/gather"),
    (48, 55, "all-to-all-done.2", None),
    (60, 70, "fusion.10", "jit(serp)/shard_map/combine/select"),
    (70, 80, "all-to-all.4", "jit(other)/all_to_all"),
    (1200, 1300, "all_to_all.14", "jit(sfwdp)/shard_map/route/all_to_all"),
]
HOST = [(0, 1000, tr.SPAN), (0, 900, "solve")]
CONFIG = {"board": {"width": 4, "height": 4, "connect": 4},
          "edges": 304_574}


def mesh_context(tmp_path, ops=OPS):
    ctx = context(xspace(ops, HOST), tmp_path)
    ctx.update(config=CONFIG, chips=4, device={"kind": "TPU v5 lite"})
    return ctx


def test_route_seconds_and_roofline_on_a_synthetic_trace(tmp_path):
    ctx = mesh_context(tmp_path)
    # Own time under route: the sort, the exchange and the start op.
    assert read("kernel.route_s", ctx) == pytest.approx(0.032, abs=1e-12)
    # Exchanges: [10, 30) and the start-to-done [30, 55).
    mod = _reader("mesh.route_roofline")
    spans = mod.exchange_intervals(ctx)
    assert list(spans.values()) == [[(10 * MS // 10**3, 55 * MS // 10**3)]]
    least = 304_574 * 3 / 4 * 5 / 4  # bytes per chip: u32 state + cell
    assert mod.least_bytes_per_chip(CONFIG, 4) == pytest.approx(least)
    want = 100 * least / 200e9 / 0.045
    assert read("mesh.route_roofline", ctx) == pytest.approx(want)


def test_route_readers_without_scopes_or_trace(tmp_path):
    ctx = mesh_context(tmp_path, [(s, e, n, None) for s, e, n, _ in OPS])
    assert read("kernel.route_s", ctx) is None
    assert read("mesh.route_roofline", ctx) is None
    untraced = {"trace": None, "profile_data": None}
    assert read("kernel.route_s", untraced) is None
    assert read("mesh.route_roofline", untraced) is None


def test_route_roofline_refuses_an_unknown_device(tmp_path):
    ctx = mesh_context(tmp_path)
    ctx["device"] = {"kind": "TPU v0"}
    with pytest.raises(KeyError, match="TPU v0"):
        read("mesh.route_roofline", ctx)


def test_spill_retries_is_the_mean_over_the_window():
    solves = [{"done": {"spill_retries": 1}}, {"done": {"spill_retries": 2}}]
    assert read("mesh.spill_retries", {"solves": solves}) == 1.5
    assert read("mesh.spill_retries", {"solves": [{"done": {}}]}) is None


def _reader(name):
    import importlib.util

    path = HERE.parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TRACED = """
import json, sys
sys.path[:0] = ["benchmarks", "benchmarks/tests"]
import run, trace_reduce
from test_trace_reduce import cpu_lines
from test_readers import read
trace_reduce.reduce.__defaults__ = (cpu_lines,)  # the CPU stands in
bench, cell, _, traffic = run.find_cell(sys.argv[1])
config = run.load_json(run.Path(sys.argv[2]))
listed = [m["name"] for m in run.metrics_for(bench, cell["name"], True)]
import tempfile
with tempfile.TemporaryDirectory() as tmp:
    out = run.run_cell(cell, config, traffic, 2**31 + 7, 0.5, True,
                       run.Path(tmp), rehearsal=True)
    ctx = out["ctx"]
    ctx["classify"] = cpu_lines
    ctx["device"]["kind"] = "TPU v5 lite"  # the peak the reader looks up
    done = ctx["solves"][0]["done"]
    print(json.dumps({
        "correct": out["line"]["correct"],
        "engine": done["engine"], "shards": done["shards"],
        "listed": listed,
        # No "XLA Modules" line on the CPU, so no launches; set-up's
        # compile events are the recorder's, not the trace's.
        "values": {n: read(n, ctx) for n in listed
                   if n not in ("compile.setup_s", "device.launches")},
    }))
"""


def test_traced_rehearsal_gets_through_to_the_readers():
    """A traced CPU run of the cell reaches every reader it lists, the
    three new ones among them, with the sharded engine on four shards."""
    proc = subprocess.run(
        [sys.executable, "-c", TRACED, CELL, str(SMALL)],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = json.loads(proc.stdout.strip().splitlines()[-1])
    assert seen["correct"]
    assert (seen["engine"], seen["shards"]) == ("sharded", 4)
    assert "kernels_roofline" not in seen["listed"]
    values = seen["values"]
    assert {"kernel.route_s", "mesh.route_roofline",
            "mesh.spill_retries"} <= set(values)
    assert all(isinstance(v, float) and v >= 0 for v in values.values()), \
        values
    assert values["kernel.route_s"] > 0
    assert 0 < values["mesh.route_roofline"] <= 100
    for name in ("kernel.expand_s", "kernel.dedup_s", "kernel.gather_s",
                 "kernel.combine_s", "engine.forward_s"):
        assert values[name] > 0, values
