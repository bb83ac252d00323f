"""The kernel-scope and idle-cause readers on a synthetic trace, where
each number is known exactly, and on traced CPU rehearsals of both cells.
No number here is a device number."""

import importlib.util
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

READERS = ["kernel.expand_s", "kernel.lookup_s", "kernel.gather_s",
           "kernel.combine_s", "kernel.dedup_s", "idle.download_s",
           "idle.kernel_wait_s", "idle.entry_s"]
MS = 10**9  # picoseconds in a millisecond


def read(name, ctx):
    path = HERE.parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def xspace(device_ops, host_events):
    """A text-proto XSpace: one TPU plane with an "XLA Ops" line of
    (start ms, end ms, name, op path or None), one host thread of
    (start ms, end ms, name). An op's path is a ``tf_op`` stat on its
    event metadata, as the TPU keeps it."""
    names = sorted({n for _, _, n, _ in device_ops}
                   | {n for _, _, n in host_events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    paths = {n: p for _, _, n, p in device_ops if p}

    def event(s, e, n):
        return (f"events {{ metadata_id: {meta[n]} offset_ps: {s * MS} "
                f"duration_ps: {(e - s) * MS} }}")

    def metadata(with_paths):
        return "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
            + (f'stats {{ metadata_id: 1 str_value: "{paths[n]}" }}'
               if with_paths and n in paths else "")
            + " } }" for n, i in meta.items())

    ops = "\n".join(event(s, e, n) for s, e, n, _ in device_ops)
    host = "\n".join(event(s, e, n) for s, e, n in host_events)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  {metadata(True)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python3" timestamp_ns: 0 {host} }}
  {metadata(False)} }}
"""


# Device ops (ms): a loop under expand holding a lookup op; a gather; a
# fused sort under expand/dedup; a combine; one op with no scope. Busy:
# [0, 160) and [200, 340); everything after 340 is idle.
OPS = [
    (0, 100, "while.1", "jit(dense_step)/expand/while"),
    (10, 40, "fusion.1", "jit(dense_step)/lookup/mul"),
    (100, 160, "fusion.2", "jit(dense_step)/lookup/add"),
    (200, 300, "fusion.3", "jit(dense_step)/gather/gather"),
    (300, 320, "sort.1", "jit(fwdp)/expand/dedup/sort"),
    (320, 330, "fusion.4", "jit(dense_step)/combine/reduce"),
    (330, 340, "copy.1", None),
    (1200, 1300, "fusion.5", "jit(dense_step)/gather/late"),  # after
]
HOST = [
    (0, 1000, tr.SPAN),
    (50, 900, "solve"),
    (340, 400, "download"),
    (600, 700, "kernel_wait"),
    (900, 980, "query"),
]
WANT = {  # seconds
    "kernel.expand_s": 0.070, "kernel.lookup_s": 0.090,
    "kernel.gather_s": 0.100, "kernel.combine_s": 0.010,
    "kernel.dedup_s": 0.020,
    "idle.download_s": 0.060, "idle.kernel_wait_s": 0.100,
    # Idle outside solve: [900, 1000); [0, 50) is busy.
    "idle.entry_s": 0.100,
}


def context(text, tmp_path):
    from jax.profiler import ProfileData

    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    pd = ProfileData.from_file(str(xplane))
    return {"trace": tr.reduce(pd), "profile_data": pd, "xplane": xplane}


def test_each_reader_reads_its_exact_number(tmp_path):
    ctx = context(xspace(OPS, HOST), tmp_path)
    got = {name: read(name, ctx) for name in READERS}
    assert got == pytest.approx(WANT, abs=1e-12)
    t = ctx["trace"]
    idle = t["window_s"] - t["busy_s"]
    assert idle == pytest.approx(0.700)
    assert sum(got[k] for k in READERS if k.startswith("idle.")) <= idle
    import scopes

    seconds = scopes.scope_seconds(ctx)
    assert seconds[None] == pytest.approx(0.010)
    assert seconds["unscoped_ops"] == {"?:copy.1": pytest.approx(0.010)}
    # Six ops in the window have a path; copy.1 has none.
    assert seconds["found_by"]["metadata"] == 6
    assert seconds["found_by"]["none"] == 1


def test_innermost_vocabulary_scope_wins():
    import scopes

    assert scopes.scope_of("jit(bwdp)/gather/combine/max:") == "combine"
    assert scopes.scope_of("jit(fwdp)/expand/dedup/sort") == "dedup"
    assert scopes.scope_of("jit(f)/expanded/add") is None
    assert scopes.scope_of(None) is None


def test_a_program_without_scopes_or_spans_gives_nothing(tmp_path):
    ops = [(s, e, n, None) for s, e, n, _ in OPS]
    host = [h for h in HOST if h[2] == tr.SPAN]
    ctx = context(xspace(ops, host), tmp_path)
    assert {name: read(name, ctx) for name in READERS} == dict.fromkeys(
        READERS)


def test_an_untraced_run_gives_nothing():
    ctx = {"trace": None, "profile_data": None}
    assert {name: read(name, ctx) for name in READERS} == dict.fromkeys(
        READERS)


REHEARSAL = """
import json, sys
sys.path[:0] = ["benchmarks", "benchmarks/tests"]
import run, trace_reduce
from test_trace_reduce import cpu_lines
from test_readers import READERS, read
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
    print(json.dumps({
        "correct": out["line"]["correct"],
        "listed": [n for n in READERS if n in listed],
        "values": {n: read(n, ctx) for n in READERS},
        "idle_s": ctx["trace"]["window_s"] - ctx["trace"]["busy_s"],
    }))
"""
CELLS = {"c4_6x4.builtin": "c4-4x4-builtin",
         "c4_6x4.gamedsl": "c4-4x4-gamedsl"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal_gets_through_to_the_readers(cell):
    """A traced CPU run of the cell reaches every reader it lists. The
    CPU's op events carry no name path, so the kernel readers find their
    scopes through the programs' HLO in the trace; the idle readers read
    the program's spans."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL, cell,
         str(HERE / "data" / f"{CELLS[cell]}.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = json.loads(proc.stdout.strip().splitlines()[-1])
    assert seen["correct"]
    want = [n for n in READERS if n != "kernel.dedup_s"]
    if cell == "c4_6x4.gamedsl":
        want = READERS
    assert seen["listed"] == want
    values = seen["values"]
    assert all(isinstance(v, float) and v >= 0 for v in values.values()), \
        values
    idle = [values[n] for n in READERS if n.startswith("idle.")]
    assert sum(idle) <= seen["idle_s"] + 1e-9
    # The classic engine on the CPU: forward expand + dedup, backward
    # lookup + combine.
    for name in ("kernel.expand_s", "kernel.dedup_s", "kernel.lookup_s",
                 "kernel.combine_s"):
        assert values[name] > 0, values


def test_scopes_from_the_programs_hlo(tmp_path):
    """On the CPU an op event names only its module and instruction; the
    scope comes from the program's HLO that the trace keeps."""
    import jax
    import jax.numpy as jnp
    import scopes
    from test_trace_reduce import cpu_lines

    def kernel(x):
        with jax.named_scope("expand"):
            y = jnp.sort(x * 3 + 1)
        with jax.named_scope("combine"):
            return jnp.cumsum(y) + y.max()

    kernel.__name__ = "probe_kernel"
    f = jax.jit(kernel)
    x = jnp.arange(1 << 16, dtype=jnp.int32)
    f(x).block_until_ready()
    with tr.traced(tmp_path):
        for _ in range(3):
            f(x).block_until_ready()
    xplane = tr.find_xplane(tmp_path)
    pd = tr.load(xplane)
    ctx = {"trace": tr.reduce(pd, classify=cpu_lines), "profile_data": pd,
           "xplane": xplane, "classify": cpu_lines}
    got = scopes.scope_seconds(ctx)
    assert got["found_by"]["hlo"] > 0
    assert got.get("expand", 0) > 0 and got.get("combine", 0) > 0
    paths = scopes._metadata(ctx)["programs"]["jit_probe_kernel"]
    assert {"expand", "combine"} <= {scopes.scope_of(p)
                                     for p in paths.values()}
