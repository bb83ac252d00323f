"""The owner-routed sharded engine against the plain reference, through the
CLI as a user runs it (``--engine auto --devices N``), on the suite's fake
CPU devices.

The yardstick is ``benchmarks/reference.py`` (scalar negamax) and
``benchmarks/enumerate_c4.py`` (NumPy per-ply counts): neither imports
anything of the program. The 4x4 board is small enough to compare the
whole table, position by position.
"""

import contextlib
import io
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import enumerate_c4  # noqa: E402
import reference  # noqa: E402

from gamesmanmpi_tpu import cli  # noqa: E402
from gamesmanmpi_tpu.core.codec import unpack_cells_np  # noqa: E402

NAMES = {1: "WIN", 2: "LOSE", 3: "TIE"}
QUERY = re.compile(r"^query (\S+): value=(\w+) remoteness=(\d+)$", re.M)


@pytest.fixture(scope="module")
def truth():
    game = reference.Connect4(4, 4)
    return game, reference.solve(game, [game.initial])


@pytest.mark.parametrize("devices", [4, 2])
def test_sharded_solve_matches_the_reference(devices, truth, tmp_path):
    game, memo = truth
    sample = reference.sample_positions(game, 2**31 + 25 + devices,
                                        per_ply=5, min_ply=3, max_ply=16)
    assert len(sample) >= 64
    jsonl, table = tmp_path / "solve.jsonl", tmp_path / "table.npz"
    argv = ["connect4:w=4,h=4", "--engine", "auto",
            "--devices", str(devices), "--jsonl", str(jsonl),
            "--table-out", str(table),
            *(a for p in sample for a in ("--query", hex(p)))]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(argv) == 0
    text = out.getvalue()
    records = [json.loads(line) for line in jsonl.read_text().splitlines()]
    done = next(r for r in records if r["phase"] == "done")
    assert done["engine"] == "sharded" and done["shards"] == devices

    # Per-ply counts, whichever backward each level ran.
    want = enumerate_c4.enumerate_board(4, 4)
    got = {int(k): v for k, v in done["reachable_per_level"].items()}
    assert got == dict(enumerate(want["level_counts"]))
    assert sum(got.values()) == want["positions"] == 161_029
    assert done["positions"] == 161_029
    assert done["bytes_routed"] <= done["bytes_routed_padded"]

    # The root and the sampled --query answers.
    report = dict(line.split(": ", 1) for line in text.splitlines()
                  if ": " in line and not line.startswith("query "))
    assert (report["value"], int(report["remoteness"])) == memo[game.initial]
    answers = {int(q, 0): (v, int(r)) for q, v, r in QUERY.findall(text)}
    assert answers == {p: memo[p] for p in sample}

    # The whole table, position by position.
    npz = np.load(table)
    seen = {}
    for key in npz.files:
        if key.startswith("states_"):
            states = npz[key]
            values, rem = unpack_cells_np(npz["cells_" + key[7:]])
            seen.update(zip(states.tolist(),
                            zip((NAMES[int(v)] for v in values),
                                rem.tolist())))
    assert seen == memo

    # The mesh's spans reach the JSONL: a forward record per discovered
    # level, an assemble per table copied to the host off the solve
    # thread (and so no table download, which would block it).
    forward = [r for r in records if r["phase"] == "forward"]
    assert sorted(r["level"] for r in forward) == list(range(16))
    assert all(r["n"] == got[r["level"]] and r["children"] > 0
               and r["bytes_routed"] > 0 for r in forward)
    assembled = [r for r in records if r["phase"] == "assemble"]
    assert sorted(r["level"] for r in assembled) == list(range(17))
    assert all(r["kind"] == "table" and r["n"] == got[r["level"]]
               for r in assembled)
    assert not [r for r in records
                if r["phase"] == "download" and r["kind"] == "table"]
    assert done["tables_async_levels"] == 17
