"""gamedsl acceptance: spec validation, compiler byte-parity, staleness
plumbing, and the new pure-description games end-to-end.

The contract under test (ISSUE 16):

* compiled connect4/tictactoe specs produce solved tables sha256-equal
  to the hand-written modules (including the sym variants);
* the spec's canonical hash flows into the kernel cache key and the DB
  manifest, so a mutated spec provably misses the kernel cache and
  fails ``check_db --same-as``;
* two genuinely new games — exact-k gomoku and misere m,n,k — exist
  purely as .json descriptions and pass the same DB-oracle and serve
  round-trips as the hand-written games;
* the CLI solves/exports straight from ``--spec`` with zero Python;
* tools/spec_lint.py and the GM901 gamesman-lint checker reject broken
  specs with per-finding GS codes.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

from gamesmanmpi_tpu.core.values import value_name
from gamesmanmpi_tpu.db import DbReader, export_result
from gamesmanmpi_tpu.db.check import db_equal
from gamesmanmpi_tpu.db.format import read_manifest
from gamesmanmpi_tpu.gamedsl import (
    GameSpec,
    SpecError,
    lint_file,
    load_spec,
    spec_problems,
)
from gamesmanmpi_tpu.gamedsl.compiler import compile_spec
from gamesmanmpi_tpu.games import get_game
from gamesmanmpi_tpu.serve import QueryServer
from gamesmanmpi_tpu.solve import Solver
from gamesmanmpi_tpu.solve.engine import _cache_key
from gamesmanmpi_tpu.solve.oracle import oracle_solve

from helpers import REF_GAMES, REPO, load_module, table_sha256

SPECS = REPO / "examples" / "specs"

#: (committed spec file, reference-style scalar twin) — the new games.
NEW_GAMES = [
    ("gomoku_4x3x3.json", "gomoku_4x3x3.py"),
    ("mnk_3x3x3_misere.json", "mnk_333_misere.py"),
]


def _doc(name="g", w=3, h=3, family="place", win=None, symmetry=None):
    doc = {
        "gamedsl": 1,
        "name": name,
        "board": {"width": w, "height": h},
        "moves": {"family": family},
        "win": win or {"kind": "k_in_line", "k": 3},
    }
    if symmetry is not None:
        doc["symmetry"] = symmetry
    return doc


# ------------------------------------------------------------ spec identity


def test_canonical_hash_stable_across_spellings():
    """Defaults, key order, and direction aliases never change the hash —
    only the rules do."""
    a = GameSpec.from_dict(_doc(win={"kind": "k_in_line", "k": 3,
                                     "directions": ["e", "n", "ne", "se"],
                                     "misere": False}))
    b = GameSpec.from_dict({
        "name": "g",
        "board": {"width": 3, "height": 3},
        "win": {"k": 3, "directions": ["w", "s", "sw", "nw"]},
    })
    assert a == b
    assert a.spec_hash == b.spec_hash
    for mutated in (
        _doc(win={"k": 2}),
        _doc(name="g2"),
        _doc(w=4),
        _doc(win={"k": 3, "misere": True}),
        _doc(win={"k": 3, "exact": True}),
        _doc(win={"k": 3, "directions": ["e", "n"]}),
        _doc(symmetry=["mirror_h", "transpose"]),
    ):
        assert GameSpec.from_dict(mutated).spec_hash != a.spec_hash, mutated


@pytest.mark.parametrize("breaker", [
    {"extra_key": 1},
    {"name": None},
    {"board": {"width": 3}},
    {"board": {"width": 3, "height": True}},
    {"board": {"width": 0, "height": 3}},
    {"moves": {"family": "slide"}},
    {"win": {"kind": "count", "k": 3}},   # schema-reserved, not compilable
    {"win": {"kind": "capture", "k": 3}},
    {"win": {"kind": "k_in_line", "k": 0}},
    {"win": {"k": 3, "directions": []}},
    {"win": {"k": 3, "directions": ["x"]}},
    {"symmetry": ["spiral"]},
    {"gamedsl": 99},
])
def test_from_dict_rejects(breaker):
    doc = _doc()
    doc.update(breaker)
    with pytest.raises(SpecError):
        GameSpec.from_dict(doc)


def test_spec_problem_catalogue():
    """Each GS finding fires on its minimal trigger, with the documented
    severity."""
    def codes(spec):
        return {(p["code"], p["severity"]) for p in spec_problems(spec)}

    # GS101: does not fit uint64 packing
    assert ("GS101", "error") in codes(
        GameSpec(name="g", width=8, height=8, family="drop", k=4))
    # GS103: no direction fits a k-window
    assert ("GS103", "error") in codes(
        GameSpec(name="g", width=3, height=3, k=5))
    # GS104: some (not all) directions dead
    dead = codes(GameSpec(name="g", width=3, height=4, k=4))
    assert ("GS104", "warning") in dead and ("GS103", "error") not in dead
    # GS105: generator incompatible with gravity / non-square board
    assert ("GS105", "error") in codes(
        GameSpec(name="g", width=4, height=4, family="drop", k=4,
                 symmetry=("mirror_v",)))
    assert ("GS105", "error") in codes(
        GameSpec(name="g", width=4, height=3, k=3,
                 symmetry=("transpose",)))
    # GS106: generators don't preserve an asymmetric direction set
    assert ("GS106", "error") in codes(
        GameSpec(name="g", width=3, height=3, k=3, directions=("ne",),
                 symmetry=("mirror_h",)))
    # GS108: exact-k has no drop lowering
    assert ("GS108", "error") in codes(
        GameSpec(name="g", width=4, height=4, family="drop", k=3,
                 exact=True))
    # GS109: k=1 is trivially won
    assert ("GS109", "warning") in codes(
        GameSpec(name="g", width=3, height=3, k=1))
    # clean spec: no findings at all
    assert spec_problems(GameSpec(name="g", width=3, height=3, k=3)) == []


def test_committed_specs_are_clean():
    for path in sorted(SPECS.glob("*.json")):
        errors = [f for f in lint_file(str(path))
                  if f["severity"] == "error"]
        assert errors == [], path


def test_compile_refuses_error_specs():
    with pytest.raises(SpecError) as e:
        compile_spec(GameSpec(name="g", width=3, height=3, k=5))
    assert "GS103" in str(e.value)


# ------------------------------------------------------------- byte parity


#: hand-written registry spec vs equivalent GameSpec (committed file
#: where one exists; sym variants as inline docs).
PARITY_CASES = [
    ("tictactoe", str(SPECS / "tictactoe_3x3.json")),
    ("connect4:w=4,h=4", str(SPECS / "connect4_4x4.json")),
    ("tictactoe:sym=1",
     _doc(name="tictactoe_3x3x3_sym",
          symmetry=["mirror_h", "transpose"])),
    ("connect4:w=4,h=3,sym=1",
     _doc(name="connect4_4x3_sym", w=4, h=3, family="drop",
          win={"k": 4}, symmetry=["mirror_h"])),
]


@pytest.mark.parametrize(
    "hand_spec,compiled_src", PARITY_CASES,
    ids=[c[0] for c in PARITY_CASES])
def test_compiled_tables_byte_identical(hand_spec, compiled_src):
    """The acceptance bar: a compiled spec's solved table is sha256-equal
    to the hand-written module's — masks, smears, symmetry group and all."""
    if isinstance(compiled_src, str):
        game = compile_spec(load_spec(compiled_src))
    else:
        game = compile_spec(GameSpec.from_dict(compiled_src))
    hand = Solver(get_game(hand_spec)).solve()
    compiled = Solver(game).solve()
    assert table_sha256(hand) == table_sha256(compiled)
    assert (hand.value, hand.remoteness) == (
        compiled.value, compiled.remoteness)


def test_drop_strides_are_derived_not_hardcoded():
    """The compiler's smear strides come from the adjacency directions:
    the full compass on (w, h) must reproduce connect4's hand-derived
    {1, h, h+1, h+2}, and a direction subset must drop the matching
    strides."""
    full = compile_spec(GameSpec.from_dict(
        _doc(name="d", w=5, h=4, family="drop", win={"k": 4})))
    assert tuple(int(d) for d in full._dirs) == (1, 4, 5, 6)
    ortho = compile_spec(GameSpec.from_dict(
        _doc(name="d", w=5, h=4, family="drop",
             win={"k": 4, "directions": ["e", "n"]})))
    assert tuple(int(d) for d in ortho._dirs) == (1, 5)


# ------------------------------------------------------- staleness plumbing


def test_spec_hash_flows_into_kernel_cache_key(tmp_path):
    """A rules change (same name, same shapes) must miss the kernel
    cache: the canonical hash participates in engine._cache_key."""
    path = tmp_path / "game.json"
    path.write_text(json.dumps(_doc(name="mutant")))
    g1 = get_game(str(path))
    path.write_text(json.dumps(_doc(name="mutant", win={"k": 2})))
    g2 = get_game(str(path))
    assert g1.name == g2.name and g1.state_bits == g2.state_bits
    assert g1.cache_key != g2.cache_key
    k1 = _cache_key(g1, "forward", (1024,), None)
    k2 = _cache_key(g2, "forward", (1024,), None)
    assert k1 != k2
    # ... and an unchanged spec re-read from disk HITS the cache.
    g3 = get_game(str(path))
    assert g3.cache_key == g2.cache_key
    assert _cache_key(g3, "forward", (1024,), None) == k2


def test_mutated_spec_fails_check_db_same_as(tmp_path):
    """The DB half of the staleness contract: two exports of the same
    path with different rules disagree on spec_sha256, and the CLI gate
    (tools/check_db.py --same-as) exits nonzero."""
    path = tmp_path / "game.json"
    db1, db2 = tmp_path / "db1", tmp_path / "db2"
    path.write_text(json.dumps(_doc(name="mutant")))
    export_result(Solver(get_game(str(path))).solve(), db1, str(path))
    path.write_text(json.dumps(_doc(name="mutant", win={"k": 2})))
    export_result(Solver(get_game(str(path))).solve(), db2, str(path))
    diffs = db_equal(db1, db2)
    assert any(d.startswith("spec_sha256") for d in diffs), diffs
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_db.py"),
         str(db1), "--same-as", str(db2), "--quiet"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "spec_sha256" in proc.stderr
    # Sanity: the gate passes against itself.
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_db.py"),
         str(db1), "--same-as", str(db1), "--quiet"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0


# ------------------------------------------- new games: DB oracle + serve


@pytest.fixture(scope="module")
def gamedsl_db(tmp_path_factory):
    """Lazy per-spec cache: (SolveResult, DbReader, oracle table, dir)."""
    built = {}

    def get(spec_file, ref_file):
        if spec_file not in built:
            d = tmp_path_factory.mktemp("gamedsl_db")
            spec_path = str(SPECS / spec_file)
            result = Solver(get_game(spec_path)).solve()
            export_result(result, d, spec_path)
            _, _, oracle = oracle_solve(load_module(REF_GAMES / ref_file))
            built[spec_file] = (result, DbReader(d), oracle, d)
        return built[spec_file]

    yield get
    for _, reader, _, _ in built.values():
        reader.close()


@pytest.mark.parametrize("spec_file,ref_file", NEW_GAMES)
def test_new_game_db_roundtrip_matches_oracle(gamedsl_db, spec_file,
                                              ref_file):
    """The pure-description games clear the same bar as the hand-written
    ones: solve → export-db → lookup == scalar oracle for EVERY
    reachable position."""
    _, reader, oracle, _ = gamedsl_db(spec_file, ref_file)
    positions = np.array(sorted(oracle), dtype=np.uint64)
    values, rem, found = reader.lookup(positions)
    assert found.all(), "reachable positions missing from the DB"
    for i, pos in enumerate(positions):
        assert (int(values[i]), int(rem[i])) == oracle[int(pos)], (
            f"{spec_file}: mismatch at {int(pos):#x}"
        )


@pytest.mark.parametrize("spec_file,ref_file", NEW_GAMES)
def test_new_game_manifest_carries_spec_identity(gamedsl_db, spec_file,
                                                 ref_file):
    _, reader, _, d = gamedsl_db(spec_file, ref_file)
    spec = load_spec(str(SPECS / spec_file))
    manifest = read_manifest(d)
    assert manifest["spec_sha256"] == spec.spec_hash
    assert manifest["game_spec"] == spec.to_doc()
    assert reader.game.name == spec.name


@pytest.mark.parametrize("spec_file,ref_file", NEW_GAMES)
def test_new_game_serve_roundtrip(gamedsl_db, spec_file, ref_file):
    """POST /query answers a sample of every-Nth oracle position
    correctly for the compiled games (the serve path runs the compiled
    canonicalize/expand kernels)."""
    import urllib.request

    _, reader, oracle, _ = gamedsl_db(spec_file, ref_file)
    sample = sorted(oracle)[::max(1, len(oracle) // 128)]
    with QueryServer(reader) as server:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/query",
            data=json.dumps(
                {"positions": [hex(p) for p in sample]}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
    assert len(body["results"]) == len(sample)
    for pos, rec in zip(sample, body["results"]):
        v, r = oracle[pos]
        assert rec["found"], hex(pos)
        assert rec["value"] == value_name(v), hex(pos)
        assert rec["remoteness"] == r, hex(pos)


def test_reader_reconstructs_from_embedded_spec(tmp_path):
    """A gamedsl DB is self-describing: the reader rebuilds the game from
    the manifest's embedded canonical doc even after the original .json
    vanished."""
    path = tmp_path / "ephemeral.json"
    path.write_text((SPECS / "mnk_3x3x3_misere.json").read_text())
    d = tmp_path / "db"
    result = Solver(get_game(str(path))).solve()
    export_result(result, d, str(path))
    path.unlink()
    with DbReader(d) as reader:
        assert reader.game.name == "mnk_3x3x3_misere"
        root = int(np.asarray(reader.game.initial_state()))
        values, rem, found = reader.lookup(
            np.array([root], dtype=np.uint64))
        assert found.all()
        assert (int(values[0]), int(rem[0])) == (
            result.value, result.remoteness)


# ----------------------------------------------------------------- the CLI


def test_cli_solve_spec_flag(capsys):
    """`gamesman solve --spec game.json` solves with zero Python — and
    agrees with the engine's direct answer."""
    from gamesmanmpi_tpu import cli

    rc = cli.main(["solve", "--spec",
                   str(SPECS / "mnk_3x3x3_misere.json")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "game: mnk_3x3x3_misere" in out
    assert "value: TIE" in out


def test_cli_spec_and_game_are_exclusive(capsys):
    from gamesmanmpi_tpu import cli

    assert cli.main(["tictactoe", "--spec", "x.json"]) == 2
    assert "not both" in capsys.readouterr().err
    assert cli.main([]) == 2
    assert "--spec" in capsys.readouterr().err


def test_cli_export_db_spec(tmp_path, capsys):
    from gamesmanmpi_tpu import cli

    out = tmp_path / "db"
    rc = cli.main(["export-db", "--spec",
                   str(SPECS / "mnk_3x3x3_misere.json"),
                   "--out", str(out)])
    assert rc == 0
    manifest = read_manifest(out)
    spec = load_spec(str(SPECS / "mnk_3x3x3_misere.json"))
    assert manifest["spec_sha256"] == spec.spec_hash
    capsys.readouterr()
    assert cli.main(["export-db", "--out", str(tmp_path / "x")]) == 2
    assert "--spec" in capsys.readouterr().err


# ------------------------------------------------------------ lint tooling


def test_spec_lint_tool(tmp_path, capsys):
    spec_lint = load_module(REPO / "tools" / "spec_lint.py")
    # The committed specs lint clean.
    assert spec_lint.main([]) == 0
    capsys.readouterr()
    # A broken spec fails with its GS codes on stdout.
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        _doc(name="bad", w=8, h=8, family="drop", win={"k": 9})))
    assert spec_lint.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "GS101" in out and "GS103" in out
    # Unparseable JSON is a finding (GS001), not a crash.
    bad.write_text("{nope")
    assert spec_lint.main([str(bad)]) == 1
    assert "GS001" in capsys.readouterr().out


def test_gamesman_lint_flags_bad_committed_spec(tmp_path):
    """GM901: a broken spec under examples/specs/ fails gamesman-lint."""
    from gamesmanmpi_tpu.analysis.runner import run_project

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    specs = tmp_path / "examples" / "specs"
    specs.mkdir(parents=True)
    (specs / "bad.json").write_text(json.dumps(
        _doc(name="bad", win={"k": 9})))
    res = run_project(tmp_path)
    got = [(d.id, d.path) for d in res.new]
    assert ("GM901", "examples/specs/bad.json") in got
    # The message carries the underlying GS code.
    assert any("GS103" in d.message for d in res.new
               if d.id == "GM901")
    # Fixing the spec clears the finding.
    (specs / "bad.json").write_text(json.dumps(_doc(name="good")))
    res = run_project(tmp_path)
    assert not [d for d in res.new if d.id == "GM901"]
