"""GM3xx — environment-variable registry parity.

The degradation contract for config knobs lives in ``utils/env.py``
(warn-and-default) and ``utils/platform.py`` (platform-auto, strict);
the human registry is ``docs/CONFIG.md``. Three things drift without a
machine check:

| id | finding |
|---|---|
| GM301 | raw ``os.environ`` read (``.get``/``[...]``/``os.getenv``/``in``) outside ``utils/env.py`` — bypasses the shared parsing/degradation contract |
| GM302 | a ``GAMESMAN_*``/``BENCH_*`` var is read but missing from docs/CONFIG.md |
| GM303 | a var documented in CONFIG.md's tables is never read anywhere |

Reads are collected from helper calls (``env_int``/``env_float``/
``env_str``/``env_opt``/``platform_auto_flag``/``platform_auto_bool``,
leading underscores ignored so engine's ``_env_int`` re-export
matches) and from raw reads. Collect-only driver scripts (bench.py —
which deliberately cannot import this package) are scanned textually
for var tokens so their reads count toward GM303 without the scripts
being lint targets.

Writes (``os.environ[k] = v``, ``.setdefault``, ``.pop``) are not
findings: the CLI's flag-mirroring and test setup legitimately set the
environment; the contract under lint is how values are *read*.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from gamesmanmpi_tpu.analysis.diagnostics import Diagnostic
from gamesmanmpi_tpu.analysis.project import (
    CONFIG_MD,
    Project,
    SourceFile,
    attr_chain,
    call_name,
    const_str,
    module_string_consts,
)

#: Helper callables whose first argument is an env-var name.
ENV_HELPERS = {
    "env_int", "env_float", "env_int_strict", "env_str", "env_opt",
    "env_bool", "platform_auto_flag", "platform_auto_bool",
}

#: Files allowed to touch os.environ directly: the helper home and the
#: platform helpers built on it.
RAW_OK_SUFFIXES = ("utils/env.py",)

_VAR_RE = re.compile(r"\b((?:GAMESMAN|BENCH)_[A-Z0-9_]+)\b")

#: CONFIG.md table cells: | `GAMESMAN_X` | ... — the first cell of a
#: row documents the variable; prose mentions don't register a row.
_DOC_ROW_RE = re.compile(r"^\|\s*`((?:GAMESMAN|BENCH)_[A-Z0-9_]+)`\s*\|")


def _is_environ(node: ast.AST) -> bool:
    chain = attr_chain(node)
    if not chain or chain[-1] != "environ":
        return False
    return len(chain) == 1 or chain[-2] == "os"


def _raw_reads(tree: ast.AST):
    """Yield (node, name_or_None) for each raw environ *read*."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in ("os.getenv", "getenv"):
                yield node, _first_str(node)
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and _is_environ(node.func.value)
            ):
                yield node, _first_str(node)
        elif isinstance(node, ast.Subscript) and _is_environ(node.value):
            if isinstance(node.ctx, ast.Load):
                name = None
                if isinstance(node.slice, ast.Constant) and isinstance(
                    node.slice.value, str
                ):
                    name = node.slice.value
                yield node, name
        elif isinstance(node, ast.Compare) and any(
            isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
        ):
            for cmp_ in node.comparators:
                if _is_environ(cmp_):
                    name = None
                    if isinstance(node.left, ast.Constant) and isinstance(
                        node.left.value, str
                    ):
                        name = node.left.value
                    yield node, name


def _first_str(call: ast.Call):
    if call.args and isinstance(call.args[0], ast.Constant) and isinstance(
        call.args[0].value, str
    ):
        return call.args[0].value
    return None


def _helper_reads(src: SourceFile):
    consts = module_string_consts(src.tree)
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node).rsplit(".", 1)[-1].lstrip("_")
        if name in ENV_HELPERS and node.args:
            yield node, const_str(node.args[0], consts)


def check(project: Project) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    reads: Dict[str, Tuple[str, int]] = {}  # var -> first (file, line)

    def note(var, rel, line):
        if var is not None and var not in reads:
            reads[var] = (rel, line)

    for src in project.files:
        if src.tree is None:
            continue
        raw_ok = src.rel.endswith(RAW_OK_SUFFIXES)
        for node, var in _raw_reads(src.tree):
            note(var, src.rel, node.lineno)
            if not raw_ok:
                diags.append(Diagnostic(
                    src.rel, node.lineno, "GM301",
                    "raw os.environ read — go through "
                    "gamesmanmpi_tpu.utils.env (env_int/env_float/"
                    "env_str/env_opt) so parsing and degradation follow "
                    "the shared contract",
                ))
        for node, var in _helper_reads(src):
            note(var, src.rel, node.lineno)

    # Driver scripts outside the lint scope: token scan (their helpers
    # wrap os.environ locally, so AST call matching misses names).
    for src in project.collect_only:
        for i, line in enumerate(src.lines, 1):
            for var in _VAR_RE.findall(line):
                note(var, src.rel, i)

    doc_text = project.config_md
    # Exact-token matching, never substring: GAMESMAN_DENSE_GATHER must
    # not count as documented just because the row of
    # GAMESMAN_DENSE_GATHER_PALLAS_MESH contains it.
    # "Documented" = a table row (first cell) or any backticked mention.
    doc_rows: Set[str] = set()
    for line in doc_text.splitlines():
        m = _DOC_ROW_RE.match(line.strip())
        if m:
            doc_rows.add(m.group(1))
    documented = doc_rows | set(
        re.findall(r"`((?:GAMESMAN|BENCH)_[A-Z0-9_]+)`", doc_text)
    )

    for var, (rel, line) in sorted(reads.items()):
        if _VAR_RE.fullmatch(var) and var not in documented:
            diags.append(Diagnostic(
                rel, line, "GM302",
                f"env var {var} is read here but not documented in "
                f"{CONFIG_MD}",
            ))
    config_rel = CONFIG_MD
    for i, line in enumerate(doc_text.splitlines(), 1):
        m = _DOC_ROW_RE.match(line.strip())
        if m and m.group(1) not in reads:
            diags.append(Diagnostic(
                config_rel, i, "GM303",
                f"{m.group(1)} is documented as an env var but nothing "
                "reads it — stale doc row or dead knob",
            ))
    return diags
