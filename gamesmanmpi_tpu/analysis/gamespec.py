"""GM9xx — committed GameSpec validity.

A GameSpec .json under ``examples/specs/`` is executable configuration:
the CLI compiles it straight into solver kernels (docs/GAMEDSL.md). A
committed spec that fails validation is therefore dead-on-arrival docs —
`gamesman solve --spec` would refuse it with the same findings this
checker reports. The checker runs gamedsl's static validator
(gamesmanmpi_tpu.gamedsl.spec — stdlib-only, no kernel tracing, in
keeping with the runner's never-import-the-code rule for accelerator
safety) over every committed spec and reports error-severity findings;
warnings (e.g. GS104's dead-direction note) are advisory and stay out
of CI.

| id | finding |
|---|---|
| GM901 | committed GameSpec file fails gamedsl validation (the GS* code and message are embedded) |
"""

from __future__ import annotations

from typing import List

from gamesmanmpi_tpu.analysis.diagnostics import Diagnostic
from gamesmanmpi_tpu.analysis.project import Project
from gamesmanmpi_tpu.gamedsl.spec import lint_file

#: repo-relative directory holding the committed spec files
SPEC_DIR = ("examples", "specs")


def check(project: Project) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    spec_dir = project.root.joinpath(*SPEC_DIR)
    if not spec_dir.is_dir():
        return out
    for path in sorted(spec_dir.glob("*.json")):
        rel = path.relative_to(project.root).as_posix()
        for finding in lint_file(str(path)):
            if finding["severity"] != "error":
                continue
            out.append(Diagnostic(
                rel, 1, "GM901",
                f"{finding['code']}: {finding['message']}",
            ))
    return out
