#!/usr/bin/env python
"""Static GameSpec validator (CI-runnable).

    python tools/spec_lint.py [SPEC.json ...]

With no arguments, lints every committed spec under examples/specs/.
Runs gamedsl's static validation (gamesmanmpi_tpu/gamedsl/spec.py) —
schema strictness, the board-vs-encoding bit budget (the 63-bit packing
limit), unreachable or
dead win predicates, symmetry generators incompatible with the move
family, and symmetry-closure preservation of the win-line set — without
tracing a kernel or touching an accelerator.

One line per finding:

    examples/specs/bad.json: GS103 error: win predicate is unreachable...

Exit 0 = no errors (warnings are advisory), 1 = error findings,
2 = usage error. The same validation gates `gamesman solve --spec` at
compile time and runs over committed specs in gamesman-lint (GM901);
this tool is the standalone spelling for spec authors.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # tools/ scripts get sys.path[0]=tools/
    sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="spec_lint",
        description="Validate declarative GameSpec files "
        "(docs/GAMEDSL.md).",
    )
    p.add_argument("specs", nargs="*",
                   help="GameSpec .json files (default: examples/specs/*)")
    p.add_argument("--errors-only", action="store_true",
                   help="suppress warning-severity findings")
    args = p.parse_args(argv)

    from gamesmanmpi_tpu.gamedsl.spec import lint_file

    paths = args.specs or sorted(
        glob.glob(os.path.join(_REPO, "examples", "specs", "*.json"))
    )
    if not paths:
        print("error: no spec files to lint", file=sys.stderr)
        return 2
    errors = 0
    for path in paths:
        findings = lint_file(path)
        for f in findings:
            if args.errors_only and f["severity"] != "error":
                continue
            print(f"{path}: {f['code']} {f['severity']}: {f['message']}")
            if f["severity"] == "error":
                errors += 1
        if not findings:
            print(f"{path}: OK")
    if errors:
        print(f"{errors} error finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
