#!/usr/bin/env python
"""Docs-drift gate: every public CLI flag must be documented.

`python -m repro.search` (plus its `sweep` and `serve` subcommands) is the
public entry point; README.md and API.md both carry flag tables. Flags have
drifted before (--family/--hidden/--mlp-datasets/--block-p landed in
README.md but not API.md), so this check enforces, without importing any
repo code:

  1. every `--flag` registered via `add_argument(...)` in
     src/repro/search/__main__.py appears in README.md  -> error;
  2. and in API.md                                      -> error;
  3. (--strict) every `--flag` mentioned in a doc's flag tables exists in
     the parsers — catches docs outliving a removed flag.

The parser source is scanned with `ast` rather than imported: the module
pulls in jax at import time and calls `parse_args` inside its entry
functions, and a docs gate should not need an accelerator stack.

Run from the repo root (CI does):  python tools/check_cli_docs.py
"""
from __future__ import annotations

import argparse
import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI_SOURCE = os.path.join("src", "repro", "search", "__main__.py")
DOCS = ("README.md", "API.md")
# a documented flag is any `--word` token; tables write them as `--flag N`
DOC_FLAG_RE = re.compile(r"(--[a-z][a-z0-9-]*)")


def parser_flags(path: str) -> set[str]:
    """All `--option` strings passed to an .add_argument(...) call."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    flags: set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        for arg in node.args:
            if (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                    and arg.value.startswith("--")):
                flags.add(arg.value)
    return flags


def doc_flags(path: str) -> set[str]:
    with open(path, encoding="utf-8") as f:
        return set(DOC_FLAG_RE.findall(f.read()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strict", action="store_true",
                    help="documented flags unknown to the parsers fail too")
    args = ap.parse_args(argv)

    src = os.path.join(REPO, CLI_SOURCE)
    if not os.path.exists(src):
        print(f"check_cli_docs: {CLI_SOURCE} not found", file=sys.stderr)
        return 1
    flags = parser_flags(src)
    if not flags:
        print(f"check_cli_docs: no add_argument flags found in {CLI_SOURCE}",
              file=sys.stderr)
        return 1

    failed = False
    documented: set[str] = set()
    for doc in DOCS:
        doc_path = os.path.join(REPO, doc)
        if not os.path.exists(doc_path):
            print(f"check_cli_docs: {doc} not found", file=sys.stderr)
            return 1
        seen = doc_flags(doc_path)
        documented |= seen
        for flag in sorted(flags - seen):
            failed = True
            print(f"ERROR: {flag} ({CLI_SOURCE}) is undocumented in {doc}",
                  file=sys.stderr)

    # flags documented for OTHER CLIs (benchmarks.run, bench/run.py,
    # tools/check_*.py, chip_smoke.py)
    other_clis = {"--quick", "--workload", "--strict", "--xla",
                  "--four-chips"}
    stale = sorted(documented - flags - other_clis)
    for flag in stale:
        level = "ERROR" if args.strict else "WARN"
        print(f"{level}: docs mention {flag}, which no "
              f"`python -m repro.search` parser registers", file=sys.stderr)
    if args.strict and stale:
        failed = True

    print(f"check_cli_docs: {len(flags)} parser flags checked against "
          f"{', '.join(DOCS)}; {'FAIL' if failed else 'OK'}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
