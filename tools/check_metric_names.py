#!/usr/bin/env python
"""Hygiene checker: metric names follow ``subsystem/name`` and every
one is documented.

The metrics registry (paddle_tpu/profiler/metrics.py) is only an
observability plane if its vocabulary stays coherent: one naming
convention, one documented table. This lint walks ``paddle_tpu/`` and
``bench.py`` ASTs for every LITERAL metric name reaching the
instrumentation APIs —

- ``metrics.declare(name, kind, help)`` registrations (the catalog);
- registry/tracer calls: ``.counter("…")``, ``.gauge("…")``,
  ``.histogram("…")``, ``.instant("…")``, ``.complete("…")`` —

and fails the build when a name violates the convention
(``^[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$``), when a name is used but never
appears in ``docs/observability.md``, when the same name is declared
with two different kinds, or — the ISSUE-13 DEAD-METRIC check — when a
``declare()``\\ d metric is never incremented/set/observed anywhere in
the tree. A metric is live when its literal name reaches a metric API
call, or when it is minted through the prefix-concat idiom
(``registry.counter("serving/" + k)`` — the engine's ``_StatsView``):
a metric call whose first argument is ``"<subsystem>/" + <expr>``
marks the prefix, and a declared name under that prefix counts as live
iff its suffix appears as a string constant in the SAME file (the
``_STAT_KEYS`` tuple) or in the file that DECLARES it (a served model
declares the pass counters it hands the engine through
``cache_spec.StepCounters`` beside the tuple that names them). A
declared name that matches neither is an
error: a declared-but-never-written metric is documentation lying
about instrumentation that does not exist. Dynamic names beyond that
idiom (f-strings over a gauges() dict etc.) are out of scope by
construction — the convention is enforced where names are minted, and
every minted family has a literal ``declare()``.

``--table`` prints the docs metric table GENERATED from the
``declare()`` catalog (name | kind | meaning) — paste into
docs/observability.md; the default mode then keeps the two in sync
forever.

Usage: python tools/check_metric_names.py [--table] [root_dir]
Exit code 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import os
import re
import sys

NAME_RE = re.compile(r"^[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$")
METRIC_CALLS = ("counter", "gauge", "histogram", "instant", "complete")
DOCS = os.path.join("docs", "observability.md")


def _const_str(node):
    return node.value if isinstance(node, ast.Constant) \
        and isinstance(node.value, str) else None


def scan_file(path):
    """(declares, uses, prefixes, strings) — declares: [(name, kind,
    help, line)]; uses: [(name, line)] for literal metric-API first
    args; prefixes: {"serving/", ...} from prefix-concat metric calls
    (``counter("serving/" + k)``); strings: every string constant in
    the file (suffix liveness for the prefix-concat idiom)."""
    try:
        tree = ast.parse(open(path, encoding="utf-8").read(),
                         filename=path)
    except SyntaxError as e:
        return [], [(f"<unparseable: {e}>", 0)], set(), set()
    declares, uses = [], []
    prefixes, strings = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, str):
            strings.add(node.value)
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        fname = func.attr if isinstance(func, ast.Attribute) else \
            func.id if isinstance(func, ast.Name) else None
        if fname == "declare" and len(node.args) >= 2:
            name = _const_str(node.args[0])
            kind = _const_str(node.args[1])
            help_ = _const_str(node.args[2]) \
                if len(node.args) >= 3 else ""
            if name is not None:
                declares.append((name, kind or "?", help_ or "",
                                 node.lineno))
        elif fname in METRIC_CALLS and node.args:
            name = _const_str(node.args[0])
            if name is not None and "/" in name:
                uses.append((name, node.lineno))
            elif isinstance(node.args[0], ast.BinOp) \
                    and isinstance(node.args[0].op, ast.Add):
                left = _const_str(node.args[0].left)
                if left is not None and left.endswith("/"):
                    prefixes.add(left)
    return declares, uses, prefixes, strings


def collect(root):
    declares, uses = {}, []   # name -> (kind, help, file, line)
    concat = []               # (prefixes, strings) per file
    strings_of = {}           # declaring file -> its string constants
    files = []
    pkg = os.path.join(root, "paddle_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        files.extend(os.path.join(dirpath, f) for f in filenames
                     if f.endswith(".py"))
    bench = os.path.join(root, "bench.py")
    if os.path.exists(bench):
        files.append(bench)
    errors = []
    for path in sorted(files):
        decl, use, prefixes, strings = scan_file(path)
        rel = os.path.relpath(path, root)
        for name, kind, help_, line in decl:
            prev = declares.get(name)
            if prev is not None and prev[0] != kind:
                errors.append(
                    f"{rel}:{line}: {name!r} declared as {kind} but "
                    f"also as {prev[0]} ({prev[2]}:{prev[3]})")
            if prev is None or (help_ and not prev[1]):
                declares[name] = (kind, help_, rel, line)
        uses.extend((name, rel, line) for name, line in use)
        if prefixes:
            concat.append((prefixes, strings))
        if decl:
            strings_of[rel] = strings
    return declares, uses, errors, concat, strings_of


def dead_metrics(declares, uses, concat, strings_of):
    """Declared-but-never-written names (module docstring): not used
    as a literal metric-API arg anywhere, and not mintable through a
    prefix-concat idiom from a suffix constant in the minting file or
    in the declaring one."""
    used = {n for n, _, _ in uses}
    dead = []
    for name in declares:
        if name in used:
            continue
        alive = False
        own = strings_of.get(declares[name][2], ())
        for prefixes, strings in concat:
            for p in prefixes:
                if name.startswith(p) and (name[len(p):] in strings
                                           or name[len(p):] in own):
                    alive = True
                    break
            if alive:
                break
        if not alive:
            dead.append(name)
    return dead


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    table = "--table" in argv
    if table:
        argv.remove("--table")
    root = argv[0] if argv else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    declares, uses, errors, concat, strings_of = collect(root)

    if table:
        print("| metric | kind | meaning |")
        print("|---|---|---|")
        for name in sorted(declares):
            kind, help_, _, _ = declares[name]
            print(f"| `{name}` | {kind} | {' '.join(help_.split())} |")
        return 0

    all_names = {n: (f, ln) for n, (_, _, f, ln) in declares.items()}
    for name, rel, line in uses:
        all_names.setdefault(name, (rel, line))

    for name, (rel, line) in sorted(all_names.items()):
        if not NAME_RE.match(name):
            errors.append(
                f"{rel}:{line}: metric name {name!r} violates the "
                "subsystem/name convention (^[a-z][a-z0-9_]*/"
                "[a-z][a-z0-9_]*$)")

    docs_path = os.path.join(root, DOCS)
    try:
        docs = open(docs_path, encoding="utf-8").read()
    except OSError:
        errors.append(f"{DOCS} missing — the metric table must exist")
        docs = ""
    for name, (rel, line) in sorted(all_names.items()):
        if docs and f"`{name}`" not in docs:
            errors.append(
                f"{rel}:{line}: metric {name!r} is not documented in "
                f"{DOCS} (add a `{name}` row; regenerate with "
                "tools/check_metric_names.py --table)")

    for name in sorted(dead_metrics(declares, uses, concat, strings_of)):
        _, _, rel, line = declares[name]
        errors.append(
            f"{rel}:{line}: metric {name!r} is declared but never "
            "incremented/set/observed anywhere in the tree (dead "
            "metric — instrument it or drop the declare())")

    for e in errors:
        print(e)
    if errors:
        print(f"{len(errors)} metric-name violation(s)")
        return 1
    print(f"metric names clean: {len(all_names)} names "
          f"({len(declares)} declared), all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
