#!/usr/bin/env python
"""Counter-name lint (tools/ci.sh ``profiler`` tier).

``profiler.incr`` is strict at runtime — an undeclared name raises — but a
counter site on a cold path can hide a typo until production.  This lint
greps every ``*.py`` in the tree for ``incr`` / ``_incr`` call sites with
a string-literal name and checks each against the declared set: the
``_counters`` dict literal in ``incubator_mxnet_tpu/profiler.py`` (parsed
with ``ast`` — no jax import needed) plus any ``declare_counter("...")``
literals found in the tree.

It ALSO (ISSUE 7) diffs the declared set against the canonical counter
table in ``docs/observability.md`` ("Counter reference" section): a
counter added without a doc row — or documented after removal — fails the
profiler CI tier, so code and doc cannot drift.

And it checks the models' ``jax.named_scope`` names (the ``SCOPE_* = "..."``
literals of ``gluon/model_zoo``): a per-layer metric selects device
operations whose scope CONTAINS its string, so no scope may lie inside
another unless nested in it (``lfm2.conv`` in ``lfm2.conv.core`` is nested;
a ``conv`` beside it would not be), and each is named in
``docs/observability.md``.

Exit 0 = every literal declared AND the doc table in sync; 1 = violations
(listed on stderr).
"""
from __future__ import annotations

import ast
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = ("incubator_mxnet_tpu", "tools", "benchmark", "tests", "example")
INCR_RE = re.compile(r"\b_?incr\(\s*[\"']([A-Za-z0-9_]+)[\"']")
DECLARE_RE = re.compile(r"\bdeclare_counter\(\s*[\"']([A-Za-z0-9_]+)[\"']")


def declared_counters():
    """Keys of the ``_counters = {...}`` literal in profiler.py."""
    path = os.path.join(ROOT, "incubator_mxnet_tpu", "profiler.py")
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "_counters"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            return {ast.literal_eval(k) for k in node.value.keys}
    raise SystemExit("lint_counters: no _counters dict literal in profiler.py")


SCOPE_RE = re.compile(r'^SCOPE_[A-Z0-9_]+ = "([^"]+)"', re.M)
DOC_PATH = os.path.join(ROOT, "docs", "observability.md")
DOC_ROW_RE = re.compile(r"^\|\s*`([A-Za-z0-9_]+)`")


def doc_counters():
    """Counter names from the ``## Counter reference`` table in
    docs/observability.md (first backticked cell of each row)."""
    names = set()
    in_section = False
    with open(DOC_PATH) as f:
        for line in f:
            if line.startswith("## "):
                in_section = line.strip().lower() == "## counter reference"
            elif in_section:
                m = DOC_ROW_RE.match(line)
                if m:
                    names.add(m.group(1))
    return names


def iter_py_files():
    for d in SCAN_DIRS:
        base = os.path.join(ROOT, d)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [x for x in dirnames
                           if x not in (".git", "__pycache__")]
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def main():
    declared = declared_counters()
    files = {p: open(p, errors="replace").read() for p in iter_py_files()}
    for text in files.values():  # pass 1: extensions opt in via declare
        declared |= set(DECLARE_RE.findall(text))
    violations = []
    for path, text in files.items():  # pass 2: check every incr literal
        for i, line in enumerate(text.splitlines(), 1):
            for name in INCR_RE.findall(line):
                if name not in declared:
                    violations.append((os.path.relpath(path, ROOT), i, name))
    if violations:
        for path, line, name in violations:
            print(f"{path}:{line}: undeclared profiler counter {name!r}",
                  file=sys.stderr)
        return 1
    # pass 3: the docs/observability.md counter table must mirror the
    # IN-TREE declared set exactly (declare_counter() extensions are
    # runtime opt-ins — tests register throwaways — and stay out of it)
    intree = declared_counters()
    documented = doc_counters()
    drift = 0
    for name in sorted(intree - documented):
        print(f"docs/observability.md: counter {name!r} declared in "
              "profiler._counters but missing from the Counter reference "
              "table", file=sys.stderr)
        drift += 1
    for name in sorted(documented - intree):
        print(f"docs/observability.md: counter {name!r} documented but not "
              "declared in profiler._counters (stale row?)", file=sys.stderr)
        drift += 1
    # pass 4: the models' named scopes
    zoo = os.path.join("incubator_mxnet_tpu", "gluon", "model_zoo")
    scopes = sorted({name for path, text in files.items()
                     if os.path.relpath(path, ROOT).startswith(zoo)
                     for name in SCOPE_RE.findall(text)})
    doc = open(DOC_PATH).read()
    for a in scopes:
        for b in scopes:
            if a != b and a in b and not b.startswith(a + "."):
                print(f"scope {a!r} lies inside {b!r} without being nested in it: "
                      f"a metric of {a!r} would read {b!r}'s operations", file=sys.stderr)
                drift += 1
        if f"`{a}" not in doc:
            print(f"docs/observability.md: scope {a!r} of the model zoo is not named",
                  file=sys.stderr)
            drift += 1
    if drift:
        return 1
    print(f"lint_counters OK: {len(declared)} declared counters, all "
          f"incr() literals match, doc table in sync "
          f"({len(documented)} rows); {len(scopes)} model scopes apart and named")
    return 0


if __name__ == "__main__":
    sys.exit(main())
