"""Metric-name drift gate: every registry metric the source emits must
be documented in docs/OBSERVABILITY.md, and every documented metric
must still exist in the source.

Telemetry names are an API: dashboards, alerts and the bench gate key
on them, and a silent rename (or an undocumented addition) breaks
consumers without failing any test. This tool walks the python source
for registry emit sites — ``.counter("name"...)``, ``.gauge(`` and
``.histogram(`` calls (including the ``"a" if cond else "b"``
conditional-name form) — and diffs the emitted set against the
**Metric inventory** table of docs/OBSERVABILITY.md. Run as a tier-1
test (tests/test_check_metrics.py), so CI enforces the sync.

Usage:
    python tools/check_metrics.py [--root /path/to/repo]

Exit code: 0 = in sync, 1 = drift (undocumented or documented-but-gone
metrics listed), 2 = usage error.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

#: source directories scanned for emit sites, relative to the repo root
SOURCE_ROOTS = ("paddle_tpu",)

#: the doc that is the single source of truth for metric names
DOC_PATH = os.path.join("docs", "OBSERVABILITY.md")

#: section marker in the doc: names are collected from backticked
#: tokens between this heading and the next `## ` heading
DOC_SECTION = "## Metric inventory"

_EMIT_RE = re.compile(r"\.(counter|gauge|histogram)\s*\(")
#: escape hatch for computed metric names the literal scanner cannot
#: see: a `# emits-metrics: a, b, c` comment next to the emit site
#: declares them (and the drift gate then also demands they stay
#: documented)
_ANNOT_RE = re.compile(r"#\s*emits-metrics:[ \t]*([a-z0-9_, \t]+)")
#: metric-name shape: lowercase snake_case with >= 1 underscore (help
#: strings are prose — spaces keep them out; single words without an
#: underscore are never metric names here)
_NAME_RE = re.compile(r'["\']([a-z][a-z0-9]*(?:_[a-z0-9]+)+)["\']')


def _first_arg_chunk(text: str, start: int) -> str:
    """The first-argument region of a call starting at ``start`` (the
    char after the open paren): up to the first comma at paren depth 0.
    Captures plain literals AND conditional-name expressions like
    ``"a" if warm else "b"``."""
    depth = 0
    for i in range(start, min(len(text), start + 400)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            if depth == 0:
                return text[start:i]
            depth -= 1
        elif c == "," and depth == 0:
            return text[start:i]
    return text[start:start + 400]


def emitted_metrics(root: str) -> Dict[str, Set[str]]:
    """{metric_name: {file:line, ...}} for every registry emit site
    under the source roots. Dynamic names that are not string literals
    in the first argument cannot be scanned — keep names literal (the
    conditional two-literal form is supported)."""
    out: Dict[str, Set[str]] = {}
    files: List[str] = []
    for src in SOURCE_ROOTS:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, src)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            files += [os.path.join(dirpath, f) for f in filenames
                      if f.endswith(".py")]
    for path in sorted(files):
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            continue
        rel = os.path.relpath(path, root)
        for m in _EMIT_RE.finditer(text):
            chunk = _first_arg_chunk(text, m.end())
            for name in _NAME_RE.findall(chunk):
                line = text.count("\n", 0, m.start()) + 1
                out.setdefault(name, set()).add(f"{rel}:{line}")
        for m in _ANNOT_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            for name in re.split(r"[,\s]+", m.group(1).strip()):
                if name:
                    out.setdefault(name, set()).add(
                        f"{rel}:{line} (annotation)")
    return out


def documented_metrics(root: str) -> Set[str]:
    """Backticked metric names inside the doc's Metric inventory
    section (up to the next ``## `` heading)."""
    path = os.path.join(root, DOC_PATH)
    with open(path) as f:
        text = f.read()
    idx = text.find(DOC_SECTION)
    if idx < 0:
        raise ValueError(
            f"{DOC_PATH} has no {DOC_SECTION!r} section — the drift "
            "gate needs it as the single source of documented names")
    section = text[idx + len(DOC_SECTION):]
    nxt = section.find("\n## ")
    if nxt >= 0:
        section = section[:nxt]
    return {m.group(1)
            for m in re.finditer(r"`([a-z][a-z0-9]*(?:_[a-z0-9]+)+)`",
                                 section)}


def check(root: str) -> Tuple[List[str], Dict[str, Set[str]], Set[str]]:
    """Returns (problems, emitted, documented)."""
    emitted = emitted_metrics(root)
    documented = documented_metrics(root)
    problems: List[str] = []
    for name in sorted(set(emitted) - documented):
        sites = ", ".join(sorted(emitted[name])[:3])
        problems.append(
            f"UNDOCUMENTED metric {name!r} (emitted at {sites}) — add "
            f"it to the {DOC_SECTION!r} table in {DOC_PATH}")
    for name in sorted(documented - set(emitted)):
        problems.append(
            f"DOCUMENTED-BUT-GONE metric {name!r} — no emit site found "
            f"in the source; remove it from {DOC_PATH} (or restore the "
            "emitter)")
    return problems, emitted, documented


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if "--root" in argv:
        i = argv.index("--root")
        try:
            root = argv[i + 1]
        except IndexError:
            print("--root needs a path", file=sys.stderr)
            return 2
        del argv[i:i + 2]
    if argv:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        problems, emitted, documented = check(root)
    except (OSError, ValueError) as e:
        print(f"check_metrics: {e}", file=sys.stderr)
        return 2
    if problems:
        print("METRIC DRIFT:")
        for p in problems:
            print("  " + p)
        return 1
    print(f"metric inventory in sync: {len(emitted)} emitted names, "
          f"{len(documented)} documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
