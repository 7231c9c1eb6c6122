"""Docs drift gate: every concrete reference in the operator docs must
resolve against the tree it documents.

Scans README.md, DESIGN.md, and docs/OPERATIONS.md for

* repo paths (``src/repro/...``, ``benchmarks/...``, ``examples/...``,
  ``tests/...``, ``docs/...``, ``tools/...``) and top-level ``*.md``
  mentions — the file or directory must exist;
* dotted module references (``repro.serving.elastic``,
  ``repro.core.program.EngineProgram``) — resolved component by
  component under ``src/``; trailing attribute names on a module are
  fine, and a name re-exported by a package ``__init__.py`` counts; a
  missing *package* component is drift;
* ``make <target>`` invocations inside code spans or fenced blocks —
  the target must exist in the Makefile (prose like "make this fast"
  is not an invocation);
* ``--flag`` tokens — the flag must be declared by some
  ``add_argument`` under ``src/repro/launch/``, ``benchmarks/`` or in
  ``chip_smoke.py`` (plus a small allowlist for flags owned by other
  tools: XLA, pytest).

Pure text scan — no jax import, no repo code import — so it runs in the
lint job in seconds. Exit status 1 lists every dangling reference.

  python tools/docs_check.py            # = make docs-check
"""

from __future__ import annotations

import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DOCS = ("README.md", "DESIGN.md", "docs/OPERATIONS.md")

# Path-looking tokens rooted at a directory this check owns. Generated
# artifacts (BENCH_*.json) are documented but not committed — skipped.
PATH_RE = re.compile(
    r"\b(?:src/repro|benchmarks|examples|tests|docs|tools)"
    r"(?:/[A-Za-z0-9_.*-]+)+")
TOP_MD_RE = re.compile(r"\b([A-Z][A-Z_a-z]*\.md)\b")
MODULE_RE = re.compile(r"\brepro(?:\.[A-Za-z_][A-Za-z0-9_]*)+")
MAKE_RE = re.compile(r"\bmake ([a-z][a-z0-9_-]*)")
FLAG_RE = re.compile(r"(--[a-z][a-z0-9_-]+)")

# Flags that appear in the docs but belong to other tools.
FLAG_ALLOW = {
    "--xla_force_host_platform_device_count",   # XLA_FLAGS
    "--timeout", "--timeout-method", "--last-failed",  # pytest
}


def _declared_flags() -> set[str]:
    flags: set[str] = set(FLAG_ALLOW)
    for pattern in ("src/repro/launch/*.py", "benchmarks/*.py",
                    "tools/*.py", "chip_smoke.py"):
        for py in ROOT.glob(pattern):
            flags.update(FLAG_RE.findall(py.read_text()))
    return flags


def _make_targets() -> set[str]:
    targets: set[str] = set()
    for line in (ROOT / "Makefile").read_text().splitlines():
        m = re.match(r"^([A-Za-z0-9_-]+):", line)
        if m:
            targets.add(m.group(1))
    return targets


def _check_path(tok: str) -> bool:
    tok = tok.rstrip(".,:;")
    if "*" in tok:      # glob mention like benchmarks/baselines/*.json
        return any(ROOT.glob(tok))
    return (ROOT / tok).exists()


def _code_spans(text: str) -> str:
    """Concatenate the document's inline code spans and fenced code
    blocks — the only places ``make <target>`` means an invocation."""
    fenced = re.findall(r"```.*?```", text, flags=re.S)
    inline = re.findall(r"`[^`\n]+`", text)
    return "\n".join(fenced + inline)


def _check_module(ref: str) -> bool:
    """Walk ``repro.a.b.C`` under src/: descend packages; once a
    component resolves to a module file, the rest are attributes (not
    checked), and a name re-exported by the package's ``__init__.py``
    resolves too. A component missing while still inside a package is
    a dangling module reference."""
    parts = ref.split(".")
    cur = ROOT / "src"
    for comp in parts:
        if (cur / comp).is_dir():
            cur = cur / comp
        elif (cur / f"{comp}.py").is_file():
            return True          # rest are attrs on this module
        else:
            init = cur / "__init__.py"
            return (init.is_file()
                    and re.search(rf"\b{re.escape(comp)}\b",
                                  init.read_text()) is not None)
    return True                  # package reference, fully resolved


def main() -> int:
    errors: list[str] = []
    flags = _declared_flags()
    targets = _make_targets()
    for doc in DOCS:
        path = ROOT / doc
        if not path.is_file():
            errors.append(f"{doc}: file missing")
            continue
        text = path.read_text()
        for tok in sorted(set(PATH_RE.findall(text))):
            if not _check_path(tok):
                errors.append(f"{doc}: path {tok!r} does not exist")
        for tok in sorted(set(TOP_MD_RE.findall(text))):
            if not (ROOT / tok).is_file() and not (ROOT / "docs" / tok).is_file():
                errors.append(f"{doc}: document {tok!r} does not exist")
        for ref in sorted(set(MODULE_RE.findall(text))):
            if not _check_module(ref):
                errors.append(f"{doc}: module reference {ref!r} does "
                              f"not resolve under src/")
        for tgt in sorted(set(MAKE_RE.findall(_code_spans(text)))):
            if tgt not in targets:
                errors.append(f"{doc}: make target {tgt!r} not in "
                              f"Makefile")
        for flag in sorted(set(FLAG_RE.findall(text))):
            if flag not in flags:
                errors.append(f"{doc}: flag {flag!r} declared by no "
                              f"CLI under src/repro/launch/ or "
                              f"benchmarks/")
    if errors:
        print(f"[docs-check] {len(errors)} dangling reference(s):")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"[docs-check] OK: {', '.join(DOCS)} resolve against the tree")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
