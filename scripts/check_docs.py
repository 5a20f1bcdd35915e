"""Docs gate (CI): DESIGN.md/README.md exist and every `DESIGN.md §<n>` /
`EXPERIMENTS.md §<name>` cross-reference in the tree resolves to a real
section header. Exits 1 listing any dangling reference.

Run: python scripts/check_docs.py  (from the repo root; no deps)
"""

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = ("src", "tests", "benchmarks", "examples", "scripts")
REQUIRED = ("DESIGN.md", "README.md", "EXPERIMENTS.md")

# sections that must exist even if nothing currently cross-references
# them — the documented API surface of record. New subsystems register
# their section here (e.g. §10: streaming ingestion / CSR cache).
REQUIRED_SECTIONS = {
    "DESIGN.md": {"1", "2", "3", "4", "5", "7", "9", "10", "11", "12",
                  "13", "14", "15", "16"},
    "EXPERIMENTS.md": {"Perf", "Memory", "Resume", "Queries"},
}

# README headings other docs/source point operators at by name — same
# contract as REQUIRED_SECTIONS, but README sections are titled, not
# §-numbered.
REQUIRED_HEADINGS = {
    "README.md": {"Running across hosts"},
}


def section_headers(path: str) -> set[str]:
    """§-tokens appearing in markdown headers of ``path``."""
    out: set[str] = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            if line.startswith("#"):
                out.update(re.findall(r"§([\w-]+)", line))
    return out


def iter_source_files():
    for d in SCAN_DIRS:
        for dirpath, _dirnames, filenames in os.walk(os.path.join(ROOT, d)):
            if "__pycache__" in dirpath:
                continue
            for fn in filenames:
                if fn.endswith((".py", ".md", ".yml", ".yaml")):
                    yield os.path.join(dirpath, fn)


def main() -> int:
    errors = []
    for doc in REQUIRED:
        if not os.path.exists(os.path.join(ROOT, doc)):
            errors.append(f"missing required doc: {doc}")
    if errors:
        print("\n".join(errors))
        return 1

    sections = {doc: section_headers(os.path.join(ROOT, doc))
                for doc in ("DESIGN.md", "EXPERIMENTS.md")}
    for doc, required in REQUIRED_SECTIONS.items():
        for miss in sorted(required - sections[doc]):
            errors.append(f"{doc}: missing required section §{miss}")
    for doc, headings in REQUIRED_HEADINGS.items():
        with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
            header_lines = [ln for ln in f if ln.startswith("#")]
        for h in sorted(headings):
            if not any(h in ln for ln in header_lines):
                errors.append(f"{doc}: missing required heading \"{h}\"")
    n_refs = 0
    for path in iter_source_files():
        rel = os.path.relpath(path, ROOT)
        if os.path.samefile(path, os.path.abspath(__file__)):
            continue  # this file's §-strings are patterns, not references
        with open(path, encoding="utf-8") as f:
            text = f.read()
        mentions = [(m.start(), m.group(0))
                    for m in re.finditer(r"(?:DESIGN|EXPERIMENTS)\.md", text)]
        # attribute each §-token to the nearest preceding doc mention
        # within a window — survives line wraps ("...EXPERIMENTS.md\n
        # §Memory") and ranges ("DESIGN.md §3/§4"); a token with no
        # nearby mention (e.g. a bare "§Perf iteration" note) is skipped
        for m in re.finditer(r"§([\w-]+)", text):
            near = [d for p, d in mentions if 0 <= m.start() - p <= 120]
            if not near:
                continue
            doc, ref = near[-1], m.group(1)
            n_refs += 1
            if ref not in sections[doc]:
                lineno = text.count("\n", 0, m.start()) + 1
                errors.append(f"{rel}:{lineno}: dangling {doc} §{ref}")
    if errors:
        print("\n".join(errors))
        print(f"\n{len(errors)} dangling doc reference(s)")
        return 1
    print(f"docs ok: {', '.join(REQUIRED)} present; "
          f"{n_refs} §-references resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
