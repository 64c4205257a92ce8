"""Unified findings model + the ``nxdi-lint-v1`` JSON artifact schema.

Every pass returns a flat list of :class:`Finding`; the driver applies
suppressions, runs the unused-suppression check, and renders one
:class:`Report` — the same object behind the console output, the ``rc``
and the ``--json`` artifact of ``scripts/nxdi_lint.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List

SCHEMA = "nxdi-lint-v1"


@dataclass(frozen=True)
class Finding:
    """One lint finding, anchored to a repo-relative path + line."""
    pass_name: str
    path: str                        # repo-relative posix path
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.pass_name}] {self.message}"

    def to_json(self) -> Dict[str, Any]:
        return {"pass": self.pass_name, "path": self.path,
                "line": self.line, "message": self.message}


@dataclass
class PassStats:
    name: str
    description: str
    files: int = 0
    findings: int = 0
    suppressed: int = 0
    duration_s: float = 0.0


@dataclass
class Report:
    """One driver run: surviving findings, what suppressions absorbed,
    and per-pass accounting."""
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    passes: List[PassStats] = field(default_factory=list)
    files: List[str] = field(default_factory=list)      # union, sorted

    @property
    def rc(self) -> int:
        return 1 if self.findings else 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "schema": SCHEMA,
            "passes": {
                p.name: {"description": p.description, "files": p.files,
                         "findings": p.findings, "suppressed": p.suppressed,
                         "duration_s": round(p.duration_s, 4)}
                for p in self.passes},
            "findings": [f.to_json() for f in self.findings],
            "suppressed": [f.to_json() for f in self.suppressed],
            "files": list(self.files),
            "totals": {"files": len(self.files),
                       "findings": len(self.findings),
                       "suppressed": len(self.suppressed)},
        }
