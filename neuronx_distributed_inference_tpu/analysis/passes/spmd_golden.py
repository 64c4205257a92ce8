"""spmd-golden: the SPMD census golden and the compile lint's pinned
graph set cannot drift apart.

The in-process, no-compile slice of ``scripts/check_spmd_sharding.py``
(the full CPU-mesh compile lint stays in that script — it is minutes of
XLA work, not a sub-second AST pass): the committed
``artifacts/spmd_golden.json`` must carry the expected schema, pin
exactly the graphs the script's ``PINNED`` table compiles (both
directions — a graph added to the code but never ``--update-golden``\\ ed,
or left in the golden after being dropped from the code, is the same
stale-pin class the old hardcoded file counts kept hitting), and every
pinned entry must be well-formed: census count/bytes ints, the requested
shardings as counts, and the jax / jaxlib its census was earned on.
"""

from __future__ import annotations

import ast
import json
from typing import List, Optional, Sequence

from ..findings import Finding
from ..registry import LintContext, Pass, register

LINT_SCRIPT = "scripts/check_spmd_sharding.py"
GOLDEN_PATH = "artifacts/spmd_golden.json"
GOLDEN_SCHEMA = "nxdi-spmd-golden-v2"


def pinned_graphs(tree: ast.AST):
    """(lineno, names) of the module-level ``PINNED`` dict in the compile
    lint — read via AST so this pass never imports jax."""
    for node in tree.body:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target] if isinstance(node, ast.AnnAssign)
                   else [])
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id == "PINNED" and \
                    isinstance(node.value, ast.Dict):
                names = [k.value for k in node.value.keys
                         if isinstance(k, ast.Constant)
                         and isinstance(k.value, str)]
                return node.lineno, names
    return None, []


@register
class SpmdGoldenPass(Pass):
    name = "spmd-golden"
    description = ("artifacts/spmd_golden.json stays schema-valid and in "
                   "sync with check_spmd_sharding's PINNED graph set")
    default_paths = (LINT_SCRIPT, GOLDEN_PATH)

    def run(self, ctx: LintContext,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        script_rel, golden_rel = (paths if paths is not None
                                  else self.default_paths)
        script_sf = ctx.source_for(script_rel)
        golden_sf = ctx.source_for(golden_rel)
        if script_sf is None:
            return [self.missing(str(script_rel))]
        if golden_sf is None:
            return [Finding(self.name, str(golden_rel), 0,
                            "golden is missing — run scripts/"
                            "check_spmd_sharding.py --update-golden")]
        pin_line, pinned = pinned_graphs(script_sf.tree)
        findings: List[Finding] = []
        if not pinned:
            return [Finding(self.name, script_sf.rel, 1,
                            "no module-level PINNED graph table found — "
                            "the compile lint moved without this pass")]
        try:
            golden = json.loads(golden_sf.text)
        except ValueError as e:
            return [Finding(self.name, golden_sf.rel, 1,
                            f"golden is not valid JSON: {e}")]
        if golden.get("schema") != GOLDEN_SCHEMA:
            findings.append(Finding(
                self.name, golden_sf.rel, 1,
                f"schema {golden.get('schema')!r} != {GOLDEN_SCHEMA!r}"))
            return findings
        graphs = golden.get("graphs")
        if not isinstance(graphs, dict):
            return [Finding(self.name, golden_sf.rel, 1,
                            "golden has no 'graphs' table")]
        for name in sorted(set(pinned) - set(graphs)):
            findings.append(Finding(
                self.name, script_sf.rel, pin_line,
                f"pinned graph {name!r} has no golden census — run "
                "check_spmd_sharding.py --update-golden to pin it"))
        for name in sorted(set(graphs) - set(pinned)):
            findings.append(Finding(
                self.name, golden_sf.rel, 1,
                f"golden pins {name!r} but the compile lint no longer "
                "builds it — stale entry; re-earn the golden with a "
                "full --update-golden run"))
        for name, entry in sorted(graphs.items()):
            coll = entry.get("collectives") if isinstance(entry, dict) \
                else None
            if not isinstance(coll, dict):
                findings.append(Finding(
                    self.name, golden_sf.rel, 1,
                    f"golden graph {name!r} has no 'collectives' table"))
                continue
            req, xla = entry.get("requested"), entry.get("xla")
            if not (isinstance(req, dict) and req
                    and all(isinstance(n, int) for n in req.values())
                    and isinstance(xla, dict)
                    and {"jax", "jaxlib"} <= set(xla)):
                findings.append(Finding(
                    self.name, golden_sf.rel, 1,
                    f"golden graph {name!r} lacks its 'requested' sharding "
                    "counts or the 'xla' (jax / jaxlib) its census was "
                    "earned on"))
            for key, c in sorted(coll.items()):
                if not (isinstance(c, dict)
                        and isinstance(c.get("count"), int)
                        and isinstance(c.get("bytes"), int)):
                    findings.append(Finding(
                        self.name, golden_sf.rel, 1,
                        f"golden census {name}/{key} is malformed — "
                        "expected {count: int, bytes: int}"))
        return findings
