"""error-paths: the serving surface raises ONLY the typed hierarchy.

Port of the PR-2 ``scripts/check_error_paths.py`` checker: any
``raise ValueError(...)`` / ``raise RuntimeError(...)`` in the serving
files must be one of the ``resilience.errors`` types instead, so an
engine can branch on exception type to pick a recovery path. Bare
re-raises and every other exception class are allowed.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Tuple

from ..findings import Finding
from ..registry import LintContext, Pass, register

BANNED = ("ValueError", "RuntimeError")

DEFAULT_PATHS = (
    "neuronx_distributed_inference_tpu/serving/adapter.py",
    "neuronx_distributed_inference_tpu/serving/engine/queue.py",
    "neuronx_distributed_inference_tpu/serving/engine/scheduler.py",
    "neuronx_distributed_inference_tpu/serving/engine/streams.py",
    "neuronx_distributed_inference_tpu/serving/engine/frontend.py",
    "neuronx_distributed_inference_tpu/serving/speculation/__init__.py",
    "neuronx_distributed_inference_tpu/serving/speculation/proposer.py",
    "neuronx_distributed_inference_tpu/serving/speculation/verifier.py",
    "neuronx_distributed_inference_tpu/serving/ragged/__init__.py",
    "neuronx_distributed_inference_tpu/serving/ragged/planner.py",
    "neuronx_distributed_inference_tpu/serving/ragged/path.py",
    "neuronx_distributed_inference_tpu/serving/fleet/__init__.py",
    "neuronx_distributed_inference_tpu/serving/fleet/router.py",
    "neuronx_distributed_inference_tpu/serving/fleet/kv_tier.py",
    "neuronx_distributed_inference_tpu/serving/fleet/handoff.py",
    "neuronx_distributed_inference_tpu/serving/fleet/aggregator.py",
    "neuronx_distributed_inference_tpu/serving/fleet/autoscaler.py",
    "neuronx_distributed_inference_tpu/serving/lora_pool.py",
    "neuronx_distributed_inference_tpu/modules/block_kv_cache.py",
    "neuronx_distributed_inference_tpu/modules/low_rank.py",
    "neuronx_distributed_inference_tpu/parallel/collectives.py",
    "neuronx_distributed_inference_tpu/resilience/controller.py",
    "neuronx_distributed_inference_tpu/resilience/chaos.py",
)


def banned_raises(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, exception name) for every ``raise`` of a banned builtin."""
    bad: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Raise) or node.exc is None:
            continue
        target = node.exc
        if isinstance(target, ast.Call):
            target = target.func
        if isinstance(target, ast.Name) and target.id in BANNED:
            bad.append((node.lineno, target.id))
    return bad


@register
class ErrorPathsPass(Pass):
    name = "error-paths"
    description = ("serving surface raises only the typed resilience "
                   "hierarchy (no bare ValueError/RuntimeError)")
    default_paths = DEFAULT_PATHS

    def run(self, ctx: LintContext,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        findings: List[Finding] = []
        for sf in self._sources(ctx, paths, findings):
            for lineno, name in banned_raises(sf.tree):
                findings.append(Finding(
                    self.name, sf.rel, lineno,
                    f"raise {name}(...) — use the typed hierarchy in "
                    "neuronx_distributed_inference_tpu/resilience/"
                    "errors.py"))
        return findings
