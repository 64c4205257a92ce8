"""host-sync: no host-blocking materialization in the dispatch region.

Port of the PR-3 ``scripts/check_host_sync.py`` checker, with the
hand-maintained EXPECTED_REGIONS table replaced by a coverage guard the
shared walker DERIVES (it needed manual updates in PRs 5, 6 and 9):

  * any function whose name starts with ``_dispatch`` must not contain a
    call spelled with a blocking/materializing attribute
    (``asarray``/``array``/``device_get``/``block_until_ready``/
    ``item``/``tolist``) — the blocking fetch belongs in the
    retire/fetch helpers, one async hop behind;
  * **derived-coverage guard** (default file set only): a function that
    issues dispatch work — calls ``_async_fetch``, calls a ``_run_*``
    dispatch primitive on an ``.app`` receiver (alias-tracked:
    ``app = self.app`` counts), or drives ``.step``/``.step_many`` on an
    ``.adapter`` receiver — without also materializing (no ``_fetch*``
    helper call and no blocking attribute of its own) IS a dispatch
    region by construction, and must carry the ``_dispatch`` prefix or
    the region lint silently loses it. A rename now moves coverage
    automatically instead of needing a list edit.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Sequence, Tuple

from ..findings import Finding
from ..registry import LintContext, Pass, register
from ..walker import SourceFile, dotted, local_aliases, walk_shallow

BANNED_ATTRS = ("asarray", "array", "device_get", "block_until_ready",
                "item", "tolist")
REGION_PREFIX = "_dispatch"
_RUN_PRIMITIVE = re.compile(r"^_run_[a-z0-9_]+$")

DEFAULT_PATHS = (
    "neuronx_distributed_inference_tpu/serving/adapter.py",
    "neuronx_distributed_inference_tpu/serving/engine/scheduler.py",
    "neuronx_distributed_inference_tpu/serving/speculation/verifier.py",
    "neuronx_distributed_inference_tpu/serving/ragged/planner.py",
    "neuronx_distributed_inference_tpu/serving/ragged/path.py",
    "neuronx_distributed_inference_tpu/serving/fleet/router.py",
    "neuronx_distributed_inference_tpu/serving/fleet/kv_tier.py",
    "neuronx_distributed_inference_tpu/serving/fleet/handoff.py",
    "neuronx_distributed_inference_tpu/serving/fleet/autoscaler.py",
    "neuronx_distributed_inference_tpu/serving/lora_pool.py",
    "neuronx_distributed_inference_tpu/parallel/collectives.py",
    "neuronx_distributed_inference_tpu/resilience/controller.py",
    "neuronx_distributed_inference_tpu/resilience/chaos.py",
)


def region_functions(sf: SourceFile) -> List[str]:
    """Names of every dispatch-region function in the file."""
    return [info.name for info in sf.functions()
            if info.name.startswith(REGION_PREFIX)]


def blocking_calls(tree: ast.AST) -> List[Tuple[int, str, str]]:
    """(lineno, function, attr) for every banned call inside a dispatch
    region function."""
    bad: List[Tuple[int, str, str]] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not node.name.startswith(REGION_PREFIX):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            if isinstance(fn, ast.Attribute) and fn.attr in BANNED_ATTRS:
                bad.append((sub.lineno, node.name, fn.attr))
    return bad


def dispatch_signal(sf: SourceFile, fn: ast.AST) -> Optional[str]:
    """The derived is-this-a-dispatch-region test: returns a description
    of the dispatch work a NON-materializing function issues, or None.
    Functions that fetch (call a ``_fetch*`` helper or a blocking
    attribute themselves) are the synchronous dispatch+fetch shape —
    exempt, because their materialization is local and visible."""
    app_aliases = local_aliases(fn, ".app")
    signal = None
    for node in walk_shallow(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if name is None:
            continue
        head, _, last = name.rpartition(".")
        if last in BANNED_ATTRS or last.startswith("_fetch"):
            return None                        # it materializes: exempt
        if signal is not None:
            continue
        if name == "_async_fetch":
            signal = "starts an async device fetch (_async_fetch)"
        elif _RUN_PRIMITIVE.match(last) and head and (
                head.endswith(".app") or head in app_aliases):
            signal = f"calls the dispatch primitive {name}"
        elif last in ("step", "step_many") and head.endswith(".adapter"):
            signal = f"drives the adapter decode surface ({name})"
    return signal


@register
class HostSyncPass(Pass):
    name = "host-sync"
    description = ("_dispatch regions never materialize device output; "
                   "dispatch-issuing functions must carry the _dispatch "
                   "prefix (derived coverage, no hand-pinned region list)")
    default_paths = DEFAULT_PATHS

    def run(self, ctx: LintContext,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        findings: List[Finding] = []
        guard = paths is None      # derived guard on the default set only
        for sf in self._sources(ctx, paths, findings):
            for lineno, func, attr in blocking_calls(sf.tree):
                findings.append(Finding(
                    self.name, sf.rel, lineno,
                    f".{attr}(...) inside dispatch-region function "
                    f"{func!r} — device output must not be materialized "
                    "before retire/fetch (decode pipeline contract)"))
            if not guard:
                continue
            for info in sf.functions():
                if info.name.startswith(REGION_PREFIX):
                    continue
                signal = dispatch_signal(sf, info.node)
                if signal is not None:
                    findings.append(Finding(
                        self.name, sf.rel, info.node.lineno,
                        f"{info.qualname} {signal} without materializing "
                        "— it is a dispatch region by construction but "
                        "lacks the _dispatch prefix, so the host-sync "
                        "region lint does not cover it; rename it "
                        "_dispatch_* (coverage follows the prefix) or "
                        "move the dispatch into a _dispatch_* helper"))
        return findings
