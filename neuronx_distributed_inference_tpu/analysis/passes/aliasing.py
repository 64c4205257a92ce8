"""aliasing-safety: the PR-3 zero-copy scratch race, as a lint.

jax's CPU backend may alias a suitably-aligned numpy array ZERO-COPY
into the running computation: refilling a host scratch buffer that a
still-in-flight async dispatch aliases corrupts that dispatch's input
mid-execution. PR 3 hit exactly this (flaky under the 8-device test
env) and fixed it by double-buffering the scratch fills ping-pong: each
``fill()`` first REBINDS the buffer attributes to the other buffer set,
then writes in place — the set a still-in-flight dispatch aliases is
never rewritten.

This pass encodes that contract structurally, per class in the serving
dispatch layer:

  * **scratch buffer attributes** are derived by the walker: attributes
    assigned from a numpy array constructor (``np.empty/zeros/...``)
    anywhere in the class, or rebound from a buffer container subscript
    (``self.ids, ... = self._bufs[self._cur]`` — the ping-pong flip);
  * in any method other than ``__init__``, an **in-place mutation** of a
    buffer attribute — a subscript store ``self.X[...] = ...`` (via a
    local alias too), or ``self.X`` passed to an in-place filler
    (``*_into(...)``, ``np.copyto``, ``fill_block_table``) — is a
    finding UNLESS the attribute was rebound (plain store to
    ``self.X``) EARLIER in the same method, i.e. the ping-pong swap ran
    first. ``__init__`` is exempt: a buffer that has never been
    dispatched cannot be aliased.

Verified red on a doctored revert of the PR-3 double-buffering fix and
green on the current tree (tests/test_nxdi_lint.py). A fill that is
provably never live across a dispatch can suppress with a reason:
``# nxdi-lint: disable=aliasing-safety``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set

from ..findings import Finding
from ..registry import LintContext, Pass, register
from ..walker import dotted, walk_shallow

NP_CTORS = ("empty", "zeros", "ones", "full", "arange", "asarray", "array",
            "concatenate", "empty_like", "zeros_like", "ones_like",
            "full_like", "copy")
_INPLACE_SINK = re.compile(r"(_into$|^copyto$|^fill_block_table$)")

DEFAULT_PATHS = (
    "neuronx_distributed_inference_tpu/serving/adapter.py",
    "neuronx_distributed_inference_tpu/serving/speculation/verifier.py",
    "neuronx_distributed_inference_tpu/serving/speculation/proposer.py",
    "neuronx_distributed_inference_tpu/serving/engine/scheduler.py",
)


def _np_aliases(sf) -> Set[str]:
    return sf.module_aliases("numpy") or {"np"}


def buffer_attrs(cls: ast.ClassDef, np_names: Set[str]) -> Set[str]:
    """Attribute names of ``cls`` that hold host numpy scratch buffers:
    assigned from a numpy constructor, or rebound (possibly as a tuple)
    from a subscript of another attribute — the double-buffer container
    pattern ``self.a, self.b = self._bufs[i]``."""
    attrs: Set[str] = set()
    for node in ast.walk(cls):
        if not isinstance(node, ast.Assign):
            continue
        value_is_np = _is_np_ctor(node.value, np_names)
        value_is_container = (isinstance(node.value, ast.Subscript)
                              and dotted(node.value.value) is not None
                              and "." in (dotted(node.value.value) or ""))
        if not (value_is_np or value_is_container):
            continue
        stack = list(node.targets)
        while stack:
            t = stack.pop()
            if isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
            elif isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self":
                attrs.add(t.attr)
    return attrs


def _is_np_ctor(node: ast.AST, np_names: Set[str]) -> bool:
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    return (isinstance(fn, ast.Attribute) and fn.attr in NP_CTORS
            and isinstance(fn.value, ast.Name) and fn.value.id in np_names)


def _self_attr(node: ast.AST, attrs: Set[str],
               aliases: Dict[str, str]) -> Optional[str]:
    """Resolve an expression to a tracked buffer attr name: ``self.X``,
    a subscript/slice of it, or a local alias of it."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self" and node.attr in attrs:
        return node.attr
    if isinstance(node, ast.Name):
        return aliases.get(node.id)
    return None


@register
class AliasingSafetyPass(Pass):
    name = "aliasing-safety"
    description = ("in-place scratch-buffer mutation requires a fresh-"
                   "buffer rebind first (ping-pong double-buffering; "
                   "jax CPU zero-copy aliasing race)")
    default_paths = DEFAULT_PATHS

    def run(self, ctx: LintContext,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        findings: List[Finding] = []
        for sf in self._sources(ctx, paths, findings):
            np_names = _np_aliases(sf)
            for cls in sf.classes():
                attrs = buffer_attrs(cls, np_names)
                if not attrs:
                    continue
                for item in cls.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) and \
                            item.name != "__init__":
                        findings.extend(self._check_method(
                            sf.rel, cls.name, item, attrs))
        return findings

    def _check_method(self, rel: str, cls_name: str, fn: ast.AST,
                      attrs: Set[str]) -> List[Finding]:
        findings: List[Finding] = []
        rebound: Dict[str, int] = {}       # attr -> rebind line
        aliases: Dict[str, str] = {}       # local name -> attr
        reported: Set[str] = set()
        for node in sorted(walk_shallow(fn),
                           key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0))):
            if isinstance(node, ast.Assign):
                stack = list(node.targets)
                plain_targets: List[ast.expr] = []
                while stack:
                    t = stack.pop()
                    if isinstance(t, (ast.Tuple, ast.List)):
                        stack.extend(t.elts)
                    else:
                        plain_targets.append(t)
                for t in plain_targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self" and t.attr in attrs:
                        rebound.setdefault(t.attr, t.lineno)
                    elif isinstance(t, ast.Name):
                        # a subscript of a buffer is a VIEW — it shares
                        # the memory, so it aliases the buffer too
                        src = _self_attr(node.value, attrs, aliases)
                        if src is not None:
                            aliases[t.id] = src
                        else:
                            aliases.pop(t.id, None)
            writes = self._inplace_writes(node, attrs, aliases)
            for attr, lineno in writes:
                if attr in reported:
                    continue
                hit = rebound.get(attr)
                if hit is None or hit > lineno:
                    reported.add(attr)
                    findings.append(Finding(
                        self.name, rel, lineno,
                        f"{cls_name}.{fn.name} mutates scratch buffer "
                        f"'self.{attr}' in place without first rebinding "
                        "it to a fresh buffer (ping-pong swap) — a "
                        "still-in-flight async dispatch may zero-copy-"
                        "alias the old buffer (jax CPU), so refilling it "
                        "races the device read; double-buffer like "
                        "_PagedScratch.fill"))
        return findings

    def _inplace_writes(self, node: ast.AST, attrs: Set[str],
                        aliases: Dict[str, str]):
        """(attr, line) in-place mutations at this node: subscript
        stores and in-place-filler call arguments."""
        out = []
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            stack = list(targets)
            while stack:
                t = stack.pop()
                if isinstance(t, (ast.Tuple, ast.List)):
                    stack.extend(t.elts)
                elif isinstance(t, ast.Subscript):
                    attr = _self_attr(t, attrs, aliases)
                    if attr is not None:
                        out.append((attr, t.lineno))
        elif isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            last = name.rsplit(".", 1)[-1]
            if _INPLACE_SINK.search(last):
                for arg in node.args:
                    attr = _self_attr(arg, attrs, aliases)
                    if attr is not None:
                        out.append((attr, node.lineno))
        return out
