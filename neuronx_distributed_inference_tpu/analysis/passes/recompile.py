"""recompile-hazard: silent bucket-ladder cache misses inside traced
code.

Cold-start grew 5.7s -> 14.3s across rounds 1-3 (ROADMAP item 5) and
every miss of the jit cache inside the serving hot path is a multi-
second stall a dashboard only sees as tail latency. This pass derives
the TRACED REGION with the shared walker and flags the constructs that
either crash under tracing or silently fork the graph key:

  * **region derivation**: ``jax.jit(...)`` sites in the jit-site files
    (``application.py``, the speculation stacks) name their roots —
    ``partial(model_base.X, ...)`` resolves into ``model_base.py``,
    bare/partial local names resolve to functions defined in the same
    file (e.g. a ``chain`` closure) — then the region closes over every
    module-level function a traced function calls within its own file,
    plus nested ``def``\\ s (scan/loop bodies);
  * ``.item()`` / ``.tolist()`` anywhere in the region: host
    materialization — a crash on a traced value, a baked-in constant
    (= per-value recompile) on a concrete one;
  * ``float(x)`` / ``int(x)`` where ``x`` mentions a traced name
    (parameters minus config-like ones and jit ``static_argnames``,
    plus locals derived from them): concretization that either raises
    ``TracerConversionError`` or bakes a constant;
  * ``np.*(...)`` (real numpy, alias-resolved) over a traced name: same
    class, via host numpy;
  * iteration over a ``set(...)`` / set literal / ``.keys()`` view in
    the region: nondeterministic order feeding shape math or cache-key
    construction makes equal inputs hash to different graphs;
  * a nested traced function capturing a name the enclosing scope
    mutates with ``+=``-style AugAssign: each trace bakes a different
    Python scalar (closure-capture hazard).

Config-like parameters (``spec``/``cfg``/``tpu_cfg``/... and anything
annotated ``DecoderSpec``/``TpuConfig``/``InferenceConfig``) are static
by contract and never tainted.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..findings import Finding
from ..registry import LintContext, Pass, register
from ..walker import SourceFile, dotted, walk_shallow

JIT_SITE_PATHS = (
    "neuronx_distributed_inference_tpu/models/application.py",
    "neuronx_distributed_inference_tpu/models/speculation.py",
    "neuronx_distributed_inference_tpu/serving/speculation/proposer.py",
)
REGION_PATHS = (
    "neuronx_distributed_inference_tpu/models/model_base.py",
    # quantized-collective call chain: model_base._row_parallel_out ->
    # layers.row_parallel_output -> collectives.quantized_row_parallel
    # (the shard_map ring bodies are traced regions too)
    "neuronx_distributed_inference_tpu/parallel/layers.py",
    "neuronx_distributed_inference_tpu/parallel/collectives.py",
    # sampled-verify call chain: model_base.paged_spec_verify /
    # paged_ragged_step -> sampling_ops.coupled_sample / stream_keys
    # (the coupled gumbel draws trace inside every decode graph)
    "neuronx_distributed_inference_tpu/ops/sampling.py",
) + JIT_SITE_PATHS

CONFIG_PARAM_NAMES = {"self", "spec", "cfg", "config", "tpu_cfg",
                      "tpu_config", "tcfg", "draft_cfg", "draft_spec",
                      "kv_view", "input_norm", "phase", "make_mask",
                      "mlp_kind"}
CONFIG_ANNOTATIONS = {"DecoderSpec", "TpuConfig", "InferenceConfig",
                      "SpeculationConfig", "bool", "int", "str", "float"}


def _static_argnames(call: ast.Call) -> Set[str]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            if isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, str):
                return {kw.value.value}
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                return {e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)}
    return set()


def _partial_root(call: ast.Call) -> Optional[Tuple[str, Set[str]]]:
    """``partial(X, ..., kw=...)`` → (dotted X, baked kwarg names); the
    application's ``_named_partial`` (a partial that keeps X's name for the
    XLA module) counts as one."""
    if not (isinstance(call, ast.Call)
            and (dotted(call.func) or "").rsplit(".", 1)[-1]
            in ("partial", "_named_partial")
            and call.args):
        return None
    name = dotted(call.args[0])
    if name is None:
        return None
    return name, {kw.arg for kw in call.keywords if kw.arg}


def jit_roots(sf: SourceFile) -> List[Tuple[str, Optional[str], Set[str]]]:
    """(root name, module hint or None, static argnames) for every
    ``jax.jit(X, ...)`` site in the file. ``X`` may be a bare name
    (resolved through a same-scope ``fn = partial(...)`` binding — the
    idiom every ``_jit_*`` helper uses), an attribute
    (``model_base.decode_loop``) or an inline ``partial(...)``. Keyword
    arguments baked into the partial count as static (they are bound at
    jit-construction time, not traced)."""
    roots: List[Tuple[str, Optional[str], Set[str]]] = []
    scopes: List[ast.AST] = [sf.tree] + [i.node for i in sf.functions()]
    for scope in scopes:
        partials: dict = {}
        for node in walk_shallow(scope):
            if isinstance(node, ast.Assign):
                pr = _partial_root(node.value) \
                    if isinstance(node.value, ast.Call) else None
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        if pr is not None:
                            partials[t.id] = pr
                        else:
                            partials.pop(t.id, None)
        for node in walk_shallow(scope):
            if not isinstance(node, ast.Call):
                continue
            if (dotted(node.func) or "").rsplit(".", 1)[-1] != "jit":
                continue
            if not node.args:
                continue
            target = node.args[0]
            statics = _static_argnames(node)
            pr = _partial_root(target) if isinstance(target, ast.Call) \
                else None
            if pr is not None:
                name, baked = pr
                statics |= baked
            else:
                name = dotted(target)
                if name in partials:
                    name, baked = partials[name]
                    statics = statics | baked
            if name is None:
                continue
            head, _, last = name.rpartition(".")
            roots.append((last, head or None, statics))
    return roots


def _tainted_params(fn: ast.AST, statics: Set[str]) -> Set[str]:
    tainted: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs +
              ([args.vararg] if args.vararg else []) +
              ([args.kwarg] if args.kwarg else [])):
        ann = ""
        if a.annotation is not None:
            ann = (dotted(a.annotation) or "").rsplit(".", 1)[-1]
        if a.arg in CONFIG_PARAM_NAMES or a.arg in statics or \
                ann in CONFIG_ANNOTATIONS:
            continue
        tainted.add(a.arg)
    return tainted


def _mentions(node: ast.AST, names: Set[str]) -> Optional[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return sub.id
    return None


class _RegionScan:
    """Hazard scan of one traced function (nested defs included, with
    their params tainted too)."""

    def __init__(self, pass_name: str, rel: str, fn: ast.AST,
                 np_names: Set[str], statics: Set[str]):
        self.pass_name = pass_name
        self.rel = rel
        self.fn = fn
        self.np_names = np_names
        self.statics = statics
        self.findings: List[Finding] = []

    def run(self) -> List[Finding]:
        self._scan_scope(self.fn, _tainted_params(self.fn, self.statics),
                         outer_aug=set())
        return self.findings

    def _scan_scope(self, fn: ast.AST, tainted: Set[str],
                    outer_aug: Set[str]):
        tainted = set(tainted)
        aug_here: Set[str] = set()
        assigned_here: Set[str] = set()
        nested: List[ast.AST] = []
        for node in sorted(walk_shallow(fn),
                           key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0))):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.append(node)
                continue
            if isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name):
                aug_here.add(node.target.id)
                assigned_here.add(node.target.id)
            if isinstance(node, ast.Assign):
                has_taint = _mentions(node.value, tainted) is not None
                for tgt in node.targets:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, ast.Name):
                            assigned_here.add(sub.id)
                            if has_taint:
                                tainted.add(sub.id)
            self._hazards(node, tainted)
            # closure-capture hazard: loads of outer AugAssign'd names
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load) and \
                    node.id in outer_aug and node.id not in assigned_here:
                self.findings.append(Finding(
                    self.pass_name, self.rel, node.lineno,
                    f"traced closure reads {node.id!r}, a Python value "
                    "the enclosing scope mutates with augmented "
                    "assignment — each trace bakes a different constant "
                    "into the graph (closure-capture recompile hazard); "
                    "pass it as a traced argument instead"))
        for sub in nested:
            sub_tainted = tainted | _tainted_params(sub, set())
            self._scan_scope(sub, sub_tainted, outer_aug | aug_here)

    def _hazards(self, node: ast.AST, tainted: Set[str]):
        if isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            head, _, last = name.rpartition(".")
            if last in ("item", "tolist") and not node.args:
                self.findings.append(Finding(
                    self.pass_name, self.rel, node.lineno,
                    f".{last}() inside a traced region — host "
                    "materialization: crashes on a traced value, bakes "
                    "a per-value constant (one graph per value) on a "
                    "concrete one"))
            elif name in ("float", "int") and node.args and \
                    _mentions(node.args[0], tainted):
                self.findings.append(Finding(
                    self.pass_name, self.rel, node.lineno,
                    f"{name}(...) over traced value "
                    f"{_mentions(node.args[0], tainted)!r} inside a "
                    "traced region — concretization raises under "
                    "tracing or bakes a per-value constant (bucket-"
                    "ladder cache miss)"))
            elif head in self.np_names and \
                    any(_mentions(a, tainted) for a in node.args):
                self.findings.append(Finding(
                    self.pass_name, self.rel, node.lineno,
                    f"np.{last}(...) over traced value "
                    f"{next(filter(None, (_mentions(a, tainted) for a in node.args)))!r}"
                    " inside a traced region — host numpy forces a "
                    "sync/concretization; use jnp"))
        iters: List[ast.AST] = []
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters.extend(g.iter for g in node.generators)
        for it in iters:
            bad = None
            if isinstance(it, ast.Set):
                bad = "a set literal"
            elif isinstance(it, ast.Call):
                cal = dotted(it.func) or ""
                if cal == "set":
                    bad = "set(...)"
                elif cal.endswith(".keys"):
                    bad = f"{cal}() (unsorted dict view)"
            if bad is not None:
                self.findings.append(Finding(
                    self.pass_name, self.rel, it.lineno,
                    f"iteration over {bad} inside a traced region — "
                    "nondeterministic order feeding graph construction "
                    "makes equal inputs trace different graphs (silent "
                    "jit-cache miss); iterate sorted(...) or a tuple"))


@register
class RecompileHazardPass(Pass):
    name = "recompile-hazard"
    description = ("no host concretization, unordered iteration or "
                   "mutated-closure capture inside jitted/traced regions "
                   "(bucket-ladder jit-cache contract)")
    default_paths = REGION_PATHS

    def run(self, ctx: LintContext,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        findings: List[Finding] = []
        region_paths = list(paths) if paths is not None \
            else list(REGION_PATHS)
        sources = self._sources(ctx, region_paths, findings)
        by_rel = {sf.rel: sf for sf in sources}
        by_stem = {rel.rsplit("/", 1)[-1][:-3]: sf
                   for rel, sf in by_rel.items()}
        # 1) roots from every jit site in the scanned set
        region: Dict[Tuple[str, str], Set[str]] = {}   # (rel, fn) -> statics
        work: List[Tuple[str, str]] = []
        for sf in sources:
            for name, module_hint, statics in jit_roots(sf):
                site = self._resolve(name, module_hint, sf, by_stem)
                if site is None:
                    continue
                key = (site.rel, name)
                if key not in region:
                    region[key] = set()
                    work.append(key)
                region[key] |= statics
        # 2) close over callees ACROSS the scanned set: bare names
        #    (same file / imported-from), and module-attribute calls
        #    whose module stem is a scanned file (model_base.X)
        while work:
            rel, name = work.pop()
            sf = by_rel[rel]
            fn = sf.toplevel_functions().get(name) or \
                sf.function_index().get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cal = dotted(node.func)
                if cal is None:
                    continue
                head, _, last = cal.rpartition(".")
                target = self._resolve(last, head or None, sf, by_stem)
                if target is None:
                    continue
                key = (target.rel, last)
                if key not in region:
                    region[key] = set()
                    work.append(key)
        # 3) hazard-scan every region function once
        for (rel, name), statics in sorted(region.items()):
            sf = by_rel[rel]
            fn = sf.toplevel_functions().get(name) or \
                sf.function_index().get(name)
            if fn is None:
                continue
            findings.extend(_RegionScan(
                self.name, sf.rel, fn, sf.module_aliases("numpy"),
                statics).run())
        return findings

    def _resolve(self, name: str, module_hint: Optional[str],
                 site_sf: SourceFile, by_stem: Dict[str, SourceFile]
                 ) -> Optional[SourceFile]:
        """Which scanned file defines function ``name``: an explicit
        module attribute (``model_base.X``) resolves by file stem, a
        bare name by same-file definition or imported-from lookup
        against the scanned stems."""
        if module_hint:
            sf = by_stem.get(module_hint.rsplit(".", 1)[-1])
            if sf is not None and name in sf.toplevel_functions():
                return sf
            return None
        if name in site_sf.function_index():
            return site_sf
        for stem, sf in by_stem.items():
            if sf is not site_sf and name in site_sf.imported_names(stem) \
                    and name in sf.toplevel_functions():
                return sf
        return None
