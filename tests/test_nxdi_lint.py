"""nxdi-lint: unified static-analysis framework (tier-1).

Covers: the full in-process ``--all`` run GREEN over the live tree (the
acceptance gate — every encoded invariant holds on today's code), the
``nxdi-lint-v1`` JSON artifact schema, RED-then-green doctored negatives
for each of the three new tracing-safety passes — donation
read-after-dispatch injected into the REAL ``application.py``, the
aliasing pass on a doctored REVERT of the PR-3 double-buffering fix in
the REAL ``adapter.py``, a traced ``.item()`` injected into the REAL
``model_base.py`` — the derived host-sync coverage guard firing on a
``_dispatch_decode`` rename, spmd-golden drift both directions, and
suppression + unused-suppression round-trips. Everything runs
IN-PROCESS (pure AST, no jax, no subprocess): the whole module targets
well under 15s warm.
"""

import importlib
import json
import shutil
import sys
from pathlib import Path

import pytest

from conftest import load_nxdi_lint

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "neuronx_distributed_inference_tpu"

nxdi_lint = load_nxdi_lint()
analysis = nxdi_lint.load_analysis()

ALL_PASSES = ("aliasing-safety", "donation-safety", "error-paths",
              "host-sync", "metric-names", "recompile-hazard",
              "spmd-golden")


@pytest.fixture(scope="module")
def live_report():
    return nxdi_lint.run()


# ---------------------------------------------------------------------------
# the live tree is green, in-process, through the unified driver
# ---------------------------------------------------------------------------

def test_all_passes_green_on_live_tree(live_report):
    assert [f.render() for f in live_report.findings] == []
    assert live_report.rc == 0
    ran = {p.name for p in live_report.passes}
    assert set(ALL_PASSES) <= ran
    assert analysis.UNUSED_PASS in ran


def test_json_artifact_schema(tmp_path, live_report):
    out = tmp_path / "lint.json"
    rc = nxdi_lint.main(["--all", "--json", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["schema"] == "nxdi-lint-v1"
    assert set(ALL_PASSES) <= set(data["passes"])
    for entry in data["passes"].values():
        assert {"description", "files", "findings", "suppressed"} <= \
            set(entry)
    assert data["totals"]["findings"] == 0
    assert data["findings"] == []


def test_driver_cli_surface(tmp_path, capsys):
    assert nxdi_lint.main(["--list"]) == 0
    listed = capsys.readouterr().out
    for name in ALL_PASSES + (analysis.UNUSED_PASS,):
        assert name in listed
    assert nxdi_lint.main(["--passes", "no-such-pass"]) == 2
    assert nxdi_lint.main(["--passes", "error-paths,metric-names"]) == 0


# ---------------------------------------------------------------------------
# donation-safety: red on a doctored read-after-dispatch, green live
# ---------------------------------------------------------------------------

def test_donation_red_on_doctored_application(tmp_path):
    """Doctor the REAL _run_paged: touch the donated cache binding after
    the dispatch consumed it, before the rebind — the retry_safe=False
    state-loss class as a lint finding."""
    src = (PKG / "models" / "application.py").read_text()
    head = ('                out = fn(self.params, self.cache, ids, pos, slots, '
            'table,\n'
            '                         last, sampling_params, rng, **kw)\n')
    rebind = '                self.cache = out["cache"]\n'
    anchor = head + rebind
    assert src.count(anchor) == 1
    doctored = src.replace(
        anchor,
        head + '                jax.block_until_ready(self.cache)   # doctored\n'
        + rebind)
    bad = tmp_path / "application_doctored.py"
    bad.write_text(doctored)
    ctx = analysis.LintContext(tmp_path)
    findings = analysis.get_pass("donation-safety").run(
        ctx, paths=[bad.name])
    assert any("self.cache" in f.message and "consumed" in f.message
               for f in findings), [f.render() for f in findings]
    # ... and the undoctored file is clean (green side of the pin)
    good = tmp_path / "application_live.py"
    good.write_text(src)
    assert analysis.get_pass("donation-safety").run(
        ctx, paths=[good.name]) == []


# ---------------------------------------------------------------------------
# aliasing-safety: RED on a doctored revert of the PR-3 double-buffering
# fix, green on the current tree (acceptance criterion)
# ---------------------------------------------------------------------------

def test_aliasing_red_on_reverted_ping_pong(tmp_path):
    src = (PKG / "serving" / "adapter.py").read_text()
    paged_flip = ("        self._cur ^= 1\n"
                  "        (self.ids, self.pos, self.slots, self.bt,\n"
                  "         self.counts) = self._bufs[self._cur]\n")
    assert paged_flip in src, \
        "the PR-3 ping-pong flip moved — update this revert fixture"
    reverted = src.replace(paged_flip, "")
    bad = tmp_path / "adapter_reverted.py"
    bad.write_text(reverted)
    ctx = analysis.LintContext(tmp_path)
    findings = analysis.get_pass("aliasing-safety").run(
        ctx, paths=[bad.name])
    hit_classes = {f.message.split(".")[0] for f in findings}
    assert "_PagedScratch" in hit_classes, [f.render() for f in findings]
    # green on the live file: the double-buffered fills rebind first
    assert analysis.get_pass("aliasing-safety").run(
        ctx, paths=[str(PKG / "serving" / "adapter.py")]) == []


# ---------------------------------------------------------------------------
# recompile-hazard: red on a traced .item(), green live
# ---------------------------------------------------------------------------

def _fake_region_repo(tmp_path, model_base_src):
    """Minimal fake repo with the REAL application.py (the jit sites)
    and a given model_base.py, under the canonical relative paths."""
    models = tmp_path / "neuronx_distributed_inference_tpu" / "models"
    models.mkdir(parents=True)
    shutil.copy(PKG / "models" / "application.py",
                models / "application.py")
    (models / "model_base.py").write_text(model_base_src)
    return tmp_path


def test_recompile_red_on_traced_item(tmp_path):
    src = (PKG / "models" / "model_base.py").read_text()
    anchor = "    cache_len = kv_view or kv.cache_len_of(cache)"
    assert anchor in src
    doctored = src.replace(
        anchor,
        "    _probe = position_ids.item()   # doctored\n" + anchor, 1)
    root = _fake_region_repo(tmp_path, doctored)
    ctx = analysis.LintContext(root)
    findings = analysis.get_pass("recompile-hazard").run(ctx, paths=[
        "neuronx_distributed_inference_tpu/models/model_base.py",
        "neuronx_distributed_inference_tpu/models/application.py"])
    assert any(".item()" in f.message and "model_base" in f.path
               for f in findings), [f.render() for f in findings]


def test_recompile_hazard_rules_fire(tmp_path):
    """Each hazard rule on a synthetic traced region: concretization
    (float/int), host numpy over a traced value, unordered set/dict
    iteration, mutated-closure capture."""
    (tmp_path / "mb.py").write_text(
        "import numpy as np\n"
        "import jax\n"
        "from functools import partial\n"
        "def traced(spec, params, cache, ids):\n"
        "    v = float(ids)\n"
        "    w = np.asarray(cache)\n"
        "    for key in cache.keys():\n"
        "        pass\n"
        "    i = 0\n"
        "    i += 1\n"
        "    def inner(carry, xs):\n"
        "        return carry + i, xs\n"
        "    return v, w\n"
        "fn = jax.jit(partial(traced, None))\n")
    ctx = analysis.LintContext(tmp_path)
    findings = analysis.get_pass("recompile-hazard").run(
        ctx, paths=["mb.py"])
    msgs = "\n".join(f.message for f in findings)
    assert "float(...) over traced value" in msgs
    assert "np.asarray(...) over traced value" in msgs
    assert "unsorted dict view" in msgs
    assert "closure-capture recompile hazard" in msgs


def test_recompile_region_derivation_is_live(live_report):
    """The traced region is DERIVED, not pinned: every jitted
    model_base root the application wires must be reachable (a vacuously
    green pass would defend nothing)."""
    from pathlib import Path as _P
    sys.path.insert(0, str(REPO / "scripts"))
    mod = importlib.import_module(
        type(analysis.get_pass("recompile-hazard")).__module__)
    ctx = analysis.LintContext(REPO)
    sf = ctx.source("neuronx_distributed_inference_tpu/models/"
                    "application.py")
    roots = {name for name, hint, _ in mod.jit_roots(sf)
             if hint and hint.endswith("model_base")}
    assert {"context_encoding_step", "token_generation_step",
            "decode_loop", "paged_forward_step", "paged_decode_loop",
            "paged_spec_draft_loop", "paged_spec_verify"} <= roots


# ---------------------------------------------------------------------------
# host-sync: derived coverage guard (no hand-maintained region list)
# ---------------------------------------------------------------------------

def _fake_serving_repo(tmp_path, adapter_src):
    serving = tmp_path / "neuronx_distributed_inference_tpu" / "serving"
    (serving / "engine").mkdir(parents=True)
    (serving / "speculation").mkdir()
    (serving / "adapter.py").write_text(adapter_src)
    shutil.copy(PKG / "serving" / "engine" / "scheduler.py",
                serving / "engine" / "scheduler.py")
    shutil.copy(PKG / "serving" / "speculation" / "verifier.py",
                serving / "speculation" / "verifier.py")
    return tmp_path


def test_host_sync_guard_follows_renamed_region(tmp_path):
    """Renaming a dispatch region away from the _dispatch prefix is
    caught by DERIVATION (it still calls _async_fetch), not by a
    hand-pinned name list — the guard that needed manual updates in
    PRs 5, 6 and 9 now maintains itself."""
    src = (PKG / "serving" / "adapter.py").read_text()
    renamed = src.replace("_dispatch_decode", "_issue_decode")
    root = _fake_serving_repo(tmp_path, renamed)
    findings = analysis.get_pass("host-sync").run(
        analysis.LintContext(root))
    assert any("_issue_decode" in f.message and "_dispatch prefix"
               in f.message for f in findings), \
        [f.render() for f in findings]


def test_host_sync_regions_are_discovered(live_report):
    """Every dispatch region the old EXPECTED_REGIONS table hand-pinned
    is discovered by the walker on the live tree."""
    mod = importlib.import_module(
        type(analysis.get_pass("host-sync")).__module__)
    ctx = analysis.LintContext(REPO)
    regions = set()
    for rel in analysis.get_pass("host-sync").default_paths:
        regions.update(mod.region_functions(ctx.source(rel)))
    assert {"_dispatch_decode", "_dispatch_prefill_chunk",
            "_dispatch_engine_pass", "_dispatch_spec_draft",
            "_dispatch_propose", "_dispatch_spec_verify"} <= regions


# ---------------------------------------------------------------------------
# spmd-golden: pin <-> golden drift, both directions
# ---------------------------------------------------------------------------

def _fake_golden_repo(tmp_path, golden):
    (tmp_path / "scripts").mkdir()
    (tmp_path / "artifacts").mkdir()
    shutil.copy(REPO / "scripts" / "check_spmd_sharding.py",
                tmp_path / "scripts" / "check_spmd_sharding.py")
    (tmp_path / "artifacts" / "spmd_golden.json").write_text(
        json.dumps(golden))
    return tmp_path


def test_spmd_golden_drift_red_both_ways(tmp_path):
    golden = json.loads(
        (REPO / "artifacts" / "spmd_golden.json").read_text())
    # drop a pinned graph AND add a stale one
    dropped = next(iter(sorted(golden["graphs"])))
    doctored = {**golden, "graphs": {
        **{k: v for k, v in golden["graphs"].items() if k != dropped},
        "ghost_graph_dp9": {"collectives": {}},
    }}
    root = _fake_golden_repo(tmp_path, doctored)
    findings = analysis.get_pass("spmd-golden").run(
        analysis.LintContext(root))
    msgs = "\n".join(f.message for f in findings)
    assert dropped in msgs and "no golden census" in msgs
    assert "ghost_graph_dp9" in msgs and "stale" in msgs
    # an entry without its requested shardings or its XLA stamp is red
    assert "lacks its 'requested' sharding counts" in msgs


# ---------------------------------------------------------------------------
# metric-names label contract: rename-red (ISSUE 16)
# ---------------------------------------------------------------------------

def test_label_contract_red_on_undocumented_label(tmp_path):
    """Rename-red for the label contract: strip one backticked label
    from the REAL README row of a labeled metric — the pass must name
    both the metric and the missing label."""
    metrics_src = (PKG / "telemetry" / "metrics.py").read_text()
    readme = (REPO / "README.md").read_text()
    assert "| `nxdi_hbm_kv_bytes` | gauge | `state`" in readme
    doctored = readme.replace(
        "| `nxdi_hbm_kv_bytes` | gauge | `state`",
        "| `nxdi_hbm_kv_bytes` | gauge | state")   # un-backtick the label
    (tmp_path / "metrics.py").write_text(metrics_src)
    (tmp_path / "README.md").write_text(doctored)
    findings = analysis.get_pass("metric-names").run(
        analysis.LintContext(tmp_path),
        paths=(str(tmp_path / "metrics.py"), str(tmp_path / "README.md")))
    msgs = [f.message for f in findings]
    assert any("nxdi_hbm_kv_bytes" in m and "`state`" in m for m in msgs)
    # and ONLY the doctored label — the live tree's rows all conform
    assert all("nxdi_hbm_kv_bytes" in m for m in msgs)


# ---------------------------------------------------------------------------
# suppressions: absorb a finding, and go stale loudly
# ---------------------------------------------------------------------------

def test_suppression_and_unused_suppression_roundtrip(tmp_path):
    (tmp_path / "bad.py").write_text(
        "def f():\n"
        "    raise ValueError('x')  # nxdi-lint: disable=error-paths\n"
        "def g():\n"
        "    # nxdi-lint: disable=error-paths\n"
        "    raise RuntimeError('y')\n")
    report = analysis.run_passes(
        tmp_path, names=["error-paths"],
        overrides={"error-paths": ["bad.py"]})
    # both spellings (same-line and standalone-comment) absorb
    assert report.findings == [] and len(report.suppressed) == 2
    assert report.rc == 0

    (tmp_path / "stale.py").write_text(
        "def f():\n"
        "    return 1  # nxdi-lint: disable=error-paths\n")
    report = analysis.run_passes(
        tmp_path, names=["error-paths"],
        overrides={"error-paths": ["bad.py", "stale.py"]})
    unused = [f for f in report.findings
              if f.pass_name == analysis.UNUSED_PASS]
    assert len(unused) == 1 and unused[0].path == "stale.py"
    assert report.rc == 1
    # a suppression naming a pass that did NOT run is not "unused"
    (tmp_path / "other.py").write_text(
        "def f():\n"
        "    return 1  # nxdi-lint: disable=aliasing-safety\n")
    report = analysis.run_passes(
        tmp_path, names=["error-paths"],
        overrides={"error-paths": ["bad.py", "other.py"]})
    assert all(f.pass_name != analysis.UNUSED_PASS
               for f in report.findings)


# ---------------------------------------------------------------------------
# back-compat shims: CWD path resolution, non-.py inputs, --list-regions
# ---------------------------------------------------------------------------

def _load_script(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_shim_argv_paths_resolve_against_cwd(tmp_path, monkeypatch, capsys):
    """FILE arguments resolve against CWD like the old standalone CLIs —
    a shim run from outside the repo lints the user's file, not a
    same-named repo file (or a phantom 'missing')."""
    (tmp_path / "bad.py").write_text(
        "def f():\n    raise ValueError('x')\n")
    monkeypatch.chdir(tmp_path)
    cep = _load_script("check_error_paths")
    assert cep.main(["bad.py"]) == 1
    assert "bad.py" in capsys.readouterr().err


def test_non_python_input_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "notes.txt").write_text("not python at all {{{\n")
    ctx = analysis.LintContext(tmp_path)
    findings = analysis.get_pass("error-paths").run(
        ctx, paths=["notes.txt"])
    assert [f for f in findings if "not parseable as Python" in f.message]


def test_metric_names_shim_accepts_non_py_metrics_copy(tmp_path):
    """The old CLI ast.parse'd any path regardless of extension."""
    shutil.copy(PKG / "telemetry" / "metrics.py",
                tmp_path / "metrics_copy.txt")
    cmn = _load_script("check_metric_names")
    assert cmn.main(["--metrics", str(tmp_path / "metrics_copy.txt")]) == 0


def test_host_sync_list_regions_still_lints(tmp_path, capsys):
    """--list-regions lists AND lints (the old CLI did both): a CI step
    using it must not report success on a tree with a violation."""
    chs = _load_script("check_host_sync")
    assert chs.main(["--list-regions"]) == 0
    assert "_dispatch_decode" in capsys.readouterr().out
    bad = tmp_path / "adap.py"
    bad.write_text(
        "class A:\n"
        "    def _dispatch_decode(self):\n"
        "        out = self.app._run_decode(1)\n"
        "        return out.block_until_ready()\n")
    assert chs.main(["--list-regions", str(bad)]) == 1
    captured = capsys.readouterr()
    assert "_dispatch_decode" in captured.out      # still listed
    assert "block_until_ready" in captured.err     # and still linted
