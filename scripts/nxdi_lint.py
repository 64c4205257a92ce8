#!/usr/bin/env python
"""nxdi-lint driver: run every static-analysis pass in ONE process.

The unified front door for the framework in
``neuronx_distributed_inference_tpu/analysis/`` — shared AST walker,
``Pass`` registry, per-line ``# nxdi-lint: disable=<pass>`` suppressions
with an unused-suppression check, and the ``nxdi-lint-v1`` ``--json``
artifact. All passes run in-process (no per-lint subprocess, and via
:func:`load_analysis` no jax import either — the whole run is well under
a second against the 870s tier-1 budget).

Passes (see README "Static analysis" for the catalog):

  error-paths, host-sync, metric-names, spmd-golden   (ported checkers)
  donation-safety, aliasing-safety, recompile-hazard  (tracing safety)
  unused-suppression                                   (always-on check)

The old per-checker CLIs (``check_error_paths.py``, ``check_host_sync
.py``, ``check_metric_names.py``) remain as thin back-compat shims over
the same passes; the CPU-mesh compile lint stays in
``check_spmd_sharding.py`` (its static golden/pin consistency slice runs
here as ``spmd-golden``).

Usage::

    python scripts/nxdi_lint.py                    # --all (default)
    python scripts/nxdi_lint.py --passes host-sync,donation-safety
    python scripts/nxdi_lint.py --list             # pass catalog
    python scripts/nxdi_lint.py --all --json /tmp/lint_report.json

Wired into the suite as tier-1 (``tests/test_nxdi_lint.py``).
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
_PKG_DIR = (REPO_ROOT / "neuronx_distributed_inference_tpu" / "analysis")


def load_analysis():
    """Import the analysis package WITHOUT executing the parent
    package's ``__init__`` (which pulls jax): registered under the
    synthetic top-level name ``nxdi_analysis`` so its relative imports
    resolve. Reuses the already-imported package when the caller (e.g.
    the test suite) imported it the normal way."""
    for name in ("nxdi_analysis", "neuronx_distributed_inference_tpu.analysis"):
        if name in sys.modules:
            return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        "nxdi_analysis", _PKG_DIR / "__init__.py",
        submodule_search_locations=[str(_PKG_DIR)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["nxdi_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def run(names=None, repo_root=REPO_ROOT):
    """In-process API (the tests): returns the analysis Report."""
    return load_analysis().run_passes(repo_root, names=names)


def write_artifact(report, path) -> None:
    """THE ``nxdi-lint-v1`` serialization behind ``--json``."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report.to_json(), indent=1,
                               sort_keys=True) + "\n")


def main(argv=()) -> int:
    argv = list(argv)
    analysis = load_analysis()
    if "--list" in argv:
        for name, p in analysis.all_passes().items():
            print(f"{name}: {p.description}")
        print(f"{analysis.UNUSED_PASS}: every nxdi-lint disable comment "
              "still absorbs a finding")
        return 0
    names = None
    if "--passes" in argv:
        i = argv.index("--passes")
        if i + 1 >= len(argv):
            print("nxdi_lint: --passes needs a comma-separated value",
                  file=sys.stderr)
            return 2
        names = [n.strip() for n in argv[i + 1].split(",") if n.strip()]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("nxdi_lint: --json needs a path", file=sys.stderr)
            return 2
        json_path = Path(argv[i + 1])
    try:
        report = analysis.run_passes(REPO_ROOT, names=names)
    except KeyError as e:
        print(f"nxdi_lint: {e.args[0]}", file=sys.stderr)
        return 2
    for f in report.findings:
        print(f"nxdi_lint: {f.render()}", file=sys.stderr)
    if json_path is not None:
        write_artifact(report, json_path)
    n_passes = len(report.passes)
    verdict = "OK" if not report.findings else "FAIL"
    print(f"nxdi_lint: {verdict} ({n_passes} passes, "
          f"{len(report.files)} files, {len(report.findings)} finding(s), "
          f"{len(report.suppressed)} suppressed)")
    return report.rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
