"""The rules that keep a missing chip, a stale binary or a moved cache from
hiding: one compile-cache resolver, measurement paths that fail without a
TPU, interpret-mode kernels only on request, peaks from a ``device_kind``
table, and a native library keyed on its source. All in-process, no
subprocess, no device work."""

import re
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from neuronx_distributed_inference_tpu import native
from neuronx_distributed_inference_tpu.ops import decode_attention
from neuronx_distributed_inference_tpu.utils import compile_cache, device

REPO = Path(__file__).resolve().parent.parent
#: every committed Python source: the top-level scripts, the package,
#: scripts/ and tests/ (not whatever scratch copies sit in ignored dirs)
SOURCES = [*REPO.glob("*.py"),
           *(p for d in ("neuronx_distributed_inference_tpu", "scripts",
                         "tests") for p in (REPO / d).rglob("*.py"))]


# ---------------------------------------------------------------------------
# the compile cache is placed from outside
# ---------------------------------------------------------------------------

def test_cache_dir_env_wins_else_fixed_path_in_checkout(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "outside"))
    assert compile_cache.resolve_compile_cache_dir() == str(tmp_path / "outside")
    monkeypatch.delenv(compile_cache.ENV_VAR)
    default = Path(compile_cache.resolve_compile_cache_dir())
    assert default == REPO / ".jax_cache"          # fixed, inside the checkout
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored                # ... and git-ignored


def test_configure_points_jax_at_the_resolved_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "c"))
        assert compile_cache.configure_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")
        assert (tmp_path / "c").is_dir()
    finally:
        monkeypatch.setenv(compile_cache.ENV_VAR, before)
        assert compile_cache.configure_compile_cache() == before


def test_exactly_one_place_sets_the_cache_dir():
    setter = re.compile(r"""(update|set_cache_dir)\(\s*["']?jax_compilation_cache_dir|set_cache_dir\(""")
    hits = [str(p.relative_to(REPO)) for p in SOURCES
            if p.name != Path(__file__).name and setter.search(p.read_text())]
    assert hits == ["neuronx_distributed_inference_tpu/utils/compile_cache.py"]


# ---------------------------------------------------------------------------
# no chip, no number
# ---------------------------------------------------------------------------

def test_require_tpu_raises_on_cpu():
    with pytest.raises(device.NoAcceleratorError, match="platform='cpu'"):
        device.require_tpu()


def test_the_benchmark_fails_without_a_chip(capsys, monkeypatch):
    """The one place that prints a speed: on the CPU it exits 2 with one
    line on stderr and no result line."""
    monkeypatch.syspath_prepend(str(REPO))
    monkeypatch.syspath_prepend(str(REPO / "benchmark"))
    import run as bench_run
    assert bench_run.main(["--workload", "olmoe-chat-steady"]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err and len(err.strip().splitlines()) == 1


def test_no_second_benchmark_grows_beside_the_first():
    """``benchmark/run.py`` measures and ``PERF_LEDGER.jsonl`` records: no
    other bench / profile / smoke script, no artifact with a timing."""
    strays = [str(p.relative_to(REPO)) for p in SOURCES
              if re.match(r"bench.*\.py|profile_.*\.py|chip_smoke\.py",
                          p.name)]
    # the reference's own latency collector behind ``inference_demo``
    assert strays == ["neuronx_distributed_inference_tpu/utils/benchmark.py"]
    timing = re.compile(r'"[^"]*(wall_ms|_seconds|tokens_per_s)[^"]*"\s*:')
    timed = [p.name for p in (REPO / "artifacts").glob("*.json")
             if timing.search(p.read_text())]
    assert timed == []


def test_every_script_is_loaded_by_a_test():
    """A script that no test loads rots unseen (five did, PR 32)."""
    tests = "".join(p.read_text() for p in (REPO / "tests").glob("*.py"))
    unloaded = [p.name for p in sorted((REPO / "scripts").glob("*.py"))
                if not re.search(rf"\b{p.stem}\b", tests)]
    assert unloaded == []


def test_launcher_hands_its_flags_and_environment_to_jax(monkeypatch):
    """Flags win over ``NXDI_TPU_*``, which win over ``SLURM_*``; exactly that
    reaches ``jax.distributed.initialize`` and the module. Nothing starts."""
    import importlib.util
    import runpy
    spec = importlib.util.spec_from_file_location(
        "nxdi_tpu_launcher", REPO / "scripts" / "nxdi_tpu_launcher.py")
    launcher = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launcher)
    for name, value in (("NXDI_TPU_COORDINATOR", "host0:8476"),
                        ("SLURM_NTASKS", "4"), ("SLURM_PROCID", "3"),
                        ("NXDI_TPU_PROCESS_ID", "2")):
        monkeypatch.setenv(name, value)
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(runpy, "run_module", lambda mod, run_name:
                        calls.append((mod, run_name, list(sys.argv))))
    monkeypatch.setattr(sys, "argv", list(sys.argv))
    assert launcher.main(["--local-device-ids", "0,1", "-m", "mod", "x"]) == 0
    assert calls == [
        {"coordinator_address": "host0:8476", "num_processes": 4,
         "process_id": 2, "local_device_ids": [0, 1]},
        ("mod", "__main__", ["mod", "x"])]
    del calls[:]
    assert launcher.main(["--num-processes", "1", "-m", "mod"]) == 0
    assert calls == [("mod", "__main__", ["mod"])]     # one process: no init


def test_peaks_come_from_the_device_kind_table():
    v5e = device.device_peaks("TPU v5 lite")
    assert (v5e.bf16_tflops, v5e.hbm_gbps) == (197.0, 819.0) and v5e.source
    with pytest.raises(ValueError, match="no peaks on record"):
        device.device_peaks("TPU v9000")
    with pytest.raises(ValueError):
        device.device_peaks(jax.devices()[0].device_kind)   # "cpu"


# ---------------------------------------------------------------------------
# kernels: interpret mode on request only; the SMEM bound is stated
# ---------------------------------------------------------------------------

def test_no_kernel_mode_is_read_off_the_default_backend():
    pkg = REPO / "neuronx_distributed_inference_tpu"
    for p in pkg.rglob("*.py"):
        text = p.read_text()
        assert not re.search(r"interpret\s*=\s*jax\.default_backend", text), p
        for line in text.splitlines():
            if "default_backend()" in line:
                # the one remaining use labels a report, selects nothing
                assert p.name == "observatory.py" and '"backend"' in line, \
                    (p, line)


def test_paged_kernel_states_its_smem_bound():
    sds = jax.ShapeDtypeStruct
    b, mb = 64, 4096                   # 4 * (2 + 64 + 64*4096) B > 1 MiB
    with pytest.raises(ValueError, match="SMEM"):
        jax.eval_shape(
            lambda *a: decode_attention.paged_decode_attention(
                *a, scale=1.0, interpret=True),
            sds((b, 8, 64), np.float32), sds((1, 8, 32, 8, 64), np.float32),
            sds((1, 8, 32, 8, 64), np.float32), sds((b, 8, 64), np.float32),
            sds((b, 8, 64), np.float32), sds((), np.int32),
            sds((b,), np.int32), sds((b, mb), np.int32))


# ---------------------------------------------------------------------------
# the native library is the one the sources describe
# ---------------------------------------------------------------------------

@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ on this box")
def test_native_library_is_keyed_on_source_content(monkeypatch, tmp_path):
    src = tmp_path / "block_allocator.cpp"
    shutil.copy(Path(native._DIR) / "block_allocator.cpp", src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "_build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.delenv("NXDI_TPU_NATIVE", raising=False)
    first = native.library_path()
    assert native.load_library() is not None and Path(first).exists()
    # a newer mtime alone changes nothing ...
    src.touch()
    assert native.library_path() == first
    # ... a changed source is a different library, and the old one goes
    src.write_text(src.read_text() + "\n// changed\n")
    monkeypatch.setattr(native, "_lib", None)
    second = native.library_path()
    assert second != first
    assert native.load_library() is not None
    assert Path(second).exists() and not Path(first).exists()
    # a build that fails is an error, not a quiet switch of allocator
    src.write_text("this is not C++")
    monkeypatch.setattr(native, "_lib", None)
    with pytest.raises(native.NativeBuildError):
        native.load_library()
