"""From a configuration file to a warm, served application — and the logit
gate that runs before it.

Everything here goes through the program's public entry points:
``models.family.get_family`` -> ``PagedCausalLMApplication`` ->
``serving.warmup.precompile`` -> ``PagedEngineAdapter`` -> ``ServingEngine``
-> ``ServingFrontend``. Nothing is specific to one family: a configuration
of any registered family is a new file under ``configs/``, and the plain
reference of its architecture a file ``references/<model_type>.py`` found by
name (:func:`load_reference`).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import types
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

#: keys of a configuration file that are the harness's, not the model's
HARNESS_KEYS = ("family", "source", "reduced", "reduced_why", "assumed",
                "deployment", "chips", "tp", "dtype", "serve",
                "pool_arithmetic", "adapter", "gate")


#: where BENCHMARK.json is read and where a file of the benchmark (cell,
#: configuration, mix, reference, a layer metric's .json) is looked up first;
#: the tests point it at a toy copy (tests/toy), whose own files come before
#: this directory's.
DATA_ROOT = ROOT


def data_dirs() -> List[str]:
    """Where a file of the benchmark is looked for, in order."""
    return [os.path.join(DATA_ROOT, "benchmark"), BENCH_DIR]


def find_file(*parts: str) -> Optional[str]:
    """The first file at ``parts`` under :func:`data_dirs`, or None."""
    for base in data_dirs():
        path = os.path.join(base, *parts)
        if os.path.exists(path):
            return path
    return None


def load_json(*parts: str) -> Dict[str, Any]:
    """A data file of the benchmark, by its path under ``benchmark/``."""
    path = find_file(*parts)
    if path is None:
        raise FileNotFoundError(
            f"no {os.path.join(*parts)} under benchmark/: a cell, "
            "configuration, mix or layer metric is a data file found by its "
            "name")
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> types.ModuleType:
    """The Python file at ``path`` as a module of its own (a reference, a
    layer metric's reader): executed anew at every call, registered nowhere."""
    stem = os.path.splitext(os.path.basename(path))[0]
    name = "benchmark_file_" + "".join(c if c.isalnum() else "_"
                                       for c in stem)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: the model types ``harness/reference.py`` + ``harness/weights.py`` cover
BUILTIN_REFERENCES = ("olmoe", "mistral")


def load_reference(model_type: str):
    """The plain reference of an architecture, found by its ``model_type``:
    the file ``references/<model_type>.py`` (looked up as :func:`load_json`
    looks up data files), else the built-in pair for the types it covers.
    What it exports — ``forward(cfg, w, ids, with_margins=False)`` and
    ``weight_shapes(cfg)`` — is set out in ``benchmark/README.md``."""
    path = find_file("references", model_type + ".py")
    if path is not None:
        return load_module(path)
    if model_type in BUILTIN_REFERENCES:
        from . import reference, weights
        return types.SimpleNamespace(forward=reference.forward,
                                     weight_shapes=weights.weight_table)
    raise FileNotFoundError(
        f"no reference for model_type {model_type!r}: add "
        f"benchmark/references/{model_type}.py with forward(cfg, w, ids, "
        "with_margins=False) and weight_shapes(cfg) (benchmark/README.md, "
        "'A reference')")


def hf_config(cfg: Dict[str, Any],
              overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The model's own keys of a configuration file, with ``overrides``
    (published keys, as ``gate.config`` gives them) in place of the file's."""
    hf = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    hf.update(overrides or {})
    return hf


def gate_overrides(gate: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys that the gate's twin replaces: ``gate["config"]``,
    or ``gate["layers"]`` as the short form of ``{"num_hidden_layers": n}``
    (a model with a layer pattern cuts the pattern with its depth, so it
    spells out ``config``)."""
    if ("config" in gate) == ("layers" in gate):
        raise ValueError("a configuration's gate gives either 'config' (a "
                         "dict of published keys) or 'layers', not both and "
                         f"not neither: {sorted(gate)}")
    if "config" in gate:
        return dict(gate["config"])
    return {"num_hidden_layers": gate["layers"]}


def build_app(cfg: Dict[str, Any], *,
              overrides: Optional[Dict[str, Any]] = None,
              serve: Dict[str, Any] | None = None, **tcfg_kw):
    """A paged application of ``cfg``'s family on the first ``cfg['tp']``
    devices. ``overrides`` replaces published keys (the gate's twin:
    :func:`gate_overrides`); ``serve`` replaces the configuration's serving
    shape."""
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.family import get_family
    family = get_family(cfg["family"])
    hf = hf_config(cfg, overrides)
    tcfg = TpuConfig(tp_degree=cfg["tp"], dtype=cfg["dtype"],
                     **(cfg["serve"] if serve is None else serve), **tcfg_kw)
    return PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                    family)


def warm_widths(cfg: Dict[str, Any], mix: Dict[str, Any]) -> List[int]:
    """The step widths a cell's traffic can reach: 1 (decode) and every
    prefill bucket some chunk of some prompt can land in. The adapter walks a
    prompt in chunks of the largest bucket and packs the remainder into the
    smallest bucket that covers it."""
    buckets = sorted(cfg["serve"]["context_encoding_buckets"])
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    top = buckets[-1]
    chunks = {(n - 1) % top + 1 for n in range(lo, hi + 1)}
    if hi > top:
        chunks.add(top)
    return [1] + sorted({next(b for b in buckets if b >= c) for c in chunks})


def serve_stack(app, cfg: Dict[str, Any]):
    """``(adapter, engine, frontend)`` over a warm application, with the
    configuration's keywords (``{}`` = the defaults a user gets)."""
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.serving.engine import (
        ServingEngine, ServingFrontend)
    adapter = PagedEngineAdapter(app, **cfg.get("adapter", {}))
    engine = ServingEngine(adapter)
    return adapter, engine, ServingFrontend(engine)


# ---------------------------------------------------------------------------
# the logit gate
# ---------------------------------------------------------------------------

def logit_gate(cfg: Dict[str, Any], seed: int,
               served_precision: str | None = None) -> Dict[str, Any]:
    """Rule (c) of the correctness gate, as the configuration's ``gate``
    states it. Builds the gate's twin (:func:`gate_overrides`) and the
    reference of its ``model_type`` (:func:`load_reference`) from one dict,
    loads seeded weights through the family's own checkpoint converter,
    teacher-forces it through the paged cache and holds every compared logit
    to ``atol + rtol * |reference|``.
    Everything it put on the device is freed before it returns.

    ``served_precision`` is for the CPU tests only: XLA:CPU multiplies
    float32 matrices inexactly at the default precision, so the toy twin runs
    under "highest" there. On the chip the served side runs as it is served
    (None)."""
    import contextlib
    import time
    import jax
    import jax.numpy as jnp
    from . import weights
    gate = cfg["gate"]
    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))
    twin = gate_overrides(gate)
    hf = hf_config(cfg, twin)
    reference = load_reference(hf["model_type"])
    table = reference.weight_shapes(hf)
    b, s, n_new = gate["batch"], gate["prompt_len"], gate["new_tokens"]
    rng = np.random.default_rng([seed, 0x67617465])
    ids = rng.integers(1, hf["vocab_size"], size=(b, s + n_new),
                       dtype=np.int64).astype(np.int32)
    w = weights.make_weights(table, seed)
    with jax.default_matmul_precision("highest"):
        want, margins = jax.jit(
            lambda w_, ids_: reference.forward(hf, w_, ids_,
                                               with_margins=True))(
                w, jnp.asarray(ids))
    want, margins = np.asarray(want), np.asarray(margins)
    mark("weights+reference")
    bucket = -(-s // 32) * 32
    app = build_app(cfg, overrides=twin, output_logits=True,
                    serve=dict(cfg["serve"], batch_size=b,
                               seq_len=2 * bucket, pa_num_blocks=4 * b,
                               context_encoding_buckets=[bucket]))
    view = weights.HfView(table, w, dtype=None if cfg["dtype"] == "bfloat16"
                          else np.dtype(cfg["dtype"]))
    mark("to_host")
    host = app.family.convert_hf_state_dict(view, app.spec)
    del view
    mark("convert")
    del w
    app._put_params(host)
    del host
    app.init_cache()
    mark("to_device")
    with (jax.default_matmul_precision(served_precision)
          if served_precision else contextlib.nullcontext()):
        res = app.generate(ids[:, :s], max_new_tokens=n_new + 1,
                           return_logits=True, teacher_tokens=ids[:, s:])
    steps = res["logits"]
    vocab = hf["vocab_size"]
    got = np.concatenate(
        [np.asarray(steps[0])[:, :s, :vocab]]
        + [np.asarray(x)[:, -1:, :vocab] for x in steps[1:n_new + 1]], axis=1)
    del app, res, steps
    gc.collect()
    mark("served_forward")
    seconds = {b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])}
    if got.shape != want.shape:
        return {"passed": False, "why": f"logit shapes {got.shape} vs "
                                        f"{want.shape}"}
    err = np.abs(got - want)
    # per position: the worst logit's error as a share of its bound
    ratio = (err / (gate["atol"] + gate["rtol"] * np.abs(want))).max(axis=-1)
    return dict(judge_gate(ratio, margins, s, gate),
                max_error=float(err.max()),
                max_reference=float(np.abs(want).max()),
                compared=int(err.size), atol=gate["atol"], rtol=gate["rtol"],
                seconds=seconds)


def judge_gate(ratio: np.ndarray, margins: np.ndarray, prompt_len: int,
               gate: Dict[str, Any]) -> Dict[str, Any]:
    """The gate's verdict from, per position ``(B, S)``, the worst logit
    error as a share of its bound (``ratio``) and the reference's smallest
    routing margin (``margins``; ``inf`` for a dense model). The rule is the
    configuration's ``gate["aggregation"]``:

    * a position is held if its ratio is at most 1;
    * of the prefill positions, and of the decode positions, at least
      ``min_positions_held`` are held;
    * the median ratio over all positions is at most ``median_ratio_max``;
    * no position's ratio is over ``worst_ratio_max``;
    * a position not held is excused only by a near-tie in the routing: the
      reference's margin at it, or at an earlier position of its sequence
      (which it attends to), is at most ``excuse_margin_max``."""
    s = prompt_len
    held = ratio <= 1.0
    shares = {"prefill": float(held[:, :s].mean()),
              "decode": float(held[:, s:].mean())}
    need = gate.get("min_positions_held", 1.0)
    median, worst = float(np.median(ratio)), float(ratio.max())
    near_tie_so_far = np.minimum.accumulate(margins, axis=1)
    unexcused = ~held & ~(near_tie_so_far
                          <= gate.get("excuse_margin_max", 0.0))
    not_held = [(int(i), int(j), round(float(ratio[i, j]), 3),
                 round(float(margins[i, j]), 4),
                 round(float(near_tie_so_far[i, j]), 4))
                for i, j in zip(*np.where(~held))]
    why = []
    if any(v < need for v in shares.values()):
        why.append(f"held shares {shares} under {need}")
    if median > gate.get("median_ratio_max", 1.0):
        why.append(f"median ratio {median:.3f}")
    if worst > gate.get("worst_ratio_max", 1.0):
        why.append(f"worst ratio {worst:.3f}")
    if unexcused.any():
        why.append(f"{int(unexcused.sum())} positions not held with no "
                   "near-tie at or before them")
    return {"passed": not why, "why": why, "held_share": shares,
            "need": need, "median_ratio": median, "worst_ratio": worst,
            "n_not_held": int((~held).sum()),
            "not_held_b_pos_ratio_margin_sofar": not_held[:16]}


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is platform={d0.platform!r} "
                     f"kind={d0.device_kind!r}; the benchmark measures the "
                     "chip and does not fall back")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax reports "
                     f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def peaks_for(kind: str) -> Dict[str, Any]:
    table = load_json("harness", "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks on record for device_kind {kind!r}; known: "
                       f"{sorted(table)} — add the chip's published numbers "
                       "to benchmark/harness/peaks.json with their source")
    return table[kind]


def memory_peak_bytes(n: int) -> int:
    """Peak bytes on the fullest of the first ``n`` devices, as the runtime
    reports it (it misses executable temps on this runtime: PERF.md §6)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()[:n]]
    return max(int(s["peak_bytes_in_use"]) for s in stats if s) \
        if any(stats) else 0
