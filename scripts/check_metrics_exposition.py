#!/usr/bin/env python
"""check_metrics_exposition: the served /v1/metrics scrape is valid
Prometheus text — a tier-1 lint (ISSUE 14).

Two halves, both importable so the tier-1 test runs them IN-PROCESS
(never a subprocess that pays a fresh jax import against the tight
suite budget):

  * :func:`validate_prometheus_text` — a dependency-free validating
    parser for the text exposition format 0.0.4: every sample line must
    parse (name, label pairs, float value), every sample's metric family
    must have exactly one ``# TYPE`` line BEFORE its first sample,
    histogram families must expose cumulative non-decreasing ``_bucket``
    series whose ``+Inf`` bucket equals ``_count``, and counters must
    never be negative. Returns a list of problems (empty = valid).
  * :func:`scrape_frontend` — boots a :class:`ServingFrontend` over a
    (caller-provided or tiny synthetic) engine, serves one real request,
    and returns the body of ``GET /v1/metrics`` fetched over the actual
    socket — the scrape a Prometheus agent would see, not a shortcut
    through ``render_prometheus()``.

CLI: ``python scripts/check_metrics_exposition.py`` builds the tiny
synthetic paged engine (CPU), scrapes, validates, and exits 0/1.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))     # package import when run as a script

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>\S+)(?: (?P<ts>-?\d+))?$")
_LABEL_RE = re.compile(
    r'\s*(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"\s*(?:,|$)')

_SUFFIXES = ("_bucket", "_sum", "_count")


def _parse_value(text: str) -> Optional[float]:
    if text in ("+Inf", "Inf"):
        return float("inf")
    if text == "-Inf":
        return float("-inf")
    if text == "NaN":
        return float("nan")
    try:
        return float(text)
    except ValueError:
        return None


def _parse_labels(text: str) -> Optional[Dict[str, str]]:
    labels: Dict[str, str] = {}
    pos = 0
    while pos < len(text):
        m = _LABEL_RE.match(text, pos)
        if m is None:
            return None
        labels[m.group("k")] = m.group("v")
        pos = m.end()
    return labels


def _family(name: str, types: Dict[str, str]) -> str:
    """The metric family a sample line belongs to: histogram samples
    carry _bucket/_sum/_count suffixes on the family name."""
    for suf in _SUFFIXES:
        base = name[:-len(suf)] if name.endswith(suf) else None
        if base and types.get(base) == "histogram":
            return base
    return name


def validate_prometheus_text(text: str) -> List[str]:
    """Problems with a text-exposition body; empty list = valid."""
    problems: List[str] = []
    types: Dict[str, str] = {}
    seen_samples = False
    # (family, labels-sans-le sorted) -> list of (le, cumulative count)
    buckets: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                  List[Tuple[float, float]]] = {}
    counts: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
    if text and not text.endswith("\n"):
        problems.append("body must end with a newline")
    for i, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split(None, 3)
            if len(parts) < 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                problems.append(f"line {i}: malformed TYPE line: {line!r}")
                continue
            name = parts[2]
            if name in types:
                problems.append(f"line {i}: duplicate TYPE for {name}")
            types[name] = parts[3]
            continue
        if line.startswith("# HELP "):
            if len(line.split(None, 3)) < 4:
                problems.append(f"line {i}: malformed HELP line: {line!r}")
            continue
        if line.startswith("#"):
            continue                     # free-form comment: allowed
        m = _SAMPLE_RE.match(line)
        if m is None:
            problems.append(f"line {i}: unparseable sample: {line!r}")
            continue
        seen_samples = True
        name = m.group("name")
        value = _parse_value(m.group("value"))
        if value is None:
            problems.append(f"line {i}: bad sample value "
                            f"{m.group('value')!r}")
            continue
        labels = _parse_labels(m.group("labels") or "")
        if labels is None:
            problems.append(f"line {i}: unparseable labels in {line!r}")
            continue
        family = _family(name, types)
        ftype = types.get(family)
        if ftype is None:
            problems.append(f"line {i}: sample {name} has no preceding "
                            "# TYPE line for its family")
            continue
        if ftype == "counter" and value < 0:
            problems.append(f"line {i}: counter {name} is negative")
        if ftype == "histogram":
            key_labels = tuple(sorted((k, v) for k, v in labels.items()
                                      if k != "le"))
            key = (family, key_labels)
            if name.endswith("_bucket"):
                if "le" not in labels:
                    problems.append(f"line {i}: histogram bucket without "
                                    "an le label")
                    continue
                le = _parse_value(labels["le"])
                if le is None:
                    problems.append(f"line {i}: bad le value "
                                    f"{labels['le']!r}")
                    continue
                buckets.setdefault(key, []).append((le, value))
            elif name.endswith("_count"):
                counts[key] = value
    for key, series in buckets.items():
        family, labels = key
        les = [le for le, _ in series]
        if les != sorted(les):
            problems.append(f"{family}{dict(labels)}: bucket le bounds "
                            "out of order")
        cums = [c for _, c in series]
        if cums != sorted(cums):
            problems.append(f"{family}{dict(labels)}: bucket counts are "
                            "not cumulative")
        if les and les[-1] != float("inf"):
            problems.append(f"{family}{dict(labels)}: missing +Inf bucket")
        n = counts.get(key)
        if n is None:
            problems.append(f"{family}{dict(labels)}: histogram without "
                            "a _count sample")
        elif series and series[-1][1] != n:
            problems.append(f"{family}{dict(labels)}: +Inf bucket "
                            f"{series[-1][1]} != _count {n}")
    if not seen_samples:
        problems.append("no samples at all — nothing was measured before "
                        "the scrape")
    return problems


def scrape_frontend(engine, path: str = "/v1/metrics", fleet=None,
                    generate: bool = True) -> str:
    """Serve one request through a :class:`ServingFrontend` over
    ``engine`` (skipped with ``generate=False`` — a fleet that already
    served its load) and return the body of ``GET <path>`` fetched over
    the real listener socket."""
    import asyncio
    import json

    from neuronx_distributed_inference_tpu.serving.engine import \
        ServingFrontend

    async def http(host, port, raw):
        r, w = await asyncio.open_connection(host, port)
        w.write(raw)
        await w.drain()
        data = await asyncio.wait_for(r.read(), timeout=90)
        w.close()
        return data

    async def main():
        fe = ServingFrontend(engine, fleet=fleet)
        host, port = await fe.start()
        if generate:
            body = json.dumps({"prompt": [3, 5, 7, 11, 13],
                               "max_new_tokens": 3,
                               "tenant": "scrape"}).encode()
            await http(host, port,
                       b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                       + str(len(body)).encode() + b"\r\n\r\n" + body)
        resp = await http(host, port,
                          f"GET {path} HTTP/1.1\r\n\r\n".encode())
        await fe.stop()
        head, _, payload = resp.decode().partition("\r\n\r\n")
        status = head.split()[1]
        if status != "200":
            raise RuntimeError(f"GET {path} -> {status}: {payload[:200]}")
        if "text/plain" not in head:
            raise RuntimeError(f"GET {path} served a non-text "
                               f"content type: {head.splitlines()[1:4]}")
        return payload

    return asyncio.run(main())


def scrape_frontend_fleet(engine, router, path: str = "/v1/metrics") -> str:
    """``GET <path>`` on a frontend built with ``fleet=router`` — the
    fleet-aggregated exposition (no extra request served; the router
    already drove its load)."""
    return scrape_frontend(engine, path, fleet=router, generate=False)


def _tiny_engine():
    """The suite's tiny synthetic paged engine (same shapes as
    test_serving_engine, so the persistent compile cache is warm)."""
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        PagedCausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    from neuronx_distributed_inference_tpu.telemetry.slo import (SLOPolicy,
                                                                 SLOTracker)
    from neuronx_distributed_inference_tpu.serving.engine import ServingEngine

    hf = dict(model_type="llama", hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, vocab_size=512,
              rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
              tie_word_embeddings=False, torch_dtype="float32")
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    tracker = SLOTracker(SLOPolicy(targets={"ttft": 0.5, "tpot": 0.1,
                                            "queue_wait": 1.0}))
    return ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9,
                         slo=tracker)


def main(argv=None) -> int:
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.compat import force_cpu_devices

    force_cpu_devices(1)
    telemetry.enable()
    try:
        text = scrape_frontend(_tiny_engine())
    finally:
        telemetry.disable()
    problems = validate_prometheus_text(text)
    samples = sum(1 for l in text.splitlines()
                  if l and not l.startswith("#"))
    if problems:
        for p in problems:
            print(f"check_metrics_exposition: {p}", file=sys.stderr)
        print(f"check_metrics_exposition: FAIL ({len(problems)} "
              f"problem(s) over {samples} sample(s))", file=sys.stderr)
        return 1
    print(f"check_metrics_exposition: OK — /v1/metrics served {samples} "
          "well-formed sample(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
