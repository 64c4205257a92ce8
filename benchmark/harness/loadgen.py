"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``benchmark/traffic/<mix>.json``) names a loop kind (``open`` or
``closed``), two length distributions and how long the load runs before the
window opens. Nothing here knows a mix by name: a new mix is a new data file.

Steadiness rule (the builder's contract): every seed gets the SAME requests
at the SAME instants. Lengths and gaps are the stratified quantiles of their
distributions — the value at ``(i + 0.5) / n`` for ``i < n`` — laid out in one
order that the mix fixes (``base_seed``); ``--seed`` draws the token ids (and,
in ``run.py``, the weights), nothing of the schedule. Two milder rules were
measured first on ``olmoe-chat-steady`` (PERF.md §6): a whole-run shuffle per
seed moved the p90 of time to first token by 30-40 % between seeds, and a
shuffle inside blocks of 8 requests still moved its median by 10 %; with the
order fixed the median moves by under 4 %. The gaps of an open loop are the
quantiles of the exponential distribution with mean ``1 / rate``, so the
arrivals are Poisson in their marginal. (The arithmetic of
``serving/fleet/loadgen.py`` — thinning on a virtual clock — was read and not
kept: the rate here is constant, so there is nothing to thin, and a draw per
seed is exactly what makes two runs differ.)
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class Request:
    """One request of a run. ``due`` is seconds after the load starts (open
    loop) or None (closed loop: sent when a client is free)."""
    index: int
    prompt_len: int
    max_new_tokens: int
    due: float | None


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def stratified_lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` whole lengths: the stratified quantiles of ``dist``, clipped.

    ``{"kind": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}`` or
    ``{"kind": "uniform", "lo": a, "hi": b}`` (both ends included)."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length distribution needs 1 <= lo <= hi: {dist}")
    kind = dist["kind"]
    if kind == "lognormal":
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        nd = NormalDist()
        raw = [math.exp(mu + sigma * nd.inv_cdf(u)) for u in _quantiles(n)]
    elif kind == "uniform":
        raw = [lo + u * (hi - lo) for u in _quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution kind {kind!r}")
    return [min(max(int(round(x)), lo), hi) for x in raw]


def stratified_gaps(rate: float, n: int) -> List[float]:
    """``n`` inter-arrival gaps: stratified quantiles of Exp(rate)."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    return [-math.log1p(-u) / rate for u in _quantiles(n)]


def _laid_out(values: list, mix: Dict[str, Any], salt: int) -> list:
    """``values`` in the mix's one fixed order."""
    out = list(values)
    random.Random(int(mix.get("base_seed", 0)) * 8 + salt).shuffle(out)
    return out


def make_requests(mix: Dict[str, Any], *, rate: float | None,
                  span_s: float) -> List[Request]:
    """The requests of one run, in sending order.

    Open loop: ``round(rate * span_s)`` requests whose gaps sum to about
    ``span_s``. Closed loop: ``mix["pool_requests"]`` requests that the
    clients take in order (running out of them is an error: a repeated
    prompt would hit the prefix cache)."""
    if mix["loop"] == "open":
        if rate is None:
            raise ValueError("an open-loop cell needs a rate in its cell "
                             "file (requests per second)")
        n = max(int(round(rate * span_s)), 1)
    elif mix["loop"] == "closed":
        n = int(mix["pool_requests"])
    else:
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    prompts = _laid_out(stratified_lengths(mix["prompt_len"], n), mix, 0)
    outputs = _laid_out(stratified_lengths(mix["output_len"], n), mix, 1)
    dues: Sequence[float | None]
    if mix["loop"] == "open":
        gaps = _laid_out(stratified_gaps(rate, n), mix, 2)
        t, dues = 0.0, []
        for g in gaps:
            t += g
            dues.append(t)
    else:
        dues = [None] * n
    return [Request(i, prompts[i], outputs[i], dues[i]) for i in range(n)]


def prompt_tokens(requests: Sequence[Request], *, seed: int,
                  vocab: int) -> List[List[int]]:
    """Token ids, uniform in ``[1, vocab)``, one list per request. No two
    requests share a prefix beyond chance (ids are independent draws)."""
    rng = np.random.default_rng([seed, 0x70726F6D])
    flat = rng.integers(1, vocab, size=sum(r.prompt_len for r in requests),
                        dtype=np.int64)
    out, at = [], 0
    for r in requests:
        out.append(flat[at:at + r.prompt_len].tolist())
        at += r.prompt_len
    return out
