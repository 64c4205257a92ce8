"""Accuracy gates vs HF CPU golden (reference: utils/accuracy.py —
``check_accuracy`` token matching :244, ``check_accuracy_logits`` :478/:707
with per-index tol_map and divergence tolerance).

The golden is always the HF transformers model on CPU — same convention as
the reference (utils/accuracy.py:585-600 generates expected logits with the
CPU model)."""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger("nxdi_tpu")


@dataclass
class AccuracyReport:
    passed: bool
    mode: str
    num_tokens_checked: int = 0
    num_divergences: int = 0
    first_divergence_index: Optional[int] = None
    max_error: float = 0.0
    details: Dict[str, Any] = field(default_factory=dict)

    def __str__(self):
        s = "PASS" if self.passed else "FAIL"
        return (f"[{self.mode}] {s}: {self.num_tokens_checked} tokens, "
                f"{self.num_divergences} divergences, max_err={self.max_error:.2e}")


def get_generate_outputs_hf(hf_model, input_ids: np.ndarray,
                            attention_mask: Optional[np.ndarray],
                            max_new_tokens: int,
                            eos_token_id: Optional[int] = None):
    """Per-row greedy generation + per-step logits from the HF CPU golden.

    Runs HF generate() one row at a time with the padding stripped — HF
    decoder-only generation requires left padding, and per-row unpadded runs
    sidestep padding-side pitfalls entirely while keeping the scores aligned
    to generation steps. Returns (gen_tokens, scores): gen_tokens[i] is the
    1-D array of tokens generated for row i (stops at EOS), scores[i] is the
    list of (V,) logit vectors per step."""
    import torch
    hf_model.eval()
    ids = np.asarray(input_ids, dtype=np.int64)
    b, s = ids.shape
    lens = (np.asarray(attention_mask).astype(int).sum(1)
            if attention_mask is not None else np.full((b,), s))
    gen_tokens, scores = [], []
    kwargs = {}
    if eos_token_id is not None:
        kwargs["eos_token_id"] = eos_token_id
    for i in range(b):
        row = torch.tensor(ids[i:i + 1, :lens[i]])
        with torch.no_grad():
            out = hf_model.generate(
                row, max_new_tokens=max_new_tokens, do_sample=False,
                output_scores=True, return_dict_in_generate=True, **kwargs)
        gen_tokens.append(out.sequences.numpy()[0, lens[i]:])
        scores.append([sc.numpy()[0] for sc in out.scores])
    return gen_tokens, scores


def check_accuracy(app, hf_model, input_ids: np.ndarray,
                   max_new_tokens: int = 32,
                   attention_mask: Optional[np.ndarray] = None,
                   eos_token_id: Optional[int] = None) -> AccuracyReport:
    """Token-matching gate (reference: utils/accuracy.py:244): greedy tokens
    from the TPU app must equal the HF CPU golden exactly, compared per row
    up to the golden's generated length (post-EOS padding excluded)."""
    golden_gen, _ = get_generate_outputs_hf(hf_model, input_ids,
                                            attention_mask, max_new_tokens,
                                            eos_token_id)
    res = app.generate(np.asarray(input_ids, np.int32),
                       attention_mask=attention_mask,
                       max_new_tokens=max_new_tokens,
                       eos_token_id=eos_token_id)
    ours_gen = res["generated"]
    num_div, first, checked = 0, None, 0
    for i, golden in enumerate(golden_gen):
        n = min(len(golden), ours_gen.shape[1])
        mism = ours_gen[i, :n] != golden[:n]
        checked += n
        if mism.any():
            num_div += int(mism.sum())
            idx = int(np.argwhere(mism)[0, 0])
            first = idx if first is None else min(first, idx)
    return AccuracyReport(passed=num_div == 0, mode="token-matching",
                          num_tokens_checked=checked,
                          num_divergences=num_div, first_divergence_index=first,
                          details={"ours": ours_gen.tolist(),
                                   "golden": [g.tolist() for g in golden_gen]})


def check_accuracy_logits(app, hf_model, input_ids: np.ndarray,
                          max_new_tokens: int = 16,
                          divergence_difference_tol: float = 0.001,
                          tol_map: Optional[Dict[int, Tuple[float, float]]] = None,
                          attention_mask: Optional[np.ndarray] = None
                          ) -> AccuracyReport:
    """Logit-matching gate (reference: utils/accuracy.py:478 v1 / :707 v2).

    Teacher-forces the golden's greedy tokens through the TPU model and
    compares per-step next-token logits within ``divergence_difference_tol``;
    ``tol_map`` = {step_index: (atol, rtol)} per-index overrides
    (reference: inference_demo.py --tol-map)."""
    if not app.tpu_config.output_logits:
        raise ValueError("app must be built with output_logits=True for "
                         "logit-matching")
    golden_gen, golden_scores = get_generate_outputs_hf(
        hf_model, input_ids, attention_mask, max_new_tokens)
    b, s = np.asarray(input_ids).shape
    # teacher tokens: per-row golden generations, right-padded with the last
    # token (padded steps are never compared)
    max_t = max(len(g) for g in golden_gen)
    teacher = np.stack([np.pad(g, (0, max_t - len(g)), mode="edge")
                        for g in golden_gen]).astype(np.int32)
    res = app.generate(np.asarray(input_ids, np.int32),
                       attention_mask=attention_mask,
                       max_new_tokens=max_t, return_logits=True,
                       teacher_tokens=teacher)
    step_logits = res["logits"]
    seq_lens = (np.asarray(attention_mask).sum(1).astype(int)
                if attention_mask is not None else np.full((b,), s))

    max_err, num_div, first = 0.0, 0, None
    checked = 0
    compared = {}        # (step, row) -> the (V,) logits held to the golden
    for step in range(min(max_t, len(step_logits))):
        atol, rtol = (tol_map or {}).get(step, (divergence_difference_tol, 0.0))
        for i in range(b):
            if step >= len(golden_scores[i]):
                continue  # row i's golden stopped at EOS before this step
            golden = golden_scores[i][step]                # (V,)
            if step == 0:
                ours = step_logits[0][i, seq_lens[i] - 1]  # prefill last pos
            else:
                ours = step_logits[step][i, -1, :]
            v = min(ours.shape[-1], golden.shape[-1])
            compared[(step, i)] = np.asarray(ours[:v])
            err = np.abs(ours[:v] - golden[:v])
            max_err = max(max_err, float(err.max()))
            div = err > (atol + rtol * np.abs(golden[:v]))
            checked += int(div.size)
            if div.any():
                num_div += int(div.sum())
                if first is None:
                    first = step
    return AccuracyReport(passed=num_div == 0, mode="logit-matching",
                          num_tokens_checked=checked, num_divergences=num_div,
                          first_divergence_index=first, max_error=max_err,
                          details={"logits": compared})
