"""Shared host-side decode loop for applications with model-specific step
state (whisper / mllama cross-attention decoders) — the same serving
conventions as ``CausalLMApplication.generate``: tokens stay ON DEVICE
through the loop (a synchronous fetch per step would drain the dispatch
queue and idle the chip), JAX's async dispatch pipelines the steps, and EOS is
checked at chunk boundaries on tokens that already finished their async
copy (reference: the ``_sample`` host hot loop of utils/hf_adapter.py
:139-258 + async_execution.py double-buffering)."""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np


def greedy_host_loop(step: Callable, first_tokens, max_new_tokens: int,
                     eos_ids: Optional[np.ndarray] = None,
                     eos_chunk: int = 8) -> np.ndarray:
    """Run up to ``max_new_tokens - 1`` decode steps after ``first_tokens``.

    step(last_dev) -> next_dev: takes/returns DEVICE (B,) int32 token
    arrays; the caller's closure advances positions and any model state.
    Returns the generated tokens (B, n) as numpy (n <= max_new_tokens;
    rows that hit EOS early may decode to the chunk boundary — harmless
    extra tokens past EOS, the same convention as the main app).
    """
    collected = [first_tokens]
    done = None
    checked = 0
    for i in range(1, max_new_tokens):
        nxt = step(collected[-1])
        try:
            nxt.copy_to_host_async()
        except AttributeError:
            pass
        collected.append(nxt)
        if eos_ids is not None and (i % eos_chunk == 0
                                    or i == max_new_tokens - 1):
            # check only the NEW chunk (already host-copied above) and OR
            # into a running done mask - O(n) total, like the main app
            chunk = np.stack([np.asarray(t)
                              for t in collected[checked:]], axis=1)
            checked = len(collected)
            hit = np.isin(chunk, eos_ids).any(axis=1)
            done = hit if done is None else (done | hit)
            if bool(done.all()):
                break
    return np.stack([np.asarray(t) for t in collected], axis=1)
