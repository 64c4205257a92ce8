"""Bytes a kernel must move, from the configuration's shapes.

The yardstick of a roofline share lives here, not in the program: a later
change to a kernel cannot change what it is measured against.
"""

from __future__ import annotations

from typing import Any, Dict

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def paged_decode_min_bytes(cfg: Dict[str, Any], live_tokens: float,
                           rows: float) -> float:
    """What ONE call of the paged decode attention kernel (one layer, one
    chip) has to read and write at the least: the keys and values of every
    live token of the step — ``2 x (KV heads on this chip) x head_dim`` values
    each — plus the query and the output of each row. Block padding, the
    block table and whatever else the kernel chooses to touch are not
    counted: they are the kernel's overhead, not the algorithm's need."""
    heads_q = cfg["num_attention_heads"]
    heads_kv = cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads_q
    tp = cfg["tp"]
    size = DTYPE_BYTES[cfg["dtype"]]
    kv = live_tokens * 2 * max(heads_kv // tp, 1) * head_dim * size
    q_and_out = rows * 2 * (heads_q // tp) * head_dim * size
    return kv + q_and_out
