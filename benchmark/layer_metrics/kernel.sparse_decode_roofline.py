"""Roofline share of the selection and the attention of one decode step of a
stack with a learned sparse selection (memory bound), whatever implements
them.

The yardstick is computed here from the configuration's published keys and
from exact counts of the program's, not from what the program reads. One
execution of the width-1 step program must, at the least, per layer: read
every live token's index key once (the indexer scores all of them), read the
K and V rows of the ``min(length, topk)`` tokens each running row selects,
move each row's queries and outputs, and stream the weights of the two
scopes that do the work - the attention's q, k, v and o projections and
norms, the indexer's three projections and its key norm. The tokens are the
adapter's counts at each decode dispatch (``host_stats.sparse_tokens_cached``
/ ``sparse_tokens_selected``: sums over the running rows of ``length`` and
``min(length, topk)``) over the dispatches of the profiled slice. Pages
walked and masked, the gathered table, float32 scores and whatever else the
program touches are its overhead, not the model's need: a form that walks
every live page to attend a third of them reads low, not high, and one that
read the selected rows alone at the stream's bandwidth could not read over
100 %.

The time is the device self time under the scopes ``indexer`` and ``attn``
per execution of ``paged.w1`` (``host_spans.program_scope_ms``). Nothing to
read (a program without the counters or the ``indexer`` scope, a
configuration without ``sa_config``): None."""

from harness import host_spans
from harness.kernel_bytes import DTYPE_BYTES


def layer_weight_values(cfg) -> int:
    """Values of the weights the scopes ``attn`` and ``indexer`` stream, a
    layer."""
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    nj, dj = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attn = 2 * hid * nq * d + 2 * hid * nkv * d + 2 * d
    indexer = hid * (nj * dj + dj + nj) + 2 * dj
    return attn + indexer


def sparse_decode_min_bytes(cfg, cached: float, selected: float,
                            rows: float) -> float:
    """Bytes the selection and the attention of ALL layers of one decode
    step must move, for ``rows`` running rows that hold ``cached`` tokens
    and select ``selected`` of them."""
    size = DTYPE_BYTES[cfg["dtype"]]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a_layer = (cached * cfg["sa_config"]["indexer_head_dim"]
               + selected * 2 * nkv * d + rows * 2 * nq * d
               + layer_weight_values(cfg))
    return cfg["num_hidden_layers"] * a_layer * size


def read(ctx):
    cfg = ctx["config"]
    edges = ctx.get("slice") or {}
    if "sa_config" not in cfg or not edges.get("before") \
            or not edges.get("after"):
        return None

    def delta(key):
        return (edges["after"]["counters"].get("host_stats." + key, 0.0)
                - edges["before"]["counters"].get("host_stats." + key, 0.0))
    steps = delta("dispatches")
    indexer = host_spans.program_scope_ms(ctx, "paged", 1, "indexer")
    attn = host_spans.program_scope_ms(ctx, "paged", 1, "attn")
    if steps <= 0 or not indexer or not attn \
            or delta("sparse_tokens_cached") <= 0:
        return None
    rows = (edges["before"]["counters"].get("kv.live_rows", 0.0)
            + edges["after"]["counters"].get("kv.live_rows", 0.0)) / 2.0
    least_s = sparse_decode_min_bytes(
        cfg, delta("sparse_tokens_cached") / steps,
        delta("sparse_tokens_selected") / steps, rows) / (
            ctx["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / ((indexer + attn) * 1e-3)
