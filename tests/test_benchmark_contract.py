"""The benchmark's contract with the program, in tier-1 (owed since ISSUE 29;
``benchmark/tests/`` holds the harness's own tests, which the driver does
not run).

  * every real cell of ``BENCHMARK.json`` loads (``run.load_cell``): its
    files are found by name, agree with its ``workloads`` entry, and it
    reports ``setup_s``, another end-to-end metric and its per-layer names;
  * every ``per_layer`` entry agrees with its metric file
    ``benchmark/layer_metrics/<name>.json`` on the keys they share, a
    ``python`` reader has its ``.py`` beside it, and every listed cell
    exists and reports the end-to-end metric the entry moves;
  * the configuration of a cell keeps every number of its published source
    that this repository can check offline (``reduced`` names what differs
    between the file's own twin and the gate's);
  * the ``granitemoehybrid`` reference, found by name, gates a toy twin
    through the paged path on the CPU — and a mixer that drops the state
    it is handed does not pass it;
  * the precision of the recurrent state, which the logit gate cannot see
    inside the tokens it runs (PERF.md §6): the cache the application
    allocates for the configuration's file has the dtypes the file's
    ``assumed`` names, and a served sequence's state slot is held to the
    reference's ``S_t`` at a tolerance a bf16-carried state fails;
  * the same for ``olmo-hybrid-7b`` (ISSUE 34): every published number, the
    cut 32 -> 16 in whole periods, the dtypes and shapes ``assumed`` names,
    the file's memory arithmetic re-derived from ``ssm_state_shapes``, the
    pool and the parameter specs, and a served slot against the reference's
    ``final_states``;
  * the same for ``qwen3-next-80b-a3b`` (ISSUE 36): every published number
    but depth, experts held and vocabulary, the share's keys, the memory
    arithmetic re-derived, the reference gating a toy twin that holds a
    share, and a served slot against ``final_states``;
  * ``smallthinker-21b-a3b`` (ISSUE 43): every published number but the
    depth and the two layouts cut with it, the twin's shrunk window, the
    two pools and the ring re-derived from what the application allocates,
    the reference gating a toy twin, and three faults in the PROGRAM (the
    window, the router's input, the gate's activation) failing it;
  * ``deepseek-v3`` (ISSUE 47): every published number but the depth, the
    leading dense layers, the experts held and the vocabulary, the share's
    keys and the mix letter for letter, the pool and the parameters by stack
    re-derived from what the application allocates, the reference gating a
    toy twin, and three faults in the PROGRAM (the groups, the shared
    expert, the selection bias) failing it;
  * ``keye-vl-2.0-30b-a3b`` (ISSUE 50): every published number but the
    depth, the experts held and the vocabulary, the share's keys, ``assumed``
    and the mix letter for letter, the three pools and the parameters
    re-derived from what the application allocates, the twin's shrunk
    ``topk``, the reference gating a toy twin, and three faults in the
    PROGRAM (the selection, the head weights, the index keys' write) failing
    it;
  * ``command-a-plus-05-2026`` (ISSUE 56): every published number but the
    depth, ``layer_types``, the experts held and the vocabulary, the share's
    keys, ``assumed`` and the mix letter for letter, the two pools, the ring
    and the parameters re-derived from what the application allocates, the
    two rooflines' yardsticks from a made-up window, and three faults in the
    PROGRAM (an unaveraged shared sum, the half-split rotary, a rotated full
    layer) failing the toy gate.
"""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
from harness import build  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
PER_LAYER = [m["name"] for m in BENCHMARK["per_layer"]]
SHARED_KEYS = ("unit", "better", "source", "layer", "moves")


@pytest.mark.parametrize("cell", CELLS)
def test_a_real_cell_loads_with_its_metrics(cell):
    spec = bench_run.load_cell(cell)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert spec["cell"]["config"] == entry["config"]
    assert spec["cell"]["traffic"] == entry["traffic"]
    assert spec["cell"]["chips"] == entry["chips"] == spec["config"]["chips"]
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    want = [m["name"] for m in BENCHMARK["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]
    assert [m["name"] for m in spec["per_layer"]] == want and want
    # every metric without a list is reported by every cell
    assert {m["name"] for m in BENCHMARK["per_layer"]
            if "workloads" not in m} <= set(want)
    # the mix's prompts reach only widths the configuration warms
    widths = build.warm_widths(spec["config"], spec["mix"])
    assert widths[0] == 1 and set(widths[1:]) <= set(
        spec["config"]["serve"]["context_encoding_buckets"])
    config = next(c for c in BENCHMARK["configs"]
                  if c["name"] == entry["config"])
    assert config["file"] == f"benchmark/configs/{entry['config']}.json"
    assert config["reduced"] == spec["config"]["reduced"]
    assert config["source"] == spec["config"]["source"]


@pytest.mark.parametrize("name", PER_LAYER)
def test_a_per_layer_entry_agrees_with_its_metric_file(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    spec = build.load_json("layer_metrics", name + ".json")
    assert {k: entry[k] for k in SHARED_KEYS} == \
        {k: spec[k] for k in SHARED_KEYS}
    assert "workloads" not in spec         # listed in BENCHMARK.json only
    kind = spec["reader"]["kind"]
    if kind == "python":
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    else:
        from harness import readers
        assert kind in readers.KINDS
    moved = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == entry["moves"])
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"], \
            f"{cell} does not report {entry['moves']}"
    if name.endswith("_roofline"):
        assert entry["unit"] == "%"


def test_granite_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 30: every key at the top level of the file, none changed, and
    ``reduced`` empty."""
    cfg = build.load_json("configs", "granite-4.0-h-micro.json")
    published = dict(
        attention_multiplier=0.015625, embedding_multiplier=12,
        hidden_size=2048, intermediate_size=8192, logits_scaling=8,
        mamba_chunk_size=256, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, max_position_embeddings=131072,
        num_attention_heads=32, num_experts_per_tok=0,
        num_hidden_layers=40, num_key_value_heads=8, num_local_experts=0,
        residual_multiplier=0.22, rms_norm_eps=1e-05, rope_theta=10000,
        shared_intermediate_size=8192, vocab_size=100352,
        model_type="granitemoehybrid", position_embedding_type="nope",
        tie_word_embeddings=True)
    assert {k: cfg[k] for k in published} == published
    types = cfg["layer_types"]
    assert len(types) == 40 and types.count("attention") == 4
    assert [i for i, t in enumerate(types) if t == "attention"] == \
        [5, 15, 25, 35]
    assert cfg["reduced"] == [] and cfg["family"] == "granitemoehybrid"
    assert cfg["assumed"]["ssm_state_dtype"] == "float32"
    gate = cfg["gate"]
    assert gate["min_positions_held"] == 1.0
    assert gate["excuse_margin_max"] == 0.0 and gate["worst_ratio_max"] == 1.0
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    assert twin["num_hidden_layers"] == len(twin["layer_types"]) == 4
    assert set(twin["layer_types"]) == {"mamba", "attention"}
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "chat-longanswer-closed.json")
    serve = cfg["serve"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] >= \
        serve["batch_size"] * (mix["prompt_len"]["hi"]
                               + mix["output_len"]["hi"])


TOY = dict(
    json.load(open(os.path.join(BENCH, "tests", "reference_cases",
                                "granitemoehybrid.json")))["config"],
    family="granitemoehybrid", tp=1, dtype="float32",
    serve=dict(batch_size=4, seq_len=128, pa_block_size=8, pa_num_blocks=96,
               context_encoding_buckets=[16, 32], enable_bucketing=True,
               is_block_kv_layout=True, is_prefix_caching=False),
    adapter={},
    # 24 prompt positions: three chunks of the toy's mamba_chunk_size 8
    gate=dict(config={}, batch=2, prompt_len=24, new_tokens=4, atol=1e-4,
              rtol=1e-4, min_positions_held=1.0, median_ratio_max=0.5,
              worst_ratio_max=1.0, excuse_margin_max=0.0))


def test_the_reference_found_by_name_gates_a_toy_twin():
    ref = build.load_reference("granitemoehybrid")
    assert ref.__file__ == os.path.join(BENCH, "references",
                                        "granitemoehybrid.py")
    res = build.logit_gate(TOY, seed=2**31 + 30, served_precision="highest")
    assert res["passed"], res
    assert res["held_share"] == {"prefill": 1.0, "decode": 1.0}
    assert res["compared"] == 2 * 28 * TOY["vocab_size"]


def test_a_dropped_state_does_not_pass_the_toy_gate(monkeypatch):
    """A control of the gate at the toy size: a mixer that forgets the SSM
    state a dispatch hands it (each decode step starts from zero) must not
    pass."""
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.modules import ssm
    mixer = ssm._SSM_BLOCKS["mamba2"]

    def forgetful(s, lw, x, state, **kw):
        return mixer(s, lw, x,
                     dict(state, ssm=jnp.zeros_like(state["ssm"])), **kw)
    monkeypatch.setitem(ssm._SSM_BLOCKS, "mamba2", forgetful)
    res = build.logit_gate(TOY, seed=2**31 + 30, served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 1.0, res
    assert res["held_share"]["prefill"] == 1.0     # one dispatch from zero


def test_the_allocated_caches_have_the_dtypes_the_file_assumes():
    """What ``assumed`` says of the state's precision is what the program
    allocates: the gate's twin of the real file, built as the harness builds
    it. A PR that carries the SSM state in fewer bits (half the decode
    step's largest byte item) changes a result the logit gate cannot see,
    and has to say so in the configuration's file, which only a benchmark
    PR may edit."""
    cfg = build.load_json("configs", "granite-4.0-h-micro.json")
    gate, assumed = cfg["gate"], cfg["assumed"]
    app = build.build_app(
        cfg, overrides=build.gate_overrides(gate),
        serve=dict(cfg["serve"], batch_size=gate["batch"], seq_len=256,
                   pa_num_blocks=4 * gate["batch"],
                   context_encoding_buckets=[128])).init_cache()
    dtypes = {k: str(v.dtype) for k, v in app.cache.items()}
    assert dtypes == {"k": assumed["kv_dtype"], "v": assumed["kv_dtype"],
                      "conv_x": assumed["conv_tail_dtype"],
                      "conv_bc": assumed["conv_tail_dtype"],
                      "ssm": assumed["ssm_state_dtype"]}
    n_mamba = gate["config"]["layer_types"].count("mamba")
    assert app.cache["ssm"].shape == (
        n_mamba, gate["batch"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_d_state"])


def _served_state_error(rounds_to=None, monkeypatch=None):
    """Serve one toy sequence through ``PagedEngineAdapter`` (a prompt in
    three chunks, then 40 decode steps) and return the largest error of its
    state slot against the reference's ``final_states``, as a share of the
    state's largest entry. ``rounds_to``: allocate the SSM state in that
    dtype instead (the control)."""
    import jax.numpy as jnp
    import numpy as np
    from harness import weights
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    if rounds_to is not None:
        shapes = ssm.ssm_state_shapes

        def rounded(*a, **kw):
            out = shapes(*a, **kw)
            return dict(out, ssm=(out["ssm"][0], rounds_to))
        monkeypatch.setattr(ssm, "ssm_state_shapes", rounded)
    hf = build.hf_config(TOY)
    ref = build.load_reference(hf["model_type"])
    table = ref.weight_shapes(hf)
    w = weights.make_weights(table, seed=2**31 + 31)
    app = build.build_app(TOY)
    app._put_params(app.family.convert_hf_state_dict(
        weights.HfView(table, w, dtype=np.dtype("float32")), app.spec))
    app.init_cache()
    ad = PagedEngineAdapter(app)
    prompt = np.random.default_rng(31).integers(
        1, hf["vocab_size"], size=70).tolist()      # 32 + 32 + 6 (padded)
    stream = [ad.add_requests([3], [prompt])[3]]
    for _ in range(40):
        stream.append(ad.step([3])[3])
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[3]], np.float32)
    want = np.asarray(ref.final_states(
        hf, w, jnp.asarray([prompt + stream[:-1]])))[:, 0]
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


#: the served float32 state agrees with the reference's to a few 1e-6 of its
#: largest entry (chunked against token-by-token summation); one bf16
#: rounding is 2e-3 of an entry
STATE_RTOL = 1e-4


def test_a_served_state_slot_holds_the_references_state():
    assert _served_state_error() < STATE_RTOL


def test_a_bf16_carried_state_fails_the_state_check(monkeypatch):
    """The control ISSUE 30 asked the logit gate for, which it cannot give
    (the gate passes it digit for digit on the chip): the state slot
    allocated in bf16, so every dispatch rounds what it carries."""
    import jax.numpy as jnp
    err = _served_state_error(jnp.bfloat16, monkeypatch)
    assert err > 10 * STATE_RTOL, err


# ---------------------------------------------------------------------------
# olmo-hybrid-7b (ISSUE 34)
# ---------------------------------------------------------------------------

OLMO_PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def test_olmo_hybrid_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 34: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth and the pattern cut with it."""
    cfg = build.load_json("configs", "olmo-hybrid-7b.json")
    published = dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu",
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-06, tie_word_embeddings=False,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    assert {k: cfg[k] for k in published} == published
    # 32 published layers = 8 periods; the file keeps whole periods
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) in (12, 16)
    assert cfg["layer_types"] == OLMO_PERIOD * (cfg["num_hidden_layers"] // 4)
    assert cfg["family"] == "olmo_hybrid" and cfg["chips"] == cfg["tp"] == 1
    assert cfg["serve"]["is_prefix_caching"] is False
    assumed = cfg["assumed"]
    assert {"norm_placement", "no_positional_embedding", "tensor_names",
            "state_dtype", "conv_tail_dtype", "kv_dtype",
            "scan_chunk"} <= set(assumed)
    gate = cfg["gate"]
    assert gate["min_positions_held"] == 1.0
    assert gate["excuse_margin_max"] == 0.0 and gate["worst_ratio_max"] == 1.0
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    assert twin["layer_types"] == OLMO_PERIOD
    assert twin["num_hidden_layers"] == 4 and twin["hidden_size"] == 3840
    # the gate's twin fits harness/build.py's 4 blocks a row
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "reason-longanswer-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest <= serve["seq_len"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 34's, letter for letter
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 10.0, 8.0, 34)
    assert mix["prompt_len"] == dict(kind="lognormal", median=192, sigma=0.8,
                                     lo=32, hi=768)
    assert mix["output_len"] == dict(kind="lognormal", median=448, sigma=0.6,
                                     lo=96, hi=1024)


def test_olmo_hybrid_allocates_what_its_file_says():
    """The dtypes and shapes ``assumed`` names and the memory arithmetic of
    the file, against what the program allocates: the cache of the gate's
    twin (built as the harness builds it), and the full configuration's
    state, pool and parameters as SHAPES (nothing of 12 GB is allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_kv_heads
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "olmo-hybrid-7b.json")
    gate, assumed, memory = cfg["gate"], cfg["assumed"], cfg["memory"]
    app = build.build_app(
        cfg, overrides=build.gate_overrides(gate),
        serve=dict(cfg["serve"], batch_size=gate["batch"], seq_len=256,
                   pa_num_blocks=4 * gate["batch"],
                   context_encoding_buckets=[128])).init_cache()
    assert {k: str(v.dtype) for k, v in app.cache.items()} == {
        "k": assumed["kv_dtype"], "v": assumed["kv_dtype"],
        "conv_x": assumed["conv_tail_dtype"], "ssm": assumed["state_dtype"]}
    assert app.cache["ssm"].shape == (3, gate["batch"], 30, 96, 192)
    assert app.cache["conv_x"].shape == (3, gate["batch"], 11520, 3)
    assert app.cache["k"].shape == (1, 4 * gate["batch"] + 1, 32,
                                    memory["kv_pool_heads"], 128)
    assert app.spec.ssm.chunk_size == 64
    # the full configuration, as shapes
    full = build.build_app(cfg)
    spec, serve = full.spec, cfg["serve"]
    n_lin = cfg["layer_types"].count("linear_attention")
    n_full = cfg["layer_types"].count("full_attention")
    assert (spec.num_ssm_layers, spec.num_attn_layers) == (n_lin, n_full)
    state = ssm.ssm_state_shapes(spec.ssm, n_lin, serve["batch_size"],
                                 jnp.dtype(cfg["dtype"]))
    assert state["ssm"][0] == (n_lin, 32, 30, 96, 192)
    state_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                      for shape, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        serve["batch_size"] * memory["state_slot_bytes"]
    heads = pool_kv_heads(spec.gqa.num_kv_heads, spec.gqa.tp)
    assert heads == memory["kv_pool_heads"] == 32
    per_token = n_full * 2 * heads * spec.head_dim * 2
    assert per_token == memory["kv_bytes_per_token"]
    assert (serve["pa_num_blocks"] + 1) * serve["pa_block_size"] * \
        per_token == memory["kv_pool_bytes"]
    weights = sum(
        math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
        for ps in jax.tree.leaves(
            model_base.decoder_param_specs(spec),
            is_leaf=lambda x: isinstance(x, ParamSpec)))
    # A_log and dt_bias are float32 in the program (2 x 30 x 12 x 2 B more
    # than the file's all-bf16 count) and the vocabulary is not padded
    assert weights - 2 * 2 * 30 * n_lin == memory["weights_bytes"]
    assert spec.padded_vocab == cfg["vocab_size"]
    total = memory["weights_bytes"] + memory["state_bytes"] \
        + memory["kv_pool_bytes"]
    assert 0.75 * 16e9 < total < 0.85 * 16e9


def _olmo_toy():
    """One period at a toy size (``tests/test_olmo_hybrid_paged.py``'s) as
    a configuration file the harness can build and gate."""
    from test_olmo_hybrid_paged import HF
    return dict(
        HF, family="olmo_hybrid", tp=1, dtype="float32",
        serve=dict(batch_size=4, seq_len=256, pa_block_size=8,
                   pa_num_blocks=160, context_encoding_buckets=[16, 64],
                   enable_bucketing=True, is_block_kv_layout=True,
                   is_prefix_caching=False),
        adapter={},
        gate=dict(config={}, batch=2, prompt_len=24, new_tokens=4,
                  atol=1e-4, rtol=1e-4, min_positions_held=1.0,
                  median_ratio_max=0.5, worst_ratio_max=1.0,
                  excuse_margin_max=0.0))


def test_the_olmo_hybrid_reference_gates_a_toy_twin():
    ref = build.load_reference("olmo_hybrid")
    assert ref.__file__ == os.path.join(BENCH, "references",
                                        "olmo_hybrid.py")
    toy = _olmo_toy()
    res = build.logit_gate(toy, seed=2**31 + 34, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 28 * toy["vocab_size"]


def _olmo_served_state_error(rounds_to=None, monkeypatch=None):
    """As :func:`_served_state_error`, for the delta rule: one toy sequence
    through ``PagedEngineAdapter`` (150 prompt tokens = 64 + 64 + 22: two
    whole one-row chunks of one scan chunk each and a padded one, then 60
    decode steps), its state slot against the reference's
    ``final_states``."""
    import jax.numpy as jnp
    import numpy as np
    from harness import weights
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    if rounds_to is not None:
        shapes = ssm.ssm_state_shapes

        def rounded(*a, **kw):
            out = shapes(*a, **kw)
            return dict(out, ssm=(out["ssm"][0], rounds_to))
        monkeypatch.setattr(ssm, "ssm_state_shapes", rounded)
    toy = _olmo_toy()
    hf = build.hf_config(toy)
    ref = build.load_reference(hf["model_type"])
    table = ref.weight_shapes(hf)
    w = weights.make_weights(table, seed=2**31 + 35)
    app = build.build_app(toy)
    app._put_params(app.family.convert_hf_state_dict(
        weights.HfView(table, w, dtype=np.dtype("float32")), app.spec))
    app.init_cache()
    ad = PagedEngineAdapter(app)
    prompt = np.random.default_rng(35).integers(
        1, hf["vocab_size"], size=150).tolist()
    stream = [ad.add_requests([3], [prompt])[3]]
    for _ in range(60):
        stream.append(ad.step([3])[3])
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[3]], np.float32)
    want = np.asarray(ref.final_states(
        hf, w, jnp.asarray([prompt + stream[:-1]])))[:, 0]
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_a_served_olmo_slot_holds_the_references_state():
    assert _olmo_served_state_error() < STATE_RTOL


def test_a_bf16_carried_olmo_state_fails_the_state_check(monkeypatch):
    import jax.numpy as jnp
    err = _olmo_served_state_error(jnp.bfloat16, monkeypatch)
    assert err > 10 * STATE_RTOL, err


# ---------------------------------------------------------------------------
# qwen3-next-80b-a3b (ISSUE 36)
# ---------------------------------------------------------------------------

def test_qwen3_next_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 36: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth, the experts held and the vocabulary, and
    nothing else."""
    cfg = build.load_json("configs", "qwen3-next-80b-a3b.json")
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts=512, num_experts_per_tok=10,
        num_hidden_layers=48, num_key_value_heads=2,
        partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=10000000, shared_expert_intermediate_size=512,
        tie_word_embeddings=False, use_sliding_window=False,
        vocab_size=151936)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    # the cut: whole periods, a quarter of the experts and of the vocabulary;
    # the router's width is the published expert count
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) in (8, 12)
    assert cfg["layer_types"] == OLMO_PERIOD * (cfg["num_hidden_layers"] // 4)
    assert cfg["router_num_experts"] == published["num_experts"]
    assert cfg["num_experts"] * 4 == cfg["router_num_experts"]
    assert cfg["vocab_size"] * 4 == published["vocab_size"]
    assert 0 <= cfg["first_expert"] <= 512 - cfg["num_experts"]
    assert cfg["family"] == "qwen3_next" and cfg["chips"] == cfg["tp"] == 1
    assert cfg["serve"]["is_prefix_caching"] is False
    assert {"state_dtype", "conv_tail_dtype", "kv_dtype", "scan_chunk",
            "mtp_left_out"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    assert twin["layer_types"] == OLMO_PERIOD
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["num_experts"], twin["router_num_experts"]) == \
        (4, 2048, 128, 512)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    # a position is excused by a routing near-tie only
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert gate["min_positions_held"] >= 0.9
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "rag-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 36's, letter for letter
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 15.0, 8.0, 36)
    assert mix["prompt_len"] == dict(kind="lognormal", median=768, sigma=0.7,
                                     lo=192, hi=3072)
    assert mix["output_len"] == dict(kind="lognormal", median=320, sigma=0.6,
                                     lo=96, hi=1024)


def test_qwen3_next_allocates_what_its_file_says():
    """The dtypes and shapes ``assumed`` names and the memory arithmetic of
    the file, against what the program would allocate: the full
    configuration's state, pool and parameters as SHAPES (nothing of 12 GB
    is allocated; the gate's twin alone is 3.3 GB and is not built here)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        BlockKVSpec, pool_page)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "qwen3-next-80b-a3b.json")
    assumed, memory, serve = cfg["assumed"], cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    n_lin = cfg["layer_types"].count("linear_attention")
    n_full = cfg["layer_types"].count("full_attention")
    assert (spec.num_ssm_layers, spec.num_attn_layers) == (n_lin, n_full)
    assert (spec.moe.num_experts, spec.moe.held_experts,
            spec.moe.first_expert, spec.moe.top_k) == (512, 128, 0, 10)
    assert spec.ssm.chunk_size == 64 and spec.ssm.key_heads == 16
    state = ssm.ssm_state_shapes(spec.ssm, n_lin, serve["batch_size"],
                                 jnp.dtype(cfg["dtype"]))
    assert state["ssm"] == ((n_lin, 32, 32, 128, 128),
                            jnp.dtype(assumed["state_dtype"]))
    assert state["conv_x"] == ((n_lin, 32, 8192, 3),
                               jnp.dtype(assumed["conv_tail_dtype"]))
    state_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                      for shape, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        serve["batch_size"] * memory["state_slot_bytes"]
    # both heads of a token share ONE slot of 512 lanes (pool_page)
    slots, lanes = pool_page(spec.gqa.num_kv_heads, spec.head_dim,
                             spec.gqa.tp)
    heads = memory["kv_pool_heads"]
    assert (slots, lanes) == (1, heads * spec.head_dim) and heads == 2
    pool = BlockKVSpec(num_layers=n_full,
                       num_blocks=serve["pa_num_blocks"] + 1,
                       block_size=serve["pa_block_size"], num_kv_heads=slots,
                       head_dim=lanes, dtype=spec.kv_dtype)
    assert pool.shape == (3, 4097, 32, 1, 512)
    assert str(jnp.dtype(pool.dtype)) == assumed["kv_dtype"]
    per_token = n_full * 2 * heads * spec.head_dim * 2
    assert per_token == memory["kv_bytes_per_token"]
    assert 2 * math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    weights = sum(
        math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
        for ps in jax.tree.leaves(
            model_base.decoder_param_specs(spec),
            is_leaf=lambda x: isinstance(x, ParamSpec)))
    # the program's count over the file's all-bf16 one: the router, A_log and
    # dt_bias in float32 (2 B more an entry), the vocabulary padded to 128s
    n = cfg["num_hidden_layers"]
    extra = 2 * (n * 2048 * 512 + n_lin * 2 * 32) \
        + 2 * 2 * 2048 * (spec.padded_vocab - cfg["vocab_size"])
    assert spec.padded_vocab - cfg["vocab_size"] == 32
    assert weights - extra == memory["weights_bytes"]
    total = memory["weights_bytes"] + memory["state_bytes"] \
        + memory["kv_pool_bytes"]
    assert 0.75 * 16e9 < total < 0.8 * 16e9


def _qwen_toy():
    """One period at a toy size (``tests/test_qwen3_next_paged.py``'s, a
    share of 4 experts of 16) as a configuration file the harness can build
    and gate."""
    from test_qwen3_next_paged import HF
    return dict(
        HF, family="qwen3_next", tp=1, dtype="float32",
        serve=dict(batch_size=4, seq_len=256, pa_block_size=8,
                   pa_num_blocks=160, context_encoding_buckets=[16, 64],
                   enable_bucketing=True, is_block_kv_layout=True,
                   is_prefix_caching=False),
        adapter={},
        gate=dict(config={}, batch=2, prompt_len=24, new_tokens=4,
                  atol=2e-4, rtol=1e-4, min_positions_held=1.0,
                  median_ratio_max=0.5, worst_ratio_max=1.0,
                  excuse_margin_max=0.0))


def test_the_qwen3_next_reference_gates_a_toy_twin():
    ref = build.load_reference("qwen3_next")
    assert ref.__file__ == os.path.join(BENCH, "references", "qwen3_next.py")
    toy = _qwen_toy()
    res = build.logit_gate(toy, seed=2**31 + 36, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 28 * toy["vocab_size"]


def _qwen_served_state_error(rounds_to=None, monkeypatch=None):
    """As :func:`_olmo_served_state_error`, with key heads shared by pairs
    of value heads: 150 prompt tokens = 64 + 64 + 22, then 60 decode steps,
    the state slot against the reference's ``final_states``."""
    import jax.numpy as jnp
    import numpy as np
    from harness import weights
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
    if rounds_to is not None:
        shapes = ssm.ssm_state_shapes

        def rounded(*a, **kw):
            out = shapes(*a, **kw)
            return dict(out, ssm=(out["ssm"][0], rounds_to))
        monkeypatch.setattr(ssm, "ssm_state_shapes", rounded)
    toy = _qwen_toy()
    hf = build.hf_config(toy)
    ref = build.load_reference(hf["model_type"])
    table = ref.weight_shapes(hf)
    w = weights.make_weights(table, seed=2**31 + 37)
    app = build.build_app(toy)
    app._put_params(app.family.convert_hf_state_dict(
        weights.HfView(table, w, dtype=np.dtype("float32")), app.spec))
    app.init_cache()
    ad = PagedEngineAdapter(app)
    prompt = np.random.default_rng(37).integers(
        1, hf["vocab_size"], size=150).tolist()
    stream = [ad.add_requests([3], [prompt])[3]]
    for _ in range(60):
        stream.append(ad.step([3])[3])
    got = np.asarray(app.cache["ssm"][:, ad._state_slot[3]], np.float32)
    want = np.asarray(ref.final_states(
        hf, w, jnp.asarray([prompt + stream[:-1]])))[:, 0]
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_a_served_qwen3_next_slot_holds_the_references_state():
    assert _qwen_served_state_error() < STATE_RTOL


def test_a_bf16_carried_qwen3_next_state_fails_the_state_check(monkeypatch):
    import jax.numpy as jnp
    err = _qwen_served_state_error(jnp.bfloat16, monkeypatch)
    assert err > 10 * STATE_RTOL, err


# ---------------------------------------------------------------------------
# longcat-flash-omni (ISSUE 40)
# ---------------------------------------------------------------------------

def test_longcat_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 40: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth, the experts held and the vocabulary, and
    nothing else."""
    cfg = build.load_json("configs", "longcat-flash-omni.json")
    published = dict(
        attention_bias=False, vocab_size=131072, hidden_size=6144,
        ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
        num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
        qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        routed_scaling_factor=6, n_routed_experts=512,
        max_position_embeddings=131072, rms_norm_eps=1e-05,
        rope_theta=10000000, attention_method="MLA", zero_expert_num=256,
        zero_expert_type="identity", moe_topk=12)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    # the cut sits ON the guide's floors: four layers, 16 of 512 experts (a
    # 32nd: one chip of a 32-chip stage), an eighth of the vocabulary; the
    # router keeps its published width
    assert cfg["num_layers"] == 4
    assert cfg["router_n_routed_experts"] == published["n_routed_experts"]
    assert cfg["n_routed_experts"] * 32 == cfg["router_n_routed_experts"]
    assert cfg["n_routed_experts"] >= 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["family"] == cfg["model_type"] == "longcat_flash"
    assert cfg["chips"] == cfg["tp"] == 1
    # one chunk of the widest bucket before each decode step (the file's
    # assumed.adapter says why not the defaults)
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        cfg["serve"]["context_encoding_buckets"])}
    assert cfg["serve"]["is_prefix_caching"] is True
    assert {"model_type", "hidden_act", "router_bias", "norm_topk_prob",
            "encoders_left_out", "kv_dtype", "latent_lanes",
            "router_dtype", "expert_names", "adapter"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    assert (twin["num_layers"], twin["hidden_size"], twin["n_routed_experts"],
            twin["router_n_routed_experts"], twin["zero_expert_num"]) == \
        (1, 6144, 16, 512, 256)
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert gate["min_positions_held"] >= 0.9
    for control in ("s_q dropped", "s_kv dropped", "identity term dropped",
                    "renormalised", "x 6 dropped", "shortcut",
                    "not interleaved", "selection bias dropped", "fp8"):
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "docqa-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 8192
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 40's, letter for letter
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 20.0, 8.0, 40)
    assert mix["prompt_len"] == dict(kind="lognormal", median=3072,
                                     sigma=0.6, lo=768, hi=7168)
    assert mix["output_len"] == dict(kind="lognormal", median=384, sigma=0.6,
                                     lo=96, hi=1024)


def test_longcat_allocates_what_its_file_says():
    """The pool's lanes and bytes a token, the weights and the total of the
    file's ``memory``, against what the program would allocate: the full
    configuration's pool and parameters as SHAPES (nothing of 13 GB is
    allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        latent_page, pool_spec)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "longcat-flash-omni.json")
    assumed, memory, serve = cfg["assumed"], cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert (spec.num_layers, spec.sub_blocks, spec.num_attn_layers,
            spec.num_moe_layers) == (4, 2, 8, 4)
    m = spec.moe
    assert (m.num_experts, m.num_routed, m.num_held, m.first_expert,
            m.zero_experts, m.top_k) == (768, 512, 16, 0, 256, 12)
    assert not m.normalize_topk and m.routed_scaling == 6.0
    assert abs(spec.mla.q_scale - 2.0) < 1e-12
    assert abs(spec.mla.kv_scale - 12 ** 0.5) < 1e-12
    # one row a token a sub-block: 576 values in 640 lanes, and no V
    slots, lanes, v_lanes = latent_page(spec.mla.latent_dim)
    assert spec.mla.latent_dim == memory["latent_values"] == 576
    assert (slots, lanes, v_lanes) == (1, memory["latent_lanes"], 0)
    assert lanes == assumed["latent_lanes"]["lanes"] == 640
    # what PagedCausalLMApplication.init_cache allocates
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.shape == (8, 8193, 32, 1, 640)
    assert pool.v_shape == (8, 8193, 32, 1, 0)
    assert str(jnp.dtype(pool.dtype)) == assumed["kv_dtype"]
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        8 * 640 * 2
    assert math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    # against the expanded heads the pool held before this PR
    assert 8 * 64 * (192 + 128) * 2 == 32 * memory["kv_bytes_per_token"]
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    assert sum(math.prod(ps.shape) for ps in leaves) == \
        memory["parameters"] == 4 * (638_874_368 + 16 * 37_748_736) \
        + 2 * 16_384 * 6_144 + 6_144
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the router and its
    # selection bias in float32 (2 B more an entry)
    extra = 2 * 4 * (6144 * 768 + 768)
    assert weights - extra == memory["weights_bytes"] == \
        2 * memory["parameters"]
    total = memory["weights_bytes"] + memory["kv_pool_bytes"]
    assert total == memory["before_temps_bytes"]
    assert 0.8 * 16e9 < total < 0.83 * 16e9


def _longcat_toy():
    """One layer's twin of a toy (``tests/test_longcat_flash_paged.py``'s: a
    share of 4 routed experts of 8 beside 4 identity columns) as a
    configuration file the harness can build and gate."""
    from test_longcat_flash_paged import _toy_file
    return _toy_file()


def test_the_longcat_reference_gates_a_toy_twin():
    ref = build.load_reference("longcat_flash")
    assert ref.__file__ == os.path.join(BENCH, "references",
                                        "longcat_flash.py")
    toy = _longcat_toy()
    res = build.logit_gate(toy, seed=2**31 + 40, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 28 * toy["vocab_size"]


def test_a_dropped_identity_term_does_not_pass_the_longcat_toy_gate(
        monkeypatch):
    from neuronx_distributed_inference_tpu.modules import moe
    monkeypatch.setattr(moe, "zero_expert_weight",
                        lambda spec, vals, idx: 0.0 * vals[..., 0])
    res = build.logit_gate(_longcat_toy(), seed=2**31 + 40,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 10


# ---------------------------------------------------------------------------
# smallthinker-21b-a3b (ISSUE 43)
# ---------------------------------------------------------------------------

def test_smallthinker_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 43: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth and the two layouts cut with it, and nothing
    else: all 64 experts and the whole vocabulary are held."""
    cfg = build.load_json("configs", "smallthinker-21b-a3b.json")
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6, moe_num_primary_experts=64,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
        rms_norm_eps=1e-06, rope_layout=[0, 1, 1, 1] * 13, rope_scaling=None,
        rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1] * 13,
        sliding_window_size=4096, tie_word_embeddings=False,
        vocab_size=151936)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    # two whole periods of the published layout
    assert cfg["num_hidden_layers"] == 8
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == \
        published["sliding_window_layout"][:8]
    assert cfg["family"] == cfg["model_type"] == "smallthinker"
    assert cfg["chips"] == cfg["tp"] == 1
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        cfg["serve"]["context_encoding_buckets"])}
    # a ring is overwritten: prefix reuse is refused, so the file has it off
    assert cfg["serve"]["is_prefix_caching"] is False
    assert {"model_type", "router_input", "attention", "expert_gate",
            "experts", "routing", "rope", "kv_dtype", "router_dtype",
            "window_pool", "adapter",
            "two_rooflines_not_listed"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: one period at the published widths, the window shrunk FOR
    # THE TWIN so that 128 tokens cross it
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["moe_num_primary_experts"], twin["vocab_size"],
            twin["sliding_window_size"]) == (4, 2560, 64, 151936, 64)
    assert twin["sliding_window_layout"] == twin["rope_layout"] == \
        [0, 1, 1, 1]
    assert "TWIN" in gate["config_why"]
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    assert twin["sliding_window_size"] < gate["prompt_len"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    # between the sound twin's least share over the seeds of PR 43 (0.703)
    # and the weakest control's decode share (0.14); the median between the
    # sound twin's largest (0.488) and one precision down (6.0)
    assert 0.14 < gate["min_positions_held"] < 0.70
    assert 0.49 < gate["median_ratio_max"] < 6.0
    for control in ("window mask dropped", "rotary applied on global",
                    "one token wide", "post-attention norm", "SiLU for ReLU",
                    "not renormalised", "fp8"):
        assert control in gate["controls"], control
    ref = build.load_reference("smallthinker")
    assert len(ref.CONTROLS) == 6
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "mixedlen-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 15360 <= \
        cfg["max_position_embeddings"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 43's, every number of it; what it does to the 95th
    # percentile of the token gaps is reported, not tuned around (the
    # file's tail_note; PERF.md section 6)
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 30.0, 8.0, 43)
    assert mix["prompt_len"] == dict(kind="lognormal", median=4096,
                                     sigma=1.0, lo=256, hi=14336)
    assert "not tuned around it" in mix["tail_note"]
    assert mix["output_len"] == dict(kind="lognormal", median=384, sigma=0.6,
                                     lo=96, hi=1024)
    # the cell is NOT on the two rooflines whose yardsticks do not fit it,
    # and on their counterparts by layer kind and for a whole expert stack
    cell = "smallthinker-mixedlen-closed"
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if cell in m.get("workloads", ())}
    assert not {"kernel.paged_decode_roofline",
                "kernel.moe_decode_roofline"} & listed
    assert {"kernel.paged_decode_window_roofline",
            "kernel.moe_decode_experts_roofline",
            "kv.window_pages_held_share", "moe.prefill_walk_share",
            "moe.experts_touched_share", "host.stall_s"} <= listed


def test_smallthinker_allocates_what_its_file_says():
    """The two pools, the ring, the weights and the total of the file's
    ``memory``, against what the program would allocate: the full
    configuration's pools and parameters as SHAPES (nothing of 11.7 GB is
    allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        pool_spec, window_pool_spec, window_ring_pages)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "smallthinker-21b-a3b.json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert spec.window_pool and spec.nope_global
    assert spec.layer_pattern == (False, True, True, True) * 2
    assert spec.sliding_window == 4096
    assert (spec.num_attn_layers, spec.num_window_layers,
            spec.num_moe_layers) == (8, memory["window_layers"], 8)
    assert spec.num_attn_layers - spec.num_window_layers == \
        memory["global_layers"] == 2
    m = spec.moe
    assert (m.num_experts, m.top_k, m.intermediate_size, m.act) == \
        (64, 6, 768, "relu")
    assert m.pre_softmax_topk and m.normalize_topk and m.router_pre_attn
    assert abs(spec.rope.rope_theta - 1.5e6) < 1e-6
    # what PagedCausalLMApplication.init_cache allocates: the global
    # layers' pool from pa_num_blocks, the window layers' from the spec,
    # the rows and the widest warmed width
    widest = max(serve["context_encoding_buckets"])
    ring = window_ring_pages(4096, widest, serve["pa_block_size"])
    assert ring == memory["window_ring_pages"] == 137
    assert ring * serve["pa_block_size"] == memory["window_ring_tokens"] \
        <= 4096 + 256 + 32 + 32
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    wpool = window_pool_spec(spec, serve["batch_size"],
                             serve["pa_block_size"], widest)
    assert pool.shape == (2, 15361, 32, 1, 512)
    assert wpool.shape == (6, 32 * 137, 32, 1, 512)
    assert str(jnp.dtype(pool.dtype)) == cfg["assumed"]["kv_dtype"]
    per_layer = pool.bytes_per_token // pool.num_layers
    assert per_layer == memory["kv_bytes_per_token_per_layer"] == \
        4 * 128 * 2 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["global_pool_bytes"]
    assert 2 * math.prod(wpool.shape) * 2 == memory["window_pool_bytes"] == \
        6 * 2048 * 32 * 4384
    # one pool for every layer at this context (8.05 GB) beside the weights
    # leaves under a gigabyte of the 15.75 GiB a program may use, less than
    # any of the cell's programs needs in temps
    assert 8 * 2048 * 32 * 15360 + memory["weights_bytes"] + 1e9 \
        > 15.75 * 2 ** 30
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    assert sum(math.prod(ps.shape) for ps in leaves) == \
        memory["parameters"] == 8 * (21_140_480 + 64 * 5_898_240) \
        + 2 * 151_936 * 2560 + 2560
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the routers in
    # float32 (2 B more an entry)
    assert weights - 2 * 8 * 2560 * 64 == memory["weights_bytes"] == \
        2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["global_pool_bytes"]
             + memory["window_pool_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.72 * 16e9 < total < 0.74 * 16e9


def test_the_smallthinker_reference_gates_a_toy_twin():
    from test_smallthinker_paged import _toy_file
    ref = build.load_reference("smallthinker")
    assert ref.__file__ == os.path.join(BENCH, "references",
                                        "smallthinker.py")
    toy = _toy_file()
    res = build.logit_gate(toy, seed=2**31 + 43, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * toy["vocab_size"]


@pytest.mark.parametrize("fault", ["window", "router", "relu"])
def test_a_fault_in_the_program_does_not_pass_the_smallthinker_toy_gate(
        monkeypatch, fault):
    """The other direction of the controls: the PROGRAM broken, the
    reference sound. A window layer that attends over everything its ring
    holds, a router fed the experts' input, SiLU in the experts."""
    import jax
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import moe
    from neuronx_distributed_inference_tpu.ops import attention as attn_ops
    from test_smallthinker_paged import _toy_file
    if fault == "window":
        causal = attn_ops.causal_mask
        monkeypatch.setattr(
            attn_ops, "causal_mask",
            lambda q, k, valid=None, window=0, chunk=0: causal(
                q, k, valid, 0, chunk))
        monkeypatch.setattr(model_base, "_paged_kernel_declined",
                            lambda spec: "the test's")
    elif fault == "router":
        block = moe.moe_block
        monkeypatch.setattr(
            model_base, "moe_block",
            lambda spec, x, w, router_x=None, **kw: block(
                spec, x, w, router_x=x, **kw))
    else:
        monkeypatch.setitem(model_base.ACT_FNS, "relu", jax.nn.silu)
    res = build.logit_gate(_toy_file(), seed=2**31 + 43,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 10


# ---------------------------------------------------------------------------
# deepseek-v3 (ISSUE 47)
# ---------------------------------------------------------------------------

def test_deepseek_v3_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 47: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth, the leading dense layers, the experts held
    and the vocabulary, and nothing else."""
    cfg = build.load_json("configs", "deepseek-v3.json")
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=3,
        hidden_act="silu", hidden_size=7168, intermediate_size=18432,
        kv_lora_rank=512, max_position_embeddings=163840,
        model_type="deepseek_v3", moe_intermediate_size=2048,
        moe_layer_freq=1, n_group=8, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128,
        num_experts_per_tok=8, num_hidden_layers=61, num_key_value_heads=128,
        num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-06,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
        v_head_dim=128, vocab_size=129280)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    # the cut sits ON the guide's floors: the leading dense layers once and
    # four expert layers, 16 of 256 experts (one chip of 16 a layer), an
    # eighth of the vocabulary; the router keeps its published width, its
    # groups and its top 8
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (5, 1)
    assert cfg["router_n_routed_experts"] == published["n_routed_experts"]
    assert cfg["n_routed_experts"] * 16 == cfg["router_n_routed_experts"]
    assert cfg["n_routed_experts"] >= 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["family"] == cfg["model_type"] == "deepseek_v3"
    assert cfg["chips"] == cfg["tp"] == 1
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        cfg["serve"]["context_encoding_buckets"])}
    assert cfg["serve"]["is_prefix_caching"] is True
    assert {"kv_dtype", "latent_lanes", "router_dtype", "mtp",
            "selection_bias_draw", "rope_interleave", "ep_size",
            "expert_names", "adapter"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    assert (twin["num_hidden_layers"], twin["first_k_dense_replace"],
            twin["hidden_size"], twin["n_routed_experts"],
            twin["router_n_routed_experts"], twin["n_group"]) == \
        (2, 1, 7168, 16, 256, 8)
    # eight rows, not ISSUE 47's four: the share's routing flips leave ~3 %
    # of sound positions over their bound, and 64 decode positions cannot
    # tell that from a control's 19 % (the file's gate.tolerance_why)
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (8, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert gate["min_positions_held"] >= 0.9
    for control in ("groups dropped", "scored by its maximum",
                    "bias dropped", "bias added to the weights",
                    "renormalisation dropped", "x 2.5 dropped",
                    "shared expert dropped", "softmax for sigmoid",
                    "mscale^2 dropped", "yarn's frequencies dropped",
                    "not interleaved", "fp8"):
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "longreason-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 12288
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # ... and its rows run past yarn's original context
    assert mix["prompt_len"]["hi"] > cfg["rope_scaling"][
        "original_max_position_embeddings"]
    # the mix is ISSUE 47's, letter for letter
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"]) == ("closed", 2, 4096, 30.0, 8.0)
    assert mix["prompt_len"] == dict(kind="lognormal", median=2048,
                                     sigma=0.8, lo=256, hi=8192)
    assert mix["output_len"] == dict(kind="lognormal", median=1536,
                                     sigma=0.6, lo=384, hi=4096)
    seeds = [build.load_json("traffic", os.path.basename(p))["base_seed"]
             for p in sorted(glob.glob(os.path.join(BENCH, "traffic",
                                                    "*.json")))]
    assert seeds.count(mix["base_seed"]) == 1
    cell = bench_run.load_cell("deepseek-v3-longreason-closed")
    assert {"moe.group_hit_share", "kernel.moe_decode_held_roofline",
            "kernel.mla_decode_roofline", "moe.experts_touched_share",
            "moe.prefill_walk_share"} <= {m["name"] for m in cell["per_layer"]}


def test_deepseek_v3_allocates_what_its_file_says():
    """The pool's lanes and bytes a token, the weights and the total of the
    file's ``memory``, against what the program would allocate: the full
    configuration's pool and parameters as SHAPES (nothing of 11.6 GB is
    allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        latent_page, pool_spec)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "deepseek-v3.json")
    assumed, memory, serve = cfg["assumed"], cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert (spec.num_layers, spec.first_dense, spec.num_attn_layers,
            spec.num_moe_layers) == (5, 1, 5, 4)
    m = spec.moe
    assert (m.num_experts, m.num_routed, m.num_held, m.first_expert,
            m.top_k, m.n_group, m.topk_group) == (256, 256, 16, 0, 8, 8, 4)
    assert m.normalize_topk and m.routed_scaling == 2.5
    assert m.shared_intermediate == 2048 and m.router_act == "sigmoid"
    assert spec.num_q_heads == 128 and spec.rope.scaling_type == "yarn"
    assert abs(spec.scale - 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2) \
        < 1e-9
    # one row a token a layer: 576 values in 640 lanes, and no V
    slots, lanes, v_lanes = latent_page(spec.mla.latent_dim)
    assert spec.mla.latent_dim == memory["latent_values"] == 576
    assert (slots, lanes, v_lanes) == (1, memory["latent_lanes"], 0)
    assert lanes == assumed["latent_lanes"]["lanes"] == 640
    # what PagedCausalLMApplication.init_cache allocates
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.shape == (5, 12289, 32, 1, 640)
    assert pool.v_shape == (5, 12289, 32, 1, 0)
    assert str(jnp.dtype(pool.dtype)) == assumed["kv_dtype"]
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        5 * 640 * 2
    assert math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    # against expanded heads: 64 x
    assert 5 * 128 * (192 + 128) * 2 == 64 * memory["kv_bytes_per_token"]
    specs = model_base.decoder_param_specs(spec)
    is_leaf = dict(is_leaf=lambda x: isinstance(x, ParamSpec))

    def count(tree):
        return sum(math.prod(ps.shape) for ps in jax.tree.leaves(tree,
                                                                 **is_leaf))
    # ISSUE 47's recount, by stack
    assert count(specs["layers"]) == 583_483_392
    assert count(specs["moe_layers"]) == 4 * 937_640_192 == \
        4 * (232_997_120 + 16 * 44_040_192)
    leaves = jax.tree.leaves(specs, **is_leaf)
    # the program rounds the vocabulary's 16,160 rows up to whole tiles
    # (16,256: model_base.pad_vocab), in the embedding and in the head
    pad = 2 * (spec.padded_vocab - cfg["vocab_size"]) * 7168
    assert spec.padded_vocab == 16_256
    assert count(specs) - pad == memory["parameters"] == 583_483_392 \
        + 4 * 937_640_192 + 2 * 16_160 * 7168 + 7168
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the padding rows,
    # and the router and its selection bias in float32 (2 B more an entry)
    extra = 2 * pad + 2 * 4 * (7168 * 256 + 256)
    assert weights - extra == memory["weights_bytes"] == \
        2 * memory["parameters"]
    total = memory["weights_bytes"] + memory["kv_pool_bytes"]
    assert total == memory["before_temps_bytes"]
    assert 0.72 * 16e9 < total < 0.74 * 16e9
    # with the widest program's temps (tests/test_chip_aot.py) under the
    # 15.75 GiB a program may use
    assert total + extra + memory["widest_program_temps_bytes"] \
        < 15.75 * 2 ** 30 - 1e9


def test_the_deepseek_v3_reference_gates_a_toy_twin():
    from test_deepseek_v3_paged import _toy_file
    ref = build.load_reference("deepseek_v3")
    assert ref.__file__ == os.path.join(BENCH, "references",
                                        "deepseek_v3.py")
    toy = _toy_file()
    res = build.logit_gate(toy, seed=2**31 + 47, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 28 * toy["vocab_size"]


@pytest.mark.parametrize("fault", ["groups", "shared", "bias"])
def test_a_fault_in_the_program_does_not_pass_the_deepseek_v3_toy_gate(
        monkeypatch, fault):
    """The other direction of the controls: the PROGRAM broken, the
    reference sound. A router that takes the top k of every group, a block
    without its shared expert, a selection bias that is not added."""
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.modules import moe
    from test_deepseek_v3_paged import _toy_file
    if fault == "groups":
        monkeypatch.setattr(
            moe, "chosen_groups",
            lambda spec, select: jnp.ones(
                select.shape[:-1] + (spec.n_group,), bool))
    elif fault == "shared":
        monkeypatch.setattr(moe, "shared_experts",
                            lambda spec, x, layer_w: jnp.zeros_like(x))
    else:
        route = moe.route_groups
        monkeypatch.setattr(
            moe, "route_groups",
            lambda spec, h, router_w, router_bias=None: route(
                spec, h, router_w, None))
    res = build.logit_gate(_toy_file(), seed=2**31 + 47,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 10


# ---------------------------------------------------------------------------
# keye-vl-2.0-30b-a3b (ISSUE 50)
# ---------------------------------------------------------------------------

KEYE_CELL = "keye-vl2-videoqa-closed"


def test_keye_vl2_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 50: every key at the top level of the file, no width changed,
    ``sa_config`` whole, and ``reduced`` names the depth, the experts held
    and the vocabulary, and nothing else."""
    cfg = build.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=262144, max_window_layers=48,
        mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        num_local_experts=128, rms_norm_eps=1e-06,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        rope_theta=10000000,
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048},
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    # one chip's share of a stage: the guide's floors kept
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_num_experts"], cfg["first_expert"]) == \
        (12, 16, 128, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["family"] == "keye_vl2" and cfg["chips"] == cfg["tp"] == 1
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        cfg["serve"]["context_encoding_buckets"])}
    assert cfg["serve"]["is_prefix_caching"] is True
    assert {"qk_norm", "indexer_input", "index_key_norm_and_rotary",
            "indexer_tensor_names", "head_weight_scales", "chunk_sizes",
            "index_score_precision", "selection", "index_key_pool", "mrope",
            "vision_tower", "kv_dtype", "router_dtype", "expert_names",
            "decode_form", "adapter", "moe_rooflines",
            "seeded_routing"} <= set(cfg["assumed"])
    assert "NO effect on the result" in cfg["assumed"]["chunk_sizes"]
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: two layers at the published widths, topk shrunk FOR THE
    # TWIN so that 128 tokens a row cross it
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["num_experts"], twin["router_num_experts"],
            twin["vocab_size"]) == (2, 2048, 16, 128, 18992)
    assert twin["sa_config"] == dict(published["sa_config"], topk=32)
    assert "FOR THE TWIN" in gate["config_why"]
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (8, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    assert twin["sa_config"]["topk"] < gate["prompt_len"]
    assert gate["excuse_margin_max"] == 0.01
    # between sound's largest median over the seeds of PR 50 (0.28) and
    # the weakest failing control's (the index key in fp8: 1.8); under
    # sound's least decode share (0.33), over the controls' (0.0)
    assert 0.28 < gate["median_ratio_max"] < 1.8
    assert 0.0 < gate["min_positions_held"] < 0.33
    for control in ("dense attention", "ReLU dropped", "head weights",
                    "topk halved", "un-normed", "un-rotated", "fp8",
                    "ONE control PASSES"):
        assert control in gate["controls"], control
    ref = build.load_reference("KeyeVL2")
    assert len(ref.CONTROLS) == 8
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "videoqa-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 12288 <= \
        cfg["max_position_embeddings"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 50's, every number of it
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 30.0, 8.0, 50)
    assert mix["prompt_len"] == dict(kind="lognormal", median=5120,
                                     sigma=0.6, lo=1024, hi=10240)
    assert mix["output_len"] == dict(kind="lognormal", median=768, sigma=0.6,
                                     lo=192, hi=2048)
    assert build.load_json("cells", KEYE_CELL + ".json") == dict(
        config="keye-vl-2.0-30b-a3b", traffic="videoqa-closed", chips=1)
    # the cell is on the five metrics this PR brings, one of them the
    # expert roofline of a share under Qwen's key names
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if KEYE_CELL in m.get("workloads", ())}
    assert {"step.decode_indexer_ms", "step.prefill_indexer_ms",
            "attn.sparse_selected_share", "kernel.sparse_decode_roofline",
            "kernel.moe_decode_share_roofline",
            "kernel.paged_decode_roofline",
            "attn.paged_prefill_kernel_share", "moe.prefill_walk_share",
            "attn.sparse_select_kernel_share",
            "moe.experts_touched_share", "host.stall_s"} <= listed
    assert KEYE_CELL in next(m for m in BENCHMARK["end_to_end"]
                             if m["name"] == "tokens_per_s")["workloads"]


def test_keye_vl2_allocates_what_its_file_says():
    """The three pools, the weights and the total of the file's ``memory``,
    against what the program would allocate: the full configuration's pools
    and parameters as SHAPES (nothing of 12.75 GB is allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        index_pool_shape, pool_spec)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    sp, m = spec.sparse, spec.moe
    assert (sp.index_heads, sp.index_dim, sp.topk) == (16, 64, 2048)
    assert abs(sp.rope.rope_theta - 1e7) < 1e-3 and sp.rope.head_dim == 64
    assert (m.num_experts, m.num_held, m.first_expert, m.top_k,
            m.intermediate_size) == (128, 16, 0, 8, 768)
    assert m.normalize_topk and spec.qk_norm and spec.num_layers == 12
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.shape == (12, 12289, 32, 1, 512)
    assert str(jnp.dtype(pool.dtype)) == cfg["assumed"]["kv_dtype"]
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        12 * 4 * 128 * 2 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    index = index_pool_shape(spec, serve["pa_num_blocks"],
                             serve["pa_block_size"])
    # two tokens of 64 values to a 128-lane row: no byte pads a key
    assert index == (12, 12289, 16, 128)
    assert math.prod(index) * 2 == memory["index_pool_bytes"]
    assert memory["index_bytes_per_token"] == 12 * 64 * 2
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    a_layer = (2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 2 * 2048 + 2 * 128
               + 2048 * (16 * 64 + 64 + 16) + 2 * 64 + 2048 * 128)
    assert a_layer == 21_401_984
    # the file counts 18,992 rows of vocabulary; the program rounds them up
    padded = 2 * (spec.padded_vocab - cfg["vocab_size"]) * 2048
    assert sum(math.prod(ps.shape) for ps in leaves) - padded == \
        memory["parameters"] == 12 * (a_layer + 16 * 3 * 2048 * 768) \
        + 2 * 18_992 * 2048 + 2048 == 1_240_586_752
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the routers in
    # float32 (2 B more an entry) and the padded rows
    assert weights - 2 * 12 * 2048 * 128 - 2 * padded == \
        memory["weights_bytes"] == 2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["kv_pool_bytes"]
             + memory["index_pool_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.79 * 16e9 < total < 0.81 * 16e9
    assert total + memory["widest_program_temps_bytes"] < 15.75 * 2 ** 30


def test_keye_vl2_share_roofline_counts_what_the_walk_must_read(monkeypatch):
    """The expert roofline of a share under Qwen's key names, from a
    made-up window: the need is the held experts a decode step TOUCHED
    times an expert's three projections plus every layer's router, at the
    stream's bandwidth; the time the ``moe`` scope's of ``paged.w1``; a
    program without the counters, or a configuration of other key names,
    reads nothing."""
    from harness import host_spans, readers
    metric = "kernel.moe_decode_share_roofline"
    cfg = build.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    expert, router = 3 * 2048 * 768 * 2, 2048 * 128 * 2
    programs = {"paged.w1": dict(count=100, total_s=1.5),
                "paged.w256": dict(count=90, total_s=1.0)}
    scopes = {"paged.w1": {"moe": 0.2, "attn": 0.9},
              "paged.w256": {"moe": 0.135, "attn": 0.4}}

    def ctx(counters, config=cfg):
        # what harness/readers.py hands a python reader, cut to what this
        # one reads; the profiled slice as each program's time by scope
        return {"config": config, "peaks": {"hbm_gbps": 819.0},
                "warm_widths": [1, 64, 256], "before": {"counters": {}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}},
                "trace": {"programs": programs},
                "_slice": {"scopes": {
                    label: dict(programs[label], scopes=scopes[label])
                    for label in programs}}}
    monkeypatch.setattr(host_spans, "load_slice", lambda c: c["_slice"])
    # 50 decode steps fetched, 12.5 of 16 held experts touched a layer
    counters = dict(moe_expert_slots=50 * 12 * 16,
                    moe_experts_touched=50 * 12 * 12.5)
    got = readers.read_metric(metric, ctx(counters))
    least = (12 * 12.5 * expert + 12 * router) / 819e9
    assert got == pytest.approx(100 * least / 2e-3) and 80 < got < 90
    assert readers.read_metric(metric, ctx({})) is None
    assert readers.read_metric(
        metric, ctx(counters, dict(cfg, router_num_experts=None))) is None
    # every held expert touched is the ceiling of the need: 2.22 ms a step
    full = dict(counters, moe_experts_touched=50 * 12 * 16)
    ceiling = (12 * 16 * expert + 12 * router) / 819e9
    assert ceiling == pytest.approx(2.2201e-3, rel=1e-3)
    assert readers.read_metric(metric, ctx(full)) == \
        pytest.approx(100 * ceiling / 2e-3)


def test_keye_vl2_select_kernel_share_reads_the_adapters_two_counters():
    """``attn.sparse_select_kernel_share`` (ISSUE 51): the dispatches whose
    program's selection ran on the kernel over the dispatches of a stack
    with an indexer, the cell's alone; a program without the counters (the
    parent, a stack without a selection) reads nothing."""
    from harness import readers
    metric = "attn.sparse_select_kernel_share"
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert entry == dict(
        name=metric, unit="%", better="higher", source="program_counter",
        layer="Step graphs", moves="itl_p50_ms", workloads=[KEYE_CELL])
    # appended where it was added: what follows it is ISSUE 52's seven,
    # then ISSUE 54's five, ISSUE 56's four, ISSUE 61's two, ISSUE 62's one,
    # ISSUE 64's two and ISSUE 67's two
    later = BENCHMARK["per_layer"][BENCHMARK["per_layer"].index(entry) + 1:]
    assert [m["name"] for m in later] == list(
        TTFT_METRICS + PHI4_METRICS + COMMAND_A_METRICS + LFM2_METRICS
        + CARRY_METRICS + NEMOTRON_METRICS + LING_METRICS)

    def ctx(**counters):
        return {"before": {"counters": {"host_stats.sparse_dispatches": 10}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}}}
    assert readers.read_metric(metric, ctx(
        sparse_dispatches=231, sparse_dispatches_select_kernel=87)) == \
        pytest.approx(100 * 87 / 221)
    assert readers.read_metric(metric, ctx(dispatches=5)) is None


#: ISSUE 54's per-layer metrics, appended last (the cell's own tests are
#: tests/test_phi4flash_paged.py)
PHI4_METRICS = ("step.decode_cross_attn_ms", "step.prefill_cross_attn_ms",
                "step.decode_gmu_ms", "kernel.paged_decode_shared_roofline",
                "prefill.cross_decoder_token_share")
#: ISSUE 56's, appended last
COMMAND_A_METRICS = ("step.decode_shared_ms", "step.prefill_shared_ms",
                     "kernel.paged_decode_layer_types_roofline",
                     "kernel.moe_decode_avg_shared_roofline")
TTFT_METRICS = ("ttft.accept_ms", "ttft.queue_ms", "ttft.prefill_wait_ms",
                "ttft.prefill_ms", "ttft.write_ms", "ttft.server_ms",
                "ttft.device_idle_share")


@pytest.mark.parametrize("metric", TTFT_METRICS)
def test_a_ttft_phase_metric_is_the_chat_cells_alone(metric):
    """ISSUE 52's seven: appended last, each moving ``ttft_p50_ms`` in the
    one cell that reports it; the six means are data files over
    ``engine.ttft_*`` (``ServingEngine.stats``, always on) and read nothing
    from a program without the keys."""
    from harness import readers
    # (ISSUE 54's five, ISSUE 56's four, ISSUE 61's two, ISSUE 62's one,
    # ISSUE 64's two and ISSUE 67's two were appended behind them)
    assert tuple(m["name"] for m in BENCHMARK["per_layer"][-23:]) == \
        TTFT_METRICS + PHI4_METRICS + COMMAND_A_METRICS + LFM2_METRICS \
        + CARRY_METRICS + NEMOTRON_METRICS + LING_METRICS
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "ttft_p50_ms"
    assert entry["workloads"] == ["olmoe-chat-steady"]
    spec = build.load_json("layer_metrics", metric + ".json")
    assert {k: spec[k] for k in ("unit", "better", "source", "layer")} == \
        {k: entry[k] for k in ("unit", "better", "source", "layer")}
    if metric == "ttft.device_idle_share":
        assert spec["reader"] == {"kind": "python"}
        return
    key = "engine.ttft_" + metric[len("ttft."):-len("_ms")] + "_s"
    assert spec["reader"] == {"kind": "counter_ratio", "args": {
        "num": [key], "den": ["engine.ttft_requests"], "scale": 1000.0}}

    def ctx(**after):
        return {"before": {"counters": {"engine.ttft_requests": 4, key: 0.1}},
                "after": {"counters": {"engine." + k: v
                                       for k, v in after.items()}}}
    assert readers.read_metric(metric, ctx(
        **{"ttft_requests": 24, key[len("engine."):]: 0.5})) == \
        pytest.approx(1000 * 0.4 / 20)
    assert readers.read_metric(metric, {
        "before": {"counters": {"engine.submitted": 1}},
        "after": {"counters": {"engine.submitted": 9}}}) is None


def test_the_keye_vl2_reference_gates_a_toy_twin():
    from test_keye_vl2_paged import _toy_file
    ref = build.load_reference("KeyeVL2")
    assert ref.__file__ == os.path.join(BENCH, "references", "KeyeVL2.py")
    toy = _toy_file()
    twin = build.hf_config(toy, build.gate_overrides(toy["gate"]))
    assert twin["sa_config"]["topk"] == 8 < toy["gate"]["prompt_len"]
    res = build.logit_gate(toy, seed=2**31 + 51, served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * toy["vocab_size"]


@pytest.mark.parametrize("fault", ["selection", "head_weights",
                                   "index_keys"])
def test_a_fault_in_the_program_does_not_pass_the_keye_vl2_toy_gate(
        monkeypatch, fault):
    """The other direction of the controls: the PROGRAM broken, the
    reference sound. An attention that reads every cached token, index
    scores that drop their head weights, index keys that never reach their
    pool."""
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import block_kv_cache
    from test_keye_vl2_paged import _toy_file
    if fault == "selection":
        monkeypatch.setattr(model_base, "topk_select",
                            lambda scores, valid, k: valid)
    elif fault == "head_weights":
        scores = model_base._index_scores
        monkeypatch.setattr(
            model_base, "_index_scores",
            lambda sp, qi, w, rows: scores(sp, qi, jnp.ones_like(w), rows))
    else:
        monkeypatch.setattr(
            block_kv_cache, "write_index_keys",
            lambda pool, new, layer, slots, positions, bs: pool)
    res = build.logit_gate(_toy_file(), seed=2**31 + 51,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 10


# ---------------------------------------------------------------------------
# command-a-plus-05-2026 (ISSUE 56)
# ---------------------------------------------------------------------------

COMMAND_A_CELL = "command-a-plus-agent-closed"
COMMAND_A_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def test_command_a_plus_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 56: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth, the layer types cut with it, the experts
    held and the vocabulary, and nothing else."""
    cfg = build.load_json("configs", "command-a-plus-05-2026.json")
    published = dict(
        attention_bias=False, expert_selection_fn="sigmoid",
        first_k_dense_replace=0, head_dim=128, hidden_act="silu",
        hidden_size=4096, intermediate_size=4096, layer_norm_eps=1e-05,
        layer_switch=4, layer_types=COMMAND_A_PERIOD * 8, logit_scale=1,
        max_position_embeddings=200000, model_type="cohere2_moe",
        norm_topk_prob=True, num_attention_heads=128, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=32, num_key_value_heads=8,
        num_shared_experts=4,
        order_of_interleaved_layers="local_attn_first",
        position_embedding_type="rope_gptj",
        prefix_dense_intermediate_size=16384,
        prefix_dense_sliding_window_pattern=1, rms_norm_eps=None,
        rope_parameters={"rope_theta": 50000, "rope_type": "default"},
        rope_theta=50000, rotary_pct=1,
        shared_expert_combination_strategy="average", sliding_window=4096,
        tf_legacy_loss=False, tie_word_embeddings=True,
        use_embedding_sharing=True, use_gated_activation=True,
        use_parallel_block=True, use_parallel_embedding=False,
        use_qk_norm=False, vocab_size=262144)
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_experts", "num_hidden_layers", "vocab_size"]
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "command-a-plus-05-2026")
    assert sorted(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"]
    # ON the guide's floors: one whole period and four layers, 16 >= 8
    # experts of a router over all 128, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 4
    assert cfg["layer_types"] == COMMAND_A_PERIOD
    assert (cfg["num_experts"], cfg["router_num_experts"],
            cfg["first_expert"]) == (16, 128, 0)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["family"] == cfg["model_type"] == "cohere2_moe"
    assert cfg["chips"] == cfg["tp"] == 1 and cfg["dtype"] == "bfloat16"
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        cfg["serve"]["context_encoding_buckets"])}
    assert cfg["serve"]["is_prefix_caching"] is False
    assert "64 v5e chips" in cfg["deployment"]
    assert {"intermediate_size", "shared_average", "routing",
            "nope_full_layers", "window_mask", "layernorm", "router_dtype",
            "tensor_names", "prefix_dense", "logit_scale", "vision_tower",
            "kv_dtype", "window_pool", "adapter",
            "rooflines_not_listed"} <= set(cfg["assumed"])
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: the file's own period at the published widths, the window
    # shrunk FOR THE TWIN so that 128 tokens cross it
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["num_experts"], twin["router_num_experts"],
            twin["vocab_size"], twin["sliding_window"]) == \
        (4, 4096, 16, 128, 32768, 64)
    assert twin["layer_types"] == COMMAND_A_PERIOD
    assert "TWIN" in gate["config_why"]
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * cfg["serve"]["pa_block_size"]
    assert twin["sliding_window"] < gate["prompt_len"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    ref = build.load_reference("cohere2_moe")
    assert {"rope_halves", "rope_on_full", "shared_sum", "router_bf16",
            "no_window"} <= set(ref.CONTROLS)
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "agent-longctx-closed.json")
    serve = cfg["serve"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 12288 <= \
        cfg["max_position_embeddings"]
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 56's, every number of it
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 40.0, 8.0, 56)
    assert mix["prompt_len"] == dict(kind="lognormal", median=4096,
                                     sigma=0.7, lo=512, hi=8192)
    assert mix["output_len"] == dict(kind="lognormal", median=1536,
                                     sigma=0.6, lo=384, hi=4096)
    assert build.warm_widths(cfg, mix) == [1, 64, 256]
    # the cell is on its own two rooflines and on none keyed on another
    # family's names
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if COMMAND_A_CELL in m.get("workloads", ())}
    assert set(COMMAND_A_METRICS) <= listed
    assert {m["name"] for m in BENCHMARK["per_layer"]
            if m.get("workloads") == [COMMAND_A_CELL]} == \
        set(COMMAND_A_METRICS)
    assert not {m for m in listed if m.endswith("_roofline")} \
        - set(COMMAND_A_METRICS)
    assert {"kv.window_pages_held_share", "moe.prefill_walk_share",
            "moe.experts_touched_share", "moe.experts_skipped_share",
            "attn.paged_prefill_kernel_share", "step.decode_moe_ms",
            "host.stall_s", "sched.live_batch_mean"} <= listed
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == COMMAND_A_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("command-a-plus-05-2026", "agent-longctx-closed", 1)
    assert COMMAND_A_CELL in next(
        m for m in BENCHMARK["end_to_end"]
        if m["name"] == "tokens_per_s")["workloads"]


def test_command_a_plus_allocates_what_its_file_says():
    """The two pools, the ring, the weights and the total of the file's
    ``memory``, against what the program would allocate: the full
    configuration's pools and parameters as SHAPES (nothing of 12.8 GB is
    allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
        pool_spec, window_pool_spec, window_ring_pages)
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "command-a-plus-05-2026.json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert spec.window_pool and spec.nope_global and spec.rope_interleaved
    assert spec.block_style == "parallel_shared"
    assert spec.norm_type == "layernorm" and spec.logits_divide is None
    assert spec.layer_pattern == (True, True, True, False)
    assert spec.sliding_window == 4096 and spec.tie_word_embeddings
    assert (spec.num_attn_layers, spec.num_window_layers,
            spec.num_moe_layers) == (4, memory["window_layers"], 4)
    assert spec.num_attn_layers - spec.num_window_layers == \
        memory["global_layers"] == 1
    m = spec.moe
    assert (m.num_experts, m.num_held, m.first_expert, m.top_k,
            m.intermediate_size, m.router_act, m.act) == \
        (128, 16, 0, 8, 4096, "sigmoid", "silu")
    assert m.normalize_topk and m.routed_scaling is None \
        and not m.has_router_bias and m.n_group == 1
    assert (m.shared_intermediate, m.shared_mean_of) == (16384, 4)
    assert abs(spec.rope.rope_theta - 50000) < 1e-6
    widest = max(serve["context_encoding_buckets"])
    ring = window_ring_pages(4096, widest, serve["pa_block_size"])
    assert ring == memory["window_ring_pages"] == 137
    assert ring * serve["pa_block_size"] == memory["window_ring_tokens"]
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    wpool = window_pool_spec(spec, serve["batch_size"],
                             serve["pa_block_size"], widest)
    # a head a slot: 8 kv heads of 128 lanes do not fold
    assert pool.shape == (1, 12289, 32, 8, 128)
    assert wpool.shape == (3, 32 * 137, 32, 8, 128)
    assert str(jnp.dtype(pool.dtype)) == cfg["assumed"]["kv_dtype"]
    assert pool.bytes_per_token // pool.num_layers == \
        memory["kv_bytes_per_token_per_layer"] == 8 * 128 * 2 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["global_pool_bytes"]
    assert 2 * math.prod(wpool.shape) * 2 == memory["window_pool_bytes"] == \
        3 * 4096 * 32 * 4384
    # one pool for every layer at this context does not fit beside the
    # weights and the widest program's temps
    assert 4 * 4096 * 32 * 12288 + memory["weights_bytes"] + 1.5e9 \
        > 15.75 * 2 ** 30
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    # (the program's tree carries an unused post_norm a layer beside the
    # one LayerNorm a parallel block reads: 4 x 4096 entries)
    assert sum(math.prod(ps.shape) for ps in leaves) - 4 * 4096 == \
        memory["parameters"] == 4 * (142_606_336 + 201_326_592 + 524_288
                                     + 4096 + 16 * 50_331_648) \
        + 32_768 * 4096 + 4096 == 4_733_292_544
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the routers in
    # float32 (2 B more an entry) and the unused post_norm
    assert weights - 2 * 4 * 4096 * 128 - 2 * 4 * 4096 == \
        memory["weights_bytes"] == 2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["global_pool_bytes"]
             + memory["window_pool_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.79 * 16e9 < total < 0.81 * 16e9


def test_command_a_plus_rooflines_count_what_the_model_needs(monkeypatch):
    """The cell's two rooflines from a made-up window: the attention's need
    is the full layer's every running token + the three window layers'
    tokens inside the window + every call's queries and outputs; the
    experts' need is the held experts a step TOUCHED + every layer's router
    over 128 + four shared experts, over the ``moe`` AND ``shared`` scopes'
    time; a configuration of other key names, or a program without the
    counters, reads nothing."""
    from harness import host_spans, readers
    cfg = build.load_json("configs", "command-a-plus-05-2026.json")
    expert = 3 * 4096 * 4096 * 2
    programs = {"paged.w1": dict(count=100, total_s=2.0),
                "paged.w256": dict(count=40, total_s=0.7)}
    scopes = {"paged.w1": {"moe": 0.8, "shared": 0.25, "attn": 0.6},
              "paged.w256": {"moe": 0.3, "shared": 0.07, "attn": 0.1}}
    edge = {"counters": {"host_stats.kv_tokens_running": 182000.0,
                         "host_stats.kv_tokens_in_window": 119000.0,
                         "kv.live_rows": 32.0}}
    ops = {"paged_decode_attention": dict(seconds=0.30, count=100),
           "paged_decode_attention.1": dict(seconds=0.10, count=300),
           "fusion.7": dict(seconds=0.5, count=100)}

    def ctx(counters, config=cfg, slice_=None):
        return {"config": config, "peaks": {"hbm_gbps": 819.0},
                "warm_widths": [1, 64, 256], "before": {"counters": {}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}},
                "slice": slice_ or {"before": edge, "after": edge},
                "trace": {"programs": programs,
                          "ops_by_program": {"paged.w1": ops}},
                "_slice": {"scopes": {
                    label: dict(programs[label], scopes=scopes[label])
                    for label in programs}}}
    monkeypatch.setattr(host_spans, "load_slice", lambda c: c["_slice"])
    # -- the experts: 50 steps fetched, 13.8 of 16 held touched a layer
    metric = "kernel.moe_decode_avg_shared_roofline"
    counters = dict(moe_expert_slots=50 * 4 * 16,
                    moe_experts_touched=50 * 4 * 13.8)
    least = (4 * 13.8 * expert
             + 4 * (4096 * 128 * 2 + 4 * expert)) / 819e9
    got = readers.read_metric(metric, ctx(counters))
    assert got == pytest.approx(100 * least / 10.5e-3) and 80 < got < 90
    assert least == pytest.approx(8.76e-3, rel=5e-3)
    assert readers.read_metric(metric, ctx({})) is None
    for key in ("router_num_experts", "num_shared_experts"):
        assert readers.read_metric(
            metric, ctx(counters, dict(cfg, **{key: None}))) is None
    # a program that keeps its shared experts under moe reads the same work
    scopes["paged.w1"] = {"moe": 1.05, "attn": 0.6}
    assert readers.read_metric(metric, ctx(counters)) == pytest.approx(got)
    # every held expert touched is the ceiling of the need
    full = dict(counters, moe_experts_touched=50 * 4 * 16)
    assert readers.read_metric(metric, ctx(full)) < 100
    # -- the attention: 1 full layer x 182k + 3 window layers x 119k tokens
    metric = "kernel.paged_decode_layer_types_roofline"
    q_out = 32 * 2 * 128 * 128 * 2
    need = (182000 + 3 * 119000) * 4096 + 4 * q_out
    got = readers.read_metric(metric, ctx({}))
    assert got == pytest.approx(100 * (need / 819e9) / 4e-3)
    assert 65 < got < 70
    assert readers.read_metric(
        metric, ctx({}, {k: v for k, v in cfg.items()
                         if k != "layer_types"})) is None
    bare = {"counters": {"kv.live_rows": 32.0}}
    assert readers.read_metric(
        metric, ctx({}, slice_={"before": bare, "after": bare})) is None
    # SmallThinker's and the one-pool yardsticks read nothing here
    for other in ("kernel.paged_decode_window_roofline",
                  "kernel.moe_decode_held_roofline",
                  "kernel.moe_decode_share_roofline"):
        assert readers.read_metric(other, ctx(counters)) is None


@pytest.mark.parametrize("fault", ["shared_sum", "rope_halves",
                                   "rope_on_full"])
def test_a_fault_in_the_program_does_not_pass_the_cohere2_moe_toy_gate(
        monkeypatch, fault):
    """The other direction of the controls: the PROGRAM broken, the
    reference sound. The four shared experts summed, the rotary half-split,
    a full layer rotated."""
    import dataclasses

    from neuronx_distributed_inference_tpu.models.family import get_family
    from test_cohere2_moe_paged import _toy_file
    from neuronx_distributed_inference_tpu.modules import moe
    family = get_family("cohere2_moe")
    build_spec = family.build_spec.__func__
    shared = moe.shared_experts

    def broken(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        if fault == "rope_halves":
            return dataclasses.replace(spec, rope_interleaved=False)
        return dataclasses.replace(spec, nope_global=False)
    if fault == "shared_sum":
        monkeypatch.setattr(moe, "shared_experts", lambda spec, x, w: shared(
            dataclasses.replace(spec, shared_mean_of=0), x, w))
    else:
        monkeypatch.setattr(family, "build_spec", classmethod(broken))
    res = build.logit_gate(_toy_file(), seed=2**31 + 56,
                           served_precision="highest")
    assert not res["passed"] and res["worst_ratio"] > 5


# ---------------------------------------------------------------------------
# lfm2-8b-a1b (ISSUE 61)
# ---------------------------------------------------------------------------

LFM2_CELL = "lfm2-moe-rag-wide-closed"
LFM2_METRICS = ("kernel.moe_decode_all_held_roofline",
                "kernel.shortconv_decode_roofline")
LFM2_TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
              + ["full_attention", "conv", "conv"] * 2)
#: ISSUE 62's, appended last: a data file, no cell and no configuration
CARRY_METRICS = ("adapter.liveset_carry_share",)


def test_liveset_carry_share_reads_the_adapters_four_counters():
    """``adapter.liveset_carry_share`` (ISSUE 62): the live-set changes by
    rows that joined or left across which the step in flight was carried,
    over those and the ones it was drained for; listed where
    ``adapter.decode_overlap_share`` is; a program without the carry
    counters (the parent) reads 0, a window without such a change nothing."""
    from harness import readers
    metric = CARRY_METRICS[0]
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    overlap = next(m for m in BENCHMARK["per_layer"]
                   if m["name"] == "adapter.decode_overlap_share")
    assert entry == dict(
        name=metric, unit="%", better="higher", source="program_counter",
        layer="Adapter", moves="itl_p95_ms", workloads=overlap["workloads"])
    spec = build.load_json("layer_metrics", metric + ".json")
    assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} == \
        {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert not os.path.exists(os.path.join(
        build.BENCH_DIR, "layer_metrics", metric + ".py"))

    def ctx(**counters):
        return {"before": {"counters": {
                    "host_stats.pipeline_drains_release": 3}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}}}
    assert readers.read_metric(metric, ctx(
        pipeline_carries_admit=30, pipeline_carries_release=50,
        pipeline_drains_admit=2, pipeline_drains_release=21)) == \
        pytest.approx(100 * 80 / 100)
    # the parent: the drains alone
    assert readers.read_metric(metric, ctx(
        pipeline_drains_admit=52, pipeline_drains_release=418)) == 0.0
    assert readers.read_metric(metric, ctx(
        pipeline_drains_release=3, dispatches=7)) is None
    # the adapter holds the counters from its first dispatch on
    from neuronx_distributed_inference_tpu.serving import adapter
    assert adapter._CARRY_CAUSES == ("admit", "release")
    assert set(adapter._CARRY_CAUSES) < set(adapter._DRAIN_CAUSES)


def test_lfm2_moe_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 61: every key at the top level of the file, no width changed, and
    ``reduced`` names the depth and the layer types cut with it, and nothing
    else: every expert and the whole vocabulary are here."""
    cfg = build.load_json("configs", "lfm2-8b-a1b.json")
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=7168, layer_types=LFM2_TYPES,
        max_position_embeddings=128000, model_type="lfm2_moe",
        moe_intermediate_size=1792, norm_eps=1e-05, norm_topk_prob=True,
        num_attention_heads=32, num_dense_layers=2, num_experts=32,
        num_experts_per_tok=4, num_hidden_layers=24, num_key_value_heads=8,
        rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
        vocab_size=65536)
    assert len(LFM2_TYPES) == 24 and LFM2_TYPES.count("full_attention") == 6
    assert [i for i, t in enumerate(LFM2_TYPES) if t == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == ["layer_types",
                                                 "num_hidden_layers"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "lfm2-8b-a1b")
    assert sorted(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    # the first stage: both dense layers and three whole periods of four
    assert cfg["num_hidden_layers"] == 14
    assert cfg["layer_types"] == LFM2_TYPES[:14]
    assert cfg["layer_types"][2:] == ["full_attention", "conv", "conv",
                                      "conv"] * 3
    assert cfg["family"] == cfg["model_type"] == "lfm2_moe"
    assert cfg["chips"] == cfg["tp"] == 1 and cfg["dtype"] == "bfloat16"
    assert cfg["tie_word_embeddings"] is True
    assert "two pipeline stages of 14 + 10" in cfg["deployment"]
    assert {"tie_word_embeddings", "tensor_names", "topk_norm_eps",
            "dense_width", "router_dtype", "conv_tail_dtype", "kv_dtype",
            "head_here", "chunk_buckets", "adapter"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    # ONE chunk of at most the widest bucket before each decode step
    assert cfg["adapter"] == {"prefill_budget_tokens": max(
        serve["context_encoding_buckets"])} == {"prefill_budget_tokens": 512}
    assert (serve["batch_size"], serve["seq_len"], serve["pa_block_size"],
            serve["pa_num_blocks"], serve["is_prefix_caching"]) == \
        (64, 4096, 32, 8192, False)
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: both dense layers and ONE whole period, every width
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["num_experts"], twin["vocab_size"],
            twin["num_dense_layers"]) == (6, 2048, 32, 65536, 2)
    assert twin["layer_types"] == LFM2_TYPES[:6]
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * serve["pa_block_size"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert "before the first chip run" in gate["rule"]
    ref = build.load_reference("lfm2_moe")
    assert {"bias_weighs", "bias_dropped", "renorm_dropped", "b_c_exchanged",
            "qk_norm_after_rope", "dense_as_expert"} == set(ref.CONTROLS)
    for control in (*ref.CONTROLS, "zero_tail", "padded_tail", "fp8"):
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "rag-wide-closed.json")
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 4096
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 61's, every number of it: rag-closed's lengths
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 15.0, 8.0, 61)
    assert mix["prompt_len"] == dict(kind="lognormal", median=768, sigma=0.7,
                                     lo=192, hi=3072)
    assert mix["output_len"] == dict(kind="lognormal", median=320, sigma=0.6,
                                     lo=96, hi=1024)
    narrow = build.load_json("traffic", "rag-closed.json")
    assert (mix["prompt_len"], mix["output_len"]) == \
        (narrow["prompt_len"], narrow["output_len"])
    assert build.warm_widths(cfg, mix) == \
        [1] + sorted(serve["context_encoding_buckets"])
    # the cell: one chip, on its two rooflines and on no other family's
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == LFM2_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2-8b-a1b", "rag-wide-closed", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if LFM2_CELL in m.get("workloads", ())}
    assert {m["name"] for m in BENCHMARK["per_layer"]
            if m.get("workloads") == [LFM2_CELL]} == set(LFM2_METRICS)
    assert {m for m in listed if m.endswith("_roofline")} == \
        set(LFM2_METRICS) | {"kernel.paged_decode_roofline"}
    assert {"sched.live_batch_mean", "adapter.prefill_pad_share",
            "host.stall_s", "step.decode_attn_ms", "step.prefill_attn_ms",
            "step.decode_moe_ms", "step.prefill_moe_ms",
            "step.decode_mixer_ms", "step.prefill_mixer_ms",
            "step.decode_mlp_ms", "attn.paged_prefill_kernel_share",
            "adapter.decode_overlap_share", "moe.experts_touched_share",
            "moe.experts_skipped_share", "moe.prefill_walk_share",
            "host.prep_inputs_ms_per_dispatch",
            "host.prep_rng_ms_per_dispatch",
            "host.prep_enqueue_ms_per_dispatch",
            "host.dispatch_build_ms_per_dispatch",
            "host.dispatch_retire_ms_per_dispatch",
            "host.deliver_ms_per_dispatch", "device.idle_prep_share",
            "sched.gaps_behind_prefill_share", "sched.stalled_gap_mean_ms",
            "sched.prefill_dispatches_per_stalled_gap"} <= listed
    assert LFM2_CELL in next(
        m for m in BENCHMARK["end_to_end"]
        if m["name"] == "tokens_per_s")["workloads"]


def test_lfm2_moe_allocates_what_its_file_says():
    """The file's ``memory`` against what the program would allocate: the
    weights from the parameter specs, the slot from ``ssm_state_shapes``, the
    pool from what the application allocates, all as SHAPES (nothing of 11 GB
    is allocated)."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_spec
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "lfm2-8b-a1b.json")
    assumed, memory, serve = cfg["assumed"], cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert (spec.first_dense, spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers) == (2, 12, 3, 11)
    assert spec.resolved_ssm_pattern == tuple(
        t == "conv" for t in cfg["layer_types"])
    m = spec.moe
    assert (m.num_experts, m.num_held, m.top_k, m.intermediate_size,
            m.router_act, m.has_router_bias, m.router_bias_mode,
            m.normalize_topk, m.topk_norm_eps, m.routed_scaling,
            m.shared_intermediate) == (32, 32, 4, 1792, "sigmoid", True,
                                       "select", True, 1e-6, 1.0, 0)
    assert (spec.ssm.kind, spec.ssm.d_inner, spec.ssm.d_conv) == \
        ("shortconv", 2048, 3)
    assert spec.qk_norm and spec.tie_word_embeddings
    assert abs(spec.rope.rope_theta - 1e6) < 1e-3
    assert spec.padded_vocab == cfg["vocab_size"]
    # a row's state: eleven conv tails of two products a channel, no matrix
    state = ssm.ssm_state_shapes(spec.ssm, 11, serve["batch_size"],
                                 jnp.dtype(cfg["dtype"]))
    assert state == {"conv_x": ((11, 64, 2, 2048),
                                jnp.dtype(assumed["conv_tail_dtype"]))}
    state_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                      for shape, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        serve["batch_size"] * memory["state_slot_bytes"]
    assert memory["state_slot_bytes"] == 90112
    # 8 kv heads of 64 lanes fold two a slot of 128
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.shape == (3, 8193, 32, 4, 128)
    assert str(jnp.dtype(pool.dtype)) == assumed["kv_dtype"]
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        3 * 2 * 8 * 64 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    conv, attn, norms = 16_783_360, 10_485_888, 4_096
    dense, experts, vocab = 44_040_192, 352_387_104, 134_217_728
    assert sum(math.prod(ps.shape) for ps in leaves) == \
        memory["parameters"] == (
            2 * (conv + norms + dense) + 3 * (attn + norms + experts)
            + 9 * (conv + norms + experts) + vocab + 2048) == 4_667_077_376
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's count over the file's all-bf16 one: the routers and
    # their selection biases in float32 (2 B more an entry)
    assert weights == memory["program_weights_bytes"]
    assert weights - 2 * 12 * (2048 * 32 + 32) == memory["weights_bytes"] \
        == 2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["kv_pool_bytes"]
             + memory["state_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.68 * 16e9 < total < 0.69 * 16e9
    # the whole model does not fit a chip; a fourth period leaves no room
    whole = 2 * (conv + norms + dense) + 6 * (attn + norms + experts) \
        + 16 * (conv + norms + experts) + vocab + 2048
    assert 2 * whole > 16e9 and whole == 8_339_930_560
    assert 2 * (memory["parameters"] + attn + 3 * conv + 4 * norms
                + 4 * experts) > 12.2e9


def test_lfm2_moe_rooflines_count_what_the_model_needs(monkeypatch):
    """The cell's two rooflines from a made-up window: the experts' need is
    the experts a step TOUCHED + the twelve routers, over the ``moe`` scope's
    time (the two dense layers' MLPs lie under ``mlp`` and are not in it);
    the short convolutions' need is eleven layers' three matrices and taps +
    each live row's tail read and written, over ``mixer``; a configuration
    of other key names, or a program without the counters, reads nothing."""
    from harness import host_spans, readers
    cfg = build.load_json("configs", "lfm2-8b-a1b.json")
    expert = 3 * 2048 * 1792 * 2
    programs = {"paged.w1": dict(count=100, total_s=1.4)}
    scopes = {"paged.w1": {"moe": 1.1, "mixer": 0.06, "mlp": 0.03,
                           "attn": 0.09}}
    edge = {"counters": {"kv.live_rows": 64.0}}

    def ctx(counters, config=cfg, slice_=None):
        return {"config": config, "peaks": {"hbm_gbps": 819.0},
                "warm_widths": build.warm_widths(
                    cfg, build.load_json("traffic", "rag-wide-closed.json")),
                "before": {"counters": {}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}},
                "slice": slice_ or {"before": edge, "after": edge},
                "trace": {"programs": programs, "ops_by_program": {}},
                "_slice": {"scopes": {
                    label: dict(programs[label], scopes=scopes[label])
                    for label in programs}}}
    monkeypatch.setattr(host_spans, "load_slice", lambda c: c["_slice"])
    # -- the experts: 50 steps fetched, every expert of 12 layers touched
    metric = LFM2_METRICS[0]
    counters = dict(moe_expert_slots=50 * 12 * 32,
                    moe_experts_touched=50 * 12 * 32)
    least = (12 * 32 * expert + 12 * (2048 * 32 + 32) * 2) / 819e9
    got = readers.read_metric(metric, ctx(counters))
    assert got == pytest.approx(100 * least / 11e-3) and 90 < got < 100
    assert least == pytest.approx(10.32e-3, rel=2e-3)
    half = dict(counters, moe_experts_touched=50 * 12 * 16)
    assert readers.read_metric(metric, ctx(half)) == pytest.approx(
        got / 2, rel=1e-3)
    assert readers.read_metric(metric, ctx({})) is None
    for key in ("num_dense_layers", "moe_intermediate_size"):
        assert readers.read_metric(
            metric, ctx(counters, {k: v for k, v in cfg.items()
                                   if k != key})) is None
    # -- the convolutions: 11 layers x (4 x 2048^2 + 3 x 2048) + 64 tails
    metric = LFM2_METRICS[1]
    need = 11 * ((4 * 2048 * 2048 + 3 * 2048) * 2 + 64 * 2 * 2048 * 2 * 2)
    got = readers.read_metric(metric, ctx({}))
    assert got == pytest.approx(100 * (need / 819e9) / 0.6e-3)
    assert need == pytest.approx(0.381e9, rel=5e-3) and 70 < got < 80
    assert readers.read_metric(
        metric, ctx({}, {k: v for k, v in cfg.items()
                         if k != "conv_L_cache"})) is None
    assert readers.read_metric(
        metric, ctx({}, slice_={"before": None, "after": None})) is None
    # the other families' yardsticks read nothing here
    for other in ("kernel.moe_decode_experts_roofline",
                  "kernel.moe_decode_held_roofline",
                  "kernel.moe_decode_share_roofline",
                  "kernel.mixer_decode_roofline"):
        assert readers.read_metric(other, ctx(counters)) is None
    # and these two nothing under another configuration's keys
    other = build.load_json("configs", "qwen3-next-80b-a3b.json")
    for metric in LFM2_METRICS:
        assert readers.read_metric(metric, ctx(counters, other)) is None


@pytest.mark.parametrize("fault", [None, "bias_weighs", "renorm_dropped",
                                   "padded_tail"])
def test_the_lfm2_moe_toy_gate_and_three_faults_in_the_program(
        monkeypatch, fault):
    """The reference, found by name, gates a toy twin through the harness's
    full-batch prefill (a padded window) and its decode steps; and the other
    direction of the controls: the PROGRAM broken, the reference sound. A
    selection bias that weighs, an unnormalised top-k, a tail taken at the
    bucket's end."""
    import dataclasses

    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.modules import ssm
    from test_lfm2_moe_paged import HF, SERVE
    family = get_family("lfm2_moe")
    build_spec = family.build_spec.__func__

    def broken(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(spec, moe=dataclasses.replace(
            spec.moe, **({"router_bias_mode": "logits"}
                         if fault == "bias_weighs"
                         else {"normalize_topk": False})))
    if fault == "padded_tail":
        import importlib.util
        at = importlib.util.spec_from_file_location(
            "gate61", os.path.join(ROOT, "scripts", "gate61.py"))
        gate61 = importlib.util.module_from_spec(at)
        at.loader.exec_module(gate61)
        monkeypatch.setattr(ssm, *gate61.carry_fault(fault))
    elif fault:
        monkeypatch.setattr(family, "build_spec", classmethod(broken))
    toy = dict(HF, family="lfm2_moe", tp=1, dtype="float32", serve=SERVE,
               adapter={},
               gate=dict(config={}, batch=2, prompt_len=24, new_tokens=8,
                         atol=2e-5, rtol=1e-4, min_positions_held=1.0,
                         median_ratio_max=0.5, worst_ratio_max=1.0,
                         excuse_margin_max=0.0))
    res = build.logit_gate(toy, seed=2**31 + 61, served_precision="highest")
    if fault is None:
        assert res["passed"], res
        assert res["compared"] == 2 * 32 * HF["vocab_size"]
    else:
        assert not res["passed"] and res["worst_ratio"] > 5


# ---------------------------------------------------------------------------
# nemotron-3-nano-30b-a3b (ISSUE 64)
# ---------------------------------------------------------------------------

NEMOTRON_CELL = "nemotron3-nano-agent-closed"
NEMOTRON_METRICS = ("kernel.moe_decode_plain_roofline",
                    "kernel.mixer_decode_groups_roofline")
NEMOTRON_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
LING_CELL = "ling3-flash-reason-closed"
LING_METRICS = ("kernel.kda_decode_roofline",
                "kernel.moe_decode_group_roofline")


def test_the_benchmark_holds_thirteen_configurations_and_fourteen_cells():
    assert len(BENCHMARK["configs"]) == 13 and len(CELLS) == 14
    assert [c["name"] for c in BENCHMARK["configs"][-2:]] == [
        "nemotron-3-nano-30b-a3b", "ling-3.0-flash"]
    assert CELLS[-2:] == [NEMOTRON_CELL, LING_CELL]
    assert [m["name"] for m in BENCHMARK["per_layer"][-4:]] == \
        list(NEMOTRON_METRICS) + list(LING_METRICS)
    assert all(w["chips"] == 1 for w in BENCHMARK["workloads"])


def test_nemotron3_nano_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), as copied into
    ISSUE 64: every key at the top level of the file, no width changed, ALL
    52 layers and the pattern as published; ``reduced`` names the experts
    held and the vocabulary, and nothing else."""
    cfg = build.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    published = dict(
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=2688,
        hybrid_override_pattern=NEMOTRON_PATTERN, intermediate_size=1856,
        layer_norm_epsilon=1e-05, mamba_head_dim=64, mamba_hidden_act="silu",
        mamba_num_heads=64, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False,
        mlp_hidden_act="relu2", model_type="nemotron_h",
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_group=1, n_groups=8, n_routed_experts=128, n_shared_experts=1,
        norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
        num_experts_per_tok=6, num_hidden_layers=52, num_key_value_heads=2,
        num_logits_to_keep=1, partial_rotary_factor=1,
        rescale_prenorm_residual=True, residual_in_fp32=False,
        rope_theta=10000, routed_scaling_factor=2.5, sliding_window=None,
        ssm_state_size=128, tie_word_embeddings=False, time_step_floor=0.0001,
        time_step_max=0.1, time_step_min=0.001, topk_group=1, use_bias=False,
        use_conv_bias=True, use_mamba_kernels=True, vocab_size=131072)
    assert len(NEMOTRON_PATTERN) == 52
    assert [NEMOTRON_PATTERN.count(c) for c in "ME*-"] == [23, 23, 6, 0]
    assert set(published) <= set(cfg)
    differs = sorted(k for k in published if cfg[k] != published[k])
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts",
                                                 "vocab_size"]
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert sorted(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    # one chip's share of a v5e-8: 16 of 128 experts, an eighth of the words
    assert (cfg["n_routed_experts"], cfg["router_n_routed_experts"],
            cfg["first_expert"], cfg["vocab_size"]) == (16, 128, 0, 16384)
    assert cfg["family"] == cfg["model_type"] == "nemotron_h"
    assert cfg["chips"] == cfg["tp"] == 1 and cfg["dtype"] == "bfloat16"
    for said in ("v5e-8", "16 of 128", "WITHOUT its exchange",
                 "12 tokens an expert", "1.5", "12.45 of 16"):
        assert said in cfg["deployment"], said
    assert "5,258,420,544" in cfg["reduced_why"]
    assert {"no_rotary", "topk_norm_eps", "tensor_names", "in_proj_rows",
            "d_inner", "gated_norm", "time_step_limit", "router_dtype",
            "state_dtype", "stored_width"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    assert cfg["adapter"] == {}
    assert (serve["batch_size"], serve["seq_len"], serve["pa_block_size"],
            serve["pa_num_blocks"], serve["context_encoding_buckets"],
            serve["is_prefix_caching"]) == (32, 4096, 32, 4096, [64, 256],
                                            False)
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: a prefix of the published pattern with every kind of layer,
    # at least two E and two M, the attention layer not the last
    pattern = twin["hybrid_override_pattern"]
    assert pattern == NEMOTRON_PATTERN[:7] == "MEMEM*E"
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["n_routed_experts"], twin["vocab_size"]) == \
        (7, 2688, 16, 16384)
    assert pattern.count("M") >= 2 and pattern.count("E") >= 2
    assert "*" in pattern[:-1] and not pattern.endswith("*")
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (4, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * serve["pa_block_size"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert "before the first chip run" in gate["rule"]
    assert "SQUARES a bf16 rounding" in gate["tolerance_why"]
    ref = build.load_reference("nemotron_h")
    for control in (*ref.CONTROLS, "fp8"):
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "agent-reason-closed.json")
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 4096
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 64's, every number of it
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 20.0, 8.0, 64)
    assert mix["prompt_len"] == dict(kind="lognormal", median=512, sigma=0.8,
                                     lo=64, hi=1536)
    assert mix["output_len"] == dict(kind="lognormal", median=1024, sigma=0.6,
                                     lo=256, hi=2560)
    assert build.warm_widths(cfg, mix) == [1, 64, 256]
    # the cell: one chip, on its two rooflines and on no other family's
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == NEMOTRON_CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        (NEMOTRON_CELL, "nemotron-3-nano-30b-a3b", "agent-reason-closed", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if NEMOTRON_CELL in m.get("workloads", ())}
    assert {m["name"] for m in BENCHMARK["per_layer"]
            if m.get("workloads") == [NEMOTRON_CELL]} == set(NEMOTRON_METRICS)
    assert {m for m in listed if m.endswith("_roofline")} == \
        set(NEMOTRON_METRICS) | {"kernel.paged_decode_roofline"}
    assert "kernel.moe_decode_held_roofline" not in listed, (
        "DeepSeek's key names, which this file shares, x THREE projections: "
        "it would read ~150 % of a two-matrix walk")
    assert "kernel.mixer_decode_roofline" not in listed, (
        "granite's key names (mamba_n_heads, layer_types): it reads nothing "
        "here")
    assert "step.decode_mlp_ms" not in listed, (
        "a single-block stack with no dense MLP layer has no mlp scope")
    assert {"sched.live_batch_mean", "adapter.prefill_pad_share",
            "adapter.decode_overlap_share", "adapter.liveset_carry_share",
            "host.stall_s", "step.decode_attn_ms", "step.prefill_attn_ms",
            "step.decode_moe_ms", "step.prefill_moe_ms",
            "step.decode_mixer_ms", "step.prefill_mixer_ms",
            "attn.paged_prefill_kernel_share", "moe.experts_touched_share",
            "moe.experts_skipped_share", "moe.prefill_walk_share",
            "mixer.state_kernel_share", "host.prep_inputs_ms_per_dispatch",
            "host.prep_rng_ms_per_dispatch",
            "host.prep_enqueue_ms_per_dispatch",
            "host.dispatch_build_ms_per_dispatch",
            "host.dispatch_retire_ms_per_dispatch",
            "host.deliver_ms_per_dispatch", "device.idle_prep_share",
            "sched.gaps_behind_prefill_share", "sched.stalled_gap_mean_ms",
            "sched.prefill_dispatches_per_stalled_gap"} <= listed
    assert NEMOTRON_CELL in next(
        m for m in BENCHMARK["end_to_end"]
        if m["name"] == "tokens_per_s")["workloads"]


def test_nemotron3_nano_allocates_what_its_file_says():
    """The file's ``memory`` against what the program would allocate: the
    weights from the parameter specs (the model's count: the stored pad and
    the float32 leaves apart), the slot from ``ssm_state_shapes``, the pool
    from what the application allocates, all as SHAPES."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_spec
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert (spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers) == (23, 6, 23)
    assert "".join({"mamba": "M", "moe": "E", "attention": "*"}[b]
                   for b in spec.layer_blocks) == NEMOTRON_PATTERN
    m = spec.moe
    assert (m.num_experts, m.num_held, m.first_expert, m.top_k,
            m.intermediate_size, m.stored_intermediate, m.glu_style, m.act,
            m.router_act, m.has_router_bias, m.router_bias_mode,
            m.normalize_topk, m.topk_norm_eps, m.routed_scaling,
            m.shared_intermediate, m.n_group) == (
        128, 16, 0, 6, 1856, 1920, "plain", "relu2", "sigmoid", True,
        "select", True, 1e-20, 2.5, 3712, 1)
    s = spec.ssm
    assert (s.kind, s.d_inner, s.num_heads, s.head_dim, s.d_state,
            s.n_groups, s.d_conv, s.gated_norm, s.norm_before_gate,
            s.dt_limit) == ("mamba2", 4096, 64, 64, 128, 8, 4, True, False,
                            (0.0, float("inf")))
    assert spec.no_rope and not spec.tie_word_embeddings
    assert spec.padded_vocab == cfg["vocab_size"] and spec.rms_eps == 1e-5
    state = ssm.ssm_state_shapes(s, 23, serve["batch_size"],
                                 jnp.dtype(cfg["dtype"]))
    assert state == {"conv_x": ((23, 32, 4096, 3), jnp.dtype("bfloat16")),
                     "conv_bc": ((23, 32, 2048, 3), jnp.dtype("bfloat16")),
                     "ssm": ((23, 32, 64, 64, 128), jnp.float32)}
    state_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                      for shape, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        32 * memory["state_slot_bytes"]
    assert memory["state_slot_bytes"] == 23 * (64 * 64 * 128 * 4
                                               + 6144 * 3 * 2) == 49_082_368
    # both kv heads of 128 lanes in ONE slot of 256
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.shape == (6, 4097, 32, 1, 256)
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        6 * 2 * 2 * 128 * 2
    assert 2 * math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    mamba, attn, experts = 38_744_896, 23_399_040, 179_948_288
    pad = 23 * 16 * 2 * 2688 * 64
    assert sum(math.prod(ps.shape) for ps in leaves) == \
        memory["stored_parameters"] == memory["parameters"] + pad
    assert memory["parameters"] == (
        23 * mamba + 6 * attn + 23 * experts + 2 * 16384 * 2688 + 2688) \
        == 5_258_420_544
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's bytes over the model's all-bf16 count: the pad, and the
    # routers, selection biases, dt_bias, A_log and D in float32
    f32 = 23 * (2688 * 128 + 128) + 23 * 3 * 64
    assert weights == memory["program_weights_bytes"] == \
        memory["weights_bytes"] + 2 * pad + 2 * f32
    assert memory["weights_bytes"] == 2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["kv_pool_bytes"]
             + memory["state_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.80 * 16e9 < total < 0.81 * 16e9
    assert memory["program_before_temps_bytes"] == total + 2 * pad + 2 * f32
    # the whole model is four chips' worth of HBM
    whole = 23 * mamba + 6 * attn + 23 * (experts + 112 * 9_977_856) \
        + 2 * 131072 * 2688 + 2688
    assert whole == 31_577_940_288 and 2 * whole > 3.9 * 16e9


def test_nemotron3_nano_rooflines_count_what_the_model_needs(monkeypatch):
    """The cell's two rooflines from a made-up window: the experts' need is
    the TWO matrices, at the published 1856, of the held experts a step
    TOUCHED + 23 routers over 128 and shared experts, over the ``moe``
    scope's time; the mixers' need is 23 layers' weights + each live row's
    state and tails read and written, over ``mixer``; a configuration of
    other key names, or a program without the counters, reads nothing - and
    DeepSeek's three-matrix yardstick WOULD read this file's keys."""
    from harness import host_spans, readers
    cfg = build.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    programs = {"paged.w1": dict(count=100, total_s=1.8)}
    scopes = {"paged.w1": {"moe": 0.9, "mixer": 0.72, "attn": 0.1}}
    edge = {"counters": {"kv.live_rows": 32.0}}

    def ctx(counters, config=cfg, slice_=None):
        return {"config": config, "peaks": {"hbm_gbps": 819.0},
                "warm_widths": [1, 64, 256],
                "before": {"counters": {}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}},
                "slice": slice_ or {"before": edge, "after": edge},
                "trace": {"programs": programs, "ops_by_program": {}},
                "_slice": {"scopes": {
                    label: dict(programs[label], scopes=scopes[label])
                    for label in programs}}}
    monkeypatch.setattr(host_spans, "load_slice", lambda c: c["_slice"])
    # -- the experts: 50 steps fetched, 12.45 of 16 touched a layer
    metric = NEMOTRON_METRICS[0]
    counters = dict(moe_expert_slots=50 * 23 * 16,
                    moe_experts_touched=round(50 * 23 * 12.45))
    expert = 2 * 2688 * 1856 * 2
    fixed = 23 * (2688 * 128 + 128 + 2 * 2688 * 3712) * 2
    need = 23 * 12.45 * expert + fixed
    got = readers.read_metric(metric, ctx(counters))
    assert got == pytest.approx(100 * (need / 819e9) / 9e-3, rel=1e-3)
    assert need == pytest.approx(6.65e9, rel=5e-3) and 85 < got < 95
    every = dict(counters, moe_experts_touched=50 * 23 * 16)
    assert readers.read_metric(metric, ctx(every)) == pytest.approx(
        100 * ((23 * 16 * expert + fixed) / 819e9) / 9e-3)
    assert readers.read_metric(metric, ctx({})) is None
    for key in ("hybrid_override_pattern", "moe_intermediate_size"):
        assert readers.read_metric(
            metric, ctx(counters, {k: v for k, v in cfg.items()
                                   if k != key})) is None
    # -- the mixers: 23 x (77.5 MB of weights + 32 rows x 2 x 2.13 MB)
    metric = NEMOTRON_METRICS[1]
    weights = (2688 * 10304 + 6144 * 5 + 3 * 64 + 4096 + 2688
               + 4096 * 2688) * 2
    row = 64 * 64 * 128 * 4 + 6144 * 3 * 2
    need = 23 * (weights + 32 * 2 * row)
    got = readers.read_metric(metric, ctx({}))
    assert got == pytest.approx(100 * (need / 819e9) / 7.2e-3)
    assert weights == 2 * 38_744_896
    assert need == pytest.approx(4.92e9, rel=2e-3) and 80 < got < 90
    assert readers.read_metric(
        metric, ctx({}, {k: v for k, v in cfg.items()
                         if k != "mamba_num_heads"})) is None
    assert readers.read_metric(
        metric, ctx({}, slice_={"before": None, "after": None})) is None
    # granite's yardstick reads nothing under these names
    assert readers.read_metric("kernel.mixer_decode_roofline",
                               ctx(counters)) is None
    # ... and these two nothing under another configuration's keys
    for name in ("granite-4.0-h-micro", "deepseek-v3", "lfm2-8b-a1b"):
        other = build.load_json("configs", name + ".json")
        for metric in NEMOTRON_METRICS:
            assert readers.read_metric(metric, ctx(counters, other)) is None


@pytest.mark.parametrize("fault", [None, "bias_weighs", "renorm_dropped",
                                   "scaling_dropped"])
def test_the_nemotron_h_toy_gate_and_three_faults_in_the_program(
        monkeypatch, fault):
    """The reference, found by name, gates a toy twin through the harness's
    full-batch prefill (a padded window) and its decode steps; and the other
    direction of the controls: the PROGRAM broken, the reference sound."""
    import dataclasses

    from neuronx_distributed_inference_tpu.models.family import get_family
    from test_nemotron_h_paged import HF, SERVE
    family = get_family("nemotron_h")
    build_spec = family.build_spec.__func__
    broken_moe = {"bias_weighs": {"router_bias_mode": "logits"},
                  "renorm_dropped": {"normalize_topk": False},
                  "scaling_dropped": {"routed_scaling": None}}

    def broken(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(spec, moe=dataclasses.replace(
            spec.moe, **broken_moe[fault]))
    if fault:
        monkeypatch.setattr(family, "build_spec", classmethod(broken))
    toy = dict(HF, family="nemotron_h", tp=1, dtype="float32", serve=SERVE,
               adapter={},
               gate=dict(config={}, batch=2, prompt_len=24, new_tokens=8,
                         atol=2e-5, rtol=1e-4, min_positions_held=1.0,
                         median_ratio_max=0.5, worst_ratio_max=1.0,
                         excuse_margin_max=0.0))
    res = build.logit_gate(toy, seed=2**31 + 64, served_precision="highest")
    if fault is None:
        assert res["passed"], res
        assert res["compared"] == 2 * 32 * HF["vocab_size"]
    else:
        assert not res["passed"] and res["worst_ratio"] > 5


# ---------------------------------------------------------------------------
# ling-3.0-flash (ISSUE 67)
# ---------------------------------------------------------------------------

#: the catalog row's ``config`` (model-configs guide, ``Ling-3.0-flash-VL``),
#: as copied into ISSUE 67
LING_PUBLISHED = dict(
    image_patch_token=157157, video_patch_token=156909,
    image_start_token=157158, video_start_token=157160,
    num_hidden_layers=42, hidden_size=2560, intermediate_size=6144,
    first_k_dense_replace=2, max_position_embeddings=131072,
    moe_intermediate_size=768, num_experts_per_tok=8, num_attention_heads=32,
    q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, num_experts=512,
    num_key_value_heads=32, rope_theta=6000000, rms_norm_eps=1e-06,
    head_dim=128, vocab_size=157184, partial_rotary_factor=0.5,
    moe_router_enable_expert_bias=True, routed_scaling_factor=2.5, n_group=8,
    topk_group=4, use_qk_norm=True, score_function="sigmoid",
    moe_shared_expert_intermediate_size=768, layer_group_size=6,
    num_kv_heads_for_linear_attn=0, group_norm_size=1, linear_silu=True,
    rotary_dim=64, use_mla_nope=False, short_conv_kernel_size=4,
    use_nGPT=False, scale_router_input=False, value_norm=False,
    up_proj_norm=False, gated_attention_proj_granularity_type="head_wise",
    mtp_use_kda=False, no_kda_lora=True, use_kda_lora=False,
    kda_safe_gate=True, kda_lower_bound=-5, norm_topk_prob=True,
    expert_swiglu_limit_list=[0] * 35 + [4] * 7,
    share_expert_swiglu_limit_list=[0] * 34 + [5] * 6 + [7] * 2)


def test_ling3_flash_keeps_every_published_number():
    """Every key of the catalog row at the top level of the file, no width
    changed, 18 layers = three whole periods in the published order;
    ``reduced`` names the depth, the experts held, the vocabulary and the two
    limit lists (cut in step with the depth, all zero), and nothing else."""
    cfg = build.load_json("configs", "ling-3.0-flash.json")
    assert set(LING_PUBLISHED) <= set(cfg)
    differs = sorted(k for k in LING_PUBLISHED if cfg[k] != LING_PUBLISHED[k])
    assert differs == sorted(cfg["reduced"]) == [
        "expert_swiglu_limit_list", "num_experts", "num_hidden_layers",
        "share_expert_swiglu_limit_list", "vocab_size"]
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == "ling-3.0-flash"
    assert sorted(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
        "config.json") and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/ling-3.0-flash.json"
    # one chip's share of a 16-chip stage: 32 of 512, an eighth of the words
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_num_experts"], cfg["first_expert"],
            cfg["vocab_size"]) == (18, 32, 512, 0, 19648)
    assert cfg["vocab_size"] * 8 == LING_PUBLISHED["vocab_size"]
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert cfg[key] == LING_PUBLISHED[key][:18] == [0] * 18, key
    assert cfg["family"] == cfg["model_type"] == "ling_kda"
    assert cfg["chips"] == cfg["tp"] == 1 and cfg["dtype"] == "bfloat16"
    for said in ("16 v5e chips", "32 of 512", "WITHOUT its exchange",
                 "16 against 1 token an expert", "20 of 32 touched"):
        assert said in cfg["deployment"], said
    assert "4,215,902,560" in cfg["reduced_why"]
    assert "124.05 B" in cfg["reduced_why"]
    assert {"A1_safe_gate", "A2_use_qk_norm", "A3_group_norm_size",
            "A4_head_gate_input", "A5_num_kv_heads_for_linear_attn",
            "A6_rotary", "A7_layer_group_size", "model_type", "tensor_names",
            "kv_dtype", "state_dtype", "router_dtype"} <= set(cfg["assumed"])
    serve = cfg["serve"]
    assert cfg["adapter"] == {}
    assert (serve["batch_size"], serve["seq_len"], serve["pa_block_size"],
            serve["pa_num_blocks"], serve["context_encoding_buckets"],
            serve["is_prefix_caching"]) == (64, 8192, 32, 16384, [64, 256],
                                            False)
    gate = cfg["gate"]
    twin = build.hf_config(cfg, build.gate_overrides(gate))
    # the twin: layers 0-6, both dense layers, a linear layer behind an
    # expert block, the latent layer and a linear layer AFTER it
    ref = build.load_reference("ling_kda")
    assert ref.linear_layers(twin) == ([0, 1, 2, 3, 4, 6], [5])
    assert (twin["num_hidden_layers"], twin["hidden_size"],
            twin["num_experts"], twin["vocab_size"],
            twin["first_k_dense_replace"]) == (7, 2560, 32, 19648, 2)
    assert twin["expert_swiglu_limit_list"] == [0] * 7
    assert (gate["batch"], gate["prompt_len"], gate["new_tokens"]) == \
        (8, 112, 16)
    assert gate["prompt_len"] + gate["new_tokens"] <= \
        4 * serve["pa_block_size"]
    assert 0 < gate["excuse_margin_max"] <= 0.02
    assert "before the first chip run" in gate["rule"]
    for control in (*ref.CONTROLS, "fp8"):
        assert control in gate["controls"], control
    # the pool cannot run dry: every row at its longest prompt and answer
    mix = build.load_json("traffic", "reason-wide-closed.json")
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == serve["seq_len"] == 8192
    assert serve["pa_num_blocks"] * serve["pa_block_size"] == \
        serve["batch_size"] * longest
    # the mix is ISSUE 67's, every number of it
    assert (mix["loop"], mix["clients_per_batch_row"], mix["pool_requests"],
            mix["lead_s"], mix["grace_s"], mix["base_seed"]) == \
        ("closed", 2, 4096, 30.0, 8.0, 67)
    assert mix["prompt_len"] == dict(kind="lognormal", median=1024,
                                     sigma=0.8, lo=128, hi=4096)
    assert mix["output_len"] == dict(kind="lognormal", median=2048,
                                     sigma=0.6, lo=512, hi=4096)
    assert build.warm_widths(cfg, mix) == [1, 64, 256]
    # the cell: one chip, on its own rooflines and the latent kernel's
    cell = BENCHMARK["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        (LING_CELL, "ling-3.0-flash", "reason-wide-closed", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if LING_CELL in m.get("workloads", ())}
    assert {m["name"] for m in BENCHMARK["per_layer"]
            if m.get("workloads") == [LING_CELL]} == set(LING_METRICS)
    assert {m for m in listed if m.endswith("_roofline")} == \
        set(LING_METRICS) | {"kernel.mla_decode_roofline"}
    assert "kernel.gdn_decode_roofline" not in listed, (
        "qwen3-next's / olmo-hybrid's key names (linear_num_value_heads, "
        "layer_types): it reads nothing under this file's keys")
    assert "kernel.moe_decode_held_roofline" not in listed, (
        "DeepSeek's key names (router_n_routed_experts, n_routed_experts): "
        "it reads nothing here; kernel.moe_decode_group_roofline is its rule "
        "under Ling's")
    assert {"sched.live_batch_mean", "adapter.prefill_pad_share",
            "adapter.decode_overlap_share", "adapter.liveset_carry_share",
            "host.stall_s", "step.decode_attn_ms", "step.prefill_attn_ms",
            "step.decode_moe_ms", "step.prefill_moe_ms",
            "step.decode_mixer_ms", "step.prefill_mixer_ms",
            "step.decode_mlp_ms", "moe.experts_touched_share",
            "moe.experts_skipped_share", "moe.prefill_walk_share",
            "moe.group_hit_share", "mixer.state_kernel_share",
            "host.prep_inputs_ms_per_dispatch",
            "host.prep_rng_ms_per_dispatch",
            "host.prep_enqueue_ms_per_dispatch",
            "host.dispatch_build_ms_per_dispatch",
            "host.dispatch_retire_ms_per_dispatch",
            "host.deliver_ms_per_dispatch", "device.idle_prep_share",
            "sched.gaps_behind_prefill_share", "sched.stalled_gap_mean_ms",
            "sched.prefill_dispatches_per_stalled_gap"} <= listed
    assert LING_CELL in next(
        m for m in BENCHMARK["end_to_end"]
        if m["name"] == "tokens_per_s")["workloads"]


def test_ling3_flash_allocates_what_its_file_says():
    """The file's ``memory`` against what the program would allocate: the
    weights from the parameter specs, the slot from ``ssm_state_shapes``, the
    latent pool from what the application allocates, all as SHAPES: three
    attention layers' latent rows AND fifteen layers' state slots in one
    cache."""
    import math

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.modules import ssm
    from neuronx_distributed_inference_tpu.modules.block_kv_cache import \
        pool_spec
    from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
    cfg = build.load_json("configs", "ling-3.0-flash.json")
    memory, serve = cfg["memory"], cfg["serve"]
    spec = build.build_app(cfg).spec
    assert (spec.num_moe_layers, spec.num_attn_layers, spec.num_ssm_layers,
            spec.first_dense) == (16, 3, 15, 2)
    assert spec.resolved_ssm_pattern == ((True,) * 5 + (False,)) * 3
    m = spec.moe
    assert (m.num_experts, m.num_held, m.first_expert, m.top_k,
            m.intermediate_size, m.router_act, m.has_router_bias,
            m.router_bias_mode, m.normalize_topk, m.routed_scaling,
            m.shared_intermediate, m.n_group, m.topk_group) == (
        512, 32, 0, 8, 768, "sigmoid", True, "select", True, 2.5, 768, 8, 4)
    s = spec.ssm
    assert (s.kind, s.d_inner, s.num_heads, s.head_dim, s.d_state, s.d_conv,
            s.chunk_size, s.decay_lower_bound, s.conv_bias) == (
        "kda", 4096, 32, 128, 128, 4, 16, -5.0, False)
    a = spec.mla
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
            a.v_head_dim, a.q_lora_rank, a.head_gate) == (
        512, 128, 64, 128, None, True)
    assert spec.rope.head_dim == 64 and spec.rope.rope_theta == 6e6
    assert not spec.rope_interleaved and not spec.tie_word_embeddings
    assert spec.scale == 192 ** -0.5
    state = ssm.ssm_state_shapes(s, 15, serve["batch_size"],
                                 jnp.dtype(cfg["dtype"]))
    assert state == {"conv_x": ((15, 64, 3, 12288), jnp.dtype("bfloat16")),
                     "ssm": ((15, 64, 32, 128, 128), jnp.float32)}
    state_bytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                      for shape, dt in state.values())
    assert state_bytes == memory["state_bytes"] == \
        64 * memory["state_slot_bytes"]
    assert memory["state_slot_bytes"] == 15 * (32 * 128 * 128 * 4
                                               + 3 * 4096 * 3 * 2) \
        == 15 * 2_170_880
    # latent rows from the THREE attention layers alone, no V lanes
    pool = pool_spec(spec, serve["pa_num_blocks"], serve["pa_block_size"])
    assert pool.is_latent and pool.shape == (3, 16385, 32, 1, 640)
    assert pool.v_shape[-1] == 0
    assert pool.bytes_per_token == memory["kv_bytes_per_token"] == \
        3 * 640 * 2
    assert math.prod(pool.shape) * 2 == memory["kv_pool_bytes"]
    leaves = jax.tree.leaves(model_base.decoder_param_specs(spec),
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    pad = 2 * (spec.padded_vocab - cfg["vocab_size"]) * 2560
    assert sum(math.prod(ps.shape) for ps in leaves) == \
        memory["stored_parameters"] == memory["parameters"] + pad
    assert memory["parameters"] == 4_215_902_560
    weights = sum(math.prod(ps.shape) * jnp.dtype(ps.dtype).itemsize
                  for ps in leaves)
    # the program's bytes over the model's all-bf16 count: the vocabulary's
    # pad, and the routers, selection biases, A_log and dt_bias in float32
    f32 = 16 * (2560 * 512 + 512) + 15 * (32 + 4096)
    assert weights == memory["program_weights_bytes"] == \
        memory["weights_bytes"] + 2 * pad + 2 * f32
    assert memory["weights_bytes"] == 2 * memory["parameters"]
    total = (memory["weights_bytes"] + memory["kv_pool_bytes"]
             + memory["state_bytes"])
    assert total == memory["before_temps_bytes"]
    assert 0.78 * 16e9 < total < 0.79 * 16e9
    # the whole language model is sixteen chips' HBM before any cache
    kda, mla, expert = 52_646_048, 31_965_696, 5_898_240
    whole = (35 * kda + 7 * mla + 42 * 5120 + 2 * 47_185_920
             + 40 * (513 * expert + 1_311_232) + 2 * 157_184 * 2560 + 2560)
    assert whole == 124_050_077_152 and 2 * whole > 15.5 * 16e9


def test_ling3_flash_rooflines_count_what_the_model_needs(monkeypatch):
    """The cell's two rooflines from a made-up window: the linear layers'
    need is fifteen blocks' weights + each live row's state and three tails
    read and written (the hand count of ISSUE 67 at 64 rows), over ``mixer``;
    the experts' need is the three projections of the held experts a step
    TOUCHED + sixteen routers over 512 and shared experts, over ``moe``; a
    configuration of other key names, or a program without the counters,
    reads nothing - and the scalar-decay and DeepSeek yardsticks read nothing
    under THIS file's keys."""
    from harness import host_spans, readers
    cfg = build.load_json("configs", "ling-3.0-flash.json")
    programs = {"paged.w1": dict(count=100, total_s=1.8)}
    scopes = {"paged.w1": {"moe": 0.55, "mixer": 0.85, "attn": 0.1}}
    edge = {"counters": {"kv.live_rows": 64.0}}

    def ctx(counters, config=cfg, slice_=None):
        return {"config": config, "peaks": {"hbm_gbps": 819.0},
                "warm_widths": [1, 64, 256],
                "before": {"counters": {}},
                "after": {"counters": {"host_stats." + k: v
                                       for k, v in counters.items()}},
                "slice": slice_ or {"before": edge, "after": edge},
                "trace": {"programs": programs, "ops_by_program": {}},
                "_slice": {"scopes": {
                    label: dict(programs[label], scopes=scopes[label])
                    for label in programs}}}
    monkeypatch.setattr(host_spans, "load_slice", lambda c: c["_slice"])
    # -- the linear layers: 15 x (105.3 MB of weights + 64 rows x 2 x 2.17 MB)
    metric = LING_METRICS[0]
    need = 15 * (105_292_096 + 64 * 2 * 2_170_880)
    assert need == 5_747_471_040
    got = readers.read_metric(metric, ctx({}))
    assert got == pytest.approx(100 * (need / 819e9) / 8.5e-3)
    assert 80 < got < 85
    for key in ("kda_lower_bound", "layer_group_size"):
        assert readers.read_metric(
            metric, ctx({}, {k: v for k, v in cfg.items()
                             if k != key})) is None
    assert readers.read_metric(
        metric, ctx({}, slice_={"before": None, "after": None})) is None
    # -- the experts: 50 steps fetched, 20.4 of 32 touched a layer
    metric = LING_METRICS[1]
    counters = dict(moe_expert_slots=50 * 16 * 32,
                    moe_experts_touched=round(50 * 16 * 20.4))
    expert = 3 * 2560 * 768 * 2
    fixed = 16 * (2560 * 512 + 3 * 2560 * 768) * 2
    need = 16 * 20.4 * expert + fixed
    got = readers.read_metric(metric, ctx(counters))
    assert got == pytest.approx(100 * (need / 819e9) / 5.5e-3, rel=1e-3)
    assert need == pytest.approx(4.08e9, rel=5e-3) and 85 < got < 95
    assert readers.read_metric(metric, ctx({})) is None
    assert readers.read_metric(
        metric, ctx(counters, {k: v for k, v in cfg.items()
                               if k != "router_num_experts"})) is None
    # the scalar-decay, Mamba and DeepSeek yardsticks read nothing here ...
    for other in ("kernel.gdn_decode_roofline",
                  "kernel.mixer_decode_roofline",
                  "kernel.moe_decode_held_roofline"):
        assert readers.read_metric(other, ctx(counters)) is None, other
    # ... and these two nothing under another configuration's keys
    for name in ("olmo-hybrid-7b", "qwen3-next-80b-a3b", "deepseek-v3",
                 "nemotron-3-nano-30b-a3b"):
        other = build.load_json("configs", name + ".json")
        for metric in LING_METRICS:
            assert readers.read_metric(metric, ctx(counters, other)) is None
    # the latent kernel's yardstick reads this file's keys at 32 heads
    sys_path_metric = build.load_module(build.find_file(
        "layer_metrics", "kernel.mla_decode_roofline.py"))
    need_bytes, need_flops = sys_path_metric.mla_decode_need(cfg, 160_000, 64)
    assert need_bytes == (160_000 * 576 + 64 * 32 * (576 + 512)) * 2
    assert need_bytes / 819e9 > need_flops / 197e12     # the bytes bind


@pytest.mark.parametrize("fault", [None, "decay_by_head", "rotary_on_pairs",
                                   "groups_dropped"])
def test_the_ling_kda_toy_gate_and_three_faults_in_the_program(
        monkeypatch, fault):
    """The reference, found by name, gates a toy twin through the harness's
    full-batch prefill (a padded window) and its decode steps; and the other
    direction of the controls: the PROGRAM broken, the reference sound."""
    import dataclasses

    import jax.numpy as jnp
    from neuronx_distributed_inference_tpu.models.family import get_family
    from neuronx_distributed_inference_tpu.modules import ssm
    from test_ling_kda_paged import HF, SERVE
    family = get_family("ling_kda")
    build_spec = family.build_spec.__func__

    def broken(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        if fault == "rotary_on_pairs":
            return dataclasses.replace(spec, rope_interleaved=True)
        return dataclasses.replace(spec, moe=dataclasses.replace(
            spec.moe, n_group=1, topk_group=1))
    if fault in ("rotary_on_pairs", "groups_dropped"):
        monkeypatch.setattr(family, "build_spec", classmethod(broken))
    if fault == "decay_by_head":
        step, chunked = ssm._kda_step, ssm._kda_chunked

        def by_head(g):
            return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        monkeypatch.setattr(ssm, "_kda_step", lambda q, k, v, g, *a:
                            step(q, k, v, by_head(g), *a))
        monkeypatch.setattr(ssm, "_kda_chunked", lambda q, k, v, g, *a:
                            chunked(q, k, v, by_head(g), *a))
    toy = dict(HF, family="ling_kda", tp=1, dtype="float32", serve=SERVE,
               adapter={},
               gate=dict(config={}, batch=2, prompt_len=24, new_tokens=8,
                         atol=2e-5, rtol=1e-4, min_positions_held=1.0,
                         median_ratio_max=0.5, worst_ratio_max=1.0,
                         excuse_margin_max=0.0))
    res = build.logit_gate(toy, seed=2**31 + 67, served_precision="highest")
    if fault is None:
        assert res["passed"], res
        assert res["compared"] == 2 * 32 * HF["vocab_size"]
    else:
        assert not res["passed"] and res["worst_ratio"] > 5
