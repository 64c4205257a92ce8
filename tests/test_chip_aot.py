"""The serving graphs compile for the chip — checked without one.

``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` hands back four abstract v5e devices while the
process itself stays on the CPU backend; lowering a jitted function over
``ShapeDtypeStruct`` arguments sharded on them runs XLA:TPU and Mosaic from
the installed libtpu. This is what keeps "the kernels compile, and stay
compiled" true between chip runs (``benchmark/run.py`` is the run itself):

  * Llama-3.2-1B widths (dense, heads of 64, depth 2), tp=1 and tp=4:
    ``paged_forward_step`` at T=1 and one prefill width,
    ``paged_decode_loop`` and ``paged_ragged_step``, kernels compiled for
    real — the T=1 graphs must hold a Mosaic custom call, and the tp=4
    compile log no involuntary full rematerialization;
  * OLMoE-1B-7B widths (ROADMAP B1, depth 1): the same graphs lower, so
    the first benchmark cell starts from a graph known to compile.
"""

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models import model_base
from neuronx_distributed_inference_tpu.models.family import get_family
from neuronx_distributed_inference_tpu.modules import ssm
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
    block_cache_pspec, index_pool_shape, pool_spec, window_pool_spec)
from neuronx_distributed_inference_tpu.ops import kernel_mode
from neuronx_distributed_inference_tpu.parallel.layers import ParamSpec
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)
from neuronx_distributed_inference_tpu.telemetry import observatory

MOSAIC = 'custom_call_target="tpu_custom_call"'

LLAMA_3_2_1B = dict(
    model_type="llama", hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
    head_dim=64, vocab_size=128256, rms_norm_eps=1e-5, rope_theta=500000.0,
    rope_scaling={"factor": 32.0, "high_freq_factor": 4.0,
                  "low_freq_factor": 1.0,
                  "original_max_position_embeddings": 8192,
                  "rope_type": "llama3"},
    max_position_embeddings=131072, hidden_act="silu",
    tie_word_embeddings=True)
# 1024 blocks of 32 = 1.07 GB of pool; XLA's account of the widest graph (a
# 256-wide ragged row) is 3.7 GB of arguments + 4.8 GB of temps, of 16.
SERVE = dict(batch_size=8, seq_len=2048, pa_block_size=32, pa_num_blocks=1024,
             context_encoding_buckets=[64, 256])

# allenai/OLMoE-1B-7B-0125-Instruct config.json (model-configs catalog)
OLMOE_1B_7B = dict(
    model_type="olmoe", hidden_size=2048, intermediate_size=1024,
    num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
    num_experts=64, num_experts_per_tok=8, norm_topk_prob=False,
    vocab_size=50304, rms_norm_eps=1e-5, rope_theta=10000.0,
    max_position_embeddings=4096, hidden_act="silu",
    tie_word_embeddings=False)


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no libtpu in this environment
        pytest.skip(f"no TPU compiler available here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(autouse=True)
def as_on_the_chip(monkeypatch):
    """Undo the two things conftest sets for CPU tests that a serving
    process on a chip does not have: the interpret-mode request, and
    float32 ("highest") matmul precision — under which Mosaic refuses the
    bf16 ragged_dot kernel of the MoE prefill outright."""
    monkeypatch.delenv(kernel_mode.INTERPRET_ENV, raising=False)
    # a CPU process cannot load a TPU executable back, so caching these
    # would only fill the suite's cache directory
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        with jax.default_matmul_precision("default"):
            yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)


def _serving_shapes(hf_attrs, layers, tp, devices, serve, prefix=True):
    """(spec, tcfg, mesh, params, cache, sds, max_blocks): the paged serving
    graphs' static arguments and their operands as shapes sharded on
    ``devices`` - the KV pool as the application allocates it, and for a
    recurrent stack its state slots beside it."""
    mesh = build_mesh(MeshConfig(tp=tp), devices)
    tcfg = TpuConfig(tp_degree=tp, dtype="bfloat16", enable_bucketing=True,
                     is_block_kv_layout=True, is_prefix_caching=prefix,
                     **serve)
    family = get_family(hf_attrs["model_type"])
    depth = "num_layers" if "num_layers" in hf_attrs else "num_hidden_layers"
    icfg = family.config_cls(tcfg, **dict(hf_attrs, **{depth: layers}))
    spec = family.build_spec(icfg, tp_degree=tp)

    def sds(shape, dtype, pspec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    params = jax.tree.map(lambda ps: sds(ps.shape, ps.dtype, ps.pspec),
                          model_base.decoder_param_specs(spec),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    bspec = pool_spec(spec, tcfg.pa_num_blocks, tcfg.pa_block_size)
    # a latent pool (MLA) has no head axis to shard
    pool_pspec = P() if bspec.is_latent else block_cache_pspec()
    cache = {"k": sds(bspec.shape, bspec.dtype, pool_pspec),
             "v": sds(bspec.v_shape, bspec.dtype, pool_pspec)}
    if spec.window_pool:
        # the window layers' ring pool, as init_cache sizes it
        wspec = window_pool_spec(spec, tcfg.batch_size, tcfg.pa_block_size,
                                 max(tcfg.context_encoding_buckets))
        cache["k_w"] = sds(wspec.shape, wspec.dtype, pool_pspec)
        cache["v_w"] = sds(wspec.v_shape, wspec.dtype, pool_pspec)
    if spec.sparse is not None:
        # a learned sparse selection's index keys, as init_cache sizes them
        cache["k_idx"] = sds(index_pool_shape(spec, tcfg.pa_num_blocks,
                                              tcfg.pa_block_size),
                             spec.kv_dtype)
    if spec.ssm is not None:
        pspecs = ssm.ssm_state_pspecs(spec.ssm)
        for k, (shape, dt) in ssm.ssm_state_shapes(
                spec.ssm, spec.num_ssm_layers, tcfg.batch_size,
                spec.dtype).items():
            cache[k] = sds(shape, dt, pspecs[k])
    return spec, tcfg, mesh, params, cache, sds, bspec.blocks_for(tcfg.seq_len)


def _compile_serving_graphs(hf_attrs, layers, tp, devices, serve, only=None):
    """Lower + compile the paged serving graph family (or the ``only``
    subset) for ``devices`` and return {name: compiled text}."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        hf_attrs, layers, tp, devices, serve)
    b = tcfg.batch_size
    i32 = jnp.int32
    rng = sds((2,), jnp.uint32)
    width = tcfg.context_encoding_buckets[0]

    def rows(w):
        return (sds((b, w), i32),) * 3 + (sds((b, mb), i32),)

    graphs = {
        "paged_t1": (partial(model_base.paged_forward_step, spec, tcfg),
                     (*rows(1), sds((b,), i32), None, rng)),
        "paged_prefill": (partial(model_base.paged_forward_step, spec, tcfg),
                          (*rows(width), sds((b,), i32), None, rng)),
        "decode_loop": (partial(model_base.paged_decode_loop, spec, tcfg,
                                num_steps=4),
                        (sds((b,), i32), sds((b,), i32), sds((b, mb), i32),
                         None, rng)),
        "ragged_w1": (partial(model_base.paged_ragged_step, spec, tcfg),
                      (*rows(1), sds((b,), i32), sds((b,), i32), None, rng)),
        "ragged_prefill": (partial(model_base.paged_ragged_step, spec, tcfg),
                           (*rows(width), sds((b,), i32), sds((b,), i32),
                            None, rng)),
    }
    out = {}
    with jax.sharding.set_mesh(mesh):
        for name, (fn, args) in graphs.items():
            if only is not None and name not in only:
                continue
            out[name] = jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, *args).compile().as_text()
    return out


@pytest.mark.parametrize("tp", [1, 4])
def test_llama_1b_serving_graphs_compile_for_v5e(v5e_devices, tp):
    counts = {"spmd_warnings": 0, "involuntary_remat": 0}
    with observatory.capture_compiler_stderr(counts, tee=False):
        texts = _compile_serving_graphs(
            LLAMA_3_2_1B, 2, tp, v5e_devices, SERVE,
            only=("paged_t1", "paged_prefill", "decode_loop", "ragged_w1"))
    # the decode graphs hold the kernel — read from the executable
    for name in ("paged_t1", "decode_loop", "ragged_w1"):
        assert MOSAIC in texts[name], f"{name}: no Mosaic custom call"
    # wide rows walk their live pages on the prefill kernel (ISSUE 49: 8 kv
    # heads of 64, two to a row of the stored page) on one chip; across
    # four the kernel declines by the mesh and the table is gathered
    assert (MOSAIC in texts["paged_prefill"]) == (tp == 1)
    assert ("paged_prefill_attention" in texts["paged_prefill"]) == (tp == 1)
    assert counts["involuntary_remat"] == 0


# meta-llama/Llama-3-8B's attention on Llama-3.2-1B's other widths: 8 kv
# heads of 128, two to a shard at tp=4
HEADS_OF_128 = dict(LLAMA_3_2_1B, hidden_size=4096, head_dim=128,
                    intermediate_size=14336, tie_word_embeddings=False)


@pytest.mark.parametrize("hf, tp, rows, width, page, kernel, temps_under", [
    (LLAMA_3_2_1B, 1, 32, 1, (4, 128), "pages=8 heads=8", 64e6),
    (LLAMA_3_2_1B, 1, 1, 256, (4, 128), None, 64e6),
    (LLAMA_3_2_1B, 1, 32, 256, (4, 128), None, 1.2e9),
    (LLAMA_3_2_1B, 4, 32, 1, (4, 128), "pages=16 heads=2", 64e6),
    (HEADS_OF_128, 4, 32, 1, (4, 256), "pages=16 heads=2", 64e6),
], ids=["step", "chunk", "pack", "step-tp4", "step-tp4-heads-of-128"])
def test_heads_that_share_a_slot_leave_the_pool_where_it_is(
        v5e_devices, hf, tp, rows, width, page, kernel, temps_under):
    """ISSUE 41: the pool is ALLOCATED as the decode kernel reads a shard's
    page (``block_kv_cache.pool_page``). Llama-3.2-1B's attention (8 kv
    heads of 64, as granite-4.0-h-micro's) at 4 layers, the granite cell's
    rows, pool and table: as pages of ``(8, 64)`` the decode step, the
    one-row chunk and the full-batch pack each moved the whole 268 MB pool
    FOUR times between the layout it was declared in and the one its
    consumer read (PR 33 had folded it around the step alone). Stored
    ``(4, 128)`` no instruction of any of the three moves a pool, in either
    shape or flat - the chunk's gather takes a page as the matrix it is in
    memory, or it pays two copies a LAYER - and the step's temps are
    activations. At tp=4 a shard's two heads share its one slot: the same,
    and the same for two heads of 128 a shard (Llama-3-8B's; a head a slot
    it paid two ``reshape`` of a shard's pool in every layer of a step)."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        hf, 4, tp, v5e_devices[:tp],
        dict(batch_size=32, seq_len=4096, pa_block_size=32,
             pa_num_blocks=2048, context_encoding_buckets=[64, 256]))
    assert cache["k"].shape == (4, 2049, 32) + page
    i32 = jnp.int32
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        program = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                          donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32)).compile()
    text = program.as_text()
    heads, lanes = 8, spec.head_dim
    want = {("kv_pool", "xla",
             f"page={page[0]}x{page[1]} heads={heads}x{lanes}")}
    assert MOSAIC in text
    if kernel:
        want.add(("paged_decode", "pallas",
                  f"{kernel} form=mxu-blockdiag fold=2 stored "
                  "prefetch=across-rows"))
    else:
        # the chunk and the pack walk the stored page on the prefill kernel
        want.add(("paged_prefill", "pallas",
                  f"rows={rows} width={width} pages=16 heads=32 fold=2 "
                  f"tile=32x{width} window=0"))
    assert notes == want
    # every instruction that MOVES a shard's pool, whatever shape it gives
    # it; a fusion of a flat shape is the slot write, in place
    size = math.prod(cache["k"].shape) // tp
    moves = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (copy|reshape|transpose|fusion)\(",
        text)
        if math.prod(int(n) for n in shape.split(",")) == size
        and (op != "fusion" or shape.count(",") == 4)]
    assert not moves, moves
    assert program.memory_analysis().temp_size_in_bytes < temps_under


def test_olmoe_1b_7b_serving_graphs_compile_for_v5e(v5e_devices):
    texts = _compile_serving_graphs(
        OLMOE_1B_7B, 1, 1, v5e_devices,
        dict(batch_size=8, seq_len=2048, pa_block_size=32, pa_num_blocks=512,
             context_encoding_buckets=[128]),
        only=("ragged_w1", "ragged_prefill"))
    assert MOSAIC in texts["ragged_w1"]


def test_olmoe_chunk_reads_experts_and_pool_in_place(v5e_devices):
    """ISSUE 31: the one-row 256-token chunk program at OLMoE's widths
    (the benchmark's ``paged.w256``, depth 2) copies no layer's expert
    weights and no layer of the pool in front of a consumer that cannot
    fuse a slice — the five ``dynamic-slice_bitcast_fusion`` ops that were
    26 of its 38 ms — and its temps are the attention's, not 3 x 268 MB of
    weights. Since ISSUE 39 its experts run on the walk over the touched
    experts, and the grouped matmuls on the stack are the 16-row pack's,
    held to the same. The decode step of the same spec is on neither form
    of the ragged path: no ``ragged-dot``, no ``moe_ragged`` note."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        OLMOE_1B_7B, 2, 1, v5e_devices[:1],
        dict(batch_size=16, seq_len=4096, pa_block_size=32,
             pa_num_blocks=1024, context_encoding_buckets=[64, 256]))
    i32 = jnp.int32

    def compiled(rows, width):
        notes = set()
        with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
            c = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                        donate_argnums=(1,)).lower(
                params, cache, *(sds((rows, width), i32),) * 3,
                sds((rows, mb), i32), sds((rows,), i32), None,
                sds((2,), jnp.uint32)).compile()
        return c, {n[1:] for n in notes if n[0] == "moe_ragged"}

    chunk, notes = compiled(1, 256)
    # ISSUE 39: the one-row chunk's experts are the walk's (the chunk's form
    # of the expert kernel, the leaves the program's own arguments); the
    # 16-row pack below keeps the grouped matmuls on the stack
    assert notes == set()
    text = chunk.as_text()
    assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    copied = re.findall(r"slice_bitcast_fusion[.\d]* = bf16\[([\d,]+)\]",
                        text)
    layer_shapes = {"64,2048,1024", "64,1024,2048", "1025,32,16,128"}
    assert not layer_shapes & set(copied), copied
    assert chunk.memory_analysis().temp_size_in_bytes < 200e6

    pack, notes = compiled(16, 64)
    assert notes == {("stacked", "")}
    text = pack.as_text()
    assert "ragged-dot" in text
    copied = re.findall(r"slice_bitcast_fusion[.\d]* = bf16\[([\d,]+)\]",
                        text)
    assert not layer_shapes & set(copied), copied

    decode, notes = compiled(16, 1)
    assert notes == set()
    assert "ragged-dot" not in decode.as_text()
    assert MOSAIC in decode.as_text()        # the paged decode kernel


def _compiled_paged_step(shapes, rows, width, **kw):
    """``paged_forward_step`` at ``rows`` x ``width`` compiled for the
    devices of ``shapes`` (:func:`_serving_shapes`), and the engagement
    records its trace left."""
    spec, tcfg, mesh, params, cache, sds, mb = shapes
    i32 = jnp.int32
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        c = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                    donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32), **kw).compile()
    return c, notes


def _state_stepped_in_place(text: str, stack: str, layers: int,
                            kernel: str = "delta_state_step"):
    """ISSUE 44, ISSUE 45: a T = 1 step's compiled text holds the state-step
    kernel once a delta-rule (``mamba_state_step``: a Mamba-2) layer, and
    nothing copies, transposes or slices the state stack ``f32[stack]`` or
    one layer of it out."""
    assert len(re.findall(rf"%{kernel}\S* = ", text)) == layers
    layer = stack.split(",", 1)[1]
    moves = re.findall(
        rf"%(\S+) = f32\[(?:{stack}|{layer})\]\S* "
        r"(copy|transpose|dynamic-slice|dynamic-update-slice|fusion)\(",
        text)
    assert not moves, moves


def _state_layout(text: str, stack: str) -> str:
    """The layout the device gives the state-stack parameter."""
    layout, = set(re.findall(
        rf"f32\[{stack}\](\S+) parameter\(", text))
    print(f"state parameter f32[{stack}]{layout}")
    return layout


#: ISSUE 55: how a delta-rule chunk program says it solves its system
BLOCKED_SOLVE = "blocked substitution, blocks of 16 merged on the MXU from 32"


def _triangular_custom_calls(text: str):
    """The custom-calls of a compiled text that are XLA's own triangular
    solve: ``jax.lax.linalg.triangular_solve`` lowers to ONE
    ``InvertDiagBlocksLowerTriangular`` a call, which inverts its diagonal
    blocks row after row off the MXU (0.60 ms a layer of Olmo-Hybrid's
    one-row chunk, 0.34 of Qwen3-Next's: PERF.md section 5, PR 55)."""
    return re.findall(
        r'custom_call_target="(\w*Triangular\w*)"', text)


# allenai/Olmo-Hybrid-7B config.json (model-configs catalog), one period
OLMO_HYBRID_7B = dict(
    model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
    intermediate_size=11008, num_attention_heads=30, num_key_value_heads=30,
    hidden_act="silu", max_position_embeddings=65536, attention_bias=False,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None})


def test_30_kv_heads_of_128_decode_on_the_kernel_with_no_pool_copy(
        v5e_devices):
    """ISSUE 34: Olmo-Hybrid-7B's attention (30 kv heads of 128, MHA) at one
    period, the benchmark's batch, pool and table. With 30 heads to a page
    the device stored the pool tokens-minor and the T=1 step moved the whole
    pool six times (1.86 GB of temps at ONE attention layer; at the cell's
    four the step did not fit the chip). The pool rounds its heads up to
    whole tiles (``block_kv_cache.pool_kv_heads``: 32 slots): the step holds
    the Mosaic call, the record says what it runs with, and no instruction
    moves a pool. The one-row 256-token chunk (the delta rule's chunked
    form) compiles beside it, its triangular system solved by blocked
    substitution (ISSUE 55): no custom-call inverts it row after row."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        OLMO_HYBRID_7B, 4, 1, v5e_devices[:1],
        dict(batch_size=32, seq_len=2048, pa_block_size=32,
             pa_num_blocks=1792, context_encoding_buckets=[64, 256]),
        prefix=False)
    assert cache["k"].shape == (1, 1793, 32, 32, 128)
    assert cache["ssm"].shape == (3, 32, 30, 96, 192)
    i32 = jnp.int32

    def compiled(rows, width, **kw):
        return _compiled_paged_step(
            (spec, tcfg, mesh, params, cache, sds, mb), rows, width, **kw)

    state = "kind=gated_delta slot_bytes=6842880 chunk=64"
    step, notes = compiled(32, 1)
    pool = ("kv_pool", "xla", "page=32x128 heads=32x128")
    # ISSUE 44: the T = 1 step's state is stepped by the kernel, in place
    assert notes == {
        ("recurrent_state", "pallas", f"{state} heads=30 tile=96x192"),
        pool, ("paged_decode", "pallas",
               "pages=1 heads=32 form=mxu-blockdiag fold=1 "
               "prefetch=across-rows")}
    text = step.as_text()
    assert MOSAIC in text
    moves = re.findall(
        r"%(\S+) = bf16\[(?:1,1793,32,32,128|57376,32,128)\]\S* "
        r"(copy|transpose)\(", text)
    assert not moves, moves
    _state_stepped_in_place(text, "3,32,30,96,192", layers=3)
    # what the device gives the state: 192 lanes tile to 256 (a third more
    # bytes in HBM than the values; PERF.md section 6, PR 44)
    assert _state_layout(text, "3,32,30,96,192") == "{4,3,2,1,0:T(8,128)}"
    assert step.memory_analysis().temp_size_in_bytes < 100e6
    chunk, notes = compiled(1, 256, state_slots=sds((1,), i32))
    # ISSUE 49: the chunk's attention walks the 32 head slots of the stored
    # page on the prefill kernel
    assert notes == {("recurrent_state", "xla",
                      f"{state}: 256 tokens a row: the chunked form, "
                      f"{BLOCKED_SOLVE}"), pool,
                     ("paged_prefill", "pallas",
                      "rows=1 width=256 pages=8 heads=32 fold=1 "
                      "tile=32x256 window=0")}
    assert "paged_prefill_attention" in chunk.as_text()
    assert "delta_state_step" not in chunk.as_text()
    assert not _triangular_custom_calls(chunk.as_text())
    assert chunk.memory_analysis().temp_size_in_bytes < 200e6


# Qwen/Qwen3-Next-80B-A3B-Instruct config.json (model-configs catalog), one
# period and one chip's share: 128 experts held of the 512 routed over
QWEN3_NEXT_SHARE = dict(
    model_type="qwen3_next", vocab_size=37984, hidden_size=2048,
    intermediate_size=5120, num_attention_heads=16, num_key_value_heads=2,
    head_dim=256, hidden_act="silu", max_position_embeddings=262144,
    rms_norm_eps=1e-6, tie_word_embeddings=False, full_attention_interval=4,
    partial_rotary_factor=0.25, rope_theta=10000000, rope_scaling=None,
    num_experts=128, router_num_experts=512, first_expert=0,
    num_experts_per_tok=10, norm_topk_prob=True, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, linear_num_key_heads=16,
    linear_num_value_heads=32, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel_dim=4)


def test_2_kv_heads_of_256_decode_on_the_kernel_with_no_pool_copy(
        v5e_devices):
    """ISSUE 36: Qwen3-Next's attention (2 kv heads of 256, GQA 8 : 1) at
    one period, the benchmark's batch, pool and table. As a page ``(32, 2,
    256)`` the device tiled the pool ``(2, 128)`` and the first chip run
    paid a ``reshape`` copy of the whole pool for K and for V in every
    attention layer of every decode step (10.4 of 28.9 ms), and six ``copy``
    of it in a chunk. Both heads of a token share one slot of 512 lanes
    (``block_kv_cache.pool_page``): the step holds the Mosaic call, the
    record says what it runs with, no instruction moves a pool, in the
    step or in the one-row chunk; the chunk reads its layer's experts out
    of the stack in place, on the share."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        QWEN3_NEXT_SHARE, 4, 1, v5e_devices[:1],
        dict(batch_size=32, seq_len=4096, pa_block_size=32,
             pa_num_blocks=4096, context_encoding_buckets=[64, 256]),
        prefix=False)
    assert cache["k"].shape == (1, 4097, 32, 1, 512)
    assert cache["ssm"].shape == (3, 32, 32, 128, 128)
    assert cache["conv_x"].shape == (3, 32, 8192, 3)
    assert params["layers"]["expert_up"].shape == (4, 128, 2048, 512)
    assert params["layers"]["router"].shape == (4, 2048, 512)
    i32 = jnp.int32

    def compiled(rows, width, **kw):
        return _compiled_paged_step(
            (spec, tcfg, mesh, params, cache, sds, mb), rows, width, **kw)

    def pool_moves(text):
        return re.findall(
            r"%(\S+) = bf16\[(?:1,)?4097,[\d,]+\]\S* "
            r"(copy|transpose|reshape)\(", text)

    state = "kind=gated_delta slot_bytes=6438912 chunk=64"
    share = ("moe_share", "xla", "held=128 of 512 from 0 top_k=10")
    pool = ("kv_pool", "xla", "page=1x512 heads=2x256")
    step, notes = compiled(32, 1)
    # ISSUE 44: the T = 1 step's state is stepped by the kernel, in place,
    # a value head reading its key head (16 for 32) through the index
    assert notes == {
        ("recurrent_state", "pallas", f"{state} heads=32 tile=128x128"),
        share, pool, (
        "paged_decode", "pallas",
        "pages=16 heads=2 form=mxu-blockdiag fold=2 stored "
        "prefetch=across-rows"),
        ("moe_decode", "pallas", "pieces=1 of 512")}
    text = step.as_text()
    assert MOSAIC in text and "ragged-dot" not in text
    assert not pool_moves(text), pool_moves(text)
    _state_stepped_in_place(text, "3,32,32,128,128", layers=3)
    assert _state_layout(text, "3,32,32,128,128") == "{4,3,2,1,0:T(8,128)}"
    assert step.memory_analysis().temp_size_in_bytes < 100e6
    chunk, notes = compiled(1, 256, state_slots=sds((1,), i32))
    # ISSUE 39: 256 x 10 / 512 = 5 rows an expert: the chunk's experts are
    # the walk's, each touched expert against ITS rows; ISSUE 49: its
    # attention cuts the slot of 512 lanes into its two heads of 256 on the
    # prefill kernel
    assert notes == {
        ("recurrent_state", "xla",
         f"{state}: 256 tokens a row: the chunked form, {BLOCKED_SOLVE}"),
        share, pool, (
        "moe_decode", "pallas",
        "pieces=1 of 512 rows=256 by expert in tiles of 128"), (
        "paged_prefill", "pallas",
        "rows=1 width=256 pages=16 heads=16 fold=2 tile=16x256 window=0")}
    text = chunk.as_text()
    assert "paged_prefill_attention" in text
    assert "delta_state_step" not in text
    assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    assert not _triangular_custom_calls(text)
    assert not pool_moves(text), pool_moves(text)
    copied = re.findall(r"slice_bitcast_fusion[.\d]* = bf16\[([\d,]+)\]",
                        text)
    assert not {"128,2048,512", "128,512,2048"} & set(copied), copied
    assert chunk.memory_analysis().temp_size_in_bytes < 200e6


# ibm-granite/granite-4.0-h-micro config.json (model-configs catalog), the
# first four layers of its pattern at the gate's order: three Mamba-2 mixers
# around one attention layer
GRANITE_H_MICRO = dict(
    model_type="granitemoehybrid", hidden_size=2048, intermediate_size=8192,
    shared_intermediate_size=8192, num_attention_heads=32,
    num_key_value_heads=8, vocab_size=100352, rms_norm_eps=1e-5,
    rope_theta=10000, max_position_embeddings=131072,
    position_embedding_type="nope", tie_word_embeddings=True,
    hidden_act="silu", attention_bias=False, num_local_experts=0,
    num_experts_per_tok=0, mamba_n_heads=64, mamba_d_head=64,
    mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    mamba_chunk_size=256, mamba_conv_bias=True, mamba_proj_bias=False,
    embedding_multiplier=12, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8,
    layer_types=["mamba", "mamba", "attention", "mamba"])


def test_64_mamba_heads_of_64x128_step_on_the_kernel_in_place(v5e_devices):
    """ISSUE 45: granite-4.0-h-micro's mixers (64 heads of ``(64, 128)`` in
    one group) at four layers, the cell's batch, pool and table. As XLA
    fusions a layer of the T = 1 step crossed its state three times (a loop
    fusion for the read-out, then ``add_dynamic-update-slice_fusion``, which
    read the state again). The step holds ``mamba_state_step`` once a
    Mamba-2 layer, no fusion, copy or slice over the stack or a layer of it,
    and the stack's parameter is the output's buffer; the one-row chunk
    keeps the chunked SSD form and holds no such call."""
    shapes = _serving_shapes(
        GRANITE_H_MICRO, 4, 1, v5e_devices[:1],
        dict(batch_size=32, seq_len=4096, pa_block_size=32,
             pa_num_blocks=2048, context_encoding_buckets=[64, 256]),
        prefix=False)
    cache, sds = shapes[4], shapes[5]
    assert cache["ssm"].shape == (3, 32, 64, 64, 128)
    assert cache["ssm"].dtype == jnp.float32
    i32 = jnp.int32

    def compiled(rows, width, **kw):
        c, notes = _compiled_paged_step(shapes, rows, width, **kw)
        return c, {n for n in notes if n[0] == "recurrent_state"}

    state = "kind=mamba2 slot_bytes=6369792 chunk=64"
    step, notes = compiled(32, 1)
    assert notes == {
        ("recurrent_state", "pallas", f"{state} heads=32 tile=64x128")}
    text = step.as_text()
    _state_stepped_in_place(text, "3,32,64,64,128", layers=3,
                            kernel="mamba_state_step")
    assert "add_dynamic-update-slice_fusion" not in text
    assert _state_layout(text, "3,32,64,64,128") == "{4,3,2,1,0:T(8,128)}"
    # the donated stack IS the output's buffer: no second copy of 201 MB
    param, = re.findall(
        r"%(\S+) = f32\[3,32,64,64,128\]\S+ parameter\((\d+)\)", text)
    assert re.search(
        rf"\{{[\d, ]*\}}: \({param[1]}, \{{\}}, may-alias\)", text), \
        "the state stack's parameter is not aliased to an output"
    assert step.memory_analysis().temp_size_in_bytes < 100e6
    chunk, notes = compiled(1, 256, state_slots=sds((1,), i32))
    assert notes == {("recurrent_state", "xla",
                      f"{state}: 256 tokens a row: the chunked form")}
    assert "mamba_state_step" not in chunk.as_text()
    assert chunk.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("hf, layers, serve, stack_shapes, plan", [
    (OLMOE_1B_7B, 2, dict(batch_size=16, seq_len=4096, pa_block_size=32,
                          pa_num_blocks=1024,
                          context_encoding_buckets=[64, 256]),
     {"2,64,2048,1024", "2,64,1024,2048", "64,2048,1024", "64,1024,2048"},
     "pieces=1 of 1024"),
    (QWEN3_NEXT_SHARE, 4, dict(batch_size=32, seq_len=4096, pa_block_size=32,
                               pa_num_blocks=4096,
                               context_encoding_buckets=[64, 256]),
     {"4,128,2048,512", "4,128,512,2048", "128,2048,512", "128,512,2048"},
     "pieces=1 of 512"),
], ids=["olmoe", "qwen3-next-share"])
def test_moe_decode_reads_the_expert_stack_in_place(v5e_devices, hf, layers,
                                                    serve, stack_shapes,
                                                    plan):
    """ISSUE 37: the decode step (the benchmark's ``paged.w1``) at OLMoE's
    and at qwen3-next's widths - the scan of the one, the static loop of
    the other - holds the ``moe_decode_experts`` call, which takes the
    expert leaves as the program's own arguments: no instruction copies,
    relays or slices an array of an expert stack's (or a layer's experts')
    shape in front of it, and the step's temps are activations, not 805 MB
    of a layer's experts."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        hf, layers, 1, v5e_devices[:1], serve, prefix=False)
    i32 = jnp.int32
    rows = tcfg.batch_size
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        step = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                       donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, 1), i32),) * 3, sds((rows, mb), i32),
            sds((rows,), i32), None, sds((2,), jnp.uint32)).compile()
    assert ("moe_decode", "pallas", plan) in notes
    text = step.as_text()
    calls = re.findall(r"%(moe_decode_experts[.\d]*) = f32\[(\d+),2048\]\S* "
                       r"custom-call\(", text)
    assert calls and all(int(n) == rows for _, n in calls), calls
    moved = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in stack_shapes
        and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not moved, moved
    assert step.memory_analysis().temp_size_in_bytes < 100e6


# meituan-longcat/LongCat-Flash-Omni config.json (model-configs catalog), the
# benchmark's share: 16 routed experts held of 512 + 256 identity columns
LONGCAT_FLASH_SHARE = dict(
    model_type="longcat_flash", attention_bias=False, vocab_size=16384,
    hidden_size=6144, ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
    num_layers=4, num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=16, router_n_routed_experts=512, first_expert=0,
    max_position_embeddings=131072, rms_norm_eps=1e-5, rope_theta=10000000,
    attention_method="MLA", zero_expert_num=256, zero_expert_type="identity",
    moe_topk=12)


LONGCAT_FLASH_SERVE = dict(batch_size=32, seq_len=8192, pa_block_size=32,
                           pa_num_blocks=8192,
                           context_encoding_buckets=[64, 256])


def _longcat_program(v5e_devices, rows, width):
    """One layer (two latent-attention sub-blocks) at the share's widths."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        LONGCAT_FLASH_SHARE, 1, 1, v5e_devices[:1], LONGCAT_FLASH_SERVE)
    assert cache["k"].shape == (2, 8193, 32, 1, 640)
    assert cache["v"].shape == (2, 8193, 32, 1, 0)
    i32 = jnp.int32
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        c = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                    donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32)).compile()
    return c, notes


def _pool_movers(text, shapes):
    """Instructions that copy, transpose or relay an array of a latent
    pool's shape (``shapes``: the pool and its flat views)."""
    return [(name, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in shapes
        and op not in ("parameter", "get-tuple-element", "bitcast",
                       "fusion", "scatter", "custom-call")]


LONGCAT_POOL = ("2,8193,32,1,640", "2,8193,32,640", "2,262176,1,640",
                "16386,32,1,640")
DEEPSEEK_V3_POOL = ("5,12289,32,1,640", "5,12289,32,640", "5,393248,1,640",
                    "61445,32,1,640")


def test_latent_decode_runs_its_kernel_on_the_pool_in_place(v5e_devices):
    """ISSUE 40: the decode step at LongCat-Flash's widths (one layer: two
    latent-attention sub-blocks, a pool of 8192 blocks of 640-lane rows)
    holds the ``mla_decode_attention`` call twice and the walk over the
    touched experts in column pieces (an expert of 6144 x 2048 is 75.5 MB,
    three times the walk's slots); no instruction copies, transposes or
    relays the latent pool; the V pool has no bytes. (The one-row chunk:
    ``test_a_latent_chunk_attends_on_the_prefill_kernel``.)"""
    decode, notes = _longcat_program(v5e_devices, 32, 1)
    assert ("mla_decode", "pallas",
            "latent lanes=640 heads=64 form=absorbed pages=32 "
            "tiles=tokens-held prefetch=across-rows") in notes
    assert ("moe_decode", "pallas", "pieces=8 of 256") in notes
    assert ("moe_share", "xla",
            "held=16 of 768 from 0 top_k=12 zero=256") in notes
    text = decode.as_text()
    calls = re.findall(r"%(mla_decode_attention[.\d]*) = bf16\[32,64,512\]",
                       text)
    assert len(calls) == 2, calls
    assert not _pool_movers(text, LONGCAT_POOL)
    assert decode.memory_analysis().temp_size_in_bytes < 400e6


@pytest.mark.parametrize("cell, heads, width", [("longcat", 64, 256),
                                                ("longcat", 64, 64),
                                                ("deepseek-v3", 128, 256)],
                         ids=["longcat-w256", "longcat-w64",
                              "deepseek-v3-w256"])
def test_a_latent_chunk_attends_on_the_prefill_kernel(v5e_devices, cell,
                                                      heads, width):
    """ISSUE 48: the one-row chunk programs of both latent cells, at their
    widths (the benchmark's ``paged.w256`` of each, and LongCat's
    ``paged.w64``), hold ``mla_prefill_attention``, lowered ONCE a call
    site whatever the depth (LongCat's layer: its two sub-blocks;
    DeepSeek-V3: its two scans); the kernel reads the pool in place (no
    instruction copies, transposes or relays it) and NO float32 tensor of
    ``heads x T x tokens`` (a group of 512 tokens' scores: 32 MB at 64
    heads x 256, 64 MB at 128) is left in the program. DeepSeek-V3's chunk
    still walks its experts by rows."""
    if cell == "longcat":
        chunk, notes = _longcat_program(v5e_devices, 1, width)
        pool = LONGCAT_POOL
    else:
        chunk, notes = _deepseek_v3_program(v5e_devices, 1, width)
        pool = DEEPSEEK_V3_POOL
        assert ("moe_decode", "pallas",
                "pieces=8 of 256 rows=256 by expert in tiles of 128") in notes
        assert not any(site == "moe_ragged" for site, _, _ in notes)
    assert {(path, why) for site, path, why in notes
            if site == "mla_prefill"} == {
        ("pallas", f"rows=1 width={width} latent lanes=640 heads={heads} "
         f"form=absorbed tile=8x{width} pages=16 folds and own tokens "
         "inside")}
    text = chunk.as_text()
    assert len(set(re.findall(
        rf"%(mla_prefill_attention[.\d]*) = bf16\[1,{width},{heads * 128}\]",
        text))) == 2
    if cell == "deepseek-v3":
        assert "moe_chunk_experts" in text
    assert not _pool_movers(text, pool), _pool_movers(text, pool)
    scores = heads * width * 512
    big = [(name, shape) for name, shape in re.findall(
        r"%(\S+) = f32\[([\d,]+)\]", text)
        if str(heads) in shape.split(",")
        and math.prod(int(d) for d in shape.split(",")) >= scores // 2]
    assert not big, big[:5]
    assert chunk.memory_analysis().temp_size_in_bytes < 400e6


def test_the_widest_longcat_program_fits_beside_weights_and_pool(
        v5e_devices):
    """ISSUE 40: ``paged_pack.w256`` at the configuration's size (4 layers,
    32 rows x 256 tokens over tables of 8192 positions) compiles for a v5e,
    which refuses a program over 15.75 GB: 13.07 GB of arguments (weights
    10.38, latent pool 2.68) and under 2.2 GB of temps. It first needed 4.39
    GB and was refused: ``experts_ragged`` held 98,304 token copies at once
    (2.25 GB of float32 outputs alone), so a pack's rows go through it in
    groups under the attention scores' budget (``experts_ragged_by_rows``);
    and the prefix walk's scores are a group of 512 tokens, not the table."""
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        LONGCAT_FLASH_SHARE, 4, 1, v5e_devices[:1], LONGCAT_FLASH_SERVE)
    i32 = jnp.int32
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        pack = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                       donate_argnums=(1,)).lower(
            params, cache, *(sds((32, 256), i32),) * 3, sds((32, mb), i32),
            sds((32,), i32), None, sds((2,), jnp.uint32)).compile()
    assert ("moe_ragged", "row-groups", "4 of 32 rows") in notes
    memory = pack.memory_analysis()
    assert 13.0e9 < memory.argument_size_in_bytes < 13.1e9
    assert memory.temp_size_in_bytes < 2.2e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


# deepseek-ai/DeepSeek-V3 config.json (model-configs catalog), the benchmark's
# share: one dense layer before four expert layers, 16 routed experts held
# of 256 in 8 groups, an eighth of the vocabulary
DEEPSEEK_V3_SHARE = dict(
    model_type="deepseek_v3", attention_bias=False, first_k_dense_replace=1,
    hidden_act="silu", hidden_size=7168, intermediate_size=18432,
    kv_lora_rank=512, max_position_embeddings=163840,
    moe_intermediate_size=2048, n_group=8, n_routed_experts=16,
    router_n_routed_experts=256, first_expert=0, n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
    num_hidden_layers=5, num_key_value_heads=128, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-6,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=4, v_head_dim=128,
    vocab_size=16160)
DEEPSEEK_V3_SERVE = dict(batch_size=32, seq_len=12288, pa_block_size=32,
                         pa_num_blocks=12288,
                         context_encoding_buckets=[64, 256])


def _deepseek_v3_program(v5e_devices, rows, width):
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        DEEPSEEK_V3_SHARE, 5, 1, v5e_devices[:1], DEEPSEEK_V3_SERVE)
    assert cache["k"].shape == (5, 12289, 32, 1, 640) and mb == 384
    assert cache["v"].shape == (5, 12289, 32, 1, 0)
    i32 = jnp.int32
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        c = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                    donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32)).compile()
    return c, notes


def test_deepseek_v3_decodes_on_both_kernels_at_its_widths(v5e_devices):
    """ISSUE 47: the decode step at the configuration's size (a dense layer
    and four expert layers, two scans) holds the latent decode kernel at 128
    heads, the heads held still on the MXU, in blocks of 16 pages (at 64
    heads 32, by the clock: ``mla_decode.block_pages``), and the walk over
    the touched experts in column pieces (an expert of 7168 x 2048 is 88
    MB); the one-row chunk of 256 tokens walks its experts by rows, its
    float32 rows and result
    held once (32.1 MiB beside the slots, 46.1 in a pipeline's pairs:
    declined before: ``test_a_latent_chunk_attends_on_the_prefill_kernel``
    holds that); no instruction copies, transposes or relays the latent
    pool."""
    decode, notes = _deepseek_v3_program(v5e_devices, 32, 1)
    assert ("mla_decode", "pallas",
            "latent lanes=640 heads=128 form=absorbed pages=16 "
            "tiles=heads-held prefetch=across-rows") in notes
    assert ("moe_decode", "pallas", "pieces=8 of 256") in notes
    assert ("moe_share", "xla",
            "held=16 of 256 from 0 top_k=8 groups=8 top=4") in notes
    text = decode.as_text()
    assert text.count(MOSAIC) >= 3          # MLA in both scans, the walk
    assert re.findall(r"%mla_decode_attention[.\d]* = bf16\[32,128,512\]",
                      text)
    assert not _pool_movers(text, DEEPSEEK_V3_POOL)
    assert decode.memory_analysis().temp_size_in_bytes < 400e6


def test_the_widest_deepseek_v3_program_fits_beside_weights_and_pool(
        v5e_devices):
    """ISSUE 47: ``paged_pack.w256`` at the configuration's size (32 rows x
    256 tokens over tables of 12288 positions, 128 heads) compiles for a
    v5e, which refuses a program over 15.75 GiB: 11.67 GB of arguments
    (weights 9.15, latent pool 2.52) and under 2.2 GB of temps (1.91
    measured: the expanded prefix a group of 512 tokens at a time, the
    pack's experts 8 rows at a time)."""
    pack, notes = _deepseek_v3_program(v5e_devices, 32, 256)
    assert ("moe_ragged", "row-groups", "8 of 32 rows") in notes
    memory = pack.memory_analysis()
    assert 11.6e9 < memory.argument_size_in_bytes < 11.7e9
    assert memory.temp_size_in_bytes < 2.2e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


# PowerInfer/SmallThinker-21BA3B-Instruct config.json (model-configs
# catalog) at the benchmark's cut: two periods of [global, window x 3]
SMALLTHINKER_21B = dict(
    model_type="smallthinker", head_dim=128, hidden_size=2560,
    max_position_embeddings=16384, moe_ffn_hidden_size=768,
    moe_num_active_primary_experts=6, moe_num_primary_experts=64,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_attention_heads=28, num_hidden_layers=8, num_key_value_heads=4,
    rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1] * 2, rope_scaling=None,
    rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=4096, tie_word_embeddings=False, vocab_size=151936)
SMALLTHINKER_SERVE = dict(batch_size=32, seq_len=15360, pa_block_size=32,
                          pa_num_blocks=15360,
                          context_encoding_buckets=[64, 256])


def _smallthinker_program(v5e_devices, rows, width):
    spec, tcfg, mesh, params, cache, sds, mb = _serving_shapes(
        SMALLTHINKER_21B, 8, 1, v5e_devices[:1], SMALLTHINKER_SERVE,
        prefix=False)
    assert spec.window_pool and mb == 480
    assert cache["k"].shape == (2, 15361, 32, 1, 512)
    assert cache["k_w"].shape == (6, 32 * 137, 32, 1, 512)
    i32 = jnp.int32
    kw = {} if rows == 32 else {"state_slots": sds((rows,), i32)}
    notes = set()
    with jax.sharding.set_mesh(mesh), kernel_mode.recording(notes):
        c = jax.jit(partial(model_base.paged_forward_step, spec, tcfg),
                    donate_argnums=(1,)).lower(
            params, cache, *(sds((rows, width), i32),) * 3,
            sds((rows, mb), i32), sds((rows,), i32), None,
            sds((2,), jnp.uint32), **kw).compile()
    return c, notes


def _smallthinker_pool_movers(text):
    """Instructions that copy, transpose or relay an array of either pool's
    shape (as stored, or as a consumer views it)."""
    pools = ("2,15361,32,1,512", "6,4384,32,1,512", "2,15361,32,512",
             "6,4384,32,512", "2,491552,1,512", "6,140288,1,512",
             "30722,32,1,512", "26304,32,1,512", "30722,32,512",
             "26304,32,512")
    return [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in pools
        and op not in ("parameter", "get-tuple-element", "bitcast",
                       "fusion", "scatter", "custom-call", "while",
                       "dynamic-update-slice")]


def test_smallthinker_decode_walks_both_pools_in_place(v5e_devices):
    """ISSUE 43: the decode step at SmallThinker's widths and the cell's
    serving shape (two periods; the global layers' pool of 15,360 blocks, the
    window layers' of 32 rings of 137 pages) holds the paged decode kernel
    eight times - two calls over the allocator's table, six over the ring's
    logical table with the window - and the walk over the touched ReLU-gated
    experts; nothing copies, transposes or relays either pool or the expert
    stacks, and the one-row chunk gathers a ring's pages, not the table."""
    step, notes = _smallthinker_program(v5e_devices, 32, 1)
    assert ("moe_decode", "pallas", "pieces=1 of 768") in notes
    assert {(s, p, w.split(" fold")[1]) for s, p, w in notes
            if s == "paged_decode"} == {
        ("paged_decode", "pallas",
         "=4 stored prefetch=across-rows window=0"),
        ("paged_decode", "pallas",
         "=4 stored prefetch=across-rows window=4096 ring=137")}
    assert any(s == "kv_window_pool" and "global=2 window=6" in w
               and "ring_pages=137" in w for s, _, w in notes)
    text = step.as_text()
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 4
    assert "%moe_decode_experts" in text
    assert not _smallthinker_pool_movers(text), \
        _smallthinker_pool_movers(text)
    moved = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in ("8,64,2560,768", "8,64,768,2560", "64,2560,768",
                     "64,768,2560")
        and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not moved, moved
    assert step.memory_analysis().temp_size_in_bytes < 300e6
    chunk, notes = _smallthinker_program(v5e_devices, 1, 256)
    assert ("moe_decode", "pallas",
            "pieces=1 of 768 rows=256 by expert in tiles of 128") in notes
    text = chunk.as_text()
    assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    assert not _smallthinker_pool_movers(text), \
        _smallthinker_pool_movers(text)
    assert chunk.memory_analysis().temp_size_in_bytes < 1.6e9


@pytest.mark.parametrize("rows, temps_under", [(1, 50e6), (32, 1.1e9)],
                         ids=["chunk", "pack"])
def test_smallthinker_chunks_walk_both_pools_on_the_prefill_kernel(
        v5e_devices, rows, temps_under):
    """ISSUE 49: SmallThinker's one-row chunk (``paged.w256``) and its pack
    (``paged_pack.w256``) at the cell's serving shape hold
    ``paged_prefill_attention`` - lowered once a call site of the scanned
    period (a global layer over the allocator's table, the window layers
    over the ring's logical table), not once a layer - and NO float32
    tensor of heads x width x table tokens: a global layer's scores were 28
    x 256 x 15,360 x 4 B = 440 MB a row, a window layer's 28 x 256 x 4,384 =
    126 MB, and a pack took its rows through ``map_row_groups``: temps 0.45
    GB / 2.30 GB before, 2.3 MB / 0.99 GB now (the pack's are its experts'
    row groups). Nothing copies, transposes or relays either pool; no
    gather of a table is left."""
    program, notes = _smallthinker_program(v5e_devices, rows, 256)
    plan = f"rows={rows} width=256 pages=16 heads=28 fold=4 tile=28x256"
    assert {(path, why) for site, path, why in notes
            if site == "paged_prefill"} == {
        ("pallas", plan + " window=0"),
        ("pallas", plan + " window=4096 ring=137")}
    text = program.as_text()
    assert MOSAIC in text
    calls = set(re.findall(
        rf"%(paged_prefill_attention[.\d]*) = bf16\[{rows},256,3584\]", text))
    assert 2 <= len(calls) <= 4, calls       # a call site, not a layer
    assert not _smallthinker_pool_movers(text), \
        _smallthinker_pool_movers(text)
    scores = 28 * 256 * 4384
    big = [(name, shape) for name, shape in re.findall(
        r"%(\S+) = f32\[([\d,]+)\]", text)
        if {"28", "7", "256", "7168"} & set(shape.split(","))
        and math.prod(int(d) for d in shape.split(",")) >= rows * scores // 2]
    assert not big, big[:5]
    assert program.memory_analysis().temp_size_in_bytes < temps_under


def test_the_widest_smallthinker_program_fits_beside_weights_and_pools(
        v5e_devices):
    """ISSUE 43: ``paged_pack.w256`` at the configuration's size (32 rows x
    256 tokens over tables of 15,360 positions) compiles for a v5e, which
    refuses a program over 15.75 GB: 11.67 GB of arguments (weights 7.94,
    global pool 2.01, window pool 1.72) and its temps."""
    pack, _ = _smallthinker_program(v5e_devices, 32, 256)
    memory = pack.memory_analysis()
    assert 11.6e9 < memory.argument_size_in_bytes < 11.75e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json (model-configs catalog) at the
# benchmark's cut: one chip's share of a stage, 12 layers, 16 held experts
# of a router over 128, an eighth of the vocabulary
KEYE_VL2_30B = dict(
    model_type="KeyeVL2", attention_bias=False, decoder_sparse_step=1,
    head_dim=128, hidden_act="silu", hidden_size=2048,
    intermediate_size=6144, max_position_embeddings=262144,
    mlp_only_layers=[], moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=16, router_num_experts=128,
    first_expert=0, num_experts_per_tok=8, num_hidden_layers=12,
    num_key_value_heads=4, rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    tie_word_embeddings=False, vocab_size=18992)
KEYE_VL2_SERVE = dict(batch_size=32, seq_len=12288, pa_block_size=32,
                      pa_num_blocks=12288,
                      context_encoding_buckets=[64, 256])
KEYE_VL2_POOLS = ("12,12289,32,1,512", "12,12289,32,512", "12,393248,1,512",
                  "147468,32,1,512", "147468,32,512", "12,12289,16,128",
                  "12,196624,128", "147468,16,128")


def _keye_vl2_program(v5e_devices, rows, width):
    shapes = _serving_shapes(KEYE_VL2_30B, 12, 1, v5e_devices[:1],
                             KEYE_VL2_SERVE)
    spec, cache, mb = shapes[0], shapes[4], shapes[6]
    assert spec.sparse.topk == 2048 and mb == 384
    assert spec.moe.num_held == 16 and spec.moe.num_experts == 128
    assert cache["k"].shape == (12, 12289, 32, 1, 512)
    assert cache["k_idx"].shape == (12, 12289, 16, 128)
    return _compiled_paged_step(shapes, rows, width)


def test_keye_vl2_decodes_over_three_pools_in_place(v5e_devices):
    """ISSUE 50: the decode step at Keye-VL-2.0's widths and the cell's
    serving shape (12 layers, K / V pools of 12,288 blocks and the index
    keys' pool on the same table) holds the paged decode kernel with the
    selection as one more input and the walk over the 16 held experts of a
    router over 128; nothing copies, transposes or relays any of the three
    pools."""
    step, notes = _keye_vl2_program(v5e_devices, 32, 1)
    assert ("moe_share", "xla", "held=16 of 128 from 0 top_k=8") in notes
    assert any(s == "moe_decode" and p == "pallas" for s, p, _ in notes)
    assert any(s == "paged_decode" and p == "pallas" and "heads=4" in w
               and w.endswith("fold=4 stored prefetch=across-rows")
               for s, p, w in notes), notes
    assert ("sparse_attn", "pallas",
            "masked: the live pages' walk, a token attended where selected, "
            "a decode step") in notes
    assert any(s == "kv_index_pool" and "page=16x128" in w and "topk=2048"
               in w for s, _, w in notes)
    # ISSUE 51: a single query a row keeps the gathered selection, by the
    # clock (``index_select.declined``)
    assert ("index_select", "xla", "rows=32 width=1: one query a row: the "
            "gathered form is ahead by the clock") in notes
    text = step.as_text()
    assert not re.findall(r"%index_select[.\d]* = ", text)
    assert re.findall(r"%paged_decode_attention[.\d]* = ", text)
    movers = _pool_movers(text, KEYE_VL2_POOLS)
    assert not movers, movers
    assert step.memory_analysis().temp_size_in_bytes < 400e6


@pytest.mark.parametrize("rows, temps_under", [(1, 0.2e9), (32, 2.0e9)],
                         ids=["chunk", "pack"])
def test_keye_vl2_chunks_select_and_attend_on_the_prefill_kernel(
        v5e_devices, rows, temps_under):
    """ISSUE 50: the one-row chunk (``paged.w256``) and the pack
    (``paged_pack.w256``) at the cell's serving shape hold
    ``paged_prefill_attention`` with the selection as one more input;
    since ISSUE 51 the selection is ``index_select``'s (the rows are its
    grid: no float32 score leaves VMEM and no row groups); the widest
    program fits beside 12.75+ GB of weights and pools; no pool is
    copied."""
    program, notes = _keye_vl2_program(v5e_devices, rows, 256)
    assert ("index_select", "pallas",
            f"rows={rows} width=256 pages=16 heads=16x64 fold=2 topk=2048 "
            "tile=256x16") in notes
    assert ("sparse_attn", "pallas",
            f"masked: rows={rows} width=256, a query attends where selected"
            ) in notes
    assert any(s == "paged_prefill" and p == "pallas" for s, p, _ in notes)
    text = program.as_text()
    assert re.findall(r"%paged_prefill_attention[.\d]* = ", text)
    assert re.findall(r"%index_select[.\d]* = ", text)
    movers = _pool_movers(text, KEYE_VL2_POOLS)
    assert not movers, movers
    memory = program.memory_analysis()
    assert memory.temp_size_in_bytes < temps_under
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


# microsoft/Phi-4-mini-flash-reasoning config.json (model-configs catalog),
# whole: nothing is cut
PHI4_FLASH = dict(
    model_type="phi4flash", embd_pdrop=0, hidden_act="silu",
    hidden_size=2560, intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, num_attention_heads=40,
    num_hidden_layers=32, num_key_value_heads=20, resid_pdrop=0,
    sliding_window=512, tie_word_embeddings=True, mlp_bias=False,
    lm_head_bias=False, vocab_size=200064)
PHI4_FLASH_SERVE = dict(batch_size=32, seq_len=16384, pa_block_size=32,
                        pa_num_blocks=16384,
                        context_encoding_buckets=[64, 256])


def _phi4_flash_program(v5e_devices, rows, width, layers=32):
    shapes = _serving_shapes(PHI4_FLASH, layers, 1, v5e_devices[:1],
                             PHI4_FLASH_SERVE, prefix=False)
    spec, cache, sds, mb = shapes[0], shapes[4], shapes[5], shapes[6]
    assert spec.diff_attn and spec.window_pool and mb == 512
    n_win = spec.count_kind("window")
    # ten pairs of heads share ONE slot of 1,280 lanes a token
    assert cache["k"].shape == (1, 16385, 32, 1, 1280)
    assert cache["k_w"].shape == (n_win, 32 * 25, 32, 1, 1280)
    assert cache["ssm"].shape == (spec.num_ssm_layers, 32, 16, 5120)
    assert cache["conv_x"].shape == (spec.num_ssm_layers, 32, 3, 5120)
    kw = {} if rows == 32 else {"state_slots": sds((rows,), jnp.int32)}
    return (*_compiled_paged_step(shapes, rows, width, **kw), spec)


def _phi4_flash_movers(text, spec):
    """Instructions that copy, transpose or relay an array with the element
    count of either pool or of one ring layer (bf16), or that transpose or
    relay the Mamba-1 state (float32); and the copies of the state."""
    n_win, n_ssm = spec.count_kind("window"), spec.num_ssm_layers
    pools = {16385 * 32 * 1280, n_win * 800 * 32 * 1280, 800 * 32 * 1280}
    state = n_ssm * 32 * 16 * 5120
    moved = [(name, dt, shape, op) for name, dt, shape, op in re.findall(
        r"%(\S+) = (bf16|f32)\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if op in ("copy", "copy-start", "transpose", "reshape")
        and ((dt == "bf16" and math.prod(map(int, shape.split(",")))
              in pools)
             or (dt == "f32" and math.prod(map(int, shape.split(",")))
                 == state))]
    return ([m for m in moved if m[1] == "bf16" or m[3] != "copy-start"],
            [m for m in moved if m[1] == "f32" and m[3] == "copy-start"])


def test_phi4_flash_decodes_over_both_pools_and_the_state_in_place(
        v5e_devices):
    """ISSUE 54: the decode step at Phi-4-mini-flash-reasoning's widths and
    the cell's serving shape (32 layers whole; ONE layer's pool of 16,384
    blocks, eight rings of 25 pages a slot, nine Mamba-1 states) holds the
    paged decode kernel SIXTEEN times - the full layer and the seven cross
    layers over the allocator's table, the eight window layers over their
    ring's logical table - as plain grouped-query attention over pairs
    placed in halves of a kv row; nothing copies, transposes or relays a
    pool, and the state is stepped where it lies."""
    step, notes, spec = _phi4_flash_program(v5e_devices, 32, 1)
    plan = ("pages=12 heads=10 form=mxu-blockdiag fold=10 stored "
            "prefetch=across-rows")
    diff = " form+=diff pairs placed in halves of a kv row"
    assert {w for s, p, w in notes if s == "paged_decode"
            and p == "pallas"} == {
        plan + " window=0" + diff,
        plan + " window=0" + diff + " cross: no write, another layer's pool",
        plan + " window=512 ring=25" + diff}
    assert ("kv_shared_pool", "xla",
            "layers=1 readers=8 bytes_a_token=5120") in notes
    assert ("kv_pool", "xla", "page=1x1280 heads=10x128") in notes
    assert any(s == "kv_window_pool" and "mamba=9 window=8 full=1 cross=7 "
               "gmu=7 window_tokens=512 ring_pages=25" in w
               and "window_pool_bytes=1048576000" in w for s, _, w in notes)
    assert any(s == "recurrent_state" and p == "xla"
               and w.startswith("kind=mamba1 slot_bytes=3225600 ")
               for s, p, w in notes)
    text = step.as_text()
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 16
    moved, state_copies = _phi4_flash_movers(text, spec)
    assert not moved, moved
    assert len(state_copies) <= 1, state_copies
    memory = step.memory_analysis()
    assert 11.5e9 < memory.argument_size_in_bytes < 11.6e9
    assert memory.temp_size_in_bytes < 0.2e9


def _apart_from_the_conditional(text):
    """Of a compiled chunk program whose second decoder runs under ONE
    conditional: the instructions OUTSIDE the conditional's computations
    (the two branches and whatever they call) that were traced inside a
    branch, which XLA would have hoisted; the parameters of the ``cross`` /
    ``gmu`` stacks with what reads them outside (an operand of the
    conditional reads nothing); and the bytes that async copies outside
    prefetch of them (XLA's memory-space assignment may stage an operand
    of a conditional in VMEM in front of it)."""
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)^\}", text,
        re.M | re.S)}
    conds = re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}",
                       text)
    assert len(conds) == 1, len(conds)
    inside, todo = set(), re.findall(r"%([\w.\-]+)", conds[0])
    while todo:
        name = todo.pop()
        if name in inside or name not in comps:
            continue
        inside.add(name)
        todo += re.findall(
            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", comps[name])
    hoisted = [line.strip()[:120] for name, body in comps.items()
               if name not in inside for line in body.splitlines()
               if "/cond/branch_" in line]
    entry = next(body for name, body in comps.items()
                 if name.startswith("main"))
    params = re.findall(
        r"%(params__(?:cross|gmu)_layers\w*\.\d+) = (\w+)\[([\d,]*)\]", entry)
    readers, prefetched = {}, 0
    for name, dtype, shape in params:
        for line in entry.splitlines():
            if f"%{name}" in line.split(" = ", 1)[-1]:
                op = re.search(r"\} ([\w-]+)\(|\) ([\w-]+)\(", line)
                op = next(g for g in op.groups() if g)
                readers.setdefault(op, set()).add(name)
                if op in ("copy-start", "slice-start"):
                    got = re.search(r"= \((?:\()?(\w+)\[([\d,]*)\]", line)
                    what = got.group(2) if op == "copy-start" else re.search(
                        r"\), (\w+)\[([\d,]*)\]", line).group(2)
                    prefetched += math.prod(map(int, what.split(","))) * (
                        4 if dtype == "f32" else 2)
    return hoisted, readers, prefetched, "\n".join(
        comps[name] for name in inside), entry


def test_phi4_flash_chunk_stops_before_the_second_decoder(v5e_devices):
    """The one-row chunk (``paged.w256``), eight layers of it (every kind;
    ISSUE 54, ISSUE 60): ``paged_prefill_attention`` on the full layer and
    the two window layers, one tile of all 40 placed heads over the ONE kv
    row a token; no pool mover, and the scan over time holds no (256, 5120,
    16) float32 tensor. The walk stops there: the cross layer (ONE query
    over the shared pool, on the decode kernel), the Gated Memory Unit,
    their two MLPs, the head's reduction over 200,064 words and the draw
    sit in ONE conditional - nothing traced in a branch is hoisted out of
    it, no instruction outside it carries their scopes, their stacks are
    read outside it by nothing but XLA's own prefetch of a conditional's
    small operands (at this depth the four matrices of the one cross and
    the one gmu layer, 78.6 MB; never an MLP stack or the head), and the
    other branch is a constant."""
    chunk, notes, spec = _phi4_flash_program(v5e_devices, 1, 256, layers=8)
    plan = ("rows=1 width=256 pages=16 heads=40 fold=10 tile=40x256")
    diff = " form+=diff pairs placed in halves of a kv row"
    assert {w for s, p, w in notes if s == "paged_prefill"
            and p == "pallas"} == {
        plan + " window=0" + diff,
        plan + " window=512 ring=25" + diff}
    assert {w for s, p, w in notes if s == "paged_decode"} == {
        "pages=12 heads=10 form=mxu-blockdiag fold=10 stored "
        "prefetch=across-rows window=0" + diff
        + " cross: no write, another layer's pool"}
    assert ("second_decoder", "xla",
            "apart: layers 6-7, the head and the draw on one token a row of "
            "256, where a row samples") in notes
    assert model_base.second_decoder_start(spec) == 6
    text = chunk.as_text()
    assert len(re.findall(r"%paged_prefill_attention[.\d]* = ", text)) == 3
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 1
    moved, _ = _phi4_flash_movers(text, spec)
    assert not moved, moved
    whole_scan = [shape for shape in re.findall(r"= f32\[([\d,]+)\]", text)
                  if {"5120", "16"} <= set(shape.split(","))
                  and math.prod(map(int, shape.split(","))) >= 256 * 5120 * 16]
    assert not whole_scan, whole_scan[:5]
    hoisted, readers, prefetched, inside, entry = \
        _apart_from_the_conditional(text)
    assert not hoisted, hoisted[:5]
    # the second decoder's scopes, its MLPs' and the head's: inside only
    for scope in ("/cross_attn/", "/gmu/", "/lm_head/",
                  "branch_1_fun/mlp/"):
        assert scope in inside and scope not in entry, scope
    assert "/mlp/" in entry and "/attn/" in entry and "/mixer/" in entry
    assert "paged_decode_attention" in inside
    assert set(readers) <= {"tuple", "copy-start", "slice-start"}, readers
    assert prefetched <= 2 * (2 * 2560 * 2560 + 2 * 2560 * 5120) + 1e5
    # the head's reduction: no array over the vocabulary outside but the
    # (tied) table itself, which the embedding gathers 256 rows of
    over_vocab = [line.strip()[:100] for line in entry.splitlines()
                  if re.search(r"= \(?\w+\[(?:[\d,]+,)?200064\]", line)]
    assert not over_vocab, over_vocab
    assert "200064" in inside
    # the other branch hands out zeros and reads nothing
    zeros = re.search(
        r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}", text).group(1)
    body = re.search(r"^%" + re.escape(zeros) + r" \(([^\n]*)\) -> [^\n]* "
                     r"\{\n(.*?)^\}", text, re.M | re.S)
    assert "empty_tuple" in body.group(1) and "fusion" not in body.group(2)
    memory = chunk.memory_analysis()
    assert 5.70e9 < memory.argument_size_in_bytes < 5.72e9
    assert memory.temp_size_in_bytes < 20e6      # 0.3e9 allowed before


def test_the_widest_phi4_flash_program_fits_beside_weights_and_pools(
        v5e_devices):
    """ISSUE 54: ``paged_pack.w256`` at the configuration's size (32 layers,
    32 rows x 256 tokens over tables of 512 pages) compiles for a v5e, which
    refuses a program over 15.75 GB: 11.54 GB of arguments (weights 7.71,
    shared pool 2.68, rings 1.05, state 0.10) and its temps; the pack
    attends on the prefill kernel and moves no pool. Since ISSUE 60 its
    walk stops at the full layer too: the seven cross layers are ONE query a
    row on the decode kernel inside the one conditional (rows with their own
    ``last_idx``, the condition on any), and its temps are under 1 GB."""
    pack, notes, spec = _phi4_flash_program(v5e_devices, 32, 256)
    assert sum(s == "paged_prefill" and p == "pallas"
               for s, p, _ in notes) == 2
    assert [w for s, p, w in notes if s == "paged_decode" and p == "pallas"
            and w.endswith("cross: no write, another layer's pool")]
    assert ("second_decoder", "xla",
            "apart: layers 18-31, the head and the draw on one token a row "
            "of 256, where a row samples") in notes
    text = pack.as_text()
    moved, _ = _phi4_flash_movers(text, spec)
    assert not moved, moved
    hoisted, readers, _, inside, entry = _apart_from_the_conditional(text)
    assert not hoisted, hoisted[:5]
    assert set(readers) <= {"tuple", "copy-start", "slice-start"}, readers
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", inside)) == 7
    assert "paged_decode_attention" not in entry
    memory = pack.memory_analysis()
    assert 11.5e9 < memory.argument_size_in_bytes < 11.6e9
    assert memory.temp_size_in_bytes < 1.0e9         # 1.5e9 allowed before
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


def _command_a_plus_program(v5e_devices, rows, width):
    """A paged step of ``benchmark/configs/command-a-plus-05-2026.json`` (the
    file itself: its model keys and its serving shape) compiled for a v5e,
    with the engagement records of its trace and its cache's shapes."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import build
    cfg = build.load_json("configs", "command-a-plus-05-2026.json")
    hf = build.hf_config(cfg)
    serve = {k: cfg["serve"][k] for k in (
        "batch_size", "seq_len", "pa_block_size", "pa_num_blocks",
        "context_encoding_buckets")}
    shapes = _serving_shapes(hf, 4, 1, v5e_devices[:1], serve, prefix=False)
    spec, _, _, _, cache, sds, mb = shapes
    assert spec.window_pool and spec.block_style == "parallel_shared"
    assert mb == 384
    # the page a head a slot: 8 kv heads of 128 lanes are a whole row tile
    # and do NOT fold (folded, the widest chunk's prefill plan has no fit)
    assert cache["k"].shape == (1, 12289, 32, 8, 128)
    assert cache["k_w"].shape == (3, 32 * 137, 32, 8, 128)
    kw = {} if rows == 32 else {"state_slots": sds((rows,), jnp.int32)}
    program, notes = _compiled_paged_step(shapes, rows, width, **kw)
    text = program.as_text()
    pools = {",".join(map(str, a.shape)) for a in cache.values()}
    pools |= {"1,12289,32,1024", "3,4384,32,1024", "12289,32,8,128",
              "13152,32,8,128", "1,393248,8,128", "3,140288,8,128"}
    moved = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in pools
        and op not in ("parameter", "get-tuple-element", "bitcast",
                       "fusion", "scatter", "custom-call", "while",
                       "dynamic-update-slice")]
    return program, notes, text, moved


def test_command_a_plus_decodes_on_three_kernels_in_place(v5e_devices):
    """ISSUE 56: the decode step of the Command A+ cell (a parallel block
    on the walk by layer kind: 128 query heads over 8 kv heads of 128, 16
    held experts of 4096 x 4096, four averaged shared experts as one MLP of
    16384) holds the paged decode kernel four times - one call over the
    allocator's table, three over the ring's logical table with the window -
    scored kv row by kv row, eight pages a compute block (ISSUE 57: 8 kv
    rows x 16 query heads, ``form=mxu-kv-rows``; ONE page of the
    block-diagonal form before), and the walk over the touched experts in
    column pieces; nothing copies, transposes, relays or converts either
    pool or the expert stacks."""
    step, notes, text, moved = _command_a_plus_program(v5e_devices, 32, 1)
    assert ("moe_decode", "pallas", "pieces=8 of 512") in notes
    assert ("moe_share", "xla", "held=16 of 128 from 0 top_k=8 "
            "shared=4 x 4096 mean") in notes
    assert {(s, p, w) for s, p, w in notes if s == "paged_decode"} == {
        ("paged_decode", "pallas",
         "pages=8 heads=8 form=mxu-kv-rows fold=1 prefetch=across-rows "
         "window=0"),
        ("paged_decode", "pallas",
         "pages=8 heads=8 form=mxu-kv-rows fold=1 prefetch=across-rows "
         "window=4096 ring=137")}
    assert ("kv_pool", "xla", "page=8x128 heads=8x128") in notes
    assert any(s == "kv_window_pool" and "global=1 window=3" in w
               and "window_tokens=4096 ring_pages=137" in w
               for s, _, w in notes)
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 4
    assert "%moe_decode_experts" in text and not moved, moved
    # ... and no pool-sized array of another type stands beside a pool (a
    # float32 copy in front of the kernel would be 800 MB a layer)
    sizes = {layers * pages * 32 * 8 * 128
             for layers, pages in ((1, 12289), (3, 4384), (1, 4384))}
    wide = [(name, shape) for name, shape in re.findall(
        r"%(\S+) = (?:f32|u32|s32|f16)\[([\d,]+)\]", text)
        if math.prod(map(int, shape.split(","))) in sizes]
    assert not wide, wide
    stacks = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in ("4,16,4096,4096", "16,4096,4096", "4,4096,16384",
                     "4,16384,4096")
        and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not stacks, stacks
    assert step.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("rows, temps_under", [(1, 50e6), (32, 1.7e9)],
                         ids=["chunk", "pack"])
def test_command_a_plus_chunks_attend_on_the_prefill_kernel(
        v5e_devices, rows, temps_under):
    """ISSUE 56: the one-row chunk (``paged.w256``) and the pack
    (``paged_pack.w256``) of the Command A+ cell attend on
    ``paged_prefill_attention`` with a tile of 2 kv rows = 32 heads x 256
    queries (``prefill_plan(128, 128, pool, 256)``: fit 2), the full layer
    over the allocator's table and the window layers over their rings; the
    chunk's experts go through the walk by expert, the pack's through the
    grouped matmuls eight rows at a time; no pool is moved; the widest
    program fits a v5e beside 12.8 GB of weights and pools."""
    program, notes, text, moved = _command_a_plus_program(v5e_devices, rows,
                                                          256)
    plan = f"rows={rows} width=256 pages=16 heads=128 fold=1 tile=32x256"
    assert {(p, w) for s, p, w in notes if s == "paged_prefill"} == {
        ("pallas", plan + " window=0"),
        ("pallas", plan + " window=4096 ring=137")}
    assert MOSAIC in text and not moved, moved
    if rows == 1:
        assert ("moe_decode", "pallas", "pieces=8 of 512 rows=256 by expert "
                "in tiles of 128") in notes
        assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    else:
        assert ("moe_ragged", "stacked", "") in notes
        assert ("moe_ragged", "row-groups", "8 of 32 rows") in notes
    memory = program.memory_analysis()
    assert memory.temp_size_in_bytes < temps_under
    assert 12.79e9 < memory.argument_size_in_bytes < 12.82e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


def _lfm2_moe_program(v5e_devices, rows, width=None):
    """A paged step of ``benchmark/configs/lfm2-8b-a1b.json`` (the file
    itself: its model keys and its serving shape; ``width`` None: its widest
    chunk bucket) compiled for a v5e, with the engagement records of its
    trace."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import build
    cfg = build.load_json("configs", "lfm2-8b-a1b.json")
    hf = build.hf_config(cfg)
    serve = {k: cfg["serve"][k] for k in (
        "batch_size", "seq_len", "pa_block_size", "pa_num_blocks",
        "context_encoding_buckets")}
    shapes = _serving_shapes(hf, 14, 1, v5e_devices[:1], serve, prefix=False)
    spec, _, _, _, cache, sds, mb = shapes
    assert (spec.first_dense, spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers) == (2, 12, 3, 11)
    assert mb == 128
    # the state a row carries is eleven conv tails of two tokens, time-major
    assert cache["conv_x"].shape == (11, 64, 2, 2048) and "ssm" not in cache
    kw = {} if rows == 64 else {"state_slots": sds((rows,), jnp.int32)}
    program, notes = _compiled_paged_step(
        shapes, rows, width or max(serve["context_encoding_buckets"]), **kw)
    return program, notes, program.as_text()


def test_lfm2_moe_decodes_64_rows_on_both_kernels_in_place(v5e_devices):
    """ISSUE 61: ``paged.w1`` of the LFM2-8B-A1B cell at its 64 rows: three
    calls of the paged decode kernel (32 query / 8 kv heads of 64), twelve
    expert layers on the walk over the touched experts and none for the two
    leading dense layers, eleven conv tails slid in place; nothing copies
    the pool or an expert stack, and the step's temps are small beside 11 GB
    of weights and pool."""
    step, notes, text = _lfm2_moe_program(v5e_devices, 64, 1)
    assert ("recurrent_state", "xla",
            "kind=shortconv slot_bytes=90112 chunk=128: no matrix state") \
        in notes
    assert any(s == "paged_decode" and p == "pallas" for s, p, _ in notes)
    assert any(s == "moe_decode" and p == "pallas" for s, p, _ in notes)
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 3
    assert len(re.findall(r"%moe_decode_experts[.\d]* = ", text)) == 12
    stacks = [(name, shape, op) for name, shape, op in re.findall(
        r"%(\S+) = bf16\[([\d,]+)\]\S* (\w[\w-]*)\(", text)
        if shape in ("12,32,2048,1792", "12,32,1792,2048", "32,2048,1792",
                     "32,1792,2048")
        and op not in ("parameter", "get-tuple-element", "bitcast",
                       "dynamic-update-slice", "fusion")]
    assert not stacks, stacks
    memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < 150e6
    assert 10.9e9 < memory.argument_size_in_bytes < 11.0e9


@pytest.mark.parametrize("rows, temps_under", [(1, 50e6), (64, 1.1e9)],
                         ids=["chunk", "pack"])
def test_the_widest_lfm2_moe_programs_fit_beside_weights_and_pool(
        v5e_devices, rows, temps_under):
    """ISSUE 61: the widest chunk (one row) and the widest pack (64 rows) of
    the LFM2-8B-A1B cell attend on the prefill kernel, run their experts on
    the walk by expert (the chunk) or the grouped matmuls (the pack), and
    fit a v5e: weights 9.34 GB + pool 1.61 GB + state + temps under 16 GB
    less what the runtime keeps."""
    program, notes, text = _lfm2_moe_program(v5e_devices, rows)
    assert any(s == "paged_prefill" and p == "pallas" for s, p, _ in notes)
    assert MOSAIC in text
    if rows == 1:
        assert any(s == "moe_decode" and p == "pallas" and "by expert" in w
                   for s, p, w in notes)
        assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    else:
        assert ("moe_ragged", "stacked", "") in notes
    memory = program.memory_analysis()
    assert memory.temp_size_in_bytes < temps_under
    assert 10.9e9 < memory.argument_size_in_bytes < 11.0e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


def _nemotron3_nano_program(v5e_devices, rows, width=None):
    """A paged step of ``benchmark/configs/nemotron-3-nano-30b-a3b.json``
    (the file itself: all 52 layers and its serving shape; ``width`` None: its
    widest chunk bucket) compiled for a v5e, with the engagement records of
    its trace."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import build
    cfg = build.load_json("configs", "nemotron-3-nano-30b-a3b.json")
    hf = build.hf_config(cfg)
    serve = {k: cfg["serve"][k] for k in (
        "batch_size", "seq_len", "pa_block_size", "pa_num_blocks",
        "context_encoding_buckets")}
    shapes = _serving_shapes(hf, 52, 1, v5e_devices[:1], serve, prefix=False)
    spec, _, _, _, cache, sds, mb = shapes
    assert (spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers) == (23, 6, 23)
    assert mb == 128
    # 2 kv heads of 128 lanes in ONE slot of 256; a state of 64 x (64, 128)
    assert cache["k"].shape == (6, 4097, 32, 1, 256)
    assert cache["ssm"].shape == (23, 32, 64, 64, 128)
    kw = {} if rows == 32 else {"state_slots": sds((rows,), jnp.int32)}
    program, notes = _compiled_paged_step(
        shapes, rows, width or max(serve["context_encoding_buckets"]), **kw)
    return program, notes, program.as_text()


#: element counts of what no program of the cell may copy, transpose or
#: reshape: the KV pool (one of K / V) and an expert leaf's stack, a layer of
#: it, the state stack and a layer of it (PERF.md section 3's trap: match on
#: the element count, whatever the shape is called)
_NEMOTRON_BIG = {6 * 4097 * 32 * 256: "the pool",
                 23 * 16 * 2688 * 1920: "an expert stack",
                 16 * 2688 * 1920: "a layer's experts",
                 23 * 32 * 64 * 64 * 128: "the state stack",
                 32 * 64 * 64 * 128: "a layer's states"}


def _nemotron_movers(text, but=()):
    out = []
    for name, shape, op in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* (copy|transpose|reshape)\(", text):
        what = _NEMOTRON_BIG.get(math.prod(map(int, shape.split(","))))
        if what and what not in but:
            out.append((name, shape, op, what))
    return out


def test_nemotron3_nano_decodes_32_rows_on_three_kernels_in_place(
        v5e_devices):
    """ISSUE 64: ``paged.w1`` of the Nemotron 3 Nano cell at its 32 rows and
    all 52 single-block layers: six calls of the paged decode kernel (32
    query / 2 kv heads of 128 in one 256-lane slot), 23 expert layers on the
    PLAIN walk over the touched experts (two matrices a unit, three column
    pieces of 640 of the stored 1920), 23 states stepped in place by the
    state kernel at 8 groups; nothing copies the pool, an expert stack or the
    state, and the step's temps are small beside 13 GB of arguments."""
    step, notes, text = _nemotron3_nano_program(v5e_devices, 32, 1)
    assert ("layer_blocks", "xla", "mamba=23 attention=6 moe=23") in notes
    assert ("moe_decode", "pallas",
            "plain pieces=3 of 640 (1856 of 1920 stored)") in notes
    assert ("moe_share", "xla", "held=16 of 128 from 0 top_k=6") in notes
    state = [w for s, p, w in notes if s == "recurrent_state"
             and p == "pallas"]
    assert state == ["kind=mamba2 slot_bytes=49082368 chunk=64 heads=32 "
                     "tile=64x128 groups=8"]
    assert any(s == "paged_decode" and p == "pallas" and "heads=2" in w
               for s, p, w in notes)
    assert len(re.findall(r"%paged_decode_attention[.\d]* = ", text)) == 6
    assert len(re.findall(r"%moe_decode_experts[.\d]* = ", text)) == 23
    assert len(re.findall(r"%mamba_state_step[.\d]* = ", text)) == 23
    assert not _nemotron_movers(text), _nemotron_movers(text)
    memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < 200e6
    assert 13.1e9 < memory.argument_size_in_bytes < 13.25e9


@pytest.mark.parametrize("rows, temps_under", [(1, 60e6), (32, 1.6e9)],
                         ids=["chunk", "pack"])
def test_the_widest_nemotron3_nano_programs_fit_beside_weights_and_pool(
        v5e_devices, rows, temps_under):
    """ISSUE 64: the widest chunk (one row of 256) and the widest pack (32
    rows) of the cell attend on the prefill kernel, run their experts on the
    plain walk by expert (the chunk) or the grouped matmuls over the stored
    stack (the pack), scan their mixers in chunks of 64, and fit a v5e:
    weights 10.79 GB + state 1.57 GB + pool 0.81 GB + temps under 16 GB less
    what the runtime keeps."""
    program, notes, text = _nemotron3_nano_program(v5e_devices, rows)
    assert any(s == "paged_prefill" and p == "pallas" for s, p, _ in notes)
    assert ("layer_blocks", "xla", "mamba=23 attention=6 moe=23") in notes
    if rows == 1:
        assert ("moe_decode", "pallas",
                "plain pieces=3 of 640 rows=256 by expert in tiles of 128 "
                "(1856 of 1920 stored)") in notes
        assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    else:
        assert ("moe_ragged", "stacked", "") in notes
    # (a full-batch pack hands the chunked scan a layer's 32 states, 67 MB,
    # and puts the last chunk's back: the scan's carry, as granite's pack has
    # it, 0.1 ms a layer of a program of hundreds; the step and the one-row
    # chunk move no state at all)
    movers = _nemotron_movers(text, but=("a layer's states",) * (rows > 1))
    assert not movers, movers
    memory = program.memory_analysis()
    assert memory.temp_size_in_bytes < temps_under
    assert 13.1e9 < memory.argument_size_in_bytes < 13.25e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


def _ling3_flash_program(v5e_devices, rows, width=None):
    """A paged step of ``benchmark/configs/ling-3.0-flash.json`` (the file
    itself: its 18 layers and its serving shape; ``width`` None: its widest
    chunk bucket) compiled for a v5e, with the engagement records of its
    trace."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import build
    cfg = build.load_json("configs", "ling-3.0-flash.json")
    hf = build.hf_config(cfg)
    serve = {k: cfg["serve"][k] for k in (
        "batch_size", "seq_len", "pa_block_size", "pa_num_blocks",
        "context_encoding_buckets")}
    shapes = _serving_shapes(hf, 18, 1, v5e_devices[:1], serve, prefix=False)
    spec, _, _, _, cache, sds, mb = shapes
    assert (spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers) == (16, 3, 15)
    assert mb == 256
    # three layers of latent rows (no V) BESIDE fifteen layers of slots
    assert cache["k"].shape == (3, 16385, 32, 1, 640)
    assert cache["v"].shape == (3, 16385, 32, 1, 0)
    assert cache["ssm"].shape == (15, 64, 32, 128, 128)
    assert cache["conv_x"].shape == (15, 64, 3, 12288)
    kw = {} if rows == 64 else {"state_slots": sds((rows,), jnp.int32)}
    program, notes = _compiled_paged_step(
        shapes, rows, width or max(serve["context_encoding_buckets"]), **kw)
    return program, notes, program.as_text()


#: element counts of what no program of the cell may copy, transpose or
#: reshape (PERF.md section 3's trap: match on the element count)
_LING_BIG = {3 * 16385 * 32 * 640: "the latent pool",
             16 * 32 * 2560 * 768: "an expert stack",
             32 * 2560 * 768: "a layer's experts",
             15 * 64 * 32 * 128 * 128: "the state stack",
             64 * 32 * 128 * 128: "a layer's states",
             15 * 64 * 3 * 12288: "the conv tails' stack"}


def _ling_movers(text, but=()):
    out = []
    for name, shape, op in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* (copy|transpose|reshape)\(", text):
        what = _LING_BIG.get(math.prod(map(int, shape.split(","))))
        if what and what not in but:
            out.append((name, shape, op, what))
    return out


def test_ling3_flash_decodes_64_rows_on_three_kernels_in_place(v5e_devices):
    """ISSUE 67: ``paged.w1`` of the Ling-3.0-flash cell at its 64 rows and
    18 layers: fifteen states stepped in place by the state kernel with
    their decay BY CHANNEL, three calls of the latent decode kernel at 32
    heads over a latent pool that sits beside the slots, sixteen expert
    layers on the walk over the touched experts; nothing copies the pool, an
    expert stack, the state or the tails, and the step's temps are small
    beside 12.6 GB of arguments."""
    step, notes, text = _ling3_flash_program(v5e_devices, 64, 1)
    assert ("recurrent_state", "pallas",
            "kind=kda slot_bytes=32563200 chunk=16 heads=32 tile=128x128 "
            "decay=channel") in notes
    assert ("latent_cache", "xla", "lanes=640 of 576 values "
            "bytes_a_token=3840 sub_blocks=3") in notes
    assert any(s == "mla_decode" and p == "pallas" and "heads=32" in w
               and "form=absorbed" in w for s, p, w in notes)
    assert ("moe_decode", "pallas", "pieces=1 of 768") in notes
    assert ("moe_share", "xla",
            "held=32 of 512 from 0 top_k=8 groups=8 top=4") in notes
    assert len(re.findall(r"%kda_state_step[.\d]* = ", text)) == 15
    assert len(re.findall(r"%mla_decode_attention[.\d]* = ", text)) == 3
    assert len(re.findall(r"%moe_decode_experts[.\d]* = ", text)) == 16
    assert "%delta_state_step" not in text
    assert not _ling_movers(text), _ling_movers(text)
    memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < 100e6
    assert 12.5e9 < memory.argument_size_in_bytes < 12.65e9


@pytest.mark.parametrize("rows, temps_under", [(1, 0.3e9), (64, 3.3e9)],
                         ids=["chunk", "pack"])
def test_the_widest_ling3_flash_programs_fit_beside_weights_pool_and_slots(
        v5e_devices, rows, temps_under):
    """ISSUE 67: the widest chunk (one row of 256) and the widest pack (64
    rows) of the cell attend on the latent prefill kernel at 32 heads, run
    their experts on the walk by expert (the chunk) or the grouped matmuls
    (the pack), run the channel-gated delta rule in chunks of 16 (the rows 8
    at a time in the pack), and fit a v5e: weights 8.47 GB + slots 2.08 GB +
    pool 2.01 GB + temps under 16 GB less what the runtime keeps."""
    program, notes, text = _ling3_flash_program(v5e_devices, rows)
    assert any(s == "mla_prefill" and p == "pallas" and "heads=32" in w
               for s, p, w in notes)
    assert any(s == "recurrent_state" and p == "xla" and
               "256 tokens a row: the chunked form, decay by channel" in w
               for s, p, w in notes)
    if rows == 1:
        assert ("moe_decode", "pallas", "pieces=1 of 768 rows=256 by expert "
                "in tiles of 128") in notes
        assert "ragged-dot" not in text and "%moe_chunk_experts" in text
    else:
        assert ("moe_ragged", "stacked", "") in notes
    # (a pack hands the chunked form a layer's 64 states, 134 MB, and puts
    # the last chunk's back, as the scalar rule's pack does; in the ONE-ROW
    # chunk the compiler keeps the 70 MB stack of conv tails in VMEM between
    # the layers' writes and moves it in and out: PERF.md section 7)
    movers = _ling_movers(
        text, but=("a layer's states",) if rows > 1
        else ("the conv tails' stack",))
    assert not movers, movers
    memory = program.memory_analysis()
    assert memory.temp_size_in_bytes < temps_under
    assert 12.5e9 < memory.argument_size_in_bytes < 12.65e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30 - 258e6


def test_without_the_request_nothing_is_interpreted(v5e_devices,
                                                    monkeypatch):
    """The other side of the rule: with interpret mode requested the same
    lowering holds NO Mosaic call — so the request must never reach a
    serving process on a chip."""
    monkeypatch.setenv(kernel_mode.INTERPRET_ENV, "1")
    texts = _compile_serving_graphs(LLAMA_3_2_1B, 1, 1,
                                    v5e_devices[:1], SERVE,
                                    only=("paged_t1",))
    assert MOSAIC not in texts["paged_t1"]
