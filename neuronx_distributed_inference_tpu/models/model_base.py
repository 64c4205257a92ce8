"""Functional decoder model base — the traced-graph layer of the framework
(reference: models/model_base.py ``NeuronBaseModel``:70-1596).

TPU-first redesign:
  * The reference builds an nn.Module and traces it per (submodel, bucket).
    Here the model IS a pure function ``(params, cache, inputs) -> outputs``;
    ``jax.jit`` + AOT lowering replaces ModelBuilder.trace.
  * The per-layer Python loop (reference: get_model_output :1216-1469) becomes
    ``lax.scan`` over stacked layer weights — one compiled layer body,
    O(1) compile time in depth, XLA-pipelined.
  * KV-cache persistence via donated buffers (reference used I/O aliasing,
    model_wrapper.py:1578-1627).
  * On-device sampling (reference: :1151-1185) runs at the end of the graph.

Two step graphs per model, mirroring the reference submodel tags
(model_wrapper.py:37-42): ``context_encoding`` (prefill) and
``token_generation`` (decode). Speculation graphs live in
models/speculation.py; both reuse the layer stack here.

Everything the jitted entry points here reach is a TRACED REGION: the
``recompile-hazard`` pass of ``scripts/nxdi_lint.py`` derives it from
the ``jax.jit``/``partial`` sites and flags host concretization
(``.item()``/``float()``/host numpy on traced values), unordered
set/dict iteration and mutated-closure captures — each one a silent
bucket-ladder jit-cache miss (or a tracing crash) in production.
"""

from __future__ import annotations

import math

from dataclasses import dataclass, field, replace
from functools import lru_cache, partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import InferenceConfig, TpuConfig
from ..ops import attention as attn_ops
from ..ops import decode_attention
from ..ops import flash_attention
from ..ops import kernel_mode
from ..ops import paged_prefill
from ..ops import sampling as sampling_ops
from ..ops.normalization import layer_norm, rms_norm
from ..ops.rope import RopeConfig, apply_rope, rope_cos_sin
from ..parallel.layers import (GQASharding, ParamSpec, column_parallel,
                               expert_column_parallel, expert_row_parallel,
                               replicated_param, resolve_gqa_sharding,
                               row_parallel, row_parallel_output,
                               vocab_parallel_embedding)
from ..parallel.mesh import (AXIS_CP, AXIS_DP, AXIS_EP, AXIS_MP, AXIS_TP,
                             shard_constraint as _shard)
from ..modules import kv_cache as kv
from ..modules import low_rank as low_rank_mod
from ..modules import ssm as ssm_mod
from ..modules import moe as moe_mod
from ..modules.moe import MoESpec, moe_block
from ..modules.lora import (LoraSpec, apply_lora, lora_spec_from_config)
from ..modules.quantization import (QuantSpec, qlinear,
                                    quant_spec_from_config)

import logging
logger = logging.getLogger("nxdi_tpu")

ACT_FNS = {
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=False),
    "gelu_new": partial(jax.nn.gelu, approximate=True),
    "gelu_pytorch_tanh": partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    # squared ReLU (nemotron / arcee plain MLPs)
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


@dataclass(frozen=True)
class MLASpec:
    """Multi-head Latent Attention geometry (reference: models/deepseek/
    modeling_deepseek.py MLA attention — SURVEY §2.7).

    KV is compressed to ``kv_lora_rank`` + a shared rope head; Q optionally
    through ``q_lora_rank``. K heads are [nope | rope], V heads are
    ``v_head_dim`` wide. What a token leaves in the paged pool is its LATENT
    row, ``[normed c (kv_lora_rank) | rotated k_rope]``, one a token a layer
    (``modules/block_kv_cache.latent_lanes``); the contiguous cache of the
    unpaged application holds the expanded heads.

    ``q_scale`` / ``kv_scale``: LongCat-Flash's ``mla_scale_q_lora`` /
    ``mla_scale_kv_lora``, ``sqrt(hidden / rank)`` on the whole query and on
    the normed latent; 1.0 = DeepSeek.

    ``head_gate``: ONE sigmoid gate a head on the attention's output, read
    off the block's normed input by a projection of its own (``g_proj``,
    hidden -> heads) and applied before ``o_proj`` (Ling-3.0's
    ``gated_attention_proj_granularity_type`` ``head_wise``; the element-wise
    gate out of a doubled q projection is ``DecoderSpec.attn_out_gate``)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None
    q_scale: float = 1.0
    kv_scale: float = 1.0
    head_gate: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token leaves in the paged pool, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class SparseSpec:
    """A LEARNED SPARSE SELECTION in front of the attention (the lightning
    indexer of DeepSeek-V3.2-Exp's sparse attention; Keye-VL-2.0's
    ``sa_config``). With ``h`` the layer's normed input, per query ``t`` and
    cached token ``s <= t``:

        qI[t, j] = rot(W_Iq h_t)[j]          index_heads x index_dim
        kI[s]    = rot(LayerNorm(W_Ik h_s))  ONE head, CACHED: the third pool
        w[t, j]  = (W_Iw h_t)[j]
        I[t, s]  = sum_j w[t, j] ReLU(qI[t, j] . kI[s])         float32

    and the attention of ``t`` reads the ``topk`` tokens of largest ``I[t,
    s]`` alone (all of them while ``t < topk``; ties to the lower position),
    every head of a token the same set. The index keys live in a paged pool
    of their own on the K / V pools' block table
    (``modules/block_kv_cache.index_page``), ``cache["k_idx"]``; the walk is
    :func:`run_layer_slice`, the indexer :func:`_indexer_block` under the
    profiler scope ``indexer``. ``rope``: the rotary of the index heads (all
    ``index_dim`` lanes, halves convention).

    Both kernels attend the selection MASKED: the walk over the row's LIVE
    pages with the selection as one more mask (PERF.md section 6, PR 50, has
    the other form's numbers, the selected rows gathered by XLA: 2.6 x
    slower a step on this chip). What a selection does not run under:
    SPARSE_UNSUPPORTED."""

    index_heads: int
    index_dim: int
    topk: int
    rope: RopeConfig
    norm_eps: float = 1e-6

    @property
    def proj_width(self) -> int:
        """Columns of the fused index projection ``[qI | kI | w]``."""
        return self.index_heads * self.index_dim + self.index_dim \
            + self.index_heads


@dataclass(frozen=True)
class DecoderSpec:
    """Static architecture description, resolved from an InferenceConfig.

    This is the single source of truth the traced functions close over —
    everything here must be hashable/static for jit.
    """

    num_layers: int
    hidden_size: int
    num_q_heads: int          # original HF head count
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    padded_vocab: int
    rms_eps: float
    rope: RopeConfig
    act: str = "silu"
    gqa: GQASharding = None   # resolved for the mesh tp degree
    qkv_bias: bool = False
    o_bias: bool = False
    mlp_bias: bool = False
    qk_norm: bool = False     # qwen3-style per-head q/k RMSNorm
    # olmo2-style FULL-width q/k RMSNorm (over nq*D / nkv*D, pre head-split)
    qk_norm_full: bool = False
    # per-head sigmoid gate on the attention OUTPUT, before o_proj, out of a
    # doubled q projection (HF Qwen3NextAttention: q_proj yields [query |
    # gate] a head); the fused qkv_proj then holds [q | k | v | gate]
    attn_out_gate: bool = False
    # "pre" (llama default) or "post" (olmo2: norms on the block OUTPUTS via
    # the sandwich weights, no pre-norms)
    norm_position: str = "pre"
    # granite multipliers: residual_multiplier scales each block output
    # before the residual add; logits_divide divides the lm-head logits
    residual_multiplier: float = 1.0
    logits_divide: Optional[float] = None
    tie_word_embeddings: bool = False
    sliding_window: int = 0   # 0 = full attention
    logits_soft_cap: Optional[float] = None
    attn_soft_cap: Optional[float] = None
    attn_scale: Optional[float] = None   # None => head_dim ** -0.5
    embed_scale: Optional[float] = None  # gemma multiplies embeddings
    # --- per-layer attention variation (reference: gemma3 alternating
    # local/global layers; gpt_oss alternating sliding/full — SURVEY §2.7).
    # layer_pattern[i] True = layer i is LOCAL: sliding_window + local_rope.
    # None = uniform (sliding_window, if set, applies to every layer).
    layer_pattern: Optional[Tuple[bool, ...]] = None
    local_rope: Optional[RopeConfig] = None   # rope for local layers
    # rolling sliding-window KV (reference: kv_cache_manager.py:605-606):
    # the cache holds only ``sliding_window`` slots, written pos %% w with a
    # position-mapping decode mask — cache bytes scale with w, not seq_len
    rolling_window: bool = False
    # MIXED per-layer cache sizes (reference: gpt-oss per-layer KV,
    # modules/kvcache/gpt_oss_kv_cache_manager.py): with an alternating
    # local/global layer_pattern, local layers get ROLLING window-sized
    # cache rows (W slots) while global layers keep full-seq rows. The cache
    # pytree then carries {"k","v"} (global layers) + {"k_l","v_l"}
    # (local layers); decode selects per layer statically (unrolled).
    mixed_kv: bool = False
    # the PAGED counterpart (``modules/block_kv_cache.window_pool_spec``):
    # with a local/global ``layer_pattern`` and a ``sliding_window`` the
    # paged cache holds two pools, the global layers' {"k","v"} (the
    # allocator's blocks) and the window layers' {"k_w","v_w"} (a ring of
    # ``window + widest step + block`` tokens a batch slot); the walk is
    # :func:`run_layers_window`. Set by the family that needs it, never
    # derived; what such a pool refuses: WINDOW_POOL_UNSUPPORTED
    window_pool: bool = False
    # llama4 attention variations (reference: models/llama4/
    # modeling_llama4_text.py — chunked attention + NoPE layers):
    # local layers use CHUNKED attention (block-diagonal causal over
    # attention_chunk_size) instead of a sliding window
    attn_chunk: int = 0
    # global layers are NoPE: no rotary applied (no_rope_layers)
    nope_global: bool = False
    # weightless L2 q/k norm AFTER rope, on rope (local) layers only
    qk_l2_norm: bool = False
    # attention temperature tuning on NoPE layers (floor_scale, attn_scale):
    # q *= log1p(floor((pos+1)/floor_scale)) * attn_scale + 1
    attn_temp: Optional[Tuple[float, float]] = None
    # interleaved dense/MoE stacks (llama4 interleave_moe_layer_step):
    # pattern[i] True = layer i is MoE; params then hold a "layers" dense
    # stack and a "moe_layers" stack, walked in contiguous runs
    moe_pattern: Optional[Tuple[bool, ...]] = None
    # gemma3 sandwich norms: post_attn_norm on attention output and
    # post_ff_norm on MLP output, in addition to the two pre-norms
    sandwich_norm: bool = False
    # RMSNorm weight offset: 1.0 gives the gemma (1+w) convention
    norm_offset: float = 0.0
    # learned per-head softmax sinks (reference: modules/attention/sink.py,
    # gpt-oss); adds a (L, Hq) "sink" param
    attn_sink: bool = False
    # ALiBi positional biases (bloom / mpt): score += slope_h * kv_pos —
    # softmax shift-invariance makes the absolute form equal to the
    # relative slope_h*(kpos-qpos); adds a (L, Hq) "alibi_slopes" param
    # (per-layer rows are identical; stacking keeps the layer scan uniform)
    alibi: bool = False
    # LayerNorm over the token embeddings (bloom
    # word_embeddings_layernorm); adds embed_norm(+_b) params
    embed_norm: bool = False
    dtype: Any = jnp.bfloat16
    kv_dtype: Any = jnp.bfloat16
    # flash-kernel strategy (reference analog: FlashAttentionStrategy,
    # attention_base.py:90-96): True = use the Pallas flash kernel for
    # prefill when ops/flash_attention.supports() holds; XLA path otherwise
    flash_prefill: bool = False
    # fused Pallas decode attention (reference analog: attention_block_tkg
    # TKG kernel, attention_base.py:1186-1382). Tri-state: None = auto
    # (cost-model admission in _layer_body — on for window/sink geometries),
    # True = always when supports() holds, False = never.
    decode_kernel: Optional[bool] = None
    # MoE: when set, the MLP block is a routed mixture of experts
    # (reference: modules/moe_v2.py; intermediate_size then refers to the
    # per-expert intermediate)
    moe: Optional[MoESpec] = None
    # MLA attention (deepseek); head_dim then = mla.qk_head_dim
    mla: Optional[MLASpec] = None
    # a learned sparse selection in front of the attention (an indexer
    # over a third paged pool of index keys): the paged path only
    sparse: Optional[SparseSpec] = None
    # leading dense-MLP layers before the MoE stack (deepseek
    # first_k_dense_replace); only meaningful with moe set
    first_dense: int = 0
    # what a layer holds and where the residual is joined (HF
    # LongcatFlashDecoderLayer): ``sub_blocks`` > 1 makes a layer that many
    # [attention, dense MLP] pairs, each a pre-norm block with its own two
    # norms and its own cache layer, and - with ``moe`` set - ONE routed
    # block on a SHORTCUT: it reads the first pair's post-attention norm and
    # joins the residual only at the END of the layer. ``intermediate_size``
    # is the dense MLPs' width. Params: "layers" stacks the pairs layer-major,
    # "moe_layers" the routed blocks; the walk: :func:`run_layers_shortcut`.
    sub_blocks: int = 1
    # "rms" | "layernorm" (dbrx uses bias-free LayerNorm)
    norm_type: str = "rms"
    # no final pre-lm-head norm (GPT-1: the post-LN blocks already end
    # normed; reference: contrib/models/openai-gpt)
    skip_final_norm: bool = False
    # gemma3 multimodal: image-token spans attend BIDIRECTIONALLY within
    # their own contiguous image block, overriding causality AND the
    # sliding window (reference: contrib/models/gemma3-vision; HF
    # token_type_ids_mask_function or-mask)
    bidir_image_attn: bool = False
    # LayerNorm with learned bias (gpt2/falcon/starcoder2/phi/neox)
    norm_bias: bool = False
    # GLU MLP (act(gate)*up @ down, llama-shaped) vs plain 2-layer MLP
    # (act(x@fc1) @ fc2 — gpt2/falcon/starcoder2/phi/neox); plain reuses
    # the gate_proj/down_proj param slots as fc1/fc2
    mlp_glu: bool = True
    # skip rotary entirely (gpt2 learned positions; cos=1/sin=0)
    no_rope: bool = False
    # learned absolute position embeddings: adds a (max_positions, H)
    # "pos_embed" param gathered at position_ids and added to the token
    # embedding (gpt2 wpe)
    learned_pos: int = 0          # 0 = none, else table size
    # lm_head bias (phi-1/2)
    lm_head_bias: bool = False
    # vocab-parallel embedding: shard the (V, H) table on V over the
    # model-parallel axes (reference: ParallelEmbedding vocab_parallel,
    # models/config.py:142); False = replicated table
    vocab_parallel: bool = True
    # residual block style: "sequential" (llama), "parallel_shared" (one
    # norm feeds both attn and MLP — falcon parallel_attn / phi), or
    # "parallel_dual" (separate norms, both from the block INPUT — gpt-neox
    # use_parallel_residual)
    block_style: str = "sequential"
    # clamp q/k/v projections to ±qkv_clip (dbrx clip_qkv)
    qkv_clip: Optional[float] = None
    # interleaved (GPT-NeoX pair) rope convention (deepseek rope_interleave)
    rope_interleaved: bool = False
    # apply the per-head q/k RMSNorm AFTER rope instead of before
    # (hunyuan-dense query/key_layernorm ordering)
    qk_norm_after_rope: bool = False
    # per-head q/k norm flavor: "rms" (qwen3 et al) or "layernorm" with
    # bias (persimmon q/k_layernorm)
    qk_norm_type: str = "rms"
    # Medusa speculation heads on the target model (reference:
    # medusa_speculation, model_base.py / models/config.py:243-274):
    # head j = ResBlock(H->H) + its own lm head, predicting position +j+2
    medusa_heads: int = 0
    # multi-LoRA serving (reference: modules/lora_serving/): stacked
    # per-adapter A/B weights selected by per-request adapter_ids
    lora: Optional[LoraSpec] = None
    # intermediate-tensor capture points appended to graph outputs
    # (reference: models/model_base.py:1076-1149 tensor capture)
    capture: Optional[Tuple[str, ...]] = None
    # --- scale-out (reference: SURVEY §2.8 parallelism inventory) ---
    # SP: shard prefill activations on seq over the "cp" axis between blocks
    # (reference: sequence_parallel_enabled, model_base.py:1482-1517)
    seq_parallel: bool = False
    # CP prefill: Q stays seq-sharded over "cp", KV replicated on seq so XLA
    # inserts the all-gather — the reference's all-gather-KV CP strategy
    # (attention_base.py:548-563), not ring attention
    cp_prefill: bool = False
    # flash decoding: KV cache seq dim sharded over "cp"; decode scores and
    # softmax are computed distributed over the seq shards (reference:
    # modules/flashdecode/utils.py decode-time S-sharding)
    flash_decoding: bool = False
    # weight-only quantization (reference: models/config.py:216-241); the
    # param tree then carries {"qweight","scale"} leaf-groups for the
    # converted weights (modules/quantization.py)
    quant: Optional[QuantSpec] = None
    # scaled KV quantization: values are stored as x/kv_scale in kv_dtype and
    # rescaled on read (reference: kv_cache_manager.py:636-692 scaled fp8
    # mode; None = direct cast)
    kv_scale: Optional[float] = None
    # quantized decode collectives (parallel/collectives.py, EQuARX-style):
    # wire dtype for the row-parallel o_proj/down_proj reduction during the
    # decode and paged phases ("int8"/"fp8"); None keeps the implicit fp32
    # GSPMD all-reduce and the graphs bit-unchanged. Prefill always stays on
    # the fp32 collective — its reduction is amortized over the whole prompt.
    collective_dtype: Optional[str] = None
    collective_block: int = 32
    # low-rank (SVD-compressed) MLP (modules/low_rank.py, NeuronMLP
    # arxiv 2510.25977): rank of the {"lr_u","lr_v"} factor pairs the
    # gate/up/down projections are compressed to host-side; None = dense
    low_rank: Optional[low_rank_mod.LowRankSpec] = None
    # --- recurrent / hybrid state axis (reference: contrib/models/
    # Falcon-H1-0.5B-Instruct hybrid attention+mamba2 and contrib/models/
    # recurrentgemma-2b-it Griffin blocks — a SECOND cache pytree of
    # conv tails + recurrent states carried next to the KV cache) ---
    ssm: Optional[ssm_mod.SSMSpec] = None
    # per-layer flag: True = layer i carries the SSM block; None with ssm
    # set = every layer. With ssm_parallel the SSM runs NEXT TO attention
    # inside each flagged layer (falcon-h1 parallel hybrid); otherwise it
    # REPLACES attention there (recurrentgemma rec/rec/attn pattern).
    ssm_pattern: Optional[Tuple[bool, ...]] = None
    ssm_parallel: bool = False
    # a decoder-hybrid-decoder's temporal block BY LAYER, any order (the
    # recurrent walk is unrolled, so nothing has to repeat): "mamba" (the
    # ``ssm`` block), "window" (attention on the row's ring, ``window_pool``),
    # "full" (attention on the allocator's pool), "cross" (attention with a
    # query projection only, over the pool of the nearest "full" layer below
    # it: no key, value or cache of its own), "gmu" (a Gated Memory Unit: the
    # nearest mixer below hands it its scan output, ``ssm.SCAN_OUT``). With
    # ``ssm_pattern`` / ``layer_pattern`` for its "mamba" / "window" layers
    layer_kinds: Optional[Tuple[str, ...]] = None
    # a stack whose layer is ONE sub-block (Nemotron-H), by layer: "mamba"
    # (the ``ssm`` block), "attention", "moe" (the routed block), "mlp" (a
    # dense MLP), each behind the layer's one norm and joined by one residual
    # add; ``ssm_pattern`` / ``moe_pattern`` mark its "mamba" / "moe" layers.
    # The weights stack by kind, the norm with them (:func:`block_stack`); the
    # walk is :func:`run_layers_blocks`, on the paged path only
    layer_blocks: Optional[Tuple[str, ...]] = None
    # differential attention (arXiv:2410.05258 as Phi-4-mini-flash has it):
    # heads pair up by neighbours, ``o_j = rmsnorm(A(q_2j) - lam A(q_2j+1))
    # (1 - lam_init)`` over the pair's SHARED value of two heads' width.
    # ``head_dim`` is then the PAIR's width (two published heads: the pool's
    # kv row and the value), ``num_kv_heads`` the kv pairs, ``num_q_heads``
    # the published query heads, each projected at ``head_dim / 2`` and placed
    # in its own half of a ``head_dim`` row with zeros in the other, where it
    # scores against its own member of the key pair (``_attn_block``)
    diff_attn: bool = False
    # family-specific static constants that conversion / layer hooks need
    # (falcon-h1 MuP multipliers): hashable (name, value) pairs, jit-static
    extras: Optional[Tuple[Tuple[str, Any], ...]] = None

    def extra(self, name: str, default=None):
        for k, v in (self.extras or ()):
            if k == name:
                return v
        return default

    @property
    def resolved_ssm_pattern(self) -> Optional[Tuple[bool, ...]]:
        if self.ssm is None:
            return None
        return (self.ssm_pattern if self.ssm_pattern is not None
                else (True,) * self.num_layers)

    @property
    def num_attn_layers(self) -> int:
        """Layers that read/write the KV cache (SSM-only layers don't)."""
        if (self.layer_kinds or self.layer_blocks) is not None:
            return sum(map(self.count_kind, ("window", "full", "attention")))
        pat = self.resolved_ssm_pattern
        if pat is None or self.ssm_parallel:
            return self.num_layers * self.sub_blocks
        return self.num_layers - sum(pat)

    def count_kind(self, kind: str) -> int:
        """Layers of ``layer_kinds`` / ``layer_blocks`` that are ``kind``."""
        return sum(k == kind for k in self.layer_kinds or self.layer_blocks
                   or ())

    @property
    def num_window_layers(self) -> int:
        """Layers whose KV lies in the window layers' ring pool."""
        return sum(self.layer_pattern) if self.window_pool else 0

    @property
    def num_ssm_layers(self) -> int:
        pat = self.resolved_ssm_pattern
        return 0 if pat is None else sum(pat)

    @property
    def num_moe_layers(self) -> int:
        """Layers whose MLP is the routed block."""
        if self.moe is None:
            return 0
        if self.sub_blocks > 1:
            return self.num_layers
        if self.moe_pattern is not None:
            return sum(self.moe_pattern)
        return self.num_layers - self.first_dense

    @property
    def scale(self) -> float:
        return self.attn_scale if self.attn_scale is not None else self.head_dim ** -0.5

    @property
    def q_size(self) -> int:
        return self.gqa.num_q_heads * self.head_dim

    @property
    def q_proj_size(self) -> int:
        """What the query projection emits and ``o_proj`` takes: a
        differential head is projected at half the row it is placed in."""
        return self.q_size // 2 if self.diff_attn else self.q_size

    @property
    def kv_size(self) -> int:
        return self.gqa.num_kv_heads * self.head_dim

    @property
    def v_head_dim(self) -> int:
        return self.mla.v_head_dim if self.mla is not None else self.head_dim


def pad_vocab(vocab: int, tp: int, multiple: int = 128) -> int:
    m = max(tp, 1) * multiple
    return int(np.ceil(vocab / m) * m)


# ---------------------------------------------------------------------------
# Parameter specs (shapes + shardings) — reference analog: the parallel-layer
# module tree built in each model's init_model.
# ---------------------------------------------------------------------------

def _attn_param_specs(spec: DecoderSpec, L: int) -> Dict[str, ParamSpec]:
    H = spec.hidden_size
    dt = spec.dtype
    layers: Dict[str, ParamSpec] = {
        "input_norm": ParamSpec((L, H), P(), dt, "ones"),
        "post_norm": ParamSpec((L, H), P(), dt, "ones"),
    }
    if spec.norm_bias:
        layers["input_norm_b"] = ParamSpec((L, H), P(), dt, "zeros")
        layers["post_norm_b"] = ParamSpec((L, H), P(), dt, "zeros")
    if spec.mla is not None:
        m = spec.mla
        nh = spec.gqa.num_q_heads
        if m.q_lora_rank:
            layers["q_a_proj"] = ParamSpec((L, H, m.q_lora_rank), P(), dt)
            layers["q_a_norm"] = ParamSpec((L, m.q_lora_rank), P(), dt, "ones")
            layers["q_b_proj"] = column_parallel(
                m.q_lora_rank, nh * m.qk_head_dim, dt, True, L)
        else:
            layers["q_proj"] = column_parallel(H, nh * m.qk_head_dim, dt, True, L)
        layers["kv_a_proj"] = ParamSpec(
            (L, H, m.kv_lora_rank + m.qk_rope_head_dim), P(), dt)
        layers["kv_a_norm"] = ParamSpec((L, m.kv_lora_rank), P(), dt, "ones")
        layers["kv_b_proj"] = column_parallel(
            m.kv_lora_rank, nh * (m.qk_nope_head_dim + m.v_head_dim), dt, True, L)
        layers["o_proj"] = row_parallel(nh * m.v_head_dim, H, dt, True, L)
        if m.head_gate:
            layers["g_proj"] = column_parallel(H, nh, dt, True, L)
    else:
        # q/k/v fused into ONE stacked weight: a decode step is a GEMV per
        # weight — one (H, q+2kv) matmul streams the bytes at a higher
        # effective bandwidth than three separate ones (fewer fusion
        # boundaries; measured on v5e). The reference fuses the same way
        # (fused_qkv, modules/attention/gqa.py GroupQueryAttention_QKV).
        layers.update({
            "qkv_proj": column_parallel(
                H, spec.q_proj_size * (2 if spec.attn_out_gate else 1)
                + 2 * spec.kv_size, dt, True, L),
            "o_proj": row_parallel(spec.q_proj_size, H, dt, True, L),
        })
        if spec.qkv_bias:
            layers["qkv_bias"] = ParamSpec(
                (L, spec.q_proj_size + 2 * spec.kv_size), P(None, AXIS_MP),
                dt, "zeros")
        if spec.diff_attn:
            layers.update(_diff_param_specs(spec, L))
        if spec.qk_norm:
            layers["q_norm"] = ParamSpec((L, spec.head_dim), P(), dt, "ones")
            layers["k_norm"] = ParamSpec((L, spec.head_dim), P(), dt, "ones")
        if spec.qk_norm_full:
            layers["q_norm"] = ParamSpec((L, spec.q_size), P(None, AXIS_MP),
                                         dt, "ones")
            layers["k_norm"] = ParamSpec((L, spec.kv_size), P(None, AXIS_MP),
                                         dt, "ones")
    if spec.sparse is not None:
        # the indexer, replicated: [qI | kI | w] as ONE projection (a decode
        # step streams it in one pass) and the index key's LayerNorm
        sp = spec.sparse
        layers["idx_proj"] = ParamSpec((L, H, sp.proj_width), P(), dt)
        layers["idx_k_norm"] = ParamSpec((L, sp.index_dim), P(), dt, "ones")
        layers["idx_k_norm_b"] = ParamSpec((L, sp.index_dim), P(), dt,
                                           "zeros")
    if spec.qk_norm and spec.qk_norm_type == "layernorm":
        layers["q_norm_b"] = ParamSpec((L, spec.head_dim), P(), dt, "zeros")
        layers["k_norm_b"] = ParamSpec((L, spec.head_dim), P(), dt, "zeros")
    if spec.o_bias:
        # row-parallel bias: replicated, added after the psum'd projection
        layers["o_bias"] = ParamSpec((L, H), P(), dt, "zeros")
    if spec.sandwich_norm:
        layers["post_attn_norm"] = ParamSpec((L, H), P(), dt, "ones")
        layers["post_ff_norm"] = ParamSpec((L, H), P(), dt, "ones")
    if spec.attn_sink:
        layers["sink"] = ParamSpec((L, spec.gqa.num_q_heads),
                                   P(None, AXIS_MP), jnp.float32, "zeros")
    if spec.alibi:
        layers["alibi_slopes"] = ParamSpec((L, spec.gqa.num_q_heads),
                                           P(), jnp.float32, "zeros")
    if spec.lora is not None and spec.mla is None:
        _add_lora_specs(spec, layers, L, {
            "q_proj": (H, spec.q_size), "k_proj": (H, spec.kv_size),
            "v_proj": (H, spec.kv_size), "o_proj": (spec.q_size, H)})
    return layers


def _diff_param_specs(spec: DecoderSpec, L: int) -> Dict[str, ParamSpec]:
    """Differential attention's own leaves: the four vectors of ``lam``
    ([q1, k1, q2, k2], a published head wide) and the gain of the norm over
    the pair's value."""
    return {
        "diff_lambda": ParamSpec((L, 4, spec.head_dim // 2), P(),
                                 jnp.float32, "zeros"),
        "diff_subln": ParamSpec((L, spec.head_dim), P(), spec.dtype, "ones"),
    }


def _cross_param_specs(spec: DecoderSpec, L: int) -> Dict[str, ParamSpec]:
    """A "cross" layer of ``layer_kinds``: a query and an output projection
    (replicated: the stack is served at tp = 1) and no key or value."""
    H, dt = spec.hidden_size, spec.dtype
    layers = {"q_proj": ParamSpec((L, H, spec.q_proj_size), P(), dt),
              "o_proj": ParamSpec((L, spec.q_proj_size, H), P(), dt)}
    if spec.qkv_bias:
        layers["q_bias"] = ParamSpec((L, spec.q_proj_size), P(), dt, "zeros")
    if spec.o_bias:
        layers["o_bias"] = ParamSpec((L, H), P(), dt, "zeros")
    if spec.diff_attn:
        layers.update(_diff_param_specs(spec, L))
    return layers


def _add_lora_specs(spec: DecoderSpec, layers: Dict[str, ParamSpec], L: int,
                    dims: Dict[str, Tuple[int, int]]) -> None:
    """Stacked adapter weights for each targeted module
    (reference: modules/lora_serving/lora_layer.py parallel LoRA linears).
    A (L, max_loras, in, r) replicated; B (L, max_loras, r, out) sharded
    like the base weight's out dim when it is model-parallel."""
    lo = spec.lora
    dt = spec.dtype
    col_sharded = {"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"}
    for mod, (d_in, d_out) in dims.items():
        if not lo.targets(mod):
            continue
        a_spec = P(None, None, AXIS_MP, None) if mod in ("o_proj", "down_proj") \
            else P()
        b_spec = P(None, None, None, AXIS_MP) if mod in col_sharded else P()
        layers[f"lora_A_{mod}"] = ParamSpec(
            (L, lo.max_loras, d_in, lo.rank), a_spec, dt, "zeros")
        layers[f"lora_B_{mod}"] = ParamSpec(
            (L, lo.max_loras, lo.rank, d_out), b_spec, dt, "zeros")


def _dense_mlp_param_specs(spec: DecoderSpec, L: int) -> Dict[str, ParamSpec]:
    H, I = spec.hidden_size, spec.intermediate_size
    dt = spec.dtype
    layers = {
        "gate_proj": column_parallel(H, I, dt, True, L),
        "down_proj": row_parallel(I, H, dt, True, L),
    }
    if spec.mlp_glu:
        layers["up_proj"] = column_parallel(H, I, dt, True, L)
    if spec.mlp_bias:
        layers["gate_bias"] = ParamSpec((L, I), P(None, AXIS_MP), dt, "zeros")
        layers["down_bias"] = ParamSpec((L, H), P(), dt, "zeros")
        if spec.mlp_glu:
            layers["up_bias"] = ParamSpec((L, I), P(None, AXIS_MP), dt,
                                          "zeros")
    if spec.act == "xielu":
        # [alpha_p_raw, alpha_n_raw, beta, eps] per layer (apertus)
        layers["xielu"] = ParamSpec((L, 4), P(), jnp.float32, "ones")
    if spec.lora is not None:
        dims = {"gate_proj": (H, I), "down_proj": (I, H)}
        if spec.mlp_glu:
            dims["up_proj"] = (H, I)
        _add_lora_specs(spec, layers, L, dims)
    return layers


def _moe_param_specs(spec: DecoderSpec, L: int) -> Dict[str, ParamSpec]:
    m = spec.moe
    H, dt = spec.hidden_size, spec.dtype
    # the router scores every expert; the weights hold all of them or a
    # share (MoESpec.held_experts)
    E, Ie = m.num_held, m.stored_intermediate or m.intermediate_size
    layers: Dict[str, ParamSpec] = {
        "router": ParamSpec((L, H, m.num_experts), P(), jnp.float32),
        "expert_gate": expert_column_parallel(E, H, Ie, dt, True, L),
        "expert_up": expert_column_parallel(E, H, Ie, dt, True, L),
        "expert_down": expert_row_parallel(E, Ie, H, dt, True, L),
    }
    if m.has_router_bias:
        layers["router_bias"] = ParamSpec((L, m.num_experts), P(),
                                          jnp.float32, "zeros")
    if m.expert_bias:
        layers["expert_gate_bias"] = ParamSpec(
            (L, E, Ie), P(None, AXIS_EP, AXIS_TP), dt, "zeros")
        layers["expert_up_bias"] = ParamSpec(
            (L, E, Ie), P(None, AXIS_EP, AXIS_TP), dt, "zeros")
        layers["expert_down_bias"] = ParamSpec(
            (L, E, H), P(None, AXIS_EP, None), dt, "zeros")
    if m.shared_intermediate > 0:
        Is = m.shared_intermediate
        layers.update({
            "shared_gate": column_parallel(H, Is, dt, True, L),
            "shared_up": column_parallel(H, Is, dt, True, L),
            "shared_down": row_parallel(Is, H, dt, True, L),
        })
        if m.shared_gated:
            layers["shared_gate_w"] = ParamSpec((L, H), P(), dt)
    return _plain_moe_specs(m, layers)


def mlp_stack(spec: DecoderSpec, i: int) -> Tuple[str, int]:
    """Where an interleaved recurrent stack keeps layer ``i``'s norms and
    MLP: ``(stack name, index in it)``. One stack, "layers", unless leading
    dense layers stand under expert layers (``first_dense``): then the
    expert layers' are "moe_layers"."""
    nd = spec.first_dense if spec.moe is not None else 0
    return ("moe_layers", i - nd) if 0 < nd <= i else ("layers", i)


def decoder_param_specs(spec: DecoderSpec) -> Dict[str, Any]:
    """Shapes + shardings of the full param tree.

    Uniform models: one "layers" stack of num_layers. Mixed dense/MoE models
    (deepseek first_k_dense_replace): "layers" = the leading first_dense
    dense layers, "moe_layers" = the trailing MoE layers — two lax.scan
    stacks in run_layers."""
    L, H = spec.num_layers, spec.hidden_size
    dt = spec.dtype
    out: Dict[str, Any] = {
        "embed": (vocab_parallel_embedding(spec.padded_vocab, H, dt)
                  if spec.vocab_parallel
                  else ParamSpec((spec.padded_vocab, H), P(), dt)),
    }
    if not spec.skip_final_norm:
        out["final_norm"] = ParamSpec((H,), P(), dt, "ones")
        if spec.norm_bias:
            out["final_norm_b"] = ParamSpec((H,), P(), dt, "zeros")
    if spec.learned_pos:
        out["pos_embed"] = ParamSpec((spec.learned_pos, H), P(), dt)
    if spec.embed_norm:
        out["embed_norm"] = ParamSpec((H,), P(), dt, "ones")
        out["embed_norm_b"] = ParamSpec((H,), P(), dt, "zeros")
    if spec.layer_blocks is not None:
        out.update(_block_stack_specs(spec))
    elif spec.sub_blocks > 1:
        # several [attention, dense MLP] pairs a layer, one routed block on
        pairs = _attn_param_specs(spec, L * spec.sub_blocks)  # the shortcut
        pairs.update(_dense_mlp_param_specs(spec, L * spec.sub_blocks))
        out["layers"] = pairs
        if spec.moe is not None:
            out["moe_layers"] = _moe_param_specs(spec, L)
    elif spec.moe is not None and spec.first_dense > 0 and (
            spec.ssm is None or spec.ssm_parallel):
        n_dense, n_moe = spec.first_dense, spec.num_moe_layers
        dense = _attn_param_specs(spec, n_dense)
        dense.update(_dense_mlp_param_specs(spec, n_dense))
        moe = _attn_param_specs(spec, n_moe)
        moe.update(_moe_param_specs(spec, n_moe))
        out["layers"] = dense
        out["moe_layers"] = moe
    elif spec.moe is not None and spec.moe_pattern is not None:
        # interleaved dense/MoE (llama4): stacks hold each kind's layers in
        # order of appearance; run_layers walks the pattern
        n_moe = spec.num_moe_layers
        n_dense = L - n_moe
        moe = _attn_param_specs(spec, n_moe)
        moe.update(_moe_param_specs(spec, n_moe))
        out["moe_layers"] = moe
        if n_dense:
            dense = _attn_param_specs(spec, n_dense)
            dense.update(_dense_mlp_param_specs(spec, n_dense))
            out["layers"] = dense
    elif spec.ssm is not None and not spec.ssm_parallel:
        # interleaved recurrent/attention stacks (recurrentgemma): "layers"
        # holds every layer's norms + MLP; attention weights stack over the
        # attention layers only ("attn_layers"), SSM weights over the
        # recurrent layers ("ssm_layers") — SSM-only layers carry no dead
        # attention params and no KV cache rows. Leading dense layers under
        # an expert stack (first_dense) split the norms + MLP as the attention
        # stacks do: "layers" the dense layers', "moe_layers" the expert ones'
        norm_keys = ("input_norm", "post_norm", "input_norm_b", "post_norm_b",
                     "post_attn_norm", "post_ff_norm")
        # a post-norm stack has no input norms: its walk reads the two
        # output norms only
        walked = norm_keys[4:] if spec.norm_position == "post" else norm_keys
        n_moe = spec.num_moe_layers
        for name, n, mlp in ((mlp_stack(spec, 0)[0], L - n_moe,
                              _dense_mlp_param_specs),
                             (mlp_stack(spec, L - 1)[0], n_moe,
                              _moe_param_specs)):
            if n:
                out[name] = {k: v
                             for k, v in _attn_param_specs(spec, n).items()
                             if k in walked}
                out[name].update(mlp(spec, n))
        if spec.num_attn_layers:
            attn_full = _attn_param_specs(spec, spec.num_attn_layers)
            out["attn_layers"] = {k: v for k, v in attn_full.items()
                                  if k not in norm_keys}
        if spec.num_ssm_layers:
            out["ssm_layers"] = ssm_mod.ssm_param_specs(
                spec.ssm, H, spec.num_ssm_layers, dt)
        if spec.layer_kinds is not None:
            # a decoder-hybrid-decoder's second decoder: the layers that read
            # another layer's cache, and the Gated Memory Units (the width of
            # the mixer's scan output in, the hidden size out)
            if spec.count_kind("cross"):
                out["cross_layers"] = _cross_param_specs(
                    spec, spec.count_kind("cross"))
            if spec.count_kind("gmu"):
                n, W = spec.count_kind("gmu"), spec.ssm.d_inner
                out["gmu_layers"] = {
                    "gmu_in": ParamSpec((n, H, W), P(), dt),
                    "gmu_out": ParamSpec((n, W, H), P(), dt)}
    else:
        layers = _attn_param_specs(spec, L)
        layers.update(_dense_mlp_param_specs(spec, L) if spec.moe is None
                      else _moe_param_specs(spec, L))
        if spec.ssm is not None:
            # parallel hybrid (falcon-h1): every layer is uniform — the SSM
            # weights join the single "layers" stack
            layers.update(ssm_mod.ssm_param_specs(spec.ssm, H, L, dt))
        out["layers"] = layers
    if not spec.tie_word_embeddings:
        out["lm_head"] = ParamSpec((H, spec.padded_vocab), P(None, AXIS_MP), dt)
        if spec.lm_head_bias:
            out["lm_head_b"] = ParamSpec((spec.padded_vocab,),
                                         P(AXIS_MP), dt, "zeros")
    if spec.medusa_heads > 0:
        M = spec.medusa_heads
        out["medusa_blocks"] = ParamSpec((M, H, H), P(), dt)
        out["medusa_bias"] = ParamSpec((M, H), P(), dt, "zeros")
        out["medusa_lm"] = ParamSpec((M, H, spec.padded_vocab),
                                     P(None, None, AXIS_MP), dt)
    return out


def init_param_tree(specs: Dict[str, Any], key: jax.Array,
                    mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Random-init a ParamSpec tree. Per-leaf keys are derived from the leaf
    PATH (fold_in of a stable hash), so adding optional params (lora, medusa)
    never reshuffles the other weights for a given seed."""
    import zlib
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    leaves = []
    for path, ps in flat:
        pstr = "/".join(str(p) for p in path)
        k = jax.random.fold_in(key, zlib.crc32(pstr.encode()) & 0x7FFFFFFF)
        if mesh is None:
            leaves.append(ps.initializer(k))
        else:
            # each leaf is BORN on its sharding: every device generates its
            # own shard (partitionable threefry — same bits as the
            # unsharded draw), nothing is staged whole on the default device
            leaves.append(_sharded_initializer(
                ps, NamedSharding(mesh, ps.pspec))(k))
    return jax.tree.unflatten(treedef, leaves)


@lru_cache(maxsize=512)
def _sharded_initializer(ps: ParamSpec, sharding: NamedSharding):
    return jax.jit(ps.initializer, out_shardings=sharding)


def init_params(spec: DecoderSpec, key: jax.Array,
                mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Random-init a sharded param tree (tiny-model tests / benchmarks with
    synthetic weights — reference: modules/checkpoint.py:202-287 random
    N-layer checkpoint creation)."""
    return init_param_tree(decoder_param_specs(spec), key, mesh)


def fuse_qkv_host(host: Dict[str, Any]) -> Dict[str, Any]:
    """Fuse per-projection q/k/v host weights (family converters emit them
    separately, matching the HF checkpoint) into the stacked ``qkv_proj`` /
    ``qkv_bias`` the layer graph consumes. Walks the decoder-layer subtrees
    only — cross-attention ("cross_layers") and vision params keep their own
    layouts. No-op when already fused (pre-fused quantized checkpoints)."""
    for key in ("layers", "moe_layers"):
        d = host.get(key)
        # MLA layers (deepseek) have a bare q_proj with separate compressed
        # kv projections — only fuse the standard q/k/v triple
        if (not isinstance(d, dict) or "q_proj" not in d
                or "k_proj" not in d or "v_proj" not in d):
            continue
        d["qkv_proj"] = np.concatenate(
            [np.asarray(d.pop("q_proj")), np.asarray(d.pop("k_proj")),
             np.asarray(d.pop("v_proj"))], axis=-1)
        if "q_bias" in d:
            d["qkv_bias"] = np.concatenate(
                [np.asarray(d.pop("q_bias")), np.asarray(d.pop("k_bias")),
                 np.asarray(d.pop("v_bias"))], axis=-1)
    return host


def stack_lora_host(spec: DecoderSpec, host: Dict[str, Any]) -> Dict[str, Any]:
    """Backfill the stacked ``lora_A_<mod>`` / ``lora_B_<mod>`` host
    leaves a checkpoint never carries: HF state dicts hold BASE weights
    only — adapters arrive at serving time, swapped into device slots by
    serving/lora_pool.py — so every load path stacks zeroed
    ``(L, max_loras, ...)`` factors here (slot 0 IS the pinned zero
    adapter). No-op without lora_config or when the leaves are already
    present (init_random_weights, quantized-state round-trips)."""
    if spec.lora is None:
        return host
    specs = decoder_param_specs(spec)
    for group, d in specs.items():
        if not isinstance(d, dict) or not isinstance(host.get(group), dict):
            continue
        for k, ps in d.items():
            if k.startswith("lora_") and k not in host[group]:
                host[group][k] = np.zeros(ps.shape, ps.dtype)
    return host


def param_shardings(spec: DecoderSpec, mesh: Mesh):
    specs = decoder_param_specs(spec)
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps.pspec), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Layer stack
# ---------------------------------------------------------------------------



def _split_heads(x: jnp.ndarray, n_heads: int, head_dim: int) -> jnp.ndarray:
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, head_dim)


def _norm(spec: DecoderSpec, x, w, b=None):
    """Pre/post-block norm: RMSNorm (default, with optional gemma offset) or
    LayerNorm (dbrx bias-free; gpt2-family with bias)."""
    if spec.norm_type == "layernorm":
        return layer_norm(x, w, b, spec.rms_eps)
    return rms_norm(x, w, spec.rms_eps, spec.norm_offset)


def _mla_project(spec: DecoderSpec, h, layer_w, cos, sin):
    """Multi-head Latent Attention projections (reference: models/deepseek/
    modeling_deepseek.py MLA; HF LongcatFlashMLA): Q through the optional
    q-lora, KV compressed to the latent + the shared rope head. Returns
    ``q_nope`` (B,T,Hq,nope), ``q_rot`` (B,T,Hq,rope) - scaled by
    ``mla.q_scale`` and rotated - and the token's latent row ``lat``
    (B,T,rank+rope) = ``[N(c) x mla.kv_scale | rot(k_rope)]``: what the
    paged pool keeps and every K and V head is a projection of."""
    m = spec.mla
    nh = spec.gqa.num_q_heads
    b, t, _ = h.shape
    if m.q_lora_rank:
        qa = rms_norm(qlinear(h, layer_w["q_a_proj"]), layer_w["q_a_norm"],
                      spec.rms_eps)
        q = qlinear(qa, layer_w["q_b_proj"])
    else:
        q = qlinear(h, layer_w["q_proj"])
    if m.q_scale != 1.0:
        q = (q.astype(jnp.float32) * m.q_scale).astype(q.dtype)
    # the query projection's columns are stored [every head's nope | every
    # head's rope] (:func:`mla_q_columns`, applied by the loaders): the two
    # parts are then contiguous, tile-aligned slices. Stored a head at a
    # time, [nope | rope] of 128 + 64, the split strides heads of 192 lanes
    # and the compiler relaid the WHOLE stacked projection out in front of
    # the layer loop, 302 MB read and written a step at LongCat's widths
    # (AOT, PR 40)
    cut = nh * m.qk_nope_head_dim
    q_nope = _shard(q[..., :cut].reshape(b, t, nh, m.qk_nope_head_dim),
                    AXIS_DP, None, AXIS_MP, None)
    q_rot = _shard(q[..., cut:].reshape(b, t, nh, m.qk_rope_head_dim),
                   AXIS_DP, None, AXIS_MP, None)
    q_rot = apply_rope(q_rot, cos, sin, interleaved=spec.rope_interleaved)

    ckv = qlinear(h, layer_w["kv_a_proj"])                  # (B,T,r+rope)
    c = rms_norm(ckv[..., :m.kv_lora_rank], layer_w["kv_a_norm"],
                 spec.rms_eps)
    if m.kv_scale != 1.0:
        c = (c.astype(jnp.float32) * m.kv_scale).astype(c.dtype)
    k_rot = apply_rope(ckv[:, :, None, m.kv_lora_rank:], cos, sin,
                       interleaved=spec.rope_interleaved)[:, :, 0]
    return q_nope, q_rot, jnp.concatenate([c, k_rot], axis=-1)


def mla_q_columns(w: np.ndarray, heads: int, nope: int,
                  rope: int) -> np.ndarray:
    """A checkpoint's query projection ``(..., heads x (nope + rope))``, a
    head's ``[nope | rope]`` at a time, with its columns regrouped as
    ``[every head's nope | every head's rope]``: what :func:`_mla_project`
    reads (``q_b_proj``, or ``q_proj`` without a q-lora)."""
    w = np.asarray(w)
    by_head = w.reshape(w.shape[:-1] + (heads, nope + rope))
    return np.concatenate(
        [by_head[..., :nope].reshape(w.shape[:-1] + (heads * nope,)),
         by_head[..., nope:].reshape(w.shape[:-1] + (heads * rope,))],
        axis=-1)


def _mla_kv_b(spec: DecoderSpec, layer_w):
    """``kv_b_proj`` as (rank, Hq, nope + v): a head's K-nope and V
    up-projections of the latent side by side."""
    from ..modules.quantization import dequantize, is_quantized_leaf
    w = layer_w["kv_b_proj"]
    if is_quantized_leaf(w):
        w = dequantize(w, spec.dtype)
    m = spec.mla
    return w.reshape(m.kv_lora_rank, spec.gqa.num_q_heads,
                     m.qk_nope_head_dim + m.v_head_dim)


def _mla_qkv(spec: DecoderSpec, h, layer_w, cos, sin):
    """The EXPANDED heads of :func:`_mla_project`'s latent, for the
    contiguous cache: q/k (B,T,Hq,qk_head_dim), v (B,T,Hq,v_head_dim). The
    paged pool keeps the latent row itself (:func:`_mla_paged_block`)."""
    m = spec.mla
    nh = spec.gqa.num_q_heads
    b, t, _ = h.shape
    q_nope, q_rot, lat = _mla_project(spec, h, layer_w, cos, sin)
    kv = qlinear(lat[..., :m.kv_lora_rank], layer_w["kv_b_proj"])
    kv = _shard(kv.reshape(b, t, nh, m.qk_nope_head_dim + m.v_head_dim),
                AXIS_DP, None, AXIS_MP, None)
    k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    k_rot = jnp.broadcast_to(lat[:, :, None, m.kv_lora_rank:],
                             (b, t, nh, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rot], axis=-1)
    k = jnp.concatenate([k_nope, k_rot], axis=-1)
    return q, k, v


#: tokens of the cached prefix one step of :func:`_mla_attend` gathers and
#: scores at once (whole pages)
MLA_PREFIX_GROUP_TOKENS = 512

#: a chunk of at least this many queries a row expands the prefix's latents
#: through ``kv_b_proj``; a narrower one (and a decode step) attends in the
#: latent space. Set from the v5e timings in PERF.md section 6 (PR 40): an
#: absorbed query costs 2 x (rank + rope + rank) FLOP a head a cached token
#: against 2 x (qk_head_dim + v_head_dim) expanded, and the expansion itself
#: 2 x rank x Hq x (nope + v) a cached token whatever the width.
MLA_EXPAND_MIN_QUERIES = 128


def _mla_attend(spec: DecoderSpec, q_nope, q_rot, lat_new, w_kvb, pool, li,
                block_table, positions, absorbed: bool):
    """Attention of a paged step over the latent pool, in XLA: q_nope
    (B,T,Hq,nope), q_rot (B,T,Hq,rope), ``lat_new`` (B,T,rank+rope) the
    step's own latent rows (as stored: the pool's dtype), ``pool`` (L, N,
    Bs, 1, lanes), ``positions`` (B,T) ascending a row. Returns (B,T,Hq,v).

    Two parts, merged by their softmax statistics:

    * the step's own tokens, EXPANDED through ``kv_b_proj`` and attended
      causally by position (a decode step: its one token);
    * the cached prefix - pool positions before the row's first - walked in
      groups of :data:`MLA_PREFIX_GROUP_TOKENS` tokens by a loop whose trip
      count is the longest live prefix of the step's rows, not the table's
      width: a chunk at the head of its prompt gathers nothing. ``absorbed``
      scores the group in the latent space (``W_UK`` folded into the query,
      ``W_UV`` applied once after the softmax: rank + rope lanes a query
      head a token, rank of them doubling as values); otherwise the group's
      latents are expanded to heads first.
    """
    from ..modules import block_kv_cache as bkv
    m = spec.mla
    r, nope = m.kv_lora_rank, m.qk_nope_head_dim
    b, t, nh, _ = q_nope.shape
    dt = q_nope.dtype
    neg = attn_ops.NEG_INF
    scale = spec.scale
    f32 = dict(preferred_element_type=jnp.float32)
    lat_new = kv.dequantize_kv(lat_new, dt, spec.kv_scale)
    c_new, kr_new = lat_new[..., :r], lat_new[..., r:r + m.qk_rope_head_dim]

    def scores_of(qn, k_nope_, kr_):
        return (jnp.einsum("bthd,bshd->bhts", qn, k_nope_, **f32)
                + jnp.einsum("bthd,bsd->bhts", q_rot, kr_, **f32)) * scale

    # the step's own tokens
    kv_new = jnp.einsum("bsr,rhd->bshd", c_new, w_kvb, **f32).astype(dt)
    s_self = scores_of(q_nope, kv_new[..., :nope], kr_new)
    causal = positions[:, :, None] >= positions[:, None, :]
    s_self = jnp.where(causal[:, None], s_self, neg)
    m_s = jnp.max(s_self, axis=-1)                              # (B,Hq,T)
    p_s = jnp.exp(s_self - m_s[..., None])
    l_s = jnp.sum(p_s, axis=-1)
    o_s = jnp.einsum("bhts,bshd->bhtd", p_s.astype(dt), kv_new[..., nope:],
                     **f32)

    # the cached prefix
    bs = pool.shape[2]
    pages = max(1, min(MLA_PREFIX_GROUP_TOKENS // bs, block_table.shape[1]))
    group = pages * bs
    start = positions[:, 0]
    n_groups = (jnp.max(start) + group - 1) // group
    pad = -block_table.shape[1] % pages
    table = jnp.pad(block_table, ((0, 0), (0, pad)))
    if absorbed:
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_kvb[..., :nope],
                           **f32).astype(dt)
    width = r if absorbed else m.v_head_dim

    def step(j, carry):
        m_p, l_p, acc = carry
        rows = bkv.gather_layer_kv(
            pool, li, jax.lax.dynamic_slice_in_dim(table, j * pages, pages,
                                                   axis=1))[:, :, 0]
        rows = kv.dequantize_kv(rows, dt, spec.kv_scale)
        c, kr = rows[..., :r], rows[..., r:r + m.qk_rope_head_dim]
        if absorbed:
            s = (jnp.einsum("bthr,bsr->bhts", q_lat, c, **f32)
                 + jnp.einsum("bthd,bsd->bhts", q_rot, kr, **f32)) * scale
        else:
            heads = jnp.einsum("bsr,rhd->bshd", c, w_kvb, **f32).astype(dt)
            s = scores_of(q_nope, heads[..., :nope], kr)
        valid = ((j * group + jnp.arange(group))[None, :]
                 < start[:, None])[:, None, None, :]
        m_c = jnp.maximum(m_p, jnp.max(jnp.where(valid, s, neg), axis=-1))
        # a row with nothing valid so far keeps m at neg: its p must be 0,
        # not exp(neg - neg)
        p = jnp.where(valid, jnp.exp(s - m_c[..., None]), 0.0)
        alpha = jnp.exp(m_p - m_c)
        pv = (jnp.einsum("bhts,bsr->bhtr", p.astype(dt), c, **f32)
              if absorbed else
              jnp.einsum("bhts,bshd->bhtd", p.astype(dt), heads[..., nope:],
                         **f32))
        return (m_c, l_p * alpha + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + pv)

    m_p, l_p, acc = jax.lax.fori_loop(0, n_groups, step, (
        jnp.full((b, nh, t), neg, jnp.float32),
        jnp.zeros((b, nh, t), jnp.float32),
        jnp.zeros((b, nh, t, width), jnp.float32)))
    if absorbed:
        # W_UV after the softmax, on the prefix's normalised sum
        acc = jnp.einsum(
            "bhtr,rhd->bhtd",
            (acc / jnp.maximum(l_p, 1e-30)[..., None]).astype(dt),
            w_kvb[..., nope:], **f32) * l_p[..., None]
    m_all = jnp.maximum(m_p, m_s)
    w_p, w_s = jnp.exp(m_p - m_all), jnp.exp(m_s - m_all)
    out = ((acc * w_p[..., None] + o_s * w_s[..., None])
           / (l_p * w_p + l_s * w_s)[..., None])
    return out.transpose(0, 2, 1, 3).astype(dt)


def _mla_paged_block(spec: DecoderSpec, h, layer_w, pool, li, cos, sin,
                     positions, slot_mapping, block_table):
    """The paged attention of an MLA layer over the LATENT pool: project,
    write the step's latent rows at ``slot_mapping``, attend in the latent
    space on a kernel where one engages (T = 1: ``ops/mla_decode.py``; a
    chunk: ``ops/mla_prefill.py``); a declined chunk takes the XLA form
    :data:`MLA_EXPAND_MIN_QUERIES` gives its width, over exactly the prefix
    its rows have cached. Returns the heads' outputs (B,T,Hq x v) and the
    pool."""
    from ..modules import block_kv_cache as bkv
    from ..ops import mla_decode, mla_prefill
    m = spec.mla
    b, t, _ = h.shape
    q_nope, q_rot, lat = _mla_project(spec, h, layer_w, cos, sin)
    w_kvb = _mla_kv_b(spec, layer_w)
    lanes = pool.shape[4]
    # the engagement record names what the pool keeps: a latent row a token
    kernel_mode.note(
        "latent_cache", "xla",
        f"lanes={lanes} of {m.latent_dim} values bytes_a_token="
        f"{pool.shape[0] * lanes * pool.dtype.itemsize} "
        f"sub_blocks={pool.shape[0]}")
    stored = kv.quantize_kv(lat, pool.dtype, spec.kv_scale)
    pool = bkv.write_slots_at_layer(
        pool, jnp.pad(stored, ((0, 0), (0, 0), (0, lanes - lat.shape[-1])))
        [:, :, None, :], li, slot_mapping)
    out = None
    absorbed = t < MLA_EXPAND_MIN_QUERIES
    if t == 1:
        declined = ("decode_kernel=False" if spec.decode_kernel is False
                    else mla_decode.declined(spec, pool, block_table))
        if not declined:
            out = mla_decode.mla_decode_attention(
                q_nope[:, 0], q_rot[:, 0], stored[:, 0], w_kvb, pool, li,
                positions[:, 0], block_table, scale=spec.scale,
                rank=m.kv_lora_rank,
                interpret=kernel_mode.pallas_interpret())[:, None]
        kernel_mode.note(
            "mla_decode", "xla" if declined else kernel_mode.kernel_path(),
            declined or mla_decode.plan_note(pool, q_nope.shape[2]))
    else:
        out = mla_prefill.chunk_attention(
            spec, q_nope, q_rot, w_kvb, pool, li, positions, block_table,
            f"rows={b} width={t} prefix="
            + ("absorbed" if absorbed else "expanded through kv_b_proj")
            + f" in groups of {MLA_PREFIX_GROUP_TOKENS} tokens, own tokens "
            "expanded")
    if out is None:
        nh = q_nope.shape[2]
        # float32 temps a row: scores and their exponentials of a prefix
        # group and of the step itself, the accumulator, the folded query
        row_bytes = 4 * nh * t * (
            2 * (MLA_PREFIX_GROUP_TOKENS + t)
            + (2 * m.kv_lora_rank if absorbed else m.v_head_dim))

        def attend(q_nope_, q_rot_, lat_, table_, pos_):
            return _mla_attend(spec, q_nope_, q_rot_, lat_, w_kvb, pool, li,
                               table_, pos_, absorbed)
        out = map_row_groups(attend, row_bytes, q_nope, q_rot, stored,
                             block_table, positions)
    return out.reshape(b, t, -1), pool


def attn_inputs(spec: DecoderSpec, position_ids, make_mask,
                rope_positions=None) -> Dict[str, Any]:
    """Bundle rope cos/sin + attention mask(s) for the layer stack.

    ``make_mask(window, chunk)`` builds the phase-appropriate mask. With a
    ``layer_pattern`` set (alternating local/global layers — reference:
    gemma3 / gpt_oss / llama4 families), both the local variant (sliding
    window or chunked attention + local_rope) and the global variant
    (optionally NoPE — identity rotation) are built once here; each scanned
    layer selects by its is_local flag — one compiled layer body, no
    per-layer branching (SURVEY §2.7)."""
    rp = rope_positions if rope_positions is not None else position_ids
    cos, sin = rope_cos_sin(rp, spec.rope)
    if spec.no_rope:
        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    ai: Dict[str, Any] = {"cos": cos, "sin": sin}
    if spec.layer_pattern is None:
        ai["mask"] = make_mask(spec.sliding_window, spec.attn_chunk)
        return ai
    ai["mask"] = make_mask(0, 0)
    cos_l, sin_l = rope_cos_sin(rp, spec.local_rope or spec.rope)
    if spec.no_rope:
        # learned-position models with local/global patterns (gpt-neo):
        # neither variant rotates
        cos_l, sin_l = jnp.ones_like(cos_l), jnp.zeros_like(sin_l)
    if spec.nope_global:
        # llama4 NoPE global layers: identity rotation
        ai["cos"], ai["sin"] = jnp.ones_like(cos), jnp.zeros_like(sin)
    ai["cos_l"], ai["sin_l"] = cos_l, sin_l
    ai["mask_l"] = make_mask(spec.sliding_window, spec.attn_chunk)
    return ai


def _layer_body(spec: DecoderSpec, hidden, layer_w, k_full, v_full, li,
                ai, is_local, seq_ids, positions, phase: str,
                identity_seq_ids: bool = False,
                arange_positions: bool = False,
                slot_mapping=None, block_table=None,
                mlp_kind: Optional[str] = None,
                adapter_ids=None, replace=None, kv_view: int = None,
                deepstack=None, deepstack_mask=None, prefill_lens=None,
                mixed_local=None, tally=None, live=None, select_of=None):
    """One transformer layer. hidden (B,T,H); k/v_full: the FULL stacked
    cache (L,B,S,Hkv,D) — or, in the paged layout, (L,N_blocks,Bs,Hkv,D)
    with ``slot_mapping``/``block_table`` set (phase "paged", reference:
    modules/kvcache/block_kv_cache_manager.py). ``li``: this layer's index
    into the cache (traced scalar). The cache flows through the layer scan
    as CARRY with in-place scatters — writes cost O(tokens), not O(cache)
    (the reference gets the same effect from buffer aliasing,
    model_wrapper.py:1578-1627).

    ai: attn_inputs() bundle; is_local: this layer's local/global flag
    (traced scalar from the scan xs).

    phase "prefill": attend within the window only (no prior cache read),
      then write the window into the cache (reference CTE path).
    phase "decode": write active tokens into cache, attend over full cache
      (reference TKG path; the reference's decomposed prior/active attention
      attention_base.py:1383-1461 is one fused softmax over the cache here —
      XLA fuses it, no manual decomposition needed).
    phase "paged": write at slot_mapping, gather via block_table, attend over
      the gathered view — covers paged prefill, prefix-cached continuation,
      chunked prefill and paged decode with one body.

    ``tally`` / ``live``: ``moe_block``'s, as :func:`scan_layers` hands them
    to a paged decode step over expert layers. ``select_of(h)``, a stack
    with a learned sparse selection: called on the normed block input before
    the attention, it returns the attention's ``select``.
    """
    if mlp_kind is None:
        mlp_kind = "dense" if spec.moe is None else "moe"
    caps: Dict[str, Any] = {}

    def _tap(name, val):
        """Tensor replacement (golden injection) then capture at one point
        (reference: utils/tensor_replacement/ + tensor capture
        model_base.py:1076-1149)."""
        if replace is not None and name in replace:
            val = jnp.where(replace[name + "_on"],
                            replace[name].astype(val.dtype), val)
        if spec.capture and name in spec.capture:
            caps[name] = val
        return val
    h = (_norm(spec, hidden, layer_w["input_norm"],
               layer_w.get("input_norm_b") if spec.norm_bias else None)
         if spec.norm_position == "pre" else hidden)
    attn_in = h        # parallel blocks feed the MLP from the same norm
    h, k_full, v_full, _ = _attn_block(
        spec, h, layer_w, k_full, v_full, li, ai, is_local, seq_ids,
        positions, phase, identity_seq_ids=identity_seq_ids,
        arange_positions=arange_positions, slot_mapping=slot_mapping,
        block_table=block_table, adapter_ids=adapter_ids, kv_view=kv_view,
        prefill_lens=prefill_lens, mixed_local=mixed_local,
        select=None if select_of is None else select_of(h))
    if spec.sandwich_norm:
        h = rms_norm(h, layer_w["post_attn_norm"], spec.rms_eps,
                     spec.norm_offset)
    h = _tap("attn_output", h)
    # SP: residual stream stays seq-sharded between blocks during prefill
    # (reference: sequence-parallel reduce-scatter, model_base.py:1482-1517)
    sp_axis = AXIS_CP if (spec.seq_parallel and phase == "prefill") else None

    def _mlp(x_in):
        return _mlp_block(
            spec, x_in, layer_w, mlp_kind, adapter_ids, phase=phase,
            tally=tally, live=live,
            router_x=(attn_in if mlp_kind == "moe"
                      and spec.moe.router_pre_attn else None))

    if spec.block_style != "sequential":
        # parallel residual: x + attn(norm(x)) + mlp(norm'(x)) (falcon
        # parallel_attn / phi share the attention norm; gpt-neox
        # use_parallel_residual has its own post norm over the INPUT)
        mlp_in = attn_in if spec.block_style == "parallel_shared" else \
            _norm(spec, hidden, layer_w["post_norm"],
                  layer_w.get("post_norm_b") if spec.norm_bias else None)
        m = _tap("mlp_output", _mlp(mlp_in))
        hidden = hidden + spec.residual_multiplier * _shard(
            h + m, AXIS_DP, sp_axis, None)
        hidden = _deepstack_add(hidden, deepstack, deepstack_mask)
        hidden = _tap("layer_output", hidden)
        return hidden, k_full, v_full, caps

    if spec.norm_position == "post_residual":
        # original-transformer post-LN (openai-gpt / GPT-1: x = ln(x + sub(x))
        # — reference: contrib/models/openai-gpt)
        hidden = _norm(spec, hidden + _shard(h, AXIS_DP, sp_axis, None),
                       layer_w["input_norm"],
                       layer_w.get("input_norm_b") if spec.norm_bias else None)
        h = _tap("mlp_output", _mlp(hidden))
        hidden = _norm(spec, hidden + _shard(h, AXIS_DP, sp_axis, None),
                       layer_w["post_norm"],
                       layer_w.get("post_norm_b") if spec.norm_bias else None)
        hidden = _tap("layer_output", hidden)
        return hidden, k_full, v_full, caps

    hidden = hidden + spec.residual_multiplier * _shard(h, AXIS_DP, sp_axis, None)

    h = (_norm(spec, hidden, layer_w["post_norm"],
               layer_w.get("post_norm_b") if spec.norm_bias else None)
         if spec.norm_position == "pre" else hidden)
    h = _mlp(h)
    if spec.sandwich_norm:
        h = rms_norm(h, layer_w["post_ff_norm"], spec.rms_eps,
                     spec.norm_offset)
    h = _tap("mlp_output", h)
    hidden = hidden + spec.residual_multiplier * _shard(h, AXIS_DP, sp_axis, None)
    hidden = _deepstack_add(hidden, deepstack, deepstack_mask)
    hidden = _tap("layer_output", hidden)
    return hidden, k_full, v_full, caps


def _row_parallel_out(spec: DecoderSpec, x, w, phase: str):
    """Row-parallel output reduction for o_proj / down_proj: the quantized
    ring exchange during decode/paged phases when the collective knob is on
    ("paged" covers the whole paged serving family including its context
    graphs — the unified ragged dispatch mixes both in one step), otherwise
    the plain (q)linear whose all-reduce GSPMD inserts."""
    if isinstance(w, dict) and "lr_u" in w:
        # low-rank (SVD) factors (modules/low_rank.py): the sharded
        # x @ U contraction's all-reduce lands on the rank-r
        # intermediate — already an ~out/r smaller wire than the dense
        # output — so the quantized ring is skipped; GSPMD reduces the
        # U half and the replicated V half needs no collective
        return qlinear(x, w)
    if spec.collective_dtype is not None and phase in ("decode", "paged"):
        return row_parallel_output(x, w,
                                   collective_dtype=spec.collective_dtype,
                                   collective_block=spec.collective_block)
    return qlinear(x, w)


def _mlp_block(spec: DecoderSpec, x_in, layer_w, mlp_kind, adapter_ids,
               phase: str = "prefill", tally=None, live=None, router_x=None):
    """The MLP / MoE half of a layer (GLU, plain 2-layer, or routed MoE),
    under the profiler scope ``moe`` (router, expert matmuls, combine) or
    ``mlp``. ``tally`` / ``live`` / ``router_x``: ``moe_block``'s (a router
    that reads the attention's input runs under ``moe`` all the same). The
    shared experts of a sequential block are part of ``moe``; in a PARALLEL
    block they are a third stream off the one norm, beside attention and
    the routed experts, and run under a sibling scope ``shared``."""
    if mlp_kind == "moe":
        apart = (spec.block_style != "sequential"
                 and spec.moe.shared_intermediate > 0)
        with jax.named_scope("moe"):
            y = moe_block(spec.moe, x_in, layer_w, phase=phase, tally=tally,
                          live=live, router_x=router_x, shared=not apart)
        if apart:
            with jax.named_scope("shared"):
                y = y + moe_mod.shared_experts(spec.moe, x_in, layer_w)
        return y
    with jax.named_scope("mlp"):
        return _dense_mlp(spec, x_in, layer_w, adapter_ids, phase)


def _dense_mlp(spec: DecoderSpec, x_in, layer_w, adapter_ids, phase: str):
    if spec.act == "xielu":
        # Apertus xIELU with LEARNED per-layer alphas (reference:
        # contrib/models/Apertus-8B-Instruct-2509; HF XIELUActivation):
        # layer_w["xielu"] = [alpha_p_raw, alpha_n_raw, beta, eps]
        xp = layer_w["xielu"].astype(jnp.float32)
        alpha_p = jax.nn.softplus(xp[0])
        beta, eps = xp[2], xp[3]
        alpha_n = beta + jax.nn.softplus(xp[1])

        def act(x):
            xf = x.astype(jnp.float32)
            y = jnp.where(
                xf > 0,
                alpha_p * xf * xf + beta * xf,
                (jnp.expm1(jnp.minimum(xf, eps)) - xf) * alpha_n + beta * xf)
            return y.astype(x.dtype)
    else:
        act = ACT_FNS[spec.act]
    if not spec.mlp_glu:
        # plain 2-layer MLP (gpt2/falcon/starcoder2/phi/neox):
        # gate_proj/down_proj slots hold fc1/fc2
        inter = apply_lora(spec.lora, layer_w, "gate_proj", x_in,
                           qlinear(x_in, layer_w["gate_proj"]),
                           adapter_ids)
        if spec.mlp_bias:
            inter = inter + layer_w["gate_bias"]
        inter = _shard(act(inter), AXIS_DP, None, AXIS_MP)
        y = apply_lora(spec.lora, layer_w, "down_proj", inter,
                       _row_parallel_out(spec, inter, layer_w["down_proj"],
                                         phase), adapter_ids)
        if spec.mlp_bias:
            y = y + layer_w["down_bias"]
        return y
    gate = apply_lora(spec.lora, layer_w, "gate_proj", x_in,
                      qlinear(x_in, layer_w["gate_proj"]), adapter_ids)
    up = apply_lora(spec.lora, layer_w, "up_proj", x_in,
                    qlinear(x_in, layer_w["up_proj"]), adapter_ids)
    if spec.mlp_bias:
        gate = gate + layer_w["gate_bias"]
        up = up + layer_w["up_bias"]
    inter = _shard(act(gate) * up, AXIS_DP, None, AXIS_MP)
    y = apply_lora(spec.lora, layer_w, "down_proj", inter,
                   _row_parallel_out(spec, inter, layer_w["down_proj"],
                                     phase), adapter_ids)
    if spec.mlp_bias:
        y = y + layer_w["down_bias"]
    return y


@lru_cache(maxsize=None)
def _paged_score_budget() -> int:
    """Bytes of float32 scores (rows x query heads of a shard x width x table
    tokens x 4) one gathered paged-prefill attention may hold at once: a
    twelfth of a device's memory. The scores and their exponentials are live
    together, so the attention's temps stay within a sixth of the device
    beside weights and pool. A backend that reports no limit (the CPU, an AOT
    compile for a chip that is not there) is taken as a 16 GiB chip."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2 ** 30)) // 12


def _score_row_group(rows: int, row_bytes: int) -> int:
    """Rows of a gathered paged-prefill attention to attend at once: the
    largest divisor of ``rows`` whose float32 scores (``row_bytes`` a row)
    fit :func:`_paged_score_budget`; one row always goes."""
    fit = max(1, _paged_score_budget() // row_bytes)
    return max(d for d in range(1, min(rows, fit) + 1) if rows % d == 0)


def map_row_groups(fn, row_bytes: int, *args):
    """``fn(*args)``, every argument's leading axis the step's rows, taken
    :func:`_score_row_group` rows at a time, one group after another, where
    all rows at once (``row_bytes`` of temps a row) would outgrow the budget:
    the temps stay a group's worth."""
    b = args[0].shape[0]
    group = _score_row_group(b, row_bytes)
    if group == b:
        return fn(*args)
    out = jax.lax.map(
        lambda xs: fn(*xs),
        tuple(x.reshape((b // group, group) + x.shape[1:]) for x in args))
    return out.reshape((b,) + out.shape[2:])


#: eps of the RMSNorm over a differential pair's value (the published
#: ``subln``)
DIFF_SUBLN_EPS = 1e-5


def _diff_place(q, n_heads: int, head_dim: int):
    """Differential attention's queries (B, T, n_heads x head_dim / 2), a
    published head each, PLACED: head ``2j + c`` in lanes ``c x head_dim / 2``
    on of a ``head_dim`` row, zeros in the other half -> (B, T, n_heads,
    head_dim). Against a kv row that holds the key pair ``[k1 | k2]`` it
    scores ``q . k_{c+1}``, and its output over the value pair ``[v1 | v2]``
    is the pair-wide ``A_{c+1}``."""
    b, t, _ = q.shape
    pair = q.reshape(b, t, n_heads // 2, 2, head_dim // 2)
    zero = jnp.zeros_like(pair[:, :, :, 0])
    return jnp.stack(
        [jnp.concatenate([pair[:, :, :, 0], zero], axis=-1),
         jnp.concatenate([zero, pair[:, :, :, 1]], axis=-1)],
        axis=3).reshape(b, t, n_heads, head_dim)


def _diff_combine(attn_out, layer_w, depth: int):
    """``rmsnorm(A1 - lam A2; g_sub) (1 - lam_init)`` a pair, from the placed
    heads' outputs (B, T, heads, head_dim) -> (B, T, heads / 2 x head_dim).
    ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 -
    0.6 exp(-0.3 depth)``, ``depth`` the layer's index in the stack."""
    b, t, n, d = attn_out.shape
    f32 = jnp.float32
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    vec = layer_w["diff_lambda"].astype(f32)
    lam = (jnp.exp(jnp.sum(vec[0] * vec[1]))
           - jnp.exp(jnp.sum(vec[2] * vec[3])) + lam_init)
    pair = attn_out.astype(f32).reshape(b, t, n // 2, 2, d)
    o = pair[:, :, :, 0] - lam * pair[:, :, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + DIFF_SUBLN_EPS)
    o = o * layer_w["diff_subln"].astype(f32) * (1.0 - lam_init)
    return o.astype(attn_out.dtype).reshape(b, t, n // 2 * d)


def _attn_body(spec: DecoderSpec, h, layer_w, k_full, v_full, li, ai,
               is_local, seq_ids, positions, phase: str, *,
               identity_seq_ids=False, arange_positions=False,
               slot_mapping=None, block_table=None, adapter_ids=None,
               kv_view=None, prefill_lens=None,
               mixed_local=None, select=None, depth=None, hand_kv=False,
               cross_kv=None):
    """The attention half of a layer: q/k/v projections, cache write, the
    phase-appropriate attention compute (Pallas kernel or XLA), and the
    output projection. ``h`` is the already-normed block input (B, T, H).
    Exposed (like ``run_layer_slice``) so families with non-standard block
    structures — the hybrid attention+SSM layers of Falcon-H1
    (reference: contrib/models/Falcon-H1-0.5B-Instruct/src/
    modeling_falcon_h1.py FalconH1DecoderLayer) — can stitch it next to
    their own temporal-mixing blocks.

    ``select`` (B, T, table tokens) bool, phase "paged" with a learned
    sparse selection (:func:`_indexer_block`): the tokens each query
    attends; every other cached token is left out of its softmax.

    ``depth``: the layer's index in the stack (differential attention's
    ``lam_init``). ``cross_kv``, phase "paged": the step's own projected
    ``(k, v)`` (B, T, kv_size) of ANOTHER layer, whose pool ``k_full`` /
    ``v_full`` at ``li`` are: this layer projects a query only
    (``q_proj``), writes nothing and attends over that layer's cache (a
    "cross" layer of ``DecoderSpec.layer_kinds``). ``hand_kv``: hand this
    step's projected ``(k, v)`` back for such layers.

    Returns (attn_h, k_full, v_full, handed): attn_h the post-o_proj
    hidden delta, handed ``hand_kv``'s pair or None."""
    g = spec.gqa
    dtype = h.dtype
    off = spec.norm_offset
    if mixed_local is not None:
        # mixed per-layer cache (gpt-oss): the local/global choice is
        # STATIC per unrolled layer — the local mask is rolling-shaped (W
        # slots) and cannot be where-selected against the global one
        if mixed_local:
            cos, sin, mask = ai["cos_l"], ai["sin_l"], ai["mask_l"]
        else:
            cos, sin, mask = ai["cos"], ai["sin"], ai["mask"]
    elif "cos_l" in ai:
        cos = jnp.where(is_local, ai["cos_l"], ai["cos"])
        sin = jnp.where(is_local, ai["sin_l"], ai["sin"])
        mask = jnp.where(is_local, ai["mask_l"], ai["mask"])
    else:
        cos, sin, mask = ai["cos"], ai["sin"], ai["mask"]
    # a window layer of a stack with a window pool (run_layers_window):
    # its K / V live in the row's ring, written, gathered and masked by
    # window_ring_inputs' arrays
    ring = ai.get("ring") if mixed_local and phase == "paged" else None
    if ring is not None:
        mask = ring["mask"]
    sink = layer_w["sink"] if spec.attn_sink else None

    def _alibi_for(n_kv):
        # kv slot i holds absolute position i on every contiguous path
        if not spec.alibi:
            return None
        return (layer_w["alibi_slopes"],
                jnp.arange(n_kv, dtype=jnp.int32)[None, :])
    handed = None
    latent = spec.mla is not None and phase == "paged"
    if latent:
        # the latent pool: k_full holds a row a token, v_full nothing
        attn_out, k_full = _mla_paged_block(
            spec, h, layer_w, k_full, li, cos, sin, positions, slot_mapping,
            block_table)
    elif spec.mla is not None:
        q, k, v = _mla_qkv(spec, h, layer_w, cos, sin)
    else:
        if cross_kv is not None:
            q = qlinear(h, layer_w["q_proj"])
            if spec.qkv_bias:
                q = q + layer_w["q_bias"]
            (k, v), out_gate = cross_kv, []
        else:
            qkv = qlinear(h, layer_w["qkv_proj"])
            if spec.qkv_bias:
                qkv = qkv + layer_w["qkv_bias"]
            cuts = [spec.q_proj_size, spec.q_proj_size + spec.kv_size]
            if spec.attn_out_gate:
                cuts.append(cuts[-1] + spec.kv_size)  # [q | k | v | gate]
            q, k, v, *out_gate = jnp.split(qkv, cuts, axis=-1)
        if hand_kv:
            handed = (k, v)
        if spec.diff_attn:
            q = _diff_place(q, g.num_q_heads, spec.head_dim).reshape(
                q.shape[:2] + (spec.q_size,))
        q = apply_lora(spec.lora, layer_w, "q_proj", h, q, adapter_ids)
        k = apply_lora(spec.lora, layer_w, "k_proj", h, k, adapter_ids)
        v = apply_lora(spec.lora, layer_w, "v_proj", h, v, adapter_ids)
        if spec.qk_norm_full:
            # olmo2: RMSNorm over the whole projection, pre head-split
            q = rms_norm(q, layer_w["q_norm"], spec.rms_eps, off)
            k = rms_norm(k, layer_w["k_norm"], spec.rms_eps, off)
        if spec.qkv_clip is not None:
            q = jnp.clip(q, -spec.qkv_clip, spec.qkv_clip)
            k = jnp.clip(k, -spec.qkv_clip, spec.qkv_clip)
            v = jnp.clip(v, -spec.qkv_clip, spec.qkv_clip)
        # CP prefill: Q seq-sharded over "cp", KV forced seq-replicated —
        # GSPMD then emits the all-gather-KV pattern of the reference
        # (attention_base.py:548-563)
        q_seq_axis = AXIS_CP if (spec.cp_prefill and phase == "prefill") else None
        q = _shard(_split_heads(q, g.num_q_heads, spec.head_dim),
                   AXIS_DP, q_seq_axis, AXIS_MP, None)
        k = _shard(_split_heads(k, g.num_kv_heads, spec.head_dim), AXIS_DP, None, AXIS_MP, None)
        v = _shard(_split_heads(v, g.num_kv_heads, spec.head_dim), AXIS_DP, None, AXIS_MP, None)
        if spec.qk_norm and not spec.qk_norm_after_rope:
            if spec.qk_norm_type == "layernorm":
                q = layer_norm(q, layer_w["q_norm"], layer_w["q_norm_b"],
                               spec.rms_eps)
                k = layer_norm(k, layer_w["k_norm"], layer_w["k_norm_b"],
                               spec.rms_eps)
            else:
                q = rms_norm(q, layer_w["q_norm"], spec.rms_eps, off)
                k = rms_norm(k, layer_w["k_norm"], spec.rms_eps, off)
        q = apply_rope(q, cos, sin, interleaved=spec.rope_interleaved)
        k = apply_rope(k, cos, sin, interleaved=spec.rope_interleaved)
        if spec.qk_norm and spec.qk_norm_after_rope:
            if spec.qk_norm_type == "layernorm":
                q = layer_norm(q, layer_w["q_norm"], layer_w["q_norm_b"],
                               spec.rms_eps)
                k = layer_norm(k, layer_w["k_norm"], layer_w["k_norm_b"],
                               spec.rms_eps)
            else:
                q = rms_norm(q, layer_w["q_norm"], spec.rms_eps, off)
                k = rms_norm(k, layer_w["k_norm"], spec.rms_eps, off)
        if spec.qk_l2_norm:
            # llama4: weightless L2 norm AFTER rope, rope (local) layers only
            def _l2(x):
                xf = x.astype(jnp.float32)
                n = xf * jax.lax.rsqrt(
                    jnp.mean(xf * xf, axis=-1, keepdims=True) + spec.rms_eps)
                return n.astype(x.dtype)
            if spec.layer_pattern is not None:
                q = jnp.where(is_local, _l2(q), q)
                k = jnp.where(is_local, _l2(k), k)
            else:
                q, k = _l2(q), _l2(k)
        if spec.attn_temp is not None:
            # llama4 NoPE temperature tuning (reference:
            # modeling_llama4_text.py attn_temperature_tuning; HF
            # attn_scales = log1p(floor((pos+1)/floor_scale))*scale + 1)
            floor_scale, a_scale = spec.attn_temp
            pos_f = positions.astype(jnp.float32)
            scales = (jnp.log1p(jnp.floor((pos_f + 1.0) / floor_scale))
                      * a_scale + 1.0)[:, :, None, None]
            q_t = (q.astype(jnp.float32) * scales).astype(q.dtype)
            q = jnp.where(is_local, q, q_t) \
                if spec.layer_pattern is not None else q_t

    if latent:
        pass            # attended above, over the latent pool
    elif phase == "paged":
        from ..modules import block_kv_cache as bkv
        # a pool with more head slots than the model has kv heads
        # (bkv.pool_kv_heads): q, k and v grow zero heads to match, and the
        # output drops them again
        n_q = q.shape[2]
        pool_heads = k_full.shape[3] * (k_full.shape[4] // spec.head_dim)
        # the engagement record names the page as allocated (bkv.pool_page)
        kernel_mode.note(
            "kv_pool", "xla",
            f"page={k_full.shape[3]}x{k_full.shape[4]} heads={pool_heads}"
            f"x{spec.head_dim}")
        grown = pool_heads != k.shape[2]
        if grown:
            def grow(x):
                more = (pool_heads - k.shape[2]) * (x.shape[2] // k.shape[2])
                return jnp.pad(x, ((0, 0), (0, 0), (0, more), (0, 0)))
            q, k, v = grow(q), grow(k), grow(v)
        write_at = slot_mapping if ring is None else ring["slots"]
        read_table = block_table if ring is None else ring["table"]
        if cross_kv is None:
            k_full = bkv.write_slots_at_layer(
                k_full, kv.quantize_kv(k, k_full.dtype, spec.kv_scale), li,
                write_at)
            v_full = bkv.write_slots_at_layer(
                v_full, kv.quantize_kv(v, v_full.dtype, spec.kv_scale), li,
                write_at)
        # ragged paged decode kernel (reference: DMA-skipping TKG attention
        # over the block layout, attention_base.py:1186-1382): reads only
        # each row's LIVE pages through the block table — the gather path
        # below materializes the whole table per layer per token. Default-on
        # for single-token paged decode (decode_kernel None/True).
        use_pkernel = False
        # the layer's window and where it reads: static per kind under a
        # window pool's walk (a window layer's table is the row's ring as
        # LOGICAL pages, and a kernel reads none in front of the window's
        # first), traced under a layer_pattern on one pool
        if mixed_local is not None:
            win = jnp.asarray(
                spec.sliding_window if mixed_local else 0, jnp.int32)
            win_note = (f" window={spec.sliding_window} ring="
                        f"{ring['pages']}" if mixed_local else " window=0")
        elif spec.layer_pattern is not None:
            win = jnp.where(is_local, spec.sliding_window, 0)
            win_note = f" window={spec.sliding_window} by layer"
        else:
            win = jnp.asarray(spec.sliding_window, jnp.int32)
            win_note = f" window={spec.sliding_window}"
        kernel_table = block_table if ring is None else ring["kernel_table"]
        # differential attention rides the plain grouped-query call: the
        # record says so, and whether the layer reads another layer's pool
        diff_note = ((" form+=diff pairs placed in halves of a kv row"
                      if spec.diff_attn else "")
                     + (" cross: no write, another layer's pool"
                        if cross_kv is not None else ""))
        if h.shape[1] == 1:
            # every decline leaves a note (ops/kernel_mode.py): a decode
            # graph on the full-table gather path is never a silent choice
            declined = _paged_kernel_declined(spec)
            if not declined:
                kernel_out = decode_attention.paged_dispatch(
                    q[:, 0], k_full, v_full, k[:, 0], v[:, 0], li,
                    positions[:, 0], kernel_table, scale=spec.scale,
                    window=win, soft_cap=spec.attn_soft_cap, sink=sink,
                    kv_scale=spec.kv_scale,
                    select=None if select is None else select[:, 0],
                    interpret=kernel_mode.pallas_interpret())
                if kernel_out is None:
                    declined = "kv heads not shardable over the mp axes"
                else:
                    use_pkernel = True
                    attn_out = kernel_out[:, None]
            # ... and an engaged kernel says what it runs with: pages a
            # compute block, a shard's heads, the head form, and whether
            # heads that share a row are stored so
            kernel_mode.note("paged_decode",
                             "xla" if declined else kernel_mode.kernel_path(),
                             declined or decode_attention.paged_dispatch_plan(
                                 q.shape[2], spec.head_dim, k_full,
                                 block_table.shape[1]).note(
                                     k_full.shape[4] != spec.head_dim)
                             + ("" if mixed_local is None else win_note)
                             + diff_note)
            if select is not None:
                kernel_mode.note(
                    "sparse_attn", "xla" if declined
                    else kernel_mode.kernel_path(),
                    "masked: the live pages' walk, a token attended where "
                    "selected, a decode step"
                    + (f" ({declined}: the table gathered)" if declined
                       else ""))
        else:
            # a chunk walks its rows' live pages on the prefill kernel
            # (ops/paged_prefill.py), its own K / V read from the pool they
            # were just written to; what it declines (and says so) gathers
            # the table below
            attn_out = paged_prefill.chunk_attention(
                spec, q, k_full, v_full, li, positions, kernel_table, win,
                win_note + diff_note, select=select)
            use_pkernel = attn_out is not None
        if not use_pkernel:
            def gathered_mha(q_, bt_, mask_):
                def gathered(pool):
                    # a pool with several heads to a slot (bkv.pool_page):
                    # the lanes of the GATHERED rows split into heads
                    rows = bkv.gather_layer_kv(pool, li, bt_)
                    return kv.dequantize_kv(
                        rows.reshape(rows.shape[:2] + (-1, spec.head_dim)),
                        dtype, spec.kv_scale)
                k_all, v_all = gathered(k_full), gathered(v_full)
                return attn_ops.mha(q_, k_all, v_all, mask_, spec.scale,
                                    logits_soft_cap=spec.attn_soft_cap,
                                    sink=sink,
                                    alibi=_alibi_for(k_all.shape[1]))

            # the float32 scores of a chunk are rows x a shard's heads x
            # width x table tokens: where they outgrow the budget (a
            # full-batch chunk of a wide batch with many heads) the rows go
            # through in groups, one after another, so the temps stay a
            # group's worth
            if select is not None:
                mask = mask & select
            if spec.alibi:
                attn_out = gathered_mha(q, read_table, mask)
            else:
                attn_out = map_row_groups(
                    gathered_mha, 4 * (g.num_q_heads // g.tp) * q.shape[1]
                    * read_table.shape[1] * k_full.shape[2],
                    q, read_table, mask)
        if grown:
            attn_out = attn_out[:, :, :n_q]
    elif phase == "prefill":
        # flash kernel requirements beyond supports(): per-row positions must
        # be arange (the kernel rebuilds causality from array indices — an
        # offset/chunked prefill must use the mask path), and the
        # window/sink must be uniform across layers (static kernel).
        # dispatch_prefill shard_maps over the model-parallel axes for tp>1.
        kernel_out = None
        if (spec.flash_prefill and arange_positions
                and spec.layer_pattern is None and not spec.attn_sink
                and not spec.alibi
                and not spec.bidir_image_attn
                and spec.mla is None and not spec.cp_prefill
                and not spec.seq_parallel
                and flash_attention.supports(
                    q.shape[1], spec.head_dim, has_sink=False, chunk=0)):
            kernel_out = flash_attention.dispatch_prefill(
                q, k, v, scale=spec.scale, causal=True,
                window=spec.sliding_window, soft_cap=spec.attn_soft_cap,
                interpret=kernel_mode.pallas_interpret())
            kernel_mode.note(
                "flash_prefill",
                "xla" if kernel_out is None else kernel_mode.kernel_path(),
                "heads not shardable over the mp axes"
                if kernel_out is None else "")
        if kernel_out is not None:
            attn_out = kernel_out
        else:
            # prefill kv positions = the window's own positions
            al = ((layer_w["alibi_slopes"], positions)
                  if spec.alibi else None)
            attn_out = attn_ops.mha(q, k, v, mask, spec.scale,
                                    logits_soft_cap=spec.attn_soft_cap,
                                    sink=sink, alibi=al)
        if spec.rolling_window and prefill_lens is not None:
            # rolling prefill write: only the LAST w positions of each row
            # land (earlier ones would alias the same slots and the scatter
            # order is undefined); padded positions past seq_len are dropped
            # so they cannot clobber live slots through the modulo
            w_c = k_full.shape[4]
            valid = ((positions >= prefill_lens[:, None] - w_c)
                     & (positions < prefill_lens[:, None]))
            eff = jnp.where(valid, positions % w_c, k_full.shape[4] + 1)
            k_full = kv.write_tokens_at_layer(
                k_full, kv.quantize_kv(k, k_full.dtype, spec.kv_scale),
                li, seq_ids, eff, k_transposed=True)
            v_full = kv.write_tokens_at_layer(
                v_full, kv.quantize_kv(v, v_full.dtype, spec.kv_scale),
                li, seq_ids, eff)
        else:
            k_full = kv.write_prefill_at_layer(
                k_full, kv.quantize_kv(k, k_full.dtype, spec.kv_scale),
                li, seq_ids,
                identity_seq_ids=identity_seq_ids and arange_positions,
                k_transposed=True)
            v_full = kv.write_prefill_at_layer(
                v_full, kv.quantize_kv(v, v_full.dtype, spec.kv_scale),
                li, seq_ids,
                identity_seq_ids=identity_seq_ids and arange_positions)
    else:
        roll_w = (k_full.shape[4]
                  if (spec.rolling_window or mixed_local) else 0)
        k_full = kv.write_tokens_at_layer(
            k_full, kv.quantize_kv(k, k_full.dtype, spec.kv_scale),
            li, seq_ids, positions, window=roll_w, k_transposed=True)
        v_full = kv.write_tokens_at_layer(
            v_full, kv.quantize_kv(v, v_full.dtype, spec.kv_scale),
            li, seq_ids, positions, window=roll_w)
        use_kernel = (not mixed_local
                      and not spec.alibi
                      and spec.decode_kernel is not False
                      and decode_attention.supports(spec, h.shape[1])
                      and not spec.rolling_window
                      and identity_seq_ids
                      and h.shape[0] == k_full.shape[1]
                      and not spec.flash_decoding)
        if use_kernel and spec.decode_kernel is None:
            # auto admission (reference analog: flash-strategy heuristics,
            # attention_base.py:985-1034): the kernel wins where the XLA
            # path must stream cache slots the mask discards anyway —
            # sliding-window / alternating-local patterns and learned-sink
            # softmax (XLA's sink path pays a concat + second softmax).
            # Plain full attention with kv_view-bucketed reads measured
            # FASTER on the XLA path (v5e: 0.148 vs 0.231 ms/step at
            # S=1024 full-live), so auto keeps it off there.
            use_kernel = (spec.attn_sink or spec.sliding_window > 0
                          or spec.layer_pattern is not None)
        if use_kernel:
            # fused Pallas decode attention over the stacked cache: reads
            # only the live prefix of each row (DMA block elision) and folds
            # the active token in-registers — the cache row written above is
            # masked out (kpos < pos), so write order is irrelevant.
            # dispatch() shard_maps over the mesh's dp/mp axes for tp>1.
            if spec.layer_pattern is not None:
                win = jnp.where(is_local, spec.sliding_window, 0)
            else:
                win = jnp.asarray(spec.sliding_window, jnp.int32)
            kernel_out = decode_attention.dispatch(
                q[:, 0], k_full, v_full, k[:, 0], v[:, 0], li,
                positions[:, 0], scale=spec.scale, window=win,
                soft_cap=spec.attn_soft_cap, sink=sink,
                kv_scale=spec.kv_scale,
                interpret=kernel_mode.pallas_interpret())
            if kernel_out is None:        # heads not shardable on this mesh
                use_kernel = False
                kernel_mode.note("decode", "xla",
                                 "kv heads not shardable over the mp axes")
            else:
                attn_out = kernel_out[:, None]
                kernel_mode.note("decode", kernel_mode.kernel_path())
        if not use_kernel:
            # native-layout reads: K transposed (B, H, D, S), V (B, H, S,
            # D) — each attention einsum contracts its operand in place
            # (any shared layout costs a materialized relayout of the live
            # cache per layer per step)
            view = kv_view if (kv_view is not None
                               and kv_view < v_full.shape[3]) else None
            if isinstance(li, int) and view is not None:
                # decode unrolls layers with static indices: fold the layer
                # AND seq-bucket slice into ONE static slice so XLA stages
                # only the live prefix (two chained slices staged the full
                # row first — measured 2x the staging bytes)
                lb, hb, db = (k_full.shape[1], k_full.shape[2],
                              k_full.shape[3])
                k_layer = jax.lax.slice(
                    k_full, (li, 0, 0, 0, 0),
                    (li + 1, lb, hb, db, view))[0]       # (B, H, D, view)
                v_layer = jax.lax.slice(
                    v_full, (li, 0, 0, 0, 0),
                    (li + 1, lb, hb, view, v_full.shape[4]))[0]
            else:
                k_layer = kv.read_layer_hl(k_full, li)   # (B, H, D, S)
                v_layer = kv.read_layer_hl(v_full, li)   # (B, H, S, D)
                if view is not None:
                    # decode seq bucket: read only the live prefix (the mask
                    # is built against the same kv_view length)
                    k_layer = k_layer[:, :, :, :view]
                    v_layer = v_layer[:, :, :view]
            if identity_seq_ids and h.shape[0] == k_full.shape[1]:
                # static guarantee that seq_ids == arange (no continuous
                # batching): skip the row-gather copy of the whole cache
                k_all = kv.dequantize_kv(k_layer, dtype, spec.kv_scale)
                v_all = kv.dequantize_kv(v_layer, dtype, spec.kv_scale)
            else:
                k_all = kv.dequantize_kv(
                    kv.gather_cache_rows(k_layer, seq_ids), dtype,
                    spec.kv_scale)
                v_all = kv.dequantize_kv(
                    kv.gather_cache_rows(v_layer, seq_ids), dtype,
                    spec.kv_scale)
            attn_out = attn_ops.mha_hl(q, k_all, v_all, mask, spec.scale,
                                       logits_soft_cap=spec.attn_soft_cap,
                                       sink=sink,
                                       alibi=_alibi_for(v_all.shape[2]))

    if spec.diff_attn:
        attn_out = _diff_combine(attn_out, layer_w, depth)
    attn_out = attn_out.reshape(h.shape[0], h.shape[1], -1)
    if spec.attn_out_gate and spec.mla is None:
        attn_out = (attn_out * jax.nn.sigmoid(
            out_gate[0].astype(jnp.float32))).astype(attn_out.dtype)
    if spec.mla is not None and spec.mla.head_gate:
        # one gate a head, off the block's normed input
        gate = jax.nn.sigmoid(jnp.einsum(
            "bth,hn->btn", h, layer_w["g_proj"],
            preferred_element_type=jnp.float32))
        attn_out = (attn_out.reshape(gate.shape + (-1,))
                    * gate[..., None]).astype(attn_out.dtype).reshape(
                        attn_out.shape)
    h = _row_parallel_out(spec, attn_out, layer_w["o_proj"], phase)
    if spec.mla is None:
        h = apply_lora(spec.lora, layer_w, "o_proj", attn_out, h, adapter_ids)
    if spec.o_bias:
        h = h + layer_w["o_bias"]
    return h, k_full, v_full, handed


#: the attention half of a layer that WRITES a cache, under its profiler
#: scope; a layer that reads another layer's cache runs the same body under
#: ``cross_attn``, a sibling (``run_layers_ssm``)
_attn_block = jax.named_scope("attn")(_attn_body)


def _deepstack_add(hidden, deepstack, deepstack_mask):
    """Add this layer's deepstack visual features at the image-token
    positions (reference: qwen3-vl deepstack, models/model_base.py:1374-1387;
    layers past the deepstack depth carry zeros)."""
    if deepstack is None or deepstack_mask is None:
        return hidden
    gi = jnp.clip(jnp.cumsum(deepstack_mask, axis=1) - 1, 0,
                  deepstack.shape[1] - 1)
    img = jnp.take_along_axis(deepstack.astype(hidden.dtype),
                              gi[..., None], axis=1)
    return hidden + jnp.where(deepstack_mask[..., None], img, 0)


#: what a recurrent/hybrid stack (``spec.ssm``) cannot do, by mechanism (its
#: attention layers may keep K / V pages or, under ``spec.mla``, a latent pool
#: beside the state slots: the table holds for both) —
#: the ONE table the model code (``run_layers``, ``run_layers_ssm``, the
#: verify / ragged / multi-token steps), ``spec_from_config`` and the serving
#: adapter refuse from, through :func:`refuse_recurrent`.
RECURRENT_UNSUPPORTED = {
    "prefix caching": "reusing a cached prefix needs a snapshot of the "
                      "recurrent state at the block boundary; only KV (or "
                      "a latent pool's rows) is kept per block",
    "speculation": "verifying a draft window needs a state step over "
                   "several tokens that can be rolled back to the accepted "
                   "one",
    "ragged dispatch": "a row mixing chunk and decode widths needs a "
                       "multi-token state step per row",
    "multi-token decode": "a recurrent stack decodes one token a step",
    "fused decode loop": "the in-graph slot advance treats every row as "
                         "live; state slots need their dead rows masked",
    "flash decoding": "the KV-sequence shard has no recurrent counterpart",
    "continuous batching": "the contiguous cache addresses state rows by "
                           "batch row, not by seq_id; serve through the "
                           "paged path, which has per-sequence state slots",
    "sequence parallelism": "the scan over time runs on one shard",
    "windowed context encoding": "only the paged path continues a chunk "
                                 "from the carried state",
    "tensor capture/replacement": "the recurrent walk has no tap points",
    "deepstack": "the recurrent walk adds no per-layer visual features",
    "sandwich norm": "the recurrent walk is the pre-norm residual block "
                     "or the post-norm one (norm_position 'post': a norm "
                     "on each sub-block's output); norms on BOTH sides of "
                     "a sub-block have not been walked",
    "paged parallel hybrid": "a layer running attention NEXT TO its mixer "
                             "(ssm_parallel) has not been walked on the "
                             "paged path",
    "paged rglru state": "the rglru block prefills from zero; only the "
                         "mamba2, gated_delta, kda, mamba1 and shortconv "
                         "kinds continue from a carried state and conv "
                         "tail",
    "host KV spill / handoff": "a spilled or handed-off block carries KV "
                               "only, not the state that goes with it",
    "contiguous decoder-hybrid-decoder": "layers that read another layer's "
                                         "cache or another layer's scan "
                                         "output (DecoderSpec.layer_kinds) "
                                         "are walked on the paged path only",
    "sharded decoder-hybrid-decoder": "the cross layers' and the Gated "
                                      "Memory Units' weights and the "
                                      "Mamba-1 state are replicated: the "
                                      "stack has run at tp = 1 only",
}


def _refusal(table, lead: str, asked) -> Optional[str]:
    asked = [a for a in asked if a]
    if not asked:
        return None
    return lead + "; ".join(f"{a} ({table[a]})" for a in asked)


def recurrent_refusal(asked) -> Optional[str]:
    """The sentence that names every entry of ``asked`` (keys of
    :data:`RECURRENT_UNSUPPORTED` a caller found switched on; falsy entries
    are skipped) with its reason, or None where nothing was asked."""
    return _refusal(RECURRENT_UNSUPPORTED,
                    "a recurrent/hybrid (SSM) stack does not support: ",
                    asked)


def refuse_recurrent(asked) -> None:
    """Raise NotImplementedError with :func:`recurrent_refusal`'s sentence;
    nothing asked, nothing raised."""
    why = recurrent_refusal(asked)
    if why:
        raise NotImplementedError(why)


#: what a stack with a WINDOW POOL (``DecoderSpec.window_pool``: the window
#: layers' KV in a ring a batch slot, ``block_kv_cache.window_pool_spec``)
#: cannot do, by mechanism - the one table ``spec_from_config``, the verify /
#: ragged / multi-token steps and the serving adapter refuse from, through
#: :func:`refuse_window_pool`.
WINDOW_POOL_UNSUPPORTED = {
    "prefix caching": "a ring is overwritten as its row advances: a hit "
                      "would need the window's keys at the hit point, and "
                      "only the global layers' blocks keep theirs",
    "speculation": "a draft window that is rolled back has already "
                   "overwritten the ring slots of the window's oldest keys",
    "ragged dispatch": "a row mixing chunk and decode widths needs the ring "
                       "gathered by row kind",
    "multi-token decode": "the ring is written one token or one chunk a "
                          "row a step",
    "fused decode loop": "the in-graph slot advance computes the global "
                         "pool's slots only",
    "host KV spill / handoff": "a spilled or handed-off block carries the "
                               "global layers' KV only, not the ring that "
                               "goes with it",
    "tensor parallelism": "the ring pool and its slot arithmetic have run "
                          "on one chip only (tp = 1)",
    "tensor capture/replacement": "the walk by layer kind has no tap points",
    "deepstack": "the walk by layer kind adds no per-layer visual features",
    "alibi": "a ring's gathered keys are not at their absolute slots",
}


def window_pool_refusal(asked) -> Optional[str]:
    """The sentence that names every entry of ``asked`` (keys of
    :data:`WINDOW_POOL_UNSUPPORTED` a caller found switched on; falsy
    entries are skipped) with its reason, or None where nothing was asked."""
    return _refusal(WINDOW_POOL_UNSUPPORTED,
                    "a stack whose window layers keep a ring of pages a "
                    "batch slot (window pool) does not support: ", asked)


def refuse_window_pool(asked) -> None:
    """Raise NotImplementedError with :func:`window_pool_refusal`'s
    sentence; nothing asked, nothing raised."""
    why = window_pool_refusal(asked)
    if why:
        raise NotImplementedError(why)


def window_ring_inputs(spec: DecoderSpec, pool_w, batch_slots: int,
                       position_ids, slot_mapping, block_table,
                       state_slots=None) -> Dict[str, Any]:
    """What a window layer of a paged step reads its row's RING by
    (``block_kv_cache.window_pool_spec``; ``pool_w`` one of its two arrays,
    ``(layers, slots x R, block, ...)``), computed once a step on the
    device from the step's positions - no second table crosses from the
    host. Row ``i`` owns slot ``state_slots[i]`` (None: row ``i`` is slot
    ``i``, the full-batch step):

    * ``slots`` (B,T): where each token is written, ring page ``(pos //
      block) % R`` of the row's slot; a token the global pool drops
      (``slot_mapping`` < 0: padding, a dead row) is dropped here too;
    * ``kernel_table`` (B, table width): the decode kernel's LOGICAL table,
      entry ``j`` = ``slot x R + j % R`` (it reads from the window's first
      page to the last live one, at most ``window / block + 1`` < R pages);
    * ``table`` (B,R) and ``mask`` (B,T,R x block): the gathered form of a
      chunk (and of a decode step the kernel declines): the ``R`` logical
      pages that end at the row's last page of this step, and for each
      query which of their keys it sees (written, causal, inside the
      window)."""
    b, t = position_ids.shape
    bs = pool_w.shape[2]
    ring = pool_w.shape[1] // batch_slots
    if state_slots is None:
        if b != batch_slots:
            raise ValueError(
                f"a paged step of {b} rows over {batch_slots} ring slots "
                "needs state_slots (one slot index a row); without it row "
                "i is slot i")
        slot = jnp.arange(b, dtype=jnp.int32)
    else:
        slot = state_slots.astype(jnp.int32)
    base = slot[:, None] * ring
    pos = position_ids.astype(jnp.int32)
    live = slot_mapping >= 0
    slots = jnp.where(live, (base + (pos // bs) % ring) * bs + pos % bs, -1)
    j = jnp.arange(block_table.shape[1], dtype=jnp.int32)
    last_page = jnp.max(jnp.where(live, pos, 0), axis=1) // bs
    logical = (last_page[:, None] - (ring - 1)
               + jnp.arange(ring, dtype=jnp.int32)[None, :])       # (B, R)
    kpos = (logical[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(b, -1)
    return {"pages": ring, "slots": slots,
            "kernel_table": base + (j % ring)[None, :],
            "table": base + jnp.mod(logical, ring),
            "mask": attn_ops.causal_mask(pos, kpos, kpos >= 0,
                                         window=spec.sliding_window)}


def _pattern_period(pattern) -> int:
    """The shortest period of a per-layer pattern: the least ``p`` with
    ``pattern[i] == pattern[i % p]`` throughout (its length where it does
    not repeat). ``spec_from_config`` refuses a window pool whose period
    does not divide the depth."""
    n = len(pattern)
    return next(p for p in range(1, n + 1)
                if all(pattern[i] == pattern[i % p] for i in range(n)))


def _split_stack(spec: DecoderSpec, stack, tokens: int, experts: bool):
    """Which leaves of the stacked ``stack`` a layer loop over a step of
    ``tokens`` tokens slices a layer out of, and which stay where they lie:
    those ``moe.stack_leaves`` names (a custom call reads the layer in
    place and would be handed a copy of a slice), none of a stack without
    ``experts``. Returns (the leaves to slice, ``of_stack(layer_w, i)``:
    layer ``i``'s slices joined by a ``LayerOfStack`` for each leaf that
    stayed)."""
    held = moe_mod.stack_leaves(spec.moe, tokens, stack) if experts else ()

    def of_stack(layer_w, i):
        return {**layer_w, **{k: moe_mod.LayerOfStack(stack[k], i)
                              for k in held}}

    return {k: a for k, a in stack.items() if k not in held}, of_stack


def scan_layers(spec: DecoderSpec, stack, carry, block, *, steps: int,
                phase: str, slot_mapping=None, experts: bool = True,
                per_step: int = 1, xs=()):
    """The ONE scan of the layer walks: ``steps`` steps of ``block`` over
    ``carry`` (its first leaf the hidden states, (B, T, H)), ``per_step``
    layers of the stacked leaves ``stack`` a step (``experts`` False: a run
    of dense layers). What every scanned walk decides alike is decided here:

    * which leaves stay in their stack (:func:`_split_stack`);
    * how a layer gets the others, ``layer_w(j)``, the step's ``j``-th
      layer: with one layer a step its slice is the scan's ``xs`` (XLA
      fuses it into the consumer); with several each is indexed out of the
      stack by itself (scanned as ``(steps, per_step, ...)`` the whole
      step's weights would be materialised first: PERF.md section 6, PR 40);
    * the rows a routing tally counts, ``live``: ``slot_mapping >= 0`` on a
      paged T = 1 step over expert layers, else nothing is counted;
    * the tally's way out: ``moe_kw`` (``tally`` / ``live``, for
      ``moe_block`` through ``_mlp_block`` or ``_layer_body``) collects it
      and it joins the step's outputs as ``moe_tally``.

    ``block(carry, layer_w, l, x, moe_kw) -> (carry, outputs)`` with ``l``
    the step and ``x`` the step's slice of ``xs``. Returns (carry, the
    outputs stacked a step)."""
    b, t = jax.tree.leaves(carry)[0].shape[:2]
    experts = experts and spec.moe is not None
    sliced, of_stack = _split_stack(spec, stack, b * t, experts)
    live = (slot_mapping >= 0 if experts and phase == "paged" and t == 1
            else None)

    def body(carry, step):
        w_l, x, l = step

        def layer_w(j=0):
            i = l * per_step + j
            return of_stack(w_l if per_step == 1 else {
                k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                for k, a in sliced.items()}, i)

        tally = None if live is None else []
        carry, outs = block(carry, layer_w, l, x,
                            {"tally": tally, "live": live})
        if tally:
            outs = {**outs, "moe_tally": sum(tally[1:], tally[0])}
        return carry, outs

    return jax.lax.scan(
        body, carry, (sliced if per_step == 1 else None, xs,
                      jnp.arange(steps, dtype=jnp.int32)))


def run_layers_window(spec: DecoderSpec, params, cache, hidden, ai,
                      positions, *, slot_mapping, block_table,
                      adapter_ids=None):
    """The paged walk of a stack with a WINDOW POOL
    (``DecoderSpec.window_pool``), static per layer kind: a scan over the
    periods of ``layer_pattern`` (:func:`scan_layers`) whose block holds one
    period's layers (one global and three window layers for SmallThinker).
    A global layer reads and writes ``cache["k"] / ["v"]`` through the
    allocator's table as every paged stack does, with the global mask and
    (``nope_global``) no rotary; a window layer its slot's ring in
    ``cache["k_w"] / ["v_w"]`` by ``ai["ring"]``
    (:func:`window_ring_inputs`) with the local rotary. Global layer ``g``
    of period ``l`` is cache layer ``l x globals + g`` of its pool, window
    layers likewise. Returns (hidden, cache, per-layer outputs: a period a
    row)."""
    pat = spec.layer_pattern
    period = _pattern_period(pat)
    kinds = tuple(bool(x) for x in pat[:period])
    n_w = sum(kinds)
    n_g = period - n_w
    kw_pool = cache["k_w"]
    page_bytes = (kw_pool.shape[2] * kw_pool.shape[3] * kw_pool.shape[4]
                  * kw_pool.dtype.itemsize * 2)
    ring = ai["ring"]["pages"]
    kernel_mode.note(
        "kv_window_pool", "xla",
        f"layers global={spec.num_layers - spec.num_window_layers} "
        f"window={spec.num_window_layers} window_tokens="
        f"{spec.sliding_window} ring_pages={ring} ring_bytes_a_row="
        f"{spec.num_window_layers * ring * page_bytes} global_pool_bytes="
        f"{2 * cache['k'].size * cache['k'].dtype.itemsize} "
        f"window_pool_bytes={2 * kw_pool.size * kw_pool.dtype.itemsize}")

    def block(carry, layer_w, l, _, moe_kw):
        x, kg, vg, kw_, vw_ = carry
        seen = {False: 0, True: 0}
        for j, local in enumerate(kinds):
            w = layer_w(j)
            ci = l * (n_w if local else n_g) + seen[local]
            seen[local] += 1
            kf, vf = (kw_, vw_) if local else (kg, vg)
            x, kf, vf, _ = _layer_body(
                spec, x, w, kf, vf, ci, ai, jnp.asarray(local), None,
                positions, "paged",
                slot_mapping=slot_mapping, block_table=block_table,
                adapter_ids=adapter_ids, mixed_local=local, **moe_kw)
            if local:
                kw_, vw_ = kf, vf
            else:
                kg, vg = kf, vf
        return (x, kg, vg, kw_, vw_), {}

    (hidden, kg, vg, kw_, vw_), caps = scan_layers(
        spec, params["layers"],
        (hidden, cache["k"], cache["v"], kw_pool, cache["v_w"]), block,
        steps=spec.num_layers // period, per_step=period, phase="paged",
        slot_mapping=slot_mapping)
    return hidden, {**cache, "k": kg, "v": vg, "k_w": kw_, "v_w": vw_}, caps


#: what a stack with a LEARNED SPARSE SELECTION (``DecoderSpec.sparse``: an
#: indexer over a third paged pool, :class:`SparseSpec`) does not run under
#: yet, by mechanism - the one table ``spec_from_config``, the verify /
#: ragged / multi-token steps and the serving adapter refuse from, through
#: :func:`refuse_sparse`.
SPARSE_UNSUPPORTED = {
    "speculation": "verifying a draft window selects for each drafted "
                   "token against keys the window itself writes; the "
                   "verify step has not been walked with an indexer",
    "ragged dispatch": "a row mixing chunk and decode widths needs the "
                       "selection by row kind",
    "fused decode loop": "the in-graph slot advance computes the K / V "
                         "pools' slots only, not the index keys' rows",
    "tensor parallelism": "the index-key pool and the selection have run "
                          "on one chip only (tp = 1)",
    "host KV spill / handoff": "a spilled or handed-off block carries K "
                               "and V only, not the index keys that go "
                               "with it",
    "contiguous cache": "the index keys are paged on the K / V pools' "
                        "block table; serve through the paged path "
                        "(is_block_kv_layout)",
    "tensor capture/replacement": "the walk with an indexer has no tap "
                                  "points",
    "deepstack": "the walk with an indexer adds no per-layer visual "
                 "features",
    "other attention forms": "the selection masks plain rotary GQA "
                             "attention over one pool: no window, chunk, "
                             "sink, alibi, latent attention, recurrent "
                             "layers, sub-blocks or mixed dense / expert "
                             "stacks",
}


def sparse_refusal(asked) -> Optional[str]:
    """The sentence that names every entry of ``asked`` (keys of
    :data:`SPARSE_UNSUPPORTED` a caller found switched on; falsy entries
    are skipped) with its reason, or None where nothing was asked."""
    return _refusal(SPARSE_UNSUPPORTED,
                    "a stack with a learned sparse selection (an indexer "
                    "over a pool of index keys) does not support: ", asked)


def refuse_sparse(asked) -> None:
    """Raise NotImplementedError with :func:`sparse_refusal`'s sentence;
    nothing asked, nothing raised."""
    why = sparse_refusal(asked)
    if why:
        raise NotImplementedError(why)


def _float_order_keys(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf`` the
    least, ``+inf`` the greatest; ``-0.0`` is taken as ``+0.0`` first, as a
    comparison of floats takes it); every finite float maps above 0."""
    x = jnp.where(x == 0, jnp.zeros((), jnp.float32), x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_select(scores, valid, k: int):
    """Which of ``scores`` (..., S) float32 are among the ``k`` largest of
    the ``valid`` (..., S) ones a row, EXACTLY: ties go to the lower index,
    as ``jax.lax.top_k`` breaks them, and a row with at most ``k`` valid
    entries keeps them all. Returns bool (..., S).

    The k-th largest is found by its BIT PATTERN (:func:`_float_order_keys`),
    four bits a pass: a pass counts the entries at or above each of 15
    candidate prefixes and keeps the largest that still has ``k``; eight
    counting passes over the scores, where ``jax.lax.top_k`` at k = 2048 is
    a sort of the whole row on a TPU. The tie rule costs a running count
    along the row, taken only where some row has more entries AT its
    threshold than it has room for (with float32 scores of real
    activations: exact zeros, where every index head's ReLU is shut)."""
    key = jnp.where(valid, _float_order_keys(scores), jnp.uint32(0))
    prefix = jnp.zeros(key.shape[:-1], jnp.uint32)
    steps = jnp.arange(1, 16, dtype=jnp.uint32)
    for shift in range(28, -1, -4):
        cands = prefix[..., None] | (steps << shift)              # (..., 15)
        count = jnp.sum(key[..., None, :] >= cands[..., :, None], axis=-1,
                        dtype=jnp.int32)
        held = jnp.sum(count >= k, axis=-1, dtype=jnp.int32)
        prefix = prefix | (held.astype(jnp.uint32) << shift)
    tau = prefix[..., None]
    above = key > tau
    at = (key == tau) & valid
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(at, axis=-1, dtype=jnp.int32) > room)
    return above | jax.lax.cond(
        crowded,
        lambda: at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32)
                      <= room[..., None]),
        lambda: at)


def _index_scores(sp: SparseSpec, qi, w, rows):
    """``I[t, s]`` of a group of rows: ``qi`` (B, T, heads, index_dim) the
    rotated index queries, ``w`` (B, T, heads) their weights, ``rows`` (B,
    pages, page rows, lanes) the row's pages of the index-key pool as they
    lie (``block_kv_cache.index_page``: ``lanes // index_dim`` tokens a
    row). Returns (B, T, pages x block) float32, column ``s`` the token at
    position ``s``. The products are taken from the operands as stored
    (bfloat16 on the chip) with float32 sums; ReLU, weights and the sum
    over heads in float32."""
    b, t, nj, dim = qi.shape
    _, pages, prow, lanes = rows.shape
    fold = lanes // dim
    if fold > 1:
        # a segment's queries in its lanes, zeros in the neighbours'
        seg = jnp.arange(fold)[:, None] == jnp.arange(fold)[None, :]
        qi = jnp.where(seg[None, None, None, :, :, None],
                       qi[:, :, :, None, None, :],
                       jnp.zeros((), qi.dtype)).reshape(b, t, nj, fold, lanes)
    else:
        qi = qi[:, :, :, None, :]
    dots = jnp.einsum("btjgl,bprl->btjgpr", qi, rows.astype(qi.dtype),
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots)
                     * w.astype(jnp.float32)[:, :, :, None, None, None],
                     axis=2)                               # (B, T, g, p, r)
    return scores.transpose(0, 1, 3, 2, 4).reshape(b, t, pages * fold * prow)


@jax.named_scope("indexer")
def _indexer_block(spec: DecoderSpec, h, layer_w, pool, li, ai, positions,
                   slot_mapping, block_table):
    """The indexer of a layer with a learned sparse selection
    (:class:`SparseSpec`), a paged step: project the normed input ``h`` (B,
    T, H) to index queries, the index key and the head weights; write the
    step's keys to the index-key pool ``pool`` at ``slot_mapping``; score
    every query against its row's pages (``block_table``) and select.
    Returns (select, pool): ``select`` (B, T, table tokens) bool, True
    where query ``t`` attends the token at that position (causal, written,
    among its ``topk``). A sibling of ``attn`` in the trace, not inside it.

    The scores and the selection are ONE kernel over the row's live index
    pages where the call allows it (``ops/index_select.py``: its ``declined``
    names why not - a decode step's single query a row by the clock - and
    the engagement record ``index_select`` which form a program took); else
    :func:`_gathered_select`, the table's pages gathered. Both read the
    step's own keys from the pool, so the write comes first, and both give
    the same set wherever their float32 scores are equal bit for bit."""
    from ..modules import block_kv_cache as bkv
    from ..ops import index_select
    sp = spec.sparse
    b, t, _ = h.shape
    nj, dim = sp.index_heads, sp.index_dim
    proj = qlinear(h, layer_w["idx_proj"])
    qi, ki, w = jnp.split(proj, [nj * dim, nj * dim + dim], axis=-1)
    cos, sin = ai["cos_i"], ai["sin_i"]
    qi = apply_rope(qi.reshape(b, t, nj, dim), cos, sin)
    ki = layer_norm(ki, layer_w["idx_k_norm"], layer_w["idx_k_norm_b"],
                    sp.norm_eps)
    ki = apply_rope(ki[:, :, None, :], cos, sin)[:, :, 0]
    bs = (pool.shape[2] * pool.shape[3]) // dim
    pool = bkv.write_index_keys(pool, ki, li, slot_mapping, positions, bs)
    # None where the kernel is declined: the engagement record says why
    select = index_select.select_of(spec, qi, w, pool, li, positions,
                                    block_table)
    if select is None:
        select = _gathered_select(sp, qi, w, pool, li, positions,
                                  block_table)
    return select, pool


def _paged_kernel_declined(spec: DecoderSpec) -> str:
    """Why a single-token paged step of this spec does NOT take the paged
    decode kernel ("" where it does, the mesh permitting)."""
    return ("alibi" if spec.alibi
            else "decode_kernel=False" if spec.decode_kernel is False
            else "" if decode_attention.supports(spec, 1, paged=True)
            else "unsupported geometry (head_dim / attn_chunk)")


def _join_caps(parts):
    """The per-layer outputs of consecutive layer runs as one, layer-major
    (a key only some runs give - a dense run counts no experts - is theirs
    alone)."""
    return {k: jnp.concatenate([c[k] for c in parts if k in c])
            for k in dict.fromkeys(k for c in parts for k in c)}


def run_layers(spec: DecoderSpec, params, cache, hidden, ai,
               seq_ids, positions, phase: str,
               identity_seq_ids: bool = False,
               arange_positions: bool = False,
               slot_mapping=None, block_table=None,
               adapter_ids=None, replacements=None, kv_view: int = None,
               deepstack=None, deepstack_mask=None, prefill_lens=None,
               state_slots=None):
    """The layer walk of a step graph, by what the stack is: layers of
    several sub-blocks (:func:`run_layers_shortcut`), a recurrent / hybrid
    stack (:func:`run_layers_ssm`), a window pool
    (:func:`run_layers_window`), and everything else, a learned sparse
    selection included: :func:`run_layer_slice` over the stacked layer
    weights, a run of equal kind at a time.

    Replaces the reference's per-layer Python loop
    (models/model_base.py:1216-1469 get_model_output).
    ai: attn_inputs() bundle; replacements: {point: (L,B,T,H),
    point+"_on": (L,)} golden-injection arrays.
    Returns (hidden, new_cache, captured) on every branch: captured the
    per-layer outputs, {} unless spec.capture names per-layer points (then
    each is stacked (L, ...)) or a paged decode step counts its routing
    (``moe_tally``).

    A paged pool is walked in the shape it is stored in
    (``block_kv_cache.pool_page``: as the decode kernel reads a shard's
    page), whatever the phase's width: nothing here reshapes it.
    """
    if spec.sub_blocks > 1:
        if replacements is not None or deepstack is not None \
                or spec.capture:
            raise NotImplementedError(
                "a layer of several sub-blocks (DecoderSpec.sub_blocks) has "
                "no tap points or deepstack features")
        return run_layers_shortcut(
            spec, params, cache, hidden, ai, seq_ids, positions, phase,
            identity_seq_ids=identity_seq_ids,
            arange_positions=arange_positions, slot_mapping=slot_mapping,
            block_table=block_table, adapter_ids=adapter_ids,
            kv_view=kv_view, prefill_lens=prefill_lens)
    if spec.ssm is not None:
        refuse_recurrent([
            replacements is not None and "tensor capture/replacement",
            deepstack is not None and "deepstack"])
        return (run_layers_blocks if spec.layer_blocks else run_layers_ssm)(
            spec, params, cache, hidden, ai, seq_ids, positions, phase,
            identity_seq_ids=identity_seq_ids, adapter_ids=adapter_ids,
            kv_view=kv_view, prefill_lens=prefill_lens,
            slot_mapping=slot_mapping, block_table=block_table,
            state_slots=state_slots)
    if "k_w" in cache:
        refuse_window_pool([
            phase != "paged" and "multi-token decode",
            (replacements is not None or spec.capture)
            and "tensor capture/replacement",
            deepstack is not None and "deepstack"])
        return run_layers_window(
            spec, params, cache, hidden, ai, positions,
            slot_mapping=slot_mapping, block_table=block_table,
            adapter_ids=adapter_ids)
    if spec.sparse is not None:
        refuse_sparse([
            (phase != "paged" or "k_idx" not in cache) and "contiguous cache",
            (replacements is not None or spec.capture)
            and "tensor capture/replacement",
            deepstack is not None and "deepstack"])
    L = spec.num_layers
    is_local = jnp.asarray(spec.layer_pattern if spec.layer_pattern is not None
                           else (False,) * L)
    rep = replacements or {}
    # contiguous runs of equal kind: a mixed stack (deepseek
    # first_k_dense_replace, llama4 interleave_moe_layer_step) keeps its
    # dense and its expert layers in two stacks, each run the next layers of
    # its own; the cache layer index stays the absolute layer position
    if spec.moe is not None and spec.first_dense > 0:
        pat = (False,) * spec.first_dense + (True,) * (L - spec.first_dense)
    elif spec.moe is not None and spec.moe_pattern is not None:
        pat = tuple(bool(x) for x in spec.moe_pattern)
    else:
        pat = None
    cuts = [0] + [i for i in range(1, L) if pat and pat[i] != pat[i - 1]] + [L]
    used = {"layers": 0, "moe_layers": 0}
    caps_parts = []
    for lo, hi in zip(cuts, cuts[1:]):
        kind = None if pat is None else "moe" if pat[lo] else "dense"
        name = "moe_layers" if kind == "moe" else "layers"
        j0, n = used[name], hi - lo
        used[name] += n
        stack = params[name]
        if n != jax.tree.leaves(stack)[0].shape[0]:
            stack = jax.tree.map(lambda a: a[j0:j0 + n], stack)
        hidden, cache, c = run_layer_slice(
            spec, stack, cache, hidden, ai, cache_offset=lo,
            is_local=is_local[lo:hi],
            rep=jax.tree.map(lambda a: a[lo:hi], rep), mlp_kind=kind,
            seq_ids=seq_ids, positions=positions, phase=phase,
            identity_seq_ids=identity_seq_ids,
            arange_positions=arange_positions, slot_mapping=slot_mapping,
            block_table=block_table, adapter_ids=adapter_ids,
            replacements=replacements, kv_view=kv_view,
            deepstack=None if deepstack is None else deepstack[lo:hi],
            deepstack_mask=deepstack_mask, prefill_lens=prefill_lens)
        caps_parts.append(c)
    return hidden, cache, _join_caps(caps_parts)


def run_layer_slice(spec: DecoderSpec, layer_params, cache, hidden, ai, *,
                    cache_offset: int, is_local, rep, mlp_kind,
                    seq_ids, positions, phase,
                    identity_seq_ids=False, arange_positions=False,
                    slot_mapping=None, block_table=None, adapter_ids=None,
                    replacements=None, kv_view=None, deepstack=None,
                    deepstack_mask=None, prefill_lens=None):
    """Run one contiguous run of stacked layers against the full cache
    (cache layer index = scan index + ``cache_offset``). Exposed so families
    with interleaved non-standard layers (mllama cross-attention decoder)
    can stitch standard segments around their own blocks. Returns (hidden,
    cache, per-layer outputs), as every walk does.

    Phase "decode" at T = 1 (the contiguous cache) UNROLLS the layer loop:
    with a static layer index each layer's cache read is a lazily fused
    static slice. Every other phase SCANS one compiled body
    (:func:`scan_layers`; O(1) compile time in depth) — the paged step
    graphs too: ``paged_forward_step`` passes "paged", so ``paged.w1`` scans
    and is copy-free all the same, because its consumers either fuse their
    layer slice (the dense all-experts einsum) or take the layer index as a
    scalar (the paged decode kernel, the few-token expert kernel).

    What a scan's dynamic layer index costs is decided per consumer: XLA
    fuses a layer's slice into an einsum or an elementwise op, but a
    consumer it cannot fuse into is handed a COPY of the slice on every
    layer of every dispatch — ``ragged_dot`` is a Mosaic custom call on TPU
    (268 MB a weight kind at OLMoE's widths), the block gather read a whole
    layer of the pool (134 MB each for K and V): five
    ``dynamic-slice_bitcast_fusion`` ops, 26 of a one-row 256-token chunk's
    38 ms on a v5e (PERF.md, PR 25-31). Unrolling with static ``w[i]`` is
    worse: the compiler then copies ALL layers in one multi-output fusion.
    So those consumers get the STACKED array and the layer index and select
    the layer themselves: the expert leaves ``moe.stack_leaves`` names stay
    out of the scan's ``xs`` (``moe_block`` receives a ``LayerOfStack``:
    the grouped matmuls of many tokens, and the touched-experts kernel of
    few, ``ops/moe_decode.py``), and the paged read gathers from the flat
    pool (``block_kv_cache.gather_layer_kv``).

    A stack with a learned sparse selection (``spec.sparse``) carries a
    third pool, the index keys' ``cache["k_idx"]``, on the allocator's one
    block table: a layer's indexer (:func:`_indexer_block`, scope
    ``indexer``, a sibling of ``attn``) reads the block's normed input and
    hands the attention the tokens it selects."""
    n = jax.tree.leaves(layer_params)[0].shape[0]
    h0 = jax.tree.leaves(hidden)[0]
    kf, vf = cache["k"], cache["v"]

    if phase == "decode" and h0.shape[1] == 1:
        sliced, of_stack = _split_stack(
            spec, layer_params, h0.shape[0],
            spec.moe is not None and mlp_kind != "dense")
        caps_list = []
        for i in range(n):
            hidden, kf, vf, caps_i = _layer_body(
                spec, hidden,
                of_stack(jax.tree.map(lambda a: a[i], sliced), i),
                kf, vf, i + cache_offset, ai,
                is_local[i], seq_ids, positions, phase, identity_seq_ids,
                arange_positions, slot_mapping, block_table, mlp_kind,
                adapter_ids,
                (jax.tree.map(lambda a: a[i], rep)
                 if replacements is not None else None),
                kv_view=kv_view, prefill_lens=prefill_lens)
            caps_list.append(caps_i)
        caps = ({k: jnp.stack([c[k] for c in caps_list])
                 for k in caps_list[0]} if caps_list and caps_list[0] else {})
        return hidden, {**cache, "k": kf, "v": vf}, caps

    pools = (kf, vf)
    if spec.sparse is not None:
        pool = cache["k_idx"]
        kernel_mode.note(
            "kv_index_pool", "xla",
            f"page={pool.shape[2]}x{pool.shape[3]} values_a_token="
            f"{spec.sparse.index_dim} heads={spec.sparse.index_heads} topk="
            f"{spec.sparse.topk} pool_bytes={pool.size * pool.dtype.itemsize}")
        pools += (pool,)

    def block(carry, layer_w, li, x, moe_kw):
        h, k_, v_, *ki = carry
        loc, rp, ds = x
        w = layer_w()

        def select_of(normed):
            select, ki[0] = _indexer_block(
                spec, normed, w, ki[0], li, ai, positions, slot_mapping,
                block_table)
            return select

        h, k_, v_, caps = _layer_body(
            spec, h, w, k_, v_, li + cache_offset, ai, loc, seq_ids,
            positions, phase, identity_seq_ids, arange_positions,
            slot_mapping, block_table, mlp_kind, adapter_ids,
            rp if replacements is not None else None, kv_view=kv_view,
            deepstack=ds, deepstack_mask=deepstack_mask,
            prefill_lens=prefill_lens, select_of=select_of if ki else None,
            **moe_kw)
        return (h, k_, v_, *ki), caps

    (hidden, *pools), caps = scan_layers(
        spec, layer_params, (hidden, *pools), block, steps=n, phase=phase,
        slot_mapping=slot_mapping, experts=mlp_kind != "dense",
        xs=(is_local, rep, deepstack))
    return hidden, {**cache, **dict(zip(("k", "v", "k_idx"), pools))}, caps


def run_layers_shortcut(spec: DecoderSpec, params, cache, hidden, ai,
                        seq_ids, positions, phase: str, *,
                        identity_seq_ids=False, arange_positions=False,
                        slot_mapping=None, block_table=None,
                        adapter_ids=None, kv_view=None, prefill_lens=None):
    """The walk of a stack whose layer is ``spec.sub_blocks`` [attention,
    dense MLP] pairs and one routed block on a shortcut (HF
    LongcatFlashDecoderLayer): with ``x`` the layer's input,

        a0 = x + Attn_0(N(x));   u = N'(a0);   s = MoE(u)
        b0 = a0 + MLP_0(u);      a1 = b0 + Attn_1(N(b0))
        y  = a1 + MLP_1(N'(a1)) + s

    One scan over the layers (:func:`scan_layers`: the routed block's
    stack, a layer a step); pair ``j`` of layer ``l`` reads and writes
    cache layer ``sub_blocks * l + j``. Returns (hidden, cache, per-layer
    outputs)."""
    n = spec.sub_blocks
    pairs = params["layers"]

    def block(carry, layer_w, l, _, moe_kw):
        x, kf, vf = carry
        moe_w = layer_w()
        shortcut = None
        for j in range(n):
            # ONE pair's slice, by its own index: XLA fuses it into the
            # pair's matmuls as it fuses a scan's xs. Scanned as (L, n, ...)
            # the layer's slice of BOTH pairs was materialised first, a
            # copy of every attention and dense-MLP weight a step (0.84 GB
            # of temps at LongCat's widths: AOT, PR 40)
            w = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, l * n + j,
                                                       keepdims=False),
                pairs)
            h, kf, vf, _ = _attn_block(
                spec, _norm(spec, x, w["input_norm"]), w, kf, vf, l * n + j,
                ai, False, seq_ids, positions, phase,
                identity_seq_ids=identity_seq_ids,
                arange_positions=arange_positions,
                slot_mapping=slot_mapping, block_table=block_table,
                adapter_ids=adapter_ids, kv_view=kv_view,
                prefill_lens=prefill_lens)
            x = x + _shard(h, AXIS_DP, None, None)
            u = _norm(spec, x, w["post_norm"])
            if j == 0 and spec.moe is not None:
                shortcut = _mlp_block(spec, u, moe_w, "moe", adapter_ids,
                                      phase=phase, **moe_kw)
            x = x + _shard(_mlp_block(spec, u, w, "dense", adapter_ids,
                                      phase=phase), AXIS_DP, None, None)
        if shortcut is not None:
            x = x + shortcut
        return (x, kf, vf), {}

    (hidden, kf, vf), caps = scan_layers(
        spec, params.get("moe_layers", {}), (hidden, cache["k"], cache["v"]),
        block, steps=spec.num_layers, phase=phase, slot_mapping=slot_mapping)
    return hidden, {**cache, "k": kf, "v": vf}, caps


def _state_rows(arr, li: int, slots):
    """Rows of layer ``li`` of a stacked state array (Ls, slots, ...):
    all of them in slot order (``slots`` None: the rows of the step ARE
    the slots, nothing is gathered), else the rows ``slots`` (R,) names —
    one slot is a dynamic slice, more a gather."""
    if slots is None:
        return arr[li]
    if slots.shape[0] == 1:
        return jax.lax.dynamic_slice(
            arr, (li, slots[0]) + (0,) * (arr.ndim - 2),
            (1, 1) + arr.shape[2:])[0]
    return arr[li][slots]


def _state_put(arr, li: int, slots, val):
    """Write :func:`_state_rows`' rows back, in place on the donated
    buffer."""
    val = val.astype(arr.dtype)
    if slots is None:
        return arr.at[li].set(val)
    if slots.shape[0] == 1:
        return jax.lax.dynamic_update_slice(
            arr, val[None], (li, slots[0]) + (0,) * (arr.ndim - 2))
    return arr.at[li, slots].set(val)


def run_layers_ssm(spec: DecoderSpec, params, cache, hidden, ai,
                   seq_ids, positions, phase: str, *,
                   identity_seq_ids=False, adapter_ids=None, kv_view=None,
                   prefill_lens=None, slot_mapping=None, block_table=None,
                   state_slots=None, part=None):
    """Unrolled layer walk for recurrent/hybrid stacks (reference:
    contrib Falcon-H1 FalconH1DecoderLayer — parallel mamba+attention;
    contrib recurrentgemma RecurrentGemmaDecoderLayer — rec/rec/attn
    pattern; HF GraniteMoeHybridDecoderLayer — mamba2 or attention by
    ``layer_types``). The KV cache covers only the attention-bearing
    layers; the recurrent state rides the same cache dict as stacked conv
    tails + SSM states, updated with static per-layer indices.

    Every layer shares the sequential residual shape: pre-norm temporal
    block(s) → residual add → pre-norm MLP → residual add (each add scaled
    by ``residual_multiplier``); the temporal block is attention, the SSM,
    or (parallel hybrid) their sum. With ``norm_position="post"`` (HF
    Olmo 2 / Olmo 3 / Olmo-Hybrid) there is no input norm and each
    sub-block's OUTPUT goes through ``post_attn_norm`` / ``post_ff_norm``
    before its add, on both kinds of layer.

    Phase "paged" (the serving step graphs): attention layers write and
    read through ``slot_mapping`` / ``block_table`` like any paged stack;
    a mixer continues from its rows of the state — the second
    per-sequence cache, (Ls, slots, ...) beside the KV pool. With
    ``state_slots`` None the rows of the step ARE the slots (row i is slot
    i; a row whose ``slot_mapping`` is all negative is dead and leaves its
    slot's tail and state as they were), so the state is updated in place;
    with ``state_slots`` (R,) the R rows slice their slots in and out (the
    one-row chunk program). ``part``: :func:`walk_part`'s layers [lo, hi)
    and what the layers under lo hand on; a walk that stops early says so.
    """
    s = spec.ssm
    pat = spec.resolved_ssm_pattern
    paged = phase == "paged"
    post_norm = spec.norm_position == "post"
    refuse_recurrent([
        phase not in ("prefill", "decode", "paged") and "multi-token decode",
        phase == "decode" and hidden.shape[1] != 1 and "multi-token decode",
        spec.sandwich_norm and not post_norm and "sandwich norm",
        paged and spec.ssm_parallel and "paged parallel hybrid",
        paged and s.kind not in ssm_mod.CONTINUING_KINDS
        and "paged rglru state"])
    kinds = spec.layer_kinds
    refuse_recurrent([kinds is not None and not paged
                      and "contiguous decoder-hybrid-decoder"])
    kf, vf = cache["k"], cache["v"]
    # the window layers' ring pool (a stack with layer_kinds only)
    kw_, vw_ = cache.get("k_w"), cache.get("v_w")
    state_keys = [k for k in ("conv_x", "conv_bc", "ssm") if k in cache]
    new_state = {k: cache[k] for k in state_keys}
    valid = None
    # the ONE place that decides who steps the state: the kernel, in place
    # on the stack, or the XLA fusions on this layer's rows
    by_rows = ("no matrix state" if "ssm" not in cache
               else ssm_mod.state_kernel_declined(
                   s, cache["ssm"], *hidden.shape[:2], state_slots))
    if paged:
        valid = slot_mapping >= 0
        # the engagement record (ops/kernel_mode.py) names what the serving
        # graphs carry beside the KV pool, and what steps it
        slot_bytes = sum(v.size // v.shape[1] * v.dtype.itemsize
                         for v in new_state.values())
        what = (f"kind={s.kind} slot_bytes={slot_bytes} "
                f"chunk={s.chunk_size}")
        if by_rows:
            kernel_mode.note("recurrent_state", "xla", f"{what}: {by_rows}")
        else:
            kernel_mode.note(
                "recurrent_state", kernel_mode.kernel_path(),
                f"{what} {ssm_mod.state_kernel_note(s, cache['ssm'])}")
        if kinds is not None and part is None:
            _note_pools_by_kind(spec, cache, ai)
        n_slots = new_state[state_keys[0]].shape[1]
        if state_slots is None and hidden.shape[0] != n_slots:
            raise ValueError(
                f"a paged step of {hidden.shape[0]} rows over "
                f"{n_slots} state slots needs state_slots "
                "(one slot index a row); without it row i is slot i")
    rm = spec.residual_multiplier
    # a chunk of kind kda pins the stacks after each layer's write: without
    # it the compiler reads a later layer's rows from the stack AS IT CAME
    # IN (a slice of an update elsewhere is a slice of the original), and a
    # full-batch pack copied the conv tails' whole stack (70 MB at 64 slots
    # of 12288 channels) twice a layer, 30 copies a program by AOT (PR 67)
    pin_state = paged and hidden.shape[1] > 1 and s.kind == "kda"

    def add(res, branch):
        branch = _shard(branch, AXIS_DP, None, None)
        return res + (branch if rm == 1.0 else rm * branch)

    not_local = jnp.asarray(False)
    # a layer's MLP is dense or the routed block (under an expert stack the
    # first_dense leading layers are dense: mlp_stack). The expert leaves
    # stay in their stack where a custom call reads them in place (the
    # grouped matmuls, the few-token kernel: run_layer_slice has the
    # reason); the dense path's static slice fuses into its einsum
    n_dense = spec.num_layers - spec.num_moe_layers
    in_place = (moe_mod.stack_leaves(
        spec.moe, hidden.shape[0] * hidden.shape[1],
        params[mlp_stack(spec, spec.num_layers - 1)[0]])
        if spec.num_moe_layers else ())
    # a decode step over expert layers counts its routing and its reads
    tally = [] if (paged and hidden.shape[1] == 1
                   and spec.moe is not None) else None
    # the layers walked here, [lo, hi), and what the layers under lo hand on
    # (walk_part). A stack with layer_kinds: the layers seen so far of each
    # kind (a kind's index into its weight stack; "window" / "full" also into
    # their pool), the pool layer and the step's own K / V of the nearest
    # "full" layer below (what a "cross" layer attends over), and the nearest
    # mixer's scan output (what a "gmu" layer gates)
    lo, hi, shared, memory = part or walk_part(
        spec, ai, hidden.shape[1] if paged else 1)
    seen, attn_i, ssm_i = _layers_seen(spec, lo)
    for i in range(lo, hi):
        kind = kinds[i] if kinds is not None else None
        has_ssm = bool(pat[i])
        has_attn = (spec.ssm_parallel or not has_ssm) if kind is None \
            else kind in ("window", "full")
        mlp_kind = "dense" if i < n_dense else "moe"
        stack, jm = mlp_stack(spec, i)
        stack = params[stack]
        lw = jax.tree.map(lambda a: a[jm],
                          {k: a for k, a in stack.items()
                           if k not in in_place})
        lw.update({k: moe_mod.LayerOfStack(stack[k], jm)
                   for k in in_place if k in stack})
        if has_attn and "attn_layers" in params:
            ja = attn_i
            lw = {**lw, **jax.tree.map(lambda a: a[ja], params["attn_layers"])}
        if has_ssm and "ssm_layers" in params:
            js = ssm_i
            lw = {**lw, **jax.tree.map(lambda a: a[js], params["ssm_layers"])}
        if kind in ("cross", "gmu"):
            jk = seen[kind]
            lw = {**lw, **jax.tree.map(lambda a: a[jk],
                                       params[kind + "_layers"])}
        h = hidden if post_norm else _norm(
            spec, hidden, lw["input_norm"],
            lw.get("input_norm_b") if spec.norm_bias else None)
        t_out = None
        kw_attn = dict(identity_seq_ids=identity_seq_ids,
                       arange_positions=(phase == "prefill"),
                       slot_mapping=slot_mapping, block_table=block_table,
                       adapter_ids=adapter_ids, kv_view=kv_view,
                       prefill_lens=prefill_lens)
        if has_attn and kind is None:
            a_out, kf, vf, _ = _attn_block(
                spec, h, lw, kf, vf, attn_i, ai, not_local, seq_ids,
                positions, phase, **kw_attn)
            t_out = a_out
            attn_i += 1
        elif has_attn:
            # static per kind: a window layer on its slot's ring, the full
            # layer on the allocator's pool, handing its step's K / V on
            local = kind == "window"
            t_out, kp, vp, handed = _attn_block(
                spec, h, lw, *((kw_, vw_) if local else (kf, vf)),
                seen[kind], ai, jnp.asarray(local), seq_ids, positions,
                phase, mixed_local=local, depth=i, hand_kv=not local,
                **kw_attn)
            if local:
                kw_, vw_ = kp, vp
            else:
                kf, vf = kp, vp
                shared = (seen[kind], handed)
            attn_i += 1
        elif kind == "cross":
            # the same attention under a scope of its own, a SIBLING of
            # "attn": a query projection, the shared pool read, no write
            with jax.named_scope("cross_attn"):
                t_out, _, _, _ = _attn_body(
                    spec, h, lw, kf, vf, shared[0], ai, not_local, seq_ids,
                    positions, phase, mixed_local=False, depth=i,
                    cross_kv=shared[1], **kw_attn)
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                gate = jax.nn.silu((h @ lw["gmu_in"]).astype(jnp.float32))
                t_out = (memory.astype(jnp.float32) * gate).astype(
                    h.dtype) @ lw["gmu_out"]
        if kind is not None:
            seen[kind] += 1
        if has_ssm:
            # ONE profiler scope around the whole temporal block
            # (projections, conv, state update or chunked scan, gated norm,
            # out_proj, and the state rows read and written)
            with jax.named_scope("mixer"):
                st = {k: _state_rows(new_state[k], ssm_i, state_slots)
                      if by_rows or k != "ssm"
                      else ssm_mod.StateStack(new_state[k], ssm_i)
                      for k in state_keys}
                s_out, st_new = ssm_mod.ssm_block(
                    s, lw, h, st, phase=phase, seq_lens=prefill_lens,
                    positions=positions, valid=valid)
                memory = st_new.pop(ssm_mod.SCAN_OUT, memory)
                for k2, v2 in st_new.items():
                    new_state[k2] = (
                        v2.stack if isinstance(v2, ssm_mod.StateStack)
                        else _state_put(new_state[k2], ssm_i, state_slots,
                                        v2))
                if pin_state:
                    new_state = jax.lax.optimization_barrier(new_state)
            t_out = s_out if t_out is None else t_out + s_out
            ssm_i += 1
        if post_norm:
            t_out = rms_norm(t_out, lw["post_attn_norm"], spec.rms_eps,
                             spec.norm_offset)
        hidden = add(hidden, t_out)
        h2 = hidden if post_norm else _norm(
            spec, hidden, lw["post_norm"],
            lw.get("post_norm_b") if spec.norm_bias else None)
        m_out = _mlp_block(spec, h2, lw, mlp_kind, adapter_ids, phase=phase,
                           tally=tally, live=valid)
        if post_norm:
            m_out = rms_norm(m_out, lw["post_ff_norm"], spec.rms_eps,
                             spec.norm_offset)
        hidden = add(hidden, m_out)
    # exact counts over this walk's expert layers, [touched, assigned, read]
    # (moe.share_tally); a walk that stopped early, where, and its hand-over
    side = {"moe_tally": sum(tally)} if tally else {}
    if hi < spec.num_layers:
        side["handed"] = (hi, shared, memory)
    rings = {} if kw_ is None else {"k_w": kw_, "v_w": vw_}
    return hidden, {"k": kf, "v": vf, **rings, **new_state}, side


def _note_pools_by_kind(spec: DecoderSpec, cache, ai) -> None:
    """A paged stack with ``layer_kinds`` records its pools by layer kind
    (``kv_window_pool``, as :func:`run_layers_window` writes it) and the pool
    several layers read (``kv_shared_pool``: writers, readers, token bytes)."""
    def pool_bytes(*keys):
        return sum(cache[k].size * cache[k].dtype.itemsize for k in keys)
    n_full, n_win = spec.count_kind("full"), spec.count_kind("window")
    by_kind = " ".join(f"{k}={spec.count_kind(k)}" for k in
                       ("mamba", "window", "full", "cross", "gmu"))
    kg = cache["k"]
    token_bytes = 2 * kg.shape[3] * kg.shape[4] * kg.dtype.itemsize
    if "k_w" in cache:
        ring = ai["ring"]["pages"]
        kernel_mode.note(
            "kv_window_pool", "xla",
            f"layers {by_kind} window_tokens={spec.sliding_window} "
            f"ring_pages={ring} ring_bytes_a_row="
            f"{n_win * ring * kg.shape[2] * token_bytes} global_pool_bytes="
            f"{pool_bytes('k', 'v')} window_pool_bytes="
            f"{pool_bytes('k_w', 'v_w')}")
    kernel_mode.note(
        "kv_shared_pool", "xla",
        f"layers={n_full} readers={n_full + spec.count_kind('cross')} "
        f"bytes_a_token={n_full * token_bytes}")


def run_layers_mixed_decode(spec: DecoderSpec, params, cache, hidden, ai,
                            seq_ids, positions, kv_view=None,
                            adapter_ids=None, identity_seq_ids=True):
    """Decode layer loop over the MIXED cache (reference: gpt-oss per-layer
    KV sizes, modules/kvcache/gpt_oss_kv_cache_manager.py): local layers
    read/write the rolling {"k_l","v_l"} stacks (W slots), global layers
    the full {"k","v"} stacks — selected statically per unrolled layer.
    identity_seq_ids=False (continuous-batching serving): reads gather and
    writes scatter through seq_ids on both stack kinds."""
    lmap = kv.mixed_layer_map(spec.layer_pattern)
    kf, vf = cache["k"], cache["v"]
    kl, vl = cache["k_l"], cache["v_l"]
    caps_list = []
    for i in range(spec.num_layers):
        layer_w = jax.tree.map(lambda a: a[i], params["layers"])
        loc = bool(spec.layer_pattern[i])
        if loc:
            hidden, kl, vl, caps_i = _layer_body(
                spec, hidden, layer_w, kl, vl, lmap[i], ai,
                jnp.asarray(True), seq_ids, positions, "decode",
                identity_seq_ids=identity_seq_ids, adapter_ids=adapter_ids,
                mixed_local=True)
        else:
            hidden, kf, vf, caps_i = _layer_body(
                spec, hidden, layer_w, kf, vf, lmap[i], ai,
                jnp.asarray(False), seq_ids, positions, "decode",
                identity_seq_ids=identity_seq_ids, adapter_ids=adapter_ids,
                kv_view=kv_view, mixed_local=False)
        caps_list.append(caps_i)
    caps = ({k2: jnp.stack([c[k2] for c in caps_list])
             for k2 in caps_list[0]} if caps_list and caps_list[0] else {})
    return hidden, {"k": kf, "v": vf, "k_l": kl, "v_l": vl}, caps


def fold_mixed_prefill(spec: DecoderSpec, scratch_cache, cache, seq_lens,
                       seq_ids=None):
    """Mixed-cache prefill epilogue: copy the scratch full-length rows of
    GLOBAL layers into the persistent full stacks and FOLD local layers'
    rows into the rolling stacks (reference: gpt-oss manager CTE path).
    seq_ids (b,) — continuous-batching target rows; None = rows [0, b)."""
    pat = spec.layer_pattern
    g_idx = [i for i, x in enumerate(pat) if not x]
    l_idx = [i for i, x in enumerate(pat) if x]
    gi = jnp.asarray(g_idx, jnp.int32)
    li = jnp.asarray(l_idx, jnp.int32)
    W = cache["k_l"].shape[4]
    kl_fold = kv.fold_rolling_prefill(
        scratch_cache["k"][li], seq_lens, W, k_transposed=True)
    vl_fold = kv.fold_rolling_prefill(scratch_cache["v"][li], seq_lens, W)
    new = dict(cache)
    if seq_ids is not None:
        # continuous batching: scatter the prefilled rows at their cache
        # slots; the scratch covers only the ctx-bucket slots [0, sb)
        # (reference: single-seq CTE update, kv_cache_manager.py:483)
        sb = scratch_cache["k"].shape[4]
        new["k"] = cache["k"].at[:, seq_ids, :, :, :sb].set(
            scratch_cache["k"][gi])
        new["v"] = cache["v"].at[:, seq_ids, :, :sb, :].set(
            scratch_cache["v"][gi])
        new["k_l"] = cache["k_l"].at[:, seq_ids].set(kl_fold)
        new["v_l"] = cache["v_l"].at[:, seq_ids].set(vl_fold)
        return new
    new["k"] = jax.lax.dynamic_update_slice(
        cache["k"], scratch_cache["k"][gi], (0, 0, 0, 0, 0))
    new["v"] = jax.lax.dynamic_update_slice(
        cache["v"], scratch_cache["v"][gi], (0, 0, 0, 0, 0))
    # partial-batch prefill (2-D batch buckets): update rows [0, b) in
    # place — replacing the stacks would change the cache pytree shape
    new["k_l"] = jax.lax.dynamic_update_slice(cache["k_l"], kl_fold,
                                              (0, 0, 0, 0, 0))
    new["v_l"] = jax.lax.dynamic_update_slice(cache["v_l"], vl_fold,
                                              (0, 0, 0, 0, 0))
    return new


# ---------------------------------------------------------------------------
# Step graphs
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed(spec: DecoderSpec, params, input_ids, position_ids=None):
    h = params["embed"][input_ids]        # sharded-vocab gather; XLA SPMD handles
    if spec.embed_scale is not None:
        h = (h.astype(jnp.float32) * spec.embed_scale).astype(h.dtype)
    if spec.embed_norm:
        # bloom word_embeddings_layernorm
        h = layer_norm(h, params["embed_norm"], params["embed_norm_b"],
                       spec.rms_eps)
    if spec.learned_pos and position_ids is not None:
        # gpt2 wpe: learned absolute position table added to token embeds
        h = h + params["pos_embed"][jnp.clip(position_ids, 0,
                                             spec.learned_pos - 1)]
    return _shard(h, AXIS_DP, None, None)


@jax.named_scope("lm_head")
def _lm_head(spec: DecoderSpec, params, hidden):
    h = (hidden if spec.skip_final_norm else
         _norm(spec, hidden, params["final_norm"], params.get("final_norm_b")))
    w = params["embed"].T if spec.tie_word_embeddings else params["lm_head"]
    logits = (h @ w).astype(jnp.float32)
    if spec.lm_head_bias and "lm_head_b" in params:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    if spec.logits_divide:
        logits = logits / spec.logits_divide
    if spec.logits_soft_cap:
        logits = spec.logits_soft_cap * jnp.tanh(logits / spec.logits_soft_cap)
    logits = sampling_ops.mask_padded_logits(logits, spec.padded_vocab - spec.vocab_size)
    return _shard(logits, AXIS_DP, None, AXIS_MP)


def context_encoding_step(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                          input_ids, position_ids, seq_ids, seq_lens,
                          sampling_params, rng, adapter_ids=None,
                          replacements=None, image_embeds=None,
                          image_mask=None, rope_position_ids=None,
                          deepstack_embeds=None):
    """Prefill graph (reference submodel tag ``context_encoding_model``).

    input_ids (B, S_bucket) right-padded; seq_lens (B,) true lengths.
    image_embeds (B, N_img, H) + image_mask (B, S): multimodal prefill —
    projected vision features replace the embeddings at the image-token
    positions, in order (reference: image-to-text merge,
    models/image_to_text_model_base.py + deepstack embeds
    model_base.py:1374-1387).
    Returns dict(tokens (B,), last_logits (B, V) [optional], cache).
    """
    ai = attn_inputs(spec, position_ids, lambda w, c=0: attn_ops.prefill_causal_mask(
        input_ids.shape[1], position_ids, window=w, chunk=c),
        rope_positions=rope_position_ids)
    # padded positions: mask rows beyond seq_len attend only to themselves —
    # harmless, their outputs are discarded.
    hidden = _embed(spec, params, input_ids, position_ids)
    if image_embeds is not None:
        # scatter the i-th image feature into the i-th image-token slot
        gather_idx = jnp.clip(jnp.cumsum(image_mask, axis=1) - 1, 0,
                              image_embeds.shape[1] - 1)
        img = jnp.take_along_axis(image_embeds.astype(hidden.dtype),
                                  gather_idx[..., None], axis=1)
        hidden = jnp.where(image_mask[..., None], img, hidden)
    if spec.bidir_image_attn and image_mask is not None:
        # OR a bidirectional overlay over each contiguous image-token span
        # onto BOTH mask variants (it overrides the sliding window too —
        # reference: HF gemma3 token_type_ids_mask_function applied to the
        # full and sliding mask kwargs alike)
        is_img = image_mask.astype(bool)
        new_start = jnp.logical_and(
            is_img, ~jnp.pad(is_img, ((0, 0), (1, 0)))[:, :-1])
        gid = jnp.cumsum(new_start.astype(jnp.int32), axis=1) - 1
        gid = jnp.where(is_img, gid, -1)
        overlay = jnp.logical_and(gid[:, :, None] >= 0,
                                  gid[:, :, None] == gid[:, None, :])
        for mk in ("mask", "mask_l"):
            if mk in ai:
                ai[mk] = jnp.logical_or(ai[mk], overlay)
    if spec.seq_parallel:
        # SP: shard the embedded sequence (reference: reduce-scatter of
        # embeddings, model_base.py:1482-1517)
        hidden = _shard(hidden, AXIS_DP, AXIS_CP, None)
    # context_encoding_step always feeds arange positions per row (the host
    # shim builds them); chunked/offset prefill variants must pass False
    if deepstack_embeds is not None:
        # deepstack (qwen3-vl): per-layer visual features injected into the
        # first K layers' hidden states at the image-token positions
        # (reference: models/model_base.py:1374-1387 deepstack embeds)
        K = deepstack_embeds.shape[0]
        pad_l = spec.num_layers - K
        deepstack_embeds = jnp.pad(
            deepstack_embeds.astype(hidden.dtype),
            ((0, pad_l), (0, 0), (0, 0), (0, 0)))
    persistent = cache
    identity = not tpu_cfg.is_continuous_batching
    if spec.mixed_kv:
        # mixed per-layer cache: prefill runs on a full-length SCRATCH for
        # every layer (identity rows — the fold scatters to the real rows);
        # the epilogue folds local layers into the rolling stacks
        # (reference: gpt_oss_kv_cache_manager.py CTE path)
        b, sb = input_ids.shape
        g = spec.gqa
        kdt = cache["k"].dtype
        cache = {"k": jnp.zeros((spec.num_layers, b, g.num_kv_heads,
                                 spec.head_dim, sb), kdt),
                 "v": jnp.zeros((spec.num_layers, b, g.num_kv_heads, sb,
                                 spec.v_head_dim), kdt)}
        identity = True
    hidden, new_cache, caps = run_layers(
        spec, params, cache, hidden, ai, seq_ids, position_ids, "prefill",
        identity_seq_ids=identity,
        arange_positions=True, adapter_ids=adapter_ids,
        replacements=replacements, deepstack=deepstack_embeds,
        deepstack_mask=image_mask, prefill_lens=seq_lens)
    if spec.mixed_kv:
        new_cache = fold_mixed_prefill(
            spec, new_cache, persistent, seq_lens,
            seq_ids=None if not tpu_cfg.is_continuous_batching else seq_ids)
    # last-token gather (reference: lm-head index + logit padding mask :987-999)
    idx = jnp.maximum(seq_lens - 1, 0)
    last_h = jnp.take_along_axis(hidden, idx[:, None, None].astype(jnp.int32), axis=1)
    logits = _lm_head(spec, params, last_h)[:, 0, :]
    # last hidden state feeds EAGLE draft fusion / medusa heads
    # (reference: EAGLE draft hidden-state fusion, model_base.py:1526-1592)
    out = {"cache": new_cache, "last_hidden": last_h[:, 0, :]}
    if tpu_cfg.output_logits:
        full_logits = _lm_head(spec, params, hidden)
        out["logits"] = full_logits[..., :spec.vocab_size]
    if tpu_cfg.output_full_hidden:
        out["hidden_states"] = hidden
    if caps:
        out["captured"] = caps
    out["tokens"] = sampling_ops.sample_dp(
        logits, tpu_cfg.on_device_sampling_config, sampling_params, rng)
    return out


def token_generation_step(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                          input_ids, position_ids, seq_ids,
                          sampling_params, rng, adapter_ids=None,
                          replacements=None, rope_position_ids=None,
                          kv_view: int = None):
    """Decode graph (reference submodel tag ``token_generation_model``).

    input_ids (B, T) with T = 1 (or speculation window).
    rope_position_ids (B, T, 3): optional M-RoPE 3-axis positions
    (reference: qwen2_vl rotary_position_ids plumbing,
    models/model_base.py:566-578).
    kv_view: static decode seq bucket — the graph READS only cache slots
    [0, kv_view), so early decode streams a fraction of the allocated cache
    (reference: TKG seq buckets, autobucketing.py:226; decode is HBM-bound
    so this is a direct throughput win). Writes still address the full cache.
    """
    cache_len = kv_view or kv.cache_len_of(cache)
    if spec.rolling_window:
        # rolling cache: slot != position; the mask maps slots back to the
        # positions they hold
        ai = attn_inputs(
            spec, position_ids,
            lambda w, c=0: attn_ops.rolling_decode_mask(position_ids,
                                                        cache_len),
            rope_positions=rope_position_ids)
    else:
        ai = attn_inputs(spec, position_ids,
                         lambda w, c=0: attn_ops.decode_mask(
                             position_ids, cache_len, window=w, chunk=c),
                         rope_positions=rope_position_ids)
    hidden = _embed(spec, params, input_ids, position_ids)
    if spec.mixed_kv:
        # local layers' rolling stacks: slot != position, rolling mask
        # (reference: gpt-oss per-layer KV decode)
        ai["mask_l"] = attn_ops.rolling_decode_mask(
            position_ids, cache["k_l"].shape[4])
        hidden, new_cache, caps = run_layers_mixed_decode(
            spec, params, cache, hidden, ai, seq_ids, position_ids,
            kv_view=kv_view, adapter_ids=adapter_ids,
            identity_seq_ids=not tpu_cfg.is_continuous_batching)
    else:
        hidden, new_cache, caps = run_layers(
            spec, params, cache, hidden, ai, seq_ids, position_ids,
            "decode", identity_seq_ids=not tpu_cfg.is_continuous_batching,
            adapter_ids=adapter_ids, replacements=replacements,
            kv_view=kv_view)
    logits = _lm_head(spec, params, hidden)
    out = {"cache": new_cache}
    if caps:
        out["captured"] = caps
    if tpu_cfg.output_logits:
        out["logits"] = logits[..., :spec.vocab_size]
    out["tokens"] = sampling_ops.sample_dp(
        logits[:, -1, :], tpu_cfg.on_device_sampling_config, sampling_params, rng)
    return out


def token_generation_multi(spec: DecoderSpec, tpu_cfg: TpuConfig, params,
                           cache, input_ids, position_ids, seq_ids):
    """Decode forward over T tokens returning logits at EVERY position —
    the target-verify graph of fused speculation (reference: target model
    scoring all candidate tokens, model_base.py:2617-2642). Within-step
    causality falls out of the cache-write-then-attend order plus the
    position mask."""
    if spec.mixed_kv:
        raise NotImplementedError(
            "multi-token decode over the mixed per-layer cache is not "
            "supported; disable speculation or set mixed_kv=False")
    refuse_recurrent([spec.ssm is not None and "multi-token decode"])
    cache_len = kv.cache_len_of(cache)
    ai = attn_inputs(spec, position_ids, lambda w, c=0: attn_ops.decode_mask(
        position_ids, cache_len, window=w, chunk=c))
    hidden = _embed(spec, params, input_ids, position_ids)
    hidden, new_cache, _ = run_layers(
        spec, params, cache, hidden, ai, seq_ids, position_ids,
        "decode", identity_seq_ids=not tpu_cfg.is_continuous_batching)
    logits = _lm_head(spec, params, hidden)
    return {"logits_all": logits[..., :spec.vocab_size], "cache": new_cache,
            "hidden": hidden}


def _coupled_mode(tpu_cfg: TpuConfig, row_seeds) -> bool:
    """True when the positionally coupled sampling stream is active:
    the config opts in (``do_sample`` + ``stream_seed``) AND the caller
    threaded per-row seeds. ``row_seeds=None`` keeps the legacy graphs
    byte-identical (an absent optional arg is an empty pytree)."""
    sc = tpu_cfg.on_device_sampling_config
    return (row_seeds is not None and sc is not None and sc.do_sample
            and sc.stream_seed is not None)


def paged_forward_step(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                       input_ids, position_ids, slot_mapping, block_table,
                       last_idx, sampling_params, rng, row_seeds=None,
                       adapter_ids=None, state_slots=None):
    """Unified paged-KV step graph (reference:
    modules/kvcache/block_kv_cache_manager.py + the prefix-caching prefill of
    attention_base.py:772-914). One graph covers:

      * paged prefill            (T = window, positions from 0)
      * prefix-cached prefill    (T = uncached suffix, positions offset)
      * chunked prefill          (T = chunk, positions at running offset)
      * paged decode             (T = 1)

    input_ids (B, T); position_ids (B, T) absolute positions;
    slot_mapping (B, T) flat cache slots (negative = drop);
    block_table (B, max_blocks); last_idx (B,) index into T of the token whose
    logits are sampled, negative for a row nobody samples from (a chunk that
    is not its prompt's last: :func:`second_decoder_tokens` has what a stack
    saves by it). Cache layout (L, N_blocks, Bs, Hkv, D).
    row_seeds (B,) optional per-request sampling seeds: present under a
    config with ``stream_seed``, the draw is the positionally coupled one
    (:func:`_draw`), which every sampled-speculation bit-identity rests on.
    adapter_ids (B,) optional per-row LoRA pool slots (serving/lora_pool):
    each row gathers its own (A, B) factors from the stacked adapter params
    inside the one dispatch; slot 0 is the pinned zero adapter (base-model
    rows stay bit-identical). Absent, the graph is a LoRA-free build's.
    state_slots (B,) optional, recurrent stacks only: the state slot of
    each row of the one-row chunk program; absent, row i is slot i.
    """
    kv_len = block_table.shape[1] * cache["k"].shape[2]
    ai = attn_inputs(spec, position_ids, lambda w, c=0: attn_ops.decode_mask(
        position_ids, kv_len, window=w, chunk=c))
    if "k_w" in cache:
        # the window layers' ring, by the row's batch slot (the adapter's
        # state slots: absent, row i is slot i)
        ai["ring"] = window_ring_inputs(
            spec, cache["k_w"], tpu_cfg.batch_size, position_ids,
            slot_mapping, block_table, state_slots)
    if spec.sparse is not None:
        # the index heads' rotary (all of their lanes)
        ai["cos_i"], ai["sin_i"] = rope_cos_sin(position_ids,
                                                spec.sparse.rope)
    if spec.layer_kinds is not None and not tpu_cfg.output_logits:
        ai["sampled"] = last_idx    # all the step hands out: walk_part
    hidden = _embed(spec, params, input_ids, position_ids)
    hidden, new_cache, side = run_layers(
        spec, params, cache, hidden, ai, None, position_ids,
        "paged", slot_mapping=slot_mapping, block_table=block_table,
        adapter_ids=adapter_ids, state_slots=state_slots)
    if "handed" in side:
        # the walk stopped before a second decoder, which writes nothing:
        # it, the head and the draw run on the ONE token a row that is
        # sampled from, under one condition: that any row of the dispatch
        # is (a chunk that is not its prompt's last reads none of them)
        return {"cache": new_cache, "tokens": second_decoder_tokens(
            spec, tpu_cfg, params, new_cache, hidden, side["handed"],
            position_ids, slot_mapping, block_table, last_idx,
            sampling_params, rng, row_seeds, adapter_ids, state_slots)}
    idx = last_idx[:, None, None].astype(jnp.int32)
    last_h = jnp.take_along_axis(hidden, idx, axis=1)
    logits = _lm_head(spec, params, last_h)[:, 0, :]
    out = {"cache": new_cache}
    if "moe_tally" in side:
        # a decode step over expert layers counts what its routing touched
        # and what its expert path read (one row a layer where the walk
        # scans); the adapter fetches the sums with the tokens
        out["moe_tally"] = side["moe_tally"].reshape(
            -1, side["moe_tally"].shape[-1]).sum(axis=0)
    if tpu_cfg.output_logits:
        out["logits"] = _lm_head(spec, params, hidden)[..., :spec.vocab_size]
    out["tokens"] = _draw(tpu_cfg, logits, sampling_params, rng, row_seeds,
                          position_ids, last_idx)
    if input_ids.shape[1] == 1:
        # the decode step hands its sampled tokens on in the shape, dtype
        # and (replicated) placement of its own ``input_ids``: a serving
        # adapter with one step in flight feeds this output straight into
        # the next dispatch, with no helper program between two steps
        out["next_ids"] = _shard(
            out["tokens"].astype(input_ids.dtype)[:, None])
    return out


def decode_loop(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                first_tokens, position_ids, seq_ids, sampling_params, rng,
                num_steps: int, adapter_ids=None, rope_position_ids=None,
                kv_view: int = None):
    """Fused multi-token decode: ``lax.scan`` of ``num_steps`` decode steps in
    ONE device call. This is the TPU answer to the reference's async
    double-buffering (modules/async_execution.py) — instead of hiding the
    host-device round trip, we eliminate num_steps-1 of them.

    first_tokens (B,): the token to feed at the first step.
    position_ids (B,): position of first_tokens.
    Returns (tokens (B, num_steps), cache).
    """
    use_mrope = rope_position_ids is not None
    if rope_position_ids is None:
        rope_position_ids = jnp.zeros((first_tokens.shape[0], 3),
                                      position_ids.dtype)
    rngs = jax.random.split(rng, num_steps)

    def step(carry, step_rng):
        tok, pos, rpos, cch = carry
        out = token_generation_step(
            spec, replace_output_logits(tpu_cfg), params, cch,
            tok[:, None], pos[:, None], seq_ids, sampling_params, step_rng,
            adapter_ids,
            rope_position_ids=rpos[:, None, :] if use_mrope else None,
            kv_view=kv_view)
        nxt = out["tokens"]
        # text-token M-RoPE positions advance in lockstep on all 3 axes
        return (nxt, pos + 1, rpos + 1 if use_mrope else rpos,
                out["cache"]), nxt

    (_, _, _, new_cache), toks = jax.lax.scan(
        step, (first_tokens, position_ids, rope_position_ids, cache), rngs)
    return {"tokens": jnp.transpose(toks, (1, 0)), "cache": new_cache}


def paged_decode_loop(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                     first_tokens, position_ids, block_table,
                     sampling_params, rng, num_steps: int, row_seeds=None,
                     adapter_ids=None):
    """Fused multi-token PAGED decode: ``num_steps`` steps in one device
    call with ZERO per-token host work — slot mappings are computed
    IN-GRAPH from the (pre-extended) block tables, exactly the reference's
    in-graph tokengen slot-mapping generation
    (block_kv_cache_manager.py:376-430). The host must pre-allocate blocks
    covering positions [p, p+num_steps) before the call.

    first_tokens (B,); position_ids (B,); block_table (B, max_blocks).
    Returns tokens (B, num_steps) + cache."""
    refuse_recurrent([spec.ssm is not None and "fused decode loop"])
    refuse_window_pool(["k_w" in cache and "fused decode loop"])
    refuse_sparse([spec.sparse is not None and "fused decode loop"])
    bs = cache["k"].shape[2]                  # paged (L, N, Bs, H, D)
    b = first_tokens.shape[0]
    rows = jnp.arange(b)

    def step(carry, step_rng):
        tok, pos, cch = carry
        slot = (block_table[rows, pos // bs] * bs + pos % bs)
        out = paged_forward_step(
            spec, replace_output_logits(tpu_cfg), params, cch, tok[:, None],
            pos[:, None], slot[:, None], block_table,
            jnp.zeros((b,), jnp.int32), sampling_params, step_rng,
            row_seeds=row_seeds, adapter_ids=adapter_ids)
        return (out["tokens"], pos + 1, out["cache"]), out["tokens"]

    rngs = jax.random.split(rng, num_steps)
    (_, _, new_cache), toks = jax.lax.scan(
        step, (first_tokens, position_ids, cache), rngs)
    return {"tokens": jnp.transpose(toks, (1, 0)), "cache": new_cache}


def paged_spec_draft_loop(spec: DecoderSpec, tpu_cfg: TpuConfig, params,
                          cache, first_tokens, position_ids, block_table,
                          widths, sampling_params, rng, num_steps: int,
                          row_seeds=None, adapter_ids=None):
    """Masked greedy-k SELF-DRAFT loop over the paged cache — the
    always-available proposer of speculative serving (serving/speculation/):
    the target model drafts its own continuation through ``num_steps``
    fused T=1 paged steps, exactly :func:`paged_decode_loop` except each
    row stops drafting once it has contributed its per-row candidate
    width (``widths`` (B,) = drafts + 1; rows clamped by seq_len or a
    token budget draft fewer).

    A frozen row's step writes nothing (slot -1 → dropped) and keeps its
    token/position carry, so a ragged draft batch can never write KV past
    a short row's grown block table. Draft KV lands at positions
    [p, p+width-2]; the verify dispatch rewrites the same slots with the
    same values (same model, same inputs), so the double write is
    value-identical.

    first_tokens (B,); position_ids (B,); block_table (B, max_blocks);
    widths (B,) int32. Returns tokens (B, num_steps) + cache.
    """
    bs = cache["k"].shape[2]                  # paged (L, N, Bs, H, D)
    b = first_tokens.shape[0]
    rows = jnp.arange(b)

    def step(carry, xs):
        j, step_rng = xs
        tok, pos, cch = carry
        valid = j < widths - 1
        safe = jnp.where(valid, pos, 0)
        slot = jnp.where(valid,
                         block_table[rows, safe // bs] * bs + safe % bs,
                         -1)
        out = paged_forward_step(
            spec, replace_output_logits(tpu_cfg), params, cch, tok[:, None],
            pos[:, None], slot[:, None], block_table,
            jnp.zeros((b,), jnp.int32), sampling_params, step_rng,
            row_seeds=row_seeds, adapter_ids=adapter_ids)
        ntok = jnp.where(valid, out["tokens"], tok)
        npos = jnp.where(valid, pos + 1, pos)
        return (ntok, npos, out["cache"]), ntok

    rngs = jax.random.split(rng, num_steps)
    (_, _, new_cache), toks = jax.lax.scan(
        step, (first_tokens, position_ids, cache),
        (jnp.arange(num_steps), rngs))
    return {"tokens": jnp.transpose(toks, (1, 0)), "cache": new_cache}


def paged_spec_verify(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                      input_ids, position_ids, slot_mapping, block_table,
                      widths, sampling_params=None, row_seeds=None,
                      want_hidden: bool = False, adapter_ids=None):
    """Speculative VERIFY graph over the paged layout: score all candidate
    positions in ONE ragged multi-token dispatch and compute greedy
    acceptance in-graph (reference acceptance: the cumsum-of-mismatch
    trick, model_base.py:2726-2730; dispatch shape: the same ragged
    per-row-width paged rows as chunked prefill — "Ragged Paged
    Attention", arxiv 2604.15464).

    input_ids (B, W): column 0 is each row's last ACCEPTED token, columns
    1..W-1 its draft tokens (drafts may live on device — they never need
    a host round trip). position_ids (B, W) absolute; slot_mapping (B, W)
    with columns >= the row's width at -1 (dropped writes); widths (B,)
    per-row candidate counts in [1, W].

    Exact-match acceptance: draft j is accepted iff it equals the
    target's choice at the previous candidate position; one bonus token
    (the target's correction at the first mismatch) is always emitted,
    so ``num_emitted`` is in [1, width]. The emitted tokens ARE the
    target's choices at consecutive positions — identical to what eager
    decode would produce, whatever the draft quality.

    Under greedy the target choice is the argmax. Under the coupled
    sampled stream (``sampling_params``/``row_seeds`` threaded and the
    config carrying ``stream_seed``) it is the gumbel-coupled draw of
    ``ops/sampling.coupled_sample`` — the in-graph uniform (gumbel)
    variates are keyed by absolute position, so the ratio test of
    classic rejection sampling reduces to exact match under the shared
    noise: acceptance means the draft equals the token eager sampled
    decode would have drawn, and the bonus token is the coupled residual
    resample. Output distribution AND stream are preserved.

    Returns tokens (B, W) (emitted prefix, 0 past ``num_emitted``),
    num_emitted (B,), cache (+ hidden (B, W, H) when ``want_hidden`` —
    Medusa/EAGLE proposers feed on the verified features).
    """
    refuse_recurrent([spec.ssm is not None and "speculation"])
    refuse_window_pool(["k_w" in cache and "speculation"])
    refuse_sparse([spec.sparse is not None and "speculation"])
    if spec.mixed_kv:
        raise NotImplementedError(
            "speculative verify over mixed per-layer caches is "
            "not supported; disable speculation for this model")
    kv_len = block_table.shape[1] * cache["k"].shape[2]
    ai = attn_inputs(spec, position_ids, lambda w, c=0: attn_ops.decode_mask(
        position_ids, kv_len, window=w, chunk=c))
    hidden = _embed(spec, params, input_ids, position_ids)
    hidden, new_cache, _ = run_layers(
        spec, params, cache, hidden, ai, None, position_ids,
        "paged", slot_mapping=slot_mapping, block_table=block_table,
        adapter_ids=adapter_ids)
    logits = _lm_head(spec, params, hidden)
    if _coupled_mode(tpu_cfg, row_seeds):
        # the same coupled draw the eager paged step applies at each
        # position — bit-identity depends on it
        target = sampling_ops.coupled_sample(
            logits, tpu_cfg.on_device_sampling_config, sampling_params,
            row_seeds, position_ids)                            # (B, W)
    else:
        # the same greedy the eager paged step applies
        # (sampling_ops.sample over the untruncated head output)
        target = sampling_ops.sample(logits, None, None, None)  # (B, W)
    b, w = input_ids.shape
    idx = jnp.arange(w, dtype=jnp.int32)[None, :]
    if w > 1:
        # draft j (column j+1) must match the target choice at column j;
        # columns past the row's width are forced mismatches so a padded
        # row can never accept into its neighbour's padding
        mismatch = ((input_ids[:, 1:] != target[:, :-1])
                    | (idx[:, 1:] >= widths[:, None])).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumsum(mismatch, axis=1) == 0, axis=1)
    else:
        n_acc = jnp.zeros((b,), jnp.int32)
    # accepted drafts equal the target choices by construction, so the
    # emitted prefix is simply target[:, :n_acc+1] (bonus included)
    tokens = jnp.where(idx <= n_acc[:, None], target, 0)
    out = {"tokens": tokens, "num_emitted": n_acc + 1, "cache": new_cache}
    if want_hidden:
        out["hidden"] = hidden
    return out


def paged_ragged_step(spec: DecoderSpec, tpu_cfg: TpuConfig, params, cache,
                      input_ids, position_ids, slot_mapping, block_table,
                      widths, emit_modes, sampling_params, rng,
                      row_seeds=None, want_hidden: bool = False,
                      adapter_ids=None):
    """The RAGGED UNIFIED dispatch: ONE mixed paged forward whose rows mix
    decode steps (width 1), prefill chunks (width n, positions at the
    row's own suffix offset) and speculative verify windows (width k+1)
    over the existing slot-mapping/block-table graph — the vLLM-class
    shape of "Ragged Paged Attention" (arxiv 2604.15464), serving
    serving/ragged/'s ``RaggedBatchPlanner`` (README "Ragged dispatch").

    input_ids (B, W): per-kind row content — a decode row's last token in
    column 0, a prefill row's chunk tokens, a verify row's last accepted
    token + drafts (drafts may live on device — no host round trip).
    position_ids (B, W) absolute; slot_mapping (B, W) flat cache slots
    with columns >= the row's width at -1 (dropped writes); block_table
    (B, max_blocks); widths (B,) per-row real-token counts in [1, W].

    emit_modes (B,) selects each row's in-graph emission:

      * 0 — emit nothing (intermediate prefill chunk; frozen/pad row):
        ``num_emitted`` 0, KV writes still land per ``slot_mapping``.
      * 1 — emit the row's LAST real token's sample in column 0 (decode
        step; FINAL prefill chunk): the same ``sample_dp`` over the
        gathered last-position logits the eager paged step applies, so
        streams are bit-identical to :func:`paged_forward_step`.
      * 2 — exact-match acceptance over the candidate window
        (speculative verify): identical math to
        :func:`paged_spec_verify` — draft j accepted iff it equals the
        target's choice at the previous candidate position, columns past
        the row's width forced mismatches, one bonus token always
        emitted, so ``num_emitted`` is in [1, width] and the emitted
        tokens ARE the target's choices (greedy argmax, or the
        gumbel-coupled sampled draw when ``row_seeds`` is threaded and
        the config carries ``stream_seed`` — see
        :func:`paged_spec_verify` for why exact match IS rejection
        sampling under the shared positional noise).

    adapter_ids (B,) optional per-row LoRA pool slots: each row gathers
    its own stacked (A, B) factors in-graph (``modules/lora.lora_delta``),
    so ONE dispatch mixes rows from different adapters — slot 0 is the
    pinned zero adapter (base-model rows bit-identical), and leaving the
    argument absent keeps the graph byte-identical to a LoRA-free build.

    Returns tokens (B, W) (emitted prefix, 0 past ``num_emitted``),
    num_emitted (B,), cache (+ hidden (B, W, H) when ``want_hidden`` —
    Medusa/EAGLE proposers feed on the verified features).
    """
    refuse_recurrent([spec.ssm is not None and "ragged dispatch"])
    refuse_window_pool(["k_w" in cache and "ragged dispatch"])
    refuse_sparse([spec.sparse is not None and "ragged dispatch"])
    if spec.mixed_kv:
        raise NotImplementedError(
            "the ragged unified dispatch over mixed per-layer "
            "caches is not supported; disable ragged mode for this model")
    kv_len = block_table.shape[1] * cache["k"].shape[2]
    ai = attn_inputs(spec, position_ids, lambda w, c=0: attn_ops.decode_mask(
        position_ids, kv_len, window=w, chunk=c))
    hidden = _embed(spec, params, input_ids, position_ids)
    hidden, new_cache, _ = run_layers(
        spec, params, cache, hidden, ai, None, position_ids,
        "paged", slot_mapping=slot_mapping, block_table=block_table,
        adapter_ids=adapter_ids)
    logits = _lm_head(spec, params, hidden)
    coupled = _coupled_mode(tpu_cfg, row_seeds)
    if coupled:
        # verify-row acceptance AND emit-last sampling from the SAME
        # coupled draws the eager paged step applies at each position
        target = sampling_ops.coupled_sample(
            logits, tpu_cfg.on_device_sampling_config, sampling_params,
            row_seeds, position_ids)                            # (B, W)
    else:
        # verify-row acceptance: the same greedy the eager paged step
        # applies (sampling_ops.sample over the untruncated head output)
        target = sampling_ops.sample(logits, None, None, None)  # (B, W)
    b, w = input_ids.shape
    idx = jnp.arange(w, dtype=jnp.int32)[None, :]
    if w > 1:
        mismatch = ((input_ids[:, 1:] != target[:, :-1])
                    | (idx[:, 1:] >= widths[:, None])).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumsum(mismatch, axis=1) == 0, axis=1)
    else:
        n_acc = jnp.zeros((b,), jnp.int32)
    # emit-last rows: per-row in-graph sampling at the row's last real
    # column — the identical sample_dp (or coupled) call of
    # paged_forward_step, over the last-position slice of the SAME
    # lm_head output
    last = jnp.maximum(widths - 1, 0).astype(jnp.int32)
    if coupled:
        sampled = jnp.take_along_axis(target, last[:, None],
                                      axis=1)[:, 0]
    else:
        last_logits = jnp.take_along_axis(logits, last[:, None, None],
                                          axis=1)[:, 0, :]
        sampled = sampling_ops.sample_dp(
            last_logits, tpu_cfg.on_device_sampling_config,
            sampling_params, rng).reshape(b)
    verify_toks = jnp.where(idx <= n_acc[:, None], target, 0)
    single_toks = jnp.where(idx == 0, sampled[:, None],
                            jnp.zeros((), target.dtype))
    tokens = jnp.where((emit_modes == 2)[:, None], verify_toks,
                       jnp.where((emit_modes == 1)[:, None], single_toks,
                                 jnp.zeros((), target.dtype)))
    n_emit = jnp.where(emit_modes == 2, n_acc + 1,
                       jnp.where(emit_modes == 1, 1, 0)).astype(jnp.int32)
    out = {"tokens": tokens, "num_emitted": n_emit, "cache": new_cache}
    if want_hidden:
        out["hidden"] = hidden
    return out


def replace_output_logits(cfg: TpuConfig) -> TpuConfig:
    """decode_loop never returns per-step logits. Called at trace time only,
    so a plain copy per call is fine."""
    if not cfg.output_logits:
        return cfg
    import copy
    c2 = copy.copy(cfg)
    c2.output_logits = False
    return c2


# ---------------------------------------------------------------------------
# Spec resolution from InferenceConfig
# ---------------------------------------------------------------------------

def spec_from_config(config: InferenceConfig, tp_degree: Optional[int] = None,
                     **overrides) -> DecoderSpec:
    """Build a DecoderSpec from HF-style attributes on an InferenceConfig
    (reference analog: each model's ``setup_attr_for_model`` + init_model)."""
    tcfg = config.tpu_config
    tp = tp_degree if tp_degree is not None else tcfg.tp_degree
    # core geometry: explicit overrides win (families whose HF configs use
    # non-standard attribute names — gpt2 n_embd/n_head — pass them in)
    n_q = overrides.pop("num_q_heads",
                        getattr(config, "num_attention_heads", None))
    n_kv = overrides.pop("num_kv_heads",
                         getattr(config, "num_key_value_heads", None)) or n_q
    hidden = overrides.pop("hidden_size",
                           getattr(config, "hidden_size", None))
    head_dim = (overrides.pop("head_dim", None)
                or getattr(config, "head_dim", None) or hidden // n_q)
    n_layers = overrides.pop("num_layers",
                             getattr(config, "num_hidden_layers", None))
    inter = overrides.pop("intermediate_size",
                          getattr(config, "intermediate_size", None))
    rotary_dim = overrides.pop("rotary_dim",
                               getattr(config, "rotary_dim", None))
    gqa = resolve_gqa_sharding(n_q, n_kv, tp)
    rope_scaling = getattr(config, "rope_scaling", None) or {}
    rope_type = rope_scaling.get("rope_type", rope_scaling.get("type"))
    # "default"/"mrope" are not frequency-scaling schemes: default = plain
    # rope; mrope = 3-axis multimodal sections (qwen2-VL)
    mrope_section = None
    mrope_interleaved = False
    if rope_type == "su":          # legacy phi-3 name for longrope
        rope_type = "longrope"
    if rope_type in ("default", "mrope"):
        if "mrope_section" in rope_scaling:
            mrope_section = tuple(int(x) for x in rope_scaling["mrope_section"])
            mrope_interleaved = bool(rope_scaling.get("mrope_interleaved",
                                                      False))
        rope_type = None
    attention_factor = rope_scaling.get("attention_factor")
    rope = RopeConfig(
        head_dim=head_dim,
        rope_theta=float(getattr(config, "rope_theta", 10000.0)),
        rotary_dim=rotary_dim,
        scaling_type=rope_type,
        scaling_factor=float(rope_scaling.get("factor", 1.0)),
        low_freq_factor=float(rope_scaling.get("low_freq_factor", 1.0)),
        high_freq_factor=float(rope_scaling.get("high_freq_factor", 4.0)),
        original_max_position=int(
            rope_scaling.get("original_max_position_embeddings")
            # phi-3 longrope keeps this at the config top level
            or getattr(config, "original_max_position_embeddings", None)
            or getattr(config, "max_position_embeddings", 8192)),
        beta_fast=float(rope_scaling.get("beta_fast") or 32.0),
        beta_slow=float(rope_scaling.get("beta_slow") or 1.0),
        mscale=float(rope_scaling.get("mscale") or 0.0),
        mscale_all_dim=float(rope_scaling.get("mscale_all_dim") or 0.0),
        attention_factor=(float(attention_factor)
                          if attention_factor is not None else None),
        truncate=bool(rope_scaling.get("truncate", True)),
        mrope_section=mrope_section,
        mrope_interleaved=mrope_interleaved,
        # longrope (phi-3 / minicpm4): per-slot rescale factor lists
        short_factor=(tuple(float(x) for x in rope_scaling["short_factor"])
                      if "short_factor" in rope_scaling else None),
        long_factor=(tuple(float(x) for x in rope_scaling["long_factor"])
                     if "long_factor" in rope_scaling else None),
        max_position=int(getattr(config, "max_position_embeddings", 0) or 0),
    )
    vocab = config.vocab_size
    kw = dict(
        num_layers=n_layers,
        hidden_size=hidden,
        num_q_heads=n_q,
        num_kv_heads=n_kv,
        head_dim=head_dim,
        intermediate_size=inter,
        vocab_size=vocab,
        padded_vocab=pad_vocab(vocab, tp),
        # (a config may carry the key as null: cohere2_moe norms by
        # layer_norm_eps)
        rms_eps=float(getattr(config, "rms_norm_eps", None) or 1e-6),
        rope=rope,
        act=getattr(config, "hidden_act", "silu"),
        gqa=gqa,
        tie_word_embeddings=bool(getattr(config, "tie_word_embeddings", False)),
        sliding_window=0,
        dtype=tcfg.jax_dtype,
        kv_dtype=tcfg.jax_kv_dtype,
        # default: XLA path — measured faster than the v1 Pallas kernel on
        # v5e at every prefill length (XLA's fused attention avoids the
        # kernel's layout transposes); the kernel stays opt-in via
        # attn_kernel_enabled until it beats XLA (reference keeps the same
        # dual-path structure, attention_base.py:985-1034)
        flash_prefill=bool(tcfg.attn_kernel_enabled),
        # tri-state passthrough (None = auto cost-model admission)
        decode_kernel=tcfg.attn_block_tkg_kernel_enabled,
        vocab_parallel=tcfg.vocab_parallel,
        quant=quant_spec_from_config(tcfg),
        low_rank=low_rank_mod.low_rank_spec_from_config(tcfg),
        lora=lora_spec_from_config(tcfg),
        seq_parallel=bool(tcfg.sequence_parallel_enabled),
        cp_prefill=tcfg.cp_degree > 1,
        flash_decoding=bool(tcfg.flash_decoding_enabled),
        capture=(tuple(tcfg.tensor_capture_config.capture_targets)
                 if tcfg.tensor_capture_config else None),
        kv_scale=(tcfg.kv_cache_scale if tcfg.kv_cache_quant else None),
        collective_dtype=(tcfg.collective_config.dtype
                          if tcfg.collective_config else None),
        collective_block=(tcfg.collective_config.block
                          if tcfg.collective_config else 32),
    )
    kw.update(overrides)
    if kw.get("moe") is not None:
        mc = tcfg.moe_config
        tkg_ep = getattr(mc, "moe_tkg_ep_degree", None)
        for knob in ("moe_cte_tp_degree", "moe_cte_ep_degree",
                     "moe_tkg_tp_degree"):
            v = getattr(mc, knob, None)
            if v is not None:
                raise NotImplementedError(
                    f"{knob}={v}: under GSPMD the mesh fixes the CTE expert "
                    "layout and the TKG tp extent; only moe_tkg_ep_degree=1 "
                    "(all-experts-local decode) reshards per phase")
        if tkg_ep is not None:
            if tkg_ep != 1:
                raise NotImplementedError(
                    "hybrid MoE sharding supports moe_tkg_ep_degree=1 "
                    "(all-experts-local decode) only; the mesh fixes other "
                    "degree combinations")
            if kw.get("quant") is not None:
                # quantized expert weights keep the stored (prefill) layout
                # through decode — the per-phase reshard silently does not
                # happen (scale shapes vary per quant mode). Say so loudly
                # instead of letting the perf knob be a no-op.
                logger.warning(
                    "moe_tkg_ep_degree=1 (tkg_experts_local) has no effect "
                    "on quantized MoE expert weights: decode keeps the "
                    "prefill expert sharding (quantized leaves are not "
                    "re-constrained). Drop the knob or the quantization.")
                from ..telemetry import get_registry
                _reg = get_registry()
                if _reg.enabled:
                    from ..telemetry.metrics import moe_tkg_degraded_counter
                    moe_tkg_degraded_counter(_reg).inc()
            kw["moe"] = replace(kw["moe"], tkg_experts_local=True)
    if kw.get("ssm") is not None:
        sc = tcfg.speculation_config
        paged = tcfg.is_block_kv_layout
        refuse_recurrent([
            tcfg.is_prefix_caching and "prefix caching",
            tcfg.flash_decoding_enabled and "flash decoding",
            tcfg.is_continuous_batching and not paged
            and "continuous batching",
            tcfg.sequence_parallel_enabled and "sequence parallelism",
            tcfg.windowed_context_encoding and "windowed context encoding",
            sc and (sc.speculation_length or sc.medusa_speculation_length)
            and "speculation",
            (tcfg.tensor_capture_config or tcfg.tensor_replacement_config)
            and "tensor capture/replacement",
            paged and tcfg.decode_chunk_tokens > 1 and "fused decode loop",
            paged and kw.get("ssm_parallel") and "paged parallel hybrid",
            paged and kw["ssm"].kind not in ssm_mod.CONTINUING_KINDS
            and "paged rglru state"])
        if (kw.get("layer_kinds") or kw.get("layer_blocks")) is not None:
            (_check_layer_blocks if kw.get("layer_blocks")
             else _check_layer_kinds)(kw, paged, tp)
        # the recurrent state replaces long-range KV; keep the attention
        # cache simple (full rows, no rolling/mixed layouts)
        kw.setdefault("rolling_window", False)
        kw.setdefault("mixed_kv", False)
    if "rolling_window" not in kw:
        roll = tcfg.rolling_kv_cache
        sc = tcfg.speculation_config
        has_spec = bool(sc and (sc.speculation_length
                                or sc.medusa_speculation_length))
        blockers = []
        if not (kw.get("sliding_window", 0) > 0
                and kw.get("layer_pattern") is None
                and kw.get("attn_chunk", 0) == 0):
            blockers.append("needs a uniform sliding_window model")
        if tcfg.is_block_kv_layout:
            blockers.append("incompatible with the paged KV layout")
        if tcfg.flash_decoding_enabled:
            blockers.append("incompatible with flash decoding")
        if has_spec:
            blockers.append("incompatible with speculation")
        # a window >= seq_len simply never rolls; allow but skip (the full
        # cache is already window-sized)
        worth_it = tcfg.seq_len > kw.get("sliding_window", 0)
        if roll is None:
            roll = not blockers and worth_it
        elif roll and blockers:
            raise ValueError("rolling_kv_cache: " + "; ".join(blockers))
        elif roll and not worth_it:
            roll = False
        kw["rolling_window"] = bool(roll)
    if "mixed_kv" not in kw:
        # per-layer cache sizes for alternating local/global stacks
        # (reference: gpt_oss_kv_cache_manager.py): local layers roll at W
        sc = tcfg.speculation_config
        kw["mixed_kv"] = bool(
            kw.get("layer_pattern") is not None
            and kw.get("sliding_window", 0) > 0
            and kw.get("attn_chunk", 0) == 0
            and tcfg.seq_len > kw["sliding_window"]
            and not tcfg.windowed_context_encoding
            and not tcfg.is_block_kv_layout
            and not tcfg.flash_decoding_enabled
            and not (sc and (sc.speculation_length
                             or sc.medusa_speculation_length))
            and not (tcfg.tensor_capture_config
                     or tcfg.tensor_replacement_config))
    # the paged counterpart of mixed_kv: a pool by layer kind, only where a
    # family ASKS for it (smallthinker: one pool for every layer does not
    # fit beside its weights), and then refused by name where something it
    # cannot do is switched on. Every other local/global stack keeps the one
    # pool: none has run the walk by kind on a chip (ROADMAP B4)
    if kw.get("window_pool"):
        sc = tcfg.speculation_config
        pattern = kw.get("layer_pattern")
        if not tcfg.is_block_kv_layout:
            kw["window_pool"] = False       # the contiguous cache: mixed_kv
        elif not (pattern is not None and kw.get("sliding_window", 0) > 0
                  and kw.get("attn_chunk", 0) == 0
                  # a recurrent stack walks a ring only by layer_kinds
                  and (kw.get("ssm") is None
                       or kw.get("layer_kinds") is not None)
                  and kw.get("mla") is None
                  and kw.get("sub_blocks", 1) == 1
                  and kw.get("moe_pattern") is None
                  and not kw.get("first_dense")):
            raise ValueError(
                "window_pool needs a local/global layer_pattern with a "
                "sliding_window on a plain attention stack")
        elif kw.get("layer_kinds") is None \
                and len(pattern) % _pattern_period(pattern):
            # (the unrolled walk of layer_kinds needs no period)
            raise NotImplementedError(
                f"window_pool: layer_pattern repeats every "
                f"{_pattern_period(pattern)} layers, which does not divide "
                f"its {len(pattern)} layers; the walk by layer kind scans "
                "whole periods")
        else:
            refuse_window_pool([
                tcfg.is_prefix_caching and "prefix caching",
                sc and (sc.speculation_length
                        or sc.medusa_speculation_length) and "speculation",
                tcfg.decode_chunk_tokens > 1 and "fused decode loop",
                tp > 1 and "tensor parallelism",
                (tcfg.tensor_capture_config
                 or tcfg.tensor_replacement_config)
                and "tensor capture/replacement",
                kw.get("alibi") and "alibi"])
    if kw.get("sparse") is not None:
        # a learned sparse selection: refused by name where something it
        # does not run under yet is switched on (SPARSE_UNSUPPORTED)
        sc = tcfg.speculation_config
        refuse_sparse([
            not tcfg.is_block_kv_layout and "contiguous cache",
            sc and (sc.speculation_length or sc.medusa_speculation_length)
            and "speculation",
            tcfg.decode_chunk_tokens > 1 and "fused decode loop",
            tp > 1 and "tensor parallelism",
            (tcfg.tensor_capture_config or tcfg.tensor_replacement_config)
            and "tensor capture/replacement",
            (kw.get("sliding_window", 0) > 0
             or kw.get("layer_pattern") is not None
             or kw.get("attn_chunk", 0) or kw.get("attn_sink")
             or kw.get("alibi") or kw.get("mla") is not None
             or kw.get("ssm") is not None or kw.get("sub_blocks", 1) > 1
             or kw.get("first_dense") or kw.get("moe_pattern") is not None
             or kw.get("norm_position", "pre") != "pre"
             or kw.get("block_style", "sequential") != "sequential"
             or kw.get("sandwich_norm") or kw.get("lora") is not None)
            and "other attention forms"])
    if not kw.get("vocab_parallel", True) and tp > 1:
        # older saved configs carry vocab_parallel=false from when the knob
        # was inert; honoring it replicates the (V, H) table on every device
        logger.warning(
            "vocab_parallel=False with tp=%d: the embedding table will be "
            "REPLICATED on every device (%.0f MB each at bf16)", tp,
            kw["padded_vocab"] * kw["hidden_size"] * 2 / 1e6)
    if kw.get("learned_pos") and tcfg.seq_len > kw["learned_pos"]:
        # decoding past the learned position table would silently reuse the
        # last embedding (HF raises an index error) — fail loudly instead
        raise ValueError(
            f"seq_len {tcfg.seq_len} exceeds the learned position table "
            f"({kw['learned_pos']} positions)")
    return DecoderSpec(**kw)


def _check_layer_kinds(kw: Dict[str, Any], paged: bool, tp: int) -> None:
    """``DecoderSpec.layer_kinds`` against what goes with it, by name: one
    kind a layer from the five, the mixer and window patterns it implies, a
    "full" layer under every "cross" and a mixer under every "gmu", and
    nothing between a key's projection and the cache."""
    kinds = kw["layer_kinds"]
    known = ("mamba", "window", "full", "cross", "gmu")
    if len(kinds) != kw["num_layers"] or set(kinds) - set(known):
        raise ValueError(f"layer_kinds names {kw['num_layers']} layers, each "
                         f"one of {known}; got {kinds}")
    refuse_recurrent([
        not paged and "contiguous decoder-hybrid-decoder",
        tp > 1 and "sharded decoder-hybrid-decoder"])
    if kw.get("ssm_pattern") != tuple(k == "mamba" for k in kinds) \
            or kw.get("ssm_parallel"):
        raise ValueError("layer_kinds: ssm_pattern marks the 'mamba' layers "
                         "and no layer runs attention beside its mixer")
    windows = tuple(k == "window" for k in kinds)
    if any(windows) and not (kw.get("layer_pattern") == windows
                             and kw.get("window_pool")
                             and kw.get("sliding_window", 0) > 0):
        raise ValueError("layer_kinds: 'window' layers keep a ring a batch "
                         "slot: layer_pattern marks them, with window_pool "
                         "and a sliding_window")
    for kind, below in (("cross", "full"), ("gmu", "mamba")):
        if kind in kinds and below not in kinds[:kinds.index(kind)]:
            raise ValueError(f"layer_kinds: a {kind!r} layer reads the "
                             f"nearest {below!r} layer below it; there is "
                             f"none below layer {kinds.index(kind)}")
    if "cross" in kinds and (
            not kw.get("no_rope") or kw.get("qk_norm")
            or kw.get("qk_norm_full") or kw.get("qkv_clip") is not None
            or kw.get("qk_l2_norm") or kw.get("lora") is not None):
        raise NotImplementedError(
            "layer_kinds: a 'cross' layer is handed the 'full' layer's "
            "projected K / V as they are; rotary, q/k norms, clipping and "
            "LoRA between the projection and the cache are not walked")


def _gathered_select(sp: SparseSpec, qi, w, pool, li, positions,
                     block_table):
    """:func:`_indexer_block`'s selection where the kernel is declined
    (``ops/index_select.py``), and the tests' oracle: the pages of the WHOLE
    block table gathered from ``pool`` (the step's keys already in it),
    :func:`_index_scores`, the causal mask by position and
    :func:`topk_select`. The float32 score temps are ``heads`` x the
    selection's own size, so the rows go through in groups where they would
    outgrow the budget (:func:`map_row_groups`). Returns (B, T, table tokens)
    bool."""
    from ..modules import block_kv_cache as bkv
    t = qi.shape[1]
    bs = (pool.shape[2] * pool.shape[3]) // sp.index_dim
    kpos = jnp.arange(block_table.shape[1] * bs, dtype=jnp.int32)

    def select_of(qi_, w_, table_, pos_):
        scores = _index_scores(
            sp, qi_, w_, bkv.gather_index_rows(pool, li, table_))
        seen = kpos[None, None, :] <= pos_[:, :, None]
        return topk_select(scores, seen, sp.topk)

    return map_row_groups(
        select_of, 4 * (sp.index_heads + 4) * t * kpos.shape[0], qi, w,
        block_table, positions.astype(jnp.int32))


def _draw(tpu_cfg: TpuConfig, logits, sampling_params, rng, row_seeds,
          position_ids, last_idx):
    """A paged step's token a row from the sampled token's ``logits``
    (B, V): the positionally coupled draw (``ops/sampling.coupled_sample``,
    keyed by the ABSOLUTE position of the sampled token, the last real input
    position) where :func:`_coupled_mode` says so, else the step's ``rng``."""
    if _coupled_mode(tpu_cfg, row_seeds):
        pos_last = jnp.take_along_axis(
            position_ids, last_idx[:, None].astype(jnp.int32),
            axis=1)[:, 0]
        return sampling_ops.coupled_sample(
            logits, tpu_cfg.on_device_sampling_config, sampling_params,
            row_seeds, pos_last)
    return sampling_ops.sample_dp(
        logits, tpu_cfg.on_device_sampling_config, sampling_params, rng)


def second_decoder_start(spec: DecoderSpec) -> Optional[int]:
    """The first layer of a stack's SECOND decoder, None where it has none:
    under ``layer_kinds``, the layers after the last one that writes a cache
    or a state ("mamba", "window", "full"), all of them "cross" / "gmu". They
    read what the layers under them wrote and write nothing, so a token that
    nobody samples from need not pass them (the prefill saving of a
    decoder-hybrid-decoder, arXiv:2507.06607)."""
    kinds = spec.layer_kinds or ()
    start = 1 + max((i for i, k in enumerate(kinds)
                     if k not in ("cross", "gmu")), default=-1)
    return start if 0 < start < len(kinds) else None


def walk_part(spec: DecoderSpec, ai, width: int):
    """:func:`run_layers_ssm`'s layers [lo, hi) and the hand-over under lo
    where its caller names none: the whole stack; or, in a paged step of
    ``width`` > 1 tokens a row that hands out nothing but the token sampled
    at ``ai["sampled"]`` (:func:`paged_forward_step`), the layers under
    :func:`second_decoder_start`."""
    stop = (second_decoder_start(spec)
            if width > 1 and "sampled" in ai else None)
    return 0, stop or spec.num_layers, None, None


def _layers_seen(spec: DecoderSpec, lo: int):
    """What :func:`run_layers_ssm` has counted when it reaches layer ``lo``:
    the layers of each kind, the attention layers and the mixers."""
    kinds, pat = spec.layer_kinds, spec.resolved_ssm_pattern
    seen = {k: (kinds or ())[:lo].count(k)
            for k in ("mamba", "window", "full", "cross", "gmu")}
    attn = (seen["window"] + seen["full"] if kinds is not None
            else sum(spec.ssm_parallel or not m for m in pat[:lo]))
    return seen, attn, sum(map(bool, pat[:lo]))


def second_decoder_tokens(spec: DecoderSpec, tpu_cfg: TpuConfig, params,
                          cache, hidden, handed, position_ids, slot_mapping,
                          block_table, last_idx, sampling_params, rng,
                          row_seeds, adapter_ids, state_slots):
    """The sampled tokens (B,) of a paged chunk whose walk stopped before the
    second decoder (:func:`walk_part`): ``hidden`` (B, T, H) is the FIRST
    decoder's output, every cache and state of the step is written, and
    ``handed`` is where the walk stopped, the shared pool's layer with the
    step's own K / V, and the scan output the Gated Memory Units gate. Only
    the token at ``last_idx`` of a row is sampled from, so the second
    decoder, the head and the draw run on that ONE token a row: a cross
    layer is one query a row over the shared pool after the full layer's
    write, as in a decode step. And they run under ONE condition, whether
    any row of the dispatch is sampled from (``last_idx`` >= 0): a chunk that
    is not its prompt's last reads neither their weights nor the head's, and
    its tokens, which nobody fetches, are zeros. A row with a negative
    ``last_idx`` beside a sampled one walks its token 0, to no effect."""
    start, shared, memory = handed
    at = jnp.maximum(last_idx, 0).astype(jnp.int32)

    def token_of(x):
        return None if x is None else jnp.take_along_axis(
            x, at.reshape((-1,) + (1,) * (x.ndim - 1)), axis=1)
    hidden, pos = token_of(hidden), token_of(position_ids)
    part = (start, spec.num_layers,
            shared and (shared[0], tuple(map(token_of, shared[1]))),
            token_of(memory))
    kv_len = block_table.shape[1] * cache["k"].shape[2]
    kernel_mode.note(
        "second_decoder", "xla",
        f"apart: layers {start}-{spec.num_layers - 1}, the head and the draw "
        f"on one token a row of {position_ids.shape[1]}, where a row samples")

    def sampled():
        ai = attn_inputs(spec, pos, lambda w, c=0: attn_ops.decode_mask(
            pos, kv_len, window=w, chunk=c))
        h, _, _ = run_layers_ssm(
            spec, params, cache, hidden, ai, None, pos, "paged",
            slot_mapping=token_of(slot_mapping), block_table=block_table,
            adapter_ids=adapter_ids, state_slots=state_slots, part=part)
        return _draw(tpu_cfg, _lm_head(spec, params, h)[:, 0, :],
                     sampling_params, rng, row_seeds, pos,
                     jnp.zeros_like(at))
    # (a draw is int32 a row, ops/sampling: another type fails the trace)
    return jax.lax.cond(jnp.any(last_idx >= 0), sampled,
                        lambda: jnp.zeros(at.shape, jnp.int32))


# ---------------------------------------------------------------------------
# A stack whose layer is ONE sub-block (``DecoderSpec.layer_blocks``). New
# code stands here, behind every function a serving program's kernels are
# traced under: a Pallas kernel's serialised body holds its call stack's line
# numbers (ROADMAP trap 3), so a line moved above rebuilds every cell.
# ---------------------------------------------------------------------------

#: a block's weight stack: its own weights and, with them, the layer's ONE
#: norm ("input_norm"), over the layers of its kind in order of appearance
BLOCK_STACKS = {"mamba": "ssm_layers", "attention": "attn_layers",
                "moe": "moe_layers", "mlp": "layers"}

RECURRENT_UNSUPPORTED.update({
    "contiguous single-block stack": "a stack of one sub-block a layer "
                                     "(DecoderSpec.layer_blocks) is walked "
                                     "on the paged path only",
    "sharded single-block stack": "the stack has run at tp = 1 only (the "
                                  "mixer's per-group norm and the walk over "
                                  "the touched experts are one chip's)",
})


def block_stack(spec: DecoderSpec, i: int) -> Tuple[str, int]:
    """Where layer ``i`` of a ``layer_blocks`` stack keeps its norm and its
    block's weights: ``(stack name, index in it)``, the index being the
    layers of its kind below it (also a "mamba" layer's state layer and an
    "attention" layer's pool layer)."""
    kind = spec.layer_blocks[i]
    return BLOCK_STACKS[kind], spec.layer_blocks[:i].count(kind)


def _plain_moe_specs(m: MoESpec, layers: Dict[str, ParamSpec]
                     ) -> Dict[str, ParamSpec]:
    """:func:`_moe_param_specs`' leaves as a stack of PLAIN experts holds them
    (``glu_style`` "plain": no gate leaf on the experts or the shared expert;
    where the stack is stored wider than published, the pad behind
    ``intermediate_size`` is drawn as zeros); any other stack's as they are."""
    if m.glu_style != "plain":
        return layers
    for gate in ("expert_gate", "shared_gate", "expert_gate_bias"):
        layers.pop(gate, None)
    if m.stored_intermediate > m.intermediate_size:
        for key, axis in (("expert_up", 3), ("expert_down", 2)):
            layers[key] = replace(layers[key],
                                  live=(axis, m.intermediate_size))
    return layers


def _block_stack_specs(spec: DecoderSpec) -> Dict[str, Any]:
    """The weight stacks of a ``layer_blocks`` stack, by kind
    (:data:`BLOCK_STACKS`): a kind's own leaves over its layers and ONE norm
    a layer with them. No MLP leaf on a temporal layer, no temporal leaf on a
    feed-forward one, no second norm anywhere."""
    H, dt = spec.hidden_size, spec.dtype
    own = {
        "mamba": lambda n: ssm_mod.ssm_param_specs(spec.ssm, H, n, dt),
        "attention": lambda n: {
            k: v for k, v in _attn_param_specs(spec, n).items()
            if "norm" not in k or k in ("q_norm", "k_norm", "q_norm_b",
                                        "k_norm_b")},
        "moe": lambda n: _moe_param_specs(spec, n),
        "mlp": lambda n: _dense_mlp_param_specs(spec, n),
    }
    out = {}
    for kind, stack in BLOCK_STACKS.items():
        n = spec.count_kind(kind)
        if n:
            out[stack] = {"input_norm": ParamSpec((n, H), P(), dt, "ones"),
                          **own[kind](n)}
            if spec.norm_bias:
                out[stack]["input_norm_b"] = ParamSpec((n, H), P(), dt,
                                                       "zeros")
    return out


def _check_layer_blocks(kw: Dict[str, Any], paged: bool, tp: int) -> None:
    """``DecoderSpec.layer_blocks`` against what goes with it, by name: one
    kind a layer from :data:`BLOCK_STACKS`, the mixer and expert patterns it
    implies, and nothing of the two-sub-block walks beside it."""
    blocks = kw["layer_blocks"]
    if len(blocks) != kw["num_layers"] or set(blocks) - set(BLOCK_STACKS):
        raise ValueError(f"layer_blocks names {kw['num_layers']} layers, each "
                         f"one of {tuple(BLOCK_STACKS)}; got {blocks}")
    refuse_recurrent([not paged and "contiguous single-block stack",
                      tp > 1 and "sharded single-block stack"])
    if kw.get("ssm_pattern") != tuple(b == "mamba" for b in blocks) \
            or kw.get("ssm_parallel"):
        raise ValueError("layer_blocks: ssm_pattern marks the 'mamba' layers "
                         "and no layer runs attention beside its mixer")
    if ("moe" in blocks) != (kw.get("moe") is not None) or (
            "moe" in blocks
            and kw.get("moe_pattern") != tuple(b == "moe" for b in blocks)):
        raise ValueError("layer_blocks: moe_pattern marks the 'moe' layers "
                         "of a stack with a MoESpec, and only such a stack "
                         "has them")
    if (kw.get("layer_kinds") is not None or kw.get("first_dense")
            or kw.get("sub_blocks", 1) > 1 or kw.get("window_pool")
            or kw.get("norm_position", "pre") != "pre"
            or kw.get("sandwich_norm") or kw.get("mla") is not None
            or kw.get("block_style", "sequential") != "sequential"
            or kw.get("residual_multiplier", 1.0) != 1.0
            or (kw.get("moe") is not None and kw["moe"].router_pre_attn)):
        raise NotImplementedError(
            "layer_blocks: a layer is one pre-norm sub-block and one plain "
            "residual add; layer_kinds, first_dense, sub_blocks, a window "
            "pool, post / sandwich norms, MLA, parallel blocks, a residual "
            "multiplier and a router in front of the attention belong to the "
            "other walks")


def run_layers_blocks(spec: DecoderSpec, params, cache, hidden, ai,
                      seq_ids, positions, phase: str, *,
                      identity_seq_ids=False, adapter_ids=None, kv_view=None,
                      prefill_lens=None, slot_mapping=None, block_table=None,
                      state_slots=None):
    """The unrolled walk of a stack whose layer is ONE sub-block
    (``DecoderSpec.layer_blocks``; HF NemotronHBlock): ``h += block(norm(h))``
    a layer, the block a Mamba-2 mixer, attention, the routed experts or a
    dense MLP. Every layer reads its one norm and its block's weights from
    its kind's stack (:func:`block_stack`) and nothing else: no layer runs a
    second sub-block, a zero one or an identity stand-in. The profiler
    scopes ``mixer`` / ``attn`` / ``moe`` (``mlp``) therefore partition the
    layers; a layer's norm and residual add lie outside them.

    Phase "paged" only. The caches are :func:`run_layers_ssm`'s: the KV pool
    over the attention layers, written and read through ``slot_mapping`` /
    ``block_table``; the mixers' conv tails and states, (Ls, slots, ...),
    stepped in place by the state kernel or by rows (``state_slots`` None:
    row i is slot i; else one slot index a row, the one-row chunk program).
    Returns ``(hidden, cache, side)``; ``side["moe_tally"]`` sums a decode
    step's counts over the EXPERT layers."""
    s = spec.ssm
    blocks = spec.layer_blocks
    refuse_recurrent([
        phase != "paged" and "contiguous single-block stack",
        s.kind not in ssm_mod.CONTINUING_KINDS and "paged rglru state"])
    kf, vf = cache["k"], cache["v"]
    state_keys = [k for k in ("conv_x", "conv_bc", "ssm") if k in cache]
    new_state = {k: cache[k] for k in state_keys}
    valid = slot_mapping >= 0
    tokens = hidden.shape[0] * hidden.shape[1]
    # who steps the state: the kernel, in place on the stack, or the XLA
    # fusions on a layer's rows (run_layers_ssm decides by the same call)
    by_rows = ("no matrix state" if "ssm" not in cache
               else ssm_mod.state_kernel_declined(
                   s, cache["ssm"], *hidden.shape[:2], state_slots))
    slot_bytes = sum(v.size // v.shape[1] * v.dtype.itemsize
                     for v in new_state.values())
    what = f"kind={s.kind} slot_bytes={slot_bytes} chunk={s.chunk_size}"
    if by_rows:
        kernel_mode.note("recurrent_state", "xla", f"{what}: {by_rows}")
    else:
        kernel_mode.note(
            "recurrent_state", kernel_mode.kernel_path(),
            f"{what} {ssm_mod.state_kernel_note(s, cache['ssm'])}")
    # the stack's record by kind, beside recurrent_state and kv_pool
    kernel_mode.note("layer_blocks", "xla", " ".join(
        f"{kind}={spec.count_kind(kind)}" for kind in BLOCK_STACKS
        if spec.count_kind(kind)))
    n_slots = new_state[state_keys[0]].shape[1]
    if state_slots is None and hidden.shape[0] != n_slots:
        raise ValueError(
            f"a paged step of {hidden.shape[0]} rows over {n_slots} state "
            "slots needs state_slots (one slot index a row); without it row "
            "i is slot i")
    # the expert leaves stay in their stack where a custom call reads them
    # in place (moe.stack_leaves); everything else is a static slice
    in_place = (moe_mod.stack_leaves(spec.moe, tokens, params["moe_layers"])
                if "moe" in blocks else ())
    tally = [] if (hidden.shape[1] == 1 and "moe" in blocks) else None
    not_local = jnp.asarray(False)
    for i, kind in enumerate(blocks):
        name, j = block_stack(spec, i)
        stack = params[name]
        lw = jax.tree.map(lambda a: a[j], {k: a for k, a in stack.items()
                                           if k not in in_place})
        if kind == "moe":
            lw.update({k: moe_mod.LayerOfStack(stack[k], j)
                       for k in in_place if k in stack})
        h = _norm(spec, hidden, lw["input_norm"],
                  lw.get("input_norm_b") if spec.norm_bias else None)
        if kind == "attention":
            out, kf, vf, _ = _attn_block(
                spec, h, lw, kf, vf, j, ai, not_local, seq_ids, positions,
                phase, identity_seq_ids=identity_seq_ids,
                slot_mapping=slot_mapping, block_table=block_table,
                adapter_ids=adapter_ids, kv_view=kv_view,
                prefill_lens=prefill_lens)
        elif kind == "mamba":
            with jax.named_scope("mixer"):
                st = {k: _state_rows(new_state[k], j, state_slots)
                      if by_rows or k != "ssm"
                      else ssm_mod.StateStack(new_state[k], j)
                      for k in state_keys}
                out, st_new = ssm_mod.ssm_block(
                    s, lw, h, st, phase=phase, seq_lens=prefill_lens,
                    positions=positions, valid=valid)
                st_new.pop(ssm_mod.SCAN_OUT, None)
                for k2, v2 in st_new.items():
                    new_state[k2] = (
                        v2.stack if isinstance(v2, ssm_mod.StateStack)
                        else _state_put(new_state[k2], j, state_slots, v2))
        else:
            out = _mlp_block(spec, h, lw, "moe" if kind == "moe" else "dense",
                             adapter_ids, phase=phase, tally=tally, live=valid)
        hidden = hidden + _shard(out, AXIS_DP, None, None)
    side = {"moe_tally": sum(tally)} if tally else {}
    return hidden, {"k": kf, "v": vf, **new_state}, side
