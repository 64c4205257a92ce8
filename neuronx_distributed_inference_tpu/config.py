"""Configuration system for the TPU-native inference framework.

Mirrors the knob surface of the reference config system
(reference: src/neuronx_distributed_inference/models/config.py:84-1042 —
``NeuronConfig`` / ``InferenceConfig`` / sub-configs) but is designed TPU-first:
parallelism degrees map onto named mesh axes (tp/cp/dp/ep) of a
``jax.sharding.Mesh`` rather than process-group construction, and dtypes are
JAX dtypes.

Sub-config parity (reference: models/config.py):
  - OnDeviceSamplingConfig      (:1064)
  - ChunkedPrefillConfig        (:1078)
  - MoEConfig / MoENeuronConfig (:798-846)
  - FusedSpecConfig             (:1045)
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax.numpy as jnp

logger = logging.getLogger("nxdi_tpu")

_DTYPE_MAP = {
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float32": jnp.float32,
    "fp32": jnp.float32,
    "float16": jnp.float16,
    "fp16": jnp.float16,
    "int8": jnp.int8,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
}


def to_jax_dtype(dtype: Any):
    """Resolve a string / jnp dtype spec to a jnp dtype."""
    if isinstance(dtype, str):
        if dtype not in _DTYPE_MAP:
            raise ValueError(f"unknown dtype {dtype!r}; expected one of {sorted(_DTYPE_MAP)}")
        return _DTYPE_MAP[dtype]
    return dtype


def dtype_name(dtype: Any) -> str:
    for name, dt in _DTYPE_MAP.items():
        if dt == dtype and name in ("bfloat16", "float32", "float16", "int8",
                                    "float8_e4m3fn", "float8_e5m2"):
            return name
    return str(dtype)


@dataclass
class OnDeviceSamplingConfig:
    """On-device sampling knobs (reference: models/config.py:1064-1076)."""

    do_sample: bool = False
    top_k: int = 1
    top_p: float = 1.0
    temperature: float = 1.0
    deterministic: bool = False
    global_topk: int = 256        # stage-1 topk width for hierarchical top-k
    # Positionally coupled streams (ops/sampling.coupled_sample): every
    # draw keyed by (stream_seed, request seed, absolute position), so
    # sampled streams are reproducible and path-invariant — the knob
    # that unlocks sampled speculation / ragged serving (README
    # "Sampled speculation & compressed decode"). None = per-dispatch
    # rng (legacy; refused under speculation).
    stream_seed: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class ChunkedPrefillConfig:
    """Chunked prefill / prefix caching (reference: models/config.py:1078-1094)."""

    kernel_q_tile_size: int = 128

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class MoEConfig:
    """MoE knobs (reference: models/config.py:798-846 ``MoENeuronConfig``)."""

    routed_scaling_factor: Optional[float] = None
    # hybrid CTE/TKG expert sharding (reference: moe_v2.py:135-161
    # HybridShardingConfig): moe_tkg_ep_degree=1 switches DECODE to
    # all-experts-local with the intermediate dim split over every model
    # axis; prefill keeps the ep-sharded layout. Other degree combinations
    # are not supported (the GSPMD mesh fixes the axis extents).
    moe_cte_tp_degree: Optional[int] = None
    moe_cte_ep_degree: Optional[int] = None
    moe_tkg_tp_degree: Optional[int] = None
    moe_tkg_ep_degree: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class LoraServingConfig:
    """Multi-LoRA serving knobs (reference: modules/lora_serving/lora_serving_config.py)."""

    max_loras: int = 1
    max_lora_rank: int = 16
    target_modules: Optional[List[str]] = None
    lora_ckpt_paths: Optional[Dict[str, str]] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class SpeculationConfig:
    """Speculative decoding knobs (reference: models/config.py:243-274 block).

    Covers vanilla draft/target, EAGLE and Medusa variants.
    """

    speculation_length: int = 0
    medusa_speculation_length: int = 0
    token_tree_config: Optional[Dict[str, Any]] = None
    draft_model_path: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class TensorCaptureConfig:
    """Intermediate-tensor capture appended to graph outputs
    (reference: models/config.py:1121-1169 + utils/tensor_capture_utils.py).

    capture_targets: per-layer points — "layer_output", "attn_output",
    "mlp_output" (stacked (L, B, T, H) in the step output under
    ``captured``)."""

    capture_targets: List[str] = field(
        default_factory=lambda: ["layer_output"])

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class TensorReplacementConfig:
    """Feed golden tensors into chosen layer points for fault localization
    (reference: models/config.py:1172-1202 + utils/tensor_replacement/).

    targets: point names (same vocabulary as capture); source_path: .npz
    with one array per target, shaped (L, B, T, H); layers: which layer
    indices to replace (None = all layers present in the arrays)."""

    targets: List[str] = field(default_factory=list)
    source_path: Optional[str] = None
    layers: Optional[List[int]] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class CollectiveConfig:
    """Quantized decode-collective knobs (EQuARX-style wire compression,
    PAPERS.md arxiv 2506.17615).

    dtype: "int8" | "fp8" | None. None (default) keeps the implicit GSPMD
    fp32 collectives — graphs are bit-unchanged. int8/fp8 swaps the
    row-parallel decode all-reduce for a shard_map ring exchange whose wire
    payload is quantized (parallel/collectives.py); accumulation stays full
    precision.
    block: absmax-scale block size along each ring chunk — the activation
    analog of the weight stack's blockwise_symmetric group_size.
    """

    dtype: Optional[str] = None
    block: int = 32

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _known_keys(cls, d: Dict[str, Any]) -> Dict[str, Any]:
    """``d`` without the keys ``cls`` has no field for, each warned about
    (reference: models/config.py:639-640): an older tree's config loads."""
    known = {f.name for f in dataclasses.fields(cls)}
    for k in d:
        if k not in known:
            logger.warning("%s: ignoring unknown key %r", cls.__name__, k)
    return {k: v for k, v in d.items() if k in known}


_SUBCONFIG_TYPES = {
    "on_device_sampling_config": OnDeviceSamplingConfig,
    "chunked_prefill_config": ChunkedPrefillConfig,
    "moe_config": MoEConfig,
    "lora_config": LoraServingConfig,
    "speculation_config": SpeculationConfig,
    "tensor_capture_config": TensorCaptureConfig,
    "tensor_replacement_config": TensorReplacementConfig,
    "collective_config": CollectiveConfig,
}


@dataclass
class TpuConfig:
    """TPU-native equivalent of the reference ``NeuronConfig``
    (reference: models/config.py:84-786). Same knob names where sensible.

    Parallelism degrees are mesh-axis sizes:
      tp_degree -> "tp" axis, cp_degree -> "cp", attention_dp_degree -> "dp",
      ep_degree -> "ep" (reference: models/config.py:361-375).
    """

    # --- batch / sequence geometry (reference: models/config.py:120-164) ---
    batch_size: int = 1
    ctx_batch_size: Optional[int] = None      # prefill batch
    tkg_batch_size: Optional[int] = None      # decode batch
    is_continuous_batching: bool = False
    seq_len: int = 128                        # max total sequence length
    max_context_length: Optional[int] = None  # max prefill length
    # windowed context encoding (reference: models/model_base.py:878-933 +
    # the >=32k long-context mode, models/config.py:612-621): prompts are
    # prefilled in fixed windows re-invoking one graph with growing KV —
    # the (S, S) prefill attention materialization becomes (W, S), which
    # is what makes >=32k contexts feasible. None = one-shot prefill.
    windowed_context_encoding: Optional[int] = None
    n_positions: Optional[int] = None

    # --- dtypes ---
    dtype: str = "bfloat16"                   # weights/activations
    kv_cache_dtype: Optional[str] = None      # default = dtype; fp8 supported

    # --- parallelism degrees (reference: models/config.py:361-390) ---
    tp_degree: int = 1
    cp_degree: int = 1                        # context parallel (prefill)
    attention_dp_degree: int = 1              # data parallel decode attention
    ep_degree: int = 1
    sequence_parallel_enabled: bool = False
    # vocab-parallel embedding table (sharded on V); False replicates the
    # table on every device (reference: models/config.py:142)
    vocab_parallel: bool = True

    # --- KV cache (reference: models/config.py:167-170, 277-317) ---
    kv_cache_batch_size: Optional[int] = None
    is_block_kv_layout: bool = False
    # rolling sliding-window KV cache (reference: kv_cache_manager.py:605-606
    # pos %% (w-1) rolling write): cache holds only ``sliding_window`` slots.
    # None = auto (on for uniform-window models without speculation/paged)
    rolling_kv_cache: Optional[bool] = None
    pa_num_blocks: Optional[int] = None
    pa_block_size: int = 32
    is_prefix_caching: bool = False
    is_chunked_prefill: bool = False
    flash_decoding_enabled: bool = False

    # --- bucketing (reference: models/config.py:186-213) ---
    enable_bucketing: bool = True
    buckets: Optional[List[int]] = None           # explicit decode buckets
    context_encoding_buckets: Optional[List[int]] = None
    token_generation_buckets: Optional[List[int]] = None
    # 2-D bucketing (reference: autobucketing.py:22-64,203 — batch x seq
    # TKG buckets + prefix x prefill buckets; selection
    # model_wrapper.py:923-1045): short batches pad to the smallest BATCH
    # bucket instead of the full compiled batch, and the paged app sizes
    # its block-table width from a ladder instead of max_blocks.
    # Tradeoff: a sub-cache-batch decode graph takes the row-gather paths
    # instead of the identity fast path / fused decode kernel — worth it
    # when pad rows dominate (large batch, small requests), not for
    # window/sink models that lean on the kernel; hence default OFF
    enable_2d_bucketing: bool = False
    tkg_batch_buckets: Optional[List[int]] = None   # explicit batch ladder

    # --- sampling ---
    on_device_sampling_config: Optional[OnDeviceSamplingConfig] = None
    output_logits: bool = False               # return logits (accuracy/debug)
    # prefill returns the full (B,S,H) hidden states — needed once per
    # request to prime the EAGLE draft cache (reference: EAGLE CTE,
    # model_base.py:1931-2092)
    output_full_hidden: bool = False

    # --- speculation ---
    speculation_config: Optional[SpeculationConfig] = None

    # --- MoE ---
    moe_config: Optional[MoEConfig] = None

    # --- LoRA ---
    lora_config: Optional[LoraServingConfig] = None

    # --- chunked prefill ---
    chunked_prefill_config: Optional[ChunkedPrefillConfig] = None

    # --- observability (reference: models/config.py:320-353) ---
    tensor_capture_config: Optional[TensorCaptureConfig] = None
    tensor_replacement_config: Optional[TensorReplacementConfig] = None

    # --- quantization (reference: models/config.py:216-241) ---
    quantized: bool = False
    quantization_dtype: str = "int8"
    quantization_type: str = "per_channel_symmetric"
    modules_to_not_convert: Optional[List[str]] = None
    kv_cache_quant: bool = False
    # scaled-mode KV quantization: store x/scale (reference:
    # kv_cache_manager.py:661-692); 1.0 = direct cast
    kv_cache_scale: float = 1.0

    # --- quantized decode collectives (parallel/collectives.py) ---
    collective_config: Optional[CollectiveConfig] = None

    # --- low-rank (SVD-compressed) decode MLP (modules/low_rank.py,
    # NeuronMLP arxiv 2510.25977): factorize gate/up/down into rank-r
    # (U, V) pairs host-side; None = dense ---
    mlp_low_rank: Optional[int] = None

    # --- kernels (reference: models/config.py:417-567 — ~25 enable flags) ---
    # None/False = XLA attention path (measured faster than the v1 Pallas
    # kernel on v5e); True = opt into the Pallas flash prefill kernel where
    # ops/flash_attention.supports() holds (tp=1, arange positions)
    attn_kernel_enabled: Optional[bool] = None
    # Pallas fused decode attention (reference: attn_block_tkg NKI kernel
    # family, models/config.py:417-567); None = auto (on where supported)
    attn_block_tkg_kernel_enabled: Optional[bool] = None

    # --- host loop ---
    decode_chunk_tokens: int = 1              # tokens per device call in decode

    # --- misc / runtime ---
    save_sharded_checkpoint: bool = False
    seed: int = 0

    def __post_init__(self):
        if self.max_context_length is None:
            self.max_context_length = self.seq_len
        if self.ctx_batch_size is None:
            self.ctx_batch_size = 1 if self.is_continuous_batching else self.batch_size
        if self.tkg_batch_size is None:
            self.tkg_batch_size = self.batch_size
        if self.kv_cache_batch_size is None:
            self.kv_cache_batch_size = max(self.tkg_batch_size, self.batch_size)
        if self.kv_cache_dtype is None:
            self.kv_cache_dtype = self.dtype
        if self.n_positions is None:
            self.n_positions = self.seq_len
        self.validate()

    # -- validation (reference: models/config.py:645-721) --
    def validate(self):
        if self.seq_len < 1:
            raise ValueError("seq_len must be >= 1")
        if self.max_context_length > self.seq_len:
            raise ValueError(
                f"max_context_length ({self.max_context_length}) cannot exceed "
                f"seq_len ({self.seq_len})")
        if self.cp_degree > 1 and self.tp_degree % self.cp_degree != 0:
            raise ValueError("cp_degree must divide tp_degree (cp shards the tp axis "
                             "during prefill)")
        if self.attention_dp_degree > 1:
            if self.tp_degree % self.attention_dp_degree != 0:
                raise ValueError("attention_dp_degree must divide tp_degree")
            if self.tkg_batch_size % self.attention_dp_degree != 0:
                raise ValueError("tkg_batch_size must be divisible by attention_dp_degree")
        if self.is_chunked_prefill and not self.is_block_kv_layout:
            raise ValueError("chunked prefill requires block KV layout")
        if self.is_prefix_caching and not self.is_block_kv_layout:
            raise ValueError("prefix caching requires block KV layout")
        if self.is_block_kv_layout and self.pa_num_blocks is None:
            self.pa_num_blocks = (
                self.kv_cache_batch_size * ((self.seq_len + self.pa_block_size - 1)
                                            // self.pa_block_size))
        cc = self.collective_config
        if cc is not None and cc.dtype is not None:
            # typed refusal shared with parallel/collectives.py (lazy import:
            # resilience is self-contained, but config loads first at startup)
            from .resilience.errors import ConfigurationError
            if cc.dtype not in ("int8", "fp8"):
                raise ConfigurationError(
                    f"collective_config.dtype {cc.dtype!r} unsupported: "
                    "expected 'int8', 'fp8', or None")
            if cc.block < 1:
                raise ConfigurationError(
                    "collective_config.block must be >= 1")
        if self.mlp_low_rank is not None:
            from .resilience.errors import ConfigurationError
            if self.mlp_low_rank < 1:
                raise ConfigurationError(
                    f"mlp_low_rank must be >= 1, got {self.mlp_low_rank} "
                    "(None disables the low-rank MLP)")
        sc = self.on_device_sampling_config
        if sc is not None and sc.stream_seed is not None \
                and not sc.do_sample:
            from .resilience.errors import ConfigurationError
            raise ConfigurationError(
                "on_device_sampling_config.stream_seed requires "
                "do_sample=True: coupled streams only exist for sampled "
                "decode (greedy is already deterministic)")

    # -- dtype helpers --
    @property
    def jax_dtype(self):
        return to_jax_dtype(self.dtype)

    @property
    def jax_kv_dtype(self):
        return to_jax_dtype(self.kv_cache_dtype)

    @property
    def speculation_length(self) -> int:
        return self.speculation_config.speculation_length if self.speculation_config else 0

    # -- serialization (reference: models/config.py:927-1038 JSON round-trip) --
    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if dataclasses.is_dataclass(v):
                v = v.to_dict() if hasattr(v, "to_dict") else dataclasses.asdict(v)
            out[f.name] = v
        return out

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TpuConfig":
        d = _known_keys(cls, d)
        for key, sub_cls in _SUBCONFIG_TYPES.items():
            if isinstance(d.get(key), dict):
                d[key] = sub_cls(**_known_keys(sub_cls, d[key]))
        return cls(**d)


# Back-compat alias: reference users know this as NeuronConfig.
NeuronConfig = TpuConfig


@dataclass
class MoETpuConfig(TpuConfig):
    """Convenience subclass that always carries an MoEConfig
    (reference: models/config.py:798 ``MoENeuronConfig``)."""

    def __post_init__(self):
        if self.moe_config is None:
            self.moe_config = MoEConfig()
        super().__post_init__()


class InferenceConfig:
    """Wrapper pairing a HF-style model config with a :class:`TpuConfig`
    (reference: models/config.py:849-1042 ``InferenceConfig``).

    Arbitrary HF config attributes live directly on the object; ``tpu_config``
    (alias ``neuron_config``) holds runtime knobs. JSON round-trip via
    :meth:`save` / :meth:`load`.
    """

    _NON_HF_KEYS = ("tpu_config",)

    def __init__(self, tpu_config: TpuConfig, load_config=None, metadata=None, **kwargs):
        self.tpu_config = tpu_config
        self.metadata = metadata or {}
        if load_config is not None:
            if callable(load_config):
                load_config(self)
            else:
                for k, v in dict(load_config).items():
                    setattr(self, k, v)
        for k, v in kwargs.items():
            setattr(self, k, v)
        self.add_derived_config()
        self.validate_config()

    # alias to match reference naming
    @property
    def neuron_config(self) -> TpuConfig:
        return self.tpu_config

    def add_derived_config(self):
        """Model families override to compute derived attributes
        (reference: per-model ``setup_attr_for_model``)."""

    def get_required_attributes(self) -> List[str]:
        return []

    def validate_config(self):
        missing = [a for a in self.get_required_attributes() if not hasattr(self, a)]
        if missing:
            raise ValueError(f"InferenceConfig missing required attributes: {missing}")

    def get_text_config(self) -> "InferenceConfig":
        """Multimodal configs override to return the text sub-config
        (reference: models/config.py:946)."""
        return self

    # -- serialization --
    def to_dict(self) -> Dict[str, Any]:
        hf = {k: v for k, v in self.__dict__.items()
              if k not in self._NON_HF_KEYS and not k.startswith("_")
              and _json_safe(v)}
        return {"tpu_config": self.tpu_config.to_dict(), "hf_config": hf,
                "config_cls": f"{type(self).__module__}:{type(self).__qualname__}"}

    def to_json_string(self) -> str:
        return json.dumps(self.to_dict(), indent=2, default=str, sort_keys=True)

    def save(self, path: str):
        """Serialize next to compiled artifacts
        (reference: models/config.py:927-944)."""
        if os.path.isdir(path) or path.endswith(os.sep):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, "tpu_inference_config.json")
        else:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_json_string())

    @classmethod
    def from_json_string(cls, s: str) -> "InferenceConfig":
        d = json.loads(s)
        config_cls = cls
        if "config_cls" in d and ":" in d.get("config_cls", ""):
            import importlib
            mod_name, qual = d["config_cls"].split(":")
            try:
                mod = importlib.import_module(mod_name)
                config_cls = getattr(mod, qual.split(".")[-1], cls)
            except ImportError:
                logger.warning("could not re-import config class %s", d["config_cls"])
        obj = config_cls.__new__(config_cls)
        obj.tpu_config = TpuConfig.from_dict(d["tpu_config"])
        obj.metadata = {}
        for k, v in d.get("hf_config", {}).items():
            setattr(obj, k, v)
        obj.add_derived_config()
        return obj

    @classmethod
    def load(cls, path: str) -> "InferenceConfig":
        if os.path.isdir(path):
            path = os.path.join(path, "tpu_inference_config.json")
        with open(path) as f:
            return cls.from_json_string(f.read())


def _json_safe(v) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


def load_pretrained_config(model_path: str):
    """Build a load_config callable from a HF checkpoint dir's config.json
    (reference: utils/hf_adapter.py:36 ``load_pretrained_config``)."""

    def _load(cfg: InferenceConfig):
        cfg_path = os.path.join(model_path, "config.json")
        with open(cfg_path) as f:
            hf = json.load(f)
        for k, v in hf.items():
            setattr(cfg, k, v)
        cfg.model_path = model_path

    return _load
