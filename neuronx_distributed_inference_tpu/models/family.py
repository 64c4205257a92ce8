"""Model-family protocol (reference analog: the per-model contract described
in SURVEY §2.7 — each family provides ``get_required_attributes``,
``setup_attr_for_model``, ``init_model``, ``convert_hf_to_neuron_state_dict``,
``load_hf_model``).

A family here is a class with:
  * ``config_cls``            — InferenceConfig subclass
  * ``build_spec(config)``    — InferenceConfig -> DecoderSpec
  * ``convert_hf_state_dict`` — HF numpy state dict -> stacked TPU param tree
  * ``load_hf_model(path)``   — CPU torch model for golden accuracy checks
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Type

import numpy as np

from ..config import InferenceConfig
from ..parallel.layers import place_q_weight, replicate_kv_weight
from .model_base import DecoderSpec, spec_from_config

_REGISTRY: Dict[str, Type["DecoderFamily"]] = {}


def register_family(*names: str):
    def deco(cls):
        for n in names:
            _REGISTRY[n] = cls
        cls.family_names = names
        return cls
    return deco


def get_family(name: str) -> Type["DecoderFamily"]:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model family {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def family_for_config(config) -> Type["DecoderFamily"]:
    mt = getattr(config, "model_type", None)
    return get_family(mt)


class DecoderFamily:
    """Base implementation that covers the standard Llama-shaped decoder.
    Families override hooks for their deltas (bias, qk-norm, soft caps, MoE)."""

    family_names = ()
    config_cls: Type[InferenceConfig] = InferenceConfig
    hf_prefix = "model"
    spec_overrides: Dict[str, Any] = {}
    # HF weight name feeding the pre-MLP norm ("post_norm" in the spec);
    # sandwich-norm families (gemma3) point it at pre_feedforward_layernorm
    post_norm_src = "post_attention_layernorm"
    # HF weight name feeding the pre-attention norm (apertus uses
    # "attention_layernorm")
    input_norm_src = "input_layernorm"
    # HF attention output-projection module name (phi uses "dense")
    attn_o_src = "self_attn.o_proj"

    # -- spec --
    @classmethod
    def build_spec(cls, config: InferenceConfig, tp_degree: Optional[int] = None
                   ) -> DecoderSpec:
        return spec_from_config(config, tp_degree, **cls.spec_overrides)

    # -- weights --
    @classmethod
    def convert_hf_state_dict(cls, sd: Dict[str, np.ndarray], spec: DecoderSpec
                              ) -> Dict[str, Any]:
        """HF names/layouts -> stacked TPU tree
        (reference analog: convert_hf_to_neuron_state_dict per model).

        torch Linear stores (out, in); we store (in, out) so matmuls read
        x @ w. Q/K/V head padding + KV replication happen here at load time
        (reference: gqa.py preshard_hook :679+)."""
        p = cls.hf_prefix
        g = spec.gqa
        D = spec.head_dim

        def get(name):
            if name in sd:
                return np.asarray(sd[name])
            raise KeyError(f"missing checkpoint tensor {name}; have "
                           f"{sorted(k for k in sd)[:8]}...")

        def layer_stack(fmt, transform):
            return np.stack(
                [transform(get(fmt.format(i=i))) for i in range(spec.num_layers)])

        def q_t(w):  # (nq*D, H) -> (H, padded_q*D)
            return place_q_weight(np.ascontiguousarray(w.T), g, D, axis=-1)

        def kv_t(w):
            return replicate_kv_weight(np.ascontiguousarray(w.T), g, D, axis=-1)

        def o_t(w):  # (H, nq*D) -> (padded_q*D, H): place on input axis
            return place_q_weight(np.ascontiguousarray(w.T), g, D, axis=0)

        def t(w):
            return np.ascontiguousarray(w.T)

        def ident(w):
            return np.asarray(w)

        layers = {
            "input_norm": layer_stack(
                p + ".layers.{i}." + cls.input_norm_src + ".weight", ident),
            "q_proj": layer_stack(p + ".layers.{i}.self_attn.q_proj.weight", q_t),
            "k_proj": layer_stack(p + ".layers.{i}.self_attn.k_proj.weight", kv_t),
            "v_proj": layer_stack(p + ".layers.{i}.self_attn.v_proj.weight", kv_t),
            "o_proj": layer_stack(p + ".layers.{i}." + cls.attn_o_src + ".weight", o_t),
            "post_norm": layer_stack(
                p + ".layers.{i}." + cls.post_norm_src + ".weight", ident),
        }
        layers.update(cls.convert_mlp_weights(get, layer_stack, spec))
        layers.update(cls.convert_extra_layer_weights(get, layer_stack, spec))
        if spec.qkv_bias:
            def q_b(b):
                return place_q_weight(b, g, D)

            def kv_b(b):
                return replicate_kv_weight(b, g, D)

            layers["q_bias"] = layer_stack(p + ".layers.{i}.self_attn.q_proj.bias", q_b)
            layers["k_bias"] = layer_stack(p + ".layers.{i}.self_attn.k_proj.bias", kv_b)
            layers["v_bias"] = layer_stack(p + ".layers.{i}.self_attn.v_proj.bias", kv_b)
        if spec.o_bias:
            layers["o_bias"] = layer_stack(
                p + ".layers.{i}." + cls.attn_o_src + ".bias", ident)
        if spec.qk_norm:
            layers["q_norm"] = layer_stack(p + ".layers.{i}.self_attn.q_norm.weight", ident)
            layers["k_norm"] = layer_stack(p + ".layers.{i}.self_attn.k_norm.weight", ident)

        def vpad(w):  # pad vocab rows to padded_vocab
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0])] +
                           [(0, 0)] * (w.ndim - 1))
            return w

        out = {
            "embed": vpad(get(p + ".embed_tokens.weight")),
            "layers": layers,
            "final_norm": get(p + ".norm.weight"),
        }
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(vpad(get("lm_head.weight")).T)
        return out

    # -- extra per-layer weights hook (sandwich norms, sinks, …) --
    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec: DecoderSpec
                                    ) -> Dict[str, np.ndarray]:
        return {}

    # -- MLP / MoE weight conversion hook --
    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec: DecoderSpec
                            ) -> Dict[str, np.ndarray]:
        """Dense gate/up/down by default; MoE families override
        (reference analog: per-model convert_hf_to_neuron_state_dict MoE
        branches, e.g. mixtral/dbrx)."""
        p = cls.hf_prefix

        def t(w):
            return np.ascontiguousarray(w.T)

        return {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.gate_proj.weight", t),
            "up_proj": layer_stack(p + ".layers.{i}.mlp.up_proj.weight", t),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.down_proj.weight", t),
        }

    @classmethod
    def convert_moe_weights(cls, get, spec: DecoderSpec, router_name: str,
                            expert_fmt: str, gate: str, up: str, down: str
                            ) -> Dict[str, np.ndarray]:
        """Shared MoE conversion: stack per-layer routers (fp32, transposed to
        (H,E)) and per-layer-per-expert projections to (L,E,in,out). Name
        templates use {i} (layer), {e} (expert), {name} (projection).

        ONE CHIP'S SHARE (``MoESpec.held_experts``): the router keeps its
        every column and the ``num_held`` experts from ``first_expert`` on
        are stacked. A checkpoint that names them as the router does (a
        whole one, or a share under its global names) has the share's LAST
        expert at ``first_expert + num_held - 1`` and is read at
        ``first_expert + e``; one that holds the share alone under local
        names (the benchmark's seeded weights) has no such tensor and is
        read at ``e``. A tensor neither naming has is ``get``'s KeyError,
        by name."""
        moe = spec.moe
        L, E = spec.num_layers, moe.num_held
        first = 0
        if moe.holds_share and moe.first_expert:
            try:
                get(expert_fmt.format(
                    i=0, e=moe.first_expert + E - 1, name=up))
                first = moe.first_expert
            except KeyError:
                pass

        def experts(name):
            return np.stack([
                np.stack([np.ascontiguousarray(np.asarray(get(
                    expert_fmt.format(i=i, e=first + e, name=name))).T)
                    for e in range(E)]) for i in range(L)])

        return {
            "router": np.stack([np.ascontiguousarray(np.asarray(get(
                router_name.format(i=i))).T.astype(np.float32))
                for i in range(L)]),
            "expert_gate": experts(gate),
            "expert_up": experts(up),
            "expert_down": experts(down),
        }

    # -- golden --
    @classmethod
    def load_hf_model(cls, model_path: str):
        """CPU torch model for golden logit generation
        (reference: each model's load_hf_model; utils/accuracy.py golden flow)."""
        import transformers
        return transformers.AutoModelForCausalLM.from_pretrained(model_path)
