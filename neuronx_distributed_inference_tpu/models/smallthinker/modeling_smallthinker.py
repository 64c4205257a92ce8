"""SmallThinker family, ``model_type`` ``smallthinker``
(PowerInfer/SmallThinker-21BA3B-Instruct, SmallThinker-4BA0.6B-Instruct): a
sparse decoder in which

* three layers in four attend inside a sliding window with rotary positions
  and the fourth attends over everything with NO positional signal
  (``sliding_window_layout[l]`` / ``rope_layout[l]``: 1 = window + rotary,
  0 = global + NoPE);
* the router of a layer's experts reads the ATTENTION's normed input (it is
  placed in front of the attention block, so the routing is known before
  attention runs), while the experts read the post-attention norm
  (``MoESpec.router_pre_attn``);
* the top-k of the router's logits is taken first and the softmax over the k
  (``moe_primary_router_apply_softmax`` + ``norm_topk_prob``: the softmax
  over all experts renormalised over the picked ones, which is the same
  numbers);
* every layer's MLP is the routed block, experts ReLU-gated:
  ``down(relu(gate x) * up x)``.

On the paged serving path the window layers keep a ring of ``window + widest
step + block`` tokens a batch slot and only the global layers book the
allocator's blocks (``DecoderSpec.window_pool``): one pool for all layers at
the model's own context length does not fit a chip beside the weights.

What ``config.json`` cannot say is taken by convention and listed under
``assumed`` in ``benchmark/configs/smallthinker-21b-a3b.json``: what the
router reads, no projection bias and no q/k norm, the ReLU gate, and the
checkpoint's tensor names (``block_sparse_moe.primary_router``,
``block_sparse_moe.experts.{e}.{gate,up,down}``). transformers 4.57.6 has no
``smallthinker`` class: the loader has run on seeded weights under those
names only.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config


class SmallThinkerInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "head_dim",
                "moe_num_primary_experts", "moe_num_active_primary_experts",
                "moe_ffn_hidden_size", "sliding_window_layout",
                "sliding_window_size"]


@register_family("smallthinker")
class SmallThinkerFamily(DecoderFamily):
    config_cls = SmallThinkerInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        n = config.num_hidden_layers
        window = [int(x) for x in config.sliding_window_layout]
        rope = [int(x) for x in
                getattr(config, "rope_layout", None) or window]
        if len(window) != n or len(rope) != n:
            raise ValueError(
                f"smallthinker: sliding_window_layout ({len(window)}) and "
                f"rope_layout ({len(rope)}) give one entry a layer, "
                f"num_hidden_layers is {n}")
        if rope != window:
            # the spec says "global layers carry no rotary" (nope_global),
            # not a free rotary switch a layer
            raise NotImplementedError(
                "smallthinker: rope_layout differs from "
                "sliding_window_layout; a per-layer rotary layout that is "
                "not the window layout is not supported (the published "
                "models rotate exactly their window layers)")
        if not getattr(config, "moe_primary_router_apply_softmax", True):
            raise NotImplementedError(
                "smallthinker: moe_primary_router_apply_softmax false (a "
                "sigmoid router) is not supported")
        moe = MoESpec(
            num_experts=config.moe_num_primary_experts,
            top_k=config.moe_num_active_primary_experts,
            intermediate_size=config.moe_ffn_hidden_size,
            pre_softmax_topk=True,
            normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
            act="relu",
            router_pre_attn=True,
        )
        pattern = tuple(bool(w) for w in window)
        mixed = any(pattern) and not all(pattern)
        return spec_from_config(
            config, tp_degree, moe=moe,
            intermediate_size=config.moe_ffn_hidden_size,
            sliding_window=(int(config.sliding_window_size)
                            if any(pattern) else 0),
            # a stack of window layers only is a uniform window, one of
            # global layers only has no pattern to speak of
            layer_pattern=pattern if mixed else None,
            nope_global=mixed,
            no_rope=not any(pattern),
            # the paged path serves it with a pool by layer kind, or not at
            # all (refused by name: model_base.WINDOW_POOL_UNSUPPORTED)
            window_pool=mixed)

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec: DecoderSpec
                            ) -> Dict[str, np.ndarray]:
        """Published names: block_sparse_moe.primary_router.weight (E,H);
        block_sparse_moe.experts.{e}.gate / up / down .weight."""
        p = cls.hf_prefix
        return cls.convert_moe_weights(
            get, spec,
            router_name=p + ".layers.{i}.block_sparse_moe.primary_router"
                            ".weight",
            expert_fmt=p + ".layers.{i}.block_sparse_moe.experts.{e}."
                           "{name}.weight",
            gate="gate", up="up", down="down")
