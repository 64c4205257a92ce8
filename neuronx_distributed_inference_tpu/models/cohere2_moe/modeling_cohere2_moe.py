"""Command A+ family, ``model_type`` ``cohere2_moe``
(CohereLabs/command-a-plus-05-2026): Cohere's second-generation decoder
(``contrib2.cohere2_block``: ONE bias-free LayerNorm feeding a parallel
block, rotary over interleaved pairs on the sliding-window layers, NO
positional signal on the full layers, tied embeddings, ``logit_scale``)
whose parallel block carries THREE streams off that norm:

    y = x + attention(n) + routed(n) + shared(n),      n = LayerNorm(x)

* ``routed``: a sigmoid router over ``num_experts`` columns in float32, the
  ``num_experts_per_tok`` largest scores renormalised to sum to one
  (``norm_topk_prob``), SwiGLU experts of ``intermediate_size``;
* ``shared``: ``num_shared_experts`` always-on SwiGLU experts of the same
  width whose outputs are AVERAGED (``shared_expert_combination_strategy``
  "average") - held as one gated MLP over their concatenated width with its
  output divided by their count (``MoESpec.shared_mean_of``).

ONE CHIP'S SHARE of the expert layers is spelt as ``models/qwen3_moe``
spells it (``router_num_experts`` / ``first_expert``:
``qwen3_moe.moe_share``); the shared experts and attention are whole on
every share. On the paged serving path a stack that mixes window and full
layers keeps a pool by layer kind (``DecoderSpec.window_pool``).

What ``config.json`` cannot say is taken by convention and listed under
``assumed`` in ``benchmark/configs/command-a-plus-05-2026.json``:
``intermediate_size`` as the width of ONE routed and ONE shared expert, the
mean of the shared experts ADDED to the routed sum, no routing bias / groups
/ scaling, and the checkpoint's tensor names (``mlp.gate``,
``mlp.experts.{e}.{gate,up,down}_proj``,
``mlp.shared_experts.{s}.{gate,up,down}_proj``). transformers 4.57.6 has
``cohere2`` and no ``cohere2_moe``: the loader has run on seeded weights
under those names only. Not built, and refused by name: leading dense layers
(``first_k_dense_replace`` > 0), q / k norms, another selection function or
combination strategy, an ungated or sequential block, a share under tp / ep.

Left out: the vision tower (its config is not in the repository); the model
is served on token ids.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ..contrib2 import Cohere2Family, cohere2_block
from ..family import register_family
from ..model_base import DecoderSpec, spec_from_config
from ..qwen3_moe.modeling_qwen3_moe import moe_share


class Cohere2MoeInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "head_dim",
                "intermediate_size", "num_experts", "num_experts_per_tok",
                "num_shared_experts", "layer_types", "sliding_window"]


def _refusals(config: InferenceConfig) -> List[str]:
    """What of ``config`` this family does not build, each by its key."""
    def has(key, default):
        return getattr(config, key, default)
    return [why for asked, why in (
        (int(has("first_k_dense_replace", 0) or 0) > 0,
         "first_k_dense_replace > 0 (leading dense layers of "
         "prefix_dense_intermediate_size: their block is not described)"),
        (bool(has("use_qk_norm", False)), "use_qk_norm"),
        (has("expert_selection_fn", "sigmoid") != "sigmoid",
         f"expert_selection_fn {has('expert_selection_fn', None)!r} (the "
         "sigmoid router only)"),
        (has("shared_expert_combination_strategy", "average") != "average",
         "shared_expert_combination_strategy "
         f"{has('shared_expert_combination_strategy', None)!r} (the shared "
         "experts' mean only)"),
        (not has("use_parallel_block", True),
         "use_parallel_block false (a sequential block)"),
        (not has("use_gated_activation", True),
         "use_gated_activation false (ungated experts)"),
        (bool(has("attention_bias", False)), "attention_bias"),
        (float(has("rotary_pct", 1) or 1) != 1.0,
         "rotary_pct other than 1 (partial rotary)"),
        (has("position_embedding_type", "rope_gptj") != "rope_gptj",
         f"position_embedding_type {has('position_embedding_type', None)!r} "
         "(rope_gptj, interleaved pairs, only)"),
    ) if asked]


@register_family("cohere2_moe")
class Cohere2MoeFamily(Cohere2Family):
    """The dense family's block and loader hooks (the one norm a layer, the
    unused ``post_norm`` filled with ones) around a routed MLP."""
    config_cls = Cohere2MoeInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        refused = _refusals(config)
        if refused:
            raise NotImplementedError(
                "cohere2_moe does not build: " + "; ".join(refused))
        n = config.num_hidden_layers
        if len(config.layer_types) != n:
            raise ValueError(
                f"cohere2_moe: layer_types names {len(config.layer_types)} "
                f"layers, num_hidden_layers is {n}")
        share = moe_share(config)
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if share["held_experts"] and (tp > 1
                                      or getattr(tcfg, "ep_degree", 1) > 1):
            raise NotImplementedError(
                "cohere2_moe: a chip's share of the expert layers "
                "(router_num_experts) is served on one chip (tp_degree 1, "
                "ep_degree 1): it runs without the exchange that would join "
                "it to the other shares (PERF.md section 7)")
        shared = int(config.num_shared_experts or 0)
        width = int(config.intermediate_size)
        moe = MoESpec(
            **share,
            top_k=config.num_experts_per_tok,
            intermediate_size=width,
            router_act="sigmoid",
            normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
            shared_intermediate=shared * width,
            shared_mean_of=shared,
            act=getattr(config, "hidden_act", "silu"),
        )
        block = cohere2_block(config)
        pattern = block["layer_pattern"] or ()
        mixed = any(pattern) and not all(pattern)
        if pattern and not mixed:
            # window layers only: a uniform window, no pattern to speak of
            block.update(layer_pattern=None, nope_global=False)
        return spec_from_config(
            config, tp_degree, moe=moe, **block,
            # the paged path serves a mixed stack with a pool by layer
            # kind, or not at all (model_base.WINDOW_POOL_UNSUPPORTED)
            window_pool=mixed)

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec: DecoderSpec
                            ) -> Dict[str, np.ndarray]:
        """Assumed names: ``mlp.gate.weight`` (E, H) the router;
        ``mlp.experts.{e}.{gate,up,down}_proj.weight``;
        ``mlp.shared_experts.{s}.{gate,up,down}_proj.weight``, the
        ``shared_mean_of`` shared experts, concatenated here along their
        intermediate width into the one fused branch."""
        x = cls.hf_prefix + ".layers.{i}.mlp."
        out = cls.convert_moe_weights(
            get, spec, router_name=x + "gate.weight",
            expert_fmt=x + "experts.{e}.{name}.weight",
            gate="gate_proj", up="up_proj", down="down_proj")
        count = spec.moe.shared_mean_of

        def fused(name, axis):
            # Linear.weight is (out, in): gate / up stack their outputs
            # (axis 0), down its inputs (axis 1); then (in, out)
            return np.stack([np.ascontiguousarray(np.concatenate(
                [np.asarray(get((x + "shared_experts.{s}.{name}.weight")
                                .format(i=i, s=s, name=name)))
                 for s in range(count)], axis=axis).T)
                for i in range(spec.num_layers)])
        out.update(shared_gate=fused("gate_proj", 0),
                   shared_up=fused("up_proj", 0),
                   shared_down=fused("down_proj", 1))
        return out
