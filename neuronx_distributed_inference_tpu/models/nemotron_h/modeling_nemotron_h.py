"""NVIDIA Nemotron-H families, ``model_type`` ``nemotron_h``
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 and the dense Nemotron-H rows;
the published ``modeling_nemotron_h.py``): a stack whose layer is ONE
sub-block (``DecoderSpec.layer_blocks``; ``model_base.run_layers_blocks``).
``h += mixer(rmsnorm(h))`` a layer, the mixer by ``hybrid_override_pattern``:

* ``M``: a Mamba-2 mixer (``modules/ssm.py`` kind ``mamba2``) with
  ``n_groups`` B / C groups and the gated RMSNorm PER GROUP (gate first, the
  norm over each group's ``d_inner / n_groups`` channels: the only form
  ``mamba2_mixer`` has). ``d_inner = mamba_num_heads x mamba_head_dim``; the
  published ``expand x hidden_size`` is NOT read (Nemotron 3 Nano: 4096
  against 5376).
* ``*``: grouped-query attention with NO rotary embedding (the published
  forward applies none; ``rope_theta`` / ``partial_rotary_factor`` are unused
  keys) and no bias.
* ``E``: ``n_routed_experts`` PLAIN experts, ``down(relu²(up(x)))``
  (``MoESpec.glu_style`` "plain": two matrices, no gate), routed as
  DeepSeek-V3 routes (sigmoid scores in float32, the top
  ``num_experts_per_tok`` of ``s + e_score_correction_bias``, renormalised
  over ``sum + 1e-20``, times ``routed_scaling_factor``; no group limit
  walked: ``n_group == topk_group == 1``) and ONE shared expert of
  ``moe_shared_expert_intermediate_size``, plain too, always on. An expert
  width that is not whole 128-lane vregs (1856 = 14.5) is STORED padded with
  zero columns of ``up`` and rows of ``down`` (``MoESpec.stored_intermediate``
  1920): the walk over the touched experts and the grouped matmuls need whole
  tiles, and the pad adds exact zeros.
* ``-``: a dense plain MLP ``down(relu²(up(x)))`` of ``intermediate_size``
  (the older Nemotron-H rows), served by the same walk.

One chip's share of an expert-parallel deployment: ``n_routed_experts`` is
what the weights HOLD, ``router_n_routed_experts`` what the router scores
(default: the same), ``first_expert`` the first held one (as DeepSeek-V3's).
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ...modules.ssm import SSMSpec
from ...ops.moe_decode import LANES
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ..contrib import _ident, _t, _vpad
from ..family import DecoderFamily, register_family
from ..model_base import BLOCK_STACKS, spec_from_config
from ..recurrent import FalconH1Family

#: ``hybrid_override_pattern``'s letters, as ``DecoderSpec.layer_blocks``
BLOCK_OF = {"M": "mamba", "*": "attention", "E": "moe", "-": "mlp"}
#: added to the sum the picked sigmoids are renormalised by (the published
#: ``NemotronHTopkRouter``, DeepSeek-V3's)
TOPK_NORM_EPS = 1e-20


class NemotronHInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size",
                "hybrid_override_pattern", "mamba_num_heads",
                "mamba_head_dim", "ssm_state_size"]

    def get_text_config(self):
        return self


def layer_blocks(config) -> List[str]:
    pattern = str(config.hybrid_override_pattern)
    if len(pattern) != config.num_hidden_layers \
            or set(pattern) - set(BLOCK_OF):
        raise ValueError(
            f"hybrid_override_pattern must name {config.num_hidden_layers} "
            f"layers, each one of {''.join(BLOCK_OF)}; got {pattern!r}")
    return [BLOCK_OF[c] for c in pattern]


@register_family("nemotron_h")
class NemotronHFamily(DecoderFamily):
    config_cls = NemotronHInferenceConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if tp > 1 or getattr(tcfg, "ep_degree", 1) > 1:
            raise NotImplementedError(
                "nemotron_h is served on one chip (tp_degree 1, ep_degree 1): "
                "a stack of one sub-block a layer has not run sharded")
        blocks = layer_blocks(config)
        if "mamba" not in blocks:
            raise NotImplementedError(
                "nemotron_h without a Mamba-2 layer: the single-block walk "
                "carries a state cache; a stack with none is not walked")
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("mamba_hidden_act", "silu"), ("use_bias", False),
                          ("mlp_bias", False), ("attention_bias", False),
                          ("mamba_proj_bias", False),
                          ("mlp_hidden_act", "relu2")):
            got = getattr(config, key, want)
            if got != want:
                raise NotImplementedError(
                    f"nemotron_h with {key} = {got!r}: the published value "
                    f"is {want!r} and nothing else has been walked")
        nh, hd = int(config.mamba_num_heads), int(config.mamba_head_dim)
        g = int(getattr(config, "n_groups", 1))
        eps = float(getattr(config, "layer_norm_epsilon", None)
                    or getattr(config, "norm_eps", 1e-5))
        limit = getattr(config, "time_step_limit", None) or (0.0,
                                                             float("inf"))
        moe = None
        if "moe" in blocks:
            held = int(config.n_routed_experts)
            scored = int(getattr(config, "router_n_routed_experts", None)
                         or held)
            inter = int(config.moe_intermediate_size)
            moe = MoESpec(
                num_experts=scored,
                top_k=int(config.num_experts_per_tok),
                intermediate_size=inter,
                stored_intermediate=-(-inter // LANES) * LANES
                if inter % LANES else 0,
                glu_style="plain", act="relu2",
                normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
                topk_norm_eps=TOPK_NORM_EPS,
                routed_scaling=float(getattr(
                    config, "routed_scaling_factor", 1.0)),
                router_act="sigmoid",
                has_router_bias=True, router_bias_mode="select",
                shared_intermediate=int(getattr(
                    config, "n_shared_experts", 1)) * int(getattr(
                        config, "moe_shared_expert_intermediate_size", 0)),
                held_experts=held if held < scored else 0,
                first_expert=int(getattr(config, "first_expert", 0)))
        return spec_from_config(
            config, tp_degree,
            rms_eps=eps,
            act="relu2", mlp_glu=False,
            intermediate_size=int(getattr(config, "intermediate_size", 0)),
            moe=moe,
            ssm=SSMSpec(
                kind="mamba2", d_inner=nh * hd, num_heads=nh, head_dim=hd,
                d_state=int(config.ssm_state_size), n_groups=g,
                d_conv=int(getattr(config, "conv_kernel", 4)),
                # any chunking gives the published chunk's result: 64 keeps
                # the (heads, chunk, chunk) float32 intra-chunk tensors of a
                # full-batch pack small (granitemoehybrid's choice)
                chunk_size=min(int(getattr(config, "chunk_size", 128)), 64),
                conv_bias=bool(getattr(config, "use_conv_bias", True)),
                # y = w * rmsnorm_by_group(y * silu(z)): gate first
                gated_norm=True, norm_before_gate=False, norm_eps=eps,
                dt_limit=(float(limit[0]), float(limit[1]))),
            layer_blocks=tuple(blocks),
            ssm_pattern=tuple(b == "mamba" for b in blocks),
            moe_pattern=(tuple(b == "moe" for b in blocks)
                         if moe is not None else None),
            ssm_parallel=False,
            no_rope=True,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)))

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """The published names (``backbone.embeddings``, ``backbone.layers.
        {i}.norm`` and ``.mixer.*``, ``backbone.norm_f``, ``lm_head``) into
        the stacks by kind (``model_base.BLOCK_STACKS``), each holding its
        layers' ``input_norm`` beside the block's own leaves. The mixer's
        ``in_proj`` / ``conv1d`` are split by destination ([gate | x | B | C
        | dt], Falcon-H1's row order); an expert's ``up_proj`` / ``down_proj``
        are padded with zeros to the stored width."""
        g, D = spec.gqa, spec.head_dim
        blocks, moe = spec.layer_blocks, spec.moe
        p = "backbone.layers.{i}."
        m = p + "mixer."

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def of(kind):
            idx = [i for i, b in enumerate(blocks) if b == kind]

            def stack(fmt, tr=_ident):
                return np.stack([tr(get(fmt.format(i=i))) for i in idx])
            return idx, stack

        out = {"embed": _vpad(get("backbone.embeddings.weight"),
                              spec.padded_vocab),
               "final_norm": get("backbone.norm_f.weight")}
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(
                _vpad(get("lm_head.weight"), spec.padded_vocab).T)

        idx, stack = of("mamba")
        if idx:
            # in_proj / conv1d split by destination ([gate | x | B | C | dt]),
            # Falcon-H1's converter under this family's names
            out[BLOCK_STACKS["mamba"]] = {
                "input_norm": stack(p + "norm.weight"),
                **FalconH1Family.convert_extra_layer_weights(
                    get, stack, spec, p=m)}

        idx, stack = of("attention")
        if idx:
            def kv(w):
                return replicate_kv_weight(_t(w), g, D, axis=-1)
            out[BLOCK_STACKS["attention"]] = {
                "input_norm": stack(p + "norm.weight"),
                "qkv_proj": np.concatenate([
                    stack(m + "q_proj.weight",
                          lambda w: place_q_weight(_t(w), g, D, axis=-1)),
                    stack(m + "k_proj.weight", kv),
                    stack(m + "v_proj.weight", kv)], axis=-1),
                "o_proj": stack(m + "o_proj.weight",
                                lambda w: place_q_weight(_t(w), g, D, axis=0)),
            }

        idx, stack = of("moe")
        if idx:
            pad = ((moe.stored_intermediate or moe.intermediate_size)
                   - moe.intermediate_size)

            def experts(name, tr):
                return np.stack([np.stack([
                    tr(get((m + f"experts.{e}.{name}.weight").format(i=i)))
                    for e in range(moe.num_held)]) for i in idx])
            layers = {
                "input_norm": stack(p + "norm.weight"),
                "router": stack(m + "gate.weight", _t).astype(np.float32),
                "router_bias": stack(
                    m + "gate.e_score_correction_bias").astype(np.float32),
                # up_proj (I, H) -> (H, I | 0), down_proj (H, I) -> (I | 0, H)
                "expert_up": experts("up_proj", lambda w: np.pad(
                    _t(w), ((0, 0), (0, pad)))),
                "expert_down": experts("down_proj", lambda w: np.pad(
                    _t(w), ((0, pad), (0, 0)))),
            }
            if moe.shared_intermediate:
                layers["shared_up"] = stack(
                    m + "shared_experts.up_proj.weight", _t)
                layers["shared_down"] = stack(
                    m + "shared_experts.down_proj.weight", _t)
            out[BLOCK_STACKS["moe"]] = layers

        idx, stack = of("mlp")
        if idx:
            # the plain dense MLP's fc1 / fc2 in the gate_proj / down_proj
            # slots (DecoderSpec.mlp_glu False)
            out[BLOCK_STACKS["mlp"]] = {
                "input_norm": stack(p + "norm.weight"),
                "gate_proj": stack(m + "up_proj.weight", _t),
                "down_proj": stack(m + "down_proj.weight", _t),
            }
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        raise NotImplementedError(
            "the installed transformers has no NemotronHForCausalLM; load "
            "the checkpoint's state dict and convert_hf_state_dict it")
