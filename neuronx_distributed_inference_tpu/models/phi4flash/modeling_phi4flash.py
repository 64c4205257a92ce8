"""Phi-4-mini-flash-reasoning family, ``model_type`` ``phi4flash``
(microsoft/Phi-4-mini-flash-reasoning; arXiv:2507.06607, SambaY with
Differential Attention): a DECODER-HYBRID-DECODER. The first half of the
stack (the self-decoder) alternates Mamba-1 mixers with differential
attention inside a sliding window; ONE layer of full differential attention
follows, whose K / V are the only full-length cache of the model; the second
half (the cross-decoder) alternates Gated Memory Units, which gate the LAST
mixer's scan output of the same token, with cross-attention layers that own
a query projection only and attend over that one layer's cache. LayerNorm
with bias in front of each sub-block and at the end, a fused SwiGLU MLP in
every layer, a tied head, no positional signal anywhere.

The temporal block by layer index ``l`` of ``N`` (``mb_per_layer`` 2, ``half
= N / 2``): ``l <= half`` even = Mamba-1 (layer ``half`` is the Gated Memory
Units' source), ``l < half`` odd = window attention, ``l = half + 1`` = full
attention, ``l >= half + 2`` even = Gated Memory Unit, odd = cross-attention
(:func:`layer_kinds`).

Served through the paged path only (``PagedCausalLMApplication`` ->
``PagedEngineAdapter``): a pool BY LAYER KIND beside the state slots
(``DecoderSpec.layer_kinds``: the one full layer books the allocator's
blocks, the window layers keep a ring a batch slot, ``window_pool``), walked
by ``model_base.run_layers_ssm``. Differential attention runs as plain
grouped-query attention over the pool as it lies (``DecoderSpec.diff_attn``:
a head pair's keys and values share a 128-lane kv row, the two queries of a
pair are placed in its halves).

What ``config.json`` does not say is taken from the published modeling file
as recalled (there is no network here) and listed under ``assumed`` in
``benchmark/configs/phi-4-mini-flash-reasoning.json``: Mamba-1's widths, the
pairing of heads, ``lam_init``, the index rules above, the tensor names.
transformers 4.57.6 has no ``phi4flash`` class: the loader has run on
seeded weights under those names only.
"""

from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.ssm import SSMSpec
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config


class Phi4FlashInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "intermediate_size",
                "sliding_window", "layer_norm_eps"]

    def get_text_config(self):
        return self


def layer_kinds(num_layers: int, mb_per_layer: int = 2) -> List[str]:
    """The temporal block of every layer (module docstring)."""
    if mb_per_layer != 2 or num_layers % 4 or num_layers < 4:
        raise NotImplementedError(
            f"phi4flash: the layer rules are written for mb_per_layer 2 and "
            f"a depth that is a multiple of four; got mb_per_layer "
            f"{mb_per_layer}, num_hidden_layers {num_layers}")
    half = num_layers // 2

    def kind(l):
        if l <= half:
            return "window" if l % 2 else "mamba"
        if l == half + 1:
            return "full"
        return "cross" if l % 2 else "gmu"
    return [kind(l) for l in range(num_layers)]


@register_family("phi4flash")
class Phi4FlashFamily(DecoderFamily):
    config_cls = Phi4FlashInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        H = config.hidden_size
        nq, nkv = config.num_attention_heads, config.num_key_value_heads
        if nq % 2 or nkv % 2 or (nq // 2) % (nkv // 2):
            raise ValueError(
                f"phi4flash: differential attention pairs neighbouring "
                f"heads; {nq} query and {nkv} kv heads do not pair up")
        d = H // nq
        kinds = tuple(layer_kinds(config.num_hidden_layers,
                                  int(getattr(config, "mb_per_layer", 2))))
        windows = tuple(k == "window" for k in kinds)
        return spec_from_config(
            config, tp_degree,
            # differential attention: a PAIR of published heads is one kv
            # row (and one value) of the pool, 2 d wide; the query heads
            # stay the published ones, each placed in its half of a row
            # (DecoderSpec.diff_attn), at the published head's scale
            num_kv_heads=nkv // 2, head_dim=2 * d, attn_scale=d ** -0.5,
            diff_attn=True,
            layer_kinds=kinds,
            ssm=SSMSpec(
                kind="mamba1",
                d_inner=int(getattr(config, "mamba_expand", 2) * H),
                num_heads=1, head_dim=1,
                d_state=int(getattr(config, "mamba_d_state", 16)),
                d_conv=int(getattr(config, "mamba_d_conv", 4)),
                dt_rank=int(getattr(config, "mamba_dt_rank", None)
                            or math.ceil(H / 16)),
                conv_bias=bool(getattr(config, "mamba_conv_bias", True))),
            ssm_pattern=tuple(k == "mamba" for k in kinds),
            ssm_parallel=False,
            sliding_window=int(config.sliding_window),
            layer_pattern=windows, window_pool=any(windows),
            no_rope=True,
            norm_type="layernorm", norm_bias=True,
            rms_eps=float(config.layer_norm_eps),
            qkv_bias=True, o_bias=True,
            mlp_bias=bool(getattr(config, "mlp_bias", False)),
            lm_head_bias=bool(getattr(config, "lm_head_bias", False)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)))

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Published names: a layer holds its temporal block as ``attn``
        whatever its kind. Attention: ``attn.Wqkv`` ([q | k | v] rows; a
        cross layer's is the query alone), ``attn.out_proj``,
        ``attn.inner_cross_attn.lambda_{q1,k1,q2,k2}`` and ``.subln.weight``.
        Mamba-1: ``attn.in_proj`` ([x | z] rows), ``conv1d``, ``x_proj``,
        ``dt_proj``, ``A_log``, ``D``, ``out_proj``. A Gated Memory Unit:
        ``attn.in_proj``, ``attn.out_proj``. ``mlp.gate_up_proj`` rows are
        [gate | up]. Layout (``decoder_param_specs``): "layers" = every
        layer's norms + MLP, "attn_layers" / "ssm_layers" / "cross_layers" /
        "gmu_layers" the temporal blocks in order of appearance. A pair of
        heads IS two neighbouring published heads, so no projection is
        permuted."""
        kinds = spec.layer_kinds
        inter = spec.intermediate_size

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)

        def stack_over(*which):
            idx = [i for i, k in enumerate(kinds) if k in which]
            return lambda name, tr=np.asarray: np.stack(
                [tr(get(f"model.layers.{i}.{name}")) for i in idx])

        every = stack_over(*set(kinds))
        attn, cross = stack_over("window", "full"), stack_over("cross")
        ssm, gmu = stack_over("mamba"), stack_over("gmu")

        def diff(over):
            p = "attn.inner_cross_attn."
            return {
                "diff_lambda": np.stack(
                    [over(p + "lambda_" + v).astype(np.float32)
                     for v in ("q1", "k1", "q2", "k2")], axis=1),
                "diff_subln": over(p + "subln.weight")}

        layers = {
            "input_norm": every("input_layernorm.weight"),
            "input_norm_b": every("input_layernorm.bias"),
            "post_norm": every("post_attention_layernorm.weight"),
            "post_norm_b": every("post_attention_layernorm.bias"),
            "gate_proj": every("mlp.gate_up_proj.weight",
                               lambda w: t(np.asarray(w)[:inter])),
            "up_proj": every("mlp.gate_up_proj.weight",
                             lambda w: t(np.asarray(w)[inter:])),
            "down_proj": every("mlp.down_proj.weight", t),
        }
        embed = get("model.embed_tokens.weight")
        if embed.shape[0] < spec.padded_vocab:
            embed = np.pad(embed, [(0, spec.padded_vocab - embed.shape[0]),
                                   (0, 0)])
        C = spec.ssm.d_inner
        out = {
            "embed": embed, "layers": layers,
            "final_norm": get("model.final_layernorm.weight"),
            "final_norm_b": get("model.final_layernorm.bias"),
            "attn_layers": {
                "qkv_proj": attn("attn.Wqkv.weight", t),
                "qkv_bias": attn("attn.Wqkv.bias"),
                "o_proj": attn("attn.out_proj.weight", t),
                "o_bias": attn("attn.out_proj.bias"), **diff(attn)},
            "ssm_layers": {
                "m1_in": ssm("attn.in_proj.weight", t),
                "m1_conv": ssm("attn.conv1d.weight",
                               lambda w: np.asarray(w).reshape(C, -1)),
                "m1_x": ssm("attn.x_proj.weight", t),
                "m1_dt": ssm("attn.dt_proj.weight", t),
                "m1_dt_b": ssm("attn.dt_proj.bias").astype(np.float32),
                # the state lies (d_state, d_inner): so does A
                "m1_A_log": ssm("attn.A_log", t).astype(np.float32),
                "m1_D": ssm("attn.D").astype(np.float32),
                "m1_out": ssm("attn.out_proj.weight", t)},
        }
        if spec.ssm.conv_bias:
            out["ssm_layers"]["m1_conv_b"] = ssm("attn.conv1d.bias")
        if "cross" in kinds:
            out["cross_layers"] = {
                "q_proj": cross("attn.Wqkv.weight", t),
                "q_bias": cross("attn.Wqkv.bias"),
                "o_proj": cross("attn.out_proj.weight", t),
                "o_bias": cross("attn.out_proj.bias"), **diff(cross)}
        if "gmu" in kinds:
            out["gmu_layers"] = {
                "gmu_in": gmu("attn.in_proj.weight", t),
                "gmu_out": gmu("attn.out_proj.weight", t)}
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        raise NotImplementedError(
            "phi4flash: transformers 4.57.6 has no class for this "
            "model_type; the golden is benchmark/references/phi4flash.py")
