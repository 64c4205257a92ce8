"""Recurrent state-space blocks — the framework's recurrent/hybrid state
axis (reference: contrib/models/Falcon-H1-0.5B-Instruct/src/
modeling_falcon_h1.py FalconH1Mixer and contrib/models/recurrentgemma-2b-it/
src/modeling_recurrent_gemma.py — SURVEY §2.7 contrib inventory).

TPU-first redesign, not a translation:
  * The reference recomputes the FULL quadratic SSD form every forward (no
    decode state cache — its FalconH1Mixer.forward is O(T²) per token).
    Here the recurrent state is a first-class cache pytree carried next to
    the KV cache: prefill computes it once with a chunked ``lax.scan``
    (O(T·chunk) memory, MXU-shaped intra-chunk matmuls), decode is a pure
    O(1) recurrence step.
  * The RG-LRU linear recurrence uses ``jax.lax.associative_scan`` — the
    log-depth parallel scan XLA maps well to TPU — instead of the
    reference's per-timestep Python loop.
  * Mamba's in_proj is stored SPLIT by destination ([gate|x|B|C|dt] →
    five tensors) so tensor parallelism can shard the head-structured
    gate/x paths on the model axis while the tiny per-group B/C/dt stay
    replicated — the clean TP layout the torch reference approximates
    with gather_output=True (i.e. no sharding at all).

State layout (stacked over the SSM-bearing layers, batch-sharded on dp,
channels/heads on the model axis):
  mamba2: conv_x (Ls,B,d_inner,K-1), conv_bc (Ls,B,2·g·N,K-1),
          ssm (Ls,B,nh,hd,N) fp32
  rglru:  conv_x (Ls,B,W,K-1), ssm (Ls,B,W) fp32
The conv tails hold the last K-1 *pre-conv* projected inputs, so a decode
step is ``concat(tail, current) → depthwise dot`` exactly like the
reference's cached path (modeling_falcon_h1.py torch_forward cached branch).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.layers import ParamSpec
from ..parallel.mesh import AXIS_DP, AXIS_MP


@dataclass(frozen=True)
class SSMSpec:
    """Geometry of the recurrent block shared by all layers that carry one.

    kind "mamba2": Falcon-H1 / Mamba-2 selective SSM (SSD form).
    kind "rglru": recurrentgemma / Griffin RG-LRU linear recurrence.
    kind "shortconv": LFM2 gated short convolution (conv state only —
      reference: contrib/models/lfm2-2.6b; HF Lfm2ShortConv).
    """

    kind: str                 # "mamba2" | "rglru"
    d_inner: int              # mamba d_ssm / rglru lru_width
    num_heads: int            # mamba_n_heads / rglru num_attention_heads
    head_dim: int             # mamba_d_head / rglru block_width
    d_state: int = 0          # mamba ssm state size N (rglru: unused)
    n_groups: int = 1         # mamba B/C groups
    d_conv: int = 4           # depthwise conv kernel width K
    chunk_size: int = 128     # prefill scan chunk
    conv_bias: bool = True
    gated_norm: bool = False      # mamba_rms_norm: RMSNormGated before out
    norm_before_gate: bool = False
    norm_eps: float = 1e-6        # gated-norm eps (falcon-h1: rms_norm_eps)
    dt_limit: Tuple[float, float] = (0.0, float("inf"))

    @property
    def bc_size(self) -> int:
        return 2 * self.n_groups * self.d_state


# ---------------------------------------------------------------------------
# Parameter + state specs
# ---------------------------------------------------------------------------

def ssm_param_specs(s: SSMSpec, hidden: int, Ls: int, dtype) -> Dict[str, ParamSpec]:
    """Stacked per-layer weights for the recurrent block (layer dim Ls
    first, like every other stacked layer weight in decoder_param_specs)."""
    if s.kind == "mamba2":
        gn = s.n_groups * s.d_state
        specs = {
            "ssm_in_gate": ParamSpec((Ls, hidden, s.d_inner), P(None, None, AXIS_MP), dtype),
            "ssm_in_x": ParamSpec((Ls, hidden, s.d_inner), P(None, None, AXIS_MP), dtype),
            "ssm_in_bc": ParamSpec((Ls, hidden, 2 * gn), P(), dtype),
            "ssm_in_dt": ParamSpec((Ls, hidden, s.num_heads), P(), dtype),
            "ssm_conv_x": ParamSpec((Ls, s.d_inner, s.d_conv), P(None, AXIS_MP, None), dtype),
            "ssm_conv_bc": ParamSpec((Ls, 2 * gn, s.d_conv), P(), dtype),
            "ssm_dt_bias": ParamSpec((Ls, s.num_heads), P(), jnp.float32, "ones"),
            "ssm_A_log": ParamSpec((Ls, s.num_heads), P(), jnp.float32, "zeros"),
            "ssm_D": ParamSpec((Ls, s.num_heads), P(), jnp.float32, "ones"),
            "ssm_out": ParamSpec((Ls, s.d_inner, hidden), P(None, AXIS_MP, None), dtype),
        }
        if s.conv_bias:
            specs["ssm_conv_x_b"] = ParamSpec((Ls, s.d_inner), P(None, AXIS_MP), dtype, "zeros")
            specs["ssm_conv_bc_b"] = ParamSpec((Ls, 2 * gn), P(), dtype, "zeros")
        if s.gated_norm:
            specs["ssm_norm"] = ParamSpec((Ls, s.d_inner), P(None, AXIS_MP), dtype, "ones")
        return specs
    if s.kind == "shortconv":
        W = s.d_inner
        specs = {
            "sc_in_b": ParamSpec((Ls, hidden, W), P(None, None, AXIS_MP), dtype),
            "sc_in_c": ParamSpec((Ls, hidden, W), P(None, None, AXIS_MP), dtype),
            "sc_in_x": ParamSpec((Ls, hidden, W), P(None, None, AXIS_MP), dtype),
            "sc_conv": ParamSpec((Ls, W, s.d_conv), P(None, AXIS_MP, None), dtype),
            "sc_out": ParamSpec((Ls, W, hidden), P(None, AXIS_MP, None), dtype),
        }
        if s.conv_bias:
            specs["sc_in_b_b"] = ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros")
            specs["sc_in_c_b"] = ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros")
            specs["sc_in_x_b"] = ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros")
            specs["sc_conv_b"] = ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros")
            specs["sc_out_b"] = ParamSpec((Ls, hidden), P(), dtype, "zeros")
        return specs
    if s.kind == "rglru":
        W, nh, bw = s.d_inner, s.num_heads, s.head_dim
        return {
            "rg_y": ParamSpec((Ls, hidden, W), P(None, None, AXIS_MP), dtype),
            "rg_y_b": ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros"),
            "rg_x": ParamSpec((Ls, hidden, W), P(None, None, AXIS_MP), dtype),
            "rg_x_b": ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros"),
            "rg_conv": ParamSpec((Ls, W, s.d_conv), P(None, AXIS_MP, None), dtype),
            "rg_conv_b": ParamSpec((Ls, W), P(None, AXIS_MP), dtype, "zeros"),
            "rg_param": ParamSpec((Ls, W), P(None, AXIS_MP), jnp.float32, "ones"),
            "rg_igate_w": ParamSpec((Ls, nh, bw, bw), P(None, AXIS_MP, None, None), dtype),
            "rg_igate_b": ParamSpec((Ls, nh, bw), P(None, AXIS_MP, None), dtype, "zeros"),
            "rg_rgate_w": ParamSpec((Ls, nh, bw, bw), P(None, AXIS_MP, None, None), dtype),
            "rg_rgate_b": ParamSpec((Ls, nh, bw), P(None, AXIS_MP, None), dtype, "zeros"),
            "rg_out": ParamSpec((Ls, W, hidden), P(None, AXIS_MP, None), dtype),
            "rg_out_b": ParamSpec((Ls, hidden), P(), dtype, "zeros"),
        }
    raise ValueError(f"unknown SSM kind {s.kind!r}")


def ssm_state_shapes(s: SSMSpec, Ls: int, batch: int, dtype
                     ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """{cache_key: (shape, dtype)} for the recurrent state entries."""
    K1 = s.d_conv - 1
    if s.kind == "mamba2":
        return {
            "conv_x": ((Ls, batch, s.d_inner, K1), dtype),
            "conv_bc": ((Ls, batch, s.bc_size, K1), dtype),
            "ssm": ((Ls, batch, s.num_heads, s.head_dim, s.d_state),
                    jnp.float32),
        }
    if s.kind == "shortconv":
        return {"conv_x": ((Ls, batch, s.d_inner, K1), dtype)}
    return {
        "conv_x": ((Ls, batch, s.d_inner, K1), dtype),
        "ssm": ((Ls, batch, s.d_inner), jnp.float32),
    }


def init_ssm_state(s: SSMSpec, Ls: int, batch: int, dtype, mesh=None
                   ) -> Dict[str, Any]:
    """Zero recurrent-state entries, device-placed with their shardings —
    the state analog of kv_cache.init_cache (single source of the state
    pytree layout for the application AND the multichip dryrun)."""
    from jax.sharding import NamedSharding
    pspecs = ssm_state_pspecs(s)
    out = {}
    for k, (shape, dt) in ssm_state_shapes(s, Ls, batch, dtype).items():
        out[k] = jnp.zeros(shape, dt,
                           device=(NamedSharding(mesh, pspecs[k])
                                   if mesh is not None else None))
    return out


def ssm_state_pspecs(s: SSMSpec) -> Dict[str, P]:
    if s.kind == "mamba2":
        return {
            "conv_x": P(None, AXIS_DP, AXIS_MP, None),
            "conv_bc": P(None, AXIS_DP, None, None),
            "ssm": P(None, AXIS_DP, AXIS_MP, None, None),
        }
    if s.kind == "shortconv":
        return {"conv_x": P(None, AXIS_DP, AXIS_MP, None)}
    return {"conv_x": P(None, AXIS_DP, AXIS_MP, None),
            "ssm": P(None, AXIS_DP, AXIS_MP)}


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _causal_conv_prefill(x, w, b):
    """Depthwise causal conv over (B, T, C) with kernel (C, K): K shifted
    adds — K is 4; XLA fuses this into a handful of vector ops (vs a conv
    primitive whose tiny channel-depthwise form lowers poorly)."""
    K = w.shape[-1]
    out = x * w[:, K - 1]
    for j in range(K - 1):
        shift = K - 1 - j
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        out = out + shifted * w[:, j]
    if b is not None:
        out = out + b
    return out


def _conv_tail(x, seq_lens, K1):
    """Last K-1 columns of (B, T, C) ending at seq_len per row (zeros where
    the window reaches before position 0) → (B, C, K-1)."""
    B, T, C = x.shape
    idx = seq_lens[:, None] - K1 + jnp.arange(K1)[None, :]       # (B, K1)
    take = jnp.clip(idx, 0, T - 1)
    tail = jnp.take_along_axis(x, take[:, :, None], axis=1)      # (B, K1, C)
    tail = jnp.where((idx >= 0)[:, :, None], tail, 0)
    return tail.transpose(0, 2, 1)


def _conv_step(tail, cur, w, b):
    """One decode conv step: (B, C, K-1) tail + (B, C) current → (value
    (B, C), new tail). Matches the reference's roll-and-dot cached branch
    (modeling_falcon_h1.py torch_forward)."""
    win = jnp.concatenate([tail, cur[:, :, None]], axis=-1)       # (B,C,K)
    val = jnp.sum(win * w[None], axis=-1)
    if b is not None:
        val = val + b
    return val, win[:, :, 1:]


def _segsum(a_log):
    """Segment-sum decay matrix: M[t, s] = sum_{j=s+1..t} a_log[j] for
    s <= t, -inf otherwise. a_log (B, c, H) → (B, H, c, c)."""
    c = a_log.shape[1]
    acs = jnp.cumsum(a_log, axis=1)                               # (B,c,H)
    diff = acs[:, :, None, :] - acs[:, None, :, :]                # (B,t,s,H)
    mask = jnp.tril(jnp.ones((c, c), bool))
    diff = jnp.where(mask[None, :, :, None], diff, -jnp.inf)
    return diff.transpose(0, 3, 1, 2)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) mixer — Falcon-H1 flavor
# ---------------------------------------------------------------------------

def mamba2_mixer(s: SSMSpec, lw, x, state: Dict[str, Any], *, phase: str,
                 seq_lens=None, positions=None):
    """One mamba2 block over already-normed input x (B, T, H).

    lw: this layer's weight dict (the ssm_* entries of the stacked layer
    params, indexed at this layer). state: {"conv_x","conv_bc","ssm"} THIS
    layer's state entries. Returns (y (B,T,H), new_state).

    Prefill semantics track the reference's SSD form
    (modeling_falcon_h1.py torch_forward non-cached branch) with one
    divergence that is a fix, not a drift: positions ≥ seq_len get dt = 0
    (decay 1, input contribution 0), so a right-padded prefill leaves the
    carried state exactly as an unpadded run would — the torch reference
    only supports left-padding for this reason.
    """
    B, T, H = x.shape
    f32 = jnp.float32
    gn = s.n_groups * s.d_state
    nh, hd, N = s.num_heads, s.head_dim, s.d_state

    gate = x @ lw["ssm_in_gate"]
    xs = x @ lw["ssm_in_x"]
    bc = x @ lw["ssm_in_bc"]
    dt_raw = (x @ lw["ssm_in_dt"]).astype(f32)

    if phase == "prefill":
        valid = (positions < seq_lens[:, None])                   # (B,T)
        xs = jnp.where(valid[..., None], xs, 0)
        bc = jnp.where(valid[..., None], bc, 0)
        xs_c = jax.nn.silu(_causal_conv_prefill(
            xs, lw["ssm_conv_x"], lw.get("ssm_conv_x_b")))
        bc_c = jax.nn.silu(_causal_conv_prefill(
            bc, lw["ssm_conv_bc"], lw.get("ssm_conv_bc_b")))
        xs_c = jnp.where(valid[..., None], xs_c, 0)
        bc_c = jnp.where(valid[..., None], bc_c, 0)
        new_state = {"conv_x": _conv_tail(xs, seq_lens, s.d_conv - 1),
                     "conv_bc": _conv_tail(bc, seq_lens, s.d_conv - 1)}
    else:
        cx, ncx = _conv_step(state["conv_x"], xs[:, 0],
                             lw["ssm_conv_x"], lw.get("ssm_conv_x_b"))
        cbc, ncbc = _conv_step(state["conv_bc"], bc[:, 0],
                               lw["ssm_conv_bc"], lw.get("ssm_conv_bc_b"))
        xs_c = jax.nn.silu(cx)[:, None]
        bc_c = jax.nn.silu(cbc)[:, None]
        new_state = {"conv_x": ncx, "conv_bc": ncbc}

    dt = jax.nn.softplus(dt_raw + lw["ssm_dt_bias"].astype(f32))
    dt = jnp.clip(dt, s.dt_limit[0], min(s.dt_limit[1], 1e6))
    if phase == "prefill":
        dt = jnp.where(valid[..., None], dt, 0.0)

    A = -jnp.exp(lw["ssm_A_log"].astype(f32))                     # (nh,)
    x_h = xs_c.reshape(B, T, nh, hd).astype(f32)
    Bm = bc_c[..., :gn].reshape(B, T, s.n_groups, N).astype(f32)
    Cm = bc_c[..., gn:].reshape(B, T, s.n_groups, N).astype(f32)
    rep = nh // s.n_groups
    Bm = jnp.repeat(Bm, rep, axis=2)                              # (B,T,nh,N)
    Cm = jnp.repeat(Cm, rep, axis=2)
    dA_log = dt * A[None, None, :]                                # (B,T,nh)
    D_res = lw["ssm_D"].astype(f32)[None, None, :, None] * x_h
    x_dt = x_h * dt[..., None]

    if phase == "decode":
        ssm = state["ssm"]                                        # (B,nh,hd,N)
        dA = jnp.exp(dA_log[:, 0])                                # (B,nh)
        dBx = x_dt[:, 0, :, :, None] * Bm[:, 0, :, None, :]       # (B,nh,hd,N)
        ssm = ssm * dA[..., None, None] + dBx
        y = jnp.einsum("bhdn,bhn->bhd", ssm, Cm[:, 0]) + D_res[:, 0]
        y = y.reshape(B, 1, s.d_inner)
        new_state["ssm"] = ssm
    else:
        cs = min(s.chunk_size, T)
        pad = (cs - T % cs) % cs

        def padc(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

        nchunk = (T + pad) // cs
        xc = padc(x_dt).reshape(B, nchunk, cs, nh, hd).transpose(1, 0, 2, 3, 4)
        Bc = padc(Bm).reshape(B, nchunk, cs, nh, N).transpose(1, 0, 2, 3, 4)
        Cc = padc(Cm).reshape(B, nchunk, cs, nh, N).transpose(1, 0, 2, 3, 4)
        ac = padc(dA_log).reshape(B, nchunk, cs, nh).transpose(1, 0, 2, 3)

        def chunk_body(carry, inp):
            st = carry                                            # (B,nh,hd,N)
            xk, Bk, Ck, ak = inp
            acs = jnp.cumsum(ak, axis=1)                          # (B,c,nh)
            L = jnp.exp(_segsum(ak))                              # (B,nh,c,c)
            G = jnp.einsum("bthn,bshn->bhts", Ck, Bk)
            Yd = jnp.einsum("bhts,bshd->bthd", G * L, xk)
            dec = jnp.exp(acs)                                    # (B,c,nh)
            Yoff = jnp.einsum("bthn,bhdn->bthd", Ck * dec[..., None], st)
            last = acs[:, -1:, :]                                 # (B,1,nh)
            Bdec = Bk * jnp.exp(last - acs)[..., None]
            st_new = (st * jnp.exp(last[:, 0])[:, :, None, None]
                      + jnp.einsum("bshn,bshd->bhdn", Bdec, xk))
            return st_new, Yd + Yoff

        # prefill always starts fresh — the cache slot may hold a previous
        # request's state (the KV analog overwrites its rows the same way)
        st0 = jnp.zeros((B, nh, hd, N), f32)
        st_f, Y = jax.lax.scan(chunk_body, st0, (xc, Bc, Cc, ac))
        Y = Y.transpose(1, 0, 2, 3, 4).reshape(B, T + pad, nh, hd)[:, :T]
        y = (Y + D_res).reshape(B, T, s.d_inner)
        new_state["ssm"] = st_f

    gate = gate.astype(f32)
    if s.gated_norm:
        g = s.n_groups
        if not s.norm_before_gate:
            y = y * jax.nn.silu(gate)
        yg = y.reshape(B, T, g, s.d_inner // g)
        var = jnp.mean(yg * yg, axis=-1, keepdims=True)
        yg = yg * jax.lax.rsqrt(var + s.norm_eps)
        y = yg.reshape(B, T, s.d_inner) * lw["ssm_norm"].astype(f32)
        if s.norm_before_gate:
            y = y * jax.nn.silu(gate)
    else:
        y = y * jax.nn.silu(gate)
    out = y.astype(x.dtype) @ lw["ssm_out"]
    return out, new_state


# ---------------------------------------------------------------------------
# RG-LRU recurrent block — recurrentgemma / Griffin flavor
# ---------------------------------------------------------------------------

def rglru_block(s: SSMSpec, lw, x, state: Dict[str, Any], *, phase: str,
                seq_lens=None, positions=None):
    """One Griffin recurrent block over normed input x (B, T, H)
    (reference: contrib/models/recurrentgemma-2b-it/src/
    modeling_recurrent_gemma.py RecurrentGemmaRecurrentBlock):
    y-branch gelu gate, x-branch conv → RG-LRU, elementwise product,
    output projection. Returns (y (B,T,H), new_state)."""
    B, T, H = x.shape
    f32 = jnp.float32
    W, nh, bw = s.d_inner, s.num_heads, s.head_dim

    y_b = jax.nn.gelu(x @ lw["rg_y"] + lw["rg_y_b"], approximate=True)
    xb = x @ lw["rg_x"] + lw["rg_x_b"]

    if phase == "prefill":
        valid = (positions < seq_lens[:, None])
        xb = jnp.where(valid[..., None], xb, 0)
        xc = _causal_conv_prefill(xb, lw["rg_conv"], lw["rg_conv_b"])
        new_state = {"conv_x": _conv_tail(xb, seq_lens, s.d_conv - 1)}
    else:
        val, ntail = _conv_step(state["conv_x"], xb[:, 0],
                                lw["rg_conv"], lw["rg_conv_b"])
        xc = val[:, None]
        new_state = {"conv_x": ntail}

    xh = xc.reshape(B, T, nh, bw)
    igate = jax.nn.sigmoid(
        jnp.einsum("bthw,hwv->bthv", xh, lw["rg_igate_w"]) + lw["rg_igate_b"])
    rgate = jax.nn.sigmoid(
        jnp.einsum("bthw,hwv->bthv", xh, lw["rg_rgate_w"]) + lw["rg_rgate_b"])
    igate = igate.reshape(B, T, W).astype(f32)
    rgate = rgate.reshape(B, T, W).astype(f32)

    log_a = -8.0 * rgate * jax.nn.softplus(lw["rg_param"].astype(f32))
    a = jnp.exp(log_a)
    reset = (positions == 0)[..., None]                           # (B,T,1)
    mult = jnp.where(reset, 1.0, jnp.sqrt(1.0 - jnp.exp(2.0 * log_a)))
    gated = xc.astype(f32) * igate * mult
    a_eff = jnp.where(reset, 0.0, a)

    if phase == "decode":
        h = a_eff[:, 0] * state["ssm"] + gated[:, 0]              # (B,W)
        new_state["ssm"] = h
        seq = h[:, None]
    else:
        # padded positions: identity element (a=1, b=0) so the carried
        # state is exactly the state at seq_len
        a_eff = jnp.where(valid[..., None], a_eff, 1.0)
        gated = jnp.where(valid[..., None], gated, 0.0)

        def comb(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2

        _, hs = jax.lax.associative_scan(comb, (a_eff, gated), axis=1)
        idx = jnp.maximum(seq_lens - 1, 0)
        new_state["ssm"] = jnp.take_along_axis(
            hs, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        seq = hs

    y = seq.astype(x.dtype) * y_b
    return y @ lw["rg_out"] + lw["rg_out_b"], new_state


def shortconv_block(s: SSMSpec, lw, x, state: Dict[str, Any], *, phase: str,
                    seq_lens=None, positions=None):
    """LFM2 gated short convolution (reference: contrib/models/lfm2-2.6b;
    HF Lfm2ShortConv): y = out(C ⊙ conv(B ⊙ x_proj)) with a depthwise
    causal conv of width d_conv and no nonlinearity. Carries only the
    conv tail of B⊙x."""
    Bg = x @ lw["sc_in_b"]
    Cg = x @ lw["sc_in_c"]
    xg = x @ lw["sc_in_x"]
    if s.conv_bias:
        Bg = Bg + lw["sc_in_b_b"]
        Cg = Cg + lw["sc_in_c_b"]
        xg = xg + lw["sc_in_x_b"]
    bx = Bg * xg
    if phase == "prefill":
        valid = (positions < seq_lens[:, None])
        bx = jnp.where(valid[..., None], bx, 0)
        conv = _causal_conv_prefill(bx, lw["sc_conv"],
                                    lw.get("sc_conv_b"))
        new_state = {"conv_x": _conv_tail(bx, seq_lens, s.d_conv - 1)}
    else:
        val, ntail = _conv_step(state["conv_x"], bx[:, 0],
                                lw["sc_conv"], lw.get("sc_conv_b"))
        conv = val[:, None]
        new_state = {"conv_x": ntail}
    y = (Cg * conv) @ lw["sc_out"]
    if s.conv_bias:
        y = y + lw["sc_out_b"]
    return y, new_state


_SSM_BLOCKS = {"mamba2": mamba2_mixer, "rglru": rglru_block,
               "shortconv": shortconv_block}


def ssm_block(s: SSMSpec, lw, x, state, *, phase, seq_lens=None,
              positions=None):
    return _SSM_BLOCKS[s.kind](s, lw, x, state, phase=phase,
                               seq_lens=seq_lens, positions=positions)
