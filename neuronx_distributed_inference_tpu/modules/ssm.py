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
  gated_delta: conv_x (Ls,B,2·nkh·d_k + nh·d_v,K-1) over [q|k|v] (nkh key
          heads, each serving nh/nkh value heads),
          ssm (Ls,B,nh,d_k,d_v) fp32
  kda:    conv_x (Ls,B,K-1,2·nh·d_k + nh·d_v) over [q|k|v], time-major as
          mamba1's, ssm (Ls,B,nh,d_k,d_v) fp32 (the gated delta rule's tile)
  mamba1: conv_x (Ls,B,K-1,d_inner) (time-major: the channels fill the
          lanes), ssm (Ls,B,N,d_inner) fp32 (the state transposed so that
          its 16 values a channel lie on sublanes and the channels on lanes:
          as (d_inner, 16) the device would pad every row to 128 lanes)
The conv tails hold the last K-1 *pre-conv* projected inputs, so a decode
step is ``concat(tail, current) → depthwise dot`` exactly like the
reference's cached path (modeling_falcon_h1.py torch_forward cached branch).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import delta_state_step, kernel_mode, mamba_state_step
from ..parallel.layers import ParamSpec
from ..parallel.mesh import AXIS_DP, AXIS_MP


@dataclass(frozen=True)
class SSMSpec:
    """Geometry of the recurrent block shared by all layers that carry one.

    kind "mamba2": Falcon-H1 / Mamba-2 selective SSM (SSD form).
    kind "rglru": recurrentgemma / Griffin RG-LRU linear recurrence.
    kind "shortconv": LFM2 gated short convolution (conv state only —
      reference: contrib/models/lfm2-2.6b; HF Lfm2ShortConv).
    kind "gated_delta": gated delta-rule linear attention (Gated DeltaNet;
      HF Qwen3NextGatedDeltaNet, Olmo-Hybrid's ``linear_attention`` layers):
      a per-head (d_k, d_v) matrix state that is decayed, read back through
      the key and corrected.
    kind "kda": the delta rule gated BY CHANNEL (Kimi Delta Attention,
      arXiv:2510.26692; Ling-3.0's linear layers): the same (d_k, d_v) matrix
      state a head, its decay a vector over d_k a head a token, ``g =
      decay_lower_bound * sigmoid(exp(A_log) * (W_a x + dt_bias))`` (full
      rank, bounded below), ``S = (I - beta k k^T) diag(exp g) S0 + beta k
      v^T``; the output norm by head, then ONE sigmoid gate a head.
    kind "mamba1": Mamba's per-channel selective scan (a (d_state,) state a
      channel, the step size and B / C read off the convolved input through a
      low-rank projection of ``dt_rank``): ``num_heads`` / ``head_dim`` unused.
    """

    # "mamba2" | "rglru" | "shortconv" | "gated_delta" | "kda" | "mamba1"
    kind: str
    d_inner: int              # mamba d_ssm / rglru lru_width / delta nh * d_v
    num_heads: int            # mamba_n_heads / rglru num_attention_heads
    head_dim: int             # mamba_d_head / rglru block_width / delta d_v
    d_state: int = 0          # mamba state size N / delta d_k (rglru: unused)
    n_groups: int = 1         # mamba B/C groups
    d_conv: int = 4           # depthwise conv kernel width K
    chunk_size: int = 128     # prefill scan chunk
    conv_bias: bool = True
    gated_norm: bool = False      # mamba_rms_norm: RMSNormGated before out
    norm_before_gate: bool = False
    norm_eps: float = 1e-6        # gated-norm eps (falcon-h1: rms_norm_eps)
    dt_limit: Tuple[float, float] = (0.0, float("inf"))
    # gated_delta: the write strength beta is 2 * sigmoid (in (0, 2): the
    # state transition may have negative eigenvalues) instead of sigmoid
    beta_scale: float = 1.0
    # gated_delta: key heads, each serving ``num_heads / num_key_heads``
    # neighbouring value heads (q and k are repeated over them, HF
    # ``repeat_interleave``); 0 = as many as value heads
    num_key_heads: int = 0
    # mamba1: the rank the step size is projected through
    dt_rank: int = 0
    # kda: the bound of the log decay a token (the published
    # ``kda_lower_bound``, -5: ``g`` in [bound, 0] by channel). The chunked
    # form is exact in float32 only where ``chunk_size * |bound| / 2`` stays
    # under the exponent float32 holds (:func:`_kda_chunked`)
    decay_lower_bound: float = 0.0

    @property
    def bc_size(self) -> int:
        return 2 * self.n_groups * self.d_state

    @property
    def key_heads(self) -> int:
        return self.num_key_heads or self.num_heads

    @property
    def qk_size(self) -> int:
        """gated_delta: the projected width of q, and of k."""
        return self.key_heads * self.d_state

    @property
    def qkv_size(self) -> int:
        """gated_delta: the channels of [q | k | v], what the depthwise
        convolution runs over and the conv tail carries."""
        return 2 * self.qk_size + self.d_inner


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
    if s.kind == "mamba1":
        # replicated (the family refuses tp > 1). [u | z] is ONE projection;
        # A_log lies as the state does, (d_state, d_inner)
        C, N = s.d_inner, s.d_state
        specs = {
            "m1_in": ParamSpec((Ls, hidden, 2 * C), P(), dtype),
            "m1_conv": ParamSpec((Ls, C, s.d_conv), P(), dtype),
            "m1_x": ParamSpec((Ls, C, s.dt_rank + 2 * N), P(), dtype),
            "m1_dt": ParamSpec((Ls, s.dt_rank, C), P(), dtype),
            "m1_dt_b": ParamSpec((Ls, C), P(), jnp.float32, "ones"),
            "m1_A_log": ParamSpec((Ls, N, C), P(), jnp.float32, "zeros"),
            "m1_D": ParamSpec((Ls, C), P(), jnp.float32, "ones"),
            "m1_out": ParamSpec((Ls, C, hidden), P(), dtype),
        }
        if s.conv_bias:
            specs["m1_conv_b"] = ParamSpec((Ls, C), P(), dtype, "zeros")
        return specs
    if s.kind == "gated_delta":
        # replicated: a recurrent stack has never run sharded, and the
        # family that builds this kind refuses tp > 1. [q|k|v|gate] is ONE
        # projection (a decode step is a GEMV per weight); the decay and the
        # write strength [a|b] keep a float32 output of their own
        conv = s.qkv_size
        return {
            "gdn_in": ParamSpec((Ls, hidden, conv + s.d_inner), P(), dtype),
            "gdn_in_ab": ParamSpec((Ls, hidden, 2 * s.num_heads), P(), dtype),
            "gdn_conv": ParamSpec((Ls, conv, s.d_conv), P(), dtype),
            "gdn_dt_bias": ParamSpec((Ls, s.num_heads), P(), jnp.float32,
                                     "ones"),
            "gdn_A_log": ParamSpec((Ls, s.num_heads), P(), jnp.float32,
                                   "zeros"),
            "gdn_norm": ParamSpec((Ls, s.head_dim), P(), dtype, "ones"),
            "gdn_out": ParamSpec((Ls, s.d_inner, hidden), P(), dtype),
        }
    if s.kind == "kda":
        # replicated, as gated_delta's. [q|k|v] is ONE projection; the decay's
        # full-rank projection and [beta | head gate] keep float32 outputs of
        # their own (the decay compounds over a row's whole past)
        conv = s.qkv_size
        return {
            "kda_in": ParamSpec((Ls, hidden, conv), P(), dtype),
            "kda_in_a": ParamSpec((Ls, hidden, s.qk_size), P(), dtype),
            "kda_in_bg": ParamSpec((Ls, hidden, 2 * s.num_heads), P(), dtype),
            "kda_conv": ParamSpec((Ls, conv, s.d_conv), P(), dtype),
            "kda_dt_bias": ParamSpec((Ls, s.qk_size), P(), jnp.float32,
                                     "zeros"),
            "kda_A_log": ParamSpec((Ls, s.num_heads), P(), jnp.float32,
                                   "zeros"),
            "kda_norm": ParamSpec((Ls, s.head_dim), P(), dtype, "ones"),
            "kda_out": ParamSpec((Ls, s.d_inner, hidden), P(), dtype),
        }
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
    if s.kind == "gated_delta":
        return {
            "conv_x": ((Ls, batch, s.qkv_size, K1), dtype),
            "ssm": ((Ls, batch, s.num_heads, s.d_state, s.head_dim),
                    jnp.float32),
        }
    if s.kind == "kda":
        # the tail time-major (the channels fill the lanes: as (channels,
        # K - 1) the device pads 3 lanes to 128, 3 GB at 64 slots x 15 layers)
        return {
            "conv_x": ((Ls, batch, K1, s.qkv_size), dtype),
            "ssm": ((Ls, batch, s.num_heads, s.d_state, s.head_dim),
                    jnp.float32),
        }
    if s.kind == "mamba1":
        return {
            "conv_x": ((Ls, batch, K1, s.d_inner), dtype),
            "ssm": ((Ls, batch, s.d_state, s.d_inner), jnp.float32),
        }
    if s.kind == "shortconv":
        # time-major, as mamba1's: the channels fill the lanes (channels-
        # major, the device pads K - 1 = 2 lanes to 128)
        return {"conv_x": ((Ls, batch, K1, s.d_inner), dtype)}
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
    if s.kind in ("gated_delta", "kda"):
        return {"conv_x": P(None, AXIS_DP, None, None),
                "ssm": P(None, AXIS_DP, None, None, None)}
    if s.kind == "mamba1":
        return {"conv_x": P(None, AXIS_DP, None, None),
                "ssm": P(None, AXIS_DP, None, None)}
    if s.kind == "shortconv":
        return {"conv_x": P(None, AXIS_DP, None, AXIS_MP)}
    return {"conv_x": P(None, AXIS_DP, AXIS_MP, None),
            "ssm": P(None, AXIS_DP, AXIS_MP)}


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _with_history(x, tail, K1):
    """(B, K-1 + T, C): the K-1 inputs that came before position 0 of ``x``
    (B, T, C) in front of it — ``tail`` (B, C, K-1), a chunk continuing a
    sequence, or zeros (None: a sequence's start)."""
    if tail is None:
        return jnp.pad(x, ((0, 0), (K1, 0), (0, 0)))
    return jnp.concatenate([tail.transpose(0, 2, 1).astype(x.dtype), x],
                           axis=1)


def _causal_conv_prefill(x, w, b, tail=None):
    """Depthwise causal conv over (B, T, C) with kernel (C, K): K shifted
    adds — K is 4; XLA fuses this into a handful of vector ops (vs a conv
    primitive whose tiny channel-depthwise form lowers poorly). ``tail``:
    see :func:`_with_history`."""
    K = w.shape[-1]
    T = x.shape[1]
    hist = _with_history(x, tail, K - 1)
    out = x * w[:, K - 1]
    for j in range(K - 1):
        out = out + hist[:, j:j + T] * w[:, j]
    if b is not None:
        out = out + b
    return out


def _conv_tail(x, n_valid, K1, tail=None):
    """The K-1 inputs that end at ``n_valid`` per row of (B, T, C), reaching
    back into ``tail`` where the window starts before position 0 →
    (B, C, K-1). ``n_valid`` 0 hands ``tail`` back."""
    idx = n_valid[:, None] + jnp.arange(K1)[None, :]              # (B, K1)
    out = jnp.take_along_axis(_with_history(x, tail, K1), idx[:, :, None],
                              axis=1)                             # (B, K1, C)
    return out.transpose(0, 2, 1)


def _next_tail(x, valid, n_valid, K1, tail):
    """The tail a block hands on after ``x`` (B, T, C): :func:`_conv_tail`'s
    gather, or for ONE token the window slid by one where the token is real
    (two static slices and a select; the gather cost 18-23 us a layer of a
    decode step: PERF.md section 6, PR 44)."""
    if x.shape[1] != 1:
        return _conv_tail(x, n_valid, K1, tail)
    hist = _with_history(x, tail, K1)
    return jnp.where(valid[:, :, None], hist[:, 1:],
                     hist[:, :K1]).transpose(0, 2, 1)


def _conv_step(tail, cur, w, b):
    """One decode conv step: (B, C, K-1) tail + (B, C) current → (value
    (B, C), new tail). Matches the reference's roll-and-dot cached branch
    (modeling_falcon_h1.py torch_forward)."""
    win = jnp.concatenate([tail, cur[:, :, None]], axis=-1)       # (B,C,K)
    val = jnp.sum(win * w[None], axis=-1)
    if b is not None:
        val = val + b
    return val, win[:, :, 1:]


def _real_and_fresh(valid, phase, seq_lens, positions, shape):
    """What the blocks that CONTINUE from a carried state read off a step:
    ``(valid (B, T), n_valid (B,), keep (B,))`` - the real tokens of each
    row (a prefix of it; default ``positions < seq_lens`` in prefill,
    everything in decode), their count, and whether the row keeps the state
    it is handed (False: its first real token is position 0, so it starts
    from zeros)."""
    if valid is None:
        valid = ((positions < seq_lens[:, None]) if phase == "prefill"
                 else jnp.ones(shape, bool))
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    return valid, n_valid, ~(valid[:, 0] & (positions[:, 0] == 0))


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
                 seq_lens=None, positions=None, valid=None):
    """One mamba2 block over already-normed input x (B, T, H).

    lw: this layer's weight dict (the ssm_* entries of the stacked layer
    params, indexed at this layer). state: {"conv_x","conv_bc","ssm"} THIS
    layer's state entries, one row per row of ``x``. Returns
    (y (B,T,H), new_state).

    The block CONTINUES from ``state``: the carried SSM state and conv tail
    are what came before position ``positions[:, 0]`` of each row, so a
    prompt walked in chunks (the paged serving path) gives what one pass
    gives. A row whose first position is 0 starts from zeros instead — a
    slot is reset by the positions it is fed, with no program of its own,
    and the contiguous prefill (positions always from 0) starts fresh as
    it always did.

    ``valid`` (B, T) marks the real tokens of each row, a prefix of it
    (default: ``positions < seq_lens`` in prefill, everything in decode).
    Positions past it get dt = 0 (decay 1, input contribution 0) and leave
    the conv tail where it was, so a right-padded chunk — or a row with no
    real token at all, a dead row of a decode step — leaves the carried
    state exactly as an unpadded run would. The torch reference
    (modeling_falcon_h1.py torch_forward) only supports left-padding for
    this reason.

    T == 1 runs the O(1) recurrence step, T > 1 the chunked SSD form.
    ``state["ssm"]`` as a :class:`StateStack` (a T == 1 step whose rows are
    the slots: :func:`state_kernel_declined`) is stepped in place by the
    kernel (``ops/mamba_state_step.py``), once across the state each way,
    and handed back as a :class:`StateStack`.
    """
    B, T, H = x.shape
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    g, N = s.n_groups, s.d_state
    gn = g * N
    nh, hd = s.num_heads, s.head_dim
    r = nh // g
    K1 = s.d_conv - 1
    valid, n_valid, keep = _real_and_fresh(valid, phase, seq_lens, positions,
                                           (B, T))
    tail_x = jnp.where(keep[:, None, None], state["conv_x"], 0)
    tail_bc = jnp.where(keep[:, None, None], state["conv_bc"], 0)
    in_place = isinstance(state["ssm"], StateStack)

    gate = x @ lw["ssm_in_gate"]
    xs = jnp.where(valid[..., None], x @ lw["ssm_in_x"], 0)
    bc = jnp.where(valid[..., None], x @ lw["ssm_in_bc"], 0)
    dt_raw = (x @ lw["ssm_in_dt"]).astype(f32)

    xs_c = jax.nn.silu(_causal_conv_prefill(
        xs, lw["ssm_conv_x"], lw.get("ssm_conv_x_b"), tail_x))
    bc_c = jax.nn.silu(_causal_conv_prefill(
        bc, lw["ssm_conv_bc"], lw.get("ssm_conv_bc_b"), tail_bc))
    new_state = {"conv_x": _next_tail(xs, valid, n_valid, K1, tail_x),
                 "conv_bc": _next_tail(bc, valid, n_valid, K1, tail_bc)}

    dt = jax.nn.softplus(dt_raw + lw["ssm_dt_bias"].astype(f32))
    dt = jnp.clip(dt, s.dt_limit[0], min(s.dt_limit[1], 1e6))
    dt = jnp.where(valid[..., None], dt, 0.0).reshape(B, T, g, r)

    A = -jnp.exp(lw["ssm_A_log"].astype(f32)).reshape(g, r)
    x_h = xs_c.reshape(B, T, g, r, hd).astype(f32)
    Bm = bc_c[..., :gn].reshape(B, T, g, N).astype(f32)
    Cm = bc_c[..., gn:].reshape(B, T, g, N).astype(f32)
    dA_log = dt * A                                               # (B,T,g,r)
    D_res = lw["ssm_D"].astype(f32).reshape(g, r)[..., None] * x_h
    x_dt = x_h * dt[..., None]
    if not in_place:
        st0 = jnp.where(keep[:, None, None, None], state["ssm"].astype(f32),
                        0.0).reshape(B, g, r, hd, N)

    if in_place:
        # the kernel reads y off the new state while it is in VMEM; B and C
        # go a row a GROUP, a head reads its group's
        layer = state["ssm"].layer
        y, st_f = mamba_state_step.mamba_state_step(
            state["ssm"].stack, layer, x_dt[:, 0].reshape(B, nh, hd),
            jnp.exp(dA_log[:, 0]).reshape(B, nh), Bm[:, 0], Cm[:, 0], keep,
            valid[:, 0], interpret=kernel_mode.pallas_interpret())
        y = (y.reshape(B, g, r, hd) + D_res[:, 0]).reshape(B, 1, s.d_inner)
        new_state["ssm"] = StateStack(st_f, layer)
    elif T == 1:
        dBx = x_dt[:, 0, ..., None] * Bm[:, 0, :, None, None, :]
        st_f = st0 * jnp.exp(dA_log[:, 0])[..., None, None] + dBx
        y = jnp.einsum("bgrdn,bgn->bgrd", st_f, Cm[:, 0],
                       precision=hi) + D_res[:, 0]
        y = y.reshape(B, 1, s.d_inner)
    else:
        cs = min(s.chunk_size, T)
        pad = (cs - T % cs) % cs

        def chunks(a):
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            a = a.reshape((B, (T + pad) // cs, cs) + a.shape[2:])
            return jnp.moveaxis(a, 1, 0)

        def chunk_body(st, inp):                       # st (B,g,r,hd,N)
            xk, Bk, Ck, ak = inp
            acs = jnp.cumsum(ak, axis=1)                          # (B,c,g,r)
            L = jnp.exp(_segsum(ak.reshape(B, cs, nh))
                        ).reshape(B, g, r, cs, cs)
            G = jnp.einsum("btgn,bsgn->bgts", Ck, Bk)
            Yd = jnp.einsum("bgrts,bsgrd->btgrd", G[:, :, None] * L, xk)
            Yoff = jnp.einsum("btgn,bgrdn->btgrd", Ck, st,
                              precision=hi) * jnp.exp(acs)[..., None]
            last = acs[:, -1]                                     # (B,g,r)
            x_end = xk * jnp.exp(last[:, None] - acs)[..., None]
            st_new = (st * jnp.exp(last)[..., None, None]
                      + jnp.einsum("bsgn,bsgrd->bgrdn", Bk, x_end,
                                   precision=hi))
            return st_new, Yd + Yoff

        st_f, Y = jax.lax.scan(
            chunk_body, st0, (chunks(x_dt), chunks(Bm), chunks(Cm),
                              chunks(dA_log)))
        Y = jnp.moveaxis(Y, 0, 1).reshape(B, T + pad, g, r, hd)[:, :T]
        y = (Y + D_res).reshape(B, T, s.d_inner)
    if not in_place:
        new_state["ssm"] = st_f.reshape(B, nh, hd, N)

    gate = gate.astype(f32)
    if s.gated_norm:
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
# Gated delta rule (Gated DeltaNet) — Olmo-Hybrid / Qwen3-Next flavor
# ---------------------------------------------------------------------------

class StateStack(NamedTuple):
    """A layer's ``"ssm"`` state handed over as the whole stack (Ls, slots,
    ...) and the layer's index in it: the block steps its layer IN PLACE
    (the state-step kernel) and hands the stack back the same way, where it
    is otherwise handed its layer's rows and hands rows back. Who gets
    which is decided in :func:`state_kernel_declined` alone."""
    stack: Any
    layer: int


def state_kernel_declined(s: SSMSpec, stack, rows: int, tokens: int,
                          state_slots=None) -> str:
    """Why a step of ``rows`` rows of ``tokens`` tokens over the ``"ssm"``
    stack does not run on the state-step kernel ("" = it does), from what
    the step shows and from nothing else: the state kind, one token a row,
    the rows being the slots, a tile the kernel takes. Three rules share the
    walk (``ops/delta_state_step.py`` ``walk_state_blocks``): the gated
    delta rule's with one decay a head, the same rule with a decay by
    channel (kind ``kda``), and Mamba-2's (``ops/mamba_state_step.py``);
    each names what it declines of its own. The walk over the layers asks ONCE a
    program and hands :func:`gated_delta_mixer` / :func:`kda_mixer` /
    :func:`mamba2_mixer` a :class:`StateStack` or its rows accordingly."""
    if s.kind == "gated_delta":
        why = delta_state_step.declined(stack, rows, tokens, s.key_heads,
                                        state_slots)
        # a chunk's record names what solves its triangular system
        return f"{why}, {SOLVE_NOTE}" if tokens > 1 else why
    if s.kind == "kda":
        why = delta_state_step.declined(stack, rows, tokens, s.num_heads,
                                        state_slots)
        return f"{why}, {KDA_CHUNK_NOTE}" if tokens > 1 else why
    if s.kind == "mamba2":
        return mamba_state_step.declined(stack, rows, tokens, s.n_groups,
                                         state_slots)
    # mamba1 too: ops/mamba_state_step.py is keyed on Mamba-2's heads (one
    # decay a head, B / C a group), not on a decay a channel and state value
    return f"no state-step kernel for kind {s.kind}"


def state_kernel_note(s: SSMSpec, stack) -> str:
    """The record's text for a step the kernel takes (groups: of several;
    how the decay rides: one a head, or by channel)."""
    if s.kind != "mamba2":
        return delta_state_step.state_step_plan(
            stack.shape[2], s.key_heads, *stack.shape[3:]).note() + (
                " decay=channel" if s.kind == "kda" else "")
    groups = f" groups={s.n_groups}" * (s.n_groups > 1)
    return mamba_state_step.mamba_step_plan(
        stack.shape[2], s.n_groups, *stack.shape[3:]).note() + groups


def _delta_step(q, k, v, g, beta, st0):
    """One token of the gated delta rule. q, k (B,H,dk), v (B,H,dv), g (log
    decay) and beta (B,H), st0 (B,H,dk,dv), all float32:
    ``S = a S0 + beta k (v - (a S0)^T k)^T``, ``o = S^T q``. Both reads of
    the old state (through k, and through q: ``S^T q = a S0^T q + (k . q)
    delta``) share one pass over it; the second pass writes the new one."""
    a = jnp.exp(g)
    mem_k = jnp.sum(st0 * k[..., :, None], axis=-2)               # (B,H,dv)
    mem_q = jnp.sum(st0 * q[..., :, None], axis=-2)
    delta = beta[..., None] * (v - a[..., None] * mem_k)
    st = a[..., None, None] * st0 + k[..., :, None] * delta[..., None, :]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    return a[..., None] * mem_q + kq * delta, st


#: rows of a diagonal block of the chunk's triangular system: a block is
#: inverted by substitution, a row a step, ALL blocks of ALL systems in one
#: vectorised step (15 dependent steps, where a 64-row system asks for 63)
SOLVE_BLOCK = 16
#: blocks' inverses of fewer rows than this merge on the VPU, the systems on
#: the lanes (a product of 16 x 16 blocks fills an eighth of an MXU tile);
#: from here up the merges are float32 matmuls
SOLVE_MXU_ROWS = 32
#: the engagement record's words for how the chunked form solves its system
SOLVE_NOTE = (f"blocked substitution, blocks of {SOLVE_BLOCK} merged on the "
              f"MXU from {SOLVE_MXU_ROWS}")


def _merge_inverses(a, b, c, matmul, rows: int):
    """``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`` from ``a =
    A^-1`` and ``b = B^-1``: exact block algebra, no series. ``rows`` is the
    axis of the blocks' rows, their columns the axis after it."""
    low = -matmul(matmul(b, c), a)
    return jnp.concatenate(
        [jnp.concatenate([a, jnp.zeros_like(a)], axis=rows + 1),
         jnp.concatenate([low, b], axis=rows + 1)], axis=rows)


@jax.jit
def _unit_lower_solve(m, rhs):
    """``X`` of ``(I + m) X = rhs`` for ``m`` (..., n, n) STRICTLY lower
    triangular and ``rhs`` (..., n, w), float32: the chunk's system, every
    (scan chunk, row, head) one of the leading axes' systems.

    Blocked forward substitution. ``I + m`` is cut into blocks of
    :data:`SOLVE_BLOCK` rows (``n`` padded with identity rows to a power of
    two of them, as the chunk's padded tail already is); the diagonal blocks
    are inverted row by row, ``X_r = e_r - sum_{j<r} L_rj X_j``, with the
    systems on the lanes so that a step is one multiply-add over every
    block of every system; neighbours then merge (:func:`_merge_inverses`)
    until one inverse is left, and ``X`` is its product with ``rhs``. The
    substitution and the small merges are exact float32 on the VPU, the
    products from :data:`SOLVE_MXU_ROWS` rows float32 at
    ``Precision.HIGHEST``: no step sums a series of ``m``'s powers, so
    nothing cancels where a Neumann series would. XLA's own
    ``triangular_solve`` inverts the same diagonal blocks row after row in a
    custom-call (``InvertDiagBlocksLowerTriangular``: 0.60 ms a layer of
    Olmo-Hybrid's one-row chunk, 0.34 of Qwen3-Next's, PERF.md section 5,
    PR 55). Jitted on its own, so a program traces it once and not once a
    layer."""
    hi = jax.lax.Precision.HIGHEST
    batch, n, w = m.shape[:-2], m.shape[-1], rhs.shape[-1]
    full = SOLVE_BLOCK
    while full < n:
        full *= 2
    m = jnp.pad(m.reshape(-1, n, n), ((0, 0), (0, full - n), (0, full - n)))
    rhs = jnp.pad(rhs.reshape(-1, n, w), ((0, 0), (0, full - n), (0, 0)))
    mt = jnp.moveaxis(m, 0, -1)                        # (full, full, S)

    def under_diagonal(a, size, rows):
        """The blocks (2p + 1, 2p) of ``a`` at ``size`` rows a block."""
        lead = (slice(None),) * rows
        return jnp.stack(
            [a[lead + (slice(i + size, i + 2 * size), slice(i, i + size))]
             for i in range(0, full, 2 * size)], axis=rows)

    d = jnp.stack([mt[i:i + SOLVE_BLOCK, i:i + SOLVE_BLOCK]
                   for i in range(0, full, SOLVE_BLOCK)])   # (blocks, r, j, S)
    column = jnp.arange(SOLVE_BLOCK)[None, :, None]

    def substitute(r, x):
        # rows r and below of ``x`` are still zero, so the sum over every j
        # is the sum over j < r
        l_r = jax.lax.dynamic_index_in_dim(d, r, 1, keepdims=False)
        x_r = (column == r).astype(x.dtype) - jnp.sum(l_r[:, :, None] * x,
                                                      axis=1)
        return jax.lax.dynamic_update_index_in_dim(x, x_r, r, 1)

    inv = jax.lax.fori_loop(
        1, SOLVE_BLOCK, substitute,
        jnp.zeros_like(d).at[:, 0, 0].set(1.0))             # (blocks, r, c, S)
    size = SOLVE_BLOCK
    while size < min(SOLVE_MXU_ROWS, full):
        pair = inv.reshape((-1, 2) + inv.shape[1:])
        inv = _merge_inverses(
            pair[:, 0], pair[:, 1], under_diagonal(mt, size, 0),
            lambda a, b: jnp.sum(a[:, :, :, None] * b[:, None], axis=2), 1)
        size *= 2
    inv = jnp.moveaxis(inv, -1, 0)                          # (S, blocks, r, c)
    while size < full:
        pair = inv.reshape((inv.shape[0], -1, 2) + inv.shape[2:])
        inv = _merge_inverses(
            pair[:, :, 0], pair[:, :, 1], under_diagonal(m, size, 1),
            lambda a, b: jnp.einsum("...ij,...jk->...ik", a, b, precision=hi),
            2)
        size *= 2
    x = jnp.einsum("sij,sjk->sik", inv[:, 0], rhs, precision=hi)
    return x[:, :n].reshape(batch + (n, w))


def _delta_chunked(q, k, v, g, beta, st0, chunk: int):
    """The same recurrence over T tokens in chunks (the WY / UT form), from
    the carried state ``st0``. q, k (B,T,H,dk), v (B,T,H,dv), g and beta
    (B,T,H), float32; a position with ``g = 0`` and ``beta = 0`` (padding)
    leaves the state as it was. Returns ``(o (B,T,H,dv), S (B,H,dk,dv))``.

    Inside a chunk, with ``G_i`` the cumulative log decay and ``M`` the
    strictly lower part of ``(beta k) k^T * exp(G_i - G_j)``, the corrected
    values solve the unit-lower-triangular system ``(I + M) [U | W] =
    [beta v | beta k exp(G)]``; then ``V = U - W S`` are the rows actually
    written, ``o = (q exp(G)) S + tril(q k^T * decay) V`` and the state
    handed to the next chunk is ``S exp(G_last) + (k exp(G_last - G))^T V``.
    The system is solved by :func:`_unit_lower_solve`: blocked forward
    substitution (stable where the Neumann series of ``M`` cancels
    catastrophically: beta near 2, keys aligned), its merges matmuls."""
    B, T, H, dv = v.shape
    hi = jax.lax.Precision.HIGHEST
    cs = min(chunk, T)
    pad = (-T) % cs
    nc = (T + pad) // cs

    def chunks(a):                      # (B,T,H,...) -> (nc,B,H,cs,...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, nc, cs) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, bc = chunks(q), chunks(k), chunks(v), chunks(beta)
    gc = jnp.cumsum(chunks(g), axis=-1)                           # (nc,B,H,cs)
    lower = jnp.tril(jnp.ones((cs, cs), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))                          # i >= j
    kb = kc * bc[..., None]
    m = jnp.where(jnp.tril(lower, -1),
                  jnp.einsum("...ik,...jk->...ij", kb, kc, precision=hi)
                  * decay, 0.0)
    uw = _unit_lower_solve(
        m, jnp.concatenate([vc * bc[..., None],
                            kb * jnp.exp(gc)[..., None]], axis=-1))
    qk = jnp.einsum("...ik,...jk->...ij", qc, kc, precision=hi) * decay
    q_in = qc * jnp.exp(gc)[..., None]
    g_last = gc[..., -1]                                          # (nc,B,H)
    k_out = kc * jnp.exp(g_last[..., None] - gc)[..., None]

    def chunk_body(st, inp):                                      # (B,H,dk,dv)
        u, w, qk_i, q_i, k_i, gl = inp
        v_new = u - jnp.einsum("bhck,bhkv->bhcv", w, st, precision=hi)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_i, st, precision=hi)
             + jnp.einsum("bhcs,bhsv->bhcv", qk_i, v_new, precision=hi))
        st = (st * jnp.exp(gl)[..., None, None]
              + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new, precision=hi))
        return st, o

    st, o = jax.lax.scan(chunk_body, st0,
                         (uw[..., :dv], uw[..., dv:], qk, q_in, k_out,
                          g_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)                 # (B,nc,cs,H,dv)
    return o.reshape(B, T + pad, H, dv)[:, :T], st


def gated_delta_mixer(s: SSMSpec, lw, x, state: Dict[str, Any], *,
                      phase: str, seq_lens=None, positions=None, valid=None):
    """One gated delta-rule block over its input x (B, T, H): ``[q|k|v] =
    silu(conv(W x))`` with the carried conv tail in front, per head ``q, k``
    l2-normalised (q scaled by ``d_k ** -0.5``), ``beta = beta_scale *
    sigmoid(W_b x)``, ``alpha = exp(-exp(A_log) * softplus(W_a x +
    dt_bias))``, the state update of :func:`_delta_step`, then per head
    ``rmsnorm(o) * silu(W_g x)`` (``s.norm_before_gate``; the gate first
    otherwise) and the out-projection. Returns
    (y (B,T,H), new_state).

    state: {"conv_x", "ssm"}, THIS layer's entries, one row per row of
    ``x``. The block CONTINUES from it, resets a row whose first real
    position is 0, and leaves the state and tail of padded positions and of
    dead rows as they were — ``valid`` and the reset are exactly
    :func:`mamba2_mixer`'s. T == 1 runs the O(1) state step, T > 1 the
    chunked form in chunks of ``s.chunk_size``. ``state["ssm"]`` as a
    :class:`StateStack` (a T == 1 step whose rows are the slots:
    :func:`state_kernel_declined`) is stepped in place by the kernel, once
    across the state each way, and handed back as a :class:`StateStack`; a
    dead row's ``o`` is then zero.
    """
    B, T, _ = x.shape
    f32 = jnp.float32
    nh, dk, dv = s.num_heads, s.d_state, s.head_dim
    qk, K1 = s.qk_size, s.d_conv - 1
    conv = s.qkv_size
    valid, n_valid, keep = _real_and_fresh(valid, phase, seq_lens, positions,
                                           (B, T))
    tail = jnp.where(keep[:, None, None], state["conv_x"], 0)
    in_place = isinstance(state["ssm"], StateStack)

    proj = x @ lw["gdn_in"]
    qkv = jnp.where(valid[..., None], proj[..., :conv], 0)
    gate = proj[..., conv:].astype(f32).reshape(B, T, nh, dv)
    ab = jnp.einsum("bth,hn->btn", x, lw["gdn_in_ab"],
                    preferred_element_type=f32)
    qkv_c = jax.nn.silu(_causal_conv_prefill(qkv, lw["gdn_conv"], None, tail))
    new_state = {"conv_x": _next_tail(qkv, valid, n_valid, K1, tail)}

    def heads(a):
        # l2-normalised per KEY head, then each repeated over the value
        # heads it serves (value head j reads key head j // group); the
        # kernel reads a key head from each of its value heads instead
        a = a.astype(f32).reshape(B, T, s.key_heads, dk)
        a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        return a if in_place or s.key_heads == nh else jnp.repeat(
            a, nh // s.key_heads, axis=2)
    q = heads(qkv_c[..., :qk]) * dk ** -0.5
    k = heads(qkv_c[..., qk:2 * qk])
    v = qkv_c[..., 2 * qk:].astype(f32).reshape(B, T, nh, dv)
    g = -jnp.exp(lw["gdn_A_log"].astype(f32)) * jax.nn.softplus(
        ab[..., :nh] + lw["gdn_dt_bias"].astype(f32))
    g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid[..., None],
                     s.beta_scale * jax.nn.sigmoid(ab[..., nh:]), 0.0)

    if in_place:
        layer = state["ssm"].layer
        o, st = delta_state_step.delta_state_step(
            state["ssm"].stack, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            beta[:, 0], keep, valid[:, 0],
            interpret=kernel_mode.pallas_interpret())
        o, st = o[:, None], StateStack(st, layer)
    else:
        st0 = jnp.where(keep[:, None, None, None], state["ssm"].astype(f32),
                        0.0)
        if T == 1:
            o, st = _delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], st0)
            o = o[:, None]
        else:
            o, st = _delta_chunked(q, k, v, g, beta, st0, s.chunk_size)
    new_state["ssm"] = st

    # per head: rmsnorm(o) * silu(gate) (norm_before_gate, the published
    # order), or the gate first as the Mamba-2 mixer's gated norm has it
    if not s.norm_before_gate:
        o = o * jax.nn.silu(gate)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + s.norm_eps) * lw["gdn_norm"].astype(f32)
    if s.norm_before_gate:
        y = y * jax.nn.silu(gate)
    return y.reshape(B, T, s.d_inner).astype(x.dtype) @ lw["gdn_out"], \
        new_state



# ---------------------------------------------------------------------------
# The delta rule gated by channel (Kimi Delta Attention) - Ling-3.0 flavor
# ---------------------------------------------------------------------------

#: the engagement record's words for how a chunk of kind ``kda`` is exact
KDA_CHUNK_NOTE = ("decay by channel factored about the middle of a chunk, "
                  "blocked substitution")
#: tokens (rows x width) :func:`kda_mixer` runs at once: the block holds
#: about twenty float32 arrays of (heads, d_k) a token (0.3 MB a token at 32
#: heads of 128), so a full-batch pack of 64 rows x 256 tokens goes 8 rows
#: at a time, one group after another (3.8 GB of temps all at once by AOT,
#: PR 67: the pack did not fit beside 12.6 GB of arguments with room to
#: spare)
KDA_GROUP_TOKENS = 2048
#: the largest exponent :func:`_kda_chunked` may form on either side of a
#: chunk's middle: exp(80) is finite in float32 (exp(88.7) is not), and the
#: product of two such factors of a masked pair still is
KDA_SAFE_EXPONENT = 40.0


def kda_chunk_tokens(lower_bound: float, chunk: int = 64) -> int:
    """Tokens a chunk of :func:`_kda_chunked` may hold under the decay's
    bound: the largest power of two whose half, times ``|lower_bound|``, stays
    under :data:`KDA_SAFE_EXPONENT` (16 at the published -5), at most
    ``chunk``. An unbounded decay (0) has no safe chunk and is refused."""
    if not lower_bound < 0:
        raise ValueError(
            f"kda with decay_lower_bound {lower_bound}: the chunked form is "
            "exact in float32 only under a negative bound of the log decay")
    n = 2
    while n * 2 <= chunk and n * abs(lower_bound) <= KDA_SAFE_EXPONENT:
        n *= 2
    return n


def _kda_step(q, k, v, g, beta, st0):
    """One token of the delta rule gated by channel. q, k, g (log decay)
    (B,H,dk), v (B,H,dv), beta (B,H), st0 (B,H,dk,dv), all float32: ``S' =
    diag(exp g) S0``, ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q = S'^T
    q + (k . q) delta``. The decay lands BEFORE the read through k."""
    s1 = jnp.exp(g)[..., None] * st0
    mem_k = jnp.sum(s1 * k[..., :, None], axis=-2)                # (B,H,dv)
    mem_q = jnp.sum(s1 * q[..., :, None], axis=-2)
    delta = beta[..., None] * (v - mem_k)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    return mem_q + kq * delta, s1 + k[..., :, None] * delta[..., None, :]


def _kda_chunked(q, k, v, g, beta, st0, chunk: int):
    """:func:`_kda_step`'s recurrence over T tokens in chunks (the WY / UT
    form of :func:`_delta_chunked`, the decay a vector over d_k). q, k, g
    (B,T,H,dk), v (B,T,H,dv), beta (B,T,H), float32; a position with ``g =
    0`` and ``beta = 0`` (padding) leaves the state as it was. Returns ``(o
    (B,T,H,dv), S (B,H,dk,dv))``.

    With ``G_i`` the cumulative log decay inside a chunk, the pair weights
    ``sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` (i >= j) are one matmul only
    if the exponent is split, ``(k_i e^{G_i - R}) . (k_j e^{R - G_j})``, and
    a factor overflows float32 once ``|G - R|`` passes ~88. ``R`` is the
    chunk's MIDDLE row's ``G`` and ``chunk`` is what
    :func:`kda_chunk_tokens` allows under the bound of ``g``, so either
    factor's exponent stays under :data:`KDA_SAFE_EXPONENT` and the split is
    exact arithmetic, no clamp. Everything else is the scalar rule's algebra
    with ``exp(G)`` by channel: ``(I + M) [U | W] = [beta v | beta k
    exp(G)]`` by :func:`_unit_lower_solve`, ``V = U - W S``, ``o = (q
    exp(G)) S + tril(pair weights of q, k) V``, ``S <- diag(exp(G_last)) S +
    (k exp(G_last - G))^T V``."""
    B, T, H, dv = v.shape
    hi = jax.lax.Precision.HIGHEST
    cs = min(chunk, T)
    pad = (-T) % cs
    nc = (T + pad) // cs

    def chunks(a):                      # (B,T,H,...) -> (nc,B,H,cs,...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, nc, cs) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, bc = chunks(q), chunks(k), chunks(v), chunks(beta)
    gc = jnp.cumsum(chunks(g), axis=-2)                     # (nc,B,H,cs,dk)
    mid = max(cs // 2 - 1, 0)
    ref = gc[..., mid:mid + 1, :]
    rise, fall = jnp.exp(gc - ref), jnp.exp(ref - gc)       # the i / j sides
    kb = kc * bc[..., None]
    k_fall = kc * fall
    lower = jnp.tril(jnp.ones((cs, cs), bool))
    m = jnp.where(jnp.tril(lower, -1),
                  jnp.einsum("...ik,...jk->...ij", kb * rise, k_fall,
                             precision=hi), 0.0)
    decay_in = jnp.exp(gc)
    uw = _unit_lower_solve(
        m, jnp.concatenate([vc * bc[..., None], kb * decay_in], axis=-1))
    qk = jnp.where(lower, jnp.einsum("...ik,...jk->...ij", qc * rise, k_fall,
                                     precision=hi), 0.0)
    g_last = gc[..., -1, :]                                 # (nc,B,H,dk)
    k_out = kc * jnp.exp(g_last[..., None, :] - gc)

    def chunk_body(st, inp):                                # (B,H,dk,dv)
        u, w, qk_i, q_i, k_i, gl = inp
        v_new = u - jnp.einsum("bhck,bhkv->bhcv", w, st, precision=hi)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_i, st, precision=hi)
             + jnp.einsum("bhcs,bhsv->bhcv", qk_i, v_new, precision=hi))
        st = (st * jnp.exp(gl)[..., None]
              + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new, precision=hi))
        return st, o

    st, o = jax.lax.scan(chunk_body, st0,
                         (uw[..., :dv], uw[..., dv:], qk, qc * decay_in,
                          k_out, g_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # (B,nc,cs,H,dv)
    return o.reshape(B, T + pad, H, dv)[:, :T], st


def _kda_rows(s: SSMSpec, lw, x, tail, valid, n_valid):
    """What :func:`kda_mixer` computes around the state update, for x (B, T,
    H) and ``tail`` (B, C, K-1) already reset where a row starts fresh (the
    caller steps the state, on the kernel or by rows, with what this hands
    back). Returns ``(finish, new tail (B, C, K-1), q, k, v, g, beta)`` with
    ``finish(o) -> y (B, T, hidden)`` the norm, gate and out-projection."""
    B, T, _ = x.shape
    f32 = jnp.float32
    nh, dk, dv = s.num_heads, s.d_state, s.head_dim
    qk, K1 = s.qk_size, s.d_conv - 1
    qkv = jnp.where(valid[..., None], x @ lw["kda_in"], 0)
    a = jnp.einsum("bth,hn->btn", x, lw["kda_in_a"],
                   preferred_element_type=f32)
    bg = jnp.einsum("bth,hn->btn", x, lw["kda_in_bg"],
                    preferred_element_type=f32)
    qkv_c = jax.nn.silu(_causal_conv_prefill(qkv, lw["kda_conv"], None, tail))
    new_tail = _next_tail(qkv, valid, n_valid, K1, tail)

    def heads(a_):
        a_ = a_.astype(f32).reshape(B, T, nh, dk)
        return a_ * jax.lax.rsqrt(jnp.sum(a_ * a_, axis=-1, keepdims=True)
                                  + 1e-6)
    q = heads(qkv_c[..., :qk]) * dk ** -0.5
    k = heads(qkv_c[..., qk:2 * qk])
    v = qkv_c[..., 2 * qk:].astype(f32).reshape(B, T, nh, dv)
    rate = jnp.exp(lw["kda_A_log"].astype(f32))[:, None]          # (nh, 1)
    g = s.decay_lower_bound * jax.nn.sigmoid(
        rate * (a + lw["kda_dt_bias"].astype(f32)).reshape(B, T, nh, dk))
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], jax.nn.sigmoid(bg[..., :nh]), 0.0)

    def finish(o):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + s.norm_eps) * lw["kda_norm"].astype(f32)
        y = y * jax.nn.sigmoid(bg[..., nh:])[..., None]
        return y.reshape(B, T, s.d_inner).astype(x.dtype) @ lw["kda_out"]
    return finish, new_tail, q, k, v, g, beta


def kda_mixer(s: SSMSpec, lw, x, state: Dict[str, Any], *, phase: str,
              seq_lens=None, positions=None, valid=None):
    """One block of the delta rule gated by channel over its normed input x
    (B, T, H): ``[q|k|v] = silu(conv(W x))`` with the carried conv tail in
    front, per head ``q, k`` l2-normalised (q scaled by ``d_k ** -0.5``),
    ``g = decay_lower_bound * sigmoid(exp(A_log) * (W_a x + dt_bias))`` by
    channel in float32, ``beta = sigmoid(W_b x)``, the state update of
    :func:`_kda_step`, then per head ``rmsnorm(o) * sigmoid(W_g x)[head]``
    (ONE gate a head, after the norm) and the out-projection. Returns (y
    (B,T,H), new_state).

    state: {"conv_x" (B, K-1, channels), "ssm" (B, H, d_k, d_v)}, THIS
    layer's rows; ``valid``, the reset of a row whose first real position is
    0, the :class:`StateStack` hand-over and a dead row's zero ``o`` are
    exactly :func:`gated_delta_mixer`'s. T == 1 runs the O(1) state step
    (the kernel in place on the stack, else :func:`_kda_step`), T > 1
    :func:`_kda_chunked` in chunks of ``s.chunk_size``, the rows in groups of
    at most :data:`KDA_GROUP_TOKENS` tokens, one group after another (the
    same result; the float32 temps a group's worth)."""
    B, T, _ = x.shape
    valid, n_valid, keep = _real_and_fresh(valid, phase, seq_lens, positions,
                                           (B, T))
    # the shared conv helpers take a tail channels-major; the slot keeps it
    # time-major (the channels fill the lanes)
    tail = jnp.where(keep[:, None, None], state["conv_x"], 0).transpose(
        0, 2, 1)
    if isinstance(state["ssm"], StateStack):
        finish, new_tail, q, k, v, g, beta = _kda_rows(
            s, lw, x, tail, valid, n_valid)
        layer = state["ssm"].layer
        o, st = delta_state_step.kda_state_step(
            state["ssm"].stack, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            beta[:, 0], keep, valid[:, 0],
            interpret=kernel_mode.pallas_interpret())
        return finish(o[:, None]), {"conv_x": new_tail.transpose(0, 2, 1),
                                    "ssm": StateStack(st, layer)}
    st0 = jnp.where(keep[:, None, None, None],
                    state["ssm"].astype(jnp.float32), 0.0)

    def rows(x_, tail_, st0_, valid_, n_valid_):
        finish, new_tail, q, k, v, g, beta = _kda_rows(
            s, lw, x_, tail_, valid_, n_valid_)
        if T == 1:
            o, st = _kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              st0_)
            o = o[:, None]
        else:
            o, st = _kda_chunked(q, k, v, g, beta, st0_, s.chunk_size)
        return finish(o), new_tail, st

    fit = max(1, KDA_GROUP_TOKENS // T)
    group = max(d for d in range(1, min(B, fit) + 1) if B % d == 0)
    args = (x, tail, st0, valid, n_valid)
    if group == B:
        y, new_tail, st = rows(*args)
    else:
        y, new_tail, st = (
            a.reshape((B,) + a.shape[2:]) for a in jax.lax.map(
                lambda xs: rows(*xs),
                tuple(a.reshape((B // group, group) + a.shape[1:])
                      for a in args)))
    return y, {"conv_x": new_tail.transpose(0, 2, 1), "ssm": st}


# ---------------------------------------------------------------------------
# Mamba-1 (per-channel selective scan)
# ---------------------------------------------------------------------------

#: the key under which a Mamba-1 block hands its scan output on
SCAN_OUT = "scan_out"
#: tokens a step of the chunk's scan over time: the loop body holds this many
#: unrolled steps of the recurrence, its carry the (rows, d_state, d_inner)
#: state. The (T, d_inner, d_state) float32 of a whole chunk (2.7 GB at 32
#: rows x 256 tokens of 5120 channels) is never formed
MAMBA1_TIME_BLOCK = 8


def mamba1_mixer(s: SSMSpec, lw, x, state: Dict[str, Any], *, phase: str,
                 seq_lens=None, positions=None, valid=None):
    """One Mamba-1 block over already-normed input x (B, T, H): ``[u | z] =
    W_in x``; ``u' = silu(conv(u) + b)`` with the carried conv tail in front;
    ``[r | B | C] = W_x u'``; ``dt = softplus(W_dt r + b_dt)``; per channel
    ``S_t = exp(dt_t A) S_{t-1} + dt_t u'_t B_t`` (``A = -exp(A_log)``, a
    (d_state,) state a channel, float32), ``y_t = S_t . C_t + D u'_t``; the
    output ``W_out (y silu(z))``. Returns ``(out, new_state)``;
    ``new_state[SCAN_OUT]`` is ``y`` (B, T, d_inner) in x's dtype, the scan's
    output BEFORE the gate: not state but what a Gated Memory Unit further up
    the stack reads of the same tokens (the walk takes it out before it
    writes the state back).

    state: {"conv_x" (B, K-1, d_inner), "ssm" (B, d_state, d_inner)}, THIS
    layer's rows. The block CONTINUES from it, resets a row whose first real
    position is 0, and leaves the state and tail of padded positions and of
    dead rows as they were - ``valid`` and the reset are exactly
    :func:`mamba2_mixer`'s (a padded position has dt = 0: decay 1, input 0).
    T == 1 is one step of the recurrence; T > 1 scans over time in blocks of
    :data:`MAMBA1_TIME_BLOCK` tokens from the carried state."""
    B, T, _ = x.shape
    f32 = jnp.float32
    C, N, K1 = s.d_inner, s.d_state, s.d_conv - 1
    valid, n_valid, keep = _real_and_fresh(valid, phase, seq_lens, positions,
                                           (B, T))
    # the shared conv helpers take a tail channels-major; the slot keeps it
    # time-major (the channels fill the lanes)
    tail = jnp.where(keep[:, None, None], state["conv_x"], 0).transpose(
        0, 2, 1)
    st0 = jnp.where(keep[:, None, None], state["ssm"].astype(f32), 0.0)

    uz = x @ lw["m1_in"]
    u = jnp.where(valid[..., None], uz[..., :C], 0)
    z = uz[..., C:].astype(f32)
    up = jax.nn.silu(_causal_conv_prefill(
        u, lw["m1_conv"], lw.get("m1_conv_b"), tail))
    new_tail = _next_tail(u, valid, n_valid, K1, tail).transpose(0, 2, 1)

    rbc = up @ lw["m1_x"]
    r = rbc[..., :s.dt_rank]
    Bm = rbc[..., s.dt_rank:s.dt_rank + N].astype(f32)
    Cm = rbc[..., s.dt_rank + N:].astype(f32)
    dt = jax.nn.softplus((r @ lw["m1_dt"]).astype(f32) + lw["m1_dt_b"])
    dt = jnp.where(valid[..., None], dt, 0.0)                  # (B, T, C)
    A = -jnp.exp(lw["m1_A_log"].astype(f32))                   # (N, C)
    upf = up.astype(f32)
    dtu = dt * upf

    def step(st, dt_t, dtu_t, b_t, c_t):       # st (B, N, C); *_t one token
        st = (jnp.exp(dt_t[:, None, :] * A) * st
              + dtu_t[:, None, :] * b_t[:, :, None])
        return st, jnp.sum(st * c_t[:, :, None], axis=1)

    if T == 1:
        st, y = step(st0, dt[:, 0], dtu[:, 0], Bm[:, 0], Cm[:, 0])
        y = y[:, None]
    else:
        tb = MAMBA1_TIME_BLOCK
        pad = (-T) % tb

        def blocks(a):                 # (B, T, ..) -> (T / tb, tb, B, ..)
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return jnp.moveaxis(a, 1, 0).reshape(
                ((T + pad) // tb, tb) + a.shape[:1] + a.shape[2:])

        def block_body(st, inp):
            ys = []
            for i in range(tb):
                st, y_t = step(st, *(a[i] for a in inp))
                ys.append(y_t)
            return st, jnp.stack(ys)

        st, y = jax.lax.scan(block_body, st0,
                             (blocks(dt), blocks(dtu), blocks(Bm),
                              blocks(Cm)))
        y = jnp.moveaxis(y.reshape(T + pad, B, C), 0, 1)[:, :T]
    y = y + lw["m1_D"].astype(f32) * upf
    out = (y * jax.nn.silu(z)).astype(x.dtype) @ lw["m1_out"]
    return out, {"conv_x": new_tail, "ssm": st, SCAN_OUT: y.astype(x.dtype)}


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
                    seq_lens=None, positions=None, valid=None):
    """LFM2 gated short convolution (reference: contrib/models/lfm2-2.6b;
    HF Lfm2ShortConv): y = out(C ⊙ conv(B ⊙ x_proj)) with a depthwise
    causal conv of width d_conv and no nonlinearity. Carries only the
    conv tail of B⊙x: the K-1 products that came before the step's first
    token.

    state: {"conv_x" (B, K-1, d_inner)}, THIS layer's rows. The block
    CONTINUES from it, resets a row whose first real position is 0, and
    leaves the tail of padded positions and of dead rows as it was -
    ``valid`` and the reset are exactly :func:`mamba2_mixer`'s. The tail it
    hands on ends at each row's LAST REAL token, whatever the chunk's width
    (one token, fewer than K-1, a padded bucket)."""
    B, T, _ = x.shape
    valid, n_valid, keep = _real_and_fresh(valid, phase, seq_lens, positions,
                                           (B, T))
    # the shared conv helpers take a tail channels-major; the slot keeps it
    # time-major
    tail = jnp.where(keep[:, None, None], state["conv_x"], 0).transpose(
        0, 2, 1)
    Bg = x @ lw["sc_in_b"]
    Cg = x @ lw["sc_in_c"]
    xg = x @ lw["sc_in_x"]
    if s.conv_bias:
        Bg = Bg + lw["sc_in_b_b"]
        Cg = Cg + lw["sc_in_c_b"]
        xg = xg + lw["sc_in_x_b"]
    bx = jnp.where(valid[..., None], Bg * xg, 0)
    conv = _causal_conv_prefill(bx, lw["sc_conv"], lw.get("sc_conv_b"), tail)
    new_state = {"conv_x": _next_tail(bx, valid, n_valid, s.d_conv - 1,
                                      tail).transpose(0, 2, 1)}
    y = (Cg * conv) @ lw["sc_out"]
    if s.conv_bias:
        y = y + lw["sc_out_b"]
    return y, new_state


_SSM_BLOCKS = {"mamba2": mamba2_mixer, "rglru": rglru_block,
               "shortconv": shortconv_block,
               "gated_delta": gated_delta_mixer, "kda": kda_mixer,
               "mamba1": mamba1_mixer}

#: the kinds whose block continues from a carried state and conv tail and
#: takes ``valid``: the ones the paged serving path can run
CONTINUING_KINDS = ("mamba2", "gated_delta", "kda", "mamba1", "shortconv")


def ssm_block(s: SSMSpec, lw, x, state, *, phase, seq_lens=None,
              positions=None, valid=None):
    """``valid``: the paged step's real-token mask (the kinds of
    :data:`CONTINUING_KINDS` only: their blocks continue from a carried
    state). A Mamba-1 block's ``new_state`` also carries
    :data:`SCAN_OUT`, which is not state (:func:`mamba1_mixer`)."""
    kw = {} if valid is None else {"valid": valid}
    return _SSM_BLOCKS[s.kind](s, lw, x, state, phase=phase,
                               seq_lens=seq_lens, positions=positions, **kw)
