"""The gated delta rule of ``modules/ssm.py`` (kind ``gated_delta``, ISSUE 34)
in float32 on the CPU, held to two implementations this repository did not
write its own from: the benchmark's plain reference
(``benchmark/references/olmo_hybrid.py``, token by token) and
``transformers``' Gated DeltaNet (``torch_recurrent_gated_delta_rule``,
``torch_chunk_gated_delta_rule``, ``Qwen3NextGatedDeltaNet``).

  * the chunked form and the one-token step against the recurrence: across
    chunk boundaries, from a non-zero carried state, with a padded tail,
    with ``beta`` near 2 and keys nearly aligned (where the Neumann series
    of the chunk's triangular system would cancel catastrophically);
  * the whole mixer, conv tail and all, against ``Qwen3NextGatedDeltaNet``
    (its ``sigmoid`` write strength: the doubling off), in one pass and
    continued from a carried state and tail in two;
  * a dead row and a padded tail leave state and tail as they were;
  * what the paged path still refuses (``rglru``, ``shortconv``), by the
    table's sentence.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from harness import build  # noqa: E402

from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.modules import ssm  # noqa: E402

#: float32 sums in another order: the three forms agree to a few ulps of
#: the outputs' scale (~2), a wrong decay or a dropped correction is O(1)
ATOL = 2e-5
H, DK, DV = 3, 8, 16


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("olmo_hybrid")


def _inputs(seed, b, t, beta_lo=0.0, beta_hi=2.0, aligned=False):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((b, t, H, DK))) * DK ** -0.5
    k = rng.standard_normal((b, t, H, DK))
    if aligned:           # every key within a few degrees of one direction
        k = rng.standard_normal((b, 1, H, DK)) + 0.05 * k
    g = -rng.uniform(1e-3, 0.5, (b, t, H))
    out = dict(q=q, k=unit(k), v=rng.standard_normal((b, t, H, DV)), g=g,
               beta=rng.uniform(beta_lo, beta_hi, (b, t, H)),
               st0=rng.standard_normal((b, H, DK, DV)))
    return {n: jnp.asarray(a, jnp.float32) for n, a in out.items()}


def _torch(x):
    import torch
    return torch.tensor(np.asarray(x))


@pytest.mark.parametrize("t, chunk, kw", [
    (150, 64, {}),                              # 64 + 64 + 22: a padded tail
    (128, 64, {}),                              # whole chunks
    (70, 16, dict(beta_lo=1.9)),                # beta near 2
    (64, 64, dict(beta_lo=1.95, aligned=True)),  # reflections of one key
    (5, 64, {}),                                # shorter than a chunk
])
def test_chunked_step_reference_and_transformers_agree(ref, t, chunk, kw):
    os.environ.setdefault("USE_TF", "0")
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    x = _inputs(34, 2, t, **kw)
    q, k, v, g, beta, st0 = (x[n] for n in ("q", "k", "v", "g", "beta",
                                            "st0"))
    want_o, want_s = ref.delta_rule(q, k, v, jnp.exp(g), beta, st0)
    got_o, got_s = ssm._delta_chunked(q, k, v, g, beta, st0, chunk)
    np.testing.assert_allclose(got_o, want_o, atol=ATOL)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)
    st, outs = st0, []
    for i in range(t):
        o, st = ssm._delta_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                beta[:, i], st)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=ATOL)
    np.testing.assert_allclose(st, want_s, atol=ATOL)
    # transformers scales the query itself and takes beta as it is used
    args = [_torch(a) for a in (q * DK ** 0.5, k, v, g, beta)]
    hf_o, hf_s = hf.torch_recurrent_gated_delta_rule(
        *args, initial_state=_torch(st0), output_final_state=True)
    np.testing.assert_allclose(want_o, hf_o.numpy(), atol=ATOL)
    np.testing.assert_allclose(want_s, hf_s.numpy(), atol=ATOL)
    hf_o, hf_s = hf.torch_chunk_gated_delta_rule(
        *args, chunk_size=chunk, initial_state=_torch(st0),
        output_final_state=True)
    np.testing.assert_allclose(got_o, hf_o.numpy(), atol=ATOL)
    np.testing.assert_allclose(got_s, hf_s.numpy(), atol=ATOL)


# ---------------------------------------------------------------------------
# the chunk's triangular system, solved directly (ISSUE 55)
# ---------------------------------------------------------------------------

#: of the solution's largest entry (7-30 in these cases): float32 forward
#: substitution reads 1e-7 to 4e-7 of it against a float64 solve, XLA's
#: ``triangular_solve`` the same; a merge left out is O(1) of it
SOLVE_RTOL = 2e-6


def _systems(seed, n, cs, real, beta_lo, beta_hi, aligned):
    """``n`` systems of a chunk of ``cs`` positions, the first ``real`` of
    them tokens and the rest the padded tail (``g = 0``, ``beta = 0``), as
    ``_delta_chunked`` builds them, in float64: ``(m, rhs)``."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((n, cs, DK))
    if aligned:
        k = rng.standard_normal((n, 1, DK)) + 0.05 * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    live = np.arange(cs) < real
    beta = rng.uniform(beta_lo, beta_hi, (n, cs)) * live
    g = np.cumsum(-rng.uniform(1e-3, 0.5, (n, cs)) * live, axis=-1)
    kb = k * beta[..., None]
    m = np.tril(kb @ k.transpose(0, 2, 1)
                * np.exp(np.tril(g[:, :, None] - g[:, None, :])), -1)
    v = rng.standard_normal((n, cs, DV))
    return m, np.concatenate([v * beta[..., None],
                              kb * np.exp(g)[..., None]], axis=-1)


@pytest.mark.parametrize("beta", [(0.0, 2.0, False), (1.9, 2.0, False),
                                  (1.95, 2.0, True)],
                         ids=["beta_0_2", "beta_near_2", "keys_aligned"])
@pytest.mark.parametrize("cs, real", [(64, 64), (16, 16), (5, 5), (64, 22)],
                         ids=["cs64", "cs16", "cs5", "tail_of_22"])
@pytest.mark.parametrize("lead", [(4, 1, 30), (4, 32, 30)],
                         ids=["one_row_120", "a_pack_3840"])
def test_the_blocked_solve_is_a_float64_solve(monkeypatch, lead, cs, real,
                                              beta):
    """``_unit_lower_solve`` against ``numpy.linalg.solve`` in float64, at a
    tolerance XLA's ``triangular_solve`` meets on the same systems; the
    control: with the blocks under the diagonal left out of a merge the
    answer is a thousand tolerances away wherever there is a merge."""
    m, rhs = _systems(55, int(np.prod(lead)), cs, real, *beta)
    want = np.linalg.solve(np.eye(cs) + m, rhs)
    tol = SOLVE_RTOL * np.abs(want).max()
    m32 = jnp.asarray(m.reshape(lead + m.shape[1:]), jnp.float32)
    r32 = jnp.asarray(rhs.reshape(lead + rhs.shape[1:]), jnp.float32)

    def worst(solve):
        return float(np.abs(np.asarray(solve(m32, r32)).reshape(want.shape)
                            - want).max())
    assert worst(ssm._unit_lower_solve) < tol
    assert worst(lambda a, b: jax.lax.linalg.triangular_solve(
        a, b, left_side=True, lower=True, unit_diagonal=True)) < tol
    merge = ssm._merge_inverses
    monkeypatch.setattr(
        ssm, "_merge_inverses",
        lambda a, b, c, matmul, rows: merge(a, b, 0 * c, matmul, rows))
    # the function under the jit: this trace must not be served from, nor
    # left in, the cache of the real one
    dropped = worst(ssm._unit_lower_solve.__wrapped__)
    assert (dropped > 1000 * tol) == (real > ssm.SOLVE_BLOCK), dropped


def test_a_chunk_started_from_zero_is_not_the_recurrence(ref):
    """The control of the carry: the same chunked form handed a zero state
    at every chunk is far outside the tolerance."""
    x = _inputs(35, 1, 96)
    q, k, v, g, beta, st0 = (x[n] for n in ("q", "k", "v", "g", "beta",
                                            "st0"))
    want_o, _ = ref.delta_rule(q, k, v, jnp.exp(g), beta, st0)
    o1, _ = ssm._delta_chunked(q[:, :64], k[:, :64], v[:, :64], g[:, :64],
                               beta[:, :64], st0, 64)
    o2, _ = ssm._delta_chunked(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:],
                               beta[:, 64:], jnp.zeros_like(st0), 64)
    np.testing.assert_allclose(o1, want_o[:, :64], atol=ATOL)
    assert float(jnp.abs(o2 - want_o[:, 64:]).max()) > 1000 * ATOL


# ---------------------------------------------------------------------------
# the whole mixer against transformers' Qwen3NextGatedDeltaNet
# ---------------------------------------------------------------------------

HID, K = 32, 4


def _spec(key_heads, heads):
    return ssm.SSMSpec(kind="gated_delta", d_inner=heads * DV,
                       num_heads=heads, num_key_heads=key_heads, head_dim=DV,
                       d_state=DK, d_conv=K, chunk_size=16, conv_bias=False,
                       gated_norm=True, norm_before_gate=True, norm_eps=1e-6,
                       beta_scale=1.0)


#: as many key heads as value heads (Olmo-Hybrid), and key heads shared by
#: pairs of value heads (Qwen3-Next, ISSUE 36)
@pytest.fixture(scope="module", params=[(H, H), (2, 4)],
                ids=["a_key_head_a_value_head", "key_heads_shared_by_pairs"])
def hf_mixer(request):
    """A seeded ``Qwen3NextGatedDeltaNet``, its weights in this repository's
    layout and the spec that goes with them. Its fused ``in_proj_qkvz`` rows
    are per KEY head [q | k | v of its r value heads | z of them] and
    ``in_proj_ba`` rows per key head [b r | a r]
    (``fix_query_key_value_ordering``): regrouped here by destination."""
    os.environ.setdefault("USE_TF", "0")
    import torch
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    nk, nv = request.param
    r = nv // nk
    torch.manual_seed(34)
    cfg = hf.Qwen3NextConfig(
        hidden_size=HID, linear_num_value_heads=nv, linear_num_key_heads=nk,
        linear_key_head_dim=DK, linear_value_head_dim=DV,
        linear_conv_kernel_dim=K, hidden_act="silu", rms_norm_eps=1e-6)
    net = hf.Qwen3NextGatedDeltaNet(cfg, layer_idx=0).float().eval()
    with torch.no_grad():
        for p in net.parameters():
            p.copy_(torch.randn_like(p) * (0.3 if p.ndim > 1 else 1.0))
        net.norm.weight.add_(1.0)
    qkvz = net.in_proj_qkvz.weight.detach().numpy().reshape(
        nk, 2 * DK + 2 * r * DV, HID)
    ba = net.in_proj_ba.weight.detach().numpy().reshape(nk, 2, r, HID)

    def rows(lo, hi):
        return qkvz[:, lo:hi].reshape(-1, HID)
    lw = {
        "gdn_in": np.concatenate([
            rows(0, DK), rows(DK, 2 * DK),
            rows(2 * DK, 2 * DK + r * DV),
            rows(2 * DK + r * DV, 2 * DK + 2 * r * DV)]).T,
        "gdn_in_ab": np.concatenate([ba[:, 1].reshape(nv, HID),
                                     ba[:, 0].reshape(nv, HID)]).T,  # [a|b]
        "gdn_conv": net.conv1d.weight.detach().numpy()[:, 0, :],
        "gdn_dt_bias": net.dt_bias.detach().numpy(),
        "gdn_A_log": net.A_log.detach().numpy(),
        "gdn_norm": net.norm.weight.detach().numpy(),
        "gdn_out": net.out_proj.weight.detach().numpy().T,
    }
    return (net, {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()},
            _spec(nk, nv))


def _zero_state(spec, b):
    return {k: jnp.zeros((b,) + shape[2:], dt) for k, (shape, dt) in
            ssm.ssm_state_shapes(spec, 1, b, jnp.float32).items()}


def test_the_mixer_is_transformers_gated_deltanet(hf_mixer):
    import torch
    net, lw, SPEC = hf_mixer
    b, t = 2, 41
    x = np.random.default_rng(36).standard_normal((b, t, HID)).astype(
        np.float32)
    with torch.no_grad():
        want = net(torch.tensor(x)).numpy()
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    got, st = ssm.ssm_block(SPEC, lw, jnp.asarray(x), _zero_state(SPEC, b),
                            phase="prefill", positions=pos,
                            seq_lens=jnp.full((b,), t))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # ... and continued from the carried state and tail: 24 tokens (a chunk
    # and a padded one), then 17 one at a time, each row a different length
    # of real tokens in the first dispatch
    n1 = np.asarray([24, 19])
    valid = jnp.arange(24)[None] < n1[:, None]
    out1, st1 = ssm.ssm_block(SPEC, lw, jnp.asarray(x[:, :24]),
                              _zero_state(SPEC, b), phase="paged",
                              positions=pos[:, :24], valid=valid)
    outs = {r: [np.asarray(out1[r, :n1[r]])] for r in range(b)}
    for r in range(b):
        st_r = {k: v[r:r + 1] for k, v in st1.items()}
        for i in range(int(n1[r]), t):
            o, st_r = ssm.ssm_block(
                SPEC, lw, jnp.asarray(x[r:r + 1, i:i + 1]), st_r,
                phase="paged", positions=jnp.full((1, 1), i),
                valid=jnp.ones((1, 1), bool))
            outs[r].append(np.asarray(o[0]))
        np.testing.assert_allclose(np.concatenate(outs[r]), want[r],
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(st_r["ssm"][0], st["ssm"][r], atol=1e-4)
        np.testing.assert_allclose(st_r["conv_x"][0], st["conv_x"][r],
                                   atol=1e-5)


@pytest.mark.parametrize("t", [1, 24])
def test_a_dead_row_keeps_its_state_bit_for_bit(hf_mixer, t):
    _, lw, SPEC = hf_mixer
    rng = np.random.default_rng(37)
    state = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in _zero_state(SPEC, 2).items()}
    x = jnp.asarray(rng.standard_normal((2, t, HID)), jnp.float32)
    valid = jnp.asarray([[True] * t, [False] * t])
    _, new = ssm.ssm_block(SPEC, lw, x, state, phase="paged",
                           positions=jnp.full((2, t), 7) + jnp.arange(t),
                           valid=valid)
    for k in state:
        np.testing.assert_array_equal(new[k][1], state[k][1])
        assert not np.array_equal(new[k][0], state[k][0])


# ---------------------------------------------------------------------------
# what the paged path still refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["rglru", "shortconv"])
def test_rglru_stays_refused_on_the_paged_path_and_shortconv_does_not(kind):
    import dataclasses

    from conftest import tiny_llama_hf_config
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.llama import \
        LlamaInferenceConfig
    sentence = ("paged rglru state ("
                + model_base.RECURRENT_UNSUPPORTED["paged rglru state"] + ")")
    assert "rglru block" in sentence and "shortconv" in sentence
    block = ssm.SSMSpec(kind=kind, d_inner=64, num_heads=4, head_dim=16)
    paged = TpuConfig(batch_size=2, seq_len=32, dtype="float32",
                      is_block_kv_layout=True, pa_block_size=8,
                      pa_num_blocks=8, enable_bucketing=False)
    flat = TpuConfig(batch_size=2, seq_len=32, dtype="float32",
                     enable_bucketing=False)
    spec = model_base.spec_from_config(
        LlamaInferenceConfig(flat, **tiny_llama_hf_config()), ssm=block)
    assert set(ssm.CONTINUING_KINDS) == {"mamba2", "gated_delta", "kda",
                                         "mamba1", "shortconv"}
    assert dataclasses.replace(block, kind="gated_delta").kind \
        in ssm.CONTINUING_KINDS
    if kind == "shortconv":
        # since ISSUE 61 the block continues from a carried tail: the paged
        # path takes it (tests/test_lfm2_moe_paged.py walks it)
        assert model_base.spec_from_config(
            LlamaInferenceConfig(paged, **tiny_llama_hf_config()),
            ssm=block).ssm.kind == kind
        return
    with pytest.raises(NotImplementedError) as ei:
        model_base.spec_from_config(
            LlamaInferenceConfig(paged, **tiny_llama_hf_config()), ssm=block)
    assert sentence in str(ei.value)
    with pytest.raises(NotImplementedError) as ei:
        model_base.run_layers_ssm(spec, None, {"k": None, "v": None}, None,
                                  None, None, None, "paged")
    assert sentence in str(ei.value)
