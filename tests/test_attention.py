"""Parity tests for attention primitives against torch (CPU) ground truth.

We verify our MultiHeadAttention reproduces torch.nn.MultiheadAttention
(embed_dim=q channels, kdim=vdim=kv channels, batch_first) — the exact native
op the reference wraps (reference model.py:59-74) — by copying weights across
frameworks and comparing outputs. MLP/LayerNorm likewise.
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.attention import (
    MLP,
    CrossAttention,
    CrossAttentionLayer,
    MultiHeadAttention,
    SelfAttention,
)

B, T, S, E, K, H = 3, 5, 11, 16, 24, 4


def _np(x):
    return np.asarray(x, dtype=np.float32)


def make_torch_mha():
    torch.manual_seed(0)
    return torch.nn.MultiheadAttention(
        embed_dim=E, num_heads=H, kdim=K, vdim=K, batch_first=True
    )


def mha_params_from_torch(t_mha):
    """Map torch MHA weights into our flax param tree."""
    sd = {k: v.detach().numpy() for k, v in t_mha.state_dict().items()}
    b_in = sd["in_proj_bias"]
    return {
        "q_proj": {"kernel": sd["q_proj_weight"].T, "bias": b_in[:E]},
        "k_proj": {"kernel": sd["k_proj_weight"].T, "bias": b_in[E : 2 * E]},
        "v_proj": {"kernel": sd["v_proj_weight"].T, "bias": b_in[2 * E :]},
        "out_proj": {"kernel": sd["out_proj.weight"].T, "bias": sd["out_proj.bias"]},
    }


@pytest.mark.parametrize("use_pad_mask", [False, True])
def test_mha_matches_torch(use_pad_mask, rng):
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)
    pad = np.zeros((B, S), dtype=bool)
    if use_pad_mask:
        pad[0, -3:] = True
        pad[2, -1:] = True

    t_mha = make_torch_mha()
    with torch.no_grad():
        t_out, _ = t_mha(
            torch.tensor(x_q),
            torch.tensor(x_kv),
            torch.tensor(x_kv),
            key_padding_mask=torch.tensor(pad) if use_pad_mask else None,
        )

    mod = MultiHeadAttention(num_q_channels=E, num_kv_channels=K, num_heads=H)
    params = {"params": jax.tree.map(jnp.asarray, mha_params_from_torch(t_mha))}
    j_out = mod.apply(params, x_q, x_kv, pad_mask=jnp.asarray(pad) if use_pad_mask else None)

    np.testing.assert_allclose(_np(j_out), t_out.numpy(), atol=1e-5)


def test_mha_attn_mask(rng):
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)
    attn_mask = np.zeros((T, S), dtype=bool)
    attn_mask[:, S // 2 :] = True  # queries may not look at second half

    t_mha = make_torch_mha()
    with torch.no_grad():
        t_out, _ = t_mha(
            torch.tensor(x_q),
            torch.tensor(x_kv),
            torch.tensor(x_kv),
            attn_mask=torch.tensor(attn_mask),
        )

    mod = MultiHeadAttention(num_q_channels=E, num_kv_channels=K, num_heads=H)
    params = {"params": jax.tree.map(jnp.asarray, mha_params_from_torch(t_mha))}
    j_out = mod.apply(params, x_q, x_kv, attn_mask=jnp.asarray(attn_mask))
    np.testing.assert_allclose(_np(j_out), t_out.numpy(), atol=1e-5)


def test_mlp_matches_torch(rng):
    x = rng.standard_normal((B, T, E)).astype(np.float32)

    torch.manual_seed(1)
    ln = torch.nn.LayerNorm(E)
    l1 = torch.nn.Linear(E, E)
    l2 = torch.nn.Linear(E, E)
    with torch.no_grad():
        t_out = l2(torch.nn.functional.gelu(l1(ln(torch.tensor(x)))))

    params = {
        "params": {
            "norm": {"scale": jnp.asarray(ln.weight.detach().numpy()),
                     "bias": jnp.asarray(ln.bias.detach().numpy())},
            "dense_1": {"kernel": jnp.asarray(l1.weight.detach().numpy().T),
                        "bias": jnp.asarray(l1.bias.detach().numpy())},
            "dense_2": {"kernel": jnp.asarray(l2.weight.detach().numpy().T),
                        "bias": jnp.asarray(l2.bias.detach().numpy())},
        }
    }
    j_out = MLP(E).apply(params, x)
    np.testing.assert_allclose(_np(j_out), t_out.numpy(), atol=1e-5)


def test_cross_attention_pre_ln(rng):
    """Cross-attention = LN(q), LN(kv) then MHA — verified against torch composition."""
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)

    t_mha = make_torch_mha()
    q_ln = torch.nn.LayerNorm(E)
    kv_ln = torch.nn.LayerNorm(K)
    # non-trivial LN affine
    with torch.no_grad():
        q_ln.weight.uniform_(0.5, 1.5)
        kv_ln.bias.uniform_(-0.5, 0.5)
        t_out, _ = t_mha(
            q_ln(torch.tensor(x_q)), kv_ln(torch.tensor(x_kv)), kv_ln(torch.tensor(x_kv))
        )

    params = {
        "params": {
            "q_norm": {"scale": jnp.asarray(q_ln.weight.detach().numpy()),
                       "bias": jnp.asarray(q_ln.bias.detach().numpy())},
            "kv_norm": {"scale": jnp.asarray(kv_ln.weight.detach().numpy()),
                        "bias": jnp.asarray(kv_ln.bias.detach().numpy())},
            "attention": jax.tree.map(jnp.asarray, mha_params_from_torch(t_mha)),
        }
    }
    mod = CrossAttention(num_q_channels=E, num_kv_channels=K, num_heads=H)
    j_out = mod.apply(params, x_q, x_kv)
    np.testing.assert_allclose(_np(j_out), t_out.numpy(), atol=5e-5)


def test_self_attention_single_norm(rng):
    x = rng.standard_normal((B, T, E)).astype(np.float32)
    torch.manual_seed(2)
    t_mha = torch.nn.MultiheadAttention(embed_dim=E, num_heads=H, batch_first=True)
    ln = torch.nn.LayerNorm(E)
    with torch.no_grad():
        xt = ln(torch.tensor(x))
        t_out, _ = t_mha(xt, xt, xt)

    sd = {k: v.detach().numpy() for k, v in t_mha.state_dict().items()}
    w = sd["in_proj_weight"]
    b = sd["in_proj_bias"]
    params = {
        "params": {
            "norm": {"scale": jnp.asarray(ln.weight.detach().numpy()),
                     "bias": jnp.asarray(ln.bias.detach().numpy())},
            "attention": {
                "q_proj": {"kernel": jnp.asarray(w[:E].T), "bias": jnp.asarray(b[:E])},
                "k_proj": {"kernel": jnp.asarray(w[E : 2 * E].T), "bias": jnp.asarray(b[E : 2 * E])},
                "v_proj": {"kernel": jnp.asarray(w[2 * E :].T), "bias": jnp.asarray(b[2 * E :])},
                "out_proj": {"kernel": jnp.asarray(sd["out_proj.weight"].T),
                             "bias": jnp.asarray(sd["out_proj.bias"])},
            },
        }
    }
    mod = SelfAttention(num_channels=E, num_heads=H)
    j_out = mod.apply(params, x)
    np.testing.assert_allclose(_np(j_out), t_out.numpy(), atol=1e-5)


def test_residual_applies_to_first_arg(rng):
    """CrossAttentionLayer output must equal mlp_res(attn_res) where each
    residual adds its own first input (reference model.py:47-56)."""
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)

    layer = CrossAttentionLayer(num_q_channels=E, num_kv_channels=K, num_heads=H)
    variables = layer.init(jax.random.key(0), x_q, x_kv)
    out = layer.apply(variables, x_q, x_kv)

    # recompute manually from the sublayers
    ca = CrossAttention(num_q_channels=E, num_kv_channels=K, num_heads=H)
    attn = ca.apply({"params": variables["params"]["cross_attention"]}, x_q, x_kv)
    h = np.asarray(attn) + x_q
    mlp_out = MLP(E).apply({"params": variables["params"]["mlp"]}, h)
    expected = np.asarray(mlp_out) + h
    np.testing.assert_allclose(_np(out), expected, atol=1e-5)


def test_dropout_zero_is_deterministic(rng):
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)
    layer = CrossAttentionLayer(num_q_channels=E, num_kv_channels=K, num_heads=H, dropout=0.0)
    variables = layer.init(jax.random.key(0), x_q, x_kv)
    o1 = layer.apply(variables, x_q, x_kv, deterministic=False,
                     rngs={"dropout": jax.random.key(1)})
    o2 = layer.apply(variables, x_q, x_kv, deterministic=True)
    np.testing.assert_allclose(_np(o1), _np(o2), atol=1e-6)


def test_dropout_nonzero_varies_and_preserves_mean(rng):
    x_q = rng.standard_normal((B, T, E)).astype(np.float32)
    x_kv = rng.standard_normal((B, S, K)).astype(np.float32)
    layer = CrossAttentionLayer(num_q_channels=E, num_kv_channels=K, num_heads=H, dropout=0.5)
    variables = layer.init(jax.random.key(0), x_q, x_kv)
    o1 = layer.apply(variables, x_q, x_kv, deterministic=False,
                     rngs={"dropout": jax.random.key(1)})
    o2 = layer.apply(variables, x_q, x_kv, deterministic=False,
                     rngs={"dropout": jax.random.key(2)})
    assert not np.allclose(_np(o1), _np(o2))


def test_auto_attention_impl_rule():
    """The 'auto' dispatch table (ops/attention.py constants encode real
    attn_shapes_bench measurements — PERF.md). Covers BOTH arms: the long-KV
    trigger and the round-2 big-logits area trigger with its d >= 32 guard."""
    from perceiver_io_tpu.ops.attention import auto_attention_impl as impl

    # off-TPU: always XLA (the kernel would run in interpreter mode)
    assert impl(2, 2048, 2048, 8, 64, backend="cpu") == "xla"

    # long-KV arm (streaming cross-attention)
    assert impl(2, 512, 50176, 8, 128, backend="tpu") == "pallas"   # in-8h
    assert impl(1, 2048, 182528, 1, 512, backend="tpu") == "pallas" # flow-cross
    assert impl(2, 512, 50176, 1, 1024, backend="tpu") == "xla"     # d>512
    assert impl(8, 256, 512, 4, 16, backend="tpu") == "xla"         # mlm-cross

    # big-logits arm (self-attention stacks under the KV threshold)
    assert impl(2, 2048, 2048, 8, 64, backend="tpu") == "pallas"    # flow-self
    assert impl(2, 182528, 2048, 1, 512, backend="tpu") == "pallas" # flow dec
    assert impl(16, 512, 512, 8, 128, backend="tpu") == "pallas"    # in-self b16
    # d >= 32 guard: MXU-hostile d=16 text shapes stay on XLA at ANY batch
    # (B*H*T*S = 512*4*256*256 = 134M would otherwise trigger)
    assert impl(512, 256, 256, 4, 16, backend="tpu") == "xla"
    # area below threshold: ImageNet self-attn at batch 8 stays on XLA
    assert impl(8, 512, 512, 8, 128, backend="tpu") == "xla"


@pytest.mark.parametrize("impl", ["packed", "flash"])
def test_unknown_attn_impl_is_refused(impl):
    """A string the dispatch does not know must not fall through to the XLA
    branch under the wrong label: refused, with the accepted list."""
    x = jnp.zeros((1, T, E))
    mha = MultiHeadAttention(
        num_q_channels=E, num_kv_channels=E, num_heads=H, attn_impl=impl)
    with pytest.raises(ValueError, match="'auto', 'xla', 'pallas', 'pallas_sp'") as err:
        mha.init(jax.random.key(0), x, x)
    assert repr(impl) in str(err.value)
