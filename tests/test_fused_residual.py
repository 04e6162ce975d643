"""Fused residual epilogue LayerNorm(x + dropout(sub)) vs fp32 oracles —
kernel numerics in interpret mode (CPU), functional fallback equivalence,
and TPU-only dropout mask consistency (fwd/bwd regenerate the same mask;
``@pytest.mark.tpu`` — on the chip, ``chiprun -- tools/run_tpu_tests.sh``).

Reference analog: operators/fused/fused_attention_op.cu and
fused_feedforward_op.cu residual epilogues; OpTest-style oracle checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.kernels.pallas.fused_residual import fused_add_dropout_ln

N, H = 256, 256
EPS = 1e-12


def _oracle(x, s, w, b, eps=EPS):
    h = x.astype(jnp.float32) + s.astype(jnp.float32)
    mean = h.mean(axis=-1, keepdims=True)
    var = ((h - mean) ** 2).mean(axis=-1, keepdims=True)
    xhat = (h - mean) / jnp.sqrt(var + eps)
    return xhat * w.astype(jnp.float32) + b.astype(jnp.float32)


def _inputs(seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(N, H), dtype)
    s = jnp.asarray(rs.randn(N, H), dtype)
    w = jnp.asarray(rs.rand(H) + 0.5, jnp.float32)
    b = jnp.asarray(rs.randn(H) * 0.1, jnp.float32)
    return x, s, w, b


def test_fused_forward_matches_oracle():
    x, s, w, b = _inputs()
    seed = jnp.zeros((1,), jnp.int32)
    out = fused_add_dropout_ln(x, s, w, b, seed, 0.0, EPS, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(x, s, w, b)),
                               rtol=2e-5, atol=2e-5)


def test_fused_backward_matches_oracle():
    x, s, w, b = _inputs(1)
    seed = jnp.zeros((1,), jnp.int32)
    co = jnp.asarray(np.random.RandomState(2).randn(N, H), jnp.float32)

    def f_fused(x, s, w, b):
        return (fused_add_dropout_ln(x, s, w, b, seed, 0.0, EPS, True)
                * co).sum()

    def f_ref(x, s, w, b):
        return (_oracle(x, s, w, b) * co).sum()

    gf = jax.grad(f_fused, argnums=(0, 1, 2, 3))(x, s, w, b)
    gr = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, s, w, b)
    for a, r, nm in zip(gf, gr, "x s w b".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{nm} diverged")


def test_functional_fallback_matches_composition():
    # CPU: add_dropout_ln routes to the unfused composition; p=0 is exact
    rs = np.random.RandomState(3)
    x = paddle.to_tensor(rs.randn(4, 16, 128).astype("float32"),
                         stop_gradient=False)
    sub = paddle.to_tensor(rs.randn(4, 16, 128).astype("float32"),
                           stop_gradient=False)
    w = paddle.to_tensor((rs.rand(128) + 0.5).astype("float32"),
                         stop_gradient=False)
    b = paddle.to_tensor(rs.randn(128).astype("float32"),
                         stop_gradient=False)
    out = F.add_dropout_ln(x, sub, w, b, p=0.5, epsilon=1e-12, training=False)
    ref = F.layer_norm(x + sub, 128, w, b, epsilon=1e-12)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5, atol=1e-5)
    out.sum().backward()
    assert x.grad is not None and w.grad is not None


def test_bert_layer_uses_epilogue_consistently():
    """BertLayer forward (p=0) == the manual unfused composition."""
    from paddle_tpu.models.bert import BertConfig, BertLayer
    paddle.seed(0)
    cfg = BertConfig(hidden_size=128, num_heads=2, num_layers=1,
                     intermediate_size=256, hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    layer = BertLayer(cfg)
    layer.eval()
    x = paddle.to_tensor(
        np.random.RandomState(4).randn(2, 8, 128).astype("float32"))
    out = layer(x)
    # manual recomputation with the same parameters
    qkv = layer.qkv_proj(x)
    attn = F.flash_attention_qkv_packed(qkv, 2, causal=False, dropout=0.0,
                                        training=False)
    attn = layer.out_proj(attn)
    h = F.layer_norm(x + attn, 128, layer.attn_norm.weight,
                     layer.attn_norm.bias, epsilon=cfg.layer_norm_epsilon)
    ffn = layer.fc_out(F.gelu(layer.fc_in(h), approximate=True))
    want = F.layer_norm(h + ffn, 128, layer.ffn_norm.weight,
                        layer.ffn_norm.bias, epsilon=cfg.layer_norm_epsilon)
    np.testing.assert_allclose(out.numpy(), want.numpy(),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.tpu
def test_fused_dropout_fwd_bwd_mask_consistent():
    """The backward must regenerate the SAME keep mask as the forward:
    analytic grads vs finite differences of the seeded kernel itself."""
    x, s, w, b = _inputs(5, jnp.float32)
    seed = jnp.asarray([7], jnp.int32)

    def loss(s_):
        o = fused_add_dropout_ln(x, s_, w, b, seed, 0.3, EPS, False)
        return (o.astype(jnp.float32) ** 2).sum()

    l1, l2 = float(loss(s)), float(loss(s))
    assert l1 == l2, "per-seed determinism"
    g = jax.grad(loss)(s)
    rs = np.random.RandomState(0)
    for _ in range(3):
        v = jnp.asarray(rs.randn(N, H).astype(np.float32))
        eps_fd = 1e-2
        fd = (float(loss(s + eps_fd * v)) - float(loss(s - eps_fd * v))) \
            / (2 * eps_fd)
        an = float(jnp.vdot(g, v))
        assert abs(fd - an) <= 0.15 * max(abs(fd), abs(an), 1.0), (fd, an)
