"""Real-TPU flash-attention checks (compiled Mosaic path, hardware PRNG dropout).

The main suite pins jax to a virtual CPU platform (conftest.py) where the Pallas
kernels run in interpret mode; interpret mode cannot lower the TPU hardware PRNG,
so the in-kernel dropout path and the real Mosaic block-layout constraints are
covered here and skipped off-TPU (``@pytest.mark.tpu``, gated once in
conftest.py). On the chip: ``chiprun -- tools/run_tpu_tests.sh`` from the repo root.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.mark.tpu
def test_flash_small_blocks_compile_on_tpu():
    """Non-128-multiple user block sizes must normalize, not crash Mosaic
    (code-review finding: the (1, block_q) LSE tile needs 128-lane blocks)."""
    from paddle_tpu.kernels.pallas import flash_attention as fa

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 384, 2, 64), jnp.bfloat16)
    out = fa.flash_attention_blhd(q, q, q, causal=True, block_q=64, block_k=64)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    g = jax.grad(lambda a: jnp.sum(fa.flash_attention_blhd(
        a, a, a, causal=True, block_q=64, block_k=64).astype(jnp.float32)))(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.tpu
def test_flash_dropout_deterministic_per_seed_and_unbiased():
    from paddle_tpu.kernels.pallas import flash_attention as fa

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 512, 4, 64) * 0.5, jnp.bfloat16)
    base = fa.flash_attention_blhd(q, q, q, causal=True)
    o1 = fa.flash_attention_blhd(q, q, q, causal=True, dropout_rate=0.2, seed=7)
    o2 = fa.flash_attention_blhd(q, q, q, causal=True, dropout_rate=0.2, seed=7)
    o3 = fa.flash_attention_blhd(q, q, q, causal=True, dropout_rate=0.2, seed=8)
    a1, a2, a3 = (np.asarray(x, np.float32) for x in (o1, o2, o3))
    assert np.array_equal(a1, a2), "same seed must reproduce the mask"
    assert not np.array_equal(a1, a3), "different seed must change the mask"
    # inverted-dropout scaling keeps the expectation: means within noise
    assert abs(a1.mean() - float(jnp.mean(base.astype(jnp.float32)))) < 0.05


@pytest.mark.tpu
def test_flash_dropout_gradients_finite_and_mask_consistent():
    """The three kernels (fwd/dq/dkv) must reproduce the identical mask: if
    they disagreed, grads on dropped positions would leak and a finite-diff
    probe on a kept position would mismatch wildly."""
    from paddle_tpu.kernels.pallas import flash_attention as fa

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 256, 2, 64) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 64) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 64) * 0.5, jnp.float32)

    def loss(q, k, v):
        out = fa.flash_attention_blhd(q, k, v, causal=True, dropout_rate=0.3,
                                      seed=11)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for arr, name in zip(g, "qkv"):
        assert np.isfinite(np.asarray(arr, np.float32)).all(), name
    # directional derivative along dv must match the analytic grad. out is
    # LINEAR in v, so the central difference is exact in exact arithmetic at
    # any dv scale — use a large dv so fp noise in the O(1e3) loss is
    # negligible; an inconsistent mask between kernels would err at O(signal)
    dv = jnp.asarray(rng.randn(*v.shape) * 0.1, jnp.float32)
    num = (loss(q, k, v + dv) - loss(q, k, v - dv)) / 2.0
    ana = jnp.sum(g[2] * dv)
    np.testing.assert_allclose(float(num), float(ana), rtol=2e-2)


@pytest.mark.tpu
def test_flash_gqa_matches_repeated_kv_on_tpu():
    """Native GQA (KV-head index map) == explicitly repeated KV, values and
    gradients, on the compiled Mosaic path."""
    from paddle_tpu.kernels.pallas import flash_attention as fa

    b, l, h, hkv, d = 2, 256, 8, 2, 64
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, l, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, l, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, l, hkv, d), jnp.bfloat16)
    rep = h // hkv

    out = fa.flash_attention_blhd(q, k, v, causal=True)
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    ref = fa.flash_attention_blhd(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=1e-2,
                               atol=1e-2)

    def loss_gqa(q, k, v):
        return jnp.sum(fa.flash_attention_blhd(
            q, k, v, causal=True).astype(jnp.float32) ** 2)

    def loss_rep(q, k, v):
        kr = jnp.repeat(k, rep, axis=2)
        vr = jnp.repeat(v, rep, axis=2)
        return jnp.sum(fa.flash_attention_blhd(
            q, kr, vr, causal=True).astype(jnp.float32) ** 2)

    g = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=5e-2, atol=5e-2, err_msg=f"d{name} mismatch")


@pytest.mark.tpu
def test_flash_long_sequence_16k():
    """Long-context single chip: 16k tokens through the flash kernel stay
    O(block) in VMEM and finite."""
    from paddle_tpu.kernels.pallas import flash_attention as fa

    q = jax.random.normal(jax.random.PRNGKey(1), (1, 16384, 2, 128),
                          jnp.bfloat16)
    out = fa.flash_attention_blhd(q, q, q, causal=True)
    arr = np.asarray(out, np.float32)
    assert arr.shape == (1, 16384, 2, 128) and np.isfinite(arr).all()
