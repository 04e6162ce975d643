"""Pallas hardware-PRNG dropout (kernels/pallas/dropout.py) — TPU-only
(the hardware PRNG has no interpret lowering; CPU runs keep the XLA path).
On the chip: ``chiprun -- tools/run_tpu_tests.sh`` from the repo root.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


@pytest.mark.tpu
def test_dropout_tpu_statistics_and_determinism():
    from paddle_tpu.kernels.pallas.dropout import dropout_tpu
    import jax.numpy as jnp
    x = jnp.ones((512, 768), jnp.float32)
    a = dropout_tpu(x, 7, 0.3)
    b = dropout_tpu(x, 7, 0.3)
    c = dropout_tpu(x, 8, 0.3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    vals = np.asarray(a)
    keep_frac = (vals != 0).mean()
    assert abs(keep_frac - 0.7) < 0.02
    np.testing.assert_allclose(vals[vals != 0], 1.0 / 0.7, rtol=1e-5)


@pytest.mark.tpu
def test_dropout_functional_backward_mask_consistent():
    x = paddle.ones([256, 128], "float32")
    x.stop_gradient = False
    paddle.seed(123)
    y = F.dropout(x, p=0.4, training=True)
    y.sum().backward()
    # grad == fwd output for x=ones iff bwd regenerated the identical mask
    np.testing.assert_allclose(np.asarray(x.grad.numpy()),
                               np.asarray(y.numpy()), rtol=1e-6)


@pytest.mark.tpu
def test_dropout_eval_identity():
    x = paddle.ones([128, 128], "float32")
    y = F.dropout(x, p=0.4, training=False)
    np.testing.assert_allclose(np.asarray(y.numpy()), 1.0)
