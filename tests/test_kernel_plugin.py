"""Kernel-plugin C API tests (reference: phi/capi — out-of-tree kernels
against a stable C ABI; here utils/plugin.h + load_kernel_plugin)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.utils.cpp_extension import load_kernel_plugin

_SRC = r"""
#include <math.h>
#include "plugin.h"

extern "C" {

/* out = a * b + c   (3 inputs, 1 output) */
int fma_kernel(const PTK_Tensor* ins, int n_in, PTK_Tensor* outs, int n_out) {
  if (n_in != 3 || n_out != 1) return 1;
  const float* a = (const float*)ins[0].data;
  const float* b = (const float*)ins[1].data;
  const float* c = (const float*)ins[2].data;
  float* o = (float*)outs[0].data;
  int64_t n = 1;
  for (int64_t i = 0; i < ins[0].ndim; ++i) n *= ins[0].shape[i];
  for (int64_t i = 0; i < n; ++i) o[i] = a[i] * b[i] + c[i];
  return 0;
}

/* grads of fma: inputs = (a, b, c, upstream); outputs = (da, db, dc) */
int fma_grad(const PTK_Tensor* ins, int n_in, PTK_Tensor* outs, int n_out) {
  if (n_in != 4 || n_out != 3) return 1;
  const float* a = (const float*)ins[0].data;
  const float* b = (const float*)ins[1].data;
  const float* g = (const float*)ins[3].data;
  float* da = (float*)outs[0].data;
  float* db = (float*)outs[1].data;
  float* dc = (float*)outs[2].data;
  int64_t n = 1;
  for (int64_t i = 0; i < ins[0].ndim; ++i) n *= ins[0].shape[i];
  for (int64_t i = 0; i < n; ++i) {
    da[i] = g[i] * b[i];
    db[i] = g[i] * a[i];
    dc[i] = g[i];
  }
  return 0;
}

/* stats: 1 input -> 2 outputs (sum scalar, squared elementwise) */
int stats_kernel(const PTK_Tensor* ins, int n_in, PTK_Tensor* outs,
                 int n_out) {
  if (n_in != 1 || n_out != 2) return 1;
  const float* x = (const float*)ins[0].data;
  float* s = (float*)outs[0].data;
  float* sq = (float*)outs[1].data;
  int64_t n = 1;
  for (int64_t i = 0; i < ins[0].ndim; ++i) n *= ins[0].shape[i];
  s[0] = 0.0f;
  for (int64_t i = 0; i < n; ++i) { s[0] += x[i]; sq[i] = x[i] * x[i]; }
  return 0;
}

/* always fails: error propagation check */
int bad_kernel(const PTK_Tensor* ins, int n_in, PTK_Tensor* outs, int n_out) {
  return 42;
}

}
"""


@pytest.fixture(scope="module")
def plugin():
    return load_kernel_plugin(
        "ptk_test", sources=[_SRC],
        kernels={
            "fma_kernel": dict(n_in=3, out=lambda a, b, c: [a],
                               grad="fma_grad"),
            "stats_kernel": dict(
                n_in=1,
                out=lambda x: [((1,), np.float32), (x[0], np.float32)]),
            "bad_kernel": dict(n_in=1, out=lambda x: [x]),
        })


def test_multi_input_kernel(plugin):
    rng = np.random.RandomState(0)
    a, b, c = (rng.randn(3, 4).astype("float32") for _ in range(3))
    out = plugin.fma_kernel(paddle.to_tensor(a), paddle.to_tensor(b),
                            paddle.to_tensor(c))
    np.testing.assert_allclose(out.numpy(), a * b + c, rtol=1e-6)


def test_multi_output_kernel(plugin):
    x = np.arange(6, dtype="float32").reshape(2, 3)
    s, sq = plugin.stats_kernel(paddle.to_tensor(x))
    np.testing.assert_allclose(s.numpy(), [15.0])
    np.testing.assert_allclose(sq.numpy(), x * x)


def test_plugin_gradients_flow(plugin):
    """C gradient kernel wired as the op's explicit backward."""
    rng = np.random.RandomState(1)
    a = paddle.to_tensor(rng.randn(2, 3).astype("float32"))
    b = paddle.to_tensor(rng.randn(2, 3).astype("float32"))
    c = paddle.to_tensor(rng.randn(2, 3).astype("float32"))
    for t in (a, b, c):
        t.stop_gradient = False
    out = plugin.fma_kernel(a, b, c)
    (out * out).sum().backward()
    g = 2.0 * (a.numpy() * b.numpy() + c.numpy())
    np.testing.assert_allclose(a.grad.numpy(), g * b.numpy(), rtol=1e-5)
    np.testing.assert_allclose(b.grad.numpy(), g * a.numpy(), rtol=1e-5)
    np.testing.assert_allclose(c.grad.numpy(), g, rtol=1e-5)


def test_plugin_error_propagates(plugin):
    with pytest.raises(RuntimeError, match="rc=42"):
        plugin.bad_kernel(paddle.to_tensor(np.ones(3, "float32")))


def test_plugin_under_jit_trace(plugin):
    """Plugin kernels embed as host callbacks under jit (pure_callback;
    also verified on the v5e through libtpu 0.0.34)."""
    rng = np.random.RandomState(2)
    a, b, c = (rng.randn(2, 2).astype("float32") for _ in range(3))

    @paddle.jit.to_static
    def fn(a, b, c):
        return plugin.fma_kernel(a, b, c) + 1.0

    out = fn(paddle.to_tensor(a), paddle.to_tensor(b), paddle.to_tensor(c))
    np.testing.assert_allclose(out.numpy(), a * b + c + 1.0, rtol=1e-6)


def test_plugin_contract_errors(plugin):
    with pytest.raises(TypeError, match="takes 3 tensors"):
        plugin.fma_kernel(paddle.to_tensor(np.ones(2, "float32")),
                          paddle.to_tensor(np.ones(2, "float32")))
    with pytest.raises(ValueError, match="dtypes"):
        plugin.stats_kernel(paddle.to_tensor(
            np.ones(3, "float32")).astype("bfloat16"))
