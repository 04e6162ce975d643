"""Deferred-eager (core/lazy.py) correctness worker.

Run in a subprocess with a SINGLE device (no --xla_force_host_platform_device_count)
to exercise the production single-chip fast path (no placement bookkeeping);
the multi-device path is covered in-suite by tests/test_lazy_multidevice.py.
Prints LAZY_WORKER_OK on success.
"""
import os
import sys

os.environ.pop("XLA_FLAGS", None)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core import lazy

assert jax.device_count() == 1
assert lazy.enabled(), "FLAGS_eager_fusion should engage by default"

# --- laziness is real: a math chain defers, observation materializes --------
x = paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3))
y = x * 2.0 + 1.0
assert type(y._data) is lazy.LazyArray
np.testing.assert_allclose(y.numpy(), np.arange(6).reshape(2, 3) * 2.0 + 1.0)
assert type(y._data) is not lazy.LazyArray  # value() caches the forced array


# --- train parity: losses identical with fusion on/off ----------------------
def train(lazy_on, steps=5):
    paddle.set_flags({"FLAGS_eager_fusion": lazy_on})
    paddle.seed(0)
    np.random.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=m.parameters())
    loss_fn = nn.CrossEntropyLoss()
    xs = np.random.randn(16, 8).astype("float32")
    ys = np.random.randint(0, 4, 16).astype("int64")
    losses = []
    for _ in range(steps):
        loss = loss_fn(m(paddle.to_tensor(xs)), paddle.to_tensor(ys))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


np.testing.assert_allclose(train(True), train(False), rtol=1e-5)
paddle.set_flags({"FLAGS_eager_fusion": True})

# --- conv/BN: running stats update lazily, full fwd+bwd matches eager -------
def conv_run(lazy_on):
    paddle.set_flags({"FLAGS_eager_fusion": lazy_on})
    paddle.seed(1)
    np.random.seed(1)
    m = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.BatchNorm2D(8),
                      nn.ReLU(), nn.Flatten(), nn.Linear(8 * 64, 4))
    m.train()
    xs = paddle.to_tensor(np.random.randn(4, 3, 8, 8).astype("float32"))
    loss = m(xs).mean()
    loss.backward()
    grads = {n: p.grad.numpy().copy() for n, p in m.named_parameters()}
    bufs = {n: b.numpy().copy() for n, b in m.named_buffers()}
    return float(loss), grads, bufs


l1, g1, b1 = conv_run(True)
l0, g0, b0 = conv_run(False)
assert abs(l1 - l0) < 1e-5
for n in g0:
    np.testing.assert_allclose(g1[n], g0[n], rtol=1e-4, atol=1e-5)
for n in b0:
    np.testing.assert_allclose(b1[n], b0[n], rtol=1e-4, atol=1e-6)
paddle.set_flags({"FLAGS_eager_fusion": True})

# --- one flush per step, executable cache steady-state ----------------------
flush_count = {"n": 0}
orig = lazy.LazyGraph.flush
def counting_flush(self):
    if not self.flushed and self.nodes:
        flush_count["n"] += 1
    return orig(self)
lazy.LazyGraph.flush = counting_flush
paddle.seed(2)
m = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
xs = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
for _ in range(2):  # warm compile + signature
    loss = m(xs).mean()
    loss.backward(); opt.step(); opt.clear_grad()
before_exec = lazy.cache_stats()["exec_cache"]
flush_count["n"] = 0
for _ in range(3):
    loss = m(xs).mean()
    loss.backward(); opt.step(); opt.clear_grad()
assert flush_count["n"] == 3, f"expected 1 flush/step, got {flush_count['n']}/3"
assert lazy.cache_stats()["exec_cache"] == before_exec, "steady state recompiled"
lazy.LazyGraph.flush = orig

# --- error semantics preserved ----------------------------------------------
t = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
z = (t * t).sum()
z.backward()
try:
    z.backward()
    raise AssertionError("expected retain_graph RuntimeError")
except RuntimeError:
    pass

# --- hooks, retain_grad, double grad ----------------------------------------
t = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
seen = []
t.register_hook(lambda g: seen.append(g.numpy().copy()))
u = t * 3.0
u.retain_grads()
u.sum().backward()
assert len(seen) == 1 and np.allclose(seen[0], 3.0)

t = paddle.to_tensor(np.array([2.0], np.float32), stop_gradient=False)
z = t * t * t
(g,) = paddle.grad(z, t, create_graph=True)
(g2,) = paddle.grad(g, t)
np.testing.assert_allclose(g2.numpy(), 12.0, rtol=1e-5)

# --- in-place version check still fires under laziness ----------------------
a = paddle.to_tensor(np.ones((2, 2), np.float32), stop_gradient=False)
b = a * 2.0
a.set_value(np.zeros((2, 2), np.float32))
try:
    b.sum().backward()
    raise AssertionError("expected inplace version error")
except RuntimeError:
    pass

# --- dropout differs across calls, deterministic under seed -----------------
paddle.seed(7)
d1 = paddle.nn.functional.dropout(paddle.to_tensor(np.ones((64,), np.float32)),
                                  p=0.5, training=True).numpy()
d2 = paddle.nn.functional.dropout(paddle.to_tensor(np.ones((64,), np.float32)),
                                  p=0.5, training=True).numpy()
assert not np.allclose(d1, d2)
paddle.seed(7)
d3 = paddle.nn.functional.dropout(paddle.to_tensor(np.ones((64,), np.float32)),
                                  p=0.5, training=True).numpy()
np.testing.assert_allclose(d1, d3)

# --- sparse embedding grads (SelectedRows through the lazy boundary) --------
emb = nn.Embedding(50, 8, sparse=True)
opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=emb.parameters())
ids = paddle.to_tensor(np.array([1, 3, 3, 7], np.int64))
out = emb(ids).sum()
out.backward()
opt.step()
opt.clear_grad()

print("LAZY_WORKER_OK")
