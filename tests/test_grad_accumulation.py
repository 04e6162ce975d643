"""Compiled gradient accumulation (ISSUE 3 acceptance).

* parity: the compiled ``accumulate_steps=K`` update matches an eager loop
  accumulating the same K microbatches (allclose, fp32) for K in {1, 2, 4};
* exactly ONE executable per input-shape bucket regardless of K (recompile
  sentinel observable);
* ``accumulate_steps=1`` is bitwise-identical to the existing fast path;
* AMP dynamic loss scaling under accumulation: an injected inf in ANY
  microbatch skips the whole K-step update and adjusts the scale exactly as
  the eager GradScaler;
* HBM: peak live-array bytes at ``accumulate_steps=K`` stays ~flat versus
  the single-microbatch step, while the ×K single-step batch exceeds it;
* wiring: fleet.GradientMergeOptimizer adapter, hapi
  ``prepare(accumulate_steps=K)`` / ``train_batch(update=False)`` buffering,
  ``DeviceLoader(stack_batches=K)``, monitor accumulation gauges.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import monitor
from paddle_tpu.amp import GradScaler
from paddle_tpu.io import DeviceLoader, stack_microbatches


@pytest.fixture(autouse=True)
def _monitor_off():
    monitor.disable()
    yield
    monitor.disable()


class MLP(nn.Layer):
    def __init__(self, din=8, hidden=16, nclass=4):
        super().__init__()
        self.fc1 = nn.Linear(din, hidden)
        self.fc2 = nn.Linear(hidden, nclass)

    def forward(self, x, labels):
        h = self.fc2(F.relu(self.fc1(x)))
        return F.cross_entropy(h, labels).mean()


def _make(lr=0.1, wd=0.5, seed=7):
    paddle.seed(seed)
    model = MLP()
    opt = paddle.optimizer.AdamW(learning_rate=lr, weight_decay=wd,
                                 parameters=model.parameters())
    return model, opt


def _micro(k, bs=16, din=8, nclass=4, seed=0):
    rng = np.random.RandomState(seed)
    xs = [rng.randn(bs, din).astype("float32") for _ in range(k)]
    ys = [rng.randint(0, nclass, (bs, 1)).astype("int64") for _ in range(k)]
    return xs, ys


def _stacked(xs, ys):
    return paddle.to_tensor(np.stack(xs)), paddle.to_tensor(np.stack(ys))


def _eager_accum_update(model, opt, xs, ys, avg):
    """Reference: K eager backward passes accumulate into p._grad, one
    optimizer update (scaled by 1/K for the avg semantics)."""
    for x, y in zip(xs, ys):
        loss = model(paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
    if avg:
        k = len(xs)
        for p in model.parameters():
            if p._grad is not None:
                p._grad = p._grad * (1.0 / k)
    opt.step()
    opt.clear_grad()


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("k", [1, 2, 4])
def test_compiled_accumulation_matches_eager(k):
    xs, ys = _micro(k)

    model_e, opt_e = _make()
    if k == 1:
        loss = model_e(paddle.to_tensor(xs[0]), paddle.to_tensor(ys[0]))
        loss.backward()
        opt_e.step()
        opt_e.clear_grad()
    else:
        _eager_accum_update(model_e, opt_e, xs, ys, avg=True)

    model_c, opt_c = _make()
    step = paddle.jit.TrainStep(model_c, opt_c, accumulate_steps=k)
    if k == 1:
        step(paddle.to_tensor(xs[0]), paddle.to_tensor(ys[0]))
    else:
        step(*_stacked(xs, ys))

    for (n_e, p_e), (n_c, p_c) in zip(model_e.named_parameters(),
                                      model_c.named_parameters()):
        np.testing.assert_allclose(p_e.numpy(), p_c.numpy(), rtol=2e-5,
                                   atol=2e-6, err_msg=n_e)


def test_compiled_accumulation_sum_mode_matches_eager():
    """average_grads=False keeps the raw grad sum — exactly what K eager
    loss.backward() calls leave in p._grad."""
    k = 3
    xs, ys = _micro(k, seed=5)
    model_e, opt_e = _make(wd=0.0)
    _eager_accum_update(model_e, opt_e, xs, ys, avg=False)

    model_c, opt_c = _make(wd=0.0)
    step = paddle.jit.TrainStep(model_c, opt_c, accumulate_steps=k,
                                average_grads=False)
    step(*_stacked(xs, ys))
    for (n_e, p_e), (n_c, p_c) in zip(model_e.named_parameters(),
                                      model_c.named_parameters()):
        np.testing.assert_allclose(p_e.numpy(), p_c.numpy(), rtol=2e-5,
                                   atol=2e-6, err_msg=n_e)


def test_accumulate_steps_1_bitwise_identical_to_fast_path():
    xs, ys = _micro(3, seed=2)
    losses = {}
    for acc in (None, 1):
        model, opt = _make()
        step = paddle.jit.TrainStep(model, opt, accumulate_steps=acc)
        losses[acc] = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                       for x, y in zip(xs, ys)]
        losses[(acc, "p")] = {n: p.numpy() for n, p in
                              model.named_parameters()}
    assert losses[None] == losses[1]
    for n in losses[(None, "p")]:
        np.testing.assert_array_equal(losses[(None, "p")][n],
                                      losses[(1, "p")][n], err_msg=n)


def test_one_compile_per_bucket_regardless_of_k():
    k = 4
    xs, ys = _micro(k)
    monitor.enable(None)
    model, opt = _make()
    step = paddle.jit.TrainStep(model, opt, accumulate_steps=k)
    sx, sy = _stacked(xs, ys)
    for _ in range(3):
        step(sx, sy)
    assert step.num_compiles == 1
    assert monitor.counter("train_step/recompiles").value == 1
    # the accumulation gauges went live with the executable
    assert monitor.gauge("train_step/accumulate_steps").value == k
    assert monitor.gauge("train_step/grad_accumulator_bytes").value > 0
    assert monitor.counter("train_step/microbatches").value == 3 * k


def test_grad_clip_compiles_into_accumulated_step():
    """Global-norm clip applies to the MERGED gradient (eager merge-then-clip
    order), and the clipped trajectory differs from unclipped."""
    k = 2
    xs, ys = _micro(k, seed=9)

    def eager(avg):
        paddle.seed(7)
        model = MLP()
        opt = paddle.optimizer.AdamW(
            learning_rate=0.1, parameters=model.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(1e-2))
        _eager_accum_update(model, opt, xs, ys, avg=avg)
        return model

    model_e = eager(True)
    paddle.seed(7)
    model_c = MLP()
    opt_c = paddle.optimizer.AdamW(
        learning_rate=0.1, parameters=model_c.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(1e-2))
    step = paddle.jit.TrainStep(model_c, opt_c, accumulate_steps=k)
    step(*_stacked(xs, ys))
    for (n_e, p_e), (n_c, p_c) in zip(model_e.named_parameters(),
                                      model_c.named_parameters()):
        np.testing.assert_allclose(p_e.numpy(), p_c.numpy(), rtol=2e-5,
                                   atol=2e-6, err_msg=n_e)


# ---------------------------------------------------------------------- AMP


def test_amp_clean_window_matches_eager_scaled_accumulation():
    k = 2
    xs, ys = _micro(k, seed=3)
    scale = 1024.0

    # eager reference: scaled backward per microbatch, manual unscale+avg
    model_e, opt_e = _make(wd=0.0)
    for x, y in zip(xs, ys):
        loss = model_e(paddle.to_tensor(x), paddle.to_tensor(y))
        (loss * scale).backward()
    for p in model_e.parameters():
        if p._grad is not None:
            p._grad = p._grad * (1.0 / (scale * k))
    opt_e.step()
    opt_e.clear_grad()

    model_c, opt_c = _make(wd=0.0)
    sc = GradScaler(init_loss_scaling=scale)
    step = paddle.jit.TrainStep(model_c, opt_c, accumulate_steps=k,
                                grad_scaler=sc)
    step(*_stacked(xs, ys))
    assert sc._scale == scale  # clean window: no shrink
    for (n_e, p_e), (n_c, p_c) in zip(model_e.named_parameters(),
                                      model_c.named_parameters()):
        np.testing.assert_allclose(p_e.numpy(), p_c.numpy(), rtol=2e-4,
                                   atol=2e-5, err_msg=n_e)


def test_amp_inf_microbatch_skips_whole_window_and_shrinks_scale():
    k = 2
    xs, ys = _micro(k, seed=0)
    model, opt = _make(wd=0.0)
    sc = GradScaler(init_loss_scaling=1024.0, incr_every_n_steps=2)
    step = paddle.jit.TrainStep(model, opt, accumulate_steps=k,
                                grad_scaler=sc)
    monitor.enable(None)
    step(*_stacked(xs, ys))  # clean step
    assert sc._good_steps == 1 and sc._scale == 1024.0

    p_before = {n: p.numpy().copy() for n, p in model.named_parameters()}
    m_before = {n: np.asarray(opt._accumulators[id(p)]["moment1"]).copy()
                for n, p in model.named_parameters()}
    step_count_before = opt._step_count
    xs_bad = [xs[0], np.full_like(xs[1], np.inf)]
    step(*_stacked(xs_bad, ys))

    # whole K-step update skipped: params AND optimizer state bit-identical
    for n, p in model.named_parameters():
        np.testing.assert_array_equal(p_before[n], p.numpy(), err_msg=n)
        np.testing.assert_array_equal(
            m_before[n], np.asarray(opt._accumulators[id(p)]["moment1"]),
            err_msg=n)
    # scale shrank exactly as the eager scaler: * decr_ratio, counters reset
    assert sc._scale == 512.0
    assert sc._good_steps == 0 and sc._bad_steps == 0
    # step counter rewound — bias correction replays this step number
    assert opt._step_count == step_count_before
    assert monitor.counter("train_step/skipped_updates").value == 1

    # recovery: two clean steps then growth at incr_every_n_steps=2
    step(*_stacked(xs, ys))
    step(*_stacked(xs, ys))
    assert sc._scale == 1024.0
    # dynamic scale changes are device inputs, not recompiles
    assert step.num_compiles == 1


def test_amp_scale_state_machine_matches_eager_scaler():
    """The compiled outcome hook must replay the eager update() transitions
    for an arbitrary good/bad sequence."""
    seq = [False, True, False, False, True, False]
    eager = GradScaler(init_loss_scaling=256.0, incr_every_n_steps=2)
    compiled = GradScaler(init_loss_scaling=256.0, incr_every_n_steps=2)
    for bad in seq:
        eager._found_inf = bad
        eager._unscaled = True
        eager.update()
        compiled._compiled_outcome(bad)
        assert compiled._scale == eager._scale
        assert compiled._good_steps == eager._good_steps
        assert compiled._bad_steps == eager._bad_steps


# ------------------------------------------------------------------- memory


def test_peak_memory_flat_vs_x4_batch():
    """The HBM contract: accumulate_steps=4 over microbatch B costs ~the
    single-microbatch step (one microbatch's activations live at a time +
    fp32 accumulators), while a ×4 single-step batch pays ×4 activations."""
    from paddle_tpu.monitor.memory import executable_memory_stats

    # feed-light / activation-heavy (2-CPU host): tiny input features, wide
    # hidden activations, so temps (which accumulation keeps flat) dominate
    # the stacked-input and fp32-accumulator overheads (which it adds)
    DIN, HID, NCLS, B, K = 8, 128, 4, 8192, 4

    class Wide(nn.Layer):
        def __init__(self):
            super().__init__()
            self.inp = nn.Linear(DIN, HID)
            self.mids = nn.LayerList([nn.Linear(HID, HID) for _ in range(3)])
            self.out = nn.Linear(HID, NCLS)

        def forward(self, x, labels):
            h = F.relu(self.inp(x))
            for m in self.mids:
                h = F.relu(m(h))
            return F.cross_entropy(self.out(h), labels).mean()

    rng = np.random.RandomState(0)

    def run(bs, acc):
        paddle.seed(3)
        m = Wide()
        o = paddle.optimizer.AdamW(learning_rate=0.01,
                                   parameters=m.parameters())
        step = paddle.jit.TrainStep(m, o, accumulate_steps=acc)
        shape = (acc, bs) if acc > 1 else (bs,)
        x = rng.randn(*shape, DIN).astype("float32")
        y = rng.randint(0, NCLS, (*shape, 1)).astype("int64")
        step(paddle.to_tensor(x), paddle.to_tensor(y))
        return executable_memory_stats(next(iter(step._fast.values())))

    base = run(B, 1)
    if base is None:
        pytest.skip("backend exposes no memory_analysis()")
    accK = run(B, K)
    bigK = run(B * K, 1)

    ratio_acc = accK["total_bytes"] / base["total_bytes"]
    ratio_big = bigK["total_bytes"] / base["total_bytes"]
    # flat: the accumulated step stays within ~1.15x of one microbatch...
    assert ratio_acc <= 1.15, (ratio_acc, accK, base)
    # ...while the x4 batch measurably exceeds it
    assert ratio_big > ratio_acc * 1.5, (ratio_big, ratio_acc)


# ------------------------------------------------------------------- wiring


def test_gradient_merge_optimizer_is_thin_adapter():
    from paddle_tpu.distributed.fleet.meta_optimizer_wrappers import \
        GradientMergeOptimizer

    k = 2
    xs, ys = _micro(k)
    m1, o1 = _make()
    s1 = paddle.jit.TrainStep(m1, GradientMergeOptimizer(o1, k_steps=k,
                                                         avg=True))
    assert s1._acc_steps == k and s1._avg is True
    m2, o2 = _make()
    s2 = paddle.jit.TrainStep(m2, o2, accumulate_steps=k)
    sx, sy = _stacked(xs, ys)
    assert float(s1(sx, sy)) == float(s2(sx, sy))
    for (n1, p1), (n2, p2) in zip(m1.named_parameters(),
                                  m2.named_parameters()):
        np.testing.assert_array_equal(p1.numpy(), p2.numpy(), err_msg=n1)


def test_fleet_gradient_merge_strategy_configures_adapter():
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.meta_optimizer_wrappers import \
        GradientMergeOptimizer

    strategy = DistributedStrategy()
    strategy.gradient_merge = True
    strategy.gradient_merge_configs = {"k_steps": 4, "avg": False}
    model, opt = _make()
    merged = GradientMergeOptimizer(
        opt, k_steps=strategy.gradient_merge_configs["k_steps"],
        avg=strategy.gradient_merge_configs["avg"])
    step = paddle.jit.TrainStep(model, merged)
    assert step._acc_steps == 4 and step._avg is False


def test_device_loader_stacks_microbatches():
    rng = np.random.RandomState(0)
    batches = [(rng.randn(8, 4).astype("float32"),
                rng.randint(0, 3, (8, 1)).astype("int64"))
               for _ in range(5)]
    dl = DeviceLoader(batches, stack_batches=2)
    got = list(dl)
    assert len(dl) == 2 and len(got) == 2  # trailing partial group dropped
    assert got[0][0].shape == (2, 8, 4)
    assert got[0][1].shape == (2, 8, 1)
    np.testing.assert_array_equal(np.asarray(got[1][0])[0], batches[2][0])


def test_device_loader_stacking_composes_with_batch_sharding():
    """stack_batches must not steal batch_sharding's leading axis: the K
    (scan) axis stays replicated, the BATCH axis (now axis 1) shards."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.io import batch_sharding

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    rng = np.random.RandomState(0)
    batches = [(rng.randn(16, 4).astype("float32"),
                rng.randint(0, 3, (16, 1)).astype("int64"))
               for _ in range(4)]
    # K=4 does NOT divide the 8-device mesh: pre-fix this raised
    # "dimension 0 should be divisible by 8" from the producer thread
    dl = DeviceLoader(batches, stack_batches=4,
                      sharding=batch_sharding(mesh))
    (x, y), = list(dl)
    assert x.shape == (4, 16, 4)
    spec = x.sharding.spec
    assert tuple(spec)[:2] == (None, "data"), spec


def test_device_loader_stacking_rejects_unshiftable_sharding():
    """Sharding types whose axis semantics can't shift past the stacking
    axis fail loudly instead of silently sharding the K axis."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    # jax 0.9 dropped PositionalSharding; what is left of the position-only
    # family is GSPMDSharding (devices + an HLO tile assignment, no axis
    # names) — the form a NamedSharding degrades to, and just as unshiftable
    from jax._src.sharding_impls import GSPMDSharding

    named = NamedSharding(Mesh(np.array(jax.devices()[:8]), ("data",)),
                          P("data", None))
    positional = GSPMDSharding(named._device_assignment,
                               named._to_xla_hlo_sharding(2))
    rng = np.random.RandomState(0)
    batches = [(rng.randn(8, 4).astype("float32"),) for _ in range(4)]
    dl = DeviceLoader(batches, stack_batches=2, sharding=positional)
    with pytest.raises(ValueError, match="NamedSharding"):
        list(dl)


def test_train_step_rejects_unstacked_inputs_under_accumulation():
    """An unstacked batch must not be silently reinterpreted as shape[0]
    single-sample microbatches."""
    xs, ys = _micro(1, bs=32)
    model, opt = _make()
    step = paddle.jit.TrainStep(model, opt, accumulate_steps=4)
    with pytest.raises(ValueError, match="leading axis 4"):
        step(paddle.to_tensor(xs[0]), paddle.to_tensor(ys[0]))


def test_stack_microbatches_handles_nested_structures():
    a = {"x": np.ones((2, 3), np.float32), "y": [np.zeros(4)]}
    b = {"x": np.zeros((2, 3), np.float32), "y": [np.ones(4)]}
    out = stack_microbatches([a, b])
    assert out["x"].shape == (2, 2, 3)
    assert out["y"][0].shape == (2, 4)


# --------------------------------------------------------------------- hapi


class _Net(nn.Layer):
    def __init__(self, din=8, nclass=4):
        super().__init__()
        self.fc = nn.Linear(din, nclass)

    def forward(self, x):
        return self.fc(x)


def _hapi_data(n=32, din=8, nclass=4, seed=0):
    """paddle.io.Dataset of (x, y) samples — goes through DataLoader
    batching in Model.fit (a raw list would be treated as pre-batched)."""
    from paddle_tpu.io import Dataset

    class _DS(Dataset):
        def __init__(self):
            rng = np.random.RandomState(seed)
            self.X = rng.randn(n, din).astype("float32")
            self.Y = rng.randint(0, nclass, (n, 1)).astype("int64")

        def __getitem__(self, i):
            return self.X[i], self.Y[i]

        def __len__(self):
            return n

    return _DS()


def test_hapi_fit_accumulate_steps_runs_one_update_per_window():
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.callbacks import Callback

    class Spy(Callback):
        def __init__(self):
            super().__init__()
            self.steps = []

        def on_train_batch_end(self, step, logs=None):
            self.steps.append(step)

    paddle.seed(1)
    net = _Net()
    m = Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss(), accumulate_steps=2)
    assert m._jit_compile  # accumulation implies the compiled step
    spy = Spy()
    h = m.fit(_hapi_data(), batch_size=8, epochs=2, verbose=0, shuffle=False,
              callbacks=[spy])
    assert len(h) == 2 and np.isfinite(h[-1]["loss"])
    # 32 samples / bs 8 = 4 microbatches -> 2 accumulation windows per epoch
    assert spy.steps == [0, 1, 0, 1]
    assert m._train_step.num_compiles == 1
    assert m._train_step._acc_steps == 2


def test_hapi_train_batch_buffers_microbatches_until_update():
    from paddle_tpu.hapi import Model

    data = _hapi_data()
    X, Y = data.X, data.Y

    paddle.seed(1)
    net = _Net()
    m = Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss(), accumulate_steps=2)
    assert m.train_batch([X[:8]], [Y[:8]], update=False) is None
    loss = m.train_batch([X[8:16]], [Y[8:16]], update=True)
    assert np.isfinite(loss)

    # parity with the pre-stacked call on a fresh model
    paddle.seed(1)
    net2 = _Net()
    m2 = Model(net2)
    m2.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                    parameters=net2.parameters()),
               nn.CrossEntropyLoss(), accumulate_steps=2)
    loss2 = m2.train_batch([np.stack([X[:8], X[8:16]])],
                           [np.stack([Y[:8], Y[8:16]])], update=True)
    assert loss == loss2
    for (n1, p1), (n2, p2) in zip(net.named_parameters(),
                                  net2.named_parameters()):
        np.testing.assert_array_equal(p1.numpy(), p2.numpy(), err_msg=n1)


def test_hapi_train_batch_update_false_error_names_new_api():
    from paddle_tpu.hapi import Model

    paddle.seed(1)
    net = _Net()
    m = Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss(), jit_compile=True)
    x = np.zeros((4, 8), np.float32)
    y = np.zeros((4, 1), np.int64)
    with pytest.raises(ValueError, match="accumulate_steps"):
        m.train_batch([x], [y], update=False)


def test_hapi_fit_through_stacked_device_loader():
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import DataLoader

    paddle.seed(1)
    net = _Net()
    m = Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss(), accumulate_steps=2)
    inner = DataLoader(_hapi_data(), batch_size=8, shuffle=False)
    dl = DeviceLoader(inner, stack_batches=2)
    h = m.fit(dl, epochs=1, verbose=0)
    assert np.isfinite(h[-1]["loss"])
    assert m._train_step.num_compiles == 1


def test_hapi_fit_unstacked_equals_stacked_loader():
    """_StackedBatches (host stacking in fit) and DeviceLoader(stack_batches)
    drive the same compiled window — identical training trajectory."""
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import DataLoader

    def run(use_device_loader):
        paddle.seed(1)
        net = _Net()
        m = Model(net)
        m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters()),
                  nn.CrossEntropyLoss(), accumulate_steps=2)
        data = _hapi_data()
        if use_device_loader:
            loader = DeviceLoader(DataLoader(data, batch_size=8,
                                             shuffle=False), stack_batches=2)
            h = m.fit(loader, epochs=1, verbose=0)
        else:
            h = m.fit(data, batch_size=8, epochs=1, verbose=0, shuffle=False)
        return h[-1]["loss"], {n: p.numpy() for n, p in
                               net.named_parameters()}

    la, pa = run(False)
    lb, pb = run(True)
    assert la == pytest.approx(lb, rel=1e-6)
    for n in pa:
        np.testing.assert_array_equal(pa[n], pb[n], err_msg=n)
