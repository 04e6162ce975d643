"""ZeRO-sharded compiled training (ISSUE 5 acceptance).

On the virtual 8-device CPU mesh:

* the fp32 grad accumulators inside the ``accumulate_steps=K`` executable are
  SHARD-sized under ZeRO-2: the in-scan reduce-scatter constrains each
  microbatch's grads to the shard sharding BEFORE the add, so the measured
  temp-bytes delta of the accumulated executable stays within 1.15x of the
  1/world_size ideal (the unsharded path pays the full-size accumulator);
* numerics are unchanged: stage-2 + accumulation matches the unsharded
  accumulation path for K in {1, 2, 4};
* still ONE executable per input-shape bucket, and repeated steps keep their
  placements stable (no compile churn from the update-then-all-gather);
* fp32 master weights and Adam moments are born shard-sized and STAY
  shard-sized across compiled steps, while the bf16 working params come back
  replicated (ZeRO's update-then-all-gather inside the same executable);
* ``grad_bucket_bytes`` fuses small grads into flat fused buckets (plan
  observable, parity preserved);
* ``monitor`` shard/* gauges expose accumulator/opt-state residency;
* ``amp.GradScaler`` found-inf reduces over shard-sized grads;
* ``io.batch_sharding`` auto-axis covers the "sharding" mesh axis and
  ``DeviceLoader(stack_batches=K)`` must not let the stacking axis absorb
  the batch-sharding axis.
"""
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu import monitor
from paddle_tpu.amp import GradScaler
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.io import DeviceLoader, batch_sharding


@pytest.fixture(autouse=True)
def _reset_env():
    # each test builds its own mesh/topology; monitor never leaks
    from paddle_tpu.distributed import env
    env._env["initialized"] = False
    env._env["mesh"] = None
    env._env["hcg"] = None
    from paddle_tpu.distributed import group
    group._group_registry.clear()
    monitor.disable()
    yield
    monitor.disable()


def _init_sharding_mesh(degree=8):
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": degree, "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)


class _WithLoss(nn.Layer):
    """Model that returns its own loss (TrainStep contract) with several
    differently-shaped params so bucketing/sharding sees a mix."""

    def __init__(self, din=16, hid=32):
        super().__init__()
        self.a = nn.Linear(din, hid)
        self.b = nn.Linear(hid, din)

    def forward(self, x):
        return ((self.b((self.a(x)) ** 2)) ** 2).mean()


def _make(level=None, din=16, hid=32, seed=0, bucket=None, **opt_kw):
    paddle.seed(seed)
    m = _WithLoss(din, hid)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=m.parameters(), **opt_kw)
    if level:
        m2, opt2, _ = dist.group_sharded_parallel(m, opt, level=level,
                                                  grad_bucket_bytes=bucket)
        return m, m2, opt2
    return m, m, opt


def _inputs(k, bs=4, din=16, seed=0):
    rng = np.random.RandomState(seed)
    shape = (k, bs, din) if k > 1 else (bs, din)
    return paddle.to_tensor(rng.randn(*shape).astype("float32"))


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("k", [1, 2, 4])
def test_zero_accum_parity_with_unsharded(k):
    """Moving the reduce-scatter into the scan body must not change the
    math: stage-2 + accumulate_steps=K trains identically to the unsharded
    accumulation path."""
    _init_sharding_mesh()
    losses = {}
    weights = {}
    for level in (None, "os_g"):
        m, m2, opt2 = _make(level)
        step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=k)
        ls = [float(step(_inputs(k, seed=s))) for s in range(3)]
        losses[level] = ls
        weights[level] = {n: np.asarray(p.value(), np.float32)
                          for n, p in m.named_parameters()}
    np.testing.assert_allclose(losses[None], losses["os_g"], rtol=1e-5)
    for n in weights[None]:
        np.testing.assert_allclose(weights[None][n], weights["os_g"][n],
                                   rtol=1e-4, atol=1e-6, err_msg=n)


# ------------------------------------------------------- shard-sized memory


def _scan_accumulated_f32_bytes(compiled) -> int:
    """Per-device bytes of the fp32 arrays the compiled scan UPDATES across
    microbatches, read from the post-partitioning HLO: the while body's ROOT
    tuple hands loop invariants (params, the input stack) through as
    get-tuple-element and produces what it accumulates with an op."""
    txt = compiled.as_text()
    body = re.search(r" while\(.*?body=(%[\w.\-]+)", txt).group(1)
    block = txt[txt.index("\n" + body + " ("):]
    root = next(l for l in block.splitlines()
                if l.lstrip().startswith("ROOT"))
    types, operands = root.split(") tuple(")
    shapes = re.findall(r"(\w+)\[([\d,]*)\]", types)
    operands = re.sub(r"/\*.*?\*/", "", operands).rstrip(")").split(",")
    assert len(shapes) == len(operands), root
    return sum(4 * int(np.prod([int(d) for d in dims.split(",") if d]))
               for (dt, dims), op in zip(shapes, operands)
               if dt == "f32" and "get-tuple-element" not in op)


def test_accumulator_shard_sized_measured():
    """THE acceptance gate: with stage-2 + accumulate_steps=4 the fp32
    accumulators XLA actually carries through the scan are <= 1.15x the
    1/world_size ideal per device, while the unsharded path carries them
    full-size.

    Measured from the scan's carried state in the compiled executable, not
    from ``memory_analysis()`` temp-bytes deltas (K=4 minus K=1) as before:
    XLA:CPU in jaxlib 0.9.0 reuses the K=1 gradient temporaries for the K>1
    microbatch gradients, so that delta is no longer the accumulator — it
    reads 77,332 B for a 132,352 B full-size accumulator, and 49,052 B where
    the carried accumulators are exactly the 16,544 B ideal."""
    _init_sharding_mesh()
    DIN, HID, K = 64, 256, 4

    def run(level):
        m, m2, opt2 = _make(level, din=DIN, hid=HID)
        step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=K)
        step(_inputs(K, din=DIN))
        return step, _scan_accumulated_f32_bytes(
            next(iter(step._fast.values())))

    stepK, carried_sharded = run("os_g")
    _, carried_unsharded = run(None)

    full = stepK._full_grad_bytes()
    ideal = -(-full // 8)  # ceil: per-param sharding rounds up
    # the unsharded accumulator really is full-size (sanity: the comparison
    # below means something)
    assert carried_unsharded >= 0.9 * full, (carried_unsharded, full)
    # ...and the sharded one is genuinely 1/world-sized
    assert carried_sharded <= 1.15 * ideal, (carried_sharded, ideal, full)
    # analytic accounting agrees with the plan
    assert stepK._grad_acc_bytes() == ideal


def test_one_compile_per_bucket_and_stable_placements():
    """Repeated ZeRO-2 accumulated steps reuse ONE executable: the
    update-then-all-gather pins outputs to input placements, so step N's
    outputs feed step N+1 without a recompile."""
    _init_sharding_mesh()
    monitor.enable(None)
    m, m2, opt2 = _make("os_g")
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
    x = _inputs(4)
    for _ in range(3):
        step(x)
    assert step.num_compiles == 1
    assert monitor.counter("train_step/recompiles").value == 1


# ------------------------------------------------------------------- gauges


def test_shard_gauges_report_shard_sized_accumulators():
    _init_sharding_mesh()
    monitor.enable(None)
    m, m2, opt2 = _make("os_g")
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
    step(_inputs(4))

    assert monitor.gauge("shard/world_size").value == 8
    accum = monitor.gauge("shard/accum_bytes").value
    ideal = monitor.gauge("shard/accum_ideal_bytes").value
    full = step._full_grad_bytes()
    assert ideal == -(-full // 8)
    assert 0 < accum <= 1.15 * ideal  # tools/metrics_summary.py's regression flag
    assert monitor.gauge("shard/grad_buckets").value == 0  # bucketing is opt-in
    # moments (2x fp32) + masterless fp32 params: shard-sized, not replicated
    opt_bytes = monitor.gauge("shard/opt_state_bytes").value
    full_state = 2 * full
    assert 0 < opt_bytes < full_state / 2, (opt_bytes, full_state)
    # the grad-accumulator gauge reflects the SHARD size too
    assert monitor.gauge("train_step/grad_accumulator_bytes").value == ideal


def test_stage1_full_size_accumulator_is_not_flagged(tmp_path):
    """Stage "os" accumulators are LEGITIMATELY full-size (grads replicated
    by design): the ideal gauge must stay 0 so metrics_summary never fires
    its lost-constraint WARNING on a healthy documented config."""
    _init_sharding_mesh()
    path = tmp_path / "os.jsonl"
    monitor.enable(str(path))
    m, m2, opt2 = _make("os")
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
    step(_inputs(4))
    assert monitor.gauge("shard/accum_ideal_bytes").value == 0
    assert monitor.gauge("shard/accum_bytes").value == \
        step._full_grad_bytes()
    monitor.disable()
    out = _summarize([path])
    assert "zero sharding" in out and "WARNING" not in out


def test_shard_elems_uses_true_shard_shape():
    """Per-device residency math must be per-DIM ceil (the real shard
    shape), not ceil of the flattened size — the latter under-counts
    non-divisible dims and can mask over-ideal accumulator bloat."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from paddle_tpu.jit.train_step import _shard_elems

    mesh = Mesh(np.array(jax.devices()[:8]), ("sharding",))
    sh = NamedSharding(mesh, PartitionSpec("sharding", None))
    # ceil(10/8)*7 = 14 per device, NOT ceil(70/8) = 9
    assert _shard_elems((10, 7), sh) == 14
    assert _shard_elems((16, 4), sh) == 8
    assert _shard_elems((4,), None) == 4


# ----------------------------------------------------------------- buckets


def test_grad_bucket_bytes_fuses_small_grads():
    """An explicit grad_bucket_bytes coalesces eligible small grads into
    flat fused buckets (fewer collectives) without changing the numerics or
    the shard-sized accounting."""
    _init_sharding_mesh()

    def run(bucket):
        m, m2, opt2 = _make("os_g", bucket=bucket)
        step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
        losses = [float(step(_inputs(4, seed=s))) for s in range(2)]
        w = {n: np.asarray(p.value(), np.float32)
             for n, p in m.named_parameters()}
        return step, losses, w

    step_b, losses_b, w_b = run(1 << 20)
    plan = step_b._accum_plan
    assert plan is not None and plan.num_buckets >= 1
    # flat buckets pad to a multiple of world_size; accounting stays ~ideal
    ideal = -(-step_b._full_grad_bytes() // 8)
    assert step_b._grad_acc_bytes() <= ideal + 4 * 8 * plan.num_buckets

    step_p, losses_p, w_p = run(None)
    assert step_p._accum_plan.num_buckets == 0
    np.testing.assert_allclose(losses_b, losses_p, rtol=1e-5)
    for n in w_p:
        np.testing.assert_allclose(w_b[n], w_p[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)


# ------------------------------------------- shard-sized optimizer state


def test_masters_and_moments_stay_shard_sized_params_replicated():
    """ZeRO end-to-end state contract under the compiled step: fp32 masters
    and Adam moments live shard-sized across steps; the bf16 working params
    the model computes with come back REPLICATED (the all-gather happens
    inside the executable, after the shard-sized update)."""
    _init_sharding_mesh()
    paddle.seed(0)
    m = _WithLoss().bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=m.parameters(),
                                 multi_precision=True)
    m2, opt2, _ = dist.group_sharded_parallel(m, opt, level="os_g")
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=2)
    x = _inputs(2)
    for _ in range(2):
        step(x)

    inner = opt2._inner_opt
    world = 8

    def shard_axes(arr):
        spec = getattr(arr.sharding, "spec", ())
        return {a for s in tuple(spec) if s is not None
                for a in (s if isinstance(s, tuple) else (s,))}

    for p in inner._parameter_list:
        # the working param: bf16, mesh-placed, NOT sharded
        assert p.value().dtype == jax.numpy.bfloat16.dtype
        assert shard_axes(p.value()) == set(), p.name
        # master: fp32, shard-sized (per-device shard is 1/world of it)
        mw = inner._master_weights[id(p)]
        assert mw.dtype == np.float32
        assert "sharding" in shard_axes(mw), p.name
        shard = mw.sharding.shard_shape(mw.shape)
        assert np.prod(shard) * world == np.prod(mw.shape), (shard, mw.shape)
        # moments: shard-sized the same way
        for name, arr in inner._accumulators[id(p)].items():
            assert "sharding" in shard_axes(arr), (p.name, name)

    # placement stability: the second step hit the same executable
    assert step.num_compiles == 1
    # and the numbers still go down
    l0, l1 = float(step(x)), float(step(x))
    assert np.isfinite(l1) and l1 <= l0


# --------------------------------------------------------------------- amp


def test_gradscaler_found_inf_over_sharded_grads():
    """The compiled found-inf reduction runs over SHARD-sized grads; an inf
    microbatch anywhere in the window must still skip the whole update and
    shrink the scale exactly like the eager scaler."""
    _init_sharding_mesh()
    m, m2, opt2 = _make("os_g")
    sc = GradScaler(init_loss_scaling=1024.0)
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=2, grad_scaler=sc)

    step(_inputs(2))  # clean window
    assert sc._scale == 1024.0

    before = {n: np.asarray(p.value(), np.float32)
              for n, p in m.named_parameters()}
    bad = np.asarray(_inputs(2).value()).copy()
    bad[1] = np.inf
    step(paddle.to_tensor(bad))
    for n, p in m.named_parameters():
        np.testing.assert_array_equal(before[n],
                                      np.asarray(p.value(), np.float32),
                                      err_msg=n)
    assert sc._scale == 512.0
    assert step.num_compiles == 1


# ---------------------------------------------------------- wiring knobs


def test_fleet_strategy_stage2_wires_bucket_knob():
    from paddle_tpu.distributed.sharding.group_sharded import \
        _ShardingStage2Optimizer

    _init_sharding_mesh()
    strategy = DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 2, "grad_bucket_bytes": 4096}
    paddle.seed(0)
    m = _WithLoss()
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-2,
                               parameters=m.parameters()), strategy)
    assert isinstance(opt, _ShardingStage2Optimizer)
    assert opt._grad_bucket_bytes == 4096
    # TrainStep adopts the wrapper's knob when not overridden
    step = paddle.jit.TrainStep(m, opt, accumulate_steps=2)
    assert step._grad_bucket_bytes == 4096


def test_optimizer_states_born_sharded_before_any_placement_pass():
    """The placement hook installs at WRAPPER CONSTRUCTION: the very first
    materialization of a moment buffer (before any step/_place_states call)
    already lands shard-sized — no transient full-size replicated buffer,
    which for billion-param models is the allocation ZeRO exists to avoid."""
    _init_sharding_mesh()
    m, m2, opt2 = _make("os_g")
    inner = opt2._inner_opt
    p = next(p for p in inner._parameter_list if p.ndim == 2)
    st = inner._ensure_state(p)  # first creation, no _place_states yet
    for name, arr in st.items():
        spec = str(arr.sharding.spec)
        assert "sharding" in spec, (name, spec)


def test_placement_hook_reaches_raw_opt_through_stacked_wrappers():
    """Intermediate wrappers (GradientMergeOptimizer etc.) delegate reads
    but not writes — the hook must land on the RAW Optimizer whose
    _ensure_state consults it."""
    from paddle_tpu.distributed.fleet.meta_optimizer_wrappers import \
        GradientMergeOptimizer
    from paddle_tpu.distributed.fleet.meta_optimizers import \
        DygraphShardingOptimizer

    _init_sharding_mesh()
    paddle.seed(0)
    m = _WithLoss()
    raw = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=m.parameters())
    stacked = DygraphShardingOptimizer(GradientMergeOptimizer(raw, k_steps=2))
    assert raw._state_placement_fn is not None
    p = next(p for p in raw._parameter_list if p.ndim == 2)
    st = raw._ensure_state(p)
    assert "sharding" in str(st["moment1"].sharding.spec)
    assert stacked is not None


def test_fleet_strategy_stage2_marks_eager_tape():
    """sharding_configs stage>=2 wraps only the OPTIMIZER — the stage-2
    contract (grads shard at tape accumulation, never sitting replicated
    between backward and step) must still reach the params."""
    _init_sharding_mesh()
    strategy = DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 2}
    paddle.seed(0)
    m = _WithLoss()
    fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-2,
                               parameters=m.parameters()), strategy)
    for name, p in m.named_parameters():
        sh = getattr(p, "_grad_sharding", None)
        assert sh is not None and "sharding" in str(sh.spec), name


def test_hapi_prepare_passes_grad_bucket_bytes_through():
    from paddle_tpu.hapi import Model

    _init_sharding_mesh()
    paddle.seed(0)
    net = _WithLoss()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    _, opt2, _ = dist.group_sharded_parallel(net, opt, level="os_g")
    m = Model(net)
    m.prepare(opt2, jit_compile=True, accumulate_steps=2,
              grad_bucket_bytes=2048)
    assert m._grad_bucket_bytes == 2048
    assert m._ensure_train_step(0)._grad_bucket_bytes == 2048


# ----------------------------------------------------------------- tooling


def _summarize(paths):
    import io as _io
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import metrics_summary
    finally:
        sys.path.pop(0)
    buf = _io.StringIO()
    metrics_summary.summarize([str(p) for p in paths], out=buf)
    return buf.getvalue()


def test_metrics_summary_reports_shard_gauges(tmp_path):
    """A healthy ZeRO run gets a 'zero sharding' section (accumulator at
    ~the 1/world ideal) and NO lost-constraint warning."""
    _init_sharding_mesh()
    path = tmp_path / "run.jsonl"
    monitor.enable(str(path))
    m, m2, opt2 = _make("os_g")
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
    step(_inputs(4))
    monitor.disable()

    out = _summarize([path])
    assert "zero sharding" in out
    assert "world 8" in out
    assert "shard ideal" in out
    assert "WARNING" not in out


def test_metrics_summary_flags_full_size_accumulator(tmp_path):
    """An accumulator that is NOT 1/world_size-sized is the signature of the
    reduce-scatter falling out of the accumulation scan — the summary must
    flag it as a probable lost sharding constraint."""
    import json

    path = tmp_path / "bad.jsonl"
    snap = {"counters": {}, "histograms": {},
            "gauges": {"shard/world_size": 8,
                       "shard/accum_bytes": 132352,       # full size again
                       "shard/accum_ideal_bytes": 16544,
                       "shard/opt_state_bytes": 33088,
                       "shard/grad_buckets": 0}}
    with open(path, "w") as f:
        f.write(json.dumps({"v": 1, "ts": 0.0, "kind": "meta", "proc": 0,
                            "pid": 1, "schema": 1, "start": 0.0}) + "\n")
        f.write(json.dumps({"v": 1, "ts": 1.0, "kind": "counters",
                            "metrics": snap}) + "\n")

    out = _summarize([path])
    assert "WARNING" in out and "lost sharding constraint" in out
    assert "8.00x" in out


# ------------------------------------------------- io: inputs on the mesh


def test_batch_sharding_auto_axis_picks_sharding():
    """A ZeRO sharding group IS a data-parallel group: with only the
    "sharding" mesh axis populated, batch_sharding shards inputs over it by
    default."""
    _init_sharding_mesh()
    from paddle_tpu.distributed.env import get_mesh
    fn = batch_sharding(get_mesh())
    spec = fn(np.zeros((16, 4), np.float32)).spec
    assert tuple(spec)[0] == "sharding", spec


def test_batch_sharding_auto_axis_composes_data_and_sharding():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("data", "sharding"))
    fn = batch_sharding(mesh)
    spec = fn(np.zeros((16, 4), np.float32)).spec
    assert tuple(spec)[0] == ("data", "sharding"), spec
    # explicit override still wins
    spec = batch_sharding(mesh, "data")(np.zeros((16, 4), np.float32)).spec
    assert tuple(spec)[0] == "data", spec


def test_stacked_loader_keeps_batch_axis_sharded_on_zero_mesh():
    """DeviceLoader(stack_batches=K) + batch_sharding on the ZeRO mesh: the
    NEW K (scan) axis must stay replicated and the batch axis (now axis 1)
    keeps the "sharding" placement — the stacking axis must not absorb it."""
    _init_sharding_mesh()
    from paddle_tpu.distributed.env import get_mesh
    mesh = get_mesh()
    rng = np.random.RandomState(0)
    batches = [(rng.randn(16, 4).astype("float32"),
                rng.randint(0, 3, (16, 1)).astype("int64"))
               for _ in range(4)]
    dl = DeviceLoader(batches, stack_batches=4, sharding=batch_sharding(mesh))
    (x, y), = list(dl)
    assert x.shape == (4, 16, 4) and y.shape == (4, 16, 1)
    for arr in (x, y):
        spec = tuple(arr.sharding.spec)
        assert spec[0] is None, spec          # K axis replicated
        assert spec[1] == "sharding", spec    # batch axis sharded
    # and the stacked window feeds the ZeRO-2 accumulated step directly
    m, m2, opt2 = _make("os_g", din=4)
    step = paddle.jit.TrainStep(m2, opt2, accumulate_steps=4)
    assert np.isfinite(float(step(x)))
