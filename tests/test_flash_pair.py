"""Head-pair packed flash attention (kernels/pallas/flash_pair.py) vs an
fp32 oracle — fwd and fused dqkv backward, causal and bidirectional,
interpret mode (runs on CPU). The hardware-PRNG dropout test is
``@pytest.mark.tpu``: on the chip, ``chiprun -- tools/run_tpu_tests.sh``."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.pallas.flash_pair import flash_pair, \
    pair_layout_supported, pair_schedule


def _oracle(qkv, heads, d, causal):
    b, L, _ = qkv.shape
    q, k, v = (qkv[:, :, i * heads * d:(i + 1) * heads * d]
               .reshape(b, L, heads, d).transpose(0, 2, 1, 3)
               for i in range(3))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3).reshape(b, L, heads * d)


def _rand_qkv(b, L, heads, d, seed=0):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(b, L, 3 * heads * d) * 0.5, jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L", [256, 384, 512])
def test_pair_forward(causal, L):
    b, heads, d = 2, 4, 64
    qkv = _rand_qkv(b, L, heads, d)
    seed = jnp.asarray([0], jnp.int32)
    out = flash_pair(qkv, seed, heads, d, causal, 1.0 / math.sqrt(d),
                     256, 0.0, True)
    ref = _oracle(qkv, heads, d, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_pair_backward_dqkv(causal, d):
    b, L, heads = 2, 256, 4
    qkv = _rand_qkv(b, L, heads, d, seed=1)
    seed = jnp.asarray([0], jnp.int32)

    def f_pair(x):
        return (flash_pair(x, seed, heads, d, causal, 1.0 / math.sqrt(d),
                           128, 0.0, True) ** 2).sum()

    def f_ref(x):
        return (_oracle(x, heads, d, causal) ** 2).sum()

    g_pair = jax.grad(f_pair)(qkv)
    g_ref = jax.grad(f_ref)(qkv)
    # tolerance covers BOTH interpret mode (exact fp32) and real-TPU runs via
    # tools/run_tpu_tests.sh, where fp32 matmuls ride bf16 MXU passes
    # (measured max grad diff ~0.01 at these shapes); real bugs are O(1)
    np.testing.assert_allclose(np.asarray(g_pair), np.asarray(g_ref),
                               rtol=1e-2, atol=2e-2)


def test_pair_gate():
    assert pair_layout_supported(64, 12, 512)
    assert pair_layout_supported(64, 16, 1024)
    assert pair_layout_supported(128, 8, 1024)       # hpb=1 (fused-bwd form)
    assert pair_layout_supported(64, 12, 2048)       # round 5: multi-tile
    assert pair_layout_supported(64, 12, 8192)       # any length now
    assert not pair_layout_supported(64, 13, 512)    # odd heads
    assert not pair_layout_supported(80, 12, 512)    # block not lane-aligned


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L", [2048, 4096])
def test_pair_forward_long(causal, L):
    """Multi-tile online softmax: KV spans several tiles (block_k=1024)."""
    b, heads, d = 1, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=4)
    seed = jnp.asarray([0], jnp.int32)
    out = flash_pair(qkv, seed, heads, d, causal, 1.0 / math.sqrt(d),
                     512, 0.0, True)
    ref = _oracle(qkv, heads, d, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L", [2048, 4096])
def test_pair_backward_long_fused(causal, L):
    """Several kv tiles through the FUSED multi-tile backward (4096 takes
    the reduced 256/512 tile shape that fits the VMEM budget)."""
    b, heads, d = 1, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=5)
    seed = jnp.asarray([0], jnp.int32)

    def f_pair(x):
        return (flash_pair(x, seed, heads, d, causal, 1.0 / math.sqrt(d),
                           512, 0.0, True) ** 2).sum()

    def f_ref(x):
        return (_oracle(x, heads, d, causal) ** 2).sum()

    g_pair = jax.grad(f_pair)(qkv)
    g_ref = jax.grad(f_ref)(qkv)
    np.testing.assert_allclose(np.asarray(g_pair), np.asarray(g_ref),
                               rtol=1e-2, atol=2e-2)


@pytest.mark.parametrize("L", [1900, 1920])
def test_pair_diagonal_meets_the_tail(L):
    """A length that is no multiple of the 256-row sub-tile: 1,920 walks 15
    sub-tiles of 128, and 1,900 pads 20 columns into the last of them, where
    the last q tile's diagonal crosses too: the masked body carries both."""
    b, heads, d = 1, 2, 64
    assert pair_schedule(L, True, 2, d)["sub_k"] == 128
    qkv = _rand_qkv(b, L, heads, d, seed=9)
    seed = jnp.asarray([0], jnp.int32)

    def f_pair(x):
        return flash_pair(x, seed, heads, d, True, 1.0 / math.sqrt(d),
                          512, 0.0, True)

    out, vjp = jax.vjp(f_pair, qkv)
    ref, vjp_ref = jax.vjp(lambda x: _oracle(x, heads, d, True), qkv)
    # exact float32 on the CPU; under tools/run_tpu_tests.sh the interpreted
    # kernel's and the oracle's float32 products ride bf16 MXU passes, and
    # fifteen rescaled sub-tiles of them miss 2e-3 (PR 32's first chip run)
    loose = 1 if jax.default_backend() == "cpu" else 5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3 * loose, atol=2e-3 * loose)
    w = _rand_qkv(b, L, heads, d, seed=10)[..., :heads * d]
    np.testing.assert_allclose(np.asarray(vjp(w)[0]),
                               np.asarray(vjp_ref(w)[0]),
                               rtol=1e-2, atol=2e-2 * loose)


def _pieces(L, causal, sched):
    """(with any visible entry, of those not wholly visible) among the
    schedule's tiles of the padded square, entry by entry."""
    bq, sk = (sched[k] // sched["split"] for k in ("block_q", "sub_k"))
    pad = -(-L // 128) * 128
    rows, cols = np.arange(pad)[:, None], np.arange(pad)[None, :]
    valid = np.broadcast_to(cols < L, (pad, pad))
    if causal:
        valid = valid & (rows >= cols)
    live = partial = 0
    for r in range(0, pad, bq):
        for c in range(0, pad, sk):
            piece = valid[r:r + bq, c:c + sk]
            live += bool(piece.any())
            partial += bool(piece.any() and not piece.all())
    return live, partial


@pytest.mark.parametrize("d", [64, 128])            # hpb 2 and 1
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L", [384, 1900, 2048, 4096])
def test_pair_schedule_walks_only_what_is_visible(L, causal, d):
    """The loop bounds the kernels run by, evaluated on ints: every piece
    with a visible entry is walked and no other, and the masked body runs
    on exactly the pieces the diagonal or the padded tail touches."""
    sched = pair_schedule(L, causal, max(1, 128 // d), d)
    live, partial = _pieces(L, causal, sched)
    assert sched["tiles_run"] == sched["tiles_min"] == live
    # a slab is masked whole: a split diagonal piece masks 3 tiles for the
    # 2 the diagonal crosses
    assert partial <= sched["tiles_masked"] <= partial * (
        sched["split"] + 1) // 2
    assert sched["block_k"] % sched["sub_k"] == 0
    if not causal:
        assert sched["tiles_run"] == sched["tiles_square"]
        assert (sched["tiles_masked"] == 0) == (L % 128 == 0)
    elif L >= 2048:
        # the triangle, not three quarters of the square
        assert sched["tiles_run"] / sched["tiles_square"] <= 0.57


def test_pair_schedule_names_the_backward_form():
    assert pair_schedule(2048, True, 2, 64, "bwd")["form"] == "fused"
    assert pair_schedule(4096, True, 2, 64, "bwd")["form"] == "fused"
    assert pair_schedule(8192, True, 2, 64, "bwd")["form"] == "split"
    assert pair_schedule(2048, True, 2, 64, "bwd",
                         max_fused_bwd=1024)["form"] == "split"
    assert pair_schedule(8192, True, 2, 64, "fwd")["form"] == "fused"


def test_a_trace_leaves_its_schedule_on_the_span_layer():
    """The schedule is static per traced signature, so one
    ``flash_pair/schedule`` span a trace of ``_pair_fwd`` / ``_pair_bwd``
    says how often it engages: same numbers as ``pair_schedule``."""
    import time
    import paddle_tpu.kernels.pallas.flash_pair as fp
    from paddle_tpu.monitor import trace
    b, L, heads, d = 1, 640, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=12)
    fp._pair_fwd.clear_cache()          # a cached trace records nothing
    fp._pair_bwd.clear_cache()
    t0 = time.perf_counter()
    jax.grad(lambda x: fp.flash_pair_packed(
        x, heads, True, interpret=True).sum())(qkv)
    got = trace.spans(t0, time.perf_counter(), "flash_pair/schedule")
    assert [s.attrs["kernel"] for s in got] == ["fwd", "bwd"]
    for s in got:
        assert s.attrs == pair_schedule(L, True, 2, d, s.attrs["kernel"])
    assert got[0].attrs["tiles_masked"] > 0


def test_fused_bwd_cutoff_scales_with_lane_width():
    """head_dim > 128 widens the per-row dk/dv scratch; the fused/split
    cutoff must shrink by the same factor so VMEM stays inside budget
    (ADVICE r5: d=256 at kv_pad=4096 would otherwise double to ~8MB)."""
    import paddle_tpu.kernels.pallas.flash_pair as fp
    assert fp._max_fused_bwd(2, 64) == 4096    # hpb*d == 128: round-5 budget
    assert fp._max_fused_bwd(1, 128) == 4096
    assert fp._max_fused_bwd(1, 256) == 2048   # twice the lanes, half the len
    assert fp._max_fused_bwd(1, 512) == 1024


def test_fused_bwd_cutoff_override_env_and_kwarg(monkeypatch):
    """The cutoff is a heuristic — chips with different VMEM headroom need
    the escape hatch: PADDLE_FLASH_FUSED_BWD_MAX env or the max_fused_bwd
    kwarg (kwarg wins)."""
    import paddle_tpu.kernels.pallas.flash_pair as fp
    monkeypatch.delenv("PADDLE_FLASH_FUSED_BWD_MAX", raising=False)
    assert fp._max_fused_bwd(2, 64) == 4096
    monkeypatch.setenv("PADDLE_FLASH_FUSED_BWD_MAX", "512")
    assert fp._max_fused_bwd(2, 64) == 512
    assert fp._max_fused_bwd(1, 256) == 512     # env overrides the scaling
    assert fp._max_fused_bwd(2, 64, 2048) == 2048   # kwarg beats env
    monkeypatch.setenv("PADDLE_FLASH_FUSED_BWD_MAX", "0")
    assert fp._max_fused_bwd(2, 64) == 0        # 0 forces the split form


def test_pair_backward_kwarg_forces_split():
    """max_fused_bwd= through the keyword front door routes L=1024 to the
    SPLIT backward (block_q=32 gives this signature its own jit entry, so
    no other test's cached trace can mask the kwarg)."""
    import paddle_tpu.kernels.pallas.flash_pair as fp
    b, L, heads, d = 1, 1024, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=11)

    def f_pair(x):
        return (fp.flash_pair_packed(x, heads, True, block_q=32,
                                     interpret=True,
                                     max_fused_bwd=512) ** 2).sum()

    def f_ref(x):
        return (_oracle(x, heads, d, True) ** 2).sum()

    g_pair = jax.grad(f_pair)(qkv)
    g_ref = jax.grad(f_ref)(qkv)
    np.testing.assert_allclose(np.asarray(g_pair), np.asarray(g_ref),
                               rtol=1e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_pair_backward_split(causal, monkeypatch):
    """The SPLIT two-kernel backward (kv_pad beyond the fused VMEM bound) —
    exercised by shrinking the bound so L=1024 takes the split path."""
    import paddle_tpu.kernels.pallas.flash_pair as fp
    # 512 * 128 lanes: _max_fused_bwd(hpb, d) == 512 at hpb*d == 128
    monkeypatch.setattr(fp, "_MAX_FUSED_BWD_LANE_BUDGET", 512 * 128)
    b, L, heads, d = 1, 1024, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=6)
    seed = jnp.asarray([0], jnp.int32)

    # block_q=64 is used by NO other test: _pair_bwd is jitted and reads
    # the fused-bwd budget at trace time, so a unique static signature guarantees
    # the patched bound is seen (and the poisoned cache entry it leaves
    # behind can never be hit by another signature)
    def f_pair(x):
        return (fp.flash_pair(x, seed, heads, d, causal, 1.0 / math.sqrt(d),
                              64, 0.0, True) ** 2).sum()

    def f_ref(x):
        return (_oracle(x, heads, d, causal) ** 2).sum()

    g_pair = jax.grad(f_pair)(qkv)
    g_ref = jax.grad(f_ref)(qkv)
    np.testing.assert_allclose(np.asarray(g_pair), np.asarray(g_ref),
                               rtol=1e-2, atol=2e-2)


@pytest.mark.tpu
def test_pair_dropout_fwd_bwd_mask_consistent():
    """The fused backward must regenerate the SAME dropout mask as the
    forward: check analytic grads against finite differences of the seeded
    kernel itself (a fwd/bwd mask desync fails this immediately)."""
    b, L, heads, d = 1, 256, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=3)
    seed = jnp.asarray([5], jnp.int32)

    def loss(x):
        o = flash_pair(x, seed, heads, d, False, 1.0 / math.sqrt(d),
                       128, 0.3, False)
        return (o.astype(jnp.float32) ** 2).sum()

    # determinism per seed
    l1, l2 = float(loss(qkv)), float(loss(qkv))
    assert l1 == l2
    g = jax.grad(loss)(qkv)
    rs = np.random.RandomState(0)
    # tolerance: TPU fp32 matmuls ride bf16 passes, so directional finite
    # differences carry a measured ~3-6% noise floor EVEN AT dropout=0 (where
    # interpret-mode tests prove grads exact); a fwd/bwd mask desync would
    # decorrelate the masks and show O(1) relative error — 15% separates the
    # two regimes decisively
    for _ in range(3):
        v = jnp.asarray(rs.randn(*qkv.shape).astype(np.float32))
        eps = 1e-2
        fd = (float(loss(qkv + eps * v)) - float(loss(qkv - eps * v))) / (2 * eps)
        an = float(jnp.vdot(g, v))
        assert abs(fd - an) <= 0.15 * max(abs(fd), abs(an), 1.0), (fd, an)


def test_functional_routes_pair_path():
    # the packed functional takes the pair path for d=64 (no crash; numerics
    # against the oracle in fp32/interpret are covered above — here we check
    # the plumbing end-to-end through the dispatcher on CPU fallback rules)
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    b, L, heads, d = 2, 256, 4, 64
    qkv = paddle.to_tensor(np.random.RandomState(2)
                           .randn(b, L, 3 * heads * d).astype("float32"))
    out = F.flash_attention_qkv_packed(qkv, heads, causal=True,
                                       training=False)
    # CPU: flash_path_available is False -> sdpa fallback; just verify shape
    assert list(out.shape) == [b, L, heads * d]


def test_packed_flash_maps_over_the_program_mesh(monkeypatch):
    """A multi-device program must be able to hold the kernel: the SPMD
    partitioner cannot split a Mosaic call by itself (the first ZeRO step on
    four real chips died of exactly that). When the tracer names the
    program's mesh, packed_flash shard_maps the kernel over the batch: same
    numbers as on one device, the batch is never gathered, and in-kernel
    dropout on a split batch refuses."""
    import functools
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.core import dispatch
    from paddle_tpu.kernels.pallas import flash_pair as fp
    from paddle_tpu.nn.functional.attention import packed_flash

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh (the real thing is "
                    "chip_smoke.py --chips 4)")
    monkeypatch.setattr(fp, "flash_pair_packed", functools.partial(
        fp.flash_pair_packed, interpret=True))     # no Mosaic on the CPU
    b, L, heads, d = 8, 256, 2, 64
    qkv = _rand_qkv(b, L, heads, d, seed=4)
    w = _rand_qkv(b, L, heads, d, seed=5)[..., :heads * d]
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(1, 8, 1),
                ("data", "sharding", "model"))

    def loss_and_grad(x, mesh=None, rate=0.0):
        dispatch.push_trace(dispatch.TraceContext(mesh=mesh))
        try:
            return jax.value_and_grad(lambda t: jnp.sum(
                packed_flash(t, heads, True, rate, 0) * w[:len(t)]))(x)
        finally:
            dispatch.pop_trace()

    on_mesh = jax.jit(functools.partial(loss_and_grad, mesh=mesh))
    sharded = jax.device_put(qkv, NamedSharding(mesh, P("sharding")))
    l1, g1 = jax.jit(loss_and_grad)(qkv)
    l8, g8 = on_mesh(sharded)
    assert g8.sharding.spec == P("sharding")
    # the scalar sums over shards in another order; the grads do not
    np.testing.assert_allclose(float(l8), float(l1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(g8), np.asarray(g1))
    hlo = on_mesh.lower(sharded).compile().as_text()
    assert "all-gather" not in hlo, "the batch was gathered to run the kernel"

    with pytest.raises(NotImplementedError, match="attention_dropout_prob"):
        jax.jit(functools.partial(loss_and_grad, mesh=mesh, rate=0.1))(sharded)
    # a batch the data-like axes do not divide runs whole on every device
    l6, _ = on_mesh(qkv[:6])
    l6_ref, _ = jax.jit(loss_and_grad)(qkv[:6])
    np.testing.assert_allclose(float(l6), float(l6_ref), rtol=1e-5)
