"""The plain reference against `models/gpt.py` at `gpt_tiny`, float32 on
the CPU: same weights, same ids, logits, loss and gradients agree to
rounding. (On the chip the comparison is the one that decides
`correct`.)"""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def both():
    import jax.numpy as jnp
    from benchmark import system
    from benchmark.families import gpt as fam
    model = {"vocab_size": 256, "hidden_size": 64, "num_layers": 2,
             "num_heads": 4, "max_position_embeddings": 128}
    cfg = {"model": model, "dtype": "float32"}
    arrays = fam.make(model, 2**31 + 3, "float32")
    prog = system.build_model(fam, cfg, arrays)
    prog.eval()
    ids = np.random.default_rng(0).integers(0, 256, (2, 48)).astype("int32")
    return model, arrays, prog, ids, jnp


@pytest.mark.parametrize("name", ["gpt", "common"])
def test_import_nothing_of_the_program(name):
    """A reference file imports jax and the standard library, and of the
    benchmark only what its own directory shares (`common.py`)."""
    import ast
    import importlib
    import os
    ref = importlib.import_module("benchmark.reference." + name)
    tree = ast.parse(open(ref.__file__).read())
    froms = [n for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods = [n.module or "" for n in froms] + \
        [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
         for a in n.names]
    assert not [m for m in mods if "paddle" in m or "benchmark" in m]
    assert all((n.level, n.module) == (1, "common") for n in froms
               if n.level)
    assert os.path.basename(os.path.dirname(ref.__file__)) == "reference"


def test_logits_agree(both):
    import paddle_tpu as paddle
    from benchmark.reference import gpt as ref
    model, arrays, prog, ids, jnp = both
    with paddle.no_grad():
        got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert got.shape == want.shape == (2, 48, 256)
    assert np.max(np.abs(got - want)) < 2e-5 * max(1.0, np.abs(want).max())


def test_loss_and_gradients_agree(both):
    import paddle_tpu as paddle
    from benchmark.families import gpt as fam
    from benchmark.reference import common, gpt as ref
    model, arrays, prog, ids, jnp = both
    prog.train()
    t = paddle.to_tensor(ids)
    _, loss = prog(t, labels=t)
    loss.backward()
    want_loss, want = ref.loss_and_grads(
        {k: v.astype(jnp.float32) for k, v in arrays.items()},
        jnp.asarray(ids), model)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    lm = fam.leaf_map(model)
    worst = 0.0
    for name, p in prog.named_parameters():
        key, layer = lm[name]
        w = np.asarray(want[key] if layer is None else want[key][layer])
        g = np.asarray(p.grad.value())
        worst = max(worst, np.abs(g - w).max() / max(np.abs(w).max(), 1e-6))
    assert worst < 2e-4
    norms = common.leaf_norms(want, ref.LAYER_KEYS, fam.FUSED)
    assert norms["qkv_w"].shape == (2,) and norms["wte"].shape == ()
    # a key's bias has no gradient under softmax: its third is ~0
    assert float(norms["qkv_b.k"].max()) < 1e-3 * float(norms["qkv_b.q"].min())


def test_adamw_step_by_hand():
    import jax.numpy as jnp
    from benchmark.reference import common as ref
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, -0.25])}
    z = {"w": jnp.zeros(2)}
    p2, m, v = ref.adamw_step(p, z, z, g, 1.0, 0.1, 0.9, 0.95, 1e-8, 0.1)
    # first step: m_hat / sqrt(v_hat) = sign(g); plus lr * wd * p
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               [1.0 - 0.1 - 0.01, -2.0 + 0.1 + 0.02],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m["w"]), [0.05, -0.025], rtol=1e-6)


def test_fp8_control_rounds_products_only():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import common as ref
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    hi = ref.hi_dot("ij,jk->ik", a, a)
    lo = ref.fp8_dot("ij,jk->ik", a, a)
    rel = float(jnp.linalg.norm(lo - hi) / jnp.linalg.norm(hi))
    assert 0.01 < rel < 0.1                  # 3 mantissa bits
    g = jax.grad(lambda x: jnp.sum(ref.fp8_dot("ij,jk->ik", x, a)))(a)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0
