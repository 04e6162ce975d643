"""The parallel hybrid decoder `models/falcon_h1.py` (a Mamba-2 mixer and a
grouped-query attention mixer side by side in every block, muP multipliers)
at a tiny size on the CPU, float32, seeded weights: the three forms of
`kernels/pallas/ssd.py` against each other, the model against the
benchmark's independent reference (`benchmark/reference/falcon_h1.py`),
every multiplier and every term of the mixer shown to matter, and the
vocabulary slices against the uncut model. The serving engine's side is
`test_falcon_h1_serving.py`, which shares this file's helpers."""
import dataclasses
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.kernels.pallas import ssd                      # noqa: E402

# the program's own tiny preset as the benchmark's `model` dict: two blocks;
# 4 mixer heads of 8 in 2 groups, state 16; 5 query heads on 1 KV head of
# 16; every multiplier away from 1
from paddle_tpu.models.falcon_h1 import falcon_h1_tiny         # noqa: E402

MODEL = {k: v for k, v in dataclasses.asdict(falcon_h1_tiny()).items()
         if k not in ("dtype", "initializer_range")}
CHUNK = 16                  # the engine tests' prefill chunk
SEED = 2**31 + 5


def family():
    from benchmark.families import falcon_h1 as fam
    from benchmark.reference import falcon_h1 as ref
    return fam, ref


def program(seed=SEED, arrays=None, model=MODEL, **program_over):
    """(model object, the benchmark's arrays it was given, model dict).
    `program_over` changes the PROGRAM's configuration only: the arrays and
    the returned dict stay `model`'s, which is what the reference is given.
    A leaf the changed program does not have is left out."""
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM)
    fam, _ = family()
    if arrays is None:
        arrays = fam.make(model, seed, "float32")
    prog = FalconH1ForCausalLM(FalconH1Config(
        dtype="float32", **dict(model, **program_over)))
    lm = fam.leaf_map(model)
    for name, p in prog.named_parameters():
        p._data = arrays[lm[name][0]]
    prog.eval()
    return prog, arrays, model


@pytest.fixture(scope="module")
def tiny():
    return program()


# ------------------------------------------------------------- kernels

def ssd_case(b, s, h=4, p=8, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    dt = jnp.log1p(jnp.exp(f(b, s, h) - 1.0))
    return (f(b, s, h, p), dt, -jnp.exp(0.3 * f(h)), f(b, s, g, n),
            f(b, s, g, n), f(h), f(b, h, n, p))


@pytest.mark.parametrize("s", [1, 3, 7, 8, 9, 16, 37])
def test_chunked_scan_is_the_sequential_recurrence(s):
    """A state comes in (not zero) and one goes out; lengths that are not
    a whole number of chunks of 8."""
    args = ssd_case(2, s, seed=s)
    y1, s1 = ssd.ssd_recurrent(*args)
    y2, s2 = ssd.ssd_chunked(*args, chunk=8)
    np.testing.assert_allclose(y2, y1, rtol=0, atol=2e-5)
    np.testing.assert_allclose(s2, s1, rtol=0, atol=2e-5)


def test_switched_off_positions_leave_the_state_alone():
    """dt = 0 on a padded tail: the state after the run is the state after
    the valid positions, in both forms."""
    x, dt, a, b, c, d, state = ssd_case(1, 40, seed=7)
    dt2 = jnp.where((jnp.arange(40) < 23)[None, :, None], dt, 0.0)
    _, want = ssd.ssd_recurrent(x[:, :23], dt[:, :23], a, b[:, :23],
                                c[:, :23], d, state)
    for form in (ssd.ssd_recurrent, ssd.ssd_chunked):
        _, got = form(x, dt2, a, b, c, d, state)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("h,p,g,n", [(4, 8, 2, 16), (16, 128, 2, 128)])
def test_decode_kernel_is_one_step_of_the_recurrence(h, p, g, n):
    x, dt, a, b, c, d, state = ssd_case(3, 1, h=h, p=p, g=g, n=n, seed=h)
    live = jnp.asarray([True, False, True])
    y_want, s_want = ssd.ssd_recurrent(x, dt, a, b, c, d, state)
    step = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, state, live)
    with ssd.force_interpret():
        y, new = ssd.ssd_decode_step(*step)
    for slot in (0, 2):
        np.testing.assert_allclose(y[slot], y_want[slot, 0], rtol=0,
                                   atol=2e-5)
        np.testing.assert_allclose(new[slot], s_want[slot], rtol=0,
                                   atol=2e-6)
    # a slot that is not live gets its state back bit for bit
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))
    # and so does the plain form
    y2, plain = ssd.ssd_decode_step(*step)
    assert np.array_equal(np.asarray(plain[1]), np.asarray(state[1]))
    np.testing.assert_allclose(plain[0], new[0], rtol=0, atol=2e-6)
    np.testing.assert_allclose(y2[0], y[0], rtol=0, atol=2e-5)


def test_a_forgotten_slot_steps_from_zero_in_both_decode_forms():
    """Whatever the slot's last tenant left: a NaN and an infinity too."""
    x, dt, a, b, c, d, state = ssd_case(2, 1, seed=3)
    live, forget = jnp.asarray([True, True]), jnp.asarray([True, False])
    y_want, s_want = ssd.ssd_recurrent(x, dt, a, b, c, d,
                                       state.at[0].set(0.0))
    state = state.at[0, 0, 0, 0].set(jnp.nan).at[0, 1, 2, 3].set(jnp.inf)
    step = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, state, live)
    plain = ssd.ssd_decode_step(*step, forget=forget)
    with ssd.force_interpret():
        kernel = ssd.ssd_decode_step(*step, forget=forget)
    for y, new in (plain, kernel):
        np.testing.assert_allclose(y, y_want[:, 0], rtol=0, atol=2e-5)
        np.testing.assert_allclose(new, s_want, rtol=0, atol=2e-6)


# ------------------------------------- the model against the reference

IDS = np.random.default_rng(1).integers(0, 512, (2, 45)).astype("int32")
TOL = 2e-5


def gap(prog, arrays, model, ids=IDS):
    """Largest |program - reference| logit over the reference's scale."""
    _, ref = family()
    with paddle.no_grad():
        got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert got.shape == want.shape == ids.shape + (model["vocab_size"],)
    return np.max(np.abs(got - want)) / max(1.0, np.abs(want).max())


def test_full_forward_agrees_with_the_plain_reference(tiny):
    assert gap(*tiny) < TOL


def _one(key, at=None):
    """The configuration with one multiplier set to 1."""
    if at is None:
        return {key: 1.0}
    value = list(MODEL[key])
    value[at] = 1.0
    return {key: value}


def _no_skip(prog):
    for block in prog.model.layers:
        block.mamba.D._data = jnp.zeros_like(block.mamba.D._data)


FAULTS = {
    **{k: _one(k) for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")},
    **{f"ssm_multipliers[{i}]": _one("ssm_multipliers", i)
       for i in range(5)},
    **{f"mlp_multipliers[{i}]": _one("mlp_multipliers", i)
       for i in range(2)},
    "norm_before_gate": {"mamba_norm_before_gate": True},
    "no_conv_bias": {"mamba_conv_bias": False},
    "no_mixer_norm": {"mamba_rms_norm": False},
    "no_skip": _no_skip,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_every_multiplier_and_every_term_of_the_mixer_shows(tiny, fault):
    """Each multiplier in turn set to 1, the gate-then-norm order swapped,
    the convolution's bias dropped, or the skip term `D x` dropped: the
    program no longer agrees with the reference, by a hundred times the
    tolerance it otherwise meets."""
    change = FAULTS[fault]
    if callable(change):
        prog, arrays, model = program(arrays=tiny[1])
        change(prog)
    else:
        prog, arrays, model = program(arrays=tiny[1], **change)
    assert gap(prog, arrays, model) > 100 * TOL


def test_the_four_vocabulary_slices_side_by_side_are_the_uncut_logits():
    """The deployment slices the embedding and the head by rows four ways.
    A share's program holds its quarter of the head; the embedding's
    output is what the exchange gives every chip, so here the whole table
    stands in for it. The four [.., 128] blocks of logits, side by side,
    are the uncut reference's [.., 512]; and the reference given share 0
    alone (ids from its slice) gives the uncut model's first block."""
    fam, ref = family()
    uncut = fam.make(MODEL, 11, "float32")
    want = np.asarray(ref.logits(uncut, jnp.asarray(IDS), MODEL))
    cut = dict(MODEL, vocab_size=128)
    blocks = []
    for share in range(4):
        rows = slice(128 * share, 128 * (share + 1))
        mine = dict(uncut, head=uncut["head"][:, rows])
        prog, _, _ = program(arrays=mine, model=cut)
        prog.model.embed_tokens._data = uncut["embed"]
        with paddle.no_grad():
            blocks.append(np.asarray(prog(paddle.to_tensor(IDS)).value()))
    got = np.concatenate(blocks, axis=-1)
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.abs(want).max())
    ids0 = IDS % 128
    share0 = dict(uncut, head=uncut["head"][:, :128],
                  embed=uncut["embed"][:128])
    np.testing.assert_allclose(
        ref.logits(share0, jnp.asarray(ids0), cut),
        np.asarray(ref.logits(uncut, jnp.asarray(ids0), MODEL))[..., :128],
        rtol=0, atol=1e-5)


def test_the_reference_gives_a_loss_and_a_gradient_for_every_leaf(tiny):
    """`loss_and_grads` is `jax.grad` of the forward the serving comparison
    uses: a random model's loss is near log(vocabulary), every array has a
    finite gradient, and the mixer's scalars have one that is not zero."""
    _, arrays, model = tiny
    fam, ref = family()
    ids = np.random.default_rng(12).integers(0, 512, (2, 24)).astype("int32")
    loss, grads = ref.loss_and_grads(arrays, jnp.asarray(ids), model)
    assert abs(float(loss) - np.log(512)) < 1.5
    assert sorted(grads) == sorted(fam.shapes(model))
    assert ref.LAYER_KEYS == () and set(ref.TOP_KEYS) < set(grads)
    for k, g in grads.items():
        assert g.shape == arrays[k].shape and bool(jnp.all(jnp.isfinite(g)))
    for k in ("ssm_alog", "ssm_dt", "ssm_d", "ssm_conv_b", "ssm_norm"):
        assert float(jnp.abs(grads[ref.key(1, k)]).max()) > 0, k
