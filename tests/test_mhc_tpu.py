"""The hyper-connection boundary kernel ``mhc`` as Mosaic compiles it, on the
chip, against its ``jax.numpy`` form ``mhc_plain`` at Motif-3-Beta's widths
(4 streams of 4,096, bfloat16 streams, phi ``[24, 16384]``): a decode step's
128 rows, a prefill chunk's 512, and 130 (a ragged last tile). Interpret
mode (the CPU cases in ``test_motif3.py``) cannot show what Mosaic does with
the transposed projection, the coefficients held a token a lane and the
walk over column pieces; this does. Skipped off the TPU
(``@pytest.mark.tpu``); on a TPU host: ``tools/run_tpu_tests.sh``.

Both forms round at the same points (the streams to bfloat16 after the
clamp, the normed row to bfloat16 before the projection) and sum in float32
in different orders, so a rounding to bfloat16 may land one unit apart:
streams and ``u`` agree to one bfloat16 unit (2**-7 of the value) over an
absolute floor of what the coefficients' float32 gap moves (2e-3 on values
of order 1), the coefficients to 1e-3."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

N, C, P = 4, 4096, 24
EPS, CLAMP = 1e-6, 100.0
ULP = 2.0 ** -7


def boundary(t, seed):
    rng = np.random.default_rng(seed)
    from paddle_tpu.kernels.pallas import mhc
    bf, f32 = jnp.bfloat16, jnp.float32
    x = jnp.asarray(rng.normal(size=(t, N, C)), bf)
    y = jnp.asarray(rng.normal(size=(t, C)), bf)
    post = jnp.asarray(rng.uniform(0, 2, (t, N)), f32)
    res = mhc.sinkhorn(jnp.asarray(rng.uniform(0.1, 1, (t, N, N)), f32), 20)
    weights = (jnp.asarray(rng.normal(1, 0.1, N * C), bf),
               jnp.asarray(rng.normal(size=(P, N * C)) / np.sqrt(N * C), bf),
               jnp.asarray([1.1, 0.9, 1.2], bf),
               jnp.asarray(rng.normal(0, 0.5, P), bf))
    return x, y, post, res, weights


def check(t, mix):
    """The kernel (Mosaic on the chip, or whatever ``mhc.mhc`` takes
    where this runs) against the plain form; returns the kernel's H_res."""
    from paddle_tpu.kernels.pallas import mhc
    x, y, post, res, w = boundary(t, seed=t + mix)
    args = (x, y, post, res) if mix else (x, None, None, None)
    want = mhc.mhc_plain(*args, *w, eps=EPS, clamp=CLAMP, iters=20)
    got = mhc.mhc(*args, *w, eps=EPS, clamp=CLAMP)
    for g, v in zip(got, want):
        assert g.shape == v.shape and g.dtype == v.dtype
    streams, u, post_n, res_n = (np.asarray(g.astype(jnp.float32))
                                 for g in got)
    want = [np.asarray(v.astype(jnp.float32)) for v in want]
    np.testing.assert_allclose(streams, want[0], rtol=ULP, atol=2e-3)
    np.testing.assert_allclose(u, want[1], rtol=ULP, atol=2e-3)
    np.testing.assert_allclose(post_n, want[2], rtol=0, atol=1e-3)
    np.testing.assert_allclose(res_n, want[3], rtol=0, atol=1e-3)
    if not mix:
        np.testing.assert_array_equal(streams, want[0])
    return res_n


@pytest.mark.tpu
@pytest.mark.parametrize("mix", [True, False], ids=["write_and_read",
                                                    "first_read"])
@pytest.mark.parametrize("t", [128, 512, 130],
                         ids=["decode", "chunk", "ragged"])
def test_the_compiled_kernel_is_its_plain_form(t, mix):
    from paddle_tpu.kernels.pallas import mhc
    assert mhc.kernel_mode(jnp.zeros((1, C), jnp.bfloat16)) == "mosaic"
    res = check(t, mix)
    # twenty rounds, the last a column normalisation
    assert float(np.abs(res.sum(-2) - 1).max()) < 1e-6
