"""The paged decode kernel as Mosaic compiles it, on the chip, against the
gathered view it replaces, at the decode shapes of the benchmark's three
families: the chunk of 32 pages the kernel derives for 16 KB pages (Falcon-H1,
Qwen3-Next) and GPT-3 XL's 8 pages of 64 KB. Interpret mode (the CPU tests in
test_paged_decode_kernel.py) cannot show what the chip's DMA engine and
semaphores do with predicated copies and byte-counted waits; this does.
Skipped off the TPU (``@pytest.mark.tpu``); on the chip:
``chiprun -- tools/run_tpu_tests.sh``."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BS = 16


def gathered_view(q, pool_k, pool_v, table, lengths, n_kv):
    """Masked float32 softmax over every slot's whole table."""
    b, _, nh, hd = q.shape
    k = pool_k[table].reshape(b, -1, n_kv, hd).astype(jnp.float32)
    v = pool_v[table].reshape(b, -1, n_kv, hd).astype(jnp.float32)
    qg = q.reshape(b, 1, n_kv, nh // n_kv, hd).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bmkd->bkgqm", qg, k,
                        precision="highest") / math.sqrt(hd)
    pos = jnp.arange(k.shape[1])[None, None, None, None, :]
    scores = jnp.where(pos < lengths[:, None, None, None, None], scores,
                       -1e30)
    ctx = jnp.einsum("bkgqm,bmkd->bqkgd", jax.nn.softmax(scores, axis=-1), v,
                     precision="highest")
    return ctx.reshape(b, 1, nh, hd)


@pytest.mark.tpu
@pytest.mark.parametrize("b,nh,n_kv,hd,mbs,nb,pages,q_dtype", [
    (128, 20, 4, 128, 256, 16384, 32, jnp.bfloat16),    # Falcon-H1
    (128, 16, 2, 256, 256, 20480, 32, jnp.bfloat16),    # Qwen3-Next
    (32, 16, 16, 128, 128, 3000, 8, jnp.bfloat16),      # GPT-3 XL
    (128, 20, 4, 128, 256, 16384, 32, jnp.float32),     # float32 query
], ids=["falcon_h1", "qwen3_next", "gpt3_xl", "falcon_h1_f32_query"])
def test_the_kernel_reads_what_the_gathered_view_reads(b, nh, n_kv, hd, mbs,
                                                       nb, pages, q_dtype):
    """Dead slots on the trash row beside full tables, lengths one under, at
    and one over a chunk's edge, the rest as a closed-loop cell leaves them;
    the pools' unreferenced blocks and every live block's tail hold inf and
    nan. The output is finite and the gathered view's to bfloat16 rounding."""
    from paddle_tpu.kernels.pallas import paged_decode as pd
    rng = np.random.RandomState(b + nh)
    edge = pages * BS
    lengths = np.clip(rng.normal(1100, 350, b), 1, mbs * BS).astype(np.int32)
    lengths[:8] = [1, 1, edge - 1, edge, edge + 1, mbs * BS, 2 * edge, BS]
    perm = rng.permutation(np.arange(1, nb))
    table, used = np.zeros((b, mbs), np.int32), 0
    live = np.zeros((nb, BS), bool)
    for i, n in enumerate(lengths):
        k = -(-int(n) // BS)
        table[i, :k] = perm[used:used + k]
        live[table[i, :k]] = True
        live[table[i, k - 1], (int(n) - 1) % BS + 1:] = False
        used += k
    table[0] = 0                       # a dead slot: pos 0 on the trash row
    live[0, 0] = True
    pools = []
    for _ in range(2):
        x = (rng.randn(nb, BS, n_kv, hd) * 0.7).astype(np.float32)
        x[~live] = np.array([np.inf, -np.inf, np.nan, 3e38],
                            np.float32)[np.arange((~live).sum()) % 4,
                                        None, None]
        pools.append(jnp.asarray(x, jnp.bfloat16).reshape(nb, BS * n_kv, hd))
    q = jnp.asarray(rng.randn(b, 1, nh, hd), q_dtype)
    args = (q, *pools, jnp.asarray(table), jnp.asarray(lengths))
    assert pd.kernel_mode(q, pools[0], n_kv) == "mosaic"
    got = jax.jit(lambda *a: pd.paged_decode_attention(*a, n_kv=n_kv))(*args)
    assert pd.kernel_geometry()["kv_chunk_pages"] == pages
    clean = tuple(jnp.where(jnp.isfinite(p), p, 0) for p in pools)
    want = jax.jit(lambda *a: gathered_view(*a, n_kv))(
        q, *clean, args[3], args[4])
    got, want = np.asarray(got, np.float32), np.asarray(want)
    assert np.isfinite(got).all()
    tol = 2.0 ** -8 if q_dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * max(float(np.abs(want).max()), 1.0))
