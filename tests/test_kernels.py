"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_chunk
from repro.kernels.zskip_matmul import zskip_matmul


# ----------------------------------------------------------- zskip_matmul
@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 128), (384, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_zskip_matmul_matches_ref(M, K, N, dtype):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    # post-ReLU-like sparse activations: zero out ~half the tiles
    a = jax.nn.relu(jax.random.normal(k1, (M, K), dtype))
    tile_keep = jax.random.bernoulli(k2, 0.5, (M // 128, K // 128))
    a = a * jnp.repeat(jnp.repeat(tile_keep, 128, 0), 128, 1).astype(dtype)
    b = jax.random.normal(k2, (K, N), dtype)
    mask = ref.block_mask_ref(a, 128, 128)
    got = zskip_matmul(a, b, mask, interpret=True)
    want = ref.zskip_matmul_ref(a, b, mask, 128, 128)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_zskip_exactness_on_zero_tiles():
    """Skipping all-zero tiles must be EXACT (not approximate)."""
    a = jnp.zeros((256, 256), jnp.float32).at[:128, :128].set(1.0)
    b = jnp.ones((256, 128), jnp.float32)
    mask = ref.block_mask_ref(a, 128, 128)
    assert mask.tolist() == [[1, 0], [0, 0]]
    got = zskip_matmul(a, b, mask, interpret=True)
    want = a @ b
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_zskip_op_wrapper():
    a = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (256, 256)))
    b = jax.random.normal(jax.random.PRNGKey(2), (256, 128))
    np.testing.assert_allclose(
        np.asarray(ops.zskip_matmul_op(a, b)), np.asarray(a @ b), rtol=2e-5, atol=2e-5
    )


# -------------------------------------------------------- flash_attention
@pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256), (256, 512)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(sq, sk, causal, dtype):
    if causal and sq != sk:
        pytest.skip("causal requires square here")
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    bh, hd = 4, 64
    q = jax.random.normal(kq, (bh, sq, hd), dtype)
    k = jax.random.normal(kk, (bh, sk, hd), dtype)
    v = jax.random.normal(kv, (bh, sk, hd), dtype)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_op_matches_model_sdpa():
    """Kernel == the model's _sdpa (the path it replaces)."""
    from repro.models.layers import _sdpa

    key = jax.random.PRNGKey(4)
    b, s, h, hd = 2, 128, 4, 64
    q = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd), jnp.float32)
    got = ops.flash_attention_op(q, k, v, causal=True)
    want = _sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# -------------------------------------------------------------- ssd_chunk
@pytest.mark.parametrize("Q,H,P,N", [(32, 4, 16, 32), (64, 8, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_matches_ref(Q, H, P, N, dtype):
    key = jax.random.PRNGKey(5)
    nc = 3
    ks = jax.random.split(key, 4)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (nc, Q, H))) * 0.1
    cum = jnp.cumsum(-dt, axis=1).astype(dtype)
    xdt = (jax.random.normal(ks[1], (nc, Q, H, P)) * 0.5).astype(dtype)
    B = jax.random.normal(ks[2], (nc, Q, N), dtype)
    C = jax.random.normal(ks[3], (nc, Q, N), dtype)
    y, s = ssd_chunk(cum, xdt, B, C, head_block=min(4, H), interpret=True)
    y_ref, s_ref = ref.ssd_chunk_ref(cum, xdt, B, C)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(s, np.float32), np.asarray(s_ref, np.float32), rtol=tol, atol=tol
    )


def test_ssd_kernel_consistent_with_model_scan():
    """Kernel per-chunk outputs reproduce models.ssm.ssd_chunked end-to-end."""
    from repro.models.ssm import ssd_chunked

    key = jax.random.PRNGKey(6)
    b, s, h, p, n, chunk = 2, 64, 4, 16, 32, 32
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_want, S_want = ssd_chunked(x, dt, A, B, C, chunk=chunk)

    # rebuild via kernel: per-batch chunked terms + jnp inter-chunk scan
    nc = s // chunk
    dtc = dt.reshape(b, nc, chunk, h)
    cum = jnp.cumsum(dtc * A, axis=2)
    xdt = (x * dt[..., None]).reshape(b, nc, chunk, h, p)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)
    outs = []
    for bi in range(b):
        y_intra, S_chunk = ssd_chunk(
            cum[bi], xdt[bi], Bc[bi], Cc[bi], head_block=h, interpret=True
        )
        chunk_decay = jnp.exp(cum[bi, :, -1, :])  # (nc, h)
        S = jnp.zeros((h, n, p))
        ys = []
        for c in range(nc):
            y_inter = jnp.einsum(
                "qh,qn,hnp->qhp", jnp.exp(cum[bi, c]), Cc[bi, c], S
            )
            ys.append(y_intra[c] + y_inter)
            S = chunk_decay[c][:, None, None] * S + S_chunk[c]
        outs.append(jnp.concatenate(ys, axis=0))
    y_got = jnp.stack(outs)
    np.testing.assert_allclose(
        np.asarray(y_got), np.asarray(y_want), rtol=2e-4, atol=2e-4
    )
