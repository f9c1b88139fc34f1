"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(interpret mode executes the exact kernel body + BlockSpec tiling; the
chip's compiler is exercised by tests/test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(shape, dtype, k):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kh,sq,sk,hd,causal,window",
    [
        (2, 4, 2, 64, 64, 32, True, 0),       # GQA causal
        (1, 4, 4, 128, 128, 64, True, 0),     # MHA
        (2, 2, 1, 96, 96, 16, False, 0),      # MQA bidirectional
        (1, 4, 2, 128, 128, 32, True, 32),    # sliding window
        (1, 2, 2, 80, 112, 32, False, 0),     # ragged + cross lengths
    ],
)
def test_flash_attention_vs_oracle(b, h, kh, sq, sk, hd, causal, window, dtype):
    q = _rand((b, h, sq, hd), dtype, 0)
    k = _rand((b, kh, sk, hd), dtype, 1)
    v = _rand((b, kh, sk, hd), dtype, 2)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,di,n,bd,bs",
    [
        (2, 64, 32, 8, 16, 16),
        (1, 96, 64, 16, 32, 32),    # padding: 96 % 32 == 0, ragged in blocks
        (2, 50, 32, 4, 32, 16),     # sequence padding (50 -> 64)
    ],
)
def test_selective_scan_vs_oracle(b, s, di, n, bd, bs, dtype):
    u = _rand((b, s, di), dtype, 0)
    dt = jax.nn.softplus(_rand((b, s, di), jnp.float32, 1)).astype(dtype)
    a = -jnp.exp(_rand((di, n), jnp.float32, 2) * 0.3)
    bssm = _rand((b, s, n), dtype, 3)
    cssm = _rand((b, s, n), dtype, 4)
    d = jnp.ones((di,), jnp.float32)
    y, h = ops.selective_scan(u, dt, a, bssm, cssm, d, block_d=bd, block_s=bs,
                              interpret=True)
    yr, hr = ref.selective_scan_ref(u, dt, a, bssm, cssm, d)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d,block", [(64, 128, 32), (37, 256, 16), (5, 64, 8)])
def test_rms_norm_vs_oracle(rows, d, block, dtype):
    x = _rand((rows, d), dtype, 0)
    s = _rand((d,), jnp.float32, 1) * 0.1
    out = ops.rms_norm(x, s, block_rows=block, interpret=True)
    want = ref.rms_norm_ref(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_model_attention_matches_kernel_path():
    """layers.attention('ref'/'blockwise') and the Pallas kernel agree."""
    from repro.models import layers as L

    q = _rand((1, 4, 128, 32), jnp.float32, 0)
    k = _rand((1, 2, 128, 32), jnp.float32, 1)
    v = _rand((1, 2, 128, 32), jnp.float32, 2)
    a_ref = L.attention(q, k, v, impl="ref")
    a_blk = L.attention(q, k, v, impl="blockwise")
    a_pal = ops.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a_ref), np.asarray(a_blk), atol=2e-5)
    np.testing.assert_allclose(np.asarray(a_ref), np.asarray(a_pal), atol=2e-5)
