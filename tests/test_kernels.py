"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp
oracles, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.jacobi2d import jacobi2d_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.stream_triad import triad_pallas

F32, BF16 = jnp.float32, jnp.bfloat16


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == BF16 \
        else dict(rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- triad

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", [(8, 128), (256, 512), (300, 640),
                                   (1024, 1024)])
def test_triad(shape, dtype):
    b, c = _rand(0, shape, dtype), _rand(1, shape, dtype)
    out = triad_pallas(b, c, 2.5, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.triad_ref(b, c, 2.5), np.float32), **_tol(dtype))


# ----------------------------------------------------------------- jacobi2d

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("shape", [(16, 128), (256, 256), (384, 512),
                                   (100, 128)])
def test_jacobi2d(shape, dtype):
    a = _rand(2, shape, dtype)
    out = jacobi2d_pallas(a, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.jacobi2d_ref(a), np.float32), **_tol(dtype))


def test_jacobi2d_boundary_passthrough():
    a = _rand(3, (64, 128), F32)
    out = jacobi2d_pallas(a, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(a[0]))
    np.testing.assert_array_equal(np.asarray(out[-1]), np.asarray(a[-1]))
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(a[:, 0]))
    np.testing.assert_array_equal(np.asarray(out[:, -1]),
                                  np.asarray(a[:, -1]))


# ------------------------------------------------------------------- matmul

@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 512, 384),
                                 (512, 256, 1024), (64, 128, 256)])
def test_matmul(mnk, dtype):
    m, n, k = mnk
    a, b = _rand(4, (m, k), dtype), _rand(5, (k, n), dtype)
    out = matmul_pallas(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = dict(rtol=3e-2, atol=3e-1) if dtype == BF16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bhsd", [(1, 2, 256, 64), (2, 4, 512, 128),
                                  (1, 1, 384, 64)])
def test_flash_attention(bhsd, causal):
    B, H, S, D = bhsd
    q = _rand(6, (B, H, S, D), F32)
    k = _rand(7, (B, H, S, D), F32)
    v = _rand(8, (B, H, S, D), F32)
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- mamba scan

@pytest.mark.parametrize("dims", [(1, 128, 512, 16), (2, 256, 1024, 16),
                                  (2, 128, 640, 8)])
def test_mamba_scan(dims):
    Bt, S, D, N = dims
    dt = jax.nn.softplus(_rand(9, (Bt, S, D), F32))
    A = -jnp.exp(_rand(10, (D, N), F32) * 0.3)
    B = _rand(11, (Bt, S, N), F32)
    C = _rand(12, (Bt, S, N), F32)
    x = _rand(13, (Bt, S, D), F32)
    out = mamba_scan_pallas(dt, A, B, C, x, interpret=True)
    want = ref.mamba_scan_ref(dt, A, B, C, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(
    s=st.sampled_from([64, 128, 192]),
    d=st.sampled_from([128, 256]),
    n=st.sampled_from([8, 16]),
)
def test_property_mamba_scan_matches_oracle(s, d, n):
    dt = jax.nn.softplus(_rand(s, (1, s, d), F32))
    A = -jnp.exp(_rand(d, (d, n), F32) * 0.3)
    B = _rand(s + 1, (1, s, n), F32)
    C = _rand(s + 2, (1, s, n), F32)
    x = _rand(s + 3, (1, s, d), F32)
    out = mamba_scan_pallas(dt, A, B, C, x, interpret=True)
    want = ref.mamba_scan_ref(dt, A, B, C, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------- aligned block choice
# Shapes whose old count-down loops landed on blocks that break the TPU's
# (8,128) tiling rule (jacobi rows 1000 -> 250, matmul K 1000 -> 500,
# mamba channels 1000 -> 500). They now take aligned blocks or zero-pad.

def test_block_size_rule():
    from repro.kernels.blocks import block_size
    assert block_size(100, 256, 8) == 100            # whole dim fits
    assert block_size(1000, 256, 8) == 200           # aligned divisor
    assert block_size(384, 256, 8) == 192
    with pytest.raises(ValueError, match="1001"):
        block_size(1001, 256, 8)


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_jacobi2d_unaligned_rows(dtype):
    a = _rand(14, (1000, 1024), dtype)
    out = jacobi2d_pallas(a, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.jacobi2d_ref(a), np.float32), **_tol(dtype))


def test_jacobi2d_without_aligned_block_names_shape():
    with pytest.raises(ValueError, match=r"\(1001, 128\)"):
        jacobi2d_pallas(jnp.zeros((1001, 128), F32), interpret=True)


@pytest.mark.parametrize("mnk", [(256, 256, 1000), (64, 128, 700)])
def test_matmul_unaligned_k_is_zero_padded(mnk):
    m, n, k = mnk
    a, b = _rand(15, (m, k), F32), _rand(16, (k, n), F32)
    out = matmul_pallas(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dims", [(1, 128, 1000, 16), (1, 130, 256, 8)])
def test_mamba_scan_unaligned_dims_are_zero_padded(dims):
    Bt, S, D, N = dims
    dt = jax.nn.softplus(_rand(17, (Bt, S, D), F32))
    A = -jnp.exp(_rand(18, (D, N), F32) * 0.3)
    B = _rand(19, (Bt, S, N), F32)
    C = _rand(20, (Bt, S, N), F32)
    x = _rand(21, (Bt, S, D), F32)
    out = mamba_scan_pallas(dt, A, B, C, x, interpret=True)
    assert out.shape == (Bt, S, D)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.mamba_scan_ref(dt, A, B, C, x)),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------ ops dispatch

def test_ops_dispatch_jnp_path():
    from repro.kernels import ops
    a, b = _rand(20, (64, 64), F32), _rand(21, (64, 64), F32)
    np.testing.assert_allclose(np.asarray(ops.matmul(a, b, impl="jnp")),
                               np.asarray(ref.matmul_ref(a, b)))
    with pytest.raises(ValueError):
        ops.matmul(a, b, impl="bogus")


def test_ops_dispatch_pallas_needs_explicit_interpret():
    """The Pallas path runs the kernel as asked: interpret mode only when
    the caller passes ``interpret=True``, on any backend."""
    from repro.kernels import ops
    a, b = _rand(22, (128, 128), F32), _rand(23, (128, 128), F32)
    np.testing.assert_allclose(
        np.asarray(ops.matmul(a, b, impl="pallas", interpret=True)),
        np.asarray(ref.matmul_ref(a, b)), rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        ops.matmul(a, b, impl="auto")
