"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.mandelbrot.ops import mandelbrot
from repro.kernels.mandelbrot.ref import grid_coords, mandelbrot_reference
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_reference
from repro.kernels.rmsnorm.ops import rms_norm
from repro.kernels.rmsnorm.ref import rms_norm_reference


def keys(n):
    return [jax.random.fold_in(jax.random.PRNGKey(7), i) for i in range(n)]


# -- mandelbrot ---------------------------------------------------------------


@pytest.mark.parametrize("h,w,iters", [(16, 128, 50), (32, 300, 100), (9, 77, 30)])
def test_mandelbrot_matches_reference(h, w, iters):
    x0, y0 = grid_coords(h, w)
    it_k, col_k = mandelbrot(x0, y0, max_iters=iters, interpret=True)
    it_r, col_r = mandelbrot_reference(x0, y0, iters)
    np.testing.assert_array_equal(np.asarray(it_k), np.asarray(it_r))
    np.testing.assert_array_equal(np.asarray(col_k), np.asarray(col_r))


def test_mandelbrot_paper_counts():
    """Paper section 8: on the full 3200x5600 grid ~14M of 17.92M points are
    white.  On a 1/8-scale grid the white fraction must be comparable."""
    x0, y0 = grid_coords(400, 700)
    _iters, col = mandelbrot(x0, y0, max_iters=200, interpret=True)
    white_frac = float(jnp.mean(col.astype(jnp.float32)))
    assert 0.70 < white_frac < 0.90  # paper: 14.06/17.92 = 0.785


# -- flash attention -----------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kv,s,d,causal,window",
    [
        (2, 4, 4, 256, 64, True, 0),
        (1, 8, 2, 256, 32, True, 64),
        (2, 2, 2, 128, 128, False, 0),
        (1, 4, 1, 384, 64, True, 128),
        (1, 4, 4, 200, 64, True, 0),  # ragged
    ],
)
def test_flash_attention_sweep(b, h, kv, s, d, causal, window, dtype):
    ks = keys(3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    kr = jnp.repeat(k, h // kv, axis=1)
    vr = jnp.repeat(v, h // kv, axis=1)
    ref = attention_reference(q, kr, vr, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_matches_model_attention():
    """The Pallas kernel and the model's XLA blockwise path agree."""
    from repro.models.attention import attention_blockwise

    ks = keys(3)
    b, h, s, d = 1, 4, 256, 32
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    out_kernel = flash_attention(q, k, v, causal=True)
    out_xla = attention_blockwise(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=True, q_chunk=64,
    )
    np.testing.assert_allclose(
        np.asarray(out_kernel), np.asarray(jnp.moveaxis(out_xla, 2, 1)),
        atol=3e-6,
    )


# -- rglru ----------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,w", [(2, 64, 128), (1, 128, 200), (3, 32, 64)])
def test_rglru_sweep(b, s, w, dtype):
    ks = keys(3)
    a = jax.random.uniform(ks[0], (b, s, w), dtype, 0.5, 0.999)
    bb = jax.random.normal(ks[1], (b, s, w), dtype)
    h0 = jax.random.normal(ks[2], (b, w), dtype)
    h, hl = rglru_scan(a, bb, h0)
    hr, hlr = rglru_scan_reference(a, bb, h0)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(hl, np.float32),
                               np.asarray(hlr, np.float32), atol=tol)


def test_rglru_state_chaining():
    """Scanning two halves with carried state == scanning the whole."""
    ks = keys(2)
    a = jax.random.uniform(ks[0], (1, 64, 128), minval=0.5, maxval=0.99)
    b = jax.random.normal(ks[1], (1, 64, 128))
    h_full, hl_full = rglru_scan(a, b)
    h1, hl1 = rglru_scan(a[:, :32], b[:, :32])
    h2, hl2 = rglru_scan(a[:, 32:], b[:, 32:], hl1)
    np.testing.assert_allclose(np.asarray(hl2), np.asarray(hl_full), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([h1, h2], axis=1)),
        np.asarray(h_full), atol=1e-5,
    )


# -- rmsnorm ---------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,d", [((8, 512), 512), ((3, 100, 256), 256),
                                     ((1000,), 1000)])
def test_rmsnorm_sweep(shape, d, dtype):
    ks = keys(2)
    x = jax.random.normal(ks[0], shape[:-1] + (d,) if len(shape) > 1 else (1, d),
                          dtype)
    if len(shape) == 1:
        x = jax.random.normal(ks[0], (4, d), dtype)
    s = jax.random.normal(ks[1], (d,)) * 0.2
    out = rms_norm(x, s)
    ref = rms_norm_reference(x.reshape(-1, d), s).reshape(x.shape)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
