"""Per-kernel allclose sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import cuconv_fused as kf, conv1d_tap as kc, \
    flash_attention as kfa

TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,H,W,C,KH,KW,M,pad", [
    (1, 7, 7, 16, 3, 3, 8, 1),
    (2, 9, 11, 4, 5, 5, 6, 2),
    (1, 13, 13, 32, 3, 3, 16, 1),
    (2, 8, 8, 8, 1, 1, 12, 0),
    (1, 6, 6, 3, 3, 3, 5, 0),
])
def test_cuconv_fused_kernel(rng, N, H, W, C, KH, KW, M, pad, dtype):
    x = _rand(rng, (N, H, W, C), dtype)
    w = _rand(rng, (KH, KW, C, M), dtype)
    got = ops.cuconv_fused(x, w, (pad, pad), interpret=True)
    want = ref.conv2d_pad_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                              (pad, pad))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,D,K", [(2, 37, 24, 4), (1, 128, 64, 4),
                                     (3, 16, 8, 2)])
def test_conv1d_tap(rng, B, L, D, K, dtype):
    x = _rand(rng, (B, L, D), dtype)
    w = _rand(rng, (K, D), dtype)
    b = _rand(rng, (D,), dtype)
    got = ops.conv1d_causal(x, w, b, interpret=True)
    want = ref.conv1d_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("BH,Sq,Sk,D", [(3, 40, 40, 16), (2, 100, 100, 32),
                                        (1, 64, 128, 8)])
def test_flash_attention(rng, BH, Sq, Sk, D, causal):
    if causal and Sq != Sk:
        pytest.skip("causal requires square here")
    q = _rand(rng, (BH, Sq, D), jnp.float32)
    k = _rand(rng, (BH, Sk, D), jnp.float32)
    v = _rand(rng, (BH, Sk, D), jnp.float32)
    got = kfa.flash_attention(q, k, v, causal=causal, tq=32, tk=32,
                              interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa_wrapper(rng):
    B, S, H, KVH, D = 2, 32, 8, 2, 16
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, KVH, D), jnp.float32)
    v = _rand(rng, (B, S, KVH, D), jnp.float32)
    got = ops.flash_attention(q, k, v, interpret=True)
    from repro.nn.attention import exact_attention, _repeat_kv
    want = exact_attention(q, _repeat_kv(k, H), _repeat_kv(v, H))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [(2, 2), (2, 1), (3, 2)])
@pytest.mark.parametrize("N,H,W,C,KH,KW,M,pad", [
    (1, 9, 9, 8, 3, 3, 6, 1),
    (2, 11, 13, 4, 5, 5, 3, 2),
])
def test_cuconv_fused_strided(rng, N, H, W, C, KH, KW, M, pad, stride):
    """The generalized kernel matches the library conv at any stride."""
    x = _rand(rng, (N, H, W, C), jnp.float32)
    w = _rand(rng, (KH, KW, C, M), jnp.float32)
    got = ops.cuconv_fused(x, w, (pad, pad), stride=stride, interpret=True)
    want = jax.lax.conv_general_dilated(
        x, w, stride, ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("KH,KW", [(1, 1), (3, 3)])
def test_cuconv_fused_epilogue(rng, KH, KW, stride):
    """bias+ReLU accumulated in VMEM on the final tap == relu(conv + b)."""
    x = _rand(rng, (2, 8, 8, 8), jnp.float32)
    w = _rand(rng, (KH, KW, 8, 12), jnp.float32)
    b = _rand(rng, (12,), jnp.float32)
    pad = (KH - 1) // 2
    got = ops.cuconv_fused(x, w, (pad, pad), stride=stride, bias=b,
                           activation="relu", interpret=True)
    want = jax.nn.relu(jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# winograd_fused: the benchmark's Winograd shapes (ResNet-50 stages 1-4,
# SqueezeNet 1.0 fires) at batch 1-2, both F(m,3) variants, padding 0
# and 1, ``rows`` that leave a ragged last band (th % rows != 0) or
# take whole images per step (rows >= th), and both epilogues: bias +
# ReLU, and bias + residual addend + ReLU.
# Channel tiles below C and M run the multi-step contraction and the
# kept transform across output-channel tiles.
@pytest.mark.parametrize("N,H,W,C,M,pad,m,rows,tc,addend", [
    (1, 56, 56, 64, 64, 1, 2, 7, 64, False),
    (1, 56, 56, 64, 64, 1, 4, 4, 64, True),       # th 14: bands 4,4,4,2
    (1, 28, 28, 128, 128, 1, 2, 5, 128, True),    # th 14: ragged
    (2, 28, 28, 128, 128, 0, 4, 3, 128, False),   # th 7: ragged
    (1, 14, 14, 256, 256, 1, 2, 4, 128, False),   # th 7: ragged
    (1, 14, 14, 256, 256, 1, 4, 4, 128, True),
    (2, 7, 7, 512, 512, 1, 2, 3, 256, False),     # th 4: ragged
    (1, 7, 7, 512, 512, 0, 4, 1, 128, True),
    (4, 7, 7, 512, 512, 1, 2, 8, 128, True),      # 2 images a step
    (1, 54, 54, 16, 64, 1, 2, 4, 16, False),      # th 27: ragged
    (1, 54, 54, 16, 64, 0, 4, 5, 16, True),       # th 13: ragged
    (3, 27, 27, 32, 128, 1, 2, 28, 32, True),     # 2 images: 3 % 2, so 1
    (1, 27, 27, 32, 128, 1, 4, 2, 32, False),     # th 7: ragged
    (2, 13, 13, 64, 256, 1, 2, 14, 64, False),    # 2 images a step
    (1, 13, 13, 64, 256, 0, 4, 2, 64, True),      # th 3: ragged
])
def test_winograd_fused_matches_lax(rng, N, H, W, C, M, pad, m, rows, tc,
                                    addend):
    x = _rand(rng, (N, H, W, C), jnp.float32)
    w = _rand(rng, (3, 3, C, M), jnp.float32) / np.sqrt(9 * C)
    b = _rand(rng, (M,), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w, (1, 1), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) + b
    ad = None
    if addend:
        ad = _rand(rng, want.shape, jnp.float32)
        want = want + ad
    want = jnp.maximum(want, 0.0)
    got = ops.winograd_fused(x, w, (pad, pad), bias=b, activation="relu",
                             addend=ad, m=m, rows=rows, tm=128, tc=tc,
                             interpret=True)
    assert got.shape == want.shape
    # F(4,3)'s inverse transform (coefficients up to 8) amplifies
    # rounding an order more than F(2,3)'s
    tol = 1e-4 if m == 2 else 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
