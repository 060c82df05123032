"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def conv2d_ref(x, w, stride=1, padding=(0, 0)):
    """NHWC direct convolution via the platform library op."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=(padding[0],) * 2 if isinstance(padding[0], int) else padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv2d_pad_ref(x, w, padding=(0, 0)):
    ph, pw = padding
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv1d_ref(x, w, b=None):
    """Causal depthwise conv1d.  x: (B, L, D); w: (K, D)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(xp[:, k:k + x.shape[1], :] * w[k].astype(jnp.float32)
            for k in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def attention_ref(q, k, v, causal=True):
    """q: (BH, Sq, D); k, v: (BH, Sk, D)."""
    D = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) / (D ** 0.5)
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w.astype(q.dtype), v)
