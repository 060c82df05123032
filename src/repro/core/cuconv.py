"""cuConv: tap-decomposed direct convolution (the paper's contribution).

The paper decomposes a KH x KW convolution by *filter tap*: for every
tap (i, j), the channel-axis dot product of filter row F[:, i, j] with
every input row is a plain GEMM over data that is contiguous in the
chosen layout, with **no im2col transform**; the KH*KW per-tap partials
are summed.  On the TPU that is the fused Pallas kernel
(``kernels/cuconv_fused.py``), which accumulates the taps in VMEM
(DESIGN.md §2-§3).

This module holds the library baseline ``conv_lax`` (the ``lax``
executor and every test's reference), the tap-view helpers the int8
executor builds its patch matrix from, and ``conv2d``, the public entry
point: a thin wrapper over ``core.convspec.plan``, which negotiates the
executor over the registry (DESIGN.md §4/§8).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# geometry helpers and the Pad alias have ONE home: core.convspec
from repro.core.convspec import Pad
from repro.core.convspec import (normalize_pad as _norm_pad,
                                 normalize_stride as _norm_stride)


def _pad_input(x, ph, pw):
    if ph == 0 and pw == 0:
        return x
    return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))


def conv_lax(x, w, stride=1, padding: Pad = "same", groups=1):
    """Library convolution (XLA's native conv; the cuDNN analogue).

    ``groups`` maps to ``feature_group_count``: the only executor that
    runs grouped/depthwise specs exactly (filter depth is C/groups).
    """
    kh, kw = w.shape[0], w.shape[1]
    ph, pw = _norm_pad(padding, kh, kw)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=_norm_stride(stride),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def _tap_views(xp, kh, kw, oh, ow, stride):
    """The KH*KW shifted input views (XLA slices, nothing materialized)."""
    N, _, _, C = xp.shape
    sh, sw = _norm_stride(stride)
    views = []
    for i in range(kh):
        for j in range(kw):
            views.append(jax.lax.slice(
                xp, (0, i, j, 0),
                (N, i + sh * (oh - 1) + 1, j + sw * (ow - 1) + 1, C),
                (1, sh, sw, 1)))
    return views


def conv2d(x, w, stride=1, padding: Pad = "same", algorithm="auto",
           bias=None, activation: Optional[str] = None, groups=1):
    """Public conv entry point: a thin wrapper over the ConvSpec planner.

    x: (N,H,W,C) NHWC; w: (KH,KW,C/groups,M) HWIO; bias: optional (M,);
    activation: None | 'relu' | 'gelu' (the exact erf form; anything
    else raises — no silent epilogue drop).  groups > 1 requests a
    grouped/depthwise conv, executed via the library's
    feature_group_count or, for depthwise specs on a TPU, the
    depthwise_tap kernel (plan() routes it).
    algorithm="auto" lets plan() negotiate over the executor registry
    (measured cache > region claims > cheapest supported); naming a
    registered executor forces it, still subject to its declared
    capabilities (e.g. the fused kernel's VMEM budget).  The
    bias/activation epilogue is fused into the Pallas kernel when that
    path is planned, and applied as XLA ops otherwise.
    """
    from repro.core.convspec import ConvSpec, plan
    spec = ConvSpec.for_conv(x, w, stride, padding, bias=bias,
                             activation=activation, groups=groups)
    p = plan(spec, force=None if algorithm == "auto" else algorithm)
    return p(x, w, bias)
