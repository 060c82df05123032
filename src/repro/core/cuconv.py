"""cuConv: tap-decomposed direct convolution (the paper's contribution).

The paper decomposes a KH x KW convolution by *filter tap*: stage 1
computes, for every tap (i, j), the channel-axis dot product of filter
row F[:, i, j] with every input row — a plain GEMM per tap, over data
that is contiguous in the chosen layout with **no im2col transform**;
stage 2 sums the KH*KW per-tap partial matrices.  1x1 filters skip
stage 2 entirely (the paper's best-case region).

TPU adaptation (DESIGN.md §2): NHWC instead of NCHW so the channel
contraction is lane-contiguous; each per-tap GEMM maps onto the MXU.

All algorithms below are numerically equivalent (property-tested),
policy-free executor *functions*: each is wrapped by a registered
``core.executors.Executor`` declaring its capabilities, and which one
runs for a given configuration is decided exclusively by
``core.convspec.plan`` negotiating over that registry (DESIGN.md §4/§8),
which ``conv2d(..., algorithm="auto")`` wraps.  Every contraction
accumulates fp32 (``preferred_element_type``) so bf16 inputs keep
fp32 accumulation; outputs are cast back to the input dtype.

  lax              jax.lax.conv_general_dilated — the library baseline
                   (the cuDNN stand-in of the paper's comparison)
  im2col           explicit patch matrix + one GEMM — cuDNN "GEMM" variant
  cuconv_two_stage faithful paper algorithm: stage-1 temporaries
                   materialized (KH*KW, N, OH, OW, M), stage-2 sum
  cuconv_two_stage_pallas
                   the same pipeline on the Pallas stage-1/stage-2
                   kernels (stride 1) — the planner's VMEM fallback
  cuconv           beyond-paper fused tap accumulation (no temporaries);
                   the paper's "work-fusion" future-work realized
  cuconv_pallas    the fused Pallas TPU kernel (any stride, fused
                   bias/ReLU epilogue)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# geometry helpers and the Pad alias have ONE home: core.convspec
# (aliased/re-exported here for brevity and back-compat)
from repro.core.convspec import Pad  # noqa: F401  (public re-export)
from repro.core.convspec import (normalize_pad as _norm_pad,
                                 normalize_stride as _norm_stride,
                                 out_size as _out_size)


def _pad_input(x, ph, pw):
    if ph == 0 and pw == 0:
        return x
    return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))


# ---------------------------------------------------------------------------
# Baselines

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


def conv_im2col(x, w, stride=1, padding: Pad = "same"):
    """Explicit-GEMM convolution: materialize the patch matrix, one GEMM.

    This is the paper's "GEMM (explicit)" cuDNN baseline: the intermediate
    matrix duplicates input elements KH*KW-fold — the memory cost cuConv
    avoids.
    """
    kh, kw, C, M = w.shape
    ph, pw = _norm_pad(padding, kh, kw)
    sh, sw = _norm_stride(stride)
    xp = _pad_input(x, ph, pw)
    N = xp.shape[0]
    oh, ow = _out_size(x.shape[1], kh, ph, sh), _out_size(
        x.shape[2], kw, pw, sw)
    patches = jnp.stack(_tap_views(xp, kh, kw, oh, ow, (sh, sw)), axis=3)
    patches = patches.reshape(N * oh * ow, kh * kw * C)  # materialized!
    out = jnp.matmul(patches, w.reshape(kh * kw * C, M),
                     preferred_element_type=jnp.float32)
    return out.reshape(N, oh, ow, M).astype(x.dtype)


# ---------------------------------------------------------------------------
# cuConv: the paper's two stages

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


def cuconv_stage1(x, w, stride=1, padding: Pad = "same"):
    """Stage 1: per-tap channel contraction.

    Returns the paper's temporary tensor of shape (KH*KW, N, OH, OW, M):
    one (OH x OW) partial-result matrix per (tap, input, filter) triple.
    """
    kh, kw, C, M = w.shape
    ph, pw = _norm_pad(padding, kh, kw)
    sh, sw = _norm_stride(stride)
    xp = _pad_input(x, ph, pw)
    oh = _out_size(x.shape[1], kh, ph, sh)
    ow = _out_size(x.shape[2], kw, pw, sw)
    views = _tap_views(xp, kh, kw, oh, ow, (sh, sw))
    taps = w.reshape(kh * kw, C, M)
    outs = [jnp.einsum("nhwc,cm->nhwm", v, taps[t],
                       preferred_element_type=jnp.float32)
            for t, v in enumerate(views)]
    return jnp.stack(outs, axis=0)


def cuconv_stage2(temps):
    """Stage 2: sum the KH*KW per-tap partial matrices."""
    return jnp.sum(temps, axis=0)


def conv_cuconv_two_stage(x, w, stride=1, padding: Pad = "same"):
    """Faithful paper pipeline: materialized temporaries + separate sum.

    For 1x1 filters stage 2 is skipped (paper §3): stage 1's output *is*
    the convolution.
    """
    kh, kw = w.shape[0], w.shape[1]
    temps = cuconv_stage1(x, w, stride, padding)
    if kh == 1 and kw == 1:
        return temps[0].astype(x.dtype)
    return cuconv_stage2(temps).astype(x.dtype)


def conv_cuconv(x, w, stride=1, padding: Pad = "same"):
    """Fused tap accumulation (beyond-paper; no HBM temporaries)."""
    kh, kw, C, M = w.shape
    ph, pw = _norm_pad(padding, kh, kw)
    sh, sw = _norm_stride(stride)
    xp = _pad_input(x, ph, pw)
    oh = _out_size(x.shape[1], kh, ph, sh)
    ow = _out_size(x.shape[2], kw, pw, sw)
    taps = w.reshape(kh * kw, C, M)
    acc = None
    for t, v in enumerate(_tap_views(xp, kh, kw, oh, ow, (sh, sw))):
        y = jnp.einsum("nhwc,cm->nhwm", v, taps[t],
                       preferred_element_type=jnp.float32)
        acc = y if acc is None else acc + y
    return acc.astype(x.dtype)


def conv_cuconv_pallas(x, w, stride=1, padding: Pad = "same",
                       interpret: Optional[bool] = None):
    """Fused Pallas TPU kernel: any stride >= 1 (policy-free executor —
    VMEM budgeting lives in convspec.plan)."""
    from repro.kernels import ops
    kh, kw = w.shape[0], w.shape[1]
    ph, pw = _norm_pad(padding, kh, kw)
    return ops.cuconv_fused(x, w, (ph, pw), stride=_norm_stride(stride),
                            interpret=interpret)


def conv_conv1x1_pallas(x, w, stride=1, padding: Pad = "same",
                        interpret: Optional[bool] = None):
    """Dedicated 1x1 GEMM kernel: all N*H*W pixels flattened into MXU
    tiles — the paper's best-case region on its natural kernel."""
    kh, kw = w.shape[0], w.shape[1]
    if ((kh, kw) != (1, 1) or _norm_stride(stride) != (1, 1)
            or _norm_pad(padding, kh, kw) != (0, 0)):
        raise ValueError("conv1x1 kernel needs 1x1 filter, stride 1, pad 0; "
                         "plan() routes other specs elsewhere")
    from repro.kernels import ops
    return ops.conv1x1(x, w, interpret=interpret)


def conv_cuconv_two_stage_pallas(x, w, stride=1, padding: Pad = "same",
                                 interpret: Optional[bool] = None):
    """Faithful two-kernel Pallas pipeline (stride 1): stage-1 HBM
    temporaries + stage-2 sum — the planner's VMEM-bounded fallback."""
    if _norm_stride(stride) != (1, 1):
        raise ValueError("two-stage Pallas kernels are stride-1 only; "
                         "plan() routes strided specs elsewhere")
    from repro.kernels import ops
    kh, kw = w.shape[0], w.shape[1]
    ph, pw = _norm_pad(padding, kh, kw)
    return ops.cuconv_two_stage(x, w, (ph, pw), interpret=interpret)


def conv_winograd_pallas(x, w, stride=1, padding: Pad = "same",
                         interpret: Optional[bool] = None):
    """Tiled Pallas Winograd F(m,3) kernel (3x3 stride-1 only;
    policy-free executor — the F(m,3) variant and tile geometry come
    from the plan's launch config, default F(2x2,3x3))."""
    if (w.shape[0] != 3 or w.shape[1] != 3
            or _norm_stride(stride) != (1, 1)):
        raise ValueError("winograd_pallas needs 3x3 stride-1; "
                         "plan() routes other specs elsewhere")
    from repro.kernels import ops
    ph, pw = _norm_pad(padding, 3, 3)
    return ops.winograd_fused(x, w, (ph, pw), interpret=interpret)


def conv_direct(x, w, stride=1, padding: Pad = "same",
                interpret: Optional[bool] = None):
    """Im2col-free direct Pallas conv (Li et al. 1610.03618):
    channel-tiled VMEM accumulation, no patch matrix, any stride."""
    from repro.kernels import ops
    kh, kw = w.shape[0], w.shape[1]
    return ops.direct_conv(x, w, _norm_pad(padding, kh, kw),
                           _norm_stride(stride), interpret=interpret)


def conv_winograd_or_fallback(x, w, stride=1, padding: Pad = "same"):
    """Winograd F(2x2,3x3) for 3x3/stride-1, library conv otherwise —
    mirrors cuDNN exposing Winograd only where it is defined."""
    if (w.shape[0] == 3 and w.shape[1] == 3
            and _norm_stride(stride) == (1, 1)):
        from repro.core.winograd import conv_winograd
        return conv_winograd(x, w, 1, padding)
    return conv_lax(x, w, stride, padding)


# NOTE: there is deliberately no algorithm dict here any more.  The menu
# of executors — names, capabilities, cost models — lives in
# core/executors.py as registered Executor objects wrapping the pure
# functions above; `repro.core.executors.ALGORITHMS` is the back-compat
# {name: bare callable} view.


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
