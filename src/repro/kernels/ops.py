"""Public jit'd wrappers around the Pallas kernels.

The one place ``interpret`` is resolved (``_auto_interpret``): ``None``
means Python-interpret mode off the TPU and compiled Mosaic on it.  The
raw kernel entries in ``kernels/*.py`` take no default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import (conv1x1 as _c1, cuconv_stage1 as _s1,
                           cuconv_stage2 as _s2, cuconv_fused as _cf,
                           conv1d_tap as _c1d, direct_conv as _dcv,
                           flash_attention as _fa, int8_gemm as _i8,
                           winograd_pallas as _wg)


from repro.core.convspec import normalize_stride as _norm_stride  # one home
from repro.kernels._compat import clamp_tiles  # noqa: F401  (re-export)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def conv1x1(x, w, interpret=None, tp=256, tm=128, tc=512):
    """x: (N, H, W, C); w: (1, 1, C, M) or (C, M).

    ``tp/tm/tc`` are the GEMM launch tiles (pixels/out-channels/
    contraction); the defaults are the historical hard-coded geometry.
    """
    if w.ndim == 4:
        w = w[0, 0]
    N, H, W_, C = x.shape
    out = _c1.conv1x1_gemm(x.reshape(N * H * W_, C), w, tp=tp, tm=tm, tc=tc,
                           interpret=_auto_interpret(interpret))
    return out.reshape(N, H, W_, -1)


def int8_gemm(x2d, w, interpret=None, tp=256, tm=128, tc=512):
    """x2d: (P, C) int8; w: (C, M) int8.  Returns (P, M) **int32** — the
    raw accumulator; dequantization is the int8 executor's epilogue."""
    return _i8.int8_gemm(x2d, w, tp=tp, tm=tm, tc=tc,
                         interpret=_auto_interpret(interpret))


def cuconv_two_stage(x, w, padding=(0, 0), interpret=None,
                     tp=256, tm=128, tc=512):
    """Faithful two-kernel cuConv (stride 1): HBM temporaries + sum.

    Policy-free executor: which inputs take this path (vs the fused or
    1x1 kernels) is decided by core.convspec.plan, not here.
    ``tp/tm/tc`` thread the launch tiles into stage 1; stage 2 rides the
    same pixel tile but keeps its own out-channel tile default (it is a
    bandwidth-bound reduction — 1-9 % of total time in the paper — and
    its historical default differs from stage 1's).
    """
    from repro.core.cuconv import _tap_views  # shared view builder
    interp = _auto_interpret(interpret)
    N, H, W_, C = x.shape
    KH, KW, _, M = w.shape
    ph, pw = padding
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    OH, OW = H + 2 * ph - KH + 1, W_ + 2 * pw - KW + 1
    views = _tap_views(xp, KH, KW, OH, OW, 1)
    xs = jnp.stack([v.reshape(N * OH * OW, C) for v in views], 0)
    temps = _s1.stage1_tap_gemm(xs, w.reshape(KH * KW, C, M),
                                tp=tp, tm=tm, tc=tc, interpret=interp)
    out = _s2.stage2_tap_sum(temps, tp=tp, interpret=interp)
    return out.reshape(N, OH, OW, M).astype(x.dtype)


def cuconv_fused(x, w, padding=(0, 0), stride=1, bias=None, activation=None,
                 addend=None, pool=None, interpret=None, tm=128, rows=1):
    """Single-kernel fused cuConv, any stride >= 1, optional fused
    bias+activation epilogue.

    Policy-free executor: VMEM-budget fallback and algorithm choice live
    in core.convspec.plan — calling this directly always runs the fused
    kernel.  ``tm``/``rows`` are its launch config (output-channel tile,
    output rows per grid step; see kernels/cuconv_fused.py).  ``addend``
    (residual second operand) and ``pool`` (``(kind, psh, psw)``
    non-overlapping pool) are the cross-layer fusions of DESIGN.md §10,
    executed in VMEM before the single output write.
    """
    return _cf.cuconv_fused(x, w, bias, stride=_norm_stride(stride),
                            padding=tuple(padding), activation=activation,
                            addend=addend,
                            pool=tuple(pool) if pool is not None else None,
                            tm=tm, rows=rows,
                            interpret=_auto_interpret(interpret))


def winograd_fused(x, w, padding=(1, 1), bias=None, activation=None,
                   addend=None, m=2, rows=4, tm=128, tc=128,
                   interpret=None):
    """Tiled Pallas Winograd F(m,3) conv (3x3, stride 1) with fused
    bias/activation/residual epilogue.

    Policy-free executor: the F(m,3) variant ``m``, the tile rows per
    step ``rows`` and the ``tm/tc`` channel tiles are the
    winograd_pallas launch config (core.convspec.plan
    owns which specs take this path; see kernels/winograd_pallas.py).
    """
    return _wg.winograd_fused(x, w, tuple(padding), bias=bias,
                              activation=activation, addend=addend,
                              m=m, rows=rows, tm=tm, tc=tc,
                              interpret=_auto_interpret(interpret))


def direct_conv(x, w, padding=(0, 0), stride=(1, 1), tm=128, tc=256,
                interpret=None):
    """Im2col-free direct conv (Li et al. 1610.03618): channel-tiled
    fp32 VMEM accumulation, no patch-matrix materialization.  Any
    stride; ``tm/tc`` are the direct executor's launch config."""
    return _dcv.direct_conv(x, w, tuple(padding), _norm_stride(stride),
                            tm=tm, tc=tc,
                            interpret=_auto_interpret(interpret))


def pool2d(x, kind="max", window=(2, 2), stride=(2, 2), padding=(0, 0)):
    """Windowed max/avg pooling over NHWC (the graph IR's pool executor).

    Avg pooling divides by the full window size (padding counts as
    zeros), matching ``lax.avg_pool``-style count_include_pad semantics.
    """
    kh, kw = window
    sh, sw = stride
    ph, pw = padding
    dims, strides = (1, kh, kw, 1), (1, sh, sw, 1)
    pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
    if kind == "max":
        # init value in the operand dtype so bf16 programs (precision
        # policies) pool without an implicit f64 promotion error
        return jax.lax.reduce_window(x, jnp.asarray(-jnp.inf, x.dtype),
                                     jax.lax.max, dims, strides, pads)
    if kind == "avg":
        s = jax.lax.reduce_window(x, jnp.zeros((), x.dtype), jax.lax.add,
                                  dims, strides, pads)
        return s / (kh * kw)
    raise ValueError(f"pool kind must be 'max' or 'avg'; got {kind!r}")


def conv1d_causal(x, w, b=None, interpret=None):
    return _c1d.conv1d_tap(x, w, b, interpret=_auto_interpret(interpret))


def flash_attention(q, k, v, causal=True, interpret=None):
    """q: (B, Sq, H, D) or (BH, Sq, D); GQA KV broadcast handled here."""
    interp = _auto_interpret(interpret)
    if q.ndim == 4:
        B, Sq, H, D = q.shape
        KVH = k.shape[2]
        if KVH != H:
            rep = H // KVH
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
        out = _fa.flash_attention(qf, kf, vf, causal=causal, interpret=interp)
        return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return _fa.flash_attention(q, k, v, causal=causal, interpret=interp)
