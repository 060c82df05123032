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

from repro.kernels import (cuconv_fused as _cf, conv1d_tap as _c1d,
                           depthwise_tap as _dw, flash_attention as _fa,
                           int8_gemm as _i8, winograd_pallas as _wg)
from repro.core.convspec import normalize_stride as _norm_stride  # one home


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def int8_gemm(x2d, w, interpret=None, tp=256, tm=128, tc=512):
    """x2d: (P, C) int8; w: (C, M) int8.  Returns (P, M) **int32** — the
    raw accumulator; dequantization is the int8 executor's epilogue."""
    return _i8.int8_gemm(x2d, w, tp=tp, tm=tm, tc=tc,
                         interpret=_auto_interpret(interpret))


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


def depthwise_conv(x, w, padding=(0, 0), bias=None, relu=False, nb=1,
                   tc=128, rows=1, interpret=None):
    """Depthwise conv (stride 1, one filter per channel) one tap at a
    time in VMEM, bias and ReLU fused; ``nb``/``tc``/``rows`` are the
    depthwise_tap executor's launch config (images per step, channel
    tile, output rows per accumulator)."""
    return _dw.depthwise_tap(x, w, bias, padding=tuple(padding), relu=relu,
                             nb=nb, tc=tc, rows=rows,
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
