"""Depthwise convolution one filter tap at a time, accumulated in VMEM.

A depthwise conv (``groups == C_in == C_out``) has no channel
contraction, so it never reaches the MXU: each output element is a
``KH*KW``-term dot of one channel's window with that channel's filter.
An im2col copy of a 7x7 depthwise input is 49x the input; this kernel
reads the input once.  It is cuConv's idea where it pays most: each tap
is one broadcast multiply-add of the shifted window by the tap's
per-channel weights, accumulated in float32 before a single HBM write.

Layout: channels on lanes.  A grid step takes ``nb`` whole images of one
channel tile (``tc`` lanes; the whole channel extent when C is not a
multiple of 128, so C = 96 or 192 fill 96/128 or 192/256 of the lanes
and no XLA pad or slice pass touches HBM).  The step copies its block
into a zeroed float32 VMEM scratch at the padding offset, so the conv's
zero padding costs no HBM pass either.  Then for each block of
``rows`` output rows the ``(rows, OW, tc)`` accumulator stays in
registers across all taps: for each filter column ``dj`` the block's
``rows + KH - 1`` scratch rows are read once at column offset ``dj`` (a
static offset; the row offset is on a leading dim), and tap ``(di, dj)``
adds their rows ``di ..`` times the tap's ``(1, tc)`` weight row,
broadcast over the rows and columns.  The bias starts the
accumulator; an optional ReLU ends it.

Grid: ``(N/nb, C/tc)``, both parallel.  Stride 1 only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def _make_kernel(kh, kw, ph, pw, h, w, oh, ow, nb, rows, has_bias, relu):
    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        b_ref = refs[2] if has_bias else None
        o_ref, xp_ref = refs[-2], refs[-1]
        tc = o_ref.shape[-1]
        # the zero-padded images of this step, in VMEM
        xp_ref[...] = jnp.zeros(xp_ref.shape, xp_ref.dtype)
        xp_ref[:, ph:ph + h, pw:pw + w, :] = x_ref[...].astype(jnp.float32)
        if has_bias:
            start = jnp.broadcast_to(b_ref[...].astype(jnp.float32),
                                     (rows, ow, tc))
        else:
            start = jnp.zeros((rows, ow, tc), jnp.float32)
        blocks = oh // rows

        def row_block(i, carry):
            n, r = i // blocks, jax.lax.rem(i, blocks) * rows
            acc = start
            for dj in range(kw):
                # the rows that column dj's taps of this block read, once
                slab = xp_ref[n, pl.ds(r, rows + kh - 1), pl.ds(dj, ow), :]
                for di in range(kh):
                    # (rows, OW, tc) window times the tap's (1, tc) row
                    acc = acc + slab[di:di + rows] * w_ref[di * kw + dj]
            if relu:
                acc = jnp.maximum(acc, 0.0)
            o_ref[n, pl.ds(r, rows)] = acc.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, nb * blocks, row_block, 0)

    return kernel


def _out_hw(h, w, kh, kw, padding):
    return h + 2 * padding[0] - kh + 1, w + 2 * padding[1] - kw + 1


def vmem_bytes(in_shape, filter_shape, padding=(0, 0), nb=1, tc=128,
               itemsize=4, bias=False):
    """Live-block VMEM model of one grid step at the TPU's tiled layout
    (``_compat.tiled_bytes``): the input and output blocks and the
    tap weights double buffered, and the float32 padded scratch."""
    tb = _compat.tiled_bytes
    _, h, w, c = in_shape
    kh, kw = filter_shape[:2]
    tc = min(int(tc), c)
    oh, ow = _out_hw(h, w, kh, kw, padding)
    need = (2 * nb * tb((h, w, tc), itemsize)
            + 2 * nb * tb((oh, ow, tc), itemsize)
            + 2 * kh * kw * tb((1, tc), itemsize)
            + nb * tb((h + 2 * padding[0], w + 2 * padding[1], tc), 4))
    if bias:
        need += 2 * tb((1, tc), itemsize)
    return int(need)


@functools.partial(jax.jit, static_argnames=("padding", "relu", "nb", "tc",
                                             "rows", "interpret"))
def depthwise_tap(x, w, bias=None, padding=(0, 0), relu=False, nb=1, tc=128,
                  rows=1, *, interpret):
    """x: (N, H, W, C) NHWC; w: (KH, KW, 1, C) HWIO, one filter per
    channel; stride 1.  ``bias`` (C,) starts the float32 accumulator;
    ``relu`` clamps it before the write.
    ``nb`` (images per grid step, a divisor of N), ``tc`` (channel
    tile: C itself, or a multiple of 128 dividing C) and ``rows``
    (output rows one accumulator holds, a divisor of OH) are the launch
    config.  ``interpret`` is required: callers resolve it per backend
    (``kernels.ops``).  Returns (N, OH, OW, C) in ``x.dtype``."""
    n, h, w_, c = x.shape
    kh, kw = w.shape[:2]
    if w.shape[2:] != (1, c):
        raise ValueError(f"depthwise filter must be (KH, KW, 1, {c}); "
                         f"got {w.shape}")
    tc = min(int(tc), c)
    if n % nb or c % tc or (tc < c and tc % 128):
        raise ValueError(f"nb={nb} must divide N={n} and tc={tc} must be "
                         f"C={c} or a multiple of 128 dividing it")
    ph, pw = padding
    oh, ow = _out_hw(h, w_, kh, kw, padding)
    if oh % rows:
        raise ValueError(f"rows={rows} must divide OH={oh}")
    operands = [x, w.reshape(kh * kw, 1, c)]
    in_specs = [
        pl.BlockSpec((nb, h, w_, tc), lambda i, j: (i, 0, 0, j)),
        pl.BlockSpec((kh * kw, 1, tc), lambda i, j: (0, 0, j)),
    ]
    if bias is not None:
        operands.append(bias.reshape(1, c))
        in_specs.append(pl.BlockSpec((1, tc), lambda i, j: (0, j)))
    return pl.pallas_call(
        _make_kernel(kh, kw, ph, pw, h, w_, oh, ow, nb, rows,
                     bias is not None, relu),
        grid=(n // nb, c // tc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nb, oh, ow, tc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((nb, h + 2 * ph, w_ + 2 * pw, tc),
                                   jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="depthwise_tap",
    )(*operands)
